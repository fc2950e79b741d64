"""The float32 reference agrees with the served engine on the same weights,
dropless, at tiny widths: GQA and MLA attention, dense lead layer, shared
experts, prefill and decode through the cache, final norm and LM head."""

import pytest

from benchmark import run
from benchmark.tests import tiny


@pytest.mark.parametrize("cfg", [tiny.TINY_GQA, tiny.TINY_MLA], ids=["gqa", "mla"])
def test_reference_matches_served_engine(tmp_path, cfg):
    loop = "closed"
    root = tiny.make_root(tmp_path, [cfg], {loop: tiny.TINY_CLOSED})
    keep = {}
    res = run.run(["--workload", f"{cfg['name']}.{loop}", "--seed", "7",
                   "--seconds", "2", "--trace", "0"], require_tpu=False, root=root,
                  keep=keep)
    gaps = keep["gaps"]
    assert gaps.size >= 30
    # bf16 serving against float32: served tokens are the reference's best
    # or within bf16 rounding of it
    assert gaps.max() < 0.05, gaps.max()
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
