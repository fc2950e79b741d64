"""The control (the reference in float8 e4m3 in the program's place)
comes out not correct against the cell's limit, on the same served tokens
on which the program comes out correct, at a size a test run can hold."""

import pytest

from benchmark import run
from benchmark.tests import tiny

# the tiny cells' limit, set as the chip cell's is: above the program's
# mean gap over seeds and below the control's (readings in PERF.md)
TINY_LIMIT = 0.002


@pytest.mark.parametrize("cfg", [tiny.TINY_GQA, tiny.TINY_MLA], ids=["gqa", "mla"])
def test_control_fails_where_program_passes(tmp_path, cfg):
    root = tiny.make_root(tmp_path, [cfg], {"closed": tiny.TINY_CLOSED}, limit=TINY_LIMIT)
    keep = {}
    res = run.run(["--workload", f"{cfg['name']}.closed", "--seed", "13", "--seconds", "2",
                   "--trace", "0"], require_tpu=False, root=root, keep=keep, control="fp8")
    assert run.find_cell(root, f"{cfg['name']}.closed").limits["mean_logit_gap"] == TINY_LIMIT
    assert res["correct"] is True
    assert res["control_correct"] is False
    checks = res["checks"]
    assert checks["mean_logit_gap"]["value"] <= TINY_LIMIT
    assert checks["control_mean_logit_gap"] == {"value": float(keep["control_gaps"].mean()),
                                                "limit": TINY_LIMIT}
