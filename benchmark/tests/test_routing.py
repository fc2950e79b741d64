"""Routing shaping: the achieved hot share lands near the profile's at tiny
widths, and a weight tree that lacks a leaf fails the run."""

import pytest

import jax.numpy as jnp

from benchmark import run, serve, weights as wmod
from benchmark.reference import moe_transformer as ref
from benchmark.tests import tiny


def abstract_params(cfg):
    from repro.models import LM

    return LM(serve.build_arch(cfg), dtype=jnp.bfloat16).abstract_params()


@pytest.mark.parametrize("cfg", [tiny.TINY_GQA, tiny.TINY_MLA], ids=["gqa", "mla"])
def test_hot_share_near_profile(cfg):
    dm = ref.dims_from_config(cfg)
    w, c_hat = wmod.make_weights(abstract_params(cfg), dm.d, 2**32 + 9)
    w, rep = wmod.shape_routing(ref, w, c_hat, dm, tiny.ROUTING, 2**32 + 9)
    for target, got, neg in zip(rep["target_hot_share"], rep["calibration_hot_share"],
                                rep["negative_projection"]):
        assert abs(got - target) < 0.03
        assert neg < 0.05  # at d=64 the common direction is only 2.6 long
    # unshaped (uniform) routing would put hot_fraction of assignments there
    assert min(rep["calibration_hot_share"]) > 2 * tiny.ROUTING["hot_fraction"]


def test_served_hot_share_near_profile(tmp_path, capfd):
    root = tiny.make_root(tmp_path, [tiny.TINY_GQA], {"closed": tiny.TINY_CLOSED})
    run.run(["--workload", "tiny-gqa.closed", "--seed", "21", "--seconds", "2",
             "--trace", "0"], require_tpu=False, root=root)
    err = capfd.readouterr().err
    target = [float(x.split("/")[0]) for x in
              err.split("hot share target/calibration ")[1].split(";")[0].split()]
    served = [float(x) for x in err.split("(window, all decode rows): ")[1].splitlines()[0].split()]
    assert len(served) == len(target)
    for s, t in zip(served, target):
        assert abs(s - t) < 0.1, (s, t)


@pytest.mark.parametrize("leaf", ["embed", "w_router"])
def test_missing_leaf_fails_the_run(tmp_path, monkeypatch, leaf):
    """A program whose parameter tree lacks a leaf that routing shaping
    needs fails the run before the engine is built."""
    from repro.models import LM

    root = tiny.make_root(tmp_path, [tiny.TINY_GQA], {"closed": tiny.TINY_CLOSED})
    abstract = LM.abstract_params

    def without(self):
        tree = abstract(self)
        if leaf == "embed":
            del tree["embed"]
        else:
            del tree["blocks"]["moe"]["w_router"]
        return tree

    monkeypatch.setattr(LM, "abstract_params", without)
    with pytest.raises(SystemExit, match=leaf):
        run.run(["--workload", "tiny-gqa.closed", "--seed", "1", "--seconds", "1",
                 "--trace", "0"], require_tpu=False, root=root)
