"""A run whose timed path is broken underneath comes out not correct: the
harness is driven whole, with only the look for a chip skipped."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import run
from benchmark.tests import tiny


def alter_token(engine):
    """A served token altered where it is produced: every decode step's
    sampled tokens move to the next vocabulary id."""
    inner = engine._sample

    def sample(logits):
        t = inner(logits)
        return (t + 1) % logits.shape[-1] if logits.shape[0] == engine.cfg.n_slots else t

    engine._sample = sample


def state_unchanged(engine):
    """The decode step returns the KV cache it was given, unwritten: the
    compiled step runs on a copy, and what it wrote is dropped.  The copy
    is compiled here, before the window, so the window runs as many steps
    as a sound one."""
    inner = engine._decode
    copy = jax.jit(lambda c: jax.tree.map(jnp.copy, c))
    jax.block_until_ready(copy(engine.cache))

    def decode(params, batch, cache):
        logits, _, aux = inner(params, batch, copy(cache))
        return logits, cache, aux

    engine._decode = decode


@pytest.mark.parametrize("fault", [alter_token, state_unchanged])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    root = tiny.make_root(tmp_path, [tiny.TINY_GQA], {"closed": tiny.TINY_CLOSED},
                          limit=0.01)
    res = run.run(["--workload", "tiny-gqa.closed", "--seed", "5", "--seconds", "2",
                   "--trace", "0"], require_tpu=False, root=root, fault=fault)
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > 0.01
