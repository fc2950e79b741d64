"""Seeded weights, made on the device in one jitted call, and the routing
shaping that gives them the traffic's bimodal expert popularity.

The benchmark makes the weights itself, so that the reference takes
nothing the program made: it reads only the tree, shapes and dtypes of
the program's abstract parameters (``LM.abstract_params``), fills each
leaf by a rule of its name from the seed, and the reference reads the
same tree.

Routing shaping.  Random weights route close to uniformly.  The traffic
names a popularity profile (the paper's fit: a hot fraction of the
experts holding most of the mass, Dirichlet within the hot set and
within the tail), drawn per MoE layer from the seed.  A common direction
``c`` is added to every embedding row, so the residual stream carries a
component along ``c`` at every layer; each MoE router is made orthogonal
to ``c`` and given a rank-one term ``c (g_l log p_l)^T``.  A token's
router logits are then ``g_l (x . c) log p_l`` plus the random part of
the router on the rest of the token, which is what the paper's
exponential-race model draws from.  ``g_l`` is calibrated layer by layer
at set-up, on a short batch of calibration tokens through the
reference's bfloat16 forward, so that the share of assignments on the
hot experts matches that of the paper's model for the same popularity.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32
EMBED_STD = 1.0  # per coordinate of an embedding row
C_NORM = 0.33  # |c| / sqrt(d): the common direction's share of the residual
ROUTER_NOISE = 1.28  # std of a router logit from the random part (Gumbel's)
LOG_P_FLOOR = np.log(1e-30)
CAL_ROWS, CAL_LEN = 4, 64  # calibration tokens: 4 rows of 64 positions


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (all 64+ bits count)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**63, stream]))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_kind(path) -> str:
    """The init rule of a leaf, by its name: norm scales are ones, the
    embedding and the routers have rules of their own, every other leaf is
    a matrix (or a stack of them) with He scaling over its input dim."""
    name = str(getattr(path[-1], "key", path[-1]))
    if name.endswith("scale"):
        return "ones"
    if name in ("embed", "w_router"):
        return name
    return "he"


def init_specs(abstract):
    """(treedef, leaves) of the program's parameter tree, each leaf as
    (path, shape, dtype, init kind): its shapes and dtypes, and nothing the
    program computed."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    return treedef, tuple((jax.tree_util.keystr(p), tuple(a.shape), jnp.dtype(a.dtype).name,
                           init_kind(p)) for p, a in flat)


@functools.partial(jax.jit, static_argnames=("treedef", "leaves", "d"))
def _make(key, *, treedef, leaves, d: int):
    c_hat = jax.random.normal(jax.random.fold_in(key, 0x0C0C), (d,), F32)
    c_hat = c_hat / jnp.linalg.norm(c_hat)
    c_norm = C_NORM * np.sqrt(d)

    def leaf(path, shape, dtype, kind):
        k = jax.random.fold_in(key, zlib.crc32(path.encode()))
        if kind == "ones":
            return jnp.ones(shape, dtype)
        if kind == "he":
            return jax.random.normal(k, shape, dtype) * jnp.asarray(
                1.0 / np.sqrt(shape[-2]), dtype)
        if kind == "embed":
            e = jax.random.normal(k, shape, F32) * EMBED_STD + c_norm * c_hat
            return e.astype(dtype)
        # the router: random part orthogonal to c, logit std ROUTER_NOISE
        # for a unit-rms input; the rank-one popularity term comes later
        w = jax.random.normal(k, shape, F32) * (ROUTER_NOISE / np.sqrt(d))
        w = w - jnp.einsum("d,...de->...e", c_hat, w)[..., None, :] * c_hat[:, None]
        return w.astype(dtype)

    weights = jax.tree_util.tree_unflatten(treedef, [leaf(*spec) for spec in leaves])
    return weights, c_hat


def make_weights(abstract, d: int, seed: int):
    """(weights, c_hat) on the default device, from ``seed``, in the tree,
    shapes and dtypes of the program's abstract parameters ``abstract``;
    ``d`` is the model width."""
    treedef, leaves = init_specs(abstract)
    return _make(seed_key(seed), treedef=treedef, leaves=leaves, d=d)


# ---------------------------------------------------------------------------
# Popularity and routing shaping
# ---------------------------------------------------------------------------


def hot_count(profile: Dict, n_experts: int) -> int:
    return max(1, int(round(profile["hot_fraction"] * n_experts)))


def popularity(profile: Dict, n_experts: int, n_layers: int, seed: int):
    """Per-layer popularity (n_layers, E) and hot masks, from the seed."""
    rng = seed_rng(seed, 1)
    h = hot_count(profile, n_experts)
    pops, hots = [], []
    for _ in range(n_layers):
        perm = rng.permutation(n_experts)
        hot, tail = perm[:h], perm[h:]
        p = np.zeros(n_experts)
        p[hot] = profile["hot_mass"] * rng.dirichlet([profile["hot_alpha"]] * h)
        if len(tail):
            p[tail] = (1 - profile["hot_mass"]) * rng.dirichlet(
                [profile["tail_alpha"]] * len(tail))
        mask = np.zeros(n_experts, bool)
        mask[hot] = True
        pops.append(p)
        hots.append(mask)
    return np.stack(pops), np.stack(hots)


def race_hot_share(p: np.ndarray, hot: np.ndarray, k: int, seed: int,
                   n_tokens: int = 20000) -> float:
    """Share of assignments on the hot set when each token takes the top k
    of log p plus Gumbel noise (the paper's exponential race)."""
    rng = seed_rng(seed, 2)
    g = rng.gumbel(size=(n_tokens, len(p)))
    top = np.argpartition(-(np.log(np.maximum(p, 1e-300)) + g), k - 1, axis=1)[:, :k]
    return float(hot[top].mean())


def _achieved(g: float, proj, noise, beta, hot, k: int) -> float:
    logits = noise + g * proj[:, None] * beta[None, :]
    top = np.argpartition(-logits, k - 1, axis=1)[:, :k]
    return float(hot[top].mean())


def calibrate_gain(proj, noise, beta, hot, k: int, target: float) -> float:
    """Bisection (in log space) for the gain whose hot share meets target."""
    lo, hi = 1e-4, 1e3
    if _achieved(hi, proj, noise, beta, hot, k) < target:
        return hi
    for _ in range(40):
        mid = np.sqrt(lo * hi)
        if _achieved(mid, proj, noise, beta, hot, k) < target:
            lo = mid
        else:
            hi = mid
    return float(np.sqrt(lo * hi))


@jax.jit
def _set_router(w_router, gains, c_hat, beta):
    return w_router + gains[:, None, None] * c_hat[None, :, None] * beta[:, None, :]


def shape_routing(ref, weights, c_hat, dm, profile: Dict, seed: int):
    """Calibrate each MoE layer's gain and add the rank-one popularity term
    to its router.  ``ref`` is the configuration's reference module, whose
    bfloat16 forward (``layer``, ``pre_router``, ``moe_out``) the
    calibration runs.  Returns (weights, report) where the report holds the
    gains, the target and achieved hot shares, and the hot masks."""
    for path in (("embed",), ("blocks", "moe", "w_router")):
        node = weights
        for k in path:
            if not isinstance(node, dict) or k not in node:
                raise SystemExit("routing shaping needs the parameter leaf "
                                 + "/".join(path) + ", which the program's tree lacks")
            node = node[k]
    pops, hots = popularity(profile, dm.n_experts, dm.n_moe_layers, seed)
    betas = np.maximum(np.log(np.maximum(pops, 1e-300)), LOG_P_FLOOR).astype(np.float32)
    rng = seed_rng(seed, 3)
    toks = rng.integers(0, dm.vocab, size=(CAL_ROWS * CAL_LEN,)).astype(np.int32)
    pos = jnp.asarray(np.tile(np.arange(CAL_LEN), CAL_ROWS).astype(np.int32))
    seg = jnp.asarray(np.repeat(np.arange(CAL_ROWS), CAL_LEN).astype(np.int32))
    x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0).astype(F32)
    c_np = np.asarray(c_hat)
    gains, targets, achieved, neg = [], [], [], []
    for i in range(dm.n_layers):
        stack, li, is_moe = ref.layer_stack(weights, i, dm)
        if not is_moe:
            x, _ = ref.layer(stack, li, x, pos, seg, dm=dm, mode="bf16", is_moe=False)
            continue
        j = i - dm.n_dense_lead
        x, u = ref.pre_router(stack, li, x, pos, seg, dm=dm)
        u_np = np.asarray(u, np.float64)
        w_router = stack["moe"]["w_router"][j]
        proj = u_np @ c_np
        noise = u_np @ np.asarray(w_router, np.float64)
        target = race_hot_share(pops[j], hots[j], dm.top_k, seed + j)
        g = calibrate_gain(proj, noise, betas[j], hots[j], dm.top_k, target)
        w_router = w_router + g * jnp.outer(c_hat, jnp.asarray(betas[j]))
        x, top_i = ref.moe_out(stack, li, w_router, x, u, dm=dm)
        gains.append(g)
        targets.append(target)
        achieved.append(float(hots[j][np.asarray(top_i)].mean()))
        neg.append(float((proj <= 0).mean()))
    moe = dict(weights["blocks"]["moe"])
    moe["w_router"] = _set_router(
        moe["w_router"], jnp.asarray(gains, F32), c_hat, jnp.asarray(betas))
    blocks = dict(weights["blocks"], moe=moe)
    weights = dict(weights, blocks=blocks)
    report = {"gains": gains, "target_hot_share": targets,
              "calibration_hot_share": achieved, "negative_projection": neg,
              "hot_masks": hots}
    return weights, report
