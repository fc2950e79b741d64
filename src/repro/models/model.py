"""LM facade: init / forward / loss / prefill / decode for all 10 archs.

One class (:class:`LM`) covers the five structural families:

  * decoder-only attention (dense / MoE / VLM)  — scan over stacked blocks,
    optional dense prefix (DeepSeek-V2 first_k_dense);
  * hybrid (zamba2)  — scan over [shared-attn + (attn_every-1) Mamba2]
    segments plus a Mamba2 tail;
  * ssm (rwkv6)      — scan over RWKV6 blocks;
  * linear_hybrid (qwen3-next) — scan over periods of [Gated DeltaNet x
    (interval-1) + gated attention], MoE in every layer;
  * encoder-decoder (whisper) — encoder scan + decoder scan w/ cross-attn.

Everything is functional; ``params`` / ``cache`` are nested dicts of arrays
so they shard with PartitionSpec trees from :mod:`repro.models.sharding`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from . import attention as attn_lib
from . import gated_delta as gdn
from . import transformer as tf
from .layers import (
    apply_norm,
    embed,
    init_embedding,
    init_norm,
    sinusoidal_positions,
)
from .moe import LOCAL_MESH, ROUTED_STACKS, MeshInfo, experts_in_place
from .ssm import (
    Mamba2State,
    RWKV6State,
    mamba2_init_state,
    rwkv6_init_state,
)
from .transformer import BlockAux


class StepAux(NamedTuple):
    """Aggregated per-step diagnostics (MoE aux loss, Sieve counts, drops)."""

    moe_aux: jax.Array  # scalar
    # (n_moe_layers, E) token counts per layer and router output (Sieve
    # input); on a held expert share, those its experts take
    counts: jax.Array
    dropped: jax.Array  # scalar


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _linear_layout(arch: ArchConfig) -> Tuple[int, int]:
    """(linear layers, full-attention layers) of a linear_hybrid stack:
    whole periods of ``full_attention_interval`` layers, the last of each
    full attention."""
    per = arch.linear.full_attention_interval
    if arch.n_layers % per:
        raise ValueError(
            f"{arch.name}: {arch.n_layers} layers are not whole periods of "
            f"{per} (full_attention_interval)"
        )
    n_full = arch.n_layers // per
    return arch.n_layers - n_full, n_full


def _zamba_layout(arch: ArchConfig) -> Tuple[int, int, int]:
    """(n_segments, mambas_per_segment, tail_mambas)."""
    per = arch.attn_every - 1
    nseg = arch.n_layers // arch.attn_every
    tail = arch.n_layers - nseg * arch.attn_every
    return nseg, per, tail


class LM:
    def __init__(
        self,
        arch: ArchConfig,
        dtype=jnp.bfloat16,
        remat: bool = False,
        q_chunk: int = 1024,
        kv_chunk: int = 1024,
        loss_chunk: int = 512,
        mesh_info: MeshInfo = LOCAL_MESH,
    ):
        self.arch = arch
        self.dtype = dtype
        self.remat = remat
        self.q_chunk = q_chunk
        self.kv_chunk = kv_chunk
        self.loss_chunk = loss_chunk
        self.mi = mesh_info
        # vocab padded to a TP-friendly multiple (embeddings/logits shard
        # evenly over the model axis; padded columns masked in loss/sampling)
        self.vocab_padded = -(-arch.vocab_size // 128) * 128

    def _sp(self, x: jax.Array) -> jax.Array:
        """Sequence parallelism: between blocks the residual stream is
        sharded over the model axis along the sequence dim (Megatron-SP);
        activations and remat carries shrink by the TP degree, with GSPMD
        inserting the gather/scatter around attention."""
        mi = self.mi
        if (
            mi.mesh is None
            or mi.model_axis is None
            or x.ndim < 3
            or x.shape[1] < 2
            or x.shape[1] % mi.ep_size
            # SSM blocks operate along time (conv, cumulative decay, chunk
            # scans): sequence sharding forces GSPMD replication there.
            # Those families shard the SSM head dim instead (ssm.py).
            or self.arch.family in ("hybrid", "ssm")
        ):
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(mi.data_axes if mi.data_axes else None, mi.model_axis, None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mi.mesh, spec))

    # ==================================================================
    # Init
    # ==================================================================

    def init(self, key) -> Dict[str, Any]:
        arch, dtype = self.arch, self.dtype
        ks = jax.random.split(key, 8)
        p: Dict[str, Any] = {
            "embed": init_embedding(ks[0], self.vocab_padded, arch.d_model, dtype),
            "final_norm": init_norm(arch.d_model, arch.norm),
        }
        if not arch.tie_embeddings:
            p["w_out"] = (
                jax.random.normal(ks[1], (arch.d_model, self.vocab_padded)) * 0.02
            ).astype(dtype)

        if arch.family in ("dense", "moe", "vlm"):
            moe = arch.moe is not None
            n_prefix = arch.moe.first_k_dense if moe else 0
            n_blocks = arch.n_layers - n_prefix
            if n_prefix:
                p["prefix_blocks"] = _stack_init(
                    lambda k: tf.init_attn_mlp_block(k, arch, moe=False, dtype=dtype),
                    ks[2],
                    n_prefix,
                )
            p["blocks"] = _stack_init(
                lambda k: tf.init_attn_mlp_block(k, arch, moe=moe, dtype=dtype),
                ks[3],
                n_blocks,
            )
        elif arch.family == "hybrid":
            nseg, per, tail = _zamba_layout(arch)
            p["shared_attn"] = tf.init_attn_mlp_block(ks[2], arch, moe=False, dtype=dtype)
            p["mamba_seg"] = jax.vmap(
                lambda k: _stack_init(
                    lambda kk: tf.init_mamba_block(kk, arch, dtype), k, per
                )
            )(jax.random.split(ks[3], nseg))
            if tail:
                p["mamba_tail"] = _stack_init(
                    lambda k: tf.init_mamba_block(k, arch, dtype), ks[4], tail
                )
        elif arch.family == "ssm":
            p["blocks"] = _stack_init(
                lambda k: tf.init_rwkv_block(k, arch, dtype), ks[2], arch.n_layers
            )
        elif arch.family == "linear_hybrid":
            # the MoE (and norms) of every layer form one (L, ...) stack;
            # each mixer kind its own
            n_lin, n_full = _linear_layout(arch)
            p["blocks"] = _stack_init(
                lambda k: tf.init_norms_moe_block(k, arch, dtype), ks[3], arch.n_layers
            )
            p["linear_attn"] = _stack_init(
                lambda k: gdn.init_gated_delta(k, arch.linear, arch.d_model, dtype),
                ks[4], n_lin,
            )
            p["full_attn"] = _stack_init(
                lambda k: attn_lib.init_gqa(k, arch.attn, arch.d_model, dtype),
                ks[5], n_full,
            )
        elif arch.family == "audio":
            p["enc_blocks"] = _stack_init(
                lambda k: tf.init_enc_block(k, arch, dtype), ks[2], arch.enc_layers
            )
            p["enc_norm"] = init_norm(arch.d_model, arch.norm)
            p["blocks"] = _stack_init(
                lambda k: tf.init_dec_block(k, arch, dtype), ks[3], arch.n_layers
            )
            p["dec_pos"] = (
                jax.random.normal(ks[4], (448, arch.d_model)) * 0.01
            ).astype(dtype)
        else:
            raise ValueError(f"unknown family {arch.family}")
        return p

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ==================================================================
    # Embedding / head
    # ==================================================================

    def _embed_in(self, p, batch) -> Tuple[jax.Array, Optional[jax.Array]]:
        arch = self.arch
        mrope = batch.get("mrope_positions")
        if "embeds" in batch:  # modality-stub inputs arrive pre-embedded
            x = batch["embeds"].astype(self.dtype)
        else:
            x = embed(p["embed"], batch["tokens"])
        return x, mrope

    @jax.named_scope("lm_head")
    def _logits(self, p, h) -> jax.Array:
        w = p.get("w_out")
        logits = (h @ p["embed"].T) if w is None else (h @ w)
        if self.vocab_padded != self.arch.vocab_size:
            mask = jnp.arange(self.vocab_padded) < self.arch.vocab_size
            logits = jnp.where(mask, logits, -1e30)
        return logits

    # ==================================================================
    # Forward (training / prefill share the stack walk)
    # ==================================================================

    def moe_layers_in_place(self) -> int:
        """Number of MoE layers whose expert kernels read the routed
        weights from the whole layer-stacked arrays in ``prefill`` and
        ``decode_step`` (:func:`repro.models.moe.experts_in_place`); 0
        where the layer loop slices each layer's stacks.  ``forward``
        always slices: a closed-over stack would turn into a whole-stack
        gradient accumulator under ``scan``."""
        arch = self.arch
        if arch.family not in (
            "dense", "moe", "vlm", "linear_hybrid"
        ) or not experts_in_place(arch, self.mi):
            return 0
        return arch.n_layers - arch.moe.first_k_dense

    def _block_scan(self, blocks, in_place: bool):
        """``(xs, block_of)`` for a scan over ``p["blocks"]``: ``block_of``
        maps one step's input back to that layer's block params.  In place,
        the routed expert stacks leave ``xs`` (whose slice XLA would copy
        ahead of the Pallas calls): each is viewed whole as ``(L*E, ...)``,
        a bitcast, and every layer gets them with its base row ``l*E``."""
        if not in_place:
            return blocks, lambda blk: blk
        moe_p = blocks["moe"]
        L, E = moe_p["w_gate"].shape[:2]
        whole = {
            k: moe_p[k].reshape((L * E,) + moe_p[k].shape[2:])
            for k in ROUTED_STACKS
        }
        rest = {k: v for k, v in moe_p.items() if k not in whole}

        def block_of(inp):
            blk, layer = inp
            return {**blk, "moe": {**blk["moe"], **whole, "expert_base": layer * E}}

        return ({**blocks, "moe": rest}, jnp.arange(L, dtype=jnp.int32)), block_of

    def _walk_attn_stack(self, p, x, positions, mrope, collect_cache: bool,
                         sieve=None, in_place: bool = False):
        """dense/moe/vlm families."""
        arch, mi = self.arch, self.mi
        moe = arch.moe is not None
        auxes = []
        caches = {}

        def prefix_step(x, blk_p):
            x, cache, aux = tf.attn_mlp_block_seq(
                blk_p, x, positions, arch, mi, moe=False,
                mrope_positions=mrope, q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
            )
            return x, cache, aux

        n_prefix = arch.moe.first_k_dense if moe else 0
        if n_prefix:
            for i in range(n_prefix):
                blk = jax.tree.map(lambda a: a[i], p["prefix_blocks"])
                x, cache, aux = prefix_step(x, blk)
                auxes.append(aux)
                if collect_cache:
                    caches.setdefault("prefix", []).append(cache)

        xs, block_of = self._block_scan(p["blocks"], in_place)

        def body(x, inp):
            x, cache, aux = tf.attn_mlp_block_seq(
                block_of(inp), x, positions, arch, mi, moe=moe,
                mrope_positions=mrope, q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
                sieve=sieve,
            )
            return self._sp(x), (cache if collect_cache else None, aux)

        scan_body = jax.checkpoint(body) if self.remat else body
        x, (cache_stack, aux_stack) = jax.lax.scan(scan_body, self._sp(x), xs)
        if collect_cache:
            caches["blocks"] = cache_stack
        return x, caches, auxes, aux_stack

    def _walk_linear_hybrid(self, p, x, positions, cache, sieve, step: bool):
        """qwen3-next: a scan over periods of the layer pattern, each
        ``[linear] * (interval - 1) + [full]``.  The MoE stays one ``(L,
        ...)`` stack; where the expert kernels read it in place, layer
        ``l`` gets base row ``l*E``.  Decode carries the recurrent and conv
        states whole through the scan (linear layer ``i`` advances its own
        rows in place) and takes the full layers' KV as the scan's slices;
        prefill returns every layer's new state.  Returns ``(x, cache,
        aux)`` with aux stacked ``(L, ...)``."""
        arch, mi = self.arch, self.mi
        per = arch.linear.full_attention_interval
        L = arch.n_layers
        n_per = L // per
        moe_p = p["blocks"]["moe"]
        E = moe_p["w_gate"].shape[1]
        whole = {}
        if self.moe_layers_in_place() > 0:
            whole = {k: moe_p[k].reshape((L * E,) + moe_p[k].shape[2:])
                     for k in ROUTED_STACKS}

        def periods(tree, n):
            return jax.tree.map(lambda a: a.reshape((n_per, n) + a.shape[1:]), tree)

        xs = {
            "blocks": periods({**p["blocks"], "moe": {
                k: v for k, v in moe_p.items() if k not in whole}}, per),
            "lin": periods(p["linear_attn"], per - 1),
            "full": p["full_attn"],
            "period": jnp.arange(n_per, dtype=jnp.int32),
        }
        if step:
            xs["kv"] = cache["kv"]

        def body(carry, inp):
            x, rec, conv = carry if step else (carry, None, None)
            t = inp["period"]
            auxes, lin_states = [], []
            for j in range(per):
                blk = jax.tree.map(lambda a: a[j], inp["blocks"])
                if whole:
                    blk["moe"] = {**blk["moe"], **whole,
                                  "expert_base": (t * per + j) * E}
                if j < per - 1:
                    lp = jax.tree.map(lambda a: a[j], inp["lin"])

                    def mixer(h):
                        if step:
                            return gdn.gated_delta_decode(
                                lp, h, rec, conv, t * (per - 1) + j, arch.linear)
                        return gdn.gated_delta_prefill(lp, h, arch.linear)

                    x, st, aux = tf.mixer_moe_block(blk, x, mixer, arch, mi, sieve)
                    if step:
                        rec, conv = st
                    else:
                        lin_states.append(st)
                else:

                    def mixer(h):
                        if step:
                            y, k, v = attn_lib.gqa_decode(
                                inp["full"], h, positions, inp["kv"][0], inp["kv"][1],
                                arch.attn, mi=mi)
                        else:
                            y, k, v = attn_lib.gqa_prefill(
                                inp["full"], h, positions, arch.attn,
                                q_chunk=self.q_chunk, kv_chunk=self.kv_chunk)
                        return y, (k, v)

                    x, kv, aux = tf.mixer_moe_block(blk, x, mixer, arch, mi, sieve)
                auxes.append(aux)
            aux = jax.tree.map(lambda *a: jnp.stack(a), *auxes)
            if step:
                return (x, rec, conv), (kv, aux)
            lin = jax.tree.map(lambda *a: jnp.stack(a), *lin_states)
            return x, (kv, lin, aux)

        def flat(tree):  # (n_per, n, ...) -> (n_per * n, ...)
            return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), tree)

        if step:
            (x, rec, conv), (kv, aux) = jax.lax.scan(
                body, (x, cache["recurrent"], cache["conv"]), xs)
            return x, {"kv": kv, "recurrent": rec, "conv": conv}, flat(aux)
        x, (kv, (rec, conv), aux) = jax.lax.scan(body, x, xs)
        return x, {"kv": kv, "recurrent": flat(rec), "conv": flat(conv)}, flat(aux)

    def _walk_hybrid_stack(self, p, x, positions, states, collect_cache: bool,
                           step: bool):
        """zamba2: segments of [shared attn + per mambas] + mamba tail.

        Training (``collect_cache=False``) threads no caches at all — the
        attention KV of a 4k x 256 batch would be ~200 GB of dead weight;
        Mamba states start from zeros inside each block."""
        arch, mi = self.arch, self.mi
        nseg, per, tail = _zamba_layout(arch)
        train = not collect_cache and not step
        thread_in = step  # only decode consumes existing states

        def seg_body(carry, inp):
            x = carry
            if thread_in:
                seg_params, mamba_states, attn_cache = inp
            else:
                seg_params = inp
                mamba_states = None
                attn_cache = None
            if step:
                x, new_cache, _ = tf.attn_mlp_block_decode(
                    p["shared_attn"], x, positions, attn_cache, arch, mi, moe=False
                )
            else:
                x, new_cache, _ = tf.attn_mlp_block_seq(
                    p["shared_attn"], x, positions, arch, mi, moe=False,
                    q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
                )

            def mamba_step(xc, inp2):
                if thread_in:
                    mp, st = inp2
                else:
                    mp, st = inp2, None
                xc, new_st, _ = tf.mamba_block(mp, xc, arch, st, step=step, mi=mi)
                return self._sp(xc), (None if train else new_st)

            x, new_states = jax.lax.scan(
                mamba_step,
                x,
                (seg_params, mamba_states) if thread_in else seg_params,
            )
            return x, (None if train else (new_states, new_cache))

        seg_scan = jax.checkpoint(seg_body) if self.remat else seg_body
        seg_xs = (
            (p["mamba_seg"], states["mamba_seg"], states["attn"])
            if thread_in
            else p["mamba_seg"]
        )
        x, seg_out = jax.lax.scan(seg_scan, x, seg_xs)

        new_tail_states = None
        if tail:
            def tail_step(xc, inp2):
                if thread_in:
                    mp, st = inp2
                else:
                    mp, st = inp2, None
                xc, new_st, _ = tf.mamba_block(mp, xc, arch, st, step=step, mi=mi)
                return self._sp(xc), (None if train else new_st)

            tail_xs = (
                (p["mamba_tail"], states["mamba_tail"])
                if thread_in
                else p["mamba_tail"]
            )
            x, new_tail_states = jax.lax.scan(tail_step, x, tail_xs)

        if train:
            return x, None
        new_seg_states, new_attn_caches = seg_out
        new_states = {
            "mamba_seg": new_seg_states,
            "attn": new_attn_caches,
        }
        if tail:
            new_states["mamba_tail"] = new_tail_states
        return x, new_states

    def _walk_rwkv_stack(self, p, x, states):
        arch = self.arch

        def body(x, inp):
            blk_p, st = inp
            x, new_st = tf.rwkv_block(blk_p, x, arch, st)
            return self._sp(x), new_st

        scan_body = jax.checkpoint(body) if self.remat else body
        x, new_states = jax.lax.scan(scan_body, x, (p["blocks"], states))
        return x, new_states

    def _whisper_encode(self, p, frames):
        arch = self.arch
        x = frames.astype(self.dtype)
        x = x + sinusoidal_positions(x.shape[1], arch.d_model).astype(x.dtype)[None]

        def body(x, blk_p):
            return tf.enc_block(
                blk_p, x, arch, q_chunk=self.q_chunk, kv_chunk=self.kv_chunk
            ), None

        scan_body = jax.checkpoint(body) if self.remat else body
        x, _ = jax.lax.scan(scan_body, x, p["enc_blocks"])
        return apply_norm(p["enc_norm"], x, arch.norm)

    # ==================================================================
    # Public: forward / loss
    # ==================================================================

    def forward(self, p, batch: Dict[str, jax.Array]):
        """Full-sequence forward -> (logits, StepAux).  Used by training."""
        arch = self.arch
        x, mrope = self._embed_in(p, batch)
        B, S = x.shape[:2]
        positions = batch.get(
            "positions", jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        )

        if arch.family in ("dense", "moe", "vlm"):
            x, _, prefix_aux, aux_stack = self._walk_attn_stack(
                p, x, positions, mrope, collect_cache=False,
                sieve=batch.get("sieve"),
            )
            aux = _aggregate_aux(arch, prefix_aux, aux_stack)
        elif arch.family == "linear_hybrid":
            x, _, aux_stack = self._walk_linear_hybrid(
                p, x, positions, None, batch.get("sieve"), step=False
            )
            aux = _aggregate_aux(arch, [], aux_stack)
        elif arch.family == "hybrid":
            x, _ = self._walk_hybrid_stack(
                p, x, positions, None, collect_cache=False, step=False
            )
            aux = _empty_aux(arch)
        elif arch.family == "ssm":
            states = self.init_cache(B, 0)
            x, _ = self._walk_rwkv_stack(p, x, states["blocks"])
            aux = _empty_aux(arch)
        elif arch.family == "audio":
            enc = self._whisper_encode(p, batch["embeds"])
            tokens = batch["tokens"]
            Bd, Sd = tokens.shape
            x = embed(p["embed"], tokens) + p["dec_pos"][:Sd][None]

            def body(x, blk_p):
                from .attention import project_cross_kv
                enc_kv = project_cross_kv(blk_p["xattn"], enc, arch.attn)
                x, _ = tf.dec_block_seq(
                    blk_p, x, None, enc_kv, arch,
                    q_chunk=min(self.q_chunk, Sd), kv_chunk=min(self.kv_chunk, Sd),
                )
                return x, None

            scan_body = jax.checkpoint(body) if self.remat else body
            x, _ = jax.lax.scan(scan_body, x, p["blocks"])
            aux = _empty_aux(arch)
        else:
            raise ValueError(arch.family)

        h = apply_norm(p["final_norm"], x, arch.norm)
        return h, aux

    def loss(self, p, batch: Dict[str, jax.Array]):
        """Next-token CE with sequence-chunked logits (bounded memory)."""
        h, aux = self.forward(p, batch)
        labels = batch["labels"]
        B, S = labels.shape
        chunk = min(self.loss_chunk, S)
        while S % chunk:
            chunk //= 2
        n_chunks = S // chunk
        w = p.get("w_out")
        table = p["embed"]

        pad_mask = (
            jnp.arange(self.vocab_padded) < self.arch.vocab_size
            if self.vocab_padded != self.arch.vocab_size
            else None
        )

        def ce_chunk(i):
            hc = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
            lc = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
            logits = (hc @ (w if w is not None else table.T)).astype(jnp.float32)
            if pad_mask is not None:
                logits = jnp.where(pad_mask, logits, -1e30)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - gold)

        total = jax.lax.map(ce_chunk, jnp.arange(n_chunks)).sum()
        ce = total / (B * S)
        arch = self.arch
        aux_coef = arch.moe.router_aux_coef if arch.moe is not None else 0.0
        return ce + aux_coef * aux.moe_aux, {"ce": ce, "aux": aux}

    # ==================================================================
    # Caches
    # ==================================================================

    def init_cache(self, batch: int, max_seq: int) -> Dict[str, Any]:
        """Per-slot decode state.  Attention KV is head-major, ``(L, B,
        n_kv_heads, max_seq, d_head)``: one kv head's ``(T, d_head)`` rows
        are contiguous, so the decode-attention kernels stream
        ``(block, d_head)`` tiles that match the TPU's (sublane, lane)
        tiling.  Every reader (prefill insert, the XLA twins, paged pools,
        snapshots) follows this one layout."""
        arch, dtype = self.arch, self.dtype
        a = arch.attn
        # §Perf iteration A2: int8 KV cache (halves decode HBM traffic);
        # only honored on the seq-par decode path which folds the scales in.
        import os as _os

        kv_int8 = (
            _os.environ.get("REPRO_KV_INT8", "0") == "1"
            and arch.family in ("dense", "moe", "vlm")
            and a.kind == "gqa"
        )

        def kv(n_layers):
            shape = (n_layers, batch, a.n_kv_heads, max_seq, a.d_head)
            if kv_int8:
                return (
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:4], jnp.float32),
                    jnp.zeros(shape[:4], jnp.float32),
                )
            return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

        if arch.family in ("dense", "moe", "vlm"):
            n_prefix = arch.moe.first_k_dense if arch.moe is not None else 0
            n_blocks = arch.n_layers - n_prefix
            if a.kind == "mla":
                m = a.mla
                def mla_cache(n):
                    return (
                        jnp.zeros((n, batch, max_seq, m.kv_lora_rank), dtype),
                        jnp.zeros((n, batch, max_seq, m.qk_rope_dim), dtype),
                    )
                c = {"blocks": mla_cache(n_blocks)}
                if n_prefix:
                    c["prefix"] = mla_cache(n_prefix)
            else:
                c = {"blocks": kv(n_blocks)}
                if n_prefix:
                    c["prefix"] = kv(n_prefix)
            return c
        if arch.family == "linear_hybrid":
            # two kinds of per-slot state side by side: the full layers' KV,
            # and each linear layer's float32 recurrent state and conv tail
            lin = arch.linear
            n_lin, n_full = _linear_layout(arch)
            return {
                "kv": kv(n_full),
                "recurrent": jnp.zeros(
                    (n_lin, batch, lin.n_v_heads, lin.d_k, lin.d_v), jnp.float32
                ),
                "conv": jnp.zeros(
                    (n_lin, batch, lin.conv_width - 1, lin.conv_dim), dtype
                ),
            }
        if arch.family == "hybrid":
            nseg, per, tail = _zamba_layout(arch)
            seg_states = jax.vmap(
                lambda _: jax.vmap(
                    lambda __: mamba2_init_state(batch, arch.d_model, arch.ssm, dtype)
                )(jnp.arange(per))
            )(jnp.arange(nseg))
            c = {
                "mamba_seg": seg_states,
                "attn": kv(nseg),
            }
            if tail:
                c["mamba_tail"] = jax.vmap(
                    lambda _: mamba2_init_state(batch, arch.d_model, arch.ssm, dtype)
                )(jnp.arange(tail))
            return c
        if arch.family == "ssm":
            return {
                "blocks": jax.vmap(
                    lambda _: rwkv6_init_state(batch, arch.d_model, arch.ssm, dtype)
                )(jnp.arange(arch.n_layers))
            }
        if arch.family == "audio":
            H = a.n_heads
            return {
                "self": kv(arch.n_layers),
                "cross": (
                    jnp.zeros((arch.n_layers, batch, arch.enc_seq, H, a.d_head), dtype),
                    jnp.zeros((arch.n_layers, batch, arch.enc_seq, H, a.d_head), dtype),
                ),
            }
        raise ValueError(arch.family)

    def init_paged_cache(self, n_pool: int, page: int) -> Dict[str, Any]:
        """Paged KV cache: per-layer shared head-major block pools
        ``(n_layers, n_pool, Kv, page, dh)`` replacing the dense per-slot
        buffers.  The
        block table that maps (slot, logical block) → pool block lives
        host-side (``serving.batching.PagedKVCache``) and arrives with
        each decode batch; physical block 0 is the reserved trash block
        idle slots write into."""
        import os as _os

        arch, dtype = self.arch, self.dtype
        a = arch.attn
        if arch.family not in ("dense", "moe", "vlm") or a.kind != "gqa":
            raise ValueError(
                "paged KV cache requires a gqa decoder-only family "
                f"(got family={arch.family}, attn={a.kind})"
            )
        if _os.environ.get("REPRO_KV_INT8", "0") == "1":
            raise ValueError("paged KV cache does not support int8 KV yet")

        def kv(n_layers):
            return (
                jnp.zeros((n_layers, n_pool, a.n_kv_heads, page, a.d_head), dtype),
                jnp.zeros((n_layers, n_pool, a.n_kv_heads, page, a.d_head), dtype),
            )

        n_prefix = arch.moe.first_k_dense if arch.moe is not None else 0
        c = {"blocks": kv(arch.n_layers - n_prefix)}
        if n_prefix:
            c["prefix"] = kv(n_prefix)
        return c

    # ==================================================================
    # Prefill
    # ==================================================================

    def prefill(self, p, batch: Dict[str, jax.Array]):
        """Forward that also returns the populated cache + last-pos logits."""
        arch = self.arch
        x, mrope = self._embed_in(p, batch)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        if arch.family in ("dense", "moe", "vlm"):
            x, caches, prefix_aux, aux_stack = self._walk_attn_stack(
                p, x, positions, mrope, collect_cache=True,
                sieve=batch.get("sieve"), in_place=self.moe_layers_in_place() > 0,
            )
            cache = {"blocks": caches["blocks"]}
            if "prefix" in caches:
                ks = [c[0] for c in caches["prefix"]]
                vs = [c[1] for c in caches["prefix"]]
                cache["prefix"] = (jnp.stack(ks), jnp.stack(vs))
            aux = _aggregate_aux(arch, prefix_aux, aux_stack)
        elif arch.family == "linear_hybrid":
            x, cache, aux_stack = self._walk_linear_hybrid(
                p, x, positions, None, batch.get("sieve"), step=False
            )
            aux = _aggregate_aux(arch, [], aux_stack)
        elif arch.family == "hybrid":
            x, new_states = self._walk_hybrid_stack(
                p, x, positions, None, collect_cache=True, step=False
            )
            cache, aux = new_states, _empty_aux(arch)
        elif arch.family == "ssm":
            states = self.init_cache(B, 0)
            x, new_states = self._walk_rwkv_stack(p, x, states["blocks"])
            cache, aux = {"blocks": new_states}, _empty_aux(arch)
        elif arch.family == "audio":
            enc = self._whisper_encode(p, batch["embeds"])
            tokens = batch["tokens"]
            Bd, Sd = tokens.shape
            x = embed(p["embed"], tokens) + p["dec_pos"][:Sd][None]
            from .attention import project_cross_kv

            def body(x, blk_p):
                enc_kv = project_cross_kv(blk_p["xattn"], enc, arch.attn)
                x, kv_ = tf.dec_block_seq(
                    blk_p, x, None, enc_kv, arch,
                    q_chunk=min(self.q_chunk, Sd), kv_chunk=min(self.kv_chunk, Sd),
                )
                return x, (kv_, enc_kv)

            x, (self_kv, cross_kv) = jax.lax.scan(body, x, p["blocks"])
            cache = {"self": self_kv, "cross": cross_kv}
            aux = _empty_aux(arch)
        else:
            raise ValueError(arch.family)

        h = apply_norm(p["final_norm"], x, arch.norm)
        logits = self._logits(p, h[:, -1:, :])
        return logits, cache, aux

    # ==================================================================
    # Decode step
    # ==================================================================

    def _use_seqpar_decode(self, cache) -> bool:
        """§Perf iteration A: sequence-parallel decode attention.  Applies
        when the GQA kv cache is T-sharded over the model axis (kv heads
        don't divide the TP degree).  REPRO_SEQPAR=0 restores the GSPMD
        baseline for before/after measurement."""
        import os as _os

        arch, mi = self.arch, self.mi
        if _os.environ.get("REPRO_SEQPAR", "1") == "0":
            return False
        if arch.attn.kind != "gqa" or arch.attn.mrope_sections is not None:
            return False
        if mi.mesh is None or mi.model_axis is None or mi.ep_size <= 1:
            return False
        if arch.attn.n_kv_heads % mi.ep_size == 0:
            return False  # head-sharded cache path is already gather-free
        try:
            T = cache["blocks"][0].shape[3]
            B = cache["blocks"][0].shape[1]
        except (KeyError, IndexError, AttributeError):
            return False
        dp = 1
        for a in mi.data_axes:
            dp *= mi.mesh.shape[a]
        return T % mi.ep_size == 0 and B % max(dp, 1) == 0

    def decode_step(self, p, batch: Dict[str, jax.Array], cache: Dict[str, Any]):
        """One-token step.  batch: tokens (B,1) [or embeds], position (B,)."""
        arch, mi = self.arch, self.mi
        x, mrope = self._embed_in(p, batch)
        position = batch["position"]
        B = x.shape[0]

        if arch.family in ("dense", "moe", "vlm"):
            moe = arch.moe is not None
            n_prefix = arch.moe.first_k_dense if moe else 0
            # paged decode: cache leaves are shared block pools and the
            # batch carries the block-table indexing state (fixed shapes —
            # no extra jit keys on the decode path)
            paged = None
            if "block_tables" in batch:
                paged = (
                    batch["block_tables"],
                    batch["pool_owner"],
                    batch["pool_pos"],
                )
            seq_par = False if paged is not None else self._use_seqpar_decode(cache)
            sieve = batch.get("sieve")
            auxes = []
            new_prefix = None
            if n_prefix:
                new_list = []
                for i in range(n_prefix):
                    blk = jax.tree.map(lambda a: a[i], p["prefix_blocks"])
                    cache_l = jax.tree.map(lambda a: a[i], cache["prefix"])
                    x, new_c, aux = tf.attn_mlp_block_decode(
                        blk, x, position, cache_l, arch, mi, moe=False,
                        mrope_positions=mrope, seq_par=seq_par, paged=paged,
                    )
                    new_list.append(new_c)
                    auxes.append(aux)
                new_prefix = jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)

            xs, block_of = self._block_scan(
                p["blocks"], self.moe_layers_in_place() > 0
            )

            def body(x, inp):
                blk_in, cache_l = inp
                x, new_c, aux = tf.attn_mlp_block_decode(
                    block_of(blk_in), x, position, cache_l, arch, mi, moe=moe,
                    mrope_positions=mrope, seq_par=seq_par, sieve=sieve,
                    paged=paged,
                )
                return x, (new_c, aux)

            x, (new_blocks, aux_stack) = jax.lax.scan(
                body, x, (xs, cache["blocks"])
            )
            new_cache = {"blocks": new_blocks}
            if n_prefix:
                new_cache["prefix"] = new_prefix
            aux = _aggregate_aux(arch, auxes, aux_stack)
        elif arch.family == "linear_hybrid":
            x, new_cache, aux_stack = self._walk_linear_hybrid(
                p, x, position, cache, batch.get("sieve"), step=True
            )
            aux = _aggregate_aux(arch, [], aux_stack)
        elif arch.family == "hybrid":
            x, new_cache = self._walk_hybrid_stack(
                p, x, position, cache, collect_cache=True, step=True
            )
            aux = _empty_aux(arch)
        elif arch.family == "ssm":
            x, new_states = self._walk_rwkv_stack(p, x, cache["blocks"])
            new_cache = {"blocks": new_states}
            aux = _empty_aux(arch)
        elif arch.family == "audio":
            pos_emb = p["dec_pos"][position % 448]  # structural clamp (448 max)
            x = x + pos_emb[:, None, :]

            def body(x, inp):
                blk_p, cache_l, cross_l = inp
                x, new_c = tf.dec_block_decode(
                    blk_p, x, position, cache_l, cross_l, arch
                )
                return x, new_c

            x, new_self = jax.lax.scan(
                body, x, (p["blocks"], cache["self"], cache["cross"])
            )
            new_cache = {"self": new_self, "cross": cache["cross"]}
            aux = _empty_aux(arch)
        else:
            raise ValueError(arch.family)

        h = apply_norm(p["final_norm"], x, arch.norm)
        logits = self._logits(p, h)
        return logits, new_cache, aux

    # ==================================================================
    # Input specs (dry-run stand-ins; no allocation)
    # ==================================================================

    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every input of the step function."""
        arch = self.arch
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        def token_batch(seq):
            b: Dict[str, Any] = {"tokens": sds((B, seq), i32)}
            if arch.family == "vlm":
                b["mrope_positions"] = sds((3, B, seq), i32)
            if arch.modality_stub == "vision_patches":
                pass  # patch embeds are merged upstream; tokens suffice
            return b

        if shape.kind == "train":
            if arch.family == "audio":
                return {
                    "embeds": sds((B, S, arch.d_model), self.dtype),
                    "tokens": sds((B, 448), i32),
                    "labels": sds((B, 448), i32),
                }
            b = token_batch(S)
            b["labels"] = sds((B, S), i32)
            return b
        if shape.kind == "prefill":
            if arch.family == "audio":
                return {
                    "embeds": sds((B, S, arch.d_model), self.dtype),
                    "tokens": sds((B, 448), i32),
                }
            return token_batch(S)
        if shape.kind == "decode":
            if arch.family == "audio":
                b = {"tokens": sds((B, 1), i32), "position": sds((B,), i32)}
            else:
                b = token_batch(1)
                b["position"] = sds((B,), i32)
                if arch.family == "vlm":
                    b["mrope_positions"] = sds((3, B, 1), i32)
            cache = jax.eval_shape(lambda: self.init_cache(B, S))
            return {"batch": b, "cache": cache}
        raise ValueError(shape.kind)


def _empty_aux(arch: ArchConfig) -> StepAux:
    E = arch.moe.n_experts if arch.moe is not None else 1
    return StepAux(
        jnp.zeros((), jnp.float32),
        jnp.zeros((0, E), jnp.int32),
        jnp.zeros((), jnp.int32),
    )


def _aggregate_aux(arch: ArchConfig, prefix_auxes, aux_stack: BlockAux) -> StepAux:
    moe_aux = aux_stack.moe_aux.sum()
    dropped = aux_stack.dropped.sum()
    counts = aux_stack.counts  # (L_moe, E) — per-layer Sieve input
    for a in prefix_auxes:
        moe_aux = moe_aux + a.moe_aux
        dropped = dropped + a.dropped
    return StepAux(moe_aux, counts, dropped)
