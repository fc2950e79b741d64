"""Engine snapshot/restore: crash-consistent serving runtime state.

Format (directory per snapshot, shared codec with train checkpoints):
    snap_<n>/
      manifest.msgpack   — snapshot version, leaf manifest (shape / dtype /
                           sha256 per leaf), state-blob sha256
      state.msgpack      — host-side runtime state (RNG, requests, cost
                           table, sieve flags, feed/health monitors, stats)
      leaf_<i>.npy       — KV cache leaves, then SieveState arrays
      COMMITTED          — written last (atomic commit marker)

What makes a restore *bit-identical* (pinned by tests/test_recovery.py):

* the KV cache and batch slots round-trip exactly (sha256 per leaf), so
  the next decode step reads the same attention state;
* the device-resident ``SieveState`` arrays are snapshotted *directly*
  rather than re-exported from the restored cost table — mid-cadence
  table updates would otherwise make the re-export differ from what the
  uninterrupted run's compiled step is actually reading;
* the NumPy PCG64 RNG state round-trips exactly (128-bit state words ride
  the codec's bigint extension);
* ``CostTable.version`` is restored verbatim (``load_state_dict`` alone
  bumps it), so the refresh cadence's version-skip logic fires at the
  same steps;
* the TimingFeed telemetry cursor is *not* restored — a fresh process
  has a fresh ring, and restoring a stale index would skip events.

Corruption handling mirrors ``train.checkpoint``: every leaf and the
state blob are verified against the manifest *before* any engine field is
mutated, and :func:`restore_engine_snapshot` walks back to the previous
committed snapshot (warn + ``n_fallbacks``) when the newest fails.
"""

from __future__ import annotations

import os
import shutil
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.recovery.codec import (
    commit_dir,
    committed_dirs,
    is_committed,
    pack_state,
    read_leaf,
    sha256_array,
    sha256_bytes,
    to_storable,
    unpack_state,
)

SNAPSHOT_VERSION = 1
_SNAP_PREFIX = "snap_"

# fallback telemetry: times restore walked past a corrupt snapshot
n_fallbacks = 0


def _snap_path(snap_dir: str, snap_id: int) -> str:
    return os.path.join(snap_dir, f"{_SNAP_PREFIX}{snap_id:08d}")


def list_snapshots(snap_dir: str) -> List[Tuple[int, str]]:
    """Committed snapshots as ascending ``(snap_id, path)`` pairs."""
    return committed_dirs(snap_dir, _SNAP_PREFIX)


def latest_snapshot(snap_dir: str) -> Optional[int]:
    snaps = list_snapshots(snap_dir)
    return snaps[-1][0] if snaps else None


# ---------------------------------------------------------------------------
# Save
# ---------------------------------------------------------------------------


def _gather_state(engine) -> Dict[str, Any]:
    """Host-side runtime state blob (everything except array leaves)."""
    sched = engine.sched
    state: Dict[str, Any] = {
        "version": SNAPSHOT_VERSION,
        "rng": engine.rng.bit_generator.state,
        "requests": {
            "queue": [r.to_state() for r in sched.queue],
            "slots": [None if r is None else r.to_state() for r in sched.slots],
            "finished": [r.to_state() for r in sched.finished],
        },
        "sieve": {
            "version": engine._sieve_version,
            "gpu_only": engine._sieve_gpu_only,
            "refreshes": list(engine.sieve_refreshes),
            "max_count": getattr(engine, "_sieve_max_count", None),
        },
        "pim_healthy": engine.pim_healthy,
        "pending_tail_counts": sorted(engine._pending_tail_counts),
        "last_head_counts": list(engine._last_head_counts),
        "last_decode_batch": engine._last_decode_batch,
        "last_kv_depth": engine._last_kv_depth,
        "stats": {
            "steps": engine.stats.steps,
            "decode_tokens": engine.stats.decode_tokens,
            "prefill_tokens": engine.stats.prefill_tokens,
            "wall_time": engine.stats.wall_time,
            "dropped_tokens": engine.stats.dropped_tokens,
            "routed_tokens": engine.stats.routed_tokens,
            "truncated_requests": engine.stats.truncated_requests,
            "partitions": engine.stats.partitions,
        },
    }
    if getattr(engine, "paged", None) is not None:
        # host-side block-table state; the device pools themselves ride
        # along as ordinary cache leaves
        state["paged"] = engine.paged.state_dict()
    if engine.is_moe:
        state["cost_table"] = {
            "state": engine.cost_table.state_dict(),
            "version": engine.cost_table.version,
            "n_updates": engine.cost_table.n_updates,
            "n_fallback_lookups": engine.cost_table.n_fallback_lookups,
            "n_rejected": engine.cost_table.n_rejected,
        }
    if engine._timing_feed is not None:
        state["timing_feed"] = engine._timing_feed.state_dict()
    if engine.health is not None:
        state["health"] = engine.health.state_dict()
    return state


def save_engine_snapshot(
    engine,
    snap_dir: str,
    snap_id: Optional[int] = None,
    keep: Optional[int] = None,
) -> str:
    """Atomically snapshot ``engine``'s runtime state.

    ``snap_id`` defaults to the engine's current step count.  ``keep``
    prunes to the newest N committed snapshots after the write (the new
    snapshot is only committed once fully written, so pruning can never
    leave the directory empty-but-for-a-torn-write).
    """
    if snap_id is None:
        snap_id = engine.stats.steps
    os.makedirs(snap_dir, exist_ok=True)

    cache_leaves = jax.tree_util.tree_leaves(engine.cache)
    host_leaves = [np.asarray(jax.device_get(x)) for x in cache_leaves]
    n_cache = len(host_leaves)
    if engine._sieve_state is not None:
        host_leaves.extend(
            np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(engine._sieve_state)
        )
    state = _gather_state(engine)
    state["n_cache_leaves"] = n_cache
    state["n_sieve_leaves"] = len(host_leaves) - n_cache
    state_blob = pack_state(state)

    def _write(tmp: str) -> None:
        entries = []
        for i, arr in enumerate(host_leaves):
            storable, logical = to_storable(arr)
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), storable)
            entries.append(
                {
                    "shape": list(arr.shape),
                    "dtype": logical,
                    "sha256": sha256_array(storable),
                }
            )
        with open(os.path.join(tmp, "state.msgpack"), "wb") as f:
            f.write(state_blob)
        manifest = {
            "snapshot_version": SNAPSHOT_VERSION,
            "snap_id": snap_id,
            "n_leaves": len(entries),
            "leaves": entries,
            "state_sha256": sha256_bytes(state_blob),
        }
        with open(os.path.join(tmp, "manifest.msgpack"), "wb") as f:
            f.write(pack_state(manifest))

    final = commit_dir(_snap_path(snap_dir, snap_id), _write)
    if keep is not None and keep >= 1:
        for _, path in list_snapshots(snap_dir)[:-keep]:
            shutil.rmtree(path)
    return final


# ---------------------------------------------------------------------------
# Restore
# ---------------------------------------------------------------------------


def _load_snapshot(path: str) -> Tuple[Dict[str, Any], List[np.ndarray]]:
    """Read + fully verify one snapshot; raises before any engine mutation.

    ``IOError`` on checksum mismatch, ``FileNotFoundError`` on truncation,
    ``ValueError`` on a malformed blob — the signatures the fallback walks
    past.
    """
    with open(os.path.join(path, "manifest.msgpack"), "rb") as f:
        manifest = unpack_state(f.read())
    if manifest.get("snapshot_version") != SNAPSHOT_VERSION:
        raise ValueError(
            f"unsupported snapshot version {manifest.get('snapshot_version')!r}"
        )
    with open(os.path.join(path, "state.msgpack"), "rb") as f:
        state_blob = f.read()
    if sha256_bytes(state_blob) != manifest["state_sha256"]:
        raise IOError(f"state blob checksum mismatch in {path}")
    state = unpack_state(state_blob)
    leaves = [
        read_leaf(path, i, meta, verify=True)
        for i, meta in enumerate(manifest["leaves"])
    ]
    if len(leaves) != state["n_cache_leaves"] + state["n_sieve_leaves"]:
        raise ValueError(f"leaf count mismatch in {path}")
    return state, leaves


def _apply(engine, state: Dict[str, Any], leaves: List[np.ndarray]) -> None:
    """Mutate ``engine`` to the verified snapshot state."""
    from repro.core.scheduler_jax import SieveState
    from repro.serving.request import Request

    # ---- KV cache (structure from the fresh engine's own cache) ----
    n_cache = state["n_cache_leaves"]
    old_leaves, treedef = jax.tree_util.tree_flatten(engine.cache)
    if len(old_leaves) != n_cache:
        raise ValueError(
            f"snapshot has {n_cache} cache leaves, engine has {len(old_leaves)}"
        )
    new_cache = []
    for ref, arr in zip(old_leaves, leaves[:n_cache]):
        if tuple(arr.shape) != tuple(ref.shape):
            raise ValueError(
                f"cache leaf shape {arr.shape} != engine {ref.shape} "
                "(snapshot from a different batching config?)"
            )
        new_cache.append(jnp.asarray(arr, dtype=ref.dtype))
    engine.cache = jax.tree_util.tree_unflatten(treedef, new_cache)

    # ---- paged block-table state (host side of the paged KV cache) ----
    paged_state = state.get("paged")
    engine_paged = getattr(engine, "paged", None)
    if (paged_state is None) != (engine_paged is None):
        raise ValueError(
            "paged KV layout mismatch: snapshot "
            f"{'has' if paged_state is not None else 'lacks'} block-table "
            "state but the engine "
            f"{'lacks' if engine_paged is None else 'has'} a paged cache"
        )
    if paged_state is not None:
        engine_paged.load_state_dict(paged_state)

    # ---- device SieveState: restored verbatim, never re-exported ----
    sv = state["sieve"]
    stale = engine._sieve_state
    if state["n_sieve_leaves"]:
        pim_t, params = leaves[n_cache], leaves[n_cache + 1]
        engine._sieve_state = jax.device_put(
            SieveState(
                pim_time_by_count=jnp.asarray(pim_t),
                params=jnp.asarray(params),
            )
        )
    else:
        engine._sieve_state = None
    if stale is not None:
        for leaf in jax.tree_util.tree_leaves(stale):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
    engine._sieve_version = int(sv["version"])
    engine._sieve_gpu_only = bool(sv["gpu_only"])
    engine.sieve_refreshes = [int(s) for s in sv["refreshes"]]

    # ---- RNG (PCG64 words round-trip via the bigint extension) ----
    engine.rng = np.random.default_rng()
    engine.rng.bit_generator.state = state["rng"]

    # ---- requests (queue / slots / finished) ----
    reqs = state["requests"]
    sched = engine.sched
    sched.queue.clear()
    sched.queue.extend(Request.from_state(d) for d in reqs["queue"])
    sched.slots = [
        None if d is None else Request.from_state(d) for d in reqs["slots"]
    ]
    sched.finished = [Request.from_state(d) for d in reqs["finished"]]

    # ---- cost table (version verbatim: load_state_dict alone bumps it) ----
    ct = state.get("cost_table")
    if ct is not None:
        engine.cost_table.load_state_dict(ct["state"])
        engine.cost_table.version = int(ct["version"])
        engine.cost_table.n_updates = int(ct["n_updates"])
        engine.cost_table.n_fallback_lookups = int(ct["n_fallback_lookups"])
        engine.cost_table.n_rejected = int(ct["n_rejected"])

    # ---- measured loop + health ----
    if engine._timing_feed is not None and "timing_feed" in state:
        engine._timing_feed.load_state_dict(state["timing_feed"])
    if engine.health is not None and "health" in state:
        engine.health.load_state_dict(state["health"])
    engine.pim_healthy = bool(state["pim_healthy"])
    engine._pending_tail_counts = set(
        int(n) for n in state["pending_tail_counts"]
    )
    engine._last_head_counts = [int(n) for n in state["last_head_counts"]]
    engine._last_decode_batch = int(state["last_decode_batch"])
    engine._last_kv_depth = int(state["last_kv_depth"])

    # ---- stats ----
    s = state["stats"]
    engine.stats.steps = int(s["steps"])
    engine.stats.decode_tokens = int(s["decode_tokens"])
    engine.stats.prefill_tokens = int(s["prefill_tokens"])
    engine.stats.wall_time = float(s["wall_time"])
    engine.stats.dropped_tokens = int(s["dropped_tokens"])
    engine.stats.routed_tokens = int(s["routed_tokens"])
    engine.stats.truncated_requests = int(s.get("truncated_requests", 0))
    engine.stats.partitions = list(s["partitions"])


def restore_engine_snapshot(
    engine,
    snap_dir: str,
    snap_id: Optional[int] = None,
    fallback: bool = True,
) -> int:
    """Restore ``engine`` from a snapshot; returns the snap id restored.

    With ``snap_id=None`` the newest committed snapshot is used, walking
    back past corrupt/truncated ones when ``fallback`` (warn +
    ``n_fallbacks`` counter).  An explicit ``snap_id`` restores exactly
    that snapshot or raises.  Verification is complete before the first
    engine field is mutated, so a failed candidate never leaves the
    engine half-restored.
    """
    global n_fallbacks
    if snap_id is not None:
        path = _snap_path(snap_dir, snap_id)
        if not is_committed(path):
            raise FileNotFoundError(
                f"snapshot at {path} is missing or uncommitted"
            )
        candidates = [(snap_id, path)]
    else:
        candidates = list_snapshots(snap_dir)
        if not candidates:
            raise FileNotFoundError(f"no committed snapshots in {snap_dir}")
    last_err: Optional[Exception] = None
    for sid, path in reversed(candidates):
        try:
            state, leaves = _load_snapshot(path)
        except (IOError, ValueError, KeyError) as e:
            last_err = e
            if snap_id is not None or not fallback:
                raise
            n_fallbacks += 1
            warnings.warn(
                f"snapshot {path} failed verification ({e}); "
                f"falling back to previous committed snapshot"
            )
            continue
        _apply(engine, state, leaves)
        return sid
    raise IOError(f"no snapshot in {snap_dir} restored cleanly") from last_err
