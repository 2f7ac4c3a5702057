"""Device-side top-k selection over the mesh-sharded state.

BASELINE.json config 5 names "per-chip top-k + tree-reduce over ICI"; the
reference has no counterpart (its only app is word count). After the
stream, chip d's state shard holds the FULL merged value for every key of
its hash class (keys are disjoint across chips — parallel/shuffle.py), so
the global top-k is a subset of the union of per-chip top-k's and the host
needs only D*k candidate records instead of the whole state — at
mesh-scale vocabularies that is the difference between shipping kilobytes
and shipping the state.

Exactness guard: the app's documented tie-break is bytewise on the WORD
(apps/top_k.py), which the device cannot see (it holds hashes). A tie AT
the per-chip k boundary could therefore cut a candidate that would win the
global word-order tie-break. `lax.top_k` over k+1 values detects exactly
that case per chip; any ambiguous chip makes the driver fall back to the
full state fetch — slower, never wrong. This is the framework's standard
posture: fast path sized for the common case, faults detected on device,
exact fallback (runtime/driver.py capacity replays).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mapreduce_rust_tpu.core.kv import KVBatch
from mapreduce_rust_tpu.parallel.shuffle import AXIS

_SELECTORS: dict = {}  # (mesh, k, cap) → jitted selector


def _make_selector(mesh: Mesh, k: int, cap: int):
    key = (mesh, k, cap)
    fn = _SELECTORS.get(key)
    if fn is not None:
        return fn
    kk = min(k + 1, cap)  # +1 probes the boundary tie

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(AXIS, None),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
    )
    def select(state: KVBatch):
        st = KVBatch(*(x[0] for x in state))
        neg = jnp.iinfo(jnp.int32).min
        vals = jnp.where(st.valid, st.value, neg)
        top_vals, idx = jax.lax.top_k(vals, kk)
        if kk > k:
            # kth and (k+1)th equal AND real → the cut is word-order
            # ambiguous on this chip (neg padding never counts as a tie).
            ambiguous = (top_vals[k - 1] == top_vals[k]) & (top_vals[k] > neg)
            top_vals, idx = top_vals[:k], idx[:k]
        else:
            ambiguous = jnp.bool_(False)
        keys1 = st.k1[idx]
        keys2 = st.k2[idx]
        valid = top_vals > neg
        return (
            jnp.stack([keys1, keys2], axis=1)[None],
            jnp.where(valid, top_vals, 0)[None],
            valid[None],
            ambiguous[None],
        )

    _SELECTORS[key] = select
    return select


def topk_candidates(mesh: Mesh, state: KVBatch, k: int):
    """(keys uint32[n,2], values int64[n]) — the per-chip top-k union, or
    None when any chip's k-boundary is value-tied (caller must fall back
    to the full state fetch to preserve the word-order tie-break)."""
    cap = state.k1.shape[-1]
    select = _make_selector(mesh, k, cap)
    keys, vals, valid, ambiguous = jax.device_get(select(state))
    if bool(np.asarray(ambiguous).any()):
        return None
    keys = np.asarray(keys).reshape(-1, 2)
    vals = np.asarray(vals).reshape(-1)
    mask = np.asarray(valid).reshape(-1)
    return keys[mask], vals[mask]
