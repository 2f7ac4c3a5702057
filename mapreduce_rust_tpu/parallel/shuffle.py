"""The ICI all-to-all shuffle — the north-star hot path.

Replaces the reference's file-plane shuffle: there every map task routes
each KV pair by ``DefaultHasher(key) % reduce_n`` into one of reduce_n
files with one awaited write + one println per pair
(src/mr/worker.rs:117-140), and reduce tasks read the files back by name
(worker.rs:79-109). Here the "files" are rows of a bucket-major device
array and the routing is one ``lax.all_to_all`` over the ICI mesh inside
``shard_map``:

    per chip:  tokenize → app.device_map → combine_chunk (map-side combiner)
               → bucket_scatter into D buckets (bucket = k1 % D)
    all chips: all_to_all — bucket d of every chip lands on chip d
    per chip:  count_unique over the received records → this chip's
               distinct keys (its hash class) → merge into its state shard

Keys are disjoint across chips after the shuffle (chip d owns exactly the
keys with k1 % D == d), so per-chip states merge/spill independently and
the job total is the union of shard results — same invariant the
reference gets from hash % reduce_n file naming.

Static shapes under jit mean fixed bucket capacity; skewed buckets can
overflow (SURVEY.md §7 hard part 2). Overflow is *counted before the merge*
and the driver replays that group through a lazily-compiled full-width
path (bucket capacity = the whole update), so results are exact always —
the fast path is just sized by ``Config.bucket_capacity_factor``.

Multi-host: the same code runs over a global mesh after
``jax.distributed.initialize`` — the all_to_all then rides ICI intra-slice
and DCN across slices. This environment is single-host, so that path is
exercised only as far as compilation (see __graft_entry__.dryrun_multichip).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mapreduce_rust_tpu.apps.base import App
from mapreduce_rust_tpu.core.kv import KVBatch
from mapreduce_rust_tpu.ops.groupby import (
    clamp_batch,
    combine_chunk,
    count_unique,
    merge_batches,
)
from mapreduce_rust_tpu.ops.partition import bucket_scatter
from mapreduce_rust_tpu.ops.tokenize import tokenize_and_hash

AXIS = "shards"


def make_mesh(n_devices: int | None = None, backend: str | None = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices of ``backend`` (default:
    JAX's default backend, all of its devices). Too few devices is an
    error — a mesh never moves to another backend behind the caller's back
    (tests get their virtual CPU devices from conftest's XLA_FLAGS)."""
    devs = jax.devices(backend) if backend else jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise RuntimeError(
            f"a {n}-device mesh needs {n} {devs[0].platform} devices; "
            f"this process has {len(devs)}"
        )
    return Mesh(np.array(devs[:n]), (AXIS,))


def state_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS, None))


def sharded_empty_state(mesh: Mesh, capacity_per_shard: int) -> KVBatch:
    """KVBatch [D, capacity] sharded one row per chip."""
    d = mesh.devices.size
    host = KVBatch.empty(capacity_per_shard)
    stacked = KVBatch(*(np.broadcast_to(np.asarray(x), (d,) + x.shape).copy() for x in host))
    return jax.device_put(stacked, state_sharding(mesh))


_SHUFFLE_FNS: dict = {}  # (app, u_cap, bucket_cap, mesh, repl) → (map_shuffle, merge)


def make_shuffle_step_fns(app: App, u_cap: int, bucket_cap: int, mesh: Mesh,
                          replicate_flags: bool = False):
    """Cached wrapper: apps are frozen dataclasses and Mesh hashes by value,
    so repeated run_job calls in one process reuse the jitted closures
    (and therefore jax.jit's executable cache) instead of recompiling.

    replicate_flags=True returns the overflow counters psum-reduced —
    identical on every chip — for multi-process drivers where no host can
    see the whole global array (see _chip_shuffle_tail)."""
    key = (app, u_cap, bucket_cap, mesh, replicate_flags)
    fns = _SHUFFLE_FNS.get(key)
    if fns is None:
        fns = _SHUFFLE_FNS[key] = _build_shuffle_step_fns(
            app, u_cap, bucket_cap, mesh, replicate_flags
        )
    return fns


def _chip_shuffle_tail(kv: KVBatch, doc_table, app: App, u_cap: int,
                       bucket_cap: int, d: int, replicate_flags: bool):
    """THE shuffle body, shared by every map_shuffle variant (chunk-input,
    kv-input, flag-replicating): device_map (each record stamped with its
    doc id from the chip's doc table) and combine, as the single-chip step
    does them (ops/groupby.combine_chunk) → bucket scatter → all_to_all →
    combine, with the clamp-on-overflow contract: if ANY chip overflowed,
    every chip's local result clamps to empty (the psum makes them agree)
    and the driver replays through a wider tier — which is what lets
    merges dispatch before any flag reaches the host.

    Returns (local KVBatch, p_flag, b_flag, live): per-chip raw counters,
    or the psum-reduced (replicated) totals when replicate_flags — the
    form a multi-process driver needs, since it can only read its own
    shards. ``live`` is this chip's count of valid bucket rows sent into
    the all_to_all, the used part of its fixed-capacity buckets."""
    op = app.combine_op
    # named_scope blocks label the lowered XLA ops, so a device profile
    # (Config.profile_dir) shows combine / all_to_all / reduce as named
    # regions that line up with the host tracer's "mesh.all_to_all" spans
    # (runtime/trace.py) — the ICI-vs-compute attribution VERDICT r5 asks
    # for, readable straight off the xprof timeline.
    with jax.named_scope("shuffle.map_combine"):
        update, p_ovf = combine_chunk(kv, doc_table, app.device_map, op, u_cap)
        # Shared partition seam (ops/partition.py): the ICI shuffle always
        # routes state ownership by hash — chip d owns hash class k1 % d.
        # Range apps (sort) still shuffle by hash here; their RANGE order
        # is established at host egress, where word bytes exist
        # (apps/base.App.route_block — hashes alone cannot order words).
        buckets, b_ovf = bucket_scatter(update, num_buckets=d,
                                        capacity=bucket_cap, mode="hash")
        live = jnp.sum(buckets.valid.astype(jnp.int32))
    with jax.named_scope("shuffle.all_to_all"):
        recv = jax.tree.map(
            lambda x: jax.lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0, tiled=True),
            buckets,
        )
    with jax.named_scope("shuffle.reduce_combine"):
        flat = KVBatch(*(x.reshape(-1) for x in recv))  # [d * bucket_cap]
        local = count_unique(flat, op=op)  # distinct keys of MY hash class
    p_tot = jax.lax.psum(p_ovf, AXIS)
    b_tot = jax.lax.psum(b_ovf, AXIS)
    # Clamp keys too, not just validity: the state shard stays sorted only
    # if clamped records become SENTINEL padding (ops/groupby.clamp_batch).
    local = clamp_batch(local, (p_tot + b_tot) == 0)
    if replicate_flags:
        return local, p_tot, b_tot, live
    return local, p_ovf, b_ovf, live


_KV_SHUFFLE_FNS: dict = {}  # (app, u_cap, bucket_cap, mesh, width) → fn


def make_kv_shuffle_step_fns(app: App, u_cap: int, bucket_cap: int, mesh: Mesh):
    """map_shuffle over PRE-TOKENIZED records: KVBatch [D, W] (one row of
    tokens per chip, e.g. parallel/halo.make_sharded_tokenizer output) and
    doc tables [D, 1 + DOC_SLOTS] → (local KVBatch [D, D*bucket_cap],
    partial_ovf [D], bucket_ovf [D]).
    The combine → bucket scatter → all_to_all → combine tail is identical
    to make_shuffle_step_fns; only the tokenizer is elsewhere. Pair with
    make_shuffle_step_fns(...)[1] for the merge."""
    key = (app, u_cap, bucket_cap, mesh)
    fn = _KV_SHUFFLE_FNS.get(key)
    if fn is None:
        fn = _KV_SHUFFLE_FNS[key] = _build_kv_shuffle(app, u_cap, bucket_cap, mesh)
    return fn


def _build_kv_shuffle(app: App, u_cap: int, bucket_cap: int, mesh: Mesh):
    d = mesh.devices.size

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS)),
    )
    def map_shuffle_kv(kv: KVBatch, doc_tables: jnp.ndarray):
        local, p_ovf, b_ovf, _live = _chip_shuffle_tail(
            KVBatch(*(x[0] for x in kv)), doc_tables[0], app, u_cap, bucket_cap,
            d, replicate_flags=False,
        )
        return (
            KVBatch(*(x[None] for x in local)),
            p_ovf[None],
            b_ovf[None],
        )

    return map_shuffle_kv


def _build_shuffle_step_fns(app: App, u_cap: int, bucket_cap: int, mesh: Mesh,
                            replicate_flags: bool = False):
    """(map_shuffle, merge) — the group-of-D-chunks mesh pipeline.

    map_shuffle: chunks [D, chunk_bytes], doc tables [D, 1 + DOC_SLOTS]
        (runtime/chunker.doc_table) →
        (local KVBatch [D, D*bucket_cap], partial_ovf [D], bucket_ovf [D],
        live_rows [D]).
        partial_ovf counts capacity faults on the map side — distinct keys
        past u_cap plus raw tokens past the compaction cap
        (ops/groupby.compaction_cap); bucket_ovf counts records dropped by
        bucket skew beyond bucket_cap; live_rows counts each chip's valid
        bucket rows, of the D*bucket_cap it sends.
        Either nonzero → the driver replays the group through a wider tier
        (bucket_cap=u_cap kills bucket overflow by construction;
        u_cap=chunk capacity kills partial overflow) — results stay exact.
        The tokenize step is here; everything after is _chip_shuffle_tail.
    merge: (state [D, cap], local) → (state, evicted [D, D*bucket_cap],
        evicted_counts [D]), donating the old state.
    """
    op = app.combine_op
    d = mesh.devices.size
    use_pallas = mesh.devices.ravel()[0].platform == "tpu"

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
    )
    def map_shuffle(chunks: jnp.ndarray, doc_tables: jnp.ndarray):
        local, p_ovf, b_ovf, live = _chip_shuffle_tail(
            tokenize_and_hash(chunks[0], use_pallas=use_pallas),
            doc_tables[0], app, u_cap, bucket_cap,
            d, replicate_flags,
        )
        return (
            KVBatch(*(x[None] for x in local)),
            p_ovf[None],
            b_ovf[None],
            live[None],
        )

    @functools.partial(jax.jit, donate_argnums=(0,))
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=(P(AXIS, None), P(AXIS), P(AXIS)),
    )
    def merge(state: KVBatch, local: KVBatch):
        st = KVBatch(*(x[0] for x in state))
        lc = KVBatch(*(x[0] for x in local))
        # local is a count_unique output — key-distinct — so it joins the
        # state shard's sort as it is.
        new_state, evicted = merge_batches(st, lc, op=op, update_sorted=True)
        ev_count = jnp.sum(evicted.valid.astype(jnp.int32))
        return (
            KVBatch(*(x[None] for x in new_state)),
            KVBatch(*(x[None] for x in evicted)),
            ev_count[None],
        )

    return map_shuffle, merge


#: Wire size of one KVBatch record through the all_to_all:
#: k1 (4) + k2 (4) + value (4) + valid (1).
RECORD_WIRE_BYTES = 13


def wire_bytes_per_round(n_devices: int, bucket_cap: int) -> int:
    """Bytes one all_to_all round moves across the mesh: every chip sends
    D fixed-capacity buckets (static shapes under jit — padding crosses the
    interconnect too, which is exactly why this number, not the live-record
    count, is the ICI-attribution metric)."""
    return n_devices * n_devices * bucket_cap * RECORD_WIRE_BYTES


def default_bucket_cap(u_cap: int, n_devices: int, factor: float) -> int:
    """Per-(src,dst) bucket capacity: even split × slack factor, padded to
    the next multiple of 8 for TPU-friendly layouts."""
    cap = math.ceil(u_cap / n_devices * factor)
    return min(u_cap, (cap + 7) // 8 * 8)


# ---- multi-host (multi-process) variants ---------------------------------
#
# Across processes no host sees the whole of any global array, so every
# per-group decision the driver makes (replay? keep going?) must come back
# as a REPLICATED value each process can read from its own local shards.
# Same kernels otherwise — SPMD means the jitted programs below execute
# identically on every process over the global mesh.

def make_mh_shuffle_step_fns(app: App, u_cap: int, bucket_cap: int, mesh: Mesh):
    """(map_shuffle, merge) for multi-process meshes: the standard step fns
    with psum-REPLICATED overflow flags, so any process reads its local
    shard and agrees with every other process on whether to replay."""
    return make_shuffle_step_fns(app, u_cap, bucket_cap, mesh, replicate_flags=True)


_ROUND_FNS: dict = {}


def make_round_fn(mesh: Mesh):
    """psum a per-chip int32 over the mesh, returned replicated [D] — the
    multi-process loop's 'does anyone still have data?' coordinator and,
    because it is a collective, its round barrier."""
    fn = _ROUND_FNS.get(mesh)
    if fn is not None:
        return fn

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS))
    def round_flag(flags: jnp.ndarray):
        return jax.lax.psum(flags[0], AXIS)[None]

    _ROUND_FNS[mesh] = round_flag
    return round_flag


def local_rows(x) -> np.ndarray:
    """The rows of a [D, ...]-sharded global array owned by THIS process,
    concatenated in global order — the only part of a global array a
    multi-process participant may fetch."""
    shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])


def local_batch(batch: KVBatch) -> KVBatch:
    """local_rows over every leaf of a sharded KVBatch."""
    return KVBatch(*(local_rows(x) for x in batch))


def shard_fill_counts(state: KVBatch) -> "list[int]":
    """Valid-record count per ADDRESSABLE shard of a [D, cap]-sharded
    state, in global shard order — the hash-class skew signal: each chip's
    shard holds exactly its hash classes' distinct keys, so a hot shard
    here means the key distribution (not the interconnect) is what one
    chip's merge and egress are paying for. One blocking readback of D
    bool vectors; call at finalize, never from the stream loop."""
    shards = sorted(
        state.valid.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    return [int(np.asarray(s.data).sum()) for s in shards]
