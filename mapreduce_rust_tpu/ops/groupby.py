"""Sort + run reduce group-by — the TPU-native reduce engine.

Replaces the reference reduce path (src/mr/worker.rs:157-193): there, all
pairs of a partition are parsed from files, ``sort_by`` key
(worker.rs:162-164), then a streaming group-by calls the reduce UDF per key
run (worker.rs:169-184 — with the last group silently dropped, a bug we do
not reproduce). Here the same shape is ``lax.sort`` on the hash-pair key
(lexicographic) followed by run-boundary detection by neighbour compares,
one representative row a run, and a second sort that packs the
representatives to the front — every group flushed, including the last,
by construction.

Reduce ops (the associative-combiner contract every app must satisfy):

- ``"sum"``  — word count: total occurrences per key.
- ``"max"`` / ``"min"`` — extremal value per key.
- ``"distinct"`` — the value joins the sort key; one output record per
  distinct (key, value) pair. This is how inverted_index represents
  doc-id posting sets on device: dedup is associative, so per-chunk
  distinct sets merge into a global distinct set exactly like partial
  counts merge into totals.

All functions keep static shapes: outputs are padded to the input capacity
with SENTINEL keys so they stay jit/shard_map-friendly.

Both the map-side combine (combine_chunk, count_unique) and the
running-state merge (merge_batches) are sorts, prefix sums and neighbour
compares only. They make no gather or scatter whose index count scales
with a chunk, an update or the state: on the v5e every data-dependent
element access costs roughly 10-15 ns, while XLA's sort streams.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mapreduce_rust_tpu.core.hashing import SENTINEL
from mapreduce_rust_tpu.core.kv import KVBatch

#: Ops whose combiner is idempotent per (key, value) — the value is part of
#: the sort key and duplicates collapse to one record.
_VALUE_KEYED_OPS = frozenset({"distinct"})
REDUCE_OPS = frozenset({"sum", "max", "min", "distinct"})


def sort_kv(batch: KVBatch, by_value: bool = False) -> KVBatch:
    """Sort records by (k1, k2) — or (k1, k2, value) when ``by_value``.

    SENTINEL-keyed padding sorts to the end either way (SENTINEL is the max
    uint32, so padding keys dominate the comparison before value is reached).
    """
    num_keys = 3 if by_value else 2
    # Unstable: ~25% cheaper comparator (measured on XLA CPU at 320K rows,
    # 163→123 ms) and tie order is immaterial — combine_adjacent_unique's
    # pair combine is commutative, so records tied on the full key set
    # produce identical results in any order.
    k1, k2, value, valid = jax.lax.sort(
        (batch.k1, batch.k2, batch.value, batch.valid.astype(jnp.int32)),
        num_keys=num_keys,
        is_stable=False,
    )
    return KVBatch(k1, k2, value, valid.astype(bool))


_OP_IDENTITY = {
    "sum": 0,
    "max": jnp.iinfo(jnp.int32).min,
    "min": jnp.iinfo(jnp.int32).max,
}


def combine_adjacent_unique(merged: KVBatch, op: str = "sum") -> KVBatch:
    """Reduce a sorted batch where every key appears AT MOST TWICE among
    valid records, the two adjacent (the sorted concatenation of two
    key-distinct sides — true for every running-state merge: state and
    update are both count_unique-style reduced).

    Same output contract as segment_reduce_sorted — front-packed distinct
    keys, SENTINEL fill — by shifted compares that fold each key's two
    copies in place, plus the compaction sort (_compact_runs).
    """
    if op not in REDUCE_OPS:
        raise ValueError(f"unknown reduce op: {op}")
    k1, k2, val, valid = merged.k1, merged.k2, merged.value, merged.valid
    by_value = op in _VALUE_KEYED_OPS
    eq = (k1[:-1] == k1[1:]) & (k2[:-1] == k2[1:])
    if by_value:
        eq = eq & (val[:-1] == val[1:])
    false1 = jnp.zeros((1,), bool)
    eq_next = jnp.concatenate([eq, false1])   # i equals i+1
    first = jnp.concatenate([~false1, ~eq])   # run head mask
    nxt_valid = jnp.concatenate([valid[1:], false1])
    ident = jnp.int32(_OP_IDENTITY.get(op, 0))
    if by_value:
        # Value is part of the key: every run member shares it.
        pairv = val
    else:
        v = jnp.where(valid, val, ident)
        nxt_v = jnp.concatenate([v[1:], jnp.full((1,), ident, v.dtype)])
        other = jnp.where(eq_next, nxt_v, ident)
        if op == "sum":
            pairv = v + other
        elif op == "max":
            pairv = jnp.maximum(v, other)
        else:
            pairv = jnp.minimum(v, other)
    # A run is live iff its head or the head's twin is valid; only the
    # sentinel run (fixed below) has more than two members.
    live = first & (valid | (eq_next & nxt_valid))
    # The ONE run that can mix valid records with padding is the
    # (SENTINEL, SENTINEL) tail: the invalid⇒SENTINEL-key invariant makes
    # every real-keyed run all-valid (≤1 member per side), but a real word
    # hashing to the sentinel pair lands INSIDE the padding run, possibly
    # not adjacent to its cross-side twin. Fix that run directly with one
    # masked reduction — cheaper than ordering validity into the merge.
    is_sent = (k1 == jnp.uint32(SENTINEL)) & (k2 == jnp.uint32(SENTINEL))
    if by_value:
        # Value joins the key, so only the padding-valued (0) sentinel run
        # can contain padding; a live valid member keeps it alive.
        sent0 = is_sent & (val == 0)
        head = first & sent0
        live = live | (head & jnp.any(valid & sent0))
    else:
        sent_vals = jnp.where(valid & is_sent, val, ident)
        if op == "sum":
            sent_total = jnp.sum(sent_vals)
        elif op == "max":
            sent_total = jnp.max(sent_vals)
        else:
            sent_total = jnp.min(sent_vals)
        head = first & is_sent
        live = live | (head & jnp.any(valid & is_sent))
        pairv = jnp.where(head, sent_total, pairv)
    return _compact_runs(first, live, k1, k2, pairv)


def _compact_runs(first, live, k1, k2, value) -> KVBatch:
    """Pack the rows marked ``first`` (one a run) to the front, in order,
    by one sort on their rank; every other row ties at n behind them as
    (SENTINEL, 0, invalid), so the unstable sort is exact. ``live`` (a
    subset of ``first``) marks the runs that hold a record; it rides in
    the key's low bit and becomes the output's validity."""
    n = k1.shape[0]
    dest = jnp.where(first, jnp.cumsum(first.astype(jnp.int32)) - 1, n)
    sent = jnp.uint32(SENTINEL)
    key, k1, k2, value = jax.lax.sort(
        (
            2 * dest + live.astype(jnp.int32),
            jnp.where(live, k1, sent),
            jnp.where(live, k2, sent),
            jnp.where(live, value, 0),
        ),
        num_keys=1,
        is_stable=False,
    )
    return KVBatch(k1, k2, value, (key & 1) == 1)


def sort_valid_first(batch: KVBatch, op: str = "sum") -> KVBatch:
    """Sort a batch into the order segment_reduce_sorted reduces: by
    (k1, k2), valid records before padding on a tie, then by value for
    every op but ``sum``. Invalid records become (SENTINEL, SENTINEL, 0)
    first, so padding is one run at the very end and the valid records
    are a prefix — a real word hashing to the SENTINEL pair included."""
    sent = jnp.uint32(SENTINEL)
    valid = batch.valid
    k1, k2, pad, value = jax.lax.sort(
        (
            jnp.where(valid, batch.k1, sent),
            jnp.where(valid, batch.k2, sent),
            (~valid).astype(jnp.int32),
            jnp.where(valid, batch.value, 0),
        ),
        # Unstable: rows tied on every key are interchangeable for a sum.
        num_keys=3 if op == "sum" else 4,
        is_stable=False,
    )
    return KVBatch(k1, k2, value, pad == 0)


def segment_reduce_sorted(batch: KVBatch, op: str = "sum") -> KVBatch:
    """Reduce a sort_valid_first-ordered batch: one output record per
    distinct key.

    op: "sum" (word count totals), "max"/"min" over values, or "distinct"
    (one record per distinct (key, value) pair, value preserved).

    Output is padded to the same capacity; slot i holds the i-th distinct
    key (sorted ascending), so real records sit at the front.

    A run is a stretch of equal (k1, k2, validity) — and value, for
    distinct — found by neighbour compares. Each run gives one row: its
    last for max (values ascend within the run) and sum, its first for
    min and distinct. For sum that row carries the prefix sum of the
    values; once the rows are packed to the front, a run's total is its
    prefix sum less the one before it (the padding run, which adds
    nothing, is last). No segment op, so no scatter over the batch.
    """
    if op not in REDUCE_OPS:
        raise ValueError(f"unknown reduce op: {op}")
    k1, k2, value, valid = batch
    same = (k1[1:] == k1[:-1]) & (k2[1:] == k2[:-1]) & (valid[1:] == valid[:-1])
    if op in _VALUE_KEYED_OPS:
        same = same & (value[1:] == value[:-1])
    edge = jnp.ones((1,), bool)
    rep = jnp.concatenate([edge, ~same] if op in ("min", "distinct") else [~same, edge])
    if op == "sum":
        value = jnp.cumsum(value)  # padding's values are 0
    out = _compact_runs(rep, rep & valid, k1, k2, value)
    if op == "sum":
        before = jnp.concatenate([jnp.zeros((1,), jnp.int32), out.value[:-1]])
        out = out._replace(value=jnp.where(out.valid, out.value - before, 0))
    return out


def count_unique(batch: KVBatch, op: str = "sum") -> KVBatch:
    """Sort + reduce: (distinct keys, combined values). The map-side
    combiner — every app's combine op is associative, so per-chunk partials
    merge exactly (word count: partial sums; inverted_index: distinct
    (term, doc) pairs)."""
    return segment_reduce_sorted(sort_valid_first(batch, op), op=op)


def compaction_cap(u_cap: int, capacity: int) -> int:
    """Record budget of combine_chunk's reduce — THE single policy the
    single-chip and mesh map paths share. Scales with BOTH the
    distinct-key budget (2*u_cap) and a token-density floor (capacity/4 ≈
    1.5x typical English density), so tuning partial_capacity down for
    low-cardinality data cannot strangle the fast path into replaying
    every chunk; capped at the structural worst case (ceil(capacity/2)
    one-char tokens), which is what makes full-width replay tiers unable
    to re-overflow."""
    return min(max(2 * u_cap, capacity // 4, 1024), capacity // 2 + 1)


def position_doc_ids(doc_table: jnp.ndarray, n: int) -> jnp.ndarray:
    """int32[n]: the doc id of the token ending at each byte position of
    a chunk, given its ``doc_table`` (runtime/chunker.doc_table: first doc
    id, then each document's start offset, NO_DOC-padded): the first doc
    id plus the number of starts at or before the position, minus one.
    Only compares and sums over [DOC_SLOTS, n], no gather: a chunk's
    documents are consecutive files. An app that ignores doc ids leaves
    all of it dead, and XLA drops it."""
    starts = doc_table[1:]
    pos = jnp.arange(n, dtype=jnp.int32)
    passed = jnp.sum(starts[:, None] <= pos[None, :], axis=0, dtype=jnp.int32)
    return doc_table[0] + passed - 1


def combine_chunk(kv: KVBatch, doc_table: jnp.ndarray, device_map, op: str,
                  u_cap: int) -> tuple[KVBatch, jnp.ndarray]:
    """The map-side combine of one chunk's tokens, shared by the
    single-chip step and the mesh shuffle: (update, overflow count).

    ``kv`` holds a record at each token's end byte (ops/tokenize);
    ``device_map(kv, doc_ids)`` is the app's per-record transform, given
    each position's doc id. One sort over every position puts the valid
    records, key-sorted, in front (sort_valid_first); the reduce then
    runs over the first compaction_cap rows only. Records past that cap,
    and distinct keys past ``u_cap``, are counted in the overflow, never
    dropped silently: the caller clamps the update to empty and the driver
    replays the chunk through a tier whose caps are the exact worst case.
    The update is the first ``u_cap`` distinct keys, front-packed and
    sorted (at most compaction_cap of them)."""
    n = kv.capacity
    cap = compaction_cap(u_cap, n)
    kv = device_map(kv, position_doc_ids(doc_table, n))
    records = jnp.sum(kv.valid.astype(jnp.int32))
    partial = segment_reduce_sorted(sort_valid_first(kv, op).take_front(cap), op=op)
    ovf = jnp.maximum(records - cap, 0) + jnp.sum(partial.valid[u_cap:].astype(jnp.int32))
    return partial.take_front(u_cap), ovf


def clamp_batch(batch: KVBatch, keep) -> KVBatch:
    """Clamp a batch to empty unless ``keep`` (scalar bool) holds: validity
    drops AND keys become SENTINEL. Keys must go too: merge_batches makes
    one output slot per distinct key, so an invalid-but-real-keyed record
    would become a mid-array SENTINEL hole in the merged state.
    """
    sent = jnp.uint32(SENTINEL)
    return KVBatch(
        k1=jnp.where(keep, batch.k1, sent),
        k2=jnp.where(keep, batch.k2, sent),
        value=jnp.where(keep, batch.value, 0),
        valid=batch.valid & keep,
    )


def concat_batches(a: KVBatch, b: KVBatch) -> KVBatch:
    return KVBatch(
        k1=jnp.concatenate([a.k1, b.k1]),
        k2=jnp.concatenate([a.k2, b.k2]),
        value=jnp.concatenate([a.value, b.value]),
        valid=jnp.concatenate([a.valid, b.valid]),
    )


def merge_batches(
    state: KVBatch, update: KVBatch, op: str = "sum", update_sorted: bool = False
) -> tuple[KVBatch, KVBatch]:
    """Merge per-chunk partials into a running distinct-key state.

    PRECONDITIONS: ``state`` is key-distinct, and every invalid record
    carries the SENTINEL key pair — true by construction everywhere: the
    initial state is all SENTINEL, and every new_state below is one.
    ``update_sorted=True`` promises the same of the update (all
    count_unique and clamp_batch outputs are); otherwise the update is
    count_unique'd here first (host-scan packed updates are distinct but
    unsorted — the dedup is a no-op, the small sort is the point).

    The big state is never segment-reduced. ``lax.sort`` over
    ``state ++ update`` puts each key's (at most two) copies side by side,
    and combine_adjacent_unique folds them by neighbour compares and
    compacts the run heads with a second sort: two sorts over
    ``cap + update.capacity`` rows and elementwise work, with no gather or
    scatter over the state.

    Returns ``(new_state, evicted)``. ``new_state`` keeps the smallest
    ``state.capacity`` distinct keys (sorted ascending); any overflow — the
    largest-key tail of the merge — is returned whole as ``evicted``
    (capacity = ``update.capacity``), NOT dropped: its records carry their
    full merged values, and the driver spills them to the host accumulator
    (runtime/driver.py). For scalar ops a key never appears in both halves,
    so summing state + spills on the host reconstructs exact totals. For
    value-keyed ops ("distinct") the cut can land mid-key — (k,v1) kept,
    (k,v2) evicted — so hosts must fold spills by set-union per key, never
    treat an evicted key as final (HostAccumulator does this).
    """
    cap = state.capacity
    if not update_sorted:
        # count_unique, not a bare sort: it also DEDUPS, establishing the
        # key-distinct side contract combine_adjacent_unique needs.
        update = count_unique(update, op=op)
    merged = combine_adjacent_unique(
        sort_kv(concat_batches(state, update), by_value=op in _VALUE_KEYED_OPS), op=op
    )
    head = KVBatch(merged.k1[:cap], merged.k2[:cap], merged.value[:cap], merged.valid[:cap])
    evicted = KVBatch(merged.k1[cap:], merged.k2[cap:], merged.value[cap:], merged.valid[cap:])
    return head, evicted
