"""Sort + segment group-by reduce — the TPU-native reduce engine.

Replaces the reference reduce path (src/mr/worker.rs:157-193): there, all
pairs of a partition are parsed from files, ``sort_by`` key
(worker.rs:162-164), then a streaming group-by calls the reduce UDF per key
run (worker.rs:169-184 — with the last group silently dropped, a bug we do
not reproduce). Here the same shape is ``lax.sort`` on the hash-pair key
(lexicographic, num_keys=2) followed by segment-boundary detection and
``jax.ops.segment_sum`` — every group flushed, including the last, by
construction.

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

The running-state merge (merge_batches) is sorts and neighbour compares
only: one ``lax.sort`` interleaves state and update, shifted compares
combine each key's (at most two) copies, and a second sort on the run
heads' ranks compacts them to the front. It makes no gather or scatter
whose index count scales with the state's capacity: on the v5e every
data-dependent element access costs roughly 10-15 ns, while XLA's sort
streams.
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
    # 163→123 ms) and tie order is immaterial — every consumer aggregates
    # whole key segments (segment_reduce_sorted, combine_adjacent_unique's
    # commutative pair combine), so records tied on the full key set
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
    keys, SENTINEL fill — but via shifted compares plus ONE compaction sort
    instead of seven segment ops (each a scatter over the whole batch).
    """
    if op not in REDUCE_OPS:
        raise ValueError(f"unknown reduce op: {op}")
    n = merged.capacity
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
    # Compact run heads to the front, in order, by one sort on their rank;
    # every other row (all SENTINEL, 0, invalid) ties at n behind them, so
    # the unstable sort is exact. Liveness rides in the key's low bit.
    dest = jnp.where(first, jnp.cumsum(first.astype(jnp.int32)) - 1, n)
    sent = jnp.uint32(SENTINEL)
    key, k1, k2, value = jax.lax.sort(
        (
            2 * dest + live.astype(jnp.int32),
            jnp.where(live, k1, sent),
            jnp.where(live, k2, sent),
            jnp.where(live, pairv, 0),
        ),
        num_keys=1,
        is_stable=False,
    )
    return KVBatch(k1, k2, value, (key & 1) == 1)


def segment_reduce_sorted(batch: KVBatch, op: str = "sum") -> KVBatch:
    """Reduce a key-sorted batch: one output record per distinct key.

    op: "sum" (word count totals), "max"/"min" over values, or "distinct"
    (batch must be sorted with ``by_value=True``; one record per distinct
    (key, value) pair, value preserved).

    Output is padded to the same capacity; slot i holds the i-th distinct
    key (sorted ascending), so real records sit at the front.
    """
    if op not in REDUCE_OPS:
        raise ValueError(f"unknown reduce op: {op}")
    n = batch.capacity
    prev_k1 = jnp.concatenate([batch.k1[:1], batch.k1[:-1]])
    prev_k2 = jnp.concatenate([batch.k2[:1], batch.k2[:-1]])
    first = jnp.arange(n) == 0
    boundary = first | (batch.k1 != prev_k1) | (batch.k2 != prev_k2)
    if op in _VALUE_KEYED_OPS:
        prev_val = jnp.concatenate([batch.value[:1], batch.value[:-1]])
        boundary = boundary | (batch.value != prev_val)
    # Padding (SENTINEL,SENTINEL) forms at most one trailing segment.
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1

    # seg is a cumsum — monotone — so every segment op below can promise
    # sorted indices to XLA's scatter lowering.
    masked_val = jnp.where(batch.valid, batch.value, 0)
    if op == "sum":
        totals = jax.ops.segment_sum(
            masked_val, seg, num_segments=n, indices_are_sorted=True
        )
    elif op == "max":
        big = jnp.where(batch.valid, batch.value, jnp.iinfo(jnp.int32).min)
        totals = jax.ops.segment_max(
            big, seg, num_segments=n, indices_are_sorted=True
        )
    elif op == "min":
        small = jnp.where(batch.valid, batch.value, jnp.iinfo(jnp.int32).max)
        totals = jax.ops.segment_min(
            small, seg, num_segments=n, indices_are_sorted=True
        )
    else:  # distinct: every record in the segment shares one value
        big = jnp.where(boundary, batch.value, jnp.iinfo(jnp.int32).min)
        totals = jax.ops.segment_max(
            big, seg, num_segments=n, indices_are_sorted=True
        )

    live = jax.ops.segment_sum(
        batch.valid.astype(jnp.int32), seg, num_segments=n, indices_are_sorted=True
    )
    uk1 = jax.ops.segment_max(
        jnp.where(boundary, batch.k1, 0), seg, num_segments=n, indices_are_sorted=True
    )
    uk2 = jax.ops.segment_max(
        jnp.where(boundary, batch.k2, 0), seg, num_segments=n, indices_are_sorted=True
    )

    # Slot j is real iff j < number of segments containing >=1 valid record.
    # Valid records sort before padding, so those segments are a prefix.
    slot_valid = live > 0
    sent = jnp.uint32(SENTINEL)
    return KVBatch(
        k1=jnp.where(slot_valid, uk1, sent),
        k2=jnp.where(slot_valid, uk2, sent),
        value=jnp.where(slot_valid, totals, 0),
        valid=slot_valid,
    )


def count_unique(batch: KVBatch, op: str = "sum") -> KVBatch:
    """Sort + reduce: (distinct keys, combined values). The map-side
    combiner — every app's combine op is associative, so per-chunk partials
    merge exactly (word count: partial sums; inverted_index: distinct
    (term, doc) pairs)."""
    return segment_reduce_sorted(sort_kv(batch, by_value=op in _VALUE_KEYED_OPS), op=op)


def compaction_cap(u_cap: int, capacity: int) -> int:
    """Token-slot budget for compact_front in the map paths — THE single
    policy both the single-chip and mesh kernels use. Scales with BOTH the
    distinct-key budget (2*u_cap) and a token-density floor (capacity/4 ≈
    1.5x typical English density), so tuning partial_capacity down for
    low-cardinality data cannot strangle the fast path into replaying
    every chunk; capped at the structural worst case (ceil(capacity/2)
    one-char tokens), which is what makes full-width replay tiers unable
    to re-overflow."""
    return min(max(2 * u_cap, capacity // 4, 1024), capacity // 2 + 1)


def compact_front(batch: KVBatch, cap: int) -> tuple[KVBatch, jnp.ndarray]:
    """Scatter the valid records into the front of a cap-sized batch.

    (packed KVBatch[cap], overflow_count). The device map step's sort
    (count_unique) costs O(N log N) over EVERY byte position of a chunk,
    but only ~N/6 positions hold tokens in real text — compacting first
    makes the sort pay for tokens, not bytes. Records past cap are counted,
    never dropped silently: the driver replays the chunk through a tier
    whose cap is the exact worst case (ceil(N/2) one-char tokens), the same
    contract as every other capacity fault.
    """
    n = batch.capacity
    idx = jnp.cumsum(batch.valid.astype(jnp.int32)) - 1
    total = idx[n - 1] + 1
    ovf = jnp.maximum(total - cap, 0)
    # Invalid records and overflow scatter into the dump slot at cap.
    dest = jnp.where(batch.valid & (idx < cap), idx, cap)
    sent = jnp.uint32(SENTINEL)

    def place(x, fill):
        buf = jnp.full((cap + 1,), fill, x.dtype)
        return buf.at[dest].set(x, mode="drop")[:cap]

    packed = KVBatch(
        k1=place(batch.k1, sent),
        k2=place(batch.k2, sent),
        value=place(batch.value, jnp.int32(0)),
        valid=jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(total, cap),
    )
    return packed, ovf


def slot_doc_ids(valid: jnp.ndarray, doc_table: jnp.ndarray, cap: int) -> jnp.ndarray:
    """int32[cap]: the doc id of each slot compact_front(batch, cap) fills,
    where ``valid`` is the batch's validity by byte position and
    ``doc_table`` the chunk's (runtime/chunker.doc_table: first doc id,
    then each document's start offset, NO_DOC-padded).

    A token's doc id is the first doc id plus the number of starts at or
    before it, minus one. Counted in slot space: a start at byte s is
    passed by the slots at or past the number of tokens before s. Only
    compares and sums over [DOC_SLOTS, positions], no gather: a chunk's
    documents are consecutive files. An app that ignores doc ids leaves
    all of it dead, and XLA drops it."""
    starts = doc_table[1:]
    pos = jnp.arange(valid.shape[0], dtype=jnp.int32)
    before = jnp.sum((pos[None, :] < starts[:, None]) & valid[None, :],
                     axis=1, dtype=jnp.int32)
    slot = jnp.arange(cap, dtype=jnp.int32)
    passed = jnp.sum(slot[None, :] >= before[:, None], axis=0, dtype=jnp.int32)
    return doc_table[0] + passed - 1


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
