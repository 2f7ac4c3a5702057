"""Sort + segment reduce + partition kernels vs. numpy oracles."""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_rust_tpu.core.hashing import SENTINEL
from mapreduce_rust_tpu.core.kv import KVBatch
from mapreduce_rust_tpu.ops.groupby import (
    combine_chunk,
    compaction_cap,
    count_unique,
    merge_batches,
    segment_reduce_sorted,
    sort_valid_first,
)
from mapreduce_rust_tpu.ops.partition import bucket_scatter


def make_batch(keys, values, capacity):
    keys = np.asarray(keys, dtype=np.uint32).reshape(-1, 2)
    values = np.asarray(values, dtype=np.int32)
    return KVBatch.from_host(keys, values, capacity)


def batch_to_dict(batch: KVBatch) -> dict:
    keys, vals = batch.to_host()
    out = {}
    for (a, b), v in zip(keys.tolist(), vals.tolist()):
        out[(a, b)] = out.get((a, b), 0) + v
    return out


def test_count_unique_basic():
    keys = [(1, 1), (2, 2), (1, 1), (3, 3), (1, 1), (2, 2)]
    batch = make_batch(keys, [1] * 6, capacity=16)
    out = count_unique(batch)
    assert batch_to_dict(out) == {(1, 1): 3, (2, 2): 2, (3, 3): 1}


def test_count_unique_distinguishes_k2():
    # Same k1, different k2 must be distinct keys (the 64-bit story).
    keys = [(7, 1), (7, 2), (7, 1)]
    batch = make_batch(keys, [1] * 3, capacity=8)
    assert batch_to_dict(count_unique(batch)) == {(7, 1): 2, (7, 2): 1}


def test_count_unique_random_vs_counter():
    rng = np.random.default_rng(1)
    n = 4096
    keys = rng.integers(0, 50, size=(n, 2)).astype(np.uint32)
    vals = rng.integers(1, 5, size=n).astype(np.int32)
    batch = make_batch(keys, vals, capacity=n)
    oracle = collections.defaultdict(int)
    for (a, b), v in zip(keys.tolist(), vals.tolist()):
        oracle[(a, b)] += v
    assert batch_to_dict(count_unique(batch)) == dict(oracle)


def test_segment_reduce_max_min_vs_oracle():
    # max/min with negative values and padding: iinfo sentinel masking must
    # not leak into real segments (ADVICE r1).
    rng = np.random.default_rng(7)
    n = 256
    keys = rng.integers(0, 12, size=(n, 2)).astype(np.uint32)
    vals = rng.integers(-100, 100, size=n).astype(np.int32)
    batch = make_batch(keys, vals, capacity=n + 64)  # 64 padding slots
    for op, fold in (("max", max), ("min", min)):
        oracle: dict = {}
        for (a, b), v in zip(keys.tolist(), vals.tolist()):
            k = (a, b)
            oracle[k] = fold(oracle[k], v) if k in oracle else v
        out = segment_reduce_sorted(sort_valid_first(batch, op), op=op)
        keys_out, vals_out = out.to_host()
        got = {tuple(k): v for k, v in zip(keys_out.tolist(), vals_out.tolist())}
        assert got == oracle, op


def test_sorted_output_is_front_packed():
    keys = [(5, 5), (1, 1), (5, 5)]
    out = count_unique(make_batch(keys, [1] * 3, capacity=8))
    valid = np.asarray(out.valid)
    # valid slots form a prefix
    first_invalid = valid.argmin() if not valid.all() else len(valid)
    assert valid[:first_invalid].all() and not valid[first_invalid:].any()
    assert np.asarray(out.k1)[~valid].tolist() == [SENTINEL] * int((~valid).sum())


def test_merge_batches_accumulates():
    state = KVBatch.empty(8)
    upd1 = count_unique(make_batch([(1, 1), (2, 2), (1, 1)], [1, 1, 1], 8))
    state, ev1 = merge_batches(state, upd1)
    upd2 = count_unique(make_batch([(2, 2), (3, 3)], [1, 1], 8))
    state, ev2 = merge_batches(state, upd2)
    assert not np.asarray(ev1.valid).any() and not np.asarray(ev2.valid).any()
    assert batch_to_dict(state) == {(1, 1): 2, (2, 2): 2, (3, 3): 1}


def test_merge_overflow_evicts_whole_records():
    # 8 distinct keys into capacity 4: the 4 largest keys are evicted with
    # their full merged values — nothing is lost (ADVICE r1).
    state = make_batch([(i, i) for i in range(4)], [10 + i for i in range(4)], capacity=4)
    upd = make_batch([(i + 100, i) for i in range(4)], [1] * 4, capacity=4)
    state2, evicted = merge_batches(state, upd)
    assert evicted.capacity == 4
    combined = batch_to_dict(state2)
    for k, v in batch_to_dict(evicted).items():
        assert k not in combined  # no key in both halves
        combined[k] = v
    oracle = {(i, i): 10 + i for i in range(4)}
    oracle.update({(i + 100, i): 1 for i in range(4)})
    assert combined == oracle


def test_merge_overflow_key_straddles_and_sums():
    # A key present in state AND update, landing in the evicted tail, must
    # carry the *summed* value.
    state = make_batch([(i, 0) for i in range(4)], [1] * 4, capacity=4)
    upd = make_batch([(3, 0), (0, 0)], [5, 7], capacity=4)
    state2, evicted = merge_batches(state, upd)
    combined = {**batch_to_dict(state2), **batch_to_dict(evicted)}
    assert combined == {(0, 0): 8, (1, 0): 1, (2, 0): 1, (3, 0): 6}


def test_distinct_op_dedups_key_value_pairs():
    # inverted_index semantics: value (doc_id) joins the key; duplicates
    # collapse, different doc_ids for one term stay distinct.
    keys = [(1, 1), (1, 1), (1, 1), (2, 2), (2, 2)]
    vals = [7, 7, 9, 7, 7]
    out = count_unique(make_batch(keys, vals, capacity=16), op="distinct")
    got_keys, got_vals = out.to_host()
    got = sorted(zip(map(tuple, got_keys.tolist()), got_vals.tolist()))
    assert got == [((1, 1), 7), ((1, 1), 9), ((2, 2), 7)]


def test_distinct_op_merges_associatively():
    a = count_unique(make_batch([(1, 1), (1, 1)], [3, 4], 8), op="distinct")
    b = count_unique(make_batch([(1, 1), (2, 2)], [4, 5], 8), op="distinct")
    state, ev = merge_batches(KVBatch.empty(8), a, op="distinct")
    state, ev2 = merge_batches(state, b, op="distinct")
    assert not np.asarray(ev.valid).any() and not np.asarray(ev2.valid).any()
    got_keys, got_vals = state.to_host()
    got = sorted(zip(map(tuple, got_keys.tolist()), got_vals.tolist()))
    assert got == [((1, 1), 3), ((1, 1), 4), ((2, 2), 5)]


_FOLDS = {"sum": lambda a, b: a + b, "max": max, "min": min}


def _rows(batch: KVBatch) -> list:
    return list(zip(*(np.asarray(x).tolist() for x in batch)))


def _reference_merge(state: KVBatch, update: KVBatch, op: str) -> list:
    """merge_batches' contract, in plain Python, over the rows both sides
    hold (valid and padding alike; the update already key-distinct): one
    slot per distinct key — (k1, k2, value) for ``distinct`` — in ascending
    order, holding the fold of its valid values, or SENTINEL padding where
    the key has none; then padding to the sum of the capacities."""
    S = int(SENTINEL)
    runs: dict = {}
    for k1, k2, v, ok in _rows(state) + _rows(update):
        key = (k1, k2, v) if op == "distinct" else (k1, k2)
        runs.setdefault(key, []).append((v, ok))
    out = []
    for key in sorted(runs):
        vals = [v for v, ok in runs[key] if ok]
        if not vals:
            out.append((S, S, 0, False))
        elif op == "distinct":
            out.append((*key, True))
        else:
            acc = vals[0]
            for v in vals[1:]:
                acc = _FOLDS[op](acc, v)
            out.append((*key, int(np.int32(acc)), True))
    out += [(S, S, 0, False)] * (state.capacity + update.capacity - len(out))
    return out


@pytest.mark.parametrize("update_sorted", [True, False])
@pytest.mark.parametrize("op", ["sum", "max", "min", "distinct"])
def test_merge_batches_matches_reference_exactly(op, update_sorted):
    # Exact (new_state, evicted) arrays, slot for slot, against the plain
    # reference: cross-side key ties, a state that fills and evicts, and a
    # real word hashing to the SENTINEL pair on both sides.
    rng = np.random.default_rng(24)
    S = int(SENTINEL)
    cap, u_cap = 16, 8
    for trial in range(12):
        n_s = cap if trial % 3 == 0 else int(rng.integers(0, cap + 1))
        s_keys = rng.choice(40, size=n_s, replace=False)
        s_keys = np.stack([s_keys % 7, s_keys], 1).astype(np.uint32)
        n_u = int(rng.integers(0, u_cap + 1))
        u_keys = rng.integers(0, 40, size=n_u)
        u_keys = np.stack([u_keys % 7, u_keys], 1).astype(np.uint32)
        if trial % 2 and n_s and n_u:
            s_keys[-1] = u_keys[0] = (S, S)  # the 2^-64 corner, both sides
        lo, hi = (0, 4) if op == "distinct" else (-50, 50)
        s_vals = rng.integers(lo, hi, size=n_s).astype(np.int32)
        u_vals = rng.integers(lo, hi, size=n_u).astype(np.int32)
        state = count_unique(make_batch(s_keys, s_vals, cap), op=op)
        raw = make_batch(u_keys, u_vals, u_cap)
        update = count_unique(raw, op=op)
        new_state, evicted = merge_batches(
            state, update if update_sorted else raw, op=op, update_sorted=update_sorted
        )
        want = _reference_merge(state, update, op)
        assert _rows(new_state) == want[:cap], (op, trial)
        assert _rows(evicted) == want[cap:], (op, trial)


def test_merge_programs_make_no_state_sized_gather_or_scatter():
    # The merge programs at the benchmark cells' shapes (state 2**21; the
    # device step's update 2**19, the packed host update 65,536), traced
    # only: no gather or scatter may touch an array as long as the state.
    import jax

    from mapreduce_rust_tpu.apps.word_count import WordCount
    from mapreduce_rust_tpu.runtime.driver import make_packed_merge_fn, make_step_fns

    state_cap, u_cap, host_cap = 1 << 21, 1 << 19, 1 << 16

    def batch(n):
        return KVBatch(
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        )

    _map_combine, merge = make_step_fns(WordCount(), u_cap)
    merge_packed = make_packed_merge_fn(WordCount(), host_cap)
    programs = {
        "merge": jax.make_jaxpr(merge)(batch(state_cap), batch(u_cap)),
        "merge_packed": jax.make_jaxpr(merge_packed)(
            batch(state_cap), jax.ShapeDtypeStruct((1 + 3 * host_cap,), jnp.uint32)
        ),
    }
    for name, closed in programs.items():
        eqns = list(_all_eqns(closed.jaxpr))
        big = [
            (eqn.primitive.name, shapes)
            for eqn in eqns
            if eqn.primitive.name == "gather" or eqn.primitive.name.startswith("scatter")
            for shapes in [[v.aval.shape for v in eqn.invars + eqn.outvars]]
            if any(dim >= state_cap for shape in shapes for dim in shape)
        ]
        assert big == [], (name, big)
        assert any(eqn.primitive.name == "sort" for eqn in eqns), name


def _all_eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (pjit, cond,
    scan bodies) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


def test_merge_after_clamped_update_stays_sorted_and_exact():
    # Regression for the merged state's contract: a clamped (overflow)
    # update must leave the state SORTED — clamp_batch turns its keys into
    # SENTINEL padding, not mid-array holes — so later merges stay exact.
    from mapreduce_rust_tpu.ops.groupby import clamp_batch

    state = KVBatch.empty(8)
    upd1 = count_unique(make_batch([(2, 2), (9, 9), (5, 5)], [1, 1, 1], 8))
    state, _ = merge_batches(state, upd1, update_sorted=True)
    # Simulate the driver's overflow clamp: real sorted keys, all invalid.
    upd2 = clamp_batch(
        count_unique(make_batch([(1, 1), (7, 7)], [1, 1], 8)), jnp.bool_(False)
    )
    state, _ = merge_batches(state, upd2, update_sorted=True)
    k1 = np.asarray(state.k1)
    assert (k1[:-1] <= k1[1:]).all(), "state must stay sorted after a clamp"
    upd3 = count_unique(make_batch([(5, 5), (1, 1)], [1, 1], 8))
    state, _ = merge_batches(state, upd3, update_sorted=True)
    assert batch_to_dict(state) == {(2, 2): 1, (9, 9): 1, (5, 5): 2, (1, 1): 1}


def test_merge_sentinel_hashed_word_exact():
    # A real word can (2^-64) hash to the (SENTINEL, SENTINEL) pair — its
    # records land inside the padding run, possibly separated from their
    # cross-side twin. The masked-reduction fix in combine_adjacent_unique
    # must still sum both sides exactly (hashing.py documents this corner).
    S = int(SENTINEL)
    state = count_unique(make_batch([(S, S), (3, 3)], [5, 1], 8))
    upd = count_unique(make_batch([(S, S), (4, 4)], [7, 1], 8))
    new_state, ev = merge_batches(state, upd, update_sorted=True)
    assert not np.asarray(ev.valid).any()
    assert batch_to_dict(new_state) == {(S, S): 12, (3, 3): 1, (4, 4): 1}
    # max over the sentinel run, both sides valid
    st = count_unique(make_batch([(S, S)], [5], 8), op="max")
    up = count_unique(make_batch([(S, S), (1, 1)], [9, 2], 8), op="max")
    out, _ = merge_batches(st, up, op="max", update_sorted=True)
    assert batch_to_dict(out) == {(S, S): 9, (1, 1): 2}


def test_merge_update_larger_than_state():
    # Replay tiers can pass an update WIDER than the state (full-width
    # u_cap > merge_capacity): the merge must handle na < nb.
    state = make_batch([(1, 1), (5, 5)], [3, 4], capacity=2)
    upd = make_batch([(0, 0), (1, 1), (6, 6), (7, 7), (9, 9)], [1] * 5, capacity=8)
    new_state, evicted = merge_batches(state, upd)
    combined = {**batch_to_dict(new_state), **batch_to_dict(evicted)}
    assert combined == {(0, 0): 1, (1, 1): 4, (5, 5): 4, (6, 6): 1, (7, 7): 1, (9, 9): 1}


def test_merge_batches_fuzz_vs_oracle():
    # Property fuzz of the whole sort + pair-combine path: random
    # update streams (random sizes, duplicate raw keys, occasional
    # sentinel-pair keys, occasional clamped updates, all four ops) folded
    # through merge_batches; state + evictions must always equal the
    # oracle fold. Seeded — failures reproduce.
    from mapreduce_rust_tpu.ops.groupby import clamp_batch

    rng = np.random.default_rng(42)
    S = int(SENTINEL)
    for op, fold in (("sum", lambda a, b: a + b), ("max", max), ("min", min)):
        for cap, u_cap, rounds in ((16, 8, 6), (64, 32, 5), (32, 64, 4)):
            state = KVBatch.empty(cap)
            oracle: dict = {}
            for r in range(rounds):
                n = int(rng.integers(0, u_cap + 1))
                keys = rng.integers(0, 12, size=(n, 2)).astype(np.uint32)
                if n and rng.random() < 0.3:
                    keys[0] = (S, S)  # the 2^-64 corner, made common
                vals = rng.integers(-50, 50, size=n).astype(np.int32)
                upd = count_unique(make_batch(keys, vals, u_cap), op=op)
                if rng.random() < 0.2:
                    upd = clamp_batch(upd, jnp.bool_(False))  # overflow clamp
                else:
                    o: dict = {}
                    for (a, b), v in zip(keys.tolist(), vals.tolist()):
                        o[(a, b)] = fold(o[(a, b)], v) if (a, b) in o else v
                    for k, v in o.items():
                        oracle[k] = fold(oracle[k], v) if k in oracle else v
                state, ev = merge_batches(state, upd, op=op, update_sorted=True)
                # evictions fold to the host exactly (spill contract)
                for k, v in batch_to_dict(ev).items():
                    oracle_v = oracle.pop(k)
                    assert v == oracle_v, (op, cap, r, k)
                k1 = np.asarray(state.k1)
                assert (k1[:-1] <= k1[1:]).all(), "state must stay sorted"
            assert batch_to_dict(state) == oracle, (op, cap)


def test_merge_batches_fuzz_distinct_op():
    # Same property fuzz for the value-keyed op: (key, doc) sets must
    # stay exact through merges, evictions and clamps.
    from mapreduce_rust_tpu.ops.groupby import clamp_batch

    rng = np.random.default_rng(7)
    cap, u_cap = 32, 16
    state = KVBatch.empty(cap)
    oracle: dict = {}
    for r in range(8):
        n = int(rng.integers(0, u_cap + 1))
        keys = rng.integers(0, 8, size=(n, 2)).astype(np.uint32)
        docs = rng.integers(0, 5, size=n).astype(np.int32)
        upd = count_unique(make_batch(keys, docs, u_cap), op="distinct")
        if rng.random() < 0.2:
            upd = clamp_batch(upd, jnp.bool_(False))
        else:
            for (a, b), d in zip(keys.tolist(), docs.tolist()):
                oracle.setdefault((a, b), set()).add(d)
        state, ev = merge_batches(state, upd, op="distinct", update_sorted=True)
        ekeys, evals = ev.to_host()
        for (a, b), d in zip(map(tuple, ekeys.tolist()), evals.tolist()):
            oracle[(a, b)].remove(d)  # KeyError = wrong eviction
            if not oracle[(a, b)]:
                del oracle[(a, b)]
    got: dict = {}
    skeys, svals = state.to_host()
    for (a, b), d in zip(map(tuple, skeys.tolist()), svals.tolist()):
        got.setdefault((a, b), set()).add(d)
    assert got == oracle


def test_bucket_scatter_routes_by_k1_mod():
    nb, cap = 4, 8
    keys = [(k1, 7) for k1 in [0, 1, 2, 3, 4, 5, 8, 9]]
    batch = make_batch(keys, [10 + i for i in range(8)], capacity=16)
    out, ovf = bucket_scatter(batch, num_buckets=nb, capacity=cap)
    assert int(ovf) == 0
    k1 = np.asarray(out.k1)
    valid = np.asarray(out.valid)
    for b in range(nb):
        row_keys = k1[b][valid[b]]
        assert all(int(k) % nb == b for k in row_keys)
    # nothing lost
    assert valid.sum() == 8


def test_bucket_scatter_overflow_counted():
    nb, cap = 2, 2
    keys = [(0, i) for i in range(6)]  # all to bucket 0, capacity 2
    batch = make_batch(keys, [1] * 6, capacity=8)
    out, ovf = bucket_scatter(batch, num_buckets=nb, capacity=cap)
    assert int(ovf) == 4
    assert np.asarray(out.valid).sum() == 2


def test_bucket_scatter_preserves_totals_random():
    rng = np.random.default_rng(2)
    n, nb = 512, 8
    cap = 2 * n // nb
    keys = rng.integers(0, 1 << 31, size=(n, 2)).astype(np.uint32)
    vals = np.ones(n, dtype=np.int32)
    batch = make_batch(keys, vals, capacity=n)
    out, ovf = bucket_scatter(batch, num_buckets=nb, capacity=cap)
    assert int(ovf) == 0
    oracle = collections.defaultdict(int)
    for (a, b) in keys.tolist():
        oracle[(a, b)] += 1
    got = collections.defaultdict(int)
    k1 = np.asarray(out.k1).ravel()
    k2 = np.asarray(out.k2).ravel()
    vv = np.asarray(out.value).ravel()
    ok = np.asarray(out.valid).ravel()
    for a, b, v in zip(k1[ok].tolist(), k2[ok].tolist(), vv[ok].tolist()):
        got[(a, b)] += v
    assert got == oracle


def _keep_all(kv, doc_ids):
    return kv


def test_compact_front_exact_and_overflow():
    # combine_chunk's one sort packs the valid records in front; the reduce
    # reads the first compaction_cap of them. Invalid records carry real
    # keys and values here: none may leak into the update.
    rng = np.random.default_rng(5)
    n = 4096
    valid = rng.random(n) < 0.2
    k1 = rng.integers(0, 2**32, n, dtype=np.uint32)
    k2 = rng.integers(0, 2**32, n, dtype=np.uint32)
    val = rng.integers(0, 100, n, dtype=np.int32)
    batch = KVBatch(jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(val), jnp.asarray(valid))
    doc_table = jnp.zeros((65,), jnp.int32)
    total = int(valid.sum())
    order = np.lexsort((k2[valid], k1[valid]))
    # Roomy caps: every record kept, keys ascending, nothing lost.
    update, ovf = combine_chunk(batch, doc_table, _keep_all, "sum", u_cap=1024)
    assert int(ovf) == 0
    assert int(np.asarray(update.valid).sum()) == total
    assert np.array_equal(np.asarray(update.k1)[:total], k1[valid][order])
    assert np.array_equal(np.asarray(update.value)[:total], val[valid][order])
    # Tight caps: 1,024 records reach the reduce (compaction_cap's floor)
    # and 256 distinct keys the update; the rest are counted, and the
    # update holds the smallest keys.
    u_cap = 256
    valid2 = rng.random(n) < 0.4
    batch2 = batch._replace(valid=jnp.asarray(valid2))
    update2, ovf2 = combine_chunk(batch2, doc_table, _keep_all, "sum", u_cap=u_cap)
    total2 = int(valid2.sum())
    cap = compaction_cap(u_cap, n)
    assert total2 > cap == 1024
    assert int(ovf2) == (total2 - cap) + (cap - u_cap)
    order2 = np.lexsort((k2[valid2], k1[valid2]))
    assert np.array_equal(np.asarray(update2.k1), k1[valid2][order2][:u_cap])


def _reference_combine(batch: KVBatch, op: str, u_cap: int) -> tuple[list, int]:
    """combine_chunk's contract in plain Python, with a device_map that
    keeps every record: the valid records in key order, the first
    compaction_cap of them folded per key — (k1, k2, value) for
    ``distinct`` — into ascending rows; then SENTINEL padding. The
    overflow counts the records past the cap and the keys past u_cap."""
    S = int(SENTINEL)
    recs = sorted((a, b, v) for a, b, v, ok in _rows(batch) if ok)
    cap = compaction_cap(u_cap, batch.capacity)
    runs: dict = {}
    for a, b, v in recs[:cap]:
        runs.setdefault((a, b, v) if op == "distinct" else (a, b), []).append(v)
    out = []
    for key in sorted(runs):
        if op == "distinct":
            out.append((*key, True))
            continue
        acc = runs[key][0]
        for v in runs[key][1:]:
            acc = _FOLDS[op](acc, v)
        out.append((*key, int(np.int32(np.int64(acc) & 0xFFFFFFFF)), True))
    ovf = max(len(recs) - cap, 0) + max(len(out) - u_cap, 0)
    width = min(u_cap, cap)
    out = out[:width]
    return out + [(S, S, 0, False)] * (width - len(out)), ovf


_COMBINE_CASES = {
    # case: (valid share, distinct keys, SENTINEL-pair records)
    "long_runs": (0.2, 8, 0),         # ~100 copies of each key
    "sentinel_word": (0.2, 8, 37),    # a word hashing to the padding key
    "all_padding": (0.0, 8, 0),
    "records_past_cap": (0.4, 8, 0),  # ~1,640 records, cap 1,024
    "keys_past_u_cap": (0.2, 400, 0),  # ~350 distinct keys, u_cap 64
}


@pytest.mark.parametrize("case", sorted(_COMBINE_CASES))
@pytest.mark.parametrize("op", ["sum", "max", "min", "distinct"])
def test_combine_chunk_matches_reference(op, case):
    # Exact update rows and overflow count against the plain reference;
    # invalid rows carry real keys and values, which must not leak.
    share, n_keys, n_sent = _COMBINE_CASES[case]
    rng = np.random.default_rng(26)
    S = int(SENTINEL)
    n, u_cap = 4096, 64
    ids = rng.integers(0, n_keys, size=n)
    k1 = (ids % 3).astype(np.uint32)
    k2 = (ids * 7919).astype(np.uint32)
    sent = rng.choice(n, size=n_sent, replace=False)
    k1[sent] = k2[sent] = S
    lo, hi = (0, 4) if op == "distinct" else (-2**31, 2**31)
    vals = rng.integers(lo, hi, size=n, dtype=np.int64).astype(np.int32)
    valid = rng.random(n) < share
    valid[sent] = True
    batch = KVBatch(*(jnp.asarray(x) for x in (k1, k2, vals, valid)))
    update, ovf = combine_chunk(batch, jnp.zeros((65,), jnp.int32), _keep_all, op, u_cap)
    want, want_ovf = _reference_combine(batch, op, u_cap)
    assert int(ovf) == want_ovf
    if case.endswith("past_cap") or case.endswith("u_cap"):
        assert want_ovf > 0
    if want_ovf == 0:
        assert _rows(update) == want
    # Runs much longer than two, as a chunk of a few hundred words gives.
    if case == "long_runs":
        assert max(collections.Counter(ids[valid].tolist()).values()) > 50


def test_map_combine_doc_ids_across_packed_documents():
    # Several documents share one chunk: every (term, doc) pair of the
    # chunk, and no other, comes out of map_combine; word count of the
    # same chunk ignores the documents.
    import io

    from mapreduce_rust_tpu.apps import get_app
    from mapreduce_rust_tpu.core.hashing import hash_word
    from mapreduce_rust_tpu.runtime.chunker import pack_documents
    from mapreduce_rust_tpu.runtime.driver import make_step_fns

    rng = np.random.default_rng(3)
    vocab = [b"w%d" % i for i in range(40)]
    docs = [b" ".join(vocab[j] for j in rng.integers(0, 40, size=int(rng.integers(5, 60))))
            + b" " for _ in range(9)]
    (chunk,) = pack_documents(((i, io.BytesIO(d)) for i, d in enumerate(docs)),
                              4096, normalize=False)
    assert chunk.n_docs == len(docs)
    counts = collections.Counter(hash_word(w) for d in docs for w in d.split())
    for app, want in (
        ("inverted_index", {(*hash_word(w), i) for i, d in enumerate(docs) for w in d.split()}),
        ("word_count", {(*k, c) for k, c in counts.items()}),
    ):
        map_combine, _ = make_step_fns(get_app(app), 512)
        update, ovf = map_combine(jnp.asarray(chunk.data), jnp.asarray(chunk.doc_table))
        assert int(ovf) == 0
        keys, vals = update.to_host()
        got = {(int(a), int(b), int(v)) for (a, b), v in zip(keys.tolist(), vals.tolist())}
        assert got == want, app


def test_map_combine_clamps_an_overflowing_chunk():
    # More distinct words than u_cap: the overflow is counted and the
    # update clamps to empty padding, keys included, for the replay.
    from mapreduce_rust_tpu.apps import get_app
    from mapreduce_rust_tpu.runtime.chunker import doc_table
    from mapreduce_rust_tpu.runtime.driver import make_step_fns

    text = b" ".join(b"t%d" % i for i in range(300)).ljust(4096)
    map_combine, _ = make_step_fns(get_app("word_count"), 128)
    update, ovf = map_combine(jnp.asarray(np.frombuffer(text, np.uint8)),
                              jnp.asarray(doc_table(0)))
    assert int(ovf) == 300 - 128
    assert not np.asarray(update.valid).any()
    assert (np.asarray(update.k1) == SENTINEL).all() and (np.asarray(update.value) == 0).all()


def _big_gathers_and_scatters(stablehlo: str, limit: int = 1024) -> list:
    """The gather and scatter ops of a StableHLO module whose index
    operand (their second) holds ``limit`` or more elements."""
    import re

    found = []
    for line in stablehlo.splitlines():
        if "stablehlo.gather" not in line and "stablehlo.scatter" not in line:
            continue
        types = re.findall(r"tensor<([0-9x]*)x?[a-z]+[0-9]*>", line.split(" : ")[-1])
        dims = [int(d) for d in types[1].split("x") if d] if len(types) > 1 else []
        if int(np.prod(dims)) >= limit:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("app", ["word_count", "inverted_index"])
def test_map_combine_makes_no_chunk_sized_gather_or_scatter(app):
    # map_combine at the device cells' shapes (4 MiB chunk, u_cap 2**19),
    # lowered only: byte classes, the combine and doc ids are arithmetic,
    # sorts and neighbour compares. Word count's program has no doc-id
    # work at all: its doc table parameter is dropped.
    import jax

    from mapreduce_rust_tpu.apps import get_app
    from mapreduce_rust_tpu.runtime.chunker import DOC_SLOTS
    from mapreduce_rust_tpu.runtime.driver import make_step_fns

    map_combine, _ = make_step_fns(get_app(app), 1 << 19)
    text = map_combine.lower(
        jax.ShapeDtypeStruct((1 << 22,), jnp.uint8),
        jax.ShapeDtypeStruct((1 + DOC_SLOTS,), jnp.int32),
    ).as_text()
    assert _big_gathers_and_scatters(text) == []
    assert "stablehlo.sort" in text
    main = next(line for line in text.splitlines() if "func.func public @main" in line)
    assert ("tensor<65xi32>" in main) == (app == "inverted_index")
    assert (f"tensor<{DOC_SLOTS}x4194304x" in text) == (app == "inverted_index")


def test_big_gather_finder_sees_a_table_lookup():
    # The guard above would catch the byte-class table lookup it replaced.
    import jax

    table = jnp.arange(256, dtype=jnp.int32)
    text = jax.jit(lambda c: jnp.take(table, c.astype(jnp.int32))).lower(
        jax.ShapeDtypeStruct((4096,), jnp.uint8)).as_text()
    assert len(_big_gathers_and_scatters(text)) == 1
