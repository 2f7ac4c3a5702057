"""Bounded-memory egress tiers: disk-backed accumulator runs + dictionary
runs + the streaming merge-join finalize (VERDICT r4 missing 3 / task 4).

The contract under test: with budgets tiny enough to force both tiers to
disk, the output FILES are byte-identical to the all-RAM path's, the runs
actually exist on disk mid-job, and the in-RAM structures stay bounded.
The reference holds every pair of a partition in one Vec
(src/mr/worker.rs:82-108); this is the tier that beats it.
"""

import glob
import pathlib

import numpy as np
import pytest

from mapreduce_rust_tpu.apps import InvertedIndex, get_app
from mapreduce_rust_tpu.apps.grep import Grep
from mapreduce_rust_tpu.apps.join import Join
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.core.hashing import tokenize_host
from mapreduce_rust_tpu.runtime.dictionary import Dictionary
from mapreduce_rust_tpu.runtime.driver import HostAccumulator, run_job

WORDS = [f"tok{i:05d}" for i in range(3000)]
TEXT = " ".join(WORDS[(i * 7919) % 3000] for i in range(20000))


def write_corpus(tmp_path):
    d = tmp_path / "in"
    d.mkdir(exist_ok=True)
    p = d / "doc-0.txt"
    p.write_bytes(TEXT.encode())
    return [str(p)]


def cfg_for(tmp_path, tag, **kw) -> Config:
    return Config(
        chunk_bytes=8192,
        merge_capacity=1 << 9,   # << 3000 vocab: heavy device→host spilling
        input_dir=str(tmp_path / "in"),
        work_dir=str(tmp_path / f"work-{tag}"),
        output_dir=str(tmp_path / f"out-{tag}"),
        device="cpu",
        **kw,
    )


def read_outputs(cfg) -> dict:
    return {
        pathlib.Path(p).name: pathlib.Path(p).read_bytes()
        for p in glob.glob(str(pathlib.Path(cfg.output_dir) / "mr-*.txt"))
    }


@pytest.mark.parametrize("app_engine", ["device", "host"])
def test_budgeted_outputs_identical_and_runs_on_disk(tmp_path, app_engine):
    inputs = write_corpus(tmp_path)
    plain = cfg_for(tmp_path, f"plain-{app_engine}", map_engine=app_engine)
    res_plain = run_job(plain, inputs)

    tiered = cfg_for(
        tmp_path, f"tiered-{app_engine}", map_engine=app_engine,
        host_accum_budget_mb=0,        # every add over 0 MB → run per add
        dictionary_budget_words=512,   # 3000-word vocab → several runs
    )
    res = run_job(tiered, inputs)
    # Both DISK tiers genuinely engaged: the run-file counts are captured
    # in the stats at job end, just before the files themselves are
    # deleted (a shared work_dir must not accumulate accrun-*/dictrun-*
    # across jobs, ADVICE r5).
    assert res.stats.accum_spill_runs > 0
    assert res.stats.dict_spill_runs > 0
    assert res.stats.spill_events > 0
    assert not glob.glob(str(tmp_path / f"work-tiered-{app_engine}" / "accrun-*"))
    assert not glob.glob(str(tmp_path / f"work-tiered-{app_engine}" / "dictrun-*"))
    # Streaming egress: table empty, outputs byte-identical, stats agree.
    assert res.table == {}
    assert read_outputs(tiered) == read_outputs(plain)
    assert res.stats.distinct_keys == res_plain.stats.distinct_keys == 3000
    assert res.stats.unknown_keys == 0
    assert res.stats.dictionary_words == 3000


def _many_docs() -> list[str]:
    """120 documents, so doc ids are 1-3 digits wide: "every" is in each,
    solo<i> in one, mod<k> in every k-th, and a tail of shared tokens."""
    return [
        "every every " + f"solo{i:03d} "
        + " ".join(f"mod{k}" for k in (2, 3, 5, 7, 11) if i % k == 0) + " "
        + " ".join(f"tail{(i * 37 + j) % 300:03d}" for j in range(6))
        for i in range(120)
    ]


_PAIRS = " ".join(f"pair{i:03d}" for i in range(300))
EGRESS_CASES = {
    "three_docs": (InvertedIndex(), {"in": [
        "alpha beta gamma " * 50, "beta delta " * 40, "gamma alpha epsilon " * 30]}),
    "many_docs": (InvertedIndex(), {"in": _many_docs()}),
    "empty": (InvertedIndex(), {"in": [""]}),
    "grep": (Grep(query=("every", "solo007", "mod7", "tail123", "absent")),
             {"in": _many_docs()}),
    "join": (Join(), {"in-a": ["apple banana shared dup dup onlyleft " * 9 + _PAIRS,
                               "banana fig shared " * 7],
                      "in-b": ["banana shared onlyright " * 8 + _PAIRS,
                               "shared fig " * 5, "banana " * 4]}),
}


def egress_oracle(app, corpora) -> tuple[dict, list[bytes]]:
    """word → ascending global doc ids, and the job's sorted output lines,
    from the host tokenizer alone."""
    postings: dict = {}
    bound = len(next(iter(corpora.values())))
    texts = [t for ts in corpora.values() for t in ts]
    for doc, t in enumerate(texts):
        for w in tokenize_host(t.encode()):
            postings.setdefault(w, set()).add(doc)
    postings = {w: sorted(ds) for w, ds in postings.items()
                if not isinstance(app, Grep) or w.decode() in app.query}
    if isinstance(app, Join):
        lines = [b"%s %d %d" % (w, a, b - bound) for w, ds in postings.items()
                 for a in ds if a < bound for b in ds if b >= bound]
    else:
        lines = [w + b" " + b",".join(b"%d" % d for d in ds)
                 for w, ds in postings.items()]
    return postings, sorted(lines)


@pytest.mark.parametrize("case", sorted(EGRESS_CASES))
def test_budgeted_inverted_index_exact(tmp_path, monkeypatch, case):
    """A distinct app's output bytes are one and the same from the in-RAM
    tier, the streaming tier and the str-joining posting formatter that
    InvertedIndex.format_line replaced, and the in-RAM table maps each
    word to its ascending doc ids."""
    app, corpora = EGRESS_CASES[case]
    for name, texts in corpora.items():
        d = tmp_path / name
        d.mkdir()
        for i, t in enumerate(texts):
            (d / f"doc-{i:03d}.txt").write_bytes(t.encode())
    where = ({"input_dirs": tuple((n, str(tmp_path / n)) for n in corpora)}
             if isinstance(app, Join) else {})

    def run(tag, **kw):
        cfg = cfg_for(tmp_path, f"ii-{tag}", reduce_n=4, **where, **kw)
        return cfg, run_job(cfg, app=app)

    plain, r1 = run("plain")
    tiered, r2 = run("tiered", host_accum_budget_mb=0, dictionary_budget_words=2)
    monkeypatch.setattr(
        InvertedIndex, "format_line",
        lambda self, word, value: b"%s %s" % (
            word, ",".join(str(d) for d in value).encode()))
    str_joined, r3 = run("str-joined")
    assert read_outputs(tiered) == read_outputs(plain) == read_outputs(str_joined)
    postings, lines = egress_oracle(app, corpora)
    got = b"".join(read_outputs(plain).values()).splitlines()
    assert sorted(got) == lines
    assert r1.table == r3.table == postings  # word → ascending list[int]
    assert all(type(v) is list for v in r1.table.values())
    assert r2.table == {} and r2.stats.unknown_keys == 0
    assert r1.stats.distinct_keys == r2.stats.distinct_keys == len(r1.table)
    if case == "many_docs":
        assert b"every " + b",".join(b"%d" % d for d in range(120)) in got
        assert r2.stats.accum_spill_runs > 0 and r2.stats.dict_spill_runs > 0
    if case == "join":
        assert b"pair000 0 0" in got and len(got) > 300  # the cross product


@pytest.mark.parametrize("app_name", ["inverted_index", "word_count", "join"])
def test_distinct_keys_counts_terms_in_either_tier(tmp_path, app_name):
    """distinct_keys is the job's key count in both egress tiers: terms cut
    at key changes of the fold for a distinct app (inverted index, join),
    fold rows for word count."""
    texts = ["alpha beta alpha", "beta gamma", "gamma delta alpha"]
    for side in ("in", "in-b"):
        (tmp_path / side).mkdir()
        for i, t in enumerate(texts):
            (tmp_path / side / f"doc-{i}.txt").write_bytes(t.encode())
    kw = ({"input_dirs": (("a", str(tmp_path / "in")), ("b", str(tmp_path / "in-b")))}
          if app_name == "join" else {})
    for tag, tier in (("ram", {}), ("streaming", {"host_accum_budget_mb": 0,
                                                   "dictionary_budget_words": 2})):
        cfg = cfg_for(tmp_path, f"{app_name}-{tag}", **kw, **tier)
        res = run_job(cfg, app=get_app(app_name))
        assert (res.table == {}) == bool(tier)  # the streaming tier ran
        assert res.stats.distinct_keys == 4 and res.stats.unknown_keys == 0


def test_posting_line_format():
    """One posting line: the word, a space, ascending doc ids by commas,
    for Python and NumPy ints alike (the worker path and the fold's
    column)."""
    ii = InvertedIndex()
    assert ii.format_line(b"w", [0, 7, 120]) == b"w 0,7,120"
    assert ii.format_line(b"w", np.array([3, 45], np.uint32)) == b"w 3,45"
    assert ii.format_line(b"solo", [9]) == b"solo 9"


def test_budgeted_mesh_run_exact(tmp_path):
    # The tiers + streaming egress must compose with the mesh driver too:
    # spills arrive via the sharded evicted tails, the dictionary via the
    # ingest scans — same files out as the plain mesh run.
    inputs = write_corpus(tmp_path)
    plain = cfg_for(tmp_path, "mesh-plain", mesh_shape=4)
    run_job(plain, inputs)
    tiered = cfg_for(
        tmp_path, "mesh-tiered", mesh_shape=4,
        host_accum_budget_mb=0, dictionary_budget_words=512,
    )
    res = run_job(tiered, inputs)
    assert res.stats.mesh_rounds > 0
    assert read_outputs(tiered) == read_outputs(plain)
    assert res.stats.unknown_keys == 0


def test_budgeted_grep_filtering_app_exact(tmp_path):
    # A FILTERING app under budgets: only query keys reach the fold and
    # the dictionary, so the streaming join must emit exactly the query's
    # posting lists and nothing else.
    from mapreduce_rust_tpu.apps.grep import Grep

    inputs = write_corpus(tmp_path)
    query = ("tok00007", "tok01234", "tok02999")
    plain = cfg_for(tmp_path, "grep-plain")
    run_job(plain, inputs, app=Grep(query=query))
    tiered = cfg_for(tmp_path, "grep-tiered", host_accum_budget_mb=0,
                     dictionary_budget_words=2)
    res = run_job(tiered, inputs, app=Grep(query=query))
    assert res.table == {}  # the STREAMING join engaged, not the fallback
    assert read_outputs(tiered) == read_outputs(plain)
    got = b"".join(read_outputs(tiered).values())
    for w in query:
        assert w.encode() in got
    assert res.stats.unknown_keys == 0


def test_topk_finalize_override_rehydrates_exactly(tmp_path):
    # top_k overrides App.finalize (global selection), so a spilled
    # dictionary cannot stream — run_job must fall back to the rehydrate
    # path (exact, unbounded) and still produce the right top-k.
    from mapreduce_rust_tpu.apps import TopK

    inputs = write_corpus(tmp_path)
    plain = cfg_for(tmp_path, "topk-plain")
    r1 = run_job(plain, inputs, app=TopK(k=5))
    tiered = cfg_for(tmp_path, "topk-tiered", dictionary_budget_words=256)
    r2 = run_job(tiered, inputs, app=TopK(k=5))
    assert read_outputs(tiered) == read_outputs(plain)
    assert r2.table == r1.table  # rehydrate path returns the full table
    assert r2.stats.unknown_keys == 0


def test_accumulator_runs_fold_exactly(tmp_path):
    rng = np.random.default_rng(3)
    plain = HostAccumulator("sum")
    tiered = HostAccumulator("sum", budget_bytes=1 << 10, spill_dir=str(tmp_path))
    for _ in range(50):
        keys = rng.integers(0, 200, size=(100, 2))
        vals = rng.integers(1, 5, size=100)
        plain.add(keys, vals)
        tiered.add(keys.copy(), vals.copy())
    assert tiered.has_runs
    assert tiered.table == plain.table


def test_run_files_unique_beyond_pid_and_removable(tmp_path):
    # Two accumulators in ONE process (same pid) must never collide on run
    # names, and remove_runs must leave the spill dir clean (ADVICE r5).
    a1 = HostAccumulator("sum", budget_bytes=0, spill_dir=str(tmp_path))
    a2 = HostAccumulator("sum", budget_bytes=0, spill_dir=str(tmp_path))
    keys = np.array([[1, 2], [3, 4]])
    vals = np.array([5, 6])
    a1.add(keys, vals)
    a2.add(keys, vals)
    assert a1._runs and a2._runs
    assert set(a1._runs).isdisjoint(a2._runs)
    d1 = Dictionary(budget_words=1, spill_dir=str(tmp_path))
    d2 = Dictionary(budget_words=1, spill_dir=str(tmp_path))
    d1.add_words([b"alpha", b"beta"])
    d2.add_words([b"alpha", b"beta"])
    assert d1._runs and d2._runs and set(d1._runs).isdisjoint(d2._runs)
    for tier in (a1, a2, d1, d2):
        tier.remove_runs()
        tier.remove_runs()  # idempotent
    assert not glob.glob(str(tmp_path / "accrun-*"))
    assert not glob.glob(str(tmp_path / "dictrun-*"))


def test_spilled_dictionary_point_probes_raise(tmp_path):
    # After a budget flush the RAM tier is PARTIAL: __contains__/items()
    # answering from it alone would silently drop flushed words — they must
    # raise, and iter_sorted() must keep serving the whole dictionary.
    d = Dictionary(budget_words=4, spill_dir=str(tmp_path))
    words = [f"w{i:02d}".encode() for i in range(10)]
    d.add_words(words)
    assert d.spilled
    with pytest.raises(RuntimeError, match="iter_sorted"):
        # mrlint: ignore[spilled-dict-api] -- the forbidden probe IS the test
        (1, 2) in d  # noqa: B015 — the probe itself is the test
    with pytest.raises(RuntimeError, match="iter_sorted"):
        # mrlint: ignore[spilled-dict-api] -- the forbidden probe IS the test
        d.items()
    assert sorted(w for _p, _k1, _k2, w in d.iter_sorted()) == sorted(words)
    # Unspilled dictionaries keep the fast point probes.
    plain = Dictionary()
    plain.add_words([b"solo"])
    assert list(plain.items()) and len(plain) == 1


def test_dictionary_spill_dedup_and_iter_sorted(tmp_path):
    plain = Dictionary()
    tiered = Dictionary(budget_words=64, spill_dir=str(tmp_path))
    words = [f"word{i:04d}".encode() for i in range(500)]
    for start in range(0, 500, 50):
        batch = words[start:start + 50] + words[:10]  # re-inserts must dedup
        plain.add_words(batch)
        tiered.add_words(batch)
    assert tiered.spilled
    assert len(tiered) == len(plain) == 500
    got = [(k1, k2, w) for _p, k1, k2, w in tiered.iter_sorted()]
    want = sorted(
        ((k1, k2, w) for (k1, k2), w in plain.items()),
        key=lambda t: (t[0] << 32) | t[1],
    )
    assert got == want
    packed = [p for p, *_ in tiered.iter_sorted()]
    assert packed == sorted(packed) and len(set(packed)) == len(packed)


# ---- the host fold: one stable key sort over sorted runs, one combine ----

def _sorted_rows(rng, n_keys, n_rows, lo=0, hi=96):
    """Distinct (k1, k2, value) rows in (key, value) order, as the device
    merge leaves them: keys uint32-ranged hash lanes, values doc ids."""
    keys = rng.integers(0, 2**32, size=(n_keys, 2))
    rows = np.column_stack([keys[rng.integers(0, n_keys, n_rows)],
                            rng.integers(lo, hi, n_rows)])
    return np.unique(rows, axis=0)  # (k1, k2, value) order == packed order


def _cut_at_keys(rows, parts):
    """Split sorted rows into ``parts`` runs that share no key."""
    edges = np.flatnonzero(np.r_[True, np.any(rows[1:, :2] != rows[:-1, :2], axis=1)])
    cuts = edges[np.linspace(0, len(edges), parts + 1, dtype=int)[1:-1]]
    return np.split(rows, cuts)


def _fold_case(case, rng):
    """The batches (sorted runs or not) a HostAccumulator is fed."""
    if case == "empty":
        return []
    if case == "single_run":
        return [_sorted_rows(rng, 60, 400)]
    if case == "sorted_disjoint":
        runs = _cut_at_keys(_sorted_rows(rng, 80, 600), 5)
        return [runs[i] for i in (3, 0, 4, 1, 2)]
    if case == "shared_keys":
        # Two runs over the same keys, docs interleaved: even docs in one,
        # odd in the other, and every fourth row in both.
        rows = _sorted_rows(rng, 40, 800)
        odd = rows[:, 2] % 2 == 1
        return [rows[odd | (np.arange(len(rows)) % 4 == 0)], rows[~odd]]
    if case == "mid_key_cut":
        # A spill cut inside a key: the evicted tail (that key's larger
        # docs) reaches the accumulator before the state (its smaller ones).
        rows = _sorted_rows(rng, 30, 500)
        key = rows[len(rows) // 2, :2]
        cut = np.flatnonzero((rows[:, :2] == key).all(axis=1))[0] + 1
        return [rows[cut:], rows[:cut]]
    if case == "unsorted":
        keys = rng.integers(0, 2**32, size=(25, 2))
        rows = np.column_stack([keys[rng.integers(0, 25, 700)],
                                rng.integers(0, 40, 700)])
        return np.array_split(rows, 3)  # repeats and all, no order
    if case == "negative_and_wide":
        # Values below 0 and past 2**32, in runs that share keys.
        rows = _sorted_rows(rng, 20, 600, lo=-(2**40), hi=2**40)
        return [rows[1::2], rows[::2]]
    if case == "full_int64_range":
        # Values over the whole int64 range, both ends included.
        rows = _sorted_rows(rng, 20, 600, lo=-(2**63), hi=2**63 - 1)
        rows[:4, 2] = [-(2**63), 2**63 - 1, -1, 2**32]
        return [rows[1::2], rows[::2], rows[:5]]
    raise AssertionError(case)


def _plain_fold(op, batches):
    """The reference: rows sorted by (k1, k2, value) with np.lexsort and
    deduplicated ("distinct"), else a dict fold per key, sorted by key."""
    rows = np.concatenate(batches) if batches else np.empty((0, 3), np.int64)
    if op == "distinct":
        rows = rows[np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))]
        keep = np.r_[True, np.any(rows[1:] != rows[:-1], axis=1)][:len(rows)]
        return rows[keep].astype(np.int64)
    f = {"sum": lambda a, b: a + b, "max": max, "min": min}[op]
    fold: dict = {}
    for a, b, v in rows.tolist():
        fold[(a, b)] = f(fold[(a, b)], v) if (a, b) in fold else v
    return np.array([(a, b, v) for (a, b), v in sorted(fold.items())],
                    dtype=np.int64).reshape(-1, 3)


FOLD_CASES = [("distinct", c) for c in (
    "sorted_disjoint", "shared_keys", "mid_key_cut", "unsorted",
    "negative_and_wide", "full_int64_range", "empty", "single_run")] + [
    (op, c) for op in ("sum", "max", "min") for c in ("sorted_disjoint", "unsorted")]


@pytest.mark.parametrize("op,case", FOLD_CASES)
def test_combine_rows_matches_lexsort_reference(op, case):
    """_combine_rows over the concatenated batches, and HostAccumulator
    fed them one by one, give exactly the plain reference's rows: the
    same rows in the same order, int64, whatever the input's order."""
    from mapreduce_rust_tpu.runtime.driver import _combine_rows

    batches = [b.astype(np.int64) for b in _fold_case(case, np.random.default_rng(5))]
    want = _plain_fold(op, batches)
    cat = np.concatenate(batches) if batches else np.empty((0, 3), np.int64)
    got, _moved = _combine_rows(op, cat[:, :2], cat[:, 2])
    assert got.dtype == np.int64 and np.array_equal(got, want)
    acc = HostAccumulator(op)
    for b in batches:
        acc.add(b[:, :2], b[:, 2])
    folded = acc.fold_arrays()
    assert folded.dtype == np.int64 and np.array_equal(folded, want)


@pytest.mark.parametrize("op", ["distinct", "sum", "max", "min"])
def test_fold_arrays_combines_once(tmp_path, monkeypatch, op):
    """With only pending batches the fold is one combine; with three disk
    runs plus pending ones it still equals the plain reference."""
    from mapreduce_rust_tpu.runtime import driver

    rng = np.random.default_rng(11)
    batches = [b.astype(np.int64) for b in _fold_case("shared_keys", rng)
               + _fold_case("unsorted", rng)]
    calls = []
    real = driver._combine_rows
    monkeypatch.setattr(driver, "_combine_rows",
                        lambda *a: calls.append(a[0]) or real(*a))
    acc = HostAccumulator(op)
    for b in batches:
        acc.add(b[:, :2], b[:, 2])
    assert np.array_equal(acc.fold_arrays(), _plain_fold(op, batches))
    assert calls == [op]
    # Seven equal batches over a budget of one: every second add writes a
    # run, so three runs on disk and the seventh batch pending.
    rows = _sorted_rows(rng, 50, 2000)[:7 * 100]
    seven = [rows[i::7] for i in range(7)]
    tiered = HostAccumulator(op, budget_bytes=100 * 24, spill_dir=str(tmp_path),
                             async_spill=False)
    for b in seven:
        tiered.add(b[:, :2], b[:, 2])
    assert tiered.run_count == 3 and len(tiered._keys) == 1
    assert np.array_equal(tiered.fold_arrays(), _plain_fold(op, seven))
    tiered.remove_runs()


def test_fold_repaired_rows_counts_the_value_repair():
    """acc.repaired_rows (JobStats.acc_fold_repaired_rows) is 0 where the
    runs arrive in (key, value) order, and counts the rows the repair moved
    after a spill's mid-key cut."""
    rng = np.random.default_rng(2)
    for case, moved in (("sorted_disjoint", False), ("single_run", False),
                        ("mid_key_cut", True)):
        acc = HostAccumulator("distinct")
        for b in _fold_case(case, rng):
            acc.add(b[:, :2], b[:, 2])
        acc.fold_arrays()
        assert (acc.repaired_rows > 0) == moved, case
