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
