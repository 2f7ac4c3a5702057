"""Workload plane (ISSUE 15): range-partitioned global sort + two-input
equi-join, the sampled-splitter subsystem, and the multi-corpus input API.

The flagship assertions: ``sort`` output concatenated over mr-{r}.txt in
partition order is EXACTLY ``sorted()`` of the corpus token multiset,
bit-identical over the whole (host_map_workers, fold_shards) matrix, with
and without spill budgets, and under MR_SANITIZE=1; ``join`` matches a
Python dict-join oracle on two corpora including duplicate and one-sided
keys (and an empty side); splitters are DETERMINISTIC given the seeded
sample — proven end-to-end by a chaos ``kill`` leg whose re-executed task
re-derives identical routing (outputs bit-identical to the fault-free
run, mrcheck exit 0)."""

import json
import pathlib
import random

import numpy as np
import pytest

from mapreduce_rust_tpu.apps import get_app
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.core.hashing import tokenize_host
from mapreduce_rust_tpu.ops.partition import (
    bucket_scatter,
    pack_word_prefix,
    range_partition,
    splitter_pairs,
)
from mapreduce_rust_tpu.runtime import splitter
from mapreduce_rust_tpu.runtime.chunker import (
    iter_chunks,
    parse_input_spec,
    resolve_corpora,
)
from mapreduce_rust_tpu.runtime.driver import run_job

WS = [(1, 1), (2, 2), (4, 1), (1, 4), (4, 4), (2, 4)]

# Mixed-length tokens (shared 8-byte prefixes included: the range pack is
# only a PREFIX order — equal-prefix words must still sort right), plus
# duplicates and a high-cardinality tail.
SORT_TEXTS = [
    ("internationalization internationalism internationale banana "
     "apple apple banana cherry " * 40
     + " ".join(f"tok{i:04d}" for i in range(800))),
    ("zebra zebra quagga okapi date elderberry fig grape " * 50
     + " ".join(f"tok{i:04d}" for i in range(400, 1200))),
]

_PAIR_TAIL = " ".join(f"pair{i:04d}" for i in range(500))
JOIN_A = [
    "apple banana cherry apple shared dup dup onlyleft " * 20 + _PAIR_TAIL,
    "banana shared fig onlyleft2 " * 15
    + " ".join(f"la{i:04d}" for i in range(300)),
]
JOIN_B = [
    "banana shared date onlyright " * 18 + _PAIR_TAIL,
    "shared fig elderberry " * 12
    + " ".join(f"rb{i:04d}" for i in range(300)),
    "banana onlyright2 " * 10,
]


def write_docs(d: pathlib.Path, texts) -> str:
    d.mkdir(parents=True, exist_ok=True)
    for i, t in enumerate(texts):
        (d / f"doc-{i}.txt").write_bytes(t.encode())
    return str(d)


def cfg_for(tmp_path, tag, w=1, s=1, **kw) -> Config:
    defaults = dict(
        map_engine="host",
        host_map_workers=w,
        fold_shards=s,
        host_window_bytes=4096,
        chunk_bytes=4096,
        merge_capacity=2048,
        reduce_n=4,
        split_samples=128,
        device="cpu",
        output_dir=str(tmp_path / f"out-{tag}-w{w}s{s}"),
        work_dir=str(tmp_path / f"work-{tag}-w{w}s{s}"),
    )
    defaults.update(kw)
    return Config(**defaults)


def cat_lines(res) -> list[bytes]:
    """Output lines concatenated in PARTITION ORDER (the global-order
    reading of mr-{r}.txt)."""
    lines: list[bytes] = []
    for p in res.output_files:
        lines.extend(pathlib.Path(p).read_bytes().splitlines())
    return lines


def output_bytes(res) -> list[bytes]:
    return [pathlib.Path(p).read_bytes() for p in res.output_files]


def corpus_tokens(texts) -> list[bytes]:
    toks: list[bytes] = []
    for t in texts:
        toks.extend(tokenize_host(t.encode()))
    return toks


# ---------------------------------------------------------------------------
# Splitter subsystem units
# ---------------------------------------------------------------------------

def test_pack_word_prefix_is_order_preserving():
    words = [b"", b"a", b"ab", b"abc", b"abcdefgh", b"abcdefghi", b"b",
             b"zzzzzzzzzz", b"\xf0\x9f\x8d\x8c banana".split()[0]]
    packed = pack_word_prefix(words)
    for i, wi in enumerate(words):
        for j, wj in enumerate(words):
            if wi < wj:
                assert packed[i] <= packed[j], (wi, wj)


def test_derive_splitters_order_statistics_and_edges():
    samples = np.array([50, 10, 30, 20, 40], dtype=np.uint64)
    spl = splitter.derive_splitters(samples, 4)
    assert spl.dtype == np.uint64 and len(spl) == 3
    assert list(spl) == sorted(spl)
    # searchsorted(side=right): every partition id in range, monotone.
    parts = range_partition(np.sort(samples), spl)
    assert list(parts) == sorted(parts)
    assert parts.max() <= 3
    # R=1 → no splitters; empty sample → all keys to partition 0.
    assert len(splitter.derive_splitters(samples, 1)) == 0
    empty = splitter.derive_splitters(np.zeros(0, dtype=np.uint64), 4)
    assert len(empty) == 3
    assert range_partition(samples, empty).max() == 0


def test_splitters_deterministic_and_seed_sensitive(tmp_path):
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    cfg = cfg_for(tmp_path, "det", input_dir=docs)
    inputs, _b, _n = resolve_corpora(cfg)
    a = splitter.splitters_for_job(cfg, inputs)
    b = splitter.splitters_for_job(cfg, inputs)
    assert np.array_equal(a, b)  # pure in (inputs, config)
    # The per-file sample itself is reproducible and seed-keyed.
    s1 = splitter.sample_file(inputs[0], 32, seed=1, file_index=0)
    s2 = splitter.sample_file(inputs[0], 32, seed=1, file_index=0)
    s3 = splitter.sample_file(inputs[0], 32, seed=2, file_index=0)
    assert s1 == s2
    assert s1 != s3
    # And every sampled token is a REAL corpus token (pipeline rules).
    assert set(s1) <= set(corpus_tokens(SORT_TEXTS))


def test_prepare_app_binds_and_validates(tmp_path):
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    cfg = cfg_for(tmp_path, "prep", input_dir=docs)
    inputs, _b, _n = resolve_corpora(cfg)
    app = splitter.prepare_app(get_app("sort"), cfg, inputs, ())
    assert len(app.splitters) == cfg.reduce_n - 1
    # Idempotent: a bound app is not re-sampled.
    again = splitter.prepare_app(app, cfg, inputs, ())
    assert again.splitters == app.splitters
    # join's corpus-arity contract fails AT BIND, not mid-task.
    with pytest.raises(ValueError, match="exactly 2 input corpora"):
        splitter.prepare_app(get_app("join"), cfg, inputs, ())


def test_device_range_scatter_matches_host_route():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1 << 63, size=512, dtype=np.uint64)
    spl = splitter.derive_splitters(keys[:64], 8)
    host = range_partition(keys, spl)
    from mapreduce_rust_tpu.core.kv import KVBatch

    k1 = (keys >> np.uint64(32)).astype(np.uint32)
    k2 = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    batch = KVBatch(k1=k1, k2=k2,
                    value=np.ones(len(keys), dtype=np.int32),
                    valid=np.ones(len(keys), dtype=bool))
    out, ovf = bucket_scatter(batch, num_buckets=8, capacity=len(keys),
                              mode="range", splitters=splitter_pairs(spl))
    assert int(ovf) == 0
    got = np.asarray(out.valid).nonzero()
    # Reconstruct each record's bucket from the scatter layout and match
    # the host router exactly (the device twin contract).
    packed_out = (np.asarray(out.k1).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(out.k2).astype(np.uint64)
    for b in range(8):
        want = np.sort(keys[host == b])
        have = np.sort(packed_out[b][np.asarray(out.valid)[b]])
        assert np.array_equal(want, have), b


# ---------------------------------------------------------------------------
# Sort: the global-order contract
# ---------------------------------------------------------------------------

def test_sort_oracle_exact_and_bit_identical_across_matrix(tmp_path):
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    oracle = sorted(corpus_tokens(SORT_TEXTS))
    first = None
    for w, s in WS:
        res = run_job(cfg_for(tmp_path, "sort", w, s, input_dir=docs),
                      app=get_app("sort"))
        assert res.stats.partition_mode == "range"
        assert res.stats.splitter_samples > 0
        if first is None:
            first = res
            assert cat_lines(res) == oracle  # THE TeraSort contract
            # Range partitioning actually spread the keys (no degenerate
            # everything-in-one-partition pass).
            nonempty = [b for b in output_bytes(res) if b]
            assert len(nonempty) >= 2
        else:
            assert output_bytes(res) == output_bytes(first), (w, s)


def test_sort_spill_budgets_bit_identical(tmp_path):
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    plain = run_job(cfg_for(tmp_path, "sp-ref", 2, 2, input_dir=docs),
                    app=get_app("sort"))
    spilled = run_job(
        cfg_for(tmp_path, "sp", 2, 2, input_dir=docs,
                dictionary_budget_words=256, host_accum_budget_mb=1),
        app=get_app("sort"),
    )
    # The budget run really exercised the streaming merge-join egress —
    # range routing included (App.route_block, driver._stream_finalize).
    assert spilled.stats.dict_spill_runs > 0
    assert spilled.table == {}
    assert output_bytes(spilled) == output_bytes(plain)
    assert cat_lines(spilled) == sorted(corpus_tokens(SORT_TEXTS))


def test_sort_device_engine_matches_host(tmp_path):
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    host = run_job(cfg_for(tmp_path, "eng-h", input_dir=docs),
                   app=get_app("sort"))
    dev = run_job(cfg_for(tmp_path, "eng-d", input_dir=docs,
                          map_engine="device"),
                  app=get_app("sort"))
    assert output_bytes(dev) == output_bytes(host)


def test_sort_under_sanitizer(tmp_path, monkeypatch):
    monkeypatch.setenv("MR_SANITIZE", "1")
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    res = run_job(cfg_for(tmp_path, "san", 2, 2, input_dir=docs,
                          sanitize=True),
                  app=get_app("sort"))
    assert cat_lines(res) == sorted(corpus_tokens(SORT_TEXTS))


def test_sort_merge_outputs_final_txt(tmp_path):
    # `merge` (cat mr-* | sort) over range-partitioned outputs is a
    # no-op reorder: the concatenation was already globally sorted.
    from mapreduce_rust_tpu.runtime.driver import merge_outputs

    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    res = run_job(cfg_for(tmp_path, "merge", input_dir=docs),
                  app=get_app("sort"))
    out = tmp_path / "final.txt"
    merge_outputs(res.output_files, str(out))
    assert out.read_bytes().splitlines() == cat_lines(res)


# ---------------------------------------------------------------------------
# Join: the two-corpus contract
# ---------------------------------------------------------------------------

def join_oracle(texts_a, texts_b) -> list[bytes]:
    """Python dict-join: word → (left docs) × (right docs), relative doc
    ids, duplicates collapsed per (word, doc) like combine_op distinct."""
    left: dict[bytes, set] = {}
    right: dict[bytes, set] = {}
    for i, t in enumerate(texts_a):
        for w in tokenize_host(t.encode()):
            left.setdefault(w, set()).add(i)
    for i, t in enumerate(texts_b):
        for w in tokenize_host(t.encode()):
            right.setdefault(w, set()).add(i)
    lines = []
    for w in set(left) & set(right):
        for a in left[w]:
            for b in right[w]:
                lines.append(b"%s %d %d" % (w, a, b))
    return sorted(lines)


def _join_cfg(tmp_path, tag, w=1, s=1, **kw) -> Config:
    return cfg_for(
        tmp_path, tag, w, s,
        input_dirs=(("a", str(tmp_path / "in-a")),
                    ("b", str(tmp_path / "in-b"))),
        **kw,
    )


def test_join_matches_dict_join_oracle_across_matrix(tmp_path):
    write_docs(tmp_path / "in-a", JOIN_A)
    write_docs(tmp_path / "in-b", JOIN_B)
    oracle = join_oracle(JOIN_A, JOIN_B)
    assert oracle  # the corpora really share keys
    first = None
    for w, s in [(1, 1), (2, 2), (4, 4), (2, 4)]:
        res = run_job(_join_cfg(tmp_path, "join", w, s), app=get_app("join"))
        if first is None:
            first = res
            assert sorted(cat_lines(res)) == oracle
            # One-sided keys vanished (inner join).
            words = {ln.split()[0] for ln in cat_lines(res)}
            assert b"onlyleft" not in words and b"onlyright" not in words
        else:
            assert output_bytes(res) == output_bytes(first), (w, s)


def test_join_with_spill_budgets_and_device_engine(tmp_path):
    write_docs(tmp_path / "in-a", JOIN_A)
    write_docs(tmp_path / "in-b", JOIN_B)
    plain = run_job(_join_cfg(tmp_path, "jref"), app=get_app("join"))
    spilled = run_job(
        _join_cfg(tmp_path, "jsp", 2, 2, dictionary_budget_words=64,
                  host_accum_budget_mb=1),
        app=get_app("join"),
    )
    assert spilled.stats.dict_spill_runs > 0
    assert output_bytes(spilled) == output_bytes(plain)
    dev = run_job(_join_cfg(tmp_path, "jdev", map_engine="device"),
                  app=get_app("join"))
    assert output_bytes(dev) == output_bytes(plain)


def test_join_empty_side_yields_empty_output(tmp_path):
    write_docs(tmp_path / "in-a", JOIN_A)
    (tmp_path / "in-b").mkdir()  # side b: a corpus with no documents
    res = run_job(_join_cfg(tmp_path, "jempty"), app=get_app("join"))
    assert cat_lines(res) == []
    assert all(b == b"" for b in output_bytes(res))


def test_join_requires_two_corpora_everywhere(tmp_path):
    docs = write_docs(tmp_path / "in", JOIN_A)
    with pytest.raises(ValueError, match="exactly 2 input corpora"):
        run_job(cfg_for(tmp_path, "jone", input_dir=docs),
                app=get_app("join"))


# ---------------------------------------------------------------------------
# Multi-corpus input API
# ---------------------------------------------------------------------------

def test_parse_input_spec_forms():
    assert parse_input_spec(["data"]) == ("data", None)
    # ONE value is always the classic dir form — '=' is a legal path char.
    assert parse_input_spec(["data/run=5"]) == ("data/run=5", None)
    d, pairs = parse_input_spec(["b=y", "a=x"])
    assert pairs == (("a", "x"), ("b", "y"))  # canonical name order
    assert d == "x"
    with pytest.raises(ValueError, match="name=DIR"):
        parse_input_spec(["x", "y"])
    with pytest.raises(ValueError, match="duplicate"):
        parse_input_spec(["a=x", "a=y"])


def test_resolve_corpora_bounds_and_chunk_tagging(tmp_path):
    write_docs(tmp_path / "in-a", JOIN_A)     # 2 docs
    write_docs(tmp_path / "in-b", JOIN_B)     # 3 docs
    cfg = _join_cfg(tmp_path, "bounds")
    inputs, bounds, names = resolve_corpora(cfg)
    assert len(inputs) == 5 and bounds == (2,) and names == ("a", "b")
    # The chunker packs each corpus on its own and tags each chunk with
    # its documents' corpus id.
    corpora = {d: c.corpus
               for c in iter_chunks(inputs, 4096, corpus_bounds=bounds)
               for d in range(c.first_doc, c.first_doc + c.n_docs)}
    assert corpora == {0: 0, 1: 0, 2: 1, 3: 1, 4: 1}


def test_config_input_dirs_validation():
    with pytest.raises(ValueError, match="string pairs"):
        Config(input_dirs=(("a",),))
    with pytest.raises(ValueError, match="duplicate"):
        Config(input_dirs=(("a", "x"), ("a", "y")))
    cfg = Config(input_dirs=[("a", "x"), ("b", "y")])
    assert cfg.corpora() == (("a", "x"), ("b", "y"))
    assert Config(input_dir="z").corpora() == (("corpus", "z"),)


def test_multi_corpus_digest_stability(tmp_path):
    """ISSUE 15 acceptance: the service cache key over N corpora is
    stable across submission spelling (order, trailing slash) and
    SENSITIVE to the name→dir assignment (join's sides swapping IS a
    different job)."""
    from mapreduce_rust_tpu.service.server import (
        _ResultCache,
        scan_corpus_spec,
        validate_spec,
    )

    da = write_docs(tmp_path / "in-a", JOIN_A)
    db = write_docs(tmp_path / "in-b", JOIN_B)
    s1 = validate_spec({"app": "join", "inputs": [["a", da], ["b", db]]})
    s2 = validate_spec({"app": "join", "inputs": [["b", db], ["a", da]]})
    assert s1 == s2  # canonicalized: same job however spelled
    assert _ResultCache.key(s1) == _ResultCache.key(s2)
    swapped = validate_spec({"app": "join",
                             "inputs": [["a", db], ["b", da]]})
    assert _ResultCache.key(swapped) != _ResultCache.key(s1)
    # The combined scan: flat listing + total bytes over both corpora.
    paths, nbytes, digest = scan_corpus_spec(s1)
    assert len(paths) == 5 and nbytes > 0 and len(digest) == 16
    assert scan_corpus_spec(s2)[2] == digest
    # Touching one corpus changes the combined digest.
    (tmp_path / "in-b" / "doc-0.txt").write_bytes(b"changed tokens here")
    assert scan_corpus_spec(s1)[2] != digest
    # join via the service demands exactly two corpora.
    with pytest.raises(ValueError, match="exactly two"):
        validate_spec({"app": "join", "input_dir": da})
    # split_samples canonicalizes to an EXPLICIT spec field (the whole
    # fleet must sample identically — no per-worker CLI fallback) and
    # splits the config digest: different samples = different splitters
    # = different partition boundaries = a different cached output.
    s_sort = validate_spec({"app": "sort", "input_dir": da})
    assert s_sort["split_samples"] == 512
    s_sort2 = validate_spec({"app": "sort", "input_dir": da,
                             "split_samples": 64})
    assert _ResultCache.key(s_sort) != _ResultCache.key(s_sort2)
    with pytest.raises(ValueError, match="split_samples"):
        validate_spec({"app": "sort", "input_dir": da,
                       "split_samples": 0})


# ---------------------------------------------------------------------------
# Doctor: splitter quality + deliberate skew
# ---------------------------------------------------------------------------

def _zipfish_texts(vocab=400, n=30000, s=1.4, seed=11) -> list[str]:
    rng = random.Random(seed)
    weights = [1.0 / (r + 1) ** s for r in range(vocab)]
    total = sum(weights)
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    import bisect

    toks = [f"z{bisect.bisect_left(cdf, rng.random()):05d}"
            for _ in range(n)]
    return [" ".join(toks[: n // 2]), " ".join(toks[n // 2:])]


def test_partition_skew_scored_on_skewed_zipf_sort_run(tmp_path):
    """ISSUE 15 satellite: the existing partition-skew score fires on a
    DELIBERATELY skewed Zipf sort run, and the range-mode finding is
    splitter-quality (raise --split-samples), not the hash-mode
    reduce-skew advice."""
    texts = _zipfish_texts()
    docs = write_docs(tmp_path / "in", texts)
    manifest = tmp_path / "m.json"
    # One sample per file: splitters under-resolve the Zipf head and the
    # hot token's partition dominates — the skew the doctor must name.
    cfg = cfg_for(tmp_path, "skew", input_dir=docs, split_samples=1,
                  manifest_path=str(manifest))
    res = run_job(cfg, app=get_app("sort"))
    assert cat_lines(res) == sorted(corpus_tokens(texts))  # skewed ≠ wrong
    from mapreduce_rust_tpu.analysis.doctor import diagnose
    from mapreduce_rust_tpu.runtime.telemetry import load_manifest

    diag = diagnose(load_manifest(str(manifest)))
    score = diag["skew"]["reduce_partition_bytes"]["score"]
    assert score and score > 1.5, diag["skew"]
    codes = {f["code"] for f in diag["findings"]}
    assert "splitter-quality" in codes
    assert "reduce-skew" not in codes
    finding = next(f for f in diag["findings"]
                   if f["code"] == "splitter-quality")
    assert "split-samples" in finding["message"] \
        or "split_samples" in finding["message"]


def test_splitter_quality_quiet_on_balanced_run(tmp_path):
    docs = write_docs(tmp_path / "in", SORT_TEXTS)
    manifest = tmp_path / "m.json"
    cfg = cfg_for(tmp_path, "bal", input_dir=docs, split_samples=512,
                  manifest_path=str(manifest))
    run_job(cfg, app=get_app("sort"))
    from mapreduce_rust_tpu.analysis.doctor import diagnose
    from mapreduce_rust_tpu.runtime.telemetry import load_manifest

    m = load_manifest(str(manifest))
    assert m["stats"]["partition_mode"] == "range"
    assert m["stats"]["splitter_samples"] > 0
    diag = diagnose(m)
    assert "splitter-quality" not in {f["code"] for f in diag["findings"]}


# ---------------------------------------------------------------------------
# Chaos: kill a sort job's worker — splitters re-derive identically
# ---------------------------------------------------------------------------

def test_chaos_kill_on_sort_job_rederives_identical_splitters(tmp_path):
    """ISSUE 15 acceptance: a SIGKILLed map task re-executes on another
    worker process, which re-derives splitters from the SAME seeded
    sample — the job completes with mrcheck exit 0 and outputs
    bit-identical to the fault-free run (one re-derivation disagreement
    would route keys to different partitions and the byte compare would
    catch it)."""
    from cluster_harness import CHAOS_TEXTS, chaos_cluster

    clean = chaos_cluster("sort-clean", tmp_path, None, False, app="sort")
    assert clean["recovered"], clean.get("error")
    assert clean["outputs"]
    oracle = sorted(
        tok for t in CHAOS_TEXTS for tok in tokenize_host(t)
    )
    got = []
    for _name, data in sorted(clean["outputs"].items()):
        got.extend(data.splitlines())
    assert got == oracle

    chaos = chaos_cluster("sort-kill", tmp_path, "seed=5;kill:map:1",
                          False, app="sort")
    assert chaos["recovered"], chaos.get("error")
    assert chaos["outputs"] == clean["outputs"]
    rep = json.loads(
        (pathlib.Path(chaos["dir"]) / "work" / "job_report.json").read_text()
    )["report"]
    assert sum(t.get("expiries", 0) for t in rep["totals"].values()) >= 1

    from mapreduce_rust_tpu.analysis.mrcheck import run_check

    for leg in (clean, chaos):
        doc = run_check(str(pathlib.Path(leg["dir"]) / "work"))
        assert doc["ok"], (leg["scenario"], doc["violations"])
