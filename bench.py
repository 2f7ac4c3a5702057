#!/usr/bin/env python
"""End-to-end benchmark: word-count GB/s on TPU vs the CPU multi-process
baseline (BASELINE.md configs 1-3).

Prints ONE JSON line on stdout, ALWAYS (an "error" field appears on partial
failure):
    {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N}

Structure (round-3 verdict: the old layout ran the fragile TPU leg first,
unguarded, and lost the number three rounds running):
  1. corpus build (cheap, deterministic, cached in .bench/);
  2. CPU multi-process baseline FIRST — needs no JAX and no chip.
     Faithful to the reference's ARCHITECTURE: map
     tasks tokenize (regex strip + split, src/app/wc.rs:6-17) and
     hash-partition every token occurrence into mr-{m}-{r}.txt files,
     phase barrier, reduce tasks read them back and count — the
     file-plane shuffle that defines the reference (src/mr/worker.rs:
     117-140), on a process pool like its map_n×worker_n model
     (src/bin/mrworker.rs:43-151). Batched file writes and a Counter
     reduce are deliberate generosities (the original pays one awaited
     write + one println per KV and a full sort per partition);
  3. device leg in a SUBPROCESS with a hard timeout — a crashed or hung
     device runtime costs us the leg, not the JSON line;
  4. on device-leg failure, a bounded CPU-XLA fallback subprocess (smaller
     corpus) so "value" is still a measured number of the same pipeline.

The device leg itself relies on two caches so warm != cold is real:
module-level step-fn caches (runtime/driver.py make_step_fns) and the
persistent XLA compilation cache (<repo>/.jax_cache), which survives across
processes — the warmup pass compiles at most once per machine image.
"""

from __future__ import annotations

import collections
import json
import multiprocessing
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
REF_DATA = pathlib.Path("/root/reference/src/data")
BENCH_DIR = REPO / ".bench"
TARGET_MB = int(os.environ.get("BENCH_TARGET_MB", "512"))  # big enough that
# one-time costs (state fetch, finalize, egress) amortize into the rate,
# small enough to stay page-cache-resident next to the CPU baseline run
# 64 MB halves the baseline's run-to-run noise vs 32 MB (the 1-core pool
# measurement swings ±50% at small sizes) at ~6 s per run.
BASELINE_MB = int(os.environ.get("BENCH_BASELINE_MB", "64"))
# Fallback is sized so fixed costs (state egress, 46K-key dictionary
# finalize, jit dispatch) amortize: measured 0.017 GB/s at 8 MB,
# 0.078 GB/s at 64 MB, 0.122 GB/s (exact, 13× baseline) at 1 GB for the
# identical CPU-XLA pipeline. Default = the main leg's corpus (no extra
# build), CAPPED at 512 MB so the leg stays inside its fixed
# FALLBACK_TIMEOUT_S even when BENCH_TARGET_MB is cranked to 10 GB
# (~5 s of compute at 512 MB; the rest of the budget is compile headroom).
FALLBACK_MB = int(os.environ.get("BENCH_FALLBACK_MB", str(min(TARGET_MB, 512))))
DEVICE_TIMEOUT_S = int(os.environ.get("BENCH_DEVICE_TIMEOUT_S", "300"))
FALLBACK_TIMEOUT_S = int(os.environ.get("BENCH_FALLBACK_TIMEOUT_S", "150"))
# Deadline for the device leg's BENCH_DEVICE_READY heartbeat (backend
# init), NOT for the run — see _run_device_leg.
PROBE_TIMEOUT_S = int(os.environ.get("BENCH_PROBE_TIMEOUT_S", "90"))


from mapreduce_rust_tpu.runtime.zipf import (  # noqa: E402  (numpy only)
    ZIPF_S,
    ZIPF_VOCAB,
    atomic_np_save as _atomic_np_save,
    build_zipf_corpus as _build_zipf_corpus,
    rank_counts,
    write_zipf_tokens as _write_zipf_tokens,
    zipf_sampler as _zipf_sampler,
)


def _cpu_env() -> dict:
    """A child environment on CPU-XLA: legs that exercise host-side
    planes must leave the chip alone (a chip serves one process)."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


_WS = b" \t\n\r\x0b\x0c"


def _env_host_workers() -> "int | None":
    """--host-workers rides into subprocess legs as BENCH_HOST_WORKERS
    (None = Config auto: usable cores minus the consumer's)."""
    v = os.environ.get("BENCH_HOST_WORKERS")
    return int(v) if v else None


def _env_fold_shards() -> "int | None":
    """--fold-shards rides into subprocess legs as BENCH_FOLD_SHARDS
    (None = Config auto: 1 below 4 usable cores, else min(4, cores//2))."""
    v = os.environ.get("BENCH_FOLD_SHARDS")
    return int(v) if v else None


def build_corpus(target_mb: int) -> pathlib.Path:
    out = BENCH_DIR / f"corpus-{target_mb}mb.txt"
    if out.exists() and out.stat().st_size >= target_mb << 20:
        return out
    BENCH_DIR.mkdir(exist_ok=True)
    if REF_DATA.exists():
        seed = b"\n".join(p.read_bytes() for p in sorted(REF_DATA.glob("gut-*.txt")))
    else:  # synthetic fallback
        import random

        rng = random.Random(0)
        seed = (" ".join(f"w{rng.randrange(100000)}" for _ in range(2_000_000))).encode()
    try:
        with open(out, "wb") as f:
            written = 0
            while written < target_mb << 20:
                f.write(seed)
                f.write(b"\n")
                written += len(seed) + 1
    except BaseException:
        # Unlink the partial file: it pins the disk space a shrink retry
        # needs, and an interrupted loop that had already crossed the
        # target size would satisfy the >= check of a later SAME-size run
        # with a torn tail. (Different sizes use different filenames, so
        # cross-size staleness is not the hazard here.)
        try:
            out.unlink()
        except OSError:
            pass
        raise
    return out


def _zipf_cfg(work: str, out: str, reduce_n: int):
    """THE budgets-engaged config both high-cardinality legs run under —
    one copy, so the conditions 'budgets engaged / eviction constant'
    cannot silently diverge between word_count and inverted_index."""
    from mapreduce_rust_tpu.config import Config

    # --sweep-spill-budget rides into the leg as BENCH_SPILL_BUDGET_WORDS
    # (smaller budget = more, smaller runs = more spill-plane pressure).
    budget = int(os.environ.get("BENCH_SPILL_BUDGET_WORDS") or (1 << 19))
    # Dispatch-plane knobs (ISSUE 13): --sweep-dispatch-fill rides in as
    # BENCH_DISPATCH_FILL; the A/B pair turns coalescing off with
    # BENCH_DISPATCH_COALESCE=0 (MR_DISPATCH_SYNC needs no plumbing — the
    # driver reads the env directly, like MR_SPILL_SYNC).
    fill = float(os.environ.get("BENCH_DISPATCH_FILL") or 0.5)
    coalesce = os.environ.get("BENCH_DISPATCH_COALESCE", "1") != "0"
    return Config(
        dispatch_fill_frac=fill,
        dispatch_coalesce=coalesce,
        map_engine=os.environ.get("BENCH_MAP_ENGINE", "host"),
        host_map_workers=_env_host_workers(),
        fold_shards=_env_fold_shards(),
        host_window_bytes=16 << 20,
        chunk_bytes=1 << 20,
        merge_capacity=1 << 18,        # << the Zipf vocab: constant eviction
        host_accum_budget_mb=256,      # spill-run tier engaged
        dictionary_budget_words=budget,  # dictionary tier engaged
        reduce_n=reduce_n,
        work_dir=str(BENCH_DIR / work),
        output_dir=str(BENCH_DIR / out),
        device="auto",
        # A per-leg run manifest (full JobStats incl. spill_split) when the
        # sweep asks for one; distinct env var from the device leg's so the
        # zipf leg can never clobber the measured leg's manifest.
        manifest_path=os.environ.get("BENCH_ZIPF_RUN_MANIFEST") or None,
    )


def build_zipf_corpus(target_mb: int, vocab: int = ZIPF_VOCAB,
                      s: float = ZIPF_S) -> tuple[pathlib.Path, pathlib.Path]:
    """The seeded Zipf corpus (runtime/zipf.py), cached in .bench/ (VERDICT
    r4 missing 2): unlike the replicated gut corpus (~46K distinct), it
    exercises merge eviction, spill runs and dictionary growth."""
    return _build_zipf_corpus(
        BENCH_DIR / f"zipf-{target_mb}mb-v{vocab}-s{s}.txt", target_mb << 20,
        vocab, s,
    )


def zipf_leg(target_mb: int) -> None:
    """Runs in a subprocess (--zipf): word_count over the Zipf corpus with
    egress budgets engaged, verified exactly against the generator's
    ground-truth counts. Prints one JSON detail line."""
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    from mapreduce_rust_tpu.runtime.driver import enable_compilation_cache, run_job

    enable_compilation_cache("auto")
    corpus, counts_p = build_zipf_corpus(target_mb)
    truth = np.load(counts_p)
    cfg = _zipf_cfg("zipf-work", "zipf-out", reduce_n=8)
    import shutil

    shutil.rmtree(cfg.work_dir, ignore_errors=True)
    t0 = time.perf_counter()
    res = run_job(cfg, [str(corpus)])
    dt = time.perf_counter() - t0
    s = res.stats
    # Exactness vs generator ground truth, streamed from the output files.
    got, n_lines = rank_counts(res.output_files)
    exact = bool(np.array_equal(got, truth))
    from mapreduce_rust_tpu.runtime.spill import RUN_FORMAT

    # Roofline attribution (ISSUE 19): achieved scan bandwidth (bytes
    # over aggregate scan-thread seconds) vs the calibrated machine roof
    # (.bench/machine.json — measured once, reused every round). Both
    # series land top-level in history; the doctor trend watches both
    # (bad=down): efficiency eroding toward "slow scan" shows here even
    # when wall seconds drift with corpus size.
    scan_achieved_gbs = roofline_frac = None
    try:
        from mapreduce_rust_tpu.analysis.roofline import calibrate

        machine = calibrate()
        if s.host_map_s:
            scan_achieved_gbs = round(s.bytes_in / s.host_map_s / 1e9, 4)
            roof = machine.get("host_memcpy_gbs")
            if roof:
                roofline_frac = round(scan_achieved_gbs / roof, 4)
    except Exception:
        pass  # attribution is best-effort; the leg's gates stay exactness

    print(json.dumps({
        "zipf": {
            "bytes": s.bytes_in, "wall_s": round(dt, 3),
            "gbs": round(s.gb_per_s, 4), "platform": platform,
            "distinct": s.distinct_keys, "expected_distinct": int((truth > 0).sum()),
            "exact": exact, "lines": n_lines,
            "spills": s.spill_events, "spilled_keys": s.spilled_keys,
            "replays": s.partial_overflow_replays,
            "dict_words": s.dictionary_words,
            "map_engine": cfg.map_engine,
            # Spill-plane attribution (ISSUE 11): the before/after story of
            # the binary async plane lives in THESE fields' history rows.
            "spill_format": RUN_FORMAT,
            "spill_write_s": round(s.spill_s, 3),
            "spill_stall_s": round(s.spill_stall_s, 3),
            "spill_bytes": s.spill_bytes,
            "dict_runs": s.dict_spill_runs,
            "accum_runs": s.accum_spill_runs,
            "merge_fanin": s.merge_fanin,
            "budget_words": cfg.dictionary_budget_words,
            "bottleneck": s.bottleneck,
            # Dispatch-plane attribution (ISSUE 13): the before/after
            # story of the async coalescing plane lives in THESE fields'
            # history rows.
            "dispatch_mode": s.dispatch_mode,
            "dispatch_s": round(s.dispatch_s, 3),
            "dispatch_stall_s": round(s.dispatch_stall_s, 3),
            "merge_dispatches": s.merge_dispatches,
            "merge_fill_frac": round(s.merge_fill_frac, 4),
            # Roofline attribution (ISSUE 19) — see calibrate() above.
            "scan_achieved_gbs": scan_achieved_gbs,
            "roofline_frac": roofline_frac,
        }
    }))
    if not exact:
        raise SystemExit(3)


def zipf_ii_leg(target_mb: int, n_docs: int = 8) -> None:
    """Runs in a subprocess (--zipf-ii): INVERTED INDEX over a multi-doc
    Zipf corpus, budgets engaged, posting lists verified exactly against
    the generator's presence matrix (VERDICT r4 next-round 3 names both
    word_count and inverted_index). Prints one JSON detail line."""
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    from mapreduce_rust_tpu.apps import InvertedIndex
    from mapreduce_rust_tpu.runtime.driver import enable_compilation_cache, run_job

    enable_compilation_cache("auto")
    vocab = ZIPF_VOCAB
    base = BENCH_DIR / f"zipf-ii-{target_mb}mb-n{n_docs}"  # n_docs keys the
    # cache: a different doc split must never reuse another's ground truth
    docs = [base.with_name(base.name + f"-d{d}.txt") for d in range(n_docs)]
    pres_p = base.with_name(base.name + ".presence.npy")
    if not (pres_p.exists() and all(p.exists() for p in docs)):
        BENCH_DIR.mkdir(exist_ok=True)
        rng = np.random.default_rng(20260731)
        cdf, table = _zipf_sampler(vocab, ZIPF_S)
        presence = np.zeros((vocab, n_docs), dtype=bool)
        per_doc = (target_mb << 20) // (8 * n_docs) + 1
        try:
            for d, path in enumerate(docs):

                def on_block(ranks, _d=d):
                    presence[:, _d] |= np.bincount(ranks, minlength=vocab) > 0

                with open(path, "wb") as f:
                    _write_zipf_tokens(f, rng, cdf, table, per_doc, on_block)
            # Presence commits LAST, atomically: its existence implies the
            # doc files are complete — a torn generator run can never feed
            # the exactness check a bogus ground truth.
            _atomic_np_save(pres_p, presence)
        except BaseException:
            for p in [pres_p, *docs]:
                try:
                    p.unlink()
                except OSError:
                    pass
            raise
    presence = np.load(pres_p)
    assert presence.shape[1] == n_docs, "stale ground truth for this doc split"

    cfg = _zipf_cfg("zipf-ii-work", "zipf-ii-out", reduce_n=8)
    import shutil

    shutil.rmtree(cfg.work_dir, ignore_errors=True)
    t0 = time.perf_counter()
    res = run_job(cfg, [str(p) for p in docs], app=InvertedIndex())
    dt = time.perf_counter() - t0
    s = res.stats
    got = np.zeros((vocab, presence.shape[1]), dtype=bool)
    n_lines = 0
    for f in res.output_files:
        with open(f, "rb") as fh:
            for line in fh:
                w, v = line.rsplit(b" ", 1)
                got[int(w[1:], 16), [int(x) for x in v.split(b",")]] = True
                n_lines += 1
    exact = bool(np.array_equal(got, presence))
    print(json.dumps({
        "zipf_ii": {
            "bytes": s.bytes_in, "wall_s": round(dt, 3),
            "gbs": round(s.gb_per_s, 4), "platform": platform,
            "distinct_terms": n_lines,
            "expected_terms": int(presence.any(axis=1).sum()),
            "posting_pairs": int(presence.sum()), "docs": presence.shape[1],
            "exact": exact,
            "spills": s.spill_events, "spilled_keys": s.spilled_keys,
            "dict_words": s.dictionary_words,
        }
    }))
    if not exact:
        raise SystemExit(3)


def sort_leg(target_mb: int) -> None:
    """Runs in a subprocess (--sort): GLOBAL SORT over the Zipf corpus
    (range-partitioned via sampled splitters, ISSUE 15), budgets engaged.
    The output contract is TeraSort's: the concatenation of mr-{r}.txt in
    partition order must be EXACTLY sorted() of the corpus token multiset
    — verified against the generator's ground-truth counts plus a global
    order sweep (equal counts + non-decreasing sequence == the sorted
    multiset, no second sort needed). Prints one JSON detail line with
    wall, partition_bytes skew ratio and the splitter-sample overhead."""
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    from mapreduce_rust_tpu.apps import get_app
    from mapreduce_rust_tpu.runtime.driver import enable_compilation_cache, run_job

    enable_compilation_cache("auto")
    corpus, counts_p = build_zipf_corpus(target_mb)
    truth = np.load(counts_p)
    cfg = _zipf_cfg("sort-work", "sort-out", reduce_n=8)
    import shutil

    shutil.rmtree(cfg.work_dir, ignore_errors=True)
    shutil.rmtree(cfg.output_dir, ignore_errors=True)
    t0 = time.perf_counter()
    res = run_job(cfg, [str(corpus)], app=get_app("sort"))
    dt = time.perf_counter() - t0
    s = res.stats
    # Streamed oracle: every output line is the fixed-width token
    # 'w%06x' + newline (8 bytes), so each partition file parses as one
    # uint8 matrix and the hex ranks decode vectorized. Lexicographic
    # token order == numeric rank order (fixed-width hex), so the global
    # order check is one np.diff per file + the partition boundary carry.
    got = np.zeros(ZIPF_VOCAB, dtype=np.int64)
    ordered = True
    prev = -1
    lines = 0
    place = np.power(16, np.arange(5, -1, -1, dtype=np.int64))
    for f in res.output_files:  # run_job returns partition order
        data = pathlib.Path(f).read_bytes()
        if not data:
            continue
        arr = np.frombuffer(data, dtype=np.uint8).reshape(-1, 8)
        hexd = arr[:, 1:7].astype(np.int64)
        hexd = np.where(hexd >= ord("a"), hexd - (ord("a") - 10),
                        hexd - ord("0"))
        ranks = (hexd * place).sum(axis=1)
        if ranks[0] < prev or (len(ranks) > 1 and np.any(np.diff(ranks) < 0)):
            ordered = False
        prev = int(ranks[-1])
        got += np.bincount(ranks, minlength=ZIPF_VOCAB)
        lines += len(ranks)
    exact = bool(np.array_equal(got, truth)) and ordered
    pb = [b for b in s.partition_bytes]
    mean_pb = (sum(pb) / len(pb)) if pb else 0.0
    print(json.dumps({
        "sort": {
            "bytes": s.bytes_in, "wall_s": round(dt, 3),
            "platform": platform, "lines": lines,
            "ordered": ordered, "exact": exact,
            "distinct": s.distinct_keys,
            "partition_mode": s.partition_mode,
            "reduce_n": cfg.reduce_n,
            "partition_bytes": pb,
            # max/mean of realized per-partition output bytes: 1.0 =
            # ideal R-way split — THE splitter-quality number the doctor
            # scores and `doctor trend` watches (bad = up).
            "skew": round(max(pb) / mean_pb, 4) if pb and mean_pb else None,
            "splitter_samples": s.splitter_samples,
            "splitter_s": round(s.splitter_s, 4),
            "spills": s.spill_events,
            "dict_runs": s.dict_spill_runs,
            "bottleneck": s.bottleneck,
        }
    }))
    if not exact:
        raise SystemExit(3)


def sort_leg_main() -> None:
    """``bench.py --sort-leg``: the global-sort workload leg (ISSUE 15
    satellite) as its own harness — Zipf corpus, range partitioning via
    sampled splitters, outputs verified globally ordered AND oracle-exact
    vs the generator ground truth inside the subprocess leg. Appends one
    history row carrying sort_wall_s + sort_skew (both trend-watched,
    bad = up) and the splitter-sample overhead. Prints ONE JSON line;
    exit 1 when the leg failed or diverged."""
    mb = int(os.environ.get("BENCH_SORT_MB", "48"))
    res, err = _run_device_leg(
        pathlib.Path(str(mb)),
        int(os.environ.get("BENCH_SORT_TIMEOUT_S", "420")),
        _cpu_env(),  # the range-partition plane under test is host-side
        init_timeout_s=PROBE_TIMEOUT_S, mode="--sort",
    )
    det = (res or {}).get("sort")
    result: dict = {
        "metric": f"global sort over {mb}MB Zipf corpus "
                  "(range-partitioned, sampled splitters)",
        "unit": "s",
        "value": None,  # trend's GB/s series must never mix in sort walls
        "platform": (det or {}).get("platform", "none"),
        "sort_wall_s": (det or {}).get("wall_s"),
        "sort_skew": (det or {}).get("skew"),
        "sort_splitter_s": (det or {}).get("splitter_s"),
        "sort_splitter_samples": (det or {}).get("splitter_samples"),
        "sort_lines": (det or {}).get("lines"),
        "sort_exact": bool((det or {}).get("exact")),
    }
    if res is None:
        result["error"] = err
    _append_history(result)
    print(json.dumps(result))
    if det is None or not det.get("exact"):
        raise SystemExit(1)


def model_leg() -> None:
    """``bench.py --model-leg``: mrmodel exploration throughput (ISSUE
    18) — the lease and pipeline foci at a fixed budget/depth/seed, in
    process (the model checker is jax-free by contract). Appends one
    history row carrying model_schedules_per_s (trend-watched, bad =
    down: the exploration loop slowing down shrinks the schedule space a
    fixed CI budget actually covers) plus explored/pruned so a pruning
    regression (same budget, fewer pruned) is visible in the trajectory.
    Prints ONE JSON line; exit 1 when a focus finds a counterexample —
    a bench leg must never silently bless a broken control plane."""
    from mapreduce_rust_tpu.analysis.mrmodel import run_model

    budget = int(os.environ.get("BENCH_MODEL_BUDGET", "1500"))
    depth = int(os.environ.get("BENCH_MODEL_DEPTH", "12"))
    docs = {f: run_model(focus=f, budget=budget, depth=depth, seed=0)
            for f in ("lease", "pipeline")}
    explored = sum(d["explored"] for d in docs.values())
    elapsed = sum(d["elapsed_s"] for d in docs.values())
    ok = all(d["ok"] for d in docs.values())
    result: dict = {
        "metric": f"mrmodel exploration, lease+pipeline foci at "
                  f"budget {budget} depth {depth}",
        "unit": "schedules/s",
        "value": None,  # the GB/s trend series must never mix in these
        "platform": "cpu",
        "model_schedules_per_s": (round(explored / elapsed, 1)
                                  if elapsed > 0 else None),
        "model_explored": explored,
        "model_pruned": sum(d["pruned"] for d in docs.values()),
        "model_steps": sum(d["steps"] for d in docs.values()),
        "model_ok": ok,
        "model_counterexamples": [
            {"focus": f, "code": c["code"], "chaos_spec": c["chaos_spec"]}
            for f, d in docs.items() for c in d["counterexamples"]
        ],
    }
    _append_history(result)
    print(json.dumps(result))
    if not ok:
        raise SystemExit(1)


def micro_leg() -> None:
    """Runs in a subprocess (--micro): device micro-benchmarks that survive
    even when the end-to-end leg falls back — map-step ms/MB, h2d MB/s,
    merge ms (VERDICT r4 next-round 2). Heartbeat first: a device that
    never comes up kills this leg, not the bench."""
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)
    dev = jax.devices()[0]

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import enable_compilation_cache, make_step_fns
    from mapreduce_rust_tpu.apps.word_count import WordCount
    from mapreduce_rust_tpu.core.kv import KVBatch

    enable_compilation_cache("auto")
    cfg = Config(chunk_bytes=1 << 20)
    u_cap = cfg.effective_partial_capacity()
    map_combine, merge = make_step_fns(WordCount(), u_cap, platform == "tpu")

    seed_file = REF_DATA / "gut-4.txt"
    seed = seed_file.read_bytes() if seed_file.is_file() else b"a b c " * 200000
    chunk = np.frombuffer((seed * (cfg.chunk_bytes // len(seed) + 1))[: cfg.chunk_bytes], np.uint8)

    # h2d: one 64 MB transfer, timed end-to-end.
    big = np.zeros(64 << 20, dtype=np.uint8)
    jax.block_until_ready(jax.device_put(big, dev))  # warm path
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(big, dev))
    h2d_mbps = (64 << 20) / (time.perf_counter() - t0) / 1e6

    from mapreduce_rust_tpu.runtime.chunker import doc_table

    did = jax.device_put(doc_table(0), dev)
    chunk_dev = jax.device_put(chunk, dev)
    state = jax.device_put(KVBatch.empty(cfg.merge_capacity), dev)
    upd, _ = map_combine(chunk_dev, did)
    state, _ev, _n = merge(state, upd)
    jax.block_until_ready(state)

    def timed(n, fn):
        t0 = time.perf_counter()
        r = None
        for _ in range(n):
            r = fn()
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / n * 1e3

    map_ms = timed(10, lambda: map_combine(chunk_dev, did))

    def step():
        nonlocal state
        u, _ = map_combine(chunk_dev, did)
        state, _e, _c = merge(state, u)
        return state

    step_ms = timed(10, step)
    merge_ms = step_ms - map_ms
    mb = cfg.chunk_bytes / 1e6
    print(json.dumps({
        "micro": {
            "platform": platform,
            "h2d_MBps": round(h2d_mbps, 1),
            "map_combine_ms_per_mb": round(map_ms / mb, 2),
            "map_step_ms_per_mb": round(step_ms / mb, 2),
            "merge_ms": round(merge_ms, 2),
            "chunk_mb": mb,
            "merge_capacity": cfg.merge_capacity,
            "partial_capacity": u_cap,
        }
    }))


def metrics_overhead_leg(path: str) -> None:
    """Runs in a subprocess (--metrics-overhead): the sampler-tax pair
    (ISSUE 8). The SAME word_count run, metrics registry ON vs OFF,
    min-of-N per side with the sides interleaved (ON/OFF then OFF/ON)
    so warm-cache asymmetry and slow-boil machine drift hit both
    equally. Two contracts are measured, both acceptance criteria:

    - outputs bit-identical ON vs OFF — telemetry must never reach the
      data path (the sampler only READS aggregates; a registry that
      perturbed fold order would show here);
    - ``frac`` = (median_on - median_off) / median_off — the sampler is
      piggybacked on per-window/per-poll loops, so this should sit in
      measurement noise (≤ 2%). `doctor trend` watches the history series
      (metrics_overhead_frac, bad direction: up) for the slow-boil
      regression class a single noisy pair can't prove.
    """
    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    import dataclasses

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import (
        enable_compilation_cache,
        run_job,
    )

    enable_compilation_cache("auto")
    out_root = BENCH_DIR / "metrics-overhead"
    base = Config(
        map_engine="host",
        host_map_workers=_env_host_workers(),
        fold_shards=_env_fold_shards(),
        host_window_bytes=16 << 20,
        chunk_bytes=1 << 20,
        merge_capacity=1 << 17,
        reduce_n=4,
        output_dir=str(out_root / "out"),
        device="auto",
    )

    # Warmup compiles every jitted step; the persistent cache makes it
    # cheap after the first run on a machine image. Metrics OFF: the
    # warmup must not install a registry the measured runs then inherit.
    warm = BENCH_DIR / "warmup-overhead.txt"
    with open(path, "rb") as f:
        warm.write_bytes(f.read(base.host_window_bytes + 4096))
    run_job(dataclasses.replace(base, metrics_enabled=False),
            [str(warm)], write_outputs=False)

    def one(enabled: bool) -> tuple[float, float, dict]:
        side = "on" if enabled else "off"
        cfg = dataclasses.replace(
            base, metrics_enabled=enabled,
            output_dir=str(out_root / f"out-{side}"),
        )
        c0 = time.process_time()
        t0 = time.perf_counter()
        run_job(cfg, [str(path)])
        wall = time.perf_counter() - t0
        cpu = time.process_time() - c0
        outputs = {
            p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
        }
        return wall, cpu, outputs

    # Min-of-N estimator: identical back-to-back runs on this class of
    # shared host swing ±40% wall AND cpu (scheduler preemption, allocator
    # state, 10 ms process_time granularity) while the tax under test is
    # microseconds of tick work per window — any mean/median pair just
    # measures the noise. Scheduling noise is strictly ADDITIVE, so each
    # side's MINIMUM converges to its true cost and the min-vs-min frac is
    # the defensible number. Sides alternate (allocator/page-cache warmth
    # must not pool on one side); cpu_frac (process_time: every thread's
    # CPU seconds, no scheduler wait) rides beside the wall frac as the
    # jitter-immune cross-check. `doctor trend` watches the cross-round
    # series for the slow-boil drift a single round can't prove.
    # 15 short runs per side beat 5 long ones here: each ~0.3 s run is
    # likely to fit inside a quiet scheduler window, so the minima land
    # within ~1 ms of each other (measured: frac ≈ 0.002 on a host whose
    # identical back-to-back runs swing ±40%).
    repeats = 15
    walls: dict = {"on": [], "off": []}
    cpus: dict = {"on": [], "off": []}
    outputs: dict = {}
    identical = True
    for i in range(repeats):
        for enabled in ((True, False) if i % 2 == 0 else (False, True)):
            wall, cpu, out = one(enabled)
            side = "on" if enabled else "off"
            walls[side].append(wall)
            cpus[side].append(cpu)
            if not out:
                identical = False
            elif not outputs:
                outputs = out
            elif out != outputs:
                identical = False
    on_s, off_s = min(walls["on"]), min(walls["off"])
    frac = (on_s - off_s) / off_s if off_s > 0 else None
    cpu_on, cpu_off = min(cpus["on"]), min(cpus["off"])
    cpu_frac = (cpu_on - cpu_off) / cpu_off if cpu_off > 0 else None
    print(json.dumps({
        "metrics_overhead": {
            "platform": platform,
            "bytes": pathlib.Path(path).stat().st_size,
            "runs_per_side": repeats,
            "on_s": round(on_s, 4),
            "off_s": round(off_s, 4),
            "frac": round(frac, 5) if frac is not None else None,
            "cpu_frac": round(cpu_frac, 5) if cpu_frac is not None else None,
            "outputs_identical": identical,
        }
    }))


def profile_overhead_leg(path: str) -> None:
    """Runs in a subprocess (--profile-overhead): the sampler-tax pair
    for the ISSUE 19 profiler — the metrics_overhead_leg estimator
    verbatim (min-of-N, interleaved sides, bit-identical outputs gate),
    with ``Config.profile`` as the toggled knob. Metrics stay at their
    default on BOTH sides so the measured delta is the profiler alone:
    one thread waking at 97 Hz to walk sys._current_frames(). The
    acceptance bar is ≤ 2% wall; `doctor trend` watches the
    profile_overhead_frac history series (bad direction: up)."""
    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    import dataclasses

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import (
        enable_compilation_cache,
        run_job,
    )

    enable_compilation_cache("auto")
    out_root = BENCH_DIR / "profile-overhead"
    base = Config(
        map_engine="host",
        host_map_workers=_env_host_workers(),
        fold_shards=_env_fold_shards(),
        host_window_bytes=16 << 20,
        chunk_bytes=1 << 20,
        merge_capacity=1 << 17,
        reduce_n=4,
        output_dir=str(out_root / "out"),
        device="auto",
    )

    warm = BENCH_DIR / "warmup-overhead.txt"
    with open(path, "rb") as f:
        warm.write_bytes(f.read(base.host_window_bytes + 4096))
    run_job(dataclasses.replace(base, profile=False),
            [str(warm)], write_outputs=False)

    def one(enabled: bool) -> tuple[float, float, dict]:
        side = "on" if enabled else "off"
        cfg = dataclasses.replace(
            base, profile=enabled,
            output_dir=str(out_root / f"out-{side}"),
        )
        c0 = time.process_time()
        t0 = time.perf_counter()
        run_job(cfg, [str(path)])
        wall = time.perf_counter() - t0
        cpu = time.process_time() - c0
        outputs = {
            p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
        }
        return wall, cpu, outputs

    repeats = 15
    walls: dict = {"on": [], "off": []}
    cpus: dict = {"on": [], "off": []}
    outputs: dict = {}
    identical = True
    for i in range(repeats):
        for enabled in ((True, False) if i % 2 == 0 else (False, True)):
            wall, cpu, out = one(enabled)
            side = "on" if enabled else "off"
            walls[side].append(wall)
            cpus[side].append(cpu)
            if not out:
                identical = False
            elif not outputs:
                outputs = out
            elif out != outputs:
                identical = False
    on_s, off_s = min(walls["on"]), min(walls["off"])
    frac = (on_s - off_s) / off_s if off_s > 0 else None
    cpu_on, cpu_off = min(cpus["on"]), min(cpus["off"])
    cpu_frac = (cpu_on - cpu_off) / cpu_off if cpu_off > 0 else None
    print(json.dumps({
        "profile_overhead": {
            "platform": platform,
            "bytes": pathlib.Path(path).stat().st_size,
            "runs_per_side": repeats,
            "on_s": round(on_s, 4),
            "off_s": round(off_s, 4),
            "frac": round(frac, 5) if frac is not None else None,
            "cpu_frac": round(cpu_frac, 5) if cpu_frac is not None else None,
            "outputs_identical": identical,
        }
    }))


def lineage_overhead_leg(path: str) -> None:
    """Runs in a subprocess (--lineage-overhead): the ISSUE 20 provenance
    plane's two numbers in one leg.

    1. Ledger tax: the metrics/profile overhead estimator verbatim
       (min-of-N, interleaved sides, bit-identical outputs gate) with
       ``Config.lineage`` as the toggled knob — one blake2b per window in
       the scan thread plus one flushed jsonl line per chunk/partition.
       Acceptance bar ≤ 2% wall; `doctor trend` watches
       lineage_overhead_frac (bad: up).
    2. Blast radius: grow the corpus ~1% (a new file appended to the
       input list — the incremental-ingest shape ROADMAP item 4 memoizes),
       re-run with lineage on, diff the two ledgers. memo_hit_frac is the
       byte fraction a memo tier could skip (acceptance ≥ 0.95 — chunking
       must be stable for unchanged files); `doctor trend` watches
       lineage_memo_hit_frac (bad: down)."""
    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    import dataclasses

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import (
        enable_compilation_cache,
        run_job,
    )

    enable_compilation_cache("auto")
    out_root = BENCH_DIR / "lineage-overhead"
    base = Config(
        map_engine="host",
        host_map_workers=_env_host_workers(),
        fold_shards=_env_fold_shards(),
        host_window_bytes=16 << 20,
        chunk_bytes=1 << 20,
        merge_capacity=1 << 17,
        reduce_n=4,
        output_dir=str(out_root / "out"),
        device="auto",
    )

    warm = BENCH_DIR / "warmup-overhead.txt"
    with open(path, "rb") as f:
        warm.write_bytes(f.read(base.host_window_bytes + 4096))
    run_job(dataclasses.replace(base, lineage=False),
            [str(warm)], write_outputs=False)

    def one(enabled: bool) -> tuple[float, float, dict]:
        side = "on" if enabled else "off"
        cfg = dataclasses.replace(
            base, lineage=enabled,
            work_dir=str(out_root / f"work-{side}"),
            output_dir=str(out_root / f"out-{side}"),
        )
        c0 = time.process_time()
        t0 = time.perf_counter()
        run_job(cfg, [str(path)])
        wall = time.perf_counter() - t0
        cpu = time.process_time() - c0
        outputs = {
            p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
        }
        return wall, cpu, outputs

    repeats = 15
    walls: dict = {"on": [], "off": []}
    cpus: dict = {"on": [], "off": []}
    outputs: dict = {}
    identical = True
    for i in range(repeats):
        for enabled in ((True, False) if i % 2 == 0 else (False, True)):
            wall, cpu, out = one(enabled)
            side = "on" if enabled else "off"
            walls[side].append(wall)
            cpus[side].append(cpu)
            if not out:
                identical = False
            elif not outputs:
                outputs = out
            elif out != outputs:
                identical = False
    on_s, off_s = min(walls["on"]), min(walls["off"])
    frac = (on_s - off_s) / off_s if off_s > 0 else None
    cpu_on, cpu_off = min(cpus["on"]), min(cpus["off"])
    cpu_frac = (cpu_on - cpu_off) / cpu_off if cpu_off > 0 else None

    # Blast radius: +~1% new file (cut at whitespace so the tokenizer
    # sees whole words), ledgers diffed jax-free. The base-side ledger is
    # the pair loop's last ON run — same corpus, same window policy.
    blast: dict | None = None
    try:
        from mapreduce_rust_tpu.analysis import lineage as lin

        grow = pathlib.Path(path).stat().st_size // 100
        extra = out_root / "grown-extra.txt"
        with open(path, "rb") as f:
            f.seek(-min(grow + (1 << 16), f.seek(0, 2)), 2)
            tail = f.read()
        cut = next((i for i, b in enumerate(tail) if b in _WS), 0)
        extra.write_bytes(tail[cut:cut + grow])
        run_job(
            dataclasses.replace(
                base, lineage=True,
                work_dir=str(out_root / "work-grown"),
                output_dir=str(out_root / "out-grown"),
            ),
            [str(path), str(extra)],
        )
        d = lin.diff(lin.load_ledger(str(out_root / "work-on")),
                     lin.load_ledger(str(out_root / "work-grown")))
        blast = {
            "grown_bytes": extra.stat().st_size,
            "memo_hit_frac": round(d["memo_hit_frac"], 5),
            "changed_chunks": d["changed_chunks"],
            "affected_partition_frac": round(
                d["affected_partition_frac"], 5),
        }
    except Exception as e:
        blast = {"error": repr(e)}
    print(json.dumps({
        "lineage_overhead": {
            "platform": platform,
            "bytes": pathlib.Path(path).stat().st_size,
            "runs_per_side": repeats,
            "on_s": round(on_s, 4),
            "off_s": round(off_s, 4),
            "frac": round(frac, 5) if frac is not None else None,
            "cpu_frac": round(cpu_frac, 5) if cpu_frac is not None else None,
            "outputs_identical": identical,
            "blast_radius": blast,
        }
    }))


def _ws_aligned_slices(path: pathlib.Path, n: int, limit: int | None = None):
    """n byte ranges cut at whitespace (reading only boundary probes)."""
    size = min(path.stat().st_size, limit or (1 << 62))
    bounds = [0]
    with open(path, "rb") as f:
        for i in range(1, n):
            pos = size * i // n
            f.seek(pos)
            tail = f.read(1 << 16)
            off = next((j for j, b in enumerate(tail) if b in _WS), 0)
            bounds.append(pos + off)
    bounds.append(size)
    return [(int(a), int(b)) for a, b in zip(bounds, bounds[1:])]


def _map_task(args) -> int:
    """One map task with the reference's ARCHITECTURE (src/mr/worker.rs:
    142-155): read the slice, tokenize with reference semantics (regex
    strip + split, src/app/wc.rs:6-13), then route EVERY occurrence by
    hash(word) % reduce_n into per-(m, r) intermediate files — the
    file-plane shuffle that defines the reference (worker.rs:117-140).
    Deliberately GENEROUS vs the original: each partition file is written
    in one call instead of one awaited write + one println per KV pair
    (worker.rs:131-136)."""
    import re

    import zlib

    path, start, end, m, reduce_n, workdir = args
    with open(path, "rb") as f:
        f.seek(start)
        text = f.read(end - start).decode("utf-8", errors="replace")
    toks = re.sub(r"[^\w\s]", "", text, flags=re.UNICODE).split()
    bufs: list[list] = [[] for _ in range(reduce_n)]
    # Deterministic hash (builtin hash() is seed-randomized per process —
    # under a spawn start method each worker would route the same word to
    # a DIFFERENT partition and silently break the grouping invariant).
    for w in toks:  # per-KV hash + route, like worker.rs:127-137
        bufs[zlib.crc32(w.encode()) % reduce_n].append(w)
    for r, b in enumerate(bufs):
        with open(os.path.join(workdir, f"mr-{m}-{r}.txt"), "w",
                  encoding="utf-8") as f:
            if b:
                f.write(" 1\n".join(b))
                f.write(" 1\n")
    return len(toks)


def _reduce_task(args) -> collections.Counter:
    """One reduce task (worker.rs:157-193): read every map's partition-r
    file, parse the 'word 1' lines, group-count. Counter replaces the
    reference's full lexicographic sort + linear group scan
    (worker.rs:162-184) — again the generous choice."""
    r, map_n, workdir = args
    c: collections.Counter = collections.Counter()
    for m in range(map_n):
        with open(os.path.join(workdir, f"mr-{m}-{r}.txt"),
                  encoding="utf-8") as f:
            # rsplit, not a fixed-width slice: the reader must not depend
            # on the ' 1' suffix staying literally two characters wide.
            c.update(s.rsplit(" ", 1)[0] for s in f.read().splitlines())
    return c


def cpu_baseline_gbs(path: pathlib.Path, limit_bytes: int, workers: int = 8,
                     reduce_n: int = 4) -> float:
    """Multi-process reference-ARCHITECTURE word count, GB/s: map tasks
    hash-partition every token into mr-{m}-{r}.txt files, a phase barrier,
    then reduce tasks read them back and count — the reference's exact
    data movement (control via the pool, data via the filesystem), with
    batched IO and Counter reduce as generous simplifications."""
    import shutil

    workdir = str(BENCH_DIR / "baseline-shuffle")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    slices = _ws_aligned_slices(path, workers, limit_bytes)
    t0 = time.perf_counter()
    with multiprocessing.Pool(workers) as pool:
        n_tok = pool.map(
            _map_task,
            [(str(path), a, b, m, reduce_n, workdir)
             for m, (a, b) in enumerate(slices)],
        )
        # map→reduce phase barrier (the reference's get_reduce_task gate,
        # src/mr/coordinator.rs:183-185) is implicit in the two pool.maps.
        parts = pool.map(
            _reduce_task, [(r, len(slices), workdir) for r in range(reduce_n)]
        )
    dt = time.perf_counter() - t0
    total = sum(len(c) for c in parts)
    assert total > 0 and sum(n_tok) == sum(sum(c.values()) for c in parts)
    shutil.rmtree(workdir, ignore_errors=True)
    return limit_bytes / dt / 1e9


def device_leg(path: str) -> None:
    """Runs INSIDE the bench subprocess: full framework path, prints one
    JSON line {gbs, info} on stdout."""
    import jax

    # Heartbeat the parent waits on with a short deadline: backend init is
    # the only phase a healthy-but-cold device spends more than a few
    # seconds in before output appears. Printing it AFTER jax.devices()
    # means: heartbeat seen = init succeeded, run on; no heartbeat by the
    # deadline = hung, kill and fall back without burning the whole
    # DEVICE_TIMEOUT_S.
    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import enable_compilation_cache, run_job

    enable_compilation_cache("auto")
    # On the CPU fallback the XLA sort-merge runs on the same single core as
    # the scan, so the merge's static sort shape is the second-largest cost:
    # halve it (the corpus vocabulary is ~46K distinct, 2.8× headroom at
    # 2^17; overflow would spill exactly, not break) and double the window
    # so each merge amortizes over more bytes. TPU keeps the measured
    # config — its merges are on-chip and effectively free.
    on_cpu = platform == "cpu"
    cfg = Config(
        map_engine=os.environ.get("BENCH_MAP_ENGINE", "host"),
        host_map_workers=_env_host_workers(),
        fold_shards=_env_fold_shards(),
        host_window_bytes=(32 << 20) if on_cpu else (16 << 20),
        chunk_bytes=1 << 20,
        merge_capacity=(1 << 17) if on_cpu else (1 << 18),
        reduce_n=4,
        output_dir=str(BENCH_DIR / "out"),
        device="auto",
        # --trace/--manifest ride into this subprocess as env vars; the
        # measured run then emits the timeline + its own run manifest.
        trace_path=os.environ.get("BENCH_TRACE") or None,
        manifest_path=os.environ.get("BENCH_RUN_MANIFEST") or None,
    )
    # Warmup: compile every jitted step on a one-window prefix with the
    # same static shapes as the main run. The step-fn cache makes the main
    # run reuse these compiled closures; the persistent cache makes even
    # this pass cheap after the first run on a machine image. Telemetry is
    # stripped: a warmup-written run manifest at the same path could pass
    # the parent's freshness gate and be read as the MEASURED run's stats.
    import dataclasses

    warm = BENCH_DIR / "warmup.txt"
    with open(path, "rb") as f:
        warm.write_bytes(f.read(cfg.host_window_bytes + 4096))
    run_job(dataclasses.replace(cfg, trace_path=None, manifest_path=None),
            [str(warm)], write_outputs=False)

    res = run_job(cfg, [str(path)])
    s = res.stats
    info = {
        "bytes": s.bytes_in,
        "wall_s": round(s.wall_seconds, 3),
        "distinct": s.distinct_keys,
        "chunks": s.chunks,
        "spills": s.spill_events,
        "collisions": s.hash_collisions,
        "ingest_wait_s": round(s.ingest_wait_s, 3),
        "device_wait_s": round(s.device_wait_s, 3),
        "bottleneck": s.bottleneck,
        "host_map_s": round(s.host_map_s, 3),
        "host_glue_s": round(s.host_glue_s, 3),
        "host_workers": s.host_map_workers,
        "fold_shards": s.fold_shards,
        "fold_s": round(s.fold_s, 3),
        "fold_stall_s": round(s.fold_stall_s, 3),
        "scan_wait_s": round(s.scan_wait_s, 3),
        "map_engine": cfg.map_engine,
        "phases": {k: round(v, 3) for k, v in s.phase_seconds.items()},
        "platform": platform,
    }
    from mapreduce_rust_tpu.runtime.telemetry import stats_to_dict

    # The FULL JobStats rides back to the parent so the bench manifest
    # carries every counter (wait split, wire bytes), not the info subset.
    print(json.dumps({"gbs": s.gb_per_s, "info": info,
                      "stats": stats_to_dict(s)}))


def _partial_trace_note(child_env: dict) -> str:
    """Observability pointer for a failed/killed leg: the traced subprocess
    runs with the flight recorder armed (run_job does it whenever
    trace_path is set), so a timeout/SIGKILL leaves an atomic
    ``*.partial.json`` snapshot — name it in the error instead of making
    the operator rediscover it."""
    tp = child_env.get("BENCH_TRACE")
    if not tp:
        return ""
    from mapreduce_rust_tpu.runtime.trace import partial_path

    pp = partial_path(tp)
    if os.path.exists(pp):
        return (
            f"; flight recorder kept {pp} — stitch it with "
            f"`python -m mapreduce_rust_tpu trace merge merged.json {pp}`"
        )
    return ""


def _run_device_leg(corpus: pathlib.Path, timeout_s: int, env: dict | None,
                    init_timeout_s: int | None = None,
                    mode: str = "--device-leg"):
    """Launch a subprocess leg; return (parsed dict | None, error | None).

    env is the child's FULL environment (None = inherit ambient).
    init_timeout_s bounds time-to-heartbeat (BENCH_DEVICE_READY on stderr,
    printed right after jax.devices() in the child): a backend init that
    hangs has no timeout of its own, and without this deadline it would
    silently eat the whole timeout_s before the CPU fallback could start. A healthy-but-cold device only has to clear the
    init deadline, then gets the full timeout_s for the run itself —
    probing init in a separate throwaway process would instead pay backend
    init twice per run and forfeit slow-but-healthy devices entirely.
    """
    import threading

    child_env = dict(os.environ) if env is None else dict(env)
    run_manifest = None
    if mode == "--device-leg":
        # Every measured leg writes its own run manifest (full Config +
        # JobStats from inside the subprocess): the parent reads STATS from
        # that structured file, not from stdout-tail scraping — the stdout
        # JSON stays as the fallback channel for crashed/legacy legs.
        run_manifest = child_env.setdefault(
            "BENCH_RUN_MANIFEST", str(BENCH_DIR / "leg-run-manifest.json")
        )
    elif mode in ("--zipf", "--zipf-ii"):
        # The zipf legs write a manifest only when asked (the spill-budget
        # sweep) — a DIFFERENT env var, so they can never clobber the
        # measured device leg's manifest in the same bench run.
        run_manifest = child_env.get("BENCH_ZIPF_RUN_MANIFEST")
    t_start = time.time()
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "bench.py"), mode, str(corpus)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=child_env, cwd=str(REPO),
    )
    ready = threading.Event()
    err_chunks: list[str] = []
    out_chunks: list[str] = []

    # Both pipes are drained concurrently (a full, unread pipe would block
    # the child mid-write and masquerade as a timeout here).
    def _pump_err() -> None:
        for line in proc.stderr:
            err_chunks.append(line)
            if "BENCH_DEVICE_READY" in line:
                ready.set()

    def _pump_out() -> None:
        for line in proc.stdout:
            out_chunks.append(line)

    pumps = [
        threading.Thread(target=_pump_err, daemon=True),
        threading.Thread(target=_pump_out, daemon=True),
    ]
    for p in pumps:
        p.start()
    try:
        if init_timeout_s is not None:
            deadline = time.monotonic() + init_timeout_s
            # A child that EXITS before the heartbeat (import error, bad
            # path, instant backend abort) must be reported by its rc and
            # stderr tail, not mislabeled a wedge after the full deadline.
            while (
                not ready.is_set()
                and proc.poll() is None
                and time.monotonic() < deadline
            ):
                time.sleep(0.2)
            if not ready.is_set() and proc.poll() is None:
                return None, (
                    f"device backend init: no heartbeat within {init_timeout_s}s "
                    "(hung device backend?)"
                    + _partial_trace_note(child_env)
                )
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            return None, (
                f"device leg timed out after {timeout_s}s"
                + _partial_trace_note(child_env)
            )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        # The child is dead: its pipe ends are closed, so EOF is guaranteed
        # and the pumps finish once the (possibly multi-MB) residue drains.
        # The generous bound only guards a pathological descendant holding
        # the write end open.
        for p in pumps:
            p.join(timeout=30)
        sys.stderr.write("".join(err_chunks)[-4000:])
    out = "".join(out_chunks)
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                break
            if proc.returncode != 0:
                # A leg that printed its JSON but exited nonzero FAILED
                # (e.g. the zipf leg's exactness check exits 3) — the
                # designed failure signal must not be swallowed by a
                # successful parse.
                return None, f"{mode} rc={proc.returncode} (result {line[:200]})"
            m = _load_leg_manifest(run_manifest, t_start, proc.pid)
            if m is not None:
                # Structured channel won: the leg's own run manifest
                # carries the authoritative JobStats (incl. host_map_split
                # / ici_split) and phase times.
                parsed["stats"] = m["stats"]
                if m.get("phase_seconds") and "info" in parsed:
                    parsed["info"]["phases"] = {
                        k: round(v, 3) for k, v in m["phase_seconds"].items()
                    }
                parsed["run_manifest"] = run_manifest
                parsed["stats_source"] = "run_manifest"
            return parsed, None
    tail = ("".join(err_chunks) or out).strip().splitlines()
    return None, (
        f"device leg rc={proc.returncode}: {tail[-1] if tail else 'no output'}"
        + _partial_trace_note(child_env)
    )


def _load_leg_manifest(path, t_start: float, pid: int):
    """The leg's run manifest iff it is FRESH (written after this leg
    started) AND written by THIS leg's process — the manifest embeds the
    writer's pid (telemetry.platform_info), so a stale file from an
    earlier leg, a median repeat, or another run can never pass for this
    leg's stats even inside the mtime slack. None → caller keeps the
    stdout-parsed fallback (crashed legs never write a manifest)."""
    try:
        if path and os.path.getmtime(path) >= t_start - 1.0:
            with open(path) as f:
                m = json.load(f)
            if (
                m.get("kind") == "run_manifest"
                and m.get("stats")
                and m.get("platform", {}).get("pid") == pid
            ):
                return m
    except (OSError, ValueError):
        pass
    return None


def _parse_sweep_counts(spec: str, flag: str, typ=int) -> list:
    """Comma-separated sweep points. ``typ=float`` for fraction sweeps
    (--sweep-dispatch-fill) — those must land in (0, 1]; integer sweeps
    stay >= 1."""
    counts = []
    for tok in spec.split(","):
        tok = tok.strip()
        if tok:
            n = typ(tok)
            if (typ is int and n < 1) or (typ is float and not 0 < n <= 1):
                raise SystemExit(f"{flag}: bad count {n}")
            counts.append(n)
    if not counts:
        raise SystemExit(
            f"{flag} needs counts, e.g. "
            + ("0.25,0.5,0.9" if typ is float else "1,2,4")
        )
    return counts


def _run_sweep(counts: list, env_var: str, file_prefix: str, point_key: str,
               metric_label: str, manifest_cfg_key: str, point_stats,
               mode: str = "--device-leg", corpus=None,
               manifest_env: str = "BENCH_RUN_MANIFEST",
               gbs_of=None, timeout_s: "int | None" = None,
               corpus_label: "str | None" = None) -> None:
    """THE sweep harness (host-worker, fold-shard and spill-budget sweeps
    share it — one copy, so the anchoring policy / manifest schema cannot
    drift): one measured leg per count with `env_var` riding into the
    subprocess, each leg writing its own run manifest under .bench/sweep/
    (run-{prefix}{n}.json), so scaling curves come from structured files,
    not scraped logs. Prints ONE JSON line: the curve with per-point GB/s
    plus whatever `point_stats(stats_dict)` extracts, and the manifest
    path to diff (`python -m mapreduce_rust_tpu stats run-w1.json
    run-w4.json`). Non-default `mode` legs (the zipf spill sweep) plug in
    their own corpus argument, manifest env var and GB/s extractor."""
    if corpus is None:
        corpus = build_corpus(TARGET_MB)
    if gbs_of is None:
        gbs_of = lambda res: res.get("gbs")  # noqa: E731
    sweep_dir = BENCH_DIR / "sweep"
    sweep_dir.mkdir(parents=True, exist_ok=True)
    curve = []
    for n in counts:
        env = dict(os.environ)
        env[env_var] = str(n)
        env[manifest_env] = str(sweep_dir / f"run-{file_prefix}{n}.json")
        if env.get("BENCH_TRACE"):
            # Per-leg trace files: one shared --trace path would be
            # rewritten by every leg and end up holding only the last.
            env["BENCH_TRACE"] = str(sweep_dir / f"trace-{file_prefix}{n}.json")
        res, err = _run_device_leg(
            corpus, timeout_s or DEVICE_TIMEOUT_S, env,
            init_timeout_s=PROBE_TIMEOUT_S, mode=mode,
        )
        point: dict = {point_key: n, "manifest": env[manifest_env]}
        if res is None:
            point["error"] = err
        else:
            gbs = gbs_of(res)
            if gbs is not None:
                point["gbs"] = round(gbs, 4)
            point.update(point_stats(res.get("stats") or {}))
        curve.append(point)
        print(f"sweep {file_prefix}={n}: {json.dumps(point)}", file=sys.stderr)
    # Anchor strictly to the FIRST requested count: if that leg failed,
    # every speedup is null — a ratio against some other surviving count
    # would silently misstate the scaling claim the field names.
    base = curve[0].get("gbs")
    result = {
        "metric": f"word_count GB/s vs {metric_label} "
                  f"({corpus_label or f'{TARGET_MB}MB corpus'}, "
                  f"counts {counts})",
        "unit": "GB/s",
        "sweep": curve,
        "speedup_vs_first": [
            round(p["gbs"] / base, 2) if p.get("gbs") and base else None
            for p in curve
        ],
    }
    mp = os.environ.get("BENCH_MANIFEST")
    if mp:
        # --manifest in sweep mode: the curve itself is the run's result.
        try:
            from mapreduce_rust_tpu.runtime import telemetry

            telemetry.write_manifest(mp, telemetry.build_manifest(
                {manifest_cfg_key: counts, "target_mb": TARGET_MB},
                extra={"kind": "bench_sweep_manifest", "result": result},
            ))
            print(f"sweep manifest: {mp}", file=sys.stderr)
        except Exception as e:  # best-effort, like _write_bench_manifest
            print(f"sweep manifest write failed: {e!r}", file=sys.stderr)
    print(json.dumps(result))


def sweep_host_workers(spec: str) -> None:
    """`--sweep-host-workers 1,2,4`: the scan fan-out scaling curve, one
    run manifest per worker count (see _run_sweep)."""

    def point_stats(s: dict) -> dict:
        split = s.get("host_map_split") or {}
        return {
            "bottleneck": s.get("bottleneck"),
            "host_map_s": s.get("host_map_s"),
            "scan_wait_s": s.get("scan_wait_s"),
            "scan_parallelism": split.get("scan_parallelism"),
        }

    _run_sweep(
        _parse_sweep_counts(spec, "--sweep-host-workers"),
        "BENCH_HOST_WORKERS", "w", "workers", "host-map workers",
        "sweep_counts", point_stats,
    )


def sweep_fold_shards(spec: str) -> None:
    """`--sweep-fold-shards 1,2,4` (ISSUE 9 satellite): the egress-fold
    scaling curve, one run manifest per shard count (see _run_sweep)."""

    def point_stats(s: dict) -> dict:
        split = s.get("fold_split") or {}
        return {
            "bottleneck": s.get("bottleneck"),
            "host_glue_s": s.get("host_glue_s"),
            "fold_stall_s": s.get("fold_stall_s"),
            "fold_parallelism": split.get("fold_parallelism"),
            "fold_balance": split.get("balance"),
        }

    _run_sweep(
        _parse_sweep_counts(spec, "--sweep-fold-shards"),
        "BENCH_FOLD_SHARDS", "s", "fold_shards", "fold shards",
        "sweep_fold_shards", point_stats,
    )


def sweep_spill_budget(spec: str) -> None:
    """`--sweep-spill-budget 131072,262144,524288` (ISSUE 11 satellite):
    the spill-plane pressure curve — the ZIPF leg (budgets engaged,
    exactness vs generator ground truth) once per dictionary budget, the
    budget riding in as BENCH_SPILL_BUDGET_WORDS. Smaller budget = more,
    smaller runs = more writer handoffs and a wider egress fan-in; the
    per-point spill_split says whether the async writer still hides the
    disk (stall_s ~ 0) or the budget is past the knee (spill-bound)."""
    zipf_mb = int(os.environ.get("BENCH_ZIPF_MB", "256"))

    def point_stats(s: dict) -> dict:
        split = s.get("spill_split") or {}
        return {
            "bottleneck": s.get("bottleneck"),
            "wall_s": s.get("wall_seconds"),
            "spill_write_s": split.get("write_s"),
            "spill_stall_s": split.get("stall_s"),
            "dict_runs": split.get("dict_runs"),
            "merge_fanin": split.get("merge_fanin"),
        }

    _run_sweep(
        _parse_sweep_counts(spec, "--sweep-spill-budget"),
        "BENCH_SPILL_BUDGET_WORDS", "b", "budget_words",
        "dictionary spill budget (zipf leg)", "sweep_spill_budget",
        point_stats, mode="--zipf",
        corpus=pathlib.Path(str(zipf_mb)),
        manifest_env="BENCH_ZIPF_RUN_MANIFEST",
        gbs_of=lambda res: (res.get("zipf") or {}).get("gbs"),
        timeout_s=int(os.environ.get("BENCH_ZIPF_TIMEOUT_S", "420")),
        corpus_label=f"{zipf_mb}MB zipf corpus",
    )


def sweep_dispatch_fill(spec: str) -> None:
    """`--sweep-dispatch-fill 0.25,0.5,0.9` (ISSUE 13 satellite): the
    dispatch-plane coalescing curve — the ZIPF leg (budgets engaged,
    exactness vs generator ground truth) once per dispatch_fill_frac, the
    fraction riding in as BENCH_DISPATCH_FILL. Lower fill = more, emptier
    merges (less combine latency per dispatch); higher = fewer, fuller
    device hops. The per-point dispatch_split says where the knee is on
    this host."""
    zipf_mb = int(os.environ.get("BENCH_ZIPF_MB", "256"))

    def point_stats(s: dict) -> dict:
        split = s.get("dispatch_split") or {}
        return {
            "bottleneck": s.get("bottleneck"),
            "wall_s": s.get("wall_seconds"),
            "dispatch_s": split.get("dispatch_s"),
            "dispatch_stall_s": split.get("stall_s"),
            "merge_dispatches": split.get("dispatches"),
            "merge_fill_frac": split.get("fill_frac"),
        }

    _run_sweep(
        _parse_sweep_counts(spec, "--sweep-dispatch-fill", typ=float),
        "BENCH_DISPATCH_FILL", "f", "dispatch_fill_frac",
        "dispatch fill threshold (zipf leg)", "sweep_dispatch_fill",
        point_stats, mode="--zipf",
        corpus=pathlib.Path(str(zipf_mb)),
        manifest_env="BENCH_ZIPF_RUN_MANIFEST",
        gbs_of=lambda res: (res.get("zipf") or {}).get("gbs"),
        timeout_s=int(os.environ.get("BENCH_ZIPF_TIMEOUT_S", "420")),
        corpus_label=f"{zipf_mb}MB zipf corpus",
    )


def dispatch_ab_pair() -> None:
    """`--dispatch-ab` (ISSUE 13 acceptance): the Zipf spill leg with the
    FULL dispatch plane (async + cross-window coalescing) vs the PR 10
    path (sync inline dispatch, no coalescing), INTERLEAVED min-of-3 per
    side so machine drift hits both sides equally. One JSON line + one
    history row carrying both walls and the speedup — the end-to-end
    number the host-glue ROADMAP item is struck with. Exactness is
    enforced inside every leg (exit 3 on a ground-truth mismatch fails
    the pair loudly)."""
    zipf_mb = int(os.environ.get("BENCH_ZIPF_MB", "256"))
    repeats = int(os.environ.get("BENCH_DISPATCH_AB_REPEATS", "3"))
    timeout = int(os.environ.get("BENCH_ZIPF_TIMEOUT_S", "420"))
    sides: dict = {"plane": [], "pr10": []}
    errors: list[str] = []
    for r in range(repeats):
        for side in ("plane", "pr10"):  # interleaved: drift hits both
            env = _cpu_env()
            if side == "pr10":
                env["MR_DISPATCH_SYNC"] = "1"
                env["BENCH_DISPATCH_COALESCE"] = "0"
            res, err = _run_device_leg(
                pathlib.Path(str(zipf_mb)), timeout, env,
                init_timeout_s=PROBE_TIMEOUT_S, mode="--zipf",
            )
            if res is None:
                errors.append(f"{side}[{r}]: {err}")
                continue
            sides[side].append(res.get("zipf") or {})
            print(f"dispatch-ab {side}[{r}]: "
                  f"wall={sides[side][-1].get('wall_s')}s",
                  file=sys.stderr)

    def best(rows: list) -> dict | None:
        rows = [r for r in rows if r.get("wall_s")]
        return min(rows, key=lambda r: r["wall_s"]) if rows else None

    a, b = best(sides["plane"]), best(sides["pr10"])
    speedup = (
        round(b["wall_s"] / a["wall_s"], 3) if a and b else None
    )
    pick = lambda r: None if r is None else {  # noqa: E731
        k: r.get(k) for k in (
            "wall_s", "gbs", "bottleneck", "dispatch_mode", "dispatch_s",
            "dispatch_stall_s", "merge_dispatches", "merge_fill_frac",
            "spill_stall_s",
        )
    }
    result = {
        "metric": f"zipf dispatch-plane A/B ({zipf_mb}MB, async+coalesce "
                  f"vs sync uncoalesced, interleaved min-of-{repeats})",
        "unit": "x",
        "value": speedup,
        "plane": pick(a),
        "pr10": pick(b),
        "platform": "cpu",
    }
    if errors:
        result["error"] = "; ".join(errors)
    _append_history({
        "metric": result["metric"],
        "value": speedup,
        "unit": "x",
        "platform": "cpu",
        "zipf_wall_s": (a or {}).get("wall_s"),
        "zipf_gbs": (a or {}).get("gbs"),
        "merge_dispatches": (a or {}).get("merge_dispatches"),
        "merge_fill_frac": (a or {}).get("merge_fill_frac"),
        "dispatch_mode": (a or {}).get("dispatch_mode"),
        "dispatch_ab": {"plane": pick(a), "pr10": pick(b)},
        "had_errors": bool(errors),
    })
    print(json.dumps(result))
    if a is None or b is None:
        raise SystemExit(1)


def slow_dispatch_leg(path: str) -> None:
    """Runs in a subprocess (--slow-dispatch-leg): the ISSUE 13 chaos
    pair — the SAME word-count job under a seeded per-merge-dispatch
    delay (`slow_dispatch`), async dispatch plane vs the inline sync
    path. The async side overlaps the delayed device hops with the scans
    feeding it (stall only when the depth-bounded queue fills); the sync
    side eats every delay on the router's wall. Outputs must stay
    bit-identical — the overlap is a scheduling change, never a data
    change."""
    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    import dataclasses
    import shutil

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import (
        dispatch_chaos_fired,
        enable_compilation_cache,
        run_job,
    )

    enable_compilation_cache("auto")
    # Seeded p= sampling keeps the TOTAL injected delay below the
    # router-side pipeline's capacity to hide it — a delay on every
    # dispatch would just serialize both sides behind the sleep and the
    # pair would measure nothing but the injection. High-cardinality
    # corpus: the router's dictionary fold is the real work the async
    # plane overlaps the delayed hops with (the gut corpus's tiny
    # vocabulary leaves the router nearly idle, and a 2-core box then
    # shows no difference to hide).
    spec = os.environ.get("BENCH_SLOW_DISPATCH_SPEC",
                          "seed=7;slow_dispatch:0.01")
    # Rate-matched injection: a small delay on EVERY dispatch (the
    # per-window router interval is ~25 ms here) pipelines through the
    # depth-bounded queue, so the async side hides nearly the whole
    # injected total behind the router's fold — measured 1.7 s hidden on
    # this image at 48 MB. Few-but-large delays DON'T demonstrate this
    # (the bounded queue caps run-ahead per sleep episode).
    corpus, _counts = build_zipf_corpus(
        int(os.environ.get("BENCH_SLOW_DISPATCH_MB", "48"))
    )
    path = str(corpus)
    root = BENCH_DIR / "slow-dispatch"
    base = Config(
        map_engine="host",
        # Small windows: many dispatches (one per window uncoalesced), so
        # the seeded delay fires a steady stream the async plane must
        # hide. Coalescing stays ON — the delay fires per DISPATCH, and
        # both sides coalesce identically, so the pair isolates the
        # overlap, not the coalesce factor.
        # Small windows + an engaged dictionary budget: the router has
        # real per-window work of its own (fold + flush freezes) for the
        # async plane to overlap the delayed hops WITH — the hidden_s
        # margin is the router-side pipeline, so give it one.
        host_window_bytes=256 << 10,
        chunk_bytes=1 << 20,
        merge_capacity=1 << 14,          # constant device eviction = compute
        host_update_cap=1 << 13,         # small cap: the staging buffer
        # crosses its fill threshold once or more per window, so the
        # seeded delay fires a dispatch-rate stream on both sides
        dictionary_budget_words=4096,    # router-side fold + flush churn
        host_accum_budget_mb=64,
        reduce_n=4,
        device="auto",
        work_dir=str(root / "work"),
        output_dir=str(root / "out"),
    )
    # Chaos-free warmup compiles every step shape so neither measured side
    # pays XLA time (the persistent cache makes this cheap when warm).
    shutil.rmtree(root, ignore_errors=True)
    warm = BENCH_DIR / "warmup-slowdispatch.txt"
    with open(path, "rb") as f:
        warm.write_bytes(f.read(base.host_window_bytes + 4096))
    run_job(dataclasses.replace(
        base, work_dir=str(root / "warm-work"),
        output_dir=str(root / "warm-out"),
        # Budgets off: warmup exists for the XLA compiles only, and a
        # budgeted run demands write_outputs (streaming egress).
        dictionary_budget_words=None, host_accum_budget_mb=None,
    ), [str(warm)], write_outputs=False)

    os.environ["MR_CHAOS"] = spec
    sides: dict = {}
    outputs: dict = {}
    for side, async_dispatch in (("async", True), ("sync", False)):
        cfg = dataclasses.replace(
            base, dispatch_async=async_dispatch,
            work_dir=str(root / f"work-{side}"),
            output_dir=str(root / f"out-{side}"),
        )
        t0 = time.perf_counter()
        res = run_job(cfg, [str(path)])
        wall = time.perf_counter() - t0
        s = res.stats
        sides[side] = {
            "wall_s": round(wall, 3),
            "dispatch_s": round(s.dispatch_s, 3),
            "dispatch_stall_s": round(s.dispatch_stall_s, 3),
            "merge_dispatches": s.merge_dispatches,
            "glue_s": round(s.host_glue_s, 3),
        }
        outputs[side] = {
            p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
        }
    fired = len(dispatch_chaos_fired(spec))
    identical = bool(outputs["async"]) and outputs["async"] == outputs["sync"]
    hidden = round(sides["sync"]["wall_s"] - sides["async"]["wall_s"], 3)
    print(json.dumps({
        "slow_dispatch": {
            "platform": platform,
            "spec": spec,
            "fired": fired,
            "async": sides["async"],
            "sync": sides["sync"],
            "hidden_s": hidden,
            "outputs_identical": identical,
        }
    }))
    if not identical or fired == 0:
        raise SystemExit(3)


def slow_disk_leg(path: str) -> None:
    """Runs in a subprocess (--slow-disk-leg): the ISSUE 11 chaos pair —
    the SAME budgeted word-count job under a seeded per-spill-run write
    delay (`slow_disk`), async writer vs the legacy sync plane. The async
    side overlaps the delayed writes with scan/merge compute (stall only
    when the depth-2 buffer fills); the sync side eats every delay on the
    fold thread's wall. Outputs must stay bit-identical — the overlap is
    a scheduling change, never a data change."""
    import jax

    platform = jax.devices()[0].platform
    print(f"BENCH_DEVICE_READY {platform}", file=sys.stderr, flush=True)

    import dataclasses
    import shutil

    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import (
        enable_compilation_cache,
        run_job,
    )
    from mapreduce_rust_tpu.runtime.spill import chaos_fired

    enable_compilation_cache("auto")
    spec = os.environ.get("BENCH_SLOW_DISK_SPEC", "seed=6;slow_disk:0.25")
    root = BENCH_DIR / "slow-disk"
    base = Config(
        map_engine="host",
        # Small windows: a batch flush fires at most once per window, so
        # window count bounds run count — ~24 windows over the 24 MB gut
        # corpus keeps a steady stream of delayed writes to hide.
        host_window_bytes=1 << 20,
        chunk_bytes=1 << 20,
        merge_capacity=1 << 14,          # constant device eviction = compute
        dictionary_budget_words=1024,    # every new-vocab window flushes
        host_accum_budget_mb=64,
        reduce_n=4,
        device="auto",
        work_dir=str(root / "work"),
        output_dir=str(root / "out"),
    )
    # Chaos-free warmup compiles every step shape so neither measured side
    # pays XLA time (the persistent cache makes this cheap when warm).
    shutil.rmtree(root, ignore_errors=True)
    warm = BENCH_DIR / "warmup-slowdisk.txt"
    with open(path, "rb") as f:
        warm.write_bytes(f.read(base.host_window_bytes + 4096))
    run_job(dataclasses.replace(
        base, work_dir=str(root / "warm-work"),
        output_dir=str(root / "warm-out"),
        # Budgets off: warmup exists for the XLA compiles only, and a
        # budgeted run demands write_outputs (streaming egress).
        dictionary_budget_words=None, host_accum_budget_mb=None,
    ), [str(warm)], write_outputs=False)

    os.environ["MR_CHAOS"] = spec
    sides: dict = {}
    outputs: dict = {}
    for side, async_spill in (("async", True), ("sync", False)):
        cfg = dataclasses.replace(
            base, spill_async=async_spill,
            work_dir=str(root / f"work-{side}"),
            output_dir=str(root / f"out-{side}"),
        )
        t0 = time.perf_counter()
        res = run_job(cfg, [str(path)])
        wall = time.perf_counter() - t0
        s = res.stats
        sides[side] = {
            "wall_s": round(wall, 3),
            "spill_write_s": round(s.spill_s, 3),
            "spill_stall_s": round(s.spill_stall_s, 3),
            "runs": s.dict_spill_runs + s.accum_spill_runs,
        }
        outputs[side] = {
            p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
        }
    fired = len(chaos_fired(spec))
    identical = bool(outputs["async"]) and outputs["async"] == outputs["sync"]
    hidden = round(sides["sync"]["wall_s"] - sides["async"]["wall_s"], 3)
    print(json.dumps({
        "slow_disk": {
            "platform": platform,
            "spec": spec,
            "fired": fired,
            "async": sides["async"],
            "sync": sides["sync"],
            "hidden_s": hidden,
            "outputs_identical": identical,
        }
    }))
    if not identical or fired == 0:
        raise SystemExit(3)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_CHAOS_TEXTS = [
    b"the quick brown fox jumps over the lazy dog " * 400,
    b"pack my box with five dozen liquor jugs " * 400,
    b"sphinx of black quartz judge my vow " * 400,
]


def _chaos_cluster(name: str, work_root: pathlib.Path, chaos_spec: str | None,
                   speculate: bool, timeout_s: int = 120,
                   trace: bool = False, app: str = "word_count",
                   sched: str = "fifo") -> dict:
    """One chaos leg: coordinator + 2 worker OS processes over TCP (the
    REAL binaries — the recovery paths under test live in the real
    renewal/report loops, not a harness reimplementation). Faults ride in
    as MR_CHAOS on BOTH workers: the seeded spec targets (phase, tid, wid),
    so which OS process draws which task stays irrelevant. Returns wall
    time (coordinator exit = job complete), output bytes, the leg dir
    ("dir": job_report.json and trace files live under it), and the
    coordinator manifest path for the doctor. SHARED with the chaos test
    suite (tests/test_chaos.py drives this same harness), so the benched
    cluster and the tested cluster can never drift apart."""
    leg = work_root / name
    docs = leg / "in"
    docs.mkdir(parents=True)
    for i, t in enumerate(_CHAOS_TEXTS):
        (docs / f"doc-{i}.txt").write_bytes(t)
    port = _free_port()
    manifest = leg / "manifest.json"
    common = [
        "--input", str(docs), "--output", str(leg / "out"),
        "--work", str(leg / "work"), "--port", str(port), "--reduce-n", "3",
        "--app", app,  # word_count default; the sort kill leg (ISSUE 15)
        # runs the range-partitioned app through the SAME cluster harness
        "--lease-timeout", "2.0", "--lease-check-period", "0.3",
        "--renew-period", "0.3", "--poll-retry", "0.05",
    ]
    if sched != "fifo":
        # --sched rides in `common` so the coordinator and BOTH workers
        # agree on the mode (a pipelined worker against a FIFO
        # coordinator would just see NOT_READY, but measuring that
        # mismatch is not the point of any leg).
        common += ["--sched", sched]
    if trace:
        common += ["--trace", str(leg / "trace.json")]
    coord_args = ["--worker-n", "2", "--manifest", str(manifest), *common]
    if speculate:
        coord_args += ["--speculate", "--speculate-after-frac", "0.5"]
    env = _cpu_env()  # control-plane recovery needs no accelerator
    env["PYTHONPATH"] = str(REPO)
    worker_env = dict(env)
    if chaos_spec:
        worker_env["MR_CHAOS"] = chaos_spec
    t0 = time.perf_counter()
    coord = subprocess.Popen(
        [sys.executable, "-m", "mapreduce_rust_tpu", "coordinator", *coord_args],
        env=env, cwd=str(REPO), stderr=subprocess.DEVNULL,
    )
    workers = [
        subprocess.Popen(
            [sys.executable, "-m", "mapreduce_rust_tpu", "worker",
             "--engine", "host", *common],
            env=worker_env, cwd=str(REPO), stderr=subprocess.DEVNULL,
        )
        for _ in range(2)
    ]
    result: dict = {"scenario": name, "speculate": speculate, "sched": sched}
    try:
        rc = coord.wait(timeout=timeout_s)
        result["wall_s"] = round(time.perf_counter() - t0, 3)
        result["recovered"] = rc == 0
        for w in workers:
            try:
                w.wait(timeout=30)
            except subprocess.TimeoutExpired:
                w.kill()
                result["recovered"] = False
    except subprocess.TimeoutExpired:
        result["recovered"] = False
        result["error"] = f"coordinator did not finish within {timeout_s}s"
    finally:
        for p in [coord, *workers]:
            if p.poll() is None:
                p.kill()
                p.wait()
    result["outputs"] = {
        p.name: p.read_bytes()
        for p in sorted((leg / "out").glob("mr-*.txt"))
    }
    # The coordinator writes its manifest under the per-process name
    # (manifest-coord.json): co-hosted processes never clobber each other.
    from mapreduce_rust_tpu.runtime.trace import per_process_path

    coord_manifest = pathlib.Path(per_process_path(str(manifest), "coord"))
    if coord_manifest.exists():
        result["manifest"] = str(coord_manifest)
    result["dir"] = str(leg)
    return result


def chaos_legs() -> None:
    """``bench.py --chaos``: the seeded fault-injection matrix
    (analysis/chaos.SCENARIOS) over the real control plane. Each scenario
    measures recovery cost (wall vs the fault-free baseline), checks the
    outputs stay BIT-IDENTICAL to the fault-free run, runs the doctor on
    the coordinator manifest, and appends a line to .bench/history.jsonl.
    The slow_scan scenario runs twice — speculation OFF then ON — so the
    history carries the measured speculation win. Prints ONE JSON line;
    exits 1 if any scenario failed to recover or diverged."""
    import shutil

    from mapreduce_rust_tpu.analysis.chaos import SCENARIOS
    from mapreduce_rust_tpu.analysis.doctor import diagnose
    from mapreduce_rust_tpu.analysis.mrcheck import run_check
    from mapreduce_rust_tpu.runtime.telemetry import load_manifest

    work_root = BENCH_DIR / "chaos"
    shutil.rmtree(work_root, ignore_errors=True)
    legs: list[tuple[str, str | None, bool, str]] = [
        ("baseline", None, False, "fifo"),
    ]
    for name, spec in SCENARIOS.items():
        if name == "slow_scan":
            legs.append(("slow_scan-nospec", spec, False, "fifo"))
            legs.append(("slow_scan-spec", spec, True, "fifo"))
        else:
            legs.append((name, spec, False, "fifo"))
    # Pipelined pair (ISSUE 17 satellite): the same cluster under
    # --sched pipeline, fault-free and with the seeded kill:map SIGKILL.
    # Per-partition reduce release must survive a mid-map re-execution
    # (readiness retracted on lease expiry, re-established by the rerun)
    # and both legs must stay bit-identical to the fault-free FIFO
    # baseline — which also proves fifo-vs-pipeline output identity and,
    # by transitivity, identity with the FIFO kill leg above.
    legs.append(("baseline-pipeline", None, False, "pipeline"))
    legs.append(("kill-pipeline", SCENARIOS["kill"], False, "pipeline"))
    baseline_outputs = None
    baseline_wall = None
    rows = []
    ok = True
    for name, spec, speculate, sched in legs:
        r = _chaos_cluster(name, work_root, spec, speculate, sched=sched)
        outputs = r.pop("outputs")
        if name == "baseline":
            baseline_outputs, baseline_wall = outputs, r.get("wall_s")
            r["bit_identical"] = True
        else:
            r["bit_identical"] = outputs == baseline_outputs
            if baseline_wall is not None and r.get("wall_s") is not None:
                r["recovery_cost_s"] = round(r["wall_s"] - baseline_wall, 3)
        if r.get("manifest"):
            try:
                diag = diagnose(load_manifest(r["manifest"]))
                r["doctor"] = {
                    "findings": [
                        f"[{f['severity']}] {f['code']}: {f['message']}"
                        for f in (diag.get("findings") or [])[:6]
                    ],
                    "speculation": diag.get("speculation"),
                }
            except Exception as e:
                r["doctor"] = {"error": repr(e)}
        # mrcheck on the leg's control-plane artifacts (journal +
        # job_report under work/): the matrix's real oracle — "bytes
        # matched" says nothing about a double-granted lease or a report
        # accepted after revoke, and a violation fails the leg LOUDLY
        # even when the output happened to come out right (ISSUE 7).
        try:
            cdoc = run_check(str(pathlib.Path(r["dir"]) / "work"))
            r["mrcheck"] = {
                "ok": cdoc["ok"],
                "violations": [
                    f"[{v['code']}] {v['message']}"
                    for v in cdoc["violations"][:6]
                ],
            }
            if not cdoc["ok"]:
                ok = False
        except Exception as e:  # an uncheckable leg is a failed leg: the
            ok = False          # oracle must never silently not run
            r["mrcheck"] = {"ok": False, "error": repr(e)}
        ok = ok and r.get("recovered", False) and r["bit_identical"]
        rows.append(r)
        print(f"chaos {name}: wall={r.get('wall_s')}s recovered="
              f"{r.get('recovered')} identical={r['bit_identical']} "
              f"mrcheck={'ok' if r['mrcheck']['ok'] else 'VIOLATION'}",
              file=sys.stderr)
        _append_history({
            "metric": f"chaos recovery ({name})",
            "value": None,  # chaos rows must not pollute the trend series
            "unit": "s",
            "platform": "cpu",
            "doctor": r.get("doctor"),
            "chaos_scenario": name,
            "chaos_wall_s": r.get("wall_s"),
            "chaos_recovery_cost_s": r.get("recovery_cost_s"),
            "chaos_bit_identical": r["bit_identical"],
            "chaos_speculate": speculate,
            "chaos_sched": sched,
            "chaos_mrcheck": r["mrcheck"],
        })
    # Slow-disk pair (ISSUE 11 satellite): the seeded per-spill write
    # delay against a BUDGETED driver job, async writer vs the sync
    # plane — the matrix's cluster legs run unbudgeted, so the proof that
    # the async writer HIDES the delay needs its own leg. Exit 3 in the
    # leg = outputs diverged or the fault never fired; either fails here.
    slow_disk = None
    try:
        sd_corpus = build_corpus(min(TARGET_MB, 24))
        sd_res, sd_err = _run_device_leg(
            sd_corpus, int(os.environ.get("BENCH_SLOW_DISK_TIMEOUT_S", "300")),
            _cpu_env(), init_timeout_s=PROBE_TIMEOUT_S, mode="--slow-disk-leg",
        )
        if sd_res is None:
            ok = False
            slow_disk = {"error": sd_err}
        else:
            slow_disk = sd_res.get("slow_disk")
            hidden = (slow_disk or {}).get("hidden_s")
            if not (slow_disk or {}).get("outputs_identical") \
                    or hidden is None or hidden <= 0:
                ok = False  # the async writer must measurably hide the
                # injected delay the sync plane eats on its wall
        print(f"chaos slow_disk pair: {json.dumps(slow_disk)}",
              file=sys.stderr)
        _append_history({
            "metric": "chaos slow_disk: async-vs-sync spill pair",
            "value": None,  # chaos rows stay out of the trend series
            "unit": "s",
            "platform": "cpu",
            "chaos_scenario": "slow_disk-pair",
            "chaos_slow_disk": slow_disk,
        })
    except Exception as e:
        ok = False
        slow_disk = {"error": repr(e)}
    # Slow-dispatch pair (ISSUE 13 satellite): the seeded per-merge-
    # dispatch delay against a real window stream, async dispatch plane
    # vs the inline sync path — the proof the plane HIDES the device hop
    # needs its own leg exactly like slow_disk's. Exit 3 in the leg =
    # outputs diverged or the fault never fired; either fails here.
    slow_dispatch = None
    try:
        # The leg builds (and caches) its own high-cardinality zipf
        # corpus — the argument is unused (kept for the shared runner's
        # argv shape).
        sd2_res, sd2_err = _run_device_leg(
            pathlib.Path("zipf-slow-dispatch"),
            int(os.environ.get("BENCH_SLOW_DISPATCH_TIMEOUT_S", "300")),
            _cpu_env(), init_timeout_s=PROBE_TIMEOUT_S,
            mode="--slow-dispatch-leg",
        )
        if sd2_res is None:
            ok = False
            slow_dispatch = {"error": sd2_err}
        else:
            slow_dispatch = sd2_res.get("slow_dispatch")
            hidden = (slow_dispatch or {}).get("hidden_s")
            if not (slow_dispatch or {}).get("outputs_identical") \
                    or hidden is None or hidden <= 0:
                ok = False  # the dispatch thread must measurably hide the
                # injected delay the sync path eats on its wall
        print(f"chaos slow_dispatch pair: {json.dumps(slow_dispatch)}",
              file=sys.stderr)
        _append_history({
            "metric": "chaos slow_dispatch: async-vs-sync dispatch pair",
            "value": None,  # chaos rows stay out of the trend series
            "unit": "s",
            "platform": "cpu",
            "chaos_scenario": "slow_dispatch-pair",
            "chaos_slow_dispatch": slow_dispatch,
        })
    except Exception as e:
        ok = False
        slow_dispatch = {"error": repr(e)}
    nospec = next((r for r in rows if r["scenario"] == "slow_scan-nospec"), None)
    spec = next((r for r in rows if r["scenario"] == "slow_scan-spec"), None)
    result = {
        "metric": "chaos matrix: seeded fault recovery, wall seconds per "
                  "scenario (coordinator+2 workers, host engine, cpu)",
        "unit": "s",
        "ok": ok,
        "baseline_wall_s": baseline_wall,
        "scenarios": rows,
        "slow_disk_pair": slow_disk,
        "slow_dispatch_pair": slow_dispatch,
        "speculation_speedup": (
            round(nospec["wall_s"] / spec["wall_s"], 2)
            if nospec and spec and nospec.get("wall_s") and spec.get("wall_s")
            else None
        ),
    }
    print(json.dumps(result))
    if not ok:
        raise SystemExit(1)


def _service_run(k_jobs: int, sched: str, root: pathlib.Path,
                 docs_n: int = 3, scale: int = 1) -> dict:
    """One service cluster run over the mixed two-wave matrix: one
    OS-process service + 2 service workers under ``--sched {sched}``; a
    stream of K mixed submissions (three distinct (app, corpus) triples
    cycled, so repeats past the first cycle are cache hits) drives the
    admission queue. Measures jobs/minute, queue-wait p95 and the cache
    hit rate; mrcheck runs over the service work root (every job's
    journal + report) and a violation fails the run loudly, the --chaos
    doctrine; the fleet profiler (ISSUE 16) adds the bubble fraction and
    pipelining opportunity. Returns the result dict WITHOUT printing or
    touching history — shared by --service-leg (one run) and --sched-ab
    (the ISSUE 17 fifo-vs-pipeline pair)."""
    import asyncio
    import shutil

    from mapreduce_rust_tpu.analysis.mrcheck import run_check
    from mapreduce_rust_tpu.runtime.histogram import Histogram

    shutil.rmtree(root, ignore_errors=True)
    corpora = []
    for ci in range(3):
        d = root / f"corpus-{ci}"
        d.mkdir(parents=True)
        # ``docs_n``/``scale`` size the per-job map wave and per-task
        # weight: the default is the historical tiny matrix (trend-series
        # continuity); the sched A/B needs real phase windows or the
        # scheduling delta drowns in process startup.
        for i in range(max(3, docs_n)):
            t = _CHAOS_TEXTS[i % len(_CHAOS_TEXTS)] * max(1, scale)
            # Distinct corpora (distinct digests): a per-corpus marker
            # token repeated ci+1 times; a per-doc token keeps repeated
            # texts from collapsing into identical files.
            (d / f"doc-{i}.txt").write_bytes(
                t + f"doc{i} ".encode()
                + (f"corpusmark{ci} " * (ci + 1)).encode()
            )
        corpora.append(str(d))
    # The mixed stream: three distinct (app, corpus, config) triples —
    # every submission past the first cycle is an exact repeat and must
    # hit the result cache.
    triples = [
        {"app": "word_count", "input_dir": corpora[0], "reduce_n": 3},
        {"app": "inverted_index", "input_dir": corpora[1], "reduce_n": 2},
        {"app": "word_count", "input_dir": corpora[2], "reduce_n": 3},
    ]
    port = _free_port()
    env = _cpu_env()
    env["PYTHONPATH"] = str(REPO)
    common = [
        "--input", corpora[0], "--output", str(root / "out"),
        "--work", str(root / "work"), "--port", str(port),
        "--lease-timeout", "5.0", "--lease-check-period", "0.3",
        "--renew-period", "0.3", "--poll-retry", "0.05",
        # Scheduling mode rides `common` so the service AND its workers
        # agree; per-job coordinators inherit it through _job_cfg.
        "--sched", sched,
    ]
    svc = subprocess.Popen(
        [sys.executable, "-m", "mapreduce_rust_tpu", "service",
         "--max-jobs", "3", *common],
        env=env, cwd=str(REPO), stderr=subprocess.DEVNULL,
    )
    workers = [
        subprocess.Popen(
            [sys.executable, "-m", "mapreduce_rust_tpu", "worker",
             "--service", "--engine", "host", *common],
            env=env, cwd=str(REPO), stderr=subprocess.DEVNULL,
        )
        for _ in range(2)
    ]
    result: dict = {
        "metric": "job service: K mixed submissions, jobs/minute "
                  "(service+2 workers, host engine, cpu)",
        "unit": "jobs/min", "k_jobs": k_jobs, "sched": sched,
    }
    ok = True
    try:
        async def drive() -> dict:
            from mapreduce_rust_tpu.coordinator.server import (
                CoordinatorClient,
            )

            client = CoordinatorClient("127.0.0.1", port, timeout_s=15.0)
            await client.connect(retries=100, delay=0.1, budget_s=20.0)
            deadline = time.perf_counter() + int(
                os.environ.get("BENCH_SERVICE_TIMEOUT_S", "300")
            )
            jids: list = []
            states: dict = {}

            async def submit(spec) -> None:
                res = await client.call("submit_job", spec)
                if not res.get("ok"):
                    raise RuntimeError(f"submit rejected: {res}")
                jids.append(res["job"])

            async def wait_done() -> None:
                nonlocal states
                while time.perf_counter() < deadline:
                    view = await client.call("stats")
                    states = {j["job"]: j["state"] for j in view["jobs"]}
                    if all(states.get(j) == "done" for j in jids):
                        return
                    await asyncio.sleep(0.2)

            t0 = time.perf_counter()
            # Wave 1: the three distinct triples — real compute. Wave 2
            # (after wave 1 settles): every remaining submission repeats
            # a triple, so the expected cache-hit count is EXACT (K-3) —
            # a lower number means the cache broke, and the leg fails.
            for i in range(min(3, k_jobs)):
                await submit(triples[i % 3])
            await wait_done()
            for i in range(3, k_jobs):
                await submit(triples[i % 3])
            await wait_done()
            wall_s = time.perf_counter() - t0
            view = await client.call("stats")
            await client.call("shutdown")
            await client.close()
            return {"wall_s": wall_s, "states": states, "view": view}

        out = asyncio.run(drive())
        states = out["states"]
        completed = sum(1 for j in states.values() if j == "done")
        ok = completed == k_jobs
        sv = out["view"]["service"]
        cache = sv["cache"]
        lookups = cache["hits"] + cache["misses"]
        qh = Histogram.from_dict(sv["queue_wait_s"])
        result.update({
            "value": round(completed / (out["wall_s"] / 60.0), 2),
            "wall_s": round(out["wall_s"], 3),
            "completed": completed,
            "cache_hits": cache["hits"],
            "cache_hit_rate": (
                round(cache["hits"] / lookups, 3) if lookups else None
            ),
            "queue_wait_p95_s": (
                round(qh.percentile(0.95) or 0.0, 3) if qh.count else None
            ),
        })
        # The expected hit count is exact: every submission past the
        # first cycle repeats a triple. A lower number = the cache broke.
        expected_hits = max(k_jobs - 3, 0)
        if cache["hits"] < expected_hits:
            ok = False
            result["error"] = (
                f"cache hits {cache['hits']} < expected {expected_hits}"
            )
    except Exception as e:
        ok = False
        result["error"] = repr(e)
    finally:
        for p in [svc, *workers]:
            if p.poll() is None:
                try:
                    p.terminate()
                    p.wait(timeout=20)
                except (OSError, subprocess.TimeoutExpired):
                    p.kill()
                    p.wait()
    # mrcheck over the whole service work root (multi-job target): the
    # leg's conformance oracle — the chaos doctrine applied to the
    # service plane.
    try:
        cdoc = run_check(str(root / "work"))
        result["mrcheck"] = {
            "ok": cdoc["ok"],
            "jobs": cdoc["checked"].get("jobs"),
            "violations": [
                f"[{v['code']}] {v['message']}"
                for v in cdoc["violations"][:6]
            ],
        }
        ok = ok and cdoc["ok"]
    except Exception as e:  # an uncheckable leg is a failed leg
        ok = False
        result["mrcheck"] = {"ok": False, "error": repr(e)}
    # Fleet profiler (ISSUE 16) over the same work root: cross-job
    # utilization, barrier-bubble fraction and the per-job pipelining
    # opportunity — the three series doctor trend watches for the
    # scheduling plane. Post-mortem only (journal + reports), so a
    # profiler failure degrades to nulls rather than failing the leg.
    fleet_row: dict = {}
    try:
        from mapreduce_rust_tpu.runtime.fleet import (
            build_fleet_report, fleet_history_row,
        )

        frep = build_fleet_report(str(root / "work"))
        fleet_row = fleet_history_row(frep)
        result.update(fleet_row)
    except Exception as e:
        result["fleet_error"] = repr(e)
    result["ok"] = ok
    return result


def service_leg(k_jobs: int | None = None) -> None:
    """``bench.py --service-leg``: continuous-traffic throughput of the
    multi-tenant job service (ISSUE 14) — one _service_run over the
    mixed two-wave matrix, recorded into .bench/history.jsonl; ``doctor
    trend`` watches jobs/minute (bad = down: the control plane itself
    got slower). BENCH_SERVICE_SCHED=pipeline runs the single leg under
    the pipelined scheduler; the A/B pair is ``--sched-ab``. Prints ONE
    JSON line; exit 1 on failure."""
    k_jobs = k_jobs or int(os.environ.get("BENCH_SERVICE_JOBS", "12"))
    sched = os.environ.get("BENCH_SERVICE_SCHED", "fifo")
    result = _service_run(k_jobs, sched, BENCH_DIR / "service")
    _append_history({
        "metric": result["metric"],
        "value": None,  # jobs/min has its own trend series below
        "unit": "jobs/min",
        "platform": "cpu",
        "service_sched": sched,
        "service_jobs_per_min": result.get("value"),
        "service_queue_wait_p95_s": result.get("queue_wait_p95_s"),
        "service_cache_hit_rate": result.get("cache_hit_rate"),
        "service_k_jobs": k_jobs,
        "service_mrcheck": result.get("mrcheck"),
        **{k: v for k, v in result.items()
           if k.startswith(("fleet_", "pipelining_"))},
        "error": result.get("error"),
    })
    print(json.dumps(result))
    if not result["ok"]:
        raise SystemExit(1)


def service_sched_ab(k_jobs: int | None = None) -> None:
    """``bench.py --service-leg --sched-ab`` (ISSUE 17 acceptance): the
    SAME mixed two-wave matrix under ``--sched fifo`` vs ``--sched
    pipeline``, sides INTERLEAVED per repeat so machine drift hits both
    equally (the dispatch_ab_pair doctrine). Best repeat per side by
    jobs/min; one JSON line + ONE history row carrying both sides — the
    pipeline side feeds the watched series (service_jobs_per_min,
    fleet_bubble_frac, pipelining_opportunity_s: the numbers the
    scheduling ROADMAP item is struck with). Correctness (all jobs done,
    exact cache-hit count, mrcheck clean) is enforced per run and fails
    the pair loudly; the throughput DELTA is recorded, not gated — a
    noisy shared machine must not turn a perf probe into a flaky
    oracle."""
    k_jobs = k_jobs or int(os.environ.get("BENCH_SERVICE_JOBS", "12"))
    repeats = int(os.environ.get("BENCH_SCHED_AB_REPEATS", "1"))
    # Heavier matrix than the single leg's: enough docs (map tasks) and
    # bytes per task that phase windows are real and barrier bubbles
    # exist for the pipeline side to fill.
    docs_n = int(os.environ.get("BENCH_SCHED_AB_DOCS", "12"))
    scale = int(os.environ.get("BENCH_SCHED_AB_SCALE", "8"))
    sides: dict = {"fifo": [], "pipeline": []}
    ok = True
    for rep in range(repeats):
        for sched in ("fifo", "pipeline"):  # interleaved: drift hits both
            try:
                res = _service_run(
                    k_jobs, sched, BENCH_DIR / f"service-ab-{sched}",
                    docs_n=docs_n, scale=scale,
                )
            except Exception as e:
                res = {"ok": False, "error": repr(e), "sched": sched}
            ok = ok and bool(res.get("ok"))
            sides[sched].append(res)
            print(
                f"sched-ab {sched}[{rep}]: jobs/min={res.get('value')} "
                f"queue_p95={res.get('queue_wait_p95_s')}s "
                f"bubble={res.get('fleet_bubble_frac')} "
                f"pipelining_opp={res.get('pipelining_opportunity_s')}s "
                f"ok={res.get('ok')}",
                file=sys.stderr,
            )

    def best(rows: list) -> dict:
        scored = [r for r in rows if r.get("value")]
        return max(scored or rows, key=lambda r: r.get("value") or 0.0)

    f, p = best(sides["fifo"]), best(sides["pipeline"])
    pick = lambda r: {  # noqa: E731
        k: r.get(k) for k in (
            "value", "wall_s", "queue_wait_p95_s", "cache_hit_rate",
            "fleet_bubble_frac", "fleet_util_frac",
            "pipelining_opportunity_s", "ok", "error",
        )
    }
    speedup = (
        round(p["value"] / f["value"], 3)
        if p.get("value") and f.get("value") else None
    )
    result = {
        "metric": f"service sched A/B ({k_jobs} mixed jobs, fifo vs "
                  f"pipeline, interleaved best-of-{repeats})",
        "unit": "x",
        "value": speedup,
        "fifo": pick(f),
        "pipeline": pick(p),
        "ok": ok,
        "platform": "cpu",
    }
    _append_history({
        "metric": result["metric"],
        "value": None,  # the watched series ride the service_/fleet_ keys
        "unit": "x",
        "platform": "cpu",
        "service_sched_ab": {"fifo": pick(f), "pipeline": pick(p)},
        "service_sched_speedup": speedup,
        # The pipeline side feeds the watched series: it is the
        # configuration the scheduling plane ships with.
        "service_sched": "pipeline",
        "service_jobs_per_min": p.get("value"),
        "service_queue_wait_p95_s": p.get("queue_wait_p95_s"),
        "service_cache_hit_rate": p.get("cache_hit_rate"),
        "service_k_jobs": k_jobs,
        "service_mrcheck": p.get("mrcheck"),
        **{k: v for k, v in p.items()
           if k.startswith(("fleet_", "pipelining_"))},
    })
    print(json.dumps(result))
    if not ok:
        raise SystemExit(1)


def main() -> None:
    errors: list[str] = []
    base_gbs = None
    fallback = False

    try:
        corpus = build_corpus(TARGET_MB)
    except Exception as e:  # disk pressure etc. — shrink, never die
        errors.append(f"corpus: {e!r}")
        corpus = build_corpus(8)

    try:
        # Median of three: the 1-core pool measurement is noisy (fork +
        # import + scheduler jitter swing single runs ±20%).
        runs = sorted(
            cpu_baseline_gbs(corpus, min(BASELINE_MB << 20, corpus.stat().st_size))
            for _ in range(3)
        )
        base_gbs = runs[1]
        print(f"cpu baseline: {base_gbs:.4f} GB/s (runs: {runs})", file=sys.stderr)
    except Exception as e:
        errors.append(f"cpu_baseline: {e!r}")

    # Median of three runs — the SAME estimator as the CPU baseline (an
    # asymmetric max-vs-median pairing would bias the ratio upward).
    # Repeats are skipped when the first run was slow (cold compiles /
    # sick machine): one number beats a harness-level timeout. The
    # heartbeat init deadline applies to every attempt: a backend that
    # wedges mid-bench (not just before it) still can't eat the leg.
    def median_leg(c: pathlib.Path, timeout_s: int, env: dict | None):
        t0 = time.perf_counter()
        first, e = _run_device_leg(c, timeout_s, env, init_timeout_s=PROBE_TIMEOUT_S)
        if first is None or time.perf_counter() - t0 >= timeout_s / 3:
            return first, e
        more = [first]
        for _ in range(2):
            r, _e = _run_device_leg(c, timeout_s, env, init_timeout_s=PROBE_TIMEOUT_S)
            if r is not None:
                more.append(r)
        return sorted(more, key=lambda r: r["gbs"])[len(more) // 2], None

    probes: list[dict] = []

    def note_probe(tag: str, res, err) -> None:
        p = {"when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
             "leg": tag, "ok": res is not None}
        if res is None and err:
            p["error"] = err
        probes.append(p)

    dev, err = median_leg(corpus, DEVICE_TIMEOUT_S, None)
    note_probe("device", dev, err)
    if dev is None:
        errors.append(err)
        fallback = True
        try:
            small = build_corpus(FALLBACK_MB)
        except Exception as e:  # disk pressure — shrink, never die
            errors.append(f"fallback corpus: {e!r}")
            try:
                small = build_corpus(8)
            except Exception as e2:
                # Not even 8 MB fits: reuse whatever the main leg had. This
                # may exceed the leg's time budget if it is the full-size
                # corpus, but it is the only measurable byte stream left.
                errors.append(f"fallback corpus (8MB): {e2!r}")
                small = corpus
        dev, err = median_leg(small, FALLBACK_TIMEOUT_S, _cpu_env())
        if dev is None:
            errors.append(f"fallback: {err}")
        # Re-probe the real device AFTER the CPU legs: a device that was
        # unavailable at leg time may have come back.
        re_dev, re_err = _run_device_leg(
            corpus, DEVICE_TIMEOUT_S, None, init_timeout_s=PROBE_TIMEOUT_S
        )
        note_probe("device-reprobe", re_dev, re_err)
        if re_dev is not None and re_dev["info"].get("platform") not in (None, "cpu"):
            dev, fallback = re_dev, False  # the device came back — use it

    # Device micro-bench block: survives an end-to-end fallback, and is
    # itself re-probed on the CPU backend so the block always carries a
    # number (VERDICT r4 next-round 2).
    micro, merr = _run_device_leg(
        corpus, 180, None, init_timeout_s=PROBE_TIMEOUT_S, mode="--micro"
    )
    note_probe("micro", micro, merr)
    if micro is None:
        errors.append(f"micro: {merr}")
        micro, merr = _run_device_leg(
            corpus, 180, _cpu_env(), init_timeout_s=PROBE_TIMEOUT_S, mode="--micro"
        )
        note_probe("micro-cpu", micro, merr)

    # High-cardinality leg: Zipf corpus (2M-rank support), budgets engaged,
    # exactness vs generator ground truth (VERDICT r4 next-round 3).
    zipf, zerr = None, None
    zipf_mb = int(os.environ.get("BENCH_ZIPF_MB", "256"))
    if zipf_mb > 0:
        zipf, zerr = _run_device_leg(
            pathlib.Path(str(zipf_mb)), int(os.environ.get("BENCH_ZIPF_TIMEOUT_S", "420")),
            _cpu_env() if fallback else None,
            init_timeout_s=PROBE_TIMEOUT_S, mode="--zipf",
        )
        note_probe("zipf", zipf, zerr)
        if zipf is None:
            errors.append(f"zipf: {zerr}")

    # Sampler-tax pair (ISSUE 8): metrics ON vs OFF over the same corpus,
    # once per bench run — the history series doctor `trend` watches
    # (metrics_overhead_frac). CPU env: the tax under test is host-side
    # (registry locks + ring sampling), and ON-vs-OFF on the same backend
    # is the controlled comparison.
    overhead, oerr = None, None
    overhead_mb = int(os.environ.get("BENCH_METRICS_OVERHEAD_MB", "16"))
    if overhead_mb > 0:
        try:
            overhead_corpus = build_corpus(min(TARGET_MB, overhead_mb))
        except Exception as e:
            errors.append(f"metrics-overhead corpus: {e!r}")
            overhead_corpus = None
        if overhead_corpus is not None:
            overhead, oerr = _run_device_leg(
                overhead_corpus,
                int(os.environ.get("BENCH_METRICS_OVERHEAD_TIMEOUT_S", "300")),
                _cpu_env(), init_timeout_s=PROBE_TIMEOUT_S,
                mode="--metrics-overhead",
            )
            note_probe("metrics-overhead", overhead, oerr)
            if overhead is None:
                errors.append(f"metrics-overhead: {oerr}")

    # Profiler-tax pair (ISSUE 19): same estimator, Config.profile as the
    # toggled knob. Reuses the metrics-overhead corpus size; the series
    # doctor `trend` watches is profile_overhead_frac (bad: up), with the
    # acceptance bar at 2% wall.
    prof_overhead, perr = None, None
    if overhead_mb > 0 and os.environ.get("BENCH_PROFILE_OVERHEAD", "1") != "0":
        try:
            prof_corpus = build_corpus(min(TARGET_MB, overhead_mb))
        except Exception as e:
            errors.append(f"profile-overhead corpus: {e!r}")
            prof_corpus = None
        if prof_corpus is not None:
            prof_overhead, perr = _run_device_leg(
                prof_corpus,
                int(os.environ.get("BENCH_METRICS_OVERHEAD_TIMEOUT_S", "300")),
                _cpu_env(), init_timeout_s=PROBE_TIMEOUT_S,
                mode="--profile-overhead",
            )
            note_probe("profile-overhead", prof_overhead, perr)
            if prof_overhead is None:
                errors.append(f"profile-overhead: {perr}")

    # Provenance-plane pair (ISSUE 20): ledger tax + blast radius in one
    # leg. The series doctor `trend` watches are lineage_overhead_frac
    # (bad: up, bar 2%) and lineage_memo_hit_frac (bad: down, bar 0.95).
    lin_overhead, lerr = None, None
    if overhead_mb > 0 and os.environ.get("BENCH_LINEAGE_OVERHEAD", "1") != "0":
        try:
            lin_corpus = build_corpus(min(TARGET_MB, overhead_mb))
        except Exception as e:
            errors.append(f"lineage-overhead corpus: {e!r}")
            lin_corpus = None
        if lin_corpus is not None:
            lin_overhead, lerr = _run_device_leg(
                lin_corpus,
                int(os.environ.get("BENCH_METRICS_OVERHEAD_TIMEOUT_S", "300")),
                _cpu_env(), init_timeout_s=PROBE_TIMEOUT_S,
                mode="--lineage-overhead",
            )
            note_probe("lineage-overhead", lin_overhead, lerr)
            if lin_overhead is None:
                errors.append(f"lineage-overhead: {lerr}")

    value = round(dev["gbs"], 4) if dev else None
    platform = dev["info"].get("platform", "unknown") if dev else "none"
    # The corpus label comes from the bytes the measured leg actually
    # processed — never from what was merely intended.
    measured_mb = round(dev["info"]["bytes"] / (1 << 20)) if dev else 0
    result = {
        "metric": (
            f"word_count GB/s end-to-end ({measured_mb}MB corpus, single {platform} chip"
            f"{' [cpu-xla fallback]' if fallback else ''} "
            f"vs {BASELINE_MB}MB 8-proc CPU baseline)"
            if dev
            else "word_count GB/s end-to-end (no device measurement)"
        ),
        "value": value,
        "unit": "GB/s",
        "vs_baseline": (
            round(value / base_gbs, 2) if value is not None and base_gbs else None
        ),
        "platform": platform,
        "probes": probes,
    }
    # The measured leg's fold-shard setting rides into the history line
    # (ISSUE 9 satellite): "the doctor stopped naming host-glue" is only
    # checkable from history if each row says what fold config produced it.
    if dev is not None and dev.get("stats"):
        result["fold_shards"] = dev["stats"].get("fold_shards")
    if micro is not None:
        result["device_micro"] = micro.get("micro")
    if zipf is not None:
        result["zipf"] = zipf.get("zipf")
    if overhead is not None:
        result["metrics_overhead"] = overhead.get("metrics_overhead")
    if prof_overhead is not None:
        result["profile_overhead"] = prof_overhead.get("profile_overhead")
    if lin_overhead is not None:
        result["lineage_overhead"] = lin_overhead.get("lineage_overhead")
    if errors:
        result["error"] = "; ".join(errors)
    result["doctor"] = _doctor_measured_leg(dev)
    _write_bench_manifest(result, dev, base_gbs)
    _append_history(result)
    print(json.dumps(result))
    if dev:
        print(
            json.dumps({"detail": dev["info"],
                        "cpu_baseline_gbs": round(base_gbs, 4) if base_gbs else None}),
            file=sys.stderr,
        )


def _doctor_measured_leg(dev) -> "dict | None":
    """Run the doctor (analysis/doctor.py — backend-free, in-process) on
    the measured leg's own run manifest, so every bench line names its
    bottleneck and carries the ranked findings next to the number. The
    run-manifest-on-disk describes the LAST completed leg (median repeats
    rewrite it), which is the freshest leg of the same config — the
    comment in _write_bench_manifest records the same caveat. Best-effort:
    a doctor failure is itself a recorded fact, never a lost bench."""
    path = (dev or {}).get("run_manifest")
    if not path or not os.path.exists(path):
        return None
    try:
        from mapreduce_rust_tpu.analysis.doctor import diagnose
        from mapreduce_rust_tpu.runtime.telemetry import load_manifest

        diag = diagnose(load_manifest(path))
        out = {
            "bottleneck": (diag.get("bottleneck") or {}).get("name"),
            "findings": [
                f"[{f['severity']}] {f['code']}: {f['message']}"
                for f in (diag.get("findings") or [])[:8]
            ],
            "manifest": path,
        }
        hists = diag.get("histograms_ms") or {}
        for name in ("host_map.scan_s", "a2a.round_s", "device.drain_s"):
            if name in hists:
                out.setdefault("p99_ms", {})[name] = hists[name].get("p99")
        print(f"doctor: bottleneck={out['bottleneck']} "
              f"findings={len(out['findings'])}", file=sys.stderr)
        return out
    except Exception as e:
        return {"error": repr(e)}


def _append_history(result: dict) -> None:
    """Append one line per bench run to .bench/history.jsonl — the memory
    bench.py never had: `doctor --baseline` and a human diffing rounds get
    a durable trajectory instead of whatever the last manifest overwrote.
    One compact JSON object per line; errors recorded, never raised."""
    try:
        from mapreduce_rust_tpu.runtime.telemetry import git_rev

        line = {
            "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "git_rev": git_rev(),
            "metric": result.get("metric"),
            "value": result.get("value"),
            "unit": result.get("unit"),
            "vs_baseline": result.get("vs_baseline"),
            "platform": result.get("platform"),
            "doctor_bottleneck": (result.get("doctor") or {}).get("bottleneck"),
            "fold_shards": result.get("fold_shards"),
            "zipf_gbs": (result.get("zipf") or {}).get("gbs"),
            # Spill-plane before/after evidence (ISSUE 11): wall + stall
            # per row, and the run format so the trajectory names which
            # plane (text vs binary-v1) produced each number.
            "zipf_wall_s": (result.get("zipf") or {}).get("wall_s"),
            "zipf_spill_stall_s": (result.get("zipf") or {}).get("spill_stall_s"),
            "zipf_spill_write_s": (result.get("zipf") or {}).get("spill_write_s"),
            "spill_run_format": (result.get("zipf") or {}).get("spill_format"),
            # Dispatch-plane trajectory (ISSUE 13): dispatch count + mean
            # fill per row; merge_fill_frac is trend-watched (bad = down —
            # emptier dispatches mean the coalesce factor is eroding).
            "merge_dispatches": (result.get("zipf") or {}).get("merge_dispatches"),
            "merge_fill_frac": (result.get("zipf") or {}).get("merge_fill_frac"),
            "dispatch_mode": (result.get("zipf") or {}).get("dispatch_mode"),
            "zipf_dispatch_stall_s": (result.get("zipf") or {}).get("dispatch_stall_s"),
            # Sampler tax (ISSUE 8): a watched trend series (bad
            # direction: up) — None on chaos/sweep rows keeps it clean.
            "metrics_overhead_frac": (
                (result.get("metrics_overhead") or {}).get("frac")
            ),
            # Roofline trajectory (ISSUE 19): what the zipf scan achieved
            # vs the calibrated host memcpy roof — both trend-watched with
            # bad direction: down (a shrinking frac means the host map is
            # drifting away from the bandwidth bound it should sit on).
            "scan_achieved_gbs": (result.get("zipf") or {}).get("scan_achieved_gbs"),
            "roofline_frac": (result.get("zipf") or {}).get("roofline_frac"),
            # Profiler tax (ISSUE 19): same shape as the metrics series,
            # watched with bad direction: up; acceptance bar is 0.02.
            "profile_overhead_frac": (
                (result.get("profile_overhead") or {}).get("frac")
            ),
            # Provenance plane (ISSUE 20): ledger tax (bad: up, bar 2%)
            # and the +1% grown-corpus memo fraction (bad: down — chunk
            # stability eroding shrinks what a memo tier can ever skip).
            "lineage_overhead_frac": (
                (result.get("lineage_overhead") or {}).get("frac")
            ),
            "lineage_memo_hit_frac": (
                ((result.get("lineage_overhead") or {}).get("blast_radius")
                 or {}).get("memo_hit_frac")
            ),
            "had_errors": bool(result.get("error")),
        }
        # Chaos rows (bench.py --chaos) and service rows (--service-leg)
        # carry their own fields verbatim; their "value" stays None so
        # `doctor trend`'s watched series never mix recovery walls with
        # throughput numbers (service_jobs_per_min is its own watched
        # series — bad direction: down).
        line.update({
            k: v for k, v in result.items()
            if k.startswith(("chaos_", "service_", "sort_", "fleet_",
                             "pipelining_", "model_"))
        })
        if result.get("chaos_scenario"):
            line["doctor_findings"] = [
                f.split(": ", 1)[0]
                for f in ((result.get("doctor") or {}).get("findings") or [])
            ]
        BENCH_DIR.mkdir(exist_ok=True)
        with open(BENCH_DIR / "history.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"history: appended to {BENCH_DIR / 'history.jsonl'}",
              file=sys.stderr)
    except Exception as e:
        print(f"history append failed: {e!r}", file=sys.stderr)


def _lint_counts() -> dict:
    """Run the backend-free mrlint analyzer and reduce its JSON report to
    the counts a BENCH trajectory diffs (a regressing rule shows up in the
    manifest, ROADMAP leftover). Best-effort: a broken linter is itself a
    recorded fact, never a lost bench."""
    try:
        r = subprocess.run(
            [sys.executable, "-m", "mapreduce_rust_tpu", "lint",
             "--format", "json"],
            capture_output=True, text=True, timeout=120, cwd=str(REPO),
        )
        doc = json.loads(r.stdout)
        return {
            "ok": doc.get("ok"),
            "exit_code": r.returncode,
            "findings": len(doc.get("findings", [])),
            "files_checked": doc.get("files_checked"),
            "rules": len(doc.get("rules", [])),
            "suppressed_inline": doc.get("suppressed_inline"),
            "suppressed_baseline": doc.get("suppressed_baseline"),
            "unused_baseline_entries": len(
                doc.get("unused_baseline_entries", [])
            ),
        }
    except Exception as e:
        return {"error": repr(e)}


def _write_bench_manifest(result: dict, dev, base_gbs) -> None:
    """One manifest.json per bench run — config, platform, git rev, the
    measured leg's full JobStats, probe outcomes, trace path, mrlint
    counts — so BENCH rounds read structured state instead of scraping log
    tails. Best effort: a manifest failure must never cost the stdout JSON
    line."""
    try:
        from mapreduce_rust_tpu.runtime import telemetry

        path = os.environ.get("BENCH_MANIFEST") or str(BENCH_DIR / "manifest.json")
        bench_cfg = {
            "target_mb": TARGET_MB, "baseline_mb": BASELINE_MB,
            "fallback_mb": FALLBACK_MB,
            "zipf_mb": int(os.environ.get("BENCH_ZIPF_MB", "256")),
            "map_engine": os.environ.get("BENCH_MAP_ENGINE", "host"),
            "device_timeout_s": DEVICE_TIMEOUT_S,
            "probe_timeout_s": PROBE_TIMEOUT_S,
        }
        manifest = telemetry.build_manifest(
            bench_cfg,
            probes=result.get("probes"),
            extra={
                "kind": "bench_manifest",
                "app": "word_count",
                "result": result,
                "lint": _lint_counts(),
                "cpu_baseline_gbs": round(base_gbs, 4) if base_gbs else None,
                # NOT trace_path: every traced leg (median repeats, fallback,
                # reprobe) rewrites the same trace + run-manifest files, so
                # on disk they describe the LAST completed leg — which may
                # not be the median-selected result above. The inner run
                # manifest's own trace_path pairs correctly with its stats;
                # point there instead of claiming the pairing here.
                "last_leg_run_manifest": (
                    (dev or {}).get("run_manifest")
                    or os.environ.get("BENCH_RUN_MANIFEST")
                    or None
                ),
                "last_leg_trace": os.environ.get("BENCH_TRACE") or None,
            },
        )
        if dev is not None and dev.get("stats"):
            manifest["stats"] = dev["stats"]
            manifest["phase_seconds"] = dev["info"].get("phases", {})
        telemetry.write_manifest(path, manifest)
        print(f"bench manifest: {path}", file=sys.stderr)
    except Exception as e:
        print(f"bench manifest write failed: {e!r}", file=sys.stderr)


def _take_flag(argv: list, flag: str) -> str | None:
    """Pop `flag VALUE` from argv (the legs' positional dispatch below must
    not see it). Flag values travel to subprocess legs as env vars, which
    both inherited and _cpu_env child environments preserve."""
    if flag in argv:
        i = argv.index(flag)
        if i + 1 >= len(argv):
            raise SystemExit(f"{flag} needs a value")
        v = argv[i + 1]
        del argv[i:i + 2]
        return v
    return None


def _take_switch(argv: list, flag: str) -> bool:
    """Pop a valueless `flag` from argv (same contract as _take_flag)."""
    if flag in argv:
        argv.remove(flag)
        return True
    return False


if __name__ == "__main__":
    _argv = sys.argv[1:]
    if _take_switch(_argv, "--sanitize"):
        # Thread-ownership sanitizer on every leg: the env var rides into
        # both inherited and _cpu_env subprocess environments (the
        # accel-prefix scrub doesn't touch MR_*), so a bench under
        # --sanitize measures the sanitized engines end-to-end.
        os.environ["MR_SANITIZE"] = "1"
    _trace = _take_flag(_argv, "--trace")
    if _trace:
        os.environ["BENCH_TRACE"] = str(pathlib.Path(_trace).resolve())
    _manifest = _take_flag(_argv, "--manifest")
    if _manifest:
        _mp = pathlib.Path(_manifest).resolve()
        os.environ["BENCH_MANIFEST"] = str(_mp)
        # The measured device-leg run also writes its OWN run manifest
        # (full Config + JobStats from inside the subprocess), beside the
        # bench-level one so the two never clobber each other.
        os.environ.setdefault(
            "BENCH_RUN_MANIFEST", str(_mp.with_name(_mp.stem + "-run.json"))
        )
    _workers = _take_flag(_argv, "--host-workers")
    if _workers:
        # Validate HERE, like the sweep's count parsing — a bad value must
        # be a usage error, not an opaque per-leg subprocess traceback.
        if not _workers.isdigit() or int(_workers) < 1:
            raise SystemExit(
                f"--host-workers needs a positive integer, got {_workers!r}"
            )
        os.environ["BENCH_HOST_WORKERS"] = _workers
    _fold = _take_flag(_argv, "--fold-shards")
    if _fold:
        if not _fold.isdigit() or int(_fold) < 1:
            raise SystemExit(
                f"--fold-shards needs a positive integer, got {_fold!r}"
            )
        os.environ["BENCH_FOLD_SHARDS"] = _fold
    if _take_switch(_argv, "--sync-spill"):
        # Legacy synchronous spill plane on every leg (A-B measurement):
        # the env var rides into both inherited and _cpu_env child
        # environments like MR_SANITIZE.
        os.environ["MR_SPILL_SYNC"] = "1"
    if _take_switch(_argv, "--sync-dispatch"):
        # Inline (router-thread) merge dispatch on every leg — the PR 10
        # path, same enablement pattern as --sync-spill.
        os.environ["MR_DISPATCH_SYNC"] = "1"
    _chaos = _take_switch(_argv, "--chaos")
    _service_leg = _take_switch(_argv, "--service-leg")
    _sched_ab = _take_switch(_argv, "--sched-ab")
    if _sched_ab:
        _service_leg = True  # --sched-ab alone implies the service leg
    _sort_leg = _take_switch(_argv, "--sort-leg")
    _model_leg = _take_switch(_argv, "--model-leg")
    _sweep = _take_flag(_argv, "--sweep-host-workers")
    _sweep_fold = _take_flag(_argv, "--sweep-fold-shards")
    _sweep_spill = _take_flag(_argv, "--sweep-spill-budget")
    _sweep_fill = _take_flag(_argv, "--sweep-dispatch-fill")
    _dispatch_ab = _take_switch(_argv, "--dispatch-ab")
    sys.argv = [sys.argv[0]] + _argv
    if _sort_leg:
        try:
            sort_leg_main()
        except SystemExit:
            raise
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "global sort over Zipf corpus",
                "unit": "s", "value": None,
                "error": f"sort-leg harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _model_leg:
        try:
            model_leg()
        except SystemExit:
            raise
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "mrmodel exploration, lease+pipeline foci",
                "unit": "schedules/s", "value": None,
                "error": f"model-leg harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _service_leg:
        try:
            service_sched_ab() if _sched_ab else service_leg()
        except SystemExit:
            raise
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "job service: K mixed submissions, jobs/minute",
                "unit": "jobs/min", "ok": False, "value": None,
                "error": f"service-leg harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _chaos:
        try:
            chaos_legs()
        except SystemExit:
            raise
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "chaos matrix: seeded fault recovery",
                "unit": "s", "ok": False, "scenarios": None,
                "error": f"chaos harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _sweep:
        try:
            sweep_host_workers(_sweep)
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "word_count GB/s vs host-map workers",
                "unit": "GB/s", "sweep": None,
                "error": f"sweep harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _sweep_fold:
        try:
            sweep_fold_shards(_sweep_fold)
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "word_count GB/s vs fold shards",
                "unit": "GB/s", "sweep": None,
                "error": f"sweep harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _sweep_spill:
        try:
            sweep_spill_budget(_sweep_spill)
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "zipf GB/s vs dictionary spill budget",
                "unit": "GB/s", "sweep": None,
                "error": f"sweep harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _sweep_fill:
        try:
            sweep_dispatch_fill(_sweep_fill)
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "zipf GB/s vs dispatch fill threshold",
                "unit": "GB/s", "sweep": None,
                "error": f"sweep harness: {e!r}",
            }))
            raise SystemExit(1)
    elif _dispatch_ab:
        try:
            dispatch_ab_pair()
        except SystemExit:
            raise
        except BaseException as e:  # one JSON line, like the main harness
            print(json.dumps({
                "metric": "zipf dispatch-plane A/B",
                "unit": "x", "value": None,
                "error": f"dispatch-ab harness: {e!r}",
            }))
            raise SystemExit(1)
    elif len(sys.argv) > 1 and sys.argv[1] == "--device-leg":
        device_leg(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--micro":
        micro_leg()
    elif len(sys.argv) > 1 and sys.argv[1] == "--metrics-overhead":
        metrics_overhead_leg(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--profile-overhead":
        profile_overhead_leg(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--lineage-overhead":
        lineage_overhead_leg(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--zipf":
        zipf_leg(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--zipf-ii":
        zipf_ii_leg(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--sort":
        sort_leg(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--slow-disk-leg":
        slow_disk_leg(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--slow-dispatch-leg":
        slow_dispatch_leg(sys.argv[2])
    else:
        try:
            main()
        except BaseException as e:  # the JSON line survives ANY failure
            print(json.dumps({
                "metric": "word_count GB/s end-to-end",
                "value": None, "unit": "GB/s", "vs_baseline": None,
                "error": f"bench harness: {e!r}",
            }))
            raise SystemExit(1)
