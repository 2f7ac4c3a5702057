"""Device-merge dispatch plane (ISSUE 13 tentpole): the async coalescing
dispatch must be invisible in the results — oracle-exact for EVERY
dispatch config, and bit-identical across the (host_map_workers,
fold_shards) matrix at a FIXED dispatch config (the sync-uncoalesced
config being exactly the PR 10 stream) — while the zero-memset stager
packs byte-identically to the reference packer, the native coalesce
kernel agrees with its numpy fallback, a dispatch-thread failure unwinds
cleanly (poisoned router, no deadlocked submit, original error re-raised,
no orphan arenas), the packed-merge jit cache stays bounded, the manifest
grows dispatch_split, the doctor learns merge-dispatch + the low-fill
finding, and the slow_dispatch chaos site fires without changing a byte
of output."""

import dataclasses
import gc
import json
import pathlib

import numpy as np
import pytest

from mapreduce_rust_tpu.apps import get_app
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.runtime import telemetry
from mapreduce_rust_tpu.runtime.driver import run_job

# Same corpus shape as tests/test_fold_shards.py: multi-doc, one
# whitespace-free run longer than a window (forced cut) and a
# high-cardinality tail driving device→host spills.
TEXTS = [
    ("the quick brown fox jumps over the lazy dog " * 600
     + "x" * 6000 + " "
     + "pack my box with five dozen liquor jugs " * 500),
    ("zebra quagga okapi " * 2000
     + " ".join(f"w{i:05d}" for i in range(3000))),
]

#: The four dispatch configs of the acceptance matrix. "sync" +
#: coalesce-off is the PR 10 stream verbatim.
DISPATCH_CONFIGS = {
    "async+co": dict(dispatch_async=True, dispatch_coalesce=True),
    "async": dict(dispatch_async=True, dispatch_coalesce=False),
    "sync+co": dict(dispatch_async=False, dispatch_coalesce=True),
    "sync": dict(dispatch_async=False, dispatch_coalesce=False),
}


def write_inputs(tmp_path, texts):
    paths = []
    for i, t in enumerate(texts):
        p = tmp_path / f"doc-{i}.txt"
        p.write_bytes(t if isinstance(t, bytes) else t.encode())
        paths.append(str(p))
    return paths


def cfg_for(tmp_path, tag: str, workers: int = 1, shards: int = 1,
            **kw) -> Config:
    defaults = dict(
        map_engine="host",
        host_map_workers=workers,
        fold_shards=shards,
        host_window_bytes=4096,
        host_update_cap=256,        # force multi-merge splits per window
        merge_capacity=512,         # force device→host spills
        reduce_n=4,
        output_dir=str(tmp_path / f"out-{tag}"),
        work_dir=str(tmp_path / f"work-{tag}"),
        device="cpu",
    )
    defaults.update(kw)
    return Config(**defaults)


def output_bytes(res) -> list[bytes]:
    return [pathlib.Path(p).read_bytes() for p in res.output_files]


# ---------------------------------------------------------------------------
# Exactness matrix
# ---------------------------------------------------------------------------

def test_matrix_exact_word_count(tmp_path):
    """{W}×{S}×{coalesce,sync}: outputs identical EVERYWHERE (word_count
    outputs are a pure function of the final counts), and spill totals —
    which depend on the merge stream — identical across (W, S) at each
    FIXED dispatch config."""
    paths = write_inputs(tmp_path, TEXTS)
    first = None
    for dtag, dkw in DISPATCH_CONFIGS.items():
        per_config = None
        for w, s in ((1, 1), (2, 2)):
            res = run_job(
                cfg_for(tmp_path, f"wc-{dtag}-w{w}s{s}", w, s, **dkw), paths
            )
            assert res.stats.spill_events > 0  # the device spill path ran
            assert res.stats.forced_cuts > 0   # the forced-cut window ran
            assert res.stats.merge_dispatches > 0
            mode = ("sync" if not dkw["dispatch_async"] else "async")
            assert res.stats.dispatch_mode.startswith(mode)
            # No phantom records: a staging-flush slice that overran the
            # fill once shipped stale slots as real keys — they surface
            # as fold rows no dictionary word matches.
            assert res.stats.unknown_keys == 0, (dtag, w, s)
            if first is None:
                first = res
            assert res.stats.distinct_keys == first.stats.distinct_keys
            assert res.table == first.table, (dtag, w, s)
            assert output_bytes(res) == output_bytes(first), (dtag, w, s)
            if per_config is None:
                per_config = res
                continue
            # Bit-identical merge-stream effects across (W, S) at a fixed
            # dispatch config — the PR 9 contract, now per config.
            assert res.stats.spilled_keys == per_config.stats.spilled_keys
            assert res.stats.spill_events == per_config.stats.spill_events
            assert (res.stats.merge_dispatches
                    == per_config.stats.merge_dispatches), (dtag, w, s)


def test_coalesce_reduces_dispatches(tmp_path):
    """The lever the plane exists to pull: with duplicated vocabulary
    across windows, coalescing ships strictly fewer merges."""
    paths = write_inputs(tmp_path, TEXTS)
    on = run_job(cfg_for(tmp_path, "co-on", dispatch_coalesce=True), paths)
    off = run_job(cfg_for(tmp_path, "co-off", dispatch_coalesce=False), paths)
    assert on.table == off.table
    assert on.stats.merge_dispatches < off.stats.merge_dispatches
    assert 0.0 < on.stats.merge_fill_frac <= 1.0


def test_chunked_staging_flush_ships_no_phantoms(tmp_path):
    """Regression: a staging fill above one update cap flushes as SEVERAL
    cap-sized merges with a partial tail — the tail slice must clip at
    the fill, not the buffer (shipping stale staging slots beyond the
    fill created phantom keys with stolen counts). A tiny cap against a
    large explicit stage_cap forces many multi-chunk flushes with ragged
    tails; the oracle plus unknown_keys == 0 pins it."""
    paths = write_inputs(tmp_path, TEXTS)
    res = run_job(
        cfg_for(tmp_path, "chunked", 2, 2, host_update_cap=16,
                dispatch_stage_cap=512, dispatch_fill_frac=0.9), paths
    )
    ref = run_job(
        cfg_for(tmp_path, "chunked-ref", dispatch_async=False,
                dispatch_coalesce=False), paths
    )
    assert res.stats.unknown_keys == 0
    assert res.table == ref.table
    assert output_bytes(res) == output_bytes(ref)
    # Chunked flushes really happened: more dispatches than windows.
    assert res.stats.merge_dispatches > res.stats.chunks


def test_grep_and_topk_exact_across_dispatch_configs(tmp_path):
    paths = write_inputs(tmp_path, TEXTS)
    greps = {}
    for dtag, dkw in DISPATCH_CONFIGS.items():
        app = get_app("grep", query=("fox", "zebra", "missingword"))
        greps[dtag] = run_job(
            cfg_for(tmp_path, f"grep-{dtag}", 2, 2,
                    merge_capacity=1 << 14, **dkw),
            paths, app=app,
        )
    first = greps["sync"]
    assert first.table == {b"fox": [0], b"zebra": [1]}
    for dtag, res in greps.items():
        assert res.table == first.table, dtag
        assert output_bytes(res) == output_bytes(first), dtag
    topks = {
        dtag: run_job(
            cfg_for(tmp_path, f"topk-{dtag}", merge_capacity=1 << 14, **dkw),
            paths, app=get_app("top_k", k=10),
        )
        for dtag, dkw in DISPATCH_CONFIGS.items()
    }
    for dtag, res in topks.items():
        assert res.table == topks["sync"].table, dtag
        assert output_bytes(res) == output_bytes(topks["sync"]), dtag


def test_budget_matrix_exact(tmp_path):
    """Egress budgets engaged (streaming merge-join egress): the dispatch
    config changes the eviction pattern, never the output files."""
    paths = write_inputs(tmp_path, TEXTS)
    outs = {}
    for dtag, dkw in DISPATCH_CONFIGS.items():
        res = run_job(
            cfg_for(tmp_path, f"bud-{dtag}", 2, 2,
                    dictionary_budget_words=512,
                    host_accum_budget_mb=1, **dkw),
            paths,
        )
        assert res.stats.dict_spill_runs > 0   # the disk tier engaged
        assert res.table == {}                 # streaming egress: files only
        outs[dtag] = output_bytes(res)
    assert all(o == outs["sync"] for o in outs.values())


def test_distinct_op_never_coalesces(tmp_path):
    """Pre-summing is only exact for "sum" — a distinct-op app must run
    uncoalesced even with the knob on, and stay exact."""
    paths = write_inputs(tmp_path, TEXTS[:1])
    res = run_job(
        cfg_for(tmp_path, "ii", dispatch_coalesce=True),
        paths, app=get_app("inverted_index"),
    )
    assert res.stats.dispatch_mode == "async"  # no "+coalesce"
    assert res.table[b"fox"] == [0]


# ---------------------------------------------------------------------------
# Stager + coalesce kernel units
# ---------------------------------------------------------------------------

def test_pack_stager_matches_pack_update():
    from mapreduce_rust_tpu.runtime.driver import _PackStager, _pack_update

    class _Dev:  # duck-typed device: platform drives the barrier flag
        platform = "cpu"

    cap = 64
    rng = np.random.default_rng(7)
    stager = _PackStager(cap, _Dev())
    assert not stager.needs_barrier
    # Big, then small, then empty, then mid: the re-sentineled prefix must
    # make every pack byte-identical to the fresh-buffer reference.
    for n in (60, 3, 0, 17, 64, 1):
        keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
        vals = rng.integers(0, 2**31, size=n, dtype=np.uint32)
        got = stager.pack(keys[:, 0], keys[:, 1], vals)
        ref = _pack_update(keys, vals, cap)
        assert got.dtype == ref.dtype
        assert np.array_equal(got, ref), n


def test_pack_stager_tpu_requests_barrier():
    from mapreduce_rust_tpu.runtime.driver import _PackStager

    class _Dev:
        platform = "tpu"

    assert _PackStager(8, _Dev()).needs_barrier


def test_cpu_put_never_ships_the_stager_buffer(tmp_path, monkeypatch):
    """On the CPU backend a device_put may alias an aligned host buffer
    (zero-copy), so a put of the stager's own buffer lets the next pack
    rewrite a merge that has yet to run: counts then go wrong at random.
    Every packed update put must be a buffer the stager never rewrites."""
    import jax

    from mapreduce_rust_tpu.runtime import driver

    seen, stagers = [], []
    real_put, real_init = jax.device_put, driver._PackStager.__init__

    def put(x, *a, **k):
        if isinstance(x, np.ndarray) and x.dtype == np.uint32 and x.ndim == 1:
            seen.append(x)
        return real_put(x, *a, **k)

    def init(self, *a, **k):
        real_init(self, *a, **k)
        stagers.append(self)

    monkeypatch.setattr(driver.jax, "device_put", put)
    monkeypatch.setattr(driver._PackStager, "__init__", init)
    for dtag in ("async", "sync"):
        res = run_job(cfg_for(tmp_path, f"alias-{dtag}",
                              **DISPATCH_CONFIGS[dtag]), write_inputs(tmp_path, TEXTS))
        assert res.stats.merge_dispatches > 1
    assert stagers and len(seen) >= 2
    assert not any(np.shares_memory(x, s.flat) for x in seen for s in stagers)


def test_coalesce_native_matches_py_fallback():
    from mapreduce_rust_tpu.native.host import coalesce_updates_into
    from mapreduce_rust_tpu.runtime.driver import _coalesce_updates_py

    rng = np.random.default_rng(11)
    for trial in range(20):
        a = np.unique(rng.integers(0, 1000, size=rng.integers(0, 40),
                                   dtype=np.uint64))
        b = np.unique(rng.integers(0, 1000, size=rng.integers(1, 40),
                                   dtype=np.uint64))
        av = rng.integers(1, 100, size=len(a)).astype(np.int64)
        bv = rng.integers(1, 100, size=len(b)).astype(np.int64)
        ref_k, ref_v = _coalesce_updates_py(a, av, len(a), b, bv)
        out_k = np.empty(len(a) + len(b), dtype=np.uint64)
        out_v = np.empty(len(a) + len(b), dtype=np.int64)
        m = coalesce_updates_into(
            np.ascontiguousarray(a), np.ascontiguousarray(av), len(a),
            np.ascontiguousarray(b), np.ascontiguousarray(bv),
            out_k, out_v,
        )
        if m is None:
            pytest.skip("native lib unavailable")
        assert m == len(ref_k), trial
        assert np.array_equal(out_k[:m], ref_k)
        assert np.array_equal(out_v[:m], ref_v)
        # Duplicate keys summed, disjoint keys preserved.
        assert int(out_v[:m].sum()) == int(av.sum() + bv.sum())


# ---------------------------------------------------------------------------
# Teardown / failure containment
# ---------------------------------------------------------------------------

def test_dispatch_thread_failure_poisons_router_and_unwinds(
        tmp_path, monkeypatch):
    # Seeded failure: the dispatch thread dies mid-stream; the router's
    # bounded submit must never deadlock against the dead thread, the
    # ORIGINAL error surfaces from run_job, and no scan arenas leak.
    import mapreduce_rust_tpu.runtime.driver as drv
    from mapreduce_rust_tpu.native import host as native_host

    paths = write_inputs(tmp_path, TEXTS)
    gc.collect()
    baseline = native_host.arena_count()
    calls = [0]

    def boom(dispatch_index: int) -> None:
        calls[0] += 1
        if calls[0] >= 3:
            raise ValueError("seeded dispatch failure")

    monkeypatch.setattr(drv, "_chaos_slow_dispatch", boom)
    with pytest.raises(ValueError, match="seeded dispatch failure"):
        run_job(cfg_for(tmp_path, "boom", 2, 2), paths)
    gc.collect()
    assert native_host.arena_count() <= baseline


def test_sync_dispatch_failure_surfaces_inline(tmp_path, monkeypatch):
    import mapreduce_rust_tpu.runtime.driver as drv

    paths = write_inputs(tmp_path, TEXTS[:1])

    def boom(dispatch_index: int) -> None:
        raise ValueError("seeded sync dispatch failure")

    monkeypatch.setattr(drv, "_chaos_slow_dispatch", boom)
    with pytest.raises(ValueError, match="seeded sync dispatch failure"):
        run_job(cfg_for(tmp_path, "sboom", dispatch_async=False), paths)


def test_mr_dispatch_sync_env_forces_inline(tmp_path, monkeypatch):
    monkeypatch.setenv("MR_DISPATCH_SYNC", "1")
    paths = write_inputs(tmp_path, TEXTS[:1])
    res = run_job(cfg_for(tmp_path, "envsync"), paths)
    assert res.stats.dispatch_mode.startswith("sync")


# ---------------------------------------------------------------------------
# Packed-merge jit cache (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

def test_packed_fns_cache_bounded_and_clearable():
    import mapreduce_rust_tpu.runtime.driver as drv
    from mapreduce_rust_tpu.apps.word_count import WordCount

    drv.clear_packed_fns()
    app = WordCount()
    for cap in range(16, 16 + 2 * drv._PACKED_FNS_MAX):
        drv.make_packed_merge_fn(app, cap)
        assert len(drv._PACKED_FNS) <= drv._PACKED_FNS_MAX
    # LRU: re-fetching an entry refreshes it past younger ones.
    survivor_cap = 16 + 2 * drv._PACKED_FNS_MAX - drv._PACKED_FNS_MAX
    fn = drv.make_packed_merge_fn(app, survivor_cap)
    drv.make_packed_merge_fn(app, 4096)
    assert drv.make_packed_merge_fn(app, survivor_cap) is fn
    drv.clear_packed_fns()
    assert len(drv._PACKED_FNS) == 0


def test_run_job_trims_packed_cache(tmp_path):
    import mapreduce_rust_tpu.runtime.driver as drv
    from mapreduce_rust_tpu.apps.word_count import WordCount

    drv.clear_packed_fns()
    app = WordCount()
    for cap in range(8, 8 + 3 * drv._PACKED_FNS_MAX):
        # Simulate a long-lived multi-job process churning configs; the
        # insert-time trim plus the run_job teardown trim keep the bound.
        drv._PACKED_FNS[(app, cap)] = object()
    paths = write_inputs(tmp_path, TEXTS[:1])
    run_job(cfg_for(tmp_path, "trim"), paths)
    assert len(drv._PACKED_FNS) <= drv._PACKED_FNS_MAX
    drv.clear_packed_fns()


# ---------------------------------------------------------------------------
# Telemetry: dispatch_split, bottleneck arm, doctor findings
# ---------------------------------------------------------------------------

def test_manifest_dispatch_split_and_doctor(tmp_path):
    paths = write_inputs(tmp_path, TEXTS)
    mpath = tmp_path / "run.json"
    res = run_job(
        cfg_for(tmp_path, "man", manifest_path=str(mpath)), paths
    )
    m = json.loads(mpath.read_text())
    dp = m["stats"]["dispatch_split"]
    assert dp["mode"] == res.stats.dispatch_mode
    assert dp["dispatches"] == res.stats.merge_dispatches > 0
    assert 0.0 < dp["fill_frac"] <= 1.0
    assert dp["dispatch_s"] >= 0.0
    assert "dispatch.submit_s" in m["stats"]["histograms"]
    # The doctor's attribution mirrors JobStats.bottleneck exactly —
    # including the new merge-dispatch arm on async manifests.
    from mapreduce_rust_tpu.analysis.doctor import _bottleneck_attribution

    bn = _bottleneck_attribution(m["stats"])
    assert bn["agrees_with_stats"], bn
    assert any(
        c["component"] == "merge-dispatch" for c in bn["attribution"]
    )
    # Sync manifests keep the PR 10 attribution: no merge-dispatch arm.
    res2 = run_job(
        cfg_for(tmp_path, "man2", dispatch_async=False,
                manifest_path=str(tmp_path / "run2.json")), paths
    )
    m2 = json.loads((tmp_path / "run2.json").read_text())
    bn2 = _bottleneck_attribution(m2["stats"])
    assert bn2["agrees_with_stats"], bn2
    assert not any(
        c["component"] == "merge-dispatch" for c in bn2["attribution"]
    )
    assert res2.stats.bottleneck != "merge-dispatch"


def test_doctor_low_fill_finding():
    from mapreduce_rust_tpu.analysis.doctor import diagnose

    manifest = {
        "kind": "run_manifest",
        "stats": {
            "wall_seconds": 10.0,
            "dispatch_mode": "async+coalesce",
            "dispatch_s": 2.0,
            "dispatch_stall_s": 0.0,
            "merge_dispatches": 64,
            "merge_fill_frac": 0.03,
            "dispatch_split": {
                "mode": "async+coalesce", "dispatch_s": 2.0,
                "stall_s": 0.0, "dispatches": 64, "fill_frac": 0.03,
            },
        },
    }
    diag = diagnose(manifest)
    codes = [f["code"] for f in diag["findings"]]
    assert "dispatch-low-fill" in codes
    # A healthy fill stays quiet.
    manifest["stats"]["merge_fill_frac"] = 0.7
    manifest["stats"]["dispatch_split"]["fill_frac"] = 0.7
    assert "dispatch-low-fill" not in [
        f["code"] for f in diagnose(manifest)["findings"]
    ]


def test_doctor_merge_dispatch_bound_finding():
    from mapreduce_rust_tpu.analysis.doctor import diagnose

    manifest = {
        "kind": "run_manifest",
        "stats": {
            "wall_seconds": 10.0,
            "dispatch_mode": "async+coalesce",
            "dispatch_s": 6.0,
            "dispatch_stall_s": 5.0,
            "host_glue_s": 0.5,
            "merge_dispatches": 100,
            "merge_fill_frac": 0.8,
            "bottleneck": "merge-dispatch",
            "dispatch_split": {
                "mode": "async+coalesce", "dispatch_s": 6.0,
                "stall_s": 5.0, "dispatches": 100, "fill_frac": 0.8,
            },
        },
    }
    diag = diagnose(manifest)
    assert diag["bottleneck"]["name"] == "merge-dispatch"
    assert "merge-dispatch-bound" in [
        f["code"] for f in diag["findings"]
    ]


def test_live_collector_carries_dispatch_series(tmp_path):
    from mapreduce_rust_tpu.runtime.metrics import (
        JobStats,
        jobstats_collector,
    )

    stats = JobStats()
    stats.dispatch_s = 1.5
    stats.dispatch_stall_s = 0.25
    stats.merge_dispatches = 42
    stats.merge_fill_frac = 0.66
    vals = jobstats_collector(stats)()
    assert vals["job.dispatch_s"] == 1.5
    assert vals["job.dispatch_stall_s"] == 0.25
    assert vals["job.merge_dispatches"] == 42
    assert vals["job.merge_fill_frac"] == 0.66


# ---------------------------------------------------------------------------
# slow_dispatch chaos site
# ---------------------------------------------------------------------------

def test_slow_dispatch_spec_parses():
    from mapreduce_rust_tpu.analysis.chaos import SCENARIOS, ChaosPlan

    plan = ChaosPlan.parse(SCENARIOS["slow_dispatch"])
    f = plan.pick("slow_dispatch", tid=0)
    assert f is not None and f.seconds > 0
    # Every dispatch index matches (attempt-agnostic, like slow_disk).
    assert plan.pick("slow_dispatch", tid=123) is not None
    with pytest.raises(ValueError, match="slow_dispatch needs SECONDS"):
        ChaosPlan.parse("slow_dispatch:1:2")


def test_slow_dispatch_fires_and_outputs_exact(tmp_path, monkeypatch):
    from mapreduce_rust_tpu.runtime.driver import dispatch_chaos_fired

    paths = write_inputs(tmp_path, TEXTS[:1])
    clean = run_job(cfg_for(tmp_path, "nochaos"), paths)
    spec = "seed=7;slow_dispatch:0.001"
    monkeypatch.setenv("MR_CHAOS", spec)
    res = run_job(cfg_for(tmp_path, "chaos"), paths)
    assert res.table == clean.table
    assert output_bytes(res) == output_bytes(clean)
    assert len(dispatch_chaos_fired(spec)) >= res.stats.merge_dispatches


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

def test_dispatch_fill_frac_validated():
    with pytest.raises(ValueError, match="dispatch_fill_frac"):
        Config(dispatch_fill_frac=0.0)
    with pytest.raises(ValueError, match="dispatch_fill_frac"):
        Config(dispatch_fill_frac=1.5)
    Config(dispatch_fill_frac=1.0)  # inclusive upper bound
