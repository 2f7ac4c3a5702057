"""analysis/doctor.py: automated run diagnosis (ISSUE 5 tentpole).

Unit half: synthetic manifests/reports/traces drive every diagnosis pass
deterministically (stragglers, lease advice, skew, crash forensics,
regression gate). End-to-end half: a real host-engine run and a real mesh
run produce manifests the doctor reads — same-bottleneck agreement,
histogram percentiles for host-map windows and a2a rounds, compile spans
with cache status, and a doctored slowdown tripping the --baseline gate.
"""

import collections
import copy
import json

import pytest

from mapreduce_rust_tpu.__main__ import main
from mapreduce_rust_tpu.analysis.doctor import (
    WATCHED_METRICS,
    compare_manifests,
    diagnose,
    format_diagnosis,
)
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.runtime import telemetry
from mapreduce_rust_tpu.runtime.driver import run_job
from mapreduce_rust_tpu.runtime.histogram import Histogram

TEXTS = [
    "the quick brown fox jumps over the lazy dog " * 60,
    "pack my box with five dozen liquor jugs " * 50,
    "sphinx of black quartz judge my vow " * 40,
]


def write_corpus(tmp_path) -> list[str]:
    d = tmp_path / "in"
    d.mkdir(exist_ok=True)
    out = []
    for i, t in enumerate(TEXTS):
        p = d / f"doc-{i}.txt"
        p.write_bytes(t.encode())
        out.append(str(p))
    return out


def oracle() -> dict:
    total = collections.Counter()
    for t in TEXTS:
        total.update(reference_word_counts(t.encode()))
    return {w.encode(): c for w, c in total.items()}


def _hist_dict(samples) -> dict:
    h = Histogram()
    for s in samples:
        h.add(s)
    return h.to_dict()


# ---------------------------------------------------------------------------
# diagnosis units (synthetic inputs — no jax)
# ---------------------------------------------------------------------------

def test_bottleneck_agrees_with_stats_formula():
    diag = diagnose({"stats": {
        "ingest_wait_s": 0.1, "device_wait_s": 2.0, "host_map_s": 0.5,
        "host_glue_s": 0.2, "scan_wait_s": 0.0, "host_map_workers": 0,
        "all_to_all_s": 0.0, "bottleneck": "device", "wall_seconds": 3.0,
    }})
    bn = diag["bottleneck"]
    assert bn["name"] == "device" and bn["agrees_with_stats"] is True
    assert bn["attribution"][0]["component"] == "device"
    # With parallel scan workers the consumer stall, not the aggregate
    # scan time, attributes the ceiling — JobStats' exact rule.
    diag = diagnose({"stats": {
        "ingest_wait_s": 0.1, "device_wait_s": 0.2, "host_map_s": 9.0,
        "host_glue_s": 0.3, "scan_wait_s": 0.05, "host_map_workers": 4,
        "bottleneck": "host-glue", "wall_seconds": 3.0,
    }})
    assert diag["bottleneck"]["name"] == "host-glue"


def test_compile_and_ici_extend_the_attribution():
    diag = diagnose({"stats": {
        "ingest_wait_s": 0.1, "device_wait_s": 0.2, "host_map_s": 0.1,
        "host_glue_s": 0.1, "host_map_workers": 0, "all_to_all_s": 0.0,
        "wall_seconds": 1.0,
        "compile": {"count": 3, "total_s": 40.0, "cache_hits": 0,
                    "cache_misses": 3},
        "bottleneck": "device",
    }})
    comps = {a["component"]: a for a in diag["bottleneck"]["attribution"]}
    assert comps["compile"]["seconds"] == 40.0
    codes = {f["code"] for f in diag["findings"]}
    assert "compile-bound" in codes and "compile-dominates" in codes


def test_straggler_detection_flags_slow_worker():
    report = {
        "tasks": {}, "totals": {}, "rpc": {},
        "workers": {
            "0": {"grants": 4, "reports": 4, "task_s": _hist_dict([1.0] * 4)},
            "1": {"grants": 4, "reports": 4, "task_s": _hist_dict([4.0] * 4)},
            "2": {"grants": 4, "reports": 4, "task_s": _hist_dict([1.1] * 4)},
        },
    }
    diag = diagnose({"kind": "coordinator_manifest"}, job_report=report)
    st = diag["stragglers"]
    assert st["flagged"] == ["1"]
    assert any(f["code"] == "straggler" and "worker 1" in f["message"]
               for f in diag["findings"])
    # A higher factor un-flags it.
    diag = diagnose({"kind": "coordinator_manifest"}, job_report=report,
                    straggler_factor=5.0)
    assert diag["stragglers"]["flagged"] == []


def test_lease_advice_tight_and_loose():
    def report_with_p99(p99):
        return {
            "tasks": {}, "rpc": {},
            "totals": {"map": {"tasks": 3, "completed": 3, "expiries": 1,
                               "re_executions": 0, "late_reports": 0,
                               "task_s": _hist_dict([p99] * 5)}},
        }

    tight = diagnose({"config": {"lease_timeout_s": 5.0}},
                     job_report=report_with_p99(4.9))
    assert tight["lease"]["task_p99_s"] == pytest.approx(4.9)
    assert any(f["code"] == "lease-tight" for f in tight["findings"])
    loose = diagnose({"config": {"lease_timeout_s": 60.0}},
                     job_report=report_with_p99(0.05))
    assert any(f["code"] == "lease-loose" for f in loose["findings"])


def test_reduce_partition_skew_scored_from_bytes():
    diag = diagnose({"stats": {
        "partition_bytes": [100, 110, 90, 1000],
        "bottleneck": "device", "device_wait_s": 1.0, "wall_seconds": 1.0,
    }})
    skew = diag["skew"]["reduce_partition_bytes"]
    assert skew["n"] == 4 and skew["max"] == 1000
    assert skew["score"] > 2.0
    assert any(f["code"] == "reduce-skew" for f in diag["findings"])
    # Balanced partitions: scored, not flagged.
    diag = diagnose({"stats": {
        "partition_bytes": [100, 101, 99, 100],
        "bottleneck": "device", "device_wait_s": 1.0, "wall_seconds": 1.0,
    }})
    assert diag["skew"]["reduce_partition_bytes"]["score"] < 1.1
    assert not any(f["code"] == "reduce-skew" for f in diag["findings"])


def test_crashed_run_yields_diagnosis_not_crash():
    # The crashed-attempt shape: a task granted twice (expiry + re-exec),
    # attempt 1's flow chain unterminated in the merged trace, and the
    # driver manifest carrying an error field. The doctor must produce a
    # diagnosis flagging the incomplete chain — never raise.
    report = {
        "tasks": {"map": {
            "0": {"grants": 2, "re_executions": 1, "expiries": 1,
                  "renewals": 3, "stale_renewals": 0, "reports": 1,
                  "late_reports": 0, "duration_s": 2.5, "completed": True,
                  "wid": 1},
            "1": {"grants": 1, "re_executions": 0, "expiries": 0,
                  "renewals": 1, "stale_renewals": 0, "reports": 0,
                  "late_reports": 0, "duration_s": None, "completed": False,
                  "wid": 0},
        }},
        "totals": {"map": {"tasks": 2, "completed": 1, "re_executions": 1,
                           "expiries": 1, "late_reports": 0}},
        "rpc": {},
    }
    trace_events = [
        {"name": "task", "ph": "s", "ts": 0, "pid": 1, "tid": 1,
         "id": "map:0:1"},
        {"name": "task", "ph": "t", "ts": 5, "pid": 2, "tid": 1,
         "id": "map:0:1"},  # SIGKILLed: no "f" ever arrives
        {"name": "task", "ph": "s", "ts": 10, "pid": 1, "tid": 1,
         "id": "map:0:2"},
        {"name": "task", "ph": "t", "ts": 11, "pid": 3, "tid": 1,
         "id": "map:0:2"},
        {"name": "task", "ph": "f", "ts": 20, "pid": 1, "tid": 1,
         "id": "map:0:2"},
    ]
    diag = diagnose(
        {"kind": "coordinator_manifest", "error": "SIGKILL'd worker"},
        job_report=report, trace_events=trace_events,
    )
    assert diag["incomplete"]["flows"] == ["map:0:1"]
    assert diag["incomplete"]["tasks"] == ["map:1"]
    codes = {f["code"] for f in diag["findings"]}
    assert {"incomplete-chain", "incomplete-task", "re-execution",
            "run-error"} <= codes
    # Errors rank first; the text rendering never throws on partials.
    assert diag["findings"][0]["severity"] == "error"
    assert "incomplete" in format_diagnosis(diag)


def test_empty_manifest_is_flagged_not_crashed():
    diag = diagnose({"kind": "bench_sweep_manifest"})
    assert any(f["code"] == "no-telemetry" for f in diag["findings"])


def test_speculation_effectiveness_finding():
    # ISSUE 6: a report whose totals carry speculation blocks yields the
    # effectiveness finding (won/wasted attempts, est. time saved),
    # summed across phases.
    report = {
        "totals": {
            "map": {"tasks": 4, "completed": 4, "re_executions": 1,
                    "expiries": 0, "late_reports": 0,
                    "speculation": {"attempts": 2, "won": 1, "wasted": 1,
                                    "time_saved_s": 3.5}},
            "reduce": {"tasks": 2, "completed": 2, "re_executions": 0,
                       "expiries": 0, "late_reports": 0,
                       "speculation": {"attempts": 1, "won": 1, "wasted": 0,
                                       "time_saved_s": 1.0}},
        },
    }
    diag = diagnose({"kind": "job_report"}, job_report=report)
    assert diag["speculation"] == {
        "attempts": 3, "won": 2, "wasted": 1, "time_saved_s": 4.5,
    }
    f = next(
        f for f in diag["findings"] if f["code"] == "speculation-effectiveness"
    )
    assert f["severity"] == "info" and "4.50s saved" in f["message"]
    assert "speculation:" in format_diagnosis(diag)
    # No speculation anywhere → no finding, no block.
    quiet = diagnose({"kind": "job_report"}, job_report={"totals": {
        "map": {"tasks": 1, "completed": 1, "re_executions": 0,
                "expiries": 0, "late_reports": 0},
    }})
    assert "speculation" not in quiet
    # All attempts losing is its own warning (duplicating healthy tasks).
    wasteful = diagnose({"kind": "job_report"}, job_report={"totals": {
        "map": {"tasks": 4, "completed": 4, "re_executions": 0,
                "expiries": 0, "late_reports": 0,
                "speculation": {"attempts": 3, "won": 0, "wasted": 3,
                                "time_saved_s": 0.0}},
    }})
    assert any(
        f["code"] == "speculation-wasteful" for f in wasteful["findings"]
    )


# ---------------------------------------------------------------------------
# doctor trend (ISSUE 6 satellite: N-round drift over history.jsonl)
# ---------------------------------------------------------------------------

def _history(tmp_path, values, key="value") -> str:
    p = tmp_path / "history.jsonl"
    with open(p, "w") as f:
        for v in values:
            f.write(json.dumps({key: v, "metric": "m"}) + "\n")
    return str(p)


def test_trend_stable_series_passes(tmp_path):
    from mapreduce_rust_tpu.analysis.doctor import analyze_trend

    t = analyze_trend([{"value": v} for v in
                       [1.0, 1.02, 0.99, 1.01, 1.0, 0.98, 1.02, 1.0]])
    assert t["series"]["value"]["status"] == "stable"
    assert t["drifts"] == []


def test_trend_detects_sustained_drift_pairwise_gate_misses(tmp_path):
    from mapreduce_rust_tpu.analysis.doctor import analyze_trend

    # -3% every round: each PAIR is inside the 10% pairwise threshold,
    # but the window loses ~25% — exactly the drift class `doctor trend`
    # exists to catch.
    values = [round(1.0 * (0.97 ** i), 4) for i in range(9)]
    t = analyze_trend([{"value": v} for v in values])
    assert t["series"]["value"]["status"] == "drifting"
    assert t["drifts"] and t["drifts"][0]["metric"] == "value"
    # A single-round dip does NOT count as sustained (slope stays flat).
    blip = [1.0, 1.0, 1.01, 0.99, 1.0, 1.0, 1.0, 0.85]
    t2 = analyze_trend([{"value": v} for v in blip])
    assert t2["drifts"] == []
    # An old, recovered dip doesn't count either (endpoint is healthy).
    recovered = [1.0, 0.7, 0.7, 0.75, 0.9, 1.0, 1.0, 1.0]
    t3 = analyze_trend([{"value": v} for v in recovered])
    assert t3["drifts"] == []


def test_trend_insufficient_data_is_not_a_drift(tmp_path):
    from mapreduce_rust_tpu.analysis.doctor import analyze_trend

    t = analyze_trend([{"value": 1.0}, {"value": 0.5}])
    assert t["series"]["value"]["status"] == "insufficient"
    assert t["drifts"] == []


def test_trend_watches_metrics_overhead_frac():
    # The sampler-tax series (metrics on vs off) is a watched metric
    # whose BAD direction is UP — a creeping overhead fraction drifts, a
    # noisy-but-flat one stays quiet.
    from mapreduce_rust_tpu.analysis.doctor import analyze_trend

    creeping = [{"value": 1.0, "metrics_overhead_frac": round(0.002 * (1.5 ** i), 5)}
                for i in range(9)]
    t = analyze_trend(creeping)
    assert t["series"]["metrics_overhead_frac"]["status"] == "drifting"
    assert any(d["metric"] == "metrics_overhead_frac" for d in t["drifts"])

    noisy_flat = [{"value": 1.0, "metrics_overhead_frac": v}
                  for v in [0.01, -0.005, 0.008, 0.002, -0.01, 0.009, 0.001,
                            0.004]]
    assert analyze_trend(noisy_flat)["drifts"] == []


def test_trend_cli_exit_codes(tmp_path, capsys):
    stable = _history(tmp_path, [1.0, 1.01, 0.99, 1.0, 1.0, 1.01])
    assert main(["doctor", "trend", stable]) == 0
    out = capsys.readouterr().out
    assert "no sustained drift" in out

    drifty = tmp_path / "drift.jsonl"
    with open(drifty, "w") as f:
        f.write("this line is torn garbage\n")  # must be skipped, not fatal
        for i in range(9):
            f.write(json.dumps({"value": 1.0 - 0.04 * i}) + "\n")
    assert main(["doctor", "trend", str(drifty)]) == 1
    out = capsys.readouterr().out
    assert "SUSTAINED DRIFT" in out

    assert main(["doctor", "trend", str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()  # drain the error line before the JSON check
    # JSON shape for CI diffs.
    assert main(["doctor", "trend", str(drifty), "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["kind"] == "doctor_trend" and doc["drifts"]
    # Chaos rows (value=None) never pollute the watched series.
    mixed = tmp_path / "mixed.jsonl"
    with open(mixed, "w") as f:
        for v in [1.0, 1.0, 1.01, 0.99, 1.0, 1.0]:
            f.write(json.dumps({"value": v}) + "\n")
        for _ in range(6):
            f.write(json.dumps(
                {"value": None, "chaos_scenario": "kill", "chaos_wall_s": 9.0}
            ) + "\n")
    assert main(["doctor", "trend", str(mixed)]) == 0


# ---------------------------------------------------------------------------
# regression gate units
# ---------------------------------------------------------------------------

def _base_manifest() -> dict:
    return {
        "kind": "run_manifest",
        "stats": {
            "gb_per_s": 0.10, "wall_seconds": 10.0, "ingest_wait_s": 1.0,
            "device_wait_s": 2.0, "host_glue_s": 1.0, "scan_wait_s": 0.5,
            "all_to_all_s": 0.0, "partial_overflow_replays": 0,
            "bucket_skew_replays": 0, "spilled_keys": 100,
            "bottleneck": "device",
            "histograms": {
                "host_map.scan_s": _hist_dict([0.01] * 20),
            },
        },
    }


def test_compare_manifests_passes_identical_and_improved():
    base = _base_manifest()
    assert compare_manifests(base, copy.deepcopy(base)) == []
    better = copy.deepcopy(base)
    better["stats"]["gb_per_s"] = 0.2
    better["stats"]["wall_seconds"] = 5.0
    assert compare_manifests(base, better) == []


def test_compare_manifests_trips_on_injected_slowdown():
    base = _base_manifest()
    slow = copy.deepcopy(base)
    slow["stats"]["gb_per_s"] = 0.05      # -50% (threshold 10% down)
    slow["stats"]["wall_seconds"] = 20.0  # +100% (threshold 25% up)
    slow["stats"]["partial_overflow_replays"] = 2  # any increase trips
    regs = compare_manifests(base, slow)
    tripped = {r["metric"] for r in regs}
    assert {"stats.gb_per_s", "stats.wall_seconds",
            "stats.partial_overflow_replays"} <= tripped
    # threshold scaling loosens the gate (counts with threshold 0 stay).
    regs = compare_manifests(base, slow, threshold_scale=100.0)
    assert {r["metric"] for r in regs} == {"stats.partial_overflow_replays"}


def test_watched_metrics_table_is_well_formed():
    for metric, (direction, rel) in WATCHED_METRICS.items():
        assert direction in ("up", "down"), metric
        assert rel >= 0.0, metric


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_doctor_cli_exit_codes_and_json(tmp_path, capsys):
    base = _base_manifest()
    p_base = str(tmp_path / "base.json")
    telemetry.write_manifest(p_base, base)
    slow = copy.deepcopy(base)
    slow["stats"]["gb_per_s"] = 0.04
    p_slow = str(tmp_path / "slow.json")
    telemetry.write_manifest(p_slow, slow)

    assert main(["doctor", p_base]) == 0
    out = capsys.readouterr().out
    assert "bottleneck: device" in out

    # --baseline: the doctored slowdown trips the gate → exit 1.
    assert main(["doctor", p_slow, "--baseline", p_base]) == 1
    out = capsys.readouterr().out
    assert "REGRESSIONS" in out and "stats.gb_per_s" in out

    # JSON mode is machine-parseable and carries the regressions.
    assert main(["doctor", p_slow, "--baseline", p_base,
                 "--format", "json"]) == 1
    diag = json.loads(capsys.readouterr().out)
    assert diag["schema"] == 1
    assert any(r["metric"] == "stats.gb_per_s" for r in diag["regressions"])

    assert main(["doctor", str(tmp_path / "missing.json")]) == 2
    capsys.readouterr()


def test_stats_diff_gates_on_watched_regression(tmp_path, capsys):
    # ISSUE 5 satellite: `stats <a> <b>` exits non-zero when a watched
    # metric regressed (it used to always exit 0), so CI can gate on it.
    base = _base_manifest()
    slow = copy.deepcopy(base)
    slow["stats"]["gb_per_s"] = 0.05
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    telemetry.write_manifest(p1, base)
    telemetry.write_manifest(p2, slow)
    assert main(["stats", p1, p2]) == 3
    out = capsys.readouterr().out
    assert "REGRESSIONS" in out and "stats.gb_per_s" in out
    # Reverse direction is an improvement: no gate.
    assert main(["stats", p2, p1]) == 0
    # Opt-outs: --no-gate, and a scale wide enough to tolerate the drop.
    assert main(["stats", p1, p2, "--no-gate"]) == 0
    assert main(["stats", p1, p2, "--threshold-scale", "100"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# end-to-end (real runs, CPU backend)
# ---------------------------------------------------------------------------

def _run_cfg(tmp_path, tag: str, **kw) -> Config:
    return Config(
        chunk_bytes=8192,
        input_dir=str(tmp_path / "in"),
        work_dir=str(tmp_path / f"work-{tag}"),
        output_dir=str(tmp_path / f"out-{tag}"),
        device="cpu",
        trace_path=str(tmp_path / f"trace-{tag}.json"),
        manifest_path=str(tmp_path / f"manifest-{tag}.json"),
        **kw,
    )


def test_doctor_on_real_host_engine_run(tmp_path, capsys):
    # The acceptance criterion: on a real single-host run the doctor names
    # the manifest's own bottleneck, reports host-map window percentiles,
    # and the run recorded >= 1 XLA compile with cache status.
    inputs = write_corpus(tmp_path)
    # Unique static shapes (host_update_cap) force at least one fresh XLA
    # compile in this run even when earlier tests warmed similar fns.
    cfg = _run_cfg(tmp_path, "host", map_engine="host",
                   host_window_bytes=1 << 20, host_update_cap=1 << 12)
    res = run_job(cfg, inputs)
    assert res.table == oracle()
    s = res.stats
    assert s.compile_count >= 1, "no XLA compile recorded"
    assert s.compile_cache_hits + s.compile_cache_misses >= 0

    m = telemetry.load_manifest(cfg.manifest_path)
    hists = m["stats"]["histograms"]
    for name in ("host_map.scan_s", "host_map.glue_s", "device.drain_s"):
        assert hists[name]["count"] > 0, name
        assert hists[name]["p50"] <= hists[name]["p95"] <= hists[name]["p99"]
    assert m["stats"]["compile"]["count"] == s.compile_count
    # Per-partition output bytes recorded for the skew pass.
    assert len(m["stats"]["partition_bytes"]) == cfg.reduce_n
    assert sum(m["stats"]["partition_bytes"]) > 0

    # The trace carries the compile span with its cache status.
    events = json.load(open(cfg.trace_path))["traceEvents"]
    compiles = [e for e in events if e["name"] == "xla.compile"]
    assert len(compiles) == s.compile_count
    assert all(e["args"]["cache"] in ("hit", "miss", "uncached")
               for e in compiles)
    from mapreduce_rust_tpu.runtime.trace import validate_events

    validate_events(events)

    # Doctor agrees with the manifest's bottleneck and surfaces the hists.
    assert main(["doctor", cfg.manifest_path]) == 0
    out = capsys.readouterr().out
    assert f"bottleneck: {m['stats']['bottleneck']}" in out
    assert "host_map.scan_s" in out

    # Doctored pair: inject a slowdown into a copy → regression + exit 1.
    slow = copy.deepcopy(m)
    slow["stats"]["wall_seconds"] = m["stats"]["wall_seconds"] * 3
    slow["stats"]["gb_per_s"] = m["stats"]["gb_per_s"] / 3
    p_slow = str(tmp_path / "slow.json")
    telemetry.write_manifest(p_slow, slow)
    assert main(["doctor", p_slow, "--baseline", cfg.manifest_path]) == 1
    capsys.readouterr()


def test_doctor_on_real_mesh_run_reports_a2a_percentiles(tmp_path, capsys):
    inputs = write_corpus(tmp_path)
    cfg = _run_cfg(tmp_path, "mesh", mesh_shape=4, merge_capacity=1 << 12)
    res = run_job(cfg, inputs)
    assert res.table == oracle()
    assert res.stats.mesh_rounds > 0

    m = telemetry.load_manifest(cfg.manifest_path)
    hists = m["stats"]["histograms"]
    assert hists["a2a.round_s"]["count"] == res.stats.mesh_rounds
    assert hists["a2a.round_s"]["p50"] <= hists["a2a.round_s"]["p99"]
    assert hists["a2a.wire_bytes"]["count"] == res.stats.mesh_rounds
    # Hash-class skew signal: one fill count per mesh shard.
    assert len(m["stats"]["mesh_shard_rows"]) == 4
    assert sum(m["stats"]["mesh_shard_rows"]) == res.stats.distinct_keys

    assert main(["doctor", cfg.manifest_path]) == 0
    out = capsys.readouterr().out
    assert "a2a.round_s" in out
    assert f"bottleneck: {m['stats']['bottleneck']}" in out
