"""Chaos + speculation (ISSUE 6): deterministic fault injection at named
worker sites, speculative re-execution with first-finish-wins revocation,
and the recovery guarantees both must keep — job completion with output
BIT-IDENTICAL to the fault-free run.

Tier-1 carries the spec-grammar units, a fast seeded smoke scenario
(pause + SIGKILL as real OS processes), and the speculation
effectiveness race (in-process cluster, ON measurably faster than OFF).
The full five-scenario matrix — every SCENARIOS entry as OS processes,
merged-trace attempt chains, doctor findings — is ``slow``.
"""

import asyncio
import collections
import dataclasses
import json
import pathlib
import time

import pytest

from mapreduce_rust_tpu.analysis.chaos import SCENARIOS, ChaosPlan
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.coordinator.server import Coordinator
from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.worker.runtime import Worker

from cluster_harness import CHAOS_TEXTS, chaos_cluster, free_port

TEXTS = [
    "the quick brown fox jumps over the lazy dog " * 30,
    "pack my box with five dozen liquor jugs don’t stop " * 20,
    "sphinx of black quartz judge my vow " * 25,
]


def make_cfg(tmp_path, n_files, **kw) -> Config:
    defaults = dict(
        map_n=n_files,
        reduce_n=3,
        worker_n=2,
        chunk_bytes=4096,
        port=free_port(),
        lease_timeout_s=1.0,
        lease_check_period_s=0.2,
        lease_renew_period_s=0.2,
        poll_retry_s=0.05,
        input_dir=str(tmp_path / "in"),
        work_dir=str(tmp_path / "work"),
        output_dir=str(tmp_path / "out"),
    )
    defaults.update(kw)
    return Config(**defaults)


def write_corpus(tmp_path, texts=TEXTS):
    d = tmp_path / "in"
    d.mkdir(exist_ok=True)
    for i, t in enumerate(texts):
        (d / f"doc-{i}.txt").write_bytes(t.encode())


def oracle(texts=TEXTS) -> dict:
    total = collections.Counter()
    for t in texts:
        total.update(reference_word_counts(t.encode()))
    return {w.encode(): c for w, c in total.items()}


def read_outputs(out_dir) -> dict:
    table = {}
    for p in sorted(pathlib.Path(out_dir).glob("mr-*.txt")):
        for line in p.read_bytes().splitlines():
            w, v = line.rsplit(b" ", 1)
            table[w] = int(v)
    return table


def output_bytes(out_dir) -> dict:
    return {
        p.name: p.read_bytes()
        for p in sorted(pathlib.Path(out_dir).glob("mr-*.txt"))
    }


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------

def test_parse_spec_round_trip():
    p = ChaosPlan.parse(
        "seed=7;pause:map:0:2.0;kill:reduce:1;slow_scan:w1:0.5;"
        "drop_finish:map:*:p=0.5;wedge_renewal:reduce:2:attempt=*"
    )
    assert p.seed == 7
    assert [f.site for f in p.faults] == [
        "pause", "kill", "slow_scan", "drop_finish", "wedge_renewal",
    ]
    pause, kill, slow, drop, wedge = p.faults
    assert (pause.phase, pause.tid, pause.seconds) == ("map", 0, 2.0)
    assert pause.attempt == 1  # default: a fault must not re-fire on the
    # recovery attempt and loop forever
    assert kill.attempt == 1
    assert slow.wid == 1 and slow.attempt is None  # slow on every attempt
    assert drop.tid is None and drop.p == 0.5
    assert wedge.attempt is None


@pytest.mark.parametrize("bad", [
    "",                           # no faults
    "seed=7",                     # seed only
    "explode:map:0",              # unknown site
    "pause:map:0",                # missing seconds
    "pause:map:zero:1.0",         # bad tid
    "pause:somewhere:0:1.0",      # bad phase
    "slow_scan:0:1.0",            # wid must be wN
    "kill:map:0:p=2.0",           # p out of range
    "kill:map:0:frob=1",          # unknown key
    "kill:map:0:attempt=x",       # non-numeric key value
    "kill:map:0:p=abc",
    "pause:map:0:-1.0",           # negative seconds
])
def test_parse_rejects_bad_specs(bad):
    # Every parse error is a chaos-prefixed message naming the element —
    # a typo'd spec must read as a spec problem, not a bare int() crash.
    with pytest.raises(ValueError, match="chaos:"):
        ChaosPlan.parse(bad)


def test_config_validates_chaos_spec_at_construction(tmp_path):
    with pytest.raises(ValueError):
        make_cfg(tmp_path, 1, chaos="explode:map:0")
    make_cfg(tmp_path, 1, chaos="pause:map:0:1.0")  # valid: no raise


def test_seeded_probability_match_is_reproducible():
    spec = "seed=11;drop_finish:map:*:p=0.5:attempt=*"
    picks1 = [
        ChaosPlan.parse(spec).pick("drop_finish", phase="map", tid=t, attempt=1)
        is not None
        for t in range(32)
    ]
    picks2 = [
        ChaosPlan.parse(spec).pick("drop_finish", phase="map", tid=t, attempt=1)
        is not None
        for t in range(32)
    ]
    assert picks1 == picks2                  # same seed → same victims
    assert 0 < sum(picks1) < 32              # and it actually samples
    other = [
        ChaosPlan.parse("seed=12;drop_finish:map:*:p=0.5:attempt=*")
        .pick("drop_finish", phase="map", tid=t, attempt=1) is not None
        for t in range(32)
    ]
    assert other != picks1                   # a different seed differs


def test_plan_records_fired_events():
    p = ChaosPlan.parse("seed=1;pause:map:0:0.5")
    assert p.pick("pause", phase="map", tid=1, attempt=1) is None
    assert p.pick("pause", phase="map", tid=0, attempt=1) is not None
    assert p.fired() == [{
        "site": "pause", "phase": "map", "tid": 0, "attempt": 1,
        "wid": None, "seconds": 0.5,
    }]


# ---------------------------------------------------------------------------
# In-process cluster harness
# ---------------------------------------------------------------------------

async def _cluster_timed(cfg, worker_cfgs, engine="host", timeout=90):
    """Coordinator + one Worker per cfg; returns (coord, workers,
    job_wall_s) where job_wall is measured when the coordinator marks the
    job done — a paused straggler unwinding after the job must not count.
    Not when serve() returns: on Python 3.12 its wait_closed() returns
    only after every connection has closed, the paused loser's included."""
    coord = Coordinator(cfg)
    serve = asyncio.create_task(coord.serve())
    await asyncio.sleep(0.1)
    ws = [Worker(c, engine=engine) for c in worker_cfgs]
    t0 = time.perf_counter()
    workers = asyncio.gather(*(w.run() for w in ws))
    deadline = t0 + timeout
    while (not coord.done() and not serve.done()
           and time.perf_counter() < deadline):
        await asyncio.sleep(0.01)
    job_wall = time.perf_counter() - t0
    await asyncio.wait_for(serve, timeout=timeout)
    await asyncio.wait_for(workers, timeout=timeout)
    return coord, ws, job_wall


# ---------------------------------------------------------------------------
# Tier-1: seeded chaos smoke (pause + SIGKILL, real OS processes)
# ---------------------------------------------------------------------------


def _chaos_oracle() -> dict:
    total = collections.Counter()
    for t in CHAOS_TEXTS:
        total.update(reference_word_counts(t))
    return {w.encode(): c for w, c in total.items()}


def test_chaos_smoke_pause_plus_sigkill(tmp_path):
    """The tier-1 chaos smoke (ISSUE 6 satellite): one seeded scenario
    combining a pause (slow-but-alive straggler) and a SIGKILL (dead
    worker) completes, and the output is BIT-IDENTICAL to the fault-free
    run of the same cluster binaries."""
    clean = chaos_cluster("clean", tmp_path, None, False)
    assert clean["recovered"]
    assert clean["outputs"], "fault-free run produced no outputs"
    assert read_outputs(pathlib.Path(clean["dir"]) / "out") == _chaos_oracle()

    chaos = chaos_cluster(
        "chaos", tmp_path, "seed=9;pause:map:0:0.8;kill:reduce:1", False
    )
    assert chaos["recovered"]
    assert chaos["outputs"] == clean["outputs"]
    # The kill left its mark in the control plane: the job report shows
    # the expiry + re-execution the recovery took.
    rep = json.loads(
        (pathlib.Path(chaos["dir"]) / "work" / "job_report.json").read_text()
    )["report"]
    assert sum(t.get("expiries", 0) for t in rep["totals"].values()) >= 1

    # mrcheck is the scenario's real oracle (ISSUE 7): "bytes matched"
    # above says nothing about a double-granted lease or a report
    # accepted after revoke — the protocol replay does. Both the
    # fault-free and the recovered run must be conformant.
    from mapreduce_rust_tpu.analysis.mrcheck import run_check

    for leg in (clean, chaos):
        doc = run_check(str(pathlib.Path(leg["dir"]) / "work"))
        assert doc["ok"], (leg["scenario"], doc["violations"])


def test_chaos_pipeline_kill_bit_identical(tmp_path):
    """ISSUE 17 satellite: the seeded kill:map SIGKILL under --sched
    pipeline, real OS processes. Per-partition reduce release must
    survive the mid-map re-execution (readiness retracted on expiry,
    re-established by the rerun) and stay BIT-IDENTICAL to the
    fault-free FIFO run of the same binaries — the A/B oracle across
    both the scheduler and the fault. mrcheck (early-reduce-grant
    included) replays both legs."""
    clean = chaos_cluster("clean", tmp_path, None, False)
    assert clean["recovered"]
    pipe = chaos_cluster(
        "kill-pipe", tmp_path, "seed=2;kill:map:1", False, sched="pipeline"
    )
    assert pipe["recovered"]
    assert pipe["outputs"] == clean["outputs"]
    assert read_outputs(pathlib.Path(pipe["dir"]) / "out") == _chaos_oracle()
    rep = json.loads(
        (pathlib.Path(pipe["dir"]) / "work" / "job_report.json").read_text()
    )["report"]
    # The artifact is stamped for offline consumers (fleet, doctor), and
    # the SIGKILL left the expiry + re-execution mark recovery took.
    assert rep.get("sched") == "pipeline"
    assert sum(t.get("expiries", 0) for t in rep["totals"].values()) >= 1
    from mapreduce_rust_tpu.analysis.mrcheck import run_check

    for leg in (clean, pipe):
        doc = run_check(str(pathlib.Path(leg["dir"]) / "work"))
        assert doc["ok"], (leg["scenario"], doc["violations"])


# ---------------------------------------------------------------------------
# Tier-1: speculation effectiveness + revocation (the acceptance race)
# ---------------------------------------------------------------------------

def _speculation_run(tmp_path, sub: str, speculate: bool):
    cfg = make_cfg(
        tmp_path, len(TEXTS),
        # Lease LONGER than the pause: without speculation the job must
        # sit out the full straggler pause (renewals keep the lease
        # alive), not recover via expiry — that is the stall speculation
        # exists to cut.
        lease_timeout_s=6.0,
        speculate=speculate, speculate_after_frac=0.5,
        work_dir=str(tmp_path / sub / "work"),
        output_dir=str(tmp_path / sub / "out"),
    )
    chaos_cfg = dataclasses.replace(cfg, chaos="pause:map:0:3.0")
    coord, ws, wall = asyncio.run(
        _cluster_timed(cfg, [chaos_cfg, cfg])
    )
    return coord, ws, wall, cfg


def test_speculation_beats_straggler_and_revokes_loser(tmp_path):
    """ISSUE 6 acceptance: the injected-straggler scenario with
    speculation ON finishes measurably faster than OFF (job wall time);
    the loser is revoked, skips its finish report, and the journal holds
    exactly one line per task; the doctor reports the effectiveness."""
    write_corpus(tmp_path)
    coord_on, ws_on, wall_on, cfg_on = _speculation_run(tmp_path, "on", True)
    coord_off, _ws, wall_off, cfg_off = _speculation_run(tmp_path, "off", False)

    # OFF stalls on the pause (~3 s); ON speculates around it.
    assert wall_off >= 2.5
    assert wall_on < wall_off - 0.8, (wall_on, wall_off)

    # Outputs bit-identical either way (and exact).
    assert output_bytes(cfg_on.output_dir) == output_bytes(cfg_off.output_dir)
    assert read_outputs(cfg_on.output_dir) == oracle()

    # The race is visible in the control plane: the speculated task won.
    rep = coord_on.stats()
    spec = rep["totals"]["map"]["speculation"]
    assert spec["attempts"] >= 1 and spec["won"] >= 1
    assert spec["time_saved_s"] > 0
    tid = next(
        t for t in rep["tasks"]["map"].values() if t["speculations"] >= 1
    )
    assert tid["grants"] >= 2 and tid["completed"]
    # The loser was revoked mid-pause and SKIPPED its report: no late
    # report landed for the speculated task.
    assert tid["late_reports"] == 0
    straggler = next(w for w in ws_on if w.chaos is not None)
    assert straggler.revoked_tasks, "the paused worker never saw revocation"

    # Journal: exactly one line per map task — the loser never journaled
    # a finish after revocation (ISSUE 6 satellite).
    journal = (
        pathlib.Path(cfg_on.work_dir) / "coordinator.journal"
    ).read_text().splitlines()
    for t in range(len(TEXTS)):
        assert sum(1 for ln in journal if ln.startswith(f"map {t} ")) == 1

    # The doctor turns the report into the speculation-effectiveness
    # finding (won/wasted attempts, estimated time saved).
    from mapreduce_rust_tpu.analysis.doctor import diagnose

    diag = diagnose({"kind": "job_report"}, job_report=rep)
    codes = [f["code"] for f in diag["findings"]]
    assert "speculation-effectiveness" in codes
    assert diag["speculation"]["won"] >= 1


def test_wasted_speculation_counted_when_original_wins(tmp_path):
    # The mirror race: the original finishes first, the speculative copy
    # is the loser — counted wasted, never won, outputs exact.
    cfg = make_cfg(tmp_path, 2, worker_n=1, speculate=True,
                   speculate_after_frac=0.1)
    write_corpus(tmp_path)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task(0) == 0
    assert c.get_map_task(0) == 1
    c.report_map_task_finish(1, 1, 0)
    # A second (idle) worker arrives and speculates task 0 …
    c.worker_count += 1
    assert c.get_map_task(1) == 0
    assert c.report.attempts("map", 0) == 2
    # … but the ORIGINAL attempt reports first.
    c.report_map_task_finish(0, 1, 0)
    spec = c.stats()["totals"]["map"]["speculation"]
    assert spec == {
        "attempts": 1, "won": 0, "wasted": 1, "time_saved_s": 0.0,
    }


# ---------------------------------------------------------------------------
# Slow: the full seeded scenario matrix, as OS processes, trace-merged
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_full_chaos_matrix_bit_identical(tmp_path):
    """Every SCENARIOS entry (worker pause, SIGKILL mid-task, dropped
    finish RPC, wedged renewal, one-slow-worker) completes with output
    bit-identical to the fault-free run — the ISSUE 6 acceptance
    criterion, against the real binaries."""
    from mapreduce_rust_tpu.analysis.mrcheck import run_check

    clean = chaos_cluster("clean", tmp_path, None, False)
    assert clean["recovered"] and clean["outputs"]
    assert read_outputs(pathlib.Path(clean["dir"]) / "out") == _chaos_oracle()
    assert run_check(str(pathlib.Path(clean["dir"]) / "work"))["ok"]
    for name, spec in SCENARIOS.items():
        r = chaos_cluster(name, tmp_path, spec,
                          speculate=(name == "slow_scan"))
        assert r["recovered"], name
        assert r["outputs"] == clean["outputs"], name
        # The zero-false-positive half of the ISSUE 7 acceptance: every
        # recovery path in the matrix replays conformant — expiries,
        # re-executions, revocations and drains are all LEGAL transitions
        # and must not trip the checker.
        doc = run_check(str(pathlib.Path(r["dir"]) / "work"))
        assert doc["ok"], (name, doc["violations"])


@pytest.mark.slow
def test_speculation_race_visible_in_merged_trace(tmp_path):
    """Speculation ON under the slow-worker scenario, with tracing: the
    merged timeline carries BOTH attempt chains of the speculated task
    (the winner's and the revoked loser's), and the coordinator manifest
    yields the doctor's speculation-effectiveness finding."""
    from mapreduce_rust_tpu.runtime.trace import load_trace, merge_traces

    # A longer slow-scan than the canonical scenario: under heavy machine
    # load the speculative grant can itself arrive seconds late, and the
    # WINNER of the race must stay deterministic for the chain asserts.
    r = chaos_cluster(
        "spec", tmp_path, "seed=5;slow_scan:w0:6.0", speculate=True,
        trace=True,
    )
    assert r["recovered"]
    root = pathlib.Path(r["dir"])
    traces = [root / "trace-coord.json"] + [
        p for p in sorted(root.glob("trace-w*.json"))
        if ".partial" not in p.name
    ]
    assert len(traces) == 3
    merged = root / "merged.json"
    merge_traces(str(merged), [str(p) for p in traces])
    events, _md = load_trace(str(merged))
    chains: dict = {}
    for e in events:
        if e.get("ph") in ("s", "t", "f"):
            chains.setdefault(e["id"], set()).add(e["ph"])
    rep = json.loads(
        (root / "work" / "job_report.json").read_text()
    )["report"]
    spec_tasks = [
        (phase, t)
        for phase, tasks in rep["tasks"].items()
        for t, d in tasks.items() if d.get("speculations", 0) >= 1
    ]
    assert spec_tasks, "no task was speculated"
    phase, t = spec_tasks[0]
    # Both attempts of the speculated task are full chains in the ONE
    # merged timeline: the winner finished via the coordinator, the
    # revoked loser terminated its own chain at revocation.
    assert chains.get(f"{phase}:{t}:1") == {"s", "t", "f"}
    assert chains.get(f"{phase}:{t}:2") == {"s", "t", "f"}
    revoked = [
        e for e in events
        if e.get("ph") == "f" and (e.get("args") or {}).get("revoked")
    ]
    assert revoked, "the losing attempt never marked its revocation"

    from mapreduce_rust_tpu.analysis.doctor import diagnose
    from mapreduce_rust_tpu.runtime.telemetry import load_manifest

    diag = diagnose(load_manifest(str(root / "manifest-coord.json")))
    assert "speculation-effectiveness" in [
        f["code"] for f in diag["findings"]
    ]
    assert diag["speculation"]["won"] >= 1
