"""Control plane: scheduler semantics, sentinels, leases, fault injection.

In-process asyncio (coordinator server + worker clients as tasks) with the
host engine, so these run fast and without device compiles. Reference
behavior: src/mr/coordinator.rs, src/bin/mrworker.rs.
"""

import asyncio
import collections
import pathlib
import socket


from mapreduce_rust_tpu.apps import InvertedIndex, TopK
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.coordinator.server import (
    DONE,
    NOT_READY,
    WAIT,
    Coordinator,
    CoordinatorClient,
)
from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.worker.runtime import Worker

TEXTS = [
    "the quick brown fox jumps over the lazy dog " * 30,
    "pack my box with five dozen liquor jugs don’t stop " * 20,
    "sphinx of black quartz judge my vow " * 25,
]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


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


def read_outputs(cfg) -> dict:
    table = {}
    for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt")):
        for line in p.read_bytes().splitlines():
            w, v = line.rsplit(b" ", 1)
            table[w] = int(v)
    return table


# ---- scheduler unit semantics ----

def test_sentinels_and_barrier(tmp_path):
    cfg = make_cfg(tmp_path, 2, worker_n=2)
    c = Coordinator(cfg)
    # registration barrier: no tasks before worker_n registrations
    assert c.get_map_task() == NOT_READY
    assert c.get_worker_id() == 0
    assert c.get_map_task() == NOT_READY
    assert c.get_worker_id() == 1
    # extra worker refused, not a panic (reference asserts, coordinator.rs:220)
    assert c.get_worker_id() == DONE
    # fresh ids then straggler wait
    assert c.get_map_task() == 0
    assert c.get_map_task() == 1
    assert c.get_map_task() == WAIT
    # reduce gated until map finishes (coordinator.rs:183-185)
    assert c.get_reduce_task() == NOT_READY
    assert not c.report_map_task_finish(0)
    assert c.report_map_task_finish(1)
    assert c.map.finished
    assert c.get_map_task() == DONE
    assert c.get_reduce_task() == 0


def test_stale_renewal_returns_false_not_crash(tmp_path):
    cfg = make_cfg(tmp_path, 1, worker_n=1)
    c = Coordinator(cfg)
    c.get_worker_id()
    tid = c.get_map_task()
    assert c.renew_map_lease(tid) is True
    c.report_map_task_finish(tid)
    # the renewal-vs-report race (coordinator.rs:125): stale renewal is a no
    assert c.renew_map_lease(tid) is False


def test_lease_expiry_recycles_task(tmp_path):
    cfg = make_cfg(tmp_path, 1, worker_n=1, lease_timeout_s=0.0)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task() == 0
    assert c.get_map_task() == WAIT
    c.check_lease()  # deadline passed immediately (timeout 0)
    assert c.get_map_task() == 0  # re-granted
    c.report_map_task_finish(0)
    assert c.map.finished


def test_job_report_counts_expiry_and_reexecution(tmp_path):
    # Unit version of the fault-report contract: a lease expiry followed by
    # a re-grant shows up as expiries >= 1 and re_executions >= 1 on that
    # task, with a duration once it completes (ISSUE 1 acceptance).
    cfg = make_cfg(tmp_path, 1, worker_n=1, lease_timeout_s=0.0)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task() == 0
    c.check_lease()  # timeout 0: the lease is already stale
    assert c.get_map_task() == 0  # re-granted
    assert c.renew_map_lease(0) is True
    c.report_map_task_finish(0)
    assert c.renew_map_lease(0) is False  # stale renewal, counted separately
    t = c.stats()["tasks"]["map"]["0"]
    assert t["grants"] == 2
    assert t["re_executions"] == 1
    assert t["expiries"] == 1
    assert t["renewals"] == 1 and t["stale_renewals"] == 1
    assert t["completed"] and t["duration_s"] >= 0



# ---- pipelined scheduler (ISSUE 17): per-partition reduce release ----

def test_pipeline_reduce_gated_on_partition_readiness(tmp_path):
    """--sched pipeline: before the barrier, reduce polls are gated on
    per-partition readiness (NOT_READY, same sentinel as the classic
    gate) — a partition is grantable only once EVERY map task reported
    bytes for it, and becoming ready logs the part_ready evidence
    mrcheck's early-reduce-grant invariant replays."""
    cfg = make_cfg(tmp_path, 2, worker_n=1, sched="pipeline")
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task() == 0
    assert c.get_map_task() == 1
    # Nothing reported: no partition can be ready.
    assert c.get_reduce_task() == NOT_READY
    assert c.reduce_ready_backlog() == 0
    # First map reports bytes for all three partitions — coverage is
    # still partial (map 1 outstanding), so nothing is released.
    c.report_map_task_finish(0, part_bytes=[1, 2, 3])
    assert c.get_reduce_task() == NOT_READY
    assert c.reduce_ready_backlog() == 0
    assert not any(e["ev"] == "part_ready" for e in c.report.events())
    # Second map reports: every partition reaches full coverage, the
    # backlog surfaces (the service scheduler's scoring input) and the
    # grant path serves readiness-eligible ids.
    c.report_map_task_finish(1, part_bytes=[1, 2, 3])
    assert c.reduce_ready_backlog() == cfg.reduce_n
    ready_evs = [e for e in c.report.events() if e["ev"] == "part_ready"]
    assert sorted(e["tid"] for e in ready_evs) == list(range(cfg.reduce_n))
    assert c.get_reduce_task() == 0
    assert c.reduce_ready_backlog() == cfg.reduce_n - 1


def test_pipeline_readiness_retract_and_reestablish(tmp_path):
    """The retraction path (ISSUE 17): when a map attempt's coverage is
    withdrawn (the expiry → re-execution protocol), every partition it
    pushed to full coverage drops out of the grantable set with a
    part_retract event, and the re-executed report re-establishes it.
    Driven directly — with tid-keyed leases a reported map can never
    expire, so the path is structurally defensive today, but the replay
    evidence contract (retract net of re-establish) is load-bearing for
    mrcheck and must hold."""
    cfg = make_cfg(tmp_path, 2, worker_n=1, reduce_n=2, sched="pipeline")
    c = Coordinator(cfg)
    c._record_readiness(0, [1, 1])
    c._record_readiness(1, [1, 1])
    assert c._parts_ready == {0, 1}
    c._retract_readiness(0)
    assert c._parts_ready == set()
    assert [e["tid"] for e in c.report.events()
            if e["ev"] == "part_retract"] == [0, 1]
    # Re-execution reports again: full coverage re-established.
    c._record_readiness(0, [1, 1])
    assert c._parts_ready == {0, 1}
    # Malformed remote input is dropped whole, never partially folded.
    c._retract_readiness(1)
    c._record_readiness(1, [1, "nan"])
    assert c._parts_ready == set()


def test_cluster_pipeline_bit_identical_to_fifo(tmp_path):
    """End-to-end A/B oracle (ISSUE 17 acceptance, in-process edition):
    the same corpus through --sched fifo and --sched pipeline produces
    BIT-IDENTICAL output files, the pipelined report carries the sched
    stamp offline consumers key on, and both runs replay clean under
    mrcheck (early-reduce-grant included)."""
    write_corpus(tmp_path)
    outs, coords, cfgs = {}, {}, {}
    for sched in ("fifo", "pipeline"):
        cfg = make_cfg(
            tmp_path, len(TEXTS), worker_n=2, sched=sched,
            work_dir=str(tmp_path / sched / "work"),
            output_dir=str(tmp_path / sched / "out"),
        )
        coord, _ws = asyncio.run(_run_cluster(cfg, 2))
        outs[sched] = {
            p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
        }
        coords[sched], cfgs[sched] = coord, cfg
    assert outs["pipeline"] == outs["fifo"]
    assert read_outputs(cfgs["pipeline"]) == oracle()
    rep = coords["pipeline"].report
    assert rep.sched == "pipeline"
    assert rep.to_dict().get("sched") == "pipeline"
    # FIFO artifacts stay byte-identical to the pre-sched wire format.
    assert "sched" not in coords["fifo"].report.to_dict()
    from mapreduce_rust_tpu.analysis.mrcheck import run_check

    for sched, cfg in cfgs.items():
        doc = run_check(cfg.work_dir)
        assert doc["ok"], (sched, doc["violations"])


def test_stats_rpc_over_socket(tmp_path):
    # The 8th RPC rides the same JSON transport as the sentinels and
    # reflects the live scheduler state, including server-side RPC latency.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)

    async def go():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        client = CoordinatorClient(cfg.host, cfg.port)
        await client.connect()
        try:
            assert await client.call("get_worker_id") == 0
            tid = await client.call("get_map_task")
            assert tid == 0
            rep = await client.call("stats")
            assert rep["tasks"]["map"][str(tid)]["grants"] == 1
            assert rep["tasks"]["map"][str(tid)]["completed"] is False
            assert rep["rpc"]["get_map_task"]["count"] == 1
            assert rep["rpc"]["get_worker_id"]["max_ms"] >= 0
        finally:
            await client.close()
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)

    asyncio.run(go())


def test_worker_report_records_tasks_and_rpc_latency(tmp_path):
    # The worker keeps its own (client-observed) view: tasks it ran and
    # the round-trip latency of every RPC it made.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)
    _coord, ws = asyncio.run(_run_cluster(cfg, 1))
    rep = ws[0].report.to_dict()
    assert rep["totals"]["map"]["completed"] == len(TEXTS)
    assert rep["totals"]["reduce"]["completed"] == cfg.reduce_n
    for method in ("get_map_task", "report_map_task_finish",
                   "get_reduce_task", "report_reduce_task_finish"):
        assert rep["rpc"][method]["count"] >= 1


def test_duplicate_finish_is_idempotent_and_counted_late(tmp_path):
    # ISSUE 4 satellite: original + re-executed worker both reporting the
    # same tid used to double-journal and double-count — now the duplicate
    # is a distinct late_reports stat, the journal gets exactly one line,
    # and the recorded duration stays the FIRST completion's.
    cfg = make_cfg(tmp_path, 2, worker_n=1)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task() == 0
    assert not c.report_map_task_finish(0, 1)
    t = c.stats()["tasks"]["map"]["0"]
    first_duration = t["duration_s"]
    assert t["reports"] == 1 and t["late_reports"] == 0
    # The duplicate (a re-executed straggler's report).
    assert not c.report_map_task_finish(0, 2)
    t = c.stats()["tasks"]["map"]["0"]
    assert t["reports"] == 1          # not double-counted
    assert t["late_reports"] == 1     # counted as its own thing
    assert t["duration_s"] == first_duration
    assert c.stats()["totals"]["map"]["late_reports"] == 1
    journal = pathlib.Path(cfg.work_dir) / "coordinator.journal"
    lines = journal.read_text().splitlines()
    # Journaled exactly once — and the line carries the mrcheck context
    # annotations (winning attempt, reporting wid, report-clock time).
    wins = [ln for ln in lines if ln.startswith("map 0 ")]
    assert len(wins) == 1
    assert wins[0].split()[2:4] == ["a1", "w-1"]


def test_progress_view_tracks_lease_liveness(tmp_path):
    # The stats RPC's progress view: per-phase issued/done/in-flight/
    # expired plus lease liveness from renewal recency (ISSUE 4 tentpole).
    cfg = make_cfg(tmp_path, 3, worker_n=1)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task() == 0
    assert c.get_map_task() == 1
    c.report_map_task_finish(0, 1)
    p = c.progress()
    assert p["phase"] == "map" and p["done"] is False
    assert p["workers"]["registered"] == 1 and p["workers"]["expected"] == 1
    # Anonymous (wid-less) callers never fabricate a per-worker block.
    assert "workers" not in c.report.to_dict()
    m = p["phases"]["map"]
    assert m["tasks_total"] == 3 and m["issued"] == 2
    assert m["done"] == 1 and m["in_flight"] == 1 and m["pending"] == 1
    lease = m["leases"]["1"]
    assert lease["attempt"] == 1 and lease["live"] is True
    assert lease["lease_remaining_s"] > 0
    # An expiry shows up in the per-phase counter and frees the lease.
    c.map.leases[1] = 0.0  # force staleness
    c.check_lease()
    m = c.progress()["phases"]["map"]
    assert m["expired"] == 1 and m["in_flight"] == 0 and m["pending"] == 2
    # Fresh ids first (the reference grant order), then the expired task
    # re-grants — and the view reports its bumped attempt.
    assert c.get_map_task() == 2
    assert c.get_map_task() == 1
    assert c.progress()["phases"]["map"]["leases"]["1"]["attempt"] == 2
    # format_progress renders it (the watch view).
    from mapreduce_rust_tpu.runtime.telemetry import format_progress

    text = format_progress(c.stats())
    assert "phase map" in text and "1 expired" in text
    assert "attempt 2" in text


def test_per_worker_wid_attribution(tmp_path):
    # ISSUE 5 satellite (PR 4 leftover): grants, renewals and finishes
    # carry the worker id, so the stats/progress view grows a per-worker
    # column and the doctor's straggler pass has per-worker duration
    # histograms to compare.
    cfg = make_cfg(tmp_path, 3, worker_n=2)
    c = Coordinator(cfg)
    c.get_worker_id()
    c.get_worker_id()
    assert c.get_map_task(0) == 0
    assert c.get_map_task(1) == 1
    assert c.renew_map_lease(0, 0) is True
    assert c.renew_map_lease(1, 1) is True
    c.report_map_task_finish(0, 1, 0)
    c.report_map_task_finish(1, 1, 1)
    rep = c.stats()
    # Per-task rows name their worker; the workers block aggregates.
    assert rep["tasks"]["map"]["0"]["wid"] == 0
    assert rep["tasks"]["map"]["1"]["wid"] == 1
    w0, w1 = rep["workers"]["0"], rep["workers"]["1"]
    assert w0["grants"] == 1 and w0["reports"] == 1 and w0["renewals"] == 1
    assert w1["grants"] == 1 and w1["reports"] == 1
    # Attempt durations landed in the per-worker histogram (seconds).
    assert w0["task_s"]["count"] == 1 and w0["task_s"]["p50"] >= 0
    # Phase totals carry the fleet-wide attempt-duration distribution —
    # the doctor's lease-tuning input.
    assert rep["totals"]["map"]["task_s"]["count"] == 2
    # The stats response carries the per-worker block exactly once (the
    # top-level "workers" from JobReport.to_dict — progress() does not
    # duplicate it), and watch renders it as the per-worker column.
    assert "by_worker" not in rep["progress"]["workers"]
    from mapreduce_rust_tpu.runtime.telemetry import format_progress

    text = format_progress(rep)
    assert "w0:" in text and "w1:" in text


def test_rpc_latency_percentiles_in_stats(tmp_path):
    # record_rpc is histogram-backed: the stats RPC serves p50/p95/p99
    # beside the legacy count/mean/max keys.
    cfg = make_cfg(tmp_path, 1, worker_n=1)
    c = Coordinator(cfg)
    for ms in (1, 2, 3, 50):
        c.report.record_rpc("get_map_task", ms / 1e3)
    r = c.stats()["rpc"]["get_map_task"]
    assert r["count"] == 4
    assert r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"] <= r["max_ms"] + 1e-9
    assert 25 <= r["max_ms"] <= 75
    assert r["hist"]["count"] == 4  # mergeable raw form rides along


def test_rpc_timeout_surfaces_wedged_coordinator(tmp_path):
    # ISSUE 4 satellite: a wedged coordinator (accepts, never answers)
    # used to block a worker forever inside readline. With
    # Config.rpc_timeout_s the call raises RpcTimeout — a RuntimeError,
    # NOT a ConnectionError, so the worker's "coordinator gone = job
    # done" path can never mistake a wedge for success.
    import pytest

    from mapreduce_rust_tpu.coordinator.server import RpcTimeout

    async def go():
        release = asyncio.Event()

        async def wedged(reader, writer):
            await release.wait()  # accept, read nothing, answer nothing
            writer.close()  # wait_closed() below waits for this connection

        server = await asyncio.start_server(wedged, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = CoordinatorClient("127.0.0.1", port, timeout_s=0.2)
        await client.connect()
        t0 = asyncio.get_running_loop().time()
        try:
            with pytest.raises(RpcTimeout, match="wedged"):
                await client.call("get_map_task")
            assert asyncio.get_running_loop().time() - t0 < 5.0
            assert not isinstance(RpcTimeout("x"), ConnectionError)
        finally:
            await client.close()
            release.set()
            server.close()
            await server.wait_closed()

    asyncio.run(go())


def test_grant_response_carries_attempt_and_clock(tmp_path):
    # The RPC plane still moves small integers, but the envelope now
    # carries the coordinator's monotonic `now` (ClockSync samples it)
    # and, on grants, the attempt number for flow linkage.
    from mapreduce_rust_tpu.coordinator.server import ClockSync

    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)

    async def go():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        sync = ClockSync()
        client = CoordinatorClient(cfg.host, cfg.port, timeout_s=5.0, sync=sync)
        await client.connect()
        try:
            await client.call("get_worker_id")
            tid = await client.call("get_map_task")
            assert tid == 0 and client.last_attempt == 1
            best = sync.best()
            assert best["samples"] >= 2 and best["rtt_s"] >= 0
            # Same-host perf_counter clocks agree: the measured offset is
            # bounded by the round trip itself (plus scheduler noise).
            assert abs(best["offset_s"]) <= best["rtt_s"] + 0.05
        finally:
            await client.close()
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)

    asyncio.run(go())


def test_watch_once_renders_live_progress(tmp_path, capsys):
    # The watch subcommand: one poll against a live coordinator renders
    # the plain-text job view and exits 0.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)

    async def go():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        client = CoordinatorClient(cfg.host, cfg.port)
        await client.connect()
        await client.call("get_worker_id")
        rc = await asyncio.get_running_loop().run_in_executor(None, _watch_once, cfg)
        await client.close()
        serve.cancel()
        await asyncio.gather(serve, return_exceptions=True)
        return rc

    def _watch_once(cfg):
        import subprocess
        import sys

        return subprocess.run(
            [sys.executable, "-m", "mapreduce_rust_tpu", "watch",
             "--port", str(cfg.port), "--once"],
            capture_output=True, text=True, timeout=30,
            env={"PYTHONPATH": str(pathlib.Path(__file__).resolve().parent.parent),
                 "PATH": "/usr/bin:/bin"},
        )

    r = asyncio.run(go())
    assert r.returncode == 0, r.stderr
    assert "coordinator: phase map" in r.stdout
    assert "workers 1/2" in r.stdout


def test_watch_without_coordinator_fails_cleanly():
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-m", "mapreduce_rust_tpu", "watch",
         "--port", str(free_port()), "--once", "--connect-retries", "1"],
        capture_output=True, text=True, timeout=30,
        env={"PYTHONPATH": str(pathlib.Path(__file__).resolve().parent.parent),
             "PATH": "/usr/bin:/bin"},
    )
    assert r.returncode == 1
    assert "no coordinator" in r.stderr


# ---- end-to-end over real sockets ----

async def _run_cluster(cfg, n_workers, app=None, engine="host"):
    coord = Coordinator(cfg)
    serve = asyncio.create_task(coord.serve())
    await asyncio.sleep(0.1)

    ws = [Worker(cfg, app=app, engine=engine) for _ in range(n_workers)]
    workers = [asyncio.create_task(w.run()) for w in ws]
    await asyncio.wait_for(asyncio.gather(*workers), timeout=60)
    await asyncio.wait_for(serve, timeout=30)
    return coord, ws


def test_cluster_word_count_end_to_end(tmp_path):
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)
    asyncio.run(_run_cluster(cfg, 2))
    assert read_outputs(cfg) == oracle()


def test_cluster_survives_worker_death(tmp_path):
    # Both workers register (worker_n=2 barrier) and claim tasks; one dies
    # mid-task. Its lease must expire, the task re-grant to the survivor,
    # and the job complete with exact results (SURVEY.md §3-D).
    # Deterministic kill window (the old in_flight() gate raced: a victim
    # killed between its report RPC landing and the client-side record
    # completed the job with zero expiries): the victim signals from
    # INSIDE its map task and stalls past the lease timeout, so it always
    # dies holding an unreported lease.
    import threading
    import time as _time

    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)
    started = threading.Event()

    class SlowMapVictim(Worker):
        def run_map_task(self, tid: int) -> None:
            started.set()
            _time.sleep(1.5)  # long past the 1.0 s lease timeout
            super().run_map_task(tid)

    async def cluster():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        victim = asyncio.create_task(SlowMapVictim(cfg, engine="host").run())
        survivor = asyncio.create_task(Worker(cfg, engine="host").run())
        deadline = asyncio.get_running_loop().time() + 30
        while not started.is_set():
            assert asyncio.get_running_loop().time() < deadline, \
                "victim never started a map task"
            await asyncio.sleep(0.02)
        victim.cancel()
        await asyncio.gather(victim, return_exceptions=True)
        await asyncio.wait_for(survivor, timeout=60)
        await asyncio.wait_for(serve, timeout=30)
        return coord

    coord = asyncio.run(cluster())
    assert read_outputs(cfg) == oracle()
    # The fault is VISIBLE in the control-plane job report: the victim's
    # task (whichever phase it held a lease in when killed) shows >= 1
    # lease expiry and a re-execution, and the report agrees with the
    # scheduler that everything completed.
    rep = coord.stats()
    total_expiries = sum(t["expiries"] for t in rep["totals"].values())
    total_reexec = sum(t["re_executions"] for t in rep["totals"].values())
    assert total_expiries >= 1
    assert total_reexec >= 1
    reexecuted = [
        t for phase in rep["tasks"].values() for t in phase.values()
        if t["re_executions"] >= 1
    ]
    assert reexecuted and all(t["expiries"] >= 1 for t in reexecuted)
    for phase in rep["tasks"].values():
        for t in phase.values():
            assert t["completed"] and t["duration_s"] >= 0
    # done() dumped the same report to disk for post-hoc probes.
    import json

    dumped = json.loads(
        (pathlib.Path(cfg.work_dir) / "job_report.json").read_text()
    )
    assert sum(
        t["expiries"] for t in dumped["report"]["totals"].values()
    ) >= 1


def test_straggler_late_report_after_regrant(tmp_path):
    # A slow-but-alive straggler whose map task was re-granted reports
    # LATE (VERDICT r4 weak 6; reference hazard coordinator.rs:148-157).
    # The late report is a genuine completion — outputs are idempotent and
    # written temp+rename — so the phase may flip on it, but the scheduler
    # must stay consistent: the replacement's renewal degrades to a clean
    # False, its own report is a no-op, and reduce proceeds.
    cfg = make_cfg(tmp_path, 2, worker_n=2, lease_timeout_s=0.0)
    c = Coordinator(cfg)
    c.get_worker_id()
    c.get_worker_id()
    assert c.get_map_task() == 0  # straggler A takes task 0
    assert c.get_map_task() == 1  # B takes task 1 and finishes promptly
    assert not c.report_map_task_finish(1)
    c.check_lease()  # A's lease (timeout 0) expires; task 0 recycled
    assert c.get_map_task() == 0  # re-granted to B (the replacement)
    # A's late report arrives while B is still re-executing task 0.
    assert c.report_map_task_finish(0)
    assert c.map.finished  # sane flip: the task genuinely completed
    # B's renewal of its superseded lease: clean False, never a crash.
    assert c.renew_map_lease(0) is False
    # B's own (duplicate) completion report is a harmless no-op.
    assert c.report_map_task_finish(0)
    assert c.get_map_task() == DONE
    assert c.get_reduce_task() == 0  # phase gate open, reduce proceeds


def test_cluster_survives_worker_death_mid_reduce(tmp_path):
    # Kill a worker while it HOLDS A REDUCE LEASE (the round-4 fault-test
    # gap: the existing death test kills during map only). The victim's
    # reduce task must expire and re-grant to the survivor; results exact.
    # The victim's still-running executor thread doubles as the
    # paused-not-dead writer of SURVEY.md §3-D: it finishes its reduce in
    # the background and its atomic rewrite must not corrupt the output.
    import threading
    import time as _time

    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)
    # threading.Event, not asyncio.Event: run_reduce_task executes on an
    # executor THREAD, where asyncio.Event.set() is not thread-safe.
    started = threading.Event()

    class SlowReduceWorker(Worker):
        def run_reduce_task(self, tid: int) -> None:
            started.set()
            _time.sleep(1.5)  # long past the 1.0 s lease timeout
            super().run_reduce_task(tid)

    class SurvivorWorker(Worker):
        def run_reduce_task(self, tid: int) -> None:
            # Don't let the fast survivor sweep all reduce tasks before
            # the victim claims one — the kill window must be guaranteed,
            # not a scheduling race. (Runs on an executor thread: blocking
            # here never starves the event loop or the lease renewals.)
            started.wait(timeout=20)
            super().run_reduce_task(tid)

    async def cluster():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        victim_w = SlowReduceWorker(cfg, engine="host")
        victim = asyncio.create_task(victim_w.run())
        survivor = asyncio.create_task(SurvivorWorker(cfg, engine="host").run())
        # Deterministic: wait until the victim is INSIDE a reduce task
        # (holding its lease), then kill it mid-flight.
        deadline = asyncio.get_running_loop().time() + 30
        while not started.is_set():
            assert asyncio.get_running_loop().time() < deadline, "victim never reduced"
            await asyncio.sleep(0.02)
        assert coord.map.finished
        victim.cancel()
        await asyncio.gather(victim, return_exceptions=True)
        await asyncio.wait_for(survivor, timeout=60)
        await asyncio.wait_for(serve, timeout=30)

    asyncio.run(cluster())
    assert read_outputs(cfg) == oracle()


def test_cluster_inverted_index(tmp_path):
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)
    asyncio.run(_run_cluster(cfg, 2, app=InvertedIndex()))
    want: dict = {}
    for d, t in enumerate(TEXTS):
        for w in reference_word_counts(t.encode()):
            want.setdefault(w.encode(), set()).add(d)
    got = {}
    for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt")):
        for line in p.read_bytes().splitlines():
            w, v = line.rsplit(b" ", 1)
            got[w] = set(int(x) for x in v.split(b","))
    assert got == want


def test_cluster_top_k_candidates_then_merge(tmp_path):
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)
    app = TopK(k=5)
    asyncio.run(_run_cluster(cfg, 1, app=app))
    lines = []
    for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt")):
        lines.extend(p.read_bytes().splitlines())
    top = app.merge_lines(lines)
    want = sorted(oracle().items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    assert top == [b"%s %d" % (w, c) for w, c in want]


def test_cluster_device_engine_inverted_index(tmp_path):
    # Device-engine map tasks must stamp GLOBAL doc ids (task id), not 0.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1,
                   merge_capacity=1 << 12, device="cpu")
    asyncio.run(_run_cluster(cfg, 1, app=InvertedIndex(), engine="device"))
    want: dict = {}
    for d, t in enumerate(TEXTS):
        for w in reference_word_counts(t.encode()):
            want.setdefault(w.encode(), set()).add(d)
    got = {}
    for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt")):
        for line in p.read_bytes().splitlines():
            w, v = line.rsplit(b" ", 1)
            got[w] = set(int(x) for x in v.split(b","))
    assert got == want


def test_journal_resume_skips_completed_maps(tmp_path):
    # Run a full job, wipe ONLY the reduce outputs + reduce journal lines,
    # restart the cluster: maps must not re-run (spill mtimes unchanged),
    # reduce regenerates identical output from the materialized spills —
    # the phase-checkpoint story (SURVEY.md §5 checkpoint row).
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)
    asyncio.run(_run_cluster(cfg, 1))
    want = read_outputs(cfg)

    journal = pathlib.Path(cfg.work_dir) / "coordinator.journal"
    lines = [
        ln for ln in journal.read_text().splitlines()
        if ln.startswith(("job ", "map "))
    ]
    journal.write_text("\n".join(lines) + "\n")
    for p in pathlib.Path(cfg.output_dir).glob("mr-*.txt"):
        p.unlink()
    spill_mtimes = {
        p.name: p.stat().st_mtime_ns
        for p in pathlib.Path(cfg.work_dir).glob("mr-*.npz")
    }

    cfg2 = make_cfg(tmp_path, len(TEXTS), worker_n=1, port=free_port())
    asyncio.run(_run_cluster(cfg2, 1))
    assert read_outputs(cfg2) == want == oracle()
    after = {
        p.name: p.stat().st_mtime_ns
        for p in pathlib.Path(cfg.work_dir).glob("mr-*.npz")
    }
    assert after == spill_mtimes  # maps were not re-executed


def test_journal_replay_unit(tmp_path):
    cfg = make_cfg(tmp_path, 3, worker_n=1)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task() == 0
    c.report_map_task_finish(0)
    assert c.get_map_task() == 1
    c.report_map_task_finish(1)
    # restart: tasks 0,1 journaled; only task 2 should be granted
    c2 = Coordinator(cfg)
    c2.get_worker_id()
    assert c2.get_map_task() == 2
    assert c2.get_map_task() == WAIT
    assert c2.report_map_task_finish(2)
    assert c2.map.finished


def test_journal_shape_mismatch_ignored(tmp_path):
    cfg = make_cfg(tmp_path, 3, worker_n=1)
    c = Coordinator(cfg)
    c.get_worker_id()
    c.report_map_task_finish(c.get_map_task())
    # Different job shape in the same work_dir: journal must be ignored.
    cfg2 = make_cfg(tmp_path, 2, worker_n=1, reduce_n=2, port=free_port())
    c2 = Coordinator(cfg2)
    c2.get_worker_id()
    assert c2.get_map_task() == 0  # starts from scratch


def test_cli_run_single_process(tmp_path, capsys):
    write_corpus(tmp_path)
    from mapreduce_rust_tpu.__main__ import main

    rc = main([
        "run", "--input", str(tmp_path / "in"), "--output", str(tmp_path / "out"),
        "--chunk-mb", "0.01", "--device", "cpu", "--reduce-n", "3",
    ])
    assert rc == 0
    cfg = make_cfg(tmp_path, len(TEXTS))
    assert read_outputs(cfg) == oracle()


def test_cli_coordinator_worker_subprocesses(tmp_path):
    """The README quickstart, literally: coordinator + 2 workers as OS
    processes over TCP (reference src/bin/* usage)."""
    import subprocess
    import sys

    write_corpus(tmp_path)
    port = str(free_port())
    common = [
        "--input", str(tmp_path / "in"), "--output", str(tmp_path / "out"),
        "--work", str(tmp_path / "work"), "--port", port, "--reduce-n", "3",
    ]
    repo_root = str(pathlib.Path(__file__).resolve().parent.parent)
    env = {"PYTHONPATH": repo_root, "PATH": "/usr/bin:/bin"}
    coord = subprocess.Popen(
        [sys.executable, "-m", "mapreduce_rust_tpu", "coordinator", "--worker-n", "2", *common],
        env=env,
    )
    workers = [
        subprocess.Popen(
            [sys.executable, "-m", "mapreduce_rust_tpu", "worker", "--engine", "host", *common],
            env=env,
        )
        for _ in range(2)
    ]
    try:
        for w in workers:
            assert w.wait(timeout=60) == 0
        assert coord.wait(timeout=30) == 0
    finally:
        for p in [coord, *workers]:
            if p.poll() is None:
                p.kill()
    cfg = make_cfg(tmp_path, len(TEXTS))
    assert read_outputs(cfg) == oracle()


# ---- speculation, revocation, drain, backoff (ISSUE 6) ----

def test_speculation_grants_slowest_inflight_near_phase_end(tmp_path):
    cfg = make_cfg(tmp_path, 2, worker_n=2, speculate=True,
                   speculate_after_frac=0.5)
    c = Coordinator(cfg)
    c.get_worker_id()
    c.get_worker_id()
    assert c.get_map_task(0) == 0
    assert c.get_map_task(1) == 1
    # Below the arm fraction: the idle worker just waits.
    assert c.get_map_task(1) == WAIT
    c.report_map_task_finish(1, 1, 1)   # 1/2 done = the arm fraction
    # Now the idle worker's poll turns into a speculative attempt 2 …
    assert c.get_map_task(1) == 0
    assert c.report.attempts("map", 0) == 2
    # … capped at speculate_max_attempts (2): no third copy.
    assert c.get_map_task(1) == WAIT
    # First finish wins (the speculative attempt), the race is accounted.
    assert c.report_map_task_finish(0, 2, 1)
    spec = c.stats()["totals"]["map"]["speculation"]
    assert spec["attempts"] == 1 and spec["won"] == 1
    assert spec["wasted"] == 0 and spec["time_saved_s"] > 0
    assert c.stats()["tasks"]["map"]["0"]["speculations"] == 1
    # The loser's renewal degrades to False — and the task IS reported,
    # which is what the RPC envelope surfaces to the worker as revoked.
    assert c.renew_map_lease(0, 0) is False
    assert 0 in c.map.reported
    # Exactly one journal line for the raced task — attributed to the
    # winning (speculative) attempt.
    journal = pathlib.Path(cfg.work_dir) / "coordinator.journal"
    wins = [
        ln for ln in journal.read_text().splitlines()
        if ln.startswith("map 0 ")
    ]
    assert len(wins) == 1
    assert wins[0].split()[2] == "a2"


def test_speculation_never_duplicates_to_the_holder(tmp_path):
    # The worker already running the task must not be handed a second
    # copy of it — and anonymous (wid-less) pollers get none at all.
    cfg = make_cfg(tmp_path, 2, worker_n=1, speculate=True,
                   speculate_after_frac=0.5)
    c = Coordinator(cfg)
    c.get_worker_id()
    assert c.get_map_task(0) == 0
    assert c.get_map_task(0) == 1
    c.report_map_task_finish(1, 1, 0)
    assert c.get_map_task(0) == WAIT   # holder asks again: no self-copy
    assert c.get_map_task() == WAIT    # anonymous poller: no copy either
    assert c.stats()["totals"]["map"].get("speculation") is None


def test_attemptless_finish_on_speculated_task_scores_wasted(tmp_path):
    # A finish report with no attempt number (pre-attempt client, default
    # caller) is unattributable — it must score CONSERVATIVELY as the
    # original winning (wasted), never fabricate a speculation win with
    # invented time saved.
    cfg = make_cfg(tmp_path, 2, worker_n=2, speculate=True,
                   speculate_after_frac=0.5)
    c = Coordinator(cfg)
    c.get_worker_id()
    c.get_worker_id()
    assert c.get_map_task(0) == 0
    assert c.get_map_task(1) == 1
    c.report_map_task_finish(1, 1, 1)
    assert c.get_map_task(1) == 0          # speculative attempt 2
    c.report_map_task_finish(0)            # attempt-less report
    spec = c.stats()["totals"]["map"]["speculation"]
    assert spec["won"] == 0 and spec["wasted"] == 1
    assert spec["time_saved_s"] == 0.0


def test_speculation_expiry_counts_wasted_and_regrants(tmp_path):
    # Both attempts go silent: the shared lease expires, the speculation
    # record resolves to wasted, and the task re-grants normally.
    cfg = make_cfg(tmp_path, 2, worker_n=2, speculate=True,
                   speculate_after_frac=0.5, lease_timeout_s=0.0)
    c = Coordinator(cfg)
    c.get_worker_id()
    c.get_worker_id()
    assert c.get_map_task(0) == 0
    assert c.get_map_task(1) == 1
    c.report_map_task_finish(1, 1, 1)
    assert c.get_map_task(1) == 0      # speculative attempt 2
    c.check_lease()                    # timeout 0: the shared lease dies
    spec = c.stats()["totals"]["map"]["speculation"]
    assert spec == {"attempts": 1, "won": 0, "wasted": 1, "time_saved_s": 0.0}
    assert c.get_map_task(0) == 0      # normal re-grant, attempt 3
    assert c.report.attempts("map", 0) == 3


def test_revoked_renewal_sets_event_and_exits_loop(tmp_path):
    # ISSUE 6 satellite: the cancelled speculative loser must exit its
    # renewal loop cleanly (the bpo-42130 stop-flag machinery untouched)
    # and surface the revocation so the task loop skips its report.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1,
                   lease_renew_period_s=0.02)
    w = Worker(cfg, engine="host")

    class RevokingClient:
        last_revoked = False
        calls = 0

        async def call(self, method, *params):
            self.calls += 1
            self.last_revoked = True   # envelope: task done elsewhere
            return False

    async def go():
        stop = asyncio.Event()
        revoked = asyncio.Event()
        client = RevokingClient()
        await asyncio.wait_for(
            w._renewal_loop(client, "renew_map_lease", 0, stop, revoked),
            timeout=5.0,
        )
        assert client.calls == 1       # one failed renewal is enough
        assert revoked.is_set()
        # And the level-triggered stop flag still wins over everything:
        # a loop started with stop already set never calls out at all.
        stop2 = asyncio.Event()
        stop2.set()
        quiet = RevokingClient()
        await asyncio.wait_for(
            w._renewal_loop(quiet, "renew_map_lease", 0, stop2,
                            asyncio.Event()),
            timeout=5.0,
        )
        assert quiet.calls == 0

    asyncio.run(go())


def test_expired_but_unfinished_lease_is_not_revocation(tmp_path):
    # The other False-renewal: lease expired but the task is NOT done —
    # the worker must keep computing (its late report is a genuine
    # completion), so the envelope says revoked=False.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1, lease_timeout_s=0.0)

    async def go():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        client = CoordinatorClient(cfg.host, cfg.port, timeout_s=5.0)
        await client.connect()
        try:
            await client.call("get_worker_id")
            tid = await client.call("get_map_task", 0)
            coord.check_lease()        # timeout 0: expire it immediately
            ok = await client.call("renew_map_lease", tid, 0)
            assert ok is False
            assert client.last_revoked is False   # expired ≠ revoked
        finally:
            await client.close()
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)

    asyncio.run(go())


def test_graceful_drain_deregisters_and_survivor_finishes(tmp_path):
    # SIGTERM drain semantics, in-process: the draining worker finishes
    # its current task, reports it, deregisters, and exits cleanly while
    # the survivor completes the job — and watch/progress shows DRAINED,
    # not a crash.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)

    class DrainAfterFirstTask(Worker):
        def run_map_task(self, tid: int) -> None:
            super().run_map_task(tid)
            self.request_drain()   # as a SIGTERM mid-task would

    async def cluster():
        coord = Coordinator(cfg)
        serve = asyncio.create_task(coord.serve())
        await asyncio.sleep(0.1)
        drainer = DrainAfterFirstTask(cfg, engine="host")
        survivor = Worker(cfg, engine="host")
        await asyncio.wait_for(
            asyncio.gather(drainer.run(), survivor.run()), timeout=60
        )
        await asyncio.wait_for(serve, timeout=30)
        return coord, drainer

    coord, drainer = asyncio.run(cluster())
    assert read_outputs(cfg) == oracle()
    assert drainer.drained is True
    assert coord.drained == {drainer.worker_id}
    prog = coord.progress()
    assert prog["workers"]["drained"] == [drainer.worker_id]
    assert prog["workers"]["active"] == 1
    # The drained worker ran exactly its one map task, nothing after.
    rep = coord.stats()
    w = rep["workers"][str(drainer.worker_id)]
    assert w["reports"] == 1
    from mapreduce_rust_tpu.runtime.telemetry import format_progress

    assert "drained" in format_progress(rep)


def test_deregister_rejects_unknown_wids(tmp_path):
    cfg = make_cfg(tmp_path, 1, worker_n=1)
    c = Coordinator(cfg)
    assert c.deregister_worker(0) is False    # never registered
    assert c.deregister_worker(-1) is False
    c.get_worker_id()
    assert c.deregister_worker(0) is True
    assert c.deregister_worker(0) is True     # idempotent


def test_backoff_envelope_cap_budget_and_reset():
    import random

    import pytest

    from mapreduce_rust_tpu.runtime.backoff import Backoff, BackoffExhausted

    # No jitter: the envelope is exactly base * factor^n, capped.
    b = Backoff(0.1, cap_s=0.5, factor=2.0, jitter=0.0)
    assert [round(b.next_delay(), 3) for _ in range(5)] == \
        [0.1, 0.2, 0.4, 0.5, 0.5]
    b.reset()
    assert round(b.next_delay(), 3) == 0.1
    # Jitter only shrinks delays (decorrelation must never exceed the cap).
    bj = Backoff(0.1, cap_s=0.5, jitter=0.5, rng=random.Random(7))
    for _ in range(20):
        assert 0.0 < bj.next_delay() <= 0.5
    # The budget bounds TOTAL sleep and then surfaces the exhaustion.
    bb = Backoff(0.1, cap_s=10.0, budget_s=1.0, jitter=0.0)
    total = 0.0
    with pytest.raises(BackoffExhausted):
        while True:
            total += bb.next_delay()
    assert total <= 1.0 + 1e-9
    with pytest.raises(ValueError):
        Backoff(0.0)
    with pytest.raises(ValueError):
        Backoff(0.1, factor=0.5)


def test_call_retry_reconnects_after_transient_timeout(tmp_path):
    # A coordinator that wedges for one call and then recovers: the
    # worker's task-loop RPC retries on a fresh connection under backoff
    # instead of dying on the first RpcTimeout.
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1,
                   rpc_timeout_s=0.3, rpc_backoff_base_s=0.02,
                   rpc_backoff_cap_s=0.1, rpc_backoff_budget_s=5.0)
    w = Worker(cfg, engine="host")
    connections = []

    async def go():
        async def handler(reader, writer):
            connections.append(writer)
            line = await reader.readline()
            if len(connections) == 1:
                return  # wedge: swallow the request, never answer
            import json as _json

            req = _json.loads(line)
            writer.write(_json.dumps(
                {"id": req["id"], "result": 7}
            ).encode() + b"\n")
            await writer.drain()

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = CoordinatorClient("127.0.0.1", port, timeout_s=0.3)
        await client.connect()
        try:
            result = await asyncio.wait_for(
                w._call_with_retry(client, "get_map_task", 0), timeout=10
            )
            assert result == 7
            assert len(connections) == 2   # wedged once, retried once
        finally:
            await client.close()
            for wr in connections:  # wait_closed() waits for every one
                wr.close()
            server.close()
            await server.wait_closed()

    asyncio.run(go())


def test_worker_manifest_carries_device_memory_gauge(tmp_path):
    # PR 5 leftover: the worker task loop samples device memory too (not
    # only the single-host drain loops) — the worker manifest carries
    # device_mem_high_bytes. On the CPU test backend memory_stats() is
    # empty so the high water stays 0; the contract here is that the
    # field exists, sampling ran, and — critically — sampling NEVER
    # initializes a backend by itself (a metadata probe against an
    # absent accelerator would wedge the worker for minutes).
    import json

    write_corpus(tmp_path)
    cfg = make_cfg(
        tmp_path, len(TEXTS), worker_n=1, device="cpu",
        merge_capacity=1 << 12,
        manifest_path=str(tmp_path / "manifest.json"),
    )
    _coord, ws = asyncio.run(_run_cluster(cfg, 1, engine="device"))
    # The device engine initialized the backend, so sampling engaged.
    from jax._src import xla_bridge

    assert xla_bridge._backends, "device engine should have a live backend"
    manifests = list(pathlib.Path(tmp_path).glob("manifest-w*.json"))
    assert len(manifests) == 1
    m = json.loads(manifests[0].read_text())
    assert m["kind"] == "worker_manifest"
    assert "device_mem_high_bytes" in m
    assert m["device_mem_high_bytes"] >= 0


def test_sample_memory_never_initializes_a_backend(tmp_path):
    # The wedge guard, directly: with jax absent from sys.modules the
    # gauge is a no-op; the worker must consult the initialized-backends
    # table rather than calling a device API that would trigger init.
    import sys as _sys

    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)
    w = Worker(cfg, engine="host")
    jax_mod = _sys.modules.pop("jax", None)
    try:
        w._sample_memory()  # no jax: no-op, no import
        assert "jax" not in _sys.modules
    finally:
        if jax_mod is not None:
            _sys.modules["jax"] = jax_mod
    w._sample_memory()  # jax present (conftest initialized cpu): harmless
    assert w.stats.device_mem_high_bytes >= 0


def test_cli_merge_and_clean(tmp_path):
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=1)
    asyncio.run(_run_cluster(cfg, 1))
    from mapreduce_rust_tpu.__main__ import main

    rc = main(["merge", "--output", cfg.output_dir])
    assert rc == 0
    final = (pathlib.Path(cfg.output_dir) / "final.txt").read_bytes().splitlines()
    assert len(final) == len(oracle()) and final == sorted(final)
    rc = main(["clean", "--output", cfg.output_dir, "--work", cfg.work_dir])
    assert rc == 0
    assert not list(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))
    assert not list(pathlib.Path(cfg.work_dir).glob("mr-*.npz"))
