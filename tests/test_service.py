"""Multi-tenant job service (ISSUE 14): lifecycle, admission control,
result cache, drain/restart, and the concurrency matrix.

In-process where possible (JobService methods are plain calls on one
event loop — most admission/cache/journal semantics need no sockets);
real OS processes for the SIGKILL-restart and chaos legs, where the
thing under test IS process death. The flagship assertions: N=3
concurrent jobs over one shared fleet produce outputs bit-identical to
the same jobs run sequentially, with mrcheck exit 0 over every job's
artifacts; a repeated (app, corpus, config) submission is served from
cache with zero new task grants; SIGKILL mid-queue then restart resumes
and completes.
"""

import asyncio
import collections
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import time

import pytest

from mapreduce_rust_tpu.analysis.mrcheck import check_events, run_check
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.coordinator.server import (
    DONE,
    WAIT,
    CoordinatorClient,
)
from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.service.server import JobService, validate_spec
from mapreduce_rust_tpu.worker.runtime import ServiceWorker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TEXTS_A = [
    "the quick brown fox jumps over the lazy dog " * 30,
    "pack my box with five dozen liquor jugs stop " * 20,
    "sphinx of black quartz judge my vow " * 25,
]
TEXTS_B = [
    "how vexingly quick daft zebras jump " * 25,
    "bright vixens jump dozy fowl quack " * 20,
]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_corpus(d: pathlib.Path, texts) -> str:
    d.mkdir(parents=True, exist_ok=True)
    for i, t in enumerate(texts):
        (d / f"doc-{i}.txt").write_bytes(t.encode())
    return str(d)


def wc_oracle(texts) -> dict:
    total = collections.Counter()
    for t in texts:
        total.update(reference_word_counts(t.encode()))
    return {w.encode(): c for w, c in total.items()}


def read_wc_outputs(out_dir) -> dict:
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


def make_cfg(tmp_path, **kw) -> Config:
    defaults = dict(
        map_n=1,
        reduce_n=3,
        worker_n=1,
        chunk_bytes=4096,
        port=free_port(),
        lease_timeout_s=2.0,
        lease_check_period_s=0.2,
        lease_renew_period_s=0.2,
        poll_retry_s=0.05,
        input_dir=str(tmp_path / "in"),
        work_dir=str(tmp_path / "svc-work"),
        output_dir=str(tmp_path / "svc-out"),
    )
    defaults.update(kw)
    return Config(**defaults)


async def _drive_service(cfg, specs, n_workers=2, timeout_s=60):
    """Serve + submit ``specs`` + run ``n_workers`` ServiceWorkers until
    every submitted job is done, then shut down. Returns (service,
    submit results)."""
    svc = JobService(cfg)
    serve = asyncio.create_task(svc.serve())
    await asyncio.sleep(0.2)
    client = CoordinatorClient(cfg.host, cfg.port, timeout_s=15.0)
    await client.connect()
    results = []
    for spec in specs:
        res = await client.call("submit_job", spec)
        assert res["ok"], res
        results.append(res)
    ws = [ServiceWorker(cfg) for _ in range(n_workers)]
    workers = [asyncio.create_task(w.run()) for w in ws]
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = await client.call("stats")
        states = {j["job"]: j["state"] for j in st["jobs"]}
        if all(states[r["job"]] == "done" for r in results):
            break
        await asyncio.sleep(0.1)
    else:
        raise AssertionError(f"jobs not done within {timeout_s}s: {states}")
    await client.call("shutdown")
    await client.close()
    await asyncio.wait_for(asyncio.gather(*workers), timeout=30)
    await asyncio.wait_for(serve, timeout=30)
    return svc, results


# ---------------------------------------------------------------------------
# Spec validation + lifecycle units (no sockets)
# ---------------------------------------------------------------------------

def test_spec_validation(tmp_path):
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    ok = validate_spec({"app": "word_count", "input_dir": docs})
    assert ok["reduce_n"] == 4 and ok["input_pattern"] == "*.txt"
    with pytest.raises(ValueError):
        validate_spec({"app": "nope", "input_dir": docs})
    with pytest.raises(ValueError):
        validate_spec({"app": "word_count", "input_dir": str(tmp_path / "x")})
    with pytest.raises(ValueError):
        validate_spec({"app": "grep", "input_dir": docs})  # query required
    with pytest.raises(ValueError):
        validate_spec({"app": "word_count", "input_dir": docs,
                       "reduce_n": 0})
    # Per-app arg contracts are enforced at submission, never worker-side:
    # a string query would tuple into characters and CACHE a wrong
    # result; a non-int k would kill every worker that pulls the grant.
    with pytest.raises(ValueError):
        validate_spec({"app": "grep", "input_dir": docs,
                       "app_args": {"query": "fox"}})
    with pytest.raises(ValueError):
        validate_spec({"app": "top_k", "input_dir": docs,
                       "app_args": {"k": "abc"}})
    with pytest.raises(ValueError):
        validate_spec({"app": "word_count", "input_dir": docs,
                       "app_args": {"bogus": 1}})
    assert validate_spec({"app": "top_k", "input_dir": docs,
                          "app_args": {"k": 5}})["app_args"] == {"k": 5}
    # submit_job maps a bad spec to {"ok": False}, never a traceback.
    svc = JobService(make_cfg(tmp_path))
    res = svc.submit_job({"app": "nope", "input_dir": docs})
    assert res["ok"] is False and "unknown app" in res["error"]


def test_done_job_retention_is_bounded(tmp_path, monkeypatch):
    # A continuously-traded service must not hoard one Job record (with
    # its report snapshot) per finished job forever: past DONE_JOBS_MAX
    # the oldest terminal records drop — journal/cache keep the durable
    # state.
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    monkeypatch.setattr(JobService, "DONE_JOBS_MAX", 3)
    svc = JobService(make_cfg(tmp_path, service_max_jobs=1))
    jids = []
    for i in range(6):
        r = svc.submit_job({"app": "word_count", "input_dir": docs,
                            "reduce_n": i + 2})
        jids.append(r["job"])
        svc.cancel_job(r["job"])  # terminal without workers
    kept = [j for j in jids if j in svc.jobs]
    assert len(kept) == 3 and kept == jids[-3:]


def test_admission_budget_backpressure_and_saturated_finding(tmp_path):
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    size_mb = sum(
        os.path.getsize(p) for p in pathlib.Path(docs).glob("*.txt")
    ) / (1 << 20)
    # Budget fits ONE corpus, not two: the second submission must queue.
    cfg = make_cfg(tmp_path, service_max_jobs=3,
                   service_inflight_budget_mb=size_mb * 1.5)
    svc = JobService(cfg)
    r1 = svc.submit_job({"app": "word_count", "input_dir": docs})
    r2 = svc.submit_job({"app": "word_count", "input_dir": docs,
                         "reduce_n": 2})  # different config digest: no hit
    assert r1["state"] == "running" and r2["state"] == "queued"
    assert svc.admission_blocked
    assert svc.inflight_bytes() > 0
    # The live doctor surfaces the backpressure as service-saturated.
    svc._doctor_tick()
    assert "service-saturated" in svc._live_findings
    assert svc._live_findings["service-saturated"]["active"]
    # Head job leaves (cancel) -> the queued one admits, finding clears.
    assert svc.cancel_job(r1["job"])["ok"]
    assert svc.jobs[r2["job"]].state == "running"
    assert not svc.admission_blocked
    svc._doctor_tick()
    assert not svc._live_findings["service-saturated"]["active"]


def test_priority_admits_before_fifo(tmp_path):
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    cfg = make_cfg(tmp_path, service_max_jobs=1)
    svc = JobService(cfg)
    r1 = svc.submit_job({"app": "word_count", "input_dir": docs})
    r2 = svc.submit_job({"app": "word_count", "input_dir": docs,
                         "reduce_n": 2}, 0)
    r3 = svc.submit_job({"app": "word_count", "input_dir": docs,
                         "reduce_n": 5}, 5)
    assert svc.jobs[r1["job"]].state == "running"
    assert svc.jobs[r2["job"]].state == "queued"
    assert svc.jobs[r3["job"]].state == "queued"
    svc.cancel_job(r1["job"])
    # Higher priority admits first even though it was submitted later.
    assert svc.jobs[r3["job"]].state == "running"
    assert svc.jobs[r2["job"]].state == "queued"
    # Draining refuses new submissions.
    svc.request_drain()
    res = svc.submit_job({"app": "word_count", "input_dir": docs,
                          "reduce_n": 6})
    assert res["ok"] is False and "draining" in res["error"]


def test_service_journal_replay_requeues_and_seeds_cache(tmp_path):
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    cfg = make_cfg(tmp_path, service_max_jobs=1)
    svc = JobService(cfg)
    r1 = svc.submit_job({"app": "word_count", "input_dir": docs})
    r2 = svc.submit_job({"app": "word_count", "input_dir": docs,
                         "reduce_n": 2})
    assert svc.jobs[r1["job"]].state == "running"
    assert svc.jobs[r2["job"]].state == "queued"
    # "Crash": a second incarnation over the same work dir. Both jobs
    # were submitted, neither finished -> both re-queue (j1 resumes its
    # per-job journal at admission) and the id mint never reuses ids.
    svc2 = JobService(cfg)
    assert svc2.jobs[r1["job"]].state == "running"  # re-admitted (cap 1)
    assert svc2.jobs[r2["job"]].state == "queued"
    r3 = svc2.submit_job({"app": "word_count", "input_dir": docs,
                          "reduce_n": 7})
    assert int(r3["job"].lstrip("j")) > int(r2["job"].lstrip("j"))
    # Done rows seed the result cache on restart: fabricate a completed
    # job's journal rows + outputs, then a THIRD incarnation must serve
    # the repeat from cache.
    out = tmp_path / "done-out"
    out.mkdir()
    (out / "mr-0.txt").write_bytes(b"cached 1\n")
    key_spec = validate_spec({"app": "word_count", "input_dir": docs,
                              "reduce_n": 9})
    from mapreduce_rust_tpu.service.server import _ResultCache

    key = _ResultCache.key(key_spec)
    with open(os.path.join(cfg.work_dir, "service.journal"), "a") as f:
        f.write(json.dumps({"op": "submit", "job": "j90", "t": 1.0,
                            "spec": key_spec, "priority": 0}) + "\n")
        f.write(json.dumps({"op": "done", "job": "j90", "t": 2.0,
                            "state": "done", "cache_key": key,
                            "outputs": [str(out / "mr-0.txt")]}) + "\n")
    svc3 = JobService(cfg)
    res = svc3.submit_job(dict(key_spec))
    assert res["cached"] is True
    assert svc3.jobs[res["job"]].outputs == [str(out / "mr-0.txt")]


def test_multi_job_worker_report_keeps_task_slots_separate():
    """A multi-job writer's report (the ServiceWorker) must not merge two
    jobs' identically-numbered tasks into one slot — grants=2 would read
    as a re-execution that never happened and the second job's duration
    would vanish. Per-job coordinator reports keep plain tid keys."""
    from mapreduce_rust_tpu.runtime.telemetry import JobReport

    rep = JobReport()  # a worker's report: identity None, rows per job
    rep.row_job = "j1"
    rep.record_grant("map", 0, wid=0, attempt=1)
    rep.record_finish("map", 0, wid=0, attempt=1)
    rep.row_job = "j2"
    rep.record_grant("map", 0, wid=0, attempt=1)
    rep.record_finish("map", 0, wid=0, attempt=1)
    d = rep.to_dict()
    assert set(d["tasks"]["map"]) == {"j1:0", "j2:0"}
    assert all(
        t["grants"] == 1 and t["completed"] and t["duration_s"] is not None
        for t in d["tasks"]["map"].values()
    )
    assert d["totals"]["map"]["completed"] == 2
    assert rep.in_flight() == []
    # Per-job coordinator report: job_id == row_job → plain tid keys
    # (the shape every existing consumer parses), rows still stamped.
    rep2 = JobReport(job_id="j7")
    rep2.record_grant("map", 0, wid=0, attempt=1)
    d2 = rep2.to_dict()
    assert set(d2["tasks"]["map"]) == {"0"}
    assert d2["events"][0]["job"] == "j7"
    assert rep2.in_flight() == [("map", 0)]
    # mrcheck accepts a job-scoped worker report as a target.
    from mapreduce_rust_tpu.analysis.mrcheck import _validate_report

    _validate_report(d, "worker-report")


def test_grant_across_jobs_event_unit():
    # A finish landing under a job that never granted the (phase, tid)
    # while another job holds it: the cross-job misroute invariant.
    events = [
        {"t": 0.1, "ev": "grant", "job": "j1", "phase": "map", "tid": 0,
         "attempt": 1, "wid": 0},
        {"t": 0.2, "ev": "finish", "job": "j2", "phase": "map", "tid": 0,
         "attempt": 1, "wid": 0},
    ]
    codes = {v.code for v in check_events(events)}
    assert codes == {"grant-across-jobs"}
    # Two jobs running the same (phase, tid) legitimately: no violation —
    # the machines are keyed per job.
    events = [
        {"t": 0.1, "ev": "grant", "job": "j1", "phase": "map", "tid": 0,
         "attempt": 1},
        {"t": 0.15, "ev": "grant", "job": "j2", "phase": "map", "tid": 0,
         "attempt": 1},
        {"t": 0.2, "ev": "finish", "job": "j1", "phase": "map", "tid": 0,
         "attempt": 1},
        {"t": 0.3, "ev": "finish", "job": "j2", "phase": "map", "tid": 0,
         "attempt": 1},
    ]
    assert check_events(events) == []


def test_service_root_trace_checked_once_and_job_attributed(tmp_path):
    """run_check on a service root runs the shared trace's HB pass ONCE
    against the union of job journals: a one-job write-race is reported
    once, attributed to the owning job, and the innocent job stays ok."""
    def job_dir(jid):
        d = tmp_path / "work" / f"job-{jid}"
        d.mkdir(parents=True)
        (d / "coordinator.journal").write_text(
            f"job 1 1 deadbeef\nmap 0 a1 w0 t0.1 j{jid}\n"
        )
        (d / "job_report.json").write_text(json.dumps({
            "kind": "job_report",
            "report": {
                "job": jid,
                "tasks": {"map": {"0": {"reports": 1}}},
                "events": [
                    {"t": 0.01, "ev": "grant", "job": jid, "phase": "map",
                     "tid": 0, "attempt": 1, "wid": 0},
                    {"t": 0.1, "ev": "finish", "job": jid, "phase": "map",
                     "tid": 0, "attempt": 1, "wid": 0},
                ],
            },
        }))
        return d

    job_dir("j1")
    job_dir("j2")
    # Two journal writes for j1's (map, 0) on edge-less threads = a race;
    # j2's single write is clean.
    events = [
        {"name": "coordinator.journal", "ph": "i", "ts": 100, "pid": 1,
         "tid": 1, "args": {"phase": "map", "tid": 0, "job": "j1"}},
        {"name": "coordinator.journal", "ph": "i", "ts": 200, "pid": 2,
         "tid": 1, "args": {"phase": "map", "tid": 0, "job": "j1"}},
        {"name": "coordinator.journal", "ph": "i", "ts": 300, "pid": 1,
         "tid": 1, "args": {"phase": "map", "tid": 0, "job": "j2"}},
    ]
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": events}))
    doc = run_check(str(tmp_path / "work"), trace=str(trace))
    races = [v for v in doc["violations"] if v["code"] == "write-race"]
    assert len(races) == 1, doc["violations"]
    assert races[0]["job"] == "j1"
    assert doc["jobs"]["j1"]["ok"] is False
    assert doc["jobs"]["j2"]["ok"] is True
    assert doc["checked"]["trace_events"] == 3


def test_format_jobs_renders_table():
    from mapreduce_rust_tpu.runtime.telemetry import format_jobs

    text = format_jobs({
        "service": {"running": 1, "queued": 1, "done": 1, "workers": 2,
                    "uptime_s": 3.2, "inflight_bytes": 1 << 20,
                    "budget_bytes": 4 << 20, "admission_blocked": True,
                    "draining": False,
                    "cache": {"hits": 1, "misses": 2, "entries": 2}},
        "jobs": [
            {"job": "j1", "state": "running", "app": "word_count",
             "priority": 0, "queue_wait_s": 0.1, "run_s": 2.0,
             "tasks": {"map": {"done": 1, "total": 3}}},
            {"job": "j2", "state": "done", "app": "grep", "priority": 2,
             "queue_wait_s": 0.0, "cached": True},
        ],
    })
    assert "SATURATED" in text and "j1" in text and "map 1/3" in text
    assert "cache hit" in text


# ---------------------------------------------------------------------------
# The concurrency matrix (in-process cluster)
# ---------------------------------------------------------------------------

def _three_specs(docs_a, docs_b):
    return [
        {"app": "word_count", "input_dir": docs_a, "reduce_n": 3},
        {"app": "inverted_index", "input_dir": docs_b, "reduce_n": 2},
        {"app": "grep", "input_dir": docs_a, "reduce_n": 2,
         "app_args": {"query": ["fox", "dog", "quartz"]}},
    ]


def test_three_concurrent_jobs_bit_identical_to_sequential(tmp_path):
    """The flagship (acceptance): one long-lived service process runs 3
    concurrent jobs (different apps, shared 2-worker fleet) and every
    output byte matches the same jobs run sequentially — plus mrcheck
    exit 0 over every job's artifacts, per-job-stamped events, and the
    word-count oracle."""
    docs_a = write_corpus(tmp_path / "in-a", TEXTS_A)
    docs_b = write_corpus(tmp_path / "in-b", TEXTS_B)
    specs = _three_specs(docs_a, docs_b)

    seq_root = tmp_path / "seq"
    cfg_seq = make_cfg(tmp_path, service_max_jobs=1,
                       work_dir=str(seq_root / "work"),
                       output_dir=str(seq_root / "out"))
    svc_seq, res_seq = asyncio.run(_drive_service(cfg_seq, specs))

    con_root = tmp_path / "con"
    cfg_con = make_cfg(tmp_path, service_max_jobs=3,
                       work_dir=str(con_root / "work"),
                       output_dir=str(con_root / "out"))
    svc_con, res_con = asyncio.run(_drive_service(cfg_con, specs))

    # All three genuinely ran (no cache cross-talk between services).
    assert svc_con.cache.stats()["hits"] == 0
    for res_s, res_c, spec in zip(res_seq, res_con, specs):
        out_s = seq_root / "out" / f"job-{res_s['job']}"
        out_c = con_root / "out" / f"job-{res_c['job']}"
        bytes_s, bytes_c = output_bytes(out_s), output_bytes(out_c)
        assert bytes_s, f"no outputs for {spec}"
        assert bytes_s == bytes_c, f"outputs diverged for {spec}"
    # Exactness anchor: the word-count job matches the reference oracle.
    assert read_wc_outputs(
        con_root / "out" / f"job-{res_con[0]['job']}"
    ) == wc_oracle(TEXTS_A)
    # mrcheck over each service work root: every job's journal + report
    # replay clean (multi-job target), and events are job-stamped.
    for root in (seq_root, con_root):
        doc = run_check(str(root / "work"))
        assert doc["ok"], doc["violations"]
        assert doc["checked"]["jobs"] == 3
    rep = svc_con._load_job_report(svc_con.jobs[res_con[0]["job"]])
    assert rep["job"] == res_con[0]["job"]
    assert all(e.get("job") == res_con[0]["job"] for e in rep["events"])


def test_cache_hit_completes_with_zero_new_grants(tmp_path):
    """Acceptance: a repeated (app, corpus, config) triple is served from
    cache — the second job completes with NO task grants (its status
    carries no report) and the cache counters say hit."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    spec = {"app": "word_count", "input_dir": docs, "reduce_n": 3}

    async def go():
        cfg = make_cfg(tmp_path)
        svc = JobService(cfg)
        serve = asyncio.create_task(svc.serve())
        await asyncio.sleep(0.2)
        client = CoordinatorClient(cfg.host, cfg.port, timeout_s=15.0)
        await client.connect()
        r1 = await client.call("submit_job", spec)
        ws = [ServiceWorker(cfg) for _ in range(2)]
        workers = [asyncio.create_task(w.run()) for w in ws]
        for _ in range(300):
            st = await client.call("job_status", r1["job"])
            if st.get("state") == "done":
                break
            await asyncio.sleep(0.1)
        assert st["state"] == "done" and not st["cached"]
        # The first run really computed: every map task granted+reported.
        n_inputs = len(list(pathlib.Path(docs).glob("*.txt")))
        assert st["totals"]["map"]["completed"] == n_inputs
        grants_before = sum(
            t["grants"] for t in st["tasks"]["map"].values()
        )
        # The repeat: done at submission, zero new grants anywhere.
        r2 = await client.call("submit_job", spec)
        assert r2["cached"] is True and r2["state"] == "done"
        st2 = await client.call("job_status", r2["job"])
        assert st2["cached"] is True
        assert st2.get("totals") is None  # no report: nothing ran
        assert st2["outputs"] and all(
            os.path.exists(p) for p in st2["outputs"]
        )
        res2 = await client.call("get_result", r2["job"])
        assert res2["ok"] and res2["cached"] is True
        # The SOURCE job's counts are untouched (nothing re-ran).
        st1 = await client.call("job_status", r1["job"])
        assert sum(
            t["grants"] for t in st1["tasks"]["map"].values()
        ) == grants_before
        view = await client.call("list_jobs")
        assert view["service"]["cache"]["hits"] == 1
        await client.call("shutdown")
        await client.close()
        await asyncio.wait_for(asyncio.gather(*workers), timeout=30)
        await asyncio.wait_for(serve, timeout=30)

    asyncio.run(go())


def test_inflight_dedup_joins_running_twin_zero_new_grants(tmp_path):
    """ISSUE 15 acceptance: an identical submission made while its twin
    is RUNNING grants zero new map tasks and returns the twin's result —
    job_status reports the joined twin, and the cache counters split
    hit_done vs hit_inflight."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    spec = {"app": "word_count", "input_dir": docs, "reduce_n": 3}
    n_inputs = len(list(pathlib.Path(docs).glob("*.txt")))

    async def go():
        cfg = make_cfg(tmp_path)
        svc = JobService(cfg)
        serve = asyncio.create_task(svc.serve())
        await asyncio.sleep(0.2)
        client = CoordinatorClient(cfg.host, cfg.port, timeout_s=15.0)
        await client.connect()
        # Submit the twin FIRST (it admits and RUNS — no workers yet, so
        # it cannot finish), then the identical repeat: deterministic
        # in-flight window.
        r1 = await client.call("submit_job", spec)
        assert r1["state"] == "running"
        r2 = await client.call("submit_job", spec)
        assert r2["state"] == "joined" and r2["joined"] == r1["job"]
        st2 = await client.call("job_status", r2["job"])
        assert st2["state"] == "joined" and st2["joined"] == r1["job"]
        # No result yet: the join must not fabricate one.
        res2 = await client.call("get_result", r2["job"])
        assert res2["ok"] is False and res2["state"] == "joined"
        ws = [ServiceWorker(cfg) for _ in range(2)]
        workers = [asyncio.create_task(w.run()) for w in ws]
        for _ in range(300):
            st2 = await client.call("job_status", r2["job"])
            if st2.get("state") == "done":
                break
            await asyncio.sleep(0.1)
        assert st2["state"] == "done"
        st1 = await client.call("job_status", r1["job"])
        assert st1["state"] == "done"
        # ZERO new map tasks for the joined job: the twin computed every
        # input exactly once, and the joined job has no report at all.
        assert st1["totals"]["map"]["completed"] == n_inputs
        assert sum(
            t["grants"] for t in st1["tasks"]["map"].values()
        ) == n_inputs
        assert st2.get("totals") is None
        assert st2["cached"] is True and st2["joined"] == r1["job"]
        # The twin's result, byte for byte the same files.
        assert st2["outputs"] == st1["outputs"]
        res2 = await client.call("get_result", r2["job"])
        assert res2["ok"] and res2["outputs"] == st1["outputs"]
        # Counter split: one inflight hit, zero done hits.
        view = await client.call("list_jobs")
        cache = view["service"]["cache"]
        assert cache["hit_inflight"] == 1 and cache["hit_done"] == 0
        await client.call("shutdown")
        await client.close()
        await asyncio.wait_for(asyncio.gather(*workers), timeout=30)
        await asyncio.wait_for(serve, timeout=30)

    asyncio.run(go())


def test_inflight_dedup_requeues_when_twin_cancelled(tmp_path):
    # The failure half of the dedup contract, in-process: cancelling the
    # computing twin re-queues the joined submission as a REAL job — the
    # dedup must never amplify one cancellation into two lost results.
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    svc = JobService(make_cfg(tmp_path))
    spec = {"app": "word_count", "input_dir": docs}
    r1 = svc.submit_job(dict(spec))
    r2 = svc.submit_job(dict(spec))
    assert r2["state"] == "joined"
    svc.cancel_job(r1["job"])
    j2 = svc.jobs[r2["job"]]
    assert j2.state == "running" and j2.joined is None  # re-admitted
    # And a joined job is itself cancellable while waiting.
    r3 = svc.submit_job(dict(spec))
    assert r3["joined"] == r2["job"]
    assert svc.cancel_job(r3["job"])["ok"]
    assert svc.jobs[r3["job"]].state == "cancelled"


def test_inflight_dedup_inherits_priority(tmp_path):
    # A high-priority duplicate must not inherit its queued twin's LOW
    # queue position: the twin's priority raises to the max of the two
    # (pre-dedup, the duplicate would have admitted ahead).
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    svc = JobService(make_cfg(tmp_path, service_max_jobs=1))
    head = svc.submit_job({"app": "word_count", "input_dir": docs})
    low = svc.submit_job({"app": "word_count", "input_dir": docs,
                          "reduce_n": 2}, 0)
    mid = svc.submit_job({"app": "word_count", "input_dir": docs,
                          "reduce_n": 5}, 3)
    dup = svc.submit_job({"app": "word_count", "input_dir": docs,
                          "reduce_n": 2}, 9)
    assert dup["joined"] == low["job"]
    assert svc.jobs[low["job"]].priority == 9
    # Duplicate heap entries from the raise never double-count.
    assert svc.queued_count() == 2
    svc.cancel_job(head["job"])
    assert svc.jobs[low["job"]].state == "running"   # admitted FIRST
    assert svc.jobs[mid["job"]].state == "queued"


def test_multi_corpus_join_job_through_service(tmp_path):
    """Multi-corpus input API end to end (ISSUE 15): a join spec with two
    named corpora rides submit_job → job_spec → ServiceWorker, and the
    outputs match the same join run through the single-process driver."""
    da = write_corpus(tmp_path / "in-a", TEXTS_A)
    db = write_corpus(tmp_path / "in-b", TEXTS_B)
    spec = {"app": "join", "reduce_n": 3,
            "inputs": [["a", da], ["b", db]]}

    cfg = make_cfg(tmp_path)
    svc, results = asyncio.run(_drive_service(cfg, [spec], n_workers=2))
    jid = results[0]["job"]
    got = output_bytes(pathlib.Path(cfg.output_dir) / f"job-{jid}")
    assert got, "service join produced no outputs"

    # Driver-side reference run over the same corpora.
    from mapreduce_rust_tpu.apps import get_app
    from mapreduce_rust_tpu.runtime.driver import run_job

    ref_cfg = Config(
        map_engine="host", reduce_n=3, device="cpu", chunk_bytes=4096,
        input_dirs=(("a", da), ("b", db)),
        output_dir=str(tmp_path / "ref-out"),
        work_dir=str(tmp_path / "ref-work"),
    )
    ref = run_job(ref_cfg, app=get_app("join"))
    ref_bytes = {
        pathlib.Path(p).name: pathlib.Path(p).read_bytes()
        for p in ref.output_files
    }
    assert got == ref_bytes
    # mrcheck over the service root: the multi-corpus job's protocol
    # artifacts replay clean like every other job's.
    doc = run_check(str(cfg.work_dir))
    assert doc["ok"], doc["violations"]


def test_service_worker_trims_packed_fns_between_jobs(tmp_path):
    """ISSUE 14 satellite: the jit packed-merge cache teardown (PR 11's
    trim hook) runs at JOB boundaries in a service worker, not only at
    process end — a long-lived multi-job fleet member must not hoard one
    compiled executable per (app, cap) forever."""
    from mapreduce_rust_tpu.runtime import driver

    w = ServiceWorker(make_cfg(tmp_path))
    before = dict(driver._PACKED_FNS)
    try:
        driver._PACKED_FNS.clear()
        for i in range(driver._PACKED_FNS_MAX + 4):
            driver._PACKED_FNS[("fake", i)] = object()
        w._job_teardown()
        assert len(driver._PACKED_FNS) == driver._PACKED_FNS_MAX
    finally:
        driver._PACKED_FNS.clear()
        driver._PACKED_FNS.update(before)


def test_get_task_interleaves_jobs_and_drains(tmp_path):
    # Unit view of the shared-fleet pull: grants are job-tagged dicts,
    # WAIT when nothing is grantable, DONE once drained and empty.
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    svc = JobService(make_cfg(tmp_path, service_max_jobs=2))
    assert svc.get_task(0) == WAIT  # nothing running yet
    r1 = svc.submit_job({"app": "word_count", "input_dir": docs})
    r2 = svc.submit_job({"app": "word_count", "input_dir": docs,
                         "reduce_n": 2})
    svc.get_worker_id()
    g1 = svc.get_task(0)
    assert g1["job"] == r1["job"] and g1["phase"] == "map"
    # Job 1 still has map tasks: admission order serves it first; after
    # its fresh ids run out the fleet moves on to job 2.
    grants = [svc.get_task(0) for _ in range(5)]
    jobs_seen = {g["job"] for g in grants if isinstance(g, dict)}
    assert r2["job"] in jobs_seen
    svc.cancel_job(r1["job"])
    svc.cancel_job(r2["job"])
    svc.request_drain()
    assert svc.get_task(0) == DONE


# ---------------------------------------------------------------------------
# Fleet-wide scheduler (ISSUE 17): scoring seam + fifo/pipeline A/B
# ---------------------------------------------------------------------------

def test_sched_order_fifo_is_admission_order_single_phase(tmp_path):
    # FIFO mode reproduces the reference polling exactly: one candidate
    # per running job, admission order, map until the barrier opens.
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    svc = JobService(make_cfg(tmp_path, service_max_jobs=2))
    svc.get_worker_id()
    a = svc.submit_job({"app": "word_count", "input_dir": docs})["job"]
    b = svc.submit_job({"app": "word_count", "input_dir": docs,
                        "reduce_n": 2})["job"]
    order = [(j.jid, ph) for j, ph in svc._sched_order(0)]
    assert order == [(a, "map"), (b, "map")]
    # Open job A's barrier: its candidate flips to reduce, the order is
    # still admission order — a WAITing phase up front gates the rest.
    ca = svc.jobs[a].coord
    for t in range(ca.cfg.map_n):
        ca.get_map_task(0)
        ca.report_map_task_finish(t, wid=0,
                                  part_bytes=[1] * ca.cfg.reduce_n)
    order = [(j.jid, ph) for j, ph in svc._sched_order(0)]
    assert order == [(a, "reduce"), (b, "map")]


def test_sched_order_pipeline_scores_candidates(tmp_path):
    """Pipeline mode scores every grantable (job, phase): priority class
    first, then phase criticality (ready reduce > near-done map wave >
    fresh wave), then worker recent-job affinity, admission order as the
    deterministic tiebreak."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    svc = JobService(make_cfg(tmp_path, service_max_jobs=3,
                              sched="pipeline"))
    svc.get_worker_id()
    a = svc.submit_job({"app": "word_count", "input_dir": docs})["job"]
    b = svc.submit_job({"app": "word_count", "input_dir": docs,
                        "reduce_n": 2})["job"]
    order = [(j.jid, ph) for j, ph in svc._sched_order(0)]
    assert order == [(a, "map"), (b, "map")]  # equal score: admission
    # Affinity: a worker that last pulled from job B prefers B at equal
    # priority/criticality (its caches are warm).
    svc._worker_state.setdefault(0, {})["last_job"] = b
    order = [(j.jid, ph) for j, ph in svc._sched_order(0)]
    assert order[0] == (b, "map")
    # Criticality: push job B's map wave past half done — it outscores
    # a fresh wave for EVERY worker, affinity or not.
    cb = svc.jobs[b].coord
    half = (cb.cfg.map_n + 1) // 2
    for t in range(half):
        cb.get_map_task(0)
        cb.report_map_task_finish(t, wid=0,
                                  part_bytes=[1] * cb.cfg.reduce_n)
    order = [(j.jid, ph) for j, ph in svc._sched_order(1)]
    assert order[0] == (b, "map")
    # Barrier open on B: its ready reduce partitions are the job's exit
    # path — criticality 3, ahead of every map candidate.
    for t in range(half, cb.cfg.map_n):
        cb.get_map_task(0)
        cb.report_map_task_finish(t, wid=0,
                                  part_bytes=[1] * cb.cfg.reduce_n)
    assert cb.map.finished
    order = [(j.jid, ph) for j, ph in svc._sched_order(1)]
    assert order[0] == (b, "reduce")
    # Priority class dominates everything below it.
    c = svc.submit_job({"app": "word_count", "input_dir": docs,
                        "reduce_n": 5}, 5)["job"]
    order = [(j.jid, ph) for j, ph in svc._sched_order(1)]
    assert order[0] == (c, "map")


def test_service_pipeline_bit_identical_to_fifo(tmp_path):
    """ISSUE 17 acceptance (in-process edition): the same two-job mix
    through the service under --sched fifo and --sched pipeline yields
    BIT-IDENTICAL per-job outputs, and both work roots replay clean
    under mrcheck (early-reduce-grant included). The scheduler reorders
    who pulls what when; what a task computes must never move."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    specs = [
        {"app": "word_count", "input_dir": docs, "reduce_n": 3},
        {"app": "inverted_index", "input_dir": docs, "reduce_n": 2},
    ]
    outs: dict = {}
    for sched in ("fifo", "pipeline"):
        cfg = make_cfg(
            tmp_path, service_max_jobs=2, sched=sched,
            work_dir=str(tmp_path / sched / "work"),
            output_dir=str(tmp_path / sched / "out"),
        )
        svc, results = asyncio.run(_drive_service(cfg, specs))
        assert svc.service_summary()["sched"] == sched
        outs[sched] = {
            r["job"]: output_bytes(
                pathlib.Path(cfg.output_dir) / f"job-{r['job']}"
            )
            for r in results
        }
        doc = run_check(cfg.work_dir)
        assert doc["ok"], (sched, doc["violations"])
    # Same spec → same deterministic jid → keys align across modes.
    assert outs["pipeline"] == outs["fifo"]


def test_classic_single_job_worker_stays_wire_valid(tmp_path):
    """Old single-job RPCs stay wire-valid against the service: a
    pre-service Worker (no job tags anywhere) completes the only running
    job end to end — grants route to it, the attempt envelope rides
    back, renew/report land in its coordinator."""
    from mapreduce_rust_tpu.worker.runtime import Worker

    docs = write_corpus(tmp_path / "in", TEXTS_A)
    spec = {"app": "word_count", "input_dir": docs, "reduce_n": 3}

    async def go():
        cfg = make_cfg(tmp_path)
        svc = JobService(cfg)
        serve = asyncio.create_task(svc.serve())
        await asyncio.sleep(0.2)
        client = CoordinatorClient(cfg.host, cfg.port, timeout_s=15.0)
        await client.connect()
        r1 = await client.call("submit_job", spec)
        # A classic worker needs the job's own dirs/shape on its config
        # (no job_spec fetch in its vocabulary).
        jid = r1["job"]
        import dataclasses

        wcfg = dataclasses.replace(
            cfg, map_n=len(TEXTS_A), reduce_n=3,
            work_dir=os.path.join(cfg.work_dir, f"job-{jid}"),
            output_dir=os.path.join(cfg.output_dir, f"job-{jid}"),
        )
        w = Worker(wcfg)
        wt = asyncio.create_task(w.run())
        for _ in range(300):
            st = await client.call("job_status", jid)
            if st.get("state") == "done":
                break
            await asyncio.sleep(0.1)
        assert st["state"] == "done"
        await client.call("shutdown")
        await client.close()
        await asyncio.wait_for(wt, timeout=30)
        await asyncio.wait_for(serve, timeout=30)
        return jid

    jid = asyncio.run(go())
    assert read_wc_outputs(
        tmp_path / "svc-out" / f"job-{jid}"
    ) == wc_oracle(TEXTS_A)
    doc = run_check(str(tmp_path / "svc-work"))
    assert doc["ok"], doc["violations"]


def test_finished_job_labels_dropped_from_registry(tmp_path):
    """Registry hygiene: a finished job's job=<id>-labeled gauges leave
    the instance registry (and therefore the scrape body) instead of
    exporting stale values forever on a long-lived service."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)

    async def go():
        cfg = make_cfg(tmp_path)
        svc, results = await _drive_service(
            cfg, [{"app": "word_count", "input_dir": docs, "reduce_n": 3}]
        )
        return svc, results[0]["job"]

    svc, jid = asyncio.run(go())
    assert svc.registry is not None
    gauge = svc.registry.gauge("job.phase_done")
    assert not any(
        ("job", jid) in key for key in gauge._values
    ), gauge._values
    assert f'job="{jid}"' not in svc.registry.prometheus_text()


# ---------------------------------------------------------------------------
# OS-process legs: SIGKILL restart, SIGTERM drain, chaos
# ---------------------------------------------------------------------------

def _cpu_env() -> dict:
    from cluster_harness import cpu_env

    env = cpu_env()
    env["PYTHONPATH"] = REPO
    return env


def _spawn_service(docs, root, port, extra=()) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-m", "mapreduce_rust_tpu", "service",
         "--input", docs, "--output", str(root / "out"),
         "--work", str(root / "work"), "--port", str(port),
         "--lease-timeout", "2.0", "--lease-check-period", "0.3",
         "--renew-period", "0.3", "--poll-retry", "0.05", *extra],
        env=_cpu_env(), cwd=REPO, stderr=subprocess.DEVNULL,
    )


def _spawn_worker(docs, root, port, chaos=None) -> subprocess.Popen:
    env = _cpu_env()
    if chaos:
        env["MR_CHAOS"] = chaos
    return subprocess.Popen(
        [sys.executable, "-m", "mapreduce_rust_tpu", "worker", "--service",
         "--engine", "host",
         "--input", docs, "--output", str(root / "out"),
         "--work", str(root / "work"), "--port", str(port),
         "--lease-timeout", "2.0", "--renew-period", "0.3",
         "--poll-retry", "0.05"],
        env=env, cwd=REPO, stderr=subprocess.DEVNULL,
    )


def _submit_cli(docs, port, reduce_n=3, wait=False, timeout=120) -> dict:
    out = subprocess.run(
        [sys.executable, "-m", "mapreduce_rust_tpu", "submit",
         "--app", "word_count", "--input", docs,
         "--reduce-n", str(reduce_n), "--port", str(port),
         *(["--wait", "--wait-timeout", str(timeout)] if wait else [])],
        env=_cpu_env(), cwd=REPO, capture_output=True, text=True,
        timeout=timeout + 30,
    )
    assert out.returncode == 0, (out.returncode, out.stdout, out.stderr)
    return json.loads(out.stdout.splitlines()[0])


async def _poll_until_done(port, jids, timeout_s=90) -> dict:
    client = CoordinatorClient("127.0.0.1", port, timeout_s=15.0)
    await client.connect(retries=100, delay=0.1, budget_s=30.0)
    deadline = time.monotonic() + timeout_s
    states: dict = {}
    try:
        while time.monotonic() < deadline:
            view = await client.call("stats")
            states = {j["job"]: j["state"] for j in view["jobs"]}
            if all(states.get(j) == "done" for j in jids):
                return states
            await asyncio.sleep(0.2)
        raise AssertionError(f"jobs not done in {timeout_s}s: {states}")
    finally:
        try:
            await client.call("shutdown")
        except (ConnectionError, OSError, RuntimeError):
            pass
        await client.close()


def test_sigkill_midqueue_restart_resumes_and_completes(tmp_path):
    """Acceptance: SIGKILL the service with one job admitted and one
    queued (no workers yet — zero progress is the deterministic worst
    case), restart over the same dirs, and both jobs run to completion
    with exact outputs. The queue survives in service.journal; the
    admitted job re-admits and resumes via its per-job coordinator
    journal."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    port = free_port()
    svc = _spawn_service(docs, tmp_path, port, extra=("--max-jobs", "1"))
    try:
        r1 = _submit_cli(docs, port, reduce_n=3)
        r2 = _submit_cli(docs, port, reduce_n=2)
        assert r1["ok"] and r2["ok"]
        svc.send_signal(signal.SIGKILL)
        svc.wait(timeout=10)
    finally:
        if svc.poll() is None:
            svc.kill()
            svc.wait()
    # Restart over the same dirs: the journal re-queues both jobs.
    port2 = free_port()
    svc2 = _spawn_service(docs, tmp_path, port2, extra=("--max-jobs", "2"))
    workers = [_spawn_worker(docs, tmp_path, port2) for _ in range(2)]
    try:
        states = asyncio.run(
            _poll_until_done(port2, [r1["job"], r2["job"]])
        )
        assert states[r1["job"]] == "done"
        assert states[r2["job"]] == "done"
        svc2.wait(timeout=30)  # shutdown RPC sent by the poller
        for w in workers:
            w.wait(timeout=30)
    finally:
        for p in [svc2, *workers]:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert read_wc_outputs(
        tmp_path / "out" / f"job-{r1['job']}"
    ) == wc_oracle(TEXTS_A)
    assert read_wc_outputs(
        tmp_path / "out" / f"job-{r2['job']}"
    ) == wc_oracle(TEXTS_A)
    doc = run_check(str(tmp_path / "work"))
    assert doc["ok"], doc["violations"]


def test_sigterm_drain_journals_queue_then_restart_completes(tmp_path):
    """Acceptance (drain half): SIGTERM stops admitting and exits 0 once
    running jobs are done; a queued job survives the journal and a
    restarted service completes it."""
    docs = write_corpus(tmp_path / "in", TEXTS_A)
    port = free_port()
    svc = _spawn_service(docs, tmp_path, port, extra=("--max-jobs", "1"))
    try:
        r1 = _submit_cli(docs, port, reduce_n=3)
        r2 = _submit_cli(docs, port, reduce_n=2)  # queued behind cap 1
        # Drain with no workers: r1 is mid-flight (running, no progress),
        # r2 queued. SIGTERM must stop admission; the service stays up
        # draining r1 — cancel it over RPC so the drain can finish.
        svc.send_signal(signal.SIGTERM)
        time.sleep(0.5)

        async def cancel_r1():
            client = CoordinatorClient("127.0.0.1", port, timeout_s=10.0)
            await client.connect()
            res = await client.call("cancel_job", r1["job"])
            assert res["ok"], res
            await client.close()

        asyncio.run(cancel_r1())
        assert svc.wait(timeout=30) == 0  # drained exit
    finally:
        if svc.poll() is None:
            svc.kill()
            svc.wait()
    # Restart: r2 (never started) re-queues and completes; r1 stays
    # cancelled (its cancel row is journaled).
    port2 = free_port()
    svc2 = _spawn_service(docs, tmp_path, port2)
    workers = [_spawn_worker(docs, tmp_path, port2)]
    try:
        states = asyncio.run(_poll_until_done(port2, [r2["job"]]))
        assert states[r2["job"]] == "done"
        assert states.get(r1["job"]) == "cancelled"
        svc2.wait(timeout=30)
        for w in workers:
            w.wait(timeout=30)
    finally:
        for p in [svc2, *workers]:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert read_wc_outputs(
        tmp_path / "out" / f"job-{r2['job']}"
    ) == wc_oracle(TEXTS_A)


@pytest.mark.parametrize("scenario,chaos", [
    ("kill", "seed=2;kill:map:1"),
    ("wedge_renewal", "seed=4;wedge_renewal:map:0;pause:map:0:3.0"),
])
def test_chaos_legs_under_multi_job_service(tmp_path, scenario, chaos):
    """Acceptance: the chaos kill / wedge_renewal legs pass under the
    multi-job coordinator — two concurrent jobs on a 2-worker fleet, one
    worker carrying the seeded fault; both jobs complete with
    oracle-exact outputs and mrcheck exit 0 over every job's artifacts
    (the faults leave expiries/late-reports, never violations)."""
    docs_a = write_corpus(tmp_path / "in-a", TEXTS_A)
    docs_b = write_corpus(tmp_path / "in-b", TEXTS_B)
    port = free_port()
    svc = _spawn_service(docs_a, tmp_path, port, extra=("--max-jobs", "2"))
    # Worker 0 carries the fault; worker 1 is clean and recovers the
    # fleet (a kill takes its whole process down mid-task).
    workers = [
        _spawn_worker(docs_a, tmp_path, port, chaos=chaos),
        _spawn_worker(docs_a, tmp_path, port),
    ]
    try:
        r1 = _submit_cli(docs_a, port, reduce_n=3)
        r2 = _submit_cli(docs_b, port, reduce_n=2)
        states = asyncio.run(
            _poll_until_done(port, [r1["job"], r2["job"]], timeout_s=120)
        )
        assert all(s == "done" for s in states.values())
        svc.wait(timeout=30)
    finally:
        for p in [svc, *workers]:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert read_wc_outputs(
        tmp_path / "out" / f"job-{r1['job']}"
    ) == wc_oracle(TEXTS_A)
    assert read_wc_outputs(
        tmp_path / "out" / f"job-{r2['job']}"
    ) == wc_oracle(TEXTS_B)
    doc = run_check(str(tmp_path / "work"))
    assert doc["ok"], (scenario, doc["violations"])
