"""mrcheck (ISSUE 7 tentpole): lease/attempt protocol conformance + the
happens-before race detector.

Unit tests replay synthetic event logs / journals / traces against the
invariant catalog. The seeded-violation suite then corrupts a REAL
recorded run's artifacts with the mutation harness (mrcheck.MUTATIONS)
and proves EVERY invariant fires — exit 1, offending event pair named —
while the unmutated run passes with zero findings (the false-positive
half of the acceptance criterion; the chaos matrix covers the rest in
tests/test_check_clean.py and tests/test_chaos.py).
"""

import argparse
import json
import os
import pathlib
import shutil

import pytest

from mapreduce_rust_tpu.analysis.mrcheck import (
    INVARIANTS,
    MUTATIONS,
    check_events,
    check_journal,
    check_trace,
    parse_journal,
    run_check,
    run_cli,
)
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.coordinator.server import Coordinator
from mapreduce_rust_tpu.runtime.telemetry import write_job_report
from mapreduce_rust_tpu.runtime.trace import start_tracing, stop_tracing

TEXTS = [
    "the quick brown fox jumps over the lazy dog " * 20,
    "pack my box with five dozen liquor jugs " * 20,
]


# ---------------------------------------------------------------------------
# A real recorded run (in-process coordinator, tracing on)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    """Drive the REAL Coordinator through a clean 2-worker, 2-phase run
    with tracing active, then persist journal + job report + trace — the
    exact artifact set a cluster run leaves behind, minus the sockets.
    Module-scoped: every mutation test corrupts a COPY."""
    root = tmp_path_factory.mktemp("mrcheck-run")
    docs = root / "in"
    docs.mkdir()
    for i, t in enumerate(TEXTS):
        (docs / f"doc-{i}.txt").write_bytes(t.encode())
    cfg = Config(
        map_n=2, reduce_n=2, worker_n=2, chunk_bytes=4096,
        input_dir=str(docs), work_dir=str(root / "work"),
        output_dir=str(root / "out"),
    )
    tracer = start_tracing(tag="coord")
    try:
        c = Coordinator(cfg)
        assert c.get_worker_id() == 0
        assert c.get_worker_id() == 1
        t0, t1 = c.get_map_task(0), c.get_map_task(1)
        assert {t0, t1} == {0, 1}
        assert c.renew_map_lease(t0, 0) is True
        assert c.report_map_task_finish(t1, attempt=1, wid=1) is False
        assert c.report_map_task_finish(t0, attempt=1, wid=0) is True
        r0, r1 = c.get_reduce_task(0), c.get_reduce_task(1)
        assert {r0, r1} == {0, 1}
        c.report_reduce_task_finish(r0, attempt=1, wid=0)
        c.report_reduce_task_finish(r1, attempt=1, wid=1)
        assert c.deregister_worker(0) and c.deregister_worker(1)
        write_job_report(
            os.path.join(cfg.work_dir, "job_report.json"), c.report
        )
    finally:
        tracer = stop_tracing()
    trace = str(root / "trace.json")
    tracer.write(trace)
    return {"work": root / "work", "trace": trace}


def _copy_run(recorded_run, tmp_path) -> tuple:
    """(workdir, trace path) — a private copy safe to corrupt."""
    work = tmp_path / "work"
    work.mkdir()
    for f in ("coordinator.journal", "job_report.json"):
        shutil.copy(recorded_run["work"] / f, work / f)
    trace = str(tmp_path / "trace.json")
    shutil.copy(recorded_run["trace"], trace)
    return str(work), trace


def _cli_args(target, trace=None, fmt="text"):
    return argparse.Namespace(target=target, trace=trace, journal=None,
                              job_report=None, format=fmt, verbose=False)


def test_fault_free_run_is_conformant(recorded_run, capsys):
    doc = run_check(str(recorded_run["work"]), trace=recorded_run["trace"])
    assert doc["ok"] and doc["violations"] == []
    assert doc["checked"]["events"] > 0
    assert doc["checked"]["journal_lines"] == 4
    assert doc["checked"]["trace_events"] > 0
    assert run_cli(
        _cli_args(str(recorded_run["work"]), trace=recorded_run["trace"])
    ) == 0
    assert "ok" in capsys.readouterr().out


def test_every_invariant_has_a_seeded_fixture():
    # The catalog IS the coverage contract: an invariant without a
    # known-bad fixture is an invariant nobody has proven fires.
    assert set(MUTATIONS) == set(INVARIANTS)


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_seeded_violation_fires(recorded_run, tmp_path, name, capsys):
    needs_trace, mutate = MUTATIONS[name]
    work, trace = _copy_run(recorded_run, tmp_path)
    code = mutate(work, trace) if needs_trace else mutate(work)
    assert code == name
    doc = run_check(work, trace=trace if needs_trace else None)
    assert not doc["ok"]
    hits = [v for v in doc["violations"] if v["code"] == name]
    assert hits, doc["violations"]
    # The offending event pair is named, with context to chase it down.
    assert all(v["events"] for v in hits)
    # CLI contract: exit 1, violation code + events in the text output.
    assert run_cli(_cli_args(work, trace=trace if needs_trace else None)) == 1
    out = capsys.readouterr().out
    assert name in out and "VIOLATION" in out


def test_mutations_do_not_cross_fire(recorded_run, tmp_path):
    # Each corruption must trigger ITS invariant, not a shotgun blast:
    # cross-firing would make the offending-pair report useless.
    for name in sorted(MUTATIONS):
        needs_trace, mutate = MUTATIONS[name]
        sub = tmp_path / name
        sub.mkdir()
        work, trace = _copy_run(recorded_run, sub)
        mutate(work, trace) if needs_trace else mutate(work)
        doc = run_check(work, trace=trace if needs_trace else None)
        assert {v["code"] for v in doc["violations"]} == {name}


def test_worker_manifest_local_log_is_not_replayed(tmp_path):
    # A worker's event log is its LOCAL view: after a dropped finish RPC
    # (chaos) the lease expires and the same tid is re-granted to the
    # same worker — grant/finish/grant/finish, all legal, none
    # journaling. Replaying it as the coordinator's machine would call
    # that a double-win; a worker-manifest target must not.
    manifest = tmp_path / "manifest-w123.json"
    manifest.write_text(json.dumps({
        "kind": "run_manifest",
        "report": {
            "tasks": {"map": {"0": {"reports": 2}}},
            "events": [
                {"t": 0.1, "ev": "grant", "phase": "map", "tid": 0,
                 "attempt": 1, "wid": 0},
                {"t": 0.2, "ev": "finish", "phase": "map", "tid": 0,
                 "attempt": 1, "wid": 0},
                {"t": 0.4, "ev": "grant", "phase": "map", "tid": 0,
                 "attempt": 2, "wid": 0},
                {"t": 0.5, "ev": "finish", "phase": "map", "tid": 0,
                 "attempt": 2, "wid": 0},
            ],
        },
    }))
    doc = run_check(str(manifest))
    assert doc["ok"], doc["violations"]
    assert doc["checked"]["authoritative"] is False


def test_cli_unusable_target_exits_2(tmp_path, capsys):
    assert run_cli(_cli_args(str(tmp_path / "nope.json"))) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert run_cli(_cli_args(str(empty))) == 2  # nothing to check != clean
    capsys.readouterr()


def test_cli_mistyped_explicit_paths_exit_2(recorded_run, tmp_path, capsys):
    # An explicit --journal/--job-report that doesn't exist must be a
    # config error: silently dropping the artifact would skip its
    # invariants and report clean.
    args = _cli_args(str(recorded_run["work"]))
    args.journal = str(tmp_path / "typo.journal")
    assert run_cli(args) == 2
    args = _cli_args(str(recorded_run["work"]))
    args.job_report = str(tmp_path / "typo.json")
    assert run_cli(args) == 2
    capsys.readouterr()


def test_cli_explicit_job_report_overrides_embedded(recorded_run, tmp_path,
                                                    capsys):
    # A manifest target that EMBEDS a job_report must not shadow an
    # explicit --job-report: the named file was put on the command line
    # to be checked, and silently preferring the embedded copy is the
    # same skipped-artifact failure mode as a mistyped path.
    manifest = tmp_path / "manifest-coord.json"
    manifest.write_text(json.dumps({
        "kind": "run_manifest",
        "job_report": {"tasks": {}, "events": []},   # embedded: clean
    }))
    bad_report = tmp_path / "violating_report.json"
    bad_report.write_text(json.dumps({
        "kind": "job_report",
        "report": {"tasks": {}, "events": [
            {"t": 0.1, "ev": "finish", "phase": "map", "tid": 0,
             "attempt": 1, "wid": 0},               # never granted
        ]},
    }))
    args = _cli_args(str(manifest))
    args.job_report = str(bad_report)
    assert run_cli(args) == 1
    out = capsys.readouterr().out
    assert "finish-without-grant" in out
    doc = run_check(str(manifest), job_report=str(bad_report))
    assert doc["checked"]["sources"]["report"] == str(bad_report)
    # And the explicit report restores authority over a worker target.
    worker = tmp_path / "manifest-w9.json"
    worker.write_text(json.dumps({
        "kind": "run_manifest", "report": {"tasks": {}, "events": []},
    }))
    doc = run_check(str(worker), job_report=str(bad_report))
    assert doc["checked"]["authoritative"] is True
    assert [v["code"] for v in doc["violations"]] == ["finish-without-grant"]


def test_cli_malformed_report_exits_2_not_traceback(tmp_path, capsys):
    # A torn/corrupt report (tasks not a dict, event rows not objects) is
    # an UNUSABLE target: exit 2 with a message, never an AttributeError
    # traceback — whose exit 1 a CI gate would read as "violations found".
    for rep in (
        {"tasks": [1, 2]},                               # tasks not a dict
        {"tasks": {"map": [1]}},                         # phase not a dict
        {"tasks": {"map": {"0": 7}}},                    # entry not a dict
        {"tasks": {"map": {"zero": {"reports": 1}}}},    # tid not an int
        {"tasks": {}, "events": ["grant"]},              # row not an object
        [1, 2, 3],                                       # report not a dict
    ):
        work = tmp_path / f"w{len(list(tmp_path.iterdir()))}"
        work.mkdir()
        (work / "coordinator.journal").write_text(
            "job 1 1 deadbeef\nmap 0 a1 w0 t0.1\n")
        (work / "job_report.json").write_text(
            json.dumps({"kind": "job_report", "report": rep}))
        assert run_cli(_cli_args(str(work))) == 2, rep
        assert "mrcheck:" in capsys.readouterr().err


def test_cli_array_artifacts_exit_2_not_traceback(tmp_path, capsys):
    # A JSON array fed as the target (e.g. a raw trace mixed up with the
    # manifest) or as --job-report is an unusable target: exit 2, never an
    # AttributeError traceback.
    arr = tmp_path / "trace.json"
    arr.write_text("[]")
    assert run_cli(_cli_args(str(arr))) == 2
    assert "mrcheck:" in capsys.readouterr().err
    work = tmp_path / "w"
    work.mkdir()
    (work / "coordinator.journal").write_text(
        "job 1 1 deadbeef\nmap 0 a1 w0 t0.1\n")
    args = _cli_args(str(work))
    args.job_report = str(arr)
    assert run_cli(args) == 2
    assert "mrcheck:" in capsys.readouterr().err


def test_cli_json_document(recorded_run, capsys):
    assert run_cli(
        _cli_args(str(recorded_run["work"]), trace=recorded_run["trace"],
                  fmt="json")
    ) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["tool"] == "mrcheck" and doc["ok"]
    assert doc["invariants"] == sorted(INVARIANTS)


# ---------------------------------------------------------------------------
# State-machine replay units (synthetic event logs)
# ---------------------------------------------------------------------------

_T = [0.0]


def _ev(ev, phase="map", tid=0, **kw):
    _T[0] += 0.01
    return {"t": round(_T[0], 3), "ev": ev, "phase": phase, "tid": tid, **kw}


def _codes(violations):
    return [v.code for v in violations]


def test_events_clean_lifecycle():
    assert check_events([
        _ev("grant", attempt=1, wid=0),
        _ev("finish", attempt=1, wid=0),
        _ev("grant", tid=1, attempt=1, wid=1),
        _ev("expire", tid=1, attempt=1),
        _ev("grant", tid=1, attempt=2, wid=0),   # re-execution after expiry
        _ev("finish", tid=1, attempt=2, wid=0),
        _ev("late_finish", tid=1, attempt=1, wid=1),  # idempotence guard
    ]) == []


def test_events_speculation_shares_lease_legally():
    # speculate → grant while the original lease is live is the ONE legal
    # overlapping grant; a revoke of the loser AFTER the winner's finish
    # is the protocol working as designed.
    assert check_events([
        _ev("grant", attempt=1, wid=0),
        _ev("speculate", attempt=2, wid=1),
        _ev("grant", attempt=2, wid=1),
        _ev("finish", attempt=2, wid=1),
        _ev("revoke", wid=0),
    ]) == []


def test_events_grant_over_live_lease_fires():
    v = check_events([
        _ev("grant", attempt=1, wid=0),
        _ev("grant", attempt=2, wid=1),  # no speculate event armed it
    ])
    assert _codes(v) == ["grant-over-live-lease"]
    assert len(v[0].events) == 2


def test_events_double_win_fires():
    v = check_events([
        _ev("grant", attempt=1, wid=0),
        _ev("finish", attempt=1, wid=0),
        _ev("finish", attempt=2, wid=1),  # second JOURNALING finish
    ])
    assert _codes(v) == ["double-win"]


def test_events_report_after_revoke_fires():
    v = check_events([
        _ev("grant", attempt=1, wid=0),
        _ev("revoke", wid=0),
        _ev("finish", attempt=1, wid=0),
    ])
    assert _codes(v) == ["report-after-revoke"]


def test_events_expire_without_lease_fires():
    v = check_events([
        _ev("grant", attempt=1, wid=0),
        _ev("finish", attempt=1, wid=0),
        _ev("expire", attempt=1),  # the lease was settled by the finish
    ])
    assert _codes(v) == ["expire-without-lease"]


def test_events_finish_without_grant_fires():
    v = check_events([_ev("finish", tid=7, attempt=1, wid=0)])
    assert "finish-without-grant" in _codes(v)


def test_events_grant_after_deregister_fires():
    v = check_events([
        {"t": 0.0, "ev": "deregister", "wid": 1},
        _ev("grant", attempt=1, wid=1),
    ])
    assert _codes(v) == ["grant-after-deregister"]


# ---------------------------------------------------------------------------
# Journal cross-check units
# ---------------------------------------------------------------------------

def test_parse_journal_annotations_optional_and_torn_tail():
    lines = parse_journal(
        "job 2 2 deadbeef\n"
        "map 0 a1 w0 t0.123\n"
        "map 1\n"                 # pre-annotation format still parses
        "reduce 0 a2 wx tz\n"     # garbage annotations never invalidate
        "reduce 1 a1 w1 t0.9"     # torn tail (no newline): distrusted
    )
    assert [(ln.phase, ln.tid) for ln in lines] == [
        ("map", 0), ("map", 1), ("reduce", 0),
    ]
    assert lines[0].attempt == 1 and lines[0].wid == 0
    assert lines[1].attempt is None and lines[1].wid is None
    assert lines[2].attempt == 2 and lines[2].wid is None


def _report(tasks):
    return {"tasks": tasks}


def test_journal_double_win_fires():
    j = parse_journal("map 0 a1 w0 t0.1\nmap 0 a2 w1 t0.2\n")
    v = check_journal(j, _report({"map": {"0": {"reports": 1}}}))
    assert _codes(v) == ["double-win"]


def test_journal_without_finish_fires():
    j = parse_journal("map 0 a1 w0 t0.1\n")
    v = check_journal(j, _report({"map": {"0": {"reports": 0}}}))
    assert _codes(v) == ["journal-without-finish"]


def test_finish_without_journal_fires():
    j = parse_journal("map 0 a1 w0 t0.1\n")
    v = check_journal(j, _report({
        "map": {"0": {"reports": 1}, "1": {"reports": 1}},
    }))
    assert _codes(v) == ["finish-without-journal"]


def test_journal_checks_skip_when_journal_absent():
    # No journal artifact at all (report-only target): the cross-checks
    # stay quiet instead of calling every completion unjournaled.
    assert check_journal(None, _report({"map": {"0": {"reports": 1}}})) == []


# ---------------------------------------------------------------------------
# Happens-before race detector units (synthetic traces)
# ---------------------------------------------------------------------------

def _journal_write(ts, pid, tid=1, phase="map", task=0):
    return {"name": "coordinator.journal", "ph": "i", "ts": ts, "pid": pid,
            "tid": tid, "args": {"phase": phase, "tid": task}}


def test_trace_unordered_writes_race():
    # Two journal-state writes for ONE (phase, tid) on unrelated threads:
    # nothing orders them — the race fires even though each write alone
    # looks fine.
    v = check_trace([
        _journal_write(10.0, 100),
        _journal_write(20.0, 200),
    ])
    assert _codes(v) == ["write-race"]
    assert len(v[0].events) == 2


def test_trace_rpc_bracket_orders_writes():
    # Same two writes, but the first happens-before an rpc.send whose
    # span runs on the second writer's thread before its write: the RPC
    # edge (send ≤ handle) orders them — no race.
    events = [
        _journal_write(10.0, 100),
        {"name": "rpc.send", "ph": "i", "ts": 11.0, "pid": 100, "tid": 1,
         "args": {"cid": "100:1"}},
        {"name": "rpc.report", "ph": "X", "ts": 12.0, "dur": 2.0,
         "pid": 200, "tid": 1, "args": {"cid": "100:1"}},
        _journal_write(20.0, 200),
        {"name": "rpc.recv", "ph": "i", "ts": 21.0, "pid": 100, "tid": 1,
         "args": {"cid": "100:1"}},
    ]
    assert check_trace(events) == []


def test_trace_program_order_within_thread_is_not_a_race():
    assert check_trace([
        _journal_write(10.0, 100),
        _journal_write(20.0, 100),  # same (pid, tid): program-ordered
    ]) == []


def test_trace_revoked_terminator_is_not_a_write():
    # A revoked attempt's flow terminator mutates nothing — it must not
    # race the winner's journal append.
    assert check_trace([
        _journal_write(10.0, 100),
        {"name": "task", "ph": "f", "ts": 20.0, "pid": 200, "tid": 1,
         "id": "map:0:1",
         "args": {"phase": "map", "tid": 0, "revoked": True}},
    ]) == []


def test_trace_cycle_is_corrupt_artifact_not_a_race(tmp_path, capsys):
    # recv before send on one thread + the RPC edges = a causal cycle:
    # the artifact is UNUSABLE (exit 2), not a write-race finding — a
    # broken trace must not masquerade as a detector result.
    cyclic = [
        {"name": "rpc.recv", "ph": "i", "ts": 0.0, "pid": 100, "tid": 1,
         "args": {"cid": "c"}},
        {"name": "rpc.send", "ph": "i", "ts": 10.0, "pid": 100, "tid": 1,
         "args": {"cid": "c"}},
        {"name": "rpc.x", "ph": "X", "ts": 1.0, "dur": 1.0, "pid": 200,
         "tid": 1, "args": {"cid": "c"}},
    ]
    with pytest.raises(ValueError, match="cycle"):
        check_trace(cyclic)
    work = tmp_path / "w"
    work.mkdir()
    (work / "coordinator.journal").write_text(
        "job 1 1 deadbeef\nmap 0 a1 w0 t0.1\n")
    trace = tmp_path / "cyclic.json"
    trace.write_text(json.dumps({"traceEvents": cyclic}))
    assert run_cli(_cli_args(str(work), trace=str(trace))) == 2
    err = capsys.readouterr().err
    assert "cycle" in err and str(trace) in err


def test_trace_missing_terminator_needs_the_journal():
    journal = parse_journal("map 0 a1 w0 t0.1\n")
    chain = [
        {"name": "task", "ph": "s", "ts": 1.0, "pid": 100, "tid": 1,
         "id": "map:0:1", "args": {"phase": "map", "tid": 0}},
        {"name": "task", "ph": "t", "ts": 2.0, "pid": 200, "tid": 1,
         "id": "map:0:1", "args": {"phase": "map", "tid": 0}},
    ]
    v = check_trace(chain, journal)
    assert _codes(v) == ["missing-terminator"]
    # With the terminator present the chain is complete.
    done = chain + [
        {"name": "task", "ph": "f", "ts": 3.0, "pid": 100, "tid": 1,
         "id": "map:0:1", "args": {"phase": "map", "tid": 0}},
    ]
    assert check_trace(done, journal) == []
    # An UNJOURNALED chain may legally stay unterminated (crashed or
    # revoked attempt): only the journal winner owes a terminator.
    other = [dict(e, id="map:9:1") for e in chain]
    assert check_trace(other, journal) == []
    # A per-process WORKER trace carries only the "t" steps of chains it
    # ran — no start, so it owes no terminator (the coordinator's file,
    # or the merged view, is where s and f live).
    worker_only = [chain[1]]
    assert check_trace(worker_only, journal) == []
