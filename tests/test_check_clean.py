"""Tier-1 gate: mrcheck passes clean on what the framework actually
produces (ISSUE 7 satellite).

The seeded-violation suite (tests/test_mrcheck.py) proves every invariant
FIRES; this file proves the other half of the acceptance criterion — a
real cluster run's artifacts produce ZERO findings, so the checker can
gate CI and the chaos matrix without crying wolf. Plus the tooling
contract every analysis subcommand honors: the CLI stays jax-free.
"""

import asyncio
import json
import os
import pathlib
import subprocess
import sys

from test_control_plane import (
    _run_cluster,
    TEXTS,
    make_cfg,
    oracle,
    read_outputs,
    write_corpus,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_check_exits_zero_on_canonical_cluster_run(tmp_path):
    """A fault-free in-process cluster (real Coordinator.serve + 2 real
    Workers over TCP): the journal, event log and job report it leaves
    behind must replay conformant — exactly as CI runs it, via the CLI."""
    write_corpus(tmp_path)
    cfg = make_cfg(tmp_path, len(TEXTS), worker_n=2)
    asyncio.run(_run_cluster(cfg, 2))
    assert read_outputs(cfg) == oracle()  # the run itself was good

    from mapreduce_rust_tpu.__main__ import main

    assert (pathlib.Path(cfg.work_dir) / "job_report.json").exists()
    assert main(["check", cfg.work_dir]) == 0
    # JSON document form, as a program consumes it.
    from mapreduce_rust_tpu.analysis.mrcheck import run_check

    doc = run_check(cfg.work_dir)
    assert doc["ok"] and doc["violations"] == []
    assert doc["checked"]["events"] >= 2 * len(TEXTS)  # grants + finishes
    assert doc["checked"]["journal_lines"] == len(TEXTS) + cfg.reduce_n


def test_check_cli_is_backend_free(tmp_path):
    # Like lint/doctor/trace merge: conformance checking is control-plane
    # tooling and must run in any process in milliseconds — importing jax
    # would push it out of CI hooks (package rule, ISSUE 3).
    work = tmp_path / "work"
    work.mkdir()
    (work / "coordinator.journal").write_text(
        "job 1 1 deadbeef\nmap 0 a1 w0 t0.1\nreduce 0 a1 w0 t0.2\n"
    )
    (work / "job_report.json").write_text(json.dumps({
        "kind": "job_report",
        "report": {
            "tasks": {"map": {"0": {"reports": 1}},
                      "reduce": {"0": {"reports": 1}}},
            "events": [
                {"t": 0.01, "ev": "grant", "phase": "map", "tid": 0,
                 "attempt": 1, "wid": 0},
                {"t": 0.1, "ev": "finish", "phase": "map", "tid": 0,
                 "attempt": 1, "wid": 0},
                {"t": 0.15, "ev": "grant", "phase": "reduce", "tid": 0,
                 "attempt": 1, "wid": 0},
                {"t": 0.2, "ev": "finish", "phase": "reduce", "tid": 0,
                 "attempt": 1, "wid": 0},
            ],
        },
    }))
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from mapreduce_rust_tpu.__main__ import main; "
         f"rc = main(['check', {str(work)!r}]); "
         "sys.exit(rc if rc else (3 if 'jax' in sys.modules else 0))"],
        capture_output=True, text=True, timeout=120,
        env={"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin"}, cwd=REPO,
    )
    assert r.returncode == 0, (r.returncode, r.stdout[-2000:], r.stderr[-500:])


def test_check_catalog_documented_in_readme():
    # The invariant catalog is data (mrcheck.INVARIANTS); README's
    # "Correctness tooling" section renders it. Drift — an invariant
    # added without documentation — fails here, not in review.
    from mapreduce_rust_tpu.analysis.mrcheck import INVARIANTS

    readme = pathlib.Path(REPO, "README.md").read_text()
    for code in INVARIANTS:
        assert f"`{code}`" in readme, f"README missing invariant {code}"
