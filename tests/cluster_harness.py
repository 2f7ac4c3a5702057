"""The subprocess cluster harness the chaos tests share: a coordinator and
two worker OS processes of the real CLI, over TCP, with faults injected
through MR_CHAOS. Not a test module (pytest collects only ``test_*.py``);
the tests import it from here.
"""

from __future__ import annotations

import os
import pathlib
import socket
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

CHAOS_TEXTS = [
    b"the quick brown fox jumps over the lazy dog " * 400,
    b"pack my box with five dozen liquor jugs " * 400,
    b"sphinx of black quartz judge my vow " * 400,
]


def cpu_env() -> dict:
    """A child environment on CPU-XLA: the control plane needs no
    accelerator, and a chip serves one process."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def chaos_cluster(name: str, work_root: pathlib.Path, chaos_spec: str | None,
                  speculate: bool, timeout_s: int = 120,
                  trace: bool = False, app: str = "word_count",
                  sched: str = "fifo") -> dict:
    """One cluster run: coordinator + 2 worker OS processes over TCP (the
    REAL binaries — the recovery paths under test live in the real
    renewal/report loops, not a harness reimplementation). Faults ride in
    as MR_CHAOS on BOTH workers: the seeded spec targets (phase, tid, wid),
    so which OS process draws which task stays irrelevant. Returns whether
    the job recovered, the output bytes, the run dir ("dir":
    job_report.json and trace files live under it), and the coordinator
    manifest path for the doctor."""
    leg = work_root / name
    docs = leg / "in"
    docs.mkdir(parents=True)
    for i, t in enumerate(CHAOS_TEXTS):
        (docs / f"doc-{i}.txt").write_bytes(t)
    port = free_port()
    manifest = leg / "manifest.json"
    common = [
        "--input", str(docs), "--output", str(leg / "out"),
        "--work", str(leg / "work"), "--port", str(port), "--reduce-n", "3",
        "--app", app,  # word_count default; the sort kill test runs the
        # range-partitioned app through the SAME cluster harness
        "--lease-timeout", "2.0", "--lease-check-period", "0.3",
        "--renew-period", "0.3", "--poll-retry", "0.05",
    ]
    if sched != "fifo":
        # --sched rides in `common` so the coordinator and BOTH workers
        # agree on the mode (a pipelined worker against a FIFO
        # coordinator would just see NOT_READY, which no test is after).
        common += ["--sched", sched]
    if trace:
        common += ["--trace", str(leg / "trace.json")]
    coord_args = ["--worker-n", "2", "--manifest", str(manifest), *common]
    if speculate:
        coord_args += ["--speculate", "--speculate-after-frac", "0.5"]
    env = cpu_env()
    env["PYTHONPATH"] = str(REPO)
    worker_env = dict(env)
    if chaos_spec:
        worker_env["MR_CHAOS"] = chaos_spec
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
