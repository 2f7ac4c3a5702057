"""The mesh cell's configuration, its run fields as the chip runs them,
through the harness on the CPU's four virtual devices: a tiny corpus in
one round, exact, every key on one shard."""

import json

from conftest import BENCH, run_cell

HIBENCH_MESH4 = json.loads((BENCH / "configs" / "wordcount-hibench-mesh4.json").read_text())


def test_hibench_mesh4_run_fields_end_to_end(tiny_root, capsys):
    root, name = tiny_root(chips=4, **HIBENCH_MESH4["run"])
    rc, res = run_cell(root, name, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"input_gbps", "setup_s"}
    assert res["device"]["count"] == 4
    assert res["checks"] == {"bad_lines": {"value": 0, "limit": 0},
                             "wrong_words": {"value": 0, "limit": 0},
                             "misplaced_rows": {"value": 0, "limit": 0},
                             "jobs_failed": {"value": 0, "limit": 0}}
