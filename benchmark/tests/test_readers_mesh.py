"""The readers of the mesh cell (map_shuffle_roofline, shuffle_fill,
mesh_feed_frac) on hand-made Contexts, each returning nothing where its
input is absent, and a tiny mesh cell traced end to end on the CPU."""

import json
import types

import pytest

import devtrace
import run
from conftest import REPO, BENCH, run_cell

READERS = ("map_shuffle_roofline", "shuffle_fill", "mesh_feed_frac")


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


def cfg(d=4, chunk_bytes=4096, u_cap=512, factor=2.0):
    return types.SimpleNamespace(mesh_shape=d, chunk_bytes=chunk_bytes,
                                 bucket_capacity_factor=factor,
                                 effective_partial_capacity=lambda: u_cap)


def ctx(*stats, window_s=4.0, trace=None, d=4):
    jobs = [run.Job(out_dir=None, stats=s) for s in stats]
    return run.Context(jobs=jobs, window_s=window_s, bytes_in=0, cfg=cfg(d),
                       device_kind="TPU v5 lite", trace=trace)


def trace_of(*chips):
    """A devtrace.Trace with one device per ``chips`` entry, whose module
    line holds that entry's (name, start ns, end ns) rows."""
    none = devtrace.Events.of([])
    devs = [devtrace.Device(f"/device:TPU:{i}", ops=none, modules=devtrace.Events.of(m))
            for i, m in enumerate(chips)]
    return devtrace.Trace(window=(0.0, 1e10), devices=devs, spans=none)


def test_map_shuffle_roofline_sums_calls_over_chips():
    # u_cap 512 over 4 chips at factor 2: buckets of 256 rows.
    per_call = 4096 + 13 * (512 + 3 * 4 * 256)
    chip0 = [("jit_map_shuffle", 0.0, 1e6), ("jit_merge", 1e6, 5e6),
             ("jit_map_shuffle", 5e6, 6e6)]
    chip1 = [("jit_map_shuffle", 0.0, 2e6), ("jit_map_shuffle", 5e6, 7e6)]
    c = ctx(trace=trace_of(chip0, chip1))
    want = 100.0 * 4 * per_call / 819e9 / 6e-3
    assert reader("map_shuffle_roofline").read(c) == pytest.approx(want)


def test_map_shuffle_roofline_bytes_match_the_program_bucket_cap():
    from mapreduce_rust_tpu.config import Config

    c = Config(chunk_bytes=4194304, merge_capacity=8388608, mesh_shape=4)
    # The cell's fast tier: u_cap 524,288 rows, buckets of 262,144.
    assert reader("map_shuffle_roofline").bytes_per_call(c) == (
        4194304 + 13 * (524288 + 3 * 4 * 262144))


def test_map_shuffle_roofline_reads_nothing_without_the_calls():
    r = reader("map_shuffle_roofline")
    assert r.read(ctx()) is None  # --trace 0
    assert r.read(ctx(trace=trace_of([("jit_merge", 0.0, 1e6)]))) is None


def test_shuffle_fill_is_live_rows_over_wire_rows():
    c = ctx(types.SimpleNamespace(shuffle_live_rows=1000, shuffle_wire_bytes=13 * 4000),
            types.SimpleNamespace(shuffle_live_rows=3000, shuffle_wire_bytes=13 * 4000))
    assert reader("shuffle_fill").read(c) == pytest.approx(0.5)


@pytest.mark.parametrize("stats", [
    types.SimpleNamespace(shuffle_wire_bytes=13 * 4000),   # no such counter
    types.SimpleNamespace(shuffle_live_rows=0, shuffle_wire_bytes=0),  # no round
], ids=["older-program", "no-mesh-round"])
def test_shuffle_fill_reads_nothing_without_its_input(stats):
    assert reader("shuffle_fill").read(ctx(stats)) is None


def test_mesh_feed_frac_sums_the_span_over_the_window():
    c = ctx(types.SimpleNamespace(mesh_feed_s=0.25),
            types.SimpleNamespace(mesh_feed_s=0.75), window_s=4.0)
    assert reader("mesh_feed_frac").read(c) == pytest.approx(0.25)
    assert reader("mesh_feed_frac").read(ctx(types.SimpleNamespace())) is None


def test_tiny_mesh_cell_traced_reports_the_program_metrics(tiny_root, capsys,
                                                           monkeypatch):
    """The new metrics listed for a mesh cell: the two the program counts
    come back from a traced CPU run; the roofline finds no TPU modules."""
    root, name = tiny_root(chips=4, mesh_shape=4)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(devtrace, "tpu_lines", lambda plane, line, chips: (
        "ops" if plane == "/host:CPU" and line.startswith("tf_XLA") else None))
    rc, res = run_cell(root, name, capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["checks"]["misplaced_rows"] == {"value": 0, "limit": 0}
    got = res["metrics"]
    assert 0 < got["shuffle_fill"]["value"] <= 1
    assert 0 < got["mesh_feed_frac"]["value"] < 1
    assert "map_shuffle_roofline" not in got


def test_mesh_cell_is_in_the_spec():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}["wc.uniform.mesh4"]
    assert cell["chips"] == 4 and cell["traffic"] == "uniform-320m"
    conf = json.loads((REPO / "benchmark/configs/wordcount-hibench-mesh4.json").read_text())
    assert conf["run"]["mesh_shape"] == 4 and conf["reduced"] == {}
    for m in spec["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == ["wc.uniform.mesh4"]
