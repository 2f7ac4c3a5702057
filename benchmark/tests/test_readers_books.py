"""The readers of the inverted-index cell (packed_map_roofline, chunk_fill,
spill_frac, posting_format_frac) on hand-made Contexts, and a tiny cell of
the books' shape end to end on the CPU."""

import json
import types

import numpy as np
import pytest

import devtrace
import run
from conftest import BENCH, REPO, run_cell

READERS = ("packed_map_roofline", "chunk_fill", "spill_frac", "posting_format_frac")


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


def cfg(chunk_bytes=1000, u_cap=100):
    return types.SimpleNamespace(chunk_bytes=chunk_bytes,
                                 effective_partial_capacity=lambda: u_cap)


def ctx(*stats, window_s=4.0, bytes_in=0, trace=None):
    jobs = [run.Job(out_dir=None, stats=s) for s in stats]
    return run.Context(jobs=jobs, window_s=window_s, bytes_in=bytes_in, cfg=cfg(),
                       device_kind="TPU v5 lite", trace=trace)


def job(**fields):
    return types.SimpleNamespace(**fields)


def trace_of(modules):
    """A devtrace.Trace of one chip whose module line holds ``modules``:
    (name, start ns, end ns) rows."""
    none = devtrace.Events.of([])
    dev = devtrace.Device("/device:TPU:0", ops=none, modules=devtrace.Events.of(modules))
    return devtrace.Trace(window=(0.0, 1e10), devices=[dev], spans=none)


def test_packed_map_roofline_counts_document_bytes_not_padding():
    calls = [("jit_map_combine", 0.0, 1e6), ("jit_map_combine", 2e6, 3e6),
             ("jit_merge", 3e6, 9e6)]
    c = ctx(bytes_in=5_000, trace=trace_of(calls))
    need = 5_000 + 2 * 100 * 13  # documents once, one 100-row update a call
    want = 100.0 * need / 819e9 / 2e-3
    assert reader("packed_map_roofline").read(c) == pytest.approx(want)
    # Padding is free: the same calls over fewer document bytes read less.
    less = ctx(bytes_in=1_000, trace=trace_of(calls))
    assert reader("packed_map_roofline").read(less) < want


def test_packed_map_roofline_reads_nothing_without_the_calls():
    assert reader("packed_map_roofline").read(ctx()) is None  # --trace 0
    c = ctx(bytes_in=5_000, trace=trace_of([("jit_merge", 0.0, 1e6)]))
    assert reader("packed_map_roofline").read(c) is None


def test_chunk_fill_is_payload_over_capacity():
    c = ctx(job(chunks_dispatched=3, chunk_payload_bytes=2_500),
            job(chunks_dispatched=1, chunk_payload_bytes=500))
    assert reader("chunk_fill").read(c) == pytest.approx(3_000 / 4_000)


@pytest.mark.parametrize("metric,field", [("spill_frac", "spill_evict_s"),
                                          ("posting_format_frac", "posting_format_s")])
def test_span_share_of_the_window(metric, field):
    c = ctx(job(**{field: 0.5}), job(**{field: 1.5}), window_s=8.0)
    assert reader(metric).read(c) == pytest.approx(0.25)


@pytest.mark.parametrize("metric", ["chunk_fill", "spill_frac", "posting_format_frac"])
def test_program_without_the_counters_reads_nothing(metric):
    """A program older than these counters: its JobStats lacks them."""
    c = ctx(job(spill_s=0.0, chunks=16), job(spill_s=0.0, chunks=16))
    assert reader(metric).read(c) is None
    assert reader(metric).read(ctx()) is None  # no job finished


def test_the_cell_and_its_metrics_are_declared():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == "ii.books.device"]
    assert cell["chips"] == 1 and cell["traffic"] == "books-96"
    conf = json.loads((REPO / "benchmark/configs/invertedindex-gutenberg.json").read_text())
    assert conf["app"] == "inverted_index" and conf["datasize"] == 96 * 684_800
    mine = {m["name"]: m for m in spec["per_layer"] if "ii.books.device" in m["workloads"]}
    assert set(mine) == set(READERS)
    assert all(m["workloads"] == ["ii.books.device"] and m["moves"] == "input_gbps"
               for m in mine.values())


def test_tiny_books_cell_end_to_end(tiny_root, capsys, monkeypatch):
    """The books' shape at a CPU's size: about six documents a chunk, a
    state smaller than the (term, doc) pairs, traced. The reference reads
    every job exact, and the program's counters reach the readers."""
    traffic = {"docs": 24, "bytes_per_doc": 10_000, "vocab": 4096, "zipf_s": 1.0}
    root, name = tiny_root(app="inverted_index", traffic=traffic,
                           merge_capacity=2048)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(devtrace, "tpu_lines", lambda plane, line, chips: (
        "ops" if plane == "/host:CPU" and line.startswith("tf_XLA") else None))
    rc, res = run_cell(root, name, capsys, trace=1)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert res["checks"]["wrong_terms"] == {"value": 0, "limit": 0}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # 240,000 bytes a job in 4 chunks of 65,536.
    assert m["chunk_fill"] == pytest.approx(240_000 / (4 * 65_536))
    assert m["spill_frac"] > 0 and m["posting_format_frac"] > 0
    assert "packed_map_roofline" not in m  # no TPU modules on the CPU
    assert np.isfinite(list(m.values())).all()
