"""Program spans without the Chrome tracer: a ``jax.profiler`` session alone
puts every ``trace_span`` on the XLA trace's host plane, the job's set-up,
state readback and teardown are spans with ``JobStats`` fields of their
own, and the off path builds no annotation."""

import collections
import dataclasses
import glob

import jax
import pytest
from jax.profiler import ProfileData

from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.runtime import driver, telemetry, trace
from mapreduce_rust_tpu.runtime.driver import run_job
from mapreduce_rust_tpu.runtime.trace import start_tracing, stop_tracing, trace_span

TEXTS = [
    "the quick brown fox jumps over the lazy dog " * 40,
    "pack my box with five dozen liquor jugs " * 30,
]
LIFECYCLE = {"job.setup", "engine.setup", "phase.stream", "state.readback",
             "job.teardown"}
ENGINES = {
    "device": {},
    "host": {"map_engine": "host", "host_map_workers": 2},
    "mesh": {"mesh_shape": 4, "merge_capacity": 1 << 12},
}


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    stop_tracing()
    yield
    stop_tracing()


def tiny_job(tmp_path, engine: str, **kw):
    d = tmp_path / "in"
    d.mkdir(exist_ok=True)
    inputs = []
    for i, t in enumerate(TEXTS):
        p = d / f"doc-{i}.txt"
        p.write_bytes(t.encode())
        inputs.append(str(p))
    cfg = Config(chunk_bytes=4096, input_dir=str(d), device="cpu",
                 work_dir=str(tmp_path / "work"), output_dir=str(tmp_path / "out"),
                 **ENGINES[engine], **kw)
    return cfg, inputs


def oracle() -> dict:
    total = collections.Counter()
    for t in TEXTS:
        total.update(reference_word_counts(t.encode()))
    return {w.encode(): c for w, c in total.items()}


def host_span_counts(trace_dir) -> collections.Counter:
    (xplane,) = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
    return collections.Counter(
        e.name for p in ProfileData.from_file(xplane).planes
        if p.name.startswith("/host:") for line in p.lines for e in line.events)


def host_span_names(trace_dir) -> set:
    return set(host_span_counts(trace_dir))


@pytest.mark.parametrize("engine", ["device", "host"])
def test_profiler_alone_records_the_job_spans(tmp_path, monkeypatch, engine):
    flushed = []
    monkeypatch.setattr(telemetry, "flush_run_artifacts",
                        lambda *a, **k: flushed.append(k))
    cfg, inputs = tiny_job(tmp_path, engine)
    assert cfg.trace_path is None and cfg.manifest_path is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        res = run_job(cfg, inputs)
    finally:
        jax.profiler.stop_trace()
    assert res.table == oracle()
    names = host_span_names(tmp_path / "prof")
    assert LIFECYCLE <= names
    assert {"phase.finalize", "phase.egress"} <= names
    assert ("host_map.scan" in names) == (engine == "host")
    assert flushed == []  # no manifest, no Chrome trace
    assert not list(tmp_path.rglob("*manifest*"))


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation and counts constructions."""

    made = 0

    @staticmethod
    def is_enabled() -> bool:
        return jax.profiler.TraceAnnotation.is_enabled()

    def __init__(self, name: str) -> None:
        type(self).made += 1
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        return self._ann.__enter__()

    def __exit__(self, *exc):
        return self._ann.__exit__(*exc)


@pytest.mark.parametrize("mode,built", [("off", 0), ("tracer", 50), ("profiler", 50)])
def test_span_builds_an_annotation_only_when_someone_collects(
        tmp_path, monkeypatch, mode, built):
    monkeypatch.setattr(_CountingAnnotation, "made", 0)
    monkeypatch.setattr(trace, "_ANN", _CountingAnnotation)
    tr = start_tracing() if mode == "tracer" else None
    if mode == "profiler":
        jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for _ in range(50):
            with trace_span("op", n=1):
                pass
    finally:
        if mode == "profiler":
            jax.profiler.stop_trace()
        stop_tracing()
    assert _CountingAnnotation.made == built
    assert (len(tr) if tr is not None else 0) == (50 if mode == "tracer" else 0)


def test_mesh_feed_span_once_per_group(tmp_path):
    """The chunked mesh stream feeds each group of chunks in one
    "mesh.feed" span, beside (not inside) the round's mesh.all_to_all."""
    cfg, inputs = tiny_job(tmp_path, "mesh")
    cfg = dataclasses.replace(cfg, chunk_bytes=512)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        res = run_job(cfg, inputs)
    finally:
        jax.profiler.stop_trace()
    assert res.table == oracle()
    s = res.stats
    groups = -(-s.chunks_dispatched // 4)
    assert groups >= 2 and s.mesh_rounds == groups
    counts = host_span_counts(tmp_path / "prof")
    assert counts["mesh.feed"] == counts["mesh.all_to_all"] == groups
    assert 0 < s.mesh_feed_s <= s.phase_seconds["stream"]


@pytest.mark.parametrize("engine", ["device", "host", "mesh"])
def test_setup_readback_and_teardown_are_timed(tmp_path, engine):
    cfg, inputs = tiny_job(tmp_path, engine)
    s = run_job(cfg, inputs).stats
    assert s.job_setup_s > 0 and s.state_readback_s > 0 and s.job_teardown_s > 0
    assert s.state_readback_s <= s.phase_seconds["stream"]
    # engine.setup lies inside the stream phase, job.setup before it.
    assert s.job_setup_s + s.state_readback_s <= s.wall_seconds


def test_manifest_flush_compiles_nothing(tmp_path):
    """The manifest is written at teardown without compiling any program:
    every compile of the job falls inside the job's own phases."""
    cfg, inputs = tiny_job(tmp_path, "host",
                           manifest_path=str(tmp_path / "manifest.json"))
    log0 = len(driver._COMPILE_LOG)
    s = run_job(cfg, inputs).stats
    assert s.merge_dispatches > 0
    assert len(driver._COMPILE_LOG) - log0 == s.compile_count
    m = telemetry.load_manifest(cfg.manifest_path)
    assert "merge_cost" not in m


@pytest.mark.parametrize("tier", [
    {},  # in-RAM egress
    {"host_accum_budget_mb": 0, "dictionary_budget_words": 4},  # streaming
])
def test_dispatch_counters_spill_and_posting_spans(tmp_path, tier):
    """chunks_dispatched and chunk_payload_bytes count what the device
    engine sent; spill_evict_s times the "spill" span of the stream's
    evictions and posting_format_s the "egress.postings" span of a distinct
    app's egress, once a job in either egress tier, and acc_fold_s the
    "accumulator.fold" span of that egress's fold, all seen on the
    profiler's host plane."""
    from mapreduce_rust_tpu.apps import InvertedIndex

    cfg, inputs = tiny_job(tmp_path, "device", merge_capacity=8, **tier)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        res = run_job(cfg, inputs, app=InvertedIndex())
    finally:
        jax.profiler.stop_trace()
    s = res.stats
    assert (res.table == {}) == bool(tier)  # the streaming tier ran
    # Two documents of 1,760 and 1,200 bytes share one 4,096-byte chunk.
    assert s.chunks_dispatched == 1
    assert s.chunk_payload_bytes == sum(len(t) for t in TEXTS)
    assert s.spill_events > 0 and s.spill_evict_s > 0
    assert s.posting_format_s > 0
    assert s.posting_format_s <= s.phase_seconds["finalize"] + s.phase_seconds["egress"]
    assert 0 < s.acc_fold_s <= s.phase_seconds["finalize"] + s.phase_seconds["egress"]
    assert s.distinct_keys > 0
    spans = host_span_counts(tmp_path / "prof")
    assert spans["spill"] > 0 and spans["egress.postings"] == 1
    assert spans["accumulator.fold"] == 1
    # Word count groups no postings and, here, spills nothing.
    (tmp_path / "wc").mkdir()
    w = run_job(*tiny_job(tmp_path / "wc", "device")).stats
    assert w.posting_format_s == 0 and w.spill_evict_s == 0 and w.spill_events == 0
    assert w.chunks_dispatched == 1
