"""mrprof unit tests (ISSUE 19): sampler accounting, the collapsed-stack
export contract, capped tables, the calibration cache, and the roofline
arithmetic — all jax-free, all deterministic where the math is, loose
where the clock is.
"""

import threading
import time

import pytest

from mapreduce_rust_tpu.analysis import roofline
from mapreduce_rust_tpu.runtime.prof import (
    SamplingProfiler,
    active_profiler,
    plane_of,
    start_profiler,
    stop_profiler,
)


# ---------------------------------------------------------------------------
# plane attribution
# ---------------------------------------------------------------------------

def test_plane_of_names():
    assert plane_of("mr/scan_2") == "scan"
    assert plane_of("mr/fold-7") == "fold"
    assert plane_of("mr/spill-dict-abc") == "spill"
    assert plane_of("mr/dispatch") == "dispatch"
    assert plane_of("mr/ingest-io_0") == "ingest"
    assert plane_of("mr/metrics-http") == "metrics"
    assert plane_of("MainThread") == "router"
    assert plane_of("ThreadPoolExecutor-0_1") == "other"


# ---------------------------------------------------------------------------
# self-time accounting
# ---------------------------------------------------------------------------

def busy_until(evt):
    x = 0
    while not evt.is_set():
        x += 1
    return x


def test_self_time_sums_to_wall():
    # One always-runnable thread named as a plane: it appears in every
    # tick, so its plane's self_s is exactly samples * (wall / ticks)
    # = wall. The identity is the design (scale by MEASURED wall/ticks,
    # not the nominal period), so the assertion can be tight.
    stop = threading.Event()
    t = threading.Thread(target=busy_until, args=(stop,),
                         name="mr/scan_test", daemon=True)
    t.start()
    try:
        p = SamplingProfiler(hz=200.0).start()
        time.sleep(0.4)
        p.stop()
    finally:
        stop.set()
        t.join()
    doc = p.profile_dict()
    assert doc["ticks"] > 10, doc
    scan = doc["planes"].get("scan")
    assert scan is not None, doc["planes"]
    # The busy thread is sampled on every tick...
    assert scan["samples"] == pytest.approx(doc["ticks"], abs=2)
    # ...so its self time reproduces the sampler's wall clock.
    assert scan["self_s"] == pytest.approx(doc["wall_s"], rel=0.15)
    # And the plane split total is samples * tick_s by construction.
    total = sum(pl["self_s"] for pl in doc["planes"].values())
    tick_s = doc["wall_s"] / doc["ticks"]
    assert total == pytest.approx(doc["samples"] * tick_s, rel=0.05)


def test_profile_dict_shape_and_top_frames():
    stop = threading.Event()
    t = threading.Thread(target=busy_until, args=(stop,),
                         name="mr/fold-0", daemon=True)
    t.start()
    try:
        p = SamplingProfiler(hz=250.0).start()
        time.sleep(0.25)
        p.stop()
    finally:
        stop.set()
        t.join()
    doc = p.profile_dict()
    assert doc["hz"] == 250.0
    assert doc["samples"] >= doc["ticks"]  # >=1 thread sampled per tick
    assert doc["top_frames"], doc
    fr = doc["top_frames"][0]
    assert set(fr) == {"frame", "samples", "self_s", "pct"}
    # The busy loop should dominate the leaf histogram.
    assert any("busy_until" in f["frame"] for f in doc["top_frames"])
    assert doc["frame_table"]["dropped"] == 0
    assert doc["stack_table"]["entries"] <= doc["stack_table"]["cap"]


# ---------------------------------------------------------------------------
# folded export
# ---------------------------------------------------------------------------

def validate_folded(lines):
    """The collapsed-stack contract flamegraph.pl / speedscope parse:
    ``frame;frame;...;frame count`` — count a positive int after the
    LAST space, every frame non-empty and separator-free."""
    assert lines
    for line in lines:
        stack, count = line.rsplit(" ", 1)
        assert int(count) > 0
        frames = stack.split(";")
        assert frames
        for fr in frames:
            assert fr
            assert " " not in fr
    return len(lines)


def test_folded_roundtrip(tmp_path):
    stop = threading.Event()
    t = threading.Thread(target=busy_until, args=(stop,),
                         name="mr/spill-t", daemon=True)
    t.start()
    try:
        p = SamplingProfiler(hz=250.0).start()
        time.sleep(0.25)
        p.stop()
    finally:
        stop.set()
        t.join()
    out = tmp_path / "prof.folded"
    p.write_folded(str(out))
    lines = out.read_text().splitlines()
    validate_folded(lines)
    # Root frame is the (sanitized) thread name; our busy thread's
    # stacks must lead with it and bottom out in the busy loop.
    spill = [ln for ln in lines if ln.startswith("mr/spill-t;")]
    assert spill
    assert any("busy_until" in ln for ln in spill)
    # Counts agree with the in-memory aggregate.
    total = sum(int(ln.rsplit(" ", 1)[1]) for ln in lines)
    assert total == p.profile_dict()["samples"]


# ---------------------------------------------------------------------------
# capped tables
# ---------------------------------------------------------------------------

def _record_live(p, name):
    # Record the CALLER's still-live frame — a returned frame has its
    # back link cleared, which would collapse every stack to one frame.
    import sys
    with p._lock:
        p._record(name, sys._getframe(1))


def _frame_a(p):
    _record_live(p, "mr/scan_x")


def _frame_b(p):
    _record_live(p, "mr/scan_x")


def _frame_c(p):
    # Extra nesting level: with the frame table capped, distinct stacks
    # only stay distinct by SHAPE, so this one must differ in depth.
    def inner():
        _record_live(p, "mr/scan_x")
    inner()


def test_frame_table_caps_into_overflow_bucket():
    p = SamplingProfiler(hz=1.0, max_frames=3, max_stacks=2, max_depth=8)
    # Never started: drive _record directly with live frames so the cap
    # behavior is deterministic (3 entries incl. the reserved overflow).
    for fn in (_frame_a, _frame_b, _frame_c, _frame_a):
        fn(p)
    doc = p.profile_dict()
    assert doc["frame_table"]["entries"] <= 3
    assert doc["frame_table"]["dropped"] > 0
    # Cap + 1: the reserved overflow stack is an entry of its own.
    assert doc["stack_table"]["entries"] <= 3
    assert doc["stack_table"]["dropped"] > 0
    # Folded output still validates — overflow folds into the reserved
    # <frame-table-full> frame instead of growing without bound.
    assert validate_folded(p.folded_lines()) <= 3
    assert any("<frame-table-full>" in ln for ln in p.folded_lines())
    assert doc["samples"] == 4


def test_global_slot_compare_and_clear():
    p = start_profiler(hz=31.0)
    assert active_profiler() is p
    other = SamplingProfiler(hz=31.0)
    # A stale owner's stop must not clear the active slot...
    assert stop_profiler(other) is None
    assert active_profiler() is p
    # ...while the real owner's does.
    assert stop_profiler(p) is p
    assert active_profiler() is None


# ---------------------------------------------------------------------------
# sampler tax (loose bound: a smoke alarm, not a measurement)
# ---------------------------------------------------------------------------

def test_sample_cost_leaves_headroom_under_budget():
    # Direct per-sample cost: at 97 Hz the sampler must stay far below
    # one core. 25% of a core is ~12x looser than the 2% acceptance bar
    # of an interleaved on/off A/B — this is the smoke alarm, not the
    # measurement.
    p = SamplingProfiler(hz=97.0)
    my = threading.get_ident()
    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        p._sample_once(my)
    per_sample = (time.perf_counter() - t0) / n
    assert per_sample * 97.0 < 0.25, f"{per_sample * 1e6:.0f}us/sample"


# ---------------------------------------------------------------------------
# calibration cache
# ---------------------------------------------------------------------------

def test_calibrate_writes_then_reuses_cache(tmp_path, monkeypatch):
    path = tmp_path / "machine.json"
    monkeypatch.setattr(roofline, "measure_host_memcpy_gbs",
                        lambda size_mb=64, repeats=3: 7.5)
    m1 = roofline.calibrate(str(path), size_mb=1)
    assert path.exists()
    assert m1["host_memcpy_gbs"] == 7.5
    assert m1["schema"] == roofline.MACHINE_SCHEMA

    # Second call must come from the file, not a fresh probe.
    def boom(size_mb=64, repeats=3):
        raise AssertionError("cache miss: re-probed despite machine.json")

    monkeypatch.setattr(roofline, "measure_host_memcpy_gbs", boom)
    m2 = roofline.calibrate(str(path), size_mb=1)
    assert m2["host_memcpy_gbs"] == 7.5
    # force=True deliberately re-probes (and here, trips the sentinel).
    with pytest.raises(AssertionError):
        roofline.calibrate(str(path), force=True, size_mb=1)


def test_calibrate_persist_false_writes_nothing(tmp_path, monkeypatch):
    path = tmp_path / "machine.json"
    monkeypatch.setattr(roofline, "measure_host_memcpy_gbs",
                        lambda size_mb=64, repeats=3: 3.0)
    m = roofline.calibrate(str(path), size_mb=1, persist=False)
    assert m["host_memcpy_gbs"] == 3.0
    assert not path.exists()  # read-only callers (doctor) leave no file


def test_load_machine_rejects_wrong_schema(tmp_path):
    path = tmp_path / "machine.json"
    path.write_text('{"schema": 999, "host_memcpy_gbs": 1.0}')
    assert roofline.load_machine(str(path)) is None


# ---------------------------------------------------------------------------
# roofline arithmetic
# ---------------------------------------------------------------------------

MACHINE = {
    "schema": 1,
    "host_memcpy_gbs": 4.0,
    "devices": [{"id": 0, "kind": "TPU v5e", "platform": "tpu",
                 "hbm_gbs": 819.0, "tflops": 197.0}],
}

MANIFEST = {
    "config": {"host_update_cap": 1024},
    "stats": {
        "bytes_in": 2_000_000_000,
        "host_map_split": {"scan_s": 1.0, "workers": 4},
        "spill_split": {"bytes": 1_000_000_000, "write_s": 2.0},
        "dispatch_split": {"dispatches": 100, "dispatch_s": 0.5},
        "ici_split": {"wire_bytes": 500_000_000, "all_to_all_s": 0.25,
                      "rounds": 2},
    },
}


def test_stage_rows_units():
    rows = {r["stage"]: r for r in roofline.stage_rows(MANIFEST, MACHINE)}
    scan = rows["host-map-scan"]
    assert scan["achieved_gbs"] == 2.0          # 2e9 B / 1 s / 1e9
    assert scan["frac"] == 0.5                  # vs the 4 GB/s host roof
    assert rows["spill-write"]["achieved_gbs"] == 0.5
    # Dispatch bytes follow the packed layout: 1 + 3*cap uint32 words.
    dsp = rows["dispatch"]
    assert dsp["bytes"] == 100 * (1 + 3 * 1024) * 4
    assert "device-merge" not in rows  # measured from the device trace
    a2a = rows["a2a-shuffle"]
    assert a2a["achieved_gbs"] == 2.0           # 5e8 B / 0.25 s
    assert a2a["frac"] == round(2.0 / 819.0, 4)


def test_device_merge_has_no_host_roof_fallback():
    # Against a host-only calibration, a device stage must NOT be scored
    # against the memcpy roof (it fabricates >100% fracs); the row stays,
    # roofless.
    machine = {"schema": 1, "host_memcpy_gbs": 4.0, "devices": []}
    rows = {r["stage"]: r for r in roofline.stage_rows(MANIFEST, machine)}
    assert rows["a2a-shuffle"]["frac"] is None


def test_roofline_report_headline_and_projection():
    doc = roofline.roofline_report(MANIFEST, MACHINE)
    assert doc["scan_achieved_gbs"] == 2.0
    assert doc["roofline_frac"] == 0.5
    # Projection: half the device roof over today's achieved scan rate.
    assert doc["device_map_projection_x"] == round(0.5 * 819.0 / 2.0, 2)
    assert doc["machine"]["device_hbm_gbs"] == 819.0


def test_stage_rows_skip_absent_planes():
    # A host-only word count with no spill/dispatch/ici blocks yields
    # exactly the scan row — absent stages are skipped, not zero-filled.
    m = {"config": {}, "stats": {"bytes_in": 10**9, "host_map_s": 2.0}}
    rows = roofline.stage_rows(m, {"host_memcpy_gbs": 4.0})
    assert [r["stage"] for r in rows] == ["host-map-scan"]
    assert rows[0]["achieved_gbs"] == 0.5
