"""The program-span readers on a hand-made Context: each returns its field's
sum over the window's jobs, and nothing where there is nothing to read."""

import types

import pytest

import run
from conftest import BENCH


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


def ctx(*stats, window_s=4.0):
    jobs = [run.Job(out_dir=None, stats=s) for s in stats]
    return run.Context(jobs=jobs, window_s=window_s, bytes_in=0, cfg=None,
                       device_kind="cpu")


def job(**fields):
    base = dict(job_setup_s=0.0, state_readback_s=0.0, scan_wait_s=0.0,
                host_map_workers=0)
    return types.SimpleNamespace(**dict(base, **fields))


@pytest.mark.parametrize("metric,field", [
    ("job_setup_frac", "job_setup_s"),
    ("state_readback_frac", "state_readback_s"),
    ("scan_wait_frac", "scan_wait_s"),
])
def test_reader_sums_its_field_over_the_window(metric, field):
    c = ctx(job(**{field: 0.25}, host_map_workers=3),
            job(**{field: 0.75}, host_map_workers=3), window_s=4.0)
    assert reader(metric).read(c) == pytest.approx(0.25)


def test_scan_wait_reads_nothing_without_the_host_engine():
    c = ctx(job(scan_wait_s=0.5), job(scan_wait_s=0.5))
    assert reader("scan_wait_frac").read(c) is None


@pytest.mark.parametrize("metric", ["job_setup_frac", "state_readback_frac"])
def test_span_readers_read_nothing_from_a_program_without_the_field(metric):
    """A program older than these spans: its JobStats lacks the field."""
    c = ctx(types.SimpleNamespace(scan_wait_s=0.1, host_map_workers=2))
    assert reader(metric).read(c) is None
