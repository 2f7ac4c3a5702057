"""The HiBench word-count deployment on a four-chip mesh
(``benchmark/configs/wordcount-hibench-mesh4.json``) on four of the CPU's
virtual devices: its output against the benchmark's plain reference and
the one-chip path, every key on one shard, the live-row counter of the
shuffle against a NumPy count, on the fast tier and on the replay tiers,
and a failed shard readback that fails the job."""

import dataclasses
import json
import pathlib
import sys
import types

import numpy as np
import pytest

from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.parallel import shuffle
from mapreduce_rust_tpu.runtime.chunker import iter_chunks
from mapreduce_rust_tpu.runtime.driver import run_job

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CONF = json.loads((BENCH / "configs" / "wordcount-hibench-mesh4.json").read_text())
ONE_CHIP = json.loads((BENCH / "configs" / "wordcount-hibench.json").read_text())
UNIFORM = json.loads((BENCH / "traffic" / "uniform-320m.json").read_text())
SEED = 2**31 + 29


def _load(path: pathlib.Path, name: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def corpus(tmp_path, traffic: dict):
    """The benchmark's seeded corpus and the reference's truth of it."""
    from corpus import write_corpus

    ref = _load(BENCH / "reference" / "word_count.py", "bench_ref_word_count")
    truth = ref.Reference(traffic)
    paths = write_corpus(tmp_path / "in", traffic, SEED, truth.add)
    return [str(p) for p in paths], truth


def job_cfg(tmp_path, run: dict, out: str, **kw) -> Config:
    return Config(device="cpu", input_dir=str(tmp_path / "in"),
                  work_dir=str(tmp_path / f"work-{out}"),
                  output_dir=str(tmp_path / out), **dict(run, **kw))


def chunk_distinct_words(paths, chunk_bytes: int) -> int:
    """Σ over the job's chunks of each chunk's distinct words."""
    total = 0
    for c in iter_chunks(paths, chunk_bytes):
        total += len(np.unique(np.array(c.data[: c.nbytes].tobytes().split())))
    return total


def outputs(cfg: Config) -> dict:
    return {p.name: p.read_bytes()
            for p in sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))}


def test_hibench_mesh4_matches_reference_and_one_chip(tmp_path):
    """The configuration's own run fields on 4.5 chunks of its traffic:
    two groups, the second padded with space chunks."""
    run = CONF["run"]
    traffic = dict(UNIFORM, bytes_per_doc=run["chunk_bytes"] * 9 // 2)
    paths, truth = corpus(tmp_path, traffic)
    mesh = job_cfg(tmp_path, run, "mesh")
    s = run_job(mesh, paths).stats
    assert truth.compare(sorted(pathlib.Path(mesh.output_dir).glob("mr-*.txt"))) == {
        "bad_lines": 0, "wrong_words": 0}
    assert s.mesh_rounds == 2 and s.partial_overflow_replays == 0
    assert sum(s.mesh_shard_rows) == truth.state_keys() == 1000
    assert s.shuffle_live_rows == chunk_distinct_words(paths, run["chunk_bytes"])
    fill = _load(BENCH / "metrics" / "shuffle_fill.py", "bench_shuffle_fill").read(
        types.SimpleNamespace(jobs=[types.SimpleNamespace(stats=s)]))
    assert 0 < fill <= 1
    assert s.mesh_feed_s > 0

    one = job_cfg(tmp_path, dict(ONE_CHIP["run"], reduce_n=run["reduce_n"]), "one")
    run_job(one, paths)
    assert outputs(one) == outputs(mesh) and len(outputs(mesh)) == run["reduce_n"]


@pytest.mark.parametrize("tier", ["fast", "partial", "skew"])
def test_shuffle_live_rows_exact_on_every_tier(tmp_path, tier):
    """Zipf keys over small chunks, so each chunk holds its own number of
    distinct words. ``partial``: a partial capacity below every chunk's
    distinct words replays each group through the chunk-wide tier;
    ``skew``: buckets too small for their hash class replay each group
    through the bucket_cap = u_cap tier."""
    traffic = {"docs": 3, "bytes_per_doc": 40000, "vocab": 4096, "zipf_s": 1.05}
    paths, truth = corpus(tmp_path, traffic)
    run = dict(CONF["run"], chunk_bytes=8192, merge_capacity=1 << 14)
    kw = {"fast": {}, "partial": {"partial_capacity": 64},
          "skew": {"bucket_capacity_factor": 0.25}}[tier]
    cfg = job_cfg(tmp_path, run, "out", **kw)
    s = run_job(cfg, paths).stats
    assert truth.compare(sorted(pathlib.Path(cfg.output_dir).glob("mr-*.txt"))) == {
        "bad_lines": 0, "wrong_words": 0}
    groups = -(-s.chunks_dispatched // 4)
    replays = {"fast": 0, "partial": groups, "skew": groups}[tier]
    assert s.partial_overflow_replays + s.bucket_skew_replays == replays
    assert (s.partial_overflow_replays > 0) == (tier == "partial")
    assert s.mesh_rounds == groups + replays
    assert s.shuffle_live_rows == chunk_distinct_words(paths, run["chunk_bytes"])
    assert sum(s.mesh_shard_rows) == truth.state_keys()
    assert 0 < s.shuffle_live_rows * shuffle.RECORD_WIRE_BYTES <= s.shuffle_wire_bytes


def test_failed_shard_readback_fails_the_job(tmp_path, monkeypatch):
    """A shard count that cannot be read back is an error of the job, not
    an empty ``mesh_shard_rows`` that reads as keys on no shard."""
    traffic = {"docs": 1, "bytes_per_doc": 4000, "vocab": 64, "zipf_s": 0.0}
    paths, truth = corpus(tmp_path, traffic)
    cfg = job_cfg(tmp_path, dict(CONF["run"], chunk_bytes=2048,
                                 merge_capacity=1 << 12), "out")
    assert sum(run_job(cfg, paths).stats.mesh_shard_rows) == truth.state_keys()

    def broken(state):
        raise RuntimeError("shard readback failed")

    monkeypatch.setattr(shuffle, "shard_fill_counts", broken)
    with pytest.raises(RuntimeError, match="shard readback failed"):
        run_job(dataclasses.replace(cfg, output_dir=str(tmp_path / "again")), paths)
