"""Mesh all_to_all shuffle: results invariant to mesh size, skew replay,
sharded-state spill — on the 8-virtual-CPU-device mesh (conftest)."""

import collections
import pathlib

import numpy as np
import pytest

from mapreduce_rust_tpu.apps import InvertedIndex, WordCount
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.parallel.shuffle import make_mesh, make_shuffle_step_fns
from mapreduce_rust_tpu.runtime.chunker import doc_table
from mapreduce_rust_tpu.runtime.driver import run_job

CORPUS = pathlib.Path("/root/reference/src/data")

TEXT = (
    "we hold these truths to be self evident that all men are created equal "
    "— don’t “stop” now, naïve café friends!\n"
) * 120


def write_inputs(tmp_path, texts):
    paths = []
    for i, t in enumerate(texts):
        p = tmp_path / f"doc-{i}.txt"
        p.write_bytes(t if isinstance(t, bytes) else t.encode())
        paths.append(str(p))
    return paths


def oracle_counts(texts) -> dict:
    total = collections.Counter()
    for t in texts:
        raw = t if isinstance(t, bytes) else t.encode()
        total.update(reference_word_counts(raw))
    return {w.encode(): c for w, c in total.items()}


def mesh_cfg(tmp_path, n, **kw) -> Config:
    defaults = dict(
        chunk_bytes=2048,
        merge_capacity=1 << 14,
        reduce_n=4,
        mesh_shape=n,
        output_dir=str(tmp_path / "out"),
        device="cpu",
    )
    defaults.update(kw)
    return Config(**defaults)


def test_mesh_devices_available():
    mesh = make_mesh(8, "cpu")
    assert mesh.devices.size == 8


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_word_count_invariant_to_mesh_size(tmp_path, n_devices):
    paths = write_inputs(tmp_path, [TEXT])
    res = run_job(mesh_cfg(tmp_path, n_devices), paths, write_outputs=False)
    assert res.table == oracle_counts([TEXT])


def test_mesh_equals_single_device(tmp_path):
    texts = [TEXT, TEXT[: len(TEXT) // 2] + " unique1 unique2"]
    paths = write_inputs(tmp_path, texts)
    single = run_job(mesh_cfg(tmp_path, None, mesh_shape=None), paths, write_outputs=False)
    mesh = run_job(mesh_cfg(tmp_path, 8), paths, write_outputs=False)
    assert mesh.table == single.table == oracle_counts(texts)


def test_mesh_bucket_skew_replays_exactly(tmp_path):
    # Many distinct words per chunk + tiny bucket_capacity_factor → certain
    # bucket overflow → the skew tier must replay and stay exact.
    text = " ".join(f"k{i:05d}" for i in range(3000))
    paths = write_inputs(tmp_path, [text])
    cfg = mesh_cfg(tmp_path, 4, bucket_capacity_factor=0.05)
    res = run_job(cfg, paths, write_outputs=False)
    assert res.stats.bucket_skew_replays > 0
    assert res.table == oracle_counts([text])


def test_mesh_partial_overflow_replays_exactly(tmp_path):
    text = " ".join(f"m{i:05d}" for i in range(3000))
    paths = write_inputs(tmp_path, [text])
    cfg = mesh_cfg(tmp_path, 4, chunk_bytes=8192, partial_capacity=64)
    res = run_job(cfg, paths, write_outputs=False)
    assert res.stats.partial_overflow_replays > 0
    assert res.table == oracle_counts([text])


def test_mesh_state_spill_exact(tmp_path):
    words = " ".join(f"s{i:04d}" for i in range(1200))
    text = words + " " + words
    paths = write_inputs(tmp_path, [text])
    cfg = mesh_cfg(tmp_path, 4, merge_capacity=512, chunk_bytes=2048)
    res = run_job(cfg, paths, write_outputs=False)
    assert res.stats.spill_events > 0
    assert res.table == oracle_counts([text])


def test_mesh_inverted_index(tmp_path):
    texts = ["apple banana apple", "banana cherry", "apple date cherry", "egg"]
    paths = write_inputs(tmp_path, texts)
    res = run_job(mesh_cfg(tmp_path, 4), paths, app=InvertedIndex(), write_outputs=False)
    oracle: dict = {}
    for d, t in enumerate(texts):
        for w in reference_word_counts(t.encode()):
            oracle.setdefault(w.encode(), set()).add(d)
    assert res.table == {w: sorted(s) for w, s in oracle.items()}


@pytest.mark.skipif(not CORPUS.exists(), reason="reference corpus not mounted")
def test_mesh_real_corpus_golden(tmp_path):
    raw = (CORPUS / "gut-2.txt").read_bytes()
    paths = write_inputs(tmp_path, [raw])
    cfg = mesh_cfg(tmp_path, 8, chunk_bytes=16384, merge_capacity=1 << 15)
    res = run_job(cfg, paths, write_outputs=False)
    assert res.table == oracle_counts([raw])


def test_shuffle_partitions_keys_by_hash_class():
    # Direct kernel check: after map_shuffle, chip i's records all satisfy
    # k1 % D == i (the all_to_all routed correctly).
    mesh = make_mesh(4, "cpu")
    app = WordCount()
    fns = make_shuffle_step_fns(app, u_cap=256, bucket_cap=256, mesh=mesh)
    texts = [b"aa bb cc dd ee ff gg hh ii jj kk ll", b"mm nn oo pp", b"qq rr", b"ss tt uu"]
    chunks = np.full((4, 512), 0x20, dtype=np.uint8)
    for i, t in enumerate(texts):
        chunks[i, : len(t)] = np.frombuffer(t, dtype=np.uint8)
    tables = np.stack([doc_table(0)] * 4)
    local, p_ovf, b_ovf, live = fns[0](chunks, tables)
    assert int(np.sum(p_ovf)) == 0 and int(np.sum(b_ovf)) == 0
    # Each chip's valid bucket rows before the all_to_all: its chunk's
    # distinct words (12, 4, 2, 3).
    assert np.asarray(live).tolist() == [12, 4, 2, 3]
    k1 = np.asarray(local.k1)
    valid = np.asarray(local.valid)
    total = 0
    for chip in range(4):
        keys = k1[chip][valid[chip]]
        assert all(int(k) % 4 == chip for k in keys)
        total += len(keys)
    # 21 distinct words in all texts combined
    assert total == 21
