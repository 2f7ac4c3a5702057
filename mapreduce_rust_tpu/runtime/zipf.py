"""The seeded Zipf word corpus with generator-side ground truth.

Tokens are the fixed 8 bytes ``b'w%06x '`` of a rank drawn Zipf(s) over a
``vocab``-rank support by inverse-CDF sampling. The true per-rank counts
come from the GENERATOR (``np.bincount`` of the drawn ranks), so exactness
at 10^6+ distinct keys is checked against ground truth, not against a
second tokenizer. One copy, shared by ``bench.py`` and ``chip_smoke.py``.
Numpy only: importing it pulls in no JAX.
"""

from __future__ import annotations

import os
import pathlib

import numpy as np

ZIPF_VOCAB = 1 << 21   # 2M distinct tokens
ZIPF_S = 1.05          # exponent: heavy head, massive distinct tail
ZIPF_SEED = 20260730


def atomic_np_save(path: pathlib.Path, arr) -> None:
    """Commit a ground-truth array atomically (tmp + rename), cleaning the
    tmp on failure."""
    tmp = path.with_suffix(".npy.tmp")
    try:
        with open(tmp, "wb") as f:
            np.save(f, arr)
        os.replace(tmp, path)
    except BaseException:
        try:
            tmp.unlink()
        except OSError:
            pass
        raise


def zipf_sampler(vocab: int, s: float):
    """(cdf, token_table) — the inverse-CDF Zipf sampler every
    high-cardinality corpus draws from. Token rank r is b'w%06x '."""
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** s
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    table = np.frombuffer(
        b"".join(b"w%06x " % r for r in range(vocab)), dtype=np.uint8
    ).reshape(vocab, 8)
    return cdf, table


def write_zipf_tokens(f, rng, cdf, table, n_tokens: int, on_block) -> None:
    """Stream n_tokens sampled tokens into f in 4M-token blocks;
    on_block(ranks) records the generator-side ground truth."""
    left = n_tokens
    while left > 0:
        block = min(left, 4 << 20)
        ranks = np.searchsorted(cdf, rng.random(block))
        on_block(ranks)
        f.write(table[ranks].tobytes())
        left -= block
    f.write(b"\n")


def build_zipf_corpus(out: pathlib.Path, target_bytes: int,
                      vocab: int = ZIPF_VOCAB, s: float = ZIPF_S,
                      seed: int = ZIPF_SEED) -> tuple[pathlib.Path, pathlib.Path]:
    """Write (or reuse) a corpus of at least ``target_bytes`` at ``out``.
    Returns (corpus_path, counts_path): counts_path holds the int64[vocab]
    per-rank ground truth. The counts file commits last, atomically, so a
    torn run never passes for a finished one."""
    out = pathlib.Path(out)
    counts_p = out.with_suffix(".counts.npy")
    if out.exists() and counts_p.exists() and out.stat().st_size >= target_bytes:
        return out, counts_p
    out.parent.mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(seed)
    cdf, table = zipf_sampler(vocab, s)
    counts = np.zeros(vocab, dtype=np.int64)
    try:
        with open(out, "wb") as f:
            write_zipf_tokens(
                f, rng, cdf, table, target_bytes // 8 + 1,
                lambda ranks: counts.__iadd__(np.bincount(ranks, minlength=vocab)),
            )
        atomic_np_save(counts_p, counts)
    except BaseException:
        for p in (out, counts_p):
            try:
                p.unlink()
            except OSError:
                pass
        raise
    return out, counts_p


def rank_counts(output_files, vocab: int = ZIPF_VOCAB) -> tuple[np.ndarray, int]:
    """(int64[vocab] counts, line count) parsed from word_count's
    ``mr-*.txt`` outputs ("w<hex rank> <count>" lines) — the side of the
    exactness check that comes from the system under test."""
    got = np.zeros(vocab, dtype=np.int64)
    n_lines = 0
    for path in output_files:
        with open(path, "rb") as fh:
            for line in fh:
                w, v = line.rsplit(b" ", 1)
                got[int(w[1:], 16)] = int(v)
                n_lines += 1
    return got, n_lines
