"""On-device tokenize→hash kernel.

This is the TPU-native replacement for the reference map hot loop — the
regex strip + whitespace split in ``wc::map`` (src/app/wc.rs:6-13) and the
per-pair hash in ``write_key_value_to_file`` (src/mr/worker.rs:111-115,129).
Instead of per-word string allocations and one awaited file write per pair
(src/mr/worker.rs:131-136), the whole chunk is processed as one fixed-shape
uint8 array:

1. byte classes via 256-entry lookup tables (whitespace / word-char —
   encoding the reference's ``[^\\w\\s]`` strip as data, not control flow);
2. a *segmented* associative scan computes, per byte position, the
   polynomial hash pair of the current whitespace-delimited token with
   punctuation bytes contributing the identity transform (so "don't" hashes
   as "dont", matching wc.rs:7-8 semantics);
3. token-end positions (non-ws byte followed by ws/EOF) with at least one
   word char emit a valid (k1, k2, value=1) record; everything else is
   masked padding.

The scan monoid: each byte is (reset, m, a) acting on h by h -> h*m + a.
    word char c:  (0, MULT, c+1)
    punctuation:  (0, 1, 0)          -- identity: deleted, no token break
    whitespace:   (1, 1, 0)          -- reset: token boundary
combine(x, y) = y.reset ? y : (x.reset | y.reset, x.m*y.m, x.a*y.m + y.a)
is associative, so ``lax.associative_scan`` evaluates it in O(N) work and
O(log N) depth — XLA-friendly, no data-dependent control flow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_rust_tpu.core.hashing import (
    H1_INIT,
    H1_MULT,
    H2_INIT,
    H2_MULT,
    SENTINEL,
    byte_class_tables,
)
from mapreduce_rust_tpu.core.kv import KVBatch


def _scan_combine(x, y):
    fx, m1x, a1x, m2x, a2x, cx = x
    fy, m1y, a1y, m2y, a2y, cy = y
    f = fx | fy
    m1 = jnp.where(fy, m1y, m1x * m1y)
    a1 = jnp.where(fy, a1y, a1x * m1y + a1y)
    m2 = jnp.where(fy, m2y, m2x * m2y)
    a2 = jnp.where(fy, a2y, a2x * m2y + a2y)
    c = jnp.where(fy, cy, cx + cy)
    return f, m1, a1, m2, a2, c


def _scan_combine_len(x, y):
    """_scan_combine plus a token-byte-length lane (resets at whitespace,
    +1 per non-ws byte incl. deleted punctuation) — the halo-exchange path
    uses it to detect tokens that began before the halo window
    (parallel/halo.py)."""
    *hx, lx = x
    *hy, ly = y
    out = _scan_combine(tuple(hx), tuple(hy))
    fy = y[0]
    return (*out, jnp.where(fy, ly, lx + ly))


def _tokenize(chunk: jnp.ndarray, last_is_boundary: bool, with_len: bool,
              use_pallas: bool = False):
    ws_tab, wc_tab = byte_class_tables()
    idx = chunk.astype(jnp.int32)
    is_ws = jnp.take(jnp.asarray(ws_tab), idx).astype(bool)

    if use_pallas and not with_len:
        # The fused Mosaic scan (ops/tokenize_pallas.py): one HBM pass in
        # place of the associative_scan's log-depth passes. It walks whole
        # BLOCKs, so a ragged chunk is padded with spaces — the scan is
        # inclusive and causal, so the first n outputs are unchanged.
        from mapreduce_rust_tpu.ops.tokenize_pallas import BLOCK, hash_scan_pallas

        n = chunk.shape[0]
        padded = jnp.pad(chunk, (0, -n % BLOCK), constant_values=0x20)
        h1, h2, cnts = (x[:n] for x in hash_scan_pallas(padded))
        tlen = None
    else:
        is_wc = jnp.take(jnp.asarray(wc_tab), idx).astype(bool)
        one = jnp.uint32(1)
        zero = jnp.uint32(0)
        cplus1 = chunk.astype(jnp.uint32) + one
        m1 = jnp.where(is_wc, jnp.uint32(H1_MULT), one)
        a1 = jnp.where(is_wc, cplus1, zero)
        m2 = jnp.where(is_wc, jnp.uint32(H2_MULT), one)
        a2 = jnp.where(is_wc, cplus1, zero)
        cnt = is_wc.astype(jnp.int32)

        if with_len:
            blen = (~is_ws).astype(jnp.int32)
            _, m1s, a1s, m2s, a2s, cnts, tlen = jax.lax.associative_scan(
                _scan_combine_len, (is_ws, m1, a1, m2, a2, cnt, blen)
            )
        else:
            _, m1s, a1s, m2s, a2s, cnts = jax.lax.associative_scan(
                _scan_combine, (is_ws, m1, a1, m2, a2, cnt)
            )
            tlen = None
        h1 = jnp.uint32(H1_INIT) * m1s + a1s
        h2 = jnp.uint32(H2_INIT) * m2s + a2s

    next_is_ws = jnp.concatenate(
        [is_ws[1:], jnp.full((1,), last_is_boundary, dtype=bool)]
    )
    is_end = (~is_ws) & next_is_ws
    valid = is_end & (cnts > 0)

    sent = jnp.uint32(SENTINEL)
    kv = KVBatch(
        k1=jnp.where(valid, h1, sent),
        k2=jnp.where(valid, h2, sent),
        value=valid.astype(jnp.int32),
        valid=valid,
    )
    return kv, tlen


@functools.partial(jax.jit, static_argnames=("last_is_boundary", "use_pallas"))
def tokenize_and_hash(chunk: jnp.ndarray, last_is_boundary: bool = True,
                      use_pallas: bool = False) -> KVBatch:
    """Tokenize+hash one uint8 byte chunk.

    Args:
      chunk: uint8[N] byte array. Host chunker pads with spaces, so padding
        never produces tokens.
      last_is_boundary: whether byte N-1 ends the stream (True for
        whitespace-aligned chunks; False when a halo from the right
        neighbor follows — see parallel/halo.py).
      use_pallas: the caller targets a TPU — take the fused Mosaic scan
        (bit-identical; tests/test_tokenize.py) instead of
        lax.associative_scan.

    Returns a KVBatch[N]: valid entries sit at token-end byte positions
    with value 1 (one occurrence).
    """
    kv, _ = _tokenize(chunk, last_is_boundary, with_len=False, use_pallas=use_pallas)
    return kv


def tokenize_and_hash_with_len(chunk: jnp.ndarray, last_is_boundary: bool = True):
    """(KVBatch[N], token_byte_len int32[N]) — length at a token's end byte
    is the whole token's byte count (incl. deleted punctuation), which the
    halo path compares against the window position to detect tokens longer
    than the halo (parallel/halo.py). Trace-time only (call under jit)."""
    return _tokenize(chunk, last_is_boundary, with_len=True)


def tokenize_reference_host(data: bytes) -> dict[tuple[int, int], int]:
    """Host oracle: hash-pair → count, same semantics as the device kernel."""
    from mapreduce_rust_tpu.core.hashing import hash_word, tokenize_host

    counts: dict[tuple[int, int], int] = {}
    for w in tokenize_host(data):
        k = hash_word(w)
        counts[k] = counts.get(k, 0) + 1
    return counts
