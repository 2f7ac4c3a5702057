"""Pallas TPU kernel: the tokenize→hash segmented scan, fused to ONE pass.

`lax.associative_scan` evaluates the token-hash monoid in O(log N) array
passes — every pass streams all six uint32 lanes through HBM, ~40 full
traversals per chunk, which is why the scan dominates the device map step
(~30 ms/MB measured on v5e against sub-ms for the elementwise work). This
kernel computes the same scan in a single HBM traversal: the grid walks
16 KB blocks IN ORDER (TPU grids are sequential), each block is scanned
hierarchically in VMEM (within 128-byte rows, then across the 128 row
totals), and the running monoid element carries across blocks in SMEM
scratch — the classic blocked prefix scan, laid out for the VPU.

The monoid and byte classes are exactly ops/tokenize.py's (the combine is
shared code); outputs are the per-position inclusive hash pair and
word-char count, from which the caller derives token-end validity the same
way the scan path does. Equality with the scan path is asserted by
tests/test_tokenize.py over random bytes and real corpus slices
(interpret mode on CPU), so the two implementations cannot drift.

Used by ops/tokenize.tokenize_and_hash whenever the caller targets a TPU
(``use_pallas``); other backends keep the associative_scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mapreduce_rust_tpu.core.hashing import (
    H1_INIT,
    H1_MULT,
    H2_INIT,
    H2_MULT,
)

# The kernel runs in int32 (TPU's native 32-bit lane); the uint32 hash
# constants above 2^31 enter as their wrapped bit patterns — int32 mul/add
# wrap identically to uint32, so the final bitcast back is exact.
_H1_INIT_I32 = int(np.uint32(H1_INIT).astype(np.int32))
_H2_INIT_I32 = int(np.uint32(H2_INIT).astype(np.int32))

_ROWS = 128
_LANE = 128
BLOCK = _ROWS * _LANE  # 16 KB of bytes per grid step


def _combine(x, y):
    """The segmented-hash monoid on int32 lanes (bit-identical to uint32
    wrap-around): h -> h*m + a with reset at whitespace."""
    fx, m1x, a1x, m2x, a2x, cx = x
    fy, m1y, a1y, m2y, a2y, cy = y
    ry = fy != 0
    f = fx | fy
    m1 = jnp.where(ry, m1y, m1x * m1y)
    a1 = jnp.where(ry, a1y, a1x * m1y + a1y)
    m2 = jnp.where(ry, m2y, m2x * m2y)
    a2 = jnp.where(ry, a2y, a2x * m2y + a2y)
    c = jnp.where(ry, cy, cx + cy)
    return f, m1, a1, m2, a2, c


_IDENT = (0, 1, 0, 1, 0, 0)  # monoid identity per lane (f, m1, a1, m2, a2, c)


def _scan_inclusive(lanes, size: int):
    """Hillis-Steele inclusive scan along axis 1 (the lane axis) —
    log2(size) combine steps, every slice statically sized. Lane-axis only:
    Mosaic lowers lane concatenates fine but rejects offset sublane
    concatenates, so callers needing a sublane scan transpose around this
    (lax.associative_scan is out entirely — its recursion emits zero-width
    slices Mosaic cannot lower)."""
    res = lanes
    d = 1
    while d < size:
        shifted = []
        for ident, x in zip(_IDENT, res):
            pad = jnp.full((x.shape[0], d), jnp.int32(ident))
            shifted.append(jnp.concatenate([pad, x[:, : size - d]], axis=1))
        res = _combine(tuple(shifted), res)
        d *= 2
    return res


def _kernel(x_ref, h1_ref, h2_ref, cnt_ref, carry_ref):
    c = x_ref[:].astype(jnp.int32)  # (ROWS, LANE) byte values

    # Byte classes, arithmetically (the 256-entry tables in
    # core/hashing.byte_class_tables encode exactly these rules).
    is_ws = (c == 32) | ((c >= 9) & (c <= 13))
    lower = c | 32
    is_wc = (
        ((lower >= ord("a")) & (lower <= ord("z")) & (c < 128))
        | ((c >= ord("0")) & (c <= ord("9")))
        | (c == ord("_"))
        | (c >= 128)
    )

    one = jnp.int32(1)
    zero = jnp.int32(0)
    cp1 = c + one
    lanes = (
        is_ws.astype(jnp.int32),
        jnp.where(is_wc, jnp.int32(H1_MULT), one),
        jnp.where(is_wc, cp1, zero),
        jnp.where(is_wc, jnp.int32(H2_MULT), one),
        jnp.where(is_wc, cp1, zero),
        is_wc.astype(jnp.int32),
    )

    # Level 1: scan within each 128-byte row (consecutive bytes).
    scanned = _scan_inclusive(lanes, size=_LANE)
    # Level 2: exclusive scan of the row totals down the rows — transposed
    # to (1, ROWS) so the shifts stay on the lane axis (see _scan_inclusive).
    totals = tuple(jnp.swapaxes(x[:, _LANE - 1 :], 0, 1) for x in scanned)
    inc = _scan_inclusive(totals, size=_ROWS)
    ident = (zero, one, zero, one, zero, zero)
    exc = tuple(
        jnp.swapaxes(
            jnp.concatenate(
                [jnp.full((1, 1), i, jnp.int32), x[:, : _ROWS - 1]], axis=1
            ),
            0, 1,
        )
        for i, x in zip(ident, inc)
    )
    scanned = _combine(exc, scanned)  # broadcast (ROWS,1) over (ROWS,LANE)

    # Cross-block carry from SMEM (identity at block 0).
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for i, v in enumerate(ident):
            carry_ref[i] = v

    carry = tuple(carry_ref[i] for i in range(6))
    f, m1, a1, m2, a2, cnt = _combine(carry, scanned)
    for i, v in enumerate((f, m1, a1, m2, a2, cnt)):
        carry_ref[i] = v[_ROWS - 1, _LANE - 1]

    h1_ref[:] = jnp.int32(_H1_INIT_I32) * m1 + a1
    h2_ref[:] = jnp.int32(_H2_INIT_I32) * m2 + a2
    cnt_ref[:] = cnt


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_scan_pallas(chunk: jnp.ndarray, interpret: bool = False):
    """(h1 uint32[N], h2 uint32[N], word_char_count int32[N]) — the
    inclusive segmented scan at every byte position, one HBM pass.
    N must be a multiple of BLOCK (ops/tokenize pads ragged chunks)."""
    n = chunk.shape[0]
    if n % BLOCK != 0:
        raise ValueError(f"chunk length {n} not a multiple of {BLOCK}")
    grid = n // BLOCK
    x = chunk.reshape(grid * _ROWS, _LANE)
    # Inside shard_map the outputs vary across the mesh axis exactly like
    # the input; shard_map's vma check requires saying so.
    out = jax.ShapeDtypeStruct((grid * _ROWS, _LANE), jnp.int32,
                               vma=jax.typeof(chunk).vma)
    h1, h2, cnt = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((_ROWS, _LANE), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((_ROWS, _LANE), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, _LANE), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, _LANE), lambda i: (i, 0)),
        ],
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.SMEM((6,), jnp.int32)],
        interpret=interpret,
    )(x)
    return (
        h1.reshape(n).astype(jnp.uint32),
        h2.reshape(n).astype(jnp.uint32),
        cnt.reshape(n),
    )
