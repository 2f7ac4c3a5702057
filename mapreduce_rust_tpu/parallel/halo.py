"""Halo exchange: tokenize a byte stream sharded across chips, cut ANYWHERE.

The chunker (runtime/chunker.py) aligns chunk cuts to whitespace on the
host. For a stream already resident across the mesh — one contiguous byte
shard per chip, cut at arbitrary offsets — words straddling shard edges
must still count exactly once. This is the framework's sequence-parallel
story (SURVEY.md §5 long-context row): the reference instead requires a
whole input file per task in one String (src/mr/worker.rs:65-77), so its
sequence ceiling is host RAM and its "alignment" is the file boundary.

Scheme (one `lax.ppermute` pair over ICI, then a purely local scan):

    window_i = [ tail_H(shard_{i-1}) | shard_i | head_1(shard_{i+1}) ]

- ownership: chip i emits exactly the tokens whose END byte lies in its
  own shard — a straddling word ends in exactly one shard, so it is
  counted exactly once, with its hash completed from the left halo.
- the 1-byte right probe decides whether a token ending at the shard's
  last byte really ends there (next byte whitespace) or continues into
  the right neighbor (then THAT chip owns and hashes it via its halo).
- chips 0 / D-1 see synthetic whitespace beyond the stream ends.
- exactness guard: a token longer than the halo H (= Config.max_word_len)
  that began before the window start would hash truncated — detected via
  the token-byte-length scan lane (ops/tokenize.tokenize_and_hash_with_len)
  and *counted* per chip, like every other capacity fault in this
  framework; size H to the corpus's longest token for exact results.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mapreduce_rust_tpu.core.kv import KVBatch
from mapreduce_rust_tpu.ops.tokenize import tokenize_and_hash_with_len
from mapreduce_rust_tpu.parallel.shuffle import AXIS
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_sharded_tokenizer(mesh: Mesh, halo: int):
    """Jitted fn: shards uint8[D, N] → (KVBatch[D, halo+N+1], trunc [D]).

    Per chip the returned batch holds the tokens that END in its shard
    (valid-masked; positions are window-relative). trunc counts tokens
    whose start precedes the window — nonzero means halo too small.
    """
    d = mesh.devices.size

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(AXIS),
        out_specs=(P(AXIS), P(AXIS)),
    )
    def sharded_tokenize(shards: jnp.ndarray):
        me = shards[0]  # [N]
        n = me.shape[0]
        idx = jax.lax.axis_index(AXIS)
        space = jnp.uint8(0x20)

        # My tail goes right (chip i+1's left halo); my head byte goes left.
        left_halo = jax.lax.ppermute(
            me[-halo:], AXIS, perm=[(i, i + 1) for i in range(d - 1)]
        )
        right_probe = jax.lax.ppermute(
            me[:1], AXIS, perm=[(i + 1, i) for i in range(d - 1)]
        )
        # Non-participants receive zeros; the stream ends are whitespace.
        left_halo = jnp.where(idx == 0, space, left_halo)
        right_probe = jnp.where(idx == d - 1, space, right_probe)

        window = jnp.concatenate([left_halo, me, right_probe])
        kv, tlen = tokenize_and_hash_with_len(window, last_is_boundary=True)

        pos = jnp.arange(halo + n + 1)
        own = (pos >= halo) & (pos < halo + n)
        valid = kv.valid & own
        # Token end at pos with byte length tlen started at pos-tlen+1.
        # tlen can never exceed pos+1 (the scan sees only the window), so a
        # token reaching all the way to window start — tlen == pos+1 — may
        # have begun before it: possibly truncated hash. No false positives
        # while tokens are <= halo bytes (such a token ending in the shard
        # cannot reach window position 0).
        trunc = jnp.sum((valid & (tlen >= pos + 1)).astype(jnp.int32))

        sent = jnp.uint32(0xFFFFFFFF)
        masked = KVBatch(
            k1=jnp.where(valid, kv.k1, sent),
            k2=jnp.where(valid, kv.k2, sent),
            value=jnp.where(valid, kv.value, 0),
            valid=valid,
        )
        return (
            KVBatch(*(x[None] for x in masked)),
            trunc[None],
        )

    return sharded_tokenize


def shard_stream(data: bytes, mesh: Mesh, pad: int | None = None):
    """Host helper: pack a byte stream into the [D, N] layout the sharded
    tokenizer wants — cut at arbitrary equal offsets, trailing space pad."""
    import numpy as np

    d = mesh.devices.size
    n = pad or -(-len(data) // d)  # ceil
    buf = np.full(d * n, 0x20, dtype=np.uint8)
    buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    return buf.reshape(d, n)
