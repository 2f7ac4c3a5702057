"""KVBatch — the struct-of-arrays intermediate record batch.

Replaces the reference's ``KeyValue { key: String, value: String }``
(src/lib.rs:9-23). Strings cannot live in fixed-shape device memory, so the
universal intermediate record on TPU is a padded struct of arrays:

    k1, k2 : uint32[N]  — the 64-bit-equivalent key hash pair
    value  : int32[N]   — app payload (count=1 for word_count, doc_id for
                          inverted_index, ...)
    valid  : bool[N]    — padding/liveness mask

The reference's KeyValue deliberately does *not* derive Serialize
(src/lib.rs:9) — pairs can never cross the RPC plane and move only through
files. The same invariant holds here: KVBatch never crosses the control
plane; it moves between chips only via ICI collectives (parallel/shuffle.py,
planned).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from mapreduce_rust_tpu.core.hashing import SENTINEL


class KVBatch(NamedTuple):
    """Padded batch of (key-hash-pair, value) records. A JAX pytree."""

    k1: jnp.ndarray  # uint32[N]
    k2: jnp.ndarray  # uint32[N]
    value: jnp.ndarray  # int32[N]
    valid: jnp.ndarray  # bool[N]

    @property
    def capacity(self) -> int:
        return self.k1.shape[-1]

    def take_front(self, n: int) -> "KVBatch":
        """First n slots. Reduce outputs are front-packed (ops/groupby.py),
        so this is the compaction primitive for partial/update batches."""
        return KVBatch(self.k1[:n], self.k2[:n], self.value[:n], self.valid[:n])

    @staticmethod
    def empty(capacity: int) -> "KVBatch":
        return KVBatch(
            k1=jnp.full((capacity,), SENTINEL, dtype=jnp.uint32),
            k2=jnp.full((capacity,), SENTINEL, dtype=jnp.uint32),
            value=jnp.zeros((capacity,), dtype=jnp.int32),
            valid=jnp.zeros((capacity,), dtype=bool),
        )

    @staticmethod
    def from_host(keys: np.ndarray, values: np.ndarray, capacity: int | None = None) -> "KVBatch":
        """Build a batch from host arrays: keys uint32[n,2], values int32[n]."""
        n = keys.shape[0]
        cap = capacity or n
        if n > cap:
            raise ValueError(f"{n} records exceed capacity {cap}")
        k1 = np.full((cap,), SENTINEL, dtype=np.uint32)
        k2 = np.full((cap,), SENTINEL, dtype=np.uint32)
        val = np.zeros((cap,), dtype=np.int32)
        ok = np.zeros((cap,), dtype=bool)
        k1[:n] = keys[:, 0]
        k2[:n] = keys[:, 1]
        val[:n] = values
        ok[:n] = True
        return KVBatch(jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(val), jnp.asarray(ok))

    def to_host(self) -> tuple[np.ndarray, np.ndarray]:
        """Return (keys uint32[n,2], values int32[n]) for valid records only.

        One batched device_get for all four fields — four separate
        np.asarray calls would be four device→host round trips.
        """
        import jax

        k1, k2, value, valid = (
            np.asarray(x)
            for x in jax.device_get((self.k1, self.k2, self.value, self.valid))
        )
        keys = np.stack([k1[valid], k2[valid]], axis=1)
        return keys, value[valid]
