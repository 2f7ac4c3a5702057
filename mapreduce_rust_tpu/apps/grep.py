"""Grep: which documents contain each query word (and the full posting
list per word) — distributed exact-match search, the classic second
MapReduce application (the reference ships only word count,
src/app/mod.rs; this demonstrates the UDF seam the reference hard-wires
shut, src/mr/worker.rs:148,175, carrying a *filter*, not just a stamp).

The TPU formulation is a filtered inverted index:

- the query words are normalized + hashed ONCE on the host with the
  corpus pipeline's own rules (core/normalize + core/hashing), so a query
  like "don't" matches the corpus token "dont" exactly as the reference's
  regex strip would produce it (src/app/wc.rs:7-8);
- device_map compares every record's hash pair against the (small,
  trace-time-constant) query set — an [N, Q] broadcast compare the
  compiler fuses — and invalidates everything else, then stamps doc_id as
  the value like inverted_index;
- combine_op "distinct" builds the posting set associatively across
  chunks/chips; only query keys ever occupy state, so a grep over a
  10 GB corpus holds Q keys of device state.

The host-map engine applies the same filter via App.host_mask (the
host-side twin of device_map's invalidation) before packing updates, so
both engines stay interchangeable and tested equal.
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np

from mapreduce_rust_tpu.apps.inverted_index import InvertedIndex
from mapreduce_rust_tpu.core.kv import KVBatch


@functools.lru_cache(maxsize=64)
def _query_keys(query: tuple[str, ...]) -> np.ndarray:
    """uint32 [Q, 2] hash pairs of the normalized query words. Each query
    term must normalize to exactly one token — a term that vanishes
    (all punctuation) or splits (contains whitespace) is a usage error
    worth failing loudly over, not silently matching nothing."""
    from mapreduce_rust_tpu.core.hashing import hash_words
    from mapreduce_rust_tpu.core.normalize import normalize_unicode
    from mapreduce_rust_tpu.runtime.dictionary import extract_words

    if not query:
        raise ValueError("grep needs at least one --query word")
    words = []
    for term in query:
        raw = term.encode() if isinstance(term, str) else bytes(term)
        toks = extract_words(normalize_unicode(raw))
        if len(toks) != 1:
            raise ValueError(
                f"grep query {term!r} normalizes to {len(toks)} tokens "
                f"({toks!r}); each query must be exactly one word"
            )
        words.append(toks[0])
    arr = hash_words(words)
    # The cached array is shared by every caller (device_map, host_mask,
    # CLI validation) — freeze it so a mutating caller fails loudly
    # instead of silently corrupting all subsequent queries' filters.
    arr.flags.writeable = False
    return arr


@dataclasses.dataclass(frozen=True)
class Grep(InvertedIndex):
    """A filtered inverted index — literally: posting-list values, doc-id
    stamping (host_values) and egress format are inherited; grep adds the
    query-key filter on both engines."""

    name: str = "grep"
    query: tuple[str, ...] = ()

    def device_map(self, kv: KVBatch, doc_ids: jnp.ndarray) -> KVBatch:
        from mapreduce_rust_tpu.core.hashing import SENTINEL

        qk = _query_keys(self.query)  # trace-time constant, Q is small
        match = jnp.any(
            (kv.k1[:, None] == jnp.asarray(qk[:, 0])[None, :])
            & (kv.k2[:, None] == jnp.asarray(qk[:, 1])[None, :]),
            axis=1,
        )
        valid = kv.valid & match
        # Filtered-out records become SENTINEL-keyed padding, not
        # real-keyed invalid rows: padding sorts to the back and melts
        # into one dead segment, so state only ever holds query keys —
        # an invalid row with a real key would instead occupy a distinct
        # (dead) state slot per corpus word.
        sent = jnp.uint32(SENTINEL)
        return KVBatch(
            k1=jnp.where(valid, kv.k1, sent),
            k2=jnp.where(valid, kv.k2, sent),
            value=jnp.where(valid, doc_ids, 0),
            valid=valid,
        )

    def host_mask(self, keys: np.ndarray) -> np.ndarray:
        qk = _query_keys(self.query)
        return (
            (keys[:, 0][:, None] == qk[:, 0][None, :])
            & (keys[:, 1][:, None] == qk[:, 1][None, :])
        ).any(axis=1)
