"""Inverted index: term → sorted list of doc_ids containing it.

The inverted-index job of the MapReduce paper (Dean and Ghemawat, OSDI
2004, §2.3): map emits ⟨word, document ID⟩, reduce emits ⟨word, sorted
list(document ID)⟩. A document is one input file, and its doc_id is the
file's index (runtime/chunker.py). The TPU formulation:

- the chunker packs consecutive documents into one chunk with a table of
  where each starts; device_map stamps every record with its own doc_id
  (ops/groupby.position_doc_ids), so the stream becomes (term-hash, doc_id)
  pairs;
- combine_op "distinct" makes the value part of the sort key
  (ops/groupby.py): duplicates of (term, doc) collapse on device, and the
  posting *set* builds associatively across chunks and chips — no
  variable-length lists ever exist in device memory. Pairs past the
  device state's capacity spill to the host accumulator, which folds them
  by set union;
- finalize groups the surviving (term, doc) pairs by term on the host and
  emits 'word d0,d1,...' with doc_ids ascending: each term's postings are
  a slice of the fold's sorted doc column (App.emit_postings).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from mapreduce_rust_tpu.apps.base import App
from mapreduce_rust_tpu.core.kv import KVBatch


@dataclasses.dataclass(frozen=True)
class InvertedIndex(App):
    name: str = "inverted_index"
    combine_op: str = "distinct"

    def device_map(self, kv: KVBatch, doc_ids: jnp.ndarray) -> KVBatch:
        return KVBatch(
            k1=kv.k1,
            k2=kv.k2,
            value=jnp.where(kv.valid, doc_ids, 0),
            valid=kv.valid,
        )

    def host_values(self, counts, doc_id: int):
        """Every unique term of the window posts this window's doc_id —
        the host-engine mirror of device_map's doc_id stamp (the host
        engine's windows never span two documents)."""
        import numpy as np

        return np.full(len(counts), doc_id, dtype=np.uint32)

    def format_line(self, word: bytes, value) -> bytes:
        return b"%s %s" % (word, b",".join([b"%d" % d for d in value]))
