"""The app plugin seam — a real one this time.

The reference's UDF indirection takes boxed functions but hard-codes
``Box::new(wc::map)`` / ``Box::new(wc::reduce)`` at its only call sites
(src/mr/worker.rs:16-25,148,175), so the app is compile-time-fixed to word
count. Here an app is a first-class object the driver is parameterized by,
split along the TPU-natural seams:

- **device_map** — a jit-traceable transform applied on device to the
  tokenized KVBatch of each chunk, given each record's doc id (e.g. stamp
  it as the value). Runs
  inside the driver's compiled step; must be shape-preserving and pure.
- **combine_op** — the associative reduce op (ops/groupby.REDUCE_OPS).
  Associativity is the load-bearing contract: it is what lets per-chunk
  partials merge on device, spill tails sum on host, and per-chip partials
  merge across the mesh, all without coordination. (The reference's
  ``wc::reduce`` = values.len() is associative only by luck and is applied
  exactly once per key; src/app/wc.rs:15-17.)
- **finalize** — host-side egress: turns the final (word, value) table into
  output lines, partitioned by ``hash(key) % reduce_n`` like the reference's
  mr-{r}.txt split (src/mr/worker.rs:121,129,167).

Apps register by name in ``apps.REGISTRY`` (apps/__init__.py), the
counterpart of the reference's one-line module registry (src/app/mod.rs).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

import jax.numpy as jnp

from mapreduce_rust_tpu.core.kv import KVBatch

#: finalize receives, per key: int value for scalar ops ("sum"/"max"/"min"),
#: or a sorted list[int] of distinct values for "distinct".
FinalValue = "int | list[int]"


@dataclasses.dataclass(frozen=True)
class App:
    """Base app: identity device_map, sum combiner, 'word value' egress."""

    name: str = "app"
    combine_op: str = "sum"
    #: Range apps (sort): R−1 packed-uint64 splitters, bound by the
    #: sampled-splitter subsystem (runtime/splitter.prepare_app) before
    #: the job streams — NEVER hand-rolled (mrlint rule 15). () = unbound.
    splitters: tuple = ()
    #: Multi-corpus apps (join): cumulative doc counts of corpora[:-1] in
    #: the flat doc_id space, bound by prepare_app. A record's corpus is
    #: bisect(corpus_bounds, doc_id) — the "which side" signal device_map
    #: and finalize read. () = single corpus.
    corpus_bounds: tuple = ()

    #: "hash" routes egress by k1 % reduce_n (the reference's partitioner);
    #: "range" routes by searchsorted over the bound splitters — partition
    #: order then IS key order (ops/partition.py). CLASS attribute (no
    #: annotation — deliberately not a dataclass field): the mode is the
    #: app's shape, not per-job state, so subclasses override it with a
    #: bare assignment (sort does).
    partition_mode = "hash"
    #: Non-zero → prepare_app enforces exactly this many input corpora
    #: (join: 2) at bind time, before any lease or chunk. Class
    #: attribute, like partition_mode.
    requires_corpora = 0

    def device_map(self, kv: KVBatch, doc_ids: jnp.ndarray) -> KVBatch:
        """On-device per-chunk transform; doc_ids is a traced int32 array,
        each record's doc id (a chunk may hold several documents)."""
        return kv

    @property
    def device_select_k(self) -> "int | None":
        """Non-None → the mesh driver may finish the job by fetching only
        the per-chip top-k candidates instead of the whole state
        (parallel/topk.py). Only sound for apps whose final answer is a
        global top-k over scalar values."""
        return None

    def host_mask(self, keys) -> "object | None":
        """Host-map-engine twin of a FILTERING device_map: given the
        window's unique keys (uint32 [n, 2]), return a bool[n] keep-mask,
        or None (default) for keep-everything. Applied by the host engines
        BEFORE host_values, whose inputs are already filtered."""
        return None

    def host_values(self, counts, doc_id: int):
        """Host-map-engine counterpart of device_map: values for one
        window's unique keys, given their occurrence counts (uint32[n]).
        Must agree with device_map ∘ combine_op — the two engines are
        interchangeable and tested equal (tests/test_driver.py). The
        default is only correct for sum apps (occurrence counts); any
        other combine_op must override rather than inherit a silently
        wrong value stream."""
        if self.combine_op != "sum":
            raise NotImplementedError(
                f"app {self.name!r} (combine_op={self.combine_op!r}) must "
                "override host_values to run under map_engine='host'"
            )
        return counts

    def route(self, word: "bytes | None", k1: int, reduce_n: int) -> int:
        """Output partition of one key. Hash mode ignores the word —
        k1 % reduce_n, the reference's partitioner (src/mr/worker.rs:
        111-115,129); range apps override via the bound splitters (word
        bytes required: hashes cannot order words). EVERY egress tier
        routes through this (or its vectorized twin below): the in-RAM
        finalize, the streaming spill merge-join, and the distributed map
        task's spill/dict-shard split."""
        return k1 % reduce_n

    def route_block(self, words, k1s, reduce_n: int):
        """Vectorized route for the streaming egress (driver
        _stream_finalize): partition ids for a block of (word, k1) pairs.
        Must agree with ``route`` element-wise — the two egress tiers'
        bit-identical-outputs contract depends on it."""
        import numpy as np

        return (np.asarray(k1s, dtype=np.int64) % reduce_n).tolist()

    def emit_lines(self, word: bytes, value: "FinalValue") -> list[bytes]:
        """Output lines for ONE final key — the egress emission seam.
        Default: one 'word value' line. Sort emits the word ``value``
        times (a global sort's output is the input multiset); join emits
        one line per cross-product pair and [] for one-sided keys. Both
        egress tiers (in-RAM and streaming) call exactly this (a distinct
        app's through emit_postings below), so an app
        that only customizes emission never loses the bounded-memory
        spill path the way a finalize override does."""
        return [self.format_line(word, value)]

    def emit_postings(self, words: list[bytes], docs: list[int],
                      starts: list[int], ends: list[int], routes: list[int],
                      parts: list[list[bytes]]) -> None:
        """Output lines for a BLOCK of a distinct app's terms — the block
        emission seam both egress tiers call. Term t is ``words[t]``, its
        ascending posting list is ``docs[starts[t]:ends[t]]``, and its
        lines go to ``parts[routes[t]]`` (unsorted), each from
        ``emit_lines``, so every app keeps its exact output."""
        emit = self.emit_lines
        for w, r, s, e in zip(words, routes, starts, ends):
            parts[r].extend(emit(w, docs[s:e]))

    def finalize(
        self, items: Iterable[tuple[bytes, "FinalValue", tuple[int, int]]], reduce_n: int
    ) -> dict[int, list[bytes]]:
        """items: (word, value, key_pair) for every distinct key, unordered.

        Returns partition → output lines (no trailing newline). Default:
        route via ``self.route`` (hash or range), emit via
        ``self.emit_lines``, sorted bytewise within each partition like
        the reference's sort-then-emit reduce (src/mr/worker.rs:162-184).
        """
        parts: dict[int, list[bytes]] = {r: [] for r in range(reduce_n)}
        for word, value, (k1, _k2) in items:
            parts[self.route(word, k1, reduce_n)].extend(
                self.emit_lines(word, value)
            )
        for lines in parts.values():
            lines.sort()
        return parts

    def finalize_partition(self, items: Iterable, partition: int) -> list[bytes]:
        """Egress for ONE reduce partition — the distributed (worker/) path,
        where each reduce task owns one partition class and emits its own
        mr-{r}.txt (reference src/mr/worker.rs:167). items as in finalize
        (already routed by the map tasks via ``route``). Apps needing
        global selection emit per-partition *candidates* here and finish
        the job in merge_lines (top_k does)."""
        lines: list[bytes] = []
        for w, v, _ in items:
            lines.extend(self.emit_lines(w, v))
        lines.sort()
        return lines

    def merge_lines(self, lines: Iterable[bytes]) -> list[bytes]:
        """Global merge of all partitions' lines — the reference's
        `cat mr-* | sort > final.txt` (src/run.sh:17-21), overridable for
        apps whose final answer is a global selection."""
        return sorted(lines)

    def format_line(self, word: bytes, value: "FinalValue") -> bytes:
        return b"%s %d" % (word, value)
