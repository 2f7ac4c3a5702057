"""Host-side ingest: files → fixed-shape, whitespace-aligned byte chunks.

Replaces the reference's ``read_file_to_mem_map`` (src/mr/worker.rs:65-77),
which slurps one whole input file into a single ``String`` — its de-facto
sequence-length ceiling. Here each file is normalized (core/normalize.py)
and streamed as fixed-size uint8 chunks. The contract every engine that
feeds device chunks relies on:

- every chunk is exactly ``chunk_bytes`` long (space-padded), so the device
  kernels compile once and are reused for the whole corpus;
- consecutive documents (doc_id = input file index) are packed back to
  back into one chunk, so documents smaller than a chunk do not each pay
  for a whole one. A document that does not end in whitespace is followed
  by one separator byte, so no token spans two documents. A document
  longer than the room left continues in the next chunk, or starts one;
- ``Chunk.doc_table`` says whose bytes are where: the chunk's first doc
  id, then the byte offset where each of its documents starts (the first
  at 0), padded with ``NO_DOC``. A token's doc id is the first doc id plus
  the number of starts at or before the token, minus one
  (ops/groupby.position_doc_ids). The table holds ``DOC_SLOTS`` starts; a
  chunk closes when it is full, or when the next doc id is not the next
  file (a chunk's documents are consecutive files);
- chunks are cut at whitespace boundaries, so no token ever straddles a
  chunk edge and per-chunk counts sum exactly to whole-corpus counts
  (the reference gets the same guarantee trivially: one file = one task,
  src/mr/worker.rs:67). The one exception — a single token longer than
  ``chunk_bytes`` — is force-split and *counted* in ``Chunk.forced_cut``,
  like every other lossy path in this codebase (merge/bucket overflow);
- normalization and chunking run over a bounded sliding window, so peak
  host memory is O(window), not O(file).

The pure-device alternative for sharded byte streams (cut anywhere, fix up
boundary tokens with a ppermute halo) is parallel/halo.py.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Iterable, Iterator, Sequence

import numpy as np

from mapreduce_rust_tpu.core.hashing import WHITESPACE_BYTES
from mapreduce_rust_tpu.core.normalize import normalize_unicode

_ASCII_WS = frozenset(WHITESPACE_BYTES)

#: Document starts one chunk's table holds. The device map pays
#: O(chunk bytes x DOC_SLOTS) compares to stamp doc ids, and documents smaller
#: than chunk_bytes / DOC_SLOTS leave part of a chunk empty.
DOC_SLOTS = 64
#: Unused table slots: past every byte of any chunk.
NO_DOC = np.iinfo(np.int32).max


def doc_table(first_doc: int, starts: Sequence[int] = (0,)) -> np.ndarray:
    """int32[1 + DOC_SLOTS]: ``first_doc``, then the documents' start
    offsets, padded with NO_DOC. ``doc_table(d)`` is one whole document."""
    if not 0 < len(starts) <= DOC_SLOTS:
        raise ValueError(f"{len(starts)} document starts; a chunk holds 1..{DOC_SLOTS}")
    table = np.full(1 + DOC_SLOTS, NO_DOC, dtype=np.int32)
    table[0] = first_doc
    table[1 : 1 + len(starts)] = starts
    return table


@dataclasses.dataclass(frozen=True)
class Chunk:
    """One device-ready chunk: uint8[chunk_bytes], space padded."""

    seq: int  # chunk index within its stream
    data: np.ndarray  # uint8[chunk_bytes]
    nbytes: int  # real payload length before padding (separators included)
    doc_table: np.ndarray  # int32[1 + DOC_SLOTS], see doc_table()
    forced_cut: bool = False  # True: chunk END was cut mid-token (token > chunk_bytes)
    corpus: int = 0  # which named corpus this chunk's documents belong to
    # (multi-corpus input API): index into Config.corpora(). iter_chunks
    # never packs across a corpus bound, so one tag holds for the chunk.

    @property
    def first_doc(self) -> int:
        """Doc id (input file index, GLOBAL across corpora) of byte 0."""
        return int(self.doc_table[0])

    @property
    def n_docs(self) -> int:
        return int(np.count_nonzero(self.doc_table[1:] != NO_DOC))


def _ws_cut(data: bytes, start: int, end: int) -> tuple[int, bool]:
    """Largest cut <= end with data[cut-1] whitespace; (end, True) if none."""
    cut = end
    while cut > start and data[cut - 1] not in _ASCII_WS:
        cut -= 1
    if cut == start:
        return end, True
    return cut, False


def utf8_safe_cut(data: bytes, cut: int) -> int:
    """Largest cut' <= cut that does not split a UTF-8 sequence: back off
    past trailing continuation bytes and their lead byte (a complete
    trailing sequence also moves whole past the cut). Shared force-cut
    policy of every byte-stream splitter (chunk_stream, the host engine's
    window iterator) so the engines can never diverge on it."""
    while cut > 1 and (data[cut - 1] & 0xC0) == 0x80:
        cut -= 1
    if cut > 1 and data[cut - 1] >= 0xC0:
        cut -= 1
    return cut


def _normalized_windows(f, normalize: bool, window: int) -> Iterator[tuple[bytes, bool]]:
    """(normalized bytes, at_eof) one ~window read at a time, each read cut
    at ASCII whitespace — safe before normalization because
    normalize_unicode never alters ASCII bytes, so an ASCII-whitespace cut
    is a token boundary in both the raw and normalized streams. The raw
    tail past the cut carries into the next read."""
    raw_carry = b""
    while True:
        piece = f.read(window)
        at_eof = not piece
        buf = raw_carry + piece
        raw_carry = b""
        if not at_eof and buf:
            cut, forced_window = _ws_cut(buf, 0, len(buf))
            if forced_window:
                # No whitespace in the whole window: cut anyway, but at a
                # UTF-8 sequence boundary so per-window normalization
                # matches whole-file normalization byte for byte.
                cut = utf8_safe_cut(buf, cut)
            raw_carry = buf[cut:]
            buf = buf[:cut]
        yield (normalize_unicode(buf) if normalize else buf), at_eof
        if at_eof:
            return


class _Packer:
    """Lays documents back to back into chunk_bytes buffers. Cuts are
    greedy: after the last whitespace byte that fits, so the trailing
    partial token moves whole into the next chunk. A cut inside a token is
    forced only when the chunk holds nothing else (a token longer than
    chunk_bytes): the fragments count as separate words, and the chunk's
    forced_cut says so."""

    def __init__(self, chunk_bytes: int) -> None:
        self.chunk_bytes = chunk_bytes
        self.seq = 0
        self._fresh()

    def _fresh(self) -> None:
        self.buf = np.full(self.chunk_bytes, 0x20, dtype=np.uint8)
        self.used = 0
        self.first_doc = 0
        self.starts: list[int] = []
        self.forced = False

    def close(self) -> "Chunk | None":
        """The open chunk, or None if it holds no bytes (then it holds no
        token either, so dropping it loses nothing); a fresh one opens."""
        chunk = None
        if self.used:
            chunk = Chunk(seq=self.seq, data=self.buf, nbytes=self.used,
                          doc_table=doc_table(self.first_doc, self.starts),
                          forced_cut=self.forced)
            self.seq += 1
        self._fresh()
        return chunk

    def _is_open(self, doc: int) -> bool:
        return bool(self.starts) and self.first_doc + len(self.starts) - 1 == doc

    def _can_open(self, doc: int) -> bool:
        return not self.starts or (
            len(self.starts) < DOC_SLOTS and doc == self.first_doc + len(self.starts))

    def _sep(self) -> int:
        return int(self.used > 0 and int(self.buf[self.used - 1]) not in _ASCII_WS)

    def _room(self, doc: int) -> int:
        if self._is_open(doc):
            return self.chunk_bytes - self.used
        if not self._can_open(doc):
            return 0
        return max(self.chunk_bytes - self.used - self._sep(), 0)

    def open_doc(self, doc: int) -> Iterator[Chunk]:
        """Give ``doc`` its table slot here, closing the chunk first if it
        cannot take one. A document opens where its first byte lands, or
        at its end if it has none: ids stay consecutive either way."""
        if self._is_open(doc):
            return
        if not self._can_open(doc):
            chunk = self.close()
            if chunk is not None:
                yield chunk
        self.used += self._sep()
        if not self.starts:
            self.first_doc = doc
        self.starts.append(self.used)

    def place(self, doc: int, data: bytes, final: bool) -> Iterator[Chunk]:
        """Lay ``data`` (the document's next bytes) into chunks; returns how
        many bytes were laid. Unless ``final``, the bytes that fit without a
        cut wait for more: their cut is not known yet."""
        off, n = 0, len(data)
        while off < n:
            room = self._room(doc)
            if n - off <= room:
                if not final:
                    break
                yield from self.open_doc(doc)
                self._put(data[off:])
                return n
            cut, forced = _ws_cut(data, off, off + room)
            if forced and (self.used or self.starts):
                # No whitespace within the room left: the document goes on
                # (or starts) in the next chunk.
                chunk = self.close()
                if chunk is not None:
                    yield chunk
                continue
            yield from self.open_doc(doc)
            self._put(data[off:cut])
            self.forced = forced
            off = cut
            yield self.close()
        return off

    def _put(self, data: bytes) -> None:
        self.buf[self.used : self.used + len(data)] = np.frombuffer(data, dtype=np.uint8)
        self.used += len(data)


def pack_documents(
    docs: Iterable[tuple[int, object]],
    chunk_bytes: int,
    normalize: bool = True,
    window_bytes: int | None = None,
) -> Iterator[Chunk]:
    """Chunks of ``docs``, (doc_id, binary file object) pairs in doc order,
    each document normalized and read one window at a time, so emitted
    chunks are identical to whole-file processing while peak host memory
    is O(window) — never O(file), contrast src/mr/worker.rs:73-76. Each
    file is read to its end before the next pair is drawn.

    normalize=False skips unicode normalization (raw byte passthrough for
    ASCII-only or pre-normalized input).
    """
    window = window_bytes or max(chunk_bytes * 8, 1 << 24)
    packer = _Packer(chunk_bytes)
    for doc, f in docs:
        pending = b""  # normalized bytes whose chunk cut isn't final yet
        for piece, at_eof in _normalized_windows(f, normalize, window):
            data = pending + piece
            laid = yield from packer.place(doc, data, at_eof)
            pending = data[laid:]
        yield from packer.open_doc(doc)  # an empty document keeps its id
    chunk = packer.close()
    if chunk is not None:
        yield chunk


def chunk_stream(
    f,
    doc_id: int,
    chunk_bytes: int,
    normalize: bool = True,
    window_bytes: int | None = None,
) -> Iterator[Chunk]:
    """Chunks of one document read from a binary file object."""
    yield from pack_documents([(doc_id, f)], chunk_bytes, normalize, window_bytes)


def chunk_document(
    raw: bytes,
    doc_id: int,
    chunk_bytes: int,
    normalize: bool = True,
    window_bytes: int | None = None,
) -> Iterator[Chunk]:
    """chunk_stream over an in-memory document."""
    import io

    yield from chunk_stream(io.BytesIO(raw), doc_id, chunk_bytes, normalize, window_bytes)


def open_documents(paths: Sequence[str | os.PathLike],
                   doc_ids: Sequence[int] | None = None,
                   on_open=None) -> Iterator[tuple[int, object]]:
    """(doc_id, open file) per path for pack_documents; doc_id = position
    in ``paths`` unless ``doc_ids`` says otherwise. ``on_open(path)`` runs
    as each file opens."""
    for i, path in enumerate(paths):
        if on_open is not None:
            on_open(path)
        with open(path, "rb") as f:
            yield (doc_ids[i] if doc_ids is not None else i), f


def iter_chunks(
    paths: Sequence[str | os.PathLike], chunk_bytes: int,
    corpus_bounds: Sequence[int] = (),
) -> Iterator[Chunk]:
    """Stream all input files as packed chunks, doc_id = position in
    ``paths``. With ``corpus_bounds`` (resolve_corpora), each corpus packs
    on its own and its chunks are tagged with its id.
    """
    edges = [0, *corpus_bounds, len(paths)]
    for corpus, (lo, hi) in enumerate(zip(edges, edges[1:])):
        docs = open_documents(paths[lo:hi], doc_ids=range(lo, hi))
        for c in pack_documents(docs, chunk_bytes):
            yield dataclasses.replace(c, corpus=corpus) if corpus else c


def list_inputs(input_dir: str, pattern: str = "*.txt") -> list[str]:
    """Sorted input file list — the doc_id ordering contract."""
    import glob

    return sorted(glob.glob(os.path.join(input_dir, pattern)))


def parse_input_spec(values: Sequence[str]):
    """The CLI's ``--input`` forms → (input_dir, input_dirs):

    - ``--input DIR`` — classic single corpus: (DIR, None). ONE value is
      always this form, '=' in the path included;
    - ``--input a=DIR b=DIR`` — N (>= 2) named corpora, canonically
      sorted by name (the submission-digest and join-side ordering
      contract: ``a=X b=Y`` and ``b=Y a=X`` are the SAME job):
      (first dir, sorted ((name, dir), ...)).

    Mixing the two forms (or repeating a name) is a usage error.
    """
    vals = list(values)
    if len(vals) == 1:
        # ONE value is always the classic directory form — even when the
        # path contains '=' (a legal dir name like data/run=5). A single
        # NAMED corpus would be pointless anyway: names only distinguish
        # sides once there are two.
        return vals[0], None
    pairs = []
    for v in vals:
        name, sep, d = v.partition("=")
        if not sep or not name or not d:
            raise ValueError(
                f"multi-corpus --input wants name=DIR entries, got {v!r} "
                "(single-corpus form takes exactly one bare DIR)"
            )
        pairs.append((name, d))
    names = [n for n, _ in pairs]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate corpus names in --input: {names}")
    pairs.sort()
    return pairs[0][1], tuple(pairs)


def resolve_corpora(cfg) -> tuple[list[str], tuple, tuple]:
    """Flatten the job's corpora (Config.corpora()) into the doc_id
    space: (inputs, corpus_bounds, names). ``inputs`` concatenates each
    corpus's sorted listing in corpus order; ``corpus_bounds`` holds the
    cumulative doc counts of corpora[:-1] — the boundaries
    splitter.prepare_app binds onto multi-corpus apps. Single-corpus
    configs come back with bounds == () so every classic caller keeps
    flat-list semantics."""
    corpora = cfg.corpora()
    inputs: list[str] = []
    bounds: list[int] = []
    for name, d in corpora:
        inputs.extend(list_inputs(d, cfg.input_pattern))
        bounds.append(len(inputs))
    names = tuple(n for n, _ in corpora)
    if len(corpora) == 1:
        return inputs, (), names
    return inputs, tuple(bounds[:-1]), names
