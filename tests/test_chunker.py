"""Chunker invariants: counts are independent of chunk size and window size,
and windowed streaming equals whole-file processing."""

import collections
import pathlib

import numpy as np
import pytest

from mapreduce_rust_tpu.core.normalize import reference_word_counts
from mapreduce_rust_tpu.runtime.chunker import NO_DOC, Chunk, chunk_document

CORPUS = pathlib.Path("/root/reference/src/data")


def chunk_word_counts(raw: bytes, chunk_bytes: int, **kw) -> collections.Counter:
    """Host oracle applied per chunk — exercises only the chunking logic."""
    total: collections.Counter = collections.Counter()
    for chunk in chunk_document(raw, 0, chunk_bytes, **kw):
        payload = bytes(chunk.data[: chunk.nbytes])
        total.update(reference_word_counts(payload))
    return total


def test_spans_cover_and_align():
    data = b"the quick brown fox jumps over the lazy dog " * 50
    chunks = list(chunk_document(data, 0, 64, normalize=False))
    payloads = [bytes(c.data[: c.nbytes]) for c in chunks]
    assert b"".join(payloads) == data  # the chunks cover the document
    off = 0
    for p in payloads[:-1]:
        assert p[-1:] in (b" ", b"\n")  # whitespace-aligned cut
        off += len(p)
        # greedy: the next word and its space would not have fit
        assert len(p) + len(data[off:].split(b" ", 1)[0]) + 1 > 64
    assert not any(c.forced_cut for c in chunks)


def test_forced_cut_flagged():
    data = b"x" * 200  # one giant token
    chunks = list(chunk_document(data, 0, 64, normalize=False))
    assert [c.nbytes for c in chunks] == [64, 64, 64, 8]
    assert [c.forced_cut for c in chunks] == [True, True, True, False]
    chunks = list(chunk_document(data, 0, 64, normalize=False, window_bytes=64))
    assert any(c.forced_cut for c in chunks[:-1])


@pytest.mark.parametrize("chunk_bytes", [37, 64, 256, 4096])
def test_counts_invariant_to_chunk_size(chunk_bytes):
    raw = ("the cat — sat don’t “stop” now " * 200).encode("utf-8")
    oracle = reference_word_counts(raw)
    assert chunk_word_counts(raw, chunk_bytes) == oracle


@pytest.mark.parametrize("window_bytes", [None, 128, 1024, 5000])
def test_windowed_equals_whole_file(window_bytes):
    raw = ("don’t stop — believing “hold” on to that feeling\n" * 300).encode("utf-8")
    whole = list(chunk_document(raw, 0, 256, window_bytes=None))
    windowed = list(chunk_document(raw, 0, 256, window_bytes=window_bytes))
    assert len(whole) == len(windowed)
    for a, b in zip(whole, windowed):
        assert a.nbytes == b.nbytes and np.array_equal(a.data, b.data)


def test_chunks_are_fixed_shape_and_space_padded():
    raw = b"alpha beta gamma"
    chunks = list(chunk_document(raw, 3, 64))
    assert len(chunks) == 1
    c = chunks[0]
    assert isinstance(c, Chunk) and c.first_doc == 3 and c.seq == 0
    assert c.doc_table[1] == 0 and (c.doc_table[2:] == NO_DOC).all()
    assert c.data.shape == (64,) and c.data.dtype == np.uint8
    assert bytes(c.data[c.nbytes :]) == b" " * (64 - c.nbytes)


def test_empty_document_yields_nothing():
    assert list(chunk_document(b"", 0, 64)) == []


def test_normalize_false_is_raw_passthrough():
    raw = "a — b".encode("utf-8")  # em dash must survive when normalize=False
    chunks = list(chunk_document(raw, 0, 64, normalize=False))
    assert bytes(chunks[0].data[: chunks[0].nbytes]) == raw
    normalized = list(chunk_document(raw, 0, 64, normalize=True))
    assert bytes(normalized[0].data[: normalized[0].nbytes]) == b"a  b"


@pytest.mark.skipif(not CORPUS.exists(), reason="reference corpus not mounted")
def test_real_corpus_chunking_invariant():
    raw = (CORPUS / "gut-2.txt").read_bytes()
    oracle = reference_word_counts(raw)
    assert chunk_word_counts(raw, 8192) == oracle
    # small window forces many normalize/carry iterations
    assert chunk_word_counts(raw, 8192, window_bytes=30000) == oracle
