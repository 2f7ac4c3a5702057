"""Documents packed into chunks: the packer's layout, and every engine's
output against a plain reference over seeded corpora of many documents.

The reference is the job's meaning written out by hand: a document is one
input file, its terms are its whitespace-separated words (the corpora use
lowercase ASCII words, which the tokenizer keeps whole), and the inverted
index maps each term to the set of file indices it occurs in."""

import collections
import io
import pathlib
import random

import numpy as np
import pytest

from mapreduce_rust_tpu.apps import get_app
from mapreduce_rust_tpu.config import Config
from mapreduce_rust_tpu.runtime.chunker import (
    DOC_SLOTS,
    NO_DOC,
    doc_table,
    pack_documents,
)
from mapreduce_rust_tpu.runtime.driver import run_job

CHUNK = 512


def reference_lines(docs: list[bytes], app: str) -> list[bytes]:
    """The expected output lines, from plain dicts and sets."""
    if app == "inverted_index":
        postings: dict = {}
        for i, doc in enumerate(docs):
            for w in doc.split():
                postings.setdefault(w, set()).add(i)
        lines = [w + b" " + b",".join(b"%d" % d for d in sorted(ds))
                 for w, ds in postings.items()]
    else:
        counts = collections.Counter(w for doc in docs for w in doc.split())
        lines = [b"%s %d" % (w, c) for w, c in counts.items()]
    return sorted(lines)


def _words(rng: random.Random, vocab: list[bytes], nbytes: int) -> bytes:
    """Words of the vocabulary, space separated, exactly nbytes long and
    ending in a space."""
    out = b""
    while len(out) < nbytes:
        w = rng.choice(vocab)
        room = nbytes - len(out) - 1
        if room < 1:
            break
        out += (w if len(w) <= room else w[:room]) + b" "
    return out.ljust(nbytes)


def seeded_corpus(seed: int) -> list[bytes]:
    """Many small documents, empty ones, one of exactly a chunk, one longer
    than a chunk, one without trailing whitespace, and a run of tiny ones
    longer than a chunk's doc table, in a seeded order."""
    rng = random.Random(seed)
    vocab = [bytes(rng.choices(b"abcdefghijklmnopqrstuvwxyz", k=rng.randint(1, 9)))
             for _ in range(60)]
    docs = [_words(rng, vocab, rng.randint(4, 120)) for _ in range(30)]
    docs += [b"", b""]
    docs.append(_words(rng, vocab, CHUNK))
    docs.append(_words(rng, vocab, 3 * CHUNK + 77))
    docs.append(_words(rng, vocab, 50).rstrip() + b"\n" + rng.choice(vocab))
    rng.shuffle(docs)
    tiny = [rng.choice(vocab)[:2] + b" " for _ in range(DOC_SLOTS + 20)]
    at = rng.randint(0, len(docs))
    return docs[:at] + tiny + docs[at:]


def _write(tmp_path: pathlib.Path, docs: list[bytes]) -> list[str]:
    paths = []
    for i, d in enumerate(docs):
        p = tmp_path / f"doc-{i:04d}.txt"
        p.write_bytes(d)
        paths.append(str(p))
    return paths


ENGINES = {
    "device": dict(),
    "host": dict(map_engine="host"),
    "mesh4": dict(mesh_shape=4),
}


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("app", ["inverted_index", "word_count"])
def test_engines_match_reference(tmp_path, app, engine, seed):
    docs = seeded_corpus(seed)
    cfg = Config(chunk_bytes=CHUNK, merge_capacity=128, reduce_n=3,
                 device="cpu", output_dir=str(tmp_path / "out"),
                 work_dir=str(tmp_path / "work"), **ENGINES[engine])
    res = run_job(cfg, _write(tmp_path, docs), app=get_app(app))
    got = sorted(line for p in res.output_files
                 for line in pathlib.Path(p).read_bytes().splitlines())
    assert got == reference_lines(docs, app)
    if engine != "host":
        assert res.stats.chunks_dispatched < len(docs)  # documents shared chunks
        # (term, doc) pairs outnumber the state: the distinct fold spills.
        assert res.stats.spill_events > 0 or app == "word_count"


def _pack(docs: list[bytes], chunk_bytes: int = CHUNK, first: int = 0):
    return list(pack_documents(
        ((first + i, io.BytesIO(d)) for i, d in enumerate(docs)),
        chunk_bytes, normalize=False))


def _doc_of_bytes(chunk) -> np.ndarray:
    """Doc id of every payload byte, by the table's rule."""
    pos = np.arange(chunk.nbytes)
    starts = chunk.doc_table[1:]
    return chunk.first_doc + (pos[:, None] >= starts[None, :]).sum(axis=1) - 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_packer_layout(seed):
    docs = seeded_corpus(seed)
    chunks = _pack(docs)
    assert [c.seq for c in chunks] == list(range(len(chunks)))
    placed = collections.defaultdict(bytes)
    for c in chunks:
        assert c.data.shape == (CHUNK,) and 0 < c.nbytes <= CHUNK
        assert bytes(c.data[c.nbytes:]) == b" " * (CHUNK - c.nbytes)
        starts = c.doc_table[1:]
        n = c.n_docs
        assert 1 <= n <= DOC_SLOTS and starts[0] == 0
        assert (starts[n:] == NO_DOC).all()
        assert (np.diff(starts[:n]) >= 0).all() and starts[n - 1] <= c.nbytes
        owner = _doc_of_bytes(c)
        payload = bytes(c.data[:c.nbytes])
        for d in range(c.first_doc, c.first_doc + n):
            placed[d] += bytes(b for b, o in zip(payload, owner) if o == d)
    # Each document's bytes, in order, with at most a separator added.
    for i, d in enumerate(docs):
        assert placed[i].rstrip() == d.rstrip(), i
    # The chunks' tokens are the documents' tokens: none glued across a
    # document boundary or a chunk edge.
    got = collections.Counter()
    for c in chunks:
        owner = _doc_of_bytes(c)
        payload = bytes(c.data[:c.nbytes])
        pos = 0
        for tok in payload.split():
            pos = payload.index(tok, pos)
            ends = owner[pos:pos + len(tok)]
            assert (ends == ends[0]).all()
            got[(int(ends[0]), tok)] += 1
            pos += len(tok)
    want = collections.Counter((i, t) for i, d in enumerate(docs) for t in d.split())
    assert got == want


def test_packer_separator_and_first_doc():
    chunks = _pack([b"alpha", b"beta ", b"", b"gamma"], first=5)
    assert len(chunks) == 1
    c = chunks[0]
    assert bytes(c.data[:c.nbytes]) == b"alpha beta gamma"
    assert c.first_doc == 5
    assert c.doc_table[1:5].tolist() == [0, 6, 11, 11]


def test_packer_table_full_and_doc_longer_than_room():
    docs = [b"a "] * (DOC_SLOTS + 1)
    first, second = _pack(docs)
    assert first.n_docs == DOC_SLOTS and second.first_doc == DOC_SLOTS
    # A document that does not fit the room left continues in the next
    # chunk, cut after its last whitespace that fits.
    a, b = _pack([b"x" * 500 + b" ", b"yy zz ww"], chunk_bytes=505)
    assert bytes(a.data[:a.nbytes]) == b"x" * 500 + b" yy "
    assert a.doc_table[1:3].tolist() == [0, 501]
    assert b.first_doc == 1 and bytes(b.data[:b.nbytes]) == b"zz ww"
    # ... or starts the next chunk when no whitespace fits.
    a, b = _pack([b"x" * 500 + b" ", b"yyyyyyy zz"], chunk_bytes=505)
    assert a.n_docs == 1 and b.first_doc == 1 and b.doc_table[1] == 0


def test_packer_non_consecutive_ids_close_the_chunk():
    chunks = list(pack_documents(
        [(0, io.BytesIO(b"a ")), (1, io.BytesIO(b"b ")), (5, io.BytesIO(b"c "))],
        CHUNK, normalize=False))
    assert [(c.first_doc, c.n_docs) for c in chunks] == [(0, 2), (5, 1)]


def test_doc_table_pads_past_every_chunk():
    t = doc_table(3, [0, 10])
    assert t.dtype == np.int32 and t.shape == (1 + DOC_SLOTS,)
    assert t[:3].tolist() == [3, 0, 10] and (t[3:] == NO_DOC).all()
    with pytest.raises(ValueError):
        doc_table(0, list(range(DOC_SLOTS + 1)))


@pytest.mark.parametrize("app,params", [("word_count", ["chunk"]),
                                        ("inverted_index", ["chunk", "doc_table"])])
def test_doc_table_reaches_only_apps_that_read_doc_ids(app, params):
    """Word count's map_combine does no new work: the doc table is an
    unused parameter, which the compiled program drops."""
    import jax
    import jax.numpy as jnp

    from mapreduce_rust_tpu.runtime.driver import make_step_fns

    map_combine, _ = make_step_fns(get_app(app), 128)
    hlo = map_combine.lower(
        jax.ShapeDtypeStruct((CHUNK,), jnp.uint8),
        jax.ShapeDtypeStruct((1 + DOC_SLOTS,), jnp.int32),
    ).compile().as_text()
    entry = next(line for line in hlo.splitlines() if line.startswith("ENTRY"))
    assert [p for p in ("chunk", "doc_table") if f"{p}." in entry] == params
