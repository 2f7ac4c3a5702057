"""Tokenize+hash kernel vs. pure-host oracle (collections.Counter style)."""

import collections
import re

import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_rust_tpu.core.hashing import hash_word, tokenize_host
from mapreduce_rust_tpu.ops.tokenize import tokenize_and_hash


def oracle_counts(text: bytes) -> dict[tuple[int, int], int]:
    counts: dict[tuple[int, int], int] = collections.defaultdict(int)
    for w in tokenize_host(text):
        counts[hash_word(w)] += 1
    return dict(counts)


def device_counts(text: bytes, pad_to: int | None = None) -> dict[tuple[int, int], int]:
    arr = np.frombuffer(text, dtype=np.uint8)
    if pad_to:
        arr = np.concatenate([arr, np.full(pad_to - len(arr), 0x20, np.uint8)])
    batch = tokenize_and_hash(jnp.asarray(arr))
    k1 = np.asarray(batch.k1)[np.asarray(batch.valid)]
    k2 = np.asarray(batch.k2)[np.asarray(batch.valid)]
    counts: dict[tuple[int, int], int] = collections.defaultdict(int)
    for a, b in zip(k1.tolist(), k2.tolist()):
        counts[(a, b)] += 1
    return dict(counts)


def test_host_tokenizer_matches_reference_regex_semantics():
    # Reference: strip [^\w\s] then split_whitespace (src/app/wc.rs:6-13).
    text = "Don't stop-me now! it's A_B  c3\n\ttabs"
    stripped = re.sub(r"[^\w\s]", "", text)
    expected = [w.encode() for w in stripped.split()]
    assert tokenize_host(text.encode()) == expected


@pytest.mark.parametrize(
    "text",
    [
        b"hello world hello",
        b"Don't stop-me now! don't",
        b"  leading and trailing  ",
        b"one",
        b"",
        b"!!! --- ...",  # only punctuation: no tokens
        b"a! b? a. b, a;",  # punctuation glued to words
        b"tab\tsep\nnewline\r\ncrlf",
        b"under_score 123 mix3d _lead trail_",
        "café naïve résumé café".encode("utf-8"),
    ],
)
def test_device_matches_oracle(text):
    assert device_counts(text, pad_to=max(64, len(text) + 8)) == oracle_counts(text)


def test_punctuation_joins_not_splits():
    # "don't" and "dont" must be the SAME token (wc.rs regex deletes the ').
    a = device_counts(b"don't", pad_to=16)
    b = device_counts(b"dont ", pad_to=16)
    assert a == b and len(a) == 1


def test_case_sensitive():
    counts = device_counts(b"Word word WORD Word", pad_to=32)
    assert sorted(counts.values()) == [1, 1, 2]


def test_large_random_text():
    rng = np.random.default_rng(0)
    vocab = [b"alpha", b"Beta", b"gamma_3", b"don't", b"x"]
    words = [vocab[i] for i in rng.integers(0, len(vocab), 5000)]
    text = b" ".join(words) + b"\n"
    n = 1 << 16
    assert len(text) < n
    assert device_counts(text, pad_to=n) == oracle_counts(text)


def test_unaligned_last_byte_not_boundary():
    # last_is_boundary=False: a token touching the chunk edge must NOT emit.
    arr = jnp.asarray(np.frombuffer(b"hello wor", np.uint8))
    batch = tokenize_and_hash(arr, last_is_boundary=False)
    k1 = np.asarray(batch.k1)[np.asarray(batch.valid)]
    assert len(k1) == 1  # only "hello"; "wor" is cut off


def test_pallas_scan_matches_associative_scan():
    """The fused Pallas kernel (interpret mode off-TPU) and the
    associative_scan must agree bit-for-bit — random bytes cover invalid
    UTF-8, punctuation runs, and whitespace-free blocks; the corpus slice
    covers real text."""
    import pathlib

    import jax.numpy as jnp

    from mapreduce_rust_tpu.core.hashing import byte_class_tables
    from mapreduce_rust_tpu.ops.tokenize import _tokenize
    from mapreduce_rust_tpu.ops.tokenize_pallas import BLOCK, hash_scan_pallas

    rng = np.random.default_rng(3)
    corpus = pathlib.Path("/root/reference/src/data/gut-2.txt")
    datasets = [rng.integers(0, 256, BLOCK, dtype=np.uint8)]
    if corpus.exists():
        raw = corpus.read_bytes()[:BLOCK]
        datasets.append(np.frombuffer(raw.ljust(BLOCK, b" "), dtype=np.uint8).copy())
    ws_tab, _wc = byte_class_tables()
    for data in datasets:
        h1, h2, cnt = hash_scan_pallas(jnp.asarray(data), interpret=True)
        kv, _ = _tokenize(jnp.asarray(data), last_is_boundary=True, with_len=False)
        is_ws = np.asarray(ws_tab)[data].astype(bool)
        next_ws = np.concatenate([is_ws[1:], [True]])
        valid = (~is_ws) & next_ws & (np.asarray(cnt) > 0)
        kv_valid = np.asarray(kv.valid)
        assert np.array_equal(valid, kv_valid)
        assert np.array_equal(np.asarray(h1)[valid], np.asarray(kv.k1)[kv_valid])
        assert np.array_equal(np.asarray(h2)[valid], np.asarray(kv.k2)[kv_valid])


def test_pallas_scan_cross_block_carry():
    """grid >= 2 with a token STRADDLING the 16 KB block boundary — the
    SMEM carry across grid steps is the kernel's riskiest part and a
    single-block test can never catch a carry bug."""
    import jax.numpy as jnp

    from mapreduce_rust_tpu.core.hashing import byte_class_tables, hash_word
    from mapreduce_rust_tpu.ops.tokenize_pallas import BLOCK, hash_scan_pallas

    n = 2 * BLOCK  # grid=2: interpret-mode compile time grows with grid
    data = np.full(n, ord(" "), dtype=np.uint8)
    # A 40-byte token centered on the block boundary, plus a filler.
    tok = b"straddler_token_across_the_block_edge_xy"
    start = BLOCK - 20
    data[start : start + len(tok)] = np.frombuffer(tok, np.uint8)
    spans = [(start, tok)]
    data[100:103] = np.frombuffer(b"abc", np.uint8)
    h1, h2, cnt = hash_scan_pallas(jnp.asarray(data), interpret=True)
    ws_tab, _ = byte_class_tables()
    is_ws = np.asarray(ws_tab)[data].astype(bool)
    next_ws = np.concatenate([is_ws[1:], [True]])
    valid = (~is_ws) & next_ws & (np.asarray(cnt) > 0)
    ends = np.nonzero(valid)[0]
    assert len(ends) == 2  # abc + the straddler
    got = {e: (int(np.asarray(h1)[e]), int(np.asarray(h2)[e])) for e in ends}
    assert got[102] == hash_word(b"abc")
    for start, t in spans:
        end = start + len(t) - 1
        assert got[end] == hash_word(t), "cross-block hash carry is broken"


def test_pallas_path_pads_ragged_chunk(monkeypatch):
    """A chunk that is not a whole number of BLOCKs takes the Pallas path
    too: space-padded to a BLOCK multiple, outputs sliced back. The kernel
    is stood in for by a per-byte host loop of the same scan (the kernel's
    own equality is the interpret-mode tests above), so this checks the
    padding and slicing alone."""
    import jax.numpy as jnp

    from mapreduce_rust_tpu.core.hashing import (
        H1_INIT, H1_MULT, H2_INIT, H2_MULT, byte_class_tables,
    )
    from mapreduce_rust_tpu.ops import tokenize_pallas
    from mapreduce_rust_tpu.ops.tokenize import _tokenize, tokenize_and_hash

    ws_tab, wc_tab = (np.asarray(t).astype(bool) for t in byte_class_tables())
    init1, init2, mult1, mult2 = map(int, (H1_INIT, H2_INIT, H1_MULT, H2_MULT))
    shapes = []

    def host_scan(chunk):
        shapes.append(chunk.shape[0])
        out = np.zeros((3, chunk.shape[0]), dtype=np.int64)
        h1, h2, cnt = init1, init2, 0
        for i, c in enumerate(np.asarray(chunk).tolist()):
            if ws_tab[c]:
                h1, h2, cnt = init1, init2, 0
            elif wc_tab[c]:
                h1 = (h1 * mult1 + c + 1) & 0xFFFFFFFF
                h2 = (h2 * mult2 + c + 1) & 0xFFFFFFFF
                cnt += 1
            out[:, i] = (h1, h2, cnt)
        return (jnp.asarray(out[0], jnp.uint32), jnp.asarray(out[1], jnp.uint32),
                jnp.asarray(out[2], jnp.int32))

    monkeypatch.setattr(tokenize_pallas, "hash_scan_pallas", host_scan)
    rng = np.random.default_rng(5)
    words = [b"w%d," % rng.integers(0, 500) for _ in range(1000)]
    text = b" ".join(words)[: tokenize_pallas.BLOCK - 777]
    data = jnp.asarray(np.frombuffer(text, dtype=np.uint8))
    padded, _ = _tokenize(data, False, with_len=False, use_pallas=True)
    scan = tokenize_and_hash(data, last_is_boundary=False)
    assert shapes == [tokenize_pallas.BLOCK]
    for a, b in zip(padded, scan):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_byte_classes_equal_the_host_tables():
    """The compare rules the device paths share (ops/tokenize.byte_classes)
    are the host's 256-entry tables, byte for byte."""
    import jax.numpy as jnp

    from mapreduce_rust_tpu.core.hashing import byte_class_tables
    from mapreduce_rust_tpu.ops.tokenize import byte_classes

    is_ws, is_wc = byte_classes(jnp.arange(256, dtype=jnp.int32))
    ws_tab, wc_tab = byte_class_tables()
    assert np.array_equal(np.asarray(is_ws), ws_tab.astype(bool))
    assert np.array_equal(np.asarray(is_wc), wc_tab.astype(bool))
