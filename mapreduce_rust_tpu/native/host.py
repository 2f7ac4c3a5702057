"""ctypes bridge to the native host scanner (loader.cpp).

Builds the shared object on first use with g++ (no pybind11 in this image;
the C ABI + ctypes keeps the binding dependency-free) and caches it next to
the source under a name keyed by the source's digest and the host ISA
(``-march=native`` code built on one CPU may SIGILL on another, and a copied
tree keeps whatever was built before). If the toolchain or compile is
unavailable, callers fall back to the pure-Python path (runtime/dictionary.py
works either way — tests cover both); ``get_lib() is None`` says which.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pathlib
import platform
import subprocess
import threading  # noqa: F401 — thread-local scratch + build lock

import numpy as np

log = logging.getLogger("mapreduce_rust_tpu.native")

_SRC = pathlib.Path(__file__).with_name("loader.cpp")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def host_isa() -> str:
    """"<machine>:<cpu feature flags>" — what decides whether code compiled
    for this host (``-march=native`` here, XLA's CPU AOT results in the
    driver's compile cache) runs on another."""
    try:
        with open("/proc/cpuinfo") as f:
            # x86 spells it "flags", aarch64 "Features" — either carries the
            # ISA extensions whose mismatch makes a foreign binary crash.
            flags = next(
                (l for l in f if l.startswith(("flags", "Features"))), ""
            )
    except OSError:
        flags = ""
    return f"{platform.machine()}:{flags}"


def _so_path() -> pathlib.Path:
    h = hashlib.sha256(_SRC.read_bytes() + host_isa().encode()).hexdigest()[:16]
    return _SRC.with_name(f"_mrnative-{h}.so")


def _build() -> pathlib.Path | None:
    try:
        so = _so_path()
        if so.exists():
            return so
        # Compile to a per-process temp then atomically rename: concurrent
        # workers (README quickstart spawns several) must never observe a
        # half-written .so.
        tmp = so.with_name(f".{so.name}.{os.getpid()}.tmp")
        cmd = ["g++", "-O3", "-march=native", "-shared", "-fPIC",
               "-o", str(tmp), str(_SRC)]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native build unavailable (%s) — using Python fallback", e)
        return None


def get_lib() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            so = _build()
            if so is None:
                return None
            lib = ctypes.CDLL(str(so))
            lib.mr_scan_unique.restype = ctypes.c_int64
            lib.mr_scan_unique.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_int64,
            ]
            lib.mr_normalize.restype = ctypes.c_int64
            lib.mr_normalize.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.mr_scan_count.restype = ctypes.c_int64
            lib.mr_scan_count.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_int64,
            ]
            lib.mr_scan_count_sharded.restype = ctypes.c_int64
            lib.mr_scan_count_sharded.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
            ]
            lib.mr_coalesce_updates.restype = ctypes.c_int64
            lib.mr_coalesce_updates.argtypes = [
                ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
            ]
            lib.mr_merge_runs.restype = ctypes.c_int64
            lib.mr_merge_runs.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
            ]
        except (OSError, AttributeError) as e:
            # AttributeError: a library missing a symbol this binding
            # expects must engage the Python fallback, not crash.
            log.warning("native load failed (%s) — using Python fallback", e)
            return None
        _lib = lib
        return _lib


_CPCLASS_CACHE = pathlib.Path(__file__).with_name("_cpclass.npz")
_cpclass_arr: np.ndarray | None = None


def _cpclass() -> np.ndarray:
    """uint8[0x110000] codepoint classes (0 delete / 1 word / 2 space),
    built ONCE from the exact rules core/normalize.py uses (re \\w + str
    .isspace) and cached on disk — the C normalizer is table-driven so its
    semantics are definitionally identical to the Python path."""
    global _cpclass_arr
    if _cpclass_arr is not None:
        return _cpclass_arr
    import unicodedata

    fingerprint = unicodedata.unidata_version  # rebuild on Unicode-table change
    if _CPCLASS_CACHE.exists():
        try:
            with np.load(_CPCLASS_CACHE) as z:
                if str(z["unidata"]) == fingerprint:
                    _cpclass_arr = np.ascontiguousarray(z["cls"], dtype=np.uint8)
                    return _cpclass_arr
        except (OSError, KeyError, ValueError):
            pass  # corrupt/old cache — rebuild below
    import re

    cls = np.zeros(0x110000, dtype=np.uint8)
    everything = "".join(map(chr, range(0x80, 0x110000)))
    for ch in re.findall(r"\w", everything, re.UNICODE):
        cls[ord(ch)] = 1
    for i, ch in enumerate(everything):
        if cls[i + 0x80] == 0 and ch.isspace():
            cls[i + 0x80] = 2
    _cpclass_arr = cls
    try:
        # np.savez appends '.npz' unless the name already ends with it.
        tmp = _CPCLASS_CACHE.with_name(f".cpclass.{os.getpid()}.tmp.npz")
        np.savez_compressed(tmp, cls=cls, unidata=fingerprint)
        os.replace(tmp, _CPCLASS_CACHE)
    except OSError:
        pass
    return _cpclass_arr


def normalize_native(data: bytes) -> bytes | None:
    """One-pass C normalization of raw UTF-8 (byte-exact vs the Python
    path; tests/test_native.py), or None when the native lib is absent."""
    lib = get_lib()
    if lib is None:
        return None
    out = np.empty(max(len(data), 1), dtype=np.uint8)
    n = lib.mr_normalize(
        data, len(data),
        _cpclass().ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out[: int(n)].tobytes()


_scratch = threading.local()

# Arena accounting: one scratch arena per scan thread means the host-map
# engine's memory cost scales with host_map_workers, not with the corpus —
# the registry makes that price observable (stats.host_arena_bytes / the
# run manifest) instead of folklore. Entries are keyed by the words buffer
# and removed by a weakref finalizer when the arena is collected (thread
# death frees its thread-locals), so the gauge tracks LIVE arenas only.
_arena_lock = threading.Lock()
_arena_sizes: dict[int, int] = {}


def _arena_release(key: int) -> None:
    with _arena_lock:
        _arena_sizes.pop(key, None)


def arena_bytes() -> int:
    """Total bytes of live per-thread scan scratch arenas in this process."""
    with _arena_lock:
        return sum(_arena_sizes.values())


def arena_count() -> int:
    """How many threads currently hold a scan scratch arena."""
    with _arena_lock:
        return len(_arena_sizes)


_sanitize_cached: "bool | None" = None


def _sanitizing() -> bool:
    """MR_SANITIZE resolved once per process — _buffers is per-scan hot."""
    global _sanitize_cached
    if _sanitize_cached is None:
        from mapreduce_rust_tpu.analysis.sanitize import sanitize_enabled

        _sanitize_cached = sanitize_enabled()
    return _sanitize_cached


def _buffers(n: int, max_words: int):
    """Per-thread reusable scratch (allocating ~10 MB of numpy buffers per
    call costs ~40% of the scan; scan results are copied out before the
    next call on the same thread can overwrite them)."""
    import weakref

    bufs = getattr(_scratch, "bufs", None)
    if bufs is not None and _sanitizing():
        # Thread-locals survive os.fork(): a child reusing the parent's
        # arena would scribble over (and read) another process's scan
        # state. The sanitizer turns that silent aliasing into a raise.
        from mapreduce_rust_tpu.analysis.sanitize import check_arena_owner

        check_arena_owner(*_scratch.owner)
    if bufs is None or bufs[0].size < n + 1 or bufs[1].size < max_words:
        bufs = (
            np.empty(max(n + 1, 1 << 20), dtype=np.uint8),
            np.empty(max(max_words, 1 << 18), dtype=np.int64),
            np.empty(max(max_words, 1 << 18), dtype=np.uint32),
            np.empty(max(max_words, 1 << 18), dtype=np.uint32),
            np.empty(max(max_words, 1 << 18), dtype=np.uint32),
            # grouped->scan position map of the sharded scan (ISSUE 9);
            # rides in the arena so the gauge prices the sharded engine too
            np.empty(max(max_words, 1 << 18), dtype=np.int64),
        )
        key = id(bufs[0])
        with _arena_lock:
            _arena_sizes[key] = sum(int(b.nbytes) for b in bufs)
        weakref.finalize(bufs[0], _arena_release, key)
        _scratch.bufs = bufs
        _scratch.owner = (os.getpid(), threading.get_ident())
    return bufs


def scan_count_raw(
    data: "bytes | np.ndarray",
) -> tuple[bytes, np.ndarray, np.ndarray, np.ndarray] | None:
    """(concatenated unique words, int64[n] end offsets, uint32[n,2] hash
    pairs, uint32[n] occurrence counts) over RAW un-normalized UTF-8 — the
    fused one-pass map kernel of the host-map engine, or None when the
    native lib is unavailable. Byte-exact equivalent of
    normalize_unicode → scan_unique_raw plus per-word counting
    (tests/test_native.py proves the equivalence).

    Accepts bytes or a uint8 numpy view (e.g. a memory-mapped window) —
    the view path copies nothing on the way in."""
    lib = get_lib()
    if lib is None:
        return None
    empty = (
        b"",
        np.empty(0, dtype=np.int64),
        np.empty((0, 2), dtype=np.uint32),
        np.empty(0, dtype=np.uint32),
    )
    buf = data if isinstance(data, np.ndarray) else np.frombuffer(data, dtype=np.uint8)
    buf = np.ascontiguousarray(buf, dtype=np.uint8)  # views stay zero-copy
    n = int(buf.size)
    if n == 0:
        return empty
    max_words = n // 2 + 2
    words_buf, ends, k1, k2, counts, _pos = _buffers(n, max_words)
    count = lib.mr_scan_count(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n,
        _cpclass().ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        words_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        k1.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        k2.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        max_words,
    )
    if count < 0:  # cannot happen with max_words = n//2+2; belt and braces
        return None
    count = int(count)
    if not count:
        return empty
    raw = words_buf[: int(ends[count - 1])].tobytes()
    return (
        raw,
        ends[:count].copy(),
        np.stack([k1[:count], k2[:count]], axis=1),
        counts[:count].copy(),
    )


def scan_count_sharded_raw(
    data: "bytes | np.ndarray", n_shards: int,
) -> "tuple[bytes, np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray] | None":
    """Sharded fused scan (ISSUE 9): like :func:`scan_count_raw` but the
    unique-word outputs come back GROUPED by fold shard (shard = packed
    key % n_shards, scan order preserved within a shard), plus

    - ``pos``          int64[n] — original scan index of grouped word i
      (the driver scatters keys/counts back to exact scan order for the
      device merge, keeping outputs bit-identical to the unsharded path);
    - ``shard_counts`` int64[n_shards] — uniques per shard, so shard s's
      slice is rows [cum[s], cum[s+1]) and its word bytes are one
      contiguous span of the returned buffer.

    Returns None when the native lib is unavailable (callers fall back to
    the pure-Python scan + per-shard selection)."""
    lib = get_lib()
    if lib is None:
        return None
    shard_counts = np.zeros(max(int(n_shards), 1), dtype=np.int64)
    empty = (
        b"",
        np.empty(0, dtype=np.int64),
        np.empty((0, 2), dtype=np.uint32),
        np.empty(0, dtype=np.uint32),
        np.empty(0, dtype=np.int64),
        shard_counts,
    )
    buf = data if isinstance(data, np.ndarray) else np.frombuffer(data, dtype=np.uint8)
    buf = np.ascontiguousarray(buf, dtype=np.uint8)  # views stay zero-copy
    n = int(buf.size)
    if n == 0:
        return empty
    max_words = n // 2 + 2
    words_buf, ends, k1, k2, counts, pos = _buffers(n, max_words)
    count = lib.mr_scan_count_sharded(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n,
        _cpclass().ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        int(n_shards),
        words_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        k1.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        k2.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        shard_counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        max_words,
    )
    if count < 0:  # cannot happen with max_words = n//2+2; belt and braces
        return None
    count = int(count)
    if not count:
        return empty
    raw = words_buf[: int(ends[count - 1])].tobytes()
    return (
        raw,
        ends[:count].copy(),
        np.stack([k1[:count], k2[:count]], axis=1),
        counts[:count].copy(),
        pos[:count].copy(),
        shard_counts,
    )


def coalesce_updates_into(a_keys, a_vals, m: int, b_keys, b_vals,
                          out_keys, out_vals) -> "int | None":
    """Native staging combine (ISSUE 13: loader.cpp ``mr_coalesce_updates``):
    merge sorted unique-key column ``a[:m]`` with sorted unique-key column
    ``b`` into caller-owned ``out_*`` (capacity >= m + len(b)), summing
    counts on duplicate keys. All arrays must be contiguous uint64/int64
    and ``out_*`` must not alias either input (the dispatch plane
    ping-pongs two staging buffers). Returns the merged count, or None
    when the native lib is unavailable (callers fall back to the
    vectorized numpy merge in runtime/driver.py)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "mr_coalesce_updates"):
        return None
    n = len(b_keys)
    return int(lib.mr_coalesce_updates(
        a_keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        a_vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        int(m),
        b_keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        b_vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        out_keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out_vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    ))


def merge_runs_stream(key_arrays, block: int = 1 << 16):
    """Generator of (keys uint64[b], src int32[b], idx int64[b]) blocks
    merged over K sorted key-disjoint uint64 columns — the native
    loser-tree egress (ISSUE 11: loader.cpp ``mr_merge_runs``). Streams in
    O(block) memory however large the runs are (columns may be memory
    maps: the kernel reads them sequentially, so the OS pages them
    through). Returns None when the native lib is unavailable — callers
    fall back to the vectorized argsort merge (runtime/spill.py)."""
    lib = get_lib()
    if lib is None:
        return None
    arrays = [np.ascontiguousarray(a, dtype=np.uint64) for a in key_arrays]
    k = len(arrays)

    def gen():
        ptrs = (ctypes.c_void_p * k)(*[a.ctypes.data for a in arrays])
        lens = np.asarray([len(a) for a in arrays], dtype=np.int64)
        cursors = np.zeros(k, dtype=np.int64)
        out_keys = np.empty(block, dtype=np.uint64)
        out_src = np.empty(block, dtype=np.int32)
        out_idx = np.empty(block, dtype=np.int64)
        while True:
            n = int(lib.mr_merge_runs(
                ptrs,
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                k,
                cursors.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                out_keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                out_src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                out_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                block,
            ))
            if n <= 0:
                return
            # Copies: the kernel reuses the out buffers next call, and the
            # consumer may hold a block across iterations.
            yield out_keys[:n].copy(), out_src[:n].copy(), out_idx[:n].copy()

    return gen()


def scan_unique_raw(data: bytes) -> tuple[bytes, np.ndarray, np.ndarray] | None:
    """(concatenated unique words, int64[n] exclusive end offsets,
    uint32[n,2] hash pairs) — or None when the native lib is unavailable.
    One C pass: tokenize, dedupe, hash. The caller slices individual words
    lazily (runtime/dictionary.py slices only keys it hasn't seen)."""
    lib = get_lib()
    if lib is None:
        return None
    if not data:
        return b"", np.empty(0, dtype=np.int64), np.empty((0, 2), dtype=np.uint32)
    n = len(data)
    max_words = n // 2 + 2
    words_buf, ends, k1, k2, _counts, _pos = _buffers(n, max_words)
    count = lib.mr_scan_unique(
        data, n,
        words_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        k1.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        k2.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        max_words,
    )
    if count < 0:  # cannot happen with max_words = n//2+2; belt and braces
        return None
    count = int(count)
    raw = words_buf[: int(ends[count - 1])].tobytes() if count else b""
    return raw, ends[:count].copy(), np.stack([k1[:count], k2[:count]], axis=1)


def scan_unique(data: bytes) -> tuple[list[bytes], np.ndarray] | None:
    """(unique cleaned words, uint32[n,2] hash pairs) — list form of
    scan_unique_raw, for callers that want materialized words."""
    res = scan_unique_raw(data)
    if res is None:
        return None
    raw, ends, keys = res
    words = []
    start = 0
    for end in ends.tolist():
        words.append(raw[start:end])
        start = end
    return words, keys
