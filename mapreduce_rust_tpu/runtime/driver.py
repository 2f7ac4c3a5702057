"""Chunked streaming driver — the end-to-end engine (single-chip and mesh).

This is the TPU-native replacement for the reference's whole worker
execution path (src/mr/worker.rs:65-193): instead of per-task files and
per-record writes, a single host loop streams whitespace-aligned chunks
(runtime/chunker.py) through a compiled per-chunk step and keeps running
distinct-key state on device:

    chunk bytes ──device_put──▶ tokenize_and_hash ─▶ app.device_map
        ─▶ combine_chunk (map-side combiner) ─▶ merge into state
                                                   │
         evicted tail (rare) ◀─────────────────────┘
              └─▶ host spill accumulator (exact, nothing dropped)

With ``cfg.mesh_shape > 1`` the same loop feeds groups of D chunks to the
mesh pipeline (parallel/shuffle.py): per-chip combine → bucket scatter →
``lax.all_to_all`` over ICI → per-chip merge into a hash-class-sharded
state. That collective IS the reference's mr-{m}-{r}.txt file shuffle
(src/mr/worker.rs:117-140), lowered to the interconnect.

The loop is pipelined: JAX dispatch is async, so while the device works on
chunk k the host normalizes/chunks k+1 and feeds the egress dictionary
(runtime/dictionary.py). Overflow/spill counters come back via async
device→host copies issued at dispatch and read ``Config.pipeline_depth``
steps later, so the host never blocks a round trip per chunk: one
blocking scalar read costs a whole device→host round trip, far more than
a step's compute.

Capacity faults are handled, not asserted (VERDICT r1 weak 3):
- per-chunk distinct keys > partial_capacity → the chunk/group is
  *replayed* through a lazily-compiled wider tier (counted, exact);
- mesh bucket skew > bucket capacity → same replay, tier sized so bucket
  overflow is impossible (bucket_cap = whole update);
- merged distinct keys > merge_capacity → the evicted tail spills whole
  to the host accumulator (ops/groupby.merge_batches; counted, exact).

At egress the final table joins the hash→word dictionary and each app
formats its partitions (apps/base.py), written as mr-{r}.txt like the
reference (src/mr/worker.rs:167,180-183) — including every partition's
last key, which the reference drops (worker.rs:169-184).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import time
from typing import Sequence
from zipfile import BadZipFile

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_rust_tpu.apps.base import App
from mapreduce_rust_tpu.apps.word_count import WordCount
from mapreduce_rust_tpu.config import (
    Config,
    lineage_forced,
    profile_forced,
    sync_dispatch_forced,
)
from mapreduce_rust_tpu.core.kv import KVBatch
from mapreduce_rust_tpu.ops.groupby import (
    clamp_batch,
    combine_chunk,
    merge_batches,
)
from mapreduce_rust_tpu.ops.tokenize import tokenize_and_hash
from mapreduce_rust_tpu.runtime.chunker import (
    doc_table,
    open_documents,
    pack_documents,
    resolve_corpora,
)
from mapreduce_rust_tpu.runtime.dictionary import (
    Dictionary,
    ShardedDictionary,
    new_run_token,
    remove_run_files,
)
from mapreduce_rust_tpu.runtime.histogram import Histogram
from mapreduce_rust_tpu.runtime.metrics import (
    JobStats,
    jobstats_collector,
    log,
    metrics_tick,
    start_metrics,
    stop_metrics,
)
from mapreduce_rust_tpu.runtime.trace import (
    active_tracer,
    maybe_snapshot,
    partial_path,
    start_tracing,
    stop_tracing,
    trace_counter,
    trace_span,
)

_cc_enabled = False


# ---------------------------------------------------------------------------
# XLA compile instrumentation (ISSUE 5 tentpole: the trace layer never saw
# device-side compiles — a cold run's dominant cost was invisible)
# ---------------------------------------------------------------------------

#: Every backend compile jax reported via its monitoring events since the
#: listener was installed: {"dur_s", "cache": "hit"|"miss"|"uncached"}.
#: run_job slices [n0:] around its own interval, so the log never needs
#: clearing (concurrent run_jobs in one process are already unsupported —
#: same contract as the tracer).
_COMPILE_LOG: list[dict] = []
_COMPILE_TRACK_TID = -2  # synthetic trace track: compile intervals are
# measured by jax's wall clock, not ours — on their own track they can
# never partially overlap this thread's call-structured spans
_compile_listener_installed = False
_compile_cache_state: list[str] = []  # hit/miss events awaiting their compile


def _install_compile_listener() -> None:
    """Idempotently hook jax.monitoring: one record (and one ``xla.compile``
    trace span, when tracing) per backend compile, with persistent-cache
    hit/miss status. Listener registration is append-only in jax, hence the
    once-per-process guard."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True
    import jax.monitoring as monitoring

    def on_event(event: str, **_kw) -> None:
        # Cache events fire inside compile_or_get_cached, strictly before
        # the duration event that closes the same compile: a hit on the
        # read path, a miss when the fresh result is written back. A
        # compile with neither (cache disabled, or entry below the
        # min-compile-time/min-size write thresholds) is "uncached".
        if event.endswith("/compilation_cache/cache_hits"):
            _compile_cache_state.append("hit")
        elif event.endswith("/compilation_cache/cache_misses"):
            _compile_cache_state.append("miss")

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        cache = _compile_cache_state.pop() if _compile_cache_state else "uncached"
        _compile_cache_state.clear()  # never let a stale event cross compiles
        _COMPILE_LOG.append({"dur_s": duration, "cache": cache})
        tr = active_tracer()
        if tr is not None:
            t1 = time.perf_counter()
            tr.add_span(
                "xla.compile", t1 - duration, t1,
                {"cache": cache, "seconds": round(duration, 3)},
                tid=_COMPILE_TRACK_TID,
            )

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


_MEM_SAMPLE_PERIOD_S = 0.5
_mem_last_sample = [0.0]


def _sample_device_memory(stats) -> None:
    """Device-memory gauge, fed from the existing drain/consume loops
    (never per record): Chrome "C" counter samples per local device when
    tracing, plus a manifest high-water mark. Backends without
    ``memory_stats`` (CPU) simply contribute nothing. Throttled so a
    fast drain loop doesn't turn the gauge into the hot path."""
    now = time.monotonic()
    if now - _mem_last_sample[0] < _MEM_SAMPLE_PERIOD_S:
        return
    _mem_last_sample[0] = now
    try:
        from jax._src import xla_bridge

        if not xla_bridge._backends:
            # No backend initialized in this process: local_devices()
            # would CREATE one — a ~minutes metadata probe against an
            # absent accelerator (the PR 6 worker wedge). The gauge is
            # guarded at the source now, not just at one caller, so every
            # present and future call site inherits the safety
            # (mrlint: backend-init-in-probe).
            return
        for i, dev in enumerate(jax.local_devices()):
            ms = dev.memory_stats()
            if not ms:
                continue
            in_use = ms.get("bytes_in_use")
            if in_use is None:
                continue
            trace_counter(f"device.mem.d{i}", bytes_in_use=int(in_use))
            if in_use > stats.device_mem_high_bytes:
                stats.device_mem_high_bytes = int(in_use)
    except Exception:  # a telemetry probe must never fail the run
        pass


def enable_compilation_cache(path: str | None = "auto") -> None:
    """Point XLA's persistent compilation cache at a shared directory.

    Idempotent (first caller wins). The step-fn compiles below are tens of
    seconds each on TPU; with this cache a *process* pays them at most once
    ever per (shape, backend) instead of once per run — the difference
    between a bench that times out and one that measures steady state.
    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here overrides it. Otherwise "auto" resolves to
    <repo>/.jax_cache/<host fingerprint> and any other path is used as is.
    """
    global _cc_enabled
    if _cc_enabled or not path or os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if path == "auto":
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            ".jax_cache",
        )
        # Scope by host fingerprint — "auto" only: XLA's CPU cache key does
        # NOT cover the host's instruction-set features, so an entry
        # AOT-compiled on another machine image loads with a "could lead to
        # SIGILL" warning and may do exactly that; the per-(jax, arch,
        # cpu-flags) subdir turns cross-machine reuse into a clean cold
        # compile. An EXPLICIT caller path is used verbatim — a caller
        # pointing at a prepared/shared cache dir must actually hit it.
        path = os.path.join(path, _host_fingerprint())
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    _cc_enabled = True


def _host_fingerprint() -> str:
    import hashlib

    from mapreduce_rust_tpu.native.host import host_isa

    # JAX_PLATFORMS joins the key: a pure-CPU process and one with an
    # accelerator backend on the SAME machine compile CPU entries with
    # different XLA target pseudo-features (prefer-no-scatter/gather), and
    # loading across that line warns "could lead to SIGILL".
    isa = host_isa()
    h = hashlib.sha256(
        f"{jax.__version__}:{isa}:{os.environ.get('JAX_PLATFORMS', '')}".encode()
    ).hexdigest()[:12]
    return f"{isa.split(':', 1)[0]}-{h}"


def select_device(kind: str = "auto"):
    """cfg.device → the first jax.Device of that platform. Never falls back:
    an explicit platform this process cannot open raises, and "auto" raises
    when JAX settled on the CPU because an accelerator failed to initialize
    (a chip held by another process, most often) — JAX itself would run the
    job on the CPU and say nothing."""
    if kind == "auto":
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            from jax._src import xla_bridge

            failed = {p: e for p, e in xla_bridge._backend_errors.items()
                      if p != "cpu"}
            if failed:
                raise RuntimeError(
                    f"accelerator backend failed to initialize ({failed}); "
                    "a chip serves one process at a time — is another "
                    "process holding it? Pass --device cpu (or "
                    "JAX_PLATFORMS=cpu) to run on the CPU on purpose"
                )
        return dev
    try:
        return jax.devices(kind)[0]
    except RuntimeError as e:
        raise RuntimeError(
            f"--device {kind}: this process has no {kind} device ({e}); a "
            "chip serves one process at a time"
        ) from e


_STEP_FNS: dict = {}  # (app, u_cap, use_pallas) → (map_combine, merge)


def make_step_fns(app: App, u_cap: int, use_pallas: bool = False):
    """(map_combine, merge) jitted for one app + update capacity.

    map_combine: (chunk bytes, its doc table — runtime/chunker.doc_table)
    → per-chunk partial + overflow count (ops/groupby.combine_chunk).
    Each token's doc id (ops/groupby.position_doc_ids) reaches only
    app.device_map: an app that ignores it (word count) compiles to the
    same program as without it.
    merge: fold the partial into the running state, returning the evicted
    tail and its record count (donates the old state's buffers).
    use_pallas: target is a TPU — tokenize with the fused Mosaic kernel.

    Cached at module level: apps are frozen dataclasses, so the key is a
    value key and every run_job in a process shares one set of jitted
    closures — a second run hits jax.jit's in-process executable cache
    instead of recompiling (the round-3 bench killer: warm == cold because
    fresh closures were built per call).
    """
    key = (app, u_cap, use_pallas)
    fns = _STEP_FNS.get(key)
    if fns is None:
        fns = _STEP_FNS[key] = _build_step_fns(app, u_cap, use_pallas)
    return fns


def _build_step_fns(app: App, u_cap: int, use_pallas: bool = False):
    op = app.combine_op

    @jax.jit
    def map_combine(chunk: jnp.ndarray, doc_table: jnp.ndarray):
        kv = tokenize_and_hash(chunk, use_pallas=use_pallas)
        # One sort over every byte position, then the reduce over the
        # compaction cap's rows. The overflow covers BOTH distinct keys >
        # u_cap AND records > ops/groupby.compaction_cap — either replays
        # the chunk through the full-width tier.
        update, ovf = combine_chunk(kv, doc_table, app.device_map, op, u_cap)
        # An overflowing chunk contributes NOTHING (update clamps to empty,
        # keys included — ops/groupby.clamp_batch keeps the merged state
        # sorted): the driver replays it full-width later. This makes the
        # merge safe to dispatch before the overflow flag ever reaches the
        # host, which is what lets the stream loop batch its readbacks (one
        # device→host round trip per pipeline window, not per chunk).
        update = clamp_batch(update, ovf == 0)
        return update, ovf

    @functools.partial(jax.jit, donate_argnums=(0,))
    def merge(state: KVBatch, update: KVBatch):
        # update is a combine_chunk output — already key-distinct, so it
        # joins the state's sort as it is.
        new_state, evicted = merge_batches(state, update, op=op, update_sorted=True)
        ev_count = jnp.sum(evicted.valid.astype(jnp.int32))
        return new_state, evicted, ev_count

    return map_combine, merge


def _pack_key_cols(keys: np.ndarray) -> np.ndarray:
    """[n, 2] (k1, k2) int64 columns (uint32-ranged by construction: they
    are the device hash lanes) → one uint64 packed column. Packing turns
    every key fold below into a 1-D sort/unique — np.unique(axis=0)'s
    row-structured sort was the measured finalize wall of the spill-heavy
    Zipf leg (ISSUE 11: ~4x slower than the 1-D path at 5M rows), and
    packed order == (k1, k2) lexicographic order, so the fold's output
    ordering is bit-identical."""
    return (keys[:, 0].astype(np.uint64) << np.uint64(32)) | keys[:, 1].astype(
        np.uint64
    )


def _unpack_rows(packed: np.ndarray, vals: np.ndarray) -> np.ndarray:
    return np.column_stack([
        (packed >> np.uint64(32)).astype(np.int64),
        (packed & np.uint64(0xFFFFFFFF)).astype(np.int64),
        vals.astype(np.int64),
    ])


def _sort_key_value(packed: np.ndarray, vals: np.ndarray):
    """(packed, vals) in (key, value) order, and the rows the value repair
    moved. Every batch the fold sees is a sorted run (the device merge
    sorts by (key, value); evicted tails and readbacks keep that order), so
    one stable key sort — a timsort that merges the runs it finds — does
    most of the work. Only keys whose values still descend after it (a
    spill cut inside a key, runs that share keys) get their values sorted,
    by a lexsort of those keys' rows alone. Exact for any input; only the
    speed depends on its order."""
    order = np.argsort(packed, kind="stable")
    p_s, v_s = packed[order], vals[order]
    same = p_s[1:] == p_s[:-1]
    desc = same & (v_s[1:] < v_s[:-1])
    if not desc.any():
        return p_s, v_s, 0
    # The rows of every key with a descent, found from its ends alone.
    bad = np.unique(p_s[1:][desc])
    start = np.searchsorted(p_s, bad, side="left")
    size = np.searchsorted(p_s, bad, side="right") - start
    g = np.repeat(np.arange(len(bad)), size)  # key index: 0 .. keys - 1
    rows = np.arange(len(g)) + np.repeat(start - (np.cumsum(size) - size), size)
    sub = v_s[rows]
    fixed = sub[np.lexsort((sub, g))]
    v_s[rows] = fixed
    return p_s, v_s, int(np.count_nonzero(fixed != sub))


def _combine_rows(op: str, keys: np.ndarray, vals: np.ndarray):
    """The shared fold kernel: (keys [n,2], vals [n]) → (sorted deduped
    rows [m, 3], rows the "distinct" value repair moved), value-keyed for
    "distinct", else folded per key. All key work happens on the packed
    1-D column (see _pack_key_cols)."""
    packed = _pack_key_cols(keys)
    if op == "distinct":
        # Sort by (key, value) then mask repeats — same output order as
        # np.unique over (k1, k2, value) rows, minus the structured sort.
        p_s, v_s, moved = _sort_key_value(packed, vals)
        if len(p_s):
            keep = np.empty(len(p_s), dtype=bool)
            keep[0] = True
            keep[1:] = (p_s[1:] != p_s[:-1]) | (v_s[1:] != v_s[:-1])
            p_s, v_s = p_s[keep], v_s[keep]
        return _unpack_rows(p_s, v_s), moved
    uniq, inv = np.unique(packed, return_inverse=True)
    inv = inv.reshape(-1)
    if op == "sum":
        folded = np.zeros(len(uniq), dtype=np.int64)
        np.add.at(folded, inv, vals)
    elif op == "max":
        folded = np.full(len(uniq), np.iinfo(np.int64).min)
        np.maximum.at(folded, inv, vals)
    else:
        folded = np.full(len(uniq), np.iinfo(np.int64).max)
        np.minimum.at(folded, inv, vals)
    return _unpack_rows(uniq, folded), 0


def _key_starts(packed: np.ndarray) -> np.ndarray:
    """Index of each key's first row, given the sorted fold rows' packed
    key column (_pack_key_cols): one neighbour compare. A distinct app's
    term t owns rows[starts[t]:starts[t + 1]], its doc ids already
    ascending."""
    if not len(packed):
        return np.empty(0, np.int64)
    return np.flatnonzero(np.r_[True, packed[1:] != packed[:-1]])


def _combine_pending(op: str, keys_list, vals_list):
    """Combine pending (keys, vals) batches into sorted deduped rows
    [n, 3] (k1, k2, value) — value-keyed for "distinct", else folded —
    and the rows the value repair moved (_combine_rows). Module-level and
    pure so the async spill writer can run it against a frozen snapshot
    off the consumer thread."""
    return _combine_rows(op, np.concatenate(keys_list),
                         np.concatenate(vals_list))


class HostAccumulator:
    """Exact host-side fold of device spills + the final state, per op.

    Adds are O(1) array appends; the fold is deferred and vectorized
    (np.unique over the concatenated batches + ufunc.at), so a spill-heavy
    run costs one sort at egress instead of per-record Python per spill.
    The per-key Python dict is built exactly once, when .table is read.

    Bounded-memory tier (VERDICT r4 missing 3): with ``budget_bytes`` set,
    pending arrays above the budget are combined into a SORTED, deduped run
    on disk (``spill_dir/accrun-*.npy``) and dropped from RAM, so a
    spill-heavy high-cardinality job holds O(budget + distinct) bytes
    instead of every spilled record — the tier the reference lacks (one
    ``Vec`` per partition holds the whole partition,
    /root/reference/src/mr/worker.rs:82-108). The combine+write of each
    run happens on a background :class:`AsyncSpillWriter` against frozen
    pending arrays (ISSUE 11), so the consumer keeps draining the device
    while the disk works; ``fold_arrays()`` drains the writer and merges
    the runs back exactly at finalize; ``.table`` (the Python-dict view)
    stays for the scalar in-RAM egress and the distributed worker, while
    the streaming egress and every distinct app's egress read the arrays.
    """

    def __init__(self, op: str, budget_bytes: int | None = None,
                 spill_dir: str | None = None,
                 async_spill: bool = True) -> None:
        if budget_bytes is not None and not spill_dir:
            raise ValueError("budget_bytes needs a spill_dir")
        self.op = op
        self.budget_bytes = budget_bytes
        self.spill_dir = spill_dir
        self.async_spill = async_spill
        self._keys: list[np.ndarray] = []   # each [N, 2] int64
        self._vals: list[np.ndarray] = []   # each [N] int64
        self._pending_bytes = 0
        self._runs: list[str] = []          # sorted, deduped [n,3] .npy files
        self._table: dict | None = None
        self._run_token = new_run_token()
        self._writer = None
        # Rows the "distinct" value repair moved, over every combine of
        # this accumulator (JobStats.acc_fold_repaired_rows): 0 where the
        # batches arrived in (key, value) order.
        self.repaired_rows = 0

    def add(self, keys: np.ndarray, vals: np.ndarray) -> None:
        keys = np.asarray(keys, dtype=np.int64).reshape(-1, 2)
        if len(keys):
            vals = np.asarray(vals, dtype=np.int64).reshape(-1)
            self._keys.append(keys)
            self._vals.append(vals)
            self._pending_bytes += keys.nbytes + vals.nbytes
            self._table = None  # late add after a read: refold lazily
            if self.budget_bytes is not None and self._pending_bytes > self.budget_bytes:
                self._flush_run()

    def add_batch(self, batch: KVBatch) -> None:
        keys, vals = batch.to_host()
        self.add(keys, vals)

    @property
    def has_runs(self) -> bool:
        return bool(self._runs)

    @property
    def run_count(self) -> int:
        return len(self._runs)

    def _pending_rows(self) -> np.ndarray:
        """Combine the in-RAM pending batches into sorted deduped rows
        [n, 3] (k1, k2, value) — value-keyed for "distinct", else folded."""
        rows, moved = _combine_pending(self.op, self._keys, self._vals)
        self.repaired_rows += moved
        return rows

    def _clear_pending(self) -> None:
        self._keys.clear()
        self._vals.clear()
        self._pending_bytes = 0

    def _ensure_writer(self):
        from mapreduce_rust_tpu.runtime.spill import ensure_writer

        self._writer = ensure_writer(
            self._writer, f"mr/spill-acc-{self._run_token}",
            sync=not self.async_spill,
        )
        return self._writer

    def _flush_run(self) -> None:
        """Freeze the pending batches and hand the combine + write to the
        background writer (ISSUE 11): the np.unique fold AND the .npy
        write run off the consumer thread; this thread only swaps in
        fresh lists and enqueues."""
        from mapreduce_rust_tpu.runtime.spill import (
            run_file_name,
            write_npy_run,
        )

        keys, vals = self._keys, self._vals
        self._keys, self._vals = [], []
        self._pending_bytes = 0
        os.makedirs(self.spill_dir, exist_ok=True)
        run_index = len(self._runs)
        path = os.path.join(
            self.spill_dir,
            run_file_name("accrun", self._run_token, run_index, "npy"),
        )
        self._runs.append(path)
        op = self.op

        def task() -> int:
            with trace_span("accumulator.flush_run", run=run_index):
                rows, moved = _combine_pending(op, keys, vals)
                written = write_npy_run(path, rows, run_index=run_index)
            # The consumer reads and adds to this only after drain_spills().
            self.repaired_rows += moved
            log.info("host accumulator: spilled run %d (%d rows)",
                     run_index + 1, len(rows))
            return written

        self._ensure_writer().submit(task)

    def drain_spills(self) -> None:
        """Barrier before any read of the run files (fold_arrays) or the
        final accounting; re-raises a recorded writer error."""
        if self._writer is not None:
            self._writer.drain()

    def close_spills(self, abort: bool = True) -> None:
        if self._writer is not None:
            self._writer.close(abort=abort)

    def spill_stats(self) -> dict:
        from mapreduce_rust_tpu.runtime.spill import tier_spill_stats

        return tier_spill_stats(self._writer, len(self._runs))

    def spill_snapshot(self) -> "tuple[float, float, int] | None":
        from mapreduce_rust_tpu.runtime.spill import tier_spill_snapshot

        return tier_spill_snapshot(self._writer)

    def remove_runs(self) -> None:
        """Job-end cleanup of this accumulator's spill run files (the
        driver owns the lifecycle — see dictionary.remove_run_files).
        Closes the writer first so no run lands after its unlink."""
        self.close_spills(abort=True)
        remove_run_files(self._runs)

    def _combine_sorted(self, *parts: np.ndarray) -> np.ndarray:
        """Merge sorted deduped [n,3] row arrays into one (the same
        packed-column kernel as the pending fold — one implementation, so
        the run merge and the pending combine cannot order differently)."""
        rows = np.concatenate(parts)
        rows, moved = _combine_rows(self.op, rows[:, :2], rows[:, 2])
        self.repaired_rows += moved
        return rows

    def fold_arrays(self) -> np.ndarray:
        """The exact fold as sorted rows [n, 3] (k1, k2, value) — one row
        per distinct key (scalar ops) or per distinct (key, value) pair
        ("distinct"). Runs merge through a binary-counter tree (LSM-style:
        equal-size partials merge first), so a K-run fold costs
        O(total log K) combine work instead of re-combining the full
        accumulated result once per run; peak memory stays O(result).
        Every entry is already folded, so one entry is the result as it
        is, and the entries left over merge in one combine."""
        self.drain_spills()  # every enqueued run must be on disk first
        stack: list[tuple[int, np.ndarray]] = []  # (level, rows)

        def push(rows: np.ndarray) -> None:
            level = 0
            while stack and stack[-1][0] == level:
                _, prev = stack.pop()
                rows = self._combine_sorted(prev, rows)
                level += 1
            stack.append((level, rows))

        for path in self._runs:
            push(np.load(path))
        if self._keys:
            push(self._pending_rows())
        if len(stack) > 1:
            return self._combine_sorted(*(rows for _, rows in stack))
        return stack[0][1] if stack else np.empty((0, 3), np.int64)

    def snapshot(self) -> tuple[np.ndarray, np.ndarray]:
        """(keys [n,2], vals [n]) of everything accumulated so far — for
        the driver checkpoint. Folded form, which resumes exactly (every
        op is associative). Only valid before .table is first read."""
        if not self._keys and not self._runs:
            return np.empty((0, 2), np.int64), np.empty(0, np.int64)
        rows = self.fold_arrays()
        return rows[:, :2], rows[:, 2]

    @property
    def table(self) -> dict:
        if self._table is None:
            self._table = self._fold()
        return self._table

    def _fold(self) -> dict:
        if not self._keys and not self._runs:
            return {}
        rows = self.fold_arrays()
        if self.op == "distinct":
            t: dict = collections.defaultdict(set)
            for a, b, v in rows.tolist():
                t[(a, b)].add(v)
            return t
        return {
            (a, b): v
            for a, b, v in zip(
                rows[:, 0].tolist(), rows[:, 1].tolist(), rows[:, 2].tolist()
            )
        }


@dataclasses.dataclass
class JobResult:
    stats: JobStats
    table: dict            # word bytes → final value (int or sorted doc list)
    output_files: list[str]


def _scan_payload(payload: bytes):
    """Tagged scan result of one chunk — runs on the ingest pool. The
    native C pass releases the GIL, so scans of consecutive chunks overlap
    each other, the chunker thread, and device dispatch."""
    from mapreduce_rust_tpu.native.host import scan_unique_raw

    res = scan_unique_raw(payload)
    if res is not None:
        return ("raw", *res)
    from mapreduce_rust_tpu.core.hashing import hash_words
    from mapreduce_rust_tpu.runtime.dictionary import extract_words

    seen: set = set()
    words = [w for w in extract_words(payload) if not (w in seen or seen.add(w))]
    return ("list", words, hash_words(words))


def _slice_words(raw: bytes, ends: np.ndarray, idx) -> list[bytes]:
    """Materialize words idx (ascending indices) of a concatenated
    (raw, ends) scan result."""
    ends_l = ends.tolist()
    return [raw[(ends_l[i - 1] if i else 0): ends_l[i]] for i in idx]


def scan_keys(kind, parts) -> np.ndarray:
    """The hash-pair array of a tagged scan result."""
    return parts[2] if kind == "raw" else parts[1]


def _routed_parts(keys, mask, reduce_n: int, range_mode: bool = False):
    """Reduce partitions one chunk's (masked) keys route to — the
    provenance ledger's chunk→partition edge (ISSUE 20). Hash apps route
    k1 % reduce_n, so one vectorized unique over the scan's key column
    answers it exactly; range apps route through sampler-derived
    splitters on the WORD, which the scan result no longer carries — a
    range chunk claims every partition (conservative: the blast radius
    can only over-approximate, never miss a dependent partition)."""
    if range_mode:
        return list(range(reduce_n))
    k1 = keys[:, 0] if getattr(keys, "ndim", 1) > 1 else keys
    if mask is not None:
        k1 = k1[mask]
    n = len(k1)
    if n == 0:
        return []
    # Exact answer, sampled fast path: a strided sample that already
    # shows every partition proves the full set (an observed residue is
    # definitely present; more than reduce_n is impossible) without
    # touching the other keys — for any non-degenerate chunk with
    # reduce_n in the single digits this is the ~always branch, and it
    # keeps the ledger's per-byte tax inside the ≤2% bench contract.
    # Only a skewed chunk that genuinely misses partitions pays the full
    # bincount pass.
    if n > 4096:
        sample = np.asarray(k1[:: n // 2048], dtype=np.int64) % reduce_n
        if len(np.unique(sample)) == reduce_n:
            return list(range(reduce_n))
    hits = np.bincount(
        (np.asarray(k1, dtype=np.int64) % reduce_n).astype(np.intp),
        minlength=reduce_n,
    )
    return [int(r) for r in np.flatnonzero(hits)]


def fold_scan_into_dictionary(dictionary: Dictionary, mask, kind, parts) -> None:
    """Fold one tagged scan result — ("raw", raw, ends, keys[, ...]) or
    ("list", words, keys[, ...]) — into the egress dictionary, restricted
    to the keys a filtering app keeps. mask is the PRECOMPUTED
    App.host_mask(scan_keys(...)) result (callers that also filter their
    merge stream reuse it — the [n, Q] compare is per-window hot-path
    work), or None for keep-everything, which folds via the fast paths.
    For grep-style apps the dictionary then scales with the QUERY, not the
    corpus vocabulary — non-query words are never materialized."""
    if kind == "raw":
        raw, ends, keys = parts[0], parts[1], parts[2]
        if mask is None:
            dictionary.add_scanned_raw(raw, ends, keys)
            return
        idx = np.nonzero(mask)[0].tolist()
        if idx:
            dictionary.add_scanned(_slice_words(raw, ends, idx), keys[idx])
    else:
        words, keys = parts[0], parts[1]
        if mask is not None:
            idx = np.nonzero(mask)[0].tolist()
            if not idx:
                return
            words = [words[i] for i in idx]
            keys = keys[idx]
        dictionary.add_scanned(words, keys)


_SENTINEL = object()


@contextlib.contextmanager
def _a2a_span(stats, **span_args):
    """One mesh.all_to_all block: the trace span PLUS a wall-clock
    accumulation into stats.all_to_all_s, so the manifest's ICI-vs-compute
    split exists even for untraced runs (the tracer's per-round summary
    rides along only when tracing is on). Covers tokenize + bucket scatter
    + collective + merge dispatch of the round — on an async backend this
    is dispatch-side time; the blocking tail lands in device_wait_s, and
    the chunked mesh stream puts its group on the mesh before the block
    (mesh.feed, mesh_feed_s)."""
    t0 = time.perf_counter()
    try:
        with trace_span("mesh.all_to_all", **span_args):
            yield
    finally:
        dt = time.perf_counter() - t0
        stats.all_to_all_s += dt
        # Per-round distribution beside the aggregate: the manifest then
        # carries a2a p50/p95/p99 even for untraced runs (ISSUE 5).
        stats.record_hist("a2a.round_s", dt)
        wb = span_args.get("wire_bytes")
        if wb:
            stats.record_hist("a2a.wire_bytes", wb)


class _IngestStream:
    """Shared ingest: a prefetch thread runs read→normalize→chunk ahead of
    the consumer (bounded queue), and a thread pool runs the dictionary
    scans; scan results fold into the Dictionary only on the consumer
    thread. The producer packs consecutive documents into chunks
    (runtime/chunker.pack_documents); doc_id = position in inputs +
    doc_id_offset (a worker's map task passes its task id so
    inverted_index doc ids stay global), or ``doc_ids[i]``."""

    def __init__(self, cfg: Config, inputs: Sequence[str], stats: JobStats,
                 dictionary: Dictionary, doc_id_offset: int = 0,
                 skip_chunks: int = 0,
                 doc_ids: "Sequence[int] | None" = None,
                 host_mask=None, lineage_range: bool = False) -> None:
        import queue
        import threading
        from concurrent.futures import ThreadPoolExecutor

        from mapreduce_rust_tpu.runtime.lineage import active_ledger

        self.cfg = cfg
        self.stats = stats
        # Provenance (ISSUE 20): digests computed on the scan pool (the
        # payload is hot there), recorded in chunk order by _fold_done on
        # the consumer thread. None when the ledger is off — zero work.
        self._ledger = active_ledger()
        self._lineage_range = lineage_range
        # Chunks below a resumed checkpoint: read (the chunker must stay
        # positionally deterministic) but neither dictionary-scanned nor
        # yielded — their words and counts are already in the checkpoint.
        self.skip_chunks = skip_chunks
        self.dictionary = dictionary
        # Filtering apps (App.host_mask) restrict dictionary growth to
        # their query keys; the default keep-all mask folds via fast paths.
        self.host_mask = host_mask if host_mask is not None else (lambda keys: None)
        self.workers = max(cfg.ingest_threads, 1)
        self.pool = ThreadPoolExecutor(max_workers=self.workers,
                                       thread_name_prefix="mr/ingest-io")
        self.scans: collections.deque = collections.deque()
        self.q: "queue.Queue" = queue.Queue(maxsize=max(cfg.prefetch_chunks, 1))
        self.err: BaseException | None = None
        self._stop = False
        self._doc_ids = list(doc_ids) if doc_ids is not None else None
        self._thread = threading.Thread(
            target=self._produce, args=(list(inputs), stats, doc_id_offset),
            name="mr/ingest", daemon=True
        )
        self._thread.start()

    def _put(self, item) -> bool:
        import queue

        while True:
            try:
                self.q.put(item, timeout=0.2)
                return True
            except queue.Full:
                if self._stop:
                    return False

    def _produce(self, inputs, stats, doc_id_offset) -> None:
        # This producer thread legitimately owns bytes_in/chunks/forced_cuts
        # (disjoint from the consumer's fields); under the sanitizer it must
        # say so, or its first write raises. No-op otherwise.
        stats.register_writer()

        def opened(path) -> None:
            stats.bytes_in += os.path.getsize(path)

        doc_ids = self._doc_ids or range(doc_id_offset, doc_id_offset + len(inputs))
        try:
            docs = open_documents(inputs, doc_ids, on_open=opened)
            for chunk in pack_documents(docs, self.cfg.chunk_bytes):
                stats.chunks += 1
                stats.forced_cuts += int(chunk.forced_cut)
                if not self._put(chunk):
                    return
        except BaseException as e:  # re-raised on the consumer thread
            self.err = e
        finally:
            self._put(_SENTINEL)

    def _scan_lineage(self, payload: bytes):
        """_scan_payload plus the chunk's content digest, both on the pool
        thread where the payload is hot — the scan result grows a (dg,
        nbytes) prefix that _fold_done strips and records in FIFO order."""
        from mapreduce_rust_tpu.runtime.lineage import chunk_digest

        return (chunk_digest(payload), len(payload), *_scan_payload(payload))

    def _fold_done(self, block: bool = False) -> None:
        while self.scans and (block or self.scans[0][0].done()):
            fut, doc_id = self.scans.popleft()
            res = fut.result()
            if self._ledger is not None:
                dg, nb, kind, *rest = res
            else:
                kind, *rest = res
            keys = scan_keys(kind, rest)
            mask = self.host_mask(keys)
            fold_scan_into_dictionary(self.dictionary, mask, kind, rest)
            if self._ledger is not None:
                self._ledger.record_chunk(
                    doc_id, nb, dg,
                    parts=_routed_parts(keys, mask, self.cfg.reduce_n,
                                        self._lineage_range),
                )
            block = False  # blocking drain pops exactly one

    def __iter__(self):
        scan = self._scan_lineage if self._ledger is not None else _scan_payload
        while True:
            t0 = time.perf_counter()
            with trace_span("ingest.wait"):
                chunk = self.q.get()
            dt = time.perf_counter() - t0
            self.stats.ingest_wait_s += dt
            self.stats.record_hist("ingest.wait_s", dt)
            if chunk is _SENTINEL:
                if self.err is not None:
                    raise self.err
                return
            if self.skip_chunks > 0:
                self.skip_chunks -= 1
                continue
            self.scans.append(
                (self.pool.submit(scan, bytes(chunk.data[: chunk.nbytes])),
                 chunk.first_doc)
            )
            # Backpressure: each pending future pins a chunk-sized payload;
            # fold the oldest (blocking) once the backlog exceeds the pool.
            self._fold_done(block=len(self.scans) > 2 * self.workers + 4)
            maybe_snapshot()  # flight-recorder tick: per chunk, off-hot-path
            metrics_tick()    # live-metrics sampler, same piggyback contract
            yield chunk

    def close(self, abort: bool = False) -> None:
        """Fold remaining scans and release threads. abort=True (exception
        path) skips folding and just unblocks + reaps the producer."""
        self._stop = True
        if abort:
            try:
                while True:
                    self.q.get_nowait()
            except Exception:
                pass
            for f, _doc in self.scans:
                f.cancel()
            self.scans.clear()
        else:
            while self.scans:
                self._fold_done(block=True)
        # cancel_futures + wait: queued scans cancel, the (bounded) running
        # ones finish and are reaped — an abandoned scan must not outlive
        # the stream holding its chunk payload (same contract as the
        # host-map engine's teardown).
        self.pool.shutdown(wait=True, cancel_futures=True)
        self._thread.join(timeout=5)


def _stream_single(cfg: Config, app: App, inputs, stats, acc, dictionary,
                   doc_id_offset: int = 0) -> None:
    with stats.timed("job_setup_s", "engine.setup"):
        enable_compilation_cache(cfg.compilation_cache_dir)
        device = select_device(cfg.device)
        use_pallas = device.platform == "tpu"
        u_cap = cfg.effective_partial_capacity()
        depth = max(cfg.pipeline_depth, 1)
        map_combine, merge = make_step_fns(app, u_cap, use_pallas)
        state = jax.device_put(KVBatch.empty(cfg.merge_capacity), device)
        ingest = _IngestStream(cfg, inputs, stats, dictionary, doc_id_offset,
                               host_mask=app.host_mask,
                               lineage_range=app.partition_mode == "range")
    slow_fns = None  # full-width replay path, compiled only if ever needed
    pending: collections.deque = collections.deque()  # (ovf, ev_count, evicted, chunk_host, table)

    def replay_chunk(chunk_host: np.ndarray, table) -> None:
        # More distinct keys in the chunk than partial_capacity: the fast
        # path clamped its update to empty (make_step_fns), so re-run the
        # whole chunk at full width. Exact, never silent (VERDICT r1 weak 3).
        nonlocal state, slow_fns
        stats.partial_overflow_replays += 1
        if slow_fns is None:
            slow_fns = make_step_fns(app, cfg.chunk_bytes, use_pallas)
        with trace_span("chunk.replay"):
            update, _ = slow_fns[0](jax.device_put(chunk_host, device), table)
            state, evicted, ev_count = slow_fns[1](state, update)
            if int(ev_count) > 0:
                stats.spill_events += 1
                stats.spilled_keys += int(ev_count)
                with stats.timed("spill_evict_s", "spill"):
                    acc.add_batch(evicted)

    def drain(n: int) -> None:
        # Resolve the oldest n pipeline steps with ONE batched readback:
        # every device→host read costs a round trip no matter its size,
        # so one device_get for the whole window pays that latency once
        # per `pipeline_depth` chunks instead of once per chunk.
        if n <= 0:
            return
        batch = [pending.popleft() for _ in range(n)]
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=n):
            flat = jax.device_get([x for (ovf, evc, *_rest) in batch for x in (ovf, evc)])
        dt = time.perf_counter() - t0
        stats.device_wait_s += dt
        stats.record_hist("device.drain_s", dt)
        _sample_device_memory(stats)
        for (ovf, evc, evicted, chunk_host, table), ovf_n, ev_n in zip(
            batch, flat[::2], flat[1::2]
        ):
            if int(ev_n) > 0:
                stats.spill_events += 1
                stats.spilled_keys += int(ev_n)
                with stats.timed("spill_evict_s", "spill", keys=int(ev_n)):
                    acc.add_batch(evicted)
            if int(ovf_n) > 0:
                replay_chunk(chunk_host, table)

    try:
        for chunk in ingest:
            with trace_span("chunk.dispatch"):
                # One put for the chunk and its doc table.
                chunk_dev, table = jax.device_put((chunk.data, chunk.doc_table), device)
                stats.chunks_dispatched += 1
                stats.chunk_payload_bytes += chunk.nbytes
                update, ovf = map_combine(chunk_dev, table)
                # Merge dispatches immediately — an overflowed update is
                # empty on device, so merging before the flag reaches the
                # host is safe.
                state, evicted, ev_count = merge(state, update)
                pending.append((ovf, ev_count, evicted, chunk.data, table))
            # Keep one window in flight while draining the previous one, so
            # the batched readback's round trip overlaps dispatched work.
            if len(pending) >= 2 * depth:
                drain(depth)
        drain(len(pending))
    except BaseException:
        ingest.close(abort=True)
        raise
    ingest.close()
    with stats.timed("state_readback_s", "state.readback"):
        acc.add_batch(state)


#: (app, cap) → merge_packed, LRU-bounded (ISSUE 13 satellite): the old
#: plain dict grew one compiled merge per (app, cap) FOREVER — a
#: long-lived multi-job process (ROADMAP item 2) leaked jit executables it
#: could never drop. Bounded, back-to-back same-config runs still hit the
#: warm entry (the round-3 "warm == cold" bench killer stays fixed), while
#: a churn of distinct apps/caps evicts oldest-first.
_PACKED_FNS: "collections.OrderedDict" = collections.OrderedDict()
_PACKED_FNS_MAX = 8


def clear_packed_fns() -> None:
    """Explicit clear hook for the packed-merge jit cache: drop every
    cached closure (their XLA executables free once the last reference
    dies). For embedders that KNOW no further host-engine run is coming —
    run_job's own teardown calls :func:`trim_packed_fns` instead, which
    keeps the warm path for repeated jobs."""
    _PACKED_FNS.clear()


def trim_packed_fns(limit: int = _PACKED_FNS_MAX) -> None:
    """Evict least-recently-used packed-merge closures beyond ``limit`` —
    wired into run_job teardown so a multi-job process holds a bounded
    working set instead of one executable per (app, cap) ever seen."""
    while len(_PACKED_FNS) > max(int(limit), 0):
        _PACKED_FNS.popitem(last=False)


def make_packed_merge_fn(app: App, cap: int):
    """Merge one host-mapped update, shipped as ONE flat uint32 array
    (every host→device transfer pays a fixed round trip, so the four
    KVBatch leaves must not be four transfers):

        flat[0]           n — number of real records
        flat[1 : 1+cap]   k1 (SENTINEL-padded so padding sorts last)
        flat[1+cap : 1+2cap]  k2
        flat[1+2cap : 1+3cap] value (uint32 bit-pattern of the int32)

    Returns (new_state, evicted, evicted_count), donating the old state —
    the host-engine twin of _build_step_fns.merge.
    """
    key = (app, cap)
    fn = _PACKED_FNS.get(key)
    if fn is not None:
        _PACKED_FNS.move_to_end(key)  # LRU: reuse refreshes recency
        return fn
    op = app.combine_op

    @functools.partial(jax.jit, donate_argnums=(0,))
    def merge_packed(state: KVBatch, flat: jnp.ndarray):
        n = flat[0].astype(jnp.int32)
        update = KVBatch(
            k1=flat[1 : 1 + cap],
            k2=flat[1 + cap : 1 + 2 * cap],
            value=flat[1 + 2 * cap : 1 + 3 * cap].astype(jnp.int32),
            valid=jnp.arange(cap, dtype=jnp.int32) < n,
        )
        new_state, evicted = merge_batches(state, update, op=op)
        ev_count = jnp.sum(evicted.valid.astype(jnp.int32))
        return new_state, evicted, ev_count

    _PACKED_FNS[key] = merge_packed
    trim_packed_fns()  # the bound holds at every insert, not only job end
    return merge_packed


def _pack_update(keys: np.ndarray, values: np.ndarray, cap: int) -> np.ndarray:
    """Lay one window's (keys uint32[n,2], values) into the flat layout
    make_packed_merge_fn expects. The reference packer: allocates (and
    memsets) a fresh buffer per call — the dispatch plane's _PackStager
    produces byte-identical output from a persistent buffer (the test
    suite holds the two equal)."""
    n = len(keys)
    flat = np.full(1 + 3 * cap, 0xFFFFFFFF, dtype=np.uint32)  # SENTINEL pad
    flat[0] = n
    flat[1 : 1 + n] = keys[:, 0]
    flat[1 + cap : 1 + cap + n] = keys[:, 1]
    flat[1 + 2 * cap : 1 + 2 * cap + n] = np.asarray(values, dtype=np.uint32)
    return flat


class _PackStager:
    """Zero-memset packed-update staging (ISSUE 13 tentpole b): ONE
    persistent ``1 + 3·cap`` uint32 buffer reused across dispatches,
    re-sentineling only the previously-dirty prefix beyond the new fill.
    The old per-dispatch ``np.full`` was a ~786 KB allocate+memset at the
    default cap even for a 100-word tail window; here a small window
    touches O(n) bytes plus whatever the LAST window dirtied — by
    construction byte-identical to :func:`_pack_update`'s output.

    Reuse safety: on the CPU backend ``jax.device_put`` may alias a
    suitably aligned host buffer instead of copying it (zero-copy; even
    ``block_until_ready`` leaves the alias), so the dispatch plane puts a
    copy there. On accelerator backends the host→device transfer may be
    asynchronous w.r.t. the source buffer; ``needs_barrier`` tells the
    dispatch plane to wait for the put (``block_until_ready`` on the INPUT
    array — a dispatch-thread-local sync the router never sees) before
    this buffer is dirtied again."""

    SENTINEL = np.uint32(0xFFFFFFFF)

    def __init__(self, cap: int, device) -> None:
        self.cap = cap
        self.flat = np.full(1 + 3 * cap, self.SENTINEL, dtype=np.uint32)
        self.dirty = 0  # records of the previous pack still in the buffer
        self.needs_barrier = getattr(device, "platform", "cpu") != "cpu"

    def pack(self, k1: np.ndarray, k2: np.ndarray,
             vals: np.ndarray) -> np.ndarray:
        n = len(k1)
        cap, flat, dirty = self.cap, self.flat, self.dirty
        if dirty > n:  # re-sentinel ONLY the stale tail of each section
            flat[1 + n : 1 + dirty] = self.SENTINEL
            flat[1 + cap + n : 1 + cap + dirty] = self.SENTINEL
            flat[1 + 2 * cap + n : 1 + 2 * cap + dirty] = self.SENTINEL
        flat[0] = n
        flat[1 : 1 + n] = k1
        flat[1 + cap : 1 + cap + n] = k2
        flat[1 + 2 * cap : 1 + 2 * cap + n] = vals
        self.dirty = n
        return flat


def _coalesce_updates_py(a_keys, a_vals, m, b_keys, b_vals):
    """Vectorized numpy fallback for ``mr_coalesce_updates`` (no native
    toolchain): merge two sorted unique-key columns, summing counts on
    duplicate keys. Same output, one concatenate+argsort instead of the
    linear walk."""
    keys = np.concatenate([a_keys[:m], b_keys])
    vals = np.concatenate([a_vals[:m], b_vals])
    order = np.argsort(keys, kind="stable")
    ks, vs = keys[order], vals[order]
    if not len(ks):
        return ks, vs
    first = np.empty(len(ks), dtype=bool)
    first[0] = True
    first[1:] = ks[1:] != ks[:-1]
    idx = np.nonzero(first)[0]
    return ks[idx], np.add.reduceat(vs, idx)


# ---------------------------------------------------------------------------
# slow_dispatch chaos checkpoint (ISSUE 13 satellite) — the spill plane's
# slow_disk pattern: seeded per-merge-dispatch delay, MR_CHAOS only (the
# env form rides a whole process tree), cached per spec string.
# ---------------------------------------------------------------------------

_dispatch_chaos_cache: dict = {}


def _chaos_slow_dispatch(dispatch_index: int) -> None:
    """The ``slow_dispatch`` injection checkpoint: ONE site in the dispatch
    plane (fires per merge dispatch, so ``p=`` samples by dispatch index
    and reruns delay the same dispatches). The async plane hides the delay
    on the dispatch thread; the sync plane eats it on the router's wall."""
    spec = os.environ.get("MR_CHAOS")
    if not spec:
        return
    plan = _dispatch_chaos_cache.get(spec)
    if plan is None:
        try:
            from mapreduce_rust_tpu.analysis.chaos import ChaosPlan

            plan = ChaosPlan.parse(spec)
        except Exception:
            plan = False  # a bad ambient spec must not fail dispatches
        _dispatch_chaos_cache[spec] = plan
    if not plan:
        return
    f = plan.pick("slow_dispatch", tid=dispatch_index)
    if f is not None and f.seconds > 0:
        time.sleep(f.seconds)


def dispatch_chaos_fired(spec: str) -> list:
    """Fired slow_dispatch events for ``spec`` (test/bench introspection)."""
    plan = _dispatch_chaos_cache.get(spec)
    return plan.fired() if plan else []


# (sync_dispatch_forced is imported from config at the top of this module:
# the fold-shard auto heuristic reads the SAME check — one definition, so
# the plane and the heuristic can never disagree on what counts as async.)


class _DispatchPlane:
    """The device-merge dispatch plane (ISSUE 13 tentpole): scan-order
    scatter-back, update pack, ``device_put`` and the compiled packed
    merge — the per-window host→device hop that PR 10's doctor measured
    as ~13 s of host-glue on the Zipf leg — run on ONE dedicated
    depth-bounded dispatch thread. The router hands off O(1) per window
    (a tuple of already-materialized scan arrays) and goes back to
    routing; glue stops booking device hops.

    Three costs die here:

    - **cross-window coalescing** (``Config.dispatch_coalesce``, "sum"
      apps only — pre-summing any other op would be wrong): successive
      windows' (packed-key, count) columns merge into a staging combine
      buffer (``mr_coalesce_updates``: sorted linear merge, duplicate
      keys sum), and a device merge dispatches only when fill crosses
      ``dispatch_fill_frac·cap`` or the stream ends — under a Zipf
      vocabulary most of a window's keys already sit in staging, so far
      fewer records ship;
    - **zero-memset staging** (:class:`_PackStager`): the per-dispatch
      ``np.full(1 + 3·cap)`` becomes a persistent buffer that
      re-sentinels only the previously-dirty prefix;
    - **serialized dispatch**: the jit call and its drain readbacks run
      off the router thread entirely (``--sync-dispatch`` /
      ``MR_DISPATCH_SYNC=1`` keeps the inline path for A/B).

    Exactness: the dispatch stream is a pure function of the window
    sequence (which the router consumes in window order) and the dispatch
    config — never of (host_map_workers, fold_shards) — so outputs stay
    bit-identical across the whole (W, S) matrix at a fixed dispatch
    config; with coalescing OFF the stream is exactly PR 10's, sync or
    async. Coalescing changes WHICH merges the device sees (sorted,
    pre-summed), not what they sum to: oracle-exact by associativity.

    Failure containment is the PR 9/10 plane pattern verbatim: a dispatch
    error poisons the plane, the dead thread keeps DRAINING its queue so
    the router's bounded ``submit`` can never deadlock, the original
    error re-raises on the router at the next submit or at ``finish``,
    and ``abort`` forces the sentinel past a full queue.
    """

    _SENTINEL = object()
    _QUEUE_DEPTH = 8  # windows in flight router→dispatch; each pins one
    # window's scan arrays (shared read-only with the fold plane's slices)

    def __init__(self, cfg: Config, app: App, stats: JobStats, acc,
                 dictionary, device) -> None:
        import queue
        import threading

        self.app = app
        self.stats = stats
        self.acc = acc
        self.dictionary = dictionary
        self.device = device
        self.cap = cfg.host_update_cap
        self.depth = max(cfg.pipeline_depth, 1)
        self.sync = (not cfg.dispatch_async) or sync_dispatch_forced()
        self.coalesce = bool(cfg.dispatch_coalesce) \
            and app.combine_op == "sum"
        self.stage_cap = cfg.effective_dispatch_stage_cap()
        self.fill_threshold = max(
            1, min(self.stage_cap,
                   int(round(cfg.dispatch_fill_frac * self.stage_cap)))
        )
        self.merge_packed = make_packed_merge_fn(app, self.cap)
        self.state = jax.device_put(KVBatch.empty(cfg.merge_capacity), device)
        self.pending: collections.deque = collections.deque()  # (ev, evicted)
        self._stager = _PackStager(self.cap, device)
        if self.coalesce:
            # Ping-pong staging pair, sized stage_cap (SEVERAL windows of
            # distinct keys — a cap-sized buffer would never coalesce a
            # high-cardinality window; see Config.dispatch_stage_cap):
            # the native merge writes into the OTHER buffer (inputs must
            # not alias outputs), then the roles swap — no allocation per
            # window.
            self._skeys = [
                np.empty(self.stage_cap, np.uint64) for _ in range(2)
            ]
            self._svals = [
                np.empty(self.stage_cap, np.int64) for _ in range(2)
            ]
            self._scur = 0
            self._sn = 0
        # Plane-local tallies (the fold-plane doctrine): the dispatch
        # thread owns these cells; the router publishes benign-stale
        # copies per window (publish_live) and collect() writes the exact
        # finals after the join.
        self.dispatch_s = 0.0        # thread seconds in scatter/pack/put/jit
        self.stall_s = 0.0           # router blocked on a full queue + join
        self.idle_s = 0.0            # thread seconds waiting for windows
        self.device_wait_s = 0.0
        self.spill_events = 0
        self.spilled_keys = 0
        self.merge_dispatches = 0
        self.records_shipped = 0
        self.submit_hist = Histogram()   # per-dispatch pack+put+jit seconds
        self.drain_hist = Histogram()    # per-drain blocking readback
        self.error: "BaseException | None" = None
        self.poisoned = threading.Event()
        self._finished = False
        stats.dispatch_mode = ("sync" if self.sync else "async") \
            + ("+coalesce" if self.coalesce else "")
        if self.sync:
            self._q = None
            self._thread = None
            return
        self._q: "queue.Queue" = queue.Queue(maxsize=self._QUEUE_DEPTH)
        self._thread = threading.Thread(
            target=self._loop, name="mr/dispatch", daemon=True
        )
        self._thread.start()

    # ---- dispatch thread ----

    def _loop(self) -> None:
        # Sanitizer registration: this thread legitimately writes
        # device_mem_high_bytes (via _sample_device_memory) — every other
        # tally is plane-local until collect().
        self.stats.register_writer()
        q = self._q
        saw_sentinel = False
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                self.idle_s += time.perf_counter() - t0
                if item is self._SENTINEL:
                    saw_sentinel = True
                    if not self.poisoned.is_set():
                        self._finalize()
                    return
                if self.poisoned.is_set():
                    continue  # poisoned: drain, don't dispatch
                self._handle(item)
        except BaseException as e:
            self.error = e
            self.poisoned.set()
            if not saw_sentinel:
                # Keep consuming (discarding) until the sentinel: the
                # router's bounded put must never deadlock against a dead
                # dispatch thread.
                while q.get() is not self._SENTINEL:
                    pass

    def _handle(self, item) -> None:
        """One window: scatter back to exact scan order, apply the
        filtering app's mask, stamp values, then coalesce-or-dispatch."""
        doc_id, kind, keys, counts, pos, mask = item
        t0 = time.perf_counter()
        with trace_span("dispatch.window", doc=doc_id, n=len(keys)):
            if kind == "sharded":
                # Grouped scan result: scatter keys/counts (and the mask,
                # computed on grouped rows) back to EXACT scan order so
                # the merge stream matches the unsharded engine's.
                keys_d = np.empty_like(keys)
                keys_d[pos] = keys
                counts_d = np.empty_like(counts)
                counts_d[pos] = counts
                if mask is not None:  # filtering app: query keys only
                    mask_d = np.empty(len(pos), dtype=bool)
                    mask_d[pos] = mask
                    keys_d, counts_d = keys_d[mask_d], counts_d[mask_d]
                keys, counts = keys_d, counts_d
            elif mask is not None:  # filtering app: query keys only
                keys, counts = keys[mask], counts[mask]
            values = self.app.host_values(counts, doc_id)
            if self.coalesce:
                self._coalesce_window(keys, values)
            else:
                # PR 10 stream verbatim: scan order, split at cap.
                cap = self.cap
                for start in range(0, len(keys), cap):
                    ks = keys[start : start + cap]
                    self._dispatch(
                        ks[:, 0], ks[:, 1],
                        np.asarray(values[start : start + cap],
                                   dtype=np.uint32),
                    )
        self.dispatch_s += time.perf_counter() - t0

    def _coalesce_window(self, keys: np.ndarray, values) -> None:
        from mapreduce_rust_tpu.native.host import coalesce_updates_into

        packed = (keys[:, 0].astype(np.uint64) << np.uint64(32)) \
            | keys[:, 1].astype(np.uint64)
        order = np.argsort(packed, kind="stable")
        pk = np.ascontiguousarray(packed[order])
        pv = np.ascontiguousarray(
            np.asarray(values, dtype=np.int64)[order]
        )
        n = len(pk)
        if self._sn + n > self.stage_cap:
            # The merged result may not fit: flush first. Conservative
            # (duplicates could have made it fit), but deterministic and
            # cheap — and fill_threshold <= stage_cap means staging
            # flushes well before this bound matters under normal shapes.
            self._flush_staging()
        if n >= self.stage_cap:
            # A window wider than the whole staging buffer ships
            # directly, in sorted cap-sized slices — never through
            # staging (with the auto 64x stage cap this is the
            # degenerate single-giant-window shape only).
            for start in range(0, n, self.cap):
                self._dispatch_packed(pk[start : start + self.cap],
                                      pv[start : start + self.cap])
            return
        cur, nxt = self._scur, 1 - self._scur
        m = coalesce_updates_into(
            self._skeys[cur], self._svals[cur], self._sn, pk, pv,
            self._skeys[nxt], self._svals[nxt],
        )
        if m is None:  # no native lib: vectorized numpy merge
            ks, vs = _coalesce_updates_py(
                self._skeys[cur], self._svals[cur], self._sn, pk, pv
            )
            m = len(ks)
            self._skeys[nxt][:m] = ks
            self._svals[nxt][:m] = vs
        self._scur, self._sn = nxt, int(m)
        if self._sn >= self.fill_threshold:
            self._flush_staging()

    def _flush_staging(self) -> None:
        """Ship the staging combine buffer as cap-sized packed merges
        (sorted, pre-summed): every chunk but the tail goes out 100%
        full — the record-count reduction IS the coalesce factor."""
        if not self.coalesce or self._sn == 0:
            return
        cur, n = self._scur, self._sn
        self._sn = 0
        for start in range(0, n, self.cap):
            # Clip the tail chunk at the FILL, not the buffer: a bare
            # [start : start+cap] slice clips at stage_cap and would ship
            # stale staging slots beyond n as real records.
            end = min(start + self.cap, n)
            self._dispatch_packed(self._skeys[cur][start:end],
                                  self._svals[cur][start:end])

    def _dispatch_packed(self, pk: np.ndarray, pv: np.ndarray) -> None:
        # int64 staging counts → the uint32 bit pattern the packed layout
        # carries (the device accumulates in int32 two's complement, so
        # pre-summing mod 2^32 is bit-exact against per-window merges).
        self._dispatch(
            (pk >> np.uint64(32)).astype(np.uint32),
            (pk & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            pv.astype(np.uint32),
        )

    def _dispatch(self, k1: np.ndarray, k2: np.ndarray,
                  vals: np.ndarray) -> None:
        t0 = time.perf_counter()
        _chaos_slow_dispatch(self.merge_dispatches)
        flat = self._stager.pack(k1, k2, vals)
        with trace_span("dispatch.submit", n=len(k1)):
            if self._stager.needs_barrier:
                # Accelerator backends: the put may read the host buffer
                # asynchronously — wait before the stager dirties it again.
                flat_dev = jax.device_put(flat, self.device)
                flat_dev.block_until_ready()
            else:
                # CPU: the put may alias the host buffer (zero-copy), so
                # the next pack would rewrite a merge still to run — put
                # a copy, never the stager's buffer itself.
                flat_dev = jax.device_put(flat.copy(), self.device)
            self.state, evicted, ev_count = self.merge_packed(
                self.state, flat_dev
            )
        self.pending.append((ev_count, evicted))
        self.merge_dispatches += 1
        self.records_shipped += len(k1)
        self.submit_hist.add(time.perf_counter() - t0)
        if len(self.pending) >= 2 * self.depth:
            self._drain(self.depth)

    def _drain(self, n: int) -> None:
        # One batched readback per window batch — see _stream_single.drain.
        if n <= 0:
            return
        batch = [self.pending.popleft() for _ in range(n)]
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=n):
            counts = jax.device_get([ev for ev, _ in batch])
        dt = time.perf_counter() - t0
        self.device_wait_s += dt
        self.drain_hist.add(dt)
        _sample_device_memory(self.stats)
        for (ev, evicted), ev_n in zip(batch, counts):
            if int(ev_n) > 0:
                self.spill_events += 1
                self.spilled_keys += int(ev_n)
                with trace_span("spill", keys=int(ev_n)):
                    self.acc.add_batch(evicted)

    def _finalize(self) -> None:
        """End-of-stream: flush the staging combine buffer, resolve every
        pending merge. Runs on the dispatch thread (async) or the router
        (sync) — after it, ``self.state`` is the complete device fold."""
        self._flush_staging()
        self._drain(len(self.pending))

    # ---- router side ----

    def _raise_error(self) -> None:
        if self.error is not None:
            raise self.error
        raise RuntimeError("dispatch plane poisoned without a recorded error")

    def submit(self, item) -> None:
        """Hand one window to the plane — O(1) for the router (sync mode
        runs the dispatch inline, the PR 10 path). Blocked = dispatch
        backpressure, timed into ``stall_s`` — the wall-clock "the
        dispatch is the ceiling" signal, exactly as fold_stall_s is for
        the fold."""
        import queue as _queue

        if self.sync:
            self._handle(item)
            return
        if self.poisoned.is_set():
            self._raise_error()
        try:
            self._q.put_nowait(item)
            return
        except _queue.Full:
            pass
        t0 = time.perf_counter()
        try:
            with trace_span("host_map.dispatch_stall"):
                while True:
                    if self.poisoned.is_set():
                        self._raise_error()
                    try:
                        self._q.put(item, timeout=0.05)
                        return
                    except _queue.Full:
                        continue
        finally:
            self.stall_s += time.perf_counter() - t0

    def finish(self) -> None:
        """Clean end-of-stream: sentinel, join, surface any dispatch
        error — called AFTER the last window was submitted. The join wall
        (the plane catching up on its backlog + the final drain) counts
        as dispatch stall, mirroring the fold plane's accounting."""
        if self._finished:
            return
        self._finished = True
        if self.sync:
            self._finalize()
            return
        t0 = time.perf_counter()
        with trace_span("host_map.dispatch_stall"):
            self._q.put(self._SENTINEL)
            self._thread.join()
        self.stall_s += time.perf_counter() - t0
        if self.poisoned.is_set():
            self._raise_error()

    def abort(self) -> None:
        """Exception-path teardown: poison (the thread discards its
        backlog), force a sentinel past a full queue by displacing one
        item, reap the thread. Idempotent, never raises, never blocks
        forever."""
        import queue as _queue

        self.poisoned.set()
        if self._finished:
            return
        self._finished = True
        if self.sync:
            return
        while True:
            try:
                self._q.put_nowait(self._SENTINEL)
                break
            except _queue.Full:
                try:
                    self._q.get_nowait()
                except _queue.Empty:
                    pass
        self._thread.join(timeout=10)

    def mean_fill_frac(self) -> float:
        if not self.merge_dispatches:
            return 0.0
        return self.records_shipped / (self.merge_dispatches * self.cap)

    def publish_live(self, stats: JobStats) -> None:
        """Per-window live publication (router thread): the plane's cells
        are benign-stale at worst — the live ring, the fleet view and the
        streaming doctor must see a dispatch-bound job DURING the run
        (the PR 9 fold_s pattern). collect() writes the exact finals."""
        stats.dispatch_s = self.dispatch_s
        stats.dispatch_stall_s = self.stall_s
        stats.merge_dispatches = self.merge_dispatches
        stats.merge_fill_frac = round(self.mean_fill_frac(), 6)
        stats.device_wait_s = self.device_wait_s
        stats.spill_events = self.spill_events
        stats.spilled_keys = self.spilled_keys

    def collect(self, stats: JobStats) -> None:
        """Fold the plane's tallies into JobStats — router thread only,
        after finish/abort joined the thread (the fold-plane collect
        doctrine)."""
        stats.dispatch_s = self.dispatch_s
        stats.dispatch_stall_s = self.stall_s
        stats.merge_dispatches = self.merge_dispatches
        stats.merge_fill_frac = round(self.mean_fill_frac(), 6)
        stats.device_wait_s = self.device_wait_s
        stats.spill_events = self.spill_events
        stats.spilled_keys = self.spilled_keys
        for name, h in (("dispatch.submit_s", self.submit_hist),
                        ("device.drain_s", self.drain_hist)):
            if h.count:
                agg = stats.hists.get(name)
                if agg is None:
                    agg = stats.hists[name] = Histogram()
                agg.merge(h)


class _FoldShardPlane:
    """The sharded egress fold (ISSUE 9): S fold threads, each the SOLE
    owner of one key-hash-disjoint dictionary shard
    (runtime/dictionary.ShardedDictionary), fed per-window per-shard
    slices by the host-map router over bounded queues.

    Ownership discipline — the refactor the PR 3 sanitizer makes
    mechanically checkable: the router thread never touches shard state
    (it only slices read-only scan results and enqueues); a fold thread
    never touches another shard's queue or dictionary; each shard
    dictionary's owner is handed to its fold thread at start
    (``set_owner``), so under ``MR_SANITIZE=1`` a fold from the wrong
    thread raises at the write site and a mis-ROUTED key fails the
    vectorized ``check_shard_route`` assert before it can split a key's
    dedup state across shards.

    Failure containment: a fold thread that raises records its error,
    flips the shared poison flag and keeps DRAINING its queue (discarding)
    until the sentinel — the router's bounded ``put`` can therefore never
    deadlock against a dead consumer; the router surfaces the recorded
    error at its next route or at ``finish``. ``abort`` (exception-path
    teardown) poisons every shard, forces sentinels past full queues and
    reaps the threads without ever blocking forever.
    """

    _SENTINEL = object()

    def __init__(self, cfg: Config, stats: JobStats, shards) -> None:
        import queue
        import threading

        from mapreduce_rust_tpu.analysis.sanitize import sanitize_enabled

        self.n = len(shards)
        self.stats = stats
        self.shards = shards
        self._sanitize = sanitize_enabled(cfg)
        # Bounded per-shard queues: each entry pins one window's grouped
        # scan arrays (shared read-only across shards — slices are views),
        # so fold-plane memory stays O(depth × window result), never
        # O(corpus) — the same flat-memory contract as the scan budget.
        self.queues = [queue.Queue(maxsize=8) for _ in range(self.n)]
        self.errors: list = [None] * self.n
        self.poisoned = threading.Event()
        self.fold_s = [0.0] * self.n
        self.idle_s = [0.0] * self.n
        self.hists = [Histogram() for _ in range(self.n)]
        self.stall_s = 0.0  # router side: blocked puts + end-of-stream join
        self._finished = False
        self.threads = [
            threading.Thread(target=self._loop, args=(s,),
                             name=f"mr/fold-{s}", daemon=True)
            for s in range(self.n)
        ]
        for t in self.threads:
            t.start()

    # ---- fold threads ----

    def _loop(self, s: int) -> None:
        shard = self.shards[s]
        # Sanitizer registration (ISSUE 9 satellite): this thread becomes
        # the shard dictionary's owner and a registered stats writer —
        # no-ops unsanitized, asserts armed under MR_SANITIZE=1.
        self.stats.register_writer()
        set_owner = getattr(shard, "set_owner", None)
        if set_owner is not None:
            set_owner()
        q = self.queues[s]
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                self.idle_s[s] += time.perf_counter() - t0
                if item is self._SENTINEL:
                    return
                if self.poisoned.is_set():
                    continue  # another shard failed: drain, don't fold
                t0 = time.perf_counter()
                with trace_span("host_map.fold", shard=s):
                    self._fold_one(s, shard, item)
                dt = time.perf_counter() - t0
                self.fold_s[s] += dt
                self.hists[s].add(dt)
        except BaseException as e:
            self.errors[s] = e
            self.poisoned.set()
            # Keep consuming (discarding) until the sentinel: the router's
            # bounded put must never deadlock against a dead fold thread.
            while q.get() is not self._SENTINEL:
                pass

    def _fold_one(self, s: int, shard, item) -> None:
        kind = item[0]
        if kind == "raw":
            # Pre-partitioned native scan: rows [lo, hi) and one
            # contiguous word-bytes span belong to this shard.
            _, raw, ends, keys, lo, hi, mask = item
            if hi <= lo:
                return
            base = int(ends[lo - 1]) if lo else 0
            raw_s = raw[base:int(ends[hi - 1])]
            ends_s = ends[lo:hi] - base
            keys_s = keys[lo:hi]
            if self._sanitize:
                from mapreduce_rust_tpu.analysis.sanitize import (
                    check_shard_route,
                )

                check_shard_route(keys_s, self.n, s)
            mask_s = mask[lo:hi] if mask is not None else None
            fold_scan_into_dictionary(shard, mask_s, "raw",
                                      (raw_s, ends_s, keys_s))
        else:
            # Python-fallback scan: no pre-partitioning, so every shard
            # thread selects its own keys from the shared result — the
            # per-word slicing cost parallelizes across shards exactly
            # like the fold it feeds.
            _, words, keys, mask = item
            from mapreduce_rust_tpu.runtime.dictionary import (
                shard_ids_of_packed,
            )

            packed = (
                keys[:, 0].astype(np.uint64) << np.uint64(32)
            ) | keys[:, 1].astype(np.uint64)
            sel = shard_ids_of_packed(packed, self.n) == np.uint64(s)
            if mask is not None:
                sel &= mask
            idx = np.nonzero(sel)[0].tolist()
            if idx:
                shard.add_scanned([words[i] for i in idx], keys[idx])

    # ---- router side ----

    def _raise_error(self) -> None:
        for e in self.errors:
            if e is not None:
                raise e
        raise RuntimeError("fold plane poisoned without a recorded error")

    def _put(self, s: int, item) -> None:
        import queue as _queue

        if self.poisoned.is_set():
            self._raise_error()
        q = self.queues[s]
        try:
            q.put_nowait(item)
            return
        except _queue.Full:
            pass
        # Blocked = fold backpressure: timed separately from glue so the
        # bottleneck attribution can say "the fold is the ceiling".
        t0 = time.perf_counter()
        try:
            with trace_span("host_map.fold_stall", shard=s):
                while True:
                    if self.poisoned.is_set():
                        self._raise_error()
                    try:
                        q.put(item, timeout=0.05)
                        return
                    except _queue.Full:
                        continue
        finally:
            self.stall_s += time.perf_counter() - t0

    def route_raw(self, raw, ends, keys, shard_counts, mask) -> None:
        """Hand each shard its slice of one pre-partitioned scan result.
        O(shards) router work per window — the per-word routing loop this
        PR deletes lives in the native kernel now."""
        cum = 0
        for s, c in enumerate(shard_counts.tolist()):
            lo, hi = cum, cum + c
            cum = hi
            if c:
                self._put(s, ("raw", raw, ends, keys, lo, hi, mask))

    def route_list(self, words, keys, mask) -> None:
        for s in range(self.n):
            self._put(s, ("list", words, keys, mask))

    def finish(self) -> None:
        """Clean end-of-stream: sentinel every queue, join every thread,
        surface any fold error — called AFTER the last scan result was
        routed, so the teardown order is router → fold threads → (the
        caller's) device merge drain."""
        if self._finished:
            return
        self._finished = True
        t0 = time.perf_counter()
        with trace_span("host_map.fold_stall"):
            for q in self.queues:
                q.put(self._SENTINEL)
            for t in self.threads:
                t.join()
        self.stall_s += time.perf_counter() - t0
        if self.poisoned.is_set():
            self._raise_error()

    def abort(self) -> None:
        """Exception-path teardown: poison (fold threads discard their
        backlog), force a sentinel past a full queue by displacing one
        item, reap the threads. Idempotent, never raises, never blocks
        forever."""
        import queue as _queue

        self.poisoned.set()
        if self._finished:
            return  # finish() already joined the threads
        self._finished = True
        for q in self.queues:
            while True:
                try:
                    q.put_nowait(self._SENTINEL)
                    break
                except _queue.Full:
                    try:
                        q.get_nowait()
                    except _queue.Empty:
                        pass
        for t in self.threads:
            t.join(timeout=10)

    def collect(self, stats: JobStats) -> None:
        """Fold the per-thread tallies into JobStats — router thread only,
        after finish/abort joined the threads, so no write races exist
        (and the sanitizer's single-writer contract holds)."""
        stats.fold_s = sum(self.fold_s)
        stats.fold_stall_s = self.stall_s
        stats.fold_shard_s = [round(v, 6) for v in self.fold_s]
        stats.fold_shard_idle_s = [round(v, 6) for v in self.idle_s]
        agg = stats.hists.get("host_map.fold_s")
        if agg is None:
            agg = stats.hists["host_map.fold_s"] = Histogram()
        for h in self.hists:
            if h.count:
                agg.merge(h)


_CUT_PROBE = 1 << 16  # how far back a window cut searches for whitespace


def _iter_windows(cfg: Config, inputs, stats):
    """(doc_id, raw window view) stream — ZERO-COPY uint8 views over each
    memory-mapped input, cut at ASCII whitespace (safe before
    normalization — normalize never alters ASCII bytes). Only the last
    _CUT_PROBE bytes of a window are materialized to find the cut; a
    window whose final 64 KB contains no whitespace is force-cut at a
    UTF-8 sequence boundary and counted in stats.forced_cuts (the device
    chunker's policy; note its force threshold is a whole chunk, but any
    token past _CUT_PROBE already exceeds the tokenizer's max_word_len by
    three orders of magnitude). No read-ahead thread: the page-faulting
    sequential read happens inside the GIL-free native scan, which the
    engine already overlaps with the Python glue."""
    from mapreduce_rust_tpu.runtime.chunker import _ws_cut, utf8_safe_cut

    for doc_id, path in enumerate(inputs):
        size = os.path.getsize(path)
        stats.bytes_in += size
        if size == 0:
            continue
        mm = np.memmap(path, dtype=np.uint8, mode="r")
        try:  # sequential readahead: fault whole extents, not page by page
            import mmap as _mmap

            mm._mmap.madvise(_mmap.MADV_SEQUENTIAL)
        except (AttributeError, OSError, ValueError):
            pass
        start = 0
        while start < size:
            end = min(start + cfg.host_window_bytes, size)
            if end < size:
                probe_at = max(start, end - _CUT_PROBE)
                tail = mm[probe_at:end].tobytes()
                off, forced = _ws_cut(tail, 0, len(tail))
                if forced:
                    stats.forced_cuts += 1
                    off = utf8_safe_cut(tail, off)
                cut = probe_at + off
            else:
                cut = end
            yield doc_id, mm[start:cut]
            start = cut


def _py_scan_count(window: bytes):
    """Pure-Python fallback for scan_count_raw (no native toolchain):
    exact, an order of magnitude slower. The window is RAW bytes, so it
    normalizes first — the fused C pass does both in one sweep."""
    from mapreduce_rust_tpu.core.hashing import hash_words
    from mapreduce_rust_tpu.core.normalize import normalize_unicode
    from mapreduce_rust_tpu.runtime.dictionary import extract_words

    counter = collections.Counter(extract_words(normalize_unicode(bytes(window))))
    words = list(counter.keys())
    keys = hash_words(words)
    counts = np.asarray([counter[w] for w in words], dtype=np.uint32)
    return words, keys, counts


def _stream_host_map(cfg: Config, app: App, inputs, stats, acc, dictionary,
                     doc_id_offset: int = 0) -> None:
    """The host-map engine: one fused native pass per window tokenizes,
    dedupes, hashes and counts on the host — the very scan that feeds the
    egress dictionary — and the device merges the compacted updates. The
    map lives where the reference's map lives (the worker CPU,
    src/app/wc.rs:6-13); the framework's added value is the device-side
    combine/merge/shuffle state machine behind it. End-to-end this beats
    the device-tokenize engine whenever host→device bandwidth, not
    compute, is the ceiling: the host scan's updates are 10-30× smaller
    than the text they replace.

    The scan fans out (ISSUE 2 tentpole): ``cfg.host_map_workers`` threads
    (auto = usable cores, one reserved for this consumer) run the GIL-releasing native scan concurrently —
    per-thread scratch arenas already isolate them (native/host._buffers)
    — while THIS thread, the single consumer, folds results into the
    dictionary and dispatches packed merges strictly IN WINDOW ORDER, so
    outputs are bit-identical for any worker count. In-flight scans are
    bounded (a small multiple of the worker count), so host memory stays
    flat: O(workers) arenas + O(budget) scanned updates + O(depth) device
    buffers, never O(corpus). The scan workers are PURE functions of their
    window — all shared state (stats, dictionary, device stream) is
    touched only here, which is also what makes teardown safe: an orphaned
    scan can finish into the void without racing the unwound stream.

    The FOLD fans out too (ISSUE 9 tentpole): with a ShardedDictionary the
    consumer becomes a ROUTER — the native scan returns each window
    pre-partitioned by key-hash shard (one contiguous slice per shard),
    the router hands shard s its slice over a bounded queue, and S fold
    threads (each the sole owner of one shard dictionary) fold in window
    order. The device merge stream is scattered back to EXACT scan order
    first, so merges, evictions and therefore outputs and spill totals are
    bit-identical for every (host_map_workers, fold_shards) combination —
    the same contract the scan fan-out holds for worker counts."""
    from mapreduce_rust_tpu.native import host as native_host
    from mapreduce_rust_tpu.native.host import (
        scan_count_raw,
        scan_count_sharded_raw,
    )

    from mapreduce_rust_tpu.runtime.lineage import active_ledger, chunk_digest

    # The closures below bind the engine's set-up (device, planes, pool),
    # made after them in one timed block right before the stream loop.
    fold: "_FoldShardPlane | None" = None  # started last in that block —
    # device setup can raise, and fold threads started earlier would
    # leak, blocked forever on q.get()
    lineage_range = app.partition_mode == "range"

    def lineage_record(doc_id, lin, keys, mask) -> None:
        if lin is None:
            return
        dg, nb = lin
        ledger.record_chunk(
            doc_id, nb, dg,
            parts=_routed_parts(keys, mask, cfg.reduce_n, lineage_range),
        )

    def scan_window(item):
        # PURE: reads its window, returns its result + its own duration.
        # No shared-state writes off the consumer thread — N of these run
        # concurrently, and an abandoned one (exception teardown) cannot
        # mutate stats after the stream has unwound.
        doc_id, window = item
        t0 = time.perf_counter()
        with trace_span("host_map.scan", doc=doc_id, bytes=int(window.size)):
            if fold is not None:
                # Sharded fold: the native kernel pre-partitions the scan
                # result by key-hash shard in the same fused pass.
                res = scan_count_sharded_raw(window, fold.n)
                out = (
                    (doc_id, "raw_sharded", res) if res is not None
                    else (doc_id, "py", _py_scan_count(window))
                )
            else:
                res = scan_count_raw(window)
                out = (
                    (doc_id, "raw", res) if res is not None
                    else (doc_id, "py", _py_scan_count(window))
                )
            # Digest AFTER the scan: the scan just faulted every window
            # page in, so the sampled blake2b reads hot memory instead of
            # paying the memmap's cold-page latency itself.
            lin = (
                (chunk_digest(window), int(window.size))
                if ledger is not None else None
            )
        return (*out, lin, time.perf_counter() - t0)

    def consume(result) -> None:
        doc_id, kind, res, lin, scan_s = result
        stats.host_map_s += scan_s  # aggregate scan seconds across workers
        # Per-window scan distribution: a high-cardinality window shows up
        # as a p99 tail here long before it moves the aggregate (ISSUE 5).
        stats.record_hist("host_map.scan_s", scan_s)
        t_glue = time.perf_counter()
        stall0 = fold.stall_s if fold is not None else 0.0
        dstall0 = dispatch.stall_s
        dwait0 = dispatch.device_wait_s
        with trace_span("host_glue"):
            stats.chunks += 1
            if kind == "raw_sharded":
                # Sharded fold (ISSUE 9): route each shard its
                # pre-partitioned slice — O(shards) router work, the fold
                # threads do the word-level folding. The scan-order
                # scatter-back for the device merge moved to the dispatch
                # plane (ISSUE 13): the router hands the grouped arrays +
                # permutation over and is done in O(1).
                raw, ends, keys, counts, pos, shard_counts = res
                mask = app.host_mask(keys)  # grouped rows; per-row exact
                lineage_record(doc_id_offset + doc_id, lin, keys, mask)
                fold.route_raw(raw, ends, keys, shard_counts, mask)
                dispatch.submit(
                    (doc_id_offset + doc_id, "sharded", keys, counts, pos,
                     mask)
                )
            elif kind == "raw":
                raw, ends, keys, counts = res
                mask = app.host_mask(keys)
                lineage_record(doc_id_offset + doc_id, lin, keys, mask)
                fold_scan_into_dictionary(dictionary, mask, "raw", (raw, ends, keys))
                dispatch.submit(
                    (doc_id_offset + doc_id, "flat", keys, counts, None,
                     mask)
                )
            else:
                words, keys, counts = res
                mask = app.host_mask(keys)
                lineage_record(doc_id_offset + doc_id, lin, keys, mask)
                if fold is not None:
                    # Python-fallback scan has no pre-partitioning: the
                    # whole (read-only) result fans out and each shard
                    # thread selects its own keys.
                    fold.route_list(words, keys, mask)
                else:
                    fold_scan_into_dictionary(dictionary, mask, "list", (words, keys))
                dispatch.submit(
                    (doc_id_offset + doc_id, "flat", keys, counts, None,
                     mask)
                )
        # Glue accounting: time the router spent BLOCKED on full shard or
        # dispatch queues is backpressure (fold_stall_s /
        # dispatch_stall_s), not glue — subtracted so glue keeps meaning
        # "router's own work". In SYNC dispatch mode the inline dispatch
        # runs inside the glue span exactly as PR 10 booked it (that is
        # the A/B: sync shows the device hops in glue, async doesn't) —
        # only the drain's blocking readback is subtracted, which
        # device_wait_s already owns.
        glue_dt = time.perf_counter() - t_glue
        if fold is not None:
            glue_dt = max(glue_dt - (fold.stall_s - stall0), 0.0)
        if dispatch.sync:
            glue_dt = max(
                glue_dt - (dispatch.device_wait_s - dwait0), 0.0
            )
        else:
            glue_dt = max(glue_dt - (dispatch.stall_s - dstall0), 0.0)
        stats.host_glue_s += glue_dt
        stats.record_hist("host_map.glue_s", glue_dt)
        if fold is not None:
            # Publish the running fold totals per window (router thread):
            # the plane's tallies are plane-local until collect(), and the
            # live ring / renewal-envelope / streaming-doctor series would
            # otherwise read 0 for the whole run — a fold-bound job must
            # name host-fold LIVE, not just post-mortem. Reading the fold
            # threads' float cells is benign (slightly stale at worst);
            # collect() writes the exact finals at teardown.
            stats.fold_s = sum(fold.fold_s)
            stats.fold_stall_s = fold.stall_s
        # Running dispatch totals, same contract (ISSUE 13): a
        # dispatch-bound job must name merge-dispatch in the live ring.
        dispatch.publish_live(stats)
        # Running spill totals, same live-publication contract as fold_s:
        # a spill-bound job must name "spill" in the live ring, not just
        # in the post-mortem manifest (ISSUE 11).
        _publish_spill_live(stats, dictionary, acc)
        maybe_snapshot()  # flight-recorder tick: per window, consumer thread
        metrics_tick()    # live-metrics sampler, same piggyback contract

    from concurrent.futures import ThreadPoolExecutor

    inflight: collections.deque = collections.deque()

    def next_result():
        fut = inflight.popleft()
        t0 = time.perf_counter()
        with trace_span("host_map.stall"):
            res = fut.result()
        dt = time.perf_counter() - t0
        stats.scan_wait_s += dt
        stats.record_hist("host_map.stall_s", dt)
        trace_counter("host_map.inflight", scans=len(inflight),
                      merges=len(dispatch.pending))  # benign-stale len read
        return res

    with stats.timed("job_setup_s", "engine.setup"):
        enable_compilation_cache(cfg.compilation_cache_dir)
        device = select_device(cfg.device)
        workers = cfg.effective_host_map_workers()
        stats.host_map_workers = workers
        fold_n = (
            dictionary.n_shards if isinstance(dictionary, ShardedDictionary) else 1
        )
        stats.fold_shards = fold_n
        # The dispatch plane owns the device state, the pending
        # merges and their drain: the router never books a device hop.
        dispatch = _DispatchPlane(cfg, app, stats, acc, dictionary, device)
        # Provenance: digest each window on ITS scan thread (the
        # bytes are hot there), record on the consumer — in window order, so
        # the ledger is identical for any (workers, shards) combination.
        ledger = active_ledger()
        # In-flight budget: each submitted-but-unconsumed scan pins one
        # memmap window plus (once done) its compacted result, so
        # 2×workers + 2 keeps every worker busy while the consumer works
        # through the ordered head — deep enough to ride out a slow
        # (high-cardinality) window, shallow enough that memory stays flat.
        budget = 2 * workers + 2
        pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="mr/scan")
        if fold_n > 1:
            # Started LAST: everything that can raise during setup (device
            # selection/state allocation, pool creation) is behind us, and
            # the very next statement is the try whose except/finally owns
            # the plane's teardown — no window where an exception strands
            # S fold threads on q.get(). The dispatch plane started earlier
            # (its ctor allocates device state), so a fold ctor failure
            # must unwind it.
            try:
                fold = _FoldShardPlane(cfg, stats, dictionary.shards)
            except BaseException:
                dispatch.abort()
                raise
    try:
        for item in _iter_windows(cfg, inputs, stats):
            inflight.append(pool.submit(scan_window, item))
            if len(inflight) >= budget:
                consume(next_result())
        while inflight:
            consume(next_result())
        stats.host_arena_bytes = native_host.arena_bytes()
        if fold is not None:
            # Teardown ORDER (ISSUE 9 satellite): the router is fully
            # drained (every scan result routed above), THEN the fold
            # threads flush and join, THEN the dispatch plane flushes its
            # staging buffer + drains the device merges — each stage's
            # producers are gone before it stops. A fold error recorded
            # mid-stream surfaces here (or at the route that first
            # observed the poison).
            fold.finish()
        dispatch.finish()
    except BaseException:
        if fold is not None:
            fold.abort()
        dispatch.abort()
        raise
    finally:
        if fold is not None:
            fold.collect(stats)  # threads joined by finish()/abort()
        dispatch.collect(stats)  # same doctrine: joined before collect
        # cancel_futures + wait (the old wait=False shutdown abandoned an
        # in-flight scan on exception: the orphaned future kept its memmap
        # window alive past the stream's unwind — ISSUE 2 satellite).
        # Queued futures cancel; the ≤ workers running scans finish their
        # pure work and are reaped before the stream frame exits.
        pool.shutdown(wait=True, cancel_futures=True)
    with stats.timed("state_readback_s", "state.readback"):
        acc.add_batch(dispatch.state)


def _ckpt_paths(cfg: Config) -> tuple[str, str]:
    return (
        os.path.join(cfg.work_dir, "driver.ckpt.npz"),
        os.path.join(cfg.work_dir, "driver.ckpt.dict"),
    )


def _job_fingerprint(cfg: Config, app: App, inputs, d: int) -> str:
    """Ties a checkpoint to (inputs, app, every shape-determining knob): a
    mismatch on resume is silently ignored, never trusted."""
    import hashlib

    h = hashlib.sha256()
    for p in inputs:
        st = os.stat(p)
        h.update(f"{p}:{st.st_size}:{st.st_mtime_ns};".encode())
    # state-v2: a checkpoint from the validity-only-clamp era can hold
    # mid-array SENTINEL holes, which the front-packed state contract
    # (ops/groupby.merge_batches) excludes — reject it. state-v3: chunks
    # pack documents, so an older checkpoint's group count means other
    # chunks.
    h.update(
        f"state-v3:{app.name}:{app.combine_op}:{cfg.chunk_bytes}:{d}:"
        f"{cfg.effective_partial_capacity()}:{cfg.merge_capacity}".encode()
    )
    return h.hexdigest()


def _write_ckpt(cfg: Config, fingerprint: str, state: KVBatch, groups_done: int,
                acc, dictionary, stats) -> None:
    """Atomic driver checkpoint: device state + host spill accumulator +
    progress in one npz (the commit point), dictionary beside it. The
    dictionary file renames FIRST: its content only ever grows, so a
    newer-than-npz dictionary is a superset — safe — while the npz commit
    guarantees a complete dictionary exists. This is the single-process
    mesh driver's equivalent of the control plane's spill-file checkpoints
    + fingerprinted journal (coordinator/server.py, worker/runtime.py)."""
    npz_path, dict_path = _ckpt_paths(cfg)
    os.makedirs(cfg.work_dir, exist_ok=True)
    tmp_d = dict_path + f".{os.getpid()}.tmp"
    dictionary.save(tmp_d)
    os.replace(tmp_d, dict_path)
    k1, k2, value, valid = (np.asarray(x) for x in jax.device_get(tuple(state)))
    acc_keys, acc_vals = acc.snapshot()
    tmp_n = npz_path + f".{os.getpid()}.tmp"
    with open(tmp_n, "wb") as f:
        np.savez(
            f,
            fingerprint=np.frombuffer(fingerprint.encode(), dtype=np.uint8),
            k1=k1, k2=k2, value=value, valid=valid,
            groups_done=np.int64(groups_done),
            acc_keys=acc_keys, acc_vals=acc_vals,
            spill_events=np.int64(stats.spill_events),
            spilled_keys=np.int64(stats.spilled_keys),
        )
    os.replace(tmp_n, npz_path)
    log.info("checkpoint: %d groups done", groups_done)


def _load_ckpt(cfg: Config, fingerprint: str):
    """(state_arrays, groups_done, acc_keys, acc_vals, spill_events,
    spilled_keys, dict_path) or None (absent / torn / different job)."""
    npz_path, dict_path = _ckpt_paths(cfg)
    if not (os.path.exists(npz_path) and os.path.exists(dict_path)):
        return None
    try:
        with np.load(npz_path) as z:
            if bytes(z["fingerprint"]).decode() != fingerprint:
                log.warning("checkpoint fingerprint mismatch — starting fresh")
                return None
            return (
                KVBatch(z["k1"], z["k2"], z["value"], z["valid"]),
                int(z["groups_done"]),
                z["acc_keys"], z["acc_vals"],
                int(z["spill_events"]), int(z["spilled_keys"]),
                dict_path,
            )
    except (OSError, ValueError, KeyError, BadZipFile) as e:
        log.warning("unreadable checkpoint (%s) — starting fresh", e)
        return None


def _stream_multihost(cfg: Config, app: App, inputs, stats, acc, dictionary) -> None:
    """The mesh pipeline over a MULTI-PROCESS (jax.distributed) cluster —
    SURVEY.md §5's comm-backend row closed end-to-end: control stays on the
    coordinator's RPC plane, data rides XLA collectives over ICI/DCN, and
    the shared filesystem carries only egress artifacts (dictionaries and
    partition files), exactly the role it plays for the reference
    (src/mr/worker.rs:117-140) and for this framework's worker spills.

    Per process: ingest ONLY the inputs assigned to it (round-robin by
    global doc id), feed its local chips' rows of each global group via
    make_array_from_process_local_data, and run the same SPMD step programs
    every other process runs. Per-group decisions (replay? continue?) come
    back as psum-REPLICATED flags so every process agrees without any host
    being able to see the whole array. Rounds are lockstep: a process whose
    inputs are exhausted keeps contributing space-padded groups until the
    replicated have-data count reaches zero. At the end each process folds
    only its ADDRESSABLE state/spill shards (its hash classes), publishes
    its dictionary shard, and merges everyone's — so any process can print
    words whose bytes were only ever read by another host."""
    from mapreduce_rust_tpu.parallel.shuffle import (
        AXIS,
        default_bucket_cap,
        local_batch,
        local_rows,
        make_mesh,
        make_mh_shuffle_step_fns,
        make_round_fn,
        sharded_empty_state,
        wire_bytes_per_round,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    if cfg.checkpoint_every_groups or cfg.resume or cfg.sharded_stream:
        raise ValueError(
            "checkpoint/resume and sharded_stream are single-process features"
        )
    enable_compilation_cache(cfg.compilation_cache_dir)
    pid, nproc = jax.process_index(), jax.process_count()
    mesh = make_mesh(cfg.mesh_shape, select_device(cfg.device).platform)
    d = mesh.devices.size
    d_local = len([dev for dev in mesh.devices.ravel() if dev.process_index == pid])
    if d_local == 0:
        raise RuntimeError("this process owns no devices of the mesh")
    u_cap = cfg.effective_partial_capacity()
    bucket_cap = default_bucket_cap(u_cap, d, cfg.bucket_capacity_factor)
    fast = make_mh_shuffle_step_fns(app, u_cap, bucket_cap, mesh)
    round_fn = make_round_fn(mesh)
    tiers: dict[str, tuple] = {}

    state = sharded_empty_state(mesh, max(cfg.merge_capacity // d, 16))
    in_shard = NamedSharding(mesh, P(AXIS))
    flag_shard = NamedSharding(mesh, P(AXIS))

    # Inputs round-robin by GLOBAL doc id, so inverted_index doc ids match
    # a single-process run over the same sorted listing.
    my_inputs = [(i, p) for i, p in enumerate(inputs) if i % nproc == pid]
    ingest = _IngestStream(
        cfg, [p for _i, p in my_inputs], stats, dictionary,
        doc_ids=[i for i, _p in my_inputs], host_mask=app.host_mask,
        lineage_range=app.partition_mode == "range",
    )

    def to_global(local_np: np.ndarray, global_shape):
        return jax.make_array_from_process_local_data(
            in_shard, local_np, global_shape=global_shape
        )

    def fold_local_spill(ev_local: np.ndarray, evicted) -> None:
        n = int(ev_local.sum())
        if n > 0:
            stats.spill_events += 1
            stats.spilled_keys += n
            acc.add_batch(local_batch(evicted))

    def run_round(chunks_np: np.ndarray, docs_np: np.ndarray, have: int) -> bool:
        nonlocal state
        chunks_g = to_global(chunks_np, (d, cfg.chunk_bytes))
        docs_g = to_global(docs_np, (d, docs_np.shape[1]))
        stats.mesh_rounds += 1
        stats.shuffle_wire_bytes += wire_bytes_per_round(d, bucket_cap)
        with _a2a_span(stats, round=stats.mesh_rounds, tier="fast",
                       wire_bytes=wire_bytes_per_round(d, bucket_cap)):
            local, bad_p, bad_b, _live = fast[0](chunks_g, docs_g)
            state, evicted, ev_counts = fast[1](state, local)
            flags = round_fn(
                jax.make_array_from_process_local_data(
                    flag_shard, np.full(d_local, have, dtype=np.int32), global_shape=(d,)
                )
            )
        # ONE batched fetch per round: the replicated flags (any local
        # shard holds the global value) AND this process's spill counts —
        # every separate blocking read is a full round trip.
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=1):
            got = jax.device_get(
                [x.addressable_shards[0].data for x in (bad_p, bad_b, flags)]
                + [s.data for s in ev_counts.addressable_shards]
            )
        dt = time.perf_counter() - t0
        stats.device_wait_s += dt
        stats.record_hist("device.drain_s", dt)
        bad_p_l, bad_b_l, flags_l = got[:3]
        ev_local = np.concatenate([np.asarray(x).reshape(-1) for x in got[3:]])
        bad_p_n = int(np.asarray(bad_p_l)[0])
        bad_b_n = int(np.asarray(bad_b_l)[0])
        if bad_p_n > 0 or bad_b_n > 0:
            if bad_p_n > 0:
                stats.partial_overflow_replays += 1
                if "full" not in tiers:
                    tiers["full"] = make_mh_shuffle_step_fns(
                        app, cfg.chunk_bytes, cfg.chunk_bytes, mesh
                    )
                fns, tier_cap = tiers["full"], cfg.chunk_bytes
            else:
                stats.bucket_skew_replays += 1
                if "skew" not in tiers:
                    tiers["skew"] = make_mh_shuffle_step_fns(app, u_cap, u_cap, mesh)
                fns, tier_cap = tiers["skew"], u_cap
            stats.mesh_rounds += 1
            stats.shuffle_wire_bytes += wire_bytes_per_round(d, tier_cap)
            with _a2a_span(stats, round=stats.mesh_rounds, tier="replay",
                           wire_bytes=wire_bytes_per_round(d, tier_cap)):
                local, _p, _b, _live = fns[0](chunks_g, docs_g)
                state, evicted2, ev2 = fns[1](state, local)
            # Fetch + fold outside the a2a block (rare: own fetch) — the
            # blocking shard read must not inflate all_to_all_s.
            t0 = time.perf_counter()
            with trace_span("device.drain", steps=1):
                ev2_local = local_rows(ev2)
            stats.device_wait_s += time.perf_counter() - t0
            fold_local_spill(ev2_local, evicted2)
        fold_local_spill(ev_local, evicted)
        return int(np.asarray(flags_l)[0]) > 0

    it = iter(ingest)
    exhausted = False
    try:
        while True:
            rows: list[np.ndarray] = []
            docs: list[np.ndarray] = []
            while not exhausted and len(rows) < d_local:
                try:
                    chunk = next(it)
                    rows.append(chunk.data)
                    docs.append(chunk.doc_table)
                    stats.chunks_dispatched += 1
                    stats.chunk_payload_bytes += chunk.nbytes
                except StopIteration:
                    exhausted = True
            have = 1 if rows else 0
            while len(rows) < d_local:  # pad my contribution with spaces
                rows.append(np.full(cfg.chunk_bytes, 0x20, dtype=np.uint8))
                docs.append(doc_table(0))
            any_data = run_round(np.stack(rows), np.stack(docs), have)
            if not any_data:
                break
    except BaseException:
        ingest.close(abort=True)
        raise
    ingest.close()
    with stats.timed("state_readback_s", "state.readback"):
        acc.add_batch(local_batch(state))

    # Dictionary exchange over the shared work dir: each process publishes
    # its shard + a done marker, then merges everyone's (a chip may own
    # keys whose word bytes were only read by another process). Filenames
    # embed the job fingerprint so a leftover marker from a DIFFERENT job
    # in the same work dir can never satisfy — or break — the barrier;
    # a leftover from the SAME job is the same corpus, hence the same
    # shard content. (`clean` removes dict-* including markers.)
    # nproc is part of the name: same inputs + same d under a different
    # process split produce different shards, and stale ones must not
    # satisfy (or poison) the barrier.
    fp = f"{_job_fingerprint(cfg, app, inputs, d)[:16]}-n{nproc}"

    def shard_path(proc: int) -> str:
        return os.path.join(cfg.work_dir, f"dict-proc-{proc}-{fp}.txt")

    os.makedirs(cfg.work_dir, exist_ok=True)
    tmp = shard_path(pid) + ".tmp"
    dictionary.save(tmp)
    os.replace(tmp, shard_path(pid))
    open(shard_path(pid) + ".done", "w").close()
    _await_shard_files(shard_path, nproc, cfg.multihost_barrier_timeout_s)
    for other in range(nproc):
        if other != pid:
            dictionary.merge(Dictionary.load(shard_path(other)))


def _await_shard_files(shard_path, nproc: int, timeout_s: float) -> None:
    """The multihost dictionary-exchange barrier: wait for every process's
    published shard + done marker. A peer that died before publishing
    cannot be waited out — its chips' hash classes died with it — so the
    only honest outcome is a loud, prompt failure naming every missing
    rank (the timeout is a knob: slow shared filesystems legitimately
    need more than the default)."""
    deadline = time.monotonic() + timeout_s  # immune to wall-clock steps
    waiting = set(range(nproc))
    while waiting:
        waiting -= {
            other for other in waiting
            if os.path.exists(shard_path(other) + ".done")
            and os.path.exists(shard_path(other))
        }
        if not waiting:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"dictionary shards from process(es) {sorted(waiting)} never "
                f"arrived within {timeout_s:.0f}s (multihost_barrier_timeout_s)"
                " — peer death or a stalled shared work dir; results would be"
                " missing those hash classes, so the job fails instead."
                " Re-run the job."
            )
        time.sleep(0.05)


def _finish_mesh_state(app: App, mesh, state, stats, acc) -> None:
    """Fold the final sharded state into the host accumulator. Top-k apps
    fetch only per-chip candidates over ICI (parallel/topk.py) when that
    is provably exact: no spills (a spilled key's device value is partial)
    and no value tie at any chip's k boundary (the word tie-break needs
    bytes the device doesn't have)."""
    from mapreduce_rust_tpu.parallel.shuffle import shard_fill_counts

    # Per-chip final distinct-key counts: the hash-class skew signal the
    # doctor scores, and what shows each key on exactly one shard. One
    # readback at finalize, off the stream; a failed readback fails the job.
    stats.mesh_shard_rows = shard_fill_counts(state)
    k = app.device_select_k
    with stats.timed("state_readback_s", "state.readback"):
        if k and stats.spill_events == 0:
            from mapreduce_rust_tpu.parallel.topk import topk_candidates

            res = topk_candidates(mesh, state, k)
            if res is not None:
                keys, vals = res
                acc.add(keys, vals)
                log.info("device top-%d selection: %d candidates fetched", k, len(vals))
                return
            log.info("device top-%d selection ambiguous (value tie at boundary) "
                     "— falling back to full state fetch", k)
        acc.add_batch(state)


def _stream_sharded(cfg: Config, app: App, inputs, stats, acc, dictionary) -> None:
    """Sequence-parallel mesh ingestion: each normalized window rides the
    mesh as ONE contiguous byte stream cut at arbitrary — mid-word, even
    mid-UTF-8-sequence — equal offsets, one shard per chip. A ppermute
    halo exchange (parallel/halo.py) hashes straddling tokens exactly once
    (owned by the chip where the token ENDS), then the records take the
    standard combine → bucket scatter → all_to_all → merge pipeline. This
    is SURVEY.md §5's long-context row made end-to-end: the reference's
    sequence ceiling is one whole file in one String per task
    (src/mr/worker.rs:65-77); here no chip ever needs a token-aligned —
    or even character-aligned — view of the stream.

    Tokens longer than the halo (cfg.max_word_len) may hash truncated;
    they are DETECTED on device and counted in stats.halo_truncations,
    this framework's standard posture for capacity faults."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mapreduce_rust_tpu.core.normalize import normalize_unicode
    from mapreduce_rust_tpu.native.host import normalize_native
    from mapreduce_rust_tpu.parallel.halo import make_sharded_tokenizer, shard_stream
    from mapreduce_rust_tpu.parallel.shuffle import (
        AXIS,
        default_bucket_cap,
        make_kv_shuffle_step_fns,
        make_mesh,
        make_shuffle_step_fns,
        sharded_empty_state,
        wire_bytes_per_round,
    )

    if cfg.checkpoint_every_groups or cfg.resume:
        raise ValueError(
            "checkpoint/resume is not supported in sharded-stream mode "
            "(use the chunked mesh path, or run without sharded_stream)"
        )
    enable_compilation_cache(cfg.compilation_cache_dir)
    mesh = make_mesh(cfg.mesh_shape, select_device(cfg.device).platform)
    on_tpu = mesh.devices.ravel()[0].platform == "tpu"
    d = mesh.devices.size
    u_cap = cfg.effective_partial_capacity()
    bucket_cap = default_bucket_cap(u_cap, d, cfg.bucket_capacity_factor)
    tokenize = make_sharded_tokenizer(mesh, halo=cfg.max_word_len)
    kv_shuffle = make_kv_shuffle_step_fns(app, u_cap, bucket_cap, mesh)
    merge = make_shuffle_step_fns(app, u_cap, bucket_cap, mesh)[1]
    wide: dict = {}  # lazily-compiled full-width replay tier

    state = sharded_empty_state(mesh, max(cfg.merge_capacity // d, 16))
    in_shard = NamedSharding(mesh, P(AXIS))
    rep = NamedSharding(mesh, P(AXIS))
    depth = max(max(cfg.pipeline_depth, 1) // d, 4)
    pending: collections.deque = collections.deque()
    shard_bytes = max(cfg.chunk_bytes, 2 * cfg.max_word_len + 8)

    def tables(doc_id: int) -> np.ndarray:
        # A window is one document: every chip's share of it starts there.
        return np.stack([doc_table(doc_id)] * d)

    def replay_group(group_bytes: bytes, doc_id: int, p_n: int) -> None:
        # The fast path clamped the whole group to empty on device, so
        # re-run it through the full-width tier (u_cap = the whole token
        # window, bucket_cap = u_cap — overflow structurally impossible)
        # and merge that. Exact, never silent, like every capacity fault.
        nonlocal state
        stats.partial_overflow_replays += int(p_n > 0)
        stats.bucket_skew_replays += int(p_n == 0)
        if not wide:
            w_cap = cfg.max_word_len + shard_bytes + 1  # the full window
            wide["fns"] = make_kv_shuffle_step_fns(app, w_cap, w_cap, mesh)
            wide["merge"] = make_shuffle_step_fns(app, w_cap, w_cap, mesh)[1]
        shards = jax.device_put(shard_stream(group_bytes, mesh, pad=shard_bytes), in_shard)
        docs = jax.device_put(tables(doc_id), rep)
        stats.mesh_rounds += 1
        stats.shuffle_wire_bytes += wire_bytes_per_round(
            d, cfg.max_word_len + shard_bytes + 1
        )
        with _a2a_span(stats, round=stats.mesh_rounds, tier="replay",
                       wire_bytes=wire_bytes_per_round(
                           d, cfg.max_word_len + shard_bytes + 1)):
            kv, _trunc = tokenize(shards)
            local, _p, _b = wide["fns"](kv, docs)
            state, evicted, ev_counts = wide["merge"](state, local)
        # Readback + spill fold outside the a2a block — see _stream_mesh
        # replay_group: all_to_all_s must stay interconnect-attributable.
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=1):
            ev_n = int(np.asarray(jax.device_get(ev_counts)).sum())
        stats.device_wait_s += time.perf_counter() - t0
        if ev_n > 0:
            stats.spill_events += 1
            stats.spilled_keys += ev_n
            acc.add_batch(evicted)

    def drain(n: int) -> None:
        if n <= 0:
            return
        batch = [pending.popleft() for _ in range(n)]
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=n):
            flat = jax.device_get([x for row in batch for x in row[:4]])
        dt = time.perf_counter() - t0
        stats.device_wait_s += dt
        stats.record_hist("device.drain_s", dt)
        _sample_device_memory(stats)
        for row, trunc, p_ovf, b_ovf, ev in zip(
            batch, flat[::4], flat[1::4], flat[2::4], flat[3::4]
        ):
            stats.halo_truncations += int(np.asarray(trunc).sum())
            ev_n = int(np.asarray(ev).sum())
            if ev_n > 0:
                stats.spill_events += 1
                stats.spilled_keys += ev_n
                with trace_span("spill", keys=ev_n):
                    acc.add_batch(row[4])
            p_n = int(np.asarray(p_ovf).sum())
            b_n = int(np.asarray(b_ovf).sum())
            if p_n or b_n:
                replay_group(row[5], row[6], p_n)

    from mapreduce_rust_tpu.runtime.lineage import active_ledger, chunk_digest

    ledger = active_ledger()
    lineage_range = app.partition_mode == "range"
    for doc_id, window in _iter_windows(cfg, inputs, stats):
        stats.chunks += 1
        raw = bytes(window)
        norm = normalize_native(raw)
        if norm is None:
            norm = normalize_unicode(raw)
        kind, *scan = _scan_payload(norm)
        keys = scan_keys(kind, scan)
        mask = app.host_mask(keys)
        fold_scan_into_dictionary(dictionary, mask, kind, scan)
        if ledger is not None:
            # Digest the RAW window (pre-normalization) — same bytes the
            # other engines hash, so corpus digests agree across engines.
            ledger.record_chunk(
                doc_id, len(raw), chunk_digest(raw),
                parts=_routed_parts(keys, mask, cfg.reduce_n, lineage_range),
            )
        # Group seams are host-side cuts like window seams, so they align
        # to whitespace — a token split THERE would fragment into keys no
        # dictionary entry matches. The arbitrary (mid-word) cuts this
        # mode demonstrates are the D-1 chip seams inside each group,
        # which the halo exchange repairs on device.
        from mapreduce_rust_tpu.runtime.chunker import _ws_cut

        off = 0
        while off < len(norm):
            end = min(off + d * shard_bytes, len(norm))
            if end < len(norm):
                probe = norm[max(off, end - cfg.max_word_len - 1) : end]
                o, forced = _ws_cut(probe, 0, len(probe))
                if forced:
                    stats.forced_cuts += 1
                else:
                    end -= len(probe) - o
            group = norm[off:end]
            off = end
            stats.mesh_rounds += 1
            stats.scan_tokenize_rounds += int(on_tpu)
            stats.shuffle_wire_bytes += wire_bytes_per_round(d, bucket_cap)
            with _a2a_span(stats, round=stats.mesh_rounds, tier="fast",
                           wire_bytes=wire_bytes_per_round(d, bucket_cap)):
                shards = jax.device_put(
                    shard_stream(group, mesh, pad=shard_bytes), in_shard
                )
                docs = jax.device_put(tables(doc_id), rep)
                kv, trunc = tokenize(shards)
                local, p_ovf, b_ovf = kv_shuffle(kv, docs)
                state, evicted, ev_counts = merge(state, local)
                pending.append((trunc, p_ovf, b_ovf, ev_counts, evicted, group, doc_id))
            if len(pending) >= 2 * depth:
                drain(depth)
    drain(len(pending))
    _finish_mesh_state(app, mesh, state, stats, acc)


def _stream_mesh(cfg: Config, app: App, inputs, stats, acc, dictionary) -> None:
    """Group-of-D-chunks pipeline over the 1-D mesh (parallel/shuffle.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mapreduce_rust_tpu.parallel.shuffle import (
        AXIS,
        default_bucket_cap,
        make_mesh,
        make_shuffle_step_fns,
        sharded_empty_state,
        wire_bytes_per_round,
    )

    enable_compilation_cache(cfg.compilation_cache_dir)
    mesh = make_mesh(cfg.mesh_shape, select_device(cfg.device).platform)
    d = mesh.devices.size
    u_cap = cfg.effective_partial_capacity()
    bucket_cap = default_bucket_cap(u_cap, d, cfg.bucket_capacity_factor)
    fast = make_shuffle_step_fns(app, u_cap, bucket_cap, mesh)
    tiers: dict[str, tuple] = {}  # lazily-compiled exact replay paths

    state = sharded_empty_state(mesh, max(cfg.merge_capacity // d, 16))
    in_shard = NamedSharding(mesh, P(AXIS))
    # Each in-flight group pins d chunk-sized host arrays for the rare
    # replay, so scale the window down by d to keep pending memory at the
    # same O(depth × chunk_bytes) the single-chip path pays.
    depth = max(max(cfg.pipeline_depth, 1) // d, 4)
    pending: collections.deque = collections.deque()

    fingerprint = _job_fingerprint(cfg, app, inputs, d)
    groups_done = 0
    skip_chunks = 0
    if cfg.resume:
        ck = _load_ckpt(cfg, fingerprint)
        if ck is not None:
            st_host, groups_done, ak, av, sev, skk, dict_path = ck
            state = jax.device_put(st_host, NamedSharding(mesh, P(AXIS, None)))
            skip_chunks = groups_done * d
            acc.add(ak, av)
            dictionary.merge(Dictionary.load(dict_path))
            stats.spill_events, stats.spilled_keys = sev, skk
            log.info("resumed from checkpoint: %d groups already merged", groups_done)

    def replay_group(chunks_host, docs_host, p_ovf_n: int) -> None:
        # The fast path clamped this whole group to empty on device
        # (make_shuffle_step_fns psum clamp), so re-run it through a tier
        # wide enough that the overflow cannot recur, and merge that.
        nonlocal state
        chunks_dev = jax.device_put(chunks_host, in_shard)
        docs_dev = jax.device_put(docs_host, in_shard)
        if p_ovf_n > 0:
            # A chunk had more distinct keys than u_cap: widest tier.
            stats.partial_overflow_replays += 1
            if "full" not in tiers:
                tiers["full"] = make_shuffle_step_fns(
                    app, cfg.chunk_bytes, cfg.chunk_bytes, mesh
                )
            fns, tier_cap = tiers["full"], cfg.chunk_bytes
        else:
            # Bucket skew: bucket_cap=u_cap makes overflow impossible.
            stats.bucket_skew_replays += 1
            if "skew" not in tiers:
                tiers["skew"] = make_shuffle_step_fns(app, u_cap, u_cap, mesh)
            fns, tier_cap = tiers["skew"], u_cap
        stats.mesh_rounds += 1
        stats.shuffle_wire_bytes += wire_bytes_per_round(d, tier_cap)
        with _a2a_span(stats, round=stats.mesh_rounds, tier="replay",
                       wire_bytes=wire_bytes_per_round(d, tier_cap)):
            local, _, _, live = fns[0](chunks_dev, docs_dev)
            state, evicted, ev_counts = fns[1](state, local)
        # Blocking readback + spill fold OUTSIDE the a2a block: they are
        # device-wait/host work, and inside they would inflate all_to_all_s
        # — the ICI numerator — with non-interconnect time.
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=1):
            ev_arr, live_arr = jax.device_get([ev_counts, live])
        stats.device_wait_s += time.perf_counter() - t0
        ev_n = int(np.asarray(ev_arr).sum())
        stats.shuffle_live_rows += int(np.asarray(live_arr).sum())
        if ev_n > 0:
            stats.spill_events += 1
            stats.spilled_keys += ev_n
            acc.add_batch(evicted)

    def drain(n: int) -> None:
        # One batched readback per window — see _stream_single.drain.
        if n <= 0:
            return
        batch = [pending.popleft() for _ in range(n)]
        t0 = time.perf_counter()
        with trace_span("device.drain", steps=n):
            flat = jax.device_get([x for row in batch for x in row[:4]])
        dt = time.perf_counter() - t0
        stats.device_wait_s += dt
        stats.record_hist("device.drain_s", dt)
        _sample_device_memory(stats)
        for (*_flags, evicted, chunks_host, docs_host), p_arr, b_arr, e_arr, l_arr in zip(
            batch, flat[::4], flat[1::4], flat[2::4], flat[3::4]
        ):
            ev_n = int(np.asarray(e_arr).sum())
            if ev_n > 0:
                stats.spill_events += 1
                stats.spilled_keys += ev_n
                with trace_span("spill", keys=ev_n):
                    acc.add_batch(evicted)
            p_n = int(np.asarray(p_arr).sum())
            if p_n > 0 or int(np.asarray(b_arr).sum()) > 0:
                replay_group(chunks_host, docs_host, p_n)
            else:  # an overflowed group's rows count in its replay
                stats.shuffle_live_rows += int(np.asarray(l_arr).sum())

    group_chunks: list[np.ndarray] = []
    group_docs: list[np.ndarray] = []

    def submit_group() -> None:
        nonlocal state, groups_done
        with stats.timed("mesh_feed_s", "mesh.feed"):
            while len(group_chunks) < d:  # pad the tail group with space chunks
                group_chunks.append(np.full(cfg.chunk_bytes, 0x20, dtype=np.uint8))
                group_docs.append(doc_table(0))
            chunks_host = np.stack(group_chunks)
            docs_host = np.stack(group_docs)
            group_chunks.clear()
            group_docs.clear()
            chunks_dev = jax.device_put(chunks_host, in_shard)
            docs_dev = jax.device_put(docs_host, in_shard)
        stats.mesh_rounds += 1
        stats.shuffle_wire_bytes += wire_bytes_per_round(d, bucket_cap)
        with _a2a_span(stats, round=stats.mesh_rounds, tier="fast",
                       wire_bytes=wire_bytes_per_round(d, bucket_cap)):
            local, p_ovf, b_ovf, live = fast[0](chunks_dev, docs_dev)
            # Merge dispatches immediately — an overflowed group is empty on
            # device, so merging before the flags reach the host is safe.
            # Host arrays are kept for the rare replay, not device buffers.
            state, evicted, ev_counts = fast[1](state, local)
            pending.append((p_ovf, b_ovf, ev_counts, live, evicted,
                            chunks_host, docs_host))
        groups_done += 1
        if (
            cfg.checkpoint_every_groups > 0
            and groups_done % cfg.checkpoint_every_groups == 0
        ):
            drain(len(pending))  # state must reflect every submitted group
            # The dictionary must also reflect them: scan futures fold
            # lazily, and a checkpointed count whose word never made the
            # saved dictionary would resume into a permanent unknown key.
            while ingest.scans:
                ingest._fold_done(block=True)
            _write_ckpt(cfg, fingerprint, state, groups_done, acc, dictionary, stats)
        elif len(pending) >= 2 * depth:
            drain(depth)

    ingest = _IngestStream(cfg, inputs, stats, dictionary, skip_chunks=skip_chunks,
                           host_mask=app.host_mask,
                           lineage_range=app.partition_mode == "range")
    try:
        for chunk in ingest:
            group_chunks.append(chunk.data)
            group_docs.append(chunk.doc_table)
            stats.chunks_dispatched += 1
            stats.chunk_payload_bytes += chunk.nbytes
            if len(group_chunks) == d:
                submit_group()
        if group_chunks:
            submit_group()
        drain(len(pending))
    except BaseException:
        ingest.close(abort=True)
        raise
    ingest.close()
    _finish_mesh_state(app, mesh, state, stats, acc)


def _collect_spill_stats(stats: JobStats, dictionary, acc) -> None:
    """Fold the spill writers' final tallies into JobStats — run_job
    thread only, AFTER remove_runs joined the writer threads, so no
    write races exist (the fold-plane collect() doctrine). The per-run
    write_s histograms merge into one ``spill.write_s`` distribution."""
    d = dictionary.spill_stats()
    a = acc.spill_stats()
    stats.spill_s = d["write_s"] + a["write_s"]
    stats.spill_stall_s = d["stall_s"] + a["stall_s"]
    stats.spill_bytes = d["bytes"] + a["bytes"]
    for h in (d["hist"], a["hist"]):
        if h is not None and h.count:
            agg = stats.hists.get("spill.write_s")
            if agg is None:
                agg = stats.hists["spill.write_s"] = Histogram()
            agg.merge(h)


def _publish_spill_live(stats: JobStats, dictionary, acc) -> None:
    """Per-window live publication of the running spill totals (consumer/
    router thread): the writers' float cells are benign-stale at worst —
    the live metrics ring and the streaming doctor must see a spill-bound
    job DURING the run, not only post-mortem (the PR 9 fold_s pattern).
    Exact finals land in _collect_spill_stats at teardown."""
    total_w = total_st = 0.0
    total_b = 0
    seen = False
    for tier in (dictionary, acc):
        snap = tier.spill_snapshot()
        if snap is None:
            continue
        seen = True
        total_w += snap[0]
        total_st += snap[1]
        total_b += snap[2]
    if seen:
        stats.spill_s = total_w
        stats.spill_stall_s = total_st
        stats.spill_bytes = total_b


def run_job(
    cfg: Config,
    inputs: Sequence[str] | None = None,
    app: App | None = None,
    write_outputs: bool = True,
    corpus_bounds: Sequence[int] | None = None,
) -> JobResult:
    """Run one job end-to-end. Exact results on any device/mesh shape.

    With egress budgets set (Config.host_accum_budget_mb /
    dictionary_budget_words) and exceeded, finalize switches to the
    streaming merge-join egress and JobResult.table comes back EMPTY —
    the results live in the output files, whose content is identical to
    the in-RAM path's.

    Multi-corpus jobs (ISSUE 15): with ``inputs=None`` the corpora come
    from Config.corpora() (``input_dirs``) and the flat doc_id space
    concatenates their sorted listings; explicit ``inputs`` callers pass
    the matching ``corpus_bounds`` (resolve_corpora's) themselves.
    """
    t0 = time.perf_counter()
    # Sanitize-aware construction (analysis/sanitize.py): plain instances
    # unless Config.sanitize / MR_SANITIZE=1, in which case cross-thread
    # writes to stats or dictionary raise at the write site.
    from mapreduce_rust_tpu.analysis.sanitize import new_dictionary, new_job_stats

    stats = new_job_stats(cfg)
    with stats.timed("job_setup_s", "job.setup"):
        app = app or WordCount()
        if inputs is None:
            inputs, auto_bounds, _names = resolve_corpora(cfg)
            if corpus_bounds is None:
                corpus_bounds = auto_bounds
        else:
            inputs = list(inputs)
        if not inputs:
            raise ValueError("no input files")

        budgeted = cfg.host_accum_budget_mb is not None or cfg.dictionary_budget_words is not None
        if budgeted and (cfg.checkpoint_every_groups or cfg.resume or jax.process_count() > 1):
            raise ValueError(
                "egress budgets are incompatible with checkpoint/resume and "
                "multi-process runs"
            )
        if budgeted and not write_outputs:
            # Streaming egress delivers results ONLY through output files; a
            # budgeted run without them would compute everything and return
            # an empty table — silently discarding the job.
            raise ValueError("egress budgets require write_outputs=True")
        # Workload plane: bind corpus bounds and — for range apps —
        # sampler-derived splitters onto the app BEFORE anything streams. The
        # pre-pass is seeded and pure in (inputs, config), so every engine and
        # every re-execution derives identical routing; its cost lands in
        # stats.splitter_s/splitter_samples.
        from mapreduce_rust_tpu.runtime.splitter import prepare_app

        app = prepare_app(app, cfg, inputs, corpus_bounds or (), stats=stats)
        # Crash-safe run scavenging: a SIGKILLed job's
        # remove_runs never ran, so its dictrun-*/accrun-* files leak forever
        # in a shared work_dir. Reclaim orphans whose writer pid is gone (live
        # concurrent jobs keep answering kill(pid, 0), so theirs are never
        # touched); best-effort, before this job's own tiers exist.
        from mapreduce_rust_tpu.runtime.spill import scavenge_stale_runs

        scavenge_stale_runs(cfg.work_dir, logger=log)
        acc = HostAccumulator(
            app.combine_op,
            budget_bytes=(
                cfg.host_accum_budget_mb << 20
                if cfg.host_accum_budget_mb is not None else None
            ),
            spill_dir=cfg.work_dir,
            async_spill=cfg.spill_async,
        )
        # Sharded egress fold: the single-process host-map engine
        # splits the dictionary into S key-hash-disjoint shards, each owned by
        # one fold thread of _FoldShardPlane. Every other engine keeps the
        # single-dictionary fold (mesh tokenizes on device; multihost already
        # merges per-PROCESS dictionary shards; checkpoint/resume persists the
        # plain Dictionary). The word budget splits across shards so the
        # bounded-memory contract is per-process, not per-shard×S.
        fold_shards = 1
        if (cfg.map_engine == "host"
                and not (cfg.mesh_shape and cfg.mesh_shape > 1)
                and jax.process_count() == 1):
            fold_shards = cfg.effective_fold_shards()
        if fold_shards > 1:
            per_shard_budget = (
                max(1, cfg.dictionary_budget_words // fold_shards)
                if cfg.dictionary_budget_words is not None else None
            )
            dictionary = ShardedDictionary([
                new_dictionary(cfg, budget_words=per_shard_budget,
                               spill_dir=cfg.work_dir,
                               async_spill=cfg.spill_async)
                for _ in range(fold_shards)
            ])
        else:
            dictionary = new_dictionary(
                cfg, budget_words=cfg.dictionary_budget_words,
                spill_dir=cfg.work_dir, async_spill=cfg.spill_async,
            )
        # Compile instrumentation rides every run (cheap: two listeners, a
        # list append per compile); the slice below scopes the process-global
        # log to THIS run's interval.
        _install_compile_listener()
        compile_log_start = len(_COMPILE_LOG)
        tracer = start_tracing(tag="driver") if cfg.trace_path else None
        if tracer is not None:
            # Flight recorder: the stream loops tick maybe_snapshot() per
            # chunk/window, so a killed or wedged driver still leaves an
            # atomic *.partial.json that `trace merge` accepts.
            tracer.enable_flight_recorder(
                partial_path(cfg.trace_path),
                period_s=cfg.flight_record_period_s,
            )
        # Live metrics: the registry pulls JobStats aggregates into
        # the time-series ring when the SAME loops that tick the flight
        # recorder call metrics_tick() — no engine grows a second
        # instrumentation site, nothing runs per record. Serialized into the
        # manifest as stats.timeseries by build_manifest.
        registry = None
        if cfg.metrics_enabled:
            registry = start_metrics(cfg.metrics_sample_period_s,
                                     cfg.metrics_ring_points)
            registry.add_collector(jobstats_collector(stats))
            if tracer is not None:
                tracer.metrics_registry = registry  # partials keep the series
        # Sampling profiler: one thread walks sys._current_frames()
        # at ~97 Hz, collapsed stacks keyed by the mr/ plane-thread names.
        # Observational only — nothing the data plane reads is touched, so
        # outputs stay bit-identical ON vs OFF. Lands in the manifest as
        # stats.profile (build_manifest reads the still-active profiler).
        sprof = None
        if cfg.profile or profile_forced():
            from mapreduce_rust_tpu.runtime.prof import start_profiler

            sprof = start_profiler(cfg.profile_hz)
            if tracer is not None:
                tracer.profiler = sprof  # partials keep the flamegraph
                sprof.tracer = tracer    # per-plane self-time counter tracks
        # Provenance ledger: per-chunk content digests + partition
        # routing recorded from the same consumer loops that tick the flight
        # recorder. Observational only — outputs stay bit-identical ON vs
        # OFF. Lands in the manifest as stats.lineage (build_manifest reads
        # the still-active ledger) and in partials as body["lineage"].
        ledger = None
        if cfg.lineage or lineage_forced():
            from mapreduce_rust_tpu.runtime.lineage import (
                LEDGER_NAME,
                start_ledger,
            )

            os.makedirs(cfg.work_dir, exist_ok=True)
            ledger = start_ledger(os.path.join(cfg.work_dir, LEDGER_NAME),
                                  inputs=inputs, reduce_n=cfg.reduce_n)
            if tracer is not None:
                tracer.lineage = ledger  # partials keep the provenance tail
    output_files: list[str] = []
    table: dict = {}

    try:
        prof = (
            jax.profiler.trace(cfg.profile_dir)
            if cfg.profile_dir
            else contextlib.nullcontext()
        )
        # The profiler starts first, so its trace holds phase.stream too.
        with prof, stats.phase("stream"):
            if cfg.map_engine == "host" and cfg.mesh_shape and cfg.mesh_shape > 1:
                log.warning(
                    "map_engine='host' applies to the single-chip driver only; "
                    "mesh runs tokenize on device (the mesh IS the map engine)"
                )
            if jax.process_count() > 1:
                _stream_multihost(cfg, app, inputs, stats, acc, dictionary)
            elif cfg.mesh_shape and cfg.mesh_shape > 1 and cfg.sharded_stream:
                _stream_sharded(cfg, app, inputs, stats, acc, dictionary)
            elif cfg.mesh_shape and cfg.mesh_shape > 1:
                _stream_mesh(cfg, app, inputs, stats, acc, dictionary)
            elif cfg.map_engine == "host":
                _stream_host_map(cfg, app, inputs, stats, acc, dictionary)
            else:
                _stream_single(cfg, app, inputs, stats, acc, dictionary)

        streaming = (acc.has_runs or dictionary.spilled) and type(app).finalize is App.finalize
        if (acc.has_runs or dictionary.spilled) and not streaming:
            log.warning(
                "app %s overrides finalize — rehydrating spilled egress tiers "
                "into RAM (exact, but unbounded)", app.name
            )

        is_distinct = app.combine_op == "distinct"

        if streaming:
            # _stream_finalize opens its own finalize/egress phase blocks —
            # nesting both here would double-count one interval under two keys.
            output_files = _stream_finalize(
                cfg, app, stats, acc, dictionary, write_outputs
            )
        else:
            with stats.phase("finalize"), _postings_span(app, stats):
                stats.dictionary_words = len(dictionary)
                stats.hash_collisions = len(dictionary.collisions)
                lookup = dictionary.lookup
                if dictionary.spilled:
                    # Rehydrate fallback: serve point lookups from the full
                    # sorted stream (runs + RAM) materialized once.
                    full = {(k1, k2): w for _p, k1, k2, w in dictionary.iter_sorted()}
                    lookup = lambda k1, k2: full.get((k1, k2))  # noqa: E731
                if is_distinct:
                    # Grouped, looked up and formatted inside this one span.
                    parts = _posting_parts(cfg, app, _timed_fold(acc, stats),
                                           lookup, stats, table)
                else:
                    stats.distinct_keys = len(acc.table)
                    items = []
                    for key, v in acc.table.items():
                        word = lookup(*key)
                        if word is None:
                            stats.unknown_keys += 1
                            continue
                        items.append((word, v, key))
                        table[word] = v

            with stats.phase("egress"):
                if not is_distinct:
                    parts = app.finalize(items, cfg.reduce_n)
                if write_outputs:
                    os.makedirs(cfg.output_dir, exist_ok=True)
                    # Multi-process: each process emits ITS hash classes'
                    # lines under a process-suffixed name; `merge` globs them
                    # all (for top_k, App.merge_lines is the cross-process
                    # selection root).
                    suffix = f".p{jax.process_index()}" if jax.process_count() > 1 else ""
                    for r in range(cfg.reduce_n):
                        path = os.path.join(cfg.output_dir, f"mr-{r}{suffix}.txt")
                        lines = parts.get(r)
                        buf = b"\n".join(lines) + b"\n" if lines else b""
                        written = len(buf)
                        with open(path, "wb") as f:
                            f.write(buf)
                        # Per-partition output bytes: the reduce-side skew
                        # signal the doctor scores (index = partition r).
                        stats.partition_bytes.append(written)
                        if ledger is not None:
                            # Egress claim (ISSUE 20): partition r's bytes
                            # + the chunks whose routed keys contributed.
                            ledger.record_partition(r, written)
                        output_files.append(path)

        stats.wall_seconds = time.perf_counter() - t0
        log.info("job %s done: %s", app.name, stats.summary())
    finally:
        with stats.timed("job_teardown_s", "job.teardown"):
            # Failure path still gets real wall time: the manifest is written
            # even on a crash, and a 0.0-second crashed run would corrupt every
            # post-mortem throughput comparison.
            if not stats.wall_seconds:
                stats.wall_seconds = time.perf_counter() - t0
            # Fold this run's XLA compiles into the stats (count / seconds /
            # persistent-cache hit-miss split) — the doctor's compile-bound
            # attribution and the manifest's "compile" block.
            for rec in _COMPILE_LOG[compile_log_start:]:
                stats.compile_count += 1
                stats.compile_s += rec["dur_s"]
                if rec["cache"] == "hit":
                    stats.compile_cache_hits += 1
                elif rec["cache"] == "miss":
                    stats.compile_cache_misses += 1
                stats.record_hist("xla.compile_s", rec["dur_s"])
            # Spill runs are job-scoped scratch: a shared work_dir must not
            # accumulate accrun-*/dictrun-* files across jobs (or leak them on
            # a failed run). Their counts survive in the stats (and
            # manifest) as the proof the disk tiers engaged.
            stats.accum_spill_runs = acc.run_count
            stats.dict_spill_runs = dictionary.run_count
            # remove_runs closes (joins) every async spill writer, so the
            # collection below reads FINAL counters — no thread still adding.
            acc.remove_runs()
            dictionary.remove_runs()
            _collect_spill_stats(stats, dictionary, acc)
            # Packed-merge jit cache hygiene: enforce the
            # LRU bound at job teardown so a long-lived multi-job process
            # (ROADMAP item 2) holds a bounded working set of compiled merges
            # — clear_packed_fns() is the full-drop hook for embedders.
            trim_packed_fns()
            if sprof is not None:
                # Freeze sampling before the artifact flush: the profile
                # covers the job (stream/finalize/egress + spill joins), not
                # manifest serialization. The stopped profiler stays in the
                # global slot so build_manifest embeds its final aggregate.
                sprof.stop()
            if ledger is not None:
                # Seal the jsonl (end record: folded corpus content digest)
                # before the flush; the closed ledger stays in the global
                # slot so build_manifest embeds stats.lineage.
                try:
                    ledger.close()
                except Exception:
                    log.warning("lineage ledger close failed", exc_info=True)
            if tracer is not None:
                stop_tracing()
            if tracer is not None or cfg.manifest_path:
                # Written even on failure (with an "error" field): a crashed
                # run's manifest names what ran, which is the point. The whole
                # block is best-effort — a telemetry failure (including a
                # wedged distributed runtime below) must never mask the job's
                # real exception.
                import sys as _sys

                from mapreduce_rust_tpu.runtime.telemetry import flush_run_artifacts

                exc = _sys.exc_info()[1]
                extra: dict = {}
                if exc is not None:
                    extra["error"] = repr(exc)
                tag = None
                try:
                    if jax.process_count() > 1:
                        from mapreduce_rust_tpu.parallel.distributed import cluster_info

                        extra["cluster"] = cluster_info()
                        # Per-process file names, like the .p{rank} output
                        # suffix above: co-hosted federated drivers must not
                        # clobber each other's trace/manifest.
                        tag = f"p{jax.process_index()}"
                except Exception as e:
                    log.warning("cluster telemetry unavailable: %s", e)
                flush_run_artifacts(
                    cfg, tracer, tag=tag, logger=log,
                    stats=stats, app_name=app.name, inputs=inputs,
                    output_files=output_files, extra=extra or None,
                )
            if registry is not None:
                # After the flush: build_manifest serialized the ring from the
                # still-active registry. Compare-and-clear: an in-process
                # co-hosted worker may have replaced the global slot.
                stop_metrics(registry)
            if sprof is not None:
                # Same order and compare-and-clear discipline as the registry.
                from mapreduce_rust_tpu.runtime.prof import stop_profiler

                stop_profiler(sprof)
            if ledger is not None:
                # Same order and compare-and-clear discipline as the profiler.
                from mapreduce_rust_tpu.runtime.lineage import stop_ledger

                stop_ledger(ledger)
    return JobResult(stats=stats, table=table, output_files=output_files)


def _timed_fold(acc: HostAccumulator, stats: JobStats) -> np.ndarray:
    """The job's egress fold, acc.fold_arrays(), as the span
    "accumulator.fold" (acc_fold_s), with the rows its value repair moved
    copied into acc_fold_repaired_rows."""
    with stats.timed("acc_fold_s", "accumulator.fold"):
        rows = acc.fold_arrays()
    stats.acc_fold_repaired_rows = acc.repaired_rows
    return rows


def _postings_span(app: App, stats: JobStats):
    """"egress.postings" (posting_format_s), once a job in either egress
    tier: a distinct app's grouping of (term, doc) rows into posting
    lists, their words and their formatting. The two tiers' readings
    differ in what else the span holds: in RAM the fold and the partition
    sort, streaming the dictionary's merge and the per-block part-file
    writes, which interleave with the formatting there."""
    if app.combine_op != "distinct":
        return contextlib.nullcontext()
    return stats.timed("posting_format_s", "egress.postings")


def _posting_parts(cfg: Config, app: App, rows: np.ndarray, lookup,
                   stats: JobStats, table: dict) -> dict[int, list[bytes]]:
    """A distinct app's in-RAM egress from its sorted fold rows [n, 3]:
    terms are cut at key changes, a term's posting list is its slice of
    the doc column (ascending and deduplicated by the fold), its word is
    looked up once, and its lines come from the block seam
    App.emit_postings. Fills ``table`` (word → ascending list[int]) and
    returns partition → sorted lines, as App.finalize would."""
    starts = _key_starts(_pack_key_cols(rows[:, :2]))
    ends = np.append(starts[1:], len(rows))
    stats.distinct_keys = len(starts)
    k1s, k2s = rows[starts, 0].tolist(), rows[starts, 1].tolist()
    words = list(map(lookup, k1s, k2s))
    s_l, e_l = starts.tolist(), ends.tolist()
    known = [i for i, w in enumerate(words) if w is not None]
    if len(known) < len(words):
        stats.unknown_keys += len(words) - len(known)
        words, k1s, s_l, e_l = ([xs[i] for i in known]
                                for xs in (words, k1s, s_l, e_l))
    docs = rows[:, 2].tolist()
    values = list(map(docs.__getitem__, map(slice, s_l, e_l)))
    table.update(zip(words, values))
    routes = app.route_block(words, k1s, cfg.reduce_n)
    parts: list[list[bytes]] = [[] for _ in range(cfg.reduce_n)]
    app.emit_postings(words, docs, s_l, e_l, routes, parts)
    for lines in parts:
        lines.sort()
    return dict(enumerate(parts))


def _stream_finalize(cfg: Config, app: App, stats: JobStats, acc: HostAccumulator,
                     dictionary: Dictionary, write_outputs: bool) -> list[str]:
    """Bounded-memory egress: a single merge-join of the accumulator's
    sorted fold against the dictionary's sorted word stream, routed into
    per-partition line files, each sorted independently at the end. Peak
    RAM is O(fold rows + one partition's lines), never O(vocabulary) of
    Python objects — the tier the reference cannot have (its reduce holds
    a whole partition's pairs in one Vec, src/mr/worker.rs:82-108).

    Implements the DEFAULT egress contract (route by k1 % reduce_n,
    app.format_line, bytewise sort per partition) — run_job falls back to
    the in-RAM path for apps that override App.finalize.
    """
    import tempfile

    from mapreduce_rust_tpu.runtime.lineage import active_ledger

    ledger = active_ledger()

    with stats.phase("finalize"):
        rows = _timed_fold(acc, stats)  # sorted by (k1, k2[, value])
        is_distinct = app.combine_op == "distinct"
        packed_rows = _pack_key_cols(rows[:, :2])
        n = len(rows)
        stats.distinct_keys = len(_key_starts(packed_rows)) if is_distinct else n
        stats.dictionary_words = len(dictionary)
        stats.hash_collisions = len(dictionary.collisions)

    with stats.phase("egress"):
        os.makedirs(cfg.output_dir, exist_ok=True)
        tmpdir = tempfile.mkdtemp(prefix="egress-", dir=cfg.output_dir)
        # ONE try/finally spans the whole egress phase — the merge-join loop
        # AND the per-partition sort/rewrite — so a failure anywhere in
        # either (a bad run file, a full disk mid-sort) still removes the
        # egress tmpdir instead of leaking part-* files into the output dir
        # (ADVICE r5).
        try:
            parts = [
                open(os.path.join(tmpdir, f"part-{r}"), "wb")
                for r in range(cfg.reduce_n)
            ]
            matched = 0
            try:
                # Batched k-way merge-join (ISSUE 11): the dictionary's
                # sources (all runs, all shards, RAM tiers — key-disjoint
                # by construction) merge in key/index BLOCKS through the
                # native loser tree, and each block joins the fold with
                # one vectorized searchsorted. Word bytes are sliced only
                # for keys the fold actually holds — the per-key Python
                # heap interleave + text parse this replaces was the
                # spill-engaged egress wall.
                from mapreduce_rust_tpu.runtime import spill as spill_io

                sources = dictionary.run_sources()
                stats.merge_fanin = len(sources)
                merge_it = spill_io.merge_sources(sources)
                # One "egress.postings" span over the whole join for a
                # distinct app: word lookup, grouping and formatting.
                with _postings_span(app, stats):
                    while True:
                        t0 = time.perf_counter()
                        blk = next(merge_it, None)
                        if blk is None:
                            break
                        keys_b, src_b, idx_b = blk
                        ends_g = None
                        if n:
                            pos = np.searchsorted(packed_rows, keys_b)
                            posc = np.minimum(pos, n - 1)
                            hit = (pos < n) & (packed_rows[posc] == keys_b)
                            if is_distinct:
                                # Fold rows repeat per (key, doc): the group's
                                # exclusive end, found once per block.
                                ends_g = np.searchsorted(
                                    packed_rows, keys_b, side="right"
                                )
                        else:
                            hit = np.zeros(len(keys_b), dtype=bool)
                        stats.record_hist(
                            "egress.merge_s", time.perf_counter() - t0
                        )
                        hits = np.nonzero(hit)[0]
                        if not len(hits):
                            continue  # dictionary words absent from the fold
                        # Batched word slicing (spill_io.slice_block_words,
                        # shared with the streaming save): word bytes are
                        # materialized only for keys the fold holds — at
                        # millions of matched words the per-item .word() path
                        # was a measurable slice of egress.
                        words = spill_io.slice_block_words(
                            sources, src_b[hits], idx_b[hits]
                        )
                        # Routing goes through the app's partition seam
                        # (ISSUE 15): hash apps keep k1 % reduce_n, range
                        # apps (sort) searchsorted the word prefixes over
                        # their sampler-bound splitters — element-wise equal
                        # to App.route, the in-RAM tier's router.
                        rr = app.route_block(
                            words,
                            (keys_b[hits] >> np.uint64(32)).astype(np.int64),
                            cfg.reduce_n,
                        )
                        pos_h = pos[hits]
                        # One buffered write per (block, partition), not one
                        # per line: the formatted lines batch through a join.
                        blk_lines: list[list] = [[] for _ in range(cfg.reduce_n)]
                        if is_distinct:
                            # The block's terms own fold rows lo:hi; each
                            # term's postings are its slice, already ascending.
                            ends_h = ends_g[hits]
                            lo, hi = int(pos_h.min()), int(ends_h.max())
                            app.emit_postings(
                                words, rows[lo:hi, 2].tolist(),
                                (pos_h - lo).tolist(), (ends_h - lo).tolist(),
                                rr, blk_lines,
                            )
                        else:
                            emit = app.emit_lines
                            for w, r, v in zip(
                                words, rr, rows[pos_h, 2].tolist()
                            ):
                                blk_lines[r].extend(emit(w, v))
                        for r, ls in enumerate(blk_lines):
                            if ls:
                                parts[r].write(b"\n".join(ls) + b"\n")
                        matched += len(hits)
            finally:
                for f in parts:
                    f.close()
            stats.unknown_keys = stats.distinct_keys - matched

            output_files: list[str] = []
            for r in range(cfg.reduce_n):
                with open(os.path.join(tmpdir, f"part-{r}"), "rb") as f:
                    lines = f.read().splitlines()
                lines.sort()
                buf = b"\n".join(lines) + b"\n" if lines else b""
                # Same reduce-skew signal as the in-RAM egress path (the
                # joined buffer's length IS sum(len(line) + 1)).
                stats.partition_bytes.append(len(buf))
                if ledger is not None:
                    # Egress claim (ISSUE 20), streaming tier: same
                    # contract as the in-RAM path's record_partition.
                    ledger.record_partition(r, len(buf))
                if write_outputs:
                    path = os.path.join(cfg.output_dir, f"mr-{r}.txt")
                    with open(path, "wb") as f:
                        f.write(buf)
                    output_files.append(path)
        finally:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    return output_files


def merge_outputs(output_files: Sequence[str], out_path: str) -> None:
    """`cat mr-* | sort > final.txt` (reference src/run.sh:17-21)."""
    lines: list[bytes] = []
    for path in output_files:
        with open(path, "rb") as f:
            lines.extend(f.read().splitlines())
    lines.sort()
    with open(out_path, "wb") as f:
        for line in lines:
            f.write(line + b"\n")
