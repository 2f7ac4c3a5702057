"""Job metrics & phase timing — per-phase, never per-record — plus the
live metrics registry (ISSUE 8).

The reference's only observability is ~30 ``println!`` protocol lines plus
one log line *per emitted KV pair* inside the map hot loop
(src/mr/worker.rs:131-136) — the most expensive "observability" in the
system. Here counters accumulate in one dataclass and are logged once per
phase (driver) or once per task (worker); per-chunk detail is DEBUG level.

Two layers share this module:

- :class:`JobStats` — the one-shot per-run dataclass every engine fills
  and the manifest serializes. Unchanged contract: single-writer (the
  consumer thread), aggregate counters only.
- :class:`MetricsRegistry` — the LIVE layer on top: named counters /
  gauges / histograms with label support, registered once and sampled by
  ``maybe_sample()`` into a bounded in-memory time-series ring of
  wall-clock-bucketed points. The sampler is piggybacked on the existing
  consumer/poll/renewal loops exactly like the flight recorder
  (``trace.maybe_snapshot``) — the not-due path is two reads and a
  compare, and NOTHING here may run per record (mrlint rule
  ``metric-in-hot-loop`` enforces that at the known hot loops). The ring
  lands in run manifests as ``stats.timeseries``, rides flight-recorder
  partials so a SIGKILLed run keeps its series, ships to the coordinator
  in the renewal-RPC envelope, and renders as Prometheus text exposition
  on the coordinator's ``--metrics-port`` endpoint.

No jax import and no backend probe anywhere in this module: the registry
must be constructible in the coordinator and in ``watch`` — control-plane
processes that never load a backend.

Job-isolation audit (ISSUE 14). The module-global registry slot
(``start_metrics``/``active_registry``/``metrics_tick``) is PROCESS
state, documented as shared: it exists so build_manifest and the
engine-side ticks of an OS-process driver/worker find "the" registry
without plumbing. It is last-writer-wins under co-hosting, which is why
every multi-tenant owner uses an INSTANCE registry instead — the
coordinator and the JobService construct their own (per-job series are
``job=<id>``-LABELED on that one instance, never one registry per job),
and each Worker ships from ``self.registry``. Nothing job-scoped may
ever live in the global slot.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import re
import threading
import time
from contextlib import contextmanager

from mapreduce_rust_tpu.runtime.histogram import EDGES, Histogram
from mapreduce_rust_tpu.runtime.trace import trace_span

log = logging.getLogger("mapreduce_rust_tpu")


@dataclasses.dataclass
class JobStats:
    bytes_in: int = 0
    chunks: int = 0
    forced_cuts: int = 0          # tokens longer than chunk_bytes, split
    distinct_keys: int = 0        # final distinct key count
    spill_events: int = 0         # merges whose evicted tail was non-empty
    spilled_keys: int = 0         # records moved device → host accumulator
    partial_overflow_replays: int = 0  # chunks re-run on the full-width path
    bucket_skew_replays: int = 0       # mesh groups re-run on the skew tier
    halo_truncations: int = 0     # sharded-stream tokens longer than the halo
                                  # (possibly truncated hash — exactness fault)
    scan_tokenize_rounds: int = 0  # TPU rounds tokenized by the
                                  # associative_scan, not the Pallas kernel
                                  # (the sharded stream's halo path needs
                                  # the token-length lane the kernel lacks)
    mesh_rounds: int = 0          # all_to_all rounds executed (incl. replays)
    shuffle_wire_bytes: int = 0   # bytes through the all_to_all: the padded
    # bucket payload every chip exchanges each round — D*D*bucket_cap
    # records x 13 B (k1+k2+value+valid). This is what actually crosses the
    # interconnect (buckets are fixed-capacity under jit), so mesh runs can
    # attribute time to ICI vs compute before any multi-chip perf claim.
    accum_spill_runs: int = 0     # accrun-* disk runs the accumulator's
                                  # budget tier wrote (counted at job end,
                                  # before the run files are deleted — the
                                  # post-hoc proof the bounded-memory tier
                                  # actually engaged)
    dict_spill_runs: int = 0      # dictrun-* disk runs, same contract
    dictionary_words: int = 0
    hash_collisions: int = 0
    unknown_keys: int = 0         # final keys missing from the dictionary
    wall_seconds: float = 0.0
    phase_seconds: dict = dataclasses.field(default_factory=dict)
    # Utilization split (who is the bottleneck): time the consumer loop sat
    # idle waiting for host ingest (read→normalize→chunk) vs time it sat
    # blocked on device results. ingest_wait ≫ device_wait → host-bound.
    ingest_wait_s: float = 0.0
    device_wait_s: float = 0.0
    host_map_s: float = 0.0       # CPU seconds in the host-map engine's scan
    # — AGGREGATE across scan workers (with host_map_workers > 1 this can
    # legitimately exceed the stream wall time; divide by the worker count
    # for per-core scan time)
    host_glue_s: float = 0.0      # host-map engine consumer-thread work
    # between scans: dictionary fold + update pack + device_put + merge
    # dispatch — on a 1-core host this steals directly from the scan
    # thread, so the split names which of the two to optimize
    host_map_workers: int = 0     # scan threads the host-map engine ran
                                  # (0 = engine not used this run)
    # ---- sharded egress fold (ISSUE 9) ----
    fold_shards: int = 0          # fold shards the host-map engine ran
    # (0 = engine not used; 1 = legacy inline fold on the consumer thread;
    # >1 = the sharded fold plane: S fold threads, each the sole owner of
    # one key-hash-disjoint dictionary shard)
    fold_s: float = 0.0           # seconds fold threads spent folding scan
    # results into their shards — AGGREGATE across fold threads (like
    # host_map_s across scan workers: with S>1 this may exceed wall time;
    # per-shard balance lives in fold_shard_s)
    fold_stall_s: float = 0.0     # router wall seconds blocked on fold
    # backpressure: full shard queues plus the end-of-stream join. The
    # wall-clock "the fold is the ceiling" signal, exactly as scan_wait_s
    # is for the scans — large means more shards (or a flatter key hash)
    # would raise throughput
    fold_shard_s: list = dataclasses.field(default_factory=list)
    # per-shard fold seconds (index = shard): the fold-balance signal the
    # doctor's fold-shard-skew finding scores
    fold_shard_idle_s: list = dataclasses.field(default_factory=list)
    # per-shard seconds the fold thread sat waiting for routed work
    # ---- binary async spill plane (ISSUE 11) ----
    spill_s: float = 0.0          # background-writer seconds spent
    # sorting/packing/writing spill runs (dictionary + accumulator tiers,
    # aggregate across writer threads — overlapped with the scan, so with
    # the async plane this can exceed nothing: it is hidden time made
    # visible)
    spill_stall_s: float = 0.0    # fold/consumer wall seconds blocked on
    # a full spill-writer queue: the wall-clock "the disk is the ceiling"
    # signal, exactly as fold_stall_s is for the fold — large means raise
    # the budgets (fewer, larger runs), add fold shards (one writer per
    # shard), or find a faster disk
    spill_bytes: int = 0          # bytes written to spill runs (both tiers)
    merge_fanin: int = 0          # sources the egress k-way merge saw
    # (runs + RAM tiers across every shard; 0 = in-RAM egress)
    # ---- device-merge dispatch plane (ISSUE 13) ----
    dispatch_mode: str = ""       # "" = plane not used (non-host engines);
    # "async"/"sync" + "+coalesce" when cross-window coalescing engaged —
    # every manifest says which dispatch plane produced its numbers
    dispatch_s: float = 0.0       # dispatch-thread seconds in scan-order
    # scatter-back + staging combine + pack + device_put + the jit call —
    # with the async plane this is overlapped (hidden) time made visible,
    # exactly like spill_s for the writers; in sync mode the same work is
    # also part of host_glue_s (the PR 10 accounting, kept for A/B)
    dispatch_stall_s: float = 0.0  # router wall seconds blocked on a full
    # dispatch queue plus the end-of-stream join: the wall-clock "the
    # dispatch is the ceiling" signal, exactly as fold_stall_s is for the
    # fold — large means the device hop itself (or the coalesce combine)
    # is slower than the scans feeding it
    merge_dispatches: int = 0     # packed device merges dispatched (with
    # coalescing this is windows ÷ coalesce factor, the lever the plane
    # exists to pull)
    merge_fill_frac: float = 0.0  # mean records-per-dispatch ÷ cap: how
    # full the fixed-shape update actually was. Low = the 1+3·cap
    # transfer is mostly sentinel padding (lower host_update_cap or raise
    # dispatch_fill_frac); the doctor's merge-dispatch finding reads this
    scan_wait_s: float = 0.0      # consumer wall time blocked waiting for
    # the next IN-ORDER scan result: the parallel engine's starvation
    # signal — large scan_wait means more workers (or a faster scan) would
    # raise throughput; ~0 means the scans are fully hidden and glue or
    # device is the ceiling
    all_to_all_s: float = 0.0     # wall seconds inside mesh.all_to_all
    # blocks (tokenize + bucket scatter + collective dispatch, replays
    # included) — the ICI-vs-compute split's numerator: with the per-round
    # wire bytes (shuffle_wire_bytes) this attributes mesh time to the
    # interconnect before any multi-chip perf claim
    host_arena_bytes: int = 0     # native scan scratch resident across ALL
    # scan threads at job end (native/host.arena_bytes): the memory price
    # of host_map_workers, flat per thread by construction
    # ---- doctor instrumentation (ISSUE 5) ----
    compile_count: int = 0        # XLA backend compiles this run triggered
    compile_s: float = 0.0        # wall seconds inside those compiles —
    # overlaps the phase that triggered them (a cold first window pays it),
    # so the doctor can name "compile" as the real ceiling of a short run
    compile_cache_hits: int = 0   # persistent-compilation-cache hits
    compile_cache_misses: int = 0  # consulted-but-absent (cold) compiles
    device_mem_high_bytes: int = 0  # high-water bytes_in_use across local
    # devices, sampled from the existing drain/consume loops (0 when the
    # backend exposes no memory_stats, e.g. CPU)
    partition_bytes: list = dataclasses.field(default_factory=list)
    # bytes of formatted output per reduce partition (index = r): the
    # reduce-side skew signal the doctor scores — a hot partition here is
    # the key-distribution problem the reference can't even see
    # ---- workload plane (ISSUE 15) ----
    partition_mode: str = "hash"  # how this run's egress routed keys to
    # partitions: "hash" (k1 % reduce_n) or "range" (searchsorted over
    # sampled splitters — sort). The doctor reads it to pick which skew
    # advice applies to partition_bytes (raise reduce_n vs raise
    # split_samples).
    splitter_samples: int = 0     # tokens the sampled-splitter pre-pass
    # drew across all inputs (range apps only; 0 = no pre-pass ran)
    splitter_s: float = 0.0       # wall seconds of the sample+derive
    # pre-pass — the splitter-overhead the bench sort leg records; it
    # must stay O(samples), invisible next to the stream
    mesh_shard_rows: list = dataclasses.field(default_factory=list)
    # final valid records per mesh shard (hash-class skew across chips)
    hists: dict = dataclasses.field(default_factory=dict)
    # name → runtime.histogram.Histogram: the latency distributions behind
    # the aggregate counters above (host_map.scan_s, a2a.round_s,
    # device.drain_s, ingest.wait_s, ...). Serialized into the manifest as
    # "histograms" by telemetry.stats_to_dict; per-window/per-round sites
    # only — never per-record (the add is a bisect, not free).

    def record_hist(self, name: str, value: float) -> None:
        """Fold one sample into the named latency/size histogram. Same
        ownership contract as every other stats write: consumer thread
        only (the sanitizer's registered-writer gate covers the attribute
        reads here; the dict insert happens on first use)."""
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = Histogram()
        h.add(value)

    def register_writer(self) -> None:
        """Sanitizer hook: announce the calling thread as a legitimate
        concurrent writer (the ingest producer calls this — it owns
        bytes_in/chunks/forced_cuts by design). No-op here; the sanitized
        subclass (analysis/sanitize.SanitizedJobStats) records the thread
        and rejects writes from any thread that never registered."""

    @property
    def gb_per_s(self) -> float:
        return self.bytes_in / self.wall_seconds / 1e9 if self.wall_seconds else 0.0

    @property
    def bottleneck(self) -> str:
        # With parallel scan workers the aggregate host_map_s no longer
        # measures wall time; the consumer's scan starvation (scan_wait_s)
        # is the honest wall-clock attribution for "the scans are the
        # ceiling" — a fully hidden scan pool must not keep claiming the
        # bottleneck it used to be.
        scan = self.host_map_s if self.host_map_workers <= 1 else self.scan_wait_s
        parts = {
            "host-ingest": self.ingest_wait_s,
            "device": self.device_wait_s,
            "host-map": scan,
            "host-glue": self.host_glue_s,
        }
        if self.fold_shards > 1:
            # Sharded fold plane (ISSUE 9): folding runs off the consumer
            # thread, so host_glue_s no longer contains it — the honest
            # wall-clock "the fold is the ceiling" signal is the router's
            # fold backpressure, same logic as scan_wait_s for the scans.
            parts["host-fold"] = self.fold_stall_s
        if self.spill_s > 0 or self.spill_stall_s > 0:
            # Async spill plane (ISSUE 11): run writes happen off the hot
            # threads, so the honest "the disk is the ceiling" signal is
            # the owner-side writer backpressure — the same stall logic as
            # host-fold. (The doctor's _bottleneck_attribution mirrors
            # this arm exactly; keep them in lockstep.)
            parts["spill"] = self.spill_stall_s
        if self.dispatch_mode.startswith("async"):
            # Async dispatch plane (ISSUE 13): the device hop runs off the
            # router, so "the dispatch is the ceiling" reads as router
            # backpressure — same stall logic again. Sync mode keeps the
            # PR 10 attribution (the hop is glue), so the arm stays off
            # there and the A/B story stays honest. (Doctor mirror:
            # _bottleneck_attribution, keep in lockstep.)
            parts["merge-dispatch"] = self.dispatch_stall_s
        name, val = max(parts.items(), key=lambda kv: kv[1])
        return name if val > 0 else "balanced"

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            # Phases double as top-level timeline spans ("phase.stream",
            # "phase.finalize", "phase.egress") when tracing is on.
            with trace_span(f"phase.{name}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.phase_seconds[name] = self.phase_seconds.get(name, 0.0) + dt
            log.info("phase %-10s %8.3fs", name, dt)

    def summary(self) -> str:
        phases = " ".join(f"{k}={v:.2f}s" for k, v in self.phase_seconds.items())
        return (
            f"{self.bytes_in / 1e6:.2f} MB in {self.wall_seconds:.3f}s "
            f"({self.gb_per_s:.3f} GB/s) chunks={self.chunks} "
            f"distinct={self.distinct_keys} dict={self.dictionary_words} "
            f"spills={self.spill_events}({self.spilled_keys} keys) "
            f"replays={self.partial_overflow_replays}+{self.bucket_skew_replays}skew "
            f"shuffle[{self.mesh_rounds} rounds, {self.shuffle_wire_bytes / 1e6:.1f} MB wire] "
            f"collisions={self.hash_collisions} unknown={self.unknown_keys} "
            f"waits[ingest={self.ingest_wait_s:.2f}s device={self.device_wait_s:.2f}s "
            f"map={self.host_map_s:.2f}s"
            + (
                f"/{self.host_map_workers}w stall={self.scan_wait_s:.2f}s"
                if self.host_map_workers > 1 else ""
            )
            + f" glue={self.host_glue_s:.2f}s"
            + (
                f" fold={self.fold_s:.2f}s/{self.fold_shards}sh "
                f"fstall={self.fold_stall_s:.2f}s"
                if self.fold_shards > 1 else ""
            )
            + (
                f" spillw={self.spill_s:.2f}s sstall={self.spill_stall_s:.2f}s"
                if self.spill_s > 0 or self.spill_stall_s > 0 else ""
            )
            + (
                f" disp[{self.dispatch_mode}]={self.dispatch_s:.2f}s"
                f"/{self.merge_dispatches}m "
                f"fill={self.merge_fill_frac:.2f} "
                f"dstall={self.dispatch_stall_s:.2f}s"
                if self.dispatch_mode else ""
            )
            + f" → {self.bottleneck}] [{phases}]"
        )


# ---------------------------------------------------------------------------
# Live metrics registry (ISSUE 8 tentpole)
# ---------------------------------------------------------------------------

TIMESERIES_SCHEMA = 1

#: Prometheus metric-name charset; anything else becomes "_".
_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _series_key(name: str, labels: tuple) -> str:
    """Flat series identity: ``name`` or ``name{k=v,k2=v2}`` — the key the
    ring, the manifest and the scrape endpoint all agree on."""
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


def _prom_name(name: str, prefix: str = "mr_") -> str:
    return prefix + _NAME_RE.sub("_", name)


def _prom_labels(labels: tuple) -> str:
    if not labels:
        return ""
    body = ",".join(
        '{}="{}"'.format(k, str(v).replace("\\", r"\\").replace('"', r"\""))
        for k, v in labels
    )
    return "{" + body + "}"


def _prom_num(v) -> str:
    if isinstance(v, float):
        return format(v, ".10g")
    return str(v)


class _Instrument:
    """One named metric; label-sets map to independent values. Mutations
    take the registry lock — cheap at the allowed per-window/per-poll
    rate, and the doctrine (module docstring) forbids per-record calls."""

    kind = "untyped"

    def __init__(self, registry: "MetricsRegistry", name: str,
                 help: str = "") -> None:
        self._registry = registry
        self.name = name
        self.help = help
        self._values: dict = {}

    @staticmethod
    def _labelkey(labels: dict) -> tuple:
        return tuple(sorted((k, str(v)) for k, v in labels.items()))

    def remove_labels(self, **labels) -> int:
        """Drop every label-set whose labels INCLUDE the given pairs
        (``remove_labels(job="j3")`` drops all of j3's series whatever
        the other labels say). The long-lived-server hygiene hook
        (ISSUE 14): a multi-tenant registry that only ever adds
        label-sets grows without bound and keeps exporting a finished
        tenant's stale last values. Returns the number dropped; already-
        recorded ring points keep their history (the ring is bounded)."""
        want = {(k, str(v)) for k, v in labels.items()}
        with self._registry._lock:
            victims = [
                key for key in self._values if want <= set(key)
            ]
            for key in victims:
                del self._values[key]
        return len(victims)


class Counter(_Instrument):
    """Monotonic count. ``inc`` for push-style sites; ``set_total`` for
    pull-style mirrors of an externally-accumulated total (e.g. the
    coordinator re-publishing JobReport RPC counts each serve tick)."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = self._labelkey(labels)
        with self._registry._lock:
            self._values[key] = self._values.get(key, 0) + amount

    def set_total(self, value: float, **labels) -> None:
        key = self._labelkey(labels)
        with self._registry._lock:
            # Monotonicity kept even against a sloppy publisher: a counter
            # that goes backwards reads as a process restart to scrapers.
            if value >= self._values.get(key, 0):
                self._values[key] = value


class Gauge(_Instrument):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        key = self._labelkey(labels)
        with self._registry._lock:
            self._values[key] = value

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = self._labelkey(labels)
        with self._registry._lock:
            self._values[key] = self._values.get(key, 0) + amount


class HistogramMetric(_Instrument):
    """Label-set → runtime.histogram.Histogram (the same mergeable
    log-bucket primitive the manifests carry). ``observe`` folds one
    sample; ``set_hist`` adopts a copy of an externally-maintained
    histogram (pull-style, e.g. JobReport's per-RPC latency hists)."""

    kind = "histogram"

    def observe(self, value: float, **labels) -> None:
        key = self._labelkey(labels)
        with self._registry._lock:
            h = self._values.get(key)
            if h is None:
                h = self._values[key] = Histogram()
            h.add(value)

    def set_hist(self, hist: Histogram, **labels) -> None:
        key = self._labelkey(labels)
        snap = Histogram().merge(hist)  # copy: the source keeps mutating
        with self._registry._lock:
            self._values[key] = snap


class MetricsRegistry:
    """Named instruments + a bounded time-series ring of their sampled
    values.

    - Registration is idempotent by name; re-registering under a
      different kind raises (two subsystems fighting over one name is a
      bug, not a merge).
    - ``add_collector(fn)`` attaches a pull source: ``fn() -> {name:
      number}``, called only when a sample is actually taken (never the
      hot path); its values land in the ring and the scrape text as
      gauges. This is how JobStats rides along without double-
      instrumenting every engine (see :func:`jobstats_collector`).
    - ``maybe_sample()`` is the piggyback tick: wall-clock-bucketed (one
      point per ``period_s`` bucket however many loops tick), bounded by
      ``capacity`` points (oldest evicted, eviction counted).
    """

    def __init__(self, period_s: float = 1.0, capacity: int = 512) -> None:
        if period_s <= 0:
            raise ValueError("metrics period_s must be positive")
        if capacity < 8:
            raise ValueError("metrics ring capacity must be >= 8")
        self.period_s = float(period_s)
        self.capacity = int(capacity)
        self._instruments: dict[str, _Instrument] = {}
        self._collectors: list = []
        self._points: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._last_bucket: "int | None" = None
        self.dropped_points = 0
        self.collector_errors = 0

    # ---- registration ----

    def _register(self, cls, name: str, help: str):
        inst = self._instruments.get(name)
        if inst is not None:
            if not isinstance(inst, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {inst.kind}, "
                    f"not {cls.kind}"
                )
            return inst
        inst = self._instruments[name] = cls(self, name, help)
        return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._register(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._register(Gauge, name, help)

    def histogram(self, name: str, help: str = "") -> HistogramMetric:
        return self._register(HistogramMetric, name, help)

    def add_collector(self, fn) -> None:
        self._collectors.append(fn)

    # ---- sampling ----

    def current_values(self) -> dict:
        """Flat {series_key: number} of every instrument + collector right
        now. Histograms contribute ``<series>.count`` and ``<series>.sum``
        (rates and means are derivable; percentiles stay in the full
        histogram blocks the manifest already carries)."""
        out: dict = {}
        for fn in self._collectors:
            try:
                vals = fn() or {}
            except Exception:
                # A telemetry pull must never fail the loop that ticked it.
                self.collector_errors += 1
                continue
            for k, v in vals.items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                out[str(k)] = v
        with self._lock:
            for name, inst in self._instruments.items():
                for key, v in inst._values.items():
                    sk = _series_key(name, key)
                    if isinstance(v, Histogram):
                        out[f"{sk}.count"] = v.count
                        out[f"{sk}.sum"] = round(v.total, 9)
                    else:
                        out[sk] = v
        return out

    def due(self) -> bool:
        """Would ``maybe_sample()`` take a point right now? The cheap
        pre-check for callers whose PREPARATION for a sample is itself
        expensive (the coordinator republishes its control plane and
        renders the scrape text — work worth skipping on the serve-loop
        passes between buckets)."""
        last = self._last_bucket
        return last is None or int(time.time() / self.period_s) > last

    def maybe_sample(self, force: bool = False) -> bool:
        """The piggyback tick. Wall-clock-bucketed: however many loops
        call this, at most one point lands per ``period_s`` bucket. The
        not-due path is two reads and a compare (plus one uncontended
        lock round when the bucket rolls over). The bucket is CLAIMED
        under the lock before the (lock-taking) collector walk runs, so
        two threads ticking the same registry at the rollover cannot
        both sample it."""
        now = time.time()
        # Integer bucket index: `now - now % period` floats differently
        # across two calls inside the SAME bucket (mod rounding), which
        # would let two threads claim "different" buckets that stamp the
        # same point.
        bucket = int(now / self.period_s)
        last = self._last_bucket
        if not force and last is not None and bucket <= last:
            return False
        with self._lock:
            last = self._last_bucket
            if not force and last is not None and bucket <= last:
                return False  # another thread claimed this bucket — and a
                # stalled claimer must never move the high-water mark BACK
                # (that would re-open the newer bucket for a duplicate)
            self._last_bucket = max(bucket, last or 0)
        point = {"t": round(bucket * self.period_s if not force else now, 3),
                 "v": self.current_values()}
        with self._lock:
            if len(self._points) == self.capacity:
                self.dropped_points += 1
            self._points.append(point)
        return True

    def points(self) -> list:
        # Sorted on read: a claimer that stalled between claiming its
        # bucket and appending its point can land behind a newer one.
        with self._lock:
            return sorted(self._points, key=lambda p: p["t"])

    def latest(self) -> "dict | None":
        with self._lock:
            return self._points[-1] if self._points else None

    def ship_sample(self) -> dict:
        """The renewal-envelope payload: one fresh point (not ring-gated —
        the renewal period already paces it). Small flat dict by
        construction."""
        return {"t": round(time.time(), 3), "v": self.current_values()}

    # ---- serialization ----

    def series_catalog(self) -> dict:
        """series_key → {kind} for every series seen so far (collector
        series appear once a point holds them, as gauges)."""
        catalog: dict = {}
        with self._lock:
            for name, inst in self._instruments.items():
                for key in inst._values:
                    sk = _series_key(name, key)
                    if inst.kind == "histogram":
                        catalog[f"{sk}.count"] = {"kind": "histogram"}
                        catalog[f"{sk}.sum"] = {"kind": "histogram"}
                    else:
                        catalog[sk] = {"kind": inst.kind}
            known = set(catalog)
            for p in self._points:
                for sk in p["v"]:
                    if sk not in known:
                        catalog[sk] = {"kind": "gauge"}
                        known.add(sk)
        return catalog

    def timeseries_dict(self) -> dict:
        """The manifest block (``stats.timeseries``) and flight-recorder
        payload: the series catalog + every ring point, JSON-safe."""
        return {
            "schema": TIMESERIES_SCHEMA,
            "period_s": self.period_s,
            "capacity": self.capacity,
            "dropped_points": self.dropped_points,
            "series": self.series_catalog(),
            "points": self.points(),
        }

    # ---- Prometheus text exposition ----

    CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

    def prometheus_text(self, prefix: str = "mr_") -> str:
        """Render instruments + the freshest collector values in the
        Prometheus text exposition format (counters/gauges as single
        samples; histograms as cumulative ``_bucket{le=...}`` series over
        the log-bucket edges, plus ``_sum``/``_count``)."""
        lines: list[str] = []
        collected: dict = {}
        for fn in self._collectors:
            try:
                collected.update(fn() or {})
            except Exception:
                self.collector_errors += 1
        with self._lock:
            instruments = {
                name: (inst.kind, inst.help, dict(inst._values))
                for name, inst in sorted(self._instruments.items())
            }
        for name, (kind, help_, values) in instruments.items():
            pname = _prom_name(name, prefix)
            if help_:
                lines.append(f"# HELP {pname} {help_}")
            lines.append(f"# TYPE {pname} {kind}")
            for key, v in sorted(values.items()):
                lab = _prom_labels(key)
                if kind != "histogram":
                    lines.append(f"{pname}{lab} {_prom_num(v)}")
                    continue
                cum = 0
                for idx in sorted(v.buckets):
                    cum += v.buckets[idx]
                    le = ("+Inf" if idx >= len(EDGES)
                          else format(EDGES[min(idx, len(EDGES) - 1)], ".6g"))
                    blab = _prom_labels(key + (("le", le),))
                    lines.append(f"{pname}_bucket{blab} {cum}")
                inf_lab = _prom_labels(key + (("le", "+Inf"),))
                if f"{pname}_bucket{inf_lab} {v.count}" != (
                    lines[-1] if lines else ""
                ):
                    lines.append(f"{pname}_bucket{inf_lab} {v.count}")
                lines.append(f"{pname}_sum{lab} {_prom_num(round(v.total, 9))}")
                lines.append(f"{pname}_count{lab} {v.count}")
        for k, v in sorted(collected.items()):
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            pname = _prom_name(str(k), prefix)
            lines.append(f"# TYPE {pname} gauge")
            lines.append(f"{pname} {_prom_num(v)}")
        return "\n".join(lines) + "\n"


def jobstats_collector(stats: JobStats):
    """Pull source bridging the one-shot JobStats into the live ring: the
    sampler reads these aggregate fields when a point is due — no engine
    grows a second instrumentation site, and the read is benign (plain
    int/float attribute loads, no iteration over mutating containers)."""

    def collect() -> dict:
        return {
            "job.bytes_in": stats.bytes_in,
            "job.chunks": stats.chunks,
            "job.spill_events": stats.spill_events,
            "job.spilled_keys": stats.spilled_keys,
            "job.ingest_wait_s": round(stats.ingest_wait_s, 6),
            "job.device_wait_s": round(stats.device_wait_s, 6),
            "job.host_map_s": round(stats.host_map_s, 6),
            "job.host_glue_s": round(stats.host_glue_s, 6),
            "job.fold_s": round(stats.fold_s, 6),
            "job.fold_stall_s": round(stats.fold_stall_s, 6),
            "job.spill_s": round(stats.spill_s, 6),
            "job.spill_stall_s": round(stats.spill_stall_s, 6),
            "job.spill_bytes": stats.spill_bytes,
            "job.dispatch_s": round(stats.dispatch_s, 6),
            "job.dispatch_stall_s": round(stats.dispatch_stall_s, 6),
            "job.merge_dispatches": stats.merge_dispatches,
            "job.merge_fill_frac": round(stats.merge_fill_frac, 6),
            "job.scan_wait_s": round(stats.scan_wait_s, 6),
            "job.all_to_all_s": round(stats.all_to_all_s, 6),
            "job.mesh_rounds": stats.mesh_rounds,
            "job.shuffle_wire_bytes": stats.shuffle_wire_bytes,
            "job.compile_s": round(stats.compile_s, 6),
            "job.device_mem_high_bytes": stats.device_mem_high_bytes,
        }

    return collect


# ---------------------------------------------------------------------------
# Process-global registry lifecycle — the trace.py pattern: one registry
# per run, installed by the run owner (run_job / Worker.run / Coordinator
# CLI), ticked by module-level maybe_sample() from the existing loops.
# ---------------------------------------------------------------------------

_registry: "MetricsRegistry | None" = None


def start_metrics(period_s: float = 1.0,
                  capacity: int = 512) -> MetricsRegistry:
    global _registry
    _registry = MetricsRegistry(period_s=period_s, capacity=capacity)
    return _registry


def stop_metrics(expected: "MetricsRegistry | None" = None) \
        -> "MetricsRegistry | None":
    """Clear the global slot. With ``expected``, compare-and-clear: an
    in-process co-hosted run (tests drive several Workers in one
    interpreter) may have REPLACED the slot since this owner started —
    tearing down someone else's live registry would silence their
    renewal samples and manifest ring."""
    global _registry
    if expected is not None and _registry is not expected:
        return None
    r, _registry = _registry, None
    return r


def active_registry() -> "MetricsRegistry | None":
    return _registry


def metrics_tick() -> None:
    """Sampler tick on the active registry — no-op (one global read) when
    metrics are off. Call from consumer/poll/renewal loops, beside the
    flight recorder's ``maybe_snapshot()`` — never per record."""
    r = _registry
    if r is not None:
        r.maybe_sample()


# ---------------------------------------------------------------------------
# Prometheus scrape endpoint (coordinator --metrics-port)
# ---------------------------------------------------------------------------

class MetricsHTTPServer:
    """Text-exposition endpoint (``GET /metrics``) on its own thread —
    stdlib ``http.server``, zero new deps, so standard scrapers work
    against a long-lived coordinator.

    Publish/serve split: the OWNER thread (the coordinator's event loop,
    serialized with every RPC handler) renders the text and calls
    ``publish``; the HTTP thread only ever serves the last published
    bytes. The scrape path therefore never iterates a dict an RPC handler
    is mutating — the same discipline as the report snapshot at teardown.
    Port 0 binds an ephemeral port (tests); ``.port`` is the bound one.
    """

    def __init__(self, port: int, host: str = "127.0.0.1") -> None:
        import http.server

        outer = self
        self._body = b"# metrics: no samples published yet\n"
        self._pub_lock = threading.Lock()

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self) -> None:  # noqa: N802 (stdlib naming)
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                if path not in ("/", "/metrics"):
                    self.send_error(404, "try /metrics")
                    return
                with outer._pub_lock:
                    body = outer._body
                self.send_response(200)
                self.send_header("Content-Type", MetricsRegistry.CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args) -> None:
                pass  # scrapes poll; stderr chatter is not telemetry

        self._srv = http.server.ThreadingHTTPServer((host, port), _Handler)
        self._srv.daemon_threads = True
        self.host = host
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(
            target=self._srv.serve_forever, name="mr/metrics-http", daemon=True
        )
        self._thread.start()

    def publish(self, text: str) -> None:
        body = text.encode()
        with self._pub_lock:
            self._body = body

    def close(self) -> None:
        try:
            self._srv.shutdown()
            self._srv.server_close()
        except OSError:
            pass
        self._thread.join(timeout=5)
