"""Single config object for the framework.

The reference hard-codes every knob: TCP port 1040
(src/bin/mrcoordinator.rs:31, src/bin/mrworker.rs:21), 5 s lease timeout
(src/mr/coordinator.rs:70,86), 5-tick detector period
(src/bin/mrcoordinator.rs:47), 1 s renewal period (src/bin/mrworker.rs:141),
input path template ``data/gut-{m}.txt`` (src/mr/worker.rs:67) and the
intermediate/output file templates (src/mr/worker.rs:85,121,167). Here they
are all fields of one dataclass.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def sync_dispatch_forced() -> bool:
    """``MR_DISPATCH_SYNC`` — process-tree opt-out of the async dispatch
    plane (the MR_SPILL_SYNC enablement pattern). Lives HERE, the one
    module both the driver (plane construction) and the fold-shard auto
    heuristic below read, so the two can never disagree on what counts
    as enabled."""
    return os.environ.get("MR_DISPATCH_SYNC", "").strip().lower() in (
        "1", "true", "on", "yes"
    )


def profile_forced() -> bool:
    """``MR_PROFILE`` — process-tree opt-in to the sampling profiler
    (ISSUE 19; the MR_DISPATCH_SYNC enablement pattern): a chaos child
    or SIGKILL-test subprocess inherits profiling without plumbing a
    flag through its argv."""
    return os.environ.get("MR_PROFILE", "").strip().lower() in (
        "1", "true", "on", "yes"
    )


def lineage_forced() -> bool:
    """``MR_LINEAGE`` — process-tree opt-in to the provenance ledger
    (ISSUE 20; the MR_PROFILE enablement pattern): fleet workers and
    SIGKILL-test subprocesses inherit lineage recording without plumbing
    a flag through their argv. Canonical definition lives in
    runtime/lineage.py (the jax-free seam the analysis CLI imports);
    re-exported here so config-reading call sites have one import."""
    from mapreduce_rust_tpu.runtime.lineage import lineage_forced as _lf

    return _lf()


@dataclasses.dataclass
class Config:
    # ---- Job shape (reference: argv of mrcoordinator/mrworker) ----
    map_n: int = 6          # number of map tasks (chunks)
    reduce_n: int = 4       # number of reduce partitions
    worker_n: int = 1       # registration barrier size (coordinator.rs:42-44)

    # ---- Data plane ----
    chunk_bytes: int = 1 << 22      # bytes per map chunk fed to the device
    max_word_len: int = 64          # device tokenizer halo / truncation cap
    merge_capacity: int = 1 << 21   # running distinct-key capacity on device
    partial_capacity: Optional[int] = None  # per-chunk distinct-key cap
                                    # (None → max(chunk_bytes // 8, 1024);
                                    # overflow replays the chunk full-width,
                                    # exact — see effective_partial_capacity)
    bucket_capacity_factor: float = 2.0  # all_to_all per-bucket slack
    device: str = "auto"            # "auto" | "tpu" | "cpu"
    sharded_stream: bool = False    # mesh mode only: feed each window as ONE
                                    # contiguous device-resident stream cut at
                                    # arbitrary (mid-word) offsets across the
                                    # chips; a halo exchange (parallel/halo.py)
                                    # makes straddling tokens count exactly
                                    # once. The long-context/sequence-parallel
                                    # ingestion path (SURVEY.md §5) vs the
                                    # host-aligned chunker.
    map_engine: str = "device"      # "device": tokenize/hash/combine fully
                                    # on-chip (the TPU-native kernels;
                                    # best when the chip link is wide).
                                    # "host": the fused native C scan maps
                                    # each window on the host — the same
                                    # pass that builds the egress dictionary
                                    # — and ships compacted (key, value)
                                    # updates; the device runs merge/
                                    # shuffle/reduce. Mirrors the reference
                                    # split (map UDF on the worker CPU,
                                    # src/app/wc.rs:6-13; the framework owns
                                    # the shuffle) and wins end-to-end when
                                    # host→device bandwidth is the
                                    # bottleneck.
    host_window_bytes: int = 16 << 20  # map window for the host engine
    host_map_workers: Optional[int] = None  # scan threads of the host-map
                                    # engine. None = auto (usable cores
                                    # minus one reserved for the consumer
                                    # thread, min 1 — a ≤2-core CI host
                                    # keeps the single-worker pipeline).
                                    # The native scan releases the GIL, so
                                    # N workers scan N windows concurrently
                                    # while ONE consumer folds results in
                                    # window order — outputs are
                                    # bit-identical for any worker count.
    fold_shards: Optional[int] = None  # host-map engine egress-fold shards
                                    # (ISSUE 9). None = auto (1 below 4
                                    # usable cores, else min(4, cores // 2));
                                    # 1 = the legacy inline fold on the
                                    # consumer thread. With S > 1 the
                                    # dictionary splits into S key-hash-
                                    # disjoint shards (shard = packed key
                                    # % S), each owned by exactly ONE fold
                                    # thread; the native scan emits
                                    # pre-partitioned per-shard buffers and
                                    # the router hands each shard its slice,
                                    # so no dictionary state is ever touched
                                    # by two threads. Outputs are
                                    # bit-identical for any (host_map_workers,
                                    # fold_shards) pair — the device merge
                                    # stream stays in exact scan order.
    host_update_cap: int = 1 << 16  # fixed per-merge update capacity of the
                                    # host engine; windows with more uniques
                                    # are split across several merges. Fixed
                                    # so the engine compiles EXACTLY ONE
                                    # merge shape — variable caps meant a
                                    # tail window could trigger a fresh ~40 s
                                    # XLA compile mid-run.
    mesh_shape: Optional[int] = None  # devices in the 1-D mesh (None = all)
    ingest_threads: int = 4         # host threads for dictionary scans
    prefetch_chunks: int = 8        # chunker read-ahead depth (host queue)
    pipeline_depth: int = 64        # in-flight device steps before the host
                                    # reads back their (async-copied) counters.
                                    # Sized to hide the device→host round trip
                                    # behind ~sub-ms dispatches; costs O(depth) chunk
                                    # buffers of host RAM + update-sized device
                                    # buffers.
    profile_dir: Optional[str] = None  # write a jax.profiler trace of the
                                    # stream phase here (view with
                                    # tensorboard / xprof)
    trace_path: Optional[str] = None  # write a Chrome trace-event JSON of
                                    # the whole job here (open in Perfetto /
                                    # chrome://tracing). Spans buffer in RAM
                                    # and flush once at job end; overhead is
                                    # per-chunk/per-round, never per-record
                                    # (runtime/trace.py). Off by default.
    manifest_path: Optional[str] = None  # write the machine-readable run
                                    # manifest (config + platform + git rev
                                    # + JobStats + phase times + trace path)
                                    # here at job end; read/diff it with
                                    # `python -m mapreduce_rust_tpu stats`
    compilation_cache_dir: Optional[str] = "auto"  # persistent XLA compile
                                    # cache shared across processes ("auto"
                                    # → <repo>/.jax_cache; None/"" disables).
                                    # XLA compiles of the step fns are tens
                                    # of seconds; without this every process
                                    # (each worker, the dryrun) pays
                                    # them again.

    # ---- Bounded-memory egress tiers (VERDICT r4 missing 3) ----
    host_accum_budget_mb: Optional[int] = None  # >0: the host spill
                                    # accumulator folds pending arrays into
                                    # sorted disk runs (work_dir/accrun-*)
                                    # above this many MB of RAM, merged
                                    # exactly at finalize. None = all-RAM.
    dictionary_budget_words: Optional[int] = None  # >0: the egress
                                    # dictionary flushes its word store to
                                    # sorted disk runs (work_dir/dictrun-*)
                                    # above this many words, and finalize
                                    # switches to the streaming merge-join
                                    # egress. None = all-RAM.
    # ---- Device-merge dispatch plane (ISSUE 13) ----
    dispatch_async: bool = True     # host-map engine: scatter-back, pack,
                                    # device_put and the compiled merge run
                                    # on a dedicated depth-bounded dispatch
                                    # thread — the router hands off O(1)
                                    # per window and host-glue stops
                                    # booking device hops. False (or
                                    # MR_DISPATCH_SYNC=1 for a whole
                                    # process tree) runs the dispatch
                                    # inline on the router thread: the
                                    # measurement/debug plane. Outputs are
                                    # bit-identical either way at a fixed
                                    # coalesce setting.
    dispatch_coalesce: bool = True  # cross-window coalescing: successive
                                    # windows' (packed-key, count) results
                                    # merge into a staging combine buffer
                                    # (duplicate keys sum — the native
                                    # mr_coalesce_updates kernel), and a
                                    # device merge dispatches only when
                                    # fill crosses dispatch_fill_frac or
                                    # the stream ends. Zipf duplication
                                    # across windows means far fewer
                                    # records shipped. Engages only for
                                    # combine_op == "sum" apps (pre-summing
                                    # any other op would be wrong); outputs
                                    # stay oracle-exact — the merge stream
                                    # changes, the results cannot.
    dispatch_fill_frac: float = 0.5  # staging fill fraction (of
                                    # dispatch_stage_cap) that triggers a
                                    # coalesced dispatch. Lower = smaller,
                                    # more frequent merges (less host
                                    # combine latency); higher = fewer,
                                    # fuller merges (more cross-window
                                    # dedup per record shipped). The
                                    # doctor's merge-dispatch finding
                                    # reads the measured mean fill.
    dispatch_stage_cap: Optional[int] = None  # staging combine buffer
                                    # capacity in records. None = auto:
                                    # 64 × host_update_cap (4M records at
                                    # the default cap). MUST exceed one
                                    # window's typical distinct count to
                                    # coalesce anything (the Zipf leg's
                                    # windows hold ~400K uniques against
                                    # a 64K update cap — a cap-sized
                                    # staging buffer never engages), and
                                    # ideally spans the RUN's distinct
                                    # count so the whole stream coalesces
                                    # into one generation. Capacity is
                                    # near-free: the ping-pong buffers
                                    # are np.empty (lazily-faulted
                                    # pages), so resident bytes track the
                                    # fill actually reached —
                                    # ~2 × (fill_frac × stage + window) ×
                                    # 16 B worst case, vocabulary-sized
                                    # on ordinary corpora. Values below
                                    # host_update_cap clamp up to it.
    spill_async: bool = True        # binary async spill plane (ISSUE 11):
                                    # budget flushes freeze a snapshot and
                                    # a background writer thread per tier
                                    # (each dictionary shard, the
                                    # accumulator) sorts/packs/writes it
                                    # while the fold keeps scanning —
                                    # double-buffered, so memory stays
                                    # O(2 x budget) per tier. False (or
                                    # MR_SPILL_SYNC=1 for a whole process
                                    # tree) restores the inline write: the
                                    # debugging/measurement plane that
                                    # shows what the overlap hides. Outputs
                                    # are bit-identical either way.

    # ---- Data-plane checkpointing (single-process mesh driver) ----
    checkpoint_every_groups: int = 0  # >0: after every N mesh groups, drain
                                    # the pipeline and write an atomic
                                    # work_dir/driver.ckpt (device state +
                                    # spill accumulator + dictionary +
                                    # progress). The single-process analog
                                    # of the control plane's spill-file +
                                    # journal story (coordinator/server.py).
    resume: bool = False            # start from work_dir/driver.ckpt when it
                                    # matches this job's fingerprint

    sanitize: bool = False          # opt-in thread-ownership sanitizer
                                    # (analysis/sanitize.py): JobStats, the
                                    # egress dictionary and the native scan
                                    # arenas get ownership asserts — a
                                    # cross-thread write raises at the write
                                    # site instead of racing. MR_SANITIZE=1
                                    # in the environment enables it for a
                                    # whole process tree (e.g. the test
                                    # suite) without touching configs.

    multihost_barrier_timeout_s: float = 120.0  # how long a multi-process
                                    # run waits at the dictionary-exchange
                                    # barrier for every peer's shard before
                                    # failing the job (a dead peer cannot
                                    # be recovered here: its chips' hash
                                    # classes died with it — fail loudly,
                                    # rerun the job)

    # ---- Control plane (reference timings preserved) ----
    host: str = "127.0.0.1"
    port: int = 1040
    lease_timeout_s: float = 5.0     # coordinator.rs:70,86
    lease_check_period_s: float = 5.0  # mrcoordinator.rs:47-52 (1 Hz x 5 ticks)
    lease_renew_period_s: float = 1.0  # mrworker.rs:141 (fixed: map side too)
    poll_retry_s: float = 1.0        # worker sleep on -2/-3 (mrworker.rs:52,58)
    rpc_timeout_s: float = 15.0      # per-call deadline on the worker→
                                    # coordinator RPC plane (~3× the lease
                                    # check period): a wedged coordinator
                                    # used to block a worker FOREVER inside
                                    # readline() — the renewal loop then
                                    # never even expired client-side. A
                                    # timed-out call raises RpcTimeout (a
                                    # RuntimeError, deliberately NOT a
                                    # ConnectionError: the worker's
                                    # "coordinator gone = job done" path
                                    # must not swallow a wedge as success).
    flight_record_period_s: float = 5.0  # traced processes rewrite an
                                    # atomic {trace}.partial.json snapshot
                                    # at most this often (and at >=512 new
                                    # events), from consumer/poll loops —
                                    # a SIGKILLed worker's timeline
                                    # survives and `trace merge` accepts
                                    # the partial. MR_FLIGHT_RECORD_S
                                    # overrides (test hook).

    # ---- Live metrics plane (ISSUE 8) ----
    metrics_enabled: bool = True    # live metrics registry + time-series
                                    # ring (runtime/metrics.py): sampled
                                    # from the existing consumer/poll/
                                    # renewal loops — never per record —
                                    # into manifests as stats.timeseries,
                                    # shipped coordinator-ward in the
                                    # renewal envelope. Cheap enough to
                                    # default on; --no-metrics turns it
                                    # off.
    metrics_sample_period_s: float = 1.0  # wall-clock bucket width of the
                                    # ring's points: one point per bucket
                                    # however many loops tick the sampler
    metrics_ring_points: int = 512  # ring capacity (oldest points evicted,
                                    # eviction counted — a day-long run
                                    # keeps its newest ~8.5 min at 1 Hz;
                                    # raise the period for long jobs)
    metrics_port: int = 0           # coordinator-only: serve Prometheus
                                    # text exposition (GET /metrics) on
                                    # this port from a dedicated thread;
                                    # 0 = off. Standard scrapers work
                                    # against a long-lived coordinator.

    # ---- Sampling profiler (ISSUE 19) ----
    profile: bool = False           # in-process sampling profiler
                                    # (runtime/prof.py): one thread walks
                                    # sys._current_frames() at profile_hz,
                                    # collapsed stacks keyed by the mr/
                                    # plane-thread names, embedded in the
                                    # manifest as stats.profile and in
                                    # flight-recorder partials. Off by
                                    # default (--profile / MR_PROFILE=1).
    profile_hz: float = 97.0        # sampler rate; prime, so it never
                                    # phase-locks with 1/10/100 Hz work

    # ---- Provenance ledger (ISSUE 20) ----
    lineage: bool = False           # chunk-level data lineage
                                    # (runtime/lineage.py): per-chunk
                                    # blake2b content digests + partition
                                    # routing recorded to
                                    # {work_dir}/lineage.jsonl and
                                    # summarized as stats.lineage; the
                                    # `lineage` CLI answers forward/
                                    # backward/blast-radius queries.
                                    # Observational only — outputs stay
                                    # bit-identical ON vs OFF. Off by
                                    # default (--lineage / MR_LINEAGE=1).

    # ---- Fleet scheduler (ISSUE 17) ----
    sched: str = "fifo"             # task-grant scheduling mode. "fifo"
                                    # preserves the reference semantics:
                                    # a strict global map barrier per job
                                    # (reduce waits for the WHOLE map
                                    # phase) and admission-order job
                                    # polling in the service. "pipeline"
                                    # grants reduce task r the moment
                                    # every map task has reported bytes
                                    # for partition r (per-partition
                                    # readiness from the part_bytes
                                    # vectors, retracted when a
                                    # contributing attempt dies) and the
                                    # service scores every grantable
                                    # (job, phase) pair — priority class,
                                    # phase criticality, worker recent-job
                                    # affinity — so one job's map windows
                                    # fill another's barrier bubbles.
                                    # Outputs are bit-identical across
                                    # modes; fifo stays the A/B oracle.

    # ---- Multi-tenant job service (ISSUE 14) ----
    service_max_jobs: int = 3       # concurrent RUNNING jobs the service
                                    # admits; further submissions queue
                                    # (FIFO within priority). Each running
                                    # job owns a namespaced work/output
                                    # dir, journal, lease table and
                                    # JobReport — the per-job Coordinator
                                    # state the shared worker fleet pulls
                                    # tasks from.
    service_inflight_budget_mb: float = 256.0  # admission-control budget:
                                    # total input bytes across RUNNING
                                    # jobs. A job whose corpus would push
                                    # the sum past this stays QUEUED
                                    # (backpressure, surfaced as the
                                    # live doctor's `service-saturated`
                                    # finding) — except when nothing is
                                    # running, so one oversized job can
                                    # never wedge the queue forever.
    service_cache_entries: int = 64  # result-cache capacity: completed
                                    # jobs keyed on (app, corpus-digest,
                                    # config-digest); a repeated identical
                                    # submission is served from cache with
                                    # ZERO new task grants. LRU, evictions
                                    # counted in the metrics registry.

    # ---- Active fault tolerance (speculation / chaos / degradation) ----
    speculate: bool = False         # coordinator speculative re-execution:
                                    # near phase end, re-issue the slowest
                                    # in-flight task to an idle worker as a
                                    # NEW attempt — first finish wins, the
                                    # loser is revoked on its next renewal.
                                    # The idempotent finish journal keeps
                                    # outputs bit-identical either way.
    speculate_after_frac: float = 0.75  # fraction of a phase's tasks done
                                    # before speculation arms (too early
                                    # and healthy tasks get duplicated;
                                    # too late and the straggler tail is
                                    # already the critical path)
    speculate_slow_factor: float = 1.5  # once the phase attempt-duration
                                    # histogram has >= 3 samples, only
                                    # attempts running longer than this
                                    # multiple of the task p50 are
                                    # speculated; before that, any
                                    # in-flight task is eligible
    speculate_max_attempts: int = 2  # concurrent attempts per task,
                                    # original included (2 = at most one
                                    # speculative copy)
    chaos: Optional[str] = None     # deterministic fault-injection spec
                                    # (analysis/chaos.py grammar, e.g.
                                    # "seed=7;pause:map:0:2.0;kill:reduce:1")
                                    # — MR_CHAOS in the environment
                                    # overrides. Faults fire at named
                                    # worker sites, seeded and
                                    # reproducible, so every recovery path
                                    # gets an honest test.

    # ---- RPC-plane degradation (runtime/backoff.py) ----
    rpc_backoff_base_s: float = 0.05  # first retry delay on a connect
                                    # failure or transient call timeout
    rpc_backoff_cap_s: float = 2.0  # delay envelope cap — a worker must
                                    # not sleep minutes after a blip
    rpc_backoff_budget_s: float = 60.0  # total retry budget per operation;
                                    # spent budget surfaces the real error
                                    # (BackoffExhausted) instead of
                                    # retrying forever
    poll_retry_cap_s: Optional[float] = None  # sentinel-poll (-2/-3)
                                    # backoff cap; None = 4x poll_retry_s.
                                    # The poll starts at poll_retry_s and
                                    # backs off — an idle worker stops
                                    # hammering a long phase gate, but the
                                    # cap keeps it responsive enough to
                                    # claim speculative re-executions.

    # ---- Workload plane (ISSUE 15) ----
    split_samples: int = 512        # sampled-splitter subsystem
                                    # (runtime/splitter.py): tokens sampled
                                    # PER INPUT FILE by the seeded pre-pass
                                    # that derives range-partition
                                    # splitters for range apps (sort).
                                    # More samples = flatter partitions on
                                    # skewed corpora; the doctor's
                                    # splitter-quality finding says when
                                    # to raise it. Deterministic: the seed
                                    # is fixed (splitter.SPLIT_SEED), so
                                    # re-executed tasks re-derive
                                    # bit-identical splitters.

    # ---- Paths ----
    input_dir: str = "data"
    input_pattern: str = "*.txt"
    input_dirs: "Optional[tuple]" = None  # multi-corpus input API
                                    # (ISSUE 15): ordered ((name, dir),
                                    # ...) pairs — the CLI's
                                    # ``--input a=DIR b=DIR`` form,
                                    # canonically sorted by name. When
                                    # set it supersedes input_dir; the
                                    # flat doc_id space concatenates the
                                    # corpora's sorted listings in this
                                    # order (chunker.resolve_corpora) and
                                    # apps see the boundaries via
                                    # App.corpus_bounds (join needs
                                    # exactly two). None = the classic
                                    # single corpus at input_dir.
    work_dir: str = "mr-work"        # intermediates / checkpoints
    output_dir: str = "mr-out"       # final per-partition outputs

    def __post_init__(self) -> None:
        if self.map_n <= 0 or self.reduce_n <= 0 or self.worker_n <= 0:
            raise ValueError("map_n, reduce_n, worker_n must be positive")
        if self.chunk_bytes <= 2 * self.max_word_len:
            raise ValueError("chunk_bytes too small for max_word_len halo")
        if self.map_engine not in ("device", "host"):
            raise ValueError(f"unknown map_engine {self.map_engine!r}")
        if self.host_map_workers is not None and self.host_map_workers < 1:
            raise ValueError("host_map_workers must be >= 1 (or None for auto)")
        if self.fold_shards is not None and self.fold_shards < 1:
            raise ValueError("fold_shards must be >= 1 (or None for auto)")
        if not 0.0 < self.dispatch_fill_frac <= 1.0:
            raise ValueError("dispatch_fill_frac must be in (0, 1]")
        if self.dispatch_stage_cap is not None and self.dispatch_stage_cap < 1:
            raise ValueError("dispatch_stage_cap must be >= 1 (or None)")
        if self.rpc_timeout_s <= 0:
            raise ValueError("rpc_timeout_s must be positive")
        if self.flight_record_period_s <= 0:
            raise ValueError("flight_record_period_s must be positive")
        if not 0.0 < self.speculate_after_frac <= 1.0:
            raise ValueError("speculate_after_frac must be in (0, 1]")
        if self.speculate_slow_factor < 1.0:
            raise ValueError("speculate_slow_factor must be >= 1.0")
        if self.speculate_max_attempts < 2:
            raise ValueError(
                "speculate_max_attempts must be >= 2 (the original plus at "
                "least one speculative copy)"
            )
        if self.rpc_backoff_base_s <= 0 or self.rpc_backoff_cap_s <= 0 \
                or self.rpc_backoff_budget_s <= 0:
            raise ValueError("rpc_backoff_* must be positive")
        if self.metrics_sample_period_s <= 0:
            raise ValueError("metrics_sample_period_s must be positive")
        if self.metrics_ring_points < 8:
            raise ValueError("metrics_ring_points must be >= 8")
        if self.metrics_port < 0:
            raise ValueError("metrics_port must be >= 0 (0 = off)")
        if self.poll_retry_cap_s is not None and self.poll_retry_cap_s <= 0:
            raise ValueError("poll_retry_cap_s must be positive (or None)")
        if self.sched not in ("fifo", "pipeline"):
            raise ValueError(f"unknown sched {self.sched!r} "
                             "(expected 'fifo' or 'pipeline')")
        if self.service_max_jobs < 1:
            raise ValueError("service_max_jobs must be >= 1")
        if self.service_inflight_budget_mb <= 0:
            raise ValueError("service_inflight_budget_mb must be positive")
        if self.service_cache_entries < 0:
            raise ValueError("service_cache_entries must be >= 0 (0 = off)")
        if self.split_samples < 1:
            raise ValueError("split_samples must be >= 1")
        if self.input_dirs is not None:
            # Canonical, validated form: a non-empty tuple of (name, dir)
            # string pairs with unique non-empty names — a malformed
            # corpus spec must fail at Config time, never as a KeyError
            # inside a worker's spec fetch.
            dirs = tuple(tuple(p) for p in self.input_dirs)
            if not dirs or not all(
                len(p) == 2 and all(isinstance(x, str) and x for x in p)
                for p in dirs
            ):
                raise ValueError(
                    "input_dirs must be ((name, dir), ...) string pairs"
                )
            names = [n for n, _ in dirs]
            if len(set(names)) != len(names):
                raise ValueError(f"duplicate corpus names in {names}")
            self.input_dirs = dirs
        if self.chaos:
            # Fail at config time, not mid-task inside a worker: a typo'd
            # fault spec must be a loud error before any lease is granted.
            from mapreduce_rust_tpu.analysis.chaos import ChaosPlan

            ChaosPlan.parse(self.chaos)

    def corpora(self) -> "tuple[tuple[str, str], ...]":
        """The job's ordered (name, dir) corpus list — the ONE accessor
        every consumer (chunker, service, worker) resolves inputs
        through, multi-corpus or classic."""
        if self.input_dirs is not None:
            return tuple(self.input_dirs)
        return (("corpus", self.input_dir),)

    @property
    def sched_pipeline(self) -> bool:
        """True when the fleet scheduler pipelines phases (ISSUE 17):
        per-partition reduce release in the coordinator + scored
        cross-job granting in the service."""
        return self.sched == "pipeline"

    def effective_poll_retry_cap_s(self) -> float:
        return self.poll_retry_cap_s or 4.0 * self.poll_retry_s

    def effective_host_map_workers(self) -> int:
        """Resolved host-map scan worker count: the explicit knob, or
        USABLE cores minus one (cpuset/affinity-aware — a containerized
        2-of-64-cores host must not spawn 64 scan threads). Auto reserves
        one core for the CONSUMER thread, which is a full-time core of
        work of its own (dictionary fold + update pack + XLA merge
        compute on a CPU backend): measured on a 2-core host, 2 scan
        workers + the consumer oversubscribe and run ~9% SLOWER than the
        1-worker pipeline, so auto on ≤2 cores keeps exactly the old
        single-worker overlap. --host-workers overrides for sweeps."""
        if self.host_map_workers:
            return max(int(self.host_map_workers), 1)
        try:
            n = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):  # non-Linux
            n = os.cpu_count() or 1
        return max(n - 1, 1)

    def effective_fold_shards(self) -> int:
        """Resolved egress-fold shard count for the host-map engine. The
        explicit knob wins; auto takes min(4, cores // 2) at >= 4 usable
        cores (fold work is Python/numpy-bound per shard, so shards
        beyond ~half the cores only trade scan parallelism for idle fold
        threads). Below 4 cores auto stays at 1 (the inline fold, zero
        queue hops) — PR 9 measured fold threads just oversubscribing the
        then-dispatch-bound router there — EXCEPT when the async dispatch
        plane has freed the router AND the operator declared a
        high-cardinality job by setting a dictionary budget: there the
        off-router fold measurably wins even on 2 cores (ISSUE 13:
        256 MB Zipf leg 13.0 s -> 12.3 s at S=2 with the dictionary fold
        as the residual glue wall; the low-cardinality gut leg, which
        sets no budget, keeps the inline fold it still prefers by ~8%).
        ``--fold-shards`` overrides for sweeps."""
        if self.fold_shards:
            return max(int(self.fold_shards), 1)
        try:
            n = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):  # non-Linux
            n = os.cpu_count() or 1
        if n < 4:
            if (self.dispatch_async and not sync_dispatch_forced()
                    and self.dictionary_budget_words is not None):
                return 2
            return 1
        return min(4, n // 2)

    def effective_dispatch_stage_cap(self) -> int:
        """Resolved staging-combine capacity of the dispatch plane: the
        explicit knob (clamped up to the update cap — a staging buffer
        smaller than one dispatch could never fill one), or 64 × the
        update cap. The auto multiple is the coalesce window: staging
        must span MANY windows' distinct keys for cross-window
        duplication to cancel (at the defaults, 4M records — above the
        256 MB Zipf leg's 1.62M total distinct, so that whole stream
        coalesces into one generation). Virtual capacity, resident
        fill: the buffers fault lazily (see dispatch_stage_cap)."""
        if self.dispatch_stage_cap is not None:
            return max(int(self.dispatch_stage_cap), self.host_update_cap)
        return 64 * self.host_update_cap

    def effective_partial_capacity(self) -> int:
        """The per-chunk distinct-key capacity both stream paths must share
        (single-chip and mesh replay rates stay comparable)."""
        return self.partial_capacity or max(self.chunk_bytes // 8, 1024)
