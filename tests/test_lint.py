"""mrlint rule fixtures: every rule has at least one BAD snippet it must
fire on (the shipped-bug pattern, distilled) and a GOOD snippet it must
stay silent on (the shipped-fix pattern) — precision is the contract that
keeps the linter from being baselined into silence (ISSUE 3).

Also: inline-suppression mechanics (reasons are mandatory), the baseline
file format, and the JSON output schema.
"""

import json
import textwrap

import pytest

from mapreduce_rust_tpu.analysis.lint import (
    lint_file,
    lint_paths,
    load_baseline,
)


def run_lint(tmp_path, src, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    findings, errors, suppressed = lint_file(str(p))
    assert not errors, errors
    return findings, suppressed


def rules_fired(tmp_path, src, name="snippet.py"):
    findings, _ = run_lint(tmp_path, src, name)
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# stats-ownership
# ---------------------------------------------------------------------------

def test_stats_ownership_fires_on_pool_submitted_mutation(tmp_path):
    findings, _ = run_lint(tmp_path, """
        def scan_window(item, stats):
            stats.host_map_s += 1.0   # the PR 2 bug: worker mutates stats
            return item

        def engine(pool, stats, items):
            for it in items:
                pool.submit(scan_window, it, stats)
    """)
    assert [f.rule for f in findings] == ["stats-ownership"]
    assert "consumer thread" in findings[0].message


def test_stats_ownership_fires_on_self_stats_via_method(tmp_path):
    assert rules_fired(tmp_path, """
        class Stream:
            def _work(self):
                self.stats.chunks = self.stats.chunks + 1

            def go(self, pool):
                pool.submit(self._work)
    """) == ["stats-ownership"]


def test_stats_ownership_silent_on_pure_worker(tmp_path):
    assert rules_fired(tmp_path, """
        def scan_window(item):
            return len(item)          # pure: returns, never mutates

        def engine(pool, stats, items):
            for it in items:
                pool.submit(scan_window, it)
            stats.host_map_s += 1.0   # consumer-thread fold is fine
    """) == []


def test_stats_ownership_silent_on_unsubmitted_mutator(tmp_path):
    # Mutating stats is fine for functions that never enter a pool.
    assert rules_fired(tmp_path, """
        def consume(result, stats):
            stats.chunks += 1
    """) == []


# ---------------------------------------------------------------------------
# executor-teardown
# ---------------------------------------------------------------------------

def test_executor_teardown_fires_without_shutdown(tmp_path):
    findings, _ = run_lint(tmp_path, """
        from concurrent.futures import ThreadPoolExecutor

        def engine(items):
            pool = ThreadPoolExecutor(max_workers=4)
            for it in items:
                pool.submit(print, it)
    """)
    assert [f.rule for f in findings] == ["executor-teardown"]
    assert "never reaches shutdown" in findings[0].message


def test_executor_teardown_fires_on_shutdown_outside_finally(tmp_path):
    findings, _ = run_lint(tmp_path, """
        from concurrent.futures import ThreadPoolExecutor

        def engine(items):
            pool = ThreadPoolExecutor(max_workers=4)
            for it in items:
                pool.submit(print, it)
            pool.shutdown(wait=True, cancel_futures=True)  # skipped on raise
    """)
    assert [f.rule for f in findings] == ["executor-teardown"]
    assert "outside any finally" in findings[0].message


def test_executor_teardown_fires_without_cancel_futures(tmp_path):
    findings, _ = run_lint(tmp_path, """
        from concurrent.futures import ThreadPoolExecutor

        def engine(items):
            pool = ThreadPoolExecutor(max_workers=4)
            try:
                for it in items:
                    pool.submit(print, it)
            finally:
                pool.shutdown(wait=True)   # queued work still runs
    """)
    assert [f.rule for f in findings] == ["executor-teardown"]
    assert "cancel_futures" in findings[0].message


def test_executor_teardown_fires_on_attr_pool_without_teardown(tmp_path):
    assert rules_fired(tmp_path, """
        from concurrent.futures import ThreadPoolExecutor

        class Stream:
            def __init__(self):
                self.pool = ThreadPoolExecutor(max_workers=2)
    """) == ["executor-teardown"]


def test_executor_teardown_good_patterns_are_silent(tmp_path):
    assert rules_fired(tmp_path, """
        from concurrent.futures import ThreadPoolExecutor

        def ctx(items):
            with ThreadPoolExecutor(max_workers=4) as pool:
                for it in items:
                    pool.submit(print, it)

        def fin(items):
            pool = ThreadPoolExecutor(max_workers=4)
            try:
                for it in items:
                    pool.submit(print, it)
            finally:
                pool.shutdown(wait=True, cancel_futures=True)

        class Stream:
            def __init__(self):
                self.pool = ThreadPoolExecutor(max_workers=2)

            def close(self):
                self.pool.shutdown(wait=True, cancel_futures=True)
    """) == []


# ---------------------------------------------------------------------------
# tmpdir-cleanup
# ---------------------------------------------------------------------------

def test_tmpdir_cleanup_fires_without_finally(tmp_path):
    assert rules_fired(tmp_path, """
        import tempfile

        def egress(out_dir):
            tmpdir = tempfile.mkdtemp(prefix="egress-", dir=out_dir)
            open(tmpdir + "/part-0", "wb").close()
    """) == ["tmpdir-cleanup"]


def test_tmpdir_cleanup_silent_with_finally_rmtree(tmp_path):
    assert rules_fired(tmp_path, """
        import shutil
        import tempfile

        def egress(out_dir):
            tmpdir = tempfile.mkdtemp(prefix="egress-", dir=out_dir)
            try:
                open(tmpdir + "/part-0", "wb").close()
            finally:
                shutil.rmtree(tmpdir, ignore_errors=True)
    """) == []


# ---------------------------------------------------------------------------
# a2a-purity
# ---------------------------------------------------------------------------

def test_a2a_purity_fires_on_readback_inside_span(tmp_path):
    findings, _ = run_lint(tmp_path, """
        import jax
        import numpy as np

        def run_round(stats, step):
            with _a2a_span(stats, round=1):
                out = step()
                n = int(np.asarray(jax.device_get(out)).sum())
            return n
    """)
    assert sorted({f.rule for f in findings}) == ["a2a-purity"]
    assert len(findings) == 2  # asarray AND device_get


def test_a2a_purity_silent_when_fetch_moved_after_span(tmp_path):
    assert rules_fired(tmp_path, """
        import jax
        import numpy as np

        def run_round(stats, step):
            with _a2a_span(stats, round=1):
                out = step()
            n = int(np.asarray(jax.device_get(out)).sum())
            return n
    """) == []


# ---------------------------------------------------------------------------
# span-balance
# ---------------------------------------------------------------------------

def test_span_balance_fires_on_manual_span(tmp_path):
    findings, _ = run_lint(tmp_path, """
        from mapreduce_rust_tpu.runtime.trace import trace_span

        def leaky():
            span = trace_span("chunk")   # never balanced on an exception
            span.__enter__()
    """)
    assert [f.rule for f in findings] == ["span-balance"]


def test_span_balance_silent_on_with(tmp_path):
    assert rules_fired(tmp_path, """
        from mapreduce_rust_tpu.runtime.trace import trace_span

        def fine(stats):
            with trace_span("chunk", n=1):
                pass
            with _a2a_span(stats, round=2):
                pass
    """) == []


# ---------------------------------------------------------------------------
# spilled-dict-api
# ---------------------------------------------------------------------------

def test_spilled_dict_api_fires_on_budgeted_instance_probes(tmp_path):
    findings, _ = run_lint(tmp_path, """
        from mapreduce_rust_tpu.runtime.dictionary import Dictionary

        def egress(work):
            d = Dictionary(budget_words=4, spill_dir=work)
            if (1, 2) in d:
                return dict(d.items())
    """)
    assert [f.rule for f in findings] == ["spilled-dict-api"] * 2


def test_spilled_dict_api_fires_on_unknown_provenance_convention_name(tmp_path):
    # `dictionary` handed in from elsewhere may carry a budget — the exact
    # shape of the worker shard-partition bug this rule caught.
    assert rules_fired(tmp_path, """
        def shard(dictionary, reduce_n):
            return [(k, w) for k, w in dictionary.items()]
    """) == ["spilled-dict-api"]


def test_spilled_dict_api_silent_on_provably_ram_only(tmp_path):
    assert rules_fired(tmp_path, """
        from mapreduce_rust_tpu.runtime.dictionary import Dictionary

        def shard(reduce_n):
            d = Dictionary()          # no budget: cannot spill
            d.add_words([b"x"])
            return dict(d.items())

        def plain_dicts(table):
            return sorted(table.items())   # builtin dicts are not Dictionaries
    """) == []


def test_spilled_dict_api_silent_on_iter_sorted(tmp_path):
    assert rules_fired(tmp_path, """
        def egress(dictionary):
            for _p, k1, k2, w in dictionary.iter_sorted():
                yield k1, k2, w
    """) == []


# ---------------------------------------------------------------------------
# jit-in-loop
# ---------------------------------------------------------------------------

def test_jit_in_loop_fires_on_call_and_decorator(tmp_path):
    findings, _ = run_lint(tmp_path, """
        import jax

        def stream(chunks, step):
            for c in chunks:
                f = jax.jit(step)     # re-traces per chunk
                f(c)

        def stream2(chunks):
            while chunks:
                @jax.jit
                def step(x):
                    return x
                step(chunks.pop())
    """)
    assert [f.rule for f in findings] == ["jit-in-loop"] * 2


def test_jit_in_loop_silent_outside_loops_and_on_cached_factories(tmp_path):
    assert rules_fired(tmp_path, """
        import jax

        def stream(chunks, step, app):
            f = jax.jit(step)         # built once
            for c in chunks:
                fns = make_step_fns(app, 128)   # cached factory is fine
                f(c)
    """) == []


# ---------------------------------------------------------------------------
# suppression mechanics + output formats
# ---------------------------------------------------------------------------

def test_psum_replicated_flag_fires_on_nested_psum(tmp_path):
    assert rules_fired(tmp_path, """
        import jax

        def round_flag(flags, AXIS):
            return jax.lax.psum(jax.lax.psum(flags, AXIS), AXIS)
    """) == ["psum-replicated-flag"]


def test_psum_replicated_flag_fires_on_repsummed_name(tmp_path):
    findings, _ = run_lint(tmp_path, """
        import jax

        def tail(p_ovf, AXIS):
            p_tot = jax.lax.psum(p_ovf, AXIS)
            # the misuse: p_tot is identical on every chip already —
            # psumming it again multiplies the flag by D
            return jax.lax.psum(p_tot, AXIS)
    """)
    assert [f.rule for f in findings] == ["psum-replicated-flag"]
    assert "axis size" in findings[0].message


def test_psum_replicated_flag_silent_on_single_psum(tmp_path):
    # The shipped pattern (_chip_shuffle_tail / make_round_fn): per-chip
    # counters psum exactly once, the replicated total is then read or
    # compared, never re-psummed.
    assert rules_fired(tmp_path, """
        import jax

        def tail(p_ovf, b_ovf, local, AXIS, clamp_batch):
            p_tot = jax.lax.psum(p_ovf, AXIS)
            b_tot = jax.lax.psum(b_ovf, AXIS)
            return clamp_batch(local, (p_tot + b_tot) == 0)
    """) == []


def test_psum_replicated_flag_silent_on_single_psum_rebinding(tmp_path):
    # `x = psum(x, AXIS)` is ONE psum whose argument is the pre-assignment
    # per-chip value — the definition must not poison its own call site.
    assert rules_fired(tmp_path, """
        import jax

        def tail(flags, AXIS):
            flags = jax.lax.psum(flags, AXIS)
            return flags
    """) == []
    # ...but re-psumming the rebound name LATER is still the bug.
    assert rules_fired(tmp_path, """
        import jax

        def tail(flags, AXIS):
            flags = jax.lax.psum(flags, AXIS)
            return jax.lax.psum(flags, AXIS)
    """) == ["psum-replicated-flag"]


def test_psum_replicated_flag_scopes_per_function(tmp_path):
    # A replicated name in one function must not poison an unrelated
    # function's single psum of a same-named per-chip value.
    assert rules_fired(tmp_path, """
        import jax

        def a(x, AXIS):
            tot = jax.lax.psum(x, AXIS)
            return tot

        def b(tot, AXIS):
            return jax.lax.psum(tot, AXIS)  # its OWN per-chip arg
    """) == []


# ---------------------------------------------------------------------------
# unbounded-retry
# ---------------------------------------------------------------------------

def test_unbounded_retry_fires_on_constant_sleep_in_except(tmp_path):
    findings, _ = run_lint(tmp_path, """
        import time

        def connect_forever(host):
            while True:
                try:
                    return open_connection(host)
                except OSError:
                    time.sleep(0.1)   # the ISSUE 6 bug class: fixed-rate
                                      # retry, forever, error never surfaces
    """)
    assert [f.rule for f in findings] == ["unbounded-retry"]
    assert "Backoff" in findings[0].message


def test_unbounded_retry_fires_on_exitless_constant_poll(tmp_path):
    assert rules_fired(tmp_path, """
        import time

        def poll(worker):
            while True:
                worker.tick()
                time.sleep(1.0)       # no break/return/raise: spins forever
    """) == ["unbounded-retry"]


def test_unbounded_retry_fires_on_unreassigned_name_delay(tmp_path):
    # A delay held in a variable that never changes inside the loop is
    # still a constant sleep.
    assert rules_fired(tmp_path, """
        import time

        def retry(fn, delay):
            while True:
                try:
                    return fn()
                except ValueError:
                    time.sleep(delay)
    """) == ["unbounded-retry"]


def test_unbounded_retry_silent_on_backoff_delays(tmp_path):
    # The shipped-fix pattern: delays drawn from a Backoff — a call, so
    # the delay is assumed to grow.
    assert rules_fired(tmp_path, """
        import time
        from mapreduce_rust_tpu.runtime.backoff import Backoff

        def retry(fn):
            backoff = Backoff(0.05, 2.0, budget_s=60.0)
            while True:
                try:
                    return fn()
                except ValueError:
                    time.sleep(backoff.next_delay())
    """) == []


def test_unbounded_retry_silent_on_bounded_and_conditioned_loops(tmp_path):
    assert rules_fired(tmp_path, """
        import time

        def bounded(fn, retries=5):
            for attempt in range(retries):   # a For is inherently bounded
                try:
                    return fn()
                except ValueError:
                    if attempt == retries - 1:
                        raise
                    time.sleep(0.1)

        def conditioned(stop):
            while not stop.is_set():         # the test IS the stop condition
                time.sleep(0.2)

        def raising(fn):
            attempt = 0
            while True:
                try:
                    return fn()
                except ValueError:
                    attempt += 1
                    if attempt > 3:
                        raise                # bounded by the raise
                    time.sleep(0.1)

        def growing(fn):
            delay = 0.1
            while True:
                try:
                    return fn()
                except ValueError:
                    time.sleep(delay)
                    delay = delay * 2        # reassigned: a hand-rolled backoff
    """) == []


# ---------------------------------------------------------------------------
# metric-in-hot-loop (ISSUE 8)
# ---------------------------------------------------------------------------

def test_metric_in_hot_loop_fires_on_registry_inc_per_record(tmp_path):
    findings, _ = run_lint(tmp_path, """
        def fold_scan_into_dictionary(dictionary, rows, registry):
            for word, count in rows:
                dictionary.add(word, count)
                registry.counter("records").inc()   # per-record lock+dict
    """)
    assert [f.rule for f in findings] == ["metric-in-hot-loop"]
    assert "per record" in findings[0].message


def test_metric_in_hot_loop_fires_on_clock_and_bound_instrument(tmp_path):
    findings, _ = run_lint(tmp_path, """
        import time

        def _pack_update(rows, registry):
            h = registry.histogram("pack_s")
            out = []
            for r in rows:
                t0 = time.perf_counter()    # wall-clock read per record
                out.append(pack(r))
                h.observe(time.perf_counter() - t0)  # bisect per record
            return out
    """)
    fired = sorted(f.rule for f in findings)
    assert fired == ["metric-in-hot-loop"] * len(fired) and len(findings) >= 2


def test_metric_in_hot_loop_fires_on_hist_and_tick_in_loop(tmp_path):
    assert rules_fired(tmp_path, """
        def _fold(self, spill):
            for key, rows in spill:
                self.merge(key, rows)
                self.stats.record_hist("fold_s", 0.0)  # per-record bisect
                metrics_tick()                          # per-record sampler
    """) == ["metric-in-hot-loop"]


def test_metric_in_hot_loop_silent_outside_loop_and_scope(tmp_path):
    # The shipped pattern: accumulate in the loop, record ONCE after —
    # and the same calls in a non-hot function never match.
    assert rules_fired(tmp_path, """
        import time

        def fold_scan_into_dictionary(dictionary, rows, stats, registry):
            t0 = time.perf_counter()
            n = 0
            for word, count in rows:
                dictionary.add(word, count)
                n += 1
            stats.record_hist("fold_s", time.perf_counter() - t0)
            registry.counter("records").inc(n)
            metrics_tick()

        def consume_window(window, registry):
            for chunk in window:           # not a named hot scope
                registry.counter("chunks").inc()
                time.time()
    """) == []


def test_metric_in_hot_loop_silent_on_plain_set_calls(tmp_path):
    # `set` is a mutator verb, but only on metric-ish receivers: plain
    # dataclass/dict mutation in the fold must not fire.
    assert rules_fired(tmp_path, """
        def _insert_hashed(self, hashes, counts):
            for h, c in zip(hashes, counts):
                self.table.set(h, c)        # receiver is not a registry
                self.flags.set()
    """) == []


BAD_SNIPPET = """
    def shard(dictionary):
        return list(dictionary.items())
"""


def test_inline_ignore_with_reason_suppresses(tmp_path):
    findings, suppressed = run_lint(tmp_path, """
        def shard(dictionary):
            # mrlint: ignore[spilled-dict-api] -- provably RAM-only here
            return list(dictionary.items())
    """)
    assert findings == [] and suppressed == 1


def test_inline_ignore_without_reason_is_reported(tmp_path):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent("""
        def shard(dictionary):
            # mrlint: ignore[spilled-dict-api]
            return list(dictionary.items())
    """))
    findings, errors, _ = lint_file(str(p))
    assert [f.rule for f in findings] == ["spilled-dict-api"]
    assert [e.rule for e in errors] == ["bad-suppression"]


def test_ignore_in_string_literal_does_not_suppress(tmp_path):
    findings, suppressed = run_lint(tmp_path, """
        MARKER = "# mrlint: ignore[spilled-dict-api] -- not a comment"

        def shard(dictionary):
            return list(dictionary.items())
    """)
    assert [f.rule for f in findings] == ["spilled-dict-api"]
    assert suppressed == 0


def test_baseline_suppresses_and_tracks_unused(tmp_path):
    p = tmp_path / "legacy.py"
    p.write_text(textwrap.dedent(BAD_SNIPPET))
    baseline = [
        {"rule": "spilled-dict-api", "path": "*legacy.py",
         "reason": "grandfathered until the shard rewrite"},
        {"rule": "jit-in-loop", "path": "*never.py", "reason": "unused"},
    ]
    report = lint_paths([str(p)], baseline)
    assert report.ok and report.baselined == 1
    assert [e["path"] for e in report.unused_baseline] == ["*never.py"]


def test_baseline_requires_reasons(tmp_path):
    bad = tmp_path / ".mrlint.json"
    bad.write_text(json.dumps(
        {"suppressions": [{"rule": "jit-in-loop", "path": "*"}]}
    ))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(bad))
    good = tmp_path / "ok.json"
    good.write_text(json.dumps({"suppressions": [
        {"rule": "*", "path": "x.py", "reason": "because"},
    ]}))
    assert load_baseline(str(good))[0]["rule"] == "*"
    # A bare-list baseline is a config error (exit 2 via run_cli), never
    # an AttributeError traceback.
    arr = tmp_path / "arr.json"
    arr.write_text(json.dumps([{"rule": "x"}]))
    with pytest.raises(ValueError, match="suppressions"):
        load_baseline(str(arr))


def test_json_report_schema(tmp_path):
    p = tmp_path / "legacy.py"
    p.write_text(textwrap.dedent(BAD_SNIPPET))
    report = lint_paths([str(p)])
    doc = report.to_dict()
    assert doc["tool"] == "mrlint" and doc["schema"] == 1
    assert doc["ok"] is False and doc["files_checked"] == 1
    assert len(doc["rules"]) >= 8
    (f,) = doc["findings"]
    assert set(f) == {"rule", "path", "line", "col", "message"}
    assert f["rule"] == "spilled-dict-api"
    json.dumps(doc)  # machine-readable by construction


def test_cli_exits_2_when_explicit_paths_match_nothing(tmp_path, capsys):
    # A mistyped CI target must be a config error, never a clean pass.
    from mapreduce_rust_tpu.__main__ import main

    assert main(["lint", str(tmp_path / "nonexistent")]) == 2
    assert "nothing checked" in capsys.readouterr().err
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["lint", str(empty)]) == 2


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def broken(:\n")
    report = lint_paths([str(p)])
    assert not report.ok
    assert [e.rule for e in report.parse_errors] == ["parse-error"]


# ---------------------------------------------------------------------------
# Interprocedural dataflow rules (ISSUE 7: the CFG/reaching-defs layer).
# These run once over the whole file set via lint_paths — lint_file stays
# per-file — so the fixtures drive lint_paths.
# ---------------------------------------------------------------------------

def program_rules_fired(tmp_path, src, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    report = lint_paths([str(p)])
    assert not report.parse_errors, report.parse_errors
    return sorted({f.rule for f in report.findings}), report


def test_blocking_in_async_fires_on_direct_sleep(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        import time

        async def renewal_loop():
            time.sleep(1.0)      # starves every coroutine on the loop
    """)
    assert fired == ["blocking-in-async"]
    assert "renewal_loop" in report.findings[0].message


def test_blocking_in_async_follows_sync_helpers(tmp_path):
    # The shipped-bug shape: the blocking call hides two frames down.
    fired, report = program_rules_fired(tmp_path, """
        import subprocess

        def git_rev():
            return subprocess.run(["git", "rev-parse", "HEAD"])

        def flush_manifest():
            return git_rev()

        async def teardown():
            flush_manifest()
    """)
    assert fired == ["blocking-in-async"]
    msg = report.findings[0].message
    assert "via" in msg and "flush_manifest" in msg and "git_rev" in msg


def test_blocking_in_async_fires_on_from_import(tmp_path):
    fired, _ = program_rules_fired(tmp_path, """
        from time import sleep

        async def poll():
            sleep(0.1)
    """)
    assert fired == ["blocking-in-async"]


def test_blocking_in_async_silent_on_executor_handoff(tmp_path):
    # run_in_executor is the LEGAL boundary: the callable runs on a pool
    # thread, exactly how blocking compute coexists with the event loop.
    fired, _ = program_rules_fired(tmp_path, """
        import asyncio
        import time

        def heavy_task(tid):
            time.sleep(1.0)      # fine: pool thread, not the loop

        async def task_loop():
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, heavy_task, 0)
    """)
    assert fired == []


def test_blocking_in_async_silent_on_lambda_handoff(tmp_path):
    # A lambda handed to the executor defers its WHOLE body to the pool
    # thread — as legal as a bare callable reference.
    fired, _ = program_rules_fired(tmp_path, """
        import asyncio
        import time

        async def task_loop():
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, lambda: time.sleep(1.0))
    """)
    assert fired == []


def test_blocking_in_async_fires_on_eager_call_argument(tmp_path):
    # submit(build_payload()) runs build_payload on the CALLER's thread —
    # the handoff only ships its return value; the blocking call still
    # lands on the event loop.
    fired, report = program_rules_fired(tmp_path, """
        import subprocess

        def build_payload():
            return subprocess.run(["tar", "c", "."])

        async def ship(pool):
            pool.submit(build_payload())
    """)
    assert fired == ["blocking-in-async"]
    assert "build_payload" in report.findings[0].message


def test_blocking_in_async_silent_on_async_sleep_and_sync_only(tmp_path):
    fired, _ = program_rules_fired(tmp_path, """
        import asyncio
        import time

        async def poll():
            await asyncio.sleep(0.1)

        def sync_only():
            time.sleep(1.0)      # never reached from an async def
    """)
    assert fired == []


def test_backend_init_in_probe_fires_unguarded(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        import jax

        def sample_device_memory(stats):
            for dev in jax.local_devices():   # triggers backend init
                stats.high = dev.memory_stats()
    """)
    assert fired == ["backend-init-in-probe"]
    assert "_backends" in report.findings[0].message


def test_backend_init_in_probe_fires_through_helper(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        import jax

        def _grab():
            return jax.local_devices()

        def platform_info():
            return _grab()
    """)
    assert fired == ["backend-init-in-probe"]
    assert "platform_info" in report.findings[0].message


def test_backend_init_in_probe_silent_with_guard(tmp_path):
    # The shipped fix (PR 6 worker wedge): the _backends early-exit
    # dominates the device call — including inside try/except, which is
    # where the driver's gauge lives.
    fired, _ = program_rules_fired(tmp_path, """
        import jax

        def sample_device_memory(stats):
            try:
                from jax._src import xla_bridge

                if not xla_bridge._backends:
                    return
                for dev in jax.local_devices():
                    stats.high = dev.memory_stats()
            except Exception:
                pass
    """)
    assert fired == []


def test_backend_init_in_probe_silent_when_guarded_at_call_site(tmp_path):
    # The probe checks BEFORE descending into the helper: the hop is
    # covered even though the helper itself has no guard.
    fired, _ = program_rules_fired(tmp_path, """
        import jax

        def _grab():
            return jax.local_devices()

        def sample_memory():
            from jax._src import xla_bridge

            if not xla_bridge._backends:
                return None
            return _grab()
    """)
    assert fired == []


def test_backend_init_in_probe_ignores_non_probe_functions(tmp_path):
    # Device access outside the telemetry naming convention is the data
    # plane's business (it WANTS backend init), not this rule's.
    fired, _ = program_rules_fired(tmp_path, """
        import jax

        def run_job():
            return jax.devices()
    """)
    assert fired == []


def test_nondeterministic_partition_fires_on_set_into_shard_index(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        def partition(words, reduce_n, out):
            seen = set(words)
            for w in seen:                      # hash-randomized order
                out[hash(w) % reduce_n].append(w)
    """)
    assert fired == ["nondeterministic-partition-input"]
    assert "sorted" in report.findings[0].message


def test_nondeterministic_partition_follows_aliases(tmp_path):
    # The reaching-defs chain: an alias must not hide the set.
    fired, _ = program_rules_fired(tmp_path, """
        def partition(words, reduce_n, out):
            seen = {w for w in words}
            pending = seen
            for w in pending:
                out[hash(w) % reduce_n].append(w)
    """)
    assert fired == ["nondeterministic-partition-input"]


def test_nondeterministic_partition_silent_on_sorted_and_dicts(tmp_path):
    fired, _ = program_rules_fired(tmp_path, """
        def partition(words, reduce_n, out):
            seen = set(words)
            for w in sorted(seen):              # the shipped pattern
                out[hash(w) % reduce_n].append(w)

        def dict_partition(counts, reduce_n, out):
            for w in counts:                    # insertion-ordered
                out[hash(w) % reduce_n].append(w)
    """)
    assert fired == []


def test_nondeterministic_partition_silent_off_the_partition_path(tmp_path):
    # Unordered iteration is fine when no shard/partition index depends
    # on the order.
    fired, _ = program_rules_fired(tmp_path, """
        def count(words):
            total = 0
            for w in set(words):
                total += 1
            return total
    """)
    assert fired == []


def test_program_rule_findings_obey_inline_ignores(tmp_path):
    _, report = program_rules_fired(tmp_path, """
        import time

        async def poll():
            time.sleep(0.1)  # mrlint: ignore[blocking-in-async] -- fixture
    """)
    assert report.findings == [] and report.suppressed == 1


def test_strict_baseline_promotes_unused_entries(tmp_path, capsys):
    from mapreduce_rust_tpu.__main__ import main

    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    baseline = tmp_path / ".mrlint.json"
    baseline.write_text(json.dumps({"suppressions": [
        {"rule": "jit-in-loop", "path": "*gone.py",
         "reason": "stale suppression nothing matches"},
    ]}))
    # Default: a warning only — the lint itself is clean.
    assert main(["lint", str(clean), "--baseline", str(baseline)]) == 0
    capsys.readouterr()
    # --strict-baseline: the stale entry IS the failure (it would swallow
    # a real finding at that path tomorrow).
    assert main(["lint", str(clean), "--baseline", str(baseline),
                 "--strict-baseline"]) == 1
    assert "unused baseline" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Dataflow layer units (analysis/dataflow.py)
# ---------------------------------------------------------------------------

def _program(src):
    import ast as _ast

    from mapreduce_rust_tpu.analysis.dataflow import Program
    from mapreduce_rust_tpu.analysis.lint import attach_parents

    tree = _ast.parse(textwrap.dedent(src))
    attach_parents(tree)
    return Program([("snippet.py", tree)])


def test_dataflow_guarded_reach_branch_sensitivity():
    import ast as _ast

    from mapreduce_rust_tpu.analysis.dataflow import guarded_reach

    prog = _program("""
        def guarded(b):
            if not b._backends:
                return
            b.probe()

        def unguarded(b):
            if b.other:
                pass
            b.probe()

        def wrong_branch(b):
            if b._backends:
                return          # inverted: present means BAIL
            b.probe()
    """)
    for fu in prog.functions:
        call = next(
            n for n in _ast.walk(fu.node)
            if isinstance(n, _ast.Call) and n.func.attr == "probe"
        )
        assert guarded_reach(fu.cfg, call, "_backends") is (
            fu.name == "guarded"
        ), fu.name


def test_dataflow_origins_follow_copy_chains():
    import ast as _ast

    from mapreduce_rust_tpu.analysis.dataflow import origins

    prog = _program("""
        def f(xs):
            a = set(xs)
            b = a
            for w in b:
                pass
    """)
    fu = prog.functions[0]
    loop = next(n for n in _ast.walk(fu.node) if isinstance(n, _ast.For))
    defs, reach = fu.rd
    (origin,) = origins(fu.cfg, defs, reach, loop.iter)
    assert isinstance(origin, _ast.Call) and origin.func.id == "set"


def test_dataflow_call_graph_excludes_executor_handoffs():
    prog = _program("""
        def work():
            pass

        def direct():
            work()

        def handoff(pool):
            pool.submit(work)
    """)
    by = {fu.name: fu for fu in prog.functions}
    assert [t.name for _c, t in prog.callees(by["direct"]) if t] == ["work"]
    assert [t for _c, t in prog.callees(by["handoff"])] == [None]


def test_dataflow_resolve_prefers_same_class_then_is_conservative():
    prog = _program("""
        class A:
            def helper(self):
                pass

            def go(self):
                self.helper()

        class B:
            def helper(self):
                pass
    """)
    go = next(fu for fu in prog.functions if fu.name == "go")
    (call, target), = [(c, t) for c, t in prog.callees(go)]
    assert target is not None and target.qualname == "A.helper"
    # A bare ambiguous name (A.helper vs B.helper, neither preferred by
    # the self. heuristic) resolves to no edge: precision over recall.
    prog2 = _program("""
        class A:
            def helper(self):
                pass

        class B:
            def helper(self):
                pass

        def go():
            helper()
    """)
    go2 = next(fu for fu in prog2.functions if fu.name == "go")
    assert [t for _c, t in prog2.callees(go2)] == [None]


# ---------------------------------------------------------------------------
# Rule 12 cross-shard-fold (ISSUE 9): a function holding one shard index
# must never fold into another shard's dictionary.
# ---------------------------------------------------------------------------

def test_cross_shard_fold_fires_on_foreign_constant_index(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        def fold(shard_idx, shards, raw, ends, keys):
            shards[0].add_scanned_raw(raw, ends, keys)
    """)
    assert fired == ["cross-shard-fold"]
    assert "shard_idx" in report.findings[0].message


def test_cross_shard_fold_fires_through_alias(tmp_path):
    # The dataflow layer's reaching-defs must see through the copy: the
    # mutation receiver ALIASES a foreign-indexed shard subscript.
    fired, _ = program_rules_fired(tmp_path, """
        def fold(shard_idx, other, shards, words, keys):
            d = shards[other]
            d.add_scanned(words, keys)
    """)
    assert fired == ["cross-shard-fold"]


def test_cross_shard_fold_fires_on_fold_helper_handoff(tmp_path):
    # One-call-hop shape: a DIFFERENT shard's dictionary handed straight
    # to a fold helper that will mutate it.
    fired, _ = program_rules_fired(tmp_path, """
        def route(shard_idx, victim, shards, mask, parts):
            fold_scan_into_dictionary(shards[victim], mask, "raw", parts)
    """)
    assert fired == ["cross-shard-fold"]


def test_cross_shard_fold_silent_on_own_shard_and_params(tmp_path):
    # Own index (direct or aliased), index expressions that mention the
    # shard param, and receivers arriving as plain parameters (the fold
    # plane's _fold_one shape) all stay silent.
    fired, _ = program_rules_fired(tmp_path, """
        def fold(shard_idx, shards, raw, ends, keys, words, keys2):
            shards[shard_idx].add_scanned_raw(raw, ends, keys)
            d = shards[shard_idx]
            d.add_scanned(words, keys2)

        def route(shard_idx, shards, mask, parts):
            fold_scan_into_dictionary(shards[shard_idx], mask, "raw", parts)

        def fold_one(s, shard, words, keys):
            shard.add_scanned(words, keys)
    """)
    assert fired == []


def test_cross_shard_fold_silent_without_shard_param(tmp_path):
    # No shard-index parameter in scope: nothing to contradict (the
    # router legitimately touches every shard's queue).
    fired, _ = program_rules_fired(tmp_path, """
        def egress(shards, k1, k2):
            return shards[(k1 << 32 | k2) % len(shards)].lookup(k1, k2)
    """)
    assert fired == []


# ---------------------------------------------------------------------------
# Rule 13 blocking-io-in-fold (ISSUE 11): the fold/consumer hot scopes do
# file I/O only through the async spill-writer handoff.
# ---------------------------------------------------------------------------

def test_blocking_io_in_fold_fires_on_direct_open(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        def _fold_one(shard, item):
            with open("/tmp/run.bin", "wb") as f:
                f.write(item)
    """)
    assert fired == ["blocking-io-in-fold"]
    assert "_fold_one" in report.findings[0].message


def test_blocking_io_in_fold_follows_sync_helpers(tmp_path):
    # The pre-ISSUE-11 shipped shape: the run write hides one frame down
    # from the fold mutator (_flush_words called open inline).
    fired, report = program_rules_fired(tmp_path, """
        def write_run(path, raw):
            f = open(path, "wb")
            f.write(raw)
            f.flush()

        def _flush_words(path, raw):
            write_run(path, raw)

        def _maybe_flush(path, raw):
            _flush_words(path, raw)
    """)
    assert fired == ["blocking-io-in-fold"]
    assert "via" in report.findings[0].message


def test_blocking_io_in_fold_fires_on_np_save(tmp_path):
    fired, _ = program_rules_fired(tmp_path, """
        import numpy as np

        def _flush_run(rows, path):
            with open(path, "wb") as f:
                np.save(f, rows)
    """)
    assert fired == ["blocking-io-in-fold"]


def test_blocking_io_in_fold_silent_on_writer_handoff(tmp_path):
    # The sanctioned shape: freeze a snapshot, submit the task — the
    # executor-sink boundary makes the task's body the WRITER thread's
    # business, exactly like run_in_executor for blocking-in-async.
    fired, _ = program_rules_fired(tmp_path, """
        def _write_run(path, snapshot):
            with open(path, "wb") as f:
                f.write(snapshot)

        def _flush_words(self, path):
            snapshot = dict(self.words)
            self.writer.submit(lambda: _write_run(path, snapshot))

        def add_scanned_raw(self, path):
            self._flush_words(path)
    """)
    assert fired == []


def test_blocking_io_in_fold_silent_on_throttled_snapshot(tmp_path):
    # maybe_snapshot/metrics_tick frames are exempt: the flight recorder
    # and the sampler own their throttling budgets.
    fired, _ = program_rules_fired(tmp_path, """
        def maybe_snapshot(buf, path):
            with open(path, "w") as f:
                f.write(buf)

        def consume(result, buf, path):
            maybe_snapshot(buf, path)
    """)
    assert fired == []


def test_blocking_io_in_fold_silent_outside_hot_scopes(tmp_path):
    # The same I/O in a non-hot function (egress, checkpoints) is fine.
    fired, _ = program_rules_fired(tmp_path, """
        def _stream_finalize(path, lines):
            with open(path, "wb") as f:
                for line in lines:
                    f.write(line)
    """)
    assert fired == []


# ---------------------------------------------------------------------------
# Rule 14 device-dispatch-in-consumer (ISSUE 13): the consume/fold hot
# scopes book no device hop themselves — windows go through the dispatch
# plane's submit handoff.
# ---------------------------------------------------------------------------

def test_device_dispatch_fires_on_inline_device_put(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        import jax

        def consume(result, device):
            jax.device_put(result, device)
    """)
    assert fired == ["device-dispatch-in-consumer"]
    assert "consume" in report.findings[0].message


def test_device_dispatch_follows_sync_helpers(tmp_path):
    # The pre-ISSUE-13 shipped shape: the hop hides one frame down from
    # the consumer (pack_and_merge called device_put inline).
    fired, report = program_rules_fired(tmp_path, """
        import jax

        def pack_and_merge(flat, device):
            return jax.device_put(flat, device)

        def consume(result, device):
            pack_and_merge(result, device)
    """)
    assert fired == ["device-dispatch-in-consumer"]
    assert "via" in report.findings[0].message


def test_device_dispatch_fires_on_packed_merge_closure(tmp_path):
    # Invoking a make_packed_merge_fn(...) product inside the consumer is
    # a device hop even without a visible device_put (reaching defs
    # resolve the closure's origin through the alias).
    fired, _ = program_rules_fired(tmp_path, """
        def consume(state, flat, app, cap):
            merge_packed = make_packed_merge_fn(app, cap)
            state, evicted, n = merge_packed(state, flat)
            return state
    """)
    assert fired == ["device-dispatch-in-consumer"]


def test_device_dispatch_silent_on_plane_submit(tmp_path):
    # The sanctioned shape: the router hands the window to the dispatch
    # plane; frames below submit are the plane's own (its sync mode runs
    # them inline BY DESIGN — the A/B measurement path).
    fired, _ = program_rules_fired(tmp_path, """
        import jax

        class _DispatchPlane:
            def submit(self, item):
                self._handle(item)

            def _handle(self, item):
                flat = self.pack(item)
                jax.device_put(flat, self.device)

        def consume(self, result):
            self.dispatch.submit(result)
    """)
    assert fired == []


def test_device_dispatch_silent_outside_hot_scopes(tmp_path):
    # The same hop anywhere else (the stream setup, the drain loop of the
    # plane itself) is not this rule's business.
    fired, _ = program_rules_fired(tmp_path, """
        import jax

        def _stream_single(chunk, device):
            return jax.device_put(chunk, device)
    """)
    assert fired == []


# ---------------------------------------------------------------------------
# Rule 15 unsampled-range-partition (ISSUE 15): range-partition calls
# consume SAMPLER-derived splitters, never ad-hoc literals.
# ---------------------------------------------------------------------------

def test_range_partition_fires_on_literal_splitters(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        from mapreduce_rust_tpu.ops.partition import range_partition

        def route(keys):
            return range_partition(keys, [10, 20, 30])
    """)
    assert fired == ["unsampled-range-partition"]
    assert "sampler" in report.findings[0].message


def test_range_partition_fires_through_literal_alias(tmp_path):
    # The reaching-defs half: a name assigned from a literal container
    # (np.array over a list counts) cannot hide the provenance.
    fired, _ = program_rules_fired(tmp_path, """
        import numpy as np
        from mapreduce_rust_tpu.ops.partition import range_partition

        def route(keys):
            spl = np.array([10, 20, 30], dtype=np.uint64)
            return range_partition(keys, splitters=spl)
    """)
    assert fired == ["unsampled-range-partition"]


def test_range_partition_silent_on_sampler_derivation(tmp_path):
    fired, _ = program_rules_fired(tmp_path, """
        from mapreduce_rust_tpu.ops.partition import range_partition
        from mapreduce_rust_tpu.runtime.splitter import derive_splitters

        def route(keys, samples, reduce_n):
            spl = derive_splitters(samples, reduce_n)
            return range_partition(keys, spl)
    """)
    assert fired == []


def test_range_partition_silent_on_bound_app_splitters(tmp_path):
    # The bound-app seam: .splitters is written only by prepare_app, so
    # reading it (possibly through an asarray wrap) is sampler-derived.
    fired, _ = program_rules_fired(tmp_path, """
        import numpy as np
        from mapreduce_rust_tpu.ops.partition import range_partition

        def route_block(app, packed, reduce_n):
            return range_partition(
                packed, np.asarray(app.splitters, dtype=np.uint64)
            )
    """)
    assert fired == []


def test_range_bucket_scatter_audited_hash_mode_ignored(tmp_path):
    # The device twin: bucket_scatter(mode="range") is a range-partition
    # call site too; hash mode carries no splitters and stays silent.
    fired, _ = program_rules_fired(tmp_path, """
        from mapreduce_rust_tpu.ops.partition import bucket_scatter

        def shuffle_bad(batch, d, cap):
            return bucket_scatter(batch, d, cap, mode="range",
                                  splitters=[[0, 1], [2, 3]])

        def shuffle_ok(batch, d, cap):
            return bucket_scatter(batch, d, cap, mode="hash")
    """)
    assert fired == ["unsampled-range-partition"]


def test_range_partition_silent_on_unresolvable_value(tmp_path):
    # Precision over recall: a parameter (or foreign call) the dataflow
    # layer cannot resolve stays silent rather than crying wolf.
    fired, _ = program_rules_fired(tmp_path, """
        from mapreduce_rust_tpu.ops.partition import range_partition

        def route(keys, spl):
            return range_partition(keys, spl)
    """)
    assert fired == []


# ---------------------------------------------------------------------------
# Rule 16: unreaped-job-labels (ISSUE 16) — job=-labeled metric writes
# need a reachable remove_labels reap somewhere in the owning class.
# ---------------------------------------------------------------------------

def test_unreaped_job_labels_fires_without_reap(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        class Service:
            def __init__(self, registry):
                self.registry = registry

            def metrics_tick(self, jobs):
                g = self.registry
                for job in jobs:
                    g.gauge("job.grants").set(job.grants, job=job.jid)
                    g.gauge("job.bytes_in").set(job.bytes_in, job=job.jid)
    """)
    assert fired == ["unreaped-job-labels"]
    msg = report.findings[0].message
    assert "Service" in msg and "remove_labels" in msg


def test_unreaped_job_labels_silent_with_class_local_reap(tmp_path):
    # The shipped shape: the tick registers, _finalize_job reaps — both
    # methods of the same class.
    fired, _ = program_rules_fired(tmp_path, """
        class Service:
            def __init__(self, registry):
                self.registry = registry

            def metrics_tick(self, jobs):
                for job in jobs:
                    self.registry.gauge("job.grants").set(
                        job.grants, job=job.jid
                    )

            def finalize_job(self, job):
                for name in ("job.grants",):
                    self.registry.gauge(name).remove_labels(job=job.jid)
    """)
    assert fired == []


def test_unreaped_job_labels_silent_when_reap_is_reachable(tmp_path):
    # The reap may live in a helper the teardown method calls — the
    # sanction follows the sync call closure, not just the class body.
    fired, _ = program_rules_fired(tmp_path, """
        def reap_job_series(registry, jid):
            registry.gauge("job.grants").remove_labels(job=jid)

        class Service:
            def __init__(self, registry):
                self.registry = registry

            def metrics_tick(self, jobs):
                for job in jobs:
                    self.registry.gauge("job.grants").set(
                        job.grants, job=job.jid
                    )

            def finalize_job(self, job):
                reap_job_series(self.registry, job.jid)
    """)
    assert fired == []


def test_fifo_poll_in_scheduler_fires_on_admission_order_loop(tmp_path):
    # The shipped-bug shape: the pre-ISSUE-17 JobService.get_task — poll
    # running jobs in admission order, grant from the first with work.
    fired, report = program_rules_fired(tmp_path, """
        class JobService:
            def get_task(self, wid):
                for job in self.running.values():
                    c = job.coord
                    if not c.map.finished:
                        tid = c.get_map_task(wid)
                        if tid >= 0:
                            return {"job": job.jid, "tid": tid}
                        continue
                    tid = c.get_reduce_task(wid)
                    if tid >= 0:
                        return {"job": job.jid, "tid": tid}
                return -3
    """)
    assert fired == ["fifo-poll-in-scheduler"]
    msg = report.findings[0].message
    assert "get_task" in msg and "_sched_order" in msg


def test_fifo_poll_in_scheduler_silent_through_scoring_seam(tmp_path):
    # The shipped-fix shape: the grant loop iterates the scoring seam;
    # FIFO survives as a MODE inside it (admission order is the
    # tiebreak), which is exactly where the rule wants it.
    fired, _ = program_rules_fired(tmp_path, """
        class JobService:
            def _sched_order(self, wid):
                jobs = list(self.running.values())
                if not self.cfg.sched_pipeline:
                    return [(j, "map") for j in jobs]
                return sorted(
                    ((j, p) for j in jobs for p in ("map", "reduce")),
                    key=lambda t: -t[0].priority,
                )

            def get_task(self, wid):
                for job, phase in self._sched_order(wid):
                    tid = job.coord.get_map_task(wid)
                    if tid >= 0:
                        return {"job": job.jid, "tid": tid}
                return -3
    """)
    assert fired == []


def test_fifo_poll_in_scheduler_ignores_non_scheduler_scopes(tmp_path):
    # A bubble-accounting sweep over running jobs is not a grant loop,
    # and a grant loop outside a scheduler-named scope is some other
    # harness's business — both stay silent.
    fired, _ = program_rules_fired(tmp_path, """
        class JobService:
            def fleet_tick(self):
                for job in self.running.values():
                    if job.coord.map.reported:
                        self.bubble += 1

        def drain_harness(coord, running):
            for job in running:
                coord.get_map_task(0)
    """)
    assert fired == []


def test_unreaped_job_labels_ignores_unlabeled_and_free_functions(tmp_path):
    # Unlabeled writes carry no cardinality hazard; free functions have
    # no teardown seam to anchor a reap to — both stay silent.
    fired, _ = program_rules_fired(tmp_path, """
        def tick(registry, jobs):
            for job in jobs:
                registry.gauge("job.grants").set(job.grants, job=job.jid)

        class Worker:
            def tick(self, registry):
                registry.gauge("worker.busy").set(1.0)
    """)
    assert fired == []


# ---------------------------------------------------------------------------
# naked-clock-in-control-plane (ISSUE 18)
# ---------------------------------------------------------------------------

def test_naked_clock_fires_in_control_plane_class(tmp_path):
    fired = rules_fired(tmp_path, """
        import time

        class Coordinator:
            def progress(self):
                return time.monotonic() - self.t0
    """)
    assert fired == ["naked-clock-in-control-plane"]


def test_naked_clock_fires_on_from_import_and_methods_table(tmp_path):
    # A from-imported bare name, inside a class the rule only knows by
    # its _METHODS table (a control-plane surface by construction).
    findings, _ = run_lint(tmp_path, """
        from time import monotonic

        class FrontDesk:
            _METHODS = frozenset({"get_task"})

            def get_task(self, wid=-1):
                self.last_seen[wid] = monotonic()
                return -3
    """)
    assert [f.rule for f in findings] == ["naked-clock-in-control-plane"]
    assert "time.monotonic" in findings[0].message


def test_naked_clock_silent_on_seam_reference_and_perf_counter(tmp_path):
    # The seam's DEFAULT is a bare function reference (not a call), reads
    # route through self._now(), and perf_counter latency stamps are
    # measurement, not scheduling — all legal.
    assert rules_fired(tmp_path, """
        import time

        class Coordinator:
            def __init__(self, cfg, now=None):
                self._now = now if now is not None else time.monotonic

            def progress(self):
                t0 = time.perf_counter()
                now = self._now()
                return now, time.perf_counter() - t0
    """) == []


def test_naked_clock_silent_outside_control_plane(tmp_path):
    # Same calls in a data-plane class or a free function: out of scope.
    assert rules_fired(tmp_path, """
        import time

        class SpillWriter:
            def tick(self):
                return time.time()

        def stamp():
            return time.monotonic()
    """) == []


# ---------------------------------------------------------------------------
# unnamed-plane-thread (ISSUE 19)
# ---------------------------------------------------------------------------

def run_lint_in_package(tmp_path, src, name="worker.py"):
    # The rule is scoped to package source (a path with a
    # mapreduce_rust_tpu segment): the profiler attributes samples by
    # thread name, so only OUR planes owe one — user code is exempt.
    pkg = tmp_path / "mapreduce_rust_tpu"
    pkg.mkdir(exist_ok=True)
    p = pkg / name
    p.write_text(textwrap.dedent(src))
    findings, errors, suppressed = lint_file(str(p))
    assert not errors, errors
    return sorted({f.rule for f in findings})


def test_unnamed_plane_thread_fires_on_bare_thread(tmp_path):
    fired = run_lint_in_package(tmp_path, """
        import threading

        def start(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            return t
    """)
    assert fired == ["unnamed-plane-thread"]


def test_unnamed_plane_thread_fires_on_unprefixed_pool(tmp_path):
    fired = run_lint_in_package(tmp_path, """
        from concurrent.futures import ThreadPoolExecutor

        def pool(n, work):
            with ThreadPoolExecutor(max_workers=n) as ex:
                return list(ex.map(work, range(n)))
    """)
    assert fired == ["unnamed-plane-thread"]


def test_unnamed_plane_thread_silent_when_named(tmp_path):
    assert run_lint_in_package(tmp_path, """
        import threading
        from concurrent.futures import ThreadPoolExecutor

        def start(fn, n, work):
            t = threading.Thread(target=fn, name="mr/spill", daemon=True)
            with ThreadPoolExecutor(
                    max_workers=n, thread_name_prefix="mr/scan") as ex:
                out = list(ex.map(work, range(n)))
            return t, out
    """) == []


def test_unnamed_plane_thread_silent_outside_package(tmp_path):
    # Same snippet under a user path: not our plane, no finding.
    assert rules_fired(tmp_path, """
        import threading

        def start(fn):
            return threading.Thread(target=fn)
    """) == []


# ---------------------------------------------------------------------------
# rpc-arg-compat (ISSUE 18)
# ---------------------------------------------------------------------------

def test_rpc_arg_compat_fires_on_required_midsignature_param(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        class Coordinator:
            _METHODS = frozenset({"renew_map_lease"})

            def renew_map_lease(self, tid, wid):
                return tid in self.leases and self.holder[tid] == wid
    """)
    assert fired == ["rpc-arg-compat"]
    assert "wid" in report.findings[0].message
    assert "renew_map_lease" in report.findings[0].message


def test_rpc_arg_compat_fires_on_required_kwonly_param(tmp_path):
    fired, report = program_rules_fired(tmp_path, """
        class JobService:
            _METHODS = frozenset({"submit_job"})

            def submit_job(self, spec=None, *, priority):
                return {"ok": True, "priority": priority}
    """)
    assert fired == ["rpc-arg-compat"]
    assert "priority" in report.findings[0].message


def test_rpc_arg_compat_silent_on_trailing_defaults_and_helpers(tmp_path):
    # The shipped handler shape (one required operand, everything after
    # it defaulted) is legal; methods OUTSIDE the _METHODS table are not
    # wire surface and take whatever signature they like.
    fired, _ = program_rules_fired(tmp_path, """
        class Coordinator:
            _METHODS = frozenset({"report_map_task_finish", "stats"})

            def report_map_task_finish(self, tid, attempt=0, wid=-1,
                                       part_bytes=None):
                return True

            def stats(self):
                return {}

            def _finish(self, phase, tid, attempt, wid):
                return (phase, tid, attempt, wid)
    """)
    assert fired == []


# ---------------------------------------------------------------------------
# ad-hoc-corpus-digest (ISSUE 20)
# ---------------------------------------------------------------------------

def run_lint_in_pkg_path(tmp_path, src, relpath):
    # Package-scoped like the thread rule, but the fixture controls the
    # FULL relative path — the lineage module's exemption is by suffix.
    p = tmp_path / "mapreduce_rust_tpu" / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    findings, errors, suppressed = lint_file(str(p))
    assert not errors, errors
    return sorted({f.rule for f in findings})


def test_corpus_digest_fires_on_adhoc_chunk_hash(tmp_path):
    fired = run_lint_in_pkg_path(tmp_path, """
        import hashlib

        def identity(chunk_bytes):
            return hashlib.sha256(chunk_bytes).hexdigest()[:16]
    """, "runtime/cache.py")
    assert fired == ["ad-hoc-corpus-digest"]


def test_corpus_digest_fires_on_update_with_window(tmp_path):
    fired = run_lint_in_pkg_path(tmp_path, """
        import hashlib

        def fold(windows):
            h = hashlib.blake2b(digest_size=16)
            for window in windows:
                h.update(window)
            return h.hexdigest()
    """, "service/keys.py")
    assert fired == ["ad-hoc-corpus-digest"]


def test_corpus_digest_silent_in_lineage_module(tmp_path):
    # The seam itself is the one legitimate home.
    fired = run_lint_in_pkg_path(tmp_path, """
        import hashlib

        def chunk_digest(chunk_bytes):
            return hashlib.blake2b(chunk_bytes, digest_size=16).hexdigest()
    """, "runtime/lineage.py")
    assert fired == []


def test_corpus_digest_silent_in_scan_corpus(tmp_path):
    # scan_corpus IS the metadata fingerprint seam (delegates to
    # corpus_fingerprint; its residual hashlib use is the seam working).
    fired = run_lint_in_pkg_path(tmp_path, """
        import hashlib

        def scan_corpus(corpus_dir, pattern):
            sig = hashlib.sha256()
            sig.update(f"{corpus_dir}:{pattern}".encode())
            return sig.hexdigest()[:16]
    """, "service/server.py")
    assert fired == []


def test_corpus_digest_silent_on_non_corpus_args(tmp_path):
    # Config fingerprints, host tags, plain dict.update: none of these
    # digest corpus bytes; cfg.chunk_bytes is a shape knob, not content.
    fired = run_lint_in_pkg_path(tmp_path, """
        import hashlib

        def job_fingerprint(cfg, inputs):
            h = hashlib.sha256()
            h.update(f"{cfg.chunk_bytes}:{cfg.reduce_n}".encode())
            for p in inputs:
                h.update(p.encode())
            return h.hexdigest()

        def merge(d, window):
            out = dict(d)
            out.update(window)
            return out
    """, "runtime/driver.py")
    assert fired == []
