#!/usr/bin/env python
"""Bring-up smoke: word_count through the normal `run` path on a TPU.

One process drives the CLI in-process (``mapreduce_rust_tpu.__main__.main``)
over a seeded Zipf corpus of 320,000,000 bytes — the data size of HiBench
WordCount's ``small`` profile (conf/workloads/micro/wordcount.conf) — built
by the repo's generator (runtime/zipf.py: 2**21 ranks, s=1.05, ground truth
from the generator). Each phase's ``mr-*.txt`` must equal that ground truth
exactly; any mismatch, exception or non-TPU device exits non-zero.

    python chip_smoke.py            one chip: phase A (device engine, default
                                    Config: 4 MiB chunks, merge_capacity
                                    2**21, Pallas scan), then phase B
                                    (--map-engine host, native scan)
    python chip_smoke.py --chips 4  phase A on one chip, then `run --mesh 4`;
                                    both must equal the truth and each other

Every phase prints one JSON line (wall time, GB/s, XLA compiles and their
seconds, persistent-cache hits, device kind); the last line of stdout is
``{"ok": true, "device": {...}}``. Compiles are cached where
JAX_COMPILATION_CACHE_DIR says, else in .jax_cache/<host fingerprint>/, so a
second run in the same checkout reads them back.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
CORPUS_BYTES = 320_000_000
PLATFORM = "tpu"  # a CPU rehearsal at a tiny CORPUS_BYTES patches both
WORK = REPO / ".bench" / "chip_smoke"


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def run_phase(name: str, corpus_dir: pathlib.Path, truth, extra: list[str]):
    """One `run` through the CLI; returns (per-rank counts, report dict)."""
    from mapreduce_rust_tpu.__main__ import main
    from mapreduce_rust_tpu.runtime.zipf import rank_counts

    out, work = WORK / f"out-{name}", WORK / f"work-{name}"
    manifest_p = WORK / f"manifest-{name}.json"
    for d in (out, work):
        shutil.rmtree(d, ignore_errors=True)
    argv = ["run", "--device", PLATFORM, "--app", "word_count",
            "--input", str(corpus_dir), "--output", str(out),
            "--work", str(work), "--manifest", str(manifest_p), *extra]
    t0 = time.perf_counter()
    rc = main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        fail(f"phase {name}: run exited {rc}")
    m = json.loads(manifest_p.read_text())
    s, plat = m["stats"], m["platform"]
    if plat.get("backend") != PLATFORM:
        fail(f"phase {name}: manifest platform {plat.get('backend')!r}")
    got, n_lines = rank_counts(sorted(out.glob("mr-*.txt")))
    comp = s.get("compile", {})
    report = {
        "phase": name, "argv": argv[1:], "device_kind": plat.get("device_kind"),
        "device_count": plat.get("device_count"),
        "wall_s": wall, "job_wall_s": s["wall_seconds"],
        "gb_per_s": s["bytes_in"] / wall / 1e9, "bytes_in": s["bytes_in"],
        "compiles": comp.get("count", 0), "compile_s": comp.get("total_s", 0.0),
        "cache_hits": comp.get("cache_hits", 0),
        "cache_misses": comp.get("cache_misses", 0),
        "lines": n_lines, "distinct_keys": s["distinct_keys"],
        "spilled_keys": s["spilled_keys"],
        "partial_overflow_replays": s["partial_overflow_replays"],
        "phase_seconds": m.get("phase_seconds"),
        "exact": bool((got == truth).all()),
    }
    if "mesh_shard_rows" in s:
        report["mesh_shard_rows"] = s["mesh_shard_rows"]
    return got, report


def pallas_in_map_combine() -> bool:
    """Is the Mosaic kernel (tpu_custom_call) in the map_combine that
    phase A ran? Same app, capacity and chunk shape as the default Config;
    the compile is a persistent-cache hit after phase A."""
    import jax
    import jax.numpy as jnp

    from mapreduce_rust_tpu.apps.word_count import WordCount
    from mapreduce_rust_tpu.config import Config
    from mapreduce_rust_tpu.runtime.driver import make_step_fns

    cfg = Config()
    map_combine, _ = make_step_fns(
        WordCount(), cfg.effective_partial_capacity(), use_pallas=True
    )
    # Placed like the run's arguments (device_put to device 0), so the
    # lowering — and the persistent-cache key — is the one phase A compiled.
    on_dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    hlo = map_combine.lower(
        jax.ShapeDtypeStruct((cfg.chunk_bytes,), jnp.uint8, sharding=on_dev),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=on_dev),
    ).compile().as_text()
    return "tpu_custom_call" in hlo


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: compare `run --mesh 4` with the one-chip run")
    from mapreduce_rust_tpu.runtime.zipf import ZIPF_SEED, build_zipf_corpus

    ap.add_argument("--seed", type=int, default=ZIPF_SEED)
    args = ap.parse_args()

    import jax
    import numpy as np

    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        fail(f"no {PLATFORM}: JAX's devices are {devs}")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips}: JAX sees {len(devs)} devices")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": args.chips}

    t0 = time.perf_counter()
    corpus_dir = WORK / f"corpus-{args.seed}"
    corpus, counts_p = build_zipf_corpus(
        corpus_dir / "zipf.txt", CORPUS_BYTES, seed=args.seed
    )
    truth = np.load(counts_p)
    print(json.dumps({"phase": "corpus", "bytes": corpus.stat().st_size,
                      "distinct": int((truth > 0).sum()),
                      "seconds": time.perf_counter() - t0}), flush=True)

    reports = []
    got_a, rep = run_phase("A-device", corpus_dir, truth, [])
    rep["tpu_custom_call"] = pallas_in_map_combine()
    reports.append(rep)
    print(json.dumps(rep), flush=True)
    if args.chips == 1:
        from mapreduce_rust_tpu.native.host import get_lib

        _, rep = run_phase("B-host", corpus_dir, truth, ["--map-engine", "host"])
        rep["native_lib"] = get_lib() is not None
        reports.append(rep)
        print(json.dumps(rep), flush=True)
        if not rep["native_lib"]:
            fail("phase B ran the Python fallback, not the native scan")
    else:
        got_m, rep = run_phase("M-mesh4", corpus_dir, truth, ["--mesh", "4"])
        rep["equals_one_chip"] = bool((got_m == got_a).all())
        reports.append(rep)
        print(json.dumps(rep), flush=True)
        rows = rep.get("mesh_shard_rows", [])
        if rep["device_count"] < 4 or len(rows) != 4 or min(rows) <= 0:
            fail(f"mesh did not span 4 chips: shard rows {rows}")
        if not rep["equals_one_chip"]:
            fail("mesh outputs differ from the one-chip outputs")
    for r in reports:
        if not r["exact"]:
            fail(f"phase {r['phase']}: outputs differ from the generator's counts")
    if not reports[0]["tpu_custom_call"]:
        fail("phase A's map_combine has no tpu_custom_call: the Pallas scan did not run")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
