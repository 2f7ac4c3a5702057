"""Compile the main path's Pallas kernel for a described v5e — no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: it refuses what the chip's compiler would refuse
(misaligned slices, too much fast memory) — what interpret-mode tests
cannot see. Kernel-sized compiles take about a second each; the step
program the driver runs on a TPU is compiled at a small width (about half a
minute per app) to show that it carries the kernel. The full-width step
programs are rehearsed by hand (see the verify skill).

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every xdist worker imports this file.
"""

import functools
import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("app_name", ["word_count", "inverted_index"])
def test_step_map_combine_carries_pallas_scan_for_v5e(topo, app_name):
    # The apps of the device cells: word count drops the doc table,
    # inverted index reads it, so they compile to different programs. A
    # map_combine without the kernel would show on the chip only as a
    # slowdown.
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mapreduce_rust_tpu.apps import get_app
    from mapreduce_rust_tpu.runtime.chunker import DOC_SLOTS
    from mapreduce_rust_tpu.runtime.driver import make_step_fns

    map_combine = make_step_fns(get_app(app_name), 1 << 12, use_pallas=True)[0]
    on_dev = SingleDeviceSharding(topo.devices[0])
    hlo = map_combine.lower(
        jax.ShapeDtypeStruct((1 << 16,), jnp.uint8, sharding=on_dev),
        jax.ShapeDtypeStruct((1 + DOC_SLOTS,), jnp.int32, sharding=on_dev),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [1 << 14, 1 << 20, 1 << 22])
def test_hash_scan_pallas_compiles_for_v5e(topo, n):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mapreduce_rust_tpu.ops.tokenize_pallas import hash_scan_pallas

    chunk = jax.ShapeDtypeStruct((n,), jnp.uint8,
                                 sharding=SingleDeviceSharding(topo.devices[0]))
    hlo = hash_scan_pallas.lower(chunk).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_hash_scan_pallas_compiles_under_4_chip_shard_map(topo):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mapreduce_rust_tpu.ops.tokenize_pallas import hash_scan_pallas
    from mapreduce_rust_tpu.parallel.shuffle import AXIS

    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS))
    def scan(chunks):
        h1, _h2, _cnt = hash_scan_pallas(chunks[0])
        return h1[None]

    chunks = jax.ShapeDtypeStruct((4, 1 << 20), jnp.uint8,
                                  sharding=NamedSharding(mesh, P(AXIS)))
    hlo = scan.lower(chunks).compile().as_text()
    assert "tpu_custom_call" in hlo
    # The per-chip program takes one 1 MiB row: the input is never gathered
    # onto one chip.
    entry = next(line for line in hlo.splitlines() if line.startswith("ENTRY"))
    assert "u8[1,1048576]" in entry
