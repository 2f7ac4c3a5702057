"""Multi-host entry: jax.distributed bootstrap for DCN-spanning meshes.

SURVEY.md §5's comm-backend row: control traffic rides the coordinator's
JSON-RPC plane (coordinator/server.py — the reference's tarpc surface),
while DATA moves through XLA collectives. Intra-slice those collectives
ride ICI (parallel/shuffle.py); across hosts/slices XLA routes them over
DCN once every process has joined a jax.distributed cluster and the mesh
is built over the GLOBAL device list. The reference has no analog — its
"distribution" is multi-process on one host over a shared filesystem
(src/bin/mrcoordinator.rs:31, src/mr/worker.rs:117-140).

Usage (one process per host, same binary each — mirrors mrworker argv):

    python -m mapreduce_rust_tpu run --distributed \
        --coordinator 10.0.0.1:1234 --num-processes 4 --process-id $RANK ...

after which `make_mesh(None)` sees every host's chips and the unchanged
shard_map pipeline spans the cluster; each process feeds its local shards
(jax.make_array_from_process_local_data) and the all_to_all crosses DCN.

The 2-process localhost smoke (tests/test_distributed.py) runs on virtual
CPU clients and skips itself when they do not federate — loudly, with the
observed device counts — instead of faking a pass.
"""

from __future__ import annotations

import logging

log = logging.getLogger("mapreduce_rust_tpu.distributed")

_initialized = False


def initialize(coordinator_address: str, num_processes: int, process_id: int,
               local_device_ids=None, **kwargs) -> None:
    """Join the jax.distributed cluster (idempotent). MUST run before any
    other jax call in the process — backend creation binds the client.

    Extra kwargs pass through to jax.distributed.initialize — notably
    heartbeat_timeout_seconds: on heavily oversubscribed hosts (many
    processes per core, e.g. localhost test clusters) the coordination
    service can evict a starved-but-healthy peer at the default 100 s.
    """
    global _initialized
    if _initialized:
        return
    import time

    from mapreduce_rust_tpu.runtime.trace import trace_span

    import jax

    try:
        # Cross-process CPU collectives need gloo; harmless elsewhere.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):
        pass
    t0 = time.perf_counter()
    with trace_span("distributed.initialize", coordinator=coordinator_address,
                    process_id=process_id, num_processes=num_processes):
        jax.distributed.initialize(
            coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
            **kwargs,
        )
    _initialized = True
    log.info(
        "joined distributed cluster %s as process %d/%d in %.2fs: "
        "%d global / %d local devices",
        coordinator_address, process_id, num_processes,
        time.perf_counter() - t0,
        jax.device_count(), jax.local_device_count(),
    )


def cluster_info() -> dict:
    """Manifest-ready identity of this process's view of the cluster."""
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "federated": is_federated(),
    }


def is_federated() -> bool:
    """True when this process is part of a multi-process device cluster."""
    import jax

    return jax.device_count() > jax.local_device_count()
