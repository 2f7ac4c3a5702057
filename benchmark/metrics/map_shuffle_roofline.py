"""mesh map + shuffle (parallel/shuffle.py map_shuffle): the least time the
jit_map_shuffle calls need for their bytes at the chip's HBM peak, over
their device time, in %, summed over the chips as merge_roofline is. Bound
by bytes: the step does integer work, for which no peak is published.

One call on one chip, with u_cap the update's rows and bucket_cap each
bucket's (the fast tier's, from the cell's Config through
default_bucket_cap), and 13 B a KVBatch row:

    chunk_bytes              read the chunk
    + 13 * u_cap             write the combined update
    + 13 * d * bucket_cap    write its d buckets
    + 13 * d * bucket_cap    receive d buckets through the all_to_all
    + 13 * d * bucket_cap    write the reduced local batch

A replayed group runs the same program at a wider tier; its calls are
reckoned at the fast tier's bytes, which can only read the share low."""

from peaks import ROW_BYTES


def bytes_per_call(cfg) -> int:
    from mapreduce_rust_tpu.parallel.shuffle import default_bucket_cap

    d = cfg.mesh_shape or 1
    u_cap = cfg.effective_partial_capacity()
    bucket = default_bucket_cap(u_cap, d, cfg.bucket_capacity_factor)
    return cfg.chunk_bytes + ROW_BYTES * (u_cap + 3 * d * bucket)


def read(ctx):
    if ctx.trace is None:
        return None
    calls = seconds = 0
    for d in ctx.trace.devices:
        m = d.modules.named(lambda n: n == "jit_map_shuffle")
        calls += len(m.names)
        seconds += float((m.ends - m.starts).sum()) / 1e9
    if not calls or not seconds:
        return None
    need = calls * bytes_per_call(ctx.cfg)
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / seconds
