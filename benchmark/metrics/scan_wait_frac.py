"""host map scan (native/host.py, _stream_host_map): the share of the
window in which the host engine's router waited for the next in-order
scan result, the "host_map.stall" span, from JobStats.scan_wait_s.
Nothing to read where no job ran the host engine."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not any(s.host_map_workers for s in stats):
        return None
    return sum(s.scan_wait_s for s in stats) / ctx.window_s
