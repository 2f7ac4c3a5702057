"""spill (_stream_single's drain and replays, HostAccumulator): the share
of the window the stream spent in the program's "spill" span, moving
evicted state tails from the device into the host accumulator, from
JobStats.spill_evict_s. Nothing to read where the program has no such
field."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "spill_evict_s") for s in stats):
        return None
    return sum(s.spill_evict_s for s in stats) / ctx.window_s
