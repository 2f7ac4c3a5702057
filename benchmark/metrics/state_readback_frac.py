"""device -> host state readback: the share of the window spent in the
program's "state.readback" span, the final device state fetched into the
host accumulator, from JobStats.state_readback_s. Nothing to read where
the program has no such field."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "state_readback_s") for s in stats):
        return None
    return sum(s.state_readback_s for s in stats) / ctx.window_s
