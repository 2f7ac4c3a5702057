"""job set-up (run_job, engine construction): the share of the window
spent in the program's "job.setup" and "engine.setup" spans, from
JobStats.job_setup_s. Nothing to read where the program has no such
field."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "job_setup_s") for s in stats):
        return None
    return sum(s.job_setup_s for s in stats) / ctx.window_s
