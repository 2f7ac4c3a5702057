"""finalize / egress (run_job's egress tiers, apps/inverted_index.py): the
share of the window spent in the program's "egress.postings" span,
grouping (term, doc) rows into posting lists and formatting them, from
JobStats.posting_format_s. Nothing to read where the program has no such
field."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "posting_format_s") for s in stats):
        return None
    return sum(s.posting_format_s for s in stats) / ctx.window_s
