"""mesh shuffle (parallel/shuffle.py): the share of the all_to_all's
fixed-capacity bucket rows that carried records, Σ JobStats.shuffle_live_rows
over Σ shuffle_wire_bytes / 13 (13 B a row) over the window's jobs. Nothing
to read where the program has no such counter or ran no mesh round."""

from peaks import ROW_BYTES


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "shuffle_live_rows") for s in stats):
        return None
    slots = sum(s.shuffle_wire_bytes for s in stats) / ROW_BYTES
    if not slots:
        return None
    return sum(s.shuffle_live_rows for s in stats) / slots
