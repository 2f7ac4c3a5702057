"""device map (ops/tokenize_pallas.py, make_step_fns map_combine, fed
packed chunks by runtime/chunker.py): the least time the jit_map_combine
calls of the window need for the document bytes they carry (the window's
input bytes) plus the fixed-capacity update each call writes, at the
chip's HBM peak, over those calls' device time, in %. Unlike
map_combine_roofline, a chunk's space padding counts for nothing, so a
half-empty chunk reads as half the share."""

from peaks import ROW_BYTES


def need_bytes(doc_bytes: int, calls: int, update_rows: int) -> int:
    """Read the documents' bytes once; write one update of update_rows
    KVBatch rows a call."""
    return doc_bytes + calls * update_rows * ROW_BYTES


def read(ctx):
    if ctx.trace is None:
        return None
    calls = seconds = 0
    for d in ctx.trace.devices:
        m = d.modules.named(lambda n: n == "jit_map_combine")
        calls += len(m.names)
        seconds += float((m.ends - m.starts).sum()) / 1e9
    if not calls:
        return None
    need = need_bytes(ctx.bytes_in, calls, ctx.cfg.effective_partial_capacity())
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / seconds
