"""ingest (runtime/chunker.py pack_documents, the device engines' feeds):
how full the chunks sent to the device were, the document bytes they
carried over their capacity, from JobStats.chunk_payload_bytes and
chunks_dispatched. Nothing to read where the program has no such
counters."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "chunks_dispatched") for s in stats):
        return None
    sent = sum(s.chunks_dispatched for s in stats)
    if not sent:
        return None
    return sum(s.chunk_payload_bytes for s in stats) / (sent * ctx.cfg.chunk_bytes)
