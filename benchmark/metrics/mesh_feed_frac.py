"""mesh ingest (runtime/driver.py _stream_mesh): the share of the window in
which the job's thread stacked a group's host chunks and doc tables and put
them on the mesh, the span "mesh.feed", from JobStats.mesh_feed_s. Nothing
to read where the program has no such field."""


def read(ctx):
    stats = [j.stats for j in ctx.jobs if j.stats is not None]
    if not stats or not all(hasattr(s, "mesh_feed_s") for s in stats):
        return None
    return sum(s.mesh_feed_s for s in stats) / ctx.window_s
