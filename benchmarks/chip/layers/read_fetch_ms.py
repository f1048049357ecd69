"""Host time per read spent copying its answer to the host: the store's
``read_fetch_ms`` counter (``api/store.py`` ``LocalStore``: host ms inside
the ``graph.read.fetch`` spans, every device-to-host copy of a read,
the vertex-ID table included), as a delta over the window, over the
window's reads. None on a program without the counter.

The import puts ``scopetrace``'s reduction in place for the cell's traced
run, so that its ``breakdown.idle_gaps`` labels idle by the program's
``graph.*`` spans too."""

import scopetrace  # noqa: F401


def read(ctx):
    if "read_fetch_ms" not in ctx.counters or not ctx.n_reads:
        return None
    return ctx.counters["read_fetch_ms"] / ctx.n_reads
