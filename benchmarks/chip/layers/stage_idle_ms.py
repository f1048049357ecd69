"""Device idle per flush while the store's host code stages or dispatches
it: idle time (no ``XLA Ops`` event running) inside the window and inside
the program's ``graph.apply.stage`` or ``graph.apply.dispatch`` host spans
(``core/radixgraph.py`` ``_apply_edge_batches``), per chip, over the
window's flushes (the ``flushes`` counter). None on a program without the
spans."""

import scopetrace

SPANS = ("graph.apply.stage", "graph.apply.dispatch")


def read(ctx):
    t = ctx.trace
    n = ctx.counters.get("flushes", 0)
    if not isinstance(t, scopetrace.ScopedTrace) or not n \
            or not t.n_devices or not t.has_spans(SPANS):
        return None
    return 1e3 * t.idle_seconds_in(SPANS) / n
