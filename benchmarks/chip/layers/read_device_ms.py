"""Device time per neighbors read: the summed duration of the trace's
``XLA Modules`` events of ``jit_step_neighbors`` (``core/radixgraph.py``
``step_neighbors``: the SORT lookup, the (keys x dmax) gather and
``compact_rows``) over the window's reads."""

MODULE = "jit_step_neighbors"


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_reads or not t.module_count(MODULE):
        return None
    return 1e3 * t.module_seconds(MODULE) / ctx.n_reads
