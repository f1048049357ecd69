"""Device time per analytics query spent building the epoch's CSR
snapshot: the summed duration of the trace's ``XLA Modules`` events of
``jit_step_snapshot`` (``core/radixgraph.py`` ``step_snapshot`` over
``live_edges``, a sort of the whole pool) over the window's queries."""

MODULE = "jit_step_snapshot"


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_analytics or not t.module_count(MODULE):
        return None
    return 1e3 * t.module_seconds(MODULE) / ctx.n_analytics
