"""Device time per edge-update batch: the summed duration of the trace's
``XLA Modules`` events of ``jit_step_update_edges`` (the donating and the
plain entry of ``core/radixgraph.py`` ``step_update_edges`` share the
name) over their count in the window. One execution is one 4096-entry
device batch."""

MODULE = "jit_step_update_edges"


def read(ctx):
    t = ctx.trace
    n = t.module_count(MODULE) if t is not None else 0
    return 1e3 * t.module_seconds(MODULE) / n if n else None
