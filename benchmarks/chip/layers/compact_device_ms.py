"""Device time per edge-update batch in the step's ``compact`` named
scope (``core/edgepool.py`` ``apply_edge_updates``: the ``lax.cond`` of
the fast-path tiers, first-touch allocation and the two compaction tiers,
or the ``defrag`` rebuild): the ops of ``jit_step_update_edges`` whose
``tf_op`` path holds ``compact``, containers left out, over the step's
executions. None on a program without the scope."""

import scopetrace


def read(ctx):
    return scopetrace.scope_ms_per_execution(ctx, "compact",
                                             "jit_step_update_edges")
