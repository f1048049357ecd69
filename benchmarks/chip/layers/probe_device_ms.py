"""Device time per edge-update batch in the step's ``probe`` named scope
(``core/edgepool.py`` ``apply_edge_updates``: pair ordering, the fold
lookup, the ``probe_width`` window gather and its liveness reduction):
the ops of ``jit_step_update_edges`` whose ``tf_op`` path holds
``probe``, containers left out, over the step's executions. With the
Pallas append the probe runs inside the ``append`` kernel and is not
counted here. None on a program without the scope."""

import scopetrace


def read(ctx):
    return scopetrace.scope_ms_per_execution(ctx, "probe",
                                             "jit_step_update_edges")
