"""Share of its roofline that ``compact_rows`` (``kernels/compact.py``)
reaches inside the edge-update step: the least time its work could take
at the chip's HBM bandwidth over its measured device time.

Work: per execution of ``jit_step_update_edges``, the step's two
compaction tiers at their static sizes (``kernel_bytes.step_compact_bytes``:
k_max rows at the probe width, k_big rows at dmax, three lanes each, read
and written). Device time: the trace's ``XLA Ops`` events of the Pallas
call, named ``%compact_rows_pallas.<n> = ...``, inside those executions.
The kernel does no arithmetic worth a compute bound, so bandwidth bounds
it."""

import kernel_bytes


def read(ctx):
    return kernel_bytes.compact_rows_roofline(
        ctx, "jit_step_update_edges",
        kernel_bytes.step_compact_bytes(ctx.store_kwargs))
