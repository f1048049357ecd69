"""Host staging per flush: ``LocalStore.stats`` ``host_stage_ms`` (pad,
pack and asynchronous dispatch of a flush's batches, on the host clock)
over ``super_batches`` (dispatch groups of up to 8 device batches), both
as deltas over the window. Source: the program's counters."""


def read(ctx):
    n = ctx.counters.get("super_batches", 0)
    return ctx.counters["host_stage_ms"] / n if n else None
