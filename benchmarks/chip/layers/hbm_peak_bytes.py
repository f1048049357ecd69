"""Peak device memory in use on the fullest chip, from
``memory_stats()["peak_bytes_in_use"]`` read after the window (the
runtime's counter; it covers set-up too)."""


def read(ctx):
    return ctx.memory_peak_bytes or None
