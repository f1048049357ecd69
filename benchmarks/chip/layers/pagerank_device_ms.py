"""Device time per analytics query spent in PageRank's iterations: the
summed duration of the trace's ``XLA Modules`` events of ``jit_pagerank``
(``analytics/algorithms.py`` ``pagerank``) over the window's queries."""

MODULE = "jit_pagerank"


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_analytics or not t.module_count(MODULE):
        return None
    return 1e3 * t.module_seconds(MODULE) / ctx.n_analytics
