"""Closed loop, one client: rounds back to back. Each round is one write
(``Traffic.write``), then ``capture`` when the mix asks, one read when it
has one, and an analytics query on the rounds ``Traffic.analytics_due``
names. The window runs rounds until ``seconds`` have passed, and ends on
the round's last answer."""
import time
import traceback

import numpy as np


def _round(d, r: int, win):
    """One round of the mix; ``win`` is the Window when measured."""
    tr, run, api, span = d.traffic, d.run, d.api, d.span
    ids = run.graph["ids"]
    u, v, w = tr.write()
    t = time.perf_counter()
    with span("bench.apply"):
        res = d.store.apply(api.OpBatch.edges(ids[u], ids[v], w))
    run.failed += res.dropped
    if win is not None:
        win.apply_s.append(time.perf_counter() - t)
        win.write_ops += len(u)
    epoch = None
    if tr.mix["capture"]:
        with span("bench.capture"):
            epoch = d.store.capture()
    if tr.mix["read"] is not None:
        xs = tr.read_ids()
        t = time.perf_counter()
        try:
            with span("bench.read"):
                ans = d.store.read(api.ReadOp("neighbors", ids=ids[xs]),
                                   at=epoch)
        except Exception:               # counted as failed, run goes on
            traceback.print_exc()
            run.failed += 1
            ans = None
        dt = time.perf_counter() - t
        if win is not None:
            win.read_s.append(dt)
            if ans is not None:   # copies: the answers are views of one
                ans = [(np.array(a), np.array(b)) for a, b in ans]
            run.reads.append((tr.n_logged, xs, ans or []))
    if tr.analytics_due(r):
        a = tr.mix["analytics"]
        t = time.perf_counter()
        try:
            with span("bench.analytics"):
                val = d.store.analytics(api.AnalyticsOp(a["name"],
                                                        a["params"]),
                                        at=epoch)
        except Exception:
            traceback.print_exc()
            run.failed += 1
            val = None
        dt = time.perf_counter() - t
        if win is not None:
            win.analytics_s.append(dt)
            run.analytics = (tr.n_logged, val)


def warmup(d):
    """The mix's warm-up rounds, in set-up: every program the window uses
    runs in them (round 0 always runs analytics)."""
    for r in range(d.traffic.mix["warmup_rounds"]):
        _round(d, r, None)


def window(d, win, seconds: float):
    r = d.traffic.mix["warmup_rounds"]
    t0 = time.perf_counter()
    while True:
        _round(d, r, win)
        r += 1
        win.rounds += 1
        if time.perf_counter() - t0 >= seconds:
            break
    win.window_s = time.perf_counter() - t0
