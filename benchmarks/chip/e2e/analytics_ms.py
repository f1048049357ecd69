"""Mean time of the window's analytics queries: their total host-clock
time over their count; each builds its epoch's snapshot."""


def read(win):
    return (1e3 * sum(win.analytics_s) / len(win.analytics_s)
            if win.analytics_s else None)
