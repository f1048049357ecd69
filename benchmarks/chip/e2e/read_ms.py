"""Mean time of the window's ``neighbors`` reads: their total host-clock
time over their count."""


def read(win):
    return 1e3 * sum(win.read_s) / len(win.read_s) if win.read_s else None
