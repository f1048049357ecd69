"""Undirected edge ops applied through ``GraphStore.apply`` in the window
over the window's seconds, on the host clock; the window ends on a
synced apply."""


def read(win):
    return win.write_ops / win.window_s if win.write_ops else None
