"""Set-up time: process start to the window's start (imports, the seeded
graph, the state, programs from the compile cache, preload, warm-up
rounds), on the host clock."""


def read(win):
    return win.setup_s
