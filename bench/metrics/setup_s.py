"""Set-up: from process start to the window's start (loading, weights,
compiling or reading the compile cache, warm-up)."""


def read(rec):
    return rec["t0"] - rec["t_proc"]
