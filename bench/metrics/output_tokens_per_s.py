"""Tokens served in the window over the window: every token that became
visible to the host after the window opened, over the time from the
window's start to the end of its last step."""


def read(rec):
    t0, t1 = rec["t0"], rec["t1"]
    n = sum(1 for v in rec["info"].values() for t in v["times"] if t0 < t <= t1)
    return n / (t1 - t0)
