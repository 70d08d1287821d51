"""Mean host time of the window's steps that admitted nothing: one decode
step of every slot, from the call to step() to its return (step() ends in a
host copy of the argmax, so the device's work is inside)."""


def read(rec):
    d = [s["t1"] - s["t0"] for s in rec["steps"] if not s["admit"]]
    return sum(d) / len(d) * 1e3 if d else None
