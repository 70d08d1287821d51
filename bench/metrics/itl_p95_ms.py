"""95th percentile of the gaps between successive tokens of one request,
over every gap that ends in the window, in ms.  A token's time is when the
step that made it returned to the host."""
import numpy as np


def read(rec):
    t0, t1 = rec["t0"], rec["t1"]
    gaps = [b - a for v in rec["info"].values()
            for a, b in zip(v["times"], v["times"][1:]) if t0 < b <= t1]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
