"""Mean time of one admission in the window, in ms: from a request's
admission to its first token, as the batcher's own clock records them
(`Request.t_admit`, `Request.t_first`).  They bracket the work of its
serve.admit span: the row reset, the prefill chunks, the row scatter and
the copy of the first token to the host.  None where the program records
no such times."""


def read(rec):
    t0, t1 = rec["t0"], rec["t1"]
    d = [r.t_first - r.t_admit for r in (v["req"] for v in rec["info"].values())
         if getattr(r, "t_admit", None) is not None and r.t_first is not None
         and t0 <= r.t_admit and r.t_first <= t1]
    return sum(d) / len(d) * 1e3 if d else None
