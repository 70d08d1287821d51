"""Share of the window spent in steps that admitted a request (prefill,
row reset and scatter, then the decode step).  A step counts as admitting
when the harness saw a free slot before it and the batcher's prefill token
counter moved during it."""


def read(rec):
    adm = sum(s["t1"] - s["t0"] for s in rec["steps"]
              if s["admit"] and s["prefill_tokens"] > 0)
    return 100.0 * adm / (rec["t1"] - rec["t0"])
