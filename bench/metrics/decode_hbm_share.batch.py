"""The least HBM traffic of the window's steps that admitted nothing
(weights once, the live K/V rows read and the new ones written, from the
configuration's shapes) over their host time, as a share of the chip's
peak bandwidth."""


def read(rec):
    model, conf = rec["model"], rec["conf"]
    steps = [s for s in rec["steps"] if not s["admit"] and s["contexts"]]
    if not steps:
        return None
    need = sum(model.decode_min_bytes(conf, s["contexts"]) for s in steps)
    took = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * need / took / rec["peaks"]["hbm_bytes_per_s"]
