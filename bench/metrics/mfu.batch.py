"""Model FLOPs of every token the window processed, prompt tokens of the
prefills and fed tokens of the decode steps, each at its own position, over
the window times the chip's bf16 peak."""


def read(rec):
    model, conf = rec["model"], rec["conf"]
    flops = 0.0
    for s in rec["steps"]:
        flops += sum(model.flops_per_token(conf, c) for c in s["contexts"])
        for p in s["prefills"]:
            flops += sum(model.flops_per_token(conf, i) for i in range(p))
    return 100.0 * flops / ((rec["t1"] - rec["t0"]) * rec["peaks"]["bf16_flops_per_s"])
