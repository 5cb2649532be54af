"""The whole training step's share of the chip's bf16 peak: the algorithm's
FLOPs per trained sample times the traced window's samples per second, over
the peak. The same work whatever implements it."""


def read(ctx):
    c, afm, work = ctx["counters"], ctx["afm"], ctx["work"]
    if not c["samples"]:
        return None
    n = afm["side"] ** 2
    flops = work.step_flops(n, afm["dim"], c["samples"], c["receipts"])
    return 100.0 * flops / c["window_s"] / ctx["peaks"]["bf16_flops"]
