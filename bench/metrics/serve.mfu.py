"""Serving's share of the chip's bf16 peak: the search FLOPs of the rows
requested per second (2·N·D a row) over the peak."""


def read(ctx):
    c, afm, work = ctx["counters"], ctx["afm"], ctx["work"]
    if not c["requested"]:
        return None
    flops = work.bmu_flops(c["requested"], afm["side"] ** 2, afm["dim"])
    return 100.0 * flops / c["window_s"] / ctx["peaks"]["bf16_flops"]
