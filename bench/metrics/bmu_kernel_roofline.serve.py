"""The BMU kernel's share of its roofline in serving, counting the rows
requested and not the padded rows, so that padding shows as a lower share:
2·rows·N·D FLOPs, W read once per kernel call, over the device time of
``bmu_pallas`` ops (the BMU kernel's Pallas call)."""

KERNEL = "bmu_pallas"


def read(ctx):
    c, afm, work = ctx["counters"], ctx["afm"], ctx["work"]
    from harness import trace
    t = trace.kernel_seconds(ctx["trace"], KERNEL)
    calls = trace.kernel_calls(ctx["trace"], KERNEL)
    if t <= 0 or not c["requested"]:
        return None
    n, d = afm["side"] ** 2, afm["dim"]
    flops = work.bmu_flops(c["requested"], n, d)
    nbytes = work.bmu_bytes(calls, c["requested"], n, d)
    least, _ = work.least_time(flops, nbytes, ctx["peaks"])
    return 100.0 * least / t
