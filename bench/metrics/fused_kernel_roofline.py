"""The fused training kernel's share of its roofline: the least time of the
traced steps' work (W read and written once per step, the samples and the
counters; the algorithm's FLOPs) over the device time of the
``fused_step_pallas`` operations. Memory bound at these sizes (about 4 FLOP
per byte)."""

KERNEL = "fused_step_pallas"


def read(ctx):
    c, afm, work = ctx["counters"], ctx["afm"], ctx["work"]
    from harness import trace
    t = trace.kernel_seconds(ctx["trace"], KERNEL)
    if t <= 0 or not c.get("steps"):
        return None
    n, d = afm["side"] ** 2, afm["dim"]
    flops = work.step_flops(n, d, c["samples"], c["receipts"])
    nbytes = work.step_bytes(n, d, afm["batch"], c["steps"])
    least, _ = work.least_time(flops, nbytes, ctx["peaks"])
    return 100.0 * least / t
