"""The whole training step's share of the chips' bf16 peak under the mesh
placement: the algorithm's FLOPs per trained sample and per delivered
message times the traced window's samples and receipts, over the window,
over the peak of every chip the map spans. The same work whatever
implements it."""


def read(ctx):
    c, afm, work = ctx["counters"], ctx["afm"], ctx["work"]
    if not c["samples"] or not c.get("chips"):
        return None
    n = afm["side"] ** 2
    flops = work.step_flops(n, afm["dim"], c["samples"], c["receipts"])
    return (100.0 * flops / c["window_s"]
            / (c["chips"] * ctx["peaks"]["bf16_flops"]))
