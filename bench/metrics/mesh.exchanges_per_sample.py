"""Collective steps of the mesh per sample consumed
(``EventReport.exchanges / samples``): each lockstep drain iteration and
each sample round is one halo exchange and one min-reduce or due-flag psum.
None where the program has no such counter."""


def read(ctx):
    c = ctx["counters"]
    if c.get("exchanges") is None or not c["samples"]:
        return None
    return c["exchanges"] / c["samples"]
