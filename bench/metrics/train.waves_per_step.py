"""Mean cascade waves per training step (``StepAux.waves``)."""


def read(ctx):
    c = ctx["counters"]
    return c["waves"] / c["steps"] if c.get("steps") else None
