"""Event-engine rounds per sample consumed (``EventReport.rounds /
samples``): one round per sample arrival plus one per delivery round."""


def read(ctx):
    c = ctx["counters"]
    return c["rounds"] / c["samples"] if c["samples"] else None
