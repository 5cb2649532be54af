"""Share of the delivery rounds served at the narrow width
(``EventReport.narrow_rounds / (rounds - samples)``), summed over the
bands. None where the program has no such counter."""


def read(ctx):
    c = ctx["counters"]
    delivery = c["rounds"] - c["samples"]
    if c.get("narrow_rounds") is None or delivery <= 0:
        return None
    return 100.0 * c["narrow_rounds"] / delivery
