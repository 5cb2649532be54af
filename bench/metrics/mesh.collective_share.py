"""Share of the device's leaf-op time spent in collective operations
(``all-reduce``, ``collective-permute`` and ``all-gather``, with their
``-start`` and ``-done`` forms), averaged over the devices of the traced
window. Control flow (``while``, ``conditional``, ``call``) is left out: its
time is that of the ops inside it. None where no collective op ran."""

COLLECTIVES = ("all-reduce", "collective-permute", "all-gather")


def read(ctx):
    from harness import trace
    tr = ctx["trace"]
    lo, hi = tr["lo"], tr["hi"]
    shares = []
    for events in tr["ops"].values():
        leaf = coll = 0
        for name, s, e in events:
            if not lo <= s < hi or name.startswith(trace.CONTAINERS):
                continue
            leaf += e - s
            if trace.op_name(name).lstrip("%").startswith(COLLECTIVES):
                coll += e - s
        if coll:
            shares.append(100.0 * coll / leaf)
    return sum(shares) / len(shares) if shares else None
