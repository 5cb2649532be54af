"""Share of the messages sent that crossed a band boundary
(``EventReport.halo_sent / sent``). None where the program has no such
counter."""


def read(ctx):
    c = ctx["counters"]
    if c.get("halo_sent") is None or not c.get("sent"):
        return None
    return 100.0 * c["halo_sent"] / c["sent"]
