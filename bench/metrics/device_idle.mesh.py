"""Share of the traced window in which no operation ran on a device: 1 -
the union of device-op intervals over the window, averaged over the
devices (the trace's reduction averages busy time over them)."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
