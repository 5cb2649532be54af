"""Requests merged per gateway dispatch: ``GatewayStats.dispatch_requests /
dispatches`` over the traced window."""


def read(ctx):
    c = ctx["counters"]
    return c["dispatch_requests"] / c["dispatches"] if c["dispatches"] else None
