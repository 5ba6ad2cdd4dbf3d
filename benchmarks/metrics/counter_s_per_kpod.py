"""A seconds counter of the program over its count counter, both as
deltas over the window, per 1,000. None where the program does not
export the seconds counter (the count may be older than it)."""


def read(ctx, seconds, count):
    m1 = ctx.get("m1") or {}
    n = ctx["delta"](count)
    if not n or not any(name == seconds for name, _ in m1):
        return None
    return ctx["delta"](seconds) / n * 1000.0
