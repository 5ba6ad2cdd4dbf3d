"""One labelled child of a counter of the program over another counter,
both as deltas over the window (the denominator's children summed). None
where the program does not export the numerator's counter (a parent from
before it) or the denominator did not move; 0.0 where the counter is
there and nothing was counted under these labels."""


def read(ctx, over, labels, under):
    m1 = ctx.get("m1") or {}
    n = ctx["delta"](under)
    if not n or not any(name == over for name, _ in m1):
        return None
    return ctx["delta"](over, **labels) / n
