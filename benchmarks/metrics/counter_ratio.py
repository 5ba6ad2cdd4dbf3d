"""One counter of the program over another, both as deltas over the
window (every labelled child summed). None where the program does not
export the numerator (a parent from before it) or the denominator did
not move; 0.0 where the numerator is there and stood still."""


def read(ctx, over, under):
    m1 = ctx.get("m1") or {}
    n = ctx["delta"](under)
    if not n or not any(name == over for name, _ in m1):
        return None
    return ctx["delta"](over) / n
