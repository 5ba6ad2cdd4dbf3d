"""One labelled child of a counter of the program as a share of all its
children, both as deltas over the window, in %. None where the program
does not export the counter (a parent from before it), or nothing was
counted in the window; 0.0 where it was and none under these labels."""


def read(ctx, counter, labels):
    m1 = ctx.get("m1") or {}
    if not any(name == counter for name, _ in m1):
        return None
    total = ctx["delta"](counter)
    if not total:
        return None
    return 100.0 * ctx["delta"](counter, **labels) / total
