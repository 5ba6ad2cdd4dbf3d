"""Seconds of one labelled series of the program (a histogram's
``_sum``, say) as a delta over the window, per 1,000 pods bound in it.
None where the program exports no series of that name at all; 0.0 where
it does and none under these labels: that part of the work did not run
(the spread tensorizer in a cell with no spread pod)."""


def read(ctx, seconds, labels):
    m1 = ctx.get("m1") or {}
    bound = ctx.get("bound_in_window")
    if not bound or not any(name == seconds for name, _ in m1):
        return None
    return ctx["delta"](seconds, **labels) / bound * 1000.0
