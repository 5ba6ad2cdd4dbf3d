"""Bound: bytes. Least seconds (lib/solve_work.py, from the cell's
shapes) over the device-busy seconds of the traced interval. All device
time is charged to the solves: the served scheduler runs nothing else on
the chip, and the share can only read lower for it, never higher."""


def read(ctx):
    got = ctx["traced"]
    if not got or not got["pods"] or not got["solves"] or not ctx["trace"]["busy_s"]:
        return None
    least = ctx["solve_work"].min_seconds(
        ctx["config"], got["solves"], got["pods"], ctx["peaks"]
    )
    return 100.0 * least / ctx["trace"]["busy_s"]
