"""The generator's spans around POST /api/pods that left in the window."""


def read(ctx):
    spans = [p for p in ctx["posts"] if ctx["t0"] <= p[0] < ctx["t1"]]
    pods = sum(p[2] for p in spans)
    if not pods:
        return None
    return sum(p[1] - p[0] for p in spans) / pods * 1000.0
