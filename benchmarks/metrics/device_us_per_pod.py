def read(ctx):
    got = ctx["traced"]
    if not got or not got["pods"]:
        return None
    return ctx["trace"]["busy_s"] / got["pods"] * 1e6
