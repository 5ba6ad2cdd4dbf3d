def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
