def read(ctx):
    return ctx["serve_ready_s"]
