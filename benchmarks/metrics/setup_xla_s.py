def read(ctx):
    if ctx["m0"] is None:
        return None
    return ctx["metric_sum"](ctx["m0"], "scheduler_xla_compile_seconds_total")
