def read(ctx):
    s = ctx["delta"]("scheduler_tpu_tensorize_seconds_sum")
    if not s or not ctx["bound_in_window"]:
        return None
    return s / ctx["bound_in_window"] * 1000.0
