def read(ctx):
    b = ctx["delta"]("scheduler_tpu_host_to_device_bytes_total")
    if not b or not ctx["bound_in_window"]:
        return None
    return b / ctx["bound_in_window"]
