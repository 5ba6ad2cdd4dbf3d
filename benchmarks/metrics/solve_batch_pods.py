def read(ctx):
    n = ctx["delta"]("scheduler_tpu_solve_batch_size_count")
    if not n:
        return None
    return ctx["delta"]("scheduler_tpu_solve_batch_size_sum") / n
