"""Executables built OR fetched from the persistent cache inside the
window: either one stalls a batch. 0 is the sound reading, and it is
reported as 0: the contract wants the metric in every traced run."""


def read(ctx):
    return ctx["delta"]("scheduler_xla_compilations_total")
