"""The seven StageProfiler stages (serve --telemetry), summed."""


def read(ctx):
    total = ctx["delta"]("scheduler_profile_stage_seconds_total")
    if not total or not ctx["bound_in_window"]:
        return None
    return total / ctx["bound_in_window"] * 1000.0
