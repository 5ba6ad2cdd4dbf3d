"""Device-idle time inside the named stages of the dispatch loop's
thread (``stage:*`` annotations; "none": no stage open), % of the
traced span. The buckets partition the idle time, so the metrics that
share this reader add up to device_idle_pct."""

from benchmarks.lib import span_attrib


def read(ctx, stages):
    got = span_attrib.for_cell(ctx)
    if not got or got["idle_s"] is None or not got["window_s"]:
        return None
    return 100.0 * sum(got["idle_s"].get(s, 0.0) for s in stages) / got["window_s"]
