"""Device-busy time under none of the program's scopes, % of busy: how
much of the device's work the names do not cover."""

from benchmarks.lib import span_attrib


def read(ctx):
    got = span_attrib.for_cell(ctx)
    if not got or got["scope_s"] is None or not got["busy_s"]:
        return None
    return 100.0 * got["scope_s"].get(span_attrib.NONE, 0.0) / got["busy_s"]
