"""Device self time of every operation that has one of the named scopes
ANYWHERE on its name-stack path, per pod bound in the traced span.

How nesting is booked. ``scan_scope_us_per_pod.py`` charges an operation
to the INNERMOST of the program's scopes on its path, so that table's
``grouped_slow`` keeps only what no plugin scope inside the slow chunk
claims (the scan step's glue), and ``PodTopologySpread`` there is the
fast and the slow branch together. This reader charges by the path: an
operation under ``grouped_slow/.../NodeResourcesFit`` counts here AND,
once, in the innermost table. So the metric is a cut ACROSS the
``x_scan_*`` ones, not one more summand: time under ``grouped_slow`` +
time under ``grouped_fast`` + time under neither (``pack``, ``unpack``,
the control-flow shells, which carry no name) = ``device_us_per_pod``.
The three are printed once a run as ``{"info": "grouped_paths", ...}``.

Self time is ``trace_reduce``'s (a ``while`` event spans its body's
events; each instant goes to the innermost EVENT), over the same
``XLA Ops`` lines, so the three seconds add up to ``busy_s``.

None where the run was not traced, the capture cannot be read, or no
operation of it carries any scope (a program without scopes, or another
program's executables out of a warm compile cache). 0.0 where scopes are
there and none of the named ones is: the cell ran no such branch.
"""

import json
import sys

from benchmarks.lib import span_attrib, trace_reduce

OUTER = ("grouped_slow", "grouped_fast")
_done: dict = {}  # capture path -> {scope or "neither": seconds} or None


def outermost(op_name: str) -> str | None:
    """Which of OUTER the path runs through; "neither" for a path under
    other scopes only; None for a path under no scope of the program."""
    parts = op_name.split("/")
    for part in parts:
        if part in OUTER:
            return part
    return "neither" if any(p in span_attrib.SCOPES for p in parts) else None


def by_outer_scope(path: str) -> dict | None:
    """{"grouped_slow" | "grouped_fast" | "neither": self seconds} of the
    capture, a device's share where there are several."""
    space = span_attrib._xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    ns: dict = {}
    n_dev = 0
    any_scope = False
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        by_id = {}
        for e in plane.event_metadata:
            for s in e.value.stats:
                if stat_names.get(s.metadata_id) == "tf_op":
                    # a string, or a reference to a stat's name
                    name = stat_names.get(s.ref_value, "") if s.ref_value else s.str_value
                    by_id[e.key] = outermost(name)
        ops = [
            (by_id.get(ev.metadata_id), line.timestamp_ns + ev.offset_ps // 1000,
             ev.duration_ps // 1000)
            for line in plane.lines if line.name == trace_reduce.OPS_LINE
            for ev in line.events
        ]
        n_dev += bool(ops)
        for scope, self_ns in trace_reduce._self_times(ops):
            any_scope = any_scope or scope is not None
            key = scope or "neither"
            ns[key] = ns.get(key, 0) + self_ns
    if not any_scope:
        return None
    return {k: v / n_dev / 1e9 for k, v in sorted(ns.items())}


def read(ctx, scopes):
    traced = ctx.get("traced")
    if not ctx.get("trace") or not traced or not traced["pods"]:
        return None
    path = ctx["trace"]["xplane"]  # the capture run.py reduced
    if path not in _done:
        try:
            _done[path] = by_outer_scope(path)
            print(json.dumps({"info": "grouped_paths", "cell": ctx["cell"]["name"],
                              "seconds": _done[path]}), flush=True)
        except Exception as e:  # a reader returns nothing; it does not raise
            print(f"[bench] scope_path: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
            _done[path] = None
    got = _done[path]
    if got is None:
        return None
    return sum(got.get(s, 0.0) for s in scopes) / traced["pods"] * 1e6
