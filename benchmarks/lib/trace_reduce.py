"""From a jax.profiler trace (``*.xplane.pb``) to the numbers the
device metrics need. Two steps, so that the arithmetic is testable on a
small recorded trace without the chip:

``load_xplane(path)``  the profile as plain data,
    {plane name: {line name: [(event name, start ns, duration ns)]}}
``reduce(planes)``     busy union, idle gaps, per-operation and
    per-program device seconds, on the device planes.

What a TPU v5e trace holds (looked at by hand, PR 23): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per
executed HLO operation (a ``while`` of a scan is one event that spans
its body's operations, which are events of their own inside it, and
is named by its whole HLO line) and whose line ``XLA Modules`` carries
one event per executed program, named ``jit_<function>(<fingerprint>)``.
Host threads are lines of ``/host:CPU``. Timestamps of all planes share
one clock that starts near the start of the trace. The device's events
begin later and end earlier than the interval the profiler was on for
(its last half second or so never arrives), so the window is the span
of the device's own events, and ``anchor_ns`` places it on the host's
monotonic clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANCHOR = "bench_anchor"  # lib/serve_child.py writes it at a known monotonic time


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return hits[-1] if hits else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events
            )
    return planes


def union_ns(intervals: list) -> tuple[int, list]:
    """(covered ns, merged [(start, end)]) of possibly nested or
    overlapping (start, end) intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def short_op(name: str) -> str:
    """An operation's event is named by its whole HLO line; keep the
    result's name, the opcode and what a fusion calls."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:120]
    m = re.search(r"\}?\s([a-z][a-z0-9\-]*)\(", rhs)
    calls = re.search(r"calls=(%[\w.\-]+)", rhs)
    out = f"{lhs} {m.group(1) if m else ''}".strip()
    if calls:
        out += f" calls={calls.group(1)}"
    return out[:120]


def anchor_ns(planes: dict) -> int | None:
    """Start of the benchmark's own anchor event on the trace's clock."""
    for line in planes.get(HOST_PLANE, {}).values():
        for name, start, _ in line:
            if name == ANCHOR:
                return start
    return None


def program_of(name: str) -> str:
    """``jit_solve(123456)`` -> ``jit_solve``: the fingerprint changes
    with the shapes, the program's name does not."""
    return name.split("(", 1)[0]


def _top(totals: dict, n: int) -> list:
    return [
        [k, v / 1e9]
        for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    ]


def reduce(planes: dict, top: int = 10) -> dict | None:
    """None when no device plane holds an operation: a reader then
    returns nothing and the metric is left out of the line."""
    devices = {
        name: lines
        for name, lines in planes.items()
        if name.startswith(DEVICE_PREFIX) and lines.get(OPS_LINE)
    }
    if not devices:
        return None
    starts = [
        s for lines in devices.values() for line in lines.values() for _, s, _ in line
    ]
    ends = [
        s + d
        for lines in devices.values()
        for line in lines.values()
        for _, s, d in line
    ]
    lo, hi = min(starts), max(ends)
    busy_ns = []
    op_ns: dict = {}
    prog_ns: dict = {}
    prog_runs: dict = {}
    gaps: list = []
    for lines in devices.values():
        ops = lines[OPS_LINE]
        covered, merged = union_ns([(s, s + d) for _, s, d in ops])
        busy_ns.append(covered)
        # self time per operation: an enclosing event (while, call,
        # conditional) is charged only what its inner events leave
        for name, self_ns in _self_times(ops):
            name = short_op(name)
            op_ns[name] = op_ns.get(name, 0) + self_ns
        for name, _, d in lines.get(MODULES_LINE, []):
            p = program_of(name)
            prog_ns[p] = prog_ns.get(p, 0) + d
            prog_runs[p] = prog_runs.get(p, 0) + 1
        if len(devices) == 1:
            prev_end = lo
            names_at = _starts_by_time(lines.get(MODULES_LINE, []))
            for s, e in merged:
                if s > prev_end:
                    gaps.append((s - prev_end, prev_end, s))
                prev_end = e
            if hi > prev_end:
                gaps.append((hi - prev_end, prev_end, hi))
            gaps = [
                [_gap_label(names_at, g0, g1), ns / 1e9]
                for ns, g0, g1 in sorted(gaps, reverse=True)[:top]
            ]
    window_ns = hi - lo
    busy = sum(busy_ns) / len(busy_ns)
    return {
        "devices": len(devices),
        "window_s": window_ns / 1e9,
        "busy_s": busy / 1e9,
        "idle_share": 1.0 - busy / window_ns if window_ns else None,
        "lo_ns": lo,
        "hi_ns": hi,
        "anchor_ns": anchor_ns(planes),
        "device_ops": _top(op_ns, top),
        "programs": {
            p: {"seconds": prog_ns[p] / 1e9, "runs": prog_runs[p]}
            for p in prog_ns
        },
        "idle_gaps": gaps,
    }


def _self_times(ops: list):
    """(name, self ns) per event of one line whose events nest."""
    stack: list = []  # [name, end, self_ns]
    for name, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            yield done[0], done[2]
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    while stack:
        done = stack.pop()
        yield done[0], done[2]


def _starts_by_time(modules: list) -> list:
    return sorted((s, s + d, program_of(name)) for name, s, d in modules)


def _gap_label(modules: list, g0: int, g1: int) -> str:
    """What can be told today about an idle gap: the programs the device
    ran just before and just after it (the program carries no host
    annotation inside the traced interval)."""
    before = after = None
    for s, e, name in modules:
        if e <= g0 + 1000:
            before = name
        if s >= g1 - 1000 and after is None:
            after = name
    if before and after and any(s < g0 and e > g1 for s, e, _ in modules):
        return f"inside {before}"
    return f"after {before or 'trace start'}, before {after or 'trace end'}"
