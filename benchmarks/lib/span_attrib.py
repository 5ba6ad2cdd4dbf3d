"""Who was idle, and under which name the device worked: the program's
own ``stage:*`` annotations and ``jax.named_scope`` names, read off the
same profiler capture as the device's events.

``trace_reduce.load_xplane`` keeps (name, start, duration) and drops
what this needs, so the capture is parsed a second time here, with its
stats. What a TPU v5e capture holds (looked at by hand, PR 25):

* a device operation's scope is NOT in its event name (the HLO line,
  without ``metadata={...}``) and not in the event's own stats
  (``device_offset_ps``, ``device_duration_ps``): it is the stat
  ``tf_op`` of the event's METADATA entry, the operation's ``op_name``
  (``jit(_run_packed)/while/body/closed_call/select/reduce_max:``).
  ``jax.profiler.ProfileData`` does not expose metadata stats, so the
  file is read as the protobuf it is (``XSpace``; the few messages are
  declared below, no generated module is imported);
* a ``jax.profiler.TraceAnnotation`` is an event of a line of
  ``/host:CPU``, one line a thread, all Python threads' lines named
  alike, so a thread is told by what it writes: the dispatch loop's is
  the one with ``stage:dispatch`` events, ``stage:ingest`` is on the
  server's event-loop thread.

Busy and idle are the same ``XLA Ops`` union ``trace_reduce.reduce``
takes, over the same span ``[lo_ns, hi_ns]``, and self time follows its
rule, so that: the idle seconds of the loop thread's buckets add up to
the span's idle time, and scoped + unscoped seconds add up to ``busy_s``.

A capture of a program without the annotations (the parent of PR 25) or
without scopes (or with another program's executables out of a warm
compile cache, whose key ignores metadata) reads as None, never as 0.
"""

from __future__ import annotations

import json
import sys

from . import trace_reduce

# solver/exact.py's SCOPES, restated (the benchmark imports nothing of
# the program; tests/test_named_scopes.py holds the two equal)
SCOPES = (
    "NodeResourcesFit", "NodePorts", "PodTopologySpread", "InterPodAffinity",
    "Score", "select", "assume", "grouped_fast", "grouped_slow", "unpack",
    "pack",
)
STAGE = "stage:"
INGEST = "stage:ingest"
LOOP_MARK = "stage:dispatch"  # its thread is the dispatch loop's
NONE = "none"  # the bucket of time under no stage / no scope

_captures: dict = {}  # (xplane path, span) -> attribution or None, once a process


def _xspace_class():
    """The message class of ``tsl.profiler.XSpace``, from a descriptor
    built here: field numbers as in xplane.proto; a map field is a
    repeated {key = 1, value = 2} entry on the wire."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    one, many = T.LABEL_OPTIONAL, T.LABEL_REPEATED
    i64, u64, text = T.TYPE_INT64, T.TYPE_UINT64, T.TYPE_STRING
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="benchxp", syntax="proto3"
    )

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, typ, label in fields:
            fd = m.field.add(name=fname, number=number, label=label)
            if isinstance(typ, str):
                fd.type, fd.type_name = T.TYPE_MESSAGE, ".benchxp." + typ
            else:
                fd.type = typ

    msg("XStat", ("metadata_id", 1, i64, one), ("double_value", 2, T.TYPE_DOUBLE, one),
        ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
        ("str_value", 5, text, one), ("bytes_value", 6, T.TYPE_BYTES, one),
        ("ref_value", 7, u64, one))
    msg("XEvent", ("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
        ("duration_ps", 3, i64, one), ("stats", 4, "XStat", many))
    msg("XLine", ("id", 1, i64, one), ("name", 2, text, one),
        ("timestamp_ns", 3, i64, one), ("events", 4, "XEvent", many))
    msg("XEventMetadata", ("id", 1, i64, one), ("name", 2, text, one),
        ("stats", 5, "XStat", many))
    msg("XStatMetadata", ("id", 1, i64, one), ("name", 2, text, one))
    msg("EventMetadataEntry", ("key", 1, i64, one), ("value", 2, "XEventMetadata", one))
    msg("StatMetadataEntry", ("key", 1, i64, one), ("value", 2, "XStatMetadata", one))
    msg("XPlane", ("id", 1, i64, one), ("name", 2, text, one),
        ("lines", 3, "XLine", many), ("event_metadata", 4, "EventMetadataEntry", many),
        ("stat_metadata", 5, "StatMetadataEntry", many))
    msg("XSpace", ("planes", 1, "XPlane", many))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("benchxp.XSpace"))


def scope_of(op_name: str) -> str | None:
    """The innermost of SCOPES on an operation's name-stack path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def load(path: str) -> dict:
    """The capture as plain data: per device plane the ``XLA Ops``
    events as (scope or None, start ns, duration ns); per host thread
    (a line) its ``stage:*`` events as (name, start ns, duration ns,
    {stat: value})."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    devices: dict = {}
    threads: list = []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}

        def value(stat):
            if stat.ref_value:  # a string kept once, as a stat's name
                return stat_names.get(stat.ref_value, "")
            return stat.str_value or stat.int64_value or stat.uint64_value or stat.double_value

        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            scope_by_id = {}
            for e in plane.event_metadata:
                op_name = next(
                    (value(s) for s in e.value.stats
                     if stat_names.get(s.metadata_id) == "tf_op"), None,
                )
                scope_by_id[e.key] = scope_of(op_name) if op_name else None
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (scope_by_id.get(ev.metadata_id),
                         line.timestamp_ns + ev.offset_ps // 1000,
                         ev.duration_ps // 1000)
                        for ev in line.events
                    )
        elif plane.name == trace_reduce.HOST_PLANE:
            stage_ids = {
                e.key: e.value.name for e in plane.event_metadata
                if e.value.name.startswith(STAGE)
            }
            for line in plane.lines:
                evs = [
                    (stage_ids[ev.metadata_id],
                     line.timestamp_ns + ev.offset_ps // 1000,
                     ev.duration_ps // 1000,
                     {stat_names.get(s.metadata_id, "?"): value(s) for s in ev.stats})
                    for ev in line.events if ev.metadata_id in stage_ids
                ]
                if evs:
                    threads.append(evs)
    return {"devices": devices, "threads": threads}


def innermost(events: list) -> dict:
    """{name: [(start, end)]} of one thread's nested intervals, each
    instant charged to the innermost open one."""
    out: dict = {}
    stack: list = []  # [name, end, cursor]

    def emit(name, s, e):
        if e > s:
            out.setdefault(name, []).append((s, e))

    for name, s, d in sorted(((n, s, d) for n, s, d, *_ in events), key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            emit(done[0], done[2], done[1])
            if stack:
                stack[-1][2] = max(stack[-1][2], done[1])
        if stack:
            emit(stack[-1][0], stack[-1][2], min(s, stack[-1][1]))
            stack[-1][2] = max(stack[-1][2], s)
        stack.append([name, s + d, s])
    while stack:
        done = stack.pop()
        emit(done[0], done[2], done[1])
        if stack:
            stack[-1][2] = max(stack[-1][2], done[1])
    return out


def overlap(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint (start, end)."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def attribute(capture: dict, lo_ns: int, hi_ns: int, top: int = 10) -> dict:
    """Idle seconds per stage of the loop thread and device self-seconds
    per scope, over the device's own span. ``idle_s`` / ``scope_s`` are
    None where the capture has no stage event / no scoped operation."""
    n_dev = len(capture["devices"]) or 1
    idle: list = []  # one device: its idle intervals, for the stage split
    idle_ns = busy_ns = 0
    scope_ns: dict = {}
    for ops in capture["devices"].values():
        covered, merged = trace_reduce.union_ns([(s, s + d) for _, s, d in ops])
        busy_ns += covered
        gaps, prev = [], lo_ns
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if hi_ns > prev:
            gaps.append((prev, hi_ns))
        idle_ns += total(gaps)
        idle = gaps
        for scope, self_ns in trace_reduce._self_times(ops):
            scope_ns[scope or NONE] = scope_ns.get(scope or NONE, 0) + self_ns
    out: dict = {
        "window_s": (hi_ns - lo_ns) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "idle_total_s": idle_ns / n_dev / 1e9,
        "idle_s": None, "scope_s": None,
    }
    if set(scope_ns) - {NONE}:
        out["scope_s"] = {k: v / n_dev / 1e9 for k, v in sorted(scope_ns.items())}

    loop = [ev for th in capture["threads"] if any(e[0] == LOOP_MARK for e in th)
            for ev in th if ev[0] != INGEST]
    ingest = [
        tuple(x) for x in trace_reduce.union_ns(
            [(s, s + d) for th in capture["threads"] for n, s, d, *_ in th if n == INGEST]
        )[1]
    ]
    if (loop or ingest) and n_dev == 1:
        by_stage = {n[len(STAGE):]: segs for n, segs in innermost(loop).items()}
        idle_ingest = overlap(idle, ingest)
        idle_by = {k: total(overlap(idle, segs)) for k, segs in by_stage.items()}
        both_by = {k: total(overlap(idle_ingest, segs)) for k, segs in by_stage.items()}
        idle_by[NONE] = idle_ns - sum(idle_by.values())
        both_by[NONE] = total(idle_ingest) - sum(both_by.values())
        out["idle_s"] = {k: v / 1e9 for k, v in idle_by.items()}
        # of each bucket's idle seconds, those with stage:ingest open on
        # the server's thread at the same time
        out["idle_with_ingest_open_s"] = {k: v / 1e9 for k, v in both_by.items()}
        own = _by_name(loop)  # each stage's own events in the capture
        out["stage_open_s"] = {n[len(STAGE):]: total(v) / 1e9 for n, v in own.items()}
        out["stage_events"] = {n[len(STAGE):]: len(v) for n, v in own.items()}
        out["ingest_open_s"] = total(overlap([(lo_ns, hi_ns)], ingest)) / 1e9
        longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        out["longest_gaps"] = [_gap_row(g, by_stage, ingest, lo_ns) for g in longest]
    return out


def _by_name(events: list) -> dict:
    out: dict = {}
    for name, s, d, *_ in events:
        out.setdefault(name, []).append((s, s + d))
    return out


def _gap_row(gap, by_stage: dict, ingest: list, lo_ns: int) -> dict:
    length = gap[1] - gap[0]
    cover = {name: total(overlap([gap], segs)) for name, segs in by_stage.items()}
    cover[NONE] = length - sum(cover.values())
    stage = max(cover, key=cover.get)
    return {
        "at_s": (gap[0] - lo_ns) / 1e9, "seconds": length / 1e9, "stage": stage,
        "stage_share": cover[stage] / length if length else None,
        "ingest_open_share": total(overlap([gap], ingest)) / length if length else None,
    }


def for_cell(ctx: dict) -> dict | None:
    """The attribution of this run's capture: the file run.py reduced
    (``trace["xplane"]``; a reader is handed the reduction, not the
    planes, and the file stays in the cell's work directory until the
    next run). None when the run was not traced, or the capture cannot
    be read."""
    tr = ctx.get("trace")
    if not tr:
        return None
    path = tr["xplane"]
    key = (path, tr["lo_ns"], tr["hi_ns"])
    if key not in _captures:
        try:
            got = attribute(load(path), tr["lo_ns"], tr["hi_ns"])
            # the full table, once a run, by whichever reader came first
            print(json.dumps({"info": "attribution", "cell": ctx["cell"]["name"], **got}), flush=True)
        except Exception as e:  # a reader returns nothing; it does not raise
            print(f"[bench] span_attrib: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
            got = None
        _captures[key] = got
    return _captures[key]
