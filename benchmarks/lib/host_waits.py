"""What held the host's threads while the device sat idle, read off the
capture that span_attrib reads: the program's ``gc:gen<N>`` annotations
(one collection, on whichever thread ran it), ``wait:cluster.lock`` (an
acquire of ``cluster.lock`` that had to wait) and the ``cpu_us`` stat of
its ``stage:*`` annotations (the thread's CPU time inside the stage);
``kubernetes_tpu/obs/waits.py`` and ``Telemetry.stage`` write them.

``span_attrib.load`` keeps only ``stage:*`` events, so the capture is
parsed once more here, once a run. The readings, cuts across
span_attrib's partition of the idle time and not buckets of it:

* ``idle_under_gc_pct``: device-idle time while any ``gc:*`` event is
  open on any host thread, % of the span;
* ``idle_under_lock_wait_pct``: device-idle time while the dispatch
  loop's thread is inside ``wait:cluster.lock``, % of the span;
* ``loop_offcpu_pct``: over the loop thread's outermost ``stage:*``
  events that lie wholly in the span, except ``deferred_read`` (it waits
  on the device by design), sum(duration - cpu_us) / sum(duration), %.

A capture of a program without these instruments (no ``gc:`` or
``wait:`` event and no ``cpu_us`` stat) reads None for each, never 0;
one with them reads 0 where, say, no acquire of the loop had to wait in
the span.
"""

from __future__ import annotations

import json
import sys

from . import trace_reduce
from .span_attrib import INGEST, LOOP_MARK, STAGE, _xspace_class, overlap, total

GC = "gc:"
GC2 = "gc:gen2"
WAIT = "wait:cluster.lock"
CPU = "cpu_us"
ON_DEVICE = "stage:deferred_read"  # waits on the device by design
KEPT = (GC, "wait:", STAGE)

_captures: dict = {}  # (xplane path, span) -> reading or None, once a process


def load(path: str) -> dict:
    """The capture as plain data: per device plane the ``XLA Ops``
    events as (start ns, end ns); per host thread (a line) its ``gc:*``,
    ``wait:*`` and ``stage:*`` events as (name, start ns, duration ns,
    cpu_us or None)."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    devices: dict = {}
    threads: list = []
    for plane in space.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (line.timestamp_ns + ev.offset_ps // 1000,
                         line.timestamp_ns + (ev.offset_ps + ev.duration_ps) // 1000)
                        for ev in line.events
                    )
        elif plane.name == trace_reduce.HOST_PLANE:
            names = {
                e.key: e.value.name for e in plane.event_metadata
                if e.value.name.startswith(KEPT)
            }
            cpu_ids = {e.key for e in plane.stat_metadata if e.value.name == CPU}

            def cpu_of(ev):
                for s in ev.stats:
                    if s.metadata_id in cpu_ids:
                        return s.int64_value or s.uint64_value or int(s.double_value)
                return None

            for line in plane.lines:
                evs = [
                    (names[ev.metadata_id], line.timestamp_ns + ev.offset_ps // 1000,
                     ev.duration_ps // 1000, cpu_of(ev))
                    for ev in line.events if ev.metadata_id in names
                ]
                if evs:
                    threads.append(evs)
    return {"devices": devices, "threads": threads}


def _merged(intervals: list) -> list:
    return [tuple(x) for x in trace_reduce.union_ns(intervals)[1]]


def _outermost(events: list) -> list:
    """The events of one thread that no other of its events encloses."""
    out: list = []
    end = None
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if end is None or ev[1] >= end:
            out.append(ev)
            end = ev[1] + ev[2]
    return out


def attribute(capture: dict, lo_ns: int, hi_ns: int, top: int = 10) -> dict:
    """The three readings in seconds, and what the info line prints:
    each stage's off-CPU seconds, the split of the loop's off-CPU
    seconds, the collections in the span, the longest idle gaps with the
    share of each under ``gc:`` and under the loop's ``wait:``."""
    threads = capture["threads"]
    window = hi_ns - lo_ns
    out: dict = {
        "window_s": window / 1e9, "instrumented": any(
            n.startswith((GC, WAIT)) or cpu is not None
            for th in threads for n, _, _, cpu in th
        ),
        "idle_under_gc_s": None, "idle_under_lock_wait_s": None,
        "loop_offcpu_share": None,
    }
    if not out["instrumented"]:
        return out
    loop = [th for th in threads if any(e[0] == LOOP_MARK for e in th)]
    gc_all = _merged([(s, s + d) for th in threads for n, s, d, _ in th if n.startswith(GC)])
    gc2 = _merged([(s, s + d) for th in threads for n, s, d, _ in th if n == GC2])
    loop_ids = {id(th) for th in loop}
    gc_off_loop = _merged([
        (s, s + d) for th in threads if id(th) not in loop_ids
        for n, s, d, _ in th if n.startswith(GC)
    ])
    waits = _merged([(s, s + d) for th in loop for n, s, d, _ in th if n == WAIT])

    # the loop thread's outermost stages in the span, their CPU time
    by_stage: dict = {}
    counted: list = []
    for th in loop:
        for name, s, d, cpu in _outermost(
            [e for e in th if e[0].startswith(STAGE) and e[0] != INGEST]
        ):
            if s < lo_ns or s + d > hi_ns:
                continue
            row = by_stage.setdefault(name[len(STAGE):], {
                "events": 0, "wall_s": 0.0, "cpu_s": 0.0, "no_cpu_us": 0,
            })
            row["events"] += 1
            row["wall_s"] += d / 1e9
            if cpu is None:
                row["no_cpu_us"] += 1
            else:
                row["cpu_s"] += cpu / 1e6
            if name != ON_DEVICE:
                counted.append((s, d, cpu))
    for row in by_stage.values():
        row["off_cpu_s"] = row["wall_s"] - row["cpu_s"]
    out["stages"] = by_stage
    wall = sum(d for _, d, _ in counted)
    if wall and all(cpu is not None for *_, cpu in counted):
        off_ns = wall - sum(cpu * 1000 for *_, cpu in counted)
        out["loop_offcpu_share"] = off_ns / wall
        spans = _merged([(s, s + d) for s, d, _ in counted])
        lock_ns = total(overlap(spans, waits))
        gc_ns = total(overlap(spans, gc_off_loop))
        # off the CPU inside the counted stages: waiting for cluster.lock,
        # while another thread collected (it holds the interpreter), the rest
        out["offcpu_split_s"] = {
            "off_cpu": off_ns / 1e9, "lock_wait": lock_ns / 1e9,
            "gc_on_another_thread": gc_ns / 1e9,
            "rest": (off_ns - lock_ns - gc_ns) / 1e9,
        }

    gens: dict = {}
    for th in threads:
        for n, s, d, _ in th:
            if n.startswith(GC) and lo_ns <= s < hi_ns:
                g = gens.setdefault(n[len(GC):], {"n": 0, "s": 0.0, "longest_s": 0.0})
                g["n"] += 1
                g["s"] += d / 1e9
                g["longest_s"] = max(g["longest_s"], d / 1e9)
    out["gc_in_span"] = gens
    out["loop_lock_waits_in_span"] = {
        "n": sum(1 for th in loop for e in th if e[0] == WAIT and lo_ns <= e[1] < hi_ns),
        "s": total(overlap([(lo_ns, hi_ns)], waits)) / 1e9,
    }

    if len(capture["devices"]) == 1:
        (ops,) = capture["devices"].values()
        idle, prev = [], lo_ns
        for s, e in trace_reduce.union_ns(ops)[1]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        if hi_ns > prev:
            idle.append((prev, hi_ns))
        out["idle_under_gc_s"] = total(overlap(idle, gc_all)) / 1e9
        out["idle_under_lock_wait_s"] = total(overlap(idle, waits)) / 1e9
        out["idle_under_gc2_s"] = total(overlap(idle, gc2)) / 1e9
        out["longest_gaps"] = [
            {
                "at_s": (g[0] - lo_ns) / 1e9, "seconds": (g[1] - g[0]) / 1e9,
                "gc_share": total(overlap([g], gc_all)) / (g[1] - g[0]),
                "gc2_share": total(overlap([g], gc2)) / (g[1] - g[0]),
                "lock_wait_share": total(overlap([g], waits)) / (g[1] - g[0]),
            }
            for g in sorted(idle, key=lambda g: g[0] - g[1])[:top]
        ]
    return out


def _window(ctx: dict) -> dict:
    """The same instruments' counters over the run's window: per
    generation the collections, their seconds and the bucket the longest
    fell in; per thread the seconds and acquires that waited."""
    m0, m1 = ctx.get("m0") or {}, ctx.get("m1") or {}
    out: dict = {}

    def d(name, labels):
        key = (name, tuple(sorted(labels.items())))
        return m1.get(key, 0.0) - m0.get(key, 0.0)

    def buckets(m, g):
        return {
            dict(ls)["le"]: v for (name, ls), v in m.items()
            if name == "scheduler_gc_pause_seconds_bucket" and dict(ls).get("generation") == g
        }

    for g in "012":
        n = d("scheduler_gc_pause_seconds_count", {"generation": g})
        b0, b1 = buckets(m0, g), buckets(m1, g)
        below, longest = 0.0, None
        for le, v in sorted((float(le), v - b0.get(le, 0.0)) for le, v in b1.items()):
            if v >= n:  # cumulative: every pause of the window is at most le
                longest = [below, le]
                break
            below = le
        out[f"gen{g}"] = {
            "n": n, "s": d("scheduler_gc_pause_seconds_sum", {"generation": g}),
            "longest_between_s": longest if n else None,
        }
    out["lock_wait"] = {
        t: {"s": d("scheduler_cluster_lock_wait_seconds_total", {"thread": t}),
            "n": d("scheduler_cluster_lock_contended_total", {"thread": t})}
        for t in ("loop", "ingest", "other")
    }
    return out


def for_cell(ctx: dict) -> dict | None:
    """This run's reading, and one ``{"info": "host_waits"}`` line the
    first time a reader asks. None when the run was not traced or the
    capture cannot be read."""
    tr = ctx.get("trace")
    if not tr:
        return None
    path = tr["xplane"]
    key = (path, tr["lo_ns"], tr["hi_ns"])
    if key not in _captures:
        try:
            got = attribute(load(path), tr["lo_ns"], tr["hi_ns"])
            if got["instrumented"]:
                got["window_counters"] = _window(ctx)
            print(json.dumps({"info": "host_waits", "cell": ctx["cell"]["name"], **got}),
                  flush=True)
        except Exception as e:  # a reader returns nothing; it does not raise
            print(f"[bench] host_waits: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
            got = None
        _captures[key] = got
    return _captures[key]


def pct(ctx: dict, reading: str) -> float | None:
    """One reading as % (of the span, or of the loop's stage time)."""
    got = for_cell(ctx)
    if not got:
        return None
    if reading == "loop_offcpu_pct":
        share = got["loop_offcpu_share"]
        return None if share is None else 100.0 * share
    seconds = got[reading[: -len("_pct")] + "_s"]
    if seconds is None or not got["window_s"]:
        return None
    return 100.0 * seconds / got["window_s"]
