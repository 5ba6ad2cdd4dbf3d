"""Pods bound per second of the window, with a batch counted as bound
when it ends.

N(t), the pods bound through a batch's end (the stamp of its last
``bound`` record), is known at each batch end and interpolated linearly
between consecutive ends; the reading is (N(t1) - N(t0)) / seconds. A
steady stream reads as the count of records stamped inside the window
does; a stream of clumps (a batch of 1,024 every two seconds) reads its
own rate wherever t0 and t1 fall in a clump's cycle, where the count
reads one clump more or less."""

import sys
from bisect import bisect_right

from benchmarks.lib import journal


def batch_ends(times: list, steps: list) -> tuple[list, list]:
    """Each batch's end, in order, and N through it."""
    last: dict = {}  # step -> [end, pods]
    for t, s in zip(times, steps):
        row = last.setdefault(s, [t, 0])
        row[0] = max(row[0], t)
        row[1] += 1
    ends, through, n = [], [], 0
    for end, pods in sorted(last.values()):
        n += pods
        ends.append(end)
        through.append(n)
    return ends, through


def bound_through(ends: list, through: list, t: float):
    """N at ``t``; None where no batch ends on one side of it."""
    i = bisect_right(ends, t)  # ends[i - 1] <= t < ends[i]
    if i == 0 or i == len(ends):
        return None
    e0, e1 = ends[i - 1], ends[i]
    return through[i - 1] + (through[i] - through[i - 1]) * (t - e0) / (e1 - e0)


def read(ctx):
    """None, and why on stderr, where the journal cannot give it: never
    the count in its place."""
    times, steps, t0, t1 = ctx["bound_times"], ctx["bound_steps"], ctx["t0"], ctx["t1"]
    if not journal.batch_done_after(times, steps, t1):
        return _none("the batch at t1 is not known to be complete: no pod of a later batch was bound")
    ends, through = batch_ends(times, steps)
    n0, n1 = bound_through(ends, through, t0), bound_through(ends, through, t1)
    if n0 is None or n1 is None:
        return _none(f"no batch ends before t0 or after t1 (ends {ends[:1]}..{ends[-1:]})")
    return (n1 - n0) / ctx["seconds"]


def _none(why: str):
    print(f"[bench] no pods_bound_per_s: {why}", file=sys.stderr, flush=True)
    return None
