"""pods_bound_per_s on synthetic journals: N(t), the pods bound through
each batch's end, interpolated between batch ends, over the window.

Each case builds (times, steps) of the bound records as the program
journals them (a batch's pods share its ``step``, stamps rise in file
order), the window, and what the reading has to come to."""

import json

import pytest

from benchmarks.lib import files, journal

READ = files.load_reader(files.load_metrics()["pods_bound_per_s"])
SECONDS = 30.0


def clumps(period, phase, batch=1024, commits=8, spread_s=0.3, until=40.0):
    """Serial batches: one every ``period`` s from ``phase`` on, each
    bound in ``commits`` sub-commits spread over ``spread_s``."""
    times, steps = [], []
    k, start = 0, phase - period
    while start < until:
        for c in range(commits):
            times += [start + spread_s * c / (commits - 1)] * (batch // commits)
            steps += [k] * (batch // commits)
        k, start = k + 1, start + period
    return times, steps


def stream(rate, batch=128, until=40.0):
    """Records as a rate ``rate(t)`` gives them (evenly within each small
    step of time), ``batch`` consecutive records a batch."""
    times, n, t, dt = [], 0.0, -5.0, 0.001
    while t < until:
        n += rate(t) * dt
        while len(times) < int(n):
            times.append(t)
        t += dt
    return times, [i // batch for i in range(len(times))]


def sawtooth(t, lo=2000.0, hi=3800.0, period=15.0):
    """The basic cell's rate: up and down between lo and hi every period."""
    x = (t % period) / period
    return lo + (hi - lo) * (2 * x if x < 0.5 else 2 - 2 * x)


def straddles():
    """Four batches by hand: the second straddles t0 = 0.5, the fourth t1
    = 3.0, a fifth begins after t1. N through the ends 0.2 / 1.0 / 2.0 /
    3.2 is 10 / 30 / 60 / 100, so N(t0) = 10 + 20 × 0.3 / 0.8 and N(t1) =
    60 + 40 × 1.0 / 1.2."""
    rows = [(1, 0.0, 0.2, 10), (2, 0.4, 1.0, 20), (3, 1.6, 2.0, 30),
            (4, 2.8, 3.2, 40), (5, 3.5, 3.6, 5)]
    times, steps = [], []
    for step, a, b, n in rows:
        times += [a + (b - a) * i / (n - 1) for i in range(n)]
        steps += [step] * n
    want = ((60 + 40 * 1.0 / 1.2) - (10 + 20 * 0.3 / 0.8)) / 2.5
    return times, steps, 0.5, 2.5, want


def count(times, t0, seconds):
    return sum(1 for t in times if t0 <= t < t0 + seconds) / seconds


def case(name):
    """(times, steps, t0, seconds, the reading wanted, its tolerance)."""
    if name.startswith("clumps@"):
        phase = float(name.split("@")[1]) * 2.06
        times, steps = clumps(2.06, phase)
        return times, steps, 5.0, SECONDS, 1024 / 2.06, 0.005
    if name == "steady":
        times, steps = stream(lambda t: 3200.0)
        return times, steps, 5.0, SECONDS, count(times, 5.0, SECONDS), 0.001
    if name == "sawtooth":
        times, steps = stream(sawtooth)
        return times, steps, 5.0, SECONDS, 2900.0, 0.001  # two whole periods
    if name == "straddles":
        times, steps, t0, seconds, want = straddles()
        return times, steps, t0, seconds, want, 1e-9
    raise ValueError(name)


@pytest.mark.parametrize(
    "name",
    [f"clumps@{p / 8}" for p in range(8)]
    + ["steady", "sawtooth", "straddles", "none after t1", "none before t0"],
)
def test_the_reading(name, capsys):
    if name.startswith("none"):
        times, steps, t0, seconds, _ = straddles()
        if name == "none after t1":  # the run stopped waiting with the batch at t1 open
            times, steps = times[:-5], steps[:-5]
        else:
            t0 = -1.0
        ctx = {"bound_times": times, "bound_steps": steps, "t0": t0,
               "t1": t0 + seconds, "seconds": seconds}
        assert READ(ctx) is None  # and never the count in its place
        assert "no pods_bound_per_s: " in capsys.readouterr().err
        return
    times, steps, t0, seconds, want, tol = case(name)
    ctx = {"bound_times": times, "bound_steps": steps, "t0": t0,
           "t1": t0 + seconds, "seconds": seconds}
    assert READ(ctx) == pytest.approx(want, rel=tol)


def test_the_count_reads_a_clump_high_or_low_where_the_reading_does_not():
    """What the reading repairs: over the phases of a 2.06 s cycle the
    count of records inside the window moves by a clump (±6.9 %)."""
    rate = 1024 / 2.06
    off = [count(clumps(2.06, p / 8 * 2.06)[0], 5.0, SECONDS) / rate - 1 for p in range(8)]
    assert max(off) - min(off) > 0.06


def test_the_tail_keeps_each_bound_records_step_and_refuses_one_without(tmp_path):
    path = tmp_path / "journal.jsonl"
    recs = [
        {"k": "dec", "step": 3, "pod": "a", "outcome": "bound", "node": "n", "t": 1.0},
        {"k": "dec", "step": 3, "pod": "b", "outcome": "unschedulable", "t": 1.1},
        {"k": "span", "t": 1.2},
        {"k": "dec", "step": 4, "pod": "c", "outcome": "bound", "node": "n", "t": 1.3},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    tail = journal.JournalTail(str(path))
    assert tail.poll() == 2 and tail.steps == [3, 4] and tail.times == [1.0, 1.3]
    assert journal.batch_done_after(tail.times, tail.steps, 0.5)
    assert not journal.batch_done_after(tail.times, tail.steps, 1.2)
    with open(path, "a") as f:
        f.write(json.dumps({"k": "dec", "pod": "d", "outcome": "bound", "t": 1.4}) + "\n")
    with pytest.raises(ValueError, match="step"):
        tail.poll()
