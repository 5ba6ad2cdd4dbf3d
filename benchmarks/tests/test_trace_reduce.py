"""The reduction from a profiler trace to busy union, idle share and
per-program device time: on a case worked by hand, and on a small
recorded slice of a TPU v5e trace (data/trace_slice.json.gz: a few
programs of a mixed-5k.backlog run, PR 23) against a second method."""

import json
import os

import pytest

from benchmarks.lib import trace_reduce as tr
from conftest import recorded_slice

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HAND = {
    "/device:TPU:0": {
        "XLA Ops": [
            ("%while.1 = (s32[]{:T(128)}, s32[8]{0}) while(%tuple.1), body=%b", 100, 1000),
            ("%fusion.1 = s32[8]{0:T(128)} fusion(s32[8]{0} %p), kind=kLoop, calls=%fc.1", 200, 300),
            ("%fusion.2 = s32[8]{0:T(128)} fusion(s32[8]{0} %q), kind=kLoop, calls=%fc.2", 600, 400),
            ("%copy.3 = s32[8]{0:T(128)} copy(s32[8]{0} %r)", 2000, 500),
        ],
        "XLA Modules": [("jit_a(11)", 100, 1000), ("jit_b(22)", 2000, 500)],
    },
    "/host:CPU": {"python3": [("bench_anchor", 50, 1)], "other": [("x", 0, 9000)]},
}


def test_hand_worked_case():
    got = tr.reduce(HAND)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(2400e-9)
    assert got["busy_s"] == pytest.approx(1500e-9)  # the while covers its body
    assert got["idle_share"] == pytest.approx(0.375)
    assert got["anchor_ns"] == 50
    ops = dict(got["device_ops"])
    assert ops["%while.1 while"] == pytest.approx(300e-9)  # 1000 - 300 - 400
    assert ops["%fusion.2 fusion calls=%fc.2"] == pytest.approx(400e-9)
    assert ops["%copy.3 copy"] == pytest.approx(500e-9)
    assert got["programs"] == {
        "jit_a": {"seconds": pytest.approx(1000e-9), "runs": 1},
        "jit_b": {"seconds": pytest.approx(500e-9), "runs": 1},
    }
    assert got["idle_gaps"] == [["after jit_a, before jit_b", pytest.approx(900e-9)]]


def test_nothing_on_the_device_reads_as_nothing():
    assert tr.reduce({"/host:CPU": HAND["/host:CPU"]}) is None
    assert tr.reduce({"/device:TPU:0": {"XLA Modules": [("jit_a(1)", 0, 5)]}}) is None


def sweep_busy(intervals):
    """A second method: count open intervals along the sorted edges."""
    edges = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in intervals],
                   key=lambda x: (x[0], -x[1]))
    busy = depth = 0
    last = None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_slice():
    planes = recorded_slice()
    got = tr.reduce(planes)
    ops = planes["/device:TPU:0"]["XLA Ops"]
    mods = planes["/device:TPU:0"]["XLA Modules"]
    assert len(ops) > 500 and len(mods) >= 3  # one solve and the small programs around it
    busy = sweep_busy([(s, s + d) for _, s, d in ops])
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    every = [e for line in planes["/device:TPU:0"].values() for e in line]
    lo = min(s for _, s, _ in every)
    hi = max(s + d for _, s, d in every)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0.0 < got["idle_share"] < 1.0
    assert got["idle_share"] == pytest.approx(1 - busy / (hi - lo))
    # per-program seconds are the module events', summed by name
    by_name = {}
    for name, _, d in mods:
        key = name.split("(")[0]
        by_name[key] = by_name.get(key, 0) + d
    assert {p: v["seconds"] for p, v in got["programs"].items()} == {
        p: pytest.approx(ns / 1e9) for p, ns in by_name.items()
    }
    # operations' self times add up to the busy time: nothing counted twice
    total = sum(ns for _, ns in tr._self_times(ops))
    assert total == busy
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"] * (1 + 1e-9)
    assert got["anchor_ns"] is not None
    # pinned from this file, so that a change of the arithmetic shows
    with open(os.path.join(DATA, "trace_slice.expected.json")) as f:
        want = json.load(f)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["device_ops"][0][0] == want["top_op"]
