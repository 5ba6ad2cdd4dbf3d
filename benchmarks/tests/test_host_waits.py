"""What held the host's threads (lib/host_waits.py): a capture worked by
hand, written as a real ``.xplane.pb``, read back through the five
metrics' readers; the same capture without the program's new events
reads None for each, and span_attrib's readers read the same either way."""

import json

import pytest

from benchmarks.lib import files, host_waits as hw, span_attrib as sa, trace_reduce as tr

CELL = "spread-5k.backlog"
U = 1000  # a microsecond, in the capture's nanoseconds

OPS = [(0, 100), (300, 400), (900, 1000)]  # idle: 100-300 and 400-900
# (name, start, duration, cpu_us or None), times in U
LOOP = [
    ("stage:tensorize", 0, 250, 100),
    ("stage:bind", 50, 40, 30),  # nested: its time is the tensorize's
    ("stage:dispatch", 260, 40, 40),
    ("stage:bind", 400, 300, 150),
    ("wait:cluster.lock", 420, 100, None),
    ("stage:deferred_read", 720, 170, 10),  # waits on the device: left out
    ("stage:apply", 950, 150, 50),  # ends after the span: left out
]
SERVER = [
    ("stage:ingest", 100, 100, 80),
    ("gc:gen0", 120, 10, None),
    ("gc:gen2", 550, 100, None),
]
FIVE = (
    "gc_pause_s_per_kpod.backlog", "loop_lock_wait_s_per_kpod.backlog",
    "idle_under_gc_pct.backlog", "idle_under_lock_wait_pct.backlog",
    "loop_offcpu_pct.backlog",
)


def parent_of(events):
    """The same events as a program without the instruments wrote them."""
    return [(n, s, d, None) for n, s, d, _ in events if n.startswith("stage:")]


def write_xplane(path, loop=LOOP, server=SERVER):
    space = sa._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    e = dev.event_metadata.add(key=1)
    e.value.id, e.value.name = 1, "%fusion.1 = s32[8]{0} fusion(%p)"
    line = dev.lines.add(name=tr.OPS_LINE, timestamp_ns=0)
    for s, end in OPS:
        line.events.add(metadata_id=1, offset_ps=s * U * 1000, duration_ps=(end - s) * U * 1000)
    host = space.planes.add(name=tr.HOST_PLANE)
    for i, n in ((1, "step"), (2, "cpu_us")):
        m = host.stat_metadata.add(key=i)
        m.value.id, m.value.name = i, n
    ids: dict = {}
    for thread in (loop, server, [("PjitFunction(x)", 5, 10, None)]):
        hl = host.lines.add(name="python", timestamp_ns=3)
        for name, s, d, cpu in thread:
            if name not in ids:
                ids[name] = len(ids) + 1
                m = host.event_metadata.add(key=ids[name])
                m.value.id, m.value.name = ids[name], name
            ev = hl.events.add(
                metadata_id=ids[name], offset_ps=(s * U - 3) * 1000, duration_ps=d * U * 1000
            )
            ev.stats.add(metadata_id=1, int64_value=7)
            if cpu is not None:
                ev.stats.add(metadata_id=2, int64_value=cpu)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def ctx_for(tmp_path, name="a", counters=True, **kw):
    path = tmp_path / f"{name}.xplane.pb"
    write_xplane(path, **kw)
    m0 = {
        ("scheduler_gc_pause_seconds_sum", (("generation", "0"),)): 0.5,
        ("scheduler_gc_pause_seconds_sum", (("generation", "2"),)): 1.0,
        ("scheduler_gc_pause_seconds_count", (("generation", "2"),)): 4.0,
        ("scheduler_cluster_lock_wait_seconds_total", (("thread", "loop"),)): 0.25,
        ("scheduler_cluster_lock_wait_seconds_total", (("thread", "ingest"),)): 0.5,
    }
    m1 = {
        ("scheduler_gc_pause_seconds_sum", (("generation", "0"),)): 0.6,
        ("scheduler_gc_pause_seconds_sum", (("generation", "2"),)): 1.6,
        ("scheduler_gc_pause_seconds_count", (("generation", "2"),)): 6.0,
        ("scheduler_cluster_lock_wait_seconds_total", (("thread", "loop"),)): 0.37,
        ("scheduler_cluster_lock_wait_seconds_total", (("thread", "ingest"),)): 0.9,
    }
    for le, before, after in (("0.1", 3, 3), ("0.2", 4, 5), ("0.5", 4, 6), ("+Inf", 4, 6)):
        key = ("scheduler_gc_pause_seconds_bucket", (("generation", "2"), ("le", le)))
        m0[key], m1[key] = before, after
    if not counters:
        m0, m1 = {}, {("scheduler_pending_pods", ()): 1.0}
    from benchmarks.lib.serve import metric_sum

    return {
        "cell": {"name": CELL}, "traced": {"pods": 50}, "bound_in_window": 6000,
        "trace": {"lo_ns": 0, "hi_ns": 1000 * U, "busy_s": 300 * U / 1e9,
                  "idle_share": 0.7, "xplane": str(path)},
        "m0": m0, "m1": m1,
        "delta": lambda n, **labels: metric_sum(m1, n, **labels) - metric_sum(m0, n, **labels),
    }


def read_all(ctx, names=FIVE):
    ms = files.load_metrics()
    return {n: files.load_reader(ms[n])(ctx, **ms[n].get("args", {})) for n in names}


def test_the_five_are_owed_by_every_backlog_cell():
    ms = files.load_metrics()
    for n in FIVE:
        assert ms[n]["moves"] == "pods_bound_per_s" and "workloads" not in ms[n]
        assert ms[n]["better"] == "lower"
    for cell in files.names("workloads"):
        mine = files.metrics_of_cell(files.load_workload(cell), "per_layer")
        assert set(FIVE) <= set(mine)


def test_hand_worked_values(tmp_path, capsys):
    got = read_all(ctx_for(tmp_path))
    assert got == {
        # Δ sum over every generation (0.1 + 0.6 s) per 6,000 pods
        "gc_pause_s_per_kpod.backlog": pytest.approx(0.7 / 6.0),
        # the loop's Δ only (0.12 s), not the ingest thread's
        "loop_lock_wait_s_per_kpod.backlog": pytest.approx(0.12 / 6.0),
        # idle 100-300 and 400-900; gen0 120-130 and gen2 550-650 in them
        "idle_under_gc_pct.backlog": pytest.approx(11.0),
        # the loop's wait 420-520, inside the second gap
        "idle_under_lock_wait_pct.backlog": pytest.approx(10.0),
        # tensorize 250 (cpu 100) + dispatch 40 (40) + bind 300 (150)
        "loop_offcpu_pct.backlog": pytest.approx(100.0 * 300 / 590),
    }
    (line,) = [json.loads(r) for r in capsys.readouterr().out.splitlines()]
    assert line["info"] == "host_waits" and line["cell"] == CELL
    assert line["stages"]["bind"] == {
        "events": 1, "wall_s": pytest.approx(300e-6), "cpu_s": pytest.approx(150e-6),
        "no_cpu_us": 0, "off_cpu_s": pytest.approx(150e-6),
    }
    assert line["stages"]["deferred_read"]["off_cpu_s"] == pytest.approx(160e-6)
    assert "apply" not in line["stages"]
    # of the 300 off the CPU: 100 waiting for the lock, 110 while the
    # server's thread collected, 90 neither
    assert line["offcpu_split_s"] == {
        "off_cpu": pytest.approx(300e-6), "lock_wait": pytest.approx(100e-6),
        "gc_on_another_thread": pytest.approx(110e-6), "rest": pytest.approx(90e-6),
    }
    assert line["gc_in_span"] == {
        "gen0": {"n": 1, "s": pytest.approx(10e-6), "longest_s": pytest.approx(10e-6)},
        "gen2": {"n": 1, "s": pytest.approx(100e-6), "longest_s": pytest.approx(100e-6)},
    }
    assert line["loop_lock_waits_in_span"] == {"n": 1, "s": pytest.approx(100e-6)}
    top = line["longest_gaps"][0]
    assert top["seconds"] == pytest.approx(500e-6)
    assert (top["gc2_share"], top["lock_wait_share"]) == (pytest.approx(0.2), pytest.approx(0.2))
    assert line["longest_gaps"][1]["gc_share"] == pytest.approx(0.05)
    gen2 = line["window_counters"]["gen2"]
    # two pauses in the window, one in (0.1, 0.2] and one in (0.2, 0.5]
    assert (gen2["n"], gen2["s"]) == (2.0, pytest.approx(0.6))
    assert gen2["longest_between_s"] == [0.2, 0.5]
    assert line["window_counters"]["lock_wait"]["loop"]["s"] == pytest.approx(0.12)


def test_without_the_new_events_each_is_none(tmp_path, capsys):
    ctx = ctx_for(tmp_path, "parent", counters=False,
                  loop=parent_of(LOOP), server=parent_of(SERVER))
    assert read_all(ctx) == dict.fromkeys(FIVE)
    assert capsys.readouterr().out.count('"host_waits"') == 1
    # an untraced run: no look at the capture at all
    ctx["trace"] = None
    assert read_all(ctx) == dict.fromkeys(FIVE)


def test_instrumented_with_nothing_to_charge_reads_zero(tmp_path):
    quiet = [e for e in LOOP if not e[0].startswith("wait:")]
    ctx = ctx_for(tmp_path, "quiet", loop=quiet,
                  server=[e for e in SERVER if not e[0].startswith("gc:")])
    got = read_all(ctx, FIVE[2:])
    assert got["idle_under_gc_pct.backlog"] == 0.0
    assert got["idle_under_lock_wait_pct.backlog"] == 0.0
    assert got["loop_offcpu_pct.backlog"] == pytest.approx(100.0 * 300 / 590)


def test_span_attrib_reads_the_same_with_or_without_the_new_events(tmp_path):
    """The new events are not ``stage:``: every x_* reader keeps reading
    what it read, and the four idle shares still add up."""
    idle = ("x_idle_in_bind_pct.backlog", "x_idle_in_tensorize_pct.backlog",
            "x_idle_in_other_stage_pct.backlog", "x_idle_unattributed_pct.backlog")
    with_new = read_all(ctx_for(tmp_path, "with"), idle)
    without = read_all(
        ctx_for(tmp_path, "without", loop=parent_of(LOOP), server=parent_of(SERVER)), idle
    )
    assert with_new == without
    assert sum(with_new.values()) == pytest.approx(70.0)
