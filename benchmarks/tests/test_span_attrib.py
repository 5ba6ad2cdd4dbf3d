"""The attribution of idle time to the program's stages and of device
time to its scopes (lib/span_attrib.py): a case worked by hand, written
as a real ``.xplane.pb`` and read back through the eleven metrics'
readers; and a recorded slice of a TPU v5e capture of the spread cell
(data/attrib_slice.json.gz, PR 25) with both reconciliations."""

import gzip
import json
import os

import pytest

from benchmarks.lib import files, span_attrib as sa, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "spread-5k.backlog"

# (HLO line, op_name or None, start ns, duration ns)
OPS = [
    ("%fusion.10 = s64[8]{0} fusion(%p), kind=kLoop, calls=%fc.10",
     "jit(_run_packed)/while/body/grouped_fast/while/body/select/scatter-max", 100, 200),
    ("%while.78 = (s32[]) while(%t), body=%b", "jit(_run_packed)/while", 400, 400),
    ("%fusion.1175 = s32[8]{0} fusion(%q), kind=kLoop, calls=%fc.1175",
     "jit(_run_packed)/while/body/grouped_fast/while/body/select/PodTopologySpread/scatter-add",
     450, 100),
    ("%fusion.1182 = s32[8]{0} fusion(%r), kind=kLoop, calls=%fc.1182",
     "jit(_run_packed)/while/body/grouped_fast/while/body/select/scatter-add", 600, 100),
]
LOOP = [("stage:tensorize", 0, 350), ("stage:bind", 50, 40), ("stage:dispatch", 350, 30),
        ("stage:bind", 820, 130)]
INGEST = [("stage:ingest", 370, 50), ("stage:ingest", 900, 200)]


def write_xplane(path, ops=OPS, loop=LOOP, ingest=INGEST, ref_strings=False):
    """A capture as the profiler writes it: the scope in the metadata
    entry's ``tf_op`` stat (a string, or a reference to a stat name)."""
    space = sa._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    names = {"tf_op": 1, "hlo_category": 2}
    for n, i in names.items():
        e = dev.stat_metadata.add(key=i)
        e.value.id, e.value.name = i, n
    line = dev.lines.add(name=tr.OPS_LINE, timestamp_ns=0)
    for k, (hlo, op_name, s, d) in enumerate(ops, start=1):
        e = dev.event_metadata.add(key=k)
        e.value.id, e.value.name = k, hlo
        e.value.stats.add(metadata_id=2, str_value="fusion")
        if op_name and ref_strings:
            ref = 100 + k
            r = dev.stat_metadata.add(key=ref)
            r.value.id, r.value.name = ref, op_name
            e.value.stats.add(metadata_id=1, ref_value=ref)
        elif op_name:
            e.value.stats.add(metadata_id=1, str_value=op_name)
        line.events.add(metadata_id=k, offset_ps=s * 1000, duration_ps=d * 1000)
    dev.lines.add(name=tr.MODULES_LINE)
    host = space.planes.add(name=tr.HOST_PLANE)
    for i, n in ((1, "step"), (2, "pods")):
        e = host.stat_metadata.add(key=i)
        e.value.id, e.value.name = i, n
    ids = {}
    for thread, base in ((loop, 7), (ingest, 11), ([("PjitFunction(x)", 5, 10)], 0)):
        if not thread:
            continue
        # the line's own timestamp is part of every event's start
        hl = host.lines.add(name="python", timestamp_ns=base)
        for name, s, d in thread:
            if name not in ids:
                ids[name] = len(ids) + 1
                e = host.event_metadata.add(key=ids[name])
                e.value.id, e.value.name = ids[name], name
            ev = hl.events.add(
                metadata_id=ids[name], offset_ps=(s - base) * 1000, duration_ps=d * 1000
            )
            ev.stats.add(metadata_id=2, int64_value=64)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


@pytest.mark.parametrize("ref_strings", [False, True])
def test_load_reads_scopes_and_stages_from_the_file(tmp_path, ref_strings):
    path = tmp_path / "a.xplane.pb"
    write_xplane(path, ref_strings=ref_strings)
    got = sa.load(str(path))
    assert got["devices"] == {"/device:TPU:0": [
        ("select", 100, 200), (None, 400, 400), ("PodTopologySpread", 450, 100),
        ("select", 600, 100),
    ]}
    assert got["threads"] == [
        [(n, s, d, {"pods": 64}) for n, s, d in LOOP],
        [(n, s, d, {"pods": 64}) for n, s, d in INGEST],
    ]


def test_hand_worked_attribution(tmp_path):
    path = tmp_path / "a.xplane.pb"
    write_xplane(path)
    got = sa.attribute(sa.load(str(path)), 0, 1000)
    ns = 1e-9
    assert got["busy_s"] == pytest.approx(600 * ns)
    assert got["idle_total_s"] == pytest.approx(400 * ns)
    # self time: the while is charged what its inner operations leave
    assert got["scope_s"] == {
        "PodTopologySpread": pytest.approx(100 * ns),
        "none": pytest.approx(200 * ns),
        "select": pytest.approx(300 * ns),
    }
    # the bind nested in tensorize takes its own 40 ns; the gap 380-400
    # and the ends of the last gap have no stage open
    assert got["idle_s"] == {
        "tensorize": pytest.approx(110 * ns), "bind": pytest.approx(170 * ns),
        "dispatch": pytest.approx(30 * ns), "none": pytest.approx(90 * ns),
    }
    assert got["idle_with_ingest_open_s"] == {
        "tensorize": 0.0, "bind": pytest.approx(50 * ns),
        "dispatch": pytest.approx(10 * ns), "none": pytest.approx(70 * ns),
    }
    assert got["stage_open_s"]["bind"] == pytest.approx(170 * ns)
    assert got["stage_events"] == {"tensorize": 1, "bind": 2, "dispatch": 1}
    assert got["ingest_open_s"] == pytest.approx(150 * ns)  # clipped to the span
    top = got["longest_gaps"][0]
    assert top["seconds"] == pytest.approx(200 * ns) and top["stage"] == "bind"
    assert top["stage_share"] == pytest.approx(0.65)
    assert top["ingest_open_share"] == pytest.approx(0.5)
    assert [g["stage"] for g in got["longest_gaps"]] == ["bind", "tensorize", "tensorize"]


def test_nothing_to_read_is_none_never_zero(tmp_path):
    # the parent of PR 25: no stage annotation, no scope
    bare = [(hlo, op and "jit(_run_packed)/while/body/mul", s, d) for hlo, op, s, d in OPS]
    path = tmp_path / "parent.xplane.pb"
    write_xplane(path, ops=bare, loop=[], ingest=[])
    got = sa.attribute(sa.load(str(path)), 0, 1000)
    assert got["idle_s"] is None and got["scope_s"] is None
    assert got["busy_s"] == pytest.approx(600e-9)
    # stale names out of a warm compile cache: stages yes, scopes no
    path = tmp_path / "stale.xplane.pb"
    write_xplane(path, ops=bare)
    got = sa.attribute(sa.load(str(path)), 0, 1000)
    assert got["scope_s"] is None and got["idle_s"]["bind"] > 0


def ctx_for(tmp_path, **kw):
    """What run.py hands a reader, over a capture in the cell's work
    directory of a checkout rooted at tmp_path."""
    d = tmp_path / ".bench_work" / CELL / "trace-0" / "plugins" / "profile" / "2026_10_01"
    d.mkdir(parents=True)
    write_xplane(d / "vm.xplane.pb", **kw)
    counters = {
        "scheduler_ingest_seconds_total": 1.5, "scheduler_ingest_pods_total": 6000.0,
        "scheduler_tpu_trace_journal_seconds_total": 0.5,
        "scheduler_tpu_trace_journal_records_total": 8192.0,
    }
    return {
        "cell": {"name": CELL}, "traced": {"pods": 50},
        "trace": {"lo_ns": 0, "hi_ns": 1000, "busy_s": 600e-9, "idle_share": 0.4,
                  "xplane": str(d / "vm.xplane.pb")},
        "m1": {(name, ()): 2 * v for name, v in counters.items()},
        "delta": lambda name, **labels: counters.get(name, 0.0),
    }


# PR 25's eleven, by name: where a metric sorts, or what a later PR calls
# its own, is nothing these tests hold
ELEVEN = (
    "x_idle_in_bind_pct.backlog", "x_idle_in_tensorize_pct.backlog",
    "x_idle_in_other_stage_pct.backlog", "x_idle_unattributed_pct.backlog",
    "x_scan_spread_us_per_pod.backlog", "x_scan_fit_us_per_pod.backlog",
    "x_scan_score_us_per_pod.backlog", "x_scan_select_assume_us_per_pod.backlog",
    "x_device_unscoped_pct.backlog", "x_ingest_server_s_per_kpod.backlog",
    "x_journal_s_per_kpod.backlog",
)


def read_all(ctx):
    ms = files.load_metrics()
    return {n: files.load_reader(ms[n])(ctx, **ms[n].get("args", {})) for n in ELEVEN}


def test_the_eleven_are_owed_by_every_backlog_cell():
    ms = files.load_metrics()
    for n in ELEVEN:
        assert ms[n]["moves"] == "pods_bound_per_s" and "workloads" not in ms[n]
    for cell in files.names("workloads"):
        mine = files.metrics_of_cell(files.load_workload(cell), "per_layer")
        assert set(ELEVEN) <= set(mine)


def test_the_eleven_metrics_and_both_reconciliations(tmp_path, capsys):
    ctx = ctx_for(tmp_path)
    got = read_all(ctx)
    assert got == {
        "x_idle_in_bind_pct.backlog": pytest.approx(17.0),
        "x_idle_in_tensorize_pct.backlog": pytest.approx(11.0),
        "x_idle_in_other_stage_pct.backlog": pytest.approx(3.0),
        "x_idle_unattributed_pct.backlog": pytest.approx(9.0),
        "x_scan_spread_us_per_pod.backlog": pytest.approx(100e-9 / 50 * 1e6),
        "x_scan_fit_us_per_pod.backlog": 0.0,
        "x_scan_score_us_per_pod.backlog": 0.0,
        "x_scan_select_assume_us_per_pod.backlog": pytest.approx(300e-9 / 50 * 1e6),
        "x_device_unscoped_pct.backlog": pytest.approx(100 * 200 / 600),
        "x_ingest_server_s_per_kpod.backlog": pytest.approx(0.25),
        "x_journal_s_per_kpod.backlog": pytest.approx(0.5 / 8.192),
    }
    # the four idle shares partition device_idle_pct
    idle = [v for k, v in got.items() if k.startswith(("x_idle_in", "x_idle_un"))]
    assert len(idle) == 4 and sum(idle) == pytest.approx(100 * ctx["trace"]["idle_share"])
    # one attribution line for the run, however many readers asked
    lines = [json.loads(row) for row in capsys.readouterr().out.splitlines()]
    assert [row["info"] for row in lines] == ["attribution"]
    assert lines[0]["cell"] == CELL
    assert sum(lines[0]["scope_s"].values()) == pytest.approx(ctx["trace"]["busy_s"])
    assert len(lines[0]["longest_gaps"]) == 3


def test_readers_leave_the_metric_out_where_there_is_nothing(tmp_path):
    bare = [(hlo, None, s, d) for hlo, _, s, d in OPS]
    ctx = ctx_for(tmp_path, ops=bare, loop=[], ingest=[])
    # a program without the new counters: the journal's record count is
    # older than its seconds, and 0 seconds over 8,192 records is no reading
    ctx["m1"] = {("scheduler_tpu_trace_journal_records_total", (("outcome", "bound"),)): 9000.0}
    ctx["delta"] = lambda name, **labels: 8192.0 if "records" in name else 0.0
    assert set(read_all(ctx).values()) == {None}
    # an untraced run, or the reference in the program's place: no look at all
    ctx["trace"] = None
    assert set(read_all(ctx).values()) == {None}


def test_an_unreadable_capture_raises_nothing(tmp_path, capsys):
    ctx = ctx_for(tmp_path)
    with open(ctx["trace"]["xplane"], "wb") as f:
        f.write(b"\xff not a protobuf \xff")
    ctx["trace"]["hi_ns"] = 1001  # not the memoised capture of another test
    assert sa.for_cell(ctx) is None
    assert "span_attrib" in capsys.readouterr().err


def test_innermost_takes_the_time():
    got = sa.innermost([
        ("a", 0, 100, {}), ("b", 10, 50, {}), ("c", 20, 10, {}), ("b", 70, 10, {}),
        ("d", 200, 5, {}),
    ])
    assert got == {
        "a": [(0, 10), (60, 70), (80, 100)], "b": [(10, 20), (30, 60), (70, 80)],
        "c": [(20, 30)], "d": [(200, 205)],
    }
    assert sa.overlap([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == [(5, 10), (20, 25), (28, 30)]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "attrib_slice.json.gz"), "rt") as f:
        doc = json.load(f)
    doc["threads"] = [[tuple(e) for e in th] for th in doc["threads"]]
    doc["devices"] = {p: [tuple(e) for e in evs] for p, evs in doc["devices"].items()}
    return doc


def test_recorded_slice(recorded):
    """A quarter second of spread-5k.backlog on the chip (call c1 of PR
    25, seed 3600000013): one stage:tensorize, then the first sub-solves
    of the batch with their reads, applies and binds."""
    (ops,) = recorded["devices"].values()
    assert len(ops) > 10_000 and recorded["anchor_ns"] is not None
    assert {sc for sc, _, _ in ops} >= {
        None, "select", "assume", "PodTopologySpread", "Score", "NodeResourcesFit",
    }
    loop = [th for th in recorded["threads"] if any(e[0] == sa.LOOP_MARK for e in th)]
    assert len(loop) == 1 and len(recorded["threads"]) == 2  # and the ingest thread
    assert {e[0] for e in loop[0]} == {
        "stage:tensorize", "stage:dispatch", "stage:deferred_read", "stage:validate",
        "stage:apply", "stage:bind",
    }
    lo, hi = recorded["lo_ns"], recorded["hi_ns"]
    got = sa.attribute(recorded, lo, hi)

    # busy by a second method: open intervals along the sorted edges
    edges = sorted(
        [(s, 1) for _, s, _ in ops] + [(s + d, -1) for _, s, d in ops],
        key=lambda x: (x[0], -x[1]),
    )
    busy = depth = 0
    last = None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    # and by trace_reduce's own reduction of the same events
    reduced = tr.reduce({"/device:TPU:0": {tr.OPS_LINE: [(str(sc), s, d) for sc, s, d in ops]}})
    assert reduced["busy_s"] == pytest.approx(got["busy_s"], rel=1e-12)

    # reconciliation 1: the buckets of the loop thread partition the idle time
    idle_pct = 100.0 * (1.0 - got["busy_s"] / got["window_s"])
    buckets = {
        "bind": ["bind"], "tensorize": ["tensorize"], "unattributed": ["none"],
        "other": ["dispatch", "fence_wait", "deferred_read", "validate", "apply"],
    }
    shares = {
        k: 100.0 * sum(got["idle_s"].get(s, 0.0) for s in v) / got["window_s"]
        for k, v in buckets.items()
    }
    assert set(got["idle_s"]) <= {s for v in buckets.values() for s in v}
    assert sum(shares.values()) == pytest.approx(idle_pct, abs=0.5)
    assert shares["tensorize"] > 40 > shares["other"] > shares["bind"]  # what this slice is
    # reconciliation 2: scoped + unscoped seconds are the busy seconds
    assert sum(got["scope_s"].values()) == pytest.approx(got["busy_s"], rel=0.01)
    assert got["scope_s"]["select"] > 0.7 * got["busy_s"]
    assert got["scope_s"]["none"] < 0.2 * got["busy_s"]

    # pinned from the file, so that a change of the arithmetic shows
    want = recorded["expected"]
    assert got["scope_s"] == {k: pytest.approx(v, rel=1e-9) for k, v in want["scope_s"].items()}
    assert got["idle_s"] == {
        k: pytest.approx(v, rel=1e-9, abs=1e-12) for k, v in want["idle_s"].items()
    }
    assert [g["stage"] for g in got["longest_gaps"]] == [
        g["stage"] for g in want["longest_gaps"]
    ]
    assert got["longest_gaps"][0]["stage"] == "tensorize"


def test_recorded_slice_without_scopes_reads_as_none(recorded):
    """The same events as a warm compile cache filled by the parent gave
    them (call c1: `scope_s` null on the stale cache): no scope on any
    operation is None for every scope metric, while the stages still read."""
    stale = dict(recorded)
    stale["devices"] = {
        p: [(None, s, d) for _, s, d in evs] for p, evs in recorded["devices"].items()
    }
    got = sa.attribute(stale, recorded["lo_ns"], recorded["hi_ns"])
    assert got["scope_s"] is None
    assert got["idle_s"]["tensorize"] == pytest.approx(recorded["expected"]["idle_s"]["tensorize"])
