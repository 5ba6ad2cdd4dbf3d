"""The window's marks (run.Marks) and the traced run's rule, against the
stand-in (lib/standin.py) handing out recorded captures: a ``--trace 1``
run prints a line whose ``device`` has ``window_s`` and ``busy_s`` > 0
read from one capture that holds device work under its anchor, or prints
no result line and exits != 0.

The captures: the recorded slice of a TPU v5e trace (data/
trace_slice.json.gz), the same without its anchor, and one in which the
device did nothing (the host plane alone), during which the stand-in
holds its scheduler still as a compile inside the window does. The
tests shorten a capture (run.TRACE_SECONDS): at its real length a window
under 30 s holds one.
"""

import argparse
import json
import os

import pytest

from benchmarks import run
from benchmarks.lib import files, standin, trace_reduce as tr
from conftest import recorded_slice

CELL = "spread-5k.backlog"
TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "pace_pods_per_s": 1000.0}


SLICE = recorded_slice()
GOOD = {"planes": SLICE}
EMPTY = {"planes": {tr.HOST_PLANE: SLICE[tr.HOST_PLANE]}, "stall": True}
NO_ANCHOR = {"planes": {p: ls for p, ls in SLICE.items() if p != tr.HOST_PLANE}}


@pytest.fixture
def short_captures(monkeypatch):
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.5)
    monkeypatch.setattr(run, "SIGN_LEAD_S", 0.1)
    monkeypatch.setattr(run, "RETAKE_MARGIN_S", 0.2)


def tiny_cell():
    cell = files.load_workload(CELL)
    cfg = files.load_config(cell["config"])
    run.apply_rehearsal(cell, cfg)
    return cell, cfg


def drive(tmp_path, capsys, trace, seconds, **standin_args):
    """run.drive as a measured run makes it (no rehearsal), over the
    stand-in: (the line or the refusal, the window's info, the stand-in)."""
    cell, cfg = tiny_cell()
    system = standin.StandIn(cfg, str(tmp_path), **TPU, **standin_args)
    args = argparse.Namespace(seed=2147483659, seconds=seconds, trace=trace, rehearse_cpu=False)
    try:
        got = run.drive(args, cell, cfg, system, str(tmp_path))
    except run.NoCapture as e:
        got = e
    finally:
        system.close()
    (window,) = [
        json.loads(r) for r in capsys.readouterr().out.splitlines() if '"info": "window"' in r
    ]
    return got, window, system


def test_a_healthy_traced_run_takes_one_capture_at_its_fixed_place(
    tmp_path, capsys, short_captures
):
    line, window, system = drive(tmp_path, capsys, 1, 3.0, captures=[GOOD])
    assert system.asked == ["trace_start", "trace_stop"]
    (cap,) = window["captures"]
    assert cap["reduced"] is True and cap["why_not"] is None and cap["solves_inside"] >= 1
    assert cap["from_t0"] == pytest.approx(run.TRACE_START_SHARE * 3.0, abs=0.05)
    assert cap["asked_seconds"] == pytest.approx(run.TRACE_SECONDS, abs=0.05)
    assert window["traced"]["capture"] == 0
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    want = tr.reduce(GOOD["planes"])
    assert (dev["busy_s"], dev["window_s"]) == (want["busy_s"], want["window_s"])
    assert line["breakdown"]["device_ops"] == want["device_ops"]
    assert line["correct"] is True and "device_idle_pct.backlog" in line["metrics"]


def test_a_capture_with_no_device_work_is_followed_by_one_that_is_the_runs(
    tmp_path, capsys, short_captures
):
    line, window, system = drive(tmp_path, capsys, 1, 3.0, captures=[EMPTY, GOOD])
    assert system.asked == ["trace_start", "trace_stop"] * 2
    first, second = window["captures"]
    assert first["solves_inside"] == 0 and first["reduced"] is False
    assert first["why_not"] == "no device plane holds an operation"
    assert second["reduced"] is True and second["from_t0"] > first["from_t0"] + 0.5
    assert window["traced"]["capture"] == 1
    # every device number comes from that one: its own directory, its own anchor
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert window["traced"]["from_t0"] > second["from_t0"]
    assert os.path.isdir(tmp_path / "trace-0") and os.path.isdir(tmp_path / "trace-1")


def test_a_capture_without_its_anchor_does_not_qualify(tmp_path, capsys, short_captures):
    refusal, window, _ = drive(tmp_path, capsys, 1, 2.0, captures=[NO_ANCHOR])
    assert isinstance(refusal, run.NoCapture)
    (cap,) = window["captures"]
    assert cap["reduced"] is False and cap["why_not"] == "the anchor event is missing"
    assert "the anchor event is missing" in str(refusal)


def test_a_trace_stop_that_answers_after_t1_leaves_m1_at_t1(tmp_path, capsys, short_captures):
    line, window, _ = drive(tmp_path, capsys, 1, 2.0, captures=[GOOD], stop_seconds=1.5)
    (cap,) = window["captures"]
    assert cap["stop_seconds"] >= 1.5 and cap["from_t0"] + 0.5 + 1.5 > 2.0  # past t1
    asked, answered = window["scrapes"]["m1_from_t1"]
    assert 0 <= asked <= answered < 0.1
    asked, answered = window["scrapes"]["m0_from_t0"]
    assert 0 <= asked <= answered < 0.1
    assert line["device"]["busy_s"] > 0


def test_an_untraced_run_asks_for_no_trace(tmp_path, capsys):
    line, window, system = drive(tmp_path, capsys, 0, 1.0, captures=[GOOD])
    assert "trace_start" not in system.asked and "trace_stop" not in system.asked
    assert window["captures"] == [] and window["traced"] is None
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "breakdown" not in line and set(line["metrics"]) == {"pods_bound_per_s", "setup_s"}


def test_every_capture_empty_gives_no_result_line_and_a_nonzero_exit(
    tmp_path, capsys, monkeypatch, short_captures
):
    cell, cfg = tiny_cell()
    monkeypatch.setattr(files, "load_workload", lambda name: cell)
    monkeypatch.setattr(files, "load_config", lambda name: cfg)
    made = []

    def serve(root, workdir, state_path, platforms, telemetry):
        assert platforms == "tpu" and telemetry
        made.append(standin.StandIn(cfg, workdir, **TPU, captures=[EMPTY]))
        return made[0]

    monkeypatch.setattr(run.serve_mod, "Serve", serve)
    code = run.main(["--workload", CELL, "--seed", "7", "--seconds", "3", "--trace", "1"])
    out = capsys.readouterr()
    assert code != 0
    assert not any(row.startswith('{"correct"') for row in out.out.splitlines())
    assert made[0].asked.count("trace_start") >= 2  # it tried again while the window held one
    (window,) = [json.loads(r) for r in out.out.splitlines() if '"info": "window"' in r]
    assert all(c["reduced"] is False and c["solves_inside"] == 0 for c in window["captures"])
    assert "no result: NoCapture" in out.err and "no device plane holds an operation" in out.err


def test_pick_capture_takes_the_first_that_qualifies_and_says_why_not(tmp_path):
    stamps = {"t_ask": 0.0, "t_anchor": 0.0, "t_on": 0.0, "t_off": 0.0}
    caps = [{"dir": str(tmp_path / f"trace-{n}"), "on": stamps, "off": stamps} for n in range(6)]
    del caps[0]["off"]  # trace_stop never answered
    os.makedirs(caps[1]["dir"])  # answered, nothing written
    standin.write_capture(caps[2]["dir"], EMPTY["planes"])
    standin.write_capture(caps[3]["dir"], NO_ANCHOR["planes"])
    standin.write_capture(caps[4]["dir"], GOOD["planes"])
    standin.write_capture(caps[5]["dir"], GOOD["planes"])
    got = run.pick_capture(caps)
    assert got["capture"] == 4 and got["xplane"] == tr.find_xplane(caps[4]["dir"])
    assert got["busy_s"] == tr.reduce(GOOD["planes"])["busy_s"]
    assert [c.get("why_not") for c in caps] == [
        "no answer to trace_stop", "no *.xplane.pb was written",
        "no device plane holds an operation", "the anchor event is missing", None, None,
    ]
    assert [c.get("reduced") for c in caps] == [False, False, False, False, True, None]
    assert run.pick_capture(caps[:4]) is None and run.pick_capture([]) is None
