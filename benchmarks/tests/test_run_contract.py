"""run.py's own promises: the last line's keys, no result without a TPU,
no result without the program."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.lib import files

RUN = os.path.join(files.BENCH, "run.py")


def run(argv, cwd=files.ROOT, timeout=300, env=None):
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        timeout=timeout, cwd=cwd, env=env,
    )


def test_rehearsal_last_line_has_the_contracts_keys():
    out = run([RUN, "--workload", "spread-5k.backlog", "--seed", "3000000019",
               "--seconds", "2", "--trace", "0", "--rehearse-cpu"])
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line) == [
        "correct", "attempted", "failed", "metrics", "device", "rehearsal",
        "compared",
    ]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"pods_bound_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # a CPU run carries no device number
    assert set(line["device"]) == {"platform", "kind", "count"}
    assert line["device"]["platform"] == "cpu"
    assert out.stderr.strip().splitlines()[-1].startswith("compared ")


def test_traced_last_line_has_the_device_share(tmp_path, capsys):
    """The traced half: ``device`` has ``window_s`` and ``busy_s`` (above
    0, at most ``window_s``) and the line a ``breakdown``; against the
    stand-in handing out a recorded capture, through drive() as a
    measured run makes it (tests/test_marks.py has the refusals)."""
    from test_marks import GOOD, drive

    line, _, _ = drive(tmp_path, capsys, 1, 1.0, captures=[GOOD])
    assert list(line) == [
        "correct", "attempted", "failed", "metrics", "device", "breakdown", "compared",
    ]
    dev = line["device"]
    assert set(dev) == {
        "platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s",
    }
    assert dev["platform"] == "tpu" and 0 < dev["busy_s"] <= dev["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(rows) <= 10 for rows in line["breakdown"].values())
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert "pods_bound_per_s" not in line["metrics"]  # --trace 1: the per-layer metrics


def test_without_rehearse_flag_a_non_tpu_device_gives_no_result():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the child is given JAX_PLATFORMS=tpu anyway
    out = run([RUN, "--workload", "basic-5k.backlog", "--seed", "1",
               "--seconds", "1", "--trace", "0"], env=env)
    assert out.returncode != 0
    assert not any(row.startswith('{"correct"') for row in out.stdout.splitlines())


def test_no_result_where_only_the_benchmark_is(tmp_path):
    shutil.copytree(
        files.BENCH, tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache", "data"),
    )
    shutil.copy(os.path.join(files.ROOT, "BENCHMARK.json"), tmp_path)
    out = run([str(tmp_path / "benchmarks" / "run.py"), "--workload",
               "basic-5k.backlog", "--seed", "1", "--seconds", "1", "--trace", "0"],
              cwd=str(tmp_path))
    assert out.returncode != 0 and out.stdout.strip() == ""
