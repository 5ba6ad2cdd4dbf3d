"""A later PR adds a cell, a configuration or a per-layer metric as NEW
files (plus entries in BENCHMARK.json) and edits nothing that is there:
shown on a copy of benchmarks/ in a temporary directory."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmarks.lib import files, manifest


def digest(root):
    out = {}
    for d, dirs, fs in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", ".pytest_cache")]
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_found_with_no_edit(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(
        files.BENCH, bench,
        ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"),
    )
    before = digest(bench)

    cfg = files.load_config("sched-perf-basic-5000n")
    cfg.update(name="foo-5000n", source="a throw-away deployment of this test")
    (bench / "configs" / "foo-5000n.json").write_text(json.dumps(cfg))
    cell = files.load_workload("basic-5k.backlog")
    cell.update(name="foo-5k.backlog", config="foo-5000n", why="a throw-away cell")
    (bench / "workloads" / "foo-5k.backlog.json").write_text(json.dumps(cell))
    (bench / "metrics" / "posts_per_s.backlog.json").write_text(
        json.dumps(
            {
                "name": "posts_per_s.backlog", "kind": "per_layer",
                "unit": "posts/s", "better": "higher", "source": "program_span",
                "layer": "ingest (server/extender.py)", "moves": "pods_bound_per_s",
                "workloads": ["foo-5k.backlog"],
                "reader": "posts_per_s.py", "what": "a throw-away metric",
            }
        )
    )
    (bench / "metrics" / "posts_per_s.py").write_text(
        "def read(ctx):\n"
        "    n = sum(1 for p in ctx['posts'] if ctx['t0'] <= p[0] < ctx['t1'])\n"
        "    return n / ctx['seconds'] or None\n"
    )

    after = digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 4

    # found by name from the directory
    new_cell = files.load_workload("foo-5k.backlog", str(bench))
    per_layer = files.metrics_of_cell(new_cell, "per_layer", str(bench))
    assert "posts_per_s.backlog" in per_layer
    assert "ingest_s_per_kpod.backlog" in per_layer  # the shared ones come along
    old_cell = files.load_workload("basic-5k.backlog", str(bench))
    assert "posts_per_s.backlog" not in files.metrics_of_cell(
        old_cell, "per_layer", str(bench)
    )
    doc = manifest.build(str(bench))
    old = manifest.build()
    assert [w for w in doc["workloads"] if w not in old["workloads"]] == [
        {k: new_cell[k] for k in ("name", "config", "traffic", "chips", "why")}
    ]
    assert [c["name"] for c in doc["configs"] if c not in old["configs"]] == ["foo-5000n"]
    assert [m["name"] for m in doc["per_layer"] if m not in old["per_layer"]] == [
        "posts_per_s.backlog"
    ]

    # and the harness runs the new cell, reader and all (the reference
    # stands in for the program: no chip here)
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); from benchmarks import control; "
         "raise SystemExit(control.main(['--workload', 'foo-5k.backlog', '--seed', '3', "
         "'--seconds', '1', '--fault', 'none', '--rehearse-size'], trace=1))"],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["posts_per_s.backlog"]["unit"] == "posts/s"
    assert line["metrics"]["posts_per_s.backlog"]["value"] > 0
