"""BENCHMARK.json against the contract's letter and against the files."""

import json
import os
import re
import shutil

import pytest

from benchmarks.lib import files, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(files.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_committed_manifest_is_what_the_files_build(doc):
    assert doc == manifest.build()


def test_a_new_file_is_appended_wherever_its_name_sorts(tmp_path, doc):
    """A later PR may only append: a metric, a cell and a configuration
    whose names sort first land at the end of their lists, and every
    committed entry keeps its place."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(
        files.BENCH, bench,
        ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache", "data"),
    )
    shutil.copy(os.path.join(files.ROOT, "BENCHMARK.json"), tmp_path)
    assert manifest.build(str(bench)) == doc
    probe = dict(files.load_metrics()["window_compiles.backlog"], name="a_probe.backlog")
    (bench / "metrics" / "a_probe.backlog.json").write_text(json.dumps(probe))
    cfg = dict(files.load_config("sched-perf-basic-5000n"), name="a-5000n", source="a probe")
    (bench / "configs" / "a-5000n.json").write_text(json.dumps(cfg))
    cell = dict(files.load_workload("basic-5k.backlog"), name="a-5k.backlog", config="a-5000n")
    (bench / "workloads" / "a-5k.backlog.json").write_text(json.dumps(cell))
    built = manifest.build(str(bench))
    want = json.loads(json.dumps(doc))
    want["per_layer"].append(
        {k: probe[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    )
    want["configs"].append({
        "name": "a-5000n", "source": "a probe", "file": "benchmarks/configs/a-5000n.json",
        "reduced": [], "why": cfg["why"],
    })
    want["workloads"].append(
        {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}
    )
    want["end_to_end"][0]["workloads"].append("a-5k.backlog")
    assert built == want
    # with no committed manifest beside it, every list goes by name
    os.remove(tmp_path / "BENCHMARK.json")
    by_name = manifest.build(str(bench))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in by_name[key]]
        assert names == sorted(names) and set(names) == {e["name"] for e in want[key]}


def test_the_two_counter_shares_earlier_prs_withdrew(doc):
    """Data files over the reader metrics/counter_share_pct.py, named
    without a sorting prefix, owed by the cells in which the program
    counts something under the counter."""
    ms = files.load_metrics()
    new = ("domain_reductions_dense_pct.backlog", "spread_rows_walked_pct.backlog")
    # appended behind the 26 the manifest had, where later entries leave them
    assert [m["name"] for m in doc["per_layer"]][26:28] == list(new)
    for n in new:
        assert ms[n]["reader"] == "counter_share_pct.py" and ms[n]["unit"] == "%"
        assert ms[n]["workloads"] == ["spread-5k.backlog", "spread-5k.rollouts"]
    counters = {
        ("scheduler_tpu_domain_reductions_total", (("form", "dense"),)): 40.0,
        ("scheduler_tpu_spread_count_rows_total", (("source", "kept"),)): 30.0,
        ("scheduler_tpu_spread_count_rows_total", (("source", "walk"),)): 10.0,
    }
    ctx = {
        "m1": counters,
        "delta": lambda name, **labels: sum(
            v for (n, ls), v in counters.items()
            if n == name and set(labels.items()) <= set(ls)
        ),
    }
    read = {n: files.load_reader(ms[n])(ctx, **ms[n]["args"]) for n in new}
    assert read == {new[0]: pytest.approx(100.0), new[1]: pytest.approx(25.0)}
    # the basic cell counts nothing under either: no reading, and it owes none
    ctx["m1"] = {}
    assert [files.load_reader(ms[n])(ctx, **ms[n]["args"]) for n in new] == [None, None]
    basic = files.metrics_of_cell(files.load_workload("basic-5k.backlog"), "per_layer")
    assert not set(new) & set(basic)


def test_top_level(doc):
    assert list(doc) == [
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    ]
    assert doc["paths"] == ["benchmarks"] and PATH.match(doc["paths"][0])
    assert doc["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert len(json.dumps(doc)) < 64 * 1024


def test_configs(doc):
    used = {w["config"] for w in doc["workloads"]}
    seen_files = set()
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and PATH.match(c["file"])
        assert c["file"] not in seen_files
        seen_files.add(c["file"])
        assert os.path.isfile(os.path.join(files.ROOT, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        on_disk = files.load_config(c["name"])
        assert on_disk["guarantees"] and on_disk["source"] == c["source"]
    assert len({c["source"] for c in doc["configs"]}) == len(doc["configs"])


def test_workloads(doc):
    pairs = set()
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(doc["workloads"]) // 2)


def test_metrics(doc):
    cells = {w["name"] for w in doc["workloads"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["moves"] in e2e and line(m["layer"])
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and "_roofline" in m["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    # every cell: setup_s, one other end-to-end metric, one per-layer metric
    for w in doc["workloads"]:
        cell = files.load_workload(w["name"])
        mine = files.metrics_of_cell(cell, "end_to_end")
        assert "setup_s" in mine and len(mine) >= 2
        assert files.metrics_of_cell(cell, "per_layer")


def test_every_file_under_paths_is_named_from_allowed_characters():
    for d, _, fs in os.walk(files.BENCH):
        if "__pycache__" in d or ".pytest_cache" in d:
            continue
        for f in fs:
            rel = os.path.relpath(os.path.join(d, f), files.ROOT)
            assert PATH.match(rel), rel
