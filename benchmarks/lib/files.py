"""Where the benchmark's data lives, found by name. A configuration, a
traffic mix and a metric are each a file of their own, so a later PR
adds a cell or a metric by adding files (and entries in BENCHMARK.json)
and edits nothing that is there:

    benchmarks/configs/<config>.json      one deployment
    benchmarks/workloads/<cell>.json      one cell: config + traffic mix
    benchmarks/metrics/<metric>.json      one metric; "reader" names the
                                          .py beside it that computes it
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load(kind: str, name: str, bench: str = BENCH) -> dict:
    path = os.path.join(bench, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    with open(path) as f:
        doc = json.load(f)
    if doc.get("name") != name:
        raise ValueError(f"{path}: \"name\" is {doc.get('name')!r}, not {name!r}")
    return doc


def load_config(name: str, bench: str = BENCH) -> dict:
    return _load("configs", name, bench)


def load_workload(name: str, bench: str = BENCH) -> dict:
    return _load("workloads", name, bench)


def names(kind: str, bench: str = BENCH) -> list[str]:
    d = os.path.join(bench, kind)
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def load_metrics(bench: str = BENCH) -> dict:
    return {n: _load("metrics", n, bench) for n in names("metrics", bench)}


def metrics_of_cell(cell: dict, kind: str, bench: str = BENCH) -> dict:
    """The metrics of ``kind`` ("end_to_end" / "per_layer") this cell
    reports: the end-to-end metrics its file lists, and every per-layer
    metric that moves one of them (and does not name other cells)."""
    e2e = list(cell["end_to_end"])
    out = {}
    for name, m in load_metrics(bench).items():
        if m["kind"] != kind:
            continue
        if kind == "end_to_end":
            if name in e2e:
                out[name] = m
        elif m["moves"] in e2e and cell["name"] in m.get("workloads", [cell["name"]]):
            out[name] = m
    return out


def load_reader(metric: dict, bench: str = BENCH):
    """The ``read(ctx, **args)`` function of a metric's reader file."""
    path = os.path.join(bench, "metrics", metric["reader"])
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + metric["reader"].replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
