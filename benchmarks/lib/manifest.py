"""BENCHMARK.json, derived from the files under benchmarks/. The files
are the source; ``python benchmarks/lib/manifest.py --write`` rewrites
the manifest after a PR added files, and tests/test_manifest.py holds
the committed manifest to what this builds.

A later PR may only append to the manifest, so every list keeps the
entries the committed manifest (``BENCHMARK.json`` beside the
benchmark's directory) already has in their committed order, and what is
new follows by name, whatever it is called."""

from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import files  # noqa: E402

RUN_SECONDS = 30


def _committed(bench: str) -> dict:
    """The manifest as committed beside ``bench``; {} where there is none."""
    path = os.path.join(os.path.dirname(bench), "BENCHMARK.json")
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        return json.load(f)


def _append_new(entries: dict, committed: list) -> list:
    """``entries`` (by name): those the committed list names, in its
    order, then the rest by name."""
    old = [e["name"] for e in committed if e["name"] in entries]
    return [entries[n] for n in old + sorted(set(entries) - set(old))]


def build(bench: str = files.BENCH) -> dict:
    rel = os.path.relpath(bench, os.path.dirname(bench))
    was = _committed(bench)
    cells = {n: files.load_workload(n, bench) for n in files.names("workloads", bench)}
    reported = {m for c in cells.values() for m in c["end_to_end"]}
    configs, end_to_end, per_layer = {}, {}, {}
    for name in {c["config"] for c in cells.values()}:
        cfg = files.load_config(name, bench)
        configs[name] = {
            "name": name,
            "source": cfg["source"],
            "file": f"{rel}/configs/{name}.json",
            "reduced": list(cfg["reduced"]),
            "why": cfg["why"],
        }
    workloads = _append_new(
        {
            n: {k: c[k] for k in ("name", "config", "traffic", "chips", "why")}
            for n, c in cells.items()
        },
        was.get("workloads", []),
    )
    for name, m in files.load_metrics(bench).items():
        if (name if m["kind"] == "end_to_end" else m["moves"]) not in reported:
            continue
        entry = {k: m[k] for k in ("name", "unit", "better")}
        if m["kind"] == "end_to_end":
            entry["bound"] = m["bound"]
            entry["source"] = m["source"]
            # named cell by cell, so that a later cell with other
            # end-to-end metrics adds entries and edits none; every cell
            # owes setup_s, which therefore names none
            if name != "setup_s":
                entry["workloads"] = [
                    w["name"] for w in workloads if name in cells[w["name"]]["end_to_end"]
                ]
            end_to_end[name] = entry
        else:
            entry.update({k: m[k] for k in ("source", "layer", "moves")})
            if "workloads" in m:
                entry["workloads"] = list(m["workloads"])
            per_layer[name] = entry
    return {
        "command": ["python3", f"{rel}/run.py"],
        "paths": [rel],
        "run_seconds": RUN_SECONDS,
        "configs": _append_new(configs, was.get("configs", [])),
        "workloads": workloads,
        "end_to_end": _append_new(end_to_end, was.get("end_to_end", [])),
        "per_layer": _append_new(per_layer, was.get("per_layer", [])),
    }


if __name__ == "__main__":
    doc = build()
    text = json.dumps(doc, indent=2) + "\n"
    if "--write" in sys.argv:
        with open(os.path.join(files.ROOT, "BENCHMARK.json"), "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)
