"""BENCHMARK.json, derived from the files under benchmarks/. The files
are the source; ``python benchmarks/lib/manifest.py --write`` rewrites
the manifest after a PR added files, and tests/test_manifest.py holds
the committed manifest to what this builds."""

from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import files  # noqa: E402

RUN_SECONDS = 30


def build(bench: str = files.BENCH) -> dict:
    rel = os.path.relpath(bench, os.path.dirname(bench))
    cells = [files.load_workload(n, bench) for n in files.names("workloads", bench)]
    reported = {m for c in cells for m in c["end_to_end"]}
    used = sorted({c["config"] for c in cells})
    metrics = files.load_metrics(bench)
    out = {
        "command": ["python3", f"{rel}/run.py"],
        "paths": [rel],
        "run_seconds": RUN_SECONDS,
        "configs": [],
        "workloads": [],
        "end_to_end": [],
        "per_layer": [],
    }
    for name in used:
        cfg = files.load_config(name, bench)
        out["configs"].append(
            {
                "name": name,
                "source": cfg["source"],
                "file": f"{rel}/configs/{name}.json",
                "reduced": list(cfg["reduced"]),
                "why": cfg["why"],
            }
        )
    for c in cells:
        out["workloads"].append(
            {k: c[k] for k in ("name", "config", "traffic", "chips", "why")}
        )
    for name, m in metrics.items():
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
                    c["name"] for c in cells if name in c["end_to_end"]
                ]
            out["end_to_end"].append(entry)
        else:
            entry.update({k: m[k] for k in ("source", "layer", "moves")})
            if "workloads" in m:
                entry["workloads"] = list(m["workloads"])
            out["per_layer"].append(entry)
    return out


if __name__ == "__main__":
    doc = build()
    text = json.dumps(doc, indent=2) + "\n"
    if "--write" in sys.argv:
        with open(os.path.join(files.ROOT, "BENCHMARK.json"), "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)
