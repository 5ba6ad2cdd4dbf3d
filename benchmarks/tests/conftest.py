import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def mixed_cfg(nodes=None):
    """A throw-away configuration with every pod kind the generator and
    the reference know (no cell uses it): the spread deployment plus
    plain and anti-affinity kinds."""
    from benchmarks.lib import files

    cfg = files.load_config("sched-perf-spread-5000n")
    kinds = cfg["stream"]["kinds"]
    kinds["spread"].update(share=0.25, labelKey="app", apps=2, maxSkew=1)
    kinds["plain"] = {"share": 0.5, "apps": 8}
    kinds["anti"] = {
        "share": 0.25, "apps": 5, "topologyKey": "kubernetes.io/hostname",
    }
    if nodes:
        cfg["nodes"]["count"] = nodes
    return cfg


def recorded_slice():
    """data/trace_slice.json.gz (a recorded slice of a TPU v5e trace, PR
    23) as ``trace_reduce.load_xplane`` would give it."""
    import gzip
    import json

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_slice.json.gz")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return {
        p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
        for p, lines in doc["planes"].items()
    }
