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
