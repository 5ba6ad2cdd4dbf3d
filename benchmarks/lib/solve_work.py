"""The least work one device solve needs, counted from the cell's shapes
and NOT from the program's buffers: the bytes any implementation must
read once and write once to place ``pods`` pods on ``nodes`` nodes under
the configuration's constraint families. Integer compares and adds per
byte are few, so the bound is bytes over HBM bandwidth, not FLOPs.

Per solve (the occupancy a solve starts from must be looked at once,
whatever else stays resident):
    nodes x 4 B x 3                      free cpu, free memory, free pod slots
    nodes x 4 B x families               one occupancy word per live family:
                                         anti-affinity app bits, zone id
                                         for the spread counts
Per pod placed:
    16 B read      request (cpu, memory), class/app id, term id
     4 B written   the assignment
    12 B written   the three resource columns of the node it landed on
     4 B written   per live family, that node's occupancy word

Worked case (tests/test_solve_work.py): 5,000 nodes, 1,024 pods, no
family: 5,000 x 12 + 1,024 x 32 = 92,768 B -> 0.1133 us at 819 GB/s.
"""

from __future__ import annotations

import json
import os

NODE_RESOURCE_BYTES = 4 * 3
FAMILY_BYTES = 4
POD_READ_BYTES = 16
POD_WRITE_BYTES = 4 + 12

_FAMILY_KINDS = ("spread", "anti")


def families(cfg: dict) -> int:
    """Constraint families live in the configuration's stream."""
    return sum(1 for k in cfg["stream"]["kinds"] if k in _FAMILY_KINDS)


def solve_bytes(nodes: int, pods: int, n_families: int) -> int:
    per_node = NODE_RESOURCE_BYTES + FAMILY_BYTES * n_families
    per_pod = POD_READ_BYTES + POD_WRITE_BYTES + FAMILY_BYTES * n_families
    return nodes * per_node + pods * per_pod


def min_seconds(cfg: dict, solves: float, pods: float, peaks: dict) -> float:
    """Least device seconds for ``solves`` solves that place ``pods``
    pods between them."""
    n = int(cfg["nodes"]["count"])
    f = families(cfg)
    total = solves * solve_bytes(n, 0, f) + (solve_bytes(0, 1, f)) * pods
    return total / float(peaks["hbm_bytes_per_s"])


def load_peaks(device_kind: str) -> dict:
    """An unknown device is an error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {path}; add its peaks "
            f"with their source"
        )
    return table[device_kind]
