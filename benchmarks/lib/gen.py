"""Cluster and pod-stream generation: a pure function of ``--seed`` and
the configuration's and the cell's files. No program code is imported;
what is produced is wire-shape v1 JSON, the same a kubectl manifest or
an informer would carry.

A traffic mix is data (``benchmarks/workloads/<cell>.json``) read by the
one generator here: a *rollout stream* (waves of replicas of one app,
several waves in flight, replicas interleaved in seeded order) offered
by a closed loop on backlog depth (``loops.py``).
"""

from __future__ import annotations

import json
import random
from dataclasses import dataclass

NAMESPACE = "default"
_QUANT = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40}


def parse_cpu_milli(q: str) -> int:
    q = str(q)
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def parse_bytes(q: str) -> int:
    q = str(q)
    for suffix, mult in _QUANT.items():
        if q.endswith(suffix):
            return int(q[: -len(suffix)]) * mult
    return int(q)


# -- nodes -------------------------------------------------------------------


def node_names(cfg: dict) -> list[str]:
    nc = cfg["nodes"]
    return [nc["namePattern"] % i for i in range(nc["count"])]


def node_zone(cfg: dict, i: int) -> int:
    return i % cfg["nodes"]["zones"]


def make_nodes(cfg: dict) -> list[dict]:
    """Zones go round the nodes in turn, as scheduler_perf's
    labelNodePrepareStrategy deals its label values; ``zoneNames`` names
    them, else ``{zone}`` is the zone's number."""
    nc = cfg["nodes"]
    alloc = dict(nc["allocatable"])
    alloc["memory"] = str(parse_bytes(alloc["memory"]))
    zone_names = nc.get("zoneNames") or list(range(nc["zones"]))
    out = []
    for i, name in enumerate(node_names(cfg)):
        labels = {
            k: v.format(zone=zone_names[node_zone(cfg, i)], name=name)
            for k, v in nc["labels"].items()
        }
        out.append(
            {
                "apiVersion": "v1",
                "kind": "Node",
                "metadata": {"name": name, "labels": labels},
                "spec": {},
                "status": {"allocatable": alloc, "capacity": alloc},
            }
        )
    return out


def write_state_file(cfg: dict, path: str) -> None:
    """The ``serve --state`` file: the nodes only. ``initPods`` are
    offered through ``/api/pods`` once the server answers, as
    scheduler_perf creates them through the API."""
    with open(path, "w") as f:
        json.dump({"nodes": make_nodes(cfg)}, f)


# -- pods --------------------------------------------------------------------


@dataclass(frozen=True)
class PodSpec:
    """What the reference needs to know of one offered pod."""

    name: str
    kind: str
    app: str  # the value of the pod's one label; its own selector names it
    label_key: str = "app"

    @property
    def key(self) -> str:
        return f"{NAMESPACE}/{self.name}"


def pod_manifest(cfg: dict, spec: PodSpec) -> dict:
    req = cfg["podRequests"]
    container = {
        "name": "con0",
        "resources": {
            "requests": {
                "cpu": req["cpu"],
                "memory": str(parse_bytes(req["memory"])),
            }
        },
    }
    pod_spec: dict = {"containers": [container]}
    kinds = cfg["stream"]["kinds"]
    labels = {spec.label_key: spec.app}
    selector = {"matchLabels": labels}
    if spec.kind == "spread":
        k = kinds["spread"]
        pod_spec["topologySpreadConstraints"] = [
            {
                "maxSkew": k["maxSkew"],
                "topologyKey": k["topologyKey"],
                "whenUnsatisfiable": k["whenUnsatisfiable"],
                "labelSelector": selector,
            }
        ]
    elif spec.kind == "anti":
        pod_spec["affinity"] = {
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "topologyKey": kinds["anti"]["topologyKey"],
                        "labelSelector": selector,
                    }
                ]
            }
        }
    elif spec.kind != "plain":
        raise ValueError(f"unknown pod kind {spec.kind!r}")
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": spec.name,
            "namespace": NAMESPACE,
            "labels": labels,
        },
        "spec": pod_spec,
        "status": {"phase": "Pending"},
    }


def pods_body(cfg: dict, specs: list[PodSpec]) -> bytes:
    """One ``POST /api/pods`` body."""
    return json.dumps(
        {"items": [pod_manifest(cfg, s) for s in specs]},
        separators=(",", ":"),
    ).encode()


def init_pods(cfg: dict) -> list[PodSpec]:
    ip = cfg["initPods"]
    return [
        PodSpec(
            f"{ip['app']}-{i:05d}", ip["kind"], ip["app"],
            ip.get("labelKey", "app"),
        )
        for i in range(ip["count"])
    ]


def _kind_block(kinds: dict) -> list[str]:
    """The smallest block of waves that holds every kind in its share
    (50/25/25 -> [plain, plain, spread, anti]). Every seed
    gets the same blocks, in another order."""
    shares = {k: float(v["share"]) for k, v in kinds.items()}
    total = sum(shares.values())
    for size in range(1, 101):
        counts = {k: s / total * size for k, s in shares.items()}
        if all(abs(c - round(c)) < 1e-9 and round(c) >= 1 for c in counts.values()):
            return [k for k in kinds for _ in range(round(counts[k]))]
    raise ValueError(f"shares {shares} fit no block of up to 100 waves")


class RolloutStream:
    """The endless pod stream of one run. ``take(n)`` returns the next n
    pods; the sequence depends on nothing but the configuration and the
    seed."""

    def __init__(self, cfg: dict, seed: int, wave_base: int = 0) -> None:
        """``wave_base`` numbers the waves from there, so that two
        streams never share a pod name."""
        st = cfg["stream"]
        self.cfg = cfg
        self.kinds = dict(st["kinds"])
        self.replicas = int(st["deploymentReplicas"])
        self.rng = random.Random(int(seed))
        self._block = _kind_block(self.kinds)
        self._pending_kinds: list[str] = []
        # each kind walks its apps in a seeded order of its own
        self._app_order = {}
        self._app_next = {}
        for kind, k in self.kinds.items():
            # "apps": a count (names <kind>-<i>) or the label values
            apps = k["apps"]
            order = (
                [f"{kind}-{i}" for i in range(apps)]
                if isinstance(apps, int) else list(apps)
            )
            self.rng.shuffle(order)
            self._app_order[kind] = order
            self._app_next[kind] = 0
        self._wave = int(wave_base)
        n_slots = int(st["inFlight"])
        # slots start staggered, so waves end evenly spaced from the
        # first pod on and not all at once
        self._slots = [
            self._new_wave(first_skip=(s * self.replicas) // n_slots)
            for s in range(n_slots)
        ]

    def _next_kind(self) -> str:
        if not self._pending_kinds:
            block = list(self._block)
            self.rng.shuffle(block)
            self._pending_kinds = block
        return self._pending_kinds.pop()

    def _new_wave(self, first_skip: int = 0) -> dict:
        kind = self._next_kind()
        order = self._app_order[kind]
        app = order[self._app_next[kind] % len(order)]
        self._app_next[kind] += 1
        wave = {
            "kind": kind,
            "app": app,
            "label_key": self.kinds[kind].get("labelKey", "app"),
            "id": self._wave,
            "next": first_skip,
        }
        self._wave += 1
        return wave

    def take(self, n: int) -> list[PodSpec]:
        out = []
        for _ in range(n):
            s = self.rng.randrange(len(self._slots))
            w = self._slots[s]
            out.append(
                PodSpec(
                    f"{w['app']}-w{w['id']:05d}-{w['next']:03d}",
                    w["kind"], w["app"], w["label_key"],
                )
            )
            w["next"] += 1
            if w["next"] >= self.replicas:
                self._slots[s] = self._new_wave()
        return out
