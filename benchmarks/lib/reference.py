"""The plain reference: Kubernetes' filter semantics for the pod kinds
the configurations offer, in NumPy and pure Python, independent of the
program (nothing of ``kubernetes_tpu`` is imported).

Two uses:

* ``replay(...)`` walks the bindings the timed window produced, in
  commit (journal) order, and holds each to the configuration's
  guarantees at its place in that order. It is what decides ``correct``.
* ``ReferenceScheduler`` places pods itself with the same predicates
  (first the feasible nodes, then the least-allocated one). Put in the
  program's place it is the control and the carrier of planted faults
  (``control.py``, ``tests/test_faults.py``): with ``carry=False`` it
  solves a whole batch against the occupancy the batch started with,
  the step that would tempt a later PR, and breaks the guarantees.

Semantics (k8s.io/kubernetes pkg/scheduler/framework/plugins):
NodeResourcesFit (cpu, memory, pods), PodTopologySpread with ``DoNotSchedule`` (count in the node's
domain + 1 - the least count over all domains <= maxSkew; the pod
matches its own selector), InterPodAffinity required anti-affinity on
hostname (no pod matching the term's selector on the node, and no pod on
the node whose own term matches the incoming pod: here both are "the
same app").
"""

from __future__ import annotations

import numpy as np

from . import gen


class ClusterRef:
    """Occupancy of the configuration's cluster, by node index."""

    def __init__(self, cfg: dict) -> None:
        nc = cfg["nodes"]
        self.cfg = cfg
        self.names = gen.node_names(cfg)
        self.index = {n: i for i, n in enumerate(self.names)}
        n = len(self.names)
        self.zones = int(nc["zones"])
        self.zone_of = np.arange(n) % self.zones
        alloc = nc["allocatable"]
        self.cpu_alloc = gen.parse_cpu_milli(alloc["cpu"])
        self.mem_alloc = gen.parse_bytes(alloc["memory"])
        self.pods_alloc = int(alloc["pods"])
        req = cfg["podRequests"]
        self.cpu_req = gen.parse_cpu_milli(req["cpu"])
        self.mem_req = gen.parse_bytes(req["memory"])
        self.cpu = np.zeros(n, dtype=np.int64)
        self.mem = np.zeros(n, dtype=np.int64)
        self.pods = np.zeros(n, dtype=np.int64)
        self.app_on: dict[str, np.ndarray] = {}  # anti app -> count[n]
        self.zone_cnt: dict[str, np.ndarray] = {}  # spread app -> [zones]
        kinds = cfg["stream"]["kinds"]
        self.max_skew = int(kinds.get("spread", {}).get("maxSkew", 1))

    # -- predicates, one node -------------------------------------------

    def violations(self, spec: gen.PodSpec, node: int) -> list[str]:
        """Which guarantees binding ``spec`` to ``node`` now would break."""
        bad = []
        if self.cpu[node] + self.cpu_req > self.cpu_alloc:
            bad.append("cpu")
        if self.mem[node] + self.mem_req > self.mem_alloc:
            bad.append("memory")
        if self.pods[node] + 1 > self.pods_alloc:
            bad.append("pods")
        if spec.kind == "anti":
            on = self.app_on.get(spec.app)
            if on is not None and on[node]:
                bad.append("anti_affinity")
        if spec.kind == "spread":
            cnt = self.zone_cnt.get(spec.app)
            if cnt is not None:
                z = self.zone_of[node]
                if cnt[z] + 1 - cnt.min() > self.max_skew:
                    bad.append("zone_skew")
        return bad

    def bind(self, spec: gen.PodSpec, node: int) -> None:
        n = len(self.names)
        self.cpu[node] += self.cpu_req
        self.mem[node] += self.mem_req
        self.pods[node] += 1
        if spec.kind == "anti":
            self.app_on.setdefault(spec.app, np.zeros(n, dtype=np.int64))[
                node
            ] += 1
        if spec.kind == "spread":
            self.zone_cnt.setdefault(
                spec.app, np.zeros(self.zones, dtype=np.int64)
            )[self.zone_of[node]] += 1

    # -- predicates, every node (the reference scheduler) -----------------

    def feasible(self, spec: gen.PodSpec) -> np.ndarray:
        ok = (
            (self.cpu + self.cpu_req <= self.cpu_alloc)
            & (self.mem + self.mem_req <= self.mem_alloc)
            & (self.pods + 1 <= self.pods_alloc)
        )
        if spec.kind == "anti" and spec.app in self.app_on:
            ok &= self.app_on[spec.app] == 0
        if spec.kind == "spread" and spec.app in self.zone_cnt:
            cnt = self.zone_cnt[spec.app]
            ok &= (cnt[self.zone_of] + 1 - cnt.min()) <= self.max_skew
        return ok

    # -- the end state, exhaustively --------------------------------------

    def end_state(self) -> dict:
        """Each guarantee read off the final occupancy (0 = held)."""
        over = int(
            (
                (self.cpu > self.cpu_alloc)
                | (self.mem > self.mem_alloc)
                | (self.pods > self.pods_alloc)
            ).sum()
        )
        anti = int(sum((a > 1).sum() for a in self.app_on.values()))
        skew = int(
            max((c.max() - c.min() for c in self.zone_cnt.values()), default=0)
        )
        return {
            "nodes_over_capacity": over,
            "anti_affinity_clashes": anti,
            "max_zone_skew": skew,
        }


def replay(cfg: dict, specs: dict, bindings: list) -> dict:
    """Walk ``bindings`` ([(pod key, node name)] in commit order) over an
    empty cluster. ``specs`` maps pod key -> PodSpec for every pod that
    was offered. Returns the compared numbers and the first few
    violations in words."""
    ref = ClusterRef(cfg)
    seen: set = set()
    unknown = twice = infeasible = 0
    notes: list[str] = []
    for key, node_name in bindings:
        spec = specs.get(key)
        node = ref.index.get(node_name)
        if spec is None or node is None:
            unknown += 1
            if len(notes) < 5:
                notes.append(f"{key} -> {node_name}: not offered / no such node")
            continue
        if key in seen:
            twice += 1
            if len(notes) < 5:
                notes.append(f"{key}: bound twice")
            continue
        seen.add(key)
        bad = ref.violations(spec, node)
        if bad:
            infeasible += 1
            if len(notes) < 5:
                notes.append(f"{key} -> {node_name}: {'+'.join(bad)}")
        ref.bind(spec, node)
    return {
        "unknown_bindings": unknown,
        "bound_twice": twice,
        "infeasible_at_commit": infeasible,
        **ref.end_state(),
        "notes": notes,
        "bound": len(seen),
    }


class ReferenceScheduler:
    """The reference put in the program's place: first-come, feasible
    nodes by the predicates above, the least-allocated one (lowest index
    on a tie). ``carry=False`` is the control: a batch is solved against
    the occupancy it started with and bound without looking again."""

    def __init__(self, cfg: dict, carry: bool = True) -> None:
        self.ref = ClusterRef(cfg)
        self.carry = carry

    def _pick(self, spec: gen.PodSpec) -> int:
        ok = self.ref.feasible(spec)
        if not ok.any():
            return -1
        load = np.where(ok, self.ref.cpu, np.iinfo(np.int64).max)
        return int(load.argmin())

    def schedule(self, batch: list) -> list:
        """[(PodSpec, node name or None)] for one batch."""
        out = []
        if self.carry:
            for spec in batch:
                node = self._pick(spec)
                if node >= 0:
                    self.ref.bind(spec, node)
                out.append((spec, self.ref.names[node] if node >= 0 else None))
            return out
        picks = [self._pick(spec) for spec in batch]
        for spec, node in zip(batch, picks):
            if node >= 0:
                self.ref.bind(spec, node)
            out.append((spec, self.ref.names[node] if node >= 0 else None))
        return out
