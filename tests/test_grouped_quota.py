"""Grouped quota fast paths for spread/anti workloads (kind 2/3 chunks in
solver/exact._solve_grouped): deterministic mode must be bit-identical to
the ungrouped scan; random mode must be sequentially valid (oracle
replay) and respect the workload invariants."""

import numpy as np
import pytest

from kubernetes_tpu import metrics
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.ops.domains import DENSE_MAX_SLOTS
from kubernetes_tpu.ops.oracle.profile import FullOracle, make_oracle_nodes
from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
from kubernetes_tpu.tensorize.interpod import build_interpod_tensors
from kubernetes_tpu.tensorize.plugins import (
    build_port_tensors,
    build_static_tensors,
)
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    build_node_batch,
    build_pod_batch,
)
from kubernetes_tpu.tensorize.spread import build_spread_tensors

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
GROUP = 16


def mk_nodes(n):
    return [
        MakeNode()
        .name(f"n-{i:04}")
        .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"})
        .label(ZONE, f"z{i % 3}")
        .label(HOST, f"n-{i:04}")
        .obj()
        for i in range(n)
    ]


def mk_pods(n, kind):
    out = []
    for i in range(n):
        b = (
            MakePod()
            .name(f"p-{i:04}")
            .label("app", kind)
            .req({"cpu": "250m", "memory": "512Mi"})
        )
        if kind == "spread":
            b = b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": kind})
        elif kind == "hostspread":
            b = b.spread_constraint(1, HOST, "DoNotSchedule", {"app": kind})
        elif kind == "anti":
            b = b.pod_anti_affinity(HOST, {"app": kind})
        out.append(b.obj())
    return out


def solve(nodes, pods, tie_break, group, seed=3):
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, vocab=vocab)
    # grouped dispatch needs pod_pad % group == 0
    pad = ((len(pods) + GROUP - 1) // GROUP) * GROUP
    pbatch = build_pod_batch(pods, vocab, pad=pad)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    ports = build_port_tensors(pods, pbatch, slot_nodes, {}, nbatch.padded)
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad
    )
    interpod = build_interpod_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad
    )
    solver = ExactSolver(
        ExactSolverConfig(tie_break=tie_break, group_size=group, seed=seed)
    )
    a = solver.solve(nbatch, pbatch, static, ports, spread, interpod)
    nbatch.dispatch_counts = solver.dispatch_counts  # which chunks ran
    return a, nbatch


def test_chunk_kinds_classification():
    nodes = mk_nodes(32)
    pods = mk_pods(GROUP, "spread") + mk_pods(GROUP, "anti") + mk_pods(GROUP, "plain")
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab, pad=3 * GROUP)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    ports = build_port_tensors(pods, pbatch, slot_nodes, {}, nbatch.padded)
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad
    )
    interpod = build_interpod_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad
    )
    kinds = ExactSolver._chunk_kinds(
        pbatch, static, ports, spread, interpod, GROUP, True, True
    )
    assert list(kinds) == [2, 3, 1]


def test_spread_deterministic_grouped_equals_ungrouped():
    nodes = mk_nodes(24)
    pods = mk_pods(48, "spread")
    a_g, nb = solve(nodes, pods, "first", GROUP)
    a_u, _ = solve(nodes, pods, "first", 0)
    np.testing.assert_array_equal(a_g, a_u)


def test_anti_deterministic_grouped_equals_ungrouped():
    nodes = mk_nodes(24)
    pods = mk_pods(20, "anti")
    a_g, _ = solve(nodes, pods, "first", GROUP)
    a_u, _ = solve(nodes, pods, "first", 0)
    np.testing.assert_array_equal(a_g, a_u)


def _oracle_validate(nodes, pods, assignments, nbatch):
    oracle = FullOracle(make_oracle_nodes(nodes))
    names = [nbatch.names[a] if a >= 0 else None for a in assignments]
    errors = oracle.validate_assignments(pods, list(assignments), names=names)
    assert not errors, "\n".join(errors[:5])


from _hypothesis_compat import given, settings, st


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_spread_random_grouped_sequentially_valid(seed):
    """Random-mode quota multi-placement: every placement must be inside
    the oracle tie set given identical history, and the hard skew bound
    must hold at the end. Hypothesis varies the tie-break seed so the
    water-fill / winner / fallback branches all get exercised."""
    nodes = mk_nodes(24)
    pods = mk_pods(48, "spread")
    a, nb = solve(nodes, pods, "random", GROUP, seed=seed)
    assert int((np.asarray(a) >= 0).sum()) == 48
    _oracle_validate(nodes, pods, a, nb)
    zones = np.asarray([int(nb.names[x].split("-")[1]) % 3 for x in a])
    counts = np.bincount(zones, minlength=3)
    assert counts.max() - counts.min() <= 1


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_anti_random_grouped_sequentially_valid(seed):
    nodes = mk_nodes(32)
    pods = mk_pods(24, "anti")
    a, nb = solve(nodes, pods, "random", GROUP, seed=seed)
    assert int((np.asarray(a) >= 0).sum()) == 24
    _oracle_validate(nodes, pods, a, nb)
    # hostname exclusivity
    assert len(set(int(x) for x in a)) == 24


def test_quota_paths_valid_at_device_scale():
    """Padding/bucketing edges at a realistic node count: 512 nodes x
    mixed spread+anti chunks through the grouped solver, oracle-replayed
    with sampled tie-set checks (every 8th step + every failure)."""
    nodes = mk_nodes(512)
    pods = mk_pods(4 * GROUP, "spread") + mk_pods(4 * GROUP, "anti")
    a, nb = solve(nodes, pods, "random", GROUP)
    a = np.asarray(a)
    assert int((a >= 0).sum()) == len(pods)

    oracle = FullOracle(make_oracle_nodes(nodes))
    names = [nb.names[x] if x >= 0 else None for x in a]
    sample = {i for i in range(len(pods)) if i % 8 == 0 or a[i] < 0}
    errors = oracle.validate_assignments(
        pods, list(a), names=names, sample=sample
    )
    assert not errors, "\n".join(errors[:5])
    # invariants over the full assignment
    zones = np.asarray(
        [int(nb.names[x].split("-")[1]) % 3 for x in a[: 4 * GROUP]]
    )
    counts = np.bincount(zones, minlength=3)
    assert counts.max() - counts.min() <= 1
    anti_nodes = [int(x) for x in a[4 * GROUP :]]
    assert len(set(anti_nodes)) == 4 * GROUP  # hostname exclusivity


def test_anti_overload_marks_surplus_unschedulable():
    """More anti pods than nodes: exactly n_nodes place, the rest fail —
    and the grouped result agrees with the ungrouped scan's count."""
    nodes = mk_nodes(8)
    pods = mk_pods(12, "anti")
    a_g, _ = solve(nodes, pods, "random", GROUP)
    placed = int((np.asarray(a_g) >= 0).sum())
    assert placed == 8
    assert len(set(int(x) for x in a_g if x >= 0)) == 8


def test_spread_skew_blocks_when_unavoidable():
    """2 zones only (one zone's nodes all tainted... simpler: 3 pods onto a
    1-node-per-zone cluster with maxSkew 1 — a 4th pod would need a second
    round-robin pass, still feasible; instead make one zone absent)."""
    nodes = [
        MakeNode()
        .name(f"n-{i:04}")
        .capacity({"cpu": "16", "memory": "64Gi", "pods": "2"})
        .label(ZONE, f"z{i % 2}")  # only 2 zones
        .label(HOST, f"n-{i:04}")
        .obj()
        for i in range(4)
    ]
    # pods allowed 2 per zone (pods cap 2/node, 2 nodes/zone): with
    # maxSkew=1 all 8 can place 4/4; a 9th pod has no capacity anyway.
    pods = mk_pods(8, "spread")
    a, nb = solve(nodes, pods, "random", GROUP)
    assert int((np.asarray(a) >= 0).sum()) == 8
    zones = np.asarray([int(nb.names[x].split("-")[1]) % 2 for x in a])
    counts = np.bincount(zones, minlength=2)
    assert abs(int(counts[0]) - int(counts[1])) <= 1


@pytest.mark.parametrize("tie_break", ["random", "first"])
@pytest.mark.parametrize("kind,chunk", [("spread", "kind2"), ("anti", "kind3")])
def test_quota_chunks_dense_equal_scatter(kind, chunk, tie_break, all_scatter):
    """ops/domains.py: the per-domain sums and winners of the quota
    branches as masked reductions (as shipped: 8 zone slots, 32 hostname
    slots here) against the scatter they replace, one seeded batch:
    the same draws, quotas and tie sets, so the same assignments and the
    same carried node state."""
    nodes = mk_nodes(24)
    pods = mk_pods(48 if kind == "spread" else 20, kind)
    with all_scatter():
        a_s, nb_s = solve(nodes, pods, tie_break, GROUP, seed=11)
    a_d, nb_d = solve(nodes, pods, tie_break, GROUP, seed=11)
    assert nb_d.dispatch_counts[chunk] >= 1  # the branch under test ran
    assert nb_s.dispatch_counts["domains_scatter"] == 1
    assert nb_d.dispatch_counts["domains_dense"] == 1
    assert "domains_scatter" not in nb_d.dispatch_counts
    assert int((np.asarray(a_d) >= 0).sum()) == len(pods)
    np.testing.assert_array_equal(a_d, a_s)
    for name in ("used", "nonzero_used", "pod_count"):
        np.testing.assert_array_equal(getattr(nb_d, name), getattr(nb_s, name))


def _forms():
    return {
        f: metrics.domain_reductions_total.labels(f)._value.get()
        for f in ("dense", "scatter")
    }


@pytest.mark.parametrize(
    "kind,n_nodes,want",
    [
        ("spread", 24, {"dense": 1, "scatter": 0}),  # 3 zones: 8 slots
        ("hostspread", 300, {"dense": 0, "scatter": 1}),  # 300 hosts: 512 slots
        ("anti", 300, {"dense": 0, "scatter": 1}),  # the inter-pod table's
        ("plain", 24, {"dense": 0, "scatter": 0}),  # no domain table
    ],
)
def test_domain_reductions_counter_says_which_form(kind, n_nodes, want):
    """scheduler_tpu_domain_reductions_total: one increment a solve and
    domain table, by the form the table's padded slot count selects."""
    assert 8 <= DENSE_MAX_SLOTS < 512
    before = _forms()
    nodes = mk_nodes(n_nodes)
    a, nb = solve(nodes, mk_pods(GROUP, kind), "first", GROUP)
    assert int((np.asarray(a) >= 0).sum()) == GROUP
    after = _forms()
    assert {f: after[f] - before[f] for f in after} == want
    # one tally: /metrics and dispatch_counts are the same increments
    for form, n in want.items():
        assert nb.dispatch_counts.get(f"domains_{form}", 0) == n
