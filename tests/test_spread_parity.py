"""PodTopologySpread: oracle unit tests + solver-vs-oracle parity."""

import numpy as np
import pytest

from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.ops.oracle import spread as osp
from kubernetes_tpu.ops.oracle.profile import FullOracle, make_oracle_nodes
from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
from kubernetes_tpu.tensorize.plugins import (
    build_port_tensors,
    build_static_tensors,
)
from kubernetes_tpu.tensorize.spread import build_spread_tensors
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    build_node_batch,
    build_pod_batch,
)


def zone_nodes(n, zones):
    return [
        MakeNode()
        .name(f"node-{i:03}")
        .capacity({"cpu": "8", "memory": "32Gi", "pods": "50"})
        .label("zone", f"z{i % zones}")
        .label("kubernetes.io/hostname", f"node-{i:03}")
        .obj()
        for i in range(n)
    ]


def spread_pod(i, max_skew=1, when="DoNotSchedule", key="zone"):
    return (
        MakePod()
        .name(f"p{i:03}")
        .label("app", "web")
        .req({"cpu": "100m"})
        .spread_constraint(max_skew, key, when, match_labels={"app": "web"})
        .obj()
    )


# -- oracle unit tests ------------------------------------------------------


def test_oracle_filter_skew():
    nodes = zone_nodes(4, 2)  # z0: n0,n2; z1: n1,n3
    p_on = [MakePod().name(f"e{i}").label("app", "web").node(f"node-00{i}").obj()
            for i in range(2)]  # one web pod in each zone? e0->n0 (z0), e1->n1 (z1)
    all_nodes = [
        (nodes[0], [p_on[0]]),
        (nodes[1], [p_on[1]]),
        (nodes[2], []),
        (nodes[3], []),
    ]
    pod = spread_pod(0)
    # counts: z0=1, z1=1, min=1; skew of z0 = 1+1-1 = 1 <= 1 -> ok everywhere
    for n in nodes:
        assert osp.spread_filter(pod, n, all_nodes)
    # add another web pod to z0 -> z0=2, z1=1, min=1; placing in z0: 2+1-1=2 > 1
    all_nodes[2] = (nodes[2], [MakePod().name("e2").label("app", "web").obj()])
    assert not osp.spread_filter(pod, nodes[0], all_nodes)
    assert not osp.spread_filter(pod, nodes[2], all_nodes)
    assert osp.spread_filter(pod, nodes[1], all_nodes)


def test_oracle_filter_missing_key():
    nodes = zone_nodes(2, 2)
    bare = MakeNode().name("bare").capacity({"cpu": "8", "pods": "10"}).obj()
    all_nodes = [(n, []) for n in nodes] + [(bare, [])]
    pod = spread_pod(0)
    assert not osp.spread_filter(pod, bare, all_nodes)  # node lacks zone label


def test_oracle_min_domains():
    nodes = zone_nodes(2, 2)
    all_nodes = [(n, []) for n in nodes]
    # minDomains=3 > 2 registered domains -> global min treated as 0;
    # skew = 0+1-0 = 1 <= 1 -> still passes with empty zones
    pod = (
        MakePod().name("p").label("app", "web").req({"cpu": "100m"})
        .spread_constraint(1, "zone", "DoNotSchedule",
                           match_labels={"app": "web"}, min_domains=3)
        .obj()
    )
    assert osp.spread_filter(pod, nodes[0], all_nodes)
    # now one pod in z0: placing there gives skew 1+1-0=2 > 1 -> fails there
    all_nodes[0] = (nodes[0], [MakePod().name("e").label("app", "web").obj()])
    assert not osp.spread_filter(pod, nodes[0], all_nodes)
    assert osp.spread_filter(pod, nodes[1], all_nodes)


def test_oracle_soft_scores_prefer_sparse_domains():
    nodes = zone_nodes(4, 2)
    web = MakePod().name("e").label("app", "web").obj()
    all_nodes = [(nodes[0], [web]), (nodes[1], []), (nodes[2], []), (nodes[3], [])]
    pod = spread_pod(0, when="ScheduleAnyway")
    scores = osp.spread_scores(pod, all_nodes, all_nodes)
    # z1 nodes (1, 3) should outscore z0 nodes (0, 2)
    assert scores[1] > scores[0]
    assert scores[3] > scores[2]


# -- solver parity ----------------------------------------------------------


def run_solver(nodes, pods, placed_by_node=None, tie_break="first"):
    placed_by_node = placed_by_node or {}
    all_pods = pods + [p for ps in placed_by_node.values() for p in ps]
    vocab = ResourceVocab.build(all_pods, nodes)
    nbatch = build_node_batch(nodes, placed_by_node, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    placed_by_slot = {
        i: placed_by_node[n.name]
        for i, n in enumerate(nodes)
        if n.name in placed_by_node
    }
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    ports = build_port_tensors(pods, pbatch, slot_nodes, placed_by_slot, nbatch.padded)
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes,
        placed_by_slot, nbatch.padded, static.c_pad,
    )
    solver = ExactSolver(ExactSolverConfig(tie_break=tie_break))
    return solver.solve(nbatch, pbatch, static, ports, spread), nbatch


def assert_parity(nodes, pods, placed_by_node=None):
    assignments, nbatch = run_solver(nodes, pods, placed_by_node)
    oracle = FullOracle(make_oracle_nodes(nodes, placed_by_node))
    names = [nbatch.names[a] if a >= 0 else None for a in assignments]
    errors = oracle.validate_assignments(pods, list(assignments), names=names)
    assert not errors, "\n".join(errors[:5])
    return assignments


def test_hard_spread_balances_zones():
    nodes = zone_nodes(6, 3)
    pods = [spread_pod(i) for i in range(9)]
    a = assert_parity(nodes, pods)
    assert all(x >= 0 for x in a)
    zone_counts = [0, 0, 0]
    for x in a:
        zone_counts[x % 3] += 1
    assert max(zone_counts) - min(zone_counts) <= 1


def test_hard_spread_marks_unschedulable_when_skew_unavoidable():
    # 2 zones but z1 nodes are full -> after z0 fills to maxSkew, pods fail
    nodes = zone_nodes(2, 2)
    blocker = MakePod().name("blk").node("node-001").req({"cpu": "8"}).obj()
    pods = [spread_pod(i) for i in range(4)]
    a = assert_parity(nodes, pods, {"node-001": [blocker]})
    # z1 has no capacity; z0 can take maxSkew=1 pod above z1's count (0)
    assert list(a).count(-1) == 3
    assert (a >= 0).sum() == 1


def test_soft_spread_steers_but_never_blocks():
    nodes = zone_nodes(4, 2)
    web = MakePod().name("w").label("app", "web").node("node-000").obj()
    pods = [spread_pod(i, when="ScheduleAnyway") for i in range(4)]
    a = assert_parity(nodes, pods, {"node-000": [web]})
    assert all(x >= 0 for x in a)


def test_hostname_spread():
    nodes = zone_nodes(4, 2)
    pods = [spread_pod(i, key="kubernetes.io/hostname", max_skew=1) for i in range(8)]
    a = assert_parity(nodes, pods)
    assert all(x >= 0 for x in a)
    # per-node counts must stay within skew 1 of each other
    counts = np.bincount(a, minlength=4)
    assert counts.max() - counts.min() <= 1


def test_mixed_hard_and_soft():
    nodes = zone_nodes(6, 3)
    pods = []
    for i in range(12):
        b = (
            MakePod()
            .name(f"m{i:03}")
            .label("app", "api")
            .req({"cpu": "200m", "memory": "512Mi"})
            .spread_constraint(2, "zone", "DoNotSchedule", match_labels={"app": "api"})
            .spread_constraint(1, "kubernetes.io/hostname", "ScheduleAnyway",
                               match_labels={"app": "api"})
        )
        pods.append(b.obj())
    a = assert_parity(nodes, pods)
    assert all(x >= 0 for x in a)


def test_min_domains_through_solver():
    # 2 zones, minDomains=3 -> min treated as 0 -> each zone holds maxSkew=1
    # matching pod; 4 pods -> only 2 place (parity-checked vs oracle)
    nodes = zone_nodes(4, 2)
    pods = [
        MakePod()
        .name(f"p{i}")
        .label("app", "web")
        .req({"cpu": "100m"})
        .spread_constraint(1, "zone", "DoNotSchedule",
                           match_labels={"app": "web"}, min_domains=3)
        .obj()
        for i in range(4)
    ]
    a = assert_parity(nodes, pods)
    assert (a >= 0).sum() == 2
    assert list(a).count(-1) == 2


def test_match_label_keys_through_solver():
    # matchLabelKeys=[group]: pods of group g spread only against group g
    from kubernetes_tpu.api.objects import TopologySpreadConstraint
    from kubernetes_tpu.api.labels import selector_from_match_labels

    nodes = zone_nodes(4, 2)
    pods = []
    for i in range(4):
        b = (
            MakePod()
            .name(f"g{i}")
            .label("app", "web")
            .label("group", f"grp{i % 2}")
            .req({"cpu": "100m"})
        )
        b._pod.topology_spread_constraints = (
            TopologySpreadConstraint(
                max_skew=1,
                topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=selector_from_match_labels({"app": "web"}),
                match_label_keys=("group",),
            ),
        )
        pods.append(b.obj())
    a = assert_parity(nodes, pods)
    assert all(x >= 0 for x in a)
    # each group's two pods must land in different zones
    for g in range(2):
        zs = {int(a[i]) % 2 for i in range(4) if i % 2 == g}
        assert len(zs) == 2


def test_node_taints_policy_honor_through_solver():
    # nodeTaintsPolicy=Honor: tainted z1 nodes are excluded from domain
    # counting, so z1's emptiness doesn't pin the global min at 0
    from kubernetes_tpu.api.objects import TopologySpreadConstraint
    from kubernetes_tpu.api.labels import selector_from_match_labels

    nodes = zone_nodes(4, 2)
    nodes[1] = (
        MakeNode().name("node-001")
        .capacity({"cpu": "8", "memory": "32Gi", "pods": "50"})
        .label("zone", "z1").label("kubernetes.io/hostname", "node-001")
        .taint("gpu", "true", "NoSchedule").obj()
    )
    nodes[3] = (
        MakeNode().name("node-003")
        .capacity({"cpu": "8", "memory": "32Gi", "pods": "50"})
        .label("zone", "z1").label("kubernetes.io/hostname", "node-003")
        .taint("gpu", "true", "NoSchedule").obj()
    )
    pods = []
    for i in range(2):
        b = MakePod().name(f"h{i}").label("app", "web").req({"cpu": "100m"})
        b._pod.topology_spread_constraints = (
            TopologySpreadConstraint(
                max_skew=1,
                topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=selector_from_match_labels({"app": "web"}),
                node_taints_policy="Honor",
            ),
        )
        pods.append(b.obj())
    a = assert_parity(nodes, pods)
    # both pods place in z0 (nodes 0, 2): z1 is tainted and not counted, so
    # skew vs z1 never blocks; with Ignore policy the second pod would fail
    assert all(x >= 0 and x % 2 == 0 for x in a)


def test_spread_with_existing_cluster_state():
    nodes = zone_nodes(4, 2)
    existing = {
        "node-000": [
            MakePod().name(f"e{i}").label("app", "web").node("node-000").obj()
            for i in range(2)
        ]
    }
    pods = [spread_pod(i, max_skew=2) for i in range(4)]
    assert_parity(nodes, pods, existing)


def _mixed_pods(n):
    return [
        MakePod()
        .name(f"m{i:03}")
        .label("app", "api")
        .req({"cpu": "200m", "memory": "512Mi"})
        .spread_constraint(2, "zone", "DoNotSchedule", match_labels={"app": "api"})
        .spread_constraint(1, "zone", "ScheduleAnyway", match_labels={"app": "api"})
        .obj()
        for i in range(n)
    ]


@pytest.mark.parametrize("tie_break", ["first", "random"])
@pytest.mark.parametrize("shape", ["hard", "hard_and_soft"])
def test_dense_domain_reductions_equal_the_scatter(shape, tie_break, all_scatter):
    """ops/domains.py under this file's solves (a hard constraint: the
    grouped program; with a soft one: the per-pod scan, hard_violations and
    soft_scores): dense (as shipped, 8 zone slots) against the scatter,
    one seeded batch: the same assignments and carried node state."""
    nodes = zone_nodes(6, 3)
    pods = (
        [spread_pod(i) for i in range(12)] if shape == "hard" else _mixed_pods(12)
    )
    with all_scatter():
        a_s, nb_s = run_solver(nodes, pods, tie_break=tie_break)
    a_d, nb_d = run_solver(nodes, pods, tie_break=tie_break)
    assert all(x >= 0 for x in a_d)
    np.testing.assert_array_equal(a_d, a_s)
    for name in ("used", "nonzero_used", "pod_count"):
        np.testing.assert_array_equal(getattr(nb_d, name), getattr(nb_s, name))
