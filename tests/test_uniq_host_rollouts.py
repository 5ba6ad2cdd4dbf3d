"""Rollouts that each keep one replica a host: the stream of the
benchmark's ``sched-perf-uniq-host-rollouts-5000n`` (required pod
anti-affinity on ``kubernetes.io/hostname`` under each rollout's own
``pod-template-hash``) at a small size.

Several rollouts interleaved leave no chunk of ``group_size`` pods
uniform, so every chunk replays the full per-pod step (chunk kind 0,
scope ``grouped_slow``) with the ``InterPodAffinity`` scope inside it.
These tests hold that path to the sequential oracle and to the
benchmark's plain reference, and pin the counters of the inter-pod
tensorizer: the terms a solve carries, by side, the placed pods
``build_interpod_tensors`` walks, once a pass, and the rows of incoming
counts it was handed, kept or walked.
"""

import dataclasses

import numpy as np
import pytest

from benchmarks.lib import files, gen, reference
from kubernetes_tpu import metrics
from kubernetes_tpu import scheduler as scheduler_mod
from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.ops.oracle.profile import FullOracle, make_oracle_nodes
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.state.interpod_owners import OwnerTerms
from kubernetes_tpu.tensorize.interpod import INST_PAD, build_interpod_tensors
from kubernetes_tpu.tensorize.plugins import (
    build_port_tensors,
    build_static_tensors,
)
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    build_node_batch,
    build_pod_batch,
)

CONFIG = "sched-perf-uniq-host-rollouts-5000n"
HOST = "kubernetes.io/hostname"
LABEL = "pod-template-hash"
GROUP = 8
BATCH = 64


def small(config=CONFIG, nodes=64, replicas=16, in_flight=3, apps=8):
    """The benchmark's own configuration file, cut to a small size: 8
    rollouts of 16 replicas, 3 in flight, on 64 nodes."""
    cfg = files.load_config(config)
    cfg["nodes"]["count"] = nodes
    st = cfg["stream"]
    st["deploymentReplicas"], st["inFlight"] = replicas, in_flight
    for kind in st["kinds"].values():
        if kind.get("labelKey") == LABEL:
            kind["apps"] = apps
    return cfg


def nodes_of(cfg):
    return [Node.from_dict(d) for d in gen.make_nodes(cfg)]


def pods_of(cfg, specs):
    return [Pod.from_dict(gen.pod_manifest(cfg, s)) for s in specs]


def anti(name, app):
    return gen.PodSpec(name, "anti", app, LABEL)


def mk_sched(cfg, batch=BATCH, split=4):
    cs = ClusterState()
    for node in nodes_of(cfg):
        cs.create_node(node)
    sched = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=batch,
            pipeline_split=split,
            solver=ExactSolverConfig(tie_break="first", group_size=GROUP),
        ),
    )
    return cs, sched


def drive(cs, sched, pods):
    for p in pods:
        cs.create_pod(p)
    results = sched.run_pipelined()
    return [(key, node) for r in results for key, node in r.scheduled]


def slow_chunks():
    return metrics.solve_chunks_total.labels("slow")._value.get()


def solves():
    return sum(
        metrics.solves_total.labels(p)._value.get() for p in ("grouped", "scan")
    )


def counters():
    return (
        metrics.interpod_terms_total.labels("incoming")._value.get(),
        metrics.interpod_terms_total.labels("existing")._value.get(),
        metrics.interpod_placed_visits_total._value.get(),
        metrics.interpod_count_rows_total.labels("kept")._value.get(),
        metrics.interpod_count_rows_total.labels("walk")._value.get(),
    )


# -- (a) the served path against the oracle and the plain reference ---------


@pytest.mark.parametrize("seed", [3800000011, 2**31 + 7])
def test_rollout_stream_pipelined_equals_oracle_and_reference(seed):
    cfg = small()
    specs = gen.RolloutStream(cfg, seed=seed).take(128)
    assert {s.kind for s in specs} == {"anti"}
    assert len({s.app for s in specs}) == 8  # every rollout whole, none twice
    cs, sched = mk_sched(cfg)
    pods = pods_of(cfg, specs)
    slow0 = slow_chunks()
    bound = drive(cs, sched, pods)
    assert slow_chunks() > slow0  # the path under test ran
    assert len(bound) == len(pods)

    # the benchmark's plain reference, every limit at its value
    got = reference.replay(cfg, {s.key: s for s in specs}, bound)
    for name in ("unknown_bindings", "bound_twice", "infeasible_at_commit",
                 "nodes_over_capacity", "anti_affinity_clashes"):
        assert got[name] == 0, (name, got["notes"])
    assert got["bound"] == len(pods)

    # the sequential oracle, pod for pod
    nodes = nodes_of(cfg)
    want, _ = FullOracle(make_oracle_nodes(nodes)).schedule(pods)
    by_key = dict(bound)
    assert [by_key[p.key] for p in pods] == [nodes[i].name for i in want]


# -- (b) grouped (slow chunks) against the per-pod scan ----------------------


def solve_standalone(cfg, specs, group, placed=()):
    """One solve of ``specs`` with ``placed`` (spec, node index) pairs
    already standing."""
    nodes, pods = nodes_of(cfg), pods_of(cfg, specs)
    standing = pods_of(cfg, [s for s, _ in placed])
    vocab = ResourceVocab.build(pods + standing, nodes)
    placed_by_slot: dict = {}
    for p, (_, n_i) in zip(standing, placed):
        placed_by_slot.setdefault(n_i, []).append(p)
    nbatch = build_node_batch(
        nodes, {nodes[i].name: ps for i, ps in placed_by_slot.items()}, vocab=vocab
    )
    pbatch = build_pod_batch(pods, vocab, pad=BATCH)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    ports = build_port_tensors(pods, pbatch, slot_nodes, placed_by_slot, nbatch.padded)
    interpod = build_interpod_tensors(
        pods, static.reps, pbatch, slot_nodes, placed_by_slot, nbatch.padded,
        static.c_pad,
    )
    solver = ExactSolver(ExactSolverConfig(tie_break="first", group_size=group))
    return solver.solve(nbatch, pbatch, static, ports, None, interpod), solver, interpod


@pytest.mark.parametrize("n_labels", [3, 8])
def test_slow_chunks_equal_the_per_pod_scan(n_labels):
    cfg = small(nodes=24)
    specs = [anti(f"c{i:03d}", f"anti-{i % n_labels}") for i in range(BATCH - 5)]
    # two of the labels already hold a node each: their pods must avoid it
    placed = [(anti("old-0", "anti-0"), 3), (anti("old-1", "anti-1"), 5)]
    grouped, s_grouped, interpod = solve_standalone(cfg, specs, GROUP, placed)
    scanned, s_scan, _ = solve_standalone(cfg, specs, 0, placed)
    assert interpod.ident  # hostname domains are one node each
    assert s_grouped.dispatch_counts["kind0"] == BATCH // GROUP
    assert s_scan.dispatch_counts["scan"] == 1 and "kind0" not in s_scan.dispatch_counts
    assert (grouped[: len(specs)] >= 0).all()
    np.testing.assert_array_equal(grouped, scanned)
    # no node holds two pods of one label, the standing ones counted
    names = [f"anti-{i % n_labels}" for i in range(len(specs))] + ["anti-0", "anti-1"]
    where = list(grouped[: len(specs)]) + [3, 5]
    assert len(set(zip(names, where))) == len(names)


# -- (c) the counters ----------------------------------------------------------


@pytest.mark.parametrize(
    "n_plain, placed_labels, batch_labels",
    [(0, 2, 3), (5, 4, 4), (9, 1, 6)],
)
def test_counters_move_by_the_terms_and_the_walk_of_one_batch(
    monkeypatch, n_plain, placed_labels, batch_labels
):
    """A batch whose shapes the test sets: ``n_plain`` plain pods and two
    replicas of each of ``placed_labels`` rollouts already placed, then
    one batch of ``batch_labels`` rollouts, one of which (anti-0) is
    also placed, then a second batch of the same labels."""
    cfg = small(nodes=32)
    cs, sched = mk_sched(cfg, split=1)
    plain = [gen.PodSpec(f"init-{i}", "plain", "init") for i in range(n_plain)]
    standing = [anti(f"old-{a}-{r}", f"anti-{a}") for a in range(placed_labels) for r in range(2)]
    assert len(drive(cs, sched, pods_of(cfg, plain + standing))) == n_plain + len(standing)

    built = []

    def spy(*a, **kw):
        built.append(build_interpod_tensors(*a, **kw))
        return built[-1]

    monkeypatch.setattr(scheduler_mod, "build_interpod_tensors", spy)
    batch = [
        anti(f"new-{i:03d}", f"anti-{i % batch_labels}") for i in range(4 * batch_labels)
    ]
    c0, n0 = counters(), solves()
    d0 = dict(sched.solver.dispatch_counts)
    assert len(drive(cs, sched, pods_of(cfg, batch))) == len(batch)
    c1 = counters()
    assert solves() - n0 == 1 and len(built) == 1
    (t,) = built
    # one required anti term per class (one class per label) in the batch;
    # the existing terms are those that select a pod of the batch: its own
    # labels', whether a placed pod or a batch pod owns them
    assert t.num_in == batch_labels
    assert t.num_ex == batch_labels
    assert c1[0] - c0[0] == t.num_in
    assert c1[1] - c0[1] == t.num_ex
    # one pass over the placed pods, for the labels the placed batch did
    # not already ask about; none where every label was asked before
    placed = n_plain + len(standing)
    new = batch_labels - placed_labels
    assert c1[2] - c0[2] == (placed if new else 0)
    assert (c1[3] - c0[3], c1[4] - c0[4]) == (placed_labels, new)
    # one tally: /metrics and dispatch_counts are the same increments
    d1 = sched.solver.dispatch_counts
    assert d1["interpod_incoming"] - d0["interpod_incoming"] == t.num_in
    assert d1["interpod_existing"] - d0["interpod_existing"] == t.num_ex

    # a second batch of the same labels walks nothing: every row is kept
    again = [
        anti(f"again-{i:03d}", f"anti-{i % batch_labels}") for i in range(2 * batch_labels)
    ]
    assert len(drive(cs, sched, pods_of(cfg, again))) == len(again)
    c2 = counters()
    assert c2[2] == c1[2]
    assert (c2[3] - c1[3], c2[4] - c1[4]) == (batch_labels, 0)


def test_count_rows_kept_and_walked_by_source_and_no_spread_row():
    """k new labels of n in a batch: k rows walked, n - k kept, and the
    spread family's counter does not move."""
    cfg = small(nodes=32)
    cs, sched = mk_sched(cfg, split=1)

    def spread_rows():
        return tuple(
            metrics.spread_count_rows_total.labels(s)._value.get() for s in ("kept", "walk")
        )

    s0, c0 = spread_rows(), counters()
    labels = [f"anti-{a}" for a in range(4)]
    drive(cs, sched, pods_of(cfg, [anti(f"a-{i}", labels[i % 4]) for i in range(8)]))
    c1 = counters()
    assert (c1[3] - c0[3], c1[4] - c0[4]) == (0, 4)
    mixed = labels[:3] + ["anti-6", "anti-7"]  # k = 2 new of n = 5
    drive(cs, sched, pods_of(cfg, [anti(f"b-{i}", mixed[i % 5]) for i in range(10)]))
    c2 = counters()
    assert (c2[3] - c1[3], c2[4] - c1[4]) == (3, 2)
    assert spread_rows() == s0
    assert len(sched.cache.spread_counts) == 6


def test_the_existing_axis_stays_16_as_300_labels_are_placed():
    """te_pad follows the batch's 13 labels, not every label placed: it
    stays 16 (the padded axis of the shape key) while 300 rollouts each
    place a replica."""
    cfg = small(nodes=320)
    nodes = nodes_of(cfg)
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    slot_of = {n.name: i for i, n in enumerate(nodes)}
    batch = pods_of(cfg, [anti(f"new-{i:03d}", f"anti-{i % 13}") for i in range(64)])
    vocab = ResourceVocab.build(batch, nodes)
    nbatch = build_node_batch(nodes, {}, vocab=vocab)
    pbatch = build_pod_batch(batch, vocab, pad=BATCH)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(batch, pbatch, slot_nodes, nbatch.padded)
    for placed in range(0, 301, 60):
        for a in range(placed - 60 if placed else 0, placed):
            pod = pods_of(cfg, [anti(f"old-{a}", f"anti-{a}")])[0]
            cache.add_pod(dataclasses.replace(pod, node_name=nodes[a].name))
        t = build_interpod_tensors(
            batch, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad,
            counts=cache.spread_counts, owners=cache.interpod_owners, slot_of=slot_of,
        )
        assert len(cache.interpod_owners) == placed
        assert t.num_in == 13 and t.num_ex == 13
        assert t.ex_cnt0.shape[0] == 16 > INST_PAD
        # each label's one placed replica is counted on its node
        assert t.ex_cnt0.sum() == min(placed, 13)


@pytest.mark.parametrize(
    "config",
    ["sched-perf-basic-5000n", "sched-perf-spread-5000n", "sched-perf-spread-rollouts-5000n"],
)
def test_a_stream_with_no_inter_pod_term_leaves_the_owner_index_empty(config, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("a pod with no inter-pod term reached the owner index")

    monkeypatch.setattr(OwnerTerms, "pod_added", boom)
    monkeypatch.setattr(OwnerTerms, "pod_removed", boom)
    cfg = small(config, nodes=48, replicas=16)
    specs = gen.RolloutStream(cfg, seed=3900000013).take(64)
    cs, sched = mk_sched(cfg)
    assert len(drive(cs, sched, pods_of(cfg, specs))) == len(specs)
    assert len(sched.cache.interpod_owners) == 0


@pytest.mark.parametrize(
    "config",
    ["sched-perf-basic-5000n", "sched-perf-spread-5000n", "sched-perf-spread-rollouts-5000n"],
)
def test_spread_and_basic_streams_move_neither_counter(config):
    cfg = small(config, nodes=48, replicas=16)
    specs = gen.RolloutStream(cfg, seed=3800000029).take(96)
    cs, sched = mk_sched(cfg)
    c0, n0 = counters(), solves()
    assert len(drive(cs, sched, pods_of(cfg, specs))) == len(specs)
    assert solves() > n0
    assert counters() == c0
    assert "interpod_incoming" not in sched.solver.dispatch_counts
    assert "interpod_existing" not in sched.solver.dispatch_counts
