"""Rollouts that each spread their own replicas (ISSUE 27): many spread
selectors in one queue, the stream of the benchmark's
``sched-perf-spread-rollouts-5000n`` at a small size.

With one label in the whole stream every chunk of ``group_size`` pods is
uniform and takes the grouped program's spread fast branch; with several
rollouts interleaved no chunk is, and every chunk replays the full
per-pod step (chunk kind 0, scope ``grouped_slow``). These tests hold
that path to the sequential oracle and to the benchmark's plain
reference, pin the counters that say which branch a batch took, and pin
the family of executables a batch's label count can reach.
"""

import jax
import numpy as np
import pytest

from benchmarks.lib import gen, reference
from kubernetes_tpu import metrics
from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.obs.compile import WATCHER
from kubernetes_tpu.ops.oracle.profile import FullOracle, make_oracle_nodes
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.tensorize.plugins import (
    build_port_tensors,
    build_static_tensors,
)
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    build_node_batch,
    build_pod_batch,
)
from kubernetes_tpu.tensorize.spread import INST_PAD, build_spread_tensors

ZONE = "topology.kubernetes.io/zone"
LABEL = "pod-template-hash"
GROUP = 8
BATCH = 64


def rollout_cfg(max_skew=5, nodes=60, replicas=20, in_flight=8, apps=40):
    """The benchmark configuration's own shape (benchmarks/configs/
    sched-perf-spread-rollouts-5000n.json), small."""
    return {
        "nodes": {
            "count": nodes, "zones": 3, "zoneNames": ["moon-1", "moon-2", "moon-3"],
            "namePattern": "node-%03d",
            "allocatable": {"cpu": "4", "memory": "32Gi", "pods": "110"},
            "labels": {ZONE: "{zone}"},
        },
        "podRequests": {"cpu": "100m", "memory": "500Mi"},
        "stream": {
            "deploymentReplicas": replicas, "inFlight": in_flight,
            "kinds": {"spread": {
                "share": 1.0, "labelKey": LABEL, "apps": apps,
                "maxSkew": max_skew, "topologyKey": ZONE,
                "whenUnsatisfiable": "DoNotSchedule",
            }},
        },
    }


def nodes_of(cfg):
    return [Node.from_dict(d) for d in gen.make_nodes(cfg)]


def pods_of(cfg, specs):
    return [Pod.from_dict(gen.pod_manifest(cfg, s)) for s in specs]


def cycled(n_labels, n=BATCH, tag=""):
    """``n`` pods going round ``n_labels`` labels: no two neighbours
    alike once there are two labels, so no chunk is uniform."""
    return [
        gen.PodSpec(f"c{tag}{n_labels}-{i:03d}", "spread", f"v{i % n_labels}", LABEL)
        for i in range(n)
    ]


def mk_sched(cfg, split=4):
    cs = ClusterState()
    for node in nodes_of(cfg):
        cs.create_node(node)
    sched = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=BATCH,
            pipeline_split=split,
            solver=ExactSolverConfig(tie_break="first", group_size=GROUP),
        ),
    )
    return cs, sched


def chunk_counts():
    return {
        k: metrics.solve_chunks_total.labels(k)._value.get()
        for k in ("slow", "plain", "spread", "anti")
    }


def solves():
    return sum(
        metrics.solves_total.labels(p)._value.get() for p in ("grouped", "scan")
    )


# -- (a) the served path against the oracle and the plain reference ---------


@pytest.mark.parametrize("max_skew", [5, 1])
def test_rollout_stream_pipelined_equals_oracle_and_reference(max_skew):
    cfg = rollout_cfg(max_skew=max_skew)
    specs = gen.RolloutStream(cfg, seed=2900000011).take(200)
    assert len({s.app for s in specs}) >= 12  # 8 in flight + turnovers
    cs, sched = mk_sched(cfg)
    pods = pods_of(cfg, specs)
    for p in pods:
        cs.create_pod(p)
    slow0 = chunk_counts()["slow"]
    results = sched.run_pipelined()
    assert chunk_counts()["slow"] > slow0  # the path under test ran
    bound = [(key, node) for r in results for key, node in r.scheduled]
    assert len(bound) == len(pods)

    # the benchmark's plain reference, every limit at its value
    got = reference.replay(cfg, {s.key: s for s in specs}, bound)
    for name in ("unknown_bindings", "bound_twice", "infeasible_at_commit",
                 "nodes_over_capacity"):
        assert got[name] == 0, (name, got["notes"])
    assert got["max_zone_skew"] <= max_skew
    assert got["bound"] == len(pods)

    # the sequential oracle, pod for pod
    nodes = nodes_of(cfg)
    want, _ = FullOracle(make_oracle_nodes(nodes)).schedule(pods)
    by_key = dict(bound)
    assert [by_key[p.key] for p in pods] == [nodes[i].name for i in want]


# -- (b) grouped (slow chunks) against the per-pod scan ----------------------


def solve_standalone(cfg, specs, group):
    nodes, pods = nodes_of(cfg), pods_of(cfg, specs)
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, {}, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab, pad=BATCH)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    ports = build_port_tensors(pods, pbatch, slot_nodes, {}, nbatch.padded)
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad
    )
    solver = ExactSolver(ExactSolverConfig(tie_break="first", group_size=group))
    solver.nodes_after = nbatch  # standalone mode writes the state back
    return solver.solve(nbatch, pbatch, static, ports, spread), solver, spread


@pytest.mark.parametrize("max_skew", [5, 1])
def test_slow_chunks_equal_the_per_pod_scan(max_skew):
    cfg = rollout_cfg(max_skew=max_skew, nodes=24)
    specs = gen.RolloutStream(cfg, seed=7).take(BATCH - 5)  # a ragged last chunk
    grouped, s_grouped, _ = solve_standalone(cfg, specs, GROUP)
    scanned, s_scan, _ = solve_standalone(cfg, specs, 0)
    assert s_grouped.dispatch_counts["kind0"] == BATCH // GROUP
    assert s_grouped.dispatch_counts["grouped"] == 1
    assert s_scan.dispatch_counts["scan"] == 1 and "kind0" not in s_scan.dispatch_counts
    assert (grouped >= 0).all()
    np.testing.assert_array_equal(grouped, scanned)


@pytest.mark.parametrize("max_skew", [5, 1])
def test_slow_chunks_dense_equal_scatter(max_skew, all_scatter):
    """Chunk kind 0: the per-pod step's _domain_aggregate through
    ops/domains.py, dense (as shipped, 8 zone slots) against the scatter,
    one seeded batch: the same assignments and carried node state."""
    cfg = rollout_cfg(max_skew=max_skew, nodes=24)
    specs = gen.RolloutStream(cfg, seed=7).take(BATCH - 5)
    with all_scatter():
        a_s, s_s, _ = solve_standalone(cfg, specs, GROUP)
    a_d, s_d, _ = solve_standalone(cfg, specs, GROUP)
    assert s_d.dispatch_counts["kind0"] == BATCH // GROUP
    assert s_s.dispatch_counts["domains_scatter"] == 1
    assert s_d.dispatch_counts["domains_dense"] == 1
    assert (a_d >= 0).all()
    np.testing.assert_array_equal(a_d, a_s)
    for name in ("used", "nonzero_used", "pod_count"):
        np.testing.assert_array_equal(
            getattr(s_d.nodes_after, name), getattr(s_s.nodes_after, name)
        )


# -- (c) the counters ----------------------------------------------------------


def test_one_label_counts_spread_chunks_and_the_ragged_one_slow():
    cfg = rollout_cfg()
    c0, n0 = chunk_counts(), solves()
    i0 = metrics.spread_instances_total._value.get()
    _, solver, spread = solve_standalone(cfg, cycled(1, n=BATCH - 5), GROUP)
    c1 = chunk_counts()
    # 59 pods: 7 full chunks, and a ragged one that is slow by the rule
    # that a fast chunk is `group` identical VALID pods (_chunk_kinds)
    assert c1["spread"] - c0["spread"] == (BATCH - 5) // GROUP
    assert c1["plain"] == c0["plain"] and c1["anti"] == c0["anti"]
    assert c1["slow"] - c0["slow"] == 1  # the ragged chunk
    assert solves() - n0 == 1
    assert metrics.spread_instances_total._value.get() - i0 == 1 == spread.num_instances
    # one tally: /metrics and dispatch_counts are the same increments
    assert solver.dispatch_counts["kind2"] == (BATCH - 5) // GROUP
    assert solver.dispatch_counts["spread_instances"] == 1


def test_one_label_full_batch_counts_no_slow_chunk():
    c0 = chunk_counts()
    _, solver, _ = solve_standalone(rollout_cfg(), cycled(1), GROUP)
    c1 = chunk_counts()
    assert c1["spread"] - c0["spread"] == BATCH // GROUP
    assert c1["slow"] == c0["slow"]
    assert "padding" not in solver.dispatch_counts


@pytest.mark.parametrize("n_labels", [2, 8, 13])
def test_interleaved_labels_count_slow_chunks_and_instances(n_labels):
    c0, n0 = chunk_counts(), solves()
    i0 = metrics.spread_instances_total._value.get()
    n = BATCH - 2 * GROUP  # two chunks of padding at the end
    _, solver, spread = solve_standalone(rollout_cfg(), cycled(n_labels, n=n), GROUP)
    c1 = chunk_counts()
    assert c1["slow"] - c0["slow"] == n // GROUP
    # all-padding chunks are kind 1 on the wire and are NOT plain chunks
    assert {k: c1[k] - c0[k] for k in ("plain", "spread", "anti")} == {
        "plain": 0, "spread": 0, "anti": 0,
    }
    assert solver.dispatch_counts["padding"] == 2
    assert spread.num_instances == n_labels
    assert metrics.spread_instances_total._value.get() - i0 == n_labels
    assert solves() - n0 == 1


def test_class_table_upload_counts_a_miss_not_a_hit():
    cfg = rollout_cfg()
    cs, sched = mk_sched(cfg)
    u0 = metrics.class_table_uploads_total._value.get()
    for rnd in range(2):  # the same labels twice: one miss, one hit
        for p in pods_of(cfg, cycled(3, tag=f"r{rnd}-")):
            cs.create_pod(p)
        sched.run_pipelined()
    assert sched.solver.dispatch_counts["class_table_uploads"] == 1
    assert metrics.class_table_uploads_total._value.get() - u0 == 1
    for p in pods_of(cfg, cycled(4, tag="other-")):  # other tables: a miss
        cs.create_pod(p)
    sched.run_pipelined()
    assert metrics.class_table_uploads_total._value.get() - u0 == 2


# -- (d) the family of executables a batch's label count can reach ------------
#
# The instance axis and the class axis are padded to powers of two from 8
# (tensorize/spread.py INST_PAD, tensorize/plugins.py CLASS_PAD), and one
# label alone rides the compact wire. So every count of labels a batch can
# hold from 1 to 17 lands on one of FOUR members: compact, 8, 16, 32. Once
# a process has met those (serve: in set-up), no batch compiles.

FAMILY = (1, INST_PAD, 2 * INST_PAD, 2 * INST_PAD + 1)


def drive_labels(n_labels, tag):
    """Two batches of ``n_labels`` interleaved labels through the served
    path (first dispatch and chained dispatch, then a heal)."""
    cfg = rollout_cfg()
    cs, sched = mk_sched(cfg)
    for rnd in range(2):
        for p in pods_of(cfg, cycled(n_labels, tag=f"{tag}{rnd}-")):
            cs.create_pod(p)
        assert sum(len(r.scheduled) for r in sched.run_pipelined()) == BATCH


@pytest.fixture(scope="module")
def family_met():
    # the persistent cache would hide a compile as a fetch, which stalls
    # a batch just the same and which WATCHER counts just the same; off,
    # so that the count does not depend on what an earlier run left
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    WATCHER.install()
    for n_labels in FAMILY:
        drive_labels(n_labels, "warm")
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("n_labels", range(1, 18))
def test_label_count_meets_no_executable_outside_the_family(family_met, n_labels):
    c0, _, _ = WATCHER.totals()
    drive_labels(n_labels, "t")
    c1, _, _ = WATCHER.totals()
    assert c1 == c0, (
        f"a batch of {n_labels} labels compiled {c1 - c0} executables "
        f"beyond the family {FAMILY}: serve would stall inside a window"
    )
