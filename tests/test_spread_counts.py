"""Per-selector node counts kept in the scheduler cache (ISSUE 28).

``SpreadTensors.cnt0`` used to be counted by a walk over every placed pod,
once per constraint instance per batch. The cache now keeps the counts
where pods enter and leave a node (state/spread_counts.py), and the node
side rows (``dom``, ``elig``) are built once per distinct input. These
tests hold the kept counts to a from-scratch walk after every cache
mutation, and every array of ``build_spread_tensors`` to the per-instance
walk it replaced, which lives on here as the reference.
"""

import dataclasses
import random

import numpy as np
import pytest

from benchmarks.lib import gen
from kubernetes_tpu import metrics
from kubernetes_tpu.api.labels import (
    EXISTS,
    IN,
    NOT_IN,
    Requirement,
    Selector,
    selector_from_match_labels,
)
from kubernetes_tpu.api.objects import Node, Pod, Service, TopologySpreadConstraint
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.ops.oracle import spread as osp
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolverConfig
from kubernetes_tpu.state import spread_counts
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.tensorize.plugins import build_static_tensors
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    bucket_pow2,
    build_node_batch,
    build_pod_batch,
)
from kubernetes_tpu.tensorize.spread import (
    DOM_PAD,
    INST_PAD,
    SpreadTensors,
    build_spread_tensors,
    trivial_spread_tensors,
)
from kubernetes_tpu.utils.clock import FakeClock

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"


# -- the reference: build_spread_tensors as it stood before this change ------


def reference_spread_tensors(
    pods, class_reps, pbatch, slot_nodes, placed_by_slot, padded_n, c_pad,
    services=None, defaulting="System", nominated=(),
) -> SpreadTensors:
    """The parent commit's implementation, kept word for word: every row
    of every instance from its own loop over the nodes and its own walk
    over every placed pod."""
    per_class = []
    insts = []
    for c, rep in enumerate(class_reps):
        defaults = (
            osp.system_default_constraints(rep, services)
            if defaulting == "System" and services
            else ()
        )
        hard = osp.effective_constraints(rep, hard=True)
        soft = osp.effective_constraints(rep, hard=False, defaults=defaults)
        per_class.append((hard, soft))
        for ec in hard:
            insts.append((c, ec, True, rep))
        for ec in soft:
            insts.append((c, ec, False, rep))

    if not insts:
        return trivial_spread_tensors(pbatch, padded_n, c_pad)

    j_pad = bucket_pow2(len(insts), floor=INST_PAD)
    sh = max(max((len(h) for h, _ in per_class), default=0), 1)
    ss = max(max((len(s) for _, s in per_class), default=0), 1)
    hard_tbl = np.full((c_pad, sh), -1, dtype=np.int32)
    soft_tbl = np.full((c_pad, ss), -1, dtype=np.int32)

    all_keys = {ec.topology_key for _, ec, _, _ in insts}
    key_vocab = {k: {} for k in all_keys}
    for node in slot_nodes:
        if node is None:
            continue
        for key in all_keys:
            v = node.labels.get(key)
            if v is not None:
                vocab = key_vocab[key]
                vocab.setdefault(v, len(vocab))
    max_domains = max((len(v) for v in key_vocab.values()), default=1)
    d_pad = bucket_pow2(max_domains, floor=DOM_PAD)

    dom = np.full((j_pad, padded_n), -1, dtype=np.int32)
    elig = np.zeros((j_pad, padded_n), dtype=bool)
    max_skew = np.ones(j_pad, dtype=np.int32)
    min_domains = np.full(j_pad, -1, dtype=np.int32)
    self_match = np.zeros(j_pad, dtype=bool)
    is_hostname = np.zeros(j_pad, dtype=bool)
    cnt0 = np.zeros((j_pad, padded_n), dtype=np.int32)
    placed_match = np.zeros((pbatch.padded, j_pad), dtype=bool)

    elig_cache = {}

    def bucket_elig(c, is_hard):
        row = elig_cache.get((c, is_hard))
        if row is None:
            bucket = per_class[c][0] if is_hard else per_class[c][1]
            rep = class_reps[c]
            row = np.zeros(padded_n, dtype=bool)
            for n_i, node in enumerate(slot_nodes):
                if node is not None and n_i < padded_n:
                    row[n_i] = osp._node_counted(rep, node, bucket)
            elig_cache[(c, is_hard)] = row
        return row

    hard_fill = {}
    soft_fill = {}
    for j, (c, ec, is_hard, rep) in enumerate(insts):
        tbl, fill = (hard_tbl, hard_fill) if is_hard else (soft_tbl, soft_fill)
        s = fill.get(c, 0)
        tbl[c, s] = j
        fill[c] = s + 1

        max_skew[j] = ec.max_skew
        if ec.min_domains is not None:
            min_domains[j] = ec.min_domains
        self_match[j] = osp._sel_matches(ec.selector, rep.labels)
        is_hostname[j] = ec.topology_key == osp.HOSTNAME_KEY
        elig[j] = bucket_elig(c, is_hard)

        vocab = key_vocab.get(ec.topology_key, {})
        for n_i, node in enumerate(slot_nodes):
            if node is None or n_i >= padded_n:
                continue
            v = node.labels.get(ec.topology_key)
            if v is not None:
                dom[j, n_i] = vocab[v]
        for n_i, placed in placed_by_slot.items():
            if n_i >= padded_n:
                continue
            cnt0[j, n_i] = sum(
                1
                for p in placed
                if p.namespace == rep.namespace
                and osp._sel_matches(ec.selector, p.labels)
            )
        for p, n_i in nominated:
            if 0 <= n_i < padded_n and (
                p.namespace == rep.namespace
                and osp._sel_matches(ec.selector, p.labels)
            ):
                cnt0[j, n_i] += 1

        for p_i, pod in enumerate(pods):
            placed_match[p_i, j] = pod.namespace == rep.namespace and (
                osp._sel_matches(ec.selector, pod.labels)
            )

    return SpreadTensors(
        num_instances=len(insts), d_pad=d_pad, dom=dom, elig=elig,
        max_skew=max_skew, min_domains=min_domains, self_match=self_match,
        is_hostname=is_hostname, hard=hard_tbl, soft=soft_tbl, cnt0=cnt0,
        placed_match=placed_match,
    )


def assert_same_tensors(got: SpreadTensors, want: SpreadTensors):
    for f in dataclasses.fields(SpreadTensors):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, f.name
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, f.name


# -- helpers -------------------------------------------------------------------


def walk(cache: SchedulerCache, namespace, selector) -> dict:
    """node name -> matching pods, from scratch, over the nodes that
    count (their Node object is there)."""
    out = {}
    for name, info in cache.nodes.items():
        if info.node is None:
            continue
        n = sum(
            1 for p in info.pods.values()
            if p.namespace == namespace and osp._sel_matches(selector, p.labels)
        )
        if n:
            out[name] = n
    return out


def rows_walked():
    return {
        s: metrics.spread_count_rows_total.labels(s)._value.get()
        for s in ("kept", "walk")
    }


def cluster_node(i, zones=3, **labels):
    n = (
        MakeNode().name(f"node-{i:03d}")
        .capacity({"cpu": "8", "memory": "32Gi", "pods": "110"})
        .label(ZONE, f"z{i % zones}").label(HOSTNAME, f"node-{i:03d}")
    )
    for k, v in labels.items():
        n = n.label(k, v)
    return n.obj()


def labelled_pod(name, labels, namespace="default", node=None) -> Pod:
    p = MakePod().name(name).namespace(namespace).labels(labels).req({"cpu": "100m"})
    if node is not None:
        p = p.node(node)
    return p.obj()


def tensors_three_ways(nodes, pods, placed_by_node, services=None, nominated=()):
    """(reference, change with no cache, change with the cache's counts),
    all for the same cluster and batch. ``nominated`` is (pod, node name)."""
    placed_by_node = {k: list(v) for k, v in placed_by_node.items()}
    all_pods = pods + [p for ps in placed_by_node.values() for p in ps]
    vocab = ResourceVocab.build(all_pods, nodes)
    nbatch = build_node_batch(nodes, placed_by_node, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    slot_of = {n.name: i for i, n in enumerate(nodes)}
    placed_by_slot = {slot_of[name]: ps for name, ps in placed_by_node.items()}
    noms = [(p, slot_of.get(name, 999)) for p, name in nominated]
    args = (pods, static.reps, pbatch, slot_nodes, placed_by_slot,
            nbatch.padded, static.c_pad)
    kw = dict(services=services, nominated=noms)
    want = reference_spread_tensors(*args, **kw)
    no_cache = build_spread_tensors(*args, **kw)

    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    for name, ps in placed_by_node.items():
        for p in ps:
            cache.add_pod(dataclasses.replace(p, node_name=name))
    with_cache = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad,
        counts=cache.spread_counts, slot_of=slot_of, **kw,
    )
    return want, no_cache, with_cache, static


# -- (i) kept counts equal a from-scratch walk after every cache mutation ------

ML = selector_from_match_labels
SELECTORS = [
    ("default", ML({"app": "web"})),  # one matchLabels pair
    ("default", ML({"app": "web", "tier": "front"})),  # two pairs
    ("default", Selector((Requirement("app", IN, ("web", "api")),))),
    ("default", Selector((Requirement("app", NOT_IN, ("web",)),))),
    ("default", Selector((Requirement("tier", EXISTS),))),
    ("default", Selector(())),  # empty: every pod of the namespace
    ("default", None),  # nil: matches nothing
    ("prod", ML({"app": "web"})),  # the same selector, another namespace
]
APPS = ("web", "api", "db", None)
TIERS = ("front", "back", None)
TTL = 30.0


class Churn:
    """A seeded random sequence of every way a pod enters or leaves a
    node of the cache."""

    def __init__(self, seed):
        self.rng = random.Random(seed)
        self.clock = FakeClock()
        self.cache = SchedulerCache(self.clock, assume_ttl=TTL)
        self.nodes = {n.name: n for n in (cluster_node(i) for i in range(6))}
        for n in list(self.nodes.values())[:4]:
            self.cache.add_node(n)
        self.serial = 0
        self.ran = set()

    def labels(self):
        out = {}
        app, tier = self.rng.choice(APPS), self.rng.choice(TIERS)
        if app:
            out["app"] = app
        if tier:
            out["tier"] = tier
        return out

    def new_pod(self, node=None) -> Pod:
        self.serial += 1
        return labelled_pod(
            f"p{self.serial}", self.labels(), self.rng.choice(("default", "prod")), node
        )

    def live(self):
        return [n for n, i in self.cache.nodes.items() if i.node is not None]

    def assumed(self):
        return sorted(self.cache._assumed)

    def settled(self):
        return sorted(k for k in self.cache._pod_node if k not in self.cache._assumed)

    def stored(self, key) -> Pod:
        return self.cache.nodes[self.cache.pod_node(key)].pods[key]

    def api_copy(self, key, node, relabel) -> Pod:
        old = self.stored(key)
        return dataclasses.replace(
            old, node_name=node, labels=self.labels() if relabel else dict(old.labels)
        )

    def step(self):
        rng, cache = self.rng, self.cache
        ops = ["assume", "plain_add", "add_before_node", "add_node"]
        if self.assumed():
            ops += ["forget", "expire", "confirm_relabelled", "confirm_elsewhere"]
        if self.settled():
            ops += ["relabel", "move", "remove_pod"]
        if len(self.live()) > 2:
            ops += ["remove_node"]
        op = rng.choice(ops)
        live = self.live()
        if op == "assume":
            cache.assume_pod(self.new_pod(), rng.choice(live))
        elif op == "forget":
            cache.forget_pod(rng.choice(self.assumed()))
        elif op == "expire":
            # one bind finished and never confirmed; older unfinished
            # assumes expire with it
            cache.finish_binding(rng.choice(self.assumed()))
            self.clock.advance(TTL + 1)
            assert cache.cleanup_expired()
        elif op == "confirm_relabelled":
            key = rng.choice(self.assumed())
            cache.add_pod(self.api_copy(key, cache.pod_node(key), relabel=True))
        elif op == "confirm_elsewhere":
            key = rng.choice(self.assumed())
            cache.add_pod(self.api_copy(key, rng.choice(live), relabel=False))
        elif op == "plain_add":
            cache.add_pod(self.new_pod(node=rng.choice(live)))
        elif op == "add_before_node":
            absent = [n for n in self.nodes if n not in live]
            if not absent:
                return self.step()
            cache.add_pod(self.new_pod(node=rng.choice(absent)))
        elif op == "relabel":
            key = rng.choice(self.settled())
            cache.update_pod(self.api_copy(key, cache.pod_node(key), relabel=True))
        elif op == "move":
            key = rng.choice(self.settled())
            cache.update_pod(self.api_copy(key, rng.choice(live), relabel=False))
        elif op == "remove_pod":
            cache.remove_pod(rng.choice(self.settled()))
        elif op == "remove_node":
            # with its pods left on it more often than not
            cache.remove_node(rng.choice(live))
        elif op == "add_node":
            absent = [n for n in self.nodes if n not in live]
            if not absent:
                return self.step()
            cache.add_node(self.nodes[rng.choice(absent)])
        self.ran.add(op)


def assert_kept_equals_walk(cache):
    slot_of = {name: i for i, name in enumerate(sorted(cache.nodes))}
    rows = cache.spread_counts.rows(SELECTORS, 8, slot_of)
    for row, (namespace, selector) in zip(rows, SELECTORS):
        want = walk(cache, namespace, selector)
        if selector is not None:
            assert cache.spread_counts.counts(namespace, selector) == want
        laid_out = {n: int(row[s]) for n, s in slot_of.items() if row[s]}
        assert laid_out == want


@pytest.mark.parametrize("track_from", [0, 60])
@pytest.mark.parametrize("seed", [1, 20260928, 2900000011])
def test_kept_counts_equal_a_walk_after_every_step(seed, track_from):
    """``track_from`` 60: the selectors are first asked for on a cluster
    that already holds pods (some on removed nodes, some assumed), so
    the first count is the one pass over the placed pods."""
    churn = Churn(seed)
    for i in range(240):
        churn.step()
        if i >= track_from:
            assert_kept_equals_walk(churn.cache)
    assert churn.ran == {
        "assume", "forget", "expire", "confirm_relabelled", "confirm_elsewhere",
        "plain_add", "add_before_node", "relabel", "move", "remove_pod",
        "remove_node", "add_node",
    }
    # a removed node with pods left counted nothing while it was away
    assert len(churn.cache.spread_counts) == len(SELECTORS) - 1  # not the nil one


def test_a_removed_node_with_pods_left_counts_nothing_until_it_is_back():
    cache = SchedulerCache()
    node = cluster_node(0)
    cache.add_node(node)
    cache.add_pod(labelled_pod("a", {"app": "web"}, node=node.name))
    key = ("default", ML({"app": "web"}))
    assert cache.spread_counts.rows([key], 8, {node.name: 3})[0].tolist() == [
        0, 0, 0, 1, 0, 0, 0, 0]
    cache.remove_node(node.name)
    assert cache.nodes[node.name].node is None  # the pod keeps it there
    cache.add_pod(labelled_pod("b", {"app": "web"}, node=node.name))
    assert cache.spread_counts.counts(*key) == {}
    cache.add_node(node)
    assert cache.spread_counts.counts(*key) == {node.name: 2}
    # a name with no slot in this batch is left out of the row
    assert not cache.spread_counts.rows([key], 8, {}).any()


# -- (ii) every field of SpreadTensors equals the parent's ---------------------


def rollout_cfg(nodes=60):
    return {
        "nodes": {
            "count": nodes, "zones": 3, "zoneNames": ["moon-1", "moon-2", "moon-3"],
            "namePattern": "node-%03d",
            "allocatable": {"cpu": "4", "memory": "32Gi", "pods": "110"},
            "labels": {ZONE: "{zone}"},
        },
        "podRequests": {"cpu": "100m", "memory": "500Mi"},
        "stream": {
            "deploymentReplicas": 20, "inFlight": 8,
            "kinds": {"spread": {
                "share": 1.0, "labelKey": "pod-template-hash", "apps": 40,
                "maxSkew": 5, "topologyKey": ZONE,
                "whenUnsatisfiable": "DoNotSchedule",
            }},
        },
    }


@pytest.mark.parametrize("n_labels", [9, 12, 16])
def test_tensors_equal_the_parents_on_the_rollouts_stream(n_labels):
    cfg = rollout_cfg()
    nodes = [Node.from_dict(d) for d in gen.make_nodes(cfg)]
    rng = random.Random(n_labels)
    # the placed pods: earlier replicas of the same rollouts, and others
    placed = {}
    for i in range(300):
        spec = gen.PodSpec(f"old-{i:03d}", "spread", f"v{i % (n_labels + 4)}",
                           "pod-template-hash")
        placed.setdefault(rng.choice(nodes).name, []).append(
            Pod.from_dict(gen.pod_manifest(cfg, spec)))
    pods = [
        Pod.from_dict(gen.pod_manifest(cfg, gen.PodSpec(
            f"new-{i:03d}", "spread", f"v{i % n_labels}", "pod-template-hash")))
        for i in range(64)
    ]
    want, no_cache, with_cache, _ = tensors_three_ways(nodes, pods, placed)
    assert want.num_instances == n_labels and want.cnt0.sum() > 0
    assert_same_tensors(no_cache, want)
    assert_same_tensors(with_cache, want)


def spread_pod(name, labels, constraints, **kw):
    p = labelled_pod(name, labels, **kw)
    p.topology_spread_constraints = tuple(constraints)
    return p


def mixed_cluster():
    nodes = [cluster_node(i, pool="a" if i % 2 else "b") for i in range(10)]
    nodes.append(  # lacks the zone key, tainted
        MakeNode().name("bare").capacity({"cpu": "8", "pods": "110"})
        .label(HOSTNAME, "bare").taint("dedicated", "x").obj())
    rng = random.Random(5)
    placed = {}
    for i in range(80):
        placed.setdefault(rng.choice(nodes).name, []).append(labelled_pod(
            f"old{i}", {"app": rng.choice(("web", "api")), "tier": rng.choice(("front", "back"))},
            namespace=rng.choice(("default", "prod"))))
    return nodes, placed


def test_tensors_equal_on_hostname_and_zone_keys_hard_and_soft():
    nodes, placed = mixed_cluster()
    web = ML({"app": "web"})
    tsc = TopologySpreadConstraint
    pods = [
        spread_pod(f"w{i}", {"app": "web", "tier": "front"}, [
            tsc(1, ZONE, "DoNotSchedule", web),
            tsc(2, HOSTNAME, "DoNotSchedule", web, min_domains=4),
            tsc(1, HOSTNAME, "ScheduleAnyway", ML({"app": "web", "tier": "front"})),
        ]) for i in range(3)
    ] + [
        spread_pod(f"a{i}", {"app": "api"}, [
            tsc(3, ZONE, "ScheduleAnyway",
                Selector((Requirement("app", IN, ("web", "api")),))),
            tsc(1, HOSTNAME, "DoNotSchedule",
                Selector((Requirement("app", NOT_IN, ("web",)),)),
                node_taints_policy="Honor"),
            tsc(1, ZONE, "DoNotSchedule", None),  # nil selector
            tsc(1, ZONE, "ScheduleAnyway", Selector(())),  # empty selector
        ], namespace="prod") for i in range(2)
    ]
    want, no_cache, with_cache, _ = tensors_three_ways(nodes, pods, placed)
    assert want.num_instances == 7 and want.has_soft
    assert want.is_hostname.sum() == 3 and (want.dom == -1).any()
    assert_same_tensors(no_cache, want)
    assert_same_tensors(with_cache, want)


def test_tensors_equal_with_system_defaults_through_services():
    nodes, placed = mixed_cluster()
    services = [Service(name="web", namespace="default", selector={"app": "web"})]
    pods = [labelled_pod(f"s{i}", {"app": "web"}) for i in range(4)]
    pods.append(labelled_pod("other", {"app": "db"}))
    want, no_cache, with_cache, _ = tensors_three_ways(
        nodes, pods, placed, services=services)
    assert want.num_instances == 2 and want.has_soft  # zone + hostname defaults
    assert_same_tensors(no_cache, want)
    assert_same_tensors(with_cache, want)


def test_tensors_equal_with_nominated_peers():
    nodes, placed = mixed_cluster()
    web = ML({"app": "web"})
    pods = [spread_pod(f"w{i}", {"app": "web"},
                       [TopologySpreadConstraint(1, ZONE, "DoNotSchedule", web)])
            for i in range(3)]
    nominated = [
        (labelled_pod("nom-a", {"app": "web"}), nodes[2].name),
        (labelled_pod("nom-b", {"app": "web"}), nodes[2].name),
        (labelled_pod("nom-other-ns", {"app": "web"}, namespace="prod"), nodes[3].name),
        (labelled_pod("nom-dead-slot", {"app": "web"}), "no-such-node"),
    ]
    want, no_cache, with_cache, _ = tensors_three_ways(
        nodes, pods, placed, nominated=nominated)
    bare, _, _, _ = tensors_three_ways(nodes, pods, placed)
    assert (want.cnt0 - bare.cnt0).sum() == 2  # the two peers on node 2
    assert_same_tensors(no_cache, want)
    assert_same_tensors(with_cache, want)


def test_classes_share_an_elig_row_only_where_node_counted_cannot_tell():
    nodes, placed = mixed_cluster()
    tsc = TopologySpreadConstraint

    def rollout(name, app, pool=None, policy="Honor", tolerate=False, taints="Ignore"):
        b = MakePod().name(name).label("app", app).req({"cpu": "100m"})
        if pool:
            b = b.node_selector({"pool": pool})
        if tolerate:
            b = b.toleration("dedicated", "x")
        p = b.obj()
        p.topology_spread_constraints = (tsc(
            1, HOSTNAME, "DoNotSchedule", ML({"app": app}),
            node_affinity_policy=policy, node_taints_policy=taints),)
        return p

    pods = [
        rollout("a", "r0", pool="a"),
        rollout("b", "r1", pool="b"),  # Honor, another selector: its own row
        rollout("a2", "r2", pool="a"),  # as "a": the same row
        rollout("ign", "r3", pool="b", policy="Ignore"),  # nodeSelector not read
        rollout("ign2", "r4", policy="Ignore"),  # as "ign"
        rollout("tol", "r5", policy="Ignore", tolerate=True, taints="Honor"),
        rollout("intol", "r6", policy="Ignore", taints="Honor"),
    ]
    want, no_cache, with_cache, static = tensors_three_ways(nodes, pods, placed)
    assert_same_tensors(no_cache, want)
    assert_same_tensors(with_cache, want)
    row = {}
    for c, rep in enumerate(static.reps):
        row[rep.name] = no_cache.elig[no_cache.hard[c, 0]]
    assert not np.array_equal(row["a"], row["b"])
    assert row["a"][: len(nodes)].tolist() == [
        n.labels.get("pool") == "a" for n in nodes]
    np.testing.assert_array_equal(row["a"], row["a2"])
    np.testing.assert_array_equal(row["ign"], row["ign2"])
    assert row["ign"][: len(nodes)].all()  # every node has the hostname key
    assert row["tol"][len(nodes) - 1] and not row["intol"][len(nodes) - 1]


# -- (iii) the bound ------------------------------------------------------------


def test_a_dropped_selector_counts_right_when_it_returns(monkeypatch):
    monkeypatch.setattr(spread_counts, "KEEP_BATCHES", 3)
    cache = SchedulerCache()
    for i in range(3):
        cache.add_node(cluster_node(i))
    slot_of = {f"node-{i:03d}": i for i in range(3)}
    web, api = ("default", ML({"app": "web"})), ("default", ML({"app": "api"}))
    exists = ("default", Selector((Requirement("app", EXISTS),)))
    cache.add_pod(labelled_pod("w0", {"app": "web"}, node="node-000"))
    assert cache.spread_counts.rows([web, exists], 4, slot_of).tolist() == [
        [1, 0, 0, 0], [1, 0, 0, 0]]
    for i in range(3):  # three batches that do not name them
        assert len(cache.spread_counts) == 3 if i else 2
        cache.spread_counts.rows([api], 4, slot_of)
    assert len(cache.spread_counts) == 1
    assert cache.spread_counts.counts(*web) is None
    assert cache.spread_counts.counts(*exists) is None
    # the cluster moves on while nothing keeps their counts
    cache.add_pod(labelled_pod("w1", {"app": "web"}, node="node-002"))
    cache.remove_pod("default/w0")
    cache.add_pod(labelled_pod("a0", {"app": "api"}, node="node-001"))
    before = rows_walked()
    assert cache.spread_counts.rows([web, api, exists], 4, slot_of).tolist() == [
        [0, 0, 1, 0], [0, 1, 0, 0], [0, 1, 1, 0]]
    after = rows_walked()
    assert after["walk"] - before["walk"] == 2 and after["kept"] - before["kept"] == 1
    cache.remove_pod("default/w1")  # and they follow the cache again
    assert cache.spread_counts.counts(*web) == {}
    assert cache.spread_counts.counts(*exists) == {"node-001": 1}


# -- (iv) nothing tracked, nothing paid ----------------------------------------


def test_with_nothing_tracked_an_update_touches_nothing(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("an update reached the index with nothing tracked")

    monkeypatch.setattr(spread_counts, "_count", boom)
    monkeypatch.setattr(spread_counts.SelectorDispatch, "matching", boom)
    clock = FakeClock()
    cache = SchedulerCache(clock, assume_ttl=TTL)
    a, b = cluster_node(0), cluster_node(1)
    cache.add_node(a)
    cache.add_node(b)
    cache.assume_pod(labelled_pod("p0", {"app": "web"}), a.name)
    cache.add_pod(labelled_pod("p0", {"app": "api"}, node=a.name))  # confirm
    cache.assume_pod(labelled_pod("p1", {"app": "web"}), a.name)
    cache.forget_pod("default/p1")
    cache.assume_pod(labelled_pod("p2", {"app": "web"}), b.name)
    cache.finish_binding("default/p2")
    clock.advance(TTL + 1)
    assert cache.cleanup_expired() == ["default/p2"]
    cache.update_pod(labelled_pod("p0", {"app": "db"}, node=b.name))
    cache.remove_node(b.name)
    cache.add_node(b)
    cache.remove_pod("default/p0")
    assert len(cache.spread_counts) == 0
    # a batch with no selector (nil only) tracks nothing either
    assert not cache.spread_counts.rows([("default", None)], 4, {}).any()
    assert len(cache.spread_counts) == 0


# -- (v) the counter, through the scheduler -------------------------------------

LABEL = "pod-template-hash"


def mk_sched(cfg):
    cs = ClusterState()
    for d in gen.make_nodes(cfg):
        cs.create_node(Node.from_dict(d))
    sched = Scheduler(cs, SchedulerConfig(
        batch_size=32, pipeline_split=2,
        solver=ExactSolverConfig(tie_break="first", group_size=8),
    ))
    return cs, sched


def offer(cs, cfg, tag, labels, n=32):
    pods = [
        Pod.from_dict(gen.pod_manifest(cfg, gen.PodSpec(
            f"{tag}-{i:03d}", "spread", labels[i % len(labels)], LABEL)))
        for i in range(n)
    ]
    for p in pods:
        cs.create_pod(p)
    return pods


def test_counter_kept_for_a_second_batch_and_walk_for_k_new_labels_of_n():
    cfg = rollout_cfg(nodes=24)
    cs, sched = mk_sched(cfg)
    labels = [f"v{i}" for i in range(4)]

    c0 = rows_walked()
    offer(cs, cfg, "a", labels)
    bound = [k for r in sched.run_pipelined() for k, _ in r.scheduled]
    c1 = rows_walked()
    assert len(bound) == 32
    assert (c1["walk"] - c0["walk"], c1["kept"] - c0["kept"]) == (4, 0)

    offer(cs, cfg, "b", labels)  # the same labels: 100 % kept
    sched.run_pipelined()
    c2 = rows_walked()
    assert (c2["walk"] - c1["walk"], c2["kept"] - c1["kept"]) == (0, 4)

    offer(cs, cfg, "c", labels[:3] + ["v7", "v8"])  # k = 2 new of n = 5
    sched.run_pipelined()
    c3 = rows_walked()
    assert (c3["walk"] - c2["walk"], c3["kept"] - c2["kept"]) == (2, 3)
    assert metrics.spread_tracked_selectors._value.get() == 6
    assert len(sched.cache.spread_counts) == 6

    # assume, finish_binding and the confirming watch events all passed
    # through the kept counts: they equal a walk over the cache
    total = 0
    for v in labels + ["v7", "v8"]:
        kept = sched.cache.spread_counts.counts("default", ML({LABEL: v}))
        assert kept == walk(sched.cache, "default", ML({LABEL: v}))
        total += sum(kept.values())
    assert total == 96


def test_only_the_caches_index_tallies_and_nil_selectors_are_no_rows():
    nodes, placed = mixed_cluster()
    web = ML({"app": "web"})
    pods = [spread_pod("w", {"app": "web"}, [
        TopologySpreadConstraint(1, ZONE, "DoNotSchedule", web),
        TopologySpreadConstraint(1, HOSTNAME, "ScheduleAnyway", web),
    ])]
    c0 = rows_walked()
    want, no_cache, with_cache, _ = tensors_three_ways(nodes, pods, placed)
    c1 = rows_walked()
    # two instances of one selector, built twice: the index built on the
    # spot has no hit rate and tallies nothing, the fresh cache's counts
    # both rows as walked (one pass)
    assert (c1["walk"] - c0["walk"], c1["kept"] - c0["kept"]) == (2, 0)
    assert_same_tensors(no_cache, want)
    assert_same_tensors(with_cache, want)

    cache = SchedulerCache()
    cache.add_node(nodes[0])
    wanted = [("default", web), ("default", None), ("default", web)]
    cache.spread_counts.rows(wanted, 4, {})
    c2 = rows_walked()
    assert (c2["walk"] - c1["walk"], c2["kept"] - c1["kept"]) == (2, 0)
    cache.spread_counts.rows(wanted, 4, {})
    c3 = rows_walked()
    assert (c3["walk"] - c2["walk"], c3["kept"] - c2["kept"]) == (0, 2)


@pytest.mark.parametrize("source", ["counts alone", "slot_of alone", "both and lists"])
def test_placed_pods_come_from_one_source(source):
    nodes, placed = mixed_cluster()
    web = ML({"app": "web"})
    pods = [spread_pod("w", {"app": "web"}, [
        TopologySpreadConstraint(1, ZONE, "DoNotSchedule", web)])]
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, {}, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    slot_of = {n.name: i for i, n in enumerate(nodes)}
    counts = SchedulerCache().spread_counts
    lists, kw = {
        "counts alone": ({}, dict(counts=counts)),
        "slot_of alone": ({}, dict(slot_of=slot_of)),
        "both and lists": (
            {0: next(iter(placed.values()))}, dict(counts=counts, slot_of=slot_of)),
    }[source]
    with pytest.raises(ValueError, match="placed pods come from"):
        build_spread_tensors(
            pods, static.reps, pbatch, slot_nodes, lists, nbatch.padded,
            static.c_pad, **kw,
        )


def test_a_spread_row_and_an_inter_pod_row_share_counts_and_tally_apart():
    """One batch in which a spread constraint and a required anti term ask
    about the same (namespace, selector): the counts are one entry of the
    index, counted by one pass, and each family tallies its own row."""
    from kubernetes_tpu.tensorize.interpod import build_interpod_tensors

    nodes, placed = mixed_cluster()
    web = ML({"app": "web"})
    pod = spread_pod("w", {"app": "web"}, [
        TopologySpreadConstraint(1, ZONE, "DoNotSchedule", web)])
    pod = dataclasses.replace(pod, affinity=MakePod().pod_anti_affinity(
        HOSTNAME, {"app": "web"}).obj().affinity)
    pods = [pod]
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, {}, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    slot_of = {n.name: i for i, n in enumerate(nodes)}
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    for name, ps in placed.items():
        for p in ps:
            cache.add_pod(dataclasses.replace(p, node_name=name))

    def interpod_rows():
        return {
            s: metrics.interpod_count_rows_total.labels(s)._value.get()
            for s in ("kept", "walk")
        }

    s0, i0 = rows_walked(), interpod_rows()
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad,
        counts=cache.spread_counts, slot_of=slot_of,
    )
    interpod = build_interpod_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad,
        counts=cache.spread_counts, owners=cache.interpod_owners, slot_of=slot_of,
    )
    s1, i1 = rows_walked(), interpod_rows()
    # the spread row counted the selector; the inter-pod row found it kept
    assert (s1["walk"] - s0["walk"], s1["kept"] - s0["kept"]) == (1, 0)
    assert (i1["walk"] - i0["walk"], i1["kept"] - i0["kept"]) == (0, 1)
    assert len(cache.spread_counts) == 1
    np.testing.assert_array_equal(spread.cnt0[0], interpod.in_cnt0[0])
    want = walk(cache, "default", web)
    assert interpod.in_cnt0[0].sum() == sum(want.values()) > 0
