"""Inter-pod counts kept in the scheduler cache, and an existing-term axis
that follows the batch.

``build_interpod_tensors`` used to count ``in_cnt0`` and ``ex_cnt0`` by
walking every placed pod once for the owner terms and once per incoming
term, and carried one existing term for every selector ever placed. It now
reads the per-selector node counts (``SchedulerCache.spread_counts``) and
the term owners (``SchedulerCache.interpod_owners``) the cache keeps, and
carries only the existing terms that select a pod of the batch. These tests
hold the build from the cache to the build from pod lists (the same
indexes made on the spot) over seeded random clusters that change between
batches, both to the walk it replaced (which lives on here as the
reference) on the axis the batch keeps, and a scheduler on either to the
same bindings.
"""

import dataclasses
import random

import numpy as np
import pytest

from benchmarks.lib import files, gen
from kubernetes_tpu import scheduler as scheduler_mod
from kubernetes_tpu.api.labels import (
    EXISTS,
    IN,
    NOT_IN,
    Requirement,
    Selector,
    selector_from_match_labels,
)
from kubernetes_tpu.api.objects import (
    Affinity,
    Node,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.ops.oracle import interpod as oip
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolverConfig
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.state.interpod_owners import (
    K_PREF_AFF,
    K_PREF_ANTI,
    K_REQ_AFF,
    K_REQ_ANTI,
    owned_terms,
)
from kubernetes_tpu.tensorize.interpod import (
    DOM_PAD,
    INST_PAD,
    InterpodTensors,
    build_interpod_tensors,
    trivial_interpod_tensors,
)
from kubernetes_tpu.tensorize.plugins import build_static_tensors
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    bucket_pow2,
    build_node_batch,
    build_pod_batch,
)

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
HASH = "pod-template-hash"


# -- the reference: build_interpod_tensors as it stood before this change -----


def reference_interpod_tensors(
    pods, class_reps, pbatch, slot_nodes, placed_by_slot, padded_n, c_pad,
    hard_pod_affinity_weight=1, nominated=(),
):
    """The parent commit's implementation: one pass over every placed pod
    for the owner terms and one per incoming term, and an existing-term
    axis with every term any placed, nominated or batch pod owns. Returns
    the tensors and the axis' term keys."""
    in_terms = []
    per_class = []
    for c, rep in enumerate(class_reps):
        aff_ids, anti_ids, pref_ids = [], [], []
        for t in oip._required_aff_terms(rep):
            aff_ids.append(len(in_terms))
            in_terms.append((c, t, K_REQ_AFF, 0))
        for t in oip._required_anti_terms(rep):
            anti_ids.append(len(in_terms))
            in_terms.append((c, t, K_REQ_ANTI, 0))
        for wt in oip._preferred_terms(rep, anti=False):
            pref_ids.append(len(in_terms))
            in_terms.append((c, wt.term, K_PREF_AFF, wt.weight))
        for wt in oip._preferred_terms(rep, anti=True):
            pref_ids.append(len(in_terms))
            in_terms.append((c, wt.term, K_PREF_ANTI, -wt.weight))
        per_class.append((aff_ids, anti_ids, pref_ids))

    ex_index = {}
    ex_terms = []

    def ex_intern(kind, term, w, owner_ns):
        key = (kind, term, w, owner_ns)
        i = ex_index.get(key)
        if i is None:
            i = len(ex_terms)
            ex_index[key] = i
            ex_terms.append(key)
        return i

    placed_pods = [(slot, p) for slot, ps in placed_by_slot.items() for p in ps]
    placed_pods += [(n_i, p) for p, n_i in nominated if 0 <= n_i < padded_n]
    owner_map_placed = []
    for slot, p in placed_pods:
        for kind, t, w in owned_terms(p):
            owner_map_placed.append((slot, ex_intern(kind, t, w, p.namespace)))
    owner_map_batch = []
    for p_i, p in enumerate(pods):
        for kind, t, w in owned_terms(p):
            owner_map_batch.append((p_i, ex_intern(kind, t, w, p.namespace)))

    if not in_terms and not ex_terms:
        return trivial_interpod_tensors(pbatch, padded_n, c_pad), []

    ti_pad = bucket_pow2(max(len(in_terms), 1), floor=INST_PAD)
    te_pad = bucket_pow2(max(len(ex_terms), 1), floor=INST_PAD)
    all_keys = {t.topology_key for _, t, _, _ in in_terms} | {
        t.topology_key for _, t, _, _ in ex_terms
    }
    key_vocab = {k: {} for k in all_keys}
    for node in slot_nodes:
        if node is None:
            continue
        for key in all_keys:
            v = node.labels.get(key)
            if v is not None:
                key_vocab[key].setdefault(v, len(key_vocab[key]))
    d_pad = bucket_pow2(
        max((len(v) for v in key_vocab.values()), default=1), floor=DOM_PAD
    )

    def dom_for(key):
        row = np.full(padded_n, -1, dtype=np.int32)
        for n_i, node in enumerate(slot_nodes):
            if node is None or n_i >= padded_n:
                continue
            v = node.labels.get(key)
            if v is not None:
                row[n_i] = key_vocab[key][v]
        return row

    in_dom = np.full((ti_pad, padded_n), -1, dtype=np.int32)
    in_cnt0 = np.zeros((ti_pad, padded_n), dtype=np.int32)
    in_pref_w = np.zeros(ti_pad, dtype=np.int32)
    in_match = np.zeros((pbatch.padded, ti_pad), dtype=np.int32)
    sa = max(max((len(a) for a, _, _ in per_class), default=0), 1)
    sb = max(max((len(b) for _, b, _ in per_class), default=0), 1)
    sp = max(max((len(p) for _, _, p in per_class), default=0), 1)
    cls_req_aff = np.full((c_pad, sa), -1, dtype=np.int32)
    cls_req_anti = np.full((c_pad, sb), -1, dtype=np.int32)
    cls_pref = np.full((c_pad, sp), -1, dtype=np.int32)
    for c, (aff_ids, anti_ids, pref_ids) in enumerate(per_class):
        cls_req_aff[c, : len(aff_ids)] = aff_ids
        cls_req_anti[c, : len(anti_ids)] = anti_ids
        cls_pref[c, : len(pref_ids)] = pref_ids
    for t_i, (c, term, kind, w) in enumerate(in_terms):
        rep = class_reps[c]
        in_dom[t_i] = dom_for(term.topology_key)
        in_pref_w[t_i] = w
        for slot, q in placed_pods:
            if slot < padded_n and oip.term_matches_pod(term, rep, q):
                in_cnt0[t_i, slot] += 1
        for p_i, q in enumerate(pods):
            if oip.term_matches_pod(term, rep, q):
                in_match[p_i, t_i] = 1

    ex_dom = np.full((te_pad, padded_n), -1, dtype=np.int32)
    ex_cnt0 = np.zeros((te_pad, padded_n), dtype=np.int32)
    ex_anti = np.zeros(te_pad, dtype=bool)
    ex_owned = np.zeros((pbatch.padded, te_pad), dtype=np.int32)
    m_anti = np.zeros((pbatch.padded, te_pad), dtype=bool)
    m_w = np.zeros((pbatch.padded, te_pad), dtype=np.int32)
    for e_i, (kind, term, w, owner_ns) in enumerate(ex_terms):
        ex_dom[e_i] = dom_for(term.topology_key)
        ex_anti[e_i] = kind == K_REQ_ANTI
        score_w = w if kind in (K_PREF_AFF, K_PREF_ANTI) else (
            hard_pod_affinity_weight if kind == K_REQ_AFF else 0
        )
        for p_i, p in enumerate(pods):
            if not term.matches_namespace(owner_ns, p.namespace):
                continue
            if term.label_selector is not None and term.label_selector.matches(
                p.labels
            ):
                if kind == K_REQ_ANTI:
                    m_anti[p_i, e_i] = True
                elif score_w:
                    m_w[p_i, e_i] = score_w
    for slot, e_i in owner_map_placed:
        if slot < padded_n:
            ex_cnt0[e_i, slot] += 1
    for p_i, e_i in owner_map_batch:
        ex_owned[p_i, e_i] += 1

    self_aff = np.zeros(pbatch.padded, dtype=bool)
    for p_i, p in enumerate(pods):
        terms = oip._required_aff_terms(p)
        self_aff[p_i] = bool(terms) and all(
            oip.term_matches_pod(t, p, p) for t in terms
        )
    return InterpodTensors(
        num_in=len(in_terms), num_ex=len(ex_terms), d_pad=d_pad,
        in_dom=in_dom, in_cnt0=in_cnt0, in_pref_w=in_pref_w,
        cls_req_aff=cls_req_aff, cls_req_anti=cls_req_anti, cls_pref=cls_pref,
        ex_dom=ex_dom, ex_cnt0=ex_cnt0, ex_anti=ex_anti, in_match=in_match,
        ex_owned=ex_owned, m_anti=m_anti, m_w=m_w, self_aff=self_aff,
    ), ex_terms


IN_FIELDS = (
    "num_in", "in_dom", "in_cnt0", "in_pref_w", "cls_req_aff",
    "cls_req_anti", "cls_pref", "in_match", "self_aff",
)


def assert_same_tensors(got, want):
    for f in dataclasses.fields(InterpodTensors):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, f.name
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, f.name


def ex_columns(t, cols):
    """One hashable tuple per existing-term column: every table's slice
    of that term."""
    return sorted(
        (
            t.ex_dom[e].tobytes(), t.ex_cnt0[e].tobytes(), bool(t.ex_anti[e]),
            t.ex_owned[:, e].tobytes(), t.m_anti[:, e].tobytes(),
            t.m_w[:, e].tobytes(),
        )
        for e in cols
    )


def assert_reference_on_the_kept_axis(got, want, want_keys):
    """Incoming tables equal; the existing axis is the reference's terms
    that block or score a batch pod, column for column, padded to the
    power of two of their number."""
    for name in IN_FIELDS:
        a, b = getattr(got, name), getattr(want, name)
        if isinstance(b, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            assert a == b, name
    # the domain axis covers the topology keys of the terms carried: the
    # dropped terms' keys may leave it
    assert got.d_pad <= want.d_pad
    acting = [
        e for e in range(len(want_keys))
        if want.m_anti[:, e].any() or want.m_w[:, e].any()
    ]
    dropped = [e for e in range(len(want_keys)) if e not in acting]
    assert got.num_ex == len(acting)
    assert got.ex_cnt0.shape[0] == bucket_pow2(max(len(acting), 1), floor=INST_PAD)
    assert ex_columns(got, range(got.num_ex)) == ex_columns(want, acting)
    # past the axis is padding
    assert (got.ex_dom[got.num_ex:] == -1).all()
    for name in ("ex_cnt0", "ex_anti"):
        assert not getattr(got, name)[got.num_ex:].any(), name
    for name in ("ex_owned", "m_anti", "m_w"):
        assert not getattr(got, name)[:, got.num_ex:].any(), name
    # and what was dropped selects no pod of the batch
    assert not want.m_anti[:, dropped].any() and not want.m_w[:, dropped].any()


# -- a seeded random cluster that changes between batches ----------------------

NAMESPACES = ("default", "prod")
APPS = ("web", "api", "db")
TIERS = ("front", "back")
HASHES = ("h0", "h1", "h2", "h3")


class World:
    def __init__(self, seed, n_nodes=12):
        self.rng = random.Random(seed)
        self.nodes = []
        for i in range(n_nodes):
            b = (
                MakeNode().name(f"node-{i:03d}")
                .capacity({"cpu": "64", "memory": "256Gi", "pods": "500"})
                .label(HOSTNAME, f"node-{i:03d}")
            )
            if i != n_nodes - 1:  # the last node lacks the zone key
                b = b.label(ZONE, f"z{i % 3}")
            self.nodes.append(b.obj())
        self.cache = SchedulerCache()
        for n in self.nodes[:-2]:
            self.cache.add_node(n)
        self.absent = list(self.nodes[-2:])  # come and go
        self.serial = 0

    def selector(self):
        rng = self.rng
        return rng.choice([
            lambda: selector_from_match_labels({"app": rng.choice(APPS)}),
            lambda: selector_from_match_labels(
                {"app": rng.choice(APPS), "tier": rng.choice(TIERS)}),
            lambda: Selector((Requirement("app", IN, ("web", "api")),)),
            lambda: Selector((Requirement("tier", EXISTS),)),
            lambda: Selector((Requirement("app", NOT_IN, ("db",)),)),
            lambda: Selector(()),
            lambda: None,
        ])()

    def term(self):
        rng = self.rng
        namespaces, ns_sel = (), None
        roll = rng.random()
        if roll < 0.15:
            namespaces = ("default", "prod")
        elif roll < 0.25:
            namespaces = ("prod", "prod")
        elif roll < 0.32:
            ns_sel = Selector(())
        elif roll < 0.36:
            ns_sel = selector_from_match_labels({"team": "a"})
        return PodAffinityTerm(
            label_selector=self.selector(),
            topology_key=rng.choice((ZONE, HOSTNAME, HOSTNAME)),
            namespaces=namespaces,
            namespace_selector=ns_sel,
            match_label_keys=(HASH,) if rng.random() < 0.4 else (),
        )

    def affinity(self):
        rng = self.rng
        if rng.random() < 0.35:
            return None

        def some(weighted):
            out = []
            for _ in range(rng.choice((0, 0, 1, 2))):
                t = self.term()
                out.append(WeightedPodAffinityTerm(rng.choice((0, 1, 5)), t) if weighted else t)
            return tuple(out)

        aff = PodAffinity(required=some(False), preferred=some(True))
        anti = PodAffinity(required=some(False), preferred=some(True))
        return Affinity(
            pod_affinity=aff if aff.required or aff.preferred else None,
            pod_anti_affinity=anti if anti.required or anti.preferred else None,
        )

    def pod(self, node=None, prefix="p"):
        rng = self.rng
        self.serial += 1
        labels = {"app": rng.choice(APPS), HASH: rng.choice(HASHES)}
        if rng.random() < 0.6:
            labels["tier"] = rng.choice(TIERS)
        b = (
            MakePod().name(f"{prefix}{self.serial}")
            .namespace(rng.choice(NAMESPACES)).labels(labels).req({"cpu": "10m"})
        )
        if node is not None:
            b = b.node(node)
        p = b.obj()
        p.affinity = self.affinity()
        return p

    def live(self):
        return [n for n, i in self.cache.nodes.items() if i.node is not None]

    def churn(self, steps):
        """Pods deleted, re-added, moved and relabelled; nodes leaving
        with their pods and coming back."""
        rng, cache = self.rng, self.cache
        for _ in range(steps):
            keys = sorted(cache._pod_node)
            op = rng.choice(["add", "add", "delete", "readd", "node"])
            if op == "add" or not keys:
                cache.add_pod(self.pod(node=rng.choice(self.live())))
            elif op == "delete":
                cache.remove_pod(rng.choice(keys))
            elif op == "readd":
                key = rng.choice(keys)
                old = cache.nodes[cache.pod_node(key)].pods[key]
                cache.remove_pod(key)
                cache.add_pod(dataclasses.replace(old, node_name=rng.choice(self.live())))
            elif self.absent and rng.random() < 0.5:
                node = self.absent.pop()
                cache.add_node(node)
            elif len(self.live()) > 6:
                name = rng.choice(self.live())
                self.absent.append(cache.nodes[name].node)
                cache.remove_node(name)

    def batch_inputs(self, n_pods=14, n_nominated=3):
        """Everything one build takes, from the cache as it stands."""
        pods = [self.pod(prefix="b") for _ in range(n_pods)]
        vocab = ResourceVocab.build(pods, self.nodes)
        nbatch = build_node_batch(self.nodes, {}, vocab=vocab)
        pbatch = build_pod_batch(pods, vocab)
        slot_nodes = [
            n if self.cache.nodes.get(n.name) and self.cache.nodes[n.name].node else None
            for n in self.nodes
        ] + [None] * (nbatch.padded - len(self.nodes))
        static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
        slot_of = {n.name: i for i, n in enumerate(self.nodes)}
        placed_by_slot = {
            slot_of[name]: list(info.pods.values())
            for name, info in self.cache.nodes.items()
            if info.node is not None and info.pods
        }
        nominated = [
            (self.pod(prefix="nom"), self.rng.choice((0, 3, 5, 999)))
            for _ in range(n_nominated)
        ]
        return dict(
            pods=pods, reps=static.reps, pbatch=pbatch, slot_nodes=slot_nodes,
            padded_n=nbatch.padded, c_pad=static.c_pad, slot_of=slot_of,
            placed_by_slot=placed_by_slot, nominated=nominated,
        )


def three_builds(w, kw, weight=1):
    args = (kw["pods"], kw["reps"], kw["pbatch"], kw["slot_nodes"])
    rest = (kw["padded_n"], kw["c_pad"])
    common = dict(hard_pod_affinity_weight=weight, nominated=kw["nominated"])
    want, keys = reference_interpod_tensors(*args, kw["placed_by_slot"], *rest, **common)
    walked = build_interpod_tensors(*args, kw["placed_by_slot"], *rest, **common)
    cached = build_interpod_tensors(
        *args, {}, *rest, counts=w.cache.spread_counts,
        owners=w.cache.interpod_owners, slot_of=kw["slot_of"], **common,
    )
    return want, keys, walked, cached


@pytest.mark.parametrize("weight", [1, 0])
@pytest.mark.parametrize("seed", [7, 20261017, 3900000041])
def test_cached_equals_walked_and_the_reference_on_the_kept_axis(seed, weight):
    w = World(seed)
    w.churn(50)
    engaged = dropped = 0
    for _ in range(8):
        kw = w.batch_inputs()
        want, keys, walked, cached = three_builds(w, kw, weight)
        assert_same_tensors(cached, walked)
        assert_reference_on_the_kept_axis(cached, want, keys)
        engaged += cached.num_ex
        dropped += want.num_ex - cached.num_ex
        w.churn(12)
    # the clusters did exercise both sides, and the axis did shrink
    assert engaged > 0 and dropped > 0


def test_every_kind_of_term_is_kept_and_counted():
    """One owner of each kind of term on a node, one batch pod each
    selects: all four are on the axis with their counts, and a term that
    selects no batch pod is not."""
    node = MakeNode().name("n0").label(HOSTNAME, "n0").capacity({"cpu": "8", "pods": "50"}).obj()
    cache = SchedulerCache()
    cache.add_node(node)
    sel = {"app": "web"}

    def owner(name, builder):
        p = builder(MakePod().name(name).label("app", "x").node("n0")).obj()
        cache.add_pod(p)

    owner("ra", lambda b: b.pod_anti_affinity(HOSTNAME, sel))
    owner("pa", lambda b: b.preferred_pod_affinity(3, HOSTNAME, sel))
    owner("pn", lambda b: b.preferred_pod_affinity(2, HOSTNAME, sel, anti=True))
    owner("rf", lambda b: b.pod_affinity(HOSTNAME, sel))
    owner("none", lambda b: b.pod_anti_affinity(HOSTNAME, {"app": "nothing"}))
    assert len(cache.interpod_owners) == 5
    pods = [MakePod().name("in").label("app", "web").obj()]
    vocab = ResourceVocab.build(pods, [node])
    nbatch = build_node_batch([node], {}, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab)
    slot_nodes = [node] + [None] * (nbatch.padded - 1)
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    t = build_interpod_tensors(
        pods, static.reps, pbatch, slot_nodes, {}, nbatch.padded, static.c_pad,
        hard_pod_affinity_weight=4, counts=cache.spread_counts,
        owners=cache.interpod_owners, slot_of={"n0": 0},
    )
    assert t.num_in == 0 and t.num_ex == 4 and t.ex_cnt0.shape[0] == INST_PAD
    assert sorted(t.m_w[0, :4].tolist()) == [-2, 0, 3, 4]
    assert t.m_anti[0, :4].sum() == 1 and t.ex_cnt0[:4, 0].tolist() == [1, 1, 1, 1]
    # the last owner leaves: its term leaves the index with it
    cache.remove_pod("default/none")
    assert len(cache.interpod_owners) == 4


@pytest.mark.parametrize("source", ["counts alone", "owners and slot_of", "all and lists"])
def test_placed_pods_come_from_one_source(source):
    w = World(3)
    w.churn(20)
    kw = w.batch_inputs()
    assert kw["placed_by_slot"]
    cache = w.cache
    lists, extra = {
        "counts alone": ({}, dict(counts=cache.spread_counts)),
        "owners and slot_of": ({}, dict(owners=cache.interpod_owners, slot_of=kw["slot_of"])),
        "all and lists": (kw["placed_by_slot"], dict(
            counts=cache.spread_counts, owners=cache.interpod_owners, slot_of=kw["slot_of"])),
    }[source]
    with pytest.raises(ValueError, match="placed pods come from"):
        build_interpod_tensors(
            kw["pods"], kw["reps"], kw["pbatch"], kw["slot_nodes"], lists,
            kw["padded_n"], kw["c_pad"], **extra,
        )


# -- the same bindings through the scheduler -----------------------------------

CONFIG = "sched-perf-uniq-host-rollouts-5000n"


def small_cfg(nodes=40, replicas=12, in_flight=3, apps=10):
    cfg = files.load_config(CONFIG)
    cfg["nodes"]["count"] = nodes
    st = cfg["stream"]
    st["deploymentReplicas"], st["inFlight"] = replicas, in_flight
    st["kinds"]["anti"]["apps"] = apps
    return cfg


def bindings(cfg, specs, walk_only, monkeypatch):
    cs = ClusterState()
    for d in gen.make_nodes(cfg):
        cs.create_node(Node.from_dict(d))
    sched = Scheduler(cs, SchedulerConfig(
        batch_size=32, pipeline_split=2,
        solver=ExactSolverConfig(tie_break="first", group_size=8),
    ))
    with monkeypatch.context() as m:
        if walk_only:
            def walk(pods, reps, pbatch, slot_nodes, _lists, padded_n, c_pad, **kw):
                return reference_interpod_tensors(
                    pods, reps, pbatch, slot_nodes, sched._placed_by_slot(),
                    padded_n, c_pad,
                    hard_pod_affinity_weight=kw["hard_pod_affinity_weight"],
                    nominated=kw["nominated"],
                )[0]

            m.setattr(scheduler_mod, "build_interpod_tensors", walk)
        out = []
        for i in range(0, len(specs), 40):  # several arrivals, several batches
            for s in specs[i:i + 40]:
                cs.create_pod(Pod.from_dict(gen.pod_manifest(cfg, s)))
            out += [(k, n) for r in sched.run_pipelined() for k, n in r.scheduled]
    return out, sched


@pytest.mark.parametrize("seed", [3900000007, 2**31 + 11])
def test_scheduler_binds_the_same_as_a_walk_only_build(seed, monkeypatch):
    cfg = small_cfg()
    plain = [gen.PodSpec(f"init-{i}", "plain", "init") for i in range(20)]
    specs = plain + gen.RolloutStream(cfg, seed=seed).take(120)
    cached, sched = bindings(cfg, specs, False, monkeypatch)
    walked, _ = bindings(cfg, specs, True, monkeypatch)
    assert len(cached) == len(specs)
    assert cached == walked
    # every rollout that has a pod placed keeps its term in the cache
    assert len(sched.cache.interpod_owners) == len({s.app for s in specs if s.kind == "anti"})
