"""The static plugin rows are evaluated once per static identity.

``build_static_tensors`` keys its classes on the static spec (node name,
node selector, node affinity, tolerations, images, PVCs) AND on what the
family tensorizers read (a spread or inter-pod pod's labels). Classes that
differ only in the family part get equal static rows: the first class of a
static identity is evaluated node by node and the rest copy its rows.
These tests hold the tables to a per-class evaluation written here from
the oracle, the class axis to the one the unshared build gives, and count
the rows on ``scheduler_tpu_static_rows_total``.
"""

import numpy as np
import pytest

from benchmarks.lib import gen
from kubernetes_tpu import metrics
from kubernetes_tpu import scheduler as scheduler_mod
from kubernetes_tpu.api.objects import (
    NodeAffinity,
    PersistentVolume,
    PersistentVolumeClaim,
    Pod,
)
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.ops.oracle import plugins as opl
from kubernetes_tpu.ops.oracle import volumes as ovol
from kubernetes_tpu.tensorize import plugins
from kubernetes_tpu.tensorize.plugins import CLASS_PAD, build_static_tensors
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    build_node_batch,
    build_pod_batch,
    bucket_pow2,
)

from test_uniq_host_rollouts import anti, drive, mk_sched, nodes_of, pods_of, small

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
HASH = "pod-template-hash"
GB = 1024**3


def cluster(images=False):
    """24 nodes in three zones: some tainted NoSchedule or PreferNoSchedule,
    one cordoned, half on ssd; with ``images``, some report images."""
    nodes = []
    for i in range(24):
        b = (
            MakeNode().name(f"n{i:02d}")
            .capacity({"cpu": "8", "memory": "32Gi", "pods": "110"})
            .label(ZONE, f"z{i % 3}")
            .label(HOST, f"n{i:02d}")
            .label("disk", "ssd" if i % 2 else "hdd")
        )
        if i % 5 == 0:
            b = b.taint("dedicated", "gpu", "NoSchedule")
        if i % 4 == 1:
            b = b.taint("spot", "", "PreferNoSchedule")
        if i == 7:
            b = b.unschedulable()
        if images and i % 3 != 2:
            b = b.image("img-a:1", 300 * 2**20)
            if i % 2:
                b = b.image("img-b:2", 900 * 2**20)
        nodes.append(b.obj())
    return nodes


def volumes():
    """Two claims: one bound to a volume in zone z1, one to a volume in z2."""
    pvs = [
        PersistentVolume(
            name=f"pv{z}", labels={ZONE: f"z{z}"}, capacity_bytes=10 * GB,
            claim_ref=f"default/c{z}", access_modes=("ReadWriteOnce",),
        )
        for z in (1, 2)
    ]
    pvcs = [
        PersistentVolumeClaim(
            name=f"c{z}", namespace="default", volume_name=f"pv{z}",
            request_bytes=5 * GB,
        )
        for z in (1, 2)
    ]
    return ovol.VolumeContext.build(pvs, pvcs, {})


def rollout_pod(i, h, family):
    """A replica of revision ``h``: spread over zones or one a host, its
    selector naming its own revision."""
    b = MakePod().name(f"p{i:03d}").label("app", "web").label(HASH, f"h{h}")
    b = b.req({"cpu": "100m", "memory": "100Mi"})
    if family == "spread":
        return b.spread_constraint(1, ZONE, match_labels={HASH: f"h{h}"})
    return b.pod_anti_affinity(HOST, {HASH: f"h{h}"})


# per variant, what it adds to a pod (None: nothing), so that pods of one
# variant share a static identity and pods of two variants do not
VARIANTS = {
    "tolerations": [
        None,
        lambda b: b.toleration("dedicated", "gpu", "Equal", "NoSchedule"),
        lambda b: b.toleration("spot", "", "Exists", "PreferNoSchedule"),
    ],
    "node_selector": [None, lambda b: b.node_selector({"disk": "ssd"})],
    "preferred_affinity": [
        None,
        lambda b: b.preferred_node_affinity(5, ZONE, ["z1"]),
        lambda b: b.preferred_node_affinity(7, "disk", ["ssd"])
        .node_affinity_in(ZONE, ["z0", "z2"]),
    ],
    "node_name": [None, lambda b: b.node("n04"), lambda b: b.node("n07")],
    "pvcs": [None, lambda b: b.pvc("c1"), lambda b: b.pvc("c2")],
    "images": [
        None,
        # as many containers as each other: only the images tell them apart
        lambda b: b.container_image("img-a:1"),
        lambda b: b.container_image("img-b:2"),
    ],
}


def mixed_batch(case, labels=5):
    """Two pods of every (revision, variant, family): revisions split the
    classes, variants split the static identities."""
    pods = []
    for add in VARIANTS[case]:
        for family in ("spread", "anti"):
            for h in range(labels):
                for _ in range(2):
                    b = rollout_pod(len(pods), h, family)
                    pods.append((add(b) if add else b).obj())
    return pods


def benchmark_batch(config, labels=13, n=104):
    """Pods of a rollouts configuration's measured kind over ``labels``
    revisions, with the configuration's nodes cut to 64."""
    cfg = small(config, nodes=64)
    kind = next(iter(cfg["stream"]["kinds"]))
    specs = [gen.PodSpec(f"r{i:03d}", kind, f"rev-{i % labels}", HASH) for i in range(n)]
    return nodes_of(cfg), pods_of(cfg, specs)


def tables(nodes, pods, **kw):
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, {}, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab, pad=bucket_pow2(len(pods), floor=8))
    # a free slot inside the node axis as well as the padding after it
    slot_nodes = list(nodes[:5]) + [None] + list(nodes[5:])
    slot_nodes += [None] * (nbatch.padded - len(slot_nodes))
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded, **kw)
    return static, slot_nodes, pbatch, nbatch.padded


def per_class(static, slot_nodes, padded_n, volume_ctx=None, added_affinity=None):
    """Every class's rows evaluated from its own representative, node by
    node, with the oracle's functions."""
    c_pad = static.c_pad
    want = {
        "mask": np.zeros((c_pad, padded_n), bool),
        "taint_cnt": np.zeros((c_pad, padded_n), np.int32),
        "nodeaff_pref": np.zeros((c_pad, padded_n), np.int32),
        "image_score": np.zeros((c_pad, padded_n), np.int32),
    }
    live = [n for n in slot_nodes if n is not None]
    states = opl.build_image_states(live)
    for c, rep in enumerate(static.reps):
        for j, node in enumerate(slot_nodes):
            if node is None:
                continue
            ok = (
                opl.node_name_filter(rep, node)
                and opl.node_unschedulable_filter(rep, node)
                and opl.taint_toleration_filter(rep, node)
                and opl.node_affinity_filter(rep, node)
                and opl.added_affinity_filter(added_affinity, node)
                and (
                    volume_ctx is None
                    or not rep.pvc_names
                    or ovol.volume_filter(rep, node, volume_ctx)
                )
            )
            want["mask"][c, j] = ok
            if not ok:
                continue
            want["taint_cnt"][c, j] = opl.taint_toleration_score(rep, node)
            want["nodeaff_pref"][c, j] = opl.node_affinity_score(
                rep, node
            ) + opl.added_affinity_score(added_affinity, node)
            if states:
                want["image_score"][c, j] = opl.image_locality_score(
                    rep, node, states, len(live)
                )
    return want


ADDED = NodeAffinity.from_dict(
    {
        "requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [
                {"matchExpressions": [{"key": ZONE, "operator": "In", "values": ["z0", "z1"]}]}
            ]
        },
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {
                "weight": 3,
                "preference": {
                    "matchExpressions": [{"key": "disk", "operator": "In", "values": ["ssd"]}]
                },
            }
        ],
    }
)


def tier(pod):
    return pod.labels.get("tier")


def case_inputs(case):
    """(nodes, pods, build keywords, static identities) of one case."""
    if case in ("sched-perf-uniq-host-rollouts-5000n", "sched-perf-spread-rollouts-5000n"):
        nodes, pods = benchmark_batch(case)
        return nodes, pods, {}, 1
    if case == "added_affinity":
        return cluster(), mixed_batch("tolerations"), {"added_affinity": ADDED}, 3
    if case == "class_key_extra":
        pods = mixed_batch("node_selector")
        for i, p in enumerate(pods):
            p.labels = {**p.labels, "tier": f"t{i % 3}"}
        return cluster(), pods, {"class_key_extra": tier}, 2
    kw = {"volume_ctx": volumes()} if case == "pvcs" else {}
    return cluster(images=case == "images"), mixed_batch(case), kw, len(VARIANTS[case])


CASES = [
    "sched-perf-uniq-host-rollouts-5000n",
    "sched-perf-spread-rollouts-5000n",
    *VARIANTS,
    "added_affinity",
    "class_key_extra",
]


# -- (a) the tables against a per-class evaluation ---------------------------


@pytest.mark.parametrize("case", CASES)
def test_tables_equal_a_per_class_evaluation(case):
    nodes, pods, kw, identities = case_inputs(case)
    static, slot_nodes, _, padded_n = tables(nodes, pods, **kw)
    assert static.num_classes > identities  # rows were shared
    assert static.rows_evaluated == identities
    want = per_class(
        static, slot_nodes, padded_n,
        volume_ctx=kw.get("volume_ctx"), added_affinity=kw.get("added_affinity"),
    )
    for name, arr in want.items():
        got = getattr(static, name)
        assert got.dtype == arr.dtype and got.shape == arr.shape, name
        np.testing.assert_array_equal(got, arr, err_msg=name)
    if identities > 1:  # the case's difference shows in the rows
        rows = {
            tuple(getattr(static, name)[c].tobytes() for name in want)
            for c in range(static.num_classes)
        }
        assert len(rows) > 1


# -- (b) a copied row is the class's own ------------------------------------


@pytest.mark.parametrize("case", ["sched-perf-uniq-host-rollouts-5000n", "tolerations"])
def test_writing_one_class_row_leaves_every_other_row(case):
    nodes, pods, kw, _ = case_inputs(case)
    static, *_ = tables(nodes, pods, **kw)
    names = ("mask", "taint_cnt", "nodeaff_pref", "image_score")
    # the first class evaluated its rows, the last copied them
    for c in (0, static.num_classes - 1):
        before = {n: getattr(static, n).copy() for n in names}
        static.mask[c] = ~static.mask[c]
        static.taint_cnt[c] += 1
        static.nodeaff_pref[c] += 2
        static.image_score[c] += 3
        for n in names:
            changed = np.any(getattr(static, n) != before[n], axis=1)
            assert list(np.flatnonzero(changed)) == [c], n
            getattr(static, n)[:] = before[n]


# -- (c) the class axis is the unshared build's ------------------------------


def flat_key(pod, with_images):
    """The class key as one tuple, as it was before the split."""
    na = pod.affinity.node_affinity if pod.affinity else None
    labels = tuple(sorted(pod.labels.items()))
    spread = (
        (pod.topology_spread_constraints, pod.namespace, labels)
        if pod.topology_spread_constraints else ()
    )
    pa = pod.affinity.pod_affinity if pod.affinity else None
    paa = pod.affinity.pod_anti_affinity if pod.affinity else None
    interpod = (pa, paa, pod.namespace, labels) if (pa or paa) else ()
    return (
        pod.node_name, tuple(sorted(pod.node_selector.items())), na, pod.tolerations,
        tuple(tuple(c.images) for c in pod.containers) if with_images else (),
        len(pod.containers) if with_images else 0,
        spread, interpod,
        (pod.namespace, pod.pvc_names) if pod.pvc_names else (),
    )


@pytest.mark.parametrize("case", CASES)
def test_class_axis_is_the_unshared_builds(case, monkeypatch):
    nodes, pods, kw, _ = case_inputs(case)
    shared, *_ = tables(nodes, pods, **kw)

    # the partition the flat key gives, in order of first appearance
    extra = kw.get("class_key_extra")
    with_images = case == "images"
    index, reps, class_of = {}, [], []
    for p in pods:
        key = flat_key(p, with_images)
        if extra is not None:
            key = (key, extra(p))
        class_of.append(index.setdefault(key, len(index)))
        if len(reps) < len(index):
            reps.append(p)
    assert list(shared.class_of[: len(pods)]) == class_of
    assert not shared.class_of[len(pods):].any()
    assert [r is q for r, q in zip(shared.reps, reps)] == [True] * len(reps)
    assert shared.num_classes == len(reps)
    assert shared.c_pad == bucket_pow2(len(reps), floor=CLASS_PAD)

    # a build in which no two classes share a static identity evaluates
    # every row and gives the same tables
    real = plugins._static_key
    monkeypatch.setattr(
        plugins, "_static_key",
        lambda pod, with_images: (real(pod, with_images), plugins._family_key(pod)),
    )
    unshared, *_ = tables(nodes, pods, **kw)
    assert unshared.rows_evaluated == unshared.num_classes == shared.num_classes
    assert [r is q for r, q in zip(unshared.reps, shared.reps)] == [True] * len(reps)
    for name, arr in unshared.device_arrays().items():
        np.testing.assert_array_equal(getattr(shared, name), arr, err_msg=name)
        assert getattr(shared, name).dtype == arr.dtype


# -- (d) the counter ---------------------------------------------------------


def rows():
    return tuple(
        metrics.static_rows_total.labels(s)._value.get() for s in ("evaluated", "shared")
    )


@pytest.mark.parametrize(
    "n_plain, labels",
    [(0, 1), (0, 6), (4, 1), (5, 9)],
)
def test_counter_shares_classes_minus_identities(monkeypatch, n_plain, labels):
    """One batch of ``labels`` revisions of the uniq-host kind, with
    ``n_plain`` plain pods: every class has one static identity, so one
    row is evaluated and the other classes' rows are shared; a one-class
    batch shares none."""
    cfg = small(nodes=32)
    cs, sched = mk_sched(cfg, split=1)
    built = []

    def spy(*a, **kw):
        built.append(build_static_tensors(*a, **kw))
        return built[-1]

    monkeypatch.setattr(scheduler_mod, "build_static_tensors", spy)
    specs = [gen.PodSpec(f"plain-{i}", "plain", "plain", HASH) for i in range(n_plain)]
    specs += [anti(f"new-{i:03d}", f"anti-{i % labels}") for i in range(3 * labels)]
    r0 = rows()
    assert len(drive(cs, sched, pods_of(cfg, specs))) == len(specs)
    r1 = rows()
    assert len(built) == 1
    classes = labels + (1 if n_plain else 0)
    assert built[0].num_classes == classes
    assert (r1[0] - r0[0], r1[1] - r0[1]) == (1, classes - 1)


def test_counter_evaluates_each_identity_of_a_batch(monkeypatch):
    """Revisions that tolerate a taint form a second identity: two rows
    evaluated, the rest shared."""
    cfg = small(nodes=32)
    cs, sched = mk_sched(cfg, split=1)
    pods = []
    for i in range(24):
        d = gen.pod_manifest(cfg, anti(f"t-{i:03d}", f"anti-{i % 6}"))
        if i % 6 >= 4:
            d["spec"]["tolerations"] = [{"key": "spot", "operator": "Exists"}]
        pods.append(Pod.from_dict(d))
    r0 = rows()
    assert len(drive(cs, sched, pods)) == len(pods)
    r1 = rows()
    assert (r1[0] - r0[0], r1[1] - r0[1]) == (2, 4)
