"""POST /api/pods treats a body as the batch it is (PR 35): one parse
per distinct pod spec (``PodDecoder``), the body applied under one hold
of cluster.lock, and a watcher, GET /api/state and the queue shown
exactly what the per-pod handler showed them."""

import asyncio
import copy
import dataclasses
import json

import pytest

from kubernetes_tpu import metrics
from kubernetes_tpu.api.objects import Pod, PodDecoder
from kubernetes_tpu.api.wrappers import MakeNode
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.server.extender import ExtenderCore, make_app
from kubernetes_tpu.state.cluster import ApiError, ClusterState
from kubernetes_tpu.utils.clock import FakeClock

EXTRAS = {
    "bare": {},
    "nodeName": {"nodeName": "n1"},
    "tolerations": {
        "tolerations": [
            {"key": "dedicated", "operator": "Equal", "value": "batch",
             "effect": "NoSchedule"},
            {"key": "spot", "operator": "Exists"},
        ]
    },
    "volumes": {
        "volumes": [
            {"name": "data", "persistentVolumeClaim": {"claimName": "claim-a"}},
            {"name": "scratch", "emptyDir": {}},
        ]
    },
    "overhead": {"overhead": {"cpu": "250m", "memory": "120Mi"}},
    "nodeSelector": {"nodeSelector": {"disk": "ssd", "pool": "a"}},
    "all": {
        "nodeName": "n2",
        "priority": 7,
        "tolerations": [{"key": "spot", "operator": "Exists"}],
        "volumes": [{"name": "d", "persistentVolumeClaim": {"claimName": "c"}}],
        "overhead": {"cpu": "100m"},
        "nodeSelector": {"disk": "ssd"},
        "initContainers": [
            {"name": "init", "resources": {"requests": {"cpu": "1"}}}
        ],
    },
}


def manifest(kind, name, app="blue", extra=None):
    """One pod as benchmarks/lib/gen.py writes it, plus ``extra`` spec keys."""
    labels = {"color": app}
    selector = {"matchLabels": dict(labels)}
    spec = {
        "containers": [{
            "name": "con0",
            "resources": {"requests": {"cpu": "100m", "memory": "524288000"}},
        }],
    }
    if kind == "spread":
        spec["topologySpreadConstraints"] = [{
            "maxSkew": 5,
            "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": selector,
        }]
    elif kind == "anti":
        spec["affinity"] = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"topologyKey": "kubernetes.io/hostname", "labelSelector": selector}
            ]
        }}
    spec.update(copy.deepcopy(extra or {}))
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": name,
            "namespace": "bench",
            "uid": f"uid-{name}",
            "labels": labels,
            "annotations": {"rollout": name.split("-")[0]},
        },
        "spec": spec,
        "status": {"phase": "Pending"},
    }


def wire(doc):
    """Through JSON, as a body arrives: no object shared between pods."""
    return json.loads(json.dumps(doc))


@pytest.mark.parametrize("extra", list(EXTRAS))
@pytest.mark.parametrize("kind", ["plain", "spread", "anti"])
def test_decoded_pods_equal_from_dict_and_share_no_dict(kind, extra):
    items = wire([manifest(kind, f"a-{i}", extra=EXTRAS[extra]) for i in range(5)])
    dec = PodDecoder()
    pods = [dec.decode(d) for d in items]
    assert (dec.parsed, dec.reused) == (1, 4)
    want = [Pod.from_dict(d) for d in items]
    assert [dataclasses.asdict(p) for p in pods] == [
        dataclasses.asdict(p) for p in want
    ]
    assert pods == want
    assert all(p._resource_request is None for p in pods)
    assert [p.resource_request() for p in pods] == [
        p.resource_request() for p in want
    ]
    # the first was parsed, the second reused: a write to either's dicts,
    # or a bind's write to node_name, reaches no sibling and no later pod
    for victim in (pods[0], pods[1]):
        victim.labels["x"] = "y"
        victim.annotations["x"] = "y"
        victim.node_selector["x"] = "y"
        victim.overhead["x"] = 1
        victim.node_name = "elsewhere"
    later = dec.decode(wire(manifest(kind, "a-9", extra=EXTRAS[extra])))
    for p, w in zip(pods[2:] + [later], want[2:] + [want[2]]):
        assert p.labels == w.labels and p.annotations == w.annotations
        assert p.node_selector == w.node_selector and p.overhead == w.overhead
        assert p.node_name == w.node_name


def test_decoder_keeps_the_most_recent_specs_and_parses_the_rest():
    dec = PodDecoder()
    apps = [f"app{i}" for i in range(PodDecoder.KEEP + 1)]
    for app in apps:
        dec.decode(wire(manifest("spread", f"{app}-0", app=app)))
    assert (dec.parsed, dec.reused) == (len(apps), 0)
    # the oldest fell off the list; every other is still there
    for app in reversed(apps[1:]):
        pod = dec.decode(wire(manifest("spread", f"{app}-1", app=app)))
        sel = pod.topology_spread_constraints[0].label_selector
        assert sel == Pod.from_dict(
            manifest("spread", "x", app=app)
        ).topology_spread_constraints[0].label_selector
    assert (dec.parsed, dec.reused) == (len(apps), PodDecoder.KEEP)
    dec.decode(wire(manifest("spread", f"{apps[0]}-1", app=apps[0])))
    assert dec.parsed == len(apps) + 1
    # equal spec, other status: another pod
    other = wire(manifest("spread", f"{apps[0]}-2", app=apps[0]))
    other["status"] = {"phase": "Pending", "nominatedNodeName": "n3"}
    assert dec.decode(other).nominated_node_name == "n3"
    assert dec.parsed == len(apps) + 2
    # no spec, no status, no metadata: from_dict's defaults, both ways
    assert dec.decode({}) == Pod.from_dict({}) == dec.decode({"spec": None})


def cluster(nodes=4):
    cs = ClusterState()
    for i in range(nodes):
        cs.create_node(
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "110"}).obj()
        )
    return cs


def watched(cs):
    """What a subscriber sees, as it sees it."""
    seen = []
    cs.subscribe(lambda ev: seen.append(
        (ev.type, ev.kind, ev.obj.name, ev.obj.resource_version, ev.resource_version)
    ))
    return seen


def post(app, *bodies, probe=lambda: None):
    """POST each body to /api/pods (an app serves one loop: all in one
    call), ``probe()`` after each; the answers and GET /api/state after."""

    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        async with TestClient(TestServer(app)) as client:
            answers = []
            for body in bodies:
                resp = await client.post("/api/pods", json=body)
                answers.append(
                    (resp.status, await resp.json() if resp.status == 200 else None)
                )
                probe()
            state = await (await client.get("/api/state")).json()
            return answers, state

    return asyncio.run(go())


def per_pod_handler(cs, body):
    """The handler's loop as it stood before PR 35."""
    doc = wire(body)
    items = doc["items"] if "items" in doc else [doc]
    for pd in items:
        pod = Pod.from_dict(pd)
        try:
            cs.create_pod(pod)
        except ApiError:
            cs.update_pod(pod)
    return len(items)


def queue_order(sched):
    return [info.key for info in sched.queue.pop_batch(10_000)]


@pytest.mark.parametrize("with_scheduler", [True, False])
def test_a_body_shows_what_the_per_pod_handler_showed(with_scheduler):
    bodies = [
        {"items": [manifest("plain", f"a-{i}") for i in range(6)]},
        # a-3 exists: it alone is updated; two Deployments' replicas
        # interleaved, one of them with a priority that reorders the queue
        {"items": [
            manifest("spread", "b-0", app="green"),
            manifest("plain", "a-3", extra={"priority": 5}),
            manifest("spread", "b-1", app="green"),
            manifest("anti", "c-0", extra={"priority": 9}),
            manifest("spread", "b-2", app="green"),
            manifest("anti", "c-1", extra={"priority": 9}),
        ]},
        manifest("plain", "single"),
    ]
    sides = {}
    for side in ("per_pod", "posted"):
        cs = cluster()
        sched = (
            Scheduler(cs, SchedulerConfig(), clock=FakeClock())
            if with_scheduler else None
        )
        if sched is not None:
            # make_app's drain loop would schedule the pods in an executor
            # thread whenever it wins the race with the last POST (its
            # events and pops then land on one side only); held, both
            # sides show ingest alone
            sched.run_pipelined = lambda max_batches=64: []
        seen = watched(cs)
        if side == "per_pod":
            answers = [(200, {"applied": per_pod_handler(cs, b)}) for b in bodies]
            pods = cs.list_pods()
            state = {
                "nodes": len(cs.list_nodes()), "pods": len(pods),
                "unscheduled": sum(1 for p in pods if not p.node_name),
                "resourceVersion": cs.resource_version,
            }
        else:
            app = make_app(ExtenderCore(cs, backend="oracle"), scheduler=sched)
            answers, state = post(app, *bodies)
        sides[side] = (
            answers, state, seen,
            [dataclasses.asdict(p) for p in cs.list_pods()],
            queue_order(sched) if sched is not None else None,
        )
    assert sides["posted"] == sides["per_pod"]
    answers, state, seen, _pods, order = sides["posted"]
    assert answers == [(200, {"applied": n}) for n in (6, 6, 1)]
    assert state["pods"] == state["unscheduled"] == 12
    assert [e[:3] for e in seen if e[2] == "a-3"] == [
        ("ADDED", "Pod", "a-3"), ("MODIFIED", "Pod", "a-3"),
    ]
    assert sum(1 for e in seen if e[0] == "ADDED") == 12
    # every event carries a resourceVersion of its own, in body order
    assert [e[3] for e in seen] == sorted({e[3] for e in seen})
    if with_scheduler:
        assert order[:2] == ["bench/c-0", "bench/c-1"]
        assert sorted(order) == sorted(f"bench/{e[2]}" for e in seen if e[0] == "ADDED")


def spec_counts():
    return tuple(
        metrics.ingest_pod_specs_total.labels(d)._value.get()
        for d in ("reused", "parsed")
    )


def test_reuse_counter_and_the_pending_gauge_after_a_body():
    cs = cluster()
    sched = Scheduler(cs, SchedulerConfig(), clock=FakeClock())
    app = make_app(ExtenderCore(cs, backend="oracle"), scheduler=sched)
    active = metrics.pending_pods.labels("active")
    refreshes, real = [], sched._refresh_pending_gauge

    def counted():
        refreshes.append(active._value.get())
        real()

    sched._refresh_pending_gauge = counted
    replicas = {"items": [manifest("spread", f"r-{i}") for i in range(64)]}
    distinct = {"items": [
        manifest("plain", f"d-{i}", extra={"priority": i + 1}) for i in range(64)
    ]}
    readings = [spec_counts()]
    answers, _state = post(
        app, replicas, distinct,
        probe=lambda: readings.append(
            spec_counts() + (len(refreshes), active._value.get())
        ),
    )
    assert answers == [(200, {"applied": 64})] * 2
    (r0, p0), (r1, p1, n1, g1), (r2, p2, n2, g2) = readings
    assert (r1 - r0, p1 - p0) == (63, 1)
    assert (r2 - r1, p2 - p1) == (0, 64)
    # refreshed once a body, after its last pod, and current
    assert (n1, g1, n2, g2) == (1, 64, 2, 128)
    assert sched.queue.pending_counts()["active"] == 128
    assert not sched._in_held_run


def test_a_body_with_a_pod_that_does_not_decode_applies_nothing():
    cs = cluster()
    app = make_app(ExtenderCore(cs, backend="oracle"))
    bad = manifest("plain", "bad")
    bad["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "abc"
    answers, state = post(app, {"items": [manifest("plain", "good"), bad]})
    assert answers[0][0] >= 400
    assert state["pods"] == 0
