"""The commit pass (Scheduler._commit_all): a run of consecutive wire-free
entries commits under ONE hold of cluster.lock, an entry with a wire call
in its binding cycle commits alone and unlocked across that call, and pod
by pod the pass does what the per-pod pass did (PR 26)."""

import dataclasses
import json
import os
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from kubernetes_tpu import metrics
from kubernetes_tpu.api.dra import (
    Device,
    DeviceClass,
    DeviceRequest,
    ResourceClaim,
    ResourceSlice,
)
from kubernetes_tpu.api.objects import (
    PersistentVolume,
    PersistentVolumeClaim,
)
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.framework.interface import (
    PostBindPlugin,
    PreBindPlugin,
    Status,
)
from kubernetes_tpu.obs import ObsConfig
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolverConfig
from kubernetes_tpu.state.cluster import ApiError, ClusterState
from kubernetes_tpu.utils.clock import FakeClock
from kubernetes_tpu.utils.featuregate import FeatureGates

GB = 1024**3
ROLE = "sched"


class CountingLock:
    """Stands where cluster.lock stood: counts the acquisitions that
    really take the lock (depth 0 on the acquiring thread), not the
    re-entries."""

    def __init__(self, real):
        self.real, self.outermost = real, 0
        self._depth = threading.local()

    def acquire(self, *a, **kw):
        got = self.real.acquire(*a, **kw)
        if got:
            depth = getattr(self._depth, "n", 0)
            if depth == 0:
                self.outermost += 1
            self._depth.n = depth + 1
        return got

    def release(self):
        self._depth.n -= 1
        self.real.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()


class OnlyBinds:
    """A bind-verb extender client that wants the pods it names; its
    wire call lands at the state service like the real delegate's."""

    is_binder = True
    cfg = SimpleNamespace(filter_verb="", prioritize_verb="", bind_verb="b")

    def __init__(self, cs, names):
        self.cs, self.names, self.bound = cs, set(names), []

    def is_interested(self, pod):
        return pod.name in self.names

    def bind(self, pod, node_name):
        self.bound.append(pod.name)
        self.cs.bind(pod.namespace, pod.name, node_name)


class VetoOne(PreBindPlugin):
    def __init__(self, name):
        self.veto = name

    def pre_bind(self, state, pod, node_name):
        if pod.name == self.veto:
            return Status.unschedulable("pre-bind veto")
        return Status.success()


def build(tmp_path=None, fence_role=None, plugins=(), nodes=4, gates=None):
    clock = FakeClock(100.0)
    cs = ClusterState(clock=clock)
    cs.lock = CountingLock(cs.lock)
    for i in range(nodes):
        cs.create_node(
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "64", "memory": "256Gi", "pods": "110"})
            .obj()
        )
    s = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=128,
            solver=ExactSolverConfig(tie_break="first", group_size=64),
            obs=ObsConfig(
                journal=True,
                journal_path=str(tmp_path / "journal.jsonl") if tmp_path else None,
            ),
            fence_role=fence_role,
            out_of_tree_plugins=tuple(plugins),
            feature_gates=gates,
        ),
        clock=clock,
    )
    return clock, cs, s


def pod_with_volume(cs, name):
    """A pod whose claim binds at PreBind (volumebinding's wire call)."""
    cs.create_pv(
        PersistentVolume(name="pv", capacity_bytes=5 * GB, storage_class="std")
    )
    cs.create_pvc(
        PersistentVolumeClaim(
            name="data", storage_class="std", request_bytes=GB,
            wait_for_first_consumer=True,
        )
    )
    return MakePod().name(name).req({"cpu": "100m"}).pvc("data").obj()


def plain(name):
    return MakePod().name(name).req({"cpu": "100m", "memory": "100Mi"}).obj()


def commit_lock_takes(s, cs):
    """Wrap _commit_all: the outermost lock acquisitions of each call."""
    takes, real = [], s._commit_all

    def counted(infos, pending, res):
        before = cs.lock.outermost
        try:
            return real(infos, pending, res)
        finally:
            takes.append((len(pending), cs.lock.outermost - before))

    s._commit_all = counted
    return takes


def path_counts():
    return {
        path: metrics.bind_commits_total.labels(path)._value.get()
        for path in ("held", "wire")
    }


def path_delta(before):
    after = path_counts()
    return {k: after[k] - before[k] for k in after}


# -- (a) the lock is taken O(1) times a flight, not O(N) -----------------


@pytest.mark.parametrize("n", [4, 32, 128])
def test_plain_flight_takes_the_lock_a_fixed_number_of_times(n):
    _clock, cs, s = build()
    takes = commit_lock_takes(s, cs)
    for i in range(n):
        cs.create_pod(plain(f"p{i:03}"))
    res = s.schedule_batch()
    assert len(res.scheduled) == n
    # one hold for the run of n commits and one for the in-flight
    # teardown, whatever n is (the per-pod pass took 3n + 1)
    assert takes == [(n, 2)]


def test_per_pod_pass_takes_the_lock_per_pod():
    """The same count on the unlocked path, so the 2 above is not a
    proxy that cannot count."""
    _clock, cs, s = build()
    s._wire_free = lambda binders, entry: False
    takes = commit_lock_takes(s, cs)
    for i in range(16):
        cs.create_pod(plain(f"p{i:03}"))
    s.schedule_batch()
    assert takes == [(16, 3 * 16 + 1)]


# -- (b) pod by pod, the held pass is the per-pod pass -------------------


ODD = ("vol", "ext", "fault", "taken")


def drive_mixed(tmp_path, per_pod, plugins=()):
    """One flight of plain pods with, in the middle, a pod with a volume
    to bind, one a binder extender wants, one the state service rejects
    and one bound elsewhere before its commit; then a second flight for
    the requeues. Everything a watcher, the events API, the journal and
    the caller can see of it."""
    tmp_path.mkdir(exist_ok=True)
    clock, cs, s = build(tmp_path, fence_role=ROLE, plugins=plugins)
    if per_pod:
        s._wire_free = lambda binders, entry: False
    binder = OnlyBinds(cs, {"ext"})
    s.extender_clients = [binder]
    watched = []
    cs.subscribe(
        lambda ev: watched.append(
            (
                ev.type, ev.kind, ev.obj.name, ev.resource_version,
                ev.obj.resource_version, getattr(ev.obj, "node_name", None),
            )
        )
    )
    journal_file = tmp_path / "journal.jsonl"
    flushed = []

    def fault(pod, node_name):
        # time passes between commits, so each record has a stamp of
        # its own; and every record before this bind is on disk already
        clock.advance(1 / 64)
        flushed.append(len(journal_file.read_text().splitlines()))
        if pod.name == "fault":
            raise ApiError("Conflict", "injected: the apiserver said no")

    cs.bind_fault = fault
    orders = []

    def before_commit(pending):
        orders.append([e[2].name for e in pending])
        if "taken" in orders[-1]:
            cs.bind_fault = None
            cs.bind("default", "taken", "n3")
            cs.bind_fault = fault

    s._pre_commit_hook = before_commit
    for i in range(8):
        cs.create_pod(plain(f"a{i}"))
    cs.create_pod(pod_with_volume(cs, "vol"))
    cs.create_pod(plain("ext"))
    cs.create_pod(plain("fault"))
    cs.create_pod(plain("taken"))
    for i in range(8):
        cs.create_pod(plain(f"b{i}"))
    before = path_counts()
    results = [s.schedule_batch()]
    # the failed pods' retry: out of the unschedulable queue, past backoff
    s.queue.move_all_to_active_or_backoff("retry")
    clock.advance(30.0)
    cs.bind_fault = lambda pod, node_name: clock.advance(1 / 64)
    results.append(s.schedule_batch())
    return SimpleNamespace(
        journal=list(s.journal.lines),
        journal_file=journal_file.read_text(),
        events=[dataclasses.astuple(r) for r in cs.list_events()],
        watched=watched,
        scheduled=[r.scheduled for r in results],
        bind_failures=[r.bind_failures for r in results],
        queues=s.queue.pending_counts(),
        rv=cs.resource_version,
        nodes={p.name: p.node_name for p in cs.list_pods()},
        flushed=flushed,
        orders=orders,
        paths=path_delta(before),
        binder=binder.bound,
        fence_rejections=dict(cs.fence_rejections),
    )


SEEN = (
    "journal", "journal_file", "events", "watched", "scheduled",
    "bind_failures", "queues", "rv", "nodes", "flushed", "orders", "binder",
    "fence_rejections",
)


@pytest.mark.parametrize("seen", SEEN)
@pytest.mark.parametrize("pre_bind", [False, True], ids=["plain", "prebind"])
def test_held_pass_shows_what_the_per_pod_pass_shows(tmp_path, seen, pre_bind):
    plugins = [VetoOne("b3")] if pre_bind else []
    held = drive_mixed(tmp_path / "held", per_pod=False, plugins=plugins)
    per_pod = drive_mixed(tmp_path / "per_pod", per_pod=True, plugins=plugins)
    assert getattr(held, seen) == getattr(per_pod, seen)
    # and the drive is the one described, on the path it should take
    first = held.orders[0]
    assert set(ODD) <= set(first[1:-1])
    assert [k for k, _ in held.bind_failures[0]] == (
        ["default/fault", "default/taken"]
        + (["default/b3"] if pre_bind else [])
    )
    assert held.nodes["taken"] == "n3" and held.binder == ["ext"]
    # the rejected pods bound on retry (the vetoed one never can)
    assert [k for k, v in held.nodes.items() if not v] == ["b3"] * pre_bind
    n = len(first) + len(held.orders[1])
    assert per_pod.paths == {"held": 0, "wire": n}
    if pre_bind:  # a PreBind plugin stands in every pod's cycle
        assert held.paths == {"held": 0, "wire": n}
    else:
        assert held.paths == {"held": n - 2, "wire": 2}


def test_each_record_has_its_own_stamp_and_is_flushed_as_written(tmp_path):
    held = drive_mixed(tmp_path, per_pod=False)
    bound = [
        r for r in map(json.loads, held.journal_file.splitlines())
        if r["outcome"] == "bound"
    ]
    # one ``bound`` record per pod, in commit order, each at its own time
    scheduled = [k for flight in held.scheduled for k, _ in flight]
    assert [r["pod"] for r in bound] == scheduled
    assert len({r["t"] for r in bound}) == len(bound)
    assert [r["t"] for r in bound] == sorted(r["t"] for r in bound)
    # at every bind the file already held every earlier record: a held
    # run does not gather its records for one write at its end
    assert held.flushed == sorted(held.flushed)
    assert len(set(held.flushed)) >= len(held.flushed) - 2
    # one Scheduled event and one MODIFIED watch event per bound pod
    per_pod_events = [e for e in held.events if e[4] == "Scheduled"]
    assert sorted(e[3] for e in per_pod_events) == sorted(
        k.split("/")[1] for k in scheduled
    )
    binds = [w for w in held.watched if w[:2] == ("MODIFIED", "Pod") and w[5]]
    assert sorted(w[2] for w in binds) == sorted(held.nodes)


# -- (c) the fence cannot change inside a hold ---------------------------


def yield_between_commits(s, while_waiting):
    """Give a second thread every chance to take the lock between two
    pods' commits: where the pass drops the lock there, it gets it."""
    real = s._commit_binding

    def yielding(*a, **kw):
        if while_waiting():
            time.sleep(0.02)
        return real(*a, **kw)

    s._commit_binding = yielding


def test_revoke_waits_for_the_run_and_fences_the_next_one_whole():
    _clock, cs, s = build(fence_role=ROLE)
    for i in range(12):
        cs.create_pod(plain(f"p{i:02}"))
    started, done, blocked = threading.Event(), threading.Event(), []

    def revoke():
        started.set()
        cs.revoke_fence(ROLE)
        done.set()

    revoker = threading.Thread(target=revoke)

    def mid_run(pod, node_name):
        if pod.name == "p05":
            revoker.start()
            assert started.wait(timeout=30)
            # the revoke is asked for NOW, mid-run, and cannot land:
            # it needs the lock this run holds
            blocked.append(not done.wait(timeout=0.2))

    cs.bind_fault = mid_run
    yield_between_commits(s, lambda: started.is_set() and not done.is_set())
    fenced_before = metrics.commit_fenced_total._value.get()
    first = s.schedule_batch()
    revoker.join(timeout=30)
    assert blocked == [True] and done.is_set()
    # the run the revoke waited for landed whole: its token was good
    # for every bind of the hold
    assert len(first.scheduled) == 12 and not first.bind_failures
    assert cs.fence_rejections.get(ROLE, 0) == 0

    cs.bind_fault = None
    for i in range(9):
        cs.create_pod(plain(f"q{i:02}"))
    second = s.schedule_batch()
    assert not second.scheduled
    assert len(second.bind_failures) == 9
    assert {why for _, why in second.bind_failures} == {"Conflict"}
    assert cs.fence_rejections[ROLE] == 9
    assert metrics.commit_fenced_total._value.get() - fenced_before == 9
    assert s._fenced_commits == 9
    assert not any(
        p.node_name for p in cs.list_pods() if p.name.startswith("q")
    )


def test_grant_waits_for_the_run_too():
    """A successor taking the role over mid-run fences the next run, not
    the rest of this one."""
    _clock, cs, s = build(fence_role=ROLE)
    for i in range(6):
        cs.create_pod(plain(f"p{i}"))
    granted = []
    taker = threading.Thread(
        target=lambda: granted.append(cs.grant_fence(ROLE, "successor"))
    )
    cs.bind_fault = lambda pod, node: pod.name == "p2" and taker.start()
    yield_between_commits(s, lambda: taker.ident is not None and not granted)
    first = s.schedule_batch()
    taker.join(timeout=30)
    assert len(first.scheduled) == 6 and granted == [s._fence_token + 1]
    cs.bind_fault = None
    cs.create_pod(plain("late"))
    assert [k for k, _ in s.schedule_batch().bind_failures] == ["default/late"]


# -- (d) the counter says which path a pod took --------------------------


def test_plain_pods_count_as_held():
    _clock, cs, s = build()
    for i in range(7):
        cs.create_pod(plain(f"p{i}"))
    before = path_counts()
    s.run_pipelined()
    assert path_delta(before) == {"held": 7, "wire": 0}


def _with_volume(cs, s):
    return pod_with_volume(cs, "odd")


def _with_binder(cs, s):
    s.extender_clients = [OnlyBinds(cs, {"odd"})]
    return plain("odd")


def _with_claim(cs, s):
    cs.create_device_class(DeviceClass(name="gpu", driver="d"))
    cs.create_resource_slice(
        ResourceSlice(
            name="s0", node_name="n0", driver="d", devices=(Device(name="g0"),),
        )
    )
    cs.create_resource_claim(
        ResourceClaim(
            name="c", requests=(DeviceRequest(name="r", device_class_name="gpu"),)
        )
    )
    return MakePod().name("odd").req({"cpu": "100m"}).resource_claim("c").obj()


@pytest.mark.parametrize(
    "odd_pod", [_with_volume, _with_binder, _with_claim],
    ids=["volume", "binder_extender", "resource_claim"],
)
def test_a_pod_with_a_wire_call_counts_as_wire(odd_pod):
    _clock, cs, s = build(
        gates=FeatureGates.parse("DynamicResourceAllocation=true")
    )
    cs.create_pod(plain("p0"))
    cs.create_pod(odd_pod(cs, s))
    cs.create_pod(plain("p1"))
    before = path_counts()
    res = s.schedule_batch()
    assert len(res.scheduled) == 3
    assert path_delta(before) == {"held": 2, "wire": 1}


@pytest.mark.parametrize("point", ["pre_bind", "post_bind"])
def test_a_bind_plugin_makes_every_pod_wire(point):
    class After(PostBindPlugin):
        def post_bind(self, state, pod, node_name):
            pass

    plugin = VetoOne("nobody") if point == "pre_bind" else After()
    _clock, cs, s = build(plugins=[plugin])
    for i in range(5):
        cs.create_pod(plain(f"p{i}"))
    before = path_counts()
    assert len(s.schedule_batch().scheduled) == 5
    assert path_delta(before) == {"held": 0, "wire": 5}


def test_pending_gauge_is_current_after_a_held_run_that_requeued():
    _clock, cs, s = build()
    for i in range(6):
        cs.create_pod(plain(f"p{i}"))

    def fault(pod, node_name):
        if pod.name in ("p1", "p4"):
            raise ApiError("Conflict", "injected")

    cs.bind_fault = fault
    res = s.schedule_batch()
    assert len(res.bind_failures) == 2 and not s._in_held_run
    for queue_name, count in s.queue.pending_counts().items():
        assert metrics.pending_pods.labels(queue_name)._value.get() == count
    assert sum(s.queue.pending_counts().values()) == 2


# -- the hold against many writers ----------------------------------------


def test_held_runs_against_many_ingest_threads():
    """More writers than cores on a short switch interval, the loop
    committing held runs all the while: every pod binds exactly once,
    has one ``bound`` record, and the books are square at the end."""
    _clock, cs, s = build(nodes=8)
    writers, per_writer = (os.cpu_count() or 4) + 4, 40
    total = writers * per_writer
    binds = []
    cs.subscribe(
        lambda ev: ev.kind == "Pod" and ev.type == "MODIFIED"
        and ev.obj.node_name and binds.append(ev.obj.key)
    )

    def write(w):
        for i in range(per_writer):
            cs.create_pod(plain(f"w{w:02}-{i:03}"))

    threads = [threading.Thread(target=write, args=(w,)) for w in range(writers)]
    before, interval = path_counts(), sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        while len(binds) < total and time.monotonic() < deadline:
            s.run_pipelined(max_batches=4)
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(binds) == sorted(p.key for p in cs.list_pods())
    assert len(binds) == total
    bound = [
        r["pod"] for r in map(json.loads, s.journal.lines)
        if r["outcome"] == "bound"
    ]
    assert sorted(bound) == sorted(binds)
    assert path_delta(before) == {"held": total, "wire": 0}
    assert not s._in_held_run and not s._in_flight
    assert sum(s.queue.pending_counts().values()) == 0


@pytest.mark.parametrize("pods", [1, 64])
@pytest.mark.parametrize("with_scheduler", [True, False])
def test_a_posted_body_takes_the_lock_once(with_scheduler, pods, monkeypatch):
    """POST /api/pods applies a body under one hold (PR 35): the same
    number of takes for 64 pods as for one, a pod that exists included."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubernetes_tpu.server.extender import ExtenderCore, make_app

    _clock, cs, s = build(nodes=2)
    # the app's drain loop polls `pending` under the same lock: it
    # reads 0 here without it, so every take counted is the handler's
    monkeypatch.setattr(Scheduler, "pending", property(lambda self: 0))
    app = make_app(
        ExtenderCore(cs, backend="oracle"), scheduler=s if with_scheduler else None
    )
    cs.create_pod(plain("p0"))
    body = {"items": [plain(f"p{i}").to_dict() for i in range(pods)]}
    takes = []

    async def go():
        async with TestClient(TestServer(app)) as client:
            before = cs.lock.outermost
            resp = await client.post("/api/pods", json=body)
            takes.append(cs.lock.outermost - before)
            assert await resp.json() == {"applied": pods}

    asyncio.run(go())
    assert takes == [1]
    assert len(cs.list_pods()) == pods
    assert not s._in_held_run
