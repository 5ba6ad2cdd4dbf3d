"""Crash/restart statelessness (SURVEY §6.3): the scheduler holds no
durable state — a fresh Scheduler over the same ClusterState resyncs via
the initial informer sync and continues correctly, including in-flight
preemption intent persisted in pod.status.nominatedNodeName.

PR 8 made the restart a first-class RECOVERY pass: a fresh incarnation
(``SchedulerConfig.incarnation > 1``) re-adopts every orphaned unbound
pod with a terminal ``recovered`` journal record, rolls back
half-committed claim reservations, and deliberately RESETS
quarantine/breaker state (a poison pod re-quarantines through the
ordinary bisection path — tested below)."""

import json
import tempfile

from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.obs import ObsConfig
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolverConfig
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.utils.clock import FakeClock


def _cfg(**kw):
    kw.setdefault("solver", ExactSolverConfig(tie_break="first"))
    return SchedulerConfig(**kw)


def test_restart_resumes_pending_and_nominations():
    clock = FakeClock()
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity({"cpu": "2", "memory": "4Gi", "pods": "10"}).obj()
    )
    s1 = Scheduler(cs, _cfg(), clock=clock)

    # schedule one pod, preempt for another, then "crash" (drop s1)
    victim = MakePod().name("victim").priority(0).req({"cpu": "2"}).obj()
    cs.create_pod(victim)
    cs.bind("default", "victim", "n")
    cs.create_pod(MakePod().name("preemptor").priority(10).req({"cpu": "2"}).obj())
    r = s1.schedule_batch()
    assert r.preemptions
    assert cs.get_pod("default", "preemptor").nominated_node_name == "n"

    # restart: a NEW scheduler over the same cluster state must pick up the
    # pending preemptor (initial sync), honor its persisted nomination, and
    # protect it from a thief that arrived during the outage
    cs.create_pod(MakePod().name("thief").priority(1).req({"cpu": "2"}).obj())
    clock.advance(30.0)
    s2 = Scheduler(cs, _cfg(), clock=clock)
    assert "default/preemptor" in s2.nominated_pods
    r = s2.schedule_batch()
    placed = dict(r.scheduled)
    assert placed.get("default/preemptor") == "n"
    assert "default/thief" in r.unschedulable


def test_restart_reconstructs_bound_state():
    """Bound pods re-enter the cache on restart: a full node stays full."""
    clock = FakeClock()
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity({"cpu": "2", "memory": "4Gi", "pods": "10"}).obj()
    )
    s1 = Scheduler(cs, _cfg(), clock=clock)
    cs.create_pod(MakePod().name("a").req({"cpu": "2"}).obj())
    assert dict(s1.schedule_batch().scheduled).get("default/a") == "n"

    s2 = Scheduler(cs, _cfg(), clock=clock)
    cs.create_pod(MakePod().name("b").req({"cpu": "2"}).obj())
    r = s2.schedule_batch()
    assert "default/b" in r.unschedulable or r.preemptions == []


def _journal_outcomes(sched):
    return [json.loads(line)["outcome"] for line in sched.journal.lines]


def test_restart_journals_recovered_for_orphans():
    """A restarted incarnation terminally journals `recovered` for
    every unbound pod it re-adopts — closing histories the crash left
    dangling — tagged with the incarnation number."""
    clock = FakeClock()
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(MakePod().name("a").req({"cpu": "1"}).obj())
    cs.create_pod(MakePod().name("b").req({"cpu": "1"}).obj())
    s2 = Scheduler(
        cs, _cfg(incarnation=2, obs=ObsConfig(journal=True)), clock=clock
    )
    recs = [json.loads(line) for line in s2.journal.lines]
    assert [r["outcome"] for r in recs] == ["recovered", "recovered"]
    assert all(r["incarnation"] == 2 for r in recs)
    assert {r["pod"] for r in recs} == {"default/a", "default/b"}
    # the re-adopted pods schedule normally
    r = s2.schedule_batch()
    assert len(r.scheduled) == 2
    assert _journal_outcomes(s2)[-2:] == ["bound", "bound"]


def test_first_start_journals_no_recovered():
    """incarnation=1 (a first start) must NOT journal recovered records
    — there is no predecessor whose histories need closing, and the
    journal bytes of existing runs must not change."""
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(MakePod().name("a").req({"cpu": "1"}).obj())
    s1 = Scheduler(cs, _cfg(obs=ObsConfig(journal=True)), clock=FakeClock())
    assert s1.journal.lines == []
    assert "incarnation" not in s1.journal.tags


def test_restart_rolls_back_half_committed_claim():
    """A claim reserved for an UNBOUND pod can only mean a crash hit
    between the PreBind claim write and the bind commit: recovery
    releases the reservation (and the allocation when nobody else
    holds it), like the deallocating controller would on delete."""
    from kubernetes_tpu.api.dra import (
        DeviceRequest,
        DeviceResult,
        ResourceClaim,
    )
    from kubernetes_tpu.utils.featuregate import FeatureGates

    clock = FakeClock()
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(
        MakePod().name("orphan").req({"cpu": "1"}).resource_claim("c").obj()
    )
    cs.create_resource_claim(
        ResourceClaim(
            name="c",
            requests=(DeviceRequest(name="r", device_class_name="tpu"),),
            allocated_node="n",
            results=(DeviceResult(request="r", driver="d", pool="p", device="0"),),
            reserved_for=("default/orphan",),
        )
    )
    Scheduler(
        cs,
        _cfg(
            incarnation=2,
            feature_gates=FeatureGates.parse(
                "DynamicResourceAllocation=true"
            ),
        ),
        clock=clock,
    )
    c = cs.get_resource_claim("default", "c")
    assert c.reserved_for == ()
    assert c.allocated_node == ""  # devices freed


def test_restart_leaves_bound_pod_claims_alone():
    """Reservations naming BOUND pods are legitimate committed
    occupancy: recovery must not touch them."""
    from kubernetes_tpu.api.dra import (
        DeviceRequest,
        DeviceResult,
        ResourceClaim,
    )
    from kubernetes_tpu.utils.featuregate import FeatureGates

    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(
        MakePod().name("ok").req({"cpu": "1"}).resource_claim("c").obj()
    )
    cs.bind("default", "ok", "n")
    cs.create_resource_claim(
        ResourceClaim(
            name="c",
            requests=(DeviceRequest(name="r", device_class_name="tpu"),),
            allocated_node="n",
            results=(DeviceResult(request="r", driver="d", pool="p", device="0"),),
            reserved_for=("default/ok",),
        )
    )
    Scheduler(
        cs,
        _cfg(
            incarnation=2,
            feature_gates=FeatureGates.parse(
                "DynamicResourceAllocation=true"
            ),
        ),
        clock=FakeClock(),
    )
    c = cs.get_resource_claim("default", "c")
    assert c.reserved_for == ("default/ok",)
    assert c.allocated_node == "n"


def test_restart_leaves_foreign_scheduler_claims_alone():
    """A claim reserved for an unbound pod owned by a FOREIGN
    scheduler (spec.schedulerName outside our profiles) is not ours to
    roll back — that scheduler may be between its own PreBind claim
    write and bind right now."""
    from kubernetes_tpu.api.dra import (
        DeviceRequest,
        DeviceResult,
        ResourceClaim,
    )
    from kubernetes_tpu.utils.featuregate import FeatureGates

    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(
        MakePod()
        .name("theirs")
        .scheduler_name("other-scheduler")
        .req({"cpu": "1"})
        .resource_claim("c")
        .obj()
    )
    cs.create_resource_claim(
        ResourceClaim(
            name="c",
            requests=(DeviceRequest(name="r", device_class_name="tpu"),),
            allocated_node="n",
            results=(DeviceResult(request="r", driver="d", pool="p", device="0"),),
            reserved_for=("default/theirs",),
        )
    )
    Scheduler(
        cs,
        _cfg(
            incarnation=2,
            feature_gates=FeatureGates.parse(
                "DynamicResourceAllocation=true"
            ),
        ),
        clock=FakeClock(),
    )
    c = cs.get_resource_claim("default", "c")
    assert c.reserved_for == ("default/theirs",)
    assert c.allocated_node == "n"


def test_restart_recovers_permit_parked_orphan():
    """A pod parked at Permit when the process dies is assumed but
    unbound: the fresh incarnation re-adopts it from truth (the
    WaitingPods map evaporated with the dead process) and schedules it
    to completion."""
    from kubernetes_tpu.framework.interface import (
        PermitPlugin,
        Status,
        StatusCode,
    )

    class HoldAtPermit(PermitPlugin):
        def permit(self, state, pod, node_name):
            return Status(StatusCode.WAIT), 30.0

    clock = FakeClock()
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    s1 = Scheduler(
        cs, _cfg(out_of_tree_plugins=(HoldAtPermit(),)), clock=clock
    )
    cs.create_pod(MakePod().name("p").req({"cpu": "1"}).obj())
    s1.schedule_batch()
    assert list(s1.waiting_pods()) == ["default/p"]  # parked + assumed

    # crash: s1 evaporates with the pod assumed-but-unbound
    cs.unsubscribe(s1._on_event)
    s2 = Scheduler(
        cs, _cfg(incarnation=2, obs=ObsConfig(journal=True)), clock=clock
    )
    assert _journal_outcomes(s2) == ["recovered"]
    r = s2.schedule_batch()
    assert dict(r.scheduled).get("default/p") == "n"


def test_restart_requarantines_poison_pod():
    """Quarantine state deliberately RESETS on restart (documented in
    Scheduler._recover): a poison pod that crashed its first
    incarnation is re-discovered by the fresh incarnation through the
    ordinary bisection path — re-quarantined, not crash-looped."""
    from kubernetes_tpu.resilience import SolverFaultError

    clock = FakeClock()
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(
        MakePod().name("poison").label("poison", "1").req({"cpu": "1"}).obj()
    )
    cs.create_pod(MakePod().name("fine").req({"cpu": "1"}).obj())

    def poison_fault(pods, tier):
        if any(p.labels.get("poison") for p in pods):
            raise SolverFaultError("data poison breaks every tier")

    s1 = Scheduler(cs, _cfg(), clock=clock)
    s1._solve_fault = poison_fault
    s1.run_until_settled()
    assert "default/poison" in s1._quarantine
    # crash: incarnation 1 (and its quarantine map) evaporates
    cs.unsubscribe(s1._on_event)

    s2 = Scheduler(
        cs, _cfg(incarnation=2, obs=ObsConfig(journal=True)), clock=clock
    )
    assert s2._quarantine == {}  # reset, not carried over
    s2._solve_fault = poison_fault
    r = s2.run_until_settled()
    # re-discovered within the first batches, healthy pod unaffected
    assert "default/poison" in s2._quarantine
    assert any("quarantined" == o for o in _journal_outcomes(s2))
    assert cs.get_pod("default", "fine").node_name == "n"
    assert r is not None


def _hist_count(hist) -> float:
    for metric in hist.collect():
        for s in metric.samples:
            if s.name.endswith("_count"):
                return s.value
    raise AssertionError("histogram has no _count sample")


def test_recovery_metric_and_span_observed():
    """The recovery pass reports scheduler_restart_recovery_seconds and
    a `recover` root span with counts."""
    from kubernetes_tpu import metrics

    before = _hist_count(metrics.restart_recovery_seconds)
    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity(
            {"cpu": "4", "memory": "8Gi", "pods": "10"}
        ).obj()
    )
    cs.create_pod(MakePod().name("a").req({"cpu": "1"}).obj())
    clock = FakeClock()
    clock.advance(1.0)
    s2 = Scheduler(
        cs, _cfg(incarnation=2, obs=ObsConfig(journal=True, spans=True)),
        clock=clock,
    )
    # FakeClock makes the duration 0.0 — the observation COUNT proves
    # the metric fired (the sum stays equal on virtual time)
    assert _hist_count(metrics.restart_recovery_seconds) == before + 1
    assert s2.journal.lines  # recovered record written under the span


def test_profiler_session_shows_the_synchronous_cycle_as_its_stages(tmp_path):
    """What --trace-dir's step annotation was for: a jax-profiler
    session around schedule_batch. With telemetry on, the cycle shows as
    the stages inside it, and behaves as it does with no session."""
    import jax

    from benchmarks.lib import span_attrib, trace_reduce
    from kubernetes_tpu.obs import ObsConfig

    cs = ClusterState()
    cs.create_node(
        MakeNode().name("n").capacity({"cpu": "4", "memory": "8Gi", "pods": "10"}).obj()
    )
    cfg = _cfg()
    cfg.obs = ObsConfig(profile=True)
    sched = Scheduler(cs, cfg)
    cs.create_pod(MakePod().name("p").req({"cpu": "1"}).obj())
    jax.profiler.start_trace(str(tmp_path))
    try:
        r = sched.schedule_batch()
    finally:
        jax.profiler.stop_trace()
    assert dict(r.scheduled).get("default/p") == "n"
    capture = span_attrib.load(trace_reduce.find_xplane(str(tmp_path)))
    assert {e[0] for th in capture["threads"] for e in th} >= {
        "stage:tensorize", "stage:dispatch", "stage:deferred_read",
        "stage:apply", "stage:bind",
    }
