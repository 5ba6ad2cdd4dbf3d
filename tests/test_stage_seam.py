"""The one stage seam (Telemetry.stage): off it costs nothing, on it
hands scheduler_profile_stage_seconds the seconds it always got, and the
same intervals reach a jax-profiler session as ``stage:*`` annotations
(PR 25; the removed utils/tracing.py annotated only the synchronous
cycle and was read by nothing)."""

import time

import jax
import pytest

from kubernetes_tpu import obs as obs_mod
from kubernetes_tpu import scheduler as sched_mod
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.obs import ObsConfig
from kubernetes_tpu.obs.profile import STAGES
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.solver.exact import ExactSolverConfig
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.utils.clock import FakeClock

HOST = "kubernetes.io/hostname"


def build(obs=None, clock=None, nodes=6, pods=40):
    cs = ClusterState()
    for i in range(nodes):
        cs.create_node(
            MakeNode().name(f"n{i:03}")
            .capacity({"cpu": "8", "memory": "32Gi", "pods": "110"})
            .label(HOST, f"n{i:03}").obj()
        )
    s = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=8,
            solver=ExactSolverConfig(tie_break="first", group_size=4),
            obs=obs,
        ),
        clock=clock,
    )
    for i in range(pods):
        cs.create_pod(
            MakePod().name(f"p{i:04}").req({"cpu": "500m", "memory": "1Gi"}).obj()
        )
    return cs, s


class Counting:
    """Stands where a class stood and counts what is built of it."""

    def __init__(self, real):
        self.real, self.built = real, 0

    def __call__(self, *a, **kw):
        self.built += 1
        return self.real(*a, **kw)


def test_off_path_builds_no_stage_and_touches_no_profiler(monkeypatch):
    stages = Counting(obs_mod._Stage)
    monkeypatch.setattr(obs_mod, "_Stage", stages)

    class Poison:
        def __getattr__(self, name):
            raise AssertionError(f"telemetry off, yet jax.profiler.{name} was read")

    # telemetry off: every site is one `is not None` check
    monkeypatch.setattr(jax, "profiler", Poison())
    cs, s = build()
    assert s.telemetry is None
    assert sum(len(r.scheduled) for r in s.run_pipelined()) == 40
    cs, s = build()
    s.run_until_settled()
    assert all(p.node_name for p in cs.list_pods())
    assert stages.built == 0
    monkeypatch.undo()

    # the same drive with telemetry on does go through the seam (so the
    # zero above is not a test that cannot fail)
    stages = Counting(obs_mod._Stage)
    monkeypatch.setattr(obs_mod, "_Stage", stages)
    cs, s = build(obs=ObsConfig(profile=True))
    annotations = Counting(s.telemetry.annotation)
    s.telemetry.annotation = annotations
    s.run_pipelined()
    assert stages.built >= 6 * 5  # six block stages, five batches
    assert annotations.built == stages.built


# stage totals of the drive below on the parent of PR 25 (b5f323d), where
# every site computed its seconds by hand and called add_stage
GOLDEN = {
    (True, False): dict(tensorize=2.5, dispatch=1.25, fence_wait=0.0,
                        deferred_read=1.0, validate=0.5, apply=0.3125, bind=0.625),
    (True, True): dict(tensorize=3.5, dispatch=1.75, fence_wait=0.5,
                       deferred_read=1.0, validate=0.5, apply=0.3125, bind=0.625),
    (False, False): dict(tensorize=2.5, dispatch=1.25, fence_wait=0.0,
                         deferred_read=0.625, validate=0.3125, apply=0.3125, bind=0.625),
    (False, True): dict(tensorize=2.5, dispatch=1.25, fence_wait=0.0,
                        deferred_read=0.625, validate=0.3125, apply=0.3125, bind=0.625),
}


def tick_inside_every_stage(monkeypatch, s, tick):
    """Make time pass INSIDE each stage's region and nowhere else:
    ``tick(seconds)`` runs at the start of one call that lies in it."""

    def ticking(fn, dt):
        def inner(*a, **kw):
            tick(dt)
            return fn(*a, **kw)

        return inner

    monkeypatch.setattr(
        sched_mod, "build_static_tensors",
        ticking(sched_mod.build_static_tensors, 0.5),
    )
    monkeypatch.setattr(
        sched_mod, "validate_assignments",
        ticking(sched_mod.validate_assignments, 0.0625),
    )
    monkeypatch.setattr(
        sched_mod._InFlightSolve, "assignments",
        ticking(sched_mod._InFlightSolve.assignments, 0.125),
    )
    for solver in s.solvers.values():
        solver.solve = ticking(solver.solve, 0.25)
    s._commit_binding = ticking(s._commit_binding, 1 / 64)
    s.cache.assume_pod = ticking(s.cache.assume_pod, 1 / 128)


def churn_once(cs, s):
    """A capacity event between the second dispatch and its apply: the
    pipelined loop discards that solve (fence_wait) and re-solves."""
    real, n = s._dispatch_group, [0]

    def churny(prep, defer, allow_heal=True, **kw):
        flight = real(prep, defer, allow_heal, **kw)
        n[0] += 1
        if n[0] == 2:
            node = cs.get_node("n000")
            grown = (
                MakeNode().name("n000")
                .capacity({"cpu": "9", "memory": "32Gi", "pods": "110"})
                .label(HOST, "n000").obj()
            )
            grown.resource_version = node.resource_version
            cs.update_node(grown)
        return flight

    s._dispatch_group = churny


@pytest.mark.parametrize("pipelined,churn", sorted(GOLDEN))
def test_fake_clock_stage_totals_are_the_parents(monkeypatch, pipelined, churn):
    clock = FakeClock(100.0)
    cs, s = build(obs=ObsConfig(journal=True, profile=True), clock=clock)
    tick_inside_every_stage(monkeypatch, s, clock.advance)
    if churn:
        churn_once(cs, s)
    if pipelined:
        s.run_pipelined()
    else:
        s.run_until_settled()
    assert all(p.node_name for p in cs.list_pods())
    assert s.telemetry.profiler.snapshot()["stage_seconds"] == GOLDEN[pipelined, churn]


def test_a_stage_that_raises_books_nothing():
    tel = obs_mod.build_telemetry(ObsConfig(profile=True), FakeClock())
    with pytest.raises(KeyError):
        with tel.stage("dispatch", step=1, pods=2):
            tel.clock.advance(1.0)
            raise KeyError("solve died")
    with tel.stage("dispatch", step=1, pods=2):
        tel.clock.advance(0.25)
    assert tel.profiler.observe_batch(step=1, pods=2)["stages"]["dispatch"] == 0.25


def test_annotations_of_a_profiler_session_agree_with_the_counters(
    monkeypatch, tmp_path
):
    """Real clock, under a jax.profiler session: each stage's annotation
    durations, summed, are the seconds its counter got, within 2 %."""
    from benchmarks.lib import span_attrib, trace_reduce

    cs, s = build(obs=ObsConfig(profile=True), pods=24)
    # each stage lasts milliseconds, so that the annotation's own cost
    # (about a microsecond a side) is far inside the 2 %
    tick_inside_every_stage(monkeypatch, s, lambda dt: time.sleep(dt / 25))
    churn_once(cs, s)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s.run_pipelined()
    finally:
        jax.profiler.stop_trace()
    counters = s.telemetry.profiler.snapshot()["stage_seconds"]
    capture = span_attrib.load(trace_reduce.find_xplane(str(tmp_path)))
    loop = [th for th in capture["threads"] if any(e[0] == "stage:dispatch" for e in th)]
    assert len(loop) == 1  # one thread wrote every stage
    summed = dict.fromkeys(STAGES, 0.0)
    for name, _, dur_ns, stats in loop[0]:
        summed[name[len("stage:"):]] += dur_ns / 1e9
        # and the thread's CPU time in it, within one 10 ms tick of a
        # host whose thread clock counts ticks
        assert set(stats) == {"step", "pods", "cpu_us"}
        assert 0 <= stats["cpu_us"] <= dur_ns / 1e3 + 10_000
    assert counters["fence_wait"] > 0 and summed["fence_wait"] == 0.0  # booked after the fact
    for stage in STAGES:
        if stage != "fence_wait":
            assert counters[stage] > 0.004
            assert summed[stage] == pytest.approx(counters[stage], rel=0.02), stage
