"""Closed-loop auto-tuning (kubernetes_tpu/tuning, ISSUE 13).

Three layers:

- HillClimber convergence properties on seeded synthetic objective
  traces: settles within a bounded number of observations, never
  oscillates past the hysteresis margin, never leaves its bounds or
  alignment, never applies a guard-rejected candidate.
- CounterWindow: the split-rule EWMAs match the formula the scheduler
  used before the move (satellite: ONE home for the estimates), batch
  samples carry counter deltas, the rate signature is pop-boundary
  robust.
- TuningRuntime on a REAL Scheduler: the streaming drive converges and
  journals; the drain-chunk controller's HBM guardrail rejects
  over-budget candidates BEFORE application (BudgetExceeded never
  raised by a tuner-proposed shape); the tuned profile round-trips
  through the standard config loader.
"""

from __future__ import annotations

import pytest

from kubernetes_tpu.tuning.controllers import HillClimber
from kubernetes_tpu.tuning.runtime import TuningConfig, TuningRuntime
from kubernetes_tpu.tuning.window import CounterWindow
from kubernetes_tpu.utils.clock import FakeClock

from _hypothesis_compat import given, settings, st


def drive(climber, objective, batches):
    """Feed ``batches`` observations of ``objective(value)``; returns
    the decision list. The objective is evaluated at the climber's
    CURRENT value each batch — exactly the closed loop the runtime
    runs."""
    out = []
    for _ in range(batches):
        d = climber.observe(objective(climber.value), 1.0)
        if d is not None:
            out.append(d)
        if climber.settled:
            break
    return out


class TestHillClimber:
    def test_climbs_to_a_clean_peak_and_settles(self):
        # unimodal objective peaking at 8: the climber must walk there
        # from 2 and settle
        c = HillClimber(
            "k", 2, 1, 64, eval_batches=2, hysteresis=0.05,
            settle_after=1,
        )
        drive(c, lambda v: 100 - abs(v - 8) * 10, 200)
        assert c.settled
        assert c.value == 8
        assert c.moves >= 2  # 2 -> 4 -> 8

    def test_descends_when_down_is_better(self):
        # 1000/v doubles the objective per halving — every down-probe
        # clears the relative margin all the way to the floor
        c = HillClimber(
            "k", 32, 1, 64, eval_batches=2, hysteresis=0.05,
            settle_after=1,
        )
        drive(c, lambda v: 1000.0 / v, 200)
        assert c.settled
        assert c.value == 1

    def test_flat_objective_settles_at_start_value(self):
        # no direction improves past the margin: stay put (a tuned
        # drive's >= static guarantee rides on this)
        c = HillClimber(
            "k", 4, 1, 16, eval_batches=2, hysteresis=0.05,
            settle_after=1,
        )
        drive(c, lambda v: 50.0, 200)
        assert c.settled
        assert c.value == 4
        assert c.moves == 0

    def test_accepts_require_strict_hysteresis_margin(self):
        # a 3% improvement is under the 5% margin: never accepted
        c = HillClimber(
            "k", 4, 1, 64, eval_batches=2, hysteresis=0.05,
            settle_after=1,
        )
        drive(c, lambda v: 100.0 * (1.03 if v > 4 else 1.0), 200)
        assert c.settled
        assert c.value == 4
        assert c.moves == 0

    def test_never_leaves_bounds_or_alignment(self):
        c = HillClimber(
            "k", 64, 32, 512, eval_batches=1, hysteresis=0.05,
            settle_after=2, align=32,
        )
        seen = set()
        for i in range(300):
            c.observe(float((i * 37) % 11), 1.0)
            seen.add(c.value)
            if c.settled:
                break
        assert all(32 <= v <= 512 and v % 32 == 0 for v in seen), seen

    def test_guard_rejected_candidate_is_never_applied(self):
        # guard forbids anything above 8: the climber must not even
        # transiently hold a larger value
        tried = []

        def guard(v):
            tried.append(v)
            return v <= 8

        c = HillClimber(
            "k", 8, 1, 64, eval_batches=1, hysteresis=0.05,
            settle_after=1, guard=guard,
        )
        seen = set()
        for i in range(100):
            c.observe(float(i % 7), 1.0)
            seen.add(c.value)
            if c.settled:
                break
        assert max(seen) <= 8
        assert c.guard_rejections >= 1
        assert any(v > 8 for v in tried)  # it DID propose, guard vetoed

    def test_probe_budget_bounds_a_noisy_objective(self):
        # adversarial noise that keeps "improving" on every probe:
        # without the probe budget this random-walks forever
        c = HillClimber(
            "k", 4, 1, 4096, eval_batches=1, hysteresis=0.05,
            settle_after=3, max_probes=6,
        )
        n = [0.0]

        def noisy(_v):
            n[0] += 10.0  # strictly increasing: every probe accepts
            return n[0]

        for _ in range(500):
            c.observe(noisy(c.value), 1.0)
            if c.settled:
                break
        assert c.settled
        assert c.probes <= 6

    def test_no_oscillation_past_hysteresis(self):
        # an A<->B cycle needs obj(B) > obj(A)*(1+h) AND
        # obj(A) > obj(B)*(1+h) — impossible for a fixed objective; the
        # value sequence must never revisit an abandoned direction flip
        # more than the settle budget allows
        c = HillClimber(
            "k", 8, 1, 64, eval_batches=2, hysteresis=0.05,
            settle_after=2,
        )
        values = []
        for i in range(400):
            c.observe(100 - abs(c.value - 16) * 2, 1.0)
            values.append(c.value)
            if c.settled:
                break
        assert c.settled
        assert c.value == 16
        # each accepted move is unique (monotone walk), so accepts are
        # bounded by the octave distance, not the batch count
        accepts = [d for d in c.history if d.action == "accept"]
        assert len(accepts) == len({(d.old, d.new) for d in accepts})

    def test_unsettle_reopens_and_reconverges(self):
        c = HillClimber(
            "k", 2, 1, 64, eval_batches=2, hysteresis=0.05,
            settle_after=1,
        )
        drive(c, lambda v: 100 - abs(v - 8) * 10, 200)
        assert c.settled and c.value == 8
        c.unsettle({"why": "test"})
        assert not c.settled
        drive(c, lambda v: 100 - abs(v - 32) * 2, 400)
        assert c.settled
        assert c.value == 32

    @settings(max_examples=30, deadline=None)
    @given(
        st.integers(min_value=0, max_value=2**31 - 1),
        st.integers(min_value=1, max_value=6),
        st.integers(min_value=1, max_value=3),
    )
    def test_property_always_settles_in_bounds(
        self, seed, eval_batches, settle_after
    ):
        """Any seeded objective trace: the climber settles within the
        structural bound (probe budget x window) and never exits its
        bounds/alignment."""
        import random

        rng = random.Random(seed)
        c = HillClimber(
            "k", 8, 2, 256, eval_batches=eval_batches,
            hysteresis=0.1, settle_after=settle_after, align=2,
            max_probes=8,
        )
        # structural bound: every window is eval_batches observations;
        # episodes end after max_probes probes; between consecutive
        # probes there is at most one measure window
        limit = eval_batches * (2 * c.max_probes + 4) + eval_batches
        steps = 0
        while not c.settled and steps < 10_000:
            c.observe(rng.uniform(0, 100), 1.0)
            steps += 1
            assert 2 <= c.value <= 256 and c.value % 2 == 0
        assert c.settled, f"never settled in {steps} steps"
        assert steps <= limit, (steps, limit)


class TestCounterWindow:
    def test_note_read_ewma_matches_the_moved_formula(self):
        # the exact update rule that lived in Scheduler._note_flight_timing
        w = CounterWindow(FakeClock())
        w.note_read(0.2, 0.1, 10)
        assert w.rtt_ewma == pytest.approx(0.2)
        assert w.pod_solve_ewma == pytest.approx(0.3 / 10)
        w.note_read(0.4, 0.1, 10)
        assert w.rtt_ewma == pytest.approx(0.7 * 0.2 + 0.3 * 0.4)
        # sub-millisecond reads carry no signal (post-overlap reads are
        # the overlap working)
        before = w.rtt_ewma
        w.note_read(0.0005, 0.1, 10)
        assert w.rtt_ewma == before

    def test_split_estimate_rule(self):
        w = CounterWindow(FakeClock())
        assert w.split_estimate(100, 8) == 1  # no estimates yet
        # exact binary fractions so the rule's integer truncation is
        # deterministic in the test
        w.rtt_ewma = 0.125
        w.pod_solve_ewma = 0.0009765625  # 2^-10
        # est_solve = 0.0977 <= 2 * rtt: no split
        assert w.split_estimate(100, 8) == 1
        # est_solve = 4 s = 32x rtt: split, capped
        assert w.split_estimate(4096, 8) == 8
        assert w.split_estimate(4096, 4) == 4
        w.pod_solve_ewma = 0.0005  # est = 0.5 s = 4x rtt
        assert w.split_estimate(1000, 8) == 4

    def test_note_batch_samples_counter_deltas(self):
        from kubernetes_tpu import metrics

        clock = FakeClock()
        w = CounterWindow(clock)
        metrics.stream_unhidden_reads_total.inc(3)
        clock.advance(2.0)
        s = w.note_batch(pods=5, solve_s=0.1)
        assert s.deltas["unhidden_reads"] == 3
        assert s.pods == 5
        assert s.wall_s == pytest.approx(2.0)
        # second sample: delta resets
        s2 = w.note_batch(pods=4)
        assert s2.deltas["unhidden_reads"] == 0

    def test_rate_is_pop_boundary_robust(self):
        # one 15-pod cycle popped as [15] or as [8, 7] must read the
        # same rate (the per-batch mean would differ by 2x)
        clock = FakeClock()
        a = CounterWindow(clock)
        clock.advance(1.0)
        a.note_batch(pods=15)
        b = CounterWindow(clock)
        clock.advance(1.0)
        b.note_batch(pods=8)
        b.note_batch(pods=7)
        assert a.rate(4) == pytest.approx(b.rate(4))


def _mk_cluster(n_nodes=8, cpu="32", mem="128Gi", clock=None):
    from kubernetes_tpu.api.wrappers import MakeNode
    from kubernetes_tpu.state.cluster import ClusterState

    cs = ClusterState(clock=clock)
    for i in range(n_nodes):
        cs.create_node(
            MakeNode()
            .name(f"n{i}")
            .capacity({"cpu": cpu, "memory": mem, "pods": "110"})
            .obj()
        )
    return cs


def _mk_pods(cs, n, prefix="p"):
    from kubernetes_tpu.api.wrappers import MakePod

    for i in range(n):
        cs.create_pod(
            MakePod()
            .name(f"{prefix}{i:04}")
            .req({"cpu": "500m", "memory": "1Gi"})
            .obj()
        )


class TestRuntimeOnScheduler:
    def _scheduler(self, clock, tuning=None, n_nodes=8, cpu="32", **cfg_kw):
        from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig

        cs = _mk_cluster(n_nodes=n_nodes, cpu=cpu, clock=clock)
        cfg = SchedulerConfig(
            batch_size=8,
            tuning=tuning
            or TuningConfig(
                eval_batches=2, settle_after=1, hysteresis=0.5,
                max_probes=4,
            ),
            **cfg_kw,
        )
        return cs, Scheduler(cs, cfg, clock=clock)

    def test_streaming_drive_converges_and_journals(self):
        from kubernetes_tpu import metrics

        clock = FakeClock()
        cs, s = self._scheduler(clock)
        for c in range(20):
            _mk_pods(cs, 6, prefix=f"c{c}-")
            s.run_streaming(max_batches=50)
            clock.advance(1.0)
        summary = s.tuner.summary()
        assert summary["probes"] >= 1
        assert summary["settled"] == 1
        assert summary["guardrail_breaches"] == 0
        assert 1 <= summary["knobs"]["stream_depth"] <= 16
        assert 1 <= summary["knobs"]["pipeline_split"] <= 8
        # the applied value and the journaled gauge agree with config
        assert s.config.stream_depth == summary["knobs"]["stream_depth"]
        assert metrics.tuning_knob_value.labels(
            "stream_depth"
        )._value.get() == float(s.config.stream_depth)
        # every decision journaled through the metric family
        assert len(s.tuner.decisions) == summary["adjustments"]

    def test_choose_split_prefers_tuner_then_window(self):
        clock = FakeClock()
        cs, s = self._scheduler(clock)
        # without a tuner attachment yet: the window's EWMA rule
        s.window.rtt_ewma = 0.1
        s.window.pod_solve_ewma = 0.001
        assert s._choose_split(1000) == s.window.split_estimate(1000, 8)
        # attach: the split controller owns the knob outright
        s.tuner.attach(s)
        assert s._choose_split(1000) == s.tuner.split_override()
        # a fixed config split is a static pin over both
        s.config.pipeline_split = 3
        assert s._choose_split(1000) == 3

    def test_pipelined_drive_settles_despite_inactive_stream_knob(self):
        """Review-caught: the stream_depth controller never ticks on a
        pipelined drive (its dispatch mode never runs) — a never-ticked
        controller must not pin settled=0 forever."""
        clock = FakeClock()
        cs, s = self._scheduler(clock)
        for c in range(20):
            _mk_pods(cs, 6, prefix=f"c{c}-")
            s.run_pipelined(max_batches=50)
            clock.advance(1.0)
        summary = s.tuner.summary()
        assert summary["settled"] == 1, summary
        depth = s.tuner.controllers["stream_depth"]
        assert depth.ticks == 0 and not depth.settled  # idle, not failed

    def test_first_sample_is_a_warm_batch(self):
        """Review-caught: the first sample's wall spans scheduler
        construction (JIT compile) — it must re-anchor the window but
        feed no controller, or the deflated baseline lets the first
        probe win unconditionally."""
        clock = FakeClock()
        cs, s = self._scheduler(clock)
        clock.advance(100.0)  # "construction + compile" gap
        _mk_pods(cs, 6)
        s.run_streaming(max_batches=10)
        assert all(
            c.ticks == 0 for c in s.tuner.controllers.values()
        )
        assert len(s.window.samples) >= 1  # the window DID sample

    def test_static_pin_by_dropping_the_knob(self):
        clock = FakeClock()
        cs, s = self._scheduler(
            clock,
            tuning=TuningConfig(
                eval_batches=2, settle_after=1,
                knobs=("pipeline_split",),
            ),
        )
        for c in range(8):
            _mk_pods(cs, 6, prefix=f"c{c}-")
            s.run_streaming(max_batches=50)
            clock.advance(1.0)
        # stream_depth untouched (not governed), split governed
        assert "stream_depth" not in s.tuner.controllers
        assert s.config.stream_depth == 4
        assert "pipeline_split" in s.tuner.controllers

    def test_drain_guardrail_rejects_over_budget_chunks(self):
        """The acceptance clause: a tuner-proposed chunk must pass the
        HBM budget model BEFORE application — BudgetExceeded is never
        raised by a tuner-proposed shape, and the up-probes against a
        budget pinned one byte above the base chunk's estimate are
        rejected, not applied."""
        from kubernetes_tpu.solver import budget as hbm

        clock = FakeClock()
        cs, s = self._scheduler(clock, n_nodes=12, cpu="64")
        # chunk = LANE (128): the smallest chunk whose DOUBLING grows
        # the pod-axis padding bucket (everything below 128 floors to
        # one bucket and costs the same HBM — growth there is free and
        # correctly allowed)
        _mk_pods(cs, 768)
        shape = s.drain_shape(128)
        budget = hbm.estimate(shape).per_device_bytes + 1
        report = s.drain_backlog(chunk_pods=128, budget_bytes=budget)
        assert report.drained == 768  # the drain completed
        summary = s.tuner.summary()
        assert summary["guardrail_breaches"] == 0
        # the chunk controller's up-probes (256-pod bucket) were
        # guard-vetoed: one byte of headroom cannot fit a bigger bucket
        assert summary["guardrail_rejections"] >= 1
        # and the applied chunk never exceeded the guarded start value
        assert report.final_chunk_pods <= 128

    def test_drain_chunk_stays_group_aligned(self):
        from kubernetes_tpu.solver.exact import ExactSolverConfig

        clock = FakeClock()
        cs, s = self._scheduler(
            clock, solver=ExactSolverConfig(group_size=8)
        )
        _mk_pods(cs, 128)
        s.drain_backlog(chunk_pods=16)
        chunk = s.tuner.knob_values().get("backlog_chunk")
        # chunk started group-aligned (16 = 2 groups): every candidate
        # the controller may have applied stays a whole-group multiple
        assert chunk is not None and chunk % 8 == 0

    def test_tuned_profile_round_trips_through_standard_config(self):
        from kubernetes_tpu.config import types as config_types
        from kubernetes_tpu.tuning.profile import tuned_profile

        clock = FakeClock()
        cs, s = self._scheduler(clock)
        for c in range(12):
            _mk_pods(cs, 6, prefix=f"c{c}-")
            s.run_streaming(max_batches=50)
            clock.advance(1.0)
        doc = tuned_profile(s)
        cfg = config_types.load(doc)
        sched_cfg = config_types.scheduler_config(cfg)
        knobs = s.tuner.knob_values()
        assert sched_cfg.stream_depth == knobs["stream_depth"]
        assert sched_cfg.pipeline_split == knobs["pipeline_split"]
        assert sched_cfg.tuning is None  # standard config out: tuner off

    def test_stream_depth_applies_at_ring_drain_boundary(self):
        """An in-flight ring keeps the depth it was dispatched under:
        the loop's bound variable refreshes from config only when the
        ring is empty."""
        clock = FakeClock()
        cs, s = self._scheduler(clock, tuning=None)
        s.tuner = None  # drive the knob by hand
        s.config.stream_depth = 2
        _mk_pods(cs, 32)
        depths = []
        orig = s._dispatch_stream

        def spy(prep, **kw):
            depths.append(s.config.stream_depth)
            return orig(prep, **kw)

        s._dispatch_stream = spy
        s.run_streaming(max_batches=50)
        assert depths  # dispatches happened under depth 2
        # a live change takes effect on the next (ring-empty) entry
        s.config.stream_depth = 5
        _mk_pods(cs, 16, prefix="q")
        s.run_streaming(max_batches=50)
        assert s.config.stream_depth == 5


class TestFleetFlushKnob:
    def test_remote_exchange_buffer_cap_retargets(self):
        """The fleet_flush knob's application surface: the write-behind
        cap is an instance setting consulted on append, so a retarget
        at any moment is safe — a shrink below the live buffer simply
        flushes at the next mutation."""
        from kubernetes_tpu.fleet.runtime import RemoteOccupancyExchange

        calls = []

        class FakeClient:
            def hub_op(self, op, **meta):
                calls.append((op, meta))
                return {"version": 1}

            def close(self):
                pass

        ex = RemoteOccupancyExchange("x:1", "r0", client=FakeClient())
        assert ex._buffer_cap == RemoteOccupancyExchange._BUFFER_CAP
        ex.set_buffer_cap(2)
        from kubernetes_tpu.fleet.occupancy import PodRow

        def row(i):
            return PodRow(
                pod=f"default/p{i}", node="n0", zone="z0",
                namespace="default", labels=(),
            )

        ex.stage("r0", row(0))
        assert not any(op == "apply_ops" for op, _ in calls)
        ex.stage("r0", row(1))  # cap 2 reached -> one apply_ops flush
        flushes = [m for op, m in calls if op == "apply_ops"]
        assert len(flushes) == 1 and len(flushes[0]["ops"]) == 2

    def test_empty_knob_list_pins_everything(self):
        """Review-caught: `tuning: {knobs: []}` must mean "govern
        nothing" (the documented pin-everything recipe), not silently
        expand to all four knobs."""
        from kubernetes_tpu.config import types as config_types

        cfg = config_types.load("tuning: {enabled: true, knobs: []}")
        assert cfg.tuning.knobs == []
        sc = config_types.scheduler_config(cfg)
        assert sc.tuning.knobs == ()
        # absent key still means all knobs
        cfg2 = config_types.load("tuning: {enabled: true}")
        assert set(cfg2.tuning.knobs) == set(config_types.TUNABLE_KNOBS)

    def test_max_probes_parses_and_validates(self):
        from kubernetes_tpu.config import types as config_types

        cfg = config_types.load("tuning: {enabled: true, maxProbes: 5}")
        assert config_types.scheduler_config(cfg).tuning.max_probes == 5
        with pytest.raises(ValueError):
            config_types.load("tuning: {maxProbes: 0}")
        # TuningConfig.validate shares the SAME checker
        with pytest.raises(ValueError):
            TuningConfig(max_probes=0).validate()

    def test_config_flush_batch_threads_to_the_adapter(self):
        from kubernetes_tpu.config import types as config_types

        cfg = config_types.load(
            "fleet:\n  replica: r0\n  flushBatch: 64\n"
        )
        sc = config_types.scheduler_config(cfg)
        assert sc.fleet.flush_batch == 64
        import pytest

        with pytest.raises(ValueError):
            config_types.load("fleet:\n  replica: r0\n  flushBatch: -1\n")


class TestTuningInvariant:
    """Known-bad fixtures for sim/invariants.check_tuning: every clause
    must fire on a summary violating exactly it."""

    GOOD = {
        "probes": 4,
        "moves": 1,
        "max_knob_moves": 1,
        "settled": 1,
        "guardrail_breaches": 0,
        "shifts": 1,
        "batches_since_unsettle": 100,
        "settle_bound": 24,
        "knobs": {"stream_depth": 4},
    }

    def _violations(self, summary, **kw):
        from kubernetes_tpu.sim.invariants import check_tuning

        v = []
        check_tuning(0, v, summary=summary, **kw)
        return v

    def test_clean_summary_passes(self):
        assert self._violations(dict(self.GOOD), expect_shift=True) == []

    def test_never_engaged(self):
        v = self._violations(dict(self.GOOD, probes=0))
        assert len(v) == 1 and "never probed" in v[0].detail

    def test_unsettled(self):
        v = self._violations(dict(self.GOOD, settled=0))
        assert any("unsettled" in x.detail for x in v)
        # ... but NOT when the last unsettle (a late-detected shift)
        # left fewer batches than the structural settle bound: the
        # tuner is legitimately mid-re-convergence, not broken
        v2 = self._violations(
            dict(self.GOOD, settled=0, batches_since_unsettle=10)
        )
        assert v2 == []

    def test_guardrail_breach(self):
        v = self._violations(dict(self.GOOD, guardrail_breaches=2))
        assert any("guardrail breach" in x.detail for x in v)

    def test_knob_thrash(self):
        v = self._violations(dict(self.GOOD, max_knob_moves=40))
        assert any("thrash" in x.detail for x in v)

    def test_missed_shift(self):
        v = self._violations(dict(self.GOOD, shifts=0), expect_shift=True)
        assert any("never detected" in x.detail for x in v)
        # and not required when the profile never shifted
        assert (
            self._violations(dict(self.GOOD, shifts=0), expect_shift=False)
            == []
        )


class TestSimAcceptance:
    @pytest.mark.slow
    def test_tuning_convergence_profile_settles_and_reconverges(self):
        from kubernetes_tpu.sim.harness import run_sim

        res = run_sim("tuning_convergence", seed=0, cycles=24)
        assert res.ok, res.violations
        tu = res.summary["tuning"]
        assert tu["settled"] == 1
        assert tu["shifts"] >= 1
        assert tu["guardrail_breaches"] == 0
        assert res.tuned_profile is not None

    def test_tuning_convergence_deterministic(self):
        from kubernetes_tpu.sim.harness import run_sim

        a = run_sim("tuning_convergence", seed=3, cycles=10)
        b = run_sim("tuning_convergence", seed=3, cycles=10)
        assert a.trace.lines == b.trace.lines
        assert a.journal_lines == b.journal_lines
        assert a.summary["tuning"] == b.summary["tuning"]
