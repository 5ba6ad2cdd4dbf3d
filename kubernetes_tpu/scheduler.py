"""The scheduler orchestrator: batch-pop pods, one device solve, bind.

This is the TPU-shaped replacement of the reference's Scheduler object + run
loop (pkg/scheduler/scheduler.go#Scheduler.Run +
schedule_one.go#scheduleOne/#schedulingCycle/#bindingCycle):

    watch events ──> cache / queue            (eventhandlers.go semantics)
    pop_batch(K) ──> snapshot.update(cache)   (UpdateSnapshot, dirty columns)
              └──> exact solver (lax.scan over the K pods, dense over nodes)
    per assignment: assume -> bind -> finish_binding
                    bind failure -> forget + requeue with backoff
    infeasible    : AddUnschedulableIfNotPresent (+ nominated-node machinery
                    once preemption lands)

The assume/forget protocol and its crash-safety story carry over unchanged
(SURVEY §6.3): the solver holds no durable state — cache + snapshot rebuild
from the state service on restart.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
from dataclasses import dataclass, field

import numpy as np

from . import metrics
from .api.objects import Pod
from .framework.interface import CycleState, StatusCode
from .framework.runtime import WaitingPod
from .obs.span import _NOOP as _NOOP_SPAN
from .resilience import (
    ACT_BISECT,
    ACT_DESCEND,
    ACT_REBUILD,
    TIER_HOST,
    TIER_MESH,
    SolveCorruptError,
    SolveResilience,
    SolverFaultError,
    SolverReadError,
    build_ladder,
    host_greedy_assign,
    tier_device_context as _tier_device_context,
    validate_assignments,
)
from .server.extender_client import ExtenderError
from .solver.exact import (
    DeferredAssignments,
    ExactSolver,
    ExactSolverConfig,
    SessionDrainRequired,
)
from .solver.preemption import PreemptionEvaluator
from .state.cache import SchedulerCache
from .state.cluster import ApiError, ClusterState, Event
from .state.claim_allocator import ClaimAllocationError
from .state.volume_binder import VolumeBindingError
from .state.queue import PriorityQueue, QueuedPodInfo
from .state.snapshot import Snapshot
from .tensorize.plugins import (
    build_port_tensors,
    build_static_tensors,
    trivial_port_tensors,
)
from .tensorize.interpod import build_interpod_tensors
from .tensorize.spread import build_spread_tensors
from .tensorize.schema import build_pod_batch
from .utils.clock import Clock


@dataclass
class SchedulerConfig:
    batch_size: int = 1024  # max pods per device solve
    solver: ExactSolverConfig = field(default_factory=ExactSolverConfig)
    assume_ttl: float = 30.0
    # read-hiding batch split for run_pipelined: a popped batch may be
    # dispatched as up to K chained sub-solves so the assignment read of
    # sub-batch i overlaps the solve of i+1 (only the last read is not
    # overlapped). 0 = adaptive (split when the estimated device solve
    # time exceeds the estimated blocking read wait, from per-batch
    # EWMAs); 1 = never split; >1 = fixed cap per batch.
    pipeline_split: int = 0
    # streaming dispatcher (run_streaming): max dispatched-but-unapplied
    # batches in the device-side work ring. Popped batches tensorize,
    # stream down, and CHAIN on the previous batch's device-resident
    # occupancy carry (ExactSolver stream carry) while their deferred
    # assignment reads drain through the completion thread — the host
    # blocks on a device read once per ring drain (one per event-fence
    # in steady state), not once per batch. Depth bounds
    # both HBM held by in-flight solves and the bind latency a pod can
    # accrue behind later dispatches.
    stream_depth: int = 4
    # backlog drain (drain_backlog, ISSUE 12): pods per drain chunk fed
    # through the streaming ring against the resident session. 0 = plan
    # from the HBM budget model (solver/budget.py) starting at
    # batch_size; the planner halves group-aligned until the chunk's
    # per-device estimate fits the budget (auto-split instead of OOM).
    backlog_chunk_pods: int = 0
    # per-device HBM budget the drain planner asserts chunk shapes
    # against. 0 = auto (the device's PJRT bytes_limit; the CPU
    # backend reports none and gets solver/budget.py's default).
    hbm_budget_bytes: int = 0
    # mega-planner warm-start for drain_backlog (ISSUE 19): before the
    # first chunk pops, a convex-relaxation solve (solver/relax.py)
    # over the whole backlog ranks the activeQ so pods the relaxed
    # plan co-locates pop adjacently and chunks pack against
    # pre-fitted capacity instead of re-discovering it chunk by
    # chunk. Priority stays the primary queue key — the rank only
    # permutes pods within a priority band (queue.reorder_active).
    backlog_warm_start: bool = False
    # defaultpreemption: run the PostFilter dry-run for unschedulable pods
    enable_preemption: bool = True
    # node-axis mesh for the device solve (parallel/sharding.py): number
    # of devices to shard the node axis over. 0 = all visible devices,
    # 1 = force the single-device (unsharded) path, N > 1 = the first
    # min(N, visible) devices. A resolved count of 1 is the unsharded
    # path either way. The mesh threads through BOTH scheduling loops —
    # overlap, carry, and sync batches all dispatch sharded — and
    # results are bit-exactly device-count invariant
    # (tests/test_sharding.py). Note for tier-1: conftest forces 8
    # virtual CPU devices, so default-config Scheduler tests exercise
    # the SHARDED path; the UNSHARDED path keeps coverage through the
    # sim suite (SimHarness pins mesh_devices=1), the direct-solver
    # parity tests (ExactSolver defaults to mesh=None), and the
    # mesh_devices=1 arms of the equivalence tests.
    mesh_devices: int = 0
    # per-replica EXCLUSIVE mesh slice (fleet device-tier scale-out;
    # config key fleet.meshSlice = "rank/count"): (rank, count) cuts
    # the visible device list into count contiguous equal slices and
    # this scheduler dispatches ONLY against slice rank, so N fleet
    # replicas on one host own disjoint device sets (a 1-device slice
    # still builds a 1-way mesh — the mesh is what pins the device).
    # mesh_devices applies within the slice. None = no slice (the
    # sole-owner scheduler).
    mesh_slice: tuple | None = None
    # multi-profile (profile.NewMap): schedulerName -> solver config for
    # that profile; pods whose schedulerName matches no profile are ignored
    # at queue-add, like the reference's frameworkForPod miss. None = the
    # single default profile using `solver`.
    profiles: dict[str, ExactSolverConfig] | None = None
    # component-base/featuregate analog (--feature-gates); None = defaults
    feature_gates: object = None
    # KubeSchedulerConfiguration.extenders[] (config/types.py#Extender):
    # consulted during each solve via the outbound HTTP client
    # (server/extender_client.py) — filter/prioritize verdicts fold into
    # the per-class device tables; a bind-verb extender owns the binding
    extenders: tuple = ()
    # out-of-tree Scheduling Framework plugins (framework/interface.py),
    # classified by the extension-point protocols each implements:
    # Filter/Score (+ PreFilter incl. PreFilterResult allowlists) fold
    # into the per-class device tables each batch
    # (framework/runtime.py#fold_out_of_tree); PreEnqueue/QueueSort hook
    # the scheduling queue; PostFilter runs on the failure path after
    # default preemption; Reserve/Permit/PreBind/PostBind run host-side
    # around the bind, with Permit's WaitingPods map parking pods across
    # cycles — the in-process plugin registration point of SURVEY §8.2.
    out_of_tree_plugins: tuple = ()
    # observability (kubernetes_tpu/obs): an ObsConfig enabling span
    # tracing and/or the per-pod decision journal + flight recorder.
    # None = all off; the hot path then pays one attribute check per
    # would-be span and zero journal work.
    obs: object = None
    # degraded-mode solve resilience (kubernetes_tpu/resilience): a
    # ResilienceConfig tuning the fallback ladder (sharded mesh →
    # single device → CPU backend → pure-host serial greedy), the
    # per-profile circuit breaker in front of it, pre-apply output
    # validation, and the poison-batch bisection quarantine. None =
    # defaults (the layer is always on — it only acts on failures, so
    # the fault-free hot path is unchanged).
    resilience: object = None
    # fleet mode (kubernetes_tpu/fleet): a FleetConfig making this
    # scheduler ONE active replica of an N-way fleet. The replica's
    # informer stream is shard-filtered (its cache and snapshot hold
    # only the nodes its ring partition owns, and only the pending
    # pods the ring routes to it), every solved placement passes the
    # cross-shard occupancy admission before it is assumed, and
    # label-bearing placements are published to the fleet's occupancy
    # exchange. None = the classic sole-owner scheduler.
    fleet: object = None
    # process-lifecycle identity: which incarnation of this scheduler
    # role this process is. 1 = a first start; > 1 = a RESTART after a
    # crash — the cold-start recovery pass then treats cluster truth as
    # the wreck of a predecessor: unbound pods are re-adopted AND
    # terminally journaled `recovered` (so journal completeness holds
    # across incarnations), half-committed occupancy (claim
    # reservations for unbound pods, stale fleet pending rows) is
    # rolled back, and quarantine/breaker state deliberately RESETS
    # (the restart may be on healed hardware; a genuinely poison pod
    # re-quarantines through the ordinary bisection path within one
    # batch — tested).
    incarnation: int = 1
    # continuous rebalancer (kubernetes_tpu/rebalance): a
    # RebalanceConfig enabling the background defragmentation loop —
    # when the queues go idle and the interval elapses, detect
    # fragmentation from the snapshot, plan a consolidation target with
    # the pack-objective auction, and execute a bounded (churn-budget,
    # PDB-gated, fenced) migration plan through the eviction
    # subresource. None = off. Fleet replicas rebalance shard-scoped
    # (their cache IS their shard); a fence-revoked zombie incarnation
    # skips every pass.
    rebalance: object = None
    # closed-loop hot-path auto-tuning (kubernetes_tpu/tuning): a
    # TuningConfig enabling the online controllers that drive the
    # hot-path knobs (drain chunk size, stream_depth, pipeline_split,
    # fleet write-behind flush batch) from the measured counters —
    # bounded hill-climbing with hysteresis and settle detection, under
    # hard guardrails (a proposed drain chunk must pass the HBM budget
    # model before it is ever applied; stream-depth changes apply only
    # at ring-drain boundaries). None = static knobs. To pin ONE knob
    # while tuning the rest, set its config value and drop it from
    # TuningConfig.knobs.
    tuning: object = None
    # commit fencing (state/cluster.py fencing tokens): the lease role
    # this scheduler's binds are fenced under. The incarnation acquires
    # a fresh token at startup — superseding any predecessor — and
    # every bind carries it; a revoked/superseded token means the state
    # service rejects the commit with Conflict (scheduler_commit_fenced
    # _total) so a zombie can never double-bind. None = no fencing
    # (single-owner deployments that never restart in place); fleet
    # replicas default to their per-shard lease name.
    fence_role: str | None = None
    # gang scheduling (kubernetes_tpu/gang): a GangConfig enabling
    # all-or-nothing pod groups (the `scheduling.x-k8s.io/pod-group`
    # label + min-member annotation) — a gang's members pop as a unit,
    # solve through the ordinary chained sub-batch machinery, stage
    # through assume/Reserve/Permit like any pod, and then COMMIT AS
    # ONE: every member binds through ClusterState.bind_gang or every
    # member's placement is released and the gang requeues with a
    # `gang_incomplete` journal record. Carries the heterogeneity
    # objective too (gang/throughput.py). None = off (zero hot-path
    # cost beyond one attribute check per batch).
    gang: object = None


class _Rejected(Exception):
    """An out-of-tree Reserve/PreBind plugin returned a non-success
    status: the binding rolls back and the pod requeues with backoff."""


def _node_change_could_help(old, new) -> bool:
    """eventhandlers.go#nodeSchedulingPropertiesChange: allocatable, labels,
    taints, or spec.unschedulable changes can unblock parked pods; pure
    status-heartbeat updates cannot."""
    return (
        old.allocatable != new.allocatable
        or old.labels != new.labels
        or old.taints != new.taints
        or old.unschedulable != new.unschedulable
    )


@dataclass
class BatchResult:
    scheduled: list[tuple[str, str]] = field(default_factory=list)  # (pod, node)
    unschedulable: list[str] = field(default_factory=list)
    bind_failures: list[tuple[str, str]] = field(default_factory=list)  # (pod, err)
    # pods the poison-batch bisection quarantined this cycle: their
    # solve failure is isolated and terminal-journaled; they re-admit
    # after a TTL'd backoff (kubernetes_tpu/resilience)
    quarantined: list[str] = field(default_factory=list)
    # (pod, source node, target node) per rebalancer eviction this
    # cycle (kubernetes_tpu/rebalance): the pod re-entered the queue
    # with a nominated hint — the migration completes in later cycles
    rebalance_evictions: list[tuple[str, str, str]] = field(
        default_factory=list
    )
    # (pod, nominated node, victim keys) per successful preemption
    preemptions: list[tuple[str, str, list[str]]] = field(default_factory=list)
    # pod keys whose gang round failed all-or-nothing this cycle: their
    # staged placements were released and they requeued as a unit with
    # a `gang_incomplete` journal record (kubernetes_tpu/gang)
    gang_released: list[str] = field(default_factory=list)
    solve_seconds: float = 0.0
    host_seconds: float = 0.0
    # per-pod schedule latency (pop -> bind committed), for the p99 metric
    latencies: list[float] = field(default_factory=list)
    # per-pod end-to-end latency (first queue entry -> bind committed, on
    # the scheduler clock): what the SLO engine (obs/slo.py) and
    # `python -m kubernetes_tpu perf` read
    e2e_latencies: list[float] = field(default_factory=list)
    # perf_counter when this batch's bindings finished committing; lets
    # throughput collectors sample pods/s across overlapped batches
    completed_at: float = 0.0

    @property
    def progressed(self) -> bool:
        """Did this cycle do ANY work a drive loop should keep ticking
        for? One definition for every drain/settle loop, so a new
        outcome field can't silently go missing from some call sites."""
        return bool(
            self.scheduled
            or self.unschedulable
            or self.bind_failures
            or self.quarantined
            or self.rebalance_evictions
            or self.gang_released
        )


@dataclass
class BacklogDrainReport:
    """What one ``Scheduler.drain_backlog`` pass did, for the sim
    footer and operators (the same numbers back the
    ``scheduler_backlog_*`` metrics). ``results`` holds the underlying
    per-chunk BatchResults so callers can fold them into their own
    accounting (the sim's bind tracker)."""

    pods: int = 0  # backlog size at drain start
    drained: int = 0  # pods bound by this pass
    unschedulable: int = 0
    chunks: int = 0  # streaming batches dispatched
    chunk_pods: int = 0  # planned chunk size (post budget splits)
    # chunk size at drain end when the auto-tuner governed the knob
    # (kubernetes_tpu/tuning); 0 = untuned (chunk_pods held throughout)
    final_chunk_pods: int = 0
    budget_splits: int = 0  # halvings the HBM planner took
    budget_bytes: int = 0  # per-device budget asserted against
    drain_seconds: float = 0.0
    pods_per_sec: float = 0.0
    stream_chained_batches: int = 0  # cross-batch carry chains engaged
    chain_fraction: float = 0.0  # chained / (chunks - 1)
    estimated_per_device_bytes: int = 0  # HBM model, resident worst case
    estimated_h2d_bytes: int = 0  # HBM model's predicted upload total
    measured_h2d_bytes: int = 0  # h2d counter delta over the drain
    # mega-planner warm-start (ISSUE 19): activeQ entries re-keyed by
    # the relaxed plan's rank (0 = warm-start off or nothing ranked)
    warm_start_ranked: int = 0
    relax_iterations: int = 0  # dual-ascent iterations the warm-start ran
    relax_residual: float = 0.0  # final relative-overcommit residual
    results: list = field(default_factory=list)


@dataclass
class _PreparedGroup:
    """Everything one profile sub-batch needs between tensorization and
    result application, so the two phases can run on opposite sides of a
    deferred device read (run_pipelined). For the synchronous path the
    phases run back to back and this is pure plumbing."""

    profile: str
    infos: list
    pods: list
    cycle_offsets: list
    base_cycle: int
    t0: float  # cycle start (per-pod latency base)
    gs: float  # tensorize start (attempt-duration base)
    batch: object
    pbatch: object
    static: object
    ports: object
    spread: object
    interpod: object
    nominated: object
    nominated_slot: object
    slot_nodes: list
    names: list  # snapshot slot->name mapping AT PREP TIME (fence-stable)
    volume_ctx: object
    services: list
    dra_active: bool
    fence: int = 0  # _conflict_seq INSIDE the tensorize lock (the snapshot
    # consistency point — capturing it any later would mask events landing
    # between lock release and dispatch; review-caught)
    # the occupancy fence (_occupancy_seq at tensorize time): bumped by
    # events only HARD-shaped batches are sensitive to — assigned-pod
    # deletes / label changes that free or re-key port/spread/interpod
    # occupancy, external DRA claim writes, waiting-pod rollbacks.
    # (Nominator-map changes deliberately do NOT bump it: nominated load
    # is advisory, and our own preemption nominations land mid-apply —
    # see _ingest_event.) Plain fit batches ignore it (the device fit
    # carry absorbs frees conservatively), so delete-churn cannot
    # degrade the plain pipeline.
    occ_fence: int = 0
    occ_sensitive: bool = False  # batch reads occupancy/ctx the occ
    # fence guards (ports/spread/interpod/volumes/DRA/nominated)
    step: int = 0  # the batch's span/trace id (Scheduler._trace_step)
    tensorize_seconds: float = 0.0  # host prep cost (set at dispatch)
    unsched_reason: dict = field(default_factory=dict)
    dra_prefold: dict = field(default_factory=dict)
    # pre-apply validation accumulator (resilience.validate_assignments):
    # per-slot usage this prep's already-validated flights placed, the
    # host mirror of the device-resident chain carry. Built lazily on
    # the first validated flight.
    validated_usage: object = None
    # tensorize-duration metrics observed (once per prep: ladder-rung
    # retries reuse the prep, and re-observing would inflate the
    # tensorize/PreFilter histograms exactly when operators are
    # reading them to diagnose an outage)
    timing_observed: bool = False
    # the open telemetry stage "tensorize" (None with telemetry off, and
    # once the first dispatch has closed it)
    tensorize_stage: object = None


@dataclass
class _InFlightSolve:
    """A dispatched solve whose assignments may not have been read yet.
    Its conflict fence is ``prep.fence`` — captured inside the tensorize
    lock, NOT at dispatch (re-reading _conflict_seq any later would mask
    events landing between lock release and dispatch).

    A chained sub-batch solve (the RTT-hiding batch split) shares one
    prep with its siblings and covers only prep pods [lo, hi); the
    unsplit case is the trivial slice [0, None). ``tensorize_share`` is
    the portion of the shared tensorize cost this flight reports (full
    for the first sub-flight, 0 for the rest)."""

    prep: _PreparedGroup
    handle: object  # np.ndarray (sync) | DeferredAssignments (pipelined)
    dispatch_seconds: float
    read_seconds: float = 0.0  # blocking device-read wait (set at apply)
    lo: int = 0
    hi: int | None = None
    tensorize_share: float | None = None  # None = prep.tensorize_seconds

    def infos(self) -> list:
        return self.prep.infos[self.lo : self.hi]

    def pods(self) -> list:
        return self.prep.pods[self.lo : self.hi]

    def cycle_offsets(self) -> list:
        return self.prep.cycle_offsets[self.lo : self.hi]

    # sanctioned deferred-read point (analysis/registry.py) — the ONE
    # place the apply path may block on the device: ktpu: hot
    def assignments(self) -> np.ndarray:
        if isinstance(self.handle, DeferredAssignments):
            return self.handle.get()
        return self.handle


@dataclass
class _StreamSlot:
    """One dispatched batch in the streaming dispatcher's bounded work
    ring (run_streaming): the prep — whose ``fence``/``occ_fence``
    captures are this slot's discard EPOCH, the per-stream-slot
    refinement of the global ``_conflict_seq``/``_occupancy_seq``
    discard windows — plus the slot's in-flight sub-solves. A
    conflicting event invalidates exactly the slots whose epoch
    predates it; slots chained on a discarded slot share its epoch (the
    chain is only ever extended inside one fence window), so the
    discard cascade is structural, never a separate bookkeeping pass.
    ``carried`` marks whether the dispatch left the session's stream
    carry resident for the next batch to chain on (nominated batches
    never do)."""

    prep: _PreparedGroup
    flights: list
    carried: bool


class Scheduler:
    # consecutive fence discards before run_pipelined falls back to one
    # synchronous (fence-free) cycle — the pipelined loop's livelock
    # backstop under sustained capacity/mask event churn (ADVICE r5 #2)
    _PIPELINE_FALLBACK_AFTER = 3

    def __init__(
        self,
        cluster: ClusterState,
        config: SchedulerConfig | None = None,
        clock: Clock | None = None,
    ):
        self.cluster = cluster
        self.config = config or SchedulerConfig()
        self.clock = clock or Clock()
        # span/batch id shared by the jax-profiler step annotation and
        # the obs span layer — initialized here instead of being
        # conjured via getattr at the call site, so profiler steps and
        # trace spans number identically
        self._trace_step = 0
        from .obs import build_obs

        # tracer (span layer), per-pod decision journal, flight
        # recorder — a disabled tracer and two Nones unless config.obs
        # turns them on
        self.obs, self.journal, self.flight = build_obs(
            self.config.obs, self.clock
        )
        # compile observability (obs/compile.py): the process-wide
        # XLA-compile watcher — dispatch brackets attribute compiles to
        # their shape scope; always on (it only costs work when a
        # compile already happened)
        from .obs.compile import WATCHER as _compile_watcher

        _compile_watcher.install()
        self._compile_watcher = _compile_watcher
        # live SLO engine (obs/slo.py): sliding-window p50/p99 pod
        # latency, bind throughput, multi-window error-budget burn —
        # ticked from _record_metrics off numbers the loops already
        # compute. None = off (the production default).
        self.slo = None
        if self.config.obs is not None and getattr(
            self.config.obs, "slo", None
        ) is not None:
            from .obs.slo import SloEngine

            self.slo = SloEngine(self.config.obs.slo, self.clock)
            self.slo.on_health_change.append(self._on_slo_health)
        # degraded-flag combiner: the fleet exchange's degraded flag is
        # the OR of the solve breaker's state and the SLO engine's
        # health — either signal routes handoff refugees elsewhere,
        # and neither may clear the flag while the other still holds it
        self._breaker_degraded = False
        self._slo_degraded = False
        # flight telemetry (obs/{profile,timeseries,sentinel,bundle}):
        # continuous per-stage profiler + anomaly sentinel + capture-
        # on-anomaly replay bundles, one coordinator ticked from the
        # commit seam. None = off (the production default) — the hot
        # path then pays a single attribute check per seam.
        from .obs import build_telemetry

        self.telemetry = build_telemetry(
            self.config.obs,
            self.clock,
            journal=self.journal,
            recorder=self.flight,
        )
        self._sentinel_degraded = False
        # high-volume span-family sampling state (see _on_event and
        # _commit_all): deterministic counters, first occurrence
        # always sampled
        self._enqueue_events = 0
        self._enqueue_sample_n = (
            max(int(self.config.obs.enqueue_span_sample_n), 1)
            if self.config.obs is not None
            else 1
        )
        self._bind_commits = 0
        self._bind_sample_n = (
            max(int(self.config.obs.bind_span_sample_n), 1)
            if self.config.obs is not None
            else 1
        )
        # True inside a held run (held_run: the commit pass's, or the
        # ingest handler's): whoever holds cluster.lock for the run
        # owns it, the watch events inside are the run's own, and the
        # pending gauge is refreshed once, at its end
        self._in_held_run = False  # ktpu: guarded-by(cluster.lock)
        # labels() is a lock and a dict walk: the histogram children
        # the pass observes per pod are looked up once each, at first
        # use (_pass_child)
        self._pass_children: dict = {}
        self._commit_path_children = {
            held: metrics.bind_commits_total.labels(path)
            for held, path in ((True, "held"), (False, "wire"))
        }
        # fleet runtime (kubernetes_tpu/fleet): partition view, shard
        # watch filter, occupancy exchange client. Built before the
        # initial informer sync so the sync itself is shard-scoped.
        self.fleet = None
        self._span_tags: dict = {}
        if self.config.fleet is not None:
            from .fleet.runtime import FleetRuntime

            self.fleet = FleetRuntime(
                self.config.fleet, cluster, self.clock
            )
            # fleet-tagged observability: every journal record and the
            # per-batch root span carry the replica identity
            self._span_tags = {"replica": self.fleet.replica}
            if self.journal is not None:
                self.journal.tags["replica"] = self.fleet.replica
        if self.journal is not None:
            # journey-trace origin: the identity minted into each
            # pod's trace id at its FIRST record — replica-qualified in
            # fleet mode so a cross-replica trace names where the
            # journey started (the handoff row then ships it onward)
            self.journal.origin = (
                f"{self.fleet.replica if self.fleet is not None else 's'}"
                f"-{self.config.incarnation}"
            )
        if self.config.incarnation > 1:
            # restarted incarnations tag every record/span so a merged
            # cross-incarnation journal attributes each record to the
            # process that wrote it (first starts stay tag-free: their
            # journal bytes must not change under a config default)
            self._span_tags["incarnation"] = self.config.incarnation
            if self.journal is not None:
                self.journal.tags["incarnation"] = self.config.incarnation
        import logging

        self._log = logging.getLogger("kubernetes_tpu.scheduler")
        from .utils.featuregate import FeatureGates

        self.feature_gates = self.config.feature_gates or FeatureGates()
        self.cache = SchedulerCache(self.clock, assume_ttl=self.config.assume_ttl)
        # classify the flat out-of-tree plugin set by extension point
        from .framework.interface import Registry

        self.registry = Registry.classify(self.config.out_of_tree_plugins)

        def _pre_enqueue(pod: Pod) -> bool:
            for p in self.registry.pre_enqueue:
                if not p.pre_enqueue(pod).is_success:
                    return False
            return True

        qs = self.registry.queue_sort
        self.queue = PriorityQueue(
            self.clock,
            honor_scheduling_gates=self.feature_gates.enabled(
                "PodSchedulingReadiness"
            ),
            pre_enqueue=_pre_enqueue if self.registry.pre_enqueue else None,
            less=qs[0].less if qs else None,
        )
        # cached pending_pods gauge children: the gauge refreshes on
        # every queue transition (including per watch event), so the
        # label lookup must not be paid each time
        self._pending_gauges = {
            name: metrics.pending_pods.labels(name)
            for name in ("active", "backoff", "unschedulable", "gated")
        }
        # Permit WaitingPods map (runtime/waiting_pods_map.go): pod key ->
        # (WaitingPod, its QueuedPodInfo, scheduling cycle, CycleState,
        # pop timestamp). Verdicts recorded via WaitingPod.allow/reject
        # apply at the start of the next scheduling cycle.
        self._waiting: dict[str, tuple] = {}
        # outbound extender clients, configured order (extender.go)
        from .server.extender_client import HTTPExtenderClient

        self.extender_clients = tuple(
            HTTPExtenderClient(e) for e in self.config.extenders
        )
        # fold_out_of_tree memo (VERDICT r3 #8): signature -> (mask,
        # extra_score) outputs; LRU-capped at 8 like the class-table cache
        self._fold_cache: dict = {}
        # pods popped this cycle and not yet resolved: the unlocked solve
        # window means a MODIFIED watch event can arrive for a pod that is
        # neither queued nor waiting — without this map queue.update would
        # re-add it and double-schedule (review-caught)
        self._in_flight: dict[str, QueuedPodInfo] = {}  # ktpu: guarded-by(cluster.lock)
        # fence for the double-buffered loop (run_pipelined): bumped by any
        # watch event that could invalidate a dispatched-but-unapplied
        # solve (node capacity/mask changes, external pod placements). A
        # deferred solve whose fence no longer matches is discarded.
        self._conflict_seq = 0  # ktpu: guarded-by(cluster.lock)
        # occupancy fence for HARD-shaped deferred solves (ports/spread/
        # interpod/volumes/DRA/nominated): bumped by events that free or
        # re-key occupancy the shape's carried state cannot absorb —
        # assigned-pod deletes, assigned-pod label changes, external DRA
        # claim writes, nominator-map changes. Kept separate from
        # _conflict_seq so delete-churn never discards plain fit solves
        # (whose device carry absorbs frees conservatively).
        self._occupancy_seq = 0  # ktpu: guarded-by(cluster.lock)
        # the tuning layer's measurement surface (kubernetes_tpu/tuning):
        # ONE window of per-batch counter samples, which also owns the
        # RTT / per-pod-solve EWMAs the adaptive pipeline-split rule
        # reads (formerly private _rtt_ewma/_pod_solve_ewma — moved so
        # the split rule and the split controller can never fight over
        # the knob from two estimates). Always built: without a tuner
        # it costs one note_read per blocking flight, nothing per batch.
        from .tuning.window import CounterWindow

        self.window = CounterWindow(self.clock)
        # closed-loop auto-tuning runtime (SchedulerConfig.tuning):
        # per-knob hill-climb controllers ticked once per applied batch
        # from _record_metrics. None = static knobs.
        self.tuner = None
        if self.config.tuning is not None:
            from .tuning.runtime import TuningRuntime

            self.tuner = TuningRuntime(
                self.config.tuning, self.window, self.clock
            )
        # streaming dispatcher (run_streaming) infrastructure: the
        # completion thread + its handle queue are created lazily on the
        # first streaming cycle.
        self._completion_thread = None
        self._completion_q = None
        self._streaming_active = False
        # backlog drain (drain_backlog): while active, dispatch spans
        # and journal records carry the drain-chunk id (prep.step -
        # base) so `obs explain` attributes a pod to the chunk that
        # placed it. Driver thread only; _note_drain_chunk points the
        # journal tag at the chunk about to write records.
        self._backlog_drain_active = False
        self._drain_chunk_base = 0
        # reusable port-occupancy staging (tensorize/plugins.PortStaging):
        # consecutive tensorizes against an unchanged cache — exactly the
        # streaming burst window — skip the placed-pod port re-scan
        from .tensorize.plugins import PortStaging

        self._port_staging = PortStaging()
        # profiles whose deferred solve was discarded: that profile's
        # device session carried the discarded placements and must
        # re-upload from host truth before its next dispatch (done at
        # _dispatch_group once no other solve is in flight). A set, not
        # a bool: multi-profile configs pipeline too, and healing the
        # WRONG profile's session would leave the polluted carry live.
        self._session_stale = set()  # ktpu: guarded-by(cluster.lock)
        # consecutive fence discards with no successful apply (driver
        # thread only — never touched by watch ingest): once it reaches
        # _PIPELINE_FALLBACK_AFTER, run_pipelined falls back to one
        # synchronous cycle so sustained event churn cannot livelock the
        # pipelined loop (ADVICE r5 #2). The streak counts PREPS, not
        # sub-flights: one event discarding a whole K-sub-batch chain is
        # ONE conflicting window, and counting it K times would engage
        # the fence-free backstop off a single isolated event
        # (review-caught); _last_discard_step dedupes within a chain —
        # an int, not the prep itself, so a discarded batch's tensors
        # aren't pinned on this 1-vCPU host until the next apply.
        self._discard_streak = 0
        self._last_discard_step = -1
        # sim/fault-injection seam (kubernetes_tpu/sim): called with the
        # in-flight solve right after every dispatch, while NO lock is
        # held — the one real boundary where a concurrent actor's watch
        # events can land between a solve's dispatch and its apply. The
        # simulator delivers delayed watch events here to exercise the
        # conflict fence and the livelock backstop deterministically.
        self._post_dispatch_hook = None
        # node-axis solve mesh (SchedulerConfig.mesh_devices): resolved
        # once — every dispatch (overlap/carry/sync, all profiles) runs
        # against it. None = single-device. The snapshot's node padding
        # is forced to a device-count multiple so the trailing node axis
        # always shards evenly; padded rows stay masked unschedulable.
        from .parallel.sharding import resolve_mesh

        self.mesh = resolve_mesh(
            self.config.mesh_devices, self.config.mesh_slice
        )
        self._mesh_devices = (
            int(self.mesh.size) if self.mesh is not None else 1
        )
        metrics.mesh_devices.set(self._mesh_devices)
        # fleet device-tier scale-out: the devices this replica's
        # EXCLUSIVE slice owns (0 = no slice configured)
        metrics.fleet_mesh_slice_devices.set(
            self._mesh_devices if self.config.mesh_slice is not None else 0
        )
        # degraded-mode solve resilience (kubernetes_tpu/resilience):
        # the fallback ladder + per-profile circuit breaker both
        # scheduling loops dispatch through, pre-apply output
        # validation, and the poison-batch quarantine. In fleet mode a
        # breaker trip publishes the replica's degraded flag through
        # the occupancy exchange so peers route refugees elsewhere.
        self.resilience = SolveResilience(
            self.config.resilience,
            self.clock,
            build_ladder(self.mesh is not None),
            # the combiner ORs the breaker's state with the SLO
            # engine's health before publishing the fleet degraded
            # flag (no-op without a fleet runtime)
            on_degraded=self._on_breaker_degraded,
        )
        # poison-batch quarantine: pod key -> (QueuedPodInfo, release
        # time). Entries re-admit through _release_quarantine at the
        # next pop once their TTL'd backoff elapses.
        self._quarantine: dict[str, tuple] = {}  # ktpu: guarded-by(cluster.lock)
        self._quarantine_counts: dict[str, int] = {}  # ktpu: guarded-by(cluster.lock)
        # gang scheduling (kubernetes_tpu/gang): assembly/retry tracker
        # plus the per-batch all-or-nothing round ledger. A round is
        # created when a complete gang enters a batch (gang id ->
        # {"expect": member keys, "done": resolved keys, "staged":
        # approved pending entries, "failed": bool, "reason": str}) and
        # resolves in _commit_all: every member staged -> ONE atomic
        # bind_gang commit; any member failed -> every staged placement
        # releases and the gang requeues (journal `gang_incomplete`).
        from .gang import GangTracker

        self._gang = (
            GangTracker(self.config.gang)
            if self.config.gang is not None
            else None
        )
        self._gang_rounds: dict[str, dict] = {}  # ktpu: guarded-by(cluster.lock)
        # ladder tier each profile last dispatched at: a tier change
        # moves the solve to different devices, so the resident session
        # must re-upload from host truth (driver thread only)
        self._tier_last: dict[str, str] = {}
        # sim/fault-injection seam (kubernetes_tpu/sim): called with
        # (pods, tier) right before every solve attempt at every ladder
        # tier — dispatch, probe, bisection sub-solve, host rung. May
        # raise to inject a solver-boundary fault deterministically.
        self._solve_fault = None
        # continuous rebalancer (kubernetes_tpu/rebalance): ticked by
        # both loops at idle cycle boundaries; None = off
        self.rebalancer = None
        if self.config.rebalance is not None:
            from .rebalance.runtime import Rebalancer

            self.rebalancer = Rebalancer(self.config.rebalance, self.clock)
        self.snapshot = Snapshot()
        self.snapshot.pad_multiple = self._mesh_devices
        from .state.volume_binder import VolumeBinder

        self.volume_binder = VolumeBinder(cluster)
        # dynamicresources plugin (behind the DynamicResourceAllocation
        # gate): the claim allocator is this framework's Reserve/PreBind
        # half; the filter half folds DraContext masks into the static
        # tables per batch
        from .state.claim_allocator import ClaimAllocator

        self.claim_allocator = ClaimAllocator(cluster)
        self._dra = self.feature_gates.enabled("DynamicResourceAllocation")
        # profile map: schedulerName -> solver (profile/profile.go#NewMap)
        from .api.objects import DEFAULT_SCHEDULER_NAME

        profile_cfgs = self.config.profiles or {
            DEFAULT_SCHEDULER_NAME: self.config.solver
        }
        self.solvers = {
            name: ExactSolver(cfg) for name, cfg in profile_cfgs.items()
        }
        self.solver = next(iter(self.solvers.values()))
        if self.telemetry is not None and self.telemetry.bundles is not None:
            # telemetry input-snapshot hook: every profile solver hands
            # its resolved solve inputs to the bundle capturer (the
            # capturer only retains them for batches the scheduler
            # armed, so host-tier/bisection solves don't capture)
            for s in self.solvers.values():
                s.capture_hook = self.telemetry.bundles.on_solve_input
        self.preemptor = PreemptionEvaluator()

        # nominated-pod index (the reference's nominator map): unbound pods
        # carrying status.nominatedNodeName, maintained from watch events so
        # the per-batch lookup is O(nominated), not O(all pods)
        self.nominated_pods: dict[str, Pod] = {}

        # commit fencing: the bind-path fence token for this incarnation
        # (state/cluster.py fencing tokens). Fleet replicas fence under
        # their per-shard lease identity by default, so a replica whose
        # lease a peer observed stale is fenced the moment the peer
        # commits the membership change at the state service.
        self._fence_role = self.config.fence_role
        if self._fence_role is None and self.fleet is not None:
            self._fence_role = self.fleet.lease_name
        self._fence_token = 0
        self._fenced_commits = 0  # ktpu: guarded-by(cluster.lock)
        # sim seam: called with the approved pending list right before
        # the binding cycle of a batch commits — the "after assume,
        # before bind" point a crash-restart drive kills the process at
        self._pre_commit_hook = None
        # the cold-start recovery pass: initial informer sync
        # (WaitForCacheSync equivalent) — atomic with the subscription
        # so a concurrent writer can't slip an object between the list
        # and the watch start — plus, on a RESTART (incarnation > 1),
        # orphan re-adoption, half-committed occupancy rollback, and
        # terminal `recovered` journaling. One root span + one
        # structured log line + scheduler_restart_recovery_seconds.
        self._recover()

    def _recover(self) -> None:
        """Cold-start recovery: rebuild every piece of incarnation-local
        scheduler state from ``ClusterState`` truth.

        All starts: shard-scoped cache/queue/nominator sync + watch
        subscription + (fleet) inventory/row publication, exactly the
        WaitForCacheSync contract.

        Restarts (``config.incarnation > 1``) additionally treat truth
        as a predecessor's wreck:

        - every unbound routed pod is RE-ADOPTED and terminally
          journaled ``recovered`` — a pod the dead incarnation left
          mid-flight (assumed, Permit-parked, popped, deferred-solved)
          has a dangling non-terminal journal history that no process
          will ever continue; the recovered record closes it so journal
          completeness holds across incarnations;
        - half-committed occupancy rolls back: resource-claim
          reservations naming unbound routed pods (a crash between the
          PreBind claim write and the bind commit) are released exactly
          like the deallocating controller would on pod delete, and a
          fleet replica's exchange rows are rebuilt wholesale from
          truth (a predecessor's stale PENDING rows would distort
          peers' admission forever);
        - quarantine and breaker state deliberately RESET rather than
          re-derive: both guard against *this process's* observed
          hardware/data failures, the restart may be on healed hardware
          or a fixed build, and the cost of being wrong is one cheap
          re-discovery (a poison pod re-quarantines via bisection in
          its first batch — tested in tests/test_restart_recovery.py),
          while persisting them would let a stale breaker pin a healthy
          scheduler to its degraded ladder rung indefinitely.
        """
        cluster = self.cluster
        restart = self.config.incarnation > 1
        t_rec = self.clock.perf()
        adopted = recovered = claims_rolled = 0
        span_tags = dict(self._span_tags)
        span_tags.setdefault("incarnation", self.config.incarnation)
        with cluster.lock, self.obs.span(
            "recover", trace_id=self._trace_step, restart=restart,
            **span_tags,
        ) as rsp:
            if self._fence_role is not None:
                self._fence_token = cluster.grant_fence(
                    self._fence_role,
                    holder=f"incarnation-{self.config.incarnation}",
                )
            for node in cluster.list_nodes():
                if self.fleet is None or self.fleet.owns_node(node.name):
                    self.cache.add_node(node)
            gangs_rolled = 0
            if restart and self._gang is not None:
                # half-staged gang rollback BEFORE pod adoption: a crash
                # between a gang's member binds (or between a fleet
                # stage and the gang commit) can leave a STRICT SUBSET
                # of a pod group bound — exactly the partial gang the
                # all-or-nothing contract forbids. Evict the stranded
                # members we own (delete+recreate collapses to unbound
                # under the same identity), so the adoption loop below
                # re-queues them and the gang reassembles whole. Runs
                # before `subscribe`, so the eviction's DELETED/ADDED
                # pair reaches no one — adoption sees post-rollback
                # truth directly.
                gangs_rolled = self._rollback_partial_gangs()
            for pod in cluster.list_pods():
                if pod.node_name:
                    if self.fleet is None or self.fleet.owns_node(
                        pod.node_name
                    ):
                        self.cache.add_pod(pod)
                else:
                    if self.fleet is not None and not self.fleet.routes_pod(
                        pod.key, pod
                    ):
                        continue
                    if pod.nominated_node_name:
                        self.nominated_pods[pod.key] = pod
                    if pod.scheduler_name in self.solvers:
                        self.queue.add(pod)
                        adopted += 1
                        if restart:
                            recovered += 1
                            if self.journal is not None:
                                self.journal.record(
                                    self._trace_step, 0, pod, "recovered",
                                    reason=(
                                        "re-adopted by incarnation "
                                        f"{self.config.incarnation} after "
                                        "a crash orphaned the pod"
                                        + (
                                            "; orphaned nomination on "
                                            + pod.nominated_node_name
                                            if pod.nominated_node_name
                                            else ""
                                        )
                                    ),
                                )
            if restart and self._dra:
                claims_rolled = self._rollback_orphan_claims()
            cluster.subscribe(
                self._on_event,
                filter=self.fleet.event_filter
                if self.fleet is not None
                else None,
            )
            if self.fleet is not None:
                self.fleet.publish_inventory()
                # rebuild this replica's exchange rows from truth: a
                # prior incarnation's stale PENDING rows (assumed but
                # never bound) roll back here, wholesale
                self.fleet.rebuild_pod_rows(self.cache)
                metrics.fleet_owned_nodes.set(len(self.cache.nodes))
            rsp.set(
                adopted=adopted, recovered=recovered,
                claims_rolled_back=claims_rolled,
                gangs_rolled_back=gangs_rolled,
            )
        dt = self.clock.perf() - t_rec
        metrics.restart_recovery_seconds.observe(dt)
        self._log.info(
            "recovery pass complete: incarnation %d %s %d pod(s), "
            "journaled %d recovered record(s), rolled back %d "
            "half-committed claim reservation(s) in %.3fs",
            self.config.incarnation,
            "re-adopted" if restart else "adopted",
            adopted, recovered, claims_rolled, dt,
            extra={"step": self._trace_step},
        )

    # runs inside _recover's locked region: ktpu: holds(cluster.lock)
    def _rollback_orphan_claims(self) -> int:
        """Release resource-claim reservations naming unbound pods this
        scheduler routes: only a crash between the PreBind claim write
        (``bind_pod_claims``) and the bind commit can produce one, so
        the reservation is half-committed occupancy — roll it back the
        way the deallocating controller would on pod delete. Pods this
        scheduler does not own are never touched: fleet PEERS' routed
        pods (a live peer may be mid-bind on them right now) and pods
        of FOREIGN schedulers (``spec.schedulerName`` outside our
        profiles — their scheduler may be between its own PreBind
        claim write and bind this instant)."""
        rolled = 0
        for c in list(self.cluster.list_resource_claims()):
            if not c.reserved_for:
                continue
            stale = []
            for key in c.reserved_for:
                ns, name = key.split("/", 1)
                try:
                    pod = self.cluster.get_pod(ns, name)
                except ApiError:
                    stale.append(key)  # reserved for a deleted pod
                    continue
                if pod.node_name:
                    continue  # bound: the reservation is legitimate
                if pod.scheduler_name not in self.solvers:
                    continue  # a foreign scheduler's pod: not ours
                if self.fleet is not None and not self.fleet.routes_pod(
                    key, pod
                ):
                    continue  # a peer's pod: leave it alone
                stale.append(key)
            if not stale:
                continue
            c.reserved_for = tuple(
                k for k in c.reserved_for if k not in stale
            )
            if not c.reserved_for:
                c.allocated_node = ""
                c.results = ()
            self.cluster.update_resource_claim(c)
            rolled += 1
        return rolled

    # runs inside _recover's locked region: ktpu: holds(cluster.lock)
    def _rollback_partial_gangs(self) -> int:
        """Restart-only: find pod groups where 0 < bound members <
        min-member — a predecessor crashed mid-gang (between member
        binds, or between a fleet stage and the atomic commit) — and
        evict the stranded bound members this scheduler owns, so the
        whole gang returns to Pending and reassembles atomically.
        Members on peer-owned nodes are left alone (the peer's own
        restart pass rolls its shard back); PDB-gated evictions (429)
        are tolerated per pod — the gang then completes on a later
        pass rather than losing protected members."""
        from .gang import GangTracker

        groups: dict[str, list] = {}
        for pod in self.cluster.list_pods():
            gid = GangTracker.gang_of(pod)
            if gid is not None:
                groups.setdefault(gid, []).append(pod)
        rolled = 0
        for gid in sorted(groups):
            members = groups[gid]
            bound = [p for p in members if p.node_name]
            if not bound:
                continue
            need = max(GangTracker.min_member(p) for p in members)
            if len(bound) >= need:
                continue  # complete (or over-satisfied): legitimate
            evicted = 0
            for p in bound:
                if self.fleet is not None and not self.fleet.owns_node(
                    p.node_name
                ):
                    continue
                try:
                    self.cluster.evict(
                        p.namespace,
                        p.name,
                        fence=(self._fence_role, self._fence_token)
                        if self._fence_role is not None
                        else None,
                    )
                    evicted += 1
                except ApiError as e:
                    self._log.warning(
                        "gang rollback: could not evict stranded "
                        "member %s of %s: %s", p.key, gid, e,
                    )
            if evicted:
                rolled += 1
                metrics.gang_incomplete_total.inc()
                self._log.info(
                    "gang rollback: pod group %s had %d/%d members "
                    "bound at restart; evicted %d stranded member(s) "
                    "back to Pending", gid, len(bound), need, evicted,
                )
        return rolled

    # -- degraded-health combiner (breaker state OR SLO health) --

    def _on_breaker_degraded(self, degraded: bool) -> None:
        """SolveResilience transition hook: the first breaker trip /
        last re-close. Publishes through the combiner so an
        SLO-degraded replica stays flagged even while its breakers are
        closed."""
        self._breaker_degraded = degraded
        if degraded and self.telemetry is not None:
            # forensic capture at the trip: the batch that tripped the
            # breaker is the newest complete solve record
            self.telemetry.capture("breaker")
        self._publish_degraded()

    def _on_slo_health(self, healthy: bool) -> None:
        """SloEngine health-flip hook: the error budget started (or
        stopped) burning past the threshold. Feeds the resilience
        layer — a half-open breaker defers its top-tier probe while
        the SLO is already degraded — and the fleet degraded flag, so
        handoff chains route refugees to replicas that are actually
        meeting their SLOs."""
        self._slo_degraded = not healthy
        self.resilience.set_slo_degraded(not healthy)
        self._publish_degraded()

    def _publish_degraded(self) -> None:
        if self.fleet is not None:
            self.fleet.set_solver_degraded(
                self._breaker_degraded
                or self._slo_degraded
                or self._sentinel_degraded
            )

    def reacquire_fence(self) -> None:
        """Re-acquire this scheduler's commit fence after it was
        revoked (lease re-acquired after a partition healed / a stall
        ended). The zombie path back to legitimacy: a fresh token is
        granted at the state service AND the scheduler forces a full
        resync first — both in-flight solves (fence bump) and, in fleet
        mode, the shard view rebuild — so post-refence commits are
        computed from current truth, never the stale pre-fence view.
        Production wires this to lease re-acquisition; the sim's
        hub_partition drive calls it at heal time."""
        with self.cluster.lock:
            if self._fence_role is None:
                return
            self._fence_token = self.cluster.grant_fence(
                self._fence_role,
                holder=f"incarnation-{self.config.incarnation}",
            )
            self._conflict_seq += 1
            self._occupancy_seq += 1
            if self.fleet is not None:
                self.fleet._needs_resync = True
            self._log.info(
                "commit fence re-acquired for role %r (token %d); full "
                "resync forced before the next solve",
                self._fence_role, self._fence_token,
                extra={"step": self._trace_step},
            )

    # -- eventhandlers.go#addAllEventHandlers routing --

    # ClusterState fires watch callbacks under its lock (every public
    # mutator takes it before _emit), so this handler always holds it:
    # ktpu: holds(cluster.lock)
    def _on_event(self, ev: Event) -> None:
        if ev.kind == "Event":
            return  # the scheduler's own recorder output
        if self.obs.enabled:
            # deterministic 1-in-N sampling (ObsConfig.enqueue_span_
            # sample_n): the enqueue span is the one family whose
            # volume scales with the EVENT rate, and spanning every
            # event at sustained-stream scale blows the obs-overhead
            # budget. The first event always samples; the counter is
            # deterministic so same-seed sims stay byte-identical.
            self._enqueue_events += 1
            n = self._enqueue_sample_n
            if n <= 1 or self._enqueue_events % n == 1:
                with self.obs.span(
                    "enqueue", kind=ev.kind, type=ev.type,
                    **({"sample_n": n} if n > 1 else {}),
                ):
                    self._ingest_event(ev)
            else:
                self._ingest_event(ev)
        else:
            self._ingest_event(ev)
        # any non-Event kind can have moved pods between queues: keep
        # the pending_pods gauge current (it used to refresh only in
        # the solve-recording path and went stale between solves).
        # A held commit run refreshes it itself, once
        if not self._in_held_run:
            self._refresh_pending_gauge()

    # ktpu: holds(cluster.lock)
    def _ingest_event(self, ev: Event) -> None:
        if ev.kind in ("ResourceSlice", "DeviceClass", "ResourceClaim"):
            # DRA inventory/claim changes can unblock claim-bearing pods
            # (eventhandlers.go registers the dynamicresources plugin's
            # cluster events [U]); the hint stays conservative (move all)
            # EXCEPT for this scheduler's own binding-side claim writes
            # (reservedFor/allocation appends for a pod that just bound
            # TAKE devices — they cannot unblock a parked pod, and waking
            # the whole unschedulable map per bind defeats backoff).
            # Unreserve rollbacks FREE devices and are not suppressed.
            if self._dra and not self.claim_allocator.writing:
                # an external writer changed claim/inventory state a
                # DRA-active deferred solve folded at tensorize time
                self._occupancy_seq += 1
                self.queue.move_all_to_active_or_backoff(ev.kind + ev.type)
            return
        if ev.kind == "Pod":
            pod = ev.obj
            # nominator-map maintenance: an unbound pod with a nomination is
            # indexed; binding or clearing the nomination drops it
            if ev.type != "DELETED" and not pod.node_name and pod.nominated_node_name:
                # nominated-load changes stay advisory (the reference's
                # best-effort nominator semantics): they do NOT bump the
                # occupancy fence — our own preemption nominations land
                # mid-apply and would self-discard the rest of a chain
                self.nominated_pods[pod.key] = pod
            else:
                self.nominated_pods.pop(pod.key, None)
            if ev.type == "ADDED":
                if pod.node_name:
                    # an externally placed pod consumes capacity a deferred
                    # solve did not see
                    self._conflict_seq += 1
                    self.cache.add_pod(pod)
                elif pod.scheduler_name in self.solvers:
                    self.queue.add(pod)
            elif ev.type == "MODIFIED":
                if pod.node_name:
                    if not self.cache.is_assumed(pod.key):
                        # external bind/update of an assigned pod (our own
                        # bind confirmations arrive while still assumed).
                        # Fence-bump only when the update changes what a
                        # deferred solve consumed — placement or resource
                        # footprint; status heartbeats and label/condition
                        # flaps on running pods must not discard solves
                        # (review-caught pipeline-degeneration hazard)
                        old = None
                        old_node = self.cache.pod_node(pod.key)
                        if old_node is not None:
                            ninfo = self.cache.nodes.get(old_node)
                            if ninfo is not None:
                                old = ninfo.pods.get(pod.key)
                        if (
                            old is None
                            or old.node_name != pod.node_name
                            or old.resource_request()
                            != pod.resource_request()
                        ):
                            self._conflict_seq += 1
                        if old is None or old.labels != pod.labels:
                            # a placed pod's labels re-key spread domain
                            # counts and interpod term matching: only
                            # occupancy-carrying solves care (plain fit
                            # solves must not discard on label flaps —
                            # the original pipeline-degeneration hazard)
                            self._occupancy_seq += 1
                        self.cache.update_pod(pod)
                        # a pod this scheduler still had queued was bound
                        # by someone else: drop it (upstream's filtering
                        # handler pair fires the unassigned handler's
                        # OnDelete when a pod becomes assigned)
                        self.queue.delete(pod.key)
                    else:
                        self.cache.add_pod(pod)
                elif pod.key in self._in_flight:
                    # popped and mid-cycle (the unlocked solve window):
                    # refresh the in-flight copy; re-adding to the queue
                    # would double-schedule
                    self._in_flight[pod.key].pod = pod
                elif pod.key in self._waiting:
                    # parked at Permit: the pod is in flight (assumed +
                    # reserved), NOT queued — re-adding it here would
                    # double-schedule it. Refresh BOTH in-flight copies
                    # (the WaitingPod for the eventual bind and the
                    # QueuedPodInfo a rejection/timeout would requeue) so
                    # neither path resurrects the stale spec.
                    entry = self._waiting[pod.key]
                    entry[0].pod = pod
                    entry[1].pod = pod
                elif pod.scheduler_name in self.solvers:
                    self.queue.update(pod)
            else:  # DELETED
                if self.journal is not None:
                    # a deleted pod's journey trace can never continue;
                    # drop the entry so open-history traces stay
                    # bounded by live pods
                    self.journal.pod_traces.pop(pod.key, None)
                if pod.node_name:
                    freed_node = pod.node_name
                    self.cache.remove_pod(pod.key)
                    if self.fleet is not None:
                        # drop this replica's occupancy row (no-op on
                        # the non-owning replicas that also saw the
                        # event — withdraw only pops own rows)
                        self.fleet.withdraw(pod.key)
                    # freed ports / spread counts / interpod terms: for
                    # the fit carry a free is conservative, but a spread
                    # count overstated in the MIN domain loosens other
                    # domains' quotas and a vanished affinity peer can
                    # wrongly admit a placement — occupancy-carrying
                    # solves in flight must discard
                    self._occupancy_seq += 1
                    # AssignedPodDelete frees resources on ONE node: wake
                    # only pods whose requests fit its new free capacity
                    self.queue.move_all_to_active_or_backoff(
                        "AssignedPodDelete",
                        worth=self._fit_hint(freed_node),
                    )
                else:
                    self.queue.delete(pod.key)
                    # a pod deleted while parked at Permit: roll back its
                    # reservation (next cycle would otherwise bind it)
                    entry = self._waiting.pop(pod.key, None)
                    if entry is not None:
                        wp, _info, _cycle, state, _t0, _step = entry
                        self._unreserve_all(state, wp.pod, wp.node_name)
                        # the rollback freed assumed occupancy a deferred
                        # hard-shape solve may have counted
                        self._occupancy_seq += 1
        else:  # Node
            if ev.type == "ADDED":
                # node add/remove remaps snapshot slots: any in-flight
                # deferred solve's assignment indices go stale
                self._conflict_seq += 1
                self.cache.add_node(ev.obj)
                self.queue.move_all_to_active_or_backoff(
                    "NodeAdd", worth=self._fit_hint(ev.obj.name)
                )
            elif ev.type == "MODIFIED":
                old = self.cache.nodes.get(ev.obj.name)
                old_node = old.node if old is not None else None
                self.cache.update_node(ev.obj)
                # queueing-hint precheck (eventhandlers.go
                # #nodeSchedulingPropertiesChange): only wake parked pods for
                # node changes that could make one schedulable
                if old_node is None or _node_change_could_help(old_node, ev.obj):
                    # the same changes invalidate a deferred solve's masks
                    # and capacity math (pure heartbeats do not)
                    self._conflict_seq += 1
                    # label/taint/unschedulable changes can unblock pods
                    # regardless of resources; a pure allocatable change
                    # only helps pods that now FIT this node
                    resource_only = old_node is not None and (
                        old_node.labels == ev.obj.labels
                        and old_node.taints == ev.obj.taints
                        and old_node.unschedulable == ev.obj.unschedulable
                    )
                    self.queue.move_all_to_active_or_backoff(
                        "NodeUpdate",
                        worth=self._fit_hint(ev.obj.name, old=old_node)
                        if resource_only
                        else None,
                    )
            else:
                self._conflict_seq += 1
                self.cache.remove_node(ev.obj.name)

    def _fit_hint(self, node_name: str, old=None):
        """isPodWorthRequeuing gate for fit-shaped events (NodeAdd, a pure
        allocatable NodeUpdate, AssignedPodDelete): the event changed ONE
        node's capacity, so a parked pod is worth requeuing only if its
        requests fit that node's new free capacity (noderesources/fit.go
        #isSchedulableAfterNodeChange). Requests that don't fit there
        cannot have been unblocked by this event. With ``old`` (the
        pre-update Node on a resource-only NodeUpdate) the hint also
        checks the DELTA direction: a pod that already fit the old
        allocatable was not unblocked by this change — e.g. a shrink that
        still fits wakes nothing (the reference's hint compares old and
        new node infos the same way). Other filters (taints, selectors)
        are NOT checked — failing them here could only cause a missed
        wakeup if they also changed, which routes through the worth=None
        path. Returns None (move everything) when the
        SchedulerQueueingHints feature gate is off."""
        if not self.feature_gates.enabled("SchedulerQueueingHints"):
            return None

        def worth(info) -> bool:
            ninfo = self.cache.nodes.get(node_name)
            if ninfo is None or ninfo.node is None:
                return True  # node vanished mid-event: stay conservative
            node = ninfo.node
            if node.unschedulable:
                return False
            if len(ninfo.pods) + 1 > node.allowed_pod_number:
                return False
            for r, v in info.pod.resource_request().items():
                if v <= 0 or r == "pods":
                    continue
                if ninfo.used.get(r, 0) + v > node.allocatable.get(r, 0):
                    return False
            if old is not None:
                # fits the new capacity — but did it fail the OLD one?
                fits_old = len(ninfo.pods) + 1 <= old.allowed_pod_number
                if fits_old:
                    for r, v in info.pod.resource_request().items():
                        if v <= 0 or r == "pods":
                            continue
                        if ninfo.used.get(r, 0) + v > old.allocatable.get(
                            r, 0
                        ):
                            fits_old = False
                            break
                if fits_old:
                    return False  # change could not have unblocked it
            return True

        return worth

    # -- the scheduling loop --

    def schedule_batch(self) -> BatchResult:
        """One batched scheduling cycle: K pops -> one solve per profile ->
        K bindings. With a single profile (the common case) this is exactly
        one device solve; with multiple, pods route by spec.schedulerName
        (schedule_one.go#frameworkForPod) and sub-batches solve in pop
        order.

        Lock discipline (schedule_one.go's schedulingCycle/bindingCycle
        decoupling, batched): the cluster RLock is held in three short
        phases — (1) waiting-pod settlement + pop, (2) per group:
        snapshot + tensorize, then again for assume/Reserve/Permit after
        the solve — and NOT across the device solve or the bind commits.
        Ingest threads and a same-process extender server can therefore
        take the lock while the device works or a bind crosses the wire.
        The assume/forget protocol fences every gap: assumed pods are in
        the cache before the lock drops, so any concurrent snapshot
        counts them, and a mid-solve cache mutation lands in the NEXT
        cycle's snapshot (the same staleness window the reference's
        binding goroutines accept)."""
        self._trace_step += 1
        return self._cycle_observed(self._trace_step)

    def _cycle_observed(self, step: int) -> BatchResult:
        """One cycle under the obs root span, with the flight recorder
        dumped if the cycle dies (the crash trigger). The span and the
        telemetry stages inside it share the ``_trace_step`` id."""
        if not self.obs.enabled and self.flight is None:
            return self._schedule_cycle()
        try:
            with self.obs.span(
                "schedule_batch", trace_id=step, step=step,
                **self._span_tags,
            ) as sp:
                res = self._schedule_cycle()
                sp.set(
                    scheduled=len(res.scheduled),
                    unschedulable=len(res.unschedulable),
                    bind_failures=len(res.bind_failures),
                )
                return res
        except Exception:
            if self.flight is not None:
                path = self.flight.dump(trigger="crash")
                self._log.exception(
                    "scheduling cycle failed; flight recorder dump: %s",
                    path, extra={"step": step},
                )
            raise

    # every caller requeues inside its locked region (watch events must
    # not interleave with the bookkeeping): ktpu: holds(cluster.lock)
    def _requeue(self, info: QueuedPodInfo, cycle: int) -> None:
        """AddUnschedulableIfNotPresent + in-flight bookkeeping: once a
        pod re-enters the queue, watch events must route to queue.update
        again instead of the in-flight refresh."""
        self._in_flight.pop(info.key, None)
        self.queue.add_unschedulable(info, cycle)

    def _schedule_cycle(self) -> BatchResult:
        pending: list[tuple] = []
        res = BatchResult()
        if self.fleet is not None:
            # apply any pending partition change (membership or
            # ring move) before popping, so this cycle solves against
            # the current shard
            self.fleet.maybe_resync(self)
        if self.rebalancer is not None:
            # background defragmentation: a no-op unless the interval
            # elapsed AND the queues are idle. Evictions re-enter the
            # queue synchronously (the eviction's watch events land
            # under the cluster lock), so the pop below picks the
            # migrating pods up in this same cycle.
            self.rebalancer.maybe_run(self, res)
        t0 = self.clock.perf()
        with self.cluster.lock, self.obs.span("pop") as sp:
            # re-admit quarantined pods whose TTL'd backoff elapsed
            self._release_quarantine()
            # reap assumes whose bind confirmation never arrived
            self._reap_expired_assumes()
            # WaitOnPermit analog: settle WaitingPods whose verdict or
            # deadline arrived since the last cycle, before popping new
            # work
            if self._waiting:
                self._process_waiting(res, pending)
            # #flushUnschedulablePodsLeftover: the reference runs this on
            # a 30s timer goroutine; batching gives a natural tick — pods
            # parked longer than 5 min force back into rotation
            self.queue.flush_unschedulable_leftover()
            infos = self.queue.pop_batch(self.config.batch_size)
            for i in infos:
                self._in_flight[i.key] = i
            if self._gang is not None:
                # gang gate: complete pod groups enter the batch whole
                # (contiguous), incomplete ones park until assembled
                infos = self._gang_gate(infos, res)
            sp.set(pods=len(infos))
            # idle/empty cycles change the queues too (waiting
            # settlement, leftover flush, the pop itself)
            self._refresh_pending_gauge()
        return self._run_popped(infos, t0, res, pending)

    def _run_popped(
        self,
        infos: list[QueuedPodInfo],
        t0: float,
        res: BatchResult | None = None,
        pending: list | None = None,
    ) -> BatchResult:
        """The synchronous cycle body for an already-popped batch (the
        pipelined driver pops before it knows whether a batch can overlap
        a deferred solve; non-overlappable batches route here)."""
        res = BatchResult() if res is None else res
        pending = [] if pending is None else pending
        try:
            if infos:
                self._run_groups(infos, res, pending, t0)
                res.host_seconds = (
                    self.clock.perf() - t0 - res.solve_seconds
                )
                self._record_metrics(
                    res, len(infos),
                    # the tuning window's hard-shape fraction must not
                    # collapse just because hard batches ROUTED through
                    # the synchronous cycle (degraded mode, backstop) —
                    # that would read as a workload shift on an
                    # unchanged workload (review-caught). The pod scan
                    # only runs when a tuner is actually sampling.
                    occ_sensitive=(
                        self.tuner is not None
                        and not self._plain_batch(
                            [i.pod for i in infos]
                        )
                    ),
                )
        except Exception:
            # a mid-cycle outage (non-ignorable extender down, plugin
            # ERROR) surfaces to the caller, but must not strand work:
            # popped pods that were neither approved, parked, nor already
            # requeued go back to the queue with backoff, and approved
            # binds still commit (the finally below).
            self._requeue_unhandled(infos, pending, res)
            raise
        finally:
            self._commit_all(infos, pending, res)
            if self._gang is not None:
                # a member quarantined/bisected out of the batch never
                # resolves its round: release the leftovers so staged
                # siblings can't stay assumed across batches
                with self.cluster.lock:
                    if self._gang_rounds:
                        self._release_gang_rounds_for(
                            {i.key for i in infos},
                            "gang round unresolved at batch end", res,
                        )
            res.completed_at = self.clock.perf()
        return res

    def _requeue_unhandled(
        self, infos: list[QueuedPodInfo], pending: list, res: BatchResult
    ) -> None:
        """Backoff-requeue every popped pod a mid-cycle exception left
        neither approved, parked, nor already requeued (shared by the
        sync and pipelined failure paths)."""
        released: set = set()
        if self._gang is not None:
            # abort every gang round this batch touched FIRST: staged
            # members release (unreserve + requeue) here, so the loop
            # below must treat them as handled
            with self.cluster.lock:
                if self._gang_rounds:
                    released = self._release_gang_rounds_for(
                        {i.key for i in infos},
                        "batch aborted mid-cycle", res,
                    )
        handled = (
            {e[2].key for e in pending}
            | set(res.unschedulable)
            | {k for k, _ in res.bind_failures}
            | set(res.quarantined)
            | set(self._waiting)
            | released
        )
        with self.cluster.lock:
            base = self.queue.scheduling_cycle
            for info in infos:
                if info.key not in handled:
                    if self.fleet is not None and not self.fleet.routes_pod(
                        info.key, info.pod
                    ):
                        # handed off to a peer earlier in this batch:
                        # requeueing locally would double-track the pod
                        # (the peer claims it from the exchange)
                        self._in_flight.pop(info.key, None)
                        continue
                    self._requeue(info, base)
            self._refresh_pending_gauge()

    def _commit_all(
        self, infos: list[QueuedPodInfo], pending: list, res: BatchResult
    ) -> None:
        """The binding-cycle pass for a batch's approved pods, plus
        in-flight bookkeeping teardown for exactly this batch (the
        pipelined loop keeps other batches' in-flight entries live).
        Gang rounds resolve here first: a round whose every member
        staged commits atomically via _commit_gang below; a failed or
        short round releases every staged placement (the
        all-or-nothing contract)."""
        gang_ready: list = []
        if self._gang is not None:
            with self.cluster.lock:
                if self._gang_rounds:
                    gang_ready = self._resolve_gang_rounds(res)
        hook = self._pre_commit_hook
        hook_pending = pending
        if gang_ready:
            # the crash seam must see the gang's staged entries too:
            # killing the process here is exactly the "assumed + staged
            # but nothing committed" window the restart rollback covers
            hook_pending = pending + [
                e for _gid, rd in gang_ready for e in rd["staged"]
            ]
        if hook is not None and hook_pending:
            # sim seam: the batch has assumed + approved its pods but
            # committed nothing — the exact point a crash-restart drive
            # kills the process (sim/harness.py crash_restart)
            hook(hook_pending)
        first_err = None
        # the bind stage: every per-pod commit of this batch, journal
        # record included (the gang commits below are not in it).
        # Each maximal run of consecutive wire-free entries commits
        # under ONE hold of cluster.lock: taken and dropped per pod (as
        # _commit_binding's phases do on their own), every release
        # hands the lock to the ingest thread and the loop waits out a
        # create_pod before its next acquire — a convoy that cost more
        # than the commits. A hold lasts at most this flight's pods.
        # An entry with a wire call in its cycle commits alone,
        # unlocked across that call, exactly as before.
        binders = [cl for cl in self.extender_clients if cl.is_binder]
        wire_free = functools.partial(self._wire_free, binders)
        with (
            self.telemetry.stage(
                "bind", step=self._trace_step, pods=len(pending)
            )
            if self.telemetry is not None and pending
            else _NOOP_SPAN
        ):
            for held, run in itertools.groupby(pending, key=wire_free):
                n_run = 0
                with self.held_run() if held else _NOOP_SPAN:
                    for entry in run:
                        n_run += 1
                        err = self._commit_entry(entry, res, binders, held)
                        first_err = first_err or err
                self._commit_path_children[held].inc(n_run)
        for gid, rd in gang_ready:
            # one atomic all-or-nothing commit per complete gang round
            try:
                self._commit_gang(gid, rd, res)
            except Exception as e:
                first_err = first_err or e
        # LOCK001 (pre-analyzer gap): these pops ran unlocked, racing the
        # watch handler's in-flight refresh (_on_event could KeyError-skip
        # or resurrect an entry mid-pop on the ingest thread)
        with self.cluster.lock:
            # members of still-unresolved gang rounds (a split batch:
            # siblings ride a later flight) stay under the in-flight
            # fence — tearing them down would let a watch event
            # re-enqueue a pod whose placement is still staged
            gang_live = {
                k
                for rd2 in self._gang_rounds.values()
                for k in rd2["expect"]
            } if self._gang_rounds else set()
            for info in infos:
                if info.key not in gang_live:
                    self._in_flight.pop(info.key, None)
            for entry in pending:
                self._in_flight.pop(entry[1].key, None)
            # bind failures above requeued pods with backoff
            self._refresh_pending_gauge()
        if self.slo is not None and (
            res.e2e_latencies or res.bind_failures or res.scheduled
        ):
            # live SLO engine tick: POST-commit (the e2e latencies land
            # at _commit_binding), one chokepoint for every dispatch
            # loop — sync, pipelined, streaming, drain. Host arithmetic
            # over numbers this batch already materialized; zero new
            # device syncs (the CounterWindow sampling discipline).
            self.slo.observe_batch(res)
        if self.telemetry is not None and (infos or pending):
            # flight-telemetry tick, same post-commit chokepoint as the
            # SLO engine: close the batch's stage ledger (the bind stage
            # above was the last one) and, at window boundaries, run
            # the sentinel's regression rules. All host arithmetic;
            # anomalies journal + capture here.
            self.telemetry.observe_batch(
                self, step=self._trace_step, pods=len(pending)
            )
            if self._sentinel_degraded != self.telemetry.degraded:
                self._sentinel_degraded = self.telemetry.degraded
                self._publish_degraded()
        if first_err is not None:
            raise first_err

    def _wire_free(self, binders: list, entry: tuple) -> bool:
        """True when nothing in the entry's binding cycle can leave the
        process — exactly the calls _commit_binding's wire phase makes,
        plus PostBind: no PreBind/PostBind plugin, no volume to bind,
        no DRA claim, no binder extender that wants the pod. Read off
        the entry itself; such an entry may commit under the lock."""
        pod = entry[2]
        return not (
            self.registry.pre_bind
            or self.registry.post_bind
            or pod.pvc_names
            or (self._dra and pod.resource_claim_names)
            or any(cl.is_interested(pod) for cl in binders)
        )

    @contextlib.contextmanager
    def held_run(self):
        """One hold of cluster.lock around a run of per-pod writes whose
        watch events are the run's own: the commit pass's wire-free
        binds (_commit_all), or a POST body's upserts on the server's
        thread (server/extender.py post_pods). Every pod still gets its
        own event; the lock take and the pending gauge's refresh happen
        once. grant_fence and revoke_fence take the same lock, so the
        fence token every bind of a run is checked against cannot change
        inside the hold: a revoke waits for the run's end and refuses
        the NEXT run whole. The per-pod code re-enters the RLock (an
        owner check, no futex)."""
        with self.cluster.lock:
            self._in_held_run = True
            try:
                yield
            finally:
                self._in_held_run = False
                # the run's own events skipped this (_on_event): pods
                # entered the queue, or failed binds were requeued
                self._refresh_pending_gauge()

    def _commit_entry(
        self, entry: tuple, res: BatchResult, binders: list, held: bool
    ) -> Exception | None:
        """One pod of the commit pass: its sampled bind span, the
        binding cycle, the rollback of a plugin that raised, the Bind
        extension-point observation. Returns what the plugin raised
        (the pass goes on and re-raises the first at its end)."""
        err = None
        tb = self.clock.perf()
        # bind spans are 1-in-N sampled (ObsConfig.bind_span_
        # sample_n; deterministic counter, first bind always
        # sampled): the journal stays COMPLETE per pod — the span only
        # adds the commit's wall duration, which sampling preserves
        # statistically, and per-pod spans at sustained-stream volume
        # are what the obs-overhead budget cannot afford
        self._bind_commits += 1
        bn = self._bind_sample_n
        span_ctx = (
            self.obs.span(
                "bind", trace_id=entry[6], pod=entry[2].key,
                node=entry[3],
                **({"sample_n": bn} if bn > 1 else {}),
            )
            if bn <= 1 or self._bind_commits % bn == 1
            else _NOOP_SPAN
        )
        with span_ctx as bsp:
            try:
                ok = self._commit_binding(entry, res, binders, held)
            except Exception as e:  # a buggy PreBind/PostBind plugin
                # must not strand the REST of the approved batch:
                # roll this pod back, keep committing, re-raise last
                ok = False
                err = e
                state, info, pod, node_name, cycle, _ts, step = entry
                with self.cluster.lock:
                    self._unreserve_all(state, pod, node_name)
                    res.bind_failures.append((pod.key, repr(e)))
                    self._requeue(info, cycle)
                    if self.journal is not None:
                        self.journal.record(
                            step, cycle, pod, "bind_failure",
                            node=node_name, reason=repr(e),
                            attempts=info.attempts,
                        )
            bsp.set(ok=ok)
        self._pass_child(
            metrics.framework_extension_point_duration_seconds,
            "Bind", "Success" if ok else "Error", "all",
        ).observe(self.clock.perf() - tb)
        return err

    def _pass_child(self, metric, *labels):
        """``metric.labels(*labels)``, looked up once: the series still
        appears in /metrics at its first observation, not before."""
        child = self._pass_children.get((metric, labels))
        if child is None:
            child = self._pass_children[metric, labels] = metric.labels(
                *labels
            )
        return child

    def _group_by_profile(
        self, infos: list
    ) -> list[tuple[str, list, list[int]]]:
        """Profile sub-batches in pop order
        (schedule_one.go#frameworkForPod routing): (profile, infos,
        cycle offsets) per group — shared by the synchronous and
        pipelined loops so their batch composition can never diverge.
        Single-profile configs skip the bucketing pass."""
        if len(self.solvers) == 1:
            only = next(iter(self.solvers))
            return [(only, infos, list(range(len(infos))))]
        by_profile: dict[str, list] = {}
        order: list[str] = []
        for off, info in enumerate(infos):
            name = info.pod.scheduler_name
            if name not in by_profile:
                by_profile[name] = []
                order.append(name)
            by_profile[name].append((off, info))
        return [
            (
                name,
                [i for _, i in by_profile[name]],
                [off for off, _ in by_profile[name]],
            )
            for name in order
        ]

    def _run_groups(
        self, infos: list, res: BatchResult, pending: list, t0: float
    ) -> None:
        base_cycle = self.queue.scheduling_cycle - len(infos)
        for name, group_infos, cycle_offsets in self._group_by_profile(
            infos
        ):
            self._solve_group(
                name, group_infos, cycle_offsets, base_cycle, res, t0,
                pending,
            )

    def _solve_group(
        self,
        profile: str,
        infos: list[QueuedPodInfo],
        cycle_offsets: list[int],
        base_cycle: int,
        res: BatchResult,
        t0: float,
        pending: list,
        _depth: int = 0,
    ) -> None:
        """One profile sub-batch, synchronously: tensorize -> fold ->
        dispatch (blocking read) -> validate -> apply. run_pipelined
        drives the same phases with a deferred read between dispatch
        and apply so the next batch's host work overlaps this one's
        device→host read.

        This is also the RESILIENT path (kubernetes_tpu/resilience):
        every dispatch runs at the tier the fallback ladder currently
        allows. A solve failure (exception, read death, or pre-apply
        validation rejecting the output) triggers one device-session
        rebuild and a retry; a deterministic failure trips the tier's
        circuit breaker and the batch retries one rung lower, down to
        the pure-host serial greedy — so a sick device degrades
        throughput, never progress. A batch that fails even the host
        rung (or dies in tensorize, which no tier can fix) is
        data-shaped: it bisects to the offending pod(s), which are
        quarantined with a terminal journal outcome while the rest of
        the batch proceeds (``_bisect_or_quarantine``)."""
        solver = self.solvers[profile]
        try:
            prep = self._tensorize_group(
                profile, infos, cycle_offsets, base_cycle, t0
            )
        except Exception as e:
            # tensorize is tier-independent: no ladder rung can fix a
            # batch whose data breaks it — isolate the poison instead
            self._solver_failed(
                infos, e, "tensorize", self._trace_step, base_cycle
            )
            self._bisect_or_quarantine(
                profile, infos, cycle_offsets, base_cycle, res, t0,
                pending, e, _depth,
            )
            return
        with self.obs.span(
            "fold", trace_id=prep.step, profile=profile,
            extenders=len(self.extender_clients),
            plugins=len(self.config.out_of_tree_plugins),
        ):
            # extender/plugin folding keeps its own failure semantics
            # (a non-ignorable extender outage aborts the batch): NOT
            # wrapped by the ladder
            self._fold_group(prep)
        while True:
            tier_idx, tier = self.resilience.acquire(profile)
            act = err = None
            try:
                if tier == TIER_HOST:
                    flight = self._host_dispatch(prep)
                else:
                    flight = self._dispatch_group(
                        prep, defer=False, tier=tier
                    )
            except SessionDrainRequired:
                raise  # pipelined-protocol control flow, not a fault
            except Exception as e:
                err = e
                self._solver_failed(
                    infos, e, None, prep.step, base_cycle
                )
                act = self.resilience.on_failure(profile, tier_idx)
            else:
                try:
                    # pre-apply validation runs inside _apply_group
                    # BEFORE any mutation: a SolverFaultError here is a
                    # failed solve, retryable at a lower rung
                    self._apply_group(flight, res, pending)
                except SolverFaultError as e:
                    err = e
                    self._solver_failed(
                        infos, e, None, prep.step, base_cycle
                    )
                    act = self.resilience.on_failure(profile, tier_idx)
                else:
                    self.resilience.on_success(profile, tier_idx)
                    if tier != self.resilience.ladder[0]:
                        metrics.fallback_solves_total.labels(tier).inc()
                    return
            # breaker span + flight-recorder dump: the trip is the
            # moment worth a forensic snapshot (the ring still holds
            # the failing dispatch's spans/decisions)
            with self.obs.span(
                "breaker", trace_id=prep.step, profile=profile,
                tier=tier, action=act,
            ):
                pass
            if act == ACT_DESCEND and self.flight is not None:
                self.flight.dump(trigger="breaker")
            if act == ACT_REBUILD:
                solver.reset_session()
                continue
            if act != ACT_BISECT:
                continue  # retry / descend: re-acquire the tier
            # the last rung failed: data-shaped — isolate it
            self._bisect_or_quarantine(
                profile, infos, cycle_offsets, base_cycle, res, t0,
                pending, err, _depth,
            )
            return

    def _host_dispatch(self, prep: _PreparedGroup) -> _InFlightSolve:
        """The ladder's last rung: solve the prepared group with the
        pure-host serial greedy (resilience.host_greedy_assign) —
        zero accelerator surface, so device loss cannot take it down.
        Returns a flight shaped exactly like a device dispatch so the
        apply path downstream is identical."""
        solver = self.solvers[prep.profile]
        hook = self._solve_fault
        if hook is not None:
            hook(prep.pods, TIER_HOST)
        t1 = self.clock.perf()
        with self.cluster.lock:
            placed = self._placed_by_slot()
        with self.obs.span(
            "dispatch", trace_id=prep.step, profile=prep.profile,
            defer=False, tier=TIER_HOST,
        ):
            assignments = host_greedy_assign(
                prep, placed, solver.config
            )
        # the next device-tier dispatch must re-upload the session:
        # host-rung placements never touched the device carry
        self._tier_last[prep.profile] = TIER_HOST
        dispatch_dt = self.clock.perf() - t1
        if not prep.timing_observed:
            prep.timing_observed = True
            prep.tensorize_seconds = max(t1 - prep.gs, 0.0)
            metrics.tensorize_seconds.observe(prep.tensorize_seconds)
            metrics.framework_extension_point_duration_seconds.labels(
                "PreFilter", "Success", prep.profile
            ).observe(prep.tensorize_seconds)
        return _InFlightSolve(
            prep=prep, handle=assignments, dispatch_seconds=dispatch_dt
        )

    def _solver_failed(
        self,
        infos: list[QueuedPodInfo],
        exc: Exception,
        reason: str | None,
        step: int,
        base_cycle: int,
    ) -> None:
        """Journal + count a failed batched solve: a
        scheduler_batch_failure_total{reason} tick and a non-terminal
        ``solver_error`` journal record per pod, so `explain <pod>`
        shows the retry history instead of a silent requeue."""
        if reason is None:
            if isinstance(exc, SolveCorruptError):
                reason = "corrupt"
            elif isinstance(exc, SolverReadError):
                reason = "read"
            else:
                reason = "dispatch"
        metrics.batch_failure_total.labels(reason).inc()
        self._log.warning(
            "batched solve failed (%s, %d pods): %r",
            reason, len(infos), exc, extra={"step": step},
        )
        self._note_drain_chunk(step)
        if self.journal is not None:
            for info in infos:
                self.journal.record(
                    step, base_cycle, info.pod, "solver_error",
                    reason=f"{reason}: {exc!r}", attempts=info.attempts,
                )

    def _bisect_or_quarantine(
        self,
        profile: str,
        infos: list[QueuedPodInfo],
        cycle_offsets: list[int],
        base_cycle: int,
        res: BatchResult,
        t0: float,
        pending: list,
        exc: Exception,
        depth: int,
    ) -> None:
        """Poison-batch isolation: the batch failed every ladder rung
        (or tensorize itself), so the failure is data-dependent. Bisect
        to the offending pod(s): each half re-enters the resilient
        solve, halves without the poison proceed normally, and a
        singleton that still fails is quarantined with a terminal
        journal outcome and a TTL'd backoff re-admit.

        Gang members are an indivisible unit: bisection never splits
        THROUGH a pod group (the gate made gangs contiguous, so the
        midpoint just shifts to the nearest group boundary), and a
        slice reduced to one whole unsatisfiable gang quarantines the
        group as a unit instead of bisecting into it."""
        if self._gang is not None and infos:
            gids = [self._gang.gang_of(i.pod) for i in infos]
            if gids[0] is not None and all(g == gids[0] for g in gids):
                # the poison isolated to ONE whole gang: all-or-nothing
                # applies to quarantine too
                self._quarantine_gang(gids[0], infos, exc, res)
                return
        if len(infos) == 1:
            self._quarantine_pod(
                infos[0], base_cycle + cycle_offsets[0] + 1, exc, res
            )
            return
        mid = len(infos) // 2
        if self._gang is not None:
            # shift the split point off a gang's interior: prefer the
            # nearest boundary where the two neighbors are not members
            # of the same group (one exists — the all-same-gang case
            # returned above)
            def _boundary(b: int) -> bool:
                return not (
                    gids[b - 1] is not None and gids[b - 1] == gids[b]
                )

            if not _boundary(mid):
                for d in range(1, len(infos)):
                    if mid - d >= 1 and _boundary(mid - d):
                        mid = mid - d
                        break
                    if mid + d <= len(infos) - 1 and _boundary(mid + d):
                        mid = mid + d
                        break
        with self.obs.span(
            "bisect", trace_id=self._trace_step, profile=profile,
            pods=len(infos), depth=depth,
        ):
            for lo, hi in ((0, mid), (mid, len(infos))):
                self._solve_group(
                    profile, infos[lo:hi], cycle_offsets[lo:hi],
                    base_cycle, res, t0, pending, _depth=depth + 1,
                )

    def _quarantine_pod(
        self, info: QueuedPodInfo, cycle: int, exc: Exception,
        res: BatchResult,
    ) -> None:
        """Terminal quarantine for a pod whose presence deterministically
        breaks the solve: journaled ``quarantined`` with the exception,
        out of every queue, re-admitted after a TTL'd backoff
        (_release_quarantine)."""
        cfg = self.resilience.config
        pod = info.pod
        with self.cluster.lock:
            self._in_flight.pop(info.key, None)
            self.queue.delete(info.key)
            n = self._quarantine_counts.get(info.key, 0) + 1
            self._quarantine_counts[info.key] = n
            ttl = min(
                cfg.quarantine_ttl * cfg.quarantine_backoff ** (n - 1),
                cfg.max_quarantine_ttl,
            )
            self._quarantine[info.key] = (info, self.clock.now() + ttl)
            res.quarantined.append(info.key)
            metrics.quarantined_pods_total.inc()
            self._log.warning(
                "pod %s quarantined for %.0fs (quarantine #%d): solve "
                "failure isolated to this pod: %r",
                info.key, ttl, n, exc, extra={"step": self._trace_step},
            )
            self._event(
                pod, "FailedScheduling",
                f"quarantined: the batched solve fails whenever this "
                f"pod is included: {exc!r}", type_="Warning",
            )
            self._note_drain_chunk(self._trace_step)
            if self.journal is not None:
                self.journal.record(
                    self._trace_step, cycle, pod, "quarantined",
                    reason=repr(exc), attempts=info.attempts,
                )
            self._refresh_pending_gauge()

    # called from the locked pop regions of both loops: ktpu: holds(cluster.lock)
    def _release_quarantine(self) -> None:
        """Re-admit quarantined pods whose TTL'd backoff elapsed (the
        retry may succeed — the poison may have been a transient data
        interaction, a since-fixed webhook, or a healed tier). Pods
        deleted or bound while quarantined just drop out."""
        if not self._quarantine:
            return
        now = self.clock.now()
        for key in sorted(self._quarantine):
            info, release = self._quarantine[key]
            if release > now:
                continue
            del self._quarantine[key]
            try:
                ns, name = key.split("/", 1)
                cur = self.cluster.get_pod(ns, name)
            except ApiError:
                self._quarantine_counts.pop(key, None)
                continue  # deleted while quarantined
            if cur.node_name:
                self._quarantine_counts.pop(key, None)
                continue  # bound by someone else while quarantined
            info.pod = cur
            self.queue.requeue_popped(info)
            metrics.quarantine_readmits_total.inc()

    # called from the locked pop regions of both loops: ktpu: holds(cluster.lock)
    def _reap_expired_assumes(self) -> None:
        """Expire assumed pods whose bind confirmation never arrived
        (cache.cleanup_expired — finished assumes past their deadline,
        plus unfinished assumes a dead binding cycle leaked past the
        TTL; Permit-parked pods are protected). The release frees
        occupancy in-flight solves may have counted, so both fences
        bump; a pod still unbound in truth re-enters the queue, a pod
        actually bound (confirmation event lost) re-adopts from
        truth."""
        expired = self.cache.cleanup_expired(
            protected=frozenset(self._waiting)
        )
        if not expired:
            return
        self._conflict_seq += 1
        self._occupancy_seq += 1
        for key in expired:
            self._log.warning(
                "assumed pod %s expired without a bind confirmation; "
                "occupancy released", key,
                extra={"step": self._trace_step},
            )
            ns, name = key.split("/", 1)
            try:
                cur = self.cluster.get_pod(ns, name)
            except ApiError:
                # deleted: drop the leaked host-side reservations too
                if self.fleet is not None:
                    self.fleet.withdraw(key)
                self.volume_binder.unreserve(key)
                self.claim_allocator.unreserve(key)
                continue
            if cur.node_name:
                # the bind actually landed and only the confirmation
                # event was lost: re-adopt real occupancy from truth.
                # The exchange row stays — it was COMMITTED at bind
                # time and still represents durable occupancy peers
                # must respect (withdrawing it here would hide a bound
                # pod from cross-shard admission; review-caught)
                self.cache.add_pod(cur)
                continue
            if self.fleet is not None:
                self.fleet.withdraw(key)
            self.volume_binder.unreserve(key)
            self.claim_allocator.unreserve(key)
            if (
                key not in self.queue.entries()
                and key not in self._in_flight
                and key not in self._quarantine
                and cur.scheduler_name in self.solvers
                and (
                    self.fleet is None
                    or self.fleet.routes_pod(key, cur)
                )
            ):
                self.queue.add(cur)
        self._refresh_pending_gauge()

    def _requeue_immediate(self, infos: list[QueuedPodInfo]) -> None:
        """Requeue a batch whose deferred dispatch failed before any
        flight existed: head of the active queue, no backoff (the
        failure is the solve's, not the pods') — the retry routes
        through the synchronous resilient path. Externally bound or
        deleted pods drop out (mirrors _discard_flight)."""
        with self.cluster.lock:
            if self._gang is not None and self._gang_rounds:
                self._release_gang_rounds_for(
                    {i.key for i in infos},
                    "gang member's dispatch failed before any flight",
                )
            for info in infos:
                self._in_flight.pop(info.key, None)
                try:
                    cur = self.cluster.get_pod(
                        info.pod.namespace, info.pod.name
                    )
                except ApiError:
                    continue
                if cur.node_name:
                    continue
                info.pod = cur
                self.queue.requeue_popped(info)
            self._refresh_pending_gauge()

    # -- gang scheduling (kubernetes_tpu/gang): all-or-nothing pod
    # groups. The gate assembles groups at pop time, _apply_group
    # STAGES members instead of queueing them for individual commit,
    # and _commit_all resolves each round — one atomic bind_gang when
    # every member staged, a full release + requeue otherwise. --

    # called from the locked pop regions of all three loops:
    # ktpu: holds(cluster.lock)
    def _gang_gate(
        self, infos: list, res: BatchResult | None = None
    ) -> list:
        """Rewrite a popped batch so pod groups enter it whole or not
        at all: pull a ready gang's remaining members straight out of
        the queue (any heap position, any backoff state), park an
        incomplete gang's members back as unschedulable (journal
        ``gang_incomplete``) until the group assembles or times out,
        and quarantine a gang that timed out or exhausted its
        all-or-nothing retries. Ready gangs re-enter the batch as
        CONTIGUOUS runs — the bisection boundary alignment depends on
        it — after the non-gang pods, which keep pop order."""
        tracker = self._gang
        if tracker is None:
            return infos
        groups: dict[str, list] = {}
        out: list = []
        for info in infos:
            gid = tracker.gang_of(info.pod)
            if gid is None:
                out.append(info)
            else:
                groups.setdefault(gid, []).append(info)
        if not groups:
            return infos
        from .gang import GangUnsatisfiableError

        popped_keys = {i.key for i in infos}
        now = self.clock.now()
        cfg = tracker.config
        for gid in sorted(groups):
            members = groups[gid]
            taken = self.queue.take_for_gang(
                lambda p, _g=gid: tracker.gang_of(p) == _g,
                exclude=popped_keys,
            )
            for t in taken:
                self._in_flight[t.key] = t
            members = members + taken
            need = max(tracker.min_member(m.pod) for m in members)
            first = tracker.note_seen(gid, now)
            if len(members) >= need:
                rounds = tracker.incomplete_rounds(gid)
                if rounds >= cfg.quarantine_after:
                    self._quarantine_gang(
                        gid, members,
                        GangUnsatisfiableError(
                            f"pod group {gid} failed its all-or-"
                            f"nothing round {rounds} consecutive "
                            "times"
                        ),
                        res,
                    )
                    continue
                self._gang_rounds[gid] = {
                    "expect": {m.key for m in members},
                    "done": set(),
                    "staged": [],
                    "failed": False,
                    "reason": "",
                }
                out.extend(members)
                continue
            if now - first > cfg.min_member_timeout:
                self._quarantine_gang(
                    gid, members,
                    GangUnsatisfiableError(
                        f"pod group {gid} assembled only "
                        f"{len(members)}/{need} members within "
                        f"{cfg.min_member_timeout:.0f}s"
                    ),
                    res,
                )
                continue
            # incomplete and still inside the assembly window: park
            # every present member as unschedulable — NOT requeue_popped,
            # which would re-pop the same partial group every cycle in a
            # busy loop. A later member's pop (or the leftover flush)
            # brings them back through take_for_gang above.
            cycle = self.queue.scheduling_cycle
            for m in members:
                self._requeue(m, cycle)
                if self.journal is not None:
                    self.journal.record(
                        self._trace_step, cycle, m.pod,
                        "gang_incomplete",
                        reason=(
                            f"waiting for pod group {gid}: "
                            f"{len(members)}/{need} members present"
                        ),
                        attempts=m.attempts,
                    )
        return out

    # ktpu: holds(cluster.lock) — called from _apply_group's locked region
    def _gang_round_of(self, pod: Pod) -> dict | None:
        """The live all-or-nothing round this pod belongs to, if any."""
        if self._gang is None or not self._gang_rounds:
            return None
        gid = self._gang.gang_of(pod)
        if gid is None:
            return None
        rd = self._gang_rounds.get(gid)
        if rd is not None and pod.key in rd["expect"]:
            return rd
        return None

    # ktpu: holds(cluster.lock) — called from _apply_group's locked region
    def _gang_note_fail(self, rd: dict | None, pod: Pod, reason: str) -> None:
        """Mark a gang member's attempt resolved-as-failed: the round
        can never commit, and _commit_all releases every staged
        sibling once all members have resolved."""
        if rd is None:
            return
        rd["done"].add(pod.key)
        rd["failed"] = True
        if not rd["reason"]:
            rd["reason"] = f"member {pod.key} failed: {reason}"

    # ktpu: holds(cluster.lock)
    def _resolve_gang_rounds(self, res: BatchResult) -> list:
        """Sweep rounds whose every member has resolved: a clean round
        (all staged) moves to the atomic-commit list; a failed or
        short round releases every staged placement and the gang
        requeues whole. Returns [(gid, round)] ready to commit."""
        ready: list = []
        for gid in sorted(self._gang_rounds):
            rd = self._gang_rounds[gid]
            if not rd["expect"] <= rd["done"]:
                continue  # members still unresolved (a later flight)
            del self._gang_rounds[gid]
            if rd["failed"] or len(rd["staged"]) < len(rd["expect"]):
                self._release_gang_round(
                    gid, rd, res,
                    rd["reason"] or "not every member could be placed",
                )
            else:
                ready.append((gid, rd))
        return ready

    # ktpu: holds(cluster.lock)
    def _release_gang_round(
        self, gid: str, rd: dict, res: BatchResult | None, reason: str
    ) -> set:
        """All-or-nothing rollback: unreserve every STAGED member's
        placement (assume, volumes, claims, fleet row — the same
        rollback every individual failure path uses) and requeue it
        with backoff; journal ``gang_incomplete`` per released member.
        A partial gang is never left bound — this is the release half
        of the atomicity contract."""
        released: set = set()
        for entry in rd["staged"]:
            state, info, pod, node_name, cycle, _t0, step = entry
            self._unreserve_all(state, pod, node_name)
            self._requeue(info, cycle)
            released.add(pod.key)
            if res is not None:
                res.gang_released.append(pod.key)
            if self.journal is not None:
                self.journal.record(
                    step, cycle, pod, "gang_incomplete",
                    node=node_name, reason=reason,
                    attempts=info.attempts,
                )
        metrics.gang_incomplete_total.inc()
        if self._gang is not None:
            self._gang.note_incomplete(gid)
        self._log.info(
            "pod group %s round released (%d staged placement(s) "
            "rolled back): %s", gid, len(released), reason,
            extra={"step": self._trace_step},
        )
        self._refresh_pending_gauge()
        return released

    # ktpu: holds(cluster.lock)
    def _release_gang_rounds_for(
        self, keys: set, reason: str, res: BatchResult | None = None
    ) -> set:
        """Force-resolve every live round touching ``keys`` (a
        discarded flight, an aborted batch, a quarantined member):
        the round can no longer complete, so its staged placements
        release and the gang requeues whole."""
        released: set = set()
        if not self._gang_rounds:
            return released
        for gid in sorted(self._gang_rounds):
            rd = self._gang_rounds[gid]
            if not (rd["expect"] & keys):
                continue
            del self._gang_rounds[gid]
            released |= self._release_gang_round(gid, rd, res, reason)
        return released

    def _quarantine_gang(
        self, gid: str, members: list, exc: Exception,
        res: BatchResult | None,
    ) -> None:
        """Quarantine a WHOLE pod group — bisection never splits
        through a gang, and an unsatisfiable gang (min-member timeout,
        exhausted all-or-nothing retries) leaves the queue as a unit.
        Members re-admit together after the TTL'd backoff
        (_release_quarantine), and the gate reassembles them."""
        res = BatchResult() if res is None else res
        with self.cluster.lock:
            rd = self._gang_rounds.pop(gid, None)
            if rd is not None and rd["staged"]:
                self._release_gang_round(
                    gid, rd, res, f"gang quarantined: {exc!r}"
                )
        for m in members:
            self._quarantine_pod(
                m, self.queue.scheduling_cycle, exc, res
            )
        metrics.gang_quarantined_total.inc()
        if self._gang is not None:
            self._gang.note_quarantined(gid)
        if self.telemetry is not None:
            # forensic capture: the batch whose solve failure
            # quarantined the gang is the newest complete record
            self.telemetry.capture("quarantine", note=f"gang {gid}: {exc!r}")
        self._log.warning(
            "pod group %s quarantined whole (%d member(s)): %r",
            gid, len(members), exc, extra={"step": self._trace_step},
        )

    def _commit_gang(self, gid: str, rd: dict, res: BatchResult) -> None:
        """The atomic binding cycle for one complete gang round:
        per-member PreBind (plugins, volumes, DRA claims), then ONE
        all-or-nothing ``ClusterState.bind_gang`` commit under this
        incarnation's fence. Any failure — a PreBind rejection, a
        fence revocation, a member bound externally mid-flight —
        releases EVERY member's placement and the gang requeues whole:
        zero partial binds, by construction. Runs without the cluster
        lock held (the commit may cross a wire), like
        _commit_binding."""
        entries = rd["staged"]
        step = entries[0][6] if entries else self._trace_step
        with self.obs.span(
            "bind_gang", trace_id=step, gang=gid, pods=len(entries),
        ) as gsp:
            try:
                for entry in entries:
                    state, info, pod, node_name, cycle, _t0, _s = entry
                    for p in self.registry.pre_bind:
                        st = p.pre_bind(state, pod, node_name)
                        if not st.is_success:
                            raise _Rejected(
                                f"PreBind plugin {p.name()} rejected "
                                f"{pod.key}: " + "; ".join(st.reasons)
                            )
                    if pod.pvc_names:
                        self.volume_binder.bind_pod_volumes(pod)
                    if self._dra and pod.resource_claim_names:
                        self.claim_allocator.bind_pod_claims(pod)
                self.cluster.bind_gang(
                    [
                        (e[2].namespace, e[2].name, e[3])
                        for e in entries
                    ],
                    fence=(
                        (self._fence_role, self._fence_token)
                        if self._fence_role is not None
                        else None
                    ),
                )
            except (
                ApiError, VolumeBindingError, _Rejected, ExtenderError,
            ) as e:
                reason = e.reason if isinstance(e, ApiError) else str(e)
                fenced = isinstance(e, ApiError) and e.fenced
                gsp.set(ok=False, reason=reason)
                with self.cluster.lock:
                    if fenced:
                        metrics.commit_fenced_total.inc()
                        self._fenced_commits += 1
                        self._log.warning(
                            "gang bind of %s fenced: this "
                            "incarnation's commit fence (role %r) was "
                            "revoked — no member bound",
                            gid, self._fence_role,
                            extra={"step": step},
                        )
                    self._release_gang_round(
                        gid, rd, res, f"gang bind failed: {reason}"
                    )
                return
            gsp.set(ok=True)
        now_perf = self.clock.perf()
        with self.cluster.lock:
            for entry in entries:
                state, info, pod, node_name, cycle, _t0, estep = entry
                self.cache.finish_binding(pod.key)
                self.volume_binder.finish(pod.key)
                self.claim_allocator.finish(pod.key)
                if self.fleet is not None:
                    self.fleet.commit(pod.key)
                self._event(
                    pod, "Scheduled",
                    f"Successfully assigned {pod.key} to {node_name} "
                    f"(pod group {gid}, all {len(entries)} members "
                    "bound atomically)",
                    action="Binding",
                )
                res.scheduled.append((pod.key, node_name))
                if self.journal is not None:
                    self.journal.record(
                        estep, cycle, pod, "bound",
                        node=node_name, attempts=info.attempts,
                    )
                self._in_flight.pop(pod.key, None)
            self._refresh_pending_gauge()
        for entry in entries:
            state, info, pod, node_name, _cycle, t_start, _s = entry
            res.latencies.append(now_perf - t_start)
            e2e = max(
                self.clock.now() - info.initial_attempt_timestamp, 0.0
            )
            res.e2e_latencies.append(e2e)
            metrics.pod_scheduling_attempts.observe(info.attempts)
            metrics.pod_scheduling_sli_duration_seconds.labels(
                str(min(info.attempts, 16))
            ).observe(e2e)
            for p in self.registry.post_bind:
                p.post_bind(state, pod, node_name)
        metrics.gang_commits_total.inc()
        metrics.gang_bound_pods_total.inc(len(entries))
        first = self._gang.note_complete(gid) if self._gang else None
        if first is not None:
            metrics.gang_assembly_seconds.observe(
                max(self.clock.now() - first, 0.0)
            )

    def _tensorize_group(
        self,
        profile: str,
        infos: list[QueuedPodInfo],
        cycle_offsets: list[int],
        base_cycle: int,
        t0: float,
    ) -> _PreparedGroup:
        """Phase 2a (locked): snapshot + tensorize against a consistent
        view of cache + cluster."""
        solver = self.solvers[profile]
        gs = self.clock.perf()
        # the tensorize stage runs from here to the first dispatch's t1
        # (_dispatch_group closes it): fold, a fence drain and the
        # session bookkeeping are in it, as they are in t1 - gs
        tensorize_stage = (
            self.telemetry.stage(
                "tensorize", step=self._trace_step, pods=len(infos)
            ).__enter__()
            if self.telemetry is not None
            else None
        )
        with self.cluster.lock, self.obs.span(
            # explicit trace id: the pipelined loop has no root span, so
            # parent inheritance alone would leave these spans on trace 0
            "tensorize", trace_id=self._trace_step,
            profile=profile, pods=len(infos),
        ) as tsp:
            # phase 2a: snapshot + tensorize against a consistent view
            with self.obs.span("snapshot"):
                batch = self.snapshot.update(self.cache)
            tsp.set(nodes=batch.num_nodes, fence=self._conflict_seq)
            pods = [i.pod for i in infos]

            def has_pod_affinity(p: Pod) -> bool:
                return p.affinity is not None and (
                    p.affinity.pod_affinity is not None
                    or p.affinity.pod_anti_affinity is not None
                )

            need_ports = any(p.host_ports() for p in pods)
            need_spread = any(p.topology_spread_constraints for p in pods)
            # PodTopologySpread defaultingType=System: service-selected pods
            # without explicit constraints get soft cluster defaults
            services = (
                self.cluster.list_services()
                if solver.config.spread_defaulting == "System"
                else []
            )
            if services and not need_spread:
                from .ops.oracle.spread import default_selector

                need_spread = any(
                    not p.topology_spread_constraints
                    and default_selector(p, services) is not None
                    for p in pods
                )
            need_interpod = any(has_pod_affinity(p) for p in pods) or any(
                info.pods_with_affinity
                for info in self.cache.nodes.values()
                if info.node is not None
            )
            # Pad the pod axis to the configured batch size so every cycle —
            # including the final partial batch — reuses ONE compiled shape
            # (§8.8 recompile storms). All-padding chunks are near-free in the
            # grouped solver's fast path, so the fixed bucket only pays off when
            # that path can engage (mirror of the solver's dispatch condition);
            # otherwise the per-pod scan would walk every padding step, so keep
            # the tight pow2 bucket.
            from .solver.exact import grouped_eligible

            # nominated pods force the per-pod scan (grouped_eligible), so
            # detect them before committing to the fixed pod-axis bucket
            nom_pairs = []
            for q in self.nominated_pods.values():
                try:
                    nom_pairs.append(
                        (q, self.snapshot.slot_of(q.nominated_node_name))
                    )
                except KeyError:
                    continue  # nominated node no longer in the snapshot

            # mirror the tensor-level groupable facts from the pods (solve
            # recomputes them from the tensors; disagreement degrades to
            # padded-slow, never wrong): hard-only spread with no soft
            # constraints / no service defaults; anti-affinity-only interpod
            spread_groupable = need_spread and not services and all(
                all(
                    c.when_unsatisfiable == "DoNotSchedule"
                    for c in p.topology_spread_constraints
                )
                for p in pods
            )
            interpod_groupable = need_interpod and all(
                p.affinity is None
                or (
                    p.affinity.pod_affinity is None
                    and (
                        p.affinity.pod_anti_affinity is None
                        or not p.affinity.pod_anti_affinity.preferred
                    )
                )
                for p in pods
            )
            grouped_ok = grouped_eligible(
                solver.config, self.config.batch_size, batch.padded,
                need_spread, need_interpod, bool(nom_pairs),
                spread_groupable=spread_groupable,
                interpod_groupable=interpod_groupable,
            )
            pod_pad = (
                self.config.batch_size
                if grouped_ok and len(pods) <= self.config.batch_size
                else None
            )
            # per-plugin host tensorization timings feed the reference's
            # plugin_execution_duration_seconds series: inside the fused device
            # program per-plugin attribution doesn't exist, but the host-side
            # per-plugin-family tensorizers are real measured work
            def _timed(plugin: str, fn, *a, **kw):
                tp = self.clock.perf()
                out = fn(*a, **kw)
                metrics.plugin_execution_duration_seconds.labels(
                    plugin, "PreFilter", "Success"
                ).observe(self.clock.perf() - tp)
                return out

            pbatch = _timed(
                "NodeResourcesFit", build_pod_batch, pods, batch.vocab, pad=pod_pad
            )

            # Node objects in snapshot-slot order, for the plugin tensorizers
            # (share the solver's node index space).
            slot_nodes = []
            for name in self.snapshot.names:
                info = self.cache.nodes.get(name) if name else None
                slot_nodes.append(info.node if info is not None else None)

            volume_ctx = None
            if any(p.pvc_names for p in pods):
                from .ops.oracle.volumes import VolumeContext

                volume_ctx = VolumeContext.build(
                    self.cluster.list_pvs(),
                    self.cluster.list_pvcs(),
                    {
                        info.node.name: list(info.pods.values())
                        for info in self.cache.nodes.values()
                        if info.node is not None and info.pods
                    },
                )
            class_key_extra = None
            if services:
                from .ops.oracle.spread import default_selector_key

                def class_key_extra(p):
                    if p.topology_spread_constraints:
                        return None
                    return default_selector_key(p, services)

            dra_active = self._dra and any(
                p.resource_claim_names or p.claim_templates_unresolved
                for p in pods
            )
            if dra_active:
                # pods with different claim sets must not share a class
                # rep: the DRA mask is per-claim-set
                base_dra = class_key_extra

                def class_key_extra(p, _base=base_dra):
                    parts = (
                        p.namespace,
                        tuple(sorted(p.resource_claim_names)),
                        p.claim_templates_unresolved,
                    )
                    if _base is not None:
                        return (parts, _base(p))
                    return parts

            if self.config.out_of_tree_plugins or self.extender_clients:
                # custom plugins and extenders read pod fields the in-tree
                # class key doesn't cover (labels/annotations on spread-free
                # pods): fold them into the class identity so two pods such a
                # consumer would treat differently never share one
                # representative's verdicts. (Plugins must key off spec
                # fields in the class identity — framework/interface.py
                # documents the contract; extenders see the rep's full JSON.)
                base_extra = class_key_extra

                def class_key_extra(p, _base=base_extra):
                    parts = (
                        tuple(sorted(p.labels.items())),
                        tuple(sorted(p.annotations.items())),
                    )
                    if _base is not None:
                        return (parts, _base(p))
                    return parts

            if (
                self._gang is not None
                and self._gang.config.class_throughput
                and self._gang.config.throughput_weight > 0
            ):
                # heterogeneity objective (gang/throughput.py): pods of
                # different workload classes score differently per
                # accelerator class, so they must not share a class rep
                from .gang import WORKLOAD_CLASS_LABEL

                base_gang = class_key_extra

                def class_key_extra(p, _base=base_gang):
                    parts = (p.labels.get(WORKLOAD_CLASS_LABEL),)
                    if _base is not None:
                        return (parts, _base(p))
                    return parts

            static = _timed(
                "NodeAffinity",  # the static-mask family's dominant member
                build_static_tensors,
                pods, pbatch, slot_nodes, batch.padded, volume_ctx,
                disabled=frozenset(solver.config.disabled_filters),
                added_affinity=solver.config.added_affinity,
                class_key_extra=class_key_extra,
            )
            metrics.static_rows_total.labels("evaluated").inc(static.rows_evaluated)
            metrics.static_rows_total.labels("shared").inc(
                static.num_classes - static.rows_evaluated
            )
            if need_ports:
                ports = _timed(
                    "NodePorts", build_port_tensors,
                    pods, pbatch, slot_nodes, self._placed_by_slot(), batch.padded,
                    nominated=nom_pairs,
                    # occupancy staging reuse: valid while the cache is
                    # byte-unchanged since the staged scan (any watch
                    # event or apply bumps the generation) and the slot
                    # layout is identical — the streaming burst window
                    staging=self._port_staging,
                    staging_key=(self.cache.generation, batch.padded),
                )
            else:
                ports = trivial_port_tensors(pbatch, batch.padded)
            # spread/interpod count nominated pods host-side with no
            # device-side self-exclusion (unlike ports' nominated_slot), so
            # drop batch pods' own nominations — a pod must not see itself
            # as an already-standing peer
            if need_spread or need_interpod:
                _batch_keys = {p.key for p in pods}
                nom_peers = [
                    (q, s) for q, s in nom_pairs if q.key not in _batch_keys
                ]
            spread = None
            if need_spread:
                spread = _timed(
                    "PodTopologySpread", build_spread_tensors,
                    pods, static.reps, pbatch, slot_nodes,
                    {}, batch.padded, static.c_pad,
                    services=services,
                    defaulting=solver.config.spread_defaulting,
                    nominated=nom_peers,
                    counts=self.cache.spread_counts,
                    slot_of=self.snapshot.slots,
                )
                metrics.spread_tracked_selectors.set(
                    len(self.cache.spread_counts)
                )
            interpod = None
            if need_interpod:
                interpod = _timed(
                    "InterPodAffinity", build_interpod_tensors,
                    pods, static.reps, pbatch, slot_nodes,
                    {}, batch.padded, static.c_pad,
                    hard_pod_affinity_weight=solver.config.hard_pod_affinity_weight,
                    nominated=nom_peers,
                    visits=metrics.interpod_placed_visits_total,
                    counts=self.cache.spread_counts,
                    owners=self.cache.interpod_owners,
                    slot_of=self.snapshot.slots,
                )

            # nominated-pod load (RunFilterPluginsWithNominatedPods analog):
            # unbound pods carrying a nomination count as placed on their
            # nominated node for higher/equal-priority peers; pods in THIS
            # batch that are themselves nominated get a per-pod slot for the
            # evaluateNominatedNode-first pick and self-exclusion
            from .tensorize.schema import build_nominated_tensors

            nominated = build_nominated_tensors(
                nom_pairs, batch.vocab, batch.padded,
                ports=ports if need_ports else None,
            )
            nominated_slot = None
            if not nominated.empty:
                # batch pods carrying a nomination are in nom_pairs (same
                # objects, same slot resolution) — reuse, don't re-resolve
                slot_by_key = {p.key: slot for p, slot in nom_pairs}
                nominated_slot = np.full(len(pods), -1, dtype=np.int32)
                for i, p in enumerate(pods):
                    nominated_slot[i] = slot_by_key.get(p.key, -1)

            return _PreparedGroup(
                profile=profile, infos=infos, pods=pods,
                cycle_offsets=cycle_offsets, base_cycle=base_cycle,
                t0=t0, gs=gs, batch=batch, pbatch=pbatch, static=static,
                ports=ports, spread=spread, interpod=interpod,
                nominated=nominated, nominated_slot=nominated_slot,
                slot_nodes=slot_nodes, names=list(self.snapshot.names),
                volume_ctx=volume_ctx, services=services,
                dra_active=dra_active, fence=self._conflict_seq,
                occ_fence=self._occupancy_seq,
                occ_sensitive=bool(
                    need_ports
                    or need_spread
                    or need_interpod
                    or dra_active
                    or volume_ctx is not None
                    or nom_pairs
                ),
                step=self._trace_step,
                tensorize_stage=tensorize_stage,
            )

    def _fold_group(self, prep: _PreparedGroup) -> None:
        """Out-of-tree plugin + extender + DRA folding, OUTSIDE the
        cluster lock (arbitrary user code / HTTP round trips must not
        block ingest); it only touches the host-side static tables and
        immutable Node snapshots gathered at tensorize time."""
        static = prep.static
        slot_nodes = prep.slot_nodes
        pods = prep.pods
        dra_active = prep.dra_active
        dra_prefold = prep.dra_prefold
        unsched_reason = prep.unsched_reason
        if self.config.out_of_tree_plugins:
            # out-of-tree Scheduling Framework plugins: class-vectorized
            # folding into the static mask / extra-score tables. A
            # filter-only plugin set keeps extra_score=None so the fused
            # kernel's extra-add (and its compile variant) never engages.
            # Memoized on (plugin set, class-rep signature, node objects,
            # input mask): serve-mode batches of identical pod classes
            # against an unchanged cluster skip the O(classes x nodes)
            # Python re-run. Sound because solver-path plugins are pure
            # per (class identity, node) by the documented contract.
            from .framework.runtime import fold_out_of_tree

            sig = self._fold_signature(static, slot_nodes)
            cached = self._fold_cache.get(sig)
            # the cache holds STRONG refs to the node objects it hashed,
            # so a live entry's id()s cannot be recycled; the identity
            # re-check makes a hash collision with a dead generation
            # impossible to act on (review-caught id-reuse hazard)
            if cached is not None and len(cached[2]) == len(
                slot_nodes
            ) and all(a is b for a, b in zip(cached[2], slot_nodes)):
                self._fold_cache[sig] = self._fold_cache.pop(sig)  # LRU
                static.mask[:] = cached[0]
                if cached[1] is not None:
                    static.extra_score = cached[1].copy()
                metrics.fold_cache_total.labels("hit").inc()
            else:
                metrics.fold_cache_total.labels("miss").inc()
                extra = np.zeros(static.mask.shape, dtype=np.int32)
                fold_out_of_tree(
                    self.config.out_of_tree_plugins, static.reps,
                    slot_nodes, static.mask, extra,
                )
                if extra.any():
                    static.extra_score = extra
                if len(self._fold_cache) >= 8:
                    self._fold_cache.pop(next(iter(self._fold_cache)))
                self._fold_cache[sig] = (
                    static.mask.copy(),
                    extra.copy() if extra.any() else None,
                    list(slot_nodes),
                )
        if self.extender_clients:
            # findNodesThatPassExtenders + prioritizeNodes' extender pass,
            # folded per scheduling class like out-of-tree plugins (one
            # wire round trip per class+extender+verb per batch)
            from .server.extender_client import fold_extenders

            extra = (
                static.extra_score
                if static.extra_score is not None
                else np.zeros(static.mask.shape, dtype=np.int32)
            )
            if self.obs.enabled:
                # cross-process trace propagation: the webhook round
                # trips carry this batch's trace context so an
                # extender server sharing the obs layer attributes its
                # micro-batched evaluation to OUR trace (obs off =
                # unchanged wire bytes)
                cur = self.obs.current()
                tctx = {
                    "trace": prep.step,
                    "parent": cur.span_id if cur is not None else None,
                    "replica": (
                        self.fleet.replica if self.fleet is not None else ""
                    ),
                    "incarnation": self.config.incarnation,
                }
                for cl in self.extender_clients:
                    cl.trace_context = tctx
            try:
                fold_extenders(
                    self.extender_clients, static.reps, slot_nodes,
                    static.mask, extra,
                )
            finally:
                if self.obs.enabled:
                    for cl in self.extender_clients:
                        cl.trace_context = None
            if extra.any():
                static.extra_score = extra
        if self._gang is not None:
            # heterogeneity-aware scoring (gang/throughput.py): Gavel's
            # effective-throughput objective accumulates into the same
            # generic extra_score donor the folds above use, so every
            # solver path (fused + grouped) applies it with zero new
            # kernel surface. AFTER the fold-cache block (a cache hit
            # REPLACES extra_score) and the extender fold; BEFORE the
            # DRA mask fold, which only touches the mask.
            from .gang import fold_throughput

            fold_throughput(static, slot_nodes, self._gang.config)
        if dra_active:
            # dynamicresources Filter: fold per-class claim feasibility
            # into the static mask (allocated claims pin to their node).
            # Runs AFTER the out-of-tree/extender folds so the preemption
            # widen mask below already carries their rejections (widening
            # must never resurrect a node an extender vetoed), and keeps
            # their mask-keyed memo stable. The allocator's cached
            # context is reused — dra_generation-keyed build plus the
            # in-flight assumption overlay, so devices taken by pods
            # still binding are already masked out.
            from .ops.oracle.dra import ClaimError

            tdra = self.clock.perf()
            dra_ctx = self.claim_allocator.context()
            unresolvable: dict[int, str] = {}
            for ci, rep in enumerate(static.reps):
                if not (
                    rep.resource_claim_names
                    or rep.claim_templates_unresolved
                ):
                    continue
                try:
                    m = dra_ctx.feasible_mask(rep, slot_nodes)
                except ClaimError as e:
                    # UnschedulableAndUnresolvable: mask the class and
                    # surface the REASON on the pods' failure events
                    m = False
                    unresolvable[ci] = str(e)
                else:
                    # device exhaustion is Unschedulable, NOT
                    # Unresolvable: preemption may free devices, so
                    # candidate selection widens back to the pre-DRA
                    # mask (with a victims-release recheck —
                    # _dra_preempt_ok)
                    dra_prefold[ci] = static.mask[ci].copy()
                static.mask[ci] &= m
            if unresolvable:
                class_of = np.asarray(static.class_of)
                for i, p in enumerate(pods):
                    why = unresolvable.get(int(class_of[i]))
                    if why is not None:
                        unsched_reason[p.key] = why
            metrics.plugin_execution_duration_seconds.labels(
                "DynamicResources", "PreFilter", "Success"
            ).observe(self.clock.perf() - tdra)
    def _dispatch_group(
        self,
        prep: _PreparedGroup,
        defer: bool,
        allow_heal: bool = True,
        split: int = 1,
        tier: str | None = None,
        stream: bool = False,
        chain: bool = False,
        chain_key: tuple | None = None,
    ) -> "_InFlightSolve | list[_InFlightSolve]":
        """Upload + launch the device solve. ``defer=False`` blocks on
        the assignment read (the synchronous path); ``defer=True``
        returns immediately with an async device→host copy in flight so
        the read overlaps later host work (run_pipelined).
        ``allow_heal=False`` defers dirty-column healing while an
        earlier solve is still unapplied (see _DeviceSession.sync).
        ``split > 1`` (deferred only) dispatches the batch as chained
        sub-solves (ExactSolver.solve's RTT-hiding batch split) and
        returns one in-flight solve per sub-batch, all sharing this
        prep and its fences. ``tier`` (the resilient synchronous path)
        pins the fallback-ladder rung: TIER_MESH/None keep the
        configured mesh, TIER_SINGLE drops to one device, TIER_CPU
        additionally forces the CPU backend; None means the top tier.
        ``stream``/``chain``/``chain_key`` (run_streaming): keep the
        solve's full carried state device-resident as the session's
        stream carry, and — with ``chain`` — consume the PREVIOUS
        batch's resident carry instead of uploading host occupancy
        rows (ExactSolver.solve's cross-batch chain)."""
        solver = self.solvers[prep.profile]
        tier_name = tier or self.resilience.ladder[0]
        with self.cluster.lock:
            heal_stale = prep.profile in self._session_stale and allow_heal
            if heal_stale:
                self._session_stale.discard(prep.profile)
        if heal_stale:
            # a discarded solve polluted the device carry; with no other
            # solve in flight (allow_heal implies the pipeline drained),
            # re-upload from host truth before dispatching. The flag is
            # cleared under the lock, the device reset runs outside it
            # (only the drain thread resets sessions)
            solver.reset_session()
        if self._tier_last.get(prep.profile) != tier_name:
            # a ladder-tier change moves the solve (and its resident
            # session state) to a different device set: re-upload from
            # host truth. Only the drain/sync thread changes tiers, so
            # no other solve is in flight here.
            solver.reset_session()
            self._tier_last[prep.profile] = tier_name
        hook = self._solve_fault
        if hook is not None:
            # sim seam: after the heal bookkeeping (a raise here must
            # not strand a consumed stale flag), before the solve
            hook(prep.pods, tier_name)
        mesh = self.mesh if tier_name == TIER_MESH else None
        if prep.tensorize_stage is not None:
            # opened by _tensorize_group where prep.gs was read; a
            # re-dispatch of the same prep finds it closed
            prep.tensorize_stage.__exit__(None, None, None)
            prep.tensorize_stage = None
        t1 = self.clock.perf()
        # backlog drains thread the chunk id into the dispatch span so
        # `obs explain` can attribute a pod to its drain chunk
        span_extra = (
            {
                "drain_chunk": prep.step - self._drain_chunk_base,
                # the drain's root trace id: ties every chunk's spans
                # into ONE drain trace (set by drain_backlog)
                "drain_trace": self._drain_chunk_base,
            }
            if self._backlog_drain_active
            else {}
        )
        # session mode: node tables + carried state stay device-resident;
        # dirty snapshot columns heal by version; only assignments download
        #
        # compile attribution (obs/compile.py): any XLA compile firing
        # inside this bracket counts against the dispatch's shape/
        # static fingerprint — a steady-state batch re-compiling a
        # known shape is the silent hot-path killer the watcher's
        # recompilation gauge (and the known-shape regression test)
        # exists to catch. The span gets the delta as attributes when
        # a compile actually happened.
        compile_scope = self._compile_watcher.scope(
            f"{prep.profile}:p{prep.pbatch.padded}xn{prep.batch.padded}"
            f":split{split}:{tier_name}"
        )
        if self.telemetry is not None and self.telemetry.bundles is not None:
            # telemetry capture arm: the solver's capture_hook payload
            # that fires inside solve() below belongs to this batch step
            self.telemetry.bundles.arm(prep.step, prep.profile)
        with (
            self.telemetry.stage(
                "dispatch", step=prep.step, pods=len(prep.pods)
            )
            if self.telemetry is not None
            else _NOOP_SPAN
        ), self.obs.span(
            "dispatch", trace_id=prep.step, profile=prep.profile,
            defer=defer, healed=heal_stale, split=split,
            mesh_devices=self._mesh_devices, **span_extra,
        ) as dsp, _tier_device_context(tier_name), compile_scope:
            handle = solver.solve(
                prep.batch, prep.pbatch, prep.static, prep.ports,
                prep.spread, prep.interpod,
                col_versions=self.snapshot.col_versions,
                nominated=prep.nominated if not prep.nominated.empty else None,
                nominated_slot=prep.nominated_slot,
                defer_read=defer,
                allow_heal=allow_heal,
                split=split,
                mesh=mesh,
                chain_occupancy=chain,
                stream_carry_out=stream,
                chain_key=chain_key,
            )
            n_compiles, compile_s = compile_scope.delta()
            if n_compiles:
                dsp.set(
                    xla_compiles=n_compiles,
                    xla_compile_s=round(compile_s, 6),
                )
        dispatch_dt = self.clock.perf() - t1
        if not prep.timing_observed:
            prep.timing_observed = True
            prep.tensorize_seconds = max(t1 - prep.gs, 0.0)
            metrics.tensorize_seconds.observe(prep.tensorize_seconds)
            # extension-point durations with the reference's metric
            # names: host tensorization maps to PreFilter (documented,
            # SURVEY §6.5)
            metrics.framework_extension_point_duration_seconds.labels(
                "PreFilter", "Success", prep.profile
            ).observe(prep.tensorize_seconds)
        if isinstance(handle, list):
            # chained sub-solves (split > 1, or any streaming dispatch —
            # the stream path returns a list even unsplit): one flight
            # per sub-batch, sharing the prep. The chain's dispatch wall
            # spreads EVENLY across the sub-flights (totals stay honest,
            # and the adaptive-split estimator's per-pod rate isn't
            # inflated by charging the whole chain's dispatch to one
            # sub-batch); the shared tensorize cost reports on the first
            # flight only.
            share = dispatch_dt / len(handle)
            flights = [
                _InFlightSolve(
                    prep=prep,
                    handle=h,
                    dispatch_seconds=share,
                    lo=h.lo,
                    hi=h.lo + h.count,
                    tensorize_share=None if i == 0 else 0.0,
                )
                for i, h in enumerate(handle)
            ]
            if len(flights) > 1:
                # a clamped split (indivisible padding, nominated batch)
                # is NOT a chain: counting it would let a regression
                # that always clamps keep the chain metric (and the
                # tests reading it) green
                metrics.pipeline_subbatches_total.inc(len(flights))
            hook = self._post_dispatch_hook
            if hook is not None:
                # per sub-flight, honoring the seam's contract ("after
                # every dispatch"): the sim gets one fault-injection
                # point per dispatch→apply window, so mid-chain fence
                # interleavings are reachable from the smokes too
                for f in flights:
                    hook(f)
            return flights
        flight = _InFlightSolve(
            prep=prep, handle=handle, dispatch_seconds=dispatch_dt,
        )
        hook = self._post_dispatch_hook
        if hook is not None:
            hook(flight)
        return flight

    def _apply_group(
        self,
        flight: _InFlightSolve,
        res: BatchResult,
        pending: list,
        fence: int | None = None,
    ) -> bool:
        """Phase 2b (locked): read the assignments and apply them —
        assume / Reserve / Permit / PostFilter — atomically with the
        watch-event consumers. With ``fence`` set (pipelined path), the
        fence is RE-CHECKED inside the lock — a conflicting event can
        land during the unlocked device read — and a stale solve applies
        nothing and returns False (the caller discards). The synchronous
        path passes no fence: its solve-window staleness is the same one
        the reference's binding goroutines accept."""
        prep = flight.prep
        profile = prep.profile
        solver = self.solvers[profile]
        # a chained sub-flight covers prep pods [lo, hi); idx below is
        # slice-local — pod-indexed prep tensors use pod_base + idx
        pod_base = flight.lo
        infos, pods = flight.infos(), flight.pods()
        static, slot_nodes = prep.static, prep.slot_nodes
        volume_ctx, services = prep.volume_ctx, prep.services
        dra_active, dra_prefold = prep.dra_active, prep.dra_prefold
        unsched_reason = prep.unsched_reason
        base_cycle, cycle_offsets = prep.base_cycle, flight.cycle_offsets()
        t0, gs = prep.t0, prep.gs
        pending_before = len(pending)
        unsched_before = len(res.unschedulable)
        failures_before = len(res.bind_failures)
        tr = self.clock.perf()
        with (
            self.telemetry.stage(
                "deferred_read", step=prep.step, pods=len(infos)
            )
            if self.telemetry is not None
            else _NOOP_SPAN
        ):
            try:
                assignments = flight.assignments()
            except Exception as e:
                # the deferred device→host read itself died (session /
                # transfer loss after dispatch): surface it as a solver
                # fault so the resilience layer owns the retry instead
                # of the loop crashing (kubernetes_tpu/resilience)
                raise SolverReadError(
                    f"deferred assignment read failed: {e!r}"
                ) from e
        flight.read_seconds = self.clock.perf() - tr
        solve_dt = flight.dispatch_seconds + flight.read_seconds
        res.solve_seconds += solve_dt
        # the fused device program IS RunFilterPlugins+RunScorePlugins, so
        # its dispatch+read wall time reports under Filter (SURVEY §6.5)
        metrics.framework_extension_point_duration_seconds.labels(
            "Filter", "Success", profile
        ).observe(solve_dt)

        with self.cluster.lock, self.obs.span(
            "apply", trace_id=prep.step, profile=profile, pods=len(infos),
            read_seconds=flight.read_seconds,
        ) as asp:
            if fence is not None and (
                fence != self._conflict_seq
                or (
                    prep.occ_sensitive
                    and prep.occ_fence != self._occupancy_seq
                )
            ):
                asp.set(fence_stale=True)
                return False  # went stale during the device read
            if self.resilience.config.validate:
                # pre-apply output validation (resilience.py): a
                # silently-corrupt solve is a solve FAILURE feeding the
                # breaker, never applied. Runs after the fence check so
                # prep-time capacity can only have been FREED since the
                # solve (capacity-consuming events discard first) — a
                # flagged overcommit is always corruption, not churn.
                with (
                    self.telemetry.stage(
                        "validate", step=prep.step, pods=len(infos)
                    )
                    if self.telemetry is not None
                    else _NOOP_SPAN
                ):
                    why = validate_assignments(
                        prep, flight.lo, assignments,
                        disabled=frozenset(solver.config.disabled_filters),
                    )
                if why is not None:
                    raise SolveCorruptError(why)
            # the apply stage: the locked assume/Reserve/Permit region
            # after validation, to the end of this function (closed by
            # hand there: the region outlives this with statement)
            apply_stage = (
                self.telemetry.stage(
                    "apply", step=prep.step, pods=len(infos)
                ).__enter__()
                if self.telemetry is not None
                else None
            )
            if self.telemetry is not None and self.telemetry.bundles is not None:
                # the flight applied (fence passed, output validated):
                # its assignment slice is what a bundle replay of this
                # batch must reproduce bit-identically
                self.telemetry.bundles.note_assignments(
                    prep.step, flight.lo, assignments
                )
            # phase 2b: apply assignments — assume / Reserve / Permit /
            # PostFilter — atomically with the watch-event consumers
            preempt_placed: dict[int, list[Pod]] | None = None
            preempt_pdbs: list = []
            cluster_has_affinity = False
            postfilter_reasons: dict | None = None
            preempt_dt = 0.0
            preempt_ran = False  # a zero-duration run (FakeClock) still
            # counts as an observation — gating on the float hid the
            # PostFilter series from virtual-time runs
            bind_dt = 0.0
            # FitError diagnosis (schedule_one.go#FitError [U]): per-node
            # reasons don't exist inside the fused device pipeline, so the
            # failure path replays the scalar oracle's filters to build the
            # reference-shaped "0/N nodes are available: k Insufficient
            # cpu, ..." message. Lazy (failures only) and memoized on
            # (class, requests) — pods sharing constraint class AND
            # request vector share the diagnosis.
            fit_oracle = None
            fiterr_memo: dict[tuple, str] = {}
            # ktpu: ignore[TPU001]: static.class_of is a host-resident numpy table from tensorize — no device transfer happens here
            class_of_host = np.asarray(static.class_of)
            fe_nodes = sum(1 for n in slot_nodes if n is not None)
            fe_generic = (
                f"0/{fe_nodes} nodes are available: the batched "
                "filter pipeline rejected every candidate"
            )

            def fit_error_for(pod: Pod, idx: int) -> str:
                nonlocal fit_oracle
                # claims are already folded into the class identity when
                # DRA is active (class_key_extra); with DRA off they can't
                # influence the diagnosis, so keying them then would only
                # fragment the 16-entry replay budget
                key = (
                    int(class_of_host[idx]),
                    tuple(sorted(pod.resource_request().items())),
                    pod.host_ports(),  # ports are per-pod, not class-level
                    tuple(sorted(pod.resource_claim_names))
                    if dra_active
                    else (),
                )
                msg = fiterr_memo.get(key)
                if msg is not None:
                    return msg
                # the oracle replay is O(nodes x plugins) scalar Python on
                # a 1-vCPU host: bound the diagnosis work per batch so a
                # pathological batch of many distinct failing shapes can't
                # stall the scheduling loop (later shapes get the generic
                # message; their retry in a later batch gets a fresh budget)
                if len(fiterr_memo) >= 16:
                    return fe_generic
                if fit_oracle is None:
                    from .ops.oracle.profile import (
                        FullOracle,
                        make_oracle_nodes,
                    )

                    live = [n for n in slot_nodes if n is not None]
                    by_name = {
                        info2.node.name: list(info2.pods.values())
                        for info2 in self.cache.nodes.values()
                        if info2.node is not None and info2.pods
                    }
                    fit_oracle = FullOracle(
                        make_oracle_nodes(live, by_name),
                        volume_ctx=volume_ctx,
                        services=services,
                        spread_defaulting=solver.config.spread_defaulting,
                        disabled=frozenset(solver.config.disabled_filters),
                    )
                extra = None
                if dra_active and pod.resource_claim_names:
                    # the scalar replay has no DRA filter: contribute the
                    # claim-feasibility verdicts for nodes it accepts
                    try:
                        dm = self.claim_allocator.context().feasible_mask(
                            pod, slot_nodes
                        )
                        ok_by_name = {
                            n.name: bool(dm[i])
                            for i, n in enumerate(slot_nodes)
                            if n is not None
                        }

                        def extra(on):
                            if ok_by_name.get(on.node.name, True):
                                return None
                            return (
                                "node(s) cannot allocate the pod's "
                                "resourceclaim devices"
                            )
                    except Exception:
                        extra = None
                try:
                    msg = fit_oracle.fit_error(pod, extra=extra)
                except Exception:
                    msg = fe_generic
                if msg.endswith("nodes are available"):
                    # every scalar filter accepted some node: the rejection
                    # came from a folded filter the replay can't attribute
                    # (out-of-tree plugin / extender verdict) — stay honest
                    # instead of implying the cluster is full
                    msg = fe_generic
                fiterr_memo[key] = msg
                return msg
            gang_staged = 0
            for idx, (info, a) in enumerate(zip(infos, assignments)):
                pod = info.pod
                cycle = base_cycle + cycle_offsets[idx] + 1
                # gang members STAGE instead of entering pending, and
                # any failure marks their whole round failed — the
                # all-or-nothing resolution happens in _commit_all
                rd = self._gang_round_of(pod)
                if a < 0:
                    # failure path: PostFilter — defaultpreemption first, then
                    # out-of-tree PostFilter plugins (first success nominates)
                    nominated_node = None
                    if self.config.enable_preemption:
                        preempt_ran = True
                        if preempt_placed is None:
                            # shared across this batch's failures: occupancy
                            # snapshot, PDB list, and the cluster-wide
                            # pods-with-affinity flag (avoid per-pod rescans)
                            preempt_placed = self._placed_by_slot()
                            preempt_pdbs = self.cluster.list_pdbs()
                            cluster_has_affinity = any(
                                i2.pods_with_affinity
                                for i2 in self.cache.nodes.values()
                                if i2.node is not None
                            )
                        tpf = self.clock.perf()
                        nominated_node = self._try_preempt(
                            pod, static, pod_base + idx, res,
                            preempt_placed, slot_nodes,
                            preempt_pdbs, cluster_has_affinity, solver,
                            dra_prefold=dra_prefold,
                        )
                        preempt_dt += self.clock.perf() - tpf
                    if nominated_node is None and self.registry.post_filter:
                        preempt_ran = True
                        if postfilter_reasons is None:
                            # NodeToStatusMap analog, shared across this
                            # batch's failures: per-node reasons don't exist
                            # inside the fused pipeline, so every candidate
                            # carries the batch-level rejection
                            postfilter_reasons = {
                                n.name: "node did not satisfy the batched "
                                "filter pipeline"
                                for n in slot_nodes
                                if n is not None
                            }
                        tpf = self.clock.perf()
                        # fresh copy per pod: upstream's NodeToStatusMap is
                        # per-pod scratch a plugin may legitimately mutate
                        self._run_post_filter(pod, dict(postfilter_reasons))
                        preempt_dt += self.clock.perf() - tpf
                    res.unschedulable.append(pod.key)
                    self._requeue(info, cycle)
                    self._gang_note_fail(rd, pod, "unschedulable")
                    why = unsched_reason.get(pod.key) or fit_error_for(
                        pod, pod_base + idx
                    )
                    self._event(
                        pod, "FailedScheduling", why, type_="Warning",
                    )
                    if self.journal is not None:
                        self.journal.unschedulable(
                            prep.step, cycle, pod, prep, pod_base + idx,
                            reason=why, nominated=nominated_node or "",
                            attempts=info.attempts,
                        )
                    continue
                node_name = prep.names[int(a)]
                if self.fleet is not None:
                    # cross-shard admission (fleet/reconciler.py):
                    # ownership fence + occupancy recheck against
                    # peers' exchanged rows. A rejection is the
                    # fleet's Conflict-on-stale: requeue and retry,
                    # never block the fleet. The device session's
                    # carry counted the placement, so it heals before
                    # the next dispatch.
                    fleet_why = self.fleet.admit(pod, node_name, self.cache)
                    if fleet_why is not None:
                        self._session_stale.add(profile)
                        # trace propagation across the handoff: mint
                        # (or reuse) the pod's journey trace BEFORE the
                        # release so it rides the handoff row — the
                        # adopting replica's journal continues the SAME
                        # trace and `obs explain --fleet` renders one
                        # enqueue→handoff→re-admit→bind chain
                        pod_trace = ""
                        if self.journal is not None:
                            pod_trace = self.journal.pod_traces.get(
                                pod.key
                            ) or (
                                f"{self.journal.origin}:{prep.step}"
                                f":{pod.key}"
                            )
                            self.journal.pod_traces[pod.key] = pod_trace
                        handed_to = (
                            self.fleet.maybe_hand_off(
                                pod, trace=pod_trace
                            )
                            if rd is None
                            # gang members never hand off alone: the
                            # group must land together, so a rejected
                            # member retries locally with its siblings
                            else None
                        )
                        if handed_to is not None:
                            # released to a peer whose shard may host
                            # it: drop every local claim on the pod
                            # (its watch events now route to the peer)
                            self._in_flight.pop(pod.key, None)
                            self.queue.delete(pod.key)
                            if self.journal is not None:
                                self.journal.record(
                                    prep.step, cycle, pod, "discarded",
                                    node=node_name, profile=profile,
                                    reason=(
                                        f"handed off to {handed_to}: "
                                        + fleet_why
                                    ),
                                    attempts=info.attempts,
                                )
                                # the peer owns the journey now; keep
                                # no local trace entry behind
                                self.journal.pod_traces.pop(
                                    pod.key, None
                                )
                            continue
                        res.unschedulable.append(pod.key)
                        self._requeue(info, cycle)
                        self._gang_note_fail(rd, pod, fleet_why)
                        self._event(
                            pod, "FailedScheduling", fleet_why,
                            type_="Warning",
                        )
                        if self.journal is not None:
                            self.journal.record(
                                prep.step, cycle, pod, "unschedulable",
                                node=node_name, reason=fleet_why,
                                profile=profile, attempts=info.attempts,
                            )
                        continue
                try:
                    self.cache.assume_pod(pod, node_name)
                except Exception as e:  # cache inconsistency: requeue
                    # the device-resident solve DID place the pod; mark the
                    # column dirty so the session re-heals it from cache truth
                    self.snapshot.touch(int(a))
                    if self.fleet is not None:
                        # admit() may have CAS-staged the pending row at
                        # the hub already; a placement that never gets
                        # assumed must not keep distorting peers'
                        # admission until the next resync
                        self.fleet.withdraw(pod.key)
                    res.bind_failures.append((pod.key, str(e)))
                    self._requeue(info, cycle)
                    self._gang_note_fail(rd, pod, str(e))
                    if self.journal is not None:
                        self.journal.record(
                            prep.step, cycle, pod, "bind_failure",
                            node=node_name, reason=str(e), profile=profile,
                            attempts=info.attempts,
                        )
                    continue
                if self.fleet is not None:
                    # publish the assumed placement to the occupancy
                    # exchange so peers' admissions count it; every
                    # rollback path routes through _unreserve_all,
                    # which withdraws the row
                    self.fleet.stage(pod, node_name, self.cache)

                # Reserve point: in-tree volumebinding Reserve
                # (AssumePodVolumes) then out-of-tree ReservePlugins in
                # registration order; any failure unreserves everything
                # (reverse order), forgets the assume, and requeues
                state = CycleState()
                try:
                    tb = self.clock.perf()
                    if pod.pvc_names:
                        ninfo = self.cache.nodes.get(node_name)
                        if ninfo is None or ninfo.node is None:
                            raise VolumeBindingError(
                                f"node {node_name} vanished before volume binding"
                            )
                        self.volume_binder.assume_pod_volumes(pod, ninfo.node)
                    if self._dra and (
                        pod.resource_claim_names
                        or pod.claim_templates_unresolved
                    ):
                        # dynamicresources Reserve: assume concrete devices
                        # on the chosen node (the mask said they exist; a
                        # same-batch racer may have taken them — fail =>
                        # unreserve + requeue, like the reference's
                        # in-flight claim conflicts)
                        self.claim_allocator.assume_pod_claims(
                            pod, node_name
                        )
                    for p in self.registry.reserve:
                        st = p.reserve(state, pod, node_name)
                        if not st.is_success:
                            raise _Rejected(
                                f"Reserve plugin {p.name()} rejected: "
                                + "; ".join(st.reasons)
                            )
                    bind_dt += self.clock.perf() - tb
                except (
                    VolumeBindingError, ClaimAllocationError, _Rejected,
                ) as e:
                    self._unreserve_all(state, pod, node_name)
                    res.bind_failures.append((pod.key, str(e)))
                    self._requeue(info, cycle)
                    self._gang_note_fail(rd, pod, str(e))
                    self._event(
                        pod, "FailedScheduling", str(e), type_="Warning",
                    )
                    if self.journal is not None:
                        self.journal.record(
                            prep.step, cycle, pod, "bind_failure",
                            node=node_name, reason=str(e), profile=profile,
                            attempts=info.attempts,
                        )
                    continue

                # Permit point: approve / reject / wait
                # (framework.go#RunPermitPlugins); WAIT parks the pod in the
                # WaitingPods map — it stays assumed (+reserved) and the
                # binding completes or rolls back in a later cycle
                verdict = self._run_permit(state, pod, node_name)
                if isinstance(verdict, dict) and rd is not None:
                    # Permit WAIT is unsupported for pod-group members
                    # (documented limitation): a parked member would
                    # hold every sibling's staged placement hostage
                    # across cycles — convert to a rejection so the
                    # round resolves this batch and the gang retries
                    permit_why = (
                        "Permit WAIT is unsupported for pod-group "
                        "members (plugins: "
                        + ",".join(sorted(verdict)) + ")"
                    )
                    self._unreserve_all(state, pod, node_name)
                    res.unschedulable.append(pod.key)
                    self._requeue(info, cycle)
                    self._gang_note_fail(rd, pod, permit_why)
                    self._event(
                        pod, "FailedScheduling", permit_why,
                        type_="Warning", action="Permit",
                    )
                    if self.journal is not None:
                        self.journal.record(
                            prep.step, cycle, pod, "permit_rejected",
                            node=node_name, reason=permit_why,
                            profile=profile, attempts=info.attempts,
                        )
                    continue
                if isinstance(verdict, dict):
                    wp = WaitingPod(pod, node_name, verdict, self.clock.now())
                    self._waiting[pod.key] = (
                        wp, info, cycle, state, t0, prep.step,
                    )
                    if self.journal is not None:
                        self.journal.record(
                            prep.step, cycle, pod, "permit_wait",
                            node=node_name, profile=profile,
                            reason=",".join(sorted(verdict)),
                            attempts=info.attempts,
                        )
                    continue
                if verdict is not None:  # (plugin name, Status) rejection
                    self._unreserve_all(state, pod, node_name)
                    res.unschedulable.append(pod.key)
                    self._requeue(info, cycle)
                    permit_why = (
                        f"permit plugin {verdict[0]} rejected: "
                        + "; ".join(verdict[1].reasons)
                    )
                    self._gang_note_fail(rd, pod, permit_why)
                    self._event(
                        pod, "FailedScheduling", permit_why,
                        type_="Warning", action="Permit",
                    )
                    if self.journal is not None:
                        self.journal.record(
                            prep.step, cycle, pod, "permit_rejected",
                            node=node_name, reason=permit_why,
                            profile=profile, attempts=info.attempts,
                        )
                    continue

                # approved: the binding cycle commits AFTER the lock drops
                # (schedule_batch's pending pass). Gang members STAGE
                # on their round instead — they commit atomically (or
                # release together) when the round resolves.
                entry = (state, info, pod, node_name, cycle, t0, prep.step)
                if rd is not None:
                    rd["staged"].append(entry)
                    rd["done"].add(pod.key)
                    gang_staged += 1
                else:
                    pending.append(entry)
                # keep the lazily-snapshotted preemption view in sync with
                # assumes made later in this batch, so a subsequent failing
                # pod's dry-run sees current node occupancy (the cache-backed
                # view already counts the assume; a later bind failure
                # forgets it, making this at worst conservative)
                if preempt_placed is not None:
                    preempt_placed.setdefault(int(a), []).append(pod)
        if preempt_ran:
            metrics.framework_extension_point_duration_seconds.labels(
                "PostFilter", "Success", profile
            ).observe(preempt_dt)
        if bind_dt:
            # reserve-phase time (binds now commit post-lock and report
            # under the Bind point from schedule_batch)
            metrics.framework_extension_point_duration_seconds.labels(
                "Reserve", "Success", profile
            ).observe(bind_dt)

        # per-profile attempt metrics (this group's own wall time)
        attempt_avg = (self.clock.perf() - gs) / max(len(infos), 1)
        # "scheduled" attempts = this group's approved bindings (upstream
        # observes at scheduling-cycle end; a later bind failure records
        # separately under the error paths, like the binding goroutine)
        n_sched = len(pending) - pending_before + gang_staged
        n_unsched = len(res.unschedulable) - unsched_before
        n_fail = len(res.bind_failures) - failures_before
        if n_sched:
            metrics.schedule_attempts_total.labels("scheduled", profile).inc(n_sched)
            metrics.scheduling_attempt_duration_seconds.labels(
                "scheduled", profile
            ).observe(attempt_avg)
        if n_unsched:
            metrics.schedule_attempts_total.labels("unschedulable", profile).inc(
                n_unsched
            )
        if n_fail:
            metrics.schedule_attempts_total.labels("error", profile).inc(n_fail)
        if apply_stage is not None:
            apply_stage.__exit__(None, None, None)
        return True

    def _fold_signature(self, static, slot_nodes) -> bytes:
        """Memo key for the out-of-tree fold: plugin identities, the
        class reps' contract-visible content (labels, annotations,
        namespace, requests — the fields class_key_extra folds into the
        class identity beyond what the in-tree mask already encodes),
        the input mask bytes, and the node OBJECT identities (the cache
        replaces Node objects on update, so any node change rotates the
        key)."""
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        for p in self.config.out_of_tree_plugins:
            h.update(str(id(p)).encode())
        for rep in static.reps:
            # every field the solver-path plugin contract allows a plugin
            # to read (framework/interface.py): labels, annotations, and
            # the in-tree spec fields — selectors, affinity, tolerations,
            # requests, ports, spread. The in-tree mask does NOT encode
            # all of these (e.g. a toleration on an untainted cluster),
            # so they hash explicitly.
            h.update(
                repr(
                    (
                        sorted(rep.labels.items()),
                        sorted(rep.annotations.items()),
                        rep.namespace,
                        sorted(rep.resource_request().items()),
                        sorted(rep.node_selector.items()),
                        rep.affinity,
                        rep.tolerations,
                        rep.host_ports(),
                        rep.topology_spread_constraints,
                    )
                ).encode()
            )
        h.update(static.mask.tobytes())
        for n in slot_nodes:
            h.update(str(id(n)).encode())
        return h.digest()

    def _event(
        self, obj, reason: str, note: str,
        type_: str = "Normal", action: str = "Scheduling",
    ) -> None:
        """Events recorder (SURVEY §6.5): the broadcaster the reference
        wires through EventsToRegister, collapsed to direct records on
        the state service (the [BOUNDARY] apiserver stand-in dedups)."""
        self.cluster.record_event(
            obj, reason, note, type_=type_, action=action,
            timestamp=self.clock.now(),
        )

    # -- Reserve / Permit / Bind extension points (host-side, around the
    # device solve — framework.go#RunReservePluginsReserve,
    # #RunPermitPlugins, #WaitOnPermit, #RunPreBindPlugins,
    # #RunPostBindPlugins) --

    def _unreserve_all(self, state, pod: Pod, node_name: str) -> None:
        """Roll back a reservation: out-of-tree Unreserve in reverse
        registration order (idempotent by contract), volume unreserve,
        forget the assumed pod."""
        for p in reversed(self.registry.reserve):
            p.unreserve(state, pod, node_name)
        self.volume_binder.unreserve(pod.key)
        self.claim_allocator.unreserve(pod.key)
        if self.fleet is not None:
            self.fleet.withdraw(pod.key)
        try:
            self.cache.forget_pod(pod.key)
        except Exception:
            pass

    def _run_permit(self, state, pod: Pod, node_name: str):
        """None = approved; {plugin: timeout} = wait; (plugin, Status) =
        rejected. A rejection short-circuits, like RunPermitPlugins."""
        waits: dict[str, float] = {}
        for p in self.registry.permit:
            st, timeout = p.permit(state, pod, node_name)
            if st.code == StatusCode.WAIT:
                waits[p.name()] = max(float(timeout), 0.0)
            elif not st.is_success:
                return (p.name(), st)
        return waits or None

    def _commit_binding(
        self, entry: tuple, res: BatchResult, binders: list, held: bool
    ) -> bool:
        """The binding cycle for one approved pod — PreBind (out-of-tree
        plugins, then volumebinding's BindPodVolumes) -> Bind (extender
        delegate or the binding subresource) -> PostBind — in three
        phases: the wire phase (_bind_wire), the bind at the state
        service, the confirmation (_confirm_binding). An entry with a
        wire call in its cycle runs WITHOUT the cluster lock held (the
        call may cross a wire) and its bookkeeping re-acquires the lock
        briefly. A wire-free entry (``held``, decided by _wire_free)
        has no wire phase: _commit_all holds the lock around its whole
        run and every phase here re-enters it. Any failure unreserves
        and requeues with backoff (the bindingCycle failure path).
        Returns True when the pod bound."""
        state, _info, pod, node_name, _cycle, _t_start, _step = entry
        try:
            if held or not self._bind_wire(state, pod, node_name, binders):
                self.cluster.bind(
                    pod.namespace, pod.name, node_name,
                    fence=(
                        (self._fence_role, self._fence_token)
                        if self._fence_role is not None
                        else None
                    ),
                )
        except (ApiError, VolumeBindingError, _Rejected, ExtenderError) as e:
            self._bind_rejected(entry, res, e)
            return False
        self._confirm_binding(entry, res)
        return True

    def _bind_wire(
        self, state, pod: Pod, node_name: str, binders: list
    ) -> bool:
        """The wire phase of a binding cycle, never under the cluster
        lock: PreBind plugins, volume and claim binding, and the first
        interested binder extender's bind. True when that extender
        bound the pod (the binding subresource call is then its own)."""
        for p in self.registry.pre_bind:
            st = p.pre_bind(state, pod, node_name)
            if not st.is_success:
                raise _Rejected(
                    f"PreBind plugin {p.name()} rejected: "
                    + "; ".join(st.reasons)
                )
        if pod.pvc_names:
            self.volume_binder.bind_pod_volumes(pod)
        if self._dra and pod.resource_claim_names:
            self.claim_allocator.bind_pod_claims(pod)
        binder = next(
            (cl for cl in binders if cl.is_interested(pod)), None
        )
        if binder is None:
            return False
        # extender.go#Bind: the first interested binder extender
        # owns the binding subresource call (scope note: the
        # extender's own apiserver client carries its fence)
        binder.bind(pod, node_name)
        return True

    def _bind_rejected(
        self, entry: tuple, res: BatchResult, e: Exception
    ) -> None:
        """The bindingCycle failure path: unreserve, journal, requeue
        with backoff unless the pod is gone."""
        state, info, pod, node_name, cycle, _t_start, step = entry
        reason = e.reason if isinstance(e, ApiError) else str(e)
        fenced = isinstance(e, ApiError) and e.fenced
        with self.cluster.lock:
            if fenced:
                # this incarnation's fence token was revoked (lease
                # lost / partition / superseded): the state service
                # refused the commit — the zombie path the fence
                # exists to close. The pod requeues like any bind
                # conflict; the operator signal is the counter+log
                # (production wires reacquire_fence to lease
                # re-acquisition before commits can resume).
                metrics.commit_fenced_total.inc()
                self._fenced_commits += 1
                self._log.warning(
                    "bind of %s fenced: this incarnation's commit "
                    "fence (role %r) was revoked — operating as a "
                    "zombie until the lease is re-acquired",
                    pod.key, self._fence_role,
                    extra={"step": step},
                )
            self._unreserve_all(state, pod, node_name)
            res.bind_failures.append((pod.key, reason))
            if self.journal is not None:
                self.journal.record(
                    step, cycle, pod, "bind_failure",
                    node=node_name, reason=reason,
                    attempts=info.attempts,
                )
            try:
                self.cluster.get_pod(pod.namespace, pod.name)
            except ApiError:
                # deleted while the bind was in flight (the unlocked
                # window): don't requeue a pod that no longer exists
                return
            self._requeue(info, cycle)
            self._event(
                pod, "FailedScheduling",
                f"binding rejected: {reason}", type_="Warning",
                action="Binding",
            )

    def _confirm_binding(self, entry: tuple, res: BatchResult) -> None:
        """The confirmation phase of a bind that landed: cache and
        exchange bookkeeping, the Scheduled event and the journal's
        ``bound`` record under the lock, then the pod-level SLIs and
        PostBind."""
        state, info, pod, node_name, cycle, t_start, step = entry
        with self.cluster.lock:
            self.cache.finish_binding(pod.key)
            self.volume_binder.finish(pod.key)
            self.claim_allocator.finish(pod.key)
            if self.fleet is not None:
                # pending -> committed on the exchange: the row now
                # represents durable occupancy peers must respect
                # until the pod is deleted
                self.fleet.commit(pod.key)
            self._event(
                pod, "Scheduled",
                f"Successfully assigned {pod.key} to {node_name}",
                action="Binding",
            )
            res.scheduled.append((pod.key, node_name))
            if self.journal is not None:
                self.journal.record(
                    step, cycle, pod, "bound",
                    node=node_name, attempts=info.attempts,
                )
        res.latencies.append(self.clock.perf() - t_start)
        # pod-level SLIs: attempts-to-success histogram and e2e latency
        # from first queue entry, labeled by attempt count
        e2e = max(self.clock.now() - info.initial_attempt_timestamp, 0.0)
        res.e2e_latencies.append(e2e)
        metrics.pod_scheduling_attempts.observe(info.attempts)
        self._pass_child(
            metrics.pod_scheduling_sli_duration_seconds,
            str(min(info.attempts, 16)),
        ).observe(e2e)
        for p in self.registry.post_bind:
            p.post_bind(state, pod, node_name)
        with self.cluster.lock:
            self._in_flight.pop(pod.key, None)

    # called only from _schedule_cycle's locked region: ktpu: holds(cluster.lock)
    def _process_waiting(self, res: BatchResult, pending: list) -> None:
        """Settle WaitingPods (the batched WaitOnPermit): rejected or
        timed-out pods unreserve and requeue; fully-allowed pods complete
        their binding cycle in the post-lock pending pass."""
        now = self.clock.now()
        for key, (wp, info, cycle, state, t_start, step) in list(
            self._waiting.items()
        ):
            expired = wp.expired(now)
            if wp.rejected_by is not None or expired is not None:
                del self._waiting[key]
                self._unreserve_all(state, wp.pod, wp.node_name)
                res.unschedulable.append(key)
                self._requeue(info, cycle)
                why = (
                    f"permit plugin {wp.rejected_by} rejected: "
                    f"{wp.reject_message}"
                    if wp.rejected_by is not None
                    else f"permit plugin {expired} timed out"
                )
                self._event(
                    wp.pod, "FailedScheduling", why,
                    type_="Warning", action="Permit",
                )
                if self.journal is not None:
                    self.journal.record(
                        step, cycle, wp.pod,
                        "permit_rejected"
                        if wp.rejected_by is not None
                        else "permit_timeout",
                        node=wp.node_name, reason=why,
                        attempts=info.attempts,
                    )
            elif wp.allowed:
                del self._waiting[key]
                # back under the in-flight fence until the bind commits:
                # a MODIFIED event during the unlocked windows must not
                # re-enqueue a pod that is about to bind (review-caught)
                self._in_flight[key] = info
                pending.append(
                    (state, info, wp.pod, wp.node_name, cycle, t_start,
                     step)
                )

    def waiting_pods(self) -> dict[str, WaitingPod]:
        """GetWaitingPod/IterateOverWaitingPods surface: pod key ->
        WaitingPod; call .allow(plugin)/.reject(plugin, msg) on entries —
        verdicts apply at the start of the next scheduling cycle."""
        return {k: entry[0] for k, entry in self._waiting.items()}

    def _run_post_filter(self, pod: Pod, filtered: dict) -> str | None:
        """Out-of-tree PostFilter plugins, after default preemption found
        nothing: first success nominates (schedule_one.go's PostFilter
        loop semantics)."""
        state = CycleState()
        for p in self.registry.post_filter:
            node_name, st = p.post_filter(state, pod, filtered)
            if st.code == StatusCode.ERROR:
                raise RuntimeError(
                    f"PostFilter plugin {p.name()} error: {st.reasons}"
                )
            if st.is_success and node_name:
                try:
                    self.cluster.patch_pod_status(
                        pod.namespace, pod.name,
                        nominated_node_name=node_name,
                    )
                except ApiError:
                    return None
                pod.nominated_node_name = node_name
                return node_name
        return None

    def _record_metrics(
        self,
        res: BatchResult,
        n_pods: int,
        occ_sensitive: bool = False,
    ) -> None:
        """Batch-level metrics (per-profile attempt counters record in
        _solve_group); reference names, SURVEY §6.5. Also the tuning
        tick: every dispatch loop (sync, pipelined, streaming, drain)
        funnels applied batches through here, so this is where the
        auto-tuning runtime samples its CounterWindow and drives the
        per-knob controllers — one chokepoint, no loop grows its own
        tuning call."""
        metrics.solve_latency_seconds.observe(res.solve_seconds)
        metrics.solve_batch_size.observe(n_pods)
        for _, _, victims in res.preemptions:
            metrics.preemption_attempts_total.inc()
            metrics.preemption_victims.observe(len(victims))
        self._refresh_pending_gauge()
        if self.tuner is not None and n_pods > 0:
            self.tuner.observe_batch(
                self, res, n_pods, occ_sensitive=occ_sensitive
            )

    def _refresh_pending_gauge(self) -> None:
        """Set the pending_pods gauge from the queue's O(1) counters —
        called wherever queue contents change (watch ingest, pops,
        requeues, discards), not just the solve-recording path, so the
        gauge cannot go stale on idle cycles or queue-only
        transitions."""
        for queue_name, count in self.queue.pending_counts().items():
            self._pending_gauges[queue_name].set(count)

    # -- PostFilter: defaultpreemption (preemption.go#Evaluator.Preempt) --

    def _placed_by_slot(self) -> dict[int, list[Pod]]:
        out: dict[int, list[Pod]] = {}
        for slot, name in enumerate(self.snapshot.names):
            ninfo = self.cache.nodes.get(name) if name else None
            if ninfo is not None and ninfo.node is not None and ninfo.pods:
                out[slot] = list(ninfo.pods.values())
        return out

    def _try_preempt(
        self,
        pod: Pod,
        static,
        idx: int,
        res: BatchResult,
        placed_by_slot: dict[int, list[Pod]],
        slot_nodes: list | None,
        pdbs: list,
        cluster_has_affinity: bool,
        solver: ExactSolver,
        dra_prefold: dict | None = None,
    ) -> str | None:
        if pod.preemption_policy == "Never":
            return None
        prio = pod.effective_priority
        # cheap pre-check: any lower-priority pod anywhere?
        if not any(
            q.effective_priority < prio
            for placed in placed_by_slot.values()
            for q in placed
        ):
            return None

        batch = self.snapshot.batch
        static_row = static.mask[static.class_of[idx]]
        # DRA device exhaustion is preemptible (upstream dynamicresources
        # Filter returns Unschedulable, not Unresolvable): widen candidate
        # selection to the pre-DRA mask; a chosen node that the DRA fold
        # had excluded must pass the victims-release recheck below
        widen_row = None
        if dra_prefold and pod.resource_claim_names:
            widen_row = dra_prefold.get(int(static.class_of[idx]))
        # the pod's failure can involve beyond-fit filters when it carries
        # ports/spread constraints or pod (anti-)affinity is in play — then
        # the dry-run must re-run the full pipeline per candidate/re-add
        beyond_fit = bool(
            pod.host_ports()
            or pod.topology_spread_constraints
            or (
                pod.affinity is not None
                and (
                    pod.affinity.pod_affinity is not None
                    or pod.affinity.pod_anti_affinity is not None
                )
            )
            or cluster_has_affinity
        )
        result = self.preemptor.evaluate(
            pod, batch, self.snapshot.names, placed_by_slot,
            widen_row if widen_row is not None else static_row,
            pdbs,
            slot_nodes=slot_nodes, beyond_fit=beyond_fit,
            disabled=frozenset(solver.config.disabled_filters),
        )
        if widen_row is not None:
            # DRA path: the resource-driven dry-run doesn't model devices,
            # so its victim set (possibly empty) may not free any. Validate
            # it; when it doesn't hold up, select device-holding victims
            # directly (lowest priority first, PDB-respecting).
            ok = False
            if result is not None:
                try:
                    slot = self.snapshot.slot_of(result.node_name)
                except KeyError:
                    return None
                ok = bool(static_row[slot]) or (
                    bool(result.victims)
                    and self._dra_preempt_ok(
                        pod, result.node_name, result.victims
                    )
                )
            if not ok:
                # retry the UNWIDENED mask (a resource-only preemption on
                # a DRA-feasible node needs no device math) — but only
                # when the widened run FOUND something its recheck
                # rejected: static_row is a subset of widen_row, so a
                # widened None is already a subset None
                if result is not None:
                    result = self.preemptor.evaluate(
                        pod, batch, self.snapshot.names, placed_by_slot,
                        static_row, pdbs,
                        slot_nodes=slot_nodes, beyond_fit=beyond_fit,
                        disabled=frozenset(solver.config.disabled_filters),
                    )
                if result is None:
                    result = self._dra_victim_preempt(
                        pod, prio, placed_by_slot, widen_row, pdbs,
                        beyond_fit=beyond_fit, slot_nodes=slot_nodes,
                        disabled=frozenset(solver.config.disabled_filters),
                    )
        if result is None:
            return None
        # prepareCandidate: API-delete victims; clear lower-priority
        # nominations on the node; set our nominatedNodeName. Keep the
        # shared placed_by_slot in sync so later pods in this batch see the
        # evictions (the cache also updates via the DELETED watch events).
        victim_keys = {v.key for v in result.victims}
        for victim in result.victims:
            self._event(
                victim, "Preempted",
                f"Preempted by {pod.key} on node {result.node_name}",
                type_="Warning", action="Preempting",
            )
            try:
                self.cluster.delete_pod(victim.namespace, victim.name)
            except ApiError:
                pass  # already gone — fine
        for slot, placed in list(placed_by_slot.items()):
            remaining = [q for q in placed if q.key not in victim_keys]
            if len(remaining) != len(placed):
                if remaining:
                    placed_by_slot[slot] = remaining
                else:
                    del placed_by_slot[slot]
        for other in self.cluster.list_pods():
            if (
                not other.node_name
                and other.nominated_node_name == result.node_name
                and other.effective_priority < prio
            ):
                self.cluster.patch_pod_status(
                    other.namespace, other.name, nominated_node_name=""
                )
        try:
            self.cluster.patch_pod_status(
                pod.namespace, pod.name, nominated_node_name=result.node_name
            )
        except ApiError:
            return None  # pod vanished mid-preemption
        pod.nominated_node_name = result.node_name
        self._event(
            pod, "Nominated",
            f"preemption made room on {result.node_name}: nominated "
            f"({len(result.victims)} victim(s) evicted)",
            action="Preempting",
        )
        res.preemptions.append(
            (pod.key, result.node_name, [v.key for v in result.victims])
        )
        return result.node_name

    def _dra_victim_preempt(
        self,
        pod: Pod,
        prio: int,
        placed_by_slot: dict[int, list[Pod]],
        widen_row: np.ndarray,
        pdbs: list,
        beyond_fit: bool = False,
        slot_nodes: list | None = None,
        disabled: frozenset = frozenset(),
    ):
        """Device-driven victim selection for claim-bearing preemptors:
        per candidate node, evict the least-important claim-holding pods
        (PDB-respecting, never PDB-violating) until the pod's claims would
        allocate, and verify the pod still passes the filters with the
        victims gone (resources always; the full scalar pipeline when the
        pod/cluster carries beyond-fit constraints). Chooses the candidate
        needing the fewest victims (tie: node name) — the leading keys of
        pickOneNodeForPreemption."""
        from .ops.oracle.noderesources import fit_filter
        from .ops.oracle.preemption import classify_pdb_violations
        from .ops.oracle.profile import FullOracle, make_oracle_nodes
        from .solver.preemption import PreemptionResult

        ctx = self.claim_allocator.context()
        best: PreemptionResult | None = None
        for slot, resident in placed_by_slot.items():
            if slot >= len(widen_row) or not widen_row[slot]:
                continue
            node_name = self.snapshot.names[slot]
            info = self.cache.nodes.get(node_name)
            if info is None or info.node is None:
                continue
            lower = [q for q in resident if q.effective_priority < prio]
            _viol, safe = classify_pdb_violations(lower, pdbs)
            # claim-holding pods only, least important first
            holders = [
                q
                for q in sorted(
                    safe,
                    key=lambda q: (q.effective_priority, -q.start_time),
                )
                if any(
                    (c := ctx.claims.get(f"{q.namespace}/{n}")) is not None
                    and c.allocated_node == node_name
                    for n in q.resource_claim_names
                )
            ]
            victims: list[Pod] = []
            for q in holders:
                victims.append(q)
                if self._dra_preempt_ok(pod, node_name, victims):
                    break
            else:
                continue  # exhausted holders without freeing enough
            victim_keys = {v.key for v in victims}
            remaining = [q for q in resident if q.key not in victim_keys]
            if beyond_fit:
                # ports/spread/interpod/volume filters need the whole
                # cluster's occupancy (minus the victims) — a resource-only
                # check could evict victims on a node the pod still can't
                # land on (review-caught)
                live = [
                    (s2, n2)
                    for s2, n2 in enumerate(slot_nodes or [])
                    if n2 is not None
                ]
                by_name = {
                    n2.name: (
                        remaining
                        if n2.name == node_name
                        else placed_by_slot.get(s2, [])
                    )
                    for s2, n2 in live
                }
                oracle = FullOracle(
                    make_oracle_nodes([n2 for _, n2 in live], by_name),
                    disabled=disabled,
                )
                target = next(
                    on for on in oracle.nodes if on.node.name == node_name
                )
                if not oracle.filter_one(pod, target):
                    continue
            else:
                on = make_oracle_nodes(
                    [info.node], {node_name: remaining}
                )[0]
                if fit_filter(pod, on.res):
                    continue
            if best is None or (len(victims), node_name) < (
                len(best.victims), best.node_name
            ):
                best = PreemptionResult(
                    node_name=node_name, victims=victims, num_violating=0
                )
        return best

    def _dra_preempt_ok(self, pod: Pod, node_name: str, victims) -> bool:
        """Would evicting ``victims`` free enough claim devices on
        ``node_name`` for ``pod``'s claims? Simulates the deallocating
        controller's release (claims reserved exclusively by victims lose
        their allocation) on a copy of the claim context, then re-runs the
        greedy pick."""
        from .ops.oracle.dra import ClaimError

        ctx = self.claim_allocator.context()
        victim_keys = {v.key for v in victims}
        freed = set(ctx.taken.get(node_name, ()))
        claims = dict(ctx.claims)
        changed = False
        for key, c in list(claims.items()):
            if (
                c.allocated
                and c.allocated_node == node_name
                and c.reserved_for
                and all(k in victim_keys for k in c.reserved_for)
            ):
                for r in c.results:
                    freed.discard((r.driver, r.pool, r.device))
                from .api.dra import ResourceClaim

                claims[key] = ResourceClaim(
                    name=c.name,
                    namespace=c.namespace,
                    requests=c.requests,
                )
                changed = True
        if not changed:
            return False
        ctx.claims = claims
        ctx.taken = dict(ctx.taken)
        ctx.taken[node_name] = freed
        try:
            # resolves through the mutated ctx.claims, so released claims
            # are already the unallocated copies
            pod_claims = ctx.pod_claims(pod)
        except ClaimError:
            return False
        return ctx.pick(node_name, pod_claims) is not None

    def run_until_settled(self, max_batches: int = 10_000) -> list[BatchResult]:
        """Drain the active queue (benchmark / test driver)."""
        out = []
        for _ in range(max_batches):
            r = self.schedule_batch()
            if not r.progressed:
                break
            out.append(r)
        return out

    # -- double-buffered loop (VERDICT r4 #1) --

    def _plain_batch(self, pods: list[Pod]) -> bool:
        """True when tensorizing this batch reads NO host state that a
        previous batch's apply could change — exactly then it may be
        prepared and dispatched before the previous solve's results land
        (the device session carries the fit/balanced node state forward
        on its own). Ports/spread/interpod occupancy, volume and DRA
        context, and nominated-pod load are all rebuilt from the cache
        each batch, so any of them routes to the pipelined CARRY mode
        instead: drain in-flight solves before tensorizing, then overlap
        via the chained sub-batch split (run_pipelined)."""
        if self.nominated_pods or self._waiting:
            return False
        for p in pods:
            if p.host_ports() or p.topology_spread_constraints or p.pvc_names:
                return False
            if p.affinity is not None and (
                p.affinity.pod_affinity is not None
                or p.affinity.pod_anti_affinity is not None
            ):
                return False
            if self._dra and (
                p.resource_claim_names or p.claim_templates_unresolved
            ):
                return False
        if any(
            info.pods_with_affinity
            for info in self.cache.nodes.values()
            if info.node is not None
        ):
            return False
        if self.solver.config.spread_defaulting == "System":
            services = self.cluster.list_services()
            if services:
                from .ops.oracle.spread import default_selector

                if any(
                    not p.topology_spread_constraints
                    and default_selector(p, services) is not None
                    for p in pods
                ):
                    return False
        return True

    def _stream_chainable(self, pods: list[Pod]) -> bool:
        """Cross-batch chain eligibility (run_streaming): the device
        stream carry holds fit + port/spread/interpod occupancy rows —
        exactly those shapes may chain over an undrained ring. Volume
        and DRA feasibility are folded HOST-side at tensorize and are
        NOT in the carry, so a batch bearing them must drain first or
        it would solve against attach/device availability that misses
        the ring's pending placements (each such pod would then fail
        Reserve and requeue-churn)."""
        for p in pods:
            if p.pvc_names:
                return False
            if self._dra and (
                p.resource_claim_names or p.claim_templates_unresolved
            ):
                return False
        return True

    def _note_drain_chunk(self, step: int) -> None:
        """While a backlog drain is active, point the journal's
        drain_chunk tag at the chunk (trace step) whose records are
        about to be written. Derived PER CALL SITE — apply, discard,
        solver failure, quarantine — so failure-path records attribute
        to THEIR chunk, not whichever flight last applied (with a full
        stream ring those differ by up to stream_depth chunks). Driver
        thread only; drain_backlog pops the tag when the pass ends."""
        if self._backlog_drain_active and self.journal is not None:
            self.journal.tags["drain_chunk"] = (
                step - self._drain_chunk_base
            )

    def _discard_flight(self, flight: _InFlightSolve) -> None:
        """Drop a stale (or salvaged) deferred solve. The pods retry at
        the head of the active queue with no backoff (the failure is the
        solve's, not theirs) — EXCEPT pods that were externally bound or
        deleted mid-flight (often the very event that tripped the fence):
        requeueing those would create ghost entries that churn forever
        (review-caught). The device session's carried state counted the
        discarded placements, so it is marked stale and re-uploads from
        host truth once the pipeline has drained (a later solve may still
        be chained on it)."""
        metrics.solves_discarded_total.inc()
        prep = flight.prep
        if self.telemetry is not None:
            # fence-wait attribution: the discarded flight's dispatch +
            # read wall was work the fence threw away, and its capture
            # record can never complete
            self.telemetry.add_stage(
                "fence_wait",
                flight.dispatch_seconds + (flight.read_seconds or 0.0),
            )
            if self.telemetry.bundles is not None:
                self.telemetry.bundles.drop(prep.step)
        self._note_drain_chunk(prep.step)
        if prep.step != self._last_discard_step:
            self._discard_streak += 1
            self._last_discard_step = prep.step
        infos = flight.infos()
        with self.cluster.lock, self.obs.span(
            "fence", trace_id=prep.step, action="discard",
            pods=len(infos), fence=prep.fence,
        ):
            self._session_stale.add(prep.profile)
            if self._gang is not None and self._gang_rounds:
                # a discarded flight can never resolve its gang rounds:
                # staged siblings from earlier flights of the same
                # batch release + requeue here (this flight's own
                # members were never staged — they requeue below)
                self._release_gang_rounds_for(
                    {i.key for i in infos},
                    "gang member's solve was discarded",
                )
            for info in infos:
                self._in_flight.pop(info.key, None)
                if self.journal is not None:
                    self.journal.record(
                        prep.step, prep.base_cycle, info.pod, "discarded",
                        profile=prep.profile, attempts=info.attempts,
                    )
                try:
                    cur = self.cluster.get_pod(
                        info.pod.namespace, info.pod.name
                    )
                except ApiError:
                    continue  # deleted while the solve was in flight
                if cur.node_name:
                    continue  # bound externally while in flight
                info.pod = cur
                self.queue.requeue_popped(info)
            self._refresh_pending_gauge()

    # per-batch apply path: device reads only through the sanctioned
    # _InFlightSolve.assignments boundary: ktpu: hot
    def _apply_flight(self, flight: _InFlightSolve) -> BatchResult:
        """Apply (or discard) a deferred solve and commit its bindings."""
        res = BatchResult()
        pending: list = []
        prep = flight.prep
        infos = flight.infos()
        self._note_drain_chunk(prep.step)
        # ktpu: ignore[LOCK001]: deliberately unlocked pre-check — a torn read can only misroute to the locked re-check inside _apply_group or to a discard, both safe
        fence_fresh = prep.fence == self._conflict_seq
        # ktpu: ignore[LOCK001]: same deliberately unlocked pre-check; the locked re-check inside _apply_group is authoritative
        occ_fresh = not prep.occ_sensitive or prep.occ_fence == self._occupancy_seq
        if fence_fresh and occ_fresh:
            applied = False
            ta = self.clock.perf()
            try:
                # the fence is re-checked INSIDE _apply_group's locked
                # region: a conflicting event can land during the device
                # read (review-caught check-to-lock window)
                applied = self._apply_group(
                    flight, res, pending, fence=prep.fence
                )
                self._note_flight_timing(flight, len(infos))
                # a deferred read that blocked the driver > 1 ms was
                # not hidden by overlapped host work / the completion
                # thread's pre-wait. The threshold makes this
                # deterministic under FakeClock (virtual reads never
                # block).
                if (
                    self._streaming_active
                    and isinstance(flight.handle, DeferredAssignments)
                    and flight.read_seconds > 1e-3
                ):
                    metrics.stream_unhidden_reads_total.inc()
                if applied:
                    # host cost = this batch's own tensorize + apply
                    # phases; wall-since-pop would charge the overlapped
                    # batches' work and the hidden RTT to this batch
                    # (review-caught). Chained sub-flights report the
                    # shared tensorize cost on the first flight only.
                    tshare = (
                        prep.tensorize_seconds
                        if flight.tensorize_share is None
                        else flight.tensorize_share
                    )
                    res.host_seconds = tshare + (
                        self.clock.perf() - ta - flight.read_seconds
                    )
                    self._record_metrics(
                        res, len(infos),
                        occ_sensitive=prep.occ_sensitive,
                    )
            except SolverFaultError as e:
                # the solve is the failure (read death / corrupt
                # output), not the fence: requeue the pods for an
                # immediate retry and route it through the synchronous
                # resilient path, where the fallback ladder owns it.
                # Raised pre-mutation, so the discard is clean.
                self.resilience.note_async_failure(prep.profile)
                self._solver_failed(
                    infos, e, None, prep.step, prep.base_cycle
                )
                self._discard_flight(flight)
                res.completed_at = self.clock.perf()
                return res
            except Exception:
                # the fence matched, so _apply_group may have read the
                # device assignments before dying: the session's carried
                # state counts this batch's placements, but the requeued
                # pods never bound. Mark the carry stale so the next
                # dispatch re-uploads from host truth instead of counting
                # phantom placements against future solves (ADVICE r5 #3)
                with self.cluster.lock:
                    self._session_stale.add(prep.profile)
                self._requeue_unhandled(infos, pending, res)
                self._commit_all(infos, pending, res)
                raise
            if applied:
                # forward progress: reset the backstop (and the
                # within-chain discard dedup)
                self._discard_streak = 0
                self._last_discard_step = -1
                self._commit_all(infos, pending, res)
                if self._backlog_drain_active and self.fleet is not None:
                    # fleet drain: the per-chunk progress report feeds
                    # the hub's lease ledger AND refreshes this
                    # replica's liveness stamp — a replica deep in a
                    # long drain writes nothing else to the hub, and
                    # without the touch it would age past max_row_age_s
                    # and flip every peer conservative
                    self.fleet.drain_chunk_progress(
                        [k for k, _ in res.scheduled]
                    )
                res.completed_at = self.clock.perf()
                return res
        self._discard_flight(flight)
        res.completed_at = self.clock.perf()
        return res

    def _note_flight_timing(self, flight: _InFlightSolve, n_pods: int) -> None:
        """Feed the adaptive batch-split estimators — which live in the
        shared CounterWindow (kubernetes_tpu/tuning), the one home of
        every estimate a knob decision reads — from an applied (or
        read-then-discarded) flight. Driver thread only."""
        self.window.note_read(
            flight.read_seconds, flight.dispatch_seconds, n_pods
        )

    _MAX_PIPELINE_SPLIT = 8

    def _choose_split(self, n_pods: int) -> int:
        """Sub-batch count for one popped batch (the RTT-hiding batch
        split). A fixed config wins; with the tuning runtime governing
        the knob, its hill-climb controller owns the value outright;
        otherwise the adaptive default (CounterWindow.split_estimate)
        splits once the estimated device solve time for the batch
        exceeds the estimated read round trip, so the assignment read
        of sub-batch i can overlap the solve of i+1 — the knob that
        attacks the per-batch RTT floor. Controller and adaptive rule
        read the SAME window, so the two can never fight over the split
        from divergent private estimates (ISSUE 13 satellite). The
        solver clamps the request to a feasible (group-aligned) divisor
        of the padded pod axis."""
        cfg = self.config.pipeline_split
        if cfg == 1:
            return 1
        if cfg > 1:
            return min(cfg, self._MAX_PIPELINE_SPLIT)
        if self.tuner is not None:
            tuned = self.tuner.split_override(n_pods)
            if tuned is not None:
                return min(max(tuned, 1), self._MAX_PIPELINE_SPLIT)
        return self.window.split_estimate(
            n_pods, self._MAX_PIPELINE_SPLIT
        )

    def run_pipelined(self, max_batches: int = 10_000) -> list[BatchResult]:
        """Drain the queue with deferred solves in flight: host work for
        the NEXT dispatch overlaps the device→host read of solves
        already dispatched, so steady-state throughput pays host work,
        not blocking reads (the reference's
        scheduleOne overlaps binding the same way —
        schedule_one.go#scheduleOne's bind goroutine [U] — extended here
        to the device boundary). Every popped batch takes one of three
        modes (scheduler_pipeline_mode_total):

        - **overlap**: _plain_batch shapes — batch k+1 is tensorized and
          dispatched BEFORE batch k's assignments land (the device
          session carries fit state forward, so k+1's solve already sees
          k's placements). Extender / out-of-tree Filter+Score folding
          is a pre-dispatch host stage here: verdicts fold into the
          class tables per batch and read nothing a previous apply
          writes, so they ride the overlap instead of forcing the
          synchronous loop.
        - **carry**: hard shapes (ports/spread/interpod, volumes, DRA,
          nominated pods) and multi-profile sub-batches — in-flight
          solves drain FIRST so tensorization reads exact occupancy,
          then the batch dispatches as up to K chained sub-solves whose
          occupancy rows stay device-resident between them
          (BatchCarriedUsage): the assignment read of sub-batch i
          overlaps the solve of i+1, and each sub-batch's apply/bind
          work overlaps the next sub-batch's solve. Only the final read
          pays an un-hidden RTT per popped batch.
        - **sync**: the livelock backstop (below) and WaitingPod
          settlement, via the fence-free synchronous cycle.

        Safety: every dispatched solve is fenced on _conflict_seq, and
        occupancy-sensitive solves additionally on _occupancy_seq
        (assigned-pod deletes/label re-keys, external DRA claim writes —
        the event kinds whose effects the carried state cannot absorb).
        A conflicting event between dispatch and apply discards the
        solve, resets the device session, and requeues the pods for an
        immediate retry.

        Livelock backstop (ADVICE r5 #2): _PIPELINE_FALLBACK_AFTER
        consecutive fence discards force one synchronous (fence-free)
        cycle — counted by scheduler_pipeline_fallback_total — so
        sustained capacity/mask event churn degrades to the synchronous
        path's throughput instead of zero forward progress."""
        out: list[BatchResult] = []
        flights: list[_InFlightSolve] = []

        def apply_one() -> None:
            f = flights.pop(0)
            r = self._apply_flight(f)
            if r.progressed:
                out.append(r)

        def drain() -> None:
            while flights:
                apply_one()

        batches = 0
        try:
            while batches < max_batches:
                if self.fleet is not None and self.fleet.maybe_resync(
                    self
                ):
                    # the partition moved: in-flight solves are fenced
                    # stale (resync bumped both fences) — drain so
                    # they discard before the next shard-scoped pop
                    drain()
                if self._waiting:
                    drain()
                    # WaitingPod settlement is a synchronous cycle: it
                    # counts under mode="sync" like the backstop does
                    metrics.pipeline_mode_total.labels("sync").inc()
                    r = self.schedule_batch()
                    batches += 1
                    if not r.progressed:
                        break
                    out.append(r)
                    continue
                t0 = self.clock.perf()
                with self.cluster.lock:
                    self._release_quarantine()
                    self._reap_expired_assumes()
                    self.queue.flush_unschedulable_leftover()
                    infos = self.queue.pop_batch(self.config.batch_size)
                    for i in infos:
                        self._in_flight[i.key] = i
                    if self._gang is not None:
                        # gang gate BEFORE base_cycle: the gate moves
                        # pods in and out of the batch, and base_cycle
                        # must describe the batch that actually runs
                        infos = self._gang_gate(infos)
                    base_cycle = self.queue.scheduling_cycle - len(infos)
                    plain = bool(infos) and self._plain_batch(
                        [i.pod for i in infos]
                    )
                    self._refresh_pending_gauge()
                if not infos:
                    if flights:
                        drain()
                        continue  # discards/failures may requeue work
                    if self.rebalancer is not None:
                        # idle + pipeline drained: the one safe point
                        # for a rebalance pass in this loop (no
                        # in-flight solve can go stale on the eviction
                        # events). Evictions re-populate the queue, so
                        # loop back and schedule the migrations.
                        r = BatchResult()
                        if self.rebalancer.maybe_run(self, r) > 0:
                            r.completed_at = self.clock.perf()
                            out.append(r)
                            continue
                    break
                batches += 1
                # batch id for this pop's spans/journal (the sync branch
                # below re-enters via _run_popped, not schedule_batch)
                self._trace_step += 1
                if self.resilience.should_sync():
                    # degraded mode (kubernetes_tpu/resilience): a
                    # ladder tier is tripped or probing, an async solve
                    # failure is pending, or the ladder is pinned.
                    # Deferred dispatch assumes the healthy top tier,
                    # so the batch routes through the synchronous
                    # resilient cycle, which owns rebuilds, tier
                    # descent, probes, and quarantine.
                    metrics.pipeline_mode_total.labels("sync").inc()
                    drain()
                    r = self._run_popped(infos, t0)
                    if r.progressed:
                        out.append(r)
                    continue
                if self._discard_streak >= self._PIPELINE_FALLBACK_AFTER:
                    # livelock backstop (ADVICE r5 #2): N consecutive
                    # fence discards mean conflicting events are landing
                    # faster than one per dispatch→apply window, and the
                    # fenced pipeline can requeue forever with zero
                    # forward progress. One synchronous cycle applies
                    # WITHOUT a fence (accepting the same solve-window
                    # staleness the reference's binding goroutines do),
                    # guaranteeing at least one batch lands per N
                    # discards under sustained churn.
                    metrics.pipeline_fallback_total.inc()
                    metrics.pipeline_mode_total.labels("sync").inc()
                    self._log.warning(
                        "pipeline livelock backstop engaged after %d "
                        "consecutive fence discards: one synchronous "
                        "cycle", self._discard_streak,
                        extra={"step": self._trace_step},
                    )
                    drain()
                    r = self._run_popped(infos, t0)
                    # the synchronous cycle applied (no fence): the
                    # backstop counter restarts from real progress
                    self._discard_streak = 0
                    self._last_discard_step = -1
                    if r.progressed:
                        out.append(r)
                    continue
                # profile sub-batches in pop order (multi-profile configs
                # pipeline per group; single-profile is one group)
                groups = self._group_by_profile(infos)
                overlap_ok = plain and len(groups) == 1
                metrics.pipeline_mode_total.labels(
                    "overlap" if overlap_ok else "carry"
                ).inc()
                # ``owned``: popped groups not yet handed to a flight —
                # an exception below must requeue exactly these (handing
                # off removes a group; review-caught leak)
                owned: list[list[QueuedPodInfo]] = [g[1] for g in groups]
                try:
                    for profile, group_infos, offsets in groups:
                        self._pipeline_group(
                            profile, group_infos, offsets, base_cycle,
                            t0, overlap_ok, flights, apply_one, drain,
                            owned,
                        )
                except Exception:
                    if owned:
                        with self.cluster.lock:
                            base = self.queue.scheduling_cycle
                            for group_infos in owned:
                                for info in group_infos:
                                    self._requeue(info, base)
                    raise
            drain()
        except Exception:
            # the crash trigger for the pipelined loop (the synchronous
            # loop dumps from schedule_batch)
            if self.flight is not None:
                path = self.flight.dump(trigger="crash")
                self._log.exception(
                    "pipelined loop failed; flight recorder dump: %s",
                    path, extra={"step": self._trace_step},
                )
            raise
        finally:
            # exception escape hatch: dispatched-but-unapplied solves
            # must not strand their pods in _in_flight nor leave the
            # device session silently ahead of host truth (review-caught)
            for f in flights:
                self._discard_flight(f)
            flights.clear()
        return out

    def _pipeline_group(
        self,
        profile: str,
        infos: list[QueuedPodInfo],
        cycle_offsets: list[int],
        base_cycle: int,
        t0: float,
        overlap_ok: bool,
        flights: list,
        apply_one,
        drain,
        owned: list,
    ) -> None:
        """Tensorize, fold, and dispatch one profile group through the
        pipeline, leaving its LAST sub-flight in ``flights`` so the next
        pop/tensorize overlaps its read. Carry-mode groups (overlap_ok
        False) drain first: their occupancy tensors and volume/claim
        contexts must see every prior apply — the RTT hiding then comes
        from the chained sub-batch split and from each sub-batch's
        apply/bind work overlapping its successor's solve."""
        if not overlap_ok:
            drain()
        elif flights:
            with self.cluster.lock:
                stale = bool(self._session_stale)
            if stale or flights[0].prep.profile != profile:
                # drain before dispatch when (a) the last apply
                # discarded a solve — the stale device carry must
                # re-upload at dispatch — or (b) the in-flight solve
                # belongs to ANOTHER profile: its placements live only
                # in that profile's session carry, so this profile's
                # tensorize/session would double-book the capacity it
                # claimed (multi-profile configs overlap only
                # same-profile consecutive batches)
                drain()
        prep = self._tensorize_group(
            profile, infos, cycle_offsets, base_cycle, t0
        )
        with self.obs.span(
            "fold", trace_id=prep.step, profile=profile,
            extenders=len(self.extender_clients),
            plugins=len(self.config.out_of_tree_plugins),
        ):
            # extender / out-of-tree / DRA folding as a pre-dispatch
            # host stage: pure per (class, node) by contract, so it
            # overlaps an in-flight solve and its device→host read
            self._fold_group(prep)
        if flights and prep.fence != flights[0].prep.fence:
            # an event landed since the in-flight solve's snapshot. The
            # deferred heal (allow_heal=False) is only conservative for
            # USAGE columns — node TABLES (allocatable/valid) can
            # shrink, and a solve against stale tables would carry THIS
            # prep's fresh fence and apply a capacity violation
            # (review-caught). Drain first: the stale flight discards
            # itself, and this dispatch heals with current tables.
            drain()
        split = self._choose_split(len(infos))
        try:
            try:
                new = self._dispatch(
                    prep, allow_heal=not flights, split=split
                )
            except SessionDrainRequired:
                # node/vocab shape change with a solve still in flight:
                # apply it, then dispatch with healing
                drain()
                new = self._dispatch(prep, allow_heal=True, split=split)
        except Exception as e:
            # deferred dispatch failed at the top tier
            # (kubernetes_tpu/resilience): no flight exists, so requeue
            # the batch for an immediate retry and flag the failure —
            # the next pop routes it through the synchronous resilient
            # cycle, where the fallback ladder owns rebuild/descent/
            # bisection. The session may have consumed a partial
            # upload: mark it stale so the next dispatch heals.
            with self.cluster.lock:
                self._session_stale.add(profile)
            self.resilience.note_async_failure(profile)
            self._solver_failed(infos, e, None, prep.step, base_cycle)
            self._requeue_immediate(infos)
            owned.pop(0)
            return
        flights.extend(new)
        # handoff point: from here the flights own this group's pods —
        # a later exception must requeue them via the flight-discard
        # path, NOT the owned-groups requeue (double-requeue hazard)
        owned.pop(0)
        # apply everything but the newest sub-flight now: each read was
        # overlapped by the dispatches above (or by the next sub-solve
        # already running on device); the survivor overlaps the next
        # pop/tensorize
        while len(flights) > 1:
            apply_one()

    def _dispatch(
        self, prep: _PreparedGroup, allow_heal: bool, split: int
    ) -> list[_InFlightSolve]:
        """Deferred dispatch normalized to a flight list (split == 1
        keeps the legacy single-flight _dispatch_group signature the
        fence tests and the sim monkeypatch)."""
        if split > 1:
            got = self._dispatch_group(
                prep, defer=True, allow_heal=allow_heal, split=split
            )
            return got if isinstance(got, list) else [got]
        return [
            self._dispatch_group(prep, defer=True, allow_heal=allow_heal)
        ]

    # -- streaming dispatcher (the device-resident solve loop) --

    def _ensure_completion_thread(self) -> None:
        """Lazily start the streaming dispatcher's completion thread:
        it parks on each dispatched solve's async D2H transfer
        (DeferredAssignments.wait) so the wait is paid off the driver
        thread — by the time the driver's apply calls get(), the value
        is host-side. The thread holds
        no locks and touches no scheduler state beyond the in-flight
        gauge, so it cannot perturb the driver's (deterministic)
        apply order."""
        if self._completion_thread is not None:
            return
        import queue as _queue
        import threading
        import weakref

        self._completion_q = _queue.SimpleQueue()
        t = threading.Thread(
            # static target over the queue alone: a bound method would
            # pin this Scheduler (and its device session) alive for the
            # daemon thread's whole process lifetime
            target=Scheduler._completion_loop,
            args=(self._completion_q,),
            name="ktpu-stream-completion",
            daemon=True,
        )
        self._completion_thread = t
        t.start()
        # the static target keeps the Scheduler collectable; this makes
        # the thread follow it out — processes that build schedulers
        # repeatedly (restart recovery, fleet sims) must
        # not accumulate one parked thread + queue per instance. GC-time
        # only (atexit=False): waking a parked daemon thread during
        # interpreter shutdown exits it through C++ frames
        # (std::terminate → SIGABRT); at exit the parked threads are
        # harmless
        fin = weakref.finalize(self, self._completion_q.put, None)
        fin.atexit = False

    # the completion thread's drain loop — hot-path scoped so TPU001
    # guards it against accidental host syncs: the only device
    # interaction allowed here is the sanctioned
    # DeferredAssignments.wait (park on the async D2H; the driver's
    # get() stays the one read): ktpu: hot
    @staticmethod
    def _completion_loop(q) -> None:
        while True:
            handle = q.get()
            if handle is None:
                return  # shutdown sentinel (GC finalizer / tests)
            handle.wait()
            metrics.stream_inflight_reads.dec()

    def _stream_track(self, flights: list) -> None:
        """Hand a new slot's deferred reads to the completion thread."""
        for f in flights:
            if isinstance(f.handle, DeferredAssignments):
                metrics.stream_inflight_reads.inc()
                self._completion_q.put(f.handle)

    def run_streaming(self, max_batches: int = 10_000) -> list[BatchResult]:
        """Drain the queue through the STREAMING dispatcher: one
        persistent device-resident solve loop replacing run_pipelined's
        three modes (overlap/carry/sync) — the per-batch RTT floor
        becomes a per-event-fence floor.

        Mechanics per popped batch (mode counter ``stream``):

        - tensorize host-side (the port-occupancy staging reuses the
          previous batch's vocab scan when the cache is unchanged) and
          fold extenders/plugins/DRA as the usual pre-dispatch stage;
        - dispatch into the bounded work ring
          (SchedulerConfig.stream_depth): when the batch's occupancy
          vocabulary fingerprints identically to the previous slot's
          (ExactSolver.stream_chain_key) and no fence moved, the solve
          CHAINS on the previous batch's device-resident carry
          (BatchCarriedUsage) — occupancy advanced by earlier
          placements never round-trips through host tensorize, and
          hard shapes stop paying the drain-per-batch the carry mode
          charged;
        - assignment reads stream back asynchronously: the completion
          thread pre-waits each deferred read so the driver-side apply
          never blocks on a device read in steady state
          (scheduler_stream_unhidden_reads_total counts the ones that
          did — the ring drain pays at most one);
        - applies run strictly in dispatch order on the driver thread
          (determinism: the completion thread only warms transfers, it
          never reorders work).

        Fencing: each slot's prep carries its fence epoch
        (_conflict_seq/_occupancy_seq at tensorize). A conflicting
        event discards exactly the slots dispatched before it
        (scheduler_stream_slot_discard_total) — chained successors
        share the epoch and die with their parent, slots dispatched
        after the event survive. An un-chainable batch (vocabulary
        changed, columns dirtied by applies, fence moved) drains the
        ring first; hard shapes then re-tensorize against exact
        occupancy, which is always correct.

        Degraded mode: ``resilience.should_sync()`` routes the batch
        through the synchronous resilient cycle (fallback ladder,
        bisection quarantine), exactly like run_pipelined; the
        fence-discard livelock backstop is unchanged."""
        out: list[BatchResult] = []
        slots: list[_StreamSlot] = []
        depth = max(self.config.stream_depth, 1)
        self._ensure_completion_thread()
        self._streaming_active = True

        def apply_slot() -> None:
            slot = slots.pop(0)
            metrics.stream_depth.set(len(slots))
            clean = True
            for f in slot.flights:
                r = self._apply_flight(f)
                if r.progressed:
                    out.append(r)
                if r.bind_failures:
                    clean = False
            if self._last_discard_step == slot.prep.step:
                # the fence killed (at least the tail of) this slot —
                # count SLOTS, not sub-flights: one conflicting window
                # is one discard epoch
                clean = False
                metrics.stream_slot_discard_total.inc()
            if not clean:
                # a discard or assume/bind failure may have left the
                # session persist ahead of host truth (phantom
                # placement): the carry must not be chained on — drop
                # it; the next dispatch drains + heals. (Clean applies
                # need no action HERE: their column dirt only appears
                # when the next tensorize materializes the cache into
                # the snapshot, and _stream_group advances the carry
                # baseline at exactly that point.)
                solver = self.solvers.get(slot.prep.profile)
                if solver is not None:
                    solver.invalidate_stream_carry()

        def drain() -> None:
            while slots:
                apply_slot()

        batches = 0
        try:
            while batches < max_batches:
                if not slots:
                    # ring-drain boundary: the ONE point a stream-depth
                    # change (the auto-tuner's, or an operator flipping
                    # config.stream_depth on a live scheduler) may take
                    # effect — an in-flight ring keeps the depth it was
                    # dispatched under, so a shrink can never strand a
                    # dispatched-but-unapplied slot
                    depth = max(self.config.stream_depth, 1)
                if self.fleet is not None and self.fleet.maybe_resync(
                    self
                ):
                    # the partition moved: in-flight solves are fenced
                    # stale (resync bumped both fences) — drain so they
                    # discard before the next shard-scoped pop
                    drain()
                if self._waiting:
                    drain()
                    # WaitingPod settlement runs a synchronous cycle
                    metrics.pipeline_mode_total.labels("sync").inc()
                    r = self.schedule_batch()
                    batches += 1
                    if not r.progressed:
                        break
                    out.append(r)
                    continue
                t0 = self.clock.perf()
                with self.cluster.lock:
                    self._release_quarantine()
                    self._reap_expired_assumes()
                    self.queue.flush_unschedulable_leftover()
                    infos = self.queue.pop_batch(self.config.batch_size)
                    for i in infos:
                        self._in_flight[i.key] = i
                    if self._gang is not None:
                        # gang gate BEFORE base_cycle (see run_pipelined)
                        infos = self._gang_gate(infos)
                    base_cycle = self.queue.scheduling_cycle - len(infos)
                    self._refresh_pending_gauge()
                if not infos:
                    if slots:
                        drain()
                        continue  # discards/failures may requeue work
                    if self.rebalancer is not None:
                        # idle + ring drained: the safe rebalance point
                        r = BatchResult()
                        if self.rebalancer.maybe_run(self, r) > 0:
                            r.completed_at = self.clock.perf()
                            out.append(r)
                            continue
                    break
                batches += 1
                self._trace_step += 1
                if self.resilience.should_sync():
                    # degraded mode: the resilient synchronous cycle
                    # owns rebuilds, tier descent, probes, quarantine
                    metrics.pipeline_mode_total.labels("sync").inc()
                    drain()
                    r = self._run_popped(infos, t0)
                    if r.progressed:
                        out.append(r)
                    continue
                if self._discard_streak >= self._PIPELINE_FALLBACK_AFTER:
                    # livelock backstop (ADVICE r5 #2), unchanged from
                    # run_pipelined: one fence-free synchronous cycle
                    metrics.pipeline_fallback_total.inc()
                    metrics.pipeline_mode_total.labels("sync").inc()
                    self._log.warning(
                        "stream livelock backstop engaged after %d "
                        "consecutive fence discards: one synchronous "
                        "cycle", self._discard_streak,
                        extra={"step": self._trace_step},
                    )
                    drain()
                    r = self._run_popped(infos, t0)
                    self._discard_streak = 0
                    self._last_discard_step = -1
                    if r.progressed:
                        out.append(r)
                    continue
                metrics.pipeline_mode_total.labels("stream").inc()
                groups = self._group_by_profile(infos)
                owned: list[list[QueuedPodInfo]] = [g[1] for g in groups]
                try:
                    for profile, group_infos, offsets in groups:
                        self._stream_group(
                            profile, group_infos, offsets, base_cycle,
                            t0, slots, apply_slot, drain, owned, depth,
                        )
                except Exception:
                    if owned:
                        with self.cluster.lock:
                            base = self.queue.scheduling_cycle
                            for group_infos in owned:
                                for info in group_infos:
                                    self._requeue(info, base)
                    raise
            drain()
        except Exception:
            if self.flight is not None:
                path = self.flight.dump(trigger="crash")
                self._log.exception(
                    "streaming loop failed; flight recorder dump: %s",
                    path, extra={"step": self._trace_step},
                )
            raise
        finally:
            # exception escape hatch: dispatched-but-unapplied slots
            # must not strand their pods nor leave the device session
            # silently ahead of host truth
            for slot in slots:
                for f in slot.flights:
                    self._discard_flight(f)
            slots.clear()
            metrics.stream_depth.set(0)
            self._streaming_active = False
        return out

    def _stream_group(
        self,
        profile: str,
        infos: list[QueuedPodInfo],
        cycle_offsets: list[int],
        base_cycle: int,
        t0: float,
        slots: list,
        apply_slot,
        drain,
        owned: list,
        depth: int,
    ) -> None:
        """Tensorize, fold, and stream-dispatch one profile group into
        the work ring, chaining on the previous slot's device-resident
        occupancy carry whenever the fences and the occupancy
        vocabulary allow it. Falls back to drain-then-(re)tensorize —
        the always-correct path — on any mismatch."""
        solver = self.solvers[profile]
        with self.cluster.lock:
            stale = bool(self._session_stale)
            fences = (self._conflict_seq, self._occupancy_seq)
            group_pods = [i.pod for i in infos]
            plain = self._plain_batch(group_pods)
            chainable = self._stream_chainable(group_pods)
        if slots and (stale or slots[-1].prep.profile != profile):
            # a discarded solve polluted the carry, or the in-flight
            # slot belongs to another profile (its placements live only
            # in that profile's session — overlapping would double-book
            # capacity): drain before dispatching
            drain()
        may_chain = bool(
            chainable
            and slots
            and slots[-1].carried
            and slots[-1].prep.profile == profile
            and slots[-1].prep.fence == fences[0]
            and slots[-1].prep.occ_fence == fences[1]
        )
        def prepare():
            # tensorize + fold + chain-key: the one prep recipe, shared
            # by the primary path and both drain-then-retensorize
            # fallbacks (chain broke / SessionDrainRequired)
            p = self._tensorize_group(
                profile, infos, cycle_offsets, base_cycle, t0
            )
            with self.obs.span(
                "fold", trace_id=p.step, profile=profile,
                extenders=len(self.extender_clients),
                plugins=len(self.config.out_of_tree_plugins),
            ):
                self._fold_group(p)
            return p, solver.stream_chain_key(
                p.batch, p.pbatch, p.static, p.ports, p.spread,
                p.interpod,
            )

        if not plain and slots and not may_chain:
            # hard shapes need exact occupancy at tensorize unless the
            # dispatch chains on the resident carry
            drain()
        prep, chain_key = prepare()
        if (
            may_chain
            and slots
            and prep.fence == slots[-1].prep.fence
            and prep.occ_fence == slots[-1].prep.occ_fence
        ):
            # every ring apply since the last dispatch was CLEAN (an
            # unclean apply nulls the carry, failing can_chain below)
            # and no fence moved across the window, so the only column
            # dirt this tensorize's snapshot refresh materialized is
            # our own applied placements — usage the device already
            # assumed at those slots' solves. Advance the carry's
            # baseline past it, or steady-state chaining would die the
            # moment the ring first fills (every apply dirties the
            # next snapshot, and in-flight dispatches defer heals).
            with self.cluster.lock:
                solver.note_stream_applied(self.snapshot.col_versions)
        chain = bool(
            may_chain
            and slots
            and prep.nominated.empty
            and not prep.dra_active
            and prep.volume_ctx is None
            and prep.fence == slots[-1].prep.fence
            and prep.occ_fence == slots[-1].prep.occ_fence
            and solver.can_chain(chain_key, self.snapshot.col_versions)
        )
        if slots and not chain:
            if not plain:
                # the chain broke between the pre-check and the
                # tensorize (vocabulary changed, applies dirtied
                # columns, a late event): drain and RE-tensorize so the
                # occupancy tensors see every applied placement
                drain()
                prep, chain_key = prepare()
            elif prep.fence != slots[-1].prep.fence:
                # an event landed since the in-flight dispatch: node
                # TABLES may have changed, and the deferred heal is
                # only conservative for usage columns (run_pipelined's
                # stale-table hazard) — drain so this dispatch heals
                drain()
        split = self._choose_split(len(infos))
        try:
            try:
                flights = self._dispatch_stream(
                    prep, allow_heal=not slots, split=split,
                    chain=chain, chain_key=chain_key,
                )
            except SessionDrainRequired:
                # node/vocab shape change with solves still in flight:
                # apply them, then dispatch with healing (hard shapes
                # re-tensorize: their occupancy must see the applies)
                drain()
                if not plain:
                    prep, chain_key = prepare()
                flights = self._dispatch_stream(
                    prep, allow_heal=True, split=split,
                    chain=False, chain_key=chain_key,
                )
        except Exception as e:
            # deferred dispatch failed at the top tier: no flight
            # exists, so requeue for an immediate retry — the next pop
            # routes through the synchronous resilient cycle
            # (kubernetes_tpu/resilience), which owns rebuild/descent/
            # bisection
            with self.cluster.lock:
                self._session_stale.add(profile)
            self.resilience.note_async_failure(profile)
            self._solver_failed(infos, e, None, prep.step, base_cycle)
            self._requeue_immediate(infos)
            owned.pop(0)
            return
        slots.append(
            _StreamSlot(
                prep=prep, flights=flights,
                carried=bool(prep.nominated.empty),
            )
        )
        metrics.stream_depth.set(len(slots))
        self._stream_track(flights)
        # handoff point: the slot owns this group's pods now
        owned.pop(0)
        # bound the ring: apply the oldest slot(s) — their reads were
        # pre-waited by the completion thread while the newer dispatches
        # streamed down, so the drain is host work, not read waits
        while len(slots) > depth:
            apply_slot()

    def _dispatch_stream(
        self,
        prep: _PreparedGroup,
        allow_heal: bool,
        split: int,
        chain: bool,
        chain_key: tuple | None,
    ) -> list[_InFlightSolve]:
        """Deferred streaming dispatch normalized to a flight list (the
        stream path returns a list even unsplit — it is the one path
        that can consume/produce the cross-batch occupancy carry)."""
        got = self._dispatch_group(
            prep, defer=True, allow_heal=allow_heal, split=split,
            stream=True, chain=chain, chain_key=chain_key,
        )
        return got if isinstance(got, list) else [got]

    # -- backlog drain (the accelerator-resident mega-backlog path) --

    def drain_shape(self, chunk_pods: int, sample: int = 256):
        """The HBM budget model's inputs for draining THIS scheduler's
        queue in ``chunk_pods``-sized chunks (solver/budget.DrainShape):
        node count and padding discipline from the live cache/snapshot,
        per-family activity and row widths from a bounded sample of the
        queued pods (a 512k-pod backlog is never walked in full — the
        floor pads cover the unsampled tail conservatively, and an
        underestimate degrades to a budget miss caught by the real
        counters, never to a wrong solve)."""
        from .solver.budget import DrainShape, node_padding
        from .tensorize.plugins import PORT_PAD
        from .tensorize.schema import bucket_pow2

        with self.cluster.lock:
            n_nodes = sum(
                1
                for info in self.cache.nodes.values()
                if info.node is not None
            )
            keys = list(self.queue.entries().keys())[:sample]
        vocab_k = (
            len(self.snapshot.batch.vocab)
            if self.snapshot.batch is not None
            else 3
        )
        ports: set[int] = set()
        spread = interpod = False
        classes: set[tuple] = set()
        for key in keys:
            ns, name = key.split("/", 1)
            try:
                pod = self.cluster.get_pod(ns, name)
            except ApiError:
                continue
            ports.update(pod.host_ports())
            if pod.topology_spread_constraints:
                spread = True
            if pod.affinity is not None and (
                pod.affinity.pod_affinity is not None
                or pod.affinity.pod_anti_affinity is not None
            ):
                interpod = True
            req = pod.resource_request()
            classes.add(
                (
                    req.get("cpu", 0),
                    req.get("memory", 0),
                    tuple(sorted(pod.host_ports())),
                )
            )
        pad_mult = self.snapshot.pad_multiple
        inst = 8  # the tensorizers' INST_PAD floor
        return DrainShape(
            nodes=max(n_nodes, 1),
            chunk_pods=chunk_pods,
            vocab_k=vocab_k,
            classes=min(len(classes) or 1, 64),
            spread=spread,
            interpod=interpod,
            port_rows=max(bucket_pow2(len(ports), floor=PORT_PAD), PORT_PAD)
            if ports
            else PORT_PAD,
            spread_rows=inst,
            ipa_in_rows=inst,
            ipa_ex_rows=inst,
            # hostname topologies make every node its own domain: bound
            # the index audit by the node padding whenever a domain
            # family is active at all (conservative — d_pad is not in
            # the byte model, only the overflow clauses)
            d_pad=node_padding(max(n_nodes, 1), pad_mult)
            if (spread or interpod)
            else 8,
            mesh_devices=self._mesh_devices,
            group=max(self.solver.config.group_size, 1),
            stream_depth=max(self.config.stream_depth, 1),
            pad_multiple=pad_mult,
        )

    def _warm_start_backlog(self, report: BacklogDrainReport) -> None:
        """Mega-planner warm-start (ISSUE 19): one convex-relaxation
        solve (solver/relax.py) over the WHOLE queued backlog against
        the live snapshot, then re-key the activeQ tiebreak with the
        relaxed plan's target-node rank — pods the global plan
        co-locates pop adjacently, so each drain chunk arrives at the
        solver already packed against pre-fitted capacity. Advisory
        only: the per-chunk solves still place against cluster truth,
        so a stale plan degrades to the old ordering, never to a wrong
        binding. The relaxation's duals are exported per node group as
        the ``scheduler_relax_dual_price`` autoscaler cost signal."""
        import dataclasses

        from .api.objects import ZONE_LABELS
        from .solver.relax import RelaxConfig, RelaxSolver, group_prices

        with self.cluster.lock:
            batch = self.snapshot.update(self.cache)
            pods = self.queue.active_pods()
            slot_nodes = []
            for name in self.snapshot.names:
                info = self.cache.nodes.get(name) if name else None
                slot_nodes.append(info.node if info is not None else None)
        if not pods or batch.num_nodes == 0:
            return
        pbatch = build_pod_batch(pods, batch.vocab)
        static = build_static_tensors(
            pods, pbatch, slot_nodes, batch.padded
        )
        # the relaxation mutates its node batch's occupancy — plan on a
        # throwaway copy, cluster truth is untouched. No tail repair:
        # unranked pods just keep their FIFO order within the band.
        plan_batch = dataclasses.replace(
            batch,
            allocatable=batch.allocatable.copy(),
            used=batch.used.copy(),
            nonzero_used=batch.used[:2].copy(),
            pod_count=batch.pod_count.copy(),
        )
        solver = RelaxSolver(RelaxConfig(), repair=None)
        assigned = solver.solve(plan_batch, pbatch, static)
        stats = solver.last
        rank = {
            p.key: int(a)
            for p, a in zip(pods, assigned)
            if int(a) >= 0
        }
        with self.cluster.lock:
            report.warm_start_ranked = self.queue.reorder_active(rank)
        report.relax_iterations = stats.iterations
        report.relax_residual = stats.residual
        metrics.relax_iterations.observe(stats.iterations)
        metrics.relax_residual.set(stats.residual)
        metrics.relax_repair_rounds.observe(stats.repair_rounds)

        def zone_of(node) -> str:
            if node is not None:
                for lbl in ZONE_LABELS:
                    if lbl in node.labels:
                        return node.labels[lbl]
            return "default"

        groups = [zone_of(nd) for nd in slot_nodes]
        for grp, price in group_prices(
            stats, groups, valid=batch.valid
        ).items():
            metrics.relax_dual_price.labels(grp).set(price)
        self._log.info(
            "backlog warm-start: ranked %d/%d pods in %d relax "
            "iterations (residual %.4f)",
            report.warm_start_ranked, len(pods),
            stats.iterations, stats.residual,
            extra={"step": self._trace_step},
        )

    def drain_backlog(
        self,
        *,
        chunk_pods: int = 0,
        budget_bytes: int = 0,
        max_batches: int = 1_000_000,
        warm_start: bool | None = None,
    ) -> BacklogDrainReport:
        """Drain the queued backlog through the streaming dispatcher in
        chunk-aligned sub-batches against the resident session — the
        512k-pods x 102k-nodes path (ISSUE 12). The pod axis is cut
        into budget-planned chunks (one popped batch each) that stream
        down ``run_streaming``'s slot ring; cross-batch occupancy
        chaining keeps the port/spread/interpod carry device-resident
        across the whole drain, so hard shapes stop paying a
        drain-and-retensorize per chunk.

        Before anything dispatches, the HBM budget model
        (solver/budget.py) computes the chunk shape's per-device
        footprint from the same pad_multiple/LANE discipline the
        tensorizers use and asserts it against ``budget_bytes``
        (default: the PJRT-reported device limit). An over-budget
        chunk AUTO-SPLITS — the planner halves group-aligned,
        ``scheduler_backlog_budget_splits_total`` counts it — instead
        of OOMing mid-drain; a shape that cannot fit at any chunk size
        raises the typed ``BudgetExceeded`` with nothing dispatched.

        The estimate and the measured h2d counter delta are exported
        as the ``scheduler_backlog_hbm_*_bytes`` gauge pair so the
        model stays checkable in production."""
        from .solver import budget as hbm

        with self.cluster.lock:
            backlog = len(self.queue)
        report = BacklogDrainReport(pods=backlog)
        if backlog == 0:
            return report
        base_chunk = (
            chunk_pods
            or self.config.backlog_chunk_pods
            or self.config.batch_size
        )
        budget = hbm.device_budget_bytes(
            budget_bytes or self.config.hbm_budget_bytes
        )
        try:
            shape = self.drain_shape(base_chunk)
            est, splits = hbm.plan_chunk(shape, budget)  # BudgetExceeded -> caller
        except Exception:
            # the pre-dispatch planning path dies BEFORE run_streaming
            # (whose own crash handler would dump): a BudgetExceeded /
            # planner crash here must still leave the ring on disk —
            # the drain's flight-recorder coverage matches the loops'
            if self.flight is not None:
                path = self.flight.dump(trigger="crash")
                self._log.exception(
                    "backlog drain planning failed; flight recorder "
                    "dump: %s", path, extra={"step": self._trace_step},
                )
            raise
        chunk = est.chunk_pods
        compact = self.solver.config.compact_wire
        per_chunk = (
            est.chunk_upload_bytes_compact
            if compact
            else est.chunk_upload_bytes
        )
        n_chunks_est = max((backlog + chunk - 1) // chunk, 1)
        est_h2d = est.session_upload_bytes + (n_chunks_est - 1) * per_chunk
        metrics.backlog_budget_splits_total.inc(splits)
        metrics.backlog_hbm_estimated_bytes.set(est_h2d)
        self._log.info(
            "backlog drain: %d pods in %d-pod chunks (%d budget splits, "
            "%d B/device estimated vs %d B budget)",
            backlog, chunk, splits, est.per_device_bytes, budget,
            extra={"step": self._trace_step},
        )
        if (
            warm_start
            if warm_start is not None
            else self.config.backlog_warm_start
        ):
            self._warm_start_backlog(report)

        old_batch = self.config.batch_size
        self.config.batch_size = chunk
        self._backlog_drain_active = True
        self._drain_chunk_base = self._trace_step
        steps0 = self._trace_step
        # the drain's ROOT trace id: every chunk's spans and journal
        # records carry it (`drain_trace`), so the whole multi-chunk
        # pass reads as one trace — a chunk's own step stays its batch
        # trace id, the root ties the chunks together (the trace-id
        # stability contract tests/test_obs.py pins at a multi-chunk
        # shape)
        self._span_tags["drain_trace"] = steps0
        if self.journal is not None:
            self.journal.tags["drain_trace"] = steps0
        h2d0 = metrics.h2d_bytes_total._value.get()
        chained0 = sum(
            s.dispatch_counts.get("stream_chained", 0)
            for s in self.solvers.values()
        )
        if self.tuner is not None:
            # arm the drain-chunk controller: candidates re-run the
            # budget model (estimate + index-headroom audit) as their
            # guardrail, so a tuner-proposed chunk can never raise
            # BudgetExceeded from the dispatch path. The tuner adjusts
            # config.batch_size between pops — chunk boundaries — and
            # the streaming ring never sees a mid-chunk change.
            self.tuner.on_drain_start(self, chunk, budget)
        t0 = self.clock.perf()
        try:
            with self.obs.span(
                "drain_backlog", trace_id=steps0, pods=backlog,
                chunk_pods=chunk, budget_splits=splits,
                **self._span_tags,
            ):
                results = self.run_streaming(max_batches=max_batches)
        finally:
            self.config.batch_size = old_batch
            self._backlog_drain_active = False
            self._span_tags.pop("drain_trace", None)
            if self.tuner is not None:
                self.tuner.on_drain_end(self)
                report.final_chunk_pods = (
                    self.tuner.knob_values().get("backlog_chunk", chunk)
                )
            if self.journal is not None:
                self.journal.tags.pop("drain_chunk", None)
                self.journal.tags.pop("drain_trace", None)
        dt = self.clock.perf() - t0

        report.results = results
        report.drained = sum(len(r.scheduled) for r in results)
        report.unschedulable = sum(len(r.unschedulable) for r in results)
        report.chunks = self._trace_step - steps0
        report.chunk_pods = chunk
        report.budget_splits = splits
        report.budget_bytes = budget
        report.drain_seconds = dt
        report.pods_per_sec = report.drained / dt if dt > 0 else 0.0
        report.stream_chained_batches = (
            sum(
                s.dispatch_counts.get("stream_chained", 0)
                for s in self.solvers.values()
            )
            - chained0
        )
        report.chain_fraction = report.stream_chained_batches / max(
            report.chunks - 1, 1
        )
        report.estimated_per_device_bytes = est.per_device_bytes
        report.estimated_h2d_bytes = est_h2d
        report.measured_h2d_bytes = int(
            metrics.h2d_bytes_total._value.get() - h2d0
        )
        metrics.backlog_chunks_total.inc(report.chunks)
        metrics.backlog_drain_seconds.observe(dt)
        metrics.backlog_hbm_measured_bytes.set(report.measured_h2d_bytes)
        return report

    def relax_plan_backlog(self, pods=None) -> "dict[str, str | None]":
        """The fleet drain COORDINATOR's planning half (ROADMAP #5a):
        one relax mega-solve over the backlog, returned as a pod-key ->
        planned-node-name map (None = the relaxation left the pod
        unplaced). Same solve the warm-start runs (ISSUE 19), but here
        the OUTPUT is the plan itself — ``fleet/drain.py`` partitions
        the backlog by the shard that owns each planned node, so every
        replica drains pods the global plan already packed against its
        own nodes. Advisory like the warm-start: a stale plan only
        mis-shards (extra cross-shard CAS traffic), never mis-binds."""
        import dataclasses

        from .solver.relax import RelaxConfig, RelaxSolver

        with self.cluster.lock:
            batch = self.snapshot.update(self.cache)
            if pods is None:
                pods = self.queue.active_pods()
            slot_nodes = []
            for name in self.snapshot.names:
                info = self.cache.nodes.get(name) if name else None
                slot_nodes.append(info.node if info is not None else None)
        if not pods or batch.num_nodes == 0:
            return {p.key: None for p in pods}
        pbatch = build_pod_batch(pods, batch.vocab)
        static = build_static_tensors(
            pods, pbatch, slot_nodes, batch.padded
        )
        plan_batch = dataclasses.replace(
            batch,
            allocatable=batch.allocatable.copy(),
            used=batch.used.copy(),
            nonzero_used=batch.used[:2].copy(),
            pod_count=batch.pod_count.copy(),
        )
        assigned = RelaxSolver(RelaxConfig(), repair=None).solve(
            plan_batch, pbatch, static
        )
        plan: dict = {}
        for p, a in zip(pods, assigned):
            a = int(a)
            plan[p.key] = (
                batch.names[a] if 0 <= a < batch.num_nodes else None
            )
        return plan

    def fleet_drain_backlog(
        self,
        *,
        chunk_pods: int = 0,
        budget_bytes: int = 0,
        max_batches: int = 1_000_000,
        warm_start: bool | None = False,
        plan_keys=None,
    ) -> dict:
        """Replica half of the FLEET backlog drain (ROADMAP #5a):
        claim drain leases from the hub ledger and drain each through
        this replica's own ``drain_backlog`` slot ring until nothing is
        claimable. The claim adopts the lease's pods into this queue
        and — given ``plan_keys``, the full plan's key set — sheds pods
        the plan leased elsewhere (ring routing filled the queue by
        pod-key hash; the drain re-partitions by planned-node owner).
        Each pass runs under this replica's slice of the fleet HBM
        budget (``split_fleet_budget``); a lease completes at the hub
        only once none of its pods is still live in the queue, so a
        partially-drained lease stays reassignable. Warm-start defaults
        OFF — the global plan already packed each partition; pass
        ``warm_start=True`` to re-rank locally anyway."""
        from .solver import budget as hbm

        if self.fleet is None:
            raise RuntimeError("fleet_drain_backlog requires fleet mode")
        total = hbm.device_budget_bytes(
            budget_bytes or self.config.hbm_budget_bytes
        )
        my_budget = hbm.split_fleet_budget(
            total,
            len(self.fleet.membership.universe),
            replica_index=self.fleet.shard,
        )
        t0 = self.clock.perf()
        leases: list = []
        results: list = []
        reports: list = []
        drained = 0
        while True:
            lease = self.fleet.drain_claim(self, plan_keys)
            if not lease:
                break
            lease_keys = [str(k) for k in lease.get("keys") or []]
            rep = self.drain_backlog(
                chunk_pods=chunk_pods,
                budget_bytes=my_budget,
                max_batches=max_batches,
                warm_start=warm_start,
            )
            drained += rep.drained
            results.extend(rep.results)
            reports.append(rep)
            # complete only when no lease pod is still live in the
            # queue: unschedulable stragglers stay THIS replica's pods
            # through the routing the claim adopted them under, and an
            # un-completed lease re-serves (or returns on death) so the
            # ledger never strands them
            with self.cluster.lock:
                live = set(self.queue.entries())
            remaining = sum(1 for k in lease_keys if k in live)
            completed = False
            if remaining == 0:
                completed = self.fleet.drain_complete(lease["id"])
            leases.append(
                {
                    "id": lease["id"],
                    "kind": lease.get("kind", ""),
                    "pods": len(lease_keys),
                    "completed": completed,
                    "remaining": remaining,
                }
            )
            if remaining:
                break  # stragglers need outside help; don't spin
        dt = self.clock.perf() - t0
        metrics.fleet_drain_replica_seconds.observe(dt)
        return {
            "replica": self.fleet.replica,
            "leases": leases,
            "drained": drained,
            "seconds": dt,
            "pods_per_sec": drained / dt if dt > 0 else 0.0,
            "budget_bytes": my_budget,
            "results": results,
            "reports": reports,
        }

    def hub_status(self) -> "dict | None":
        """The ``GET /debug/hub`` body: the occupancy hub's role /
        epoch / replication cursors plus this replica's client-side
        failover view (fleet/runtime.py). None when this scheduler is
        not a fleet replica; raises ExchangeUnreachable while no hub
        endpoint answers (the HTTP handler maps it to 503)."""
        if self.fleet is None:
            return None
        return self.fleet.hub_status()

    @property
    def pending(self) -> int:
        """Work the loop must still drive: queued pods, pods parked at
        Permit, AND quarantined pods — without the latter two, a serve
        drain loop gated on pending would stop ticking while WaitingPods
        still need their timeout settled or a quarantine TTL still needs
        its re-admit, both of which happen at the next cycle's pop."""
        if self.slo is not None:
            # idle heartbeat for the SLO engine: the serve drain loop
            # polls pending every iteration, so a degraded health flip
            # heals by time even when no batch ever applies again
            self.slo.tick()
        with self.cluster.lock:
            return (
                len(self.queue)
                + len(self._waiting)
                + len(self._quarantine)
            )
