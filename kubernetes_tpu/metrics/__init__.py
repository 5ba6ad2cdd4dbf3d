"""Scheduler metrics with the reference's metric names
(pkg/scheduler/metrics/metrics.go, SURVEY.md §6.5) so existing dashboards
port, plus TPU-solve-specific series.

Uses prometheus_client against a dedicated registry (the [BOUNDARY]
equivalent of component-base metrics/legacyregistry); `render()` emits the
exposition text the /metrics endpoint serves.
"""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

REGISTRY = CollectorRegistry()

_BUCKETS = (
    0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0,
    10.0, 20.0,
)

# -- reference names (pkg/scheduler/metrics) --

schedule_attempts_total = Counter(
    "scheduler_schedule_attempts_total",
    "Number of attempts to schedule pods, by result.",
    ["result", "profile"],
    registry=REGISTRY,
)
scheduling_attempt_duration_seconds = Histogram(
    "scheduler_scheduling_attempt_duration_seconds",
    "Scheduling attempt latency (scheduling algorithm + binding).",
    ["result", "profile"],
    buckets=_BUCKETS,
    registry=REGISTRY,
)
pod_scheduling_attempts = Histogram(
    "scheduler_pod_scheduling_attempts",
    "Number of attempts to successfully schedule a pod.",
    buckets=(1, 2, 4, 8, 16),
    registry=REGISTRY,
)
pod_scheduling_sli_duration_seconds = Histogram(
    "scheduler_pod_scheduling_sli_duration_seconds",
    "E2e latency for a pod being scheduled, from first queue add.",
    ["attempts"],
    buckets=_BUCKETS,
    registry=REGISTRY,
)
framework_extension_point_duration_seconds = Histogram(
    "scheduler_framework_extension_point_duration_seconds",
    "Latency for running all plugins of an extension point.",
    ["extension_point", "status", "profile"],
    buckets=_BUCKETS,
    registry=REGISTRY,
)
plugin_execution_duration_seconds = Histogram(
    "scheduler_plugin_execution_duration_seconds",
    "Duration for running a plugin at a specific extension point.",
    ["plugin", "extension_point", "status"],
    buckets=_BUCKETS,
    registry=REGISTRY,
)
pending_pods = Gauge(
    "scheduler_pending_pods",
    "Pending pods, by queue (active|backoff|unschedulable|gated).",
    ["queue"],
    registry=REGISTRY,
)
queue_incoming_pods_total = Counter(
    "scheduler_queue_incoming_pods_total",
    "Number of pods added to scheduling queues by event and queue type.",
    ["queue", "event"],
    registry=REGISTRY,
)
preemption_attempts_total = Counter(
    "scheduler_preemption_attempts_total",
    "Total preemption attempts in the cluster.",
    registry=REGISTRY,
)
fold_cache_total = Counter(
    "scheduler_plugin_fold_cache_total",
    "Out-of-tree plugin fold results served from the per-batch memo "
    "cache vs recomputed (result=hit|miss).",
    ["result"],
    registry=REGISTRY,
)
preemption_victims = Histogram(
    "scheduler_preemption_victims",
    "Number of selected preemption victims.",
    buckets=(1, 2, 4, 8, 16, 32, 64),
    registry=REGISTRY,
)

# -- TPU-solve specific (SURVEY §6.5 additions) --

solve_latency_seconds = Histogram(
    "scheduler_tpu_solve_latency_seconds",
    "Device solve wall time per batch.",
    buckets=_BUCKETS,
    registry=REGISTRY,
)
solve_batch_size = Histogram(
    "scheduler_tpu_solve_batch_size",
    "Pods per device solve.",
    buckets=(1, 8, 32, 128, 512, 1024, 4096, 16384, 65536),
    registry=REGISTRY,
)
tensorize_seconds = Histogram(
    "scheduler_tpu_tensorize_seconds",
    "Host-side tensorization time per batch.",
    buckets=_BUCKETS,
    registry=REGISTRY,
)
solves_discarded_total = Counter(
    "scheduler_tpu_solves_discarded_total",
    "Deferred device solves discarded by the pipelined loop's conflict "
    "fence (a capacity/mask-affecting event landed between dispatch and "
    "apply); the batch's pods retry immediately without backoff.",
    registry=REGISTRY,
)
pipeline_fallback_total = Counter(
    "scheduler_pipeline_fallback_total",
    "Times the pipelined loop fell back to a synchronous (fence-free) "
    "cycle after consecutive fence discards — the livelock backstop "
    "under sustained capacity/mask-affecting event churn.",
    registry=REGISTRY,
)
pipeline_mode_total = Counter(
    "scheduler_pipeline_mode_total",
    "Popped batches by dispatch mode: overlap (plain fit shapes "
    "dispatched before the previous solve's read lands), carry (hard "
    "shapes — ports/spread/interpod/volumes/DRA/nominated/multi-"
    "profile — drained-then-chained through the occupancy-carrying "
    "sub-batch split), stream (the streaming dispatcher's unified "
    "device-resident solve loop, run_streaming), sync (livelock-"
    "backstop / degraded-mode synchronous cycle).",
    ["mode"],
    registry=REGISTRY,
)
stream_depth = Gauge(
    "scheduler_stream_depth",
    "Dispatched-but-unapplied stream slots in the streaming "
    "dispatcher's bounded work ring (run_streaming); bounded by "
    "SchedulerConfig.stream_depth.",
    registry=REGISTRY,
)
stream_inflight_reads = Gauge(
    "scheduler_stream_inflight_reads",
    "Deferred assignment reads handed to the streaming dispatcher's "
    "completion thread and not yet landed (the async D2H transfers "
    "currently being waited on off the driver thread).",
    registry=REGISTRY,
)
stream_unhidden_reads_total = Counter(
    "scheduler_stream_unhidden_reads_total",
    "Streaming-dispatcher assignment reads that actually BLOCKED the "
    "driver thread (> 1 ms) — the un-hidden device reads the "
    "device-resident solve loop exists to eliminate. Steady state "
    "should trend toward one per event-fence, not one per batch.",
    registry=REGISTRY,
)
stream_slot_discard_total = Counter(
    "scheduler_stream_slot_discard_total",
    "Stream slots discarded by the per-slot fence epochs (a "
    "conflicting/occupancy event landed between a slot's dispatch and "
    "its apply): only the affected slot and its chained successors "
    "die; unrelated slots apply normally.",
    registry=REGISTRY,
)
pipeline_subbatches_total = Counter(
    "scheduler_pipeline_subbatches_total",
    "Chained sub-batch solves dispatched by the RTT-hiding batch split "
    "(run_pipelined): sub-batch i's assignment read overlaps sub-batch "
    "i+1's device solve.",
    registry=REGISTRY,
)
batch_failure_total = Counter(
    "scheduler_batch_failure_total",
    "Batched solves that failed before applying, by reason "
    "(tensorize|dispatch|read|corrupt) — each failure requeues or "
    "bisects the batch through the resilience ladder instead of "
    "silently dropping it, and journals a non-terminal solver_error "
    "per pod.",
    ["reason"],
    registry=REGISTRY,
)
solve_tier = Gauge(
    "scheduler_tpu_solve_tier",
    "Fallback-ladder tier the profile's solves currently dispatch at "
    "(0 = the top tier; higher = more degraded, last = pure-host "
    "serial greedy).",
    ["profile"],
    registry=REGISTRY,
)
breaker_state = Gauge(
    "scheduler_tpu_breaker_state",
    "Solve circuit-breaker state per profile "
    "(0 closed | 1 open | 2 half-open probe).",
    ["profile"],
    registry=REGISTRY,
)
breaker_transitions_total = Counter(
    "scheduler_tpu_breaker_transitions_total",
    "Solve circuit-breaker transitions, by kind "
    "(rebuild|trip|probe|reclose).",
    ["transition"],
    registry=REGISTRY,
)
fallback_solves_total = Counter(
    "scheduler_tpu_fallback_solves_total",
    "Batches solved below the top ladder tier, by tier "
    "(single|cpu|host).",
    ["tier"],
    registry=REGISTRY,
)
quarantined_pods_total = Counter(
    "scheduler_tpu_quarantined_pods_total",
    "Pods quarantined by poison-batch bisection: the solve fails "
    "deterministically at every ladder tier only when this pod is in "
    "the batch.",
    registry=REGISTRY,
)
quarantine_readmits_total = Counter(
    "scheduler_tpu_quarantine_readmits_total",
    "Quarantined pods re-admitted to the scheduling queue after their "
    "TTL'd backoff elapsed.",
    registry=REGISTRY,
)
# -- gang scheduling (kubernetes_tpu/gang) --

gang_commits_total = Counter(
    "scheduler_gang_commits_total",
    "Pod groups committed atomically: every solved member bound in one "
    "all-or-nothing bind_gang call.",
    registry=REGISTRY,
)
gang_bound_pods_total = Counter(
    "scheduler_gang_bound_pods_total",
    "Pods bound as members of an atomic gang commit.",
    registry=REGISTRY,
)
gang_incomplete_total = Counter(
    "scheduler_gang_incomplete_total",
    "Gang rounds released without a commit: a member failed, a fence "
    "discarded a sub-solve, or the atomic bind was rejected — every "
    "staged placement rolled back and the gang requeued (a partial "
    "gang is never bound).",
    registry=REGISTRY,
)
gang_quarantined_total = Counter(
    "scheduler_gang_quarantined_total",
    "Pod groups quarantined as a unit: the quorum never assembled "
    "before the min-member timeout, or consecutive released rounds hit "
    "the configured limit.",
    registry=REGISTRY,
)
gang_assembly_seconds = Histogram(
    "scheduler_gang_assembly_seconds",
    "Time from a gang's first appearance at the pop gate to its atomic "
    "commit (time-to-full-gang).",
    buckets=_BUCKETS,
    registry=REGISTRY,
)

mesh_devices = Gauge(
    "scheduler_mesh_devices",
    "Devices in the node-axis solve mesh the scheduler dispatches "
    "against (SchedulerConfig.mesh_devices; 1 = the unsharded "
    "single-device path).",
    registry=REGISTRY,
)
device_info = Gauge(
    "scheduler_tpu_device_info",
    "The JAX backend this process initialised at start-up "
    "(utils/device.init_backend), as JAX reports it: labels carry "
    "jax.devices()[0].platform and .device_kind, the value is "
    "len(jax.devices()). The backend is chosen by JAX_PLATFORMS alone.",
    ["platform", "device_kind"],
    registry=REGISTRY,
)
h2d_bytes_total = Counter(
    "scheduler_tpu_host_to_device_bytes_total",
    "Host->device bytes uploaded by ExactSolver.solve: per-pod packed "
    "arrays, per-batch occupancy rows, dirty-column heals, class-table "
    "cache misses, and full session (re)uploads.",
    registry=REGISTRY,
)
solves_total = Counter(
    "scheduler_tpu_solves_total",
    "ExactSolver.solve calls (one per tensorized batch, before any "
    "split into chained sub-solves), by device program: grouped = the "
    "chunked program (_solve_grouped), scan = the per-pod scan "
    "(_solve_scan). Same increments as ExactSolver.dispatch_counts.",
    ["path"],
    registry=REGISTRY,
)
solve_chunks_total = Counter(
    "scheduler_tpu_solve_chunks_total",
    "Chunks of group_size pods handed to the grouped program, by the "
    "branch _chunk_kinds chose: slow = the full per-pod step replayed "
    "pod by pod (scope grouped_slow), plain | spread | anti = the "
    "multi-placement fast branches (scope grouped_fast). Chunks that "
    "hold no pod (padding) are not counted. Same increments as "
    "ExactSolver.dispatch_counts.",
    ["kind"],
    registry=REGISTRY,
)
spread_instances_total = Counter(
    "scheduler_tpu_spread_instances_total",
    "PodTopologySpread constraint instances (one per class and "
    "constraint: SpreadTensors.num_instances) summed over "
    "ExactSolver.solve calls; over scheduler_tpu_solves_total it is "
    "the instances one batch carries.",
    registry=REGISTRY,
)
domain_reductions_total = Counter(
    "scheduler_tpu_domain_reductions_total",
    "Domain tables carried by ExactSolver.solve calls (the "
    "PodTopologySpread table of any solve; the inter-pod table of a "
    "grouped solve, whose anti quota branch reduces it), by the form "
    "ops/domains.py gives the program's per-domain sums and winners, "
    "chosen at trace time from the table's padded domain count: dense = "
    "masked reductions over the node axis (at most DENSE_MAX_SLOTS "
    "slots), scatter = jax.ops.segment_sum / segment_max. Same "
    "increments as ExactSolver.dispatch_counts.",
    ["form"],
    registry=REGISTRY,
)
interpod_terms_total = Counter(
    "scheduler_tpu_interpod_terms_total",
    "InterPodAffinity term instances carried by ExactSolver.solve calls, "
    "by side: incoming = the batch pod classes' own terms "
    "(InterpodTensors.num_in), existing = the deduplicated terms owned by "
    "placed, nominated and batch pods that select at least one pod of the "
    "batch (num_ex; the axis padded to te_pad). Same increments as "
    "ExactSolver.dispatch_counts; over scheduler_tpu_solves_total it is "
    "the terms one batch carries.",
    ["side"],
    registry=REGISTRY,
)
interpod_placed_visits_total = Counter(
    "scheduler_tpu_interpod_placed_visits_total",
    "Placed pods that build_interpod_tensors walked for the scheduler's "
    "batches, once for each pass over them: the one pass that counts the "
    "selectors of incoming terms new to the scheduler cache's "
    "per-selector node counts, and the one pass for terms those counts "
    "cannot serve (a namespaceSelector). A batch whose selectors are all "
    "tracked walks none. A call for a caller with no scheduler cache "
    "(the extender, solver/evaluate.py) is not counted.",
    registry=REGISTRY,
)
interpod_count_rows_total = Counter(
    "scheduler_tpu_interpod_count_rows_total",
    "Rows of InterpodTensors.in_cnt0 (placed pods per node that an "
    "incoming inter-pod term selects, one row per (namespace, selector) "
    "the term asks about) handed to a batch, by where the counts came "
    "from: kept = the scheduler cache's per-selector node counts "
    "(SchedulerCache.spread_counts) already tracked the selector, walk = "
    "it was counted then, by a pass over the placed pods (one pass for "
    "all the new selectors of a batch), or the term cannot be served by "
    "those counts (a namespaceSelector) and was counted by a pass of its "
    "own. An index built on the spot for a caller with no cache (the "
    "extender, solver/evaluate.py) is not counted.",
    ["source"],
    registry=REGISTRY,
)
spread_count_rows_total = Counter(
    "scheduler_tpu_spread_count_rows_total",
    "Rows of SpreadTensors.cnt0 (matching placed pods per node, one row "
    "per constraint instance with a selector) that the scheduler cache's "
    "per-selector node counts (SchedulerCache.spread_counts) handed to a "
    "batch, by where the counts came from: kept = the selector was "
    "already tracked, walk = it was counted then, by a pass over the "
    "placed pods (one pass for all the new selectors of a batch). An "
    "index built on the spot for a caller with no cache (the extender, "
    "solver/evaluate.py) is not counted.",
    ["source"],
    registry=REGISTRY,
)
static_rows_total = Counter(
    "scheduler_tpu_static_rows_total",
    "Class rows of the static plugin tables (StaticPluginTensors: the "
    "NodeName, NodeUnschedulable, TaintToleration, NodeAffinity, "
    "ImageLocality and volume mask and score rows) that the scheduler's "
    "batches got, by source: evaluated = computed node by node for the "
    "first class of a static identity, shared = copied from that class "
    "for a class that differs only in what the spread or inter-pod "
    "tensorizers read (its labels). Calls for the warm start, the relax "
    "planner, the rebalancer, the simulator and solver/evaluate.py are "
    "not counted.",
    ["source"],
    registry=REGISTRY,
)
spread_tracked_selectors = Gauge(
    "scheduler_tpu_spread_tracked_selectors",
    "(namespace, selector) pairs whose per-node match counts the "
    "scheduler cache keeps up to date (SchedulerCache.spread_counts), "
    "read after each batch that carries a spread constraint.",
    registry=REGISTRY,
)
class_table_uploads_total = Counter(
    "scheduler_tpu_class_table_uploads_total",
    "Session solves whose per-class tables (static masks, spread and "
    "inter-pod instance tables) were not in the content-addressed "
    "device cache and were placed anew.",
    registry=REGISTRY,
)
d2h_bytes_total = Counter(
    "scheduler_tpu_device_to_host_bytes_total",
    "Device->host bytes downloaded by ExactSolver.solve: the per-batch "
    "assignment vector in session mode, the packed result buffer in "
    "standalone mode.",
    registry=REGISTRY,
)

# -- backlog drain (Scheduler.drain_backlog, ISSUE 12) --

backlog_chunks_total = Counter(
    "scheduler_backlog_chunks_total",
    "Chunk-aligned sub-batches a backlog drain dispatched through the "
    "streaming ring (Scheduler.drain_backlog): the 512k-pod backlog "
    "cut into budget-sized chunks chained against the resident "
    "session.",
    registry=REGISTRY,
)
backlog_budget_splits_total = Counter(
    "scheduler_backlog_budget_splits_total",
    "Chunk halvings the HBM budget planner (solver/budget.py "
    "plan_chunk) took before the drain chunk fit the per-device "
    "budget — the auto-split that replaces an OOM mid-drain.",
    registry=REGISTRY,
)
backlog_drain_seconds = Histogram(
    "scheduler_backlog_drain_seconds",
    "End-to-end wall time of one Scheduler.drain_backlog pass "
    "(queue full -> backlog drained through the streaming ring).",
    buckets=(0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0),
    registry=REGISTRY,
)
backlog_hbm_estimated_bytes = Gauge(
    "scheduler_backlog_hbm_estimated_bytes",
    "The HBM budget model's predicted host->device upload bytes for "
    "the last backlog drain (solver/budget.py ShapeEstimate: fresh "
    "session + per-chunk uploads). Compare against "
    "scheduler_backlog_hbm_measured_bytes — the pair is what makes "
    "the capacity-planning model checkable in production.",
    registry=REGISTRY,
)
backlog_hbm_measured_bytes = Gauge(
    "scheduler_backlog_hbm_measured_bytes",
    "Measured scheduler_tpu_host_to_device_bytes_total delta across "
    "the last backlog drain — the ground truth the HBM budget "
    "model's estimate is validated against.",
    registry=REGISTRY,
)

# -- convex-relaxation mega-planner (solver/relax.py, ISSUE 19) --

relax_iterations = Histogram(
    "scheduler_relax_iterations",
    "Dual-ascent iterations one convex-relaxation solve ran before "
    "the residual early exit (solver/relax.py): converged plans stop "
    "well short of the max_iters budget; samples pinned at the budget "
    "mean the shape is contended past the tolerance.",
    buckets=(4, 8, 16, 32, 64, 128, 256, 512),
    registry=REGISTRY,
)
relax_residual = Gauge(
    "scheduler_relax_residual",
    "Final relative-overcommit residual of the last relaxation solve "
    "(max over nodes/resources of fractional load/capacity - 1, "
    "clipped at 0). 0 = the fractional plan fit everywhere; a "
    "persistent positive value is structural oversubscription the "
    "rounding clamp absorbs.",
    registry=REGISTRY,
)
relax_repair_rounds = Histogram(
    "scheduler_relax_repair_rounds",
    "Auction rounds the integrality-tail repair ran after rounding a "
    "relaxed plan (0 = the rounding seated everything or repair was "
    "disabled). Growth here means the relaxation is leaving more "
    "work to the sequential engine it exists to replace.",
    buckets=(0, 1, 2, 4, 8, 16, 32, 64),
    registry=REGISTRY,
)
relax_dual_price = Gauge(
    "scheduler_relax_dual_price",
    "Converged per-node-group dual price of the last relaxation solve "
    "(mean over the group's nodes of sum_k lam[k,n] + mu[n], score "
    "points per normalized capacity unit) — the autoscaler cost "
    "signal (ROADMAP item #2): a group pinned at 0 has slack, a "
    "rising price is demand the group cannot absorb.",
    ["group"],
    registry=REGISTRY,
)

# -- closed-loop hot-path auto-tuning (kubernetes_tpu/tuning) --

tuning_adjustments_total = Counter(
    "scheduler_tuning_adjustments_total",
    "Auto-tuning controller decisions, by knob (backlog_chunk|"
    "stream_depth|pipeline_split|fleet_flush) and action (probe = try "
    "a neighbor value, accept = probe beat the incumbent by the "
    "hysteresis margin, revert = probe lost and the incumbent was "
    "restored, settle = both directions exhausted and the controller "
    "went inert, unsettle = a workload shift re-opened tuning).",
    ["knob", "action"],
    registry=REGISTRY,
)
tuning_knob_value = Gauge(
    "scheduler_tuning_knob_value",
    "Current value of each auto-tuned hot-path knob (the live setting "
    "the dispatch loops read; compare with scheduler_tuning_settled to "
    "tell a converged value from a mid-probe one).",
    ["knob"],
    registry=REGISTRY,
)
tuning_settled = Gauge(
    "scheduler_tuning_settled",
    "1 when the knob's controller has settled (neither direction "
    "improves past the hysteresis margin); 0 while measuring or "
    "probing.",
    ["knob"],
    registry=REGISTRY,
)
tuning_guardrail_rejections_total = Counter(
    "scheduler_tuning_guardrail_rejections_total",
    "Tuner proposals rejected by a hard guardrail BEFORE application "
    "— e.g. a drain-chunk candidate whose HBM budget-model estimate "
    "(solver/budget.py) exceeds the per-device budget. A rejection is "
    "the guardrail working; a tuner-applied value failing its guard "
    "would be a breach, which the sim's tuning invariant pins at zero.",
    ["knob"],
    registry=REGISTRY,
)
tuning_workload_shifts_total = Counter(
    "scheduler_tuning_workload_shifts_total",
    "Workload shifts the tuning runtime detected after settling (the "
    "CounterWindow signature moved past tuning.shiftThreshold): every "
    "settled controller re-opens and re-converges for the new "
    "regime.",
    registry=REGISTRY,
)

# -- crash-restart recovery + commit fencing --

restart_recovery_seconds = Histogram(
    "scheduler_restart_recovery_seconds",
    "Wall time of the cold-start recovery pass: rebuilding cache/queue "
    "from cluster truth, re-adopting pods a prior incarnation orphaned, "
    "rolling back half-committed occupancy (claim reservations, fleet "
    "pending rows), and journaling terminal 'recovered' records.",
    buckets=_BUCKETS,
    registry=REGISTRY,
)
commit_fenced_total = Counter(
    "scheduler_commit_fenced_total",
    "Bind commits rejected by the state service's fencing-token check: "
    "this incarnation's fence token was revoked (lease lost, partition, "
    "or a newer incarnation took over) — the zombie's commit never "
    "lands, extending the fleet admit-time ownership fence to bind "
    "time.",
    registry=REGISTRY,
)
bind_commits_total = Counter(
    "scheduler_bind_commits_total",
    "Pods taken through the commit pass, by path: held = the pod's "
    "binding cycle cannot leave the process (no PreBind/PostBind "
    "plugin, volume, resource claim or binder extender), so it "
    "committed inside a run of such pods under one hold of the cluster "
    "lock; wire = it went through the three-phase cycle, unlocked "
    "across its wire call. Counts attempts, failed binds included.",
    ["path"],
    registry=REGISTRY,
)
watch_delivery_error_total = Counter(
    "scheduler_watch_delivery_error_total",
    "Exceptions raised by ClusterState watch subscribers during event "
    "delivery: caught and counted so one bad callback cannot prevent "
    "delivery to the remaining subscribers or corrupt the event "
    "sequence.",
    registry=REGISTRY,
)

# -- fleet tier (kubernetes_tpu/fleet) --

fleet_occupancy_row_age_seconds = Gauge(
    "scheduler_fleet_occupancy_row_age_seconds",
    "Staleness of the cross-shard occupancy view this replica admits "
    "against: age of the last successful hub fetch PLUS the oldest "
    "peer's liveness age inside it. Beyond FleetConfig.max_row_age_s "
    "admission "
    "turns conservative — cross-shard-constrained placements are "
    "rejected rather than risking overcommit on stale rows.",
    registry=REGISTRY,
)

fleet_replicas = Gauge(
    "scheduler_fleet_replicas",
    "Alive replicas in this replica's fleet membership view "
    "(fleet/membership.py; the configured universe is static).",
    registry=REGISTRY,
)
fleet_owned_nodes = Gauge(
    "scheduler_fleet_owned_nodes",
    "Nodes the ring partition currently assigns to this replica's "
    "shard (fleet/ring.py).",
    registry=REGISTRY,
)
fleet_resyncs_total = Counter(
    "scheduler_fleet_resyncs_total",
    "Shard resyncs: the partition moved (membership change or "
    "ring remap) and the replica rebuilt its shard-scoped cache and "
    "queue from cluster truth.",
    registry=REGISTRY,
)
fleet_occupancy_rows_total = Counter(
    "scheduler_fleet_occupancy_rows_total",
    "Occupancy-exchange row operations, by op "
    "(staged|committed|withdrawn|retired|handoff).",
    ["op"],
    registry=REGISTRY,
)
fleet_reconcile_conflicts_total = Counter(
    "scheduler_fleet_reconcile_conflicts_total",
    "Placements the cross-shard reconciliation rejected pre-assume, "
    "by constraint family (ownership|spread|anti|stale|cas — stale = "
    "conservative admission under an aged-out occupancy view, cas = "
    "sustained hub compare-and-stage contention or a fenced write); "
    "the pods retried through the ordinary requeue machinery.",
    ["constraint"],
    registry=REGISTRY,
)
fleet_admit_cas_conflict_total = Counter(
    "scheduler_fleet_admit_cas_conflict_total",
    "Cross-process atomic admits rejected by the hub's fenced "
    "compare-and-stage, by kind (version = the hub moved past the "
    "admitted view — a peer's row landed first, the replica re-fetches "
    "and re-admits; fenced = the replica's hub write privilege was "
    "revoked by a membership retire — no row lands until its forced "
    "resync re-registers it wholesale).",
    ["kind"],
    registry=REGISTRY,
)
fleet_hub_rpc_seconds = Histogram(
    "scheduler_fleet_hub_rpc_seconds",
    "Wall time of one occupancy-hub RPC from RemoteOccupancyExchange "
    "(the HubOp method on the bulk gRPC boundary), by hub op — the "
    "wire cost a cross-process fleet pays per stage/commit/view that "
    "an in-process fleet gets for a lock acquire.",
    ["op"],
    buckets=_BUCKETS,
    registry=REGISTRY,
)
hub_epoch = Gauge(
    "scheduler_hub_epoch",
    "The occupancy hub's fencing epoch as last observed by this "
    "process (hub side: the lease grant this hub serves under; client "
    "side: the highest epoch RemoteOccupancyExchange has verified on a "
    "HubOp reply — replies from a lower epoch are structurally "
    "ignored). Monotone per fleet; a step is a hub failover.",
    registry=REGISTRY,
)
hub_failover_total = Counter(
    "scheduler_hub_failover_total",
    "Hub failovers: a standby hub was promoted past epoch 1 (hub "
    "side), or RemoteOccupancyExchange observed the hub epoch advance "
    "and re-anchored on the new primary (client side — the replica "
    "then forces a wholesale resync republish, the dirty-heal path).",
    registry=REGISTRY,
)
hub_replication_lag_rows = Gauge(
    "scheduler_hub_replication_lag_rows",
    "Standby replication lag in op-log entries: the primary's latest "
    "opseq minus this standby's applied cursor at the last "
    "StandbyReplicator poll (0 = caught up; the failover loss window "
    "is bounded by this).",
    registry=REGISTRY,
)
fleet_flush_dedup_total = Counter(
    "scheduler_fleet_flush_dedup_total",
    "Write-behind flushes the hub dropped as duplicates: a retried "
    "apply_ops batch whose (client, flush_seq) key was already "
    "applied — the reply of the first attempt was lost after the "
    "server-side apply, and without the dedup its rows would "
    "double-stage and its journal lines double-append.",
    registry=REGISTRY,
)
fleet_drain_partitions = Gauge(
    "scheduler_fleet_drain_partitions",
    "Replica partitions in the active fleet backlog drain's ledger "
    "(drain_init): the hub-hosted coordinator ran the global relax "
    "plan once and split the backlog by planned-node shard ownership; "
    "each partition drains concurrently under its own drain lease.",
    registry=REGISTRY,
)
fleet_drain_residual_pods = Gauge(
    "scheduler_fleet_drain_residual_pods",
    "Pods in the fleet backlog drain's residual cohort: cross-shard-"
    "constrained (spread / anti-affinity), plan-unplaced, or planned "
    "onto an unowned node — drained SERIALIZED as one lease after "
    "every shard partition completes, so constraint correctness is "
    "never traded for parallelism. A large value means the partitioner "
    "is forfeiting the fleet speedup.",
    registry=REGISTRY,
)
fleet_drain_lease_reassignments_total = Counter(
    "scheduler_fleet_drain_lease_reassignments_total",
    "Drain leases reassigned after a holder died mid-drain: the hub "
    "retire returned the lease's outstanding keys to the orphan pool "
    "and a surviving replica claimed them (the no-pod-lost half of the "
    "drain ledger's exactly-once contract).",
    registry=REGISTRY,
)
fleet_drain_replica_seconds = Histogram(
    "scheduler_fleet_drain_replica_seconds",
    "Wall time one replica spent draining one claimed lease through "
    "its own drain_backlog slot ring (fleet_drain_backlog) — the "
    "per-replica denominator behind the fleet drain speedup.",
    buckets=(0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
             600.0),
    registry=REGISTRY,
)
fleet_mesh_slice_devices = Gauge(
    "scheduler_fleet_mesh_slice_devices",
    "Devices in this replica's EXCLUSIVE mesh slice "
    "(SchedulerConfig.mesh_slice = (rank, count): contiguous first-N "
    "partitioning of the visible device set, so N fleet replicas "
    "stream-dispatch against disjoint device sets). 0 = no slice "
    "configured (the sole-owner scheduler uses mesh_devices alone).",
    registry=REGISTRY,
)
bulk_retry_total = Counter(
    "scheduler_bulk_retry_total",
    "Transient bulk-gRPC call failures retried by BulkClient's "
    "bounded exponential backoff, by method.",
    ["method"],
    registry=REGISTRY,
)

# -- scheduling trace layer (kubernetes_tpu/obs) --

trace_spans_total = Counter(
    "scheduler_tpu_trace_spans_total",
    "Spans finished by the scheduling trace layer, by span name "
    "(schedule_batch|snapshot|tensorize|fold|dispatch|fence|apply|"
    "bind|enqueue|discard|extender_batch).",
    ["name"],
    registry=REGISTRY,
)
journal_records_total = Counter(
    "scheduler_tpu_trace_journal_records_total",
    "Per-pod decision-journal records written, by outcome "
    "(bound|unschedulable|bind_failure|permit_wait|permit_rejected|"
    "permit_timeout|discarded|solver_error|quarantined|recovered|"
    "evicted_for_rebalance|gang_incomplete|telemetry_anomaly).",
    ["outcome"],
    registry=REGISTRY,
)
journal_seconds_total = Counter(
    "scheduler_tpu_trace_journal_seconds_total",
    "Wall seconds spent inside PodDecisionJournal.record (flight "
    "recorder and sink write included). Flushed together with "
    "scheduler_tpu_trace_journal_records_total, so the ratio of the "
    "two deltas over any interval is the journal's seconds per record.",
    registry=REGISTRY,
)
flight_recorder_dumps_total = Counter(
    "scheduler_tpu_flight_recorder_dumps_total",
    "Flight-recorder ring dumps, by trigger "
    "(crash|invariant|manual|breaker).",
    ["trigger"],
    registry=REGISTRY,
)

# -- ingest (server/extender.py POST /api/pods) --

ingest_seconds_total = Counter(
    "scheduler_ingest_seconds_total",
    "Wall seconds the POST /api/pods handler spent parsing a body and "
    "applying its pods to the state service (the body's network read "
    "is not in it). Time waiting for the interpreter lock while the "
    "handler runs is.",
    registry=REGISTRY,
)
ingest_pods_total = Counter(
    "scheduler_ingest_pods_total",
    "Pods applied through POST /api/pods.",
    registry=REGISTRY,
)
ingest_pod_specs_total = Counter(
    "scheduler_ingest_pod_specs_total",
    "Pods decoded by POST /api/pods, by how their spec was decoded: "
    "reused = spec and status compared equal to one of the 16 most "
    "recently parsed (a Deployment's replicas), so the pod took that "
    "parse's fields and its own metadata; parsed = through "
    "Pod.from_dict. Incremented once a body.",
    ["decode"],
    registry=REGISTRY,
)

# -- flight telemetry (kubernetes_tpu/obs/{profile,sentinel,bundle}) --

profile_stage_seconds = Counter(
    "scheduler_profile_stage_seconds",
    "Cumulative wall seconds attributed to each batch stage by the "
    "continuous per-stage profiler, by stage (tensorize|dispatch|"
    "fence_wait|deferred_read|validate|apply|bind). Assembled "
    "host-side from seams the loops already time — zero new device "
    "syncs; rate() it for the live stage mix.",
    ["stage"],
    registry=REGISTRY,
)
anomaly_total = Counter(
    "scheduler_anomaly_total",
    "Anomalies fired by the telemetry sentinel's multi-window "
    "regression rules, by signal (pods_per_sec|p99_latency_s|"
    "chain_fraction|discard_rate|cas_conflict_rate|"
    "gang_incomplete_rate|breaker). Each firing also journals a "
    "telemetry_anomaly record and arms a capture-on-anomaly replay "
    "bundle.",
    ["signal"],
    registry=REGISTRY,
)
telemetry_bundles_total = Counter(
    "scheduler_telemetry_bundles_total",
    "Capture-on-anomaly replay-bundle capture events, by trigger "
    "(sentinel|breaker|quarantine|invariant|manual). Counts the "
    "capture decision; whether a bundle directory was written "
    "additionally depends on a configured bundle dir and the "
    "per-process bundle budget.",
    ["trigger"],
    registry=REGISTRY,
)

# -- live SLO engine (kubernetes_tpu/obs/slo.py) --

slo_p50_pod_latency_seconds = Gauge(
    "scheduler_slo_p50_pod_latency_seconds",
    "Sliding-window median per-pod scheduling latency (first queue "
    "entry -> bind commit), computed by the live SLO engine from the "
    "latencies the apply path already materializes — zero new device "
    "syncs.",
    registry=REGISTRY,
)
slo_p99_pod_latency_seconds = Gauge(
    "scheduler_slo_p99_pod_latency_seconds",
    "Sliding-window p99 per-pod scheduling latency (first queue entry "
    "-> bind commit) from the live SLO engine — 'are we meeting the "
    "latency SLO right now', read off the running server.",
    registry=REGISTRY,
)
slo_bind_throughput = Gauge(
    "scheduler_slo_bind_throughput_pods_per_second",
    "Pods bound per second over the SLO engine's sliding window "
    "(ratio of sums, the CounterWindow.rate discipline).",
    registry=REGISTRY,
)
slo_error_budget_burn = Gauge(
    "scheduler_slo_error_budget_burn",
    "Multi-window error-budget burn rate: (observed bad-event "
    "fraction) / (allowed bad fraction), where a bad event is a bound "
    "pod missing the latency objective or a bind failure. 1.0 burns "
    "the budget exactly at the sustainable rate; the short window "
    "catches fast burns, the long window slow ones.",
    ["window"],
    registry=REGISTRY,
)
slo_healthy = Gauge(
    "scheduler_slo_healthy",
    "1 while the SLO engine reads healthy; 0 while the short-window "
    "burn rate exceeds the degraded threshold (with the minimum event "
    "count met). The degraded-health signal the fleet handoff "
    "ordering (exchange degraded flag) and the resilience breaker "
    "(half-open probes deferred) consume.",
    registry=REGISTRY,
)

# -- compile observability (kubernetes_tpu/obs/compile.py) --

xla_compilations_total = Counter(
    "scheduler_xla_compilations_total",
    "Executables built by the process-wide compile watcher's count "
    "(jax.monitoring backend_compile events) — each one is a dispatch "
    "that stalled on a compile, or on a retrieval from the persistent "
    "cache (scheduler_xla_persistent_cache_hits_total counts those; "
    "the difference is what XLA actually compiled).",
    registry=REGISTRY,
)
xla_persistent_cache_hits_total = Counter(
    "scheduler_xla_persistent_cache_hits_total",
    "Executables served from JAX's persistent compilation cache "
    "(utils/compile_cache.py) instead of being compiled: a restarted "
    "scheduler on a warm cache directory shows these in place of "
    "compilations.",
    registry=REGISTRY,
)
xla_compile_seconds_total = Counter(
    "scheduler_xla_compile_seconds_total",
    "Cumulative wall seconds spent in XLA backend compilation, as "
    "observed by the compile watcher.",
    registry=REGISTRY,
)
xla_compile_cache_keys = Gauge(
    "scheduler_xla_compile_cache_keys",
    "Distinct compile scopes (dispatch shape/static fingerprints) "
    "this process has compiled for — the working-set size of the jit "
    "cache as the scheduler sees it.",
    registry=REGISTRY,
)
xla_recompilations = Gauge(
    "scheduler_xla_recompilations",
    "Compilations beyond the first per compile scope: a steady-state "
    "loop re-paying a compile for a shape it already compiled — the "
    "silent streaming-hot-path killer the known-shape regression test "
    "pins at zero. Pairs with scheduler_xla_compile_cache_keys.",
    registry=REGISTRY,
)

# -- fleet trace/journal aggregation (the cross-replica obs surface) --

fleet_journal_segments_total = Counter(
    "scheduler_fleet_journal_segments_total",
    "Bounded journal segments this replica shipped to the occupancy "
    "hub's append-only aggregation surface (piggybacked on the "
    "existing write-behind flush — no new RPC cadence).",
    registry=REGISTRY,
)
fleet_journal_lines_total = Counter(
    "scheduler_fleet_journal_lines_total",
    "Decision-journal lines this replica shipped to the hub's "
    "aggregation surface (obs explain --fleet reads the merged "
    "stream).",
    registry=REGISTRY,
)

# -- continuous rebalancer (kubernetes_tpu/rebalance) --

rebalance_runs_total = Counter(
    "scheduler_rebalance_runs_total",
    "Rebalance passes by outcome: planned (evictions executed), "
    "empty_plan (fragmented but no strictly-improving executable "
    "move survived bounding), not_fragmented (detector below "
    "threshold or nothing movable), fenced (the incarnation lost "
    "its commit fence — a zombie rebalancer moves nothing).",
    ["outcome"],
    registry=REGISTRY,
)
rebalance_evictions_total = Counter(
    "scheduler_rebalance_evictions_total",
    "Pods evicted by the rebalancer through the eviction "
    "subresource (each carries a nominated-node hint toward its "
    "auction target and re-enters the scheduling queue).",
    registry=REGISTRY,
)
rebalance_migrations_total = Counter(
    "scheduler_rebalance_migrations_total",
    "Completed migrations — an evicted pod re-bound — by where it "
    "landed (target = the auction's nominated node, elsewhere = the "
    "solver placed it differently; the hint is advisory).",
    ["result"],
    registry=REGISTRY,
)
rebalance_pdb_blocked_total = Counter(
    "scheduler_rebalance_pdb_blocked_total",
    "Planned moves dropped by the PDB gate "
    "(classify_pdb_violations over the selected stream): the pod's "
    "PodDisruptionBudget had no disruptions left.",
    registry=REGISTRY,
)
rebalance_plan_seconds = Histogram(
    "scheduler_rebalance_plan_seconds",
    "Wall time of the rebalance plan solve: the single-shot auction "
    "(pack objective) re-placing every movable pod against the "
    "cluster's fixed load.",
    buckets=_BUCKETS,
    registry=REGISTRY,
)
rebalance_packing_utilization = Gauge(
    "scheduler_rebalance_packing_utilization",
    "Dominant-resource packed utilization of the in-use nodes at "
    "the last rebalance pass (detector.py): max(cpu, mem) of "
    "used/allocatable over schedulable nodes hosting pods.",
    registry=REGISTRY,
)
rebalance_stranded_fraction = Gauge(
    "scheduler_rebalance_stranded_fraction",
    "Fraction of total free capacity stranded on partly-used nodes "
    "(free slivers between resident pods) at the last rebalance "
    "pass.",
    registry=REGISTRY,
)
rebalance_priority_inversions = Gauge(
    "scheduler_rebalance_priority_inversions",
    "Pending pods more important than the least important bound pod "
    "at the last fragmented rebalance pass — re-packing could seat "
    "them (advisory: the planner itself only consolidates).",
    registry=REGISTRY,
)

# -- cluster simulator (kubernetes_tpu/sim) --

sim_events_total = Counter(
    "scheduler_sim_events_total",
    "Cluster-churn events the simulator applied, by operation "
    "(create_pod|delete_pod|create_node|delete_node|flap_label|"
    "alloc_grow|alloc_shrink|external_bind).",
    ["op"],
    registry=REGISTRY,
)
sim_faults_injected_total = Counter(
    "scheduler_sim_faults_injected_total",
    "Faults the simulator injected at real boundaries, by fault kind "
    "(bind_conflict|watch_delay|watch_duplicate|extender_timeout|"
    "extender_5xx|permit_stall|solver_fault|poison_pod|crash|"
    "hub_partition|lease_fence).",
    ["fault"],
    registry=REGISTRY,
)
sim_invariant_violations_total = Counter(
    "scheduler_sim_invariant_violations_total",
    "Invariant violations the simulator's checkers flagged, by "
    "invariant (double_bind|capacity|lost_pod|progress|monotonic|"
    "constraint|journal|global_overcommit|resilience|recovery|"
    "fencing|rebalance|tuning|no_partial_gang_ever_bound|telemetry).",
    ["invariant"],
    registry=REGISTRY,
)
sim_cycles_total = Counter(
    "scheduler_sim_cycles_total",
    "Simulator churn cycles driven to completion.",
    registry=REGISTRY,
)

extender_batch_size = Histogram(
    "scheduler_tpu_extender_batch_size",
    "Webhook requests coalesced per device evaluation (micro-batching).",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
    registry=REGISTRY,
)
extender_request_seconds = Histogram(
    "scheduler_tpu_extender_request_seconds",
    "Wall time of one micro-batched extender evaluation.",
    buckets=_BUCKETS,
    registry=REGISTRY,
)


# -- what held a thread of serve (obs/waits.py) --

gc_pause_seconds = Histogram(
    "scheduler_gc_pause_seconds",
    "Pause of one CPython garbage collection in serve, by generation "
    "(0|1|2); on in every serve, as kube-scheduler exports "
    "go_gc_duration_seconds.",
    ["generation"],
    buckets=(
        0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 4.0,
    ),
    registry=REGISTRY,
)
cluster_lock_wait_seconds_total = Counter(
    "scheduler_cluster_lock_wait_seconds_total",
    "Seconds threads waited for cluster.lock in contended acquires, by "
    "thread (loop = inside run_pipelined|ingest = the server's event "
    "loop|other); serve --telemetry only.",
    ["thread"],
    registry=REGISTRY,
)
cluster_lock_contended_total = Counter(
    "scheduler_cluster_lock_contended_total",
    "Acquires of cluster.lock that had to wait, by thread (loop|ingest|"
    "other); serve --telemetry only.",
    ["thread"],
    registry=REGISTRY,
)

# called by render() first: series whose observations were queued where
# taking a metric's lock is not safe (the collector's callback)
before_render = []


def render() -> bytes:
    """Prometheus exposition text for the /metrics endpoint."""
    for flush in before_render:
        flush()
    return generate_latest(REGISTRY)
