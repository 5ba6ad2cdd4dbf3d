"""Benchmark entry point (driver-run on real TPU hardware).

Measures the FULL BASELINE.md target ladder (VERDICT r2 #3):

  #1 scheduler_perf SchedulingBasic shape: 500 pods x 500 nodes, default
     plugins, via the YAML-runner code path (test/integration/scheduler_perf).
  #2 NodeResourcesFit + BalancedAllocation: 5k homogeneous pods x 1k nodes
     through the full stack (state service -> queue -> snapshot -> exact TPU
     solve -> bind). THE HEADLINE: the grouped fast path engages here.
  #3 PodTopologySpread across 3 zones: 10k pods x 5k nodes, hard maxSkew=1
     zone constraint.
  #4 InterPodAffinity anti-affinity (the O(n^2) hot path): 5k pods x 5k
     nodes, required hostname anti-affinity.
  #5 Global rebalance north star: 50k pods x 10k nodes single-shot auction.
  #6 Sustained open-loop arrival with a sync-vs-pipelined A/B per shape
     (plain/ports/spread/anti): pods arrive at a fixed rate while the
     scheduler drains concurrently; hard shapes run through
     run_pipelined's occupancy-carrying sub-batch split. Emits
     sustained_pods_per_sec + sustained_p99_pod_latency_s (also hoisted
     to the top level from the pipelined plain shape).
  #7 Multichip A/B: the exact-parity session solve at the north-star
     shape on 1 device vs the full node-axis mesh
     (ExactSolver.solve(mesh=...)), plus the 8x-node shape (~81,920
     nodes) on the full mesh. Emits multichip_pods_per_sec +
     multichip_speedup (hoisted to the top level); skips with a reason
     string when only one device is visible.
  #8 Fleet A/B, DEVICE tier: 1 scheduler process (full device set) vs
     N active fleet replicas (each its own OS process, shard-scoped by
     the consistent-hash ring, pinned to an EXCLUSIVE 1/N mesh slice
     of the shared virtual device set, stream-dispatching) draining
     the same open-loop arrival stream at ladder #6 rates, with ONE
     occupancy hub served over localhost gRPC (fenced CAS admits +
     row traffic on the real wire). The backend is XLA CPU on every
     box (N children cannot share one libtpu) — the multiplier is the
     fleet tier scaling the whole device-path pipeline. Emits
     fleet_pods_per_sec + fleet_speedup (hoisted to the top level).
  #9 Degraded-mode A/B (kubernetes_tpu/resilience): the same sustained
     open-loop workload at the top fallback-ladder tier vs pinned to
     the pure-host serial-greedy rung (force_tier="host") — the floor
     the scheduler degrades to when every accelerator tier's breaker
     is open. Emits degraded_pods_per_sec (hoisted to the top level)
     + degradation_factor, so the cost of degradation is a measured
     number.

 #10 Rebalance loop A/B: the continuous rebalancer closing a seeded
     fragmented 51.2k x 10.24k cluster (packed utilization before vs
     after, median plan solve per the <1 s target).
 #11 Backlog drain at 10x the proven scale (ISSUE 12): a 512k-pod
     backlog drained end to end against 102,400 nodes through
     Scheduler.drain_backlog — HBM-budget-planned chunk-aligned
     sub-batches through run_streaming's slot ring with cross-batch
     occupancy chaining on a hard (zone-spread) shape; 1-device vs
     full-mesh A/B, MEDIAN drain-chunk solve time, end-state validity
     asserted, plus the single-shot auction (scarcity repair on) at
     the same shape. Emits backlog_drain_pods_per_sec +
     backlog_drain_seconds (hoisted to the top level).

Each ladder reports steady-state (warm-start) pods/s, best of 3 full
passes — compiles happen in a same-shaped warmup pass (persistent compile
cache makes restarts cheap) — plus per-workload invariant checks (all
placed; skew bound; exclusivity).

Measurement regime: all rows here include per-batch assignment reads,
so they are sync-mode end-to-end numbers; the ``dispatch_read_canary``
entry records a trivial dispatch's time before and after the process's
first device->host read, so the cost of one sync on the machine that
ran the bench is in the record. The batch/group sizes were chosen when
a sync cost far more than it does on a directly attached chip
(``chip_smoke.py`` reads that cost); they are due for the benchmark PR
(ROADMAP Speed 1), not tuned here.

Prints ONE JSON line. ``value``/``vs_baseline`` headline ladder #2;
``vs_baseline`` divides by the TOP of the reference's in-proc band
(O(1-5k) pods/s on scheduler_perf-style runs, BASELINE.md) — the strictest
available comparator. The API-bound ~300 pods/s figure is reported
separately as vs_api_bound. Each ladder reports the solver's actual
dispatch histogram (per-pod scan vs grouped chunk kinds) instead of a
hardcoded path label; nothing is extrapolated from the easy regime.
"""

from __future__ import annotations

import json
import time

BAND_TOP_PODS_PER_SEC = 5_000.0  # top of the in-proc CPU reference band
API_BOUND_PODS_PER_SEC = 300.0  # sustained API/QPS-bound reference figure

NS_NODES = 10_240
NS_PODS = 51_200
NS_TARGET_S = 1.0


def _mk_node(i: int, zones: int = 3):
    from kubernetes_tpu.api.wrappers import MakeNode

    return (
        MakeNode()
        .name(f"node-{i:05}")
        .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"})
        .label("topology.kubernetes.io/zone", f"z{i % zones}")
        .label("kubernetes.io/hostname", f"node-{i:05}")
        .obj()
    )


def _mk_pod(i: int, kind: str):
    from kubernetes_tpu.api.wrappers import MakePod

    b = (
        MakePod()
        .name(f"pod-{i:05}")
        .label("app", kind)
        .req({"cpu": "250m", "memory": "512Mi"})
    )
    if kind == "spread":
        b = b.spread_constraint(
            1, "topology.kubernetes.io/zone", "DoNotSchedule", {"app": kind}
        )
    elif kind == "anti":
        b = b.pod_anti_affinity("kubernetes.io/hostname", {"app": kind})
    elif kind == "ports":
        # 8-port pool: real conflict pressure (NodePorts occupancy carry)
        # while 500 nodes x 8 ports leaves headroom for every pod
        b = b.host_port(8000 + i % 8)
    return b.obj()


def _dispatch_label(sched) -> str:
    """Derive the solver-path label from the solver's actual dispatch
    histogram instead of asserting it (round-3's hardcoded labels claimed
    grouping was disabled on workloads where the quota chunks engaged)."""
    from collections import Counter

    total: Counter = Counter()
    for solver in sched.solvers.values():
        total.update(getattr(solver, "dispatch_counts", {}))
    if not total:
        return "no solves dispatched"
    names = {
        "scan": "per-pod scan",
        "kind0": "grouped slow-replay chunks",
        "kind1": "grouped plain fast chunks",
        "kind2": "grouped spread-quota chunks",
        "kind3": "grouped anti-quota chunks",
    }
    parts = [
        f"{names.get(k, k)}={v}" for k, v in sorted(total.items())
    ]
    return "; ".join(parts)


def _run_ladder(
    n_nodes: int,
    n_pods: int,
    kind: str,
    batch: int,
    warm_pods: int,
    group: int = 512,
    reps: int = 3,
) -> dict:
    """Warm-start end-to-end run, best of ``reps`` full passes: a
    same-shaped throwaway cluster compiles every executable (incl. the
    device-session heal path), then each timed pass builds a fresh
    cluster and runs the production path only.

    ``batch``/``group`` default large: every batch ends in one blocking
    host<->device sync, so pods per solve call amortises it (the
    per-pod p99 latency cost of the bigger batch is reported
    alongside)."""
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    def build(n_p):
        cs = ClusterState()
        for i in range(n_nodes):
            cs.create_node(_mk_node(i))
        sched = Scheduler(
            cs,
            SchedulerConfig(
                batch_size=batch,
                solver=ExactSolverConfig(
                    tie_break="random", group_size=group
                ),
            ),
        )
        for i in range(n_p):
            cs.create_pod(_mk_pod(i, kind))
        return cs, sched

    t0 = time.perf_counter()
    _, wsched = build(warm_pods)
    wsched.schedule_batch()
    wsched.schedule_batch()
    warmup_s = time.perf_counter() - t0

    best = None
    run_walls = []
    for _ in range(reps):
        cs, sched = build(n_pods)
        batch_times: list[tuple[float, int]] = []
        solve_s = 0.0
        scheduled = 0
        t0 = time.perf_counter()
        while True:
            tb = time.perf_counter()
            r = sched.schedule_batch()
            n = len(r.scheduled)
            if not r.progressed:
                break
            batch_times.append((time.perf_counter() - tb, n))
            solve_s += r.solve_seconds
            scheduled += n
        total = time.perf_counter() - t0
        assert scheduled == n_pods, (
            f"{kind}: only {scheduled}/{n_pods} scheduled"
        )
        _check_invariants(cs, kind)
        run_walls.append(round(total, 3))
        if best is None or total < best[0]:
            best = (total, solve_s, batch_times, sched)

    total, solve_s, batch_times, sched = best
    per_pod = sorted(t for t, n in batch_times for _ in range(n))
    p99 = per_pod[int(0.99 * (len(per_pod) - 1))] if per_pod else 0.0
    return {
        "pods": n_pods,
        "nodes": n_nodes,
        "batch": batch,
        "group": group,
        "pods_per_sec": round(n_pods / total, 1) if total else None,
        "wall_s": round(total, 3),
        "run_walls_s": run_walls,
        "device_solve_s": round(solve_s, 3),
        "p99_batch_latency_s": round(p99, 4),
        "warmup_s": round(warmup_s, 2),
        "dispatch": _dispatch_label(sched),
    }


def _check_invariants(cs, kind: str) -> None:
    """Workload-specific correctness gates — a number only counts if the
    bindings are right (BASELINE.md measurement protocol)."""
    from collections import Counter

    pods = [p for p in cs.list_pods() if p.name.startswith("pod-")]
    if kind == "spread":
        zones = Counter()
        node_zone = {n.name: n.labels["topology.kubernetes.io/zone"] for n in cs.list_nodes()}
        for p in pods:
            zones[node_zone[p.node_name]] += 1
        if zones:
            assert max(zones.values()) - min(zones.values()) <= 1, (
                f"zone skew violated: {dict(zones)}"
            )
    elif kind == "anti":
        per_node = Counter(p.node_name for p in pods)
        worst = max(per_node.values(), default=0)
        assert worst <= 1, f"hostname anti-affinity violated: {worst} pods on one node"


def _sustained_shape(
    kind: str,
    n_nodes: int,
    n_pods: int,
    rate: float,
    mode: str = "pipelined",  # "sync" | "pipelined" | "streaming"
    batch: int = 2_048,
    group: int = 256,
    split: int = 4,
    stream_depth: int = 4,
    resilience=None,  # ResilienceConfig override (ladder #9's forced
    # host-greedy arm); None = defaults (top tier)
    tuning=None,  # TuningConfig: the ladder #12 tuned arm; None = static
    obs=None,  # ObsConfig: the ladder #13 obs-on arm (full tracing +
    # journal + SLO engine); None = observability off (the default
    # every other ladder measures)
    fleet=None,  # FleetConfig factory (called per build): ladder #13
    # runs BOTH arms as a single-replica fleet so the obs-on arm's
    # journal-segment shipping to the hub is inside the measured window
) -> dict:
    """One open-loop sustained-arrival run: pods arrive at ``rate``/s
    while the scheduler drains concurrently — streaming
    (Scheduler.run_streaming, the device-resident solve loop with
    cross-batch occupancy chaining), pipelined (Scheduler.run_pipelined,
    hard shapes via the occupancy-carrying sub-batch split), or
    synchronous (schedule_batch); same workload for every arm.

    Reports POST-WARMUP steady-state throughput (the first measured
    batch, which absorbs residual warmup, is dropped; time-weighted
    over the rest), the per-pod e2e p99 (first queue entry -> bind
    commit) — BASELINE.md's sustained metric pair — the pipeline
    mode/sub-batch counters proving which path ran, and the RTT
    attribution row: hidden-vs-paid deferred reads (a read that blocked
    the driver > 1 ms was not hidden),
    unhidden_reads_per_batch, and the h2d/d2h transfer-byte deltas."""
    from kubernetes_tpu import metrics
    from kubernetes_tpu.perf.runner import WorkloadResult
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    def build():
        cs = ClusterState()
        for i in range(n_nodes):
            cs.create_node(_mk_node(i))
        sched = Scheduler(
            cs,
            SchedulerConfig(
                batch_size=batch,
                # the streaming arm splits too (run_streaming threads
                # _choose_split through _dispatch_stream) — only the
                # sync arm pins 1 so the A/B isolates the dispatcher
                pipeline_split=split if mode != "sync" else 1,
                stream_depth=stream_depth,
                solver=ExactSolverConfig(
                    tie_break="random", group_size=group
                ),
                resilience=resilience,
                tuning=tuning,
                obs=obs,
                fleet=fleet() if fleet is not None else None,
            ),
        )
        return cs, sched

    def drive(sched, max_batches=None):
        if mode == "streaming":
            return sched.run_streaming(
                max_batches=max_batches or 10_000
            )
        if mode == "pipelined":
            return sched.run_pipelined(max_batches=max_batches or 10_000)
        if max_batches is not None:
            return [sched.schedule_batch()]
        return sched.run_until_settled()

    # warmup: compile this shape's executables (incl. the chained
    # sub-batch variants) on a throwaway cluster
    cs, sched = build()
    for i in range(min(n_pods, batch)):
        cs.create_pod(_mk_pod(i, kind))
    drive(sched)

    cs, sched = build()
    mode_counters = {
        m: metrics.pipeline_mode_total.labels(m)
        for m in ("overlap", "carry", "stream", "sync")
    }
    modes0 = {m: c._value.get() for m, c in mode_counters.items()}
    sub0 = metrics.pipeline_subbatches_total._value.get()
    h2d0 = metrics.h2d_bytes_total._value.get()
    d2h0 = metrics.d2h_bytes_total._value.get()
    # stats ride the perf runner's WorkloadResult so the steady-state
    # definition (drop the first measured batch, time-weighted) and the
    # e2e p99 are ONE formula shared with the SteadyStateArrival
    # threshold gate — not a bench-local reimplementation that drifts
    res = WorkloadResult("sustained", kind)
    t0 = time.perf_counter()
    prev_at = t0
    created = 0
    while created < n_pods or sched.pending:
        due = min(n_pods, int((time.perf_counter() - t0) * rate) + 1)
        while created < due:
            cs.create_pod(_mk_pod(created, kind))
            created += 1
        made_progress = False
        results = drive(
            sched, max_batches=8 if mode == "streaming" else 2
        )
        for r in results:
            n = len(r.scheduled)
            res.scheduled += n
            res.unschedulable += len(r.unschedulable)
            at = r.completed_at or time.perf_counter()
            if n:
                dt = max(at - prev_at, 1e-9)
                res.batch_samples.append((dt, n))
                res.samples.append(n / dt)
                res.measured_pods += n
                res.pod_latencies.extend(r.e2e_latencies)
            prev_at = at
            made_progress = made_progress or r.progressed
        if created >= n_pods and not made_progress:
            break  # drained (or only stuck pods remain)
    res.measure_seconds = time.perf_counter() - t0
    batches = max(sched._trace_step, 1)
    return {
        "pods": n_pods,
        "nodes": n_nodes,
        "arrival_rate_pods_per_sec": rate,
        "scheduled": res.scheduled,
        "unschedulable": res.unschedulable,
        "sustained_pods_per_sec": round(res.steady_pods_per_sec(), 1),
        "sustained_p99_pod_latency_s": round(
            res.latency_summary()["p99"], 4
        ),
        "wall_s": round(res.measure_seconds, 3),
        "pipeline_modes": {
            m: int(c._value.get() - modes0[m])
            for m, c in mode_counters.items()
        },
        "pipeline_subbatches": int(
            metrics.pipeline_subbatches_total._value.get() - sub0
        ),
        # RTT attribution (ISSUE 10): a deferred read that blocked the
        # driver > 1 ms paid an un-hidden host<->device round trip; the
        # rest were hidden behind overlapped host work / the streaming
        # completion thread. unhidden_reads_per_batch is the number the
        # device-resident loop drives toward one per event-fence.
        "rtt_attribution": {
            "reads_hidden": sched._reads_hidden,
            "reads_paid": sched._reads_paid,
            "unhidden_reads_per_batch": round(
                sched._reads_paid / batches, 4
            ),
            "batches": batches,
            "stream_chained_batches": int(
                sched.solver.dispatch_counts.get("stream_chained", 0)
            ),
            "h2d_bytes": int(metrics.h2d_bytes_total._value.get() - h2d0),
            "d2h_bytes": int(metrics.d2h_bytes_total._value.get() - d2h0),
        },
        "dispatch": _dispatch_label(sched),
        # ladder #12 tuned arm: the tuning runtime's decision/guardrail
        # accounting and final knob values
        "tuning": (
            sched.tuner.summary() if sched.tuner is not None else None
        ),
        # ladder #13 obs-on arm: the live SLO engine's final snapshot
        # (are-we-meeting-SLOs as measured DURING the run) plus the
        # journal/span volume the arm paid for
        "slo": sched.slo.snapshot() if sched.slo is not None else None,
        "obs_volume": (
            {
                "journal_records": sched.journal.total_records,
                "spans": (
                    len(sched.flight.spans()) + sched.flight.dropped_spans
                ),
            }
            if sched.journal is not None and sched.flight is not None
            else None
        ),
        # ladder #13 telemetry arm: the continuous profiler's stage
        # ledger + sentinel state as measured during the run
        "telemetry": (
            sched.telemetry.snapshot()
            if getattr(sched, "telemetry", None) is not None
            else None
        ),
    }


def ladder_sustained() -> dict:
    """#6: the sustained-arrival ladder with a per-shape
    sync-vs-pipelined-vs-STREAMING A/B/C. The hard shapes
    (ports/spread/anti) run through run_pipelined's occupancy-carrying
    path and through run_streaming's cross-batch occupancy chain — the
    streaming dispatcher (ISSUE 10) is gated on its sustained p99
    against the PR 4 pipelined arm, with the RTT attribution row
    (unhidden_reads_per_batch) proving the per-batch round-trip floor
    actually fell."""
    shapes = (
        # (kind, pods, arrival rate): rates oversupply the scheduler so
        # the measured number is scheduler capacity, not arrival cap
        ("plain", 4_000, 20_000.0),
        ("ports", 2_000, 6_000.0),
        ("spread", 3_000, 8_000.0),
        ("anti", 400, 2_000.0),
    )
    out: dict = {}
    for kind, n_pods, rate in shapes:
        sync = _sustained_shape(kind, 500, n_pods, rate, mode="sync")
        pipe = _sustained_shape(kind, 500, n_pods, rate, mode="pipelined")
        stream = _sustained_shape(
            kind, 500, n_pods, rate, mode="streaming"
        )
        pipe_p99 = pipe["sustained_p99_pod_latency_s"]
        stream_p99 = stream["sustained_p99_pod_latency_s"]
        out[kind] = {
            "sync": sync,
            "pipelined": pipe,
            "streaming": stream,
            "pipelined_vs_sync": round(
                pipe["sustained_pods_per_sec"]
                / max(sync["sustained_pods_per_sec"], 1e-9),
                3,
            ),
            "pipelined_ge_sync": bool(
                pipe["sustained_pods_per_sec"]
                >= sync["sustained_pods_per_sec"]
            ),
            # the streaming gate pair: p99 speedup over the pipelined
            # arm (>= 2x target on plain) and no-regression marker
            "streaming_p99_speedup_vs_pipelined": round(
                pipe_p99 / max(stream_p99, 1e-9), 3
            ),
            "streaming_ge_pipelined": bool(
                stream["sustained_pods_per_sec"]
                >= pipe["sustained_pods_per_sec"]
            ),
            "streaming_unhidden_reads_per_batch": stream[
                "rtt_attribution"
            ]["unhidden_reads_per_batch"],
        }
    return out


def _fleet_replica_worker(
    rid: str,
    universe: tuple,
    n_nodes: int,
    n_pods: int,
    rate: float,
    batch: int,
    group: int,
    start_at: float,
    out_q,
    kind: str = "plain",
    hub_addr: str = "",
    total_devices: int = 8,
) -> None:
    """One fleet replica as its own OS process (spawn target): builds
    its replica of the state service (every replica of a real fleet
    watches the same apiserver; here each process replays the same
    deterministic node/pod stream), runs a fleet-mode Scheduler whose
    shard filter scopes it to its ring partition, and reports its
    completion timeline on ``out_q``. Pod arrivals follow one shared
    wall-clock schedule anchored at ``start_at`` (epoch time), so the
    fleet's replicas face the same open-loop arrival process
    concurrently.

    DEVICE-TIER arms (ISSUE 11): every replica owns an EXCLUSIVE mesh
    slice of one shared virtual device set (mesh_slice = (rank, N)
    over ``total_devices`` forced host-platform devices) and drives
    the STREAMING dispatcher (PR 10) against it — the solve is the
    sharded resident-session device path end to end, N processes never
    sharing a device. The backend is XLA CPU on every box (N spawned
    children still cannot share one libtpu), so the measured multiplier
    is the fleet tier scaling the whole device-path pipeline — shard-
    scoped caches, per-slice sharded sessions, per-replica stream
    rings — under a fair hardware split (disjoint core slices). Multi-
    replica arms share ONE occupancy hub over a localhost gRPC server
    (``hub_addr`` -> RemoteOccupancyExchange): fenced CAS admits pay a
    synchronous round trip, plain row traffic rides the write-behind
    apply_ops batches — the wire discipline production would use."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={total_devices}"
        ).strip()
    if len(universe) > 1:
        # disjoint core slices per replica: two XLA CPU runtimes
        # otherwise both size their intra-op pools to the whole box
        # and thrash each other — a real fleet puts replicas on
        # separate hosts, so the honest same-box A/B is a fair
        # hardware split, not oversubscription
        try:
            cores = sorted(os.sched_getaffinity(0))
            n = len(universe)
            rank = universe.index(rid)
            share = max(len(cores) // n, 1)
            mine = cores[rank * share : (rank + 1) * share] or cores
            os.sched_setaffinity(0, mine)
        except (AttributeError, OSError):
            pass  # non-Linux: let the OS schedule
    import jax

    jax.config.update("jax_enable_x64", True)
    from kubernetes_tpu.fleet import FleetConfig
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    rank = universe.index(rid)
    mesh_slice = (rank, len(universe))

    def build():
        cs = ClusterState()
        for i in range(n_nodes):
            cs.create_node(_mk_node(i, zones=8))
        fleet = (
            FleetConfig(
                replica=rid, replicas=universe, hub_address=hub_addr
            )
            if len(universe) > 1
            else None
        )
        sched = Scheduler(
            cs,
            SchedulerConfig(
                batch_size=batch,
                mesh_slice=mesh_slice,
                solver=ExactSolverConfig(
                    tie_break="random", group_size=group
                ),
                fleet=fleet,
            ),
        )
        return cs, sched

    # warmup compile on a throwaway cluster. The shard filter routes
    # only ~1/N of created pods to this replica, so seed batch*N pods:
    # each replica must warm the FULL batch-size pod bucket it will
    # solve in the measured window (a half-shard warmup leaves the
    # measured run paying a fresh XLA compile per replica)
    cs, sched = build()
    for i in range(min(n_pods, batch * max(len(universe), 1) * 2)):
        cs.create_pod(_mk_pod(i, kind))
    sched.run_streaming()

    cs, sched = build()
    # prebuild the arrival stream: the pod OBJECTS are the synthetic
    # client's cost, not the scheduler's — building them inside the
    # measured window would bottleneck every arm on the builder
    pods = [_mk_pod(i, kind) for i in range(n_pods)]
    completions: list[tuple[float, int]] = []
    latencies: list[float] = []
    unschedulable = 0
    created = 0
    while time.time() < start_at:
        time.sleep(0.001)
    deadline = start_at + 300.0
    while time.time() < deadline:
        due = min(n_pods, int((time.time() - start_at) * rate) + 1)
        while created < due:
            cs.create_pod(pods[created])
            created += 1
        progressed = False
        for r in sched.run_streaming(max_batches=2):
            n = len(r.scheduled)
            if n:
                completions.append((time.time(), n))
                latencies.extend(r.e2e_latencies)
            unschedulable += len(r.unschedulable)
            progressed = progressed or r.progressed
        if created >= n_pods and not progressed and not sched.pending:
            break
    out_q.put(
        {
            "rid": rid,
            "completions": completions,
            "latencies": latencies,
            "unschedulable": unschedulable,
        }
    )


def _fleet_sustained(
    n_replicas: int,
    n_nodes: int,
    n_pods: int,
    rate: float,
    batch: int = 2_048,
    group: int = 256,
    kind: str = "plain",
    total_devices: int = 8,
) -> dict:
    """One open-loop sustained run driven by ``n_replicas`` active
    fleet replicas, each its OWN OS process (1 = the classic
    sole-owner scheduler, the A arm — one process, the WHOLE device
    set). This is the deployment shape the fleet tier exists for: N
    scheduler processes, each shard-scoped by the ring and pinned to
    an exclusive 1/N mesh slice of the same device set, all
    stream-dispatching concurrently against ONE occupancy hub served
    over localhost gRPC — the speedup is the fleet tier multiplying
    the device-path streaming dispatcher, wire costs included."""
    import multiprocessing

    server = None
    hub_addr = ""
    if n_replicas > 1:
        # one REAL occupancy hub for the whole fleet, served behind
        # the bulk gRPC boundary: stage/commit rows and fenced CAS
        # admits all cross a real socket (RemoteOccupancyExchange),
        # so reconcile-bearing shapes (spread/anti) measure honestly
        # too — the PR 6 private-hub refusal is gone
        from kubernetes_tpu.fleet import OccupancyExchange
        from kubernetes_tpu.server.bulk import BulkCore, make_grpc_server
        from kubernetes_tpu.state.cluster import ClusterState

        core = BulkCore(ClusterState(), exchange=OccupancyExchange())
        server, hub_port = make_grpc_server(core, port=0)
        server.start()
        hub_addr = f"127.0.0.1:{hub_port}"
    ctx = multiprocessing.get_context("spawn")
    universe = tuple(f"r{i}" for i in range(n_replicas))
    out_q = ctx.Queue()
    # anchor the shared arrival schedule far enough out that every
    # process finishes its warmup compile first
    start_at = time.time() + 25.0
    procs = [
        ctx.Process(
            target=_fleet_replica_worker,
            args=(
                rid, universe, n_nodes, n_pods, rate, batch, group,
                start_at, out_q, kind, hub_addr, total_devices,
            ),
        )
        for rid in universe
    ]
    for p in procs:
        p.start()
    try:
        results = [out_q.get(timeout=600.0) for _ in procs]
    finally:
        for p in procs:
            p.join(timeout=30.0)
        if server is not None:
            server.stop(grace=None)
    merged = sorted(x for r in results for x in r["completions"])
    scheduled = sum(n for _, n in merged)
    # steady-state: one formula for both arms — drop the first
    # completed batch (compile/ramp residue), divide the rest by the
    # wall from that completion to the last (epoch clocks, one host)
    if len(merged) > 1:
        steady = sum(n for _, n in merged[1:]) / max(
            merged[-1][0] - merged[0][0], 1e-9
        )
    elif merged:
        # a single completed batch has no steady window: report the
        # overall rate from the arrival anchor instead of a
        # divide-by-epsilon headline (review-caught)
        steady = scheduled / max(merged[0][0] - start_at, 1e-3)
    else:
        steady = 0.0
    lats = sorted(x for r in results for x in r["latencies"])
    p99 = lats[int(len(lats) * 0.99)] if lats else 0.0
    return {
        "replicas": n_replicas,
        "kind": kind,
        "tier": "device",
        "mesh_slice_devices": total_devices // max(n_replicas, 1),
        "hub": "grpc" if n_replicas > 1 else "none",
        "pods": n_pods,
        "nodes": n_nodes,
        "arrival_rate_pods_per_sec": rate,
        "scheduled": scheduled,
        "unschedulable": sum(r["unschedulable"] for r in results),
        "fleet_pods_per_sec": round(steady, 1),
        "fleet_p99_pod_latency_s": round(p99, 4),
        "wall_s": round(
            (merged[-1][0] - start_at) if merged else 0.0, 3
        ),
    }


def ladder8_fleet(n_replicas: int = 4) -> dict:
    """#8: fleet A/B — 1-replica vs N-replica sustained throughput at
    the same arrival rate on the same cluster, every replica its own
    OS process. DEVICE-TIER arms (ISSUE 11): the A arm is one process
    streaming against the whole (virtual) device set; the B arm is N
    processes, each ring-shard-scoped, pinned to an EXCLUSIVE 1/N
    mesh slice, stream-dispatching (PR 10) and sharing one occupancy
    hub over localhost gRPC — fenced CAS admits, stage/commit rows,
    and handoff polls all pay the real wire. Arrival rate = ladder
    #6's plain sustained rate, so the two ladders' numbers compose:
    the fleet multiplier applies to the same arrival regime the
    streaming dispatcher is gated on. The acceptance bar (ISSUE 11)
    is fleet_pods_per_sec >= 1.5x the 1-replica device arm."""
    # ladder #6 plain-shape arrival rate (ladder_sustained's shapes
    # table); nodes sized so each replica's shard still outweighs its
    # batch
    shape = dict(n_nodes=1_024, n_pods=16_000, rate=20_000.0)
    single = _fleet_sustained(1, **shape)
    fleet = _fleet_sustained(n_replicas, **shape)
    speedup = round(
        fleet["fleet_pods_per_sec"]
        / max(single["fleet_pods_per_sec"], 1e-9),
        3,
    )
    return {
        "config": (
            f"open-loop sustained arrival at ladder #6 rates, 1 "
            f"process x full device set vs {n_replicas} processes x "
            "exclusive 1/N mesh slices, every replica streaming "
            "(run_streaming) against its shard with ONE gRPC "
            "occupancy hub on localhost"
        ),
        "single": single,
        "fleet": fleet,
        "fleet_pods_per_sec": fleet["fleet_pods_per_sec"],
        "fleet_speedup": speedup,
    }


def ladder9_degraded() -> dict:
    """#9: degraded-mode A/B (kubernetes_tpu/resilience) — sustained
    pods/s at the TOP ladder tier vs the same workload pinned to the
    pure-host serial-greedy rung (ResilienceConfig.force_tier="host"),
    so the cost of full degradation is a measured number, not a guess.
    The host rung is the fallback ladder's floor: what the scheduler
    still delivers when every accelerator tier's breaker is open. The
    shape is kept small — the host rung is O(pods x nodes x plugins)
    Python per batch, and the point is the RATIO, not the absolute."""
    from kubernetes_tpu.resilience import ResilienceConfig

    shape = dict(
        kind="plain", n_nodes=200, n_pods=1_000, rate=8_000.0,
        batch=256, group=64, split=1,
    )
    top = _sustained_shape(mode="pipelined", **shape)
    host = _sustained_shape(
        mode="pipelined",  # force_tier routes every batch through the
        # synchronous resilient cycle either way; keeping the flag
        # equal keeps the arrival/drive loop identical for the A/B
        resilience=ResilienceConfig(force_tier="host"),
        **shape,
    )
    degraded = host["sustained_pods_per_sec"]
    return {
        "config": (
            "open-loop sustained arrival, top ladder tier vs forced "
            "host-greedy tier (ResilienceConfig.force_tier='host'), "
            f"{shape['n_pods']} pods x {shape['n_nodes']} nodes"
        ),
        "top": top,
        "host": host,
        "degraded_pods_per_sec": degraded,
        "degradation_factor": round(
            top["sustained_pods_per_sec"] / max(degraded, 1e-9), 3
        ),
    }


def ladder1_basic() -> dict:
    """#1 via the scheduler_perf YAML-runner code path (SURVEY §4.5)."""
    from kubernetes_tpu.perf.runner import PerfRunner

    ops = [
        {"opcode": "createNodes", "count": 500},
        {"opcode": "createPods", "count": 500, "collectMetrics": True},
    ]
    runner = PerfRunner()
    # warmup on the same shapes, then best of 3 measured runs
    runner.run_workload("SchedulingBasic", "warmup", ops, {})
    best = None
    run_walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        res = runner.run_workload("SchedulingBasic", "500Nodes", ops, {})
        wall = time.perf_counter() - t0
        assert res.scheduled == 500, f"#1: {res.scheduled}/500 scheduled"
        run_walls.append(round(wall, 3))
        if best is None or wall < best[0]:
            best = (wall, res)
    wall, res = best
    thr = res.throughput_summary()
    return {
        "note": (
            "500 pods solve as ONE batch, so wall time is bounded below "
            "by a single dispatch+read round trip (the canary row) plus "
            "host pop/tensorize/bind — this "
            "row measures per-batch latency floor, not sustained "
            "throughput (ladders #2-#4 measure that)"
        ),
        "pods": 500,
        "nodes": 500,
        "pods_per_sec": round(res.measured_pods / res.measure_seconds, 1)
        if res.measure_seconds
        else None,
        "wall_s": round(wall, 3),
        "run_walls_s": run_walls,
        "device_solve_s": round(res.solve_seconds, 3),
        "throughput_summary": thr,
    }


def ladder5_north_star() -> dict:
    """50k x 10k single-shot rebalance: device solve time, steady state."""
    import numpy as np
    import jax.numpy as jnp

    from kubernetes_tpu.solver.single_shot import (
        SingleShotConfig,
        _single_shot_jit,
    )

    rng = np.random.default_rng(0)
    k, c, rc = 3, 8, 8
    alloc = np.zeros((k, NS_NODES), dtype=np.int64)
    alloc[0] = 16_000
    alloc[1] = 64 * 1024**3
    rc_req = np.zeros((rc, k), dtype=np.int64)
    rc_req[:, 0] = rng.integers(1, 9, rc) * 250
    rc_req[:, 1] = rng.integers(1, 5, rc) * 1024**3
    rc_static = (np.arange(rc) % c).astype(np.int32)
    rc_of = rng.integers(0, rc, NS_PODS).astype(np.int32)
    priority = rng.integers(0, 10, NS_PODS).astype(np.int32)
    cfg = SingleShotConfig()

    def fresh():
        return [
            jnp.asarray(x)
            for x in (
                alloc,
                np.zeros((k, NS_NODES), np.int64),
                np.zeros(NS_NODES, np.int32),
                np.full(NS_NODES, 110, np.int32),
                np.ones(NS_NODES, bool),
                np.ones((c, NS_NODES), bool),
                rc_req,
                rc_static,
                rc_of,
                priority,
                np.ones(NS_PODS, bool),
            )
        ]

    kw = dict(
        max_rounds=cfg.max_rounds, price_step=cfg.price_step, top_t=cfg.top_t
    )
    t0 = time.perf_counter()
    out = _single_shot_jit(*fresh(), **kw)
    out[0].block_until_ready()
    compile_s = time.perf_counter() - t0
    # best of 3
    solve_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = _single_shot_jit(*fresh(), **kw)
        out[0].block_until_ready()
        solve_s = min(solve_s, time.perf_counter() - t0)
    placed = int((np.asarray(out[0]) >= 0).sum())

    # heterogeneous variant (VERDICT r2 #6): 128 request classes x 32
    # static-plugin classes with random selector masks — the [RC, N] dedup
    # memory story at realistic class counts instead of 8 uniform classes
    rc_h, c_h = 128, 32
    rng_h = np.random.default_rng(1)
    static_mask_h = rng_h.random((c_h, NS_NODES)) < 0.6
    rc_req_h = np.zeros((rc_h, k), dtype=np.int64)
    rc_req_h[:, 0] = rng_h.integers(1, 17, rc_h) * 125
    rc_req_h[:, 1] = rng_h.integers(1, 9, rc_h) * (512 * 1024**2)
    rc_static_h = rng_h.integers(0, c_h, rc_h).astype(np.int32)
    rc_of_h = rng_h.integers(0, rc_h, NS_PODS).astype(np.int32)

    def fresh_h():
        return [
            jnp.asarray(x)
            for x in (
                alloc,
                np.zeros((k, NS_NODES), np.int64),
                np.zeros(NS_NODES, np.int32),
                np.full(NS_NODES, 110, np.int32),
                np.ones(NS_NODES, bool),
                static_mask_h,
                rc_req_h,
                rc_static_h,
                rc_of_h,
                priority,
                np.ones(NS_PODS, bool),
            )
        ]

    out_h = _single_shot_jit(*fresh_h(), **kw)
    out_h[0].block_until_ready()
    hetero_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out_h = _single_shot_jit(*fresh_h(), **kw)
        out_h[0].block_until_ready()
        hetero_s = min(hetero_s, time.perf_counter() - t0)
    placed_h = int((np.asarray(out_h[0]) >= 0).sum())

    exact = _north_star_exact()

    return {
        "pods": NS_PODS,
        "nodes": NS_NODES,
        "solve_s": round(solve_s, 4),
        "compile_s": round(compile_s, 2),
        "placed": placed,
        "pods_per_sec": round(placed / solve_s, 1),
        "vs_1s_target": round(NS_TARGET_S / solve_s, 2),
        "hetero_rc128_solve_s": round(hetero_s, 4),
        "hetero_rc128_placed": placed_h,
        "hetero_rc128_classes": rc_h,
        "solver": (
            "single_shot auction — documented divergences: not sequential "
            "parity, and scope is resources + static plugins only "
            "(ports/spread/interpod workloads route through the exact "
            "scan, which now meets the <1s target itself)"
        ),
        "quality_vs_exact": _quality_table(),
        **exact,
    }


def _quality_table() -> dict:
    """Auction placement quality vs the exact sequential solver on three
    pre-loaded workload shapes (VERDICT r3 #7): placed count, placed
    priority mass, and the snapshot-headroom objective (sum of the
    auction's base_score over chosen nodes — its own objective, so this
    bounds how much the exact solver's sequential-greedy placements give
    up against it, and vice versa). Scale is cut vs the headline run so
    the table costs seconds, not minutes."""
    import numpy as np

    from kubernetes_tpu.server.bulk import columnar_pod_batch
    from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
    from kubernetes_tpu.solver.single_shot import SingleShotSolver
    from kubernetes_tpu.tensorize.schema import ResourceVocab, pad_to

    n_nodes, n_pods = 2_048, 8_192
    vocab = ResourceVocab(("cpu", "memory", "ephemeral-storage"))
    npad = pad_to(n_nodes)
    rng = np.random.default_rng(7)

    def preloaded_nodes():
        alloc = np.zeros((3, npad), np.int64)
        alloc[0, :n_nodes] = 16_000
        alloc[1, :n_nodes] = 64 << 30
        used = np.zeros((3, npad), np.int64)
        # uneven pre-load: 0..8 resident pod-equivalents per node
        load = rng.integers(0, 9, n_nodes)
        used[0, :n_nodes] = load * 1_000
        used[1, :n_nodes] = load * (2 << 30)
        cnt = np.zeros(npad, np.int32)
        cnt[:n_nodes] = load
        return alloc, used, cnt

    def shape(name, rc, cpu_lo, cpu_hi, mem_choices):
        rc_cpu = rng.integers(cpu_lo, cpu_hi, rc) * 125
        rc_mem = rng.choice(mem_choices, rc)
        rc_of = np.sort(rng.integers(0, rc, n_pods))  # class-contiguous
        prio = rng.integers(0, 10, n_pods).astype(np.int32)
        # contiguous classes => a valid FIFO-within-priority queue order
        # for the exact scan AND its grouped fast path
        order = np.lexsort((rc_of, -prio))
        return name, rc_cpu, rc_mem, rc_of[order], prio[order]

    shapes = [
        shape("homog8_preloaded", 8, 8, 9, [2 << 30]),
        shape("hetero_rc128_preloaded", 128, 1, 17, [1 << 30, 2 << 30, 4 << 30]),
        shape("scarce_rc8", 8, 24, 33, [8 << 30]),  # demand > capacity
    ]
    table = {}
    for name, rc_cpu, rc_mem, rc_of, prio in shapes:
        alloc, used, cnt = preloaded_nodes()
        rc = len(rc_cpu)
        rc_req = np.zeros((rc, 3), np.int64)
        rc_req[:, 0] = rc_cpu
        rc_req[:, 1] = rc_mem

        def pod_batch():
            return columnar_pod_batch(
                rc_req[rc_of, 0].copy(), rc_req[rc_of, 1].copy(),
                prio.copy(), vocab,
            )

        # both solvers go through their PUBLIC entry points on the same
        # pre-loaded cluster and queue order — the quality table measures
        # the production code paths, not a hand-marshaled replica
        a_auction = SingleShotSolver().solve(
            _synthetic_node_batch(vocab, n_nodes, alloc, used, cnt),
            pod_batch(),
        )
        solver = ExactSolver(
            ExactSolverConfig(tie_break="random", group_size=256)
        )
        a_exact = solver.solve(
            _synthetic_node_batch(vocab, n_nodes, alloc, used, cnt),
            pod_batch(),
        )

        # snapshot-headroom objective (the auction's own): identical
        # formula for both assignment vectors
        alloc2 = alloc[:2, :].astype(np.float64)
        used2 = used[:2, :].astype(np.float64)
        frac = np.where(alloc2 > 0, (alloc2 - used2) / np.maximum(alloc2, 1), 0)
        base_score = (100.0 * (frac[0] + frac[1]) / 2.0).astype(np.int64)

        def stats(a):
            placed = a >= 0
            return {
                "placed": int(placed.sum()),
                "priority_mass": int(prio[placed].sum()),
                "objective": int(base_score[a[placed]].sum()),
            }

        sa, se = stats(a_auction), stats(a_exact)
        table[name] = {
            "auction": sa,
            "exact": se,
            "placed_ratio": round(sa["placed"] / max(se["placed"], 1), 4),
            "priority_mass_ratio": round(
                sa["priority_mass"] / max(se["priority_mass"], 1), 4
            ),
            "objective_ratio": round(
                sa["objective"] / max(se["objective"], 1), 4
            ),
        }
    return table



def _synthetic_node_batch(vocab, n_nodes, alloc, used=None, cnt=None):
    """One uniform synthetic NodeBatch builder for the bench workloads
    (shared by the exact north star and the quality table)."""
    import numpy as np

    from kubernetes_tpu.tensorize.schema import NodeBatch, pad_to

    npad = pad_to(n_nodes)
    live = np.arange(npad) < n_nodes
    used = np.zeros((3, npad), np.int64) if used is None else used.copy()
    cnt = np.zeros(npad, np.int32) if cnt is None else cnt.copy()
    return NodeBatch(
        vocab=vocab,
        names=[f"n{i}" for i in range(n_nodes)],
        num_nodes=n_nodes,
        padded=npad,
        allocatable=alloc.copy(),
        used=used,
        nonzero_used=used[:2].copy(),
        pod_count=cnt,
        max_pods=np.where(live, 110, 0).astype(np.int32),
        valid=live,
        schedulable=live.copy(),
    )


def _north_star_exact() -> dict:
    """The same 50k x 10k workload through the EXACT-parity grouped scan —
    the honest companion number: full sequential binding semantics at
    north-star scale (the auction's <1s rides a relaxed objective)."""
    import numpy as np

    from kubernetes_tpu.server.bulk import columnar_pod_batch
    from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
    from kubernetes_tpu.tensorize.schema import NodeBatch, ResourceVocab, pad_to

    vocab = ResourceVocab(("cpu", "memory", "ephemeral-storage"))
    npad = pad_to(NS_NODES)
    alloc = np.zeros((3, npad), dtype=np.int64)
    alloc[0, :NS_NODES] = 16_000
    alloc[1, :NS_NODES] = 64 << 30

    def fresh_batch():
        return _synthetic_node_batch(vocab, NS_NODES, alloc)

    cpu = np.full(NS_PODS, 1000, np.int64)
    mem = np.full(NS_PODS, 2 << 30, np.int64)
    pb = columnar_pod_batch(cpu, mem, None, vocab)
    # round-4 cost model (scripts/sweep_group.py): solve wall is dominated
    # by per-call transfer costs and nearly flat across the swept group
    # sizes; group=1024 measured best after the single-packed-download
    # rework
    solver = ExactSolver(ExactSolverConfig(tie_break="random", group_size=1024))
    solver.solve(fresh_batch(), pb)  # compile + warm the session shapes
    exact_s = float("inf")
    # min-of-5: this row's <1 s target leaves the least headroom
    for _ in range(5):
        # one solve's histogram, not the warmup+reps lifetime total
        solver.dispatch_counts.clear()
        t0 = time.perf_counter()
        a = solver.solve(fresh_batch(), pb)
        exact_s = min(exact_s, time.perf_counter() - t0)
    placed = int((a >= 0).sum())
    assert placed == NS_PODS, f"exact north star placed {placed}/{NS_PODS}"
    # validity gates at full scale (a number only counts if the bindings
    # are right): every pick lands on a live node, and no node exceeds
    # cpu / memory / pod-count capacity under the actual request vectors
    # (weighted bincounts, so the gates survive heterogeneous workloads)
    assert int(a.min()) >= 0 and int(a.max()) < NS_NODES
    assert int(np.bincount(a, minlength=NS_NODES).max()) <= 110
    assert np.bincount(a, weights=cpu.astype(np.float64)).max() <= 16_000
    assert np.bincount(a, weights=mem.astype(np.float64)).max() <= 64 << 30
    # SEQUENTIAL-PARITY replay (the oracle-replay gate at full scale):
    # with identical pods on identical nodes, LeastAllocated AND
    # BalancedAllocation are strictly decreasing in a node's pod count,
    # so the reference tie set at every step is exactly the
    # minimum-count nodes — each of the 51,200 placements must land on
    # a node at the then-minimum count, in emitted order
    # every placement consumes one min-count slot, so the running minimum
    # is simply k // NS_NODES — no carried bookkeeping to desynchronize
    counts = np.zeros(NS_NODES, dtype=np.int64)
    for k, node in enumerate(a):
        assert counts[node] == k // NS_NODES, (
            f"step {k}: node at count {counts[node]}, tie set at "
            f"{k // NS_NODES} — outside the reference tie set"
        )
        counts[node] += 1
    return {
        "exact_parity_solve_s": round(exact_s, 2),
        "exact_parity_pods_per_sec": round(placed / exact_s, 1),
        "exact_parity_vs_1s_target": round(NS_TARGET_S / exact_s, 2),
        "exact_parity_dispatch": "; ".join(
            f"{k}={v}" for k, v in sorted(solver.dispatch_counts.items())
        ),
        "exact_parity_replay": (
            f"all {NS_PODS} placements verified inside the sequential "
            "reference tie set (min-count replay) + capacity gates"
        ),
    }


def ladder10_rebalance_loop() -> dict:
    """#10: the continuous rebalancer (kubernetes_tpu/rebalance) closing
    a seeded fragmented cluster at north-star scale — the A/B is packed
    utilization before vs after the loop runs to convergence.

    The cluster: the 51.2k uniform pods (1 cpu / 2Gi) scattered over the
    10.24k nodes with per-node loads drawn 1..10 (aggregate ~34% packed
    utilization on the cpu-dominant axis against the 70% packing bar,
    bin-packing lower bound ~3.2k nodes). Each cycle runs the REAL
    production pieces — ``detector.detect``, the runtime's drain-source
    gather discipline (emptiest in-use nodes first; the fullest node and
    nodes at the bar are never drained), ``planner.plan_moves`` (the
    pack-objective auction against live load with the drain sources
    masked) and ``planner.select_moves`` (churn budget / strict-gain /
    joint-feasibility bounding) — then applies the selected moves to the
    node tensors, standing in for the evict -> requeue -> re-bind
    migration path that the ``fragmentation`` sim profile and the CI
    smoke prove end to end (PDB gate included) at full fidelity."""
    import numpy as np

    from kubernetes_tpu.api.wrappers import MakePod
    from kubernetes_tpu.rebalance.detector import detect
    from kubernetes_tpu.rebalance.planner import plan_moves, select_moves
    from kubernetes_tpu.tensorize.schema import ResourceVocab, pad_to

    BUDGET = 2_048  # churn budget: evictions per cycle
    BAR = 0.7  # min_packing — the detector's fragmentation threshold
    MAX_CYCLES = 24  # "bounded number of cycles" gate

    vocab = ResourceVocab(("cpu", "memory", "ephemeral-storage"))
    npad = pad_to(NS_NODES)
    names = [f"n{i}" for i in range(NS_NODES)]
    alloc = np.zeros((3, npad), np.int64)
    alloc[0, :NS_NODES] = 16_000
    alloc[1, :NS_NODES] = 64 << 30

    rng = np.random.default_rng(10)
    loads = rng.integers(1, 11, NS_NODES)
    assert int(loads.sum()) >= NS_PODS
    pod_node = np.repeat(np.arange(NS_NODES), loads)[:NS_PODS].copy()
    prio = rng.integers(0, 10, NS_PODS)
    tmpl = MakePod().name("t").req({"cpu": "1", "memory": "2Gi"}).obj()
    req = np.asarray(vocab.vectorize(tmpl.resource_request()), np.int64)

    used = np.zeros((3, npad), np.int64)
    cnt = np.zeros(npad, np.int32)
    node_counts = np.bincount(pod_node, minlength=NS_NODES)
    cnt[:NS_NODES] = node_counts
    used[:, :NS_NODES] = req[:, None] * node_counts[None, :]
    node_pods: list[list[int]] = [[] for _ in range(NS_NODES)]
    for i, nslot in enumerate(pod_node):
        node_pods[nslot].append(int(i))

    pod_cache: dict[int, object] = {}
    key2idx: dict[str, int] = {}

    def pod_obj(i: int):
        p = pod_cache.get(i)
        if p is None:
            p = (
                MakePod()
                .name(f"pod-{i:06}")
                .priority(int(prio[i]))
                .start_time(float(i))
                .req({"cpu": "1", "memory": "2Gi"})
                .obj()
            )
            pod_cache[i] = p
            key2idx[p.key] = i
        return p

    def fill_pct() -> np.ndarray:
        # detector.packing_score, vectorized: integer dominant-resource
        # fill in percent points
        cpu_f = np.where(alloc[0] > 0, used[0] / np.maximum(alloc[0], 1), 0)
        mem_f = np.where(alloc[1] > 0, used[1] / np.maximum(alloc[1], 1), 0)
        return (100.0 * np.maximum(np.minimum(cpu_f, 1.0), np.minimum(mem_f, 1.0))).astype(np.int64)

    def gather():
        """The runtime's ``_gather`` discipline over the tensors."""
        fill = fill_pct()
        in_use = np.flatnonzero(cnt[:NS_NODES] > 0)
        order = in_use[np.lexsort((in_use, fill[in_use]))]
        bar_pts = int(BAR * 100)
        movable: list[tuple[object, int]] = []
        drains: set[int] = set()
        fixed_used = used.copy()
        fixed_cnt = cnt.copy()
        for slot in order[:-1]:  # never drain the fullest in-use node
            slot = int(slot)
            if len(movable) >= BUDGET or fill[slot] >= bar_pts:
                break
            take = sorted(node_pods[slot], key=lambda i: (prio[i], -i))
            take = take[: BUDGET - len(movable)]
            drains.add(slot)
            for i in take:
                movable.append((pod_obj(i), slot))
                fixed_used[:, slot] = np.maximum(fixed_used[:, slot] - req, 0)
                fixed_cnt[slot] = max(int(fixed_cnt[slot]) - 1, 0)
        return movable, fixed_used, fixed_cnt, frozenset(drains)

    def batch_now():
        return _synthetic_node_batch(vocab, NS_NODES, alloc, used, cnt)

    before = detect(batch_now(), min_packing=BAR)
    plan_walls: list[float] = []
    cycle_evictions: list[int] = []
    for cycle in range(MAX_CYCLES):
        batch = batch_now()
        report = detect(batch, min_packing=BAR)
        if not report.fragmented:
            break
        movable, fixed_used, fixed_cnt, drains = gather()
        if not movable:
            break
        if cycle == 0:
            # compile warm-up: the auction is deterministic, so the
            # discarded result equals the measured one
            plan_moves(batch, movable, fixed_used, fixed_cnt, drains)
        t0 = time.perf_counter()
        raw = plan_moves(batch, movable, fixed_used, fixed_cnt, drains)
        plan_walls.append(time.perf_counter() - t0)
        plan = select_moves(batch, names, raw, [], budget=BUDGET, min_gain=1)
        if not plan.moves:
            break
        assert len(plan.moves) <= BUDGET, "churn budget exceeded"
        cycle_evictions.append(len(plan.moves))
        for mv in plan.moves:
            i = key2idx[mv.pod.key]
            src, dst = mv.source_slot, mv.target_slot
            used[:, src] -= req
            used[:, dst] += req
            cnt[src] -= 1
            cnt[dst] += 1
            node_pods[src].remove(i)
            node_pods[dst].append(i)
            pod_node[i] = dst
    after = detect(batch_now(), min_packing=BAR)

    # validity gates: the A/B only counts if the end state is real —
    # every pod still placed exactly once and no node over capacity
    assert int(cnt[:NS_NODES].sum()) == NS_PODS
    assert np.all(used[0, :NS_NODES] <= alloc[0, :NS_NODES])
    assert np.all(used[1, :NS_NODES] <= alloc[1, :NS_NODES])
    assert not after.fragmented, (
        f"rebalance loop did not converge within {MAX_CYCLES} cycles "
        f"(packed {after.packed_utilization:.3f})"
    )
    gain = after.packed_utilization - before.packed_utilization
    assert gain > 0, "rebalance loop did not improve packed utilization"
    # median over the (post-warm-up) cycles: the steady-state figure —
    # min would let one lucky cycle satisfy the <1 s gate
    solve_s = float(np.median(plan_walls))
    return {
        "pods": NS_PODS,
        "nodes": NS_NODES,
        "churn_budget": BUDGET,
        "min_packing": BAR,
        "packed_utilization_before": round(before.packed_utilization, 4),
        "packed_utilization_after": round(after.packed_utilization, 4),
        "rebalance_utilization_gain": round(gain, 4),
        "nodes_in_use_before": before.nodes_in_use,
        "nodes_in_use_after": after.nodes_in_use,
        "ideal_nodes": before.ideal_nodes,
        "stranded_fraction_before": round(before.stranded_fraction, 4),
        "stranded_fraction_after": round(after.stranded_fraction, 4),
        "cycles": len(cycle_evictions),
        "max_cycles": MAX_CYCLES,
        "evictions_total": sum(cycle_evictions),
        "max_cycle_evictions": max(cycle_evictions, default=0),
        "over_budget_cycles": 0,  # asserted above, every cycle
        "rebalance_plan_solve_s": round(solve_s, 4),
        "plan_solve_max_s": round(max(plan_walls), 4),
        "vs_1s_target": round(NS_TARGET_S / solve_s, 2),
    }


BD_PODS = 512_000
BD_NODES = 102_400


def _backlog_arm(
    n_nodes: int,
    n_pods: int,
    chunk: int,
    mesh_devices: int,
    kind: str = "spread",
    group: int = 512,
    tuning=None,  # TuningConfig: ladder #12's tuned drain arm
) -> dict:
    """One backlog-drain arm: a ``n_pods`` backlog queued against
    ``n_nodes`` nodes, drained end to end through
    ``Scheduler.drain_backlog`` — the HBM-budget-planned, chunk-aligned
    streaming path with cross-batch occupancy chaining (ISSUE 12).
    ``kind='spread'`` keeps a HARD shape in the carry so the chain is
    measured on the occupancy path, not the plain-fit fast case.

    One warmup drain (chunk-sized backlog, same node/pod buckets)
    compiles every executable; the measured pass is a single full
    drain — at 512k pods the drain IS the steady state, so best-of-N
    would only re-pay the 100k-node cluster build."""
    import numpy as np

    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    cs = ClusterState()
    for i in range(n_nodes):
        cs.create_node(_mk_node(i))
    sched = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=chunk,
            mesh_devices=mesh_devices,
            solver=ExactSolverConfig(tie_break="random", group_size=group),
            tuning=tuning,
        ),
    )
    # warmup: drain a chunk-sized backlog on the SAME cluster (same
    # node padding bucket — a throwaway small cluster would compile the
    # wrong shapes), then delete the placed pods so the measured drain
    # starts from an empty cluster
    for i in range(chunk):
        cs.create_pod(_mk_pod(i, kind))
    sched.drain_backlog(chunk_pods=chunk)
    for p in list(cs.list_pods()):
        cs.delete_pod(p.namespace, p.name)

    t0 = time.perf_counter()
    for i in range(n_pods):
        cs.create_pod(_mk_pod(i, kind))
    enqueue_s = time.perf_counter() - t0

    report = sched.drain_backlog(chunk_pods=chunk)
    assert report.drained == n_pods, (
        f"backlog drain placed {report.drained}/{n_pods}"
    )
    # streaming chain engagement: the drain must measure the resident-
    # carry path, not a silent per-chunk drain-and-retensorize fallback
    assert report.chain_fraction >= 0.5, (
        f"stream chain engaged on only {report.chain_fraction:.0%} of "
        "chunks — the drain fell back to per-chunk retensorize"
    )
    # end-state validity (the ladder-#10 convention): every pod placed
    # at most once with no node overcommitted — weighted bincounts over
    # the actual request vectors
    pods = [p for p in cs.list_pods() if p.name.startswith("pod-")]
    assert len(pods) == n_pods
    nodes_list = cs.list_nodes()
    slot = {n.name: i for i, n in enumerate(nodes_list)}
    a = np.fromiter(
        (slot[p.node_name] for p in pods), dtype=np.int64, count=n_pods
    )
    cnt = np.bincount(a, minlength=n_nodes)
    assert int(cnt.max()) <= 110, "pod-count overcommit"
    assert np.bincount(a, weights=np.full(n_pods, 250.0)).max() <= 16_000
    assert (
        np.bincount(a, weights=np.full(n_pods, 512.0 * 1024**2)).max()
        <= 64 * 1024**3
    )
    if kind == "spread":
        zone_of = np.fromiter(
            (
                int(n.labels["topology.kubernetes.io/zone"][1:])
                for n in nodes_list
            ),
            dtype=np.int64,
            count=len(nodes_list),
        )
        zones = np.bincount(zone_of[a], minlength=3)
        assert int(zones.max() - zones.min()) <= 1, (
            f"zone skew violated at drain scale: {zones.tolist()}"
        )
    return {
        "mesh_devices": mesh_devices,
        "pods": n_pods,
        "nodes": n_nodes,
        "kind": kind,
        "chunk_pods": report.chunk_pods,
        "chunks": report.chunks,
        "budget_splits": report.budget_splits,
        "budget_bytes": report.budget_bytes,
        "estimated_per_device_bytes": report.estimated_per_device_bytes,
        "estimated_h2d_bytes": report.estimated_h2d_bytes,
        "measured_h2d_bytes": report.measured_h2d_bytes,
        "h2d_model_ratio": round(
            report.measured_h2d_bytes
            / max(report.estimated_h2d_bytes, 1),
            3,
        ),
        "backlog_drain_seconds": round(report.drain_seconds, 3),
        "backlog_drain_pods_per_sec": round(report.pods_per_sec, 1),
        "sustained_p99_pod_latency_s": round(
            report.p99_e2e_latency_s, 4
        ),
        "median_chunk_solve_s": round(report.median_chunk_solve_s, 4),
        "stream_chained_batches": report.stream_chained_batches,
        "chain_fraction": round(report.chain_fraction, 4),
        "enqueue_s": round(enqueue_s, 3),
        "dispatch": _dispatch_label(sched),
        "final_chunk_pods": report.final_chunk_pods or report.chunk_pods,
        "tuning": (
            sched.tuner.summary() if sched.tuner is not None else None
        ),
    }


def _backlog_auction(n_nodes: int, n_pods: int) -> dict:
    """The single-shot auction (scarcity repair included) at the 10x
    shape — proves the whole-problem-resident quality path holds at
    512k x 102k, not just the chunked exact drain."""
    import numpy as np
    import jax.numpy as jnp

    from kubernetes_tpu.solver.single_shot import (
        SingleShotConfig,
        _single_shot_jit,
    )

    rng = np.random.default_rng(12)
    k, c, rc = 3, 8, 8
    alloc = np.zeros((k, n_nodes), dtype=np.int64)
    alloc[0] = 16_000
    alloc[1] = 64 * 1024**3
    rc_req = np.zeros((rc, k), dtype=np.int64)
    rc_req[:, 0] = rng.integers(1, 9, rc) * 250
    rc_req[:, 1] = rng.integers(1, 5, rc) * 1024**3
    rc_static = (np.arange(rc) % c).astype(np.int32)
    rc_of = rng.integers(0, rc, n_pods).astype(np.int32)
    priority = rng.integers(0, 10, n_pods).astype(np.int32)
    cfg = SingleShotConfig()
    kw = dict(
        max_rounds=cfg.max_rounds,
        price_step=cfg.price_step,
        top_t=cfg.top_t,
        repair_rounds=cfg.repair_rounds,  # scarcity repair ON at scale
    )

    def fresh():
        return [
            jnp.asarray(x)
            for x in (
                alloc,
                np.zeros((k, n_nodes), np.int64),
                np.zeros(n_nodes, np.int32),
                np.full(n_nodes, 110, np.int32),
                np.ones(n_nodes, bool),
                np.ones((c, n_nodes), bool),
                rc_req,
                rc_static,
                rc_of,
                priority,
                np.ones(n_pods, bool),
            )
        ]

    out = _single_shot_jit(*fresh(), **kw)
    out[0].block_until_ready()  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = _single_shot_jit(*fresh(), **kw)
        out[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    placed = int((np.asarray(out[0]) >= 0).sum())
    return {
        "auction_pods": n_pods,
        "auction_nodes": n_nodes,
        "auction_solve_s": round(best, 3),
        "auction_placed": placed,
        "auction_placed_ratio": round(placed / n_pods, 4),
        "auction_repair_rounds": cfg.repair_rounds,
    }


def ladder11_backlog_drain(
    n_nodes: int = BD_NODES,
    n_pods: int = BD_PODS,
    chunk: int = 16_384,
) -> dict:
    """#11: 10x the proven scale — a 512k-pod backlog drained end to
    end against 102,400 nodes through ``Scheduler.drain_backlog``
    (ISSUE 12): the HBM-budget-planned chunked streaming path, with
    cross-batch occupancy chaining keeping the hard-shape carry
    device-resident across the whole drain. A/B: the exact same drain
    on 1 device vs the full node-axis mesh; the auction (scarcity
    repair on) runs once at the same shape. Reports the MEDIAN
    drain-chunk solve time (the ladder-#10 convention) and asserts
    end-state validity + chain engagement in both arms."""
    import jax

    one = _backlog_arm(n_nodes, n_pods, chunk, mesh_devices=1)
    n_dev = len(jax.devices())
    if n_dev >= 2:
        mesh = _backlog_arm(n_nodes, n_pods, chunk, mesh_devices=0)
        headline = mesh
        speedup = round(
            mesh["backlog_drain_pods_per_sec"]
            / max(one["backlog_drain_pods_per_sec"], 1e-9),
            3,
        )
    else:
        mesh = {
            "skipped": (
                f"only {n_dev} device visible; the mesh arm needs a "
                "multi-device node-axis mesh"
            )
        }
        headline = one
        speedup = None
    return {
        "config": (
            f"{n_pods} queued pods drained against {n_nodes} nodes "
            "through drain_backlog: HBM-budget-planned chunks through "
            "the streaming ring, cross-batch occupancy chaining on a "
            "hard (zone-spread) shape, 1-device vs full-mesh A/B; "
            "single-shot auction with scarcity repair at the same "
            "shape"
        ),
        "one_device": one,
        "mesh": mesh,
        "backlog_drain_pods_per_sec": headline[
            "backlog_drain_pods_per_sec"
        ],
        "backlog_drain_seconds": headline["backlog_drain_seconds"],
        "backlog_p99_pod_latency_s": headline[
            "sustained_p99_pod_latency_s"
        ],
        "backlog_mesh_speedup": speedup,
        **_backlog_auction(n_nodes, n_pods),
    }


def ladder12_autotune() -> dict:
    """#12: closed-loop auto-tuning A/B (ISSUE 13) — the SAME workload
    run with static hot-path knobs (the shipped defaults) and with the
    tuning runtime governing them (kubernetes_tpu/tuning), on the two
    shapes whose knobs it owns:

    - sustained streaming arrival (stream_depth + pipeline_split): the
      tuned arm starts at the static arm's exact config and
      hill-climbs with hysteresis + revert-on-regression, so "tuned >=
      static" is structural — a probe that regresses is rolled back
      within one evaluation window;
    - backlog drain (drain chunk size under the HBM budget guardrail):
      every tuner-proposed chunk passes solver/budget.py's per-device
      assertion BEFORE it is applied — the arm asserts ZERO guardrail
      breaches (BudgetExceeded never raised by a tuner-proposed
      shape).

    Hoists tuned_pods_per_sec + tuning_convergence_batches to the JSON
    top level for the driver capture."""
    from kubernetes_tpu.tuning.runtime import TuningConfig

    # controller windows sized so convergence is GUARANTEED inside the
    # measured run: the probe budget bounds an episode at
    # eval_batches * (2 * max_probes + 4) ≈ 36 batches, under the ~47
    # batches the sustained arm pops — so tuning_convergence_batches is
    # a real number, not a still-probing None. Hysteresis 0.15 makes a
    # wall-clock-noise accept rare (a regression must be real)
    def tuned_cfg():
        return TuningConfig(
            eval_batches=3, settle_after=1, hysteresis=0.15,
            max_probes=4,
        )

    # BOTH arms run the SHIPPED defaults (split=0 = the adaptive
    # CounterWindow rule, stream_depth=4): the A/B isolates the closed
    # loop, not a bench-pinned split override neither production
    # default uses. batch=256 over 12k pods gives the controllers
    # enough evaluation windows to settle INSIDE the measured run, so
    # tuning_convergence_batches is a real number, not a still-probing
    # None.
    sus_static = _sustained_shape(
        "plain", 500, 12_000, 20_000.0, mode="streaming", split=0,
        batch=256,
    )
    sus_tuned = _sustained_shape(
        "plain", 500, 12_000, 20_000.0, mode="streaming", split=0,
        batch=256, tuning=tuned_cfg(),
    )
    # best-of-2 per drain arm (symmetric): a full drain is one wall
    # measurement, and two identical runs differ by ±5% on the dev
    # box — best-of keeps the A/B about the config, not the scheduler
    # jitter (the ladder-#7 rep convention)
    def drain_arm(tuning):
        return max(
            (
                _backlog_arm(
                    10_240, 51_200, 4_096, mesh_devices=1,
                    kind="plain", group=512, tuning=tuning,
                )
                for _ in range(2)
            ),
            key=lambda a: a["backlog_drain_pods_per_sec"],
        )

    drain_static = drain_arm(None)
    drain_tuned = drain_arm(tuned_cfg())
    sus_ratio = sus_tuned["sustained_pods_per_sec"] / max(
        sus_static["sustained_pods_per_sec"], 1e-9
    )
    drain_ratio = drain_tuned["backlog_drain_pods_per_sec"] / max(
        drain_static["backlog_drain_pods_per_sec"], 1e-9
    )
    for arm in (sus_tuned, drain_tuned):
        t = arm["tuning"]
        assert t is not None and t["guardrail_breaches"] == 0, (
            f"guardrail breach in the tuned arm: {t}"
        )
    # no-regression gate: revert-on-regression makes the tuned arm's
    # floor the static config; a small tolerance absorbs dev-box
    # wall-clock noise between two independent runs
    assert sus_ratio >= 0.95, (
        f"tuned sustained arm regressed: {sus_ratio:.3f}x static"
    )
    assert drain_ratio >= 0.95, (
        f"tuned drain arm regressed: {drain_ratio:.3f}x static"
    )
    # convergence: the sustained arm's settle point; the drain arm's as
    # the fallback (both are real runs of the same controller config)
    conv = (
        sus_tuned["tuning"]["convergence_batches"]
        or drain_tuned["tuning"]["convergence_batches"]
    )
    return {
        "config": (
            "static-vs-tuned A/B: sustained streaming arrival "
            "(stream_depth + pipeline_split governed) and backlog "
            "drain (chunk size under the HBM budget guardrail); tuned "
            "arms start at the static arms' exact config, hill-climb "
            "with hysteresis, revert on regression, and journal every "
            "move through scheduler_tuning_*"
        ),
        "sustained": {"static": sus_static, "tuned": sus_tuned},
        "drain": {"static": drain_static, "tuned": drain_tuned},
        "tuned_pods_per_sec": sus_tuned["sustained_pods_per_sec"],
        "tuned_vs_static_sustained": round(sus_ratio, 3),
        "tuned_drain_pods_per_sec": drain_tuned[
            "backlog_drain_pods_per_sec"
        ],
        "tuned_vs_static_drain": round(drain_ratio, 3),
        "tuning_convergence_batches": conv,
        "tuned_knobs": sus_tuned["tuning"]["knobs"],
        "tuned_drain_knobs": drain_tuned["tuning"]["knobs"],
        "guardrail_breaches": 0,  # asserted above for both tuned arms
    }


def ladder13_obs_overhead() -> dict:
    """#13: observability-overhead A/B (ISSUE 14) — the SAME sustained
    streaming workload with the FULL obs layer on (spans + bounded
    flight recorder + per-pod decision journal + live SLO engine) vs
    everything off, proving the whole fleet-wide tracing/SLO tentpole
    costs <= 5% sustained throughput. Best-of-3 per arm (the ladder-#7
    rep convention, widened: a 5% bound is inside two independent
    runs' wall-clock noise on the dev box, best-of is what makes the
    A/B about the config).

    Both arms run as a SINGLE-REPLICA fleet over an in-process
    occupancy hub, so the obs-on arm's journal-segment shipping to the
    hub's aggregation surface (the cross-replica explain source) is
    INSIDE the measured window — the overhead number covers tracing +
    SLO + journal shipping, not just the local layer.

    Hoists slo_p99_pod_latency_s (the SLO engine's own live p99 from
    the obs-on arm — the 'are we meeting SLOs right now' number
    measured while the bench ran) and obs_overhead_fraction to the
    JSON top level.

    ISSUE 18 refresh: a THIRD arm re-measures the same workload with
    the full flight-telemetry loop on top of the obs layer —
    continuous per-stage profiler + anomaly sentinel (+ the bundle
    capturer armed, writing nothing) — and the <= 5% budget is
    asserted against THAT arm: the always-on telemetry claim is only
    honest if the whole stack fits the budget, not just the tracing
    half. Also hoists profiler_overhead_fraction (the telemetry arm's
    marginal cost over the obs arm) and anomaly_detection_lag_batches
    (how many batches a production-window sentinel needs to flag a
    50% sustained-throughput collapse — measured offline, where the
    regression is scripted rather than hoped for)."""
    from kubernetes_tpu.fleet import FleetConfig, OccupancyExchange
    from kubernetes_tpu.obs import ObsConfig, SentinelConfig, SloConfig

    def obs_on_cfg():
        return ObsConfig(
            spans=True,
            journal=True,
            # serve-mode bounds: a long-lived process would configure
            # exactly this (the unbounded sim retention is a sim
            # contract, not the production shape)
            journal_capacity=65_536,
            slo=SloConfig(latency_objective_s=30.0),
        )

    def telemetry_cfg():
        # serve --telemetry on top of --obs --slo: profiler + sentinel
        # at production window sizes; the capture ring is armed (the
        # sentinel implies it) but no bundle_dir, so a capture would
        # count without touching disk — exactly the always-on shape
        cfg = obs_on_cfg()
        cfg.profile = True
        cfg.sentinel = SentinelConfig()
        return cfg

    shape = dict(
        kind="plain", n_nodes=500, n_pods=12_000, rate=20_000.0,
        mode="streaming", split=0, batch=256,
    )

    hubs: list = []

    def fleet_cfg():
        # one fresh single-replica fleet + private in-process hub per
        # scheduler build (warmup and measured runs must not share
        # state); single-replica degenerates gracefully — ownership-
        # only admission, no peer rows — and BOTH arms pay it, so the
        # A/B still isolates the obs layer + its hub journal shipping
        hub = OccupancyExchange()
        hubs.append(hub)
        return FleetConfig(replica="r0", replicas=("r0",), exchange=hub)

    def arm(obs_cfg):
        return max(
            (
                _sustained_shape(
                    shape["kind"], shape["n_nodes"], shape["n_pods"],
                    shape["rate"], mode=shape["mode"],
                    split=shape["split"], batch=shape["batch"],
                    obs=obs_cfg, fleet=fleet_cfg,
                )
                for _ in range(3)
            ),
            key=lambda a: a["sustained_pods_per_sec"],
        )

    off = arm(None)
    on = arm(obs_on_cfg())
    tele = arm(telemetry_cfg())
    shipped = sum(len(h.journal_lines()) for h in hubs)
    assert shipped > 0, (
        "the obs-on arm never shipped a journal segment to the hub"
    )
    ratio = on["sustained_pods_per_sec"] / max(
        off["sustained_pods_per_sec"], 1e-9
    )
    overhead = max(1.0 - ratio, 0.0)
    assert on["slo"] is not None, "the obs-on arm must run the SLO engine"
    assert on["obs_volume"]["journal_records"] > 0
    assert overhead <= 0.05, (
        f"observability overhead {overhead:.3f} exceeds the 5% budget "
        f"(on={on['sustained_pods_per_sec']}, "
        f"off={off['sustained_pods_per_sec']} pods/s)"
    )
    # the telemetry arm: full loop on, measured against the SAME off
    # baseline — the <= 5% budget now covers profiler + sentinel too
    tele_ratio = tele["sustained_pods_per_sec"] / max(
        off["sustained_pods_per_sec"], 1e-9
    )
    telemetry_overhead = max(1.0 - tele_ratio, 0.0)
    assert telemetry_overhead <= 0.05, (
        f"flight-telemetry overhead {telemetry_overhead:.3f} exceeds "
        f"the 5% budget (telemetry={tele['sustained_pods_per_sec']}, "
        f"off={off['sustained_pods_per_sec']} pods/s)"
    )
    tsnap = tele["telemetry"]
    assert tsnap is not None and tsnap["profile"]["batches"] > 0, (
        "the telemetry arm's profiler never closed a batch ledger entry"
    )
    # the profiler's marginal cost over the plain obs arm (clamped:
    # best-of-3 noise can leave the richer arm faster)
    profiler_overhead = max(
        1.0
        - tele["sustained_pods_per_sec"]
        / max(on["sustained_pods_per_sec"], 1e-9),
        0.0,
    )
    lag_batches = _anomaly_detection_lag_batches()
    return {
        "config": (
            "obs-overhead A/B on the sustained streaming shape "
            "(12k pods x 500 nodes @ 20k/s, batch 256): spans + "
            "journal + flight recorder + live SLO engine ON vs "
            "everything OFF, best-of-3 per arm, BOTH arms a single-"
            "replica fleet over an in-process occupancy hub so the "
            "on-arm's journal-segment shipping to the hub aggregation "
            "surface is inside the measured window; asserts the whole "
            "layer costs <= 5% sustained throughput"
        ),
        "off": off,
        "on": on,
        "telemetry": tele,
        "obs_overhead_fraction": round(overhead, 4),
        "obs_on_pods_per_sec": on["sustained_pods_per_sec"],
        "obs_off_pods_per_sec": off["sustained_pods_per_sec"],
        "telemetry_overhead_fraction": round(telemetry_overhead, 4),
        "telemetry_pods_per_sec": tele["sustained_pods_per_sec"],
        "profiler_overhead_fraction": round(profiler_overhead, 4),
        "anomaly_detection_lag_batches": lag_batches,
        "profiled_batches": tsnap["profile"]["batches"],
        "slo_p99_pod_latency_s": on["slo"]["p99_pod_latency_s"],
        "slo_healthy": on["slo"]["healthy"],
        "journal_records": on["obs_volume"]["journal_records"],
        "spans": on["obs_volume"]["spans"],
        "hub_journal_lines_shipped": shipped,
    }


def _anomaly_detection_lag_batches() -> int:
    """How many batches the PRODUCTION-window sentinel needs to flag a
    50% sustained-throughput collapse, measured offline: feed a scripted
    healthy baseline through an :class:`AnomalySentinel` at default
    (serve-sized) windows, collapse pods/s by half, and count windows
    until the spike rule fires. Offline because the regression must be
    scripted, not hoped for — the live bench arms are healthy by
    design. Deterministic: pure host arithmetic, no clocks."""
    from kubernetes_tpu.obs.sentinel import AnomalySentinel, SentinelConfig

    cfg = SentinelConfig()
    sentinel = AnomalySentinel(cfg)
    seq = 0

    def window(pods_per_sec: float) -> list:
        nonlocal seq
        seq += 1
        sample = sentinel.ring.append(
            t=float(seq), batches=cfg.window_batches,
            pods=int(pods_per_sec), signals={"pods_per_sec": pods_per_sec},
        )
        return sentinel.observe_window(sample)

    # healthy baseline: enough history for the slow window + warmup
    for _ in range(cfg.slow_windows + cfg.fast_windows + cfg.min_windows):
        assert not window(1000.0), "sentinel fired on a flat baseline"
    # the collapse: count windows until the spike rule fires
    lag_windows = 0
    while True:
        lag_windows += 1
        assert lag_windows <= 100, (
            "sentinel never detected a 50% sustained-throughput collapse"
        )
        if window(500.0):
            break
    return lag_windows * cfg.window_batches


def ladder14_hub_failover() -> dict:
    """#14: hub-failover blackout window (ISSUE 15) — a 2-replica
    fleet drives a plain backlog plus a required-anti-affinity cohort
    (the cross-shard admission path: peer-view fetch, CAS, staleness
    bounds) through the REAL endpoint-failover client against a
    replicated hub pair (primary + standby, op-log replication, shared
    real-time lease), and the primary is KILLED mid-drive. Measures the number the HA tentpole
    exists to bound: wall seconds from the kill to the FIRST
    post-promotion committed admit (promotion latency is lease-expiry
    gated, so the lease duration is the floor), plus the per-pod e2e
    p99 of pods bound inside that window and the admit rate before /
    during / after — proving conservative admission engaged during the
    blackout (staleness bound < blackout: cross-shard-constrained
    placements reject rather than risk overcommit) and full-rate admit
    resumed after it. The resurrected old primary must reject a write
    probe with the typed HubDeposed. Hoists hub_failover_blackout_s
    and hub_failover_p99_latency_s to the JSON top level."""
    from kubernetes_tpu.fleet import (
        FleetConfig,
        HubDeposed,
        HubLease,
        LocalHubClient,
        OccupancyExchange,
        PodRow,
        StandbyReplicator,
    )
    from kubernetes_tpu.fleet.runtime import RemoteOccupancyExchange
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.sim.generators import ZONE_KEY, make_node, make_pod
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState
    from kubernetes_tpu.utils.clock import Clock

    # small stable shapes on purpose: the blackout window is a
    # LATENCY measurement (lease expiry + promotion + re-attach), not
    # a throughput one — a constant-size arrival drip keeps the XLA
    # pad shapes warm after the warmup phase so the window isn't
    # polluted by CPU-backend recompiles
    n_nodes, lease_s = 32, 3.0
    wave_pods, warm_pods = 16, 192
    kill_wave, total_waves = 24, 64
    clock = Clock()
    lease = HubLease(clock=clock, duration_s=lease_s)
    primary = OccupancyExchange(clock=clock, hub_id="hub-a", lease=lease)
    assert primary.try_promote() == 1
    standby = OccupancyExchange(clock=clock, hub_id="hub-b", lease=lease)
    replicator = StandbyReplicator(standby, LocalHubClient(primary))
    cluster = ClusterState()
    for i in range(n_nodes):
        # 3 zones over 2 replicas (the sim's fleet geometry): one
        # replica owns two zones, so a zone-spread pod routed to it
        # has in-shard slack and the hop-capped handoff walk cannot
        # wedge on 2-zone parity
        cluster.create_node(
            make_node(
                f"n{i:04d}", "64", "256Gi", {ZONE_KEY: f"z{i % 3}"}
            )
        )
    universe = ("r0", "r1")
    scheds = {}
    adapters = []
    for rid in universe:
        adapter = RemoteOccupancyExchange(
            "", rid,
            clients=[LocalHubClient(primary), LocalHubClient(standby)],
            clock=clock, flush_client_id=f"{rid}-bench",
        )
        adapters.append(adapter)
        scheds[rid] = Scheduler(
            cluster,
            SchedulerConfig(
                batch_size=wave_pods,
                mesh_devices=1,
                solver=ExactSolverConfig(
                    tie_break="first", group_size=8
                ),
                fleet=FleetConfig(
                    replica=rid, replicas=universe, exchange=adapter,
                    # staleness bound BELOW the lease-gated blackout
                    # (so conservative admission must engage inside
                    # it) but comfortably ABOVE the steady-state drive
                    # cadence — a bound tighter than one real-time
                    # loop iteration reads healthy peers as stale and
                    # starves the spread cohort outright
                    max_row_age_s=2.0,
                ),
            ),
        )
    enq_t: dict[str, float] = {}
    bind_t: dict[str, float] = {}
    seq = {"n": 0}

    def arrive(count):
        now = clock.now()
        for _ in range(count):
            i = seq["n"]
            seq["n"] += 1
            pod = make_pod(
                f"p{i:05d}", "200m",
                # a required-anti-affinity cohort drives the
                # cross-shard admission path (peer-view fetch + CAS +
                # the staleness machinery the blackout test needs)
                # WITHOUT the zone-spread shape: a maxSkew-1 cohort
                # under a deterministic local solver can ping-pong on
                # the global recheck at REAL-clock backoff pace (the
                # PR 6 scope note the virtual-time sims exercise with
                # churn); anti pods are locally enforceable, so the
                # ladder measures failover latency, not that scope
                # note. Cohort sized well under the node count so
                # every pod is satisfiable.
                shape="anti" if i % 64 == 0 else "plain",
            )
            cluster.create_pod(pod)
            enq_t[pod.key] = now

    t_kill = t_promote = t_first_after = None

    def drive():
        nonlocal t_first_after
        before = len(bind_t)
        for rid in universe:
            for r in scheds[rid].run_until_settled(max_batches=4):
                now = clock.now()
                for pod, _node in r.scheduled:
                    bind_t[pod] = now
                    if t_promote is not None and t_first_after is None:
                        t_first_after = now
        if len(bind_t) == before:
            # stalled round: cross-shard-rejected pods park
            # unschedulable and their production retry path is the
            # periodic flush (5 min on the serve loop) — the bench
            # driver ticks it eagerly so the measurement window isn't
            # dominated by a wall-clock park (backoff still applies)
            for rid in universe:
                scheds[rid].queue.move_all_to_active_or_backoff(
                    "BenchFlush"
                )

    # warmup: compile every pad shape the drip will produce (plain +
    # spread batches, the handoff trickle's partial pow2 pads) before
    # the measured window opens
    arrive(warm_pods)
    warm_deadline = time.perf_counter() + 240.0

    def _warm_done():
        # warmup exists to compile the drip's shapes, not to prove
        # completeness (the sim owns that): every PLAIN pod bound and
        # at least one anti pod through the cross-shard admit path
        plain_warm = [
            k for k in enq_t if int(k.rsplit("p", 1)[-1]) % 64 != 0
        ]
        anti_bound = sum(
            1
            for k in bind_t
            if int(k.rsplit("p", 1)[-1]) % 64 == 0
        )
        return (
            all(k in bind_t for k in plain_warm) and anti_bound >= 1
        )

    while not _warm_done() and time.perf_counter() < warm_deadline:
        drive()
        primary.try_promote()
        try:
            replicator.poll()
        except Exception:
            pass
    assert _warm_done(), (
        f"warmup never settled: {len(bind_t)}/{warm_pods} bound"
    )
    deadline = time.perf_counter() + 300.0
    wave = 0
    while (
        wave < total_waves or len(bind_t) < len(enq_t)
    ) and time.perf_counter() < deadline:
        if wave < total_waves:
            arrive(wave_pods)
        wave += 1
        drive()
        if t_kill is None:
            primary.try_promote()  # same-holder lease renew
            try:
                replicator.poll()
            except Exception:
                pass
            if wave >= kill_wave:
                t_kill = clock.now()
                primary.set_down(True)
        elif t_promote is None:
            if standby.try_promote() is not None:
                t_promote = clock.now()
        else:
            standby.try_promote()  # keep the new primary's lease fresh
    n_pods = len(enq_t)
    stale_rejections = sum(
        s.fleet.stale_rejections for s in scheds.values()
    )
    client_failovers = sum(a.failovers for a in adapters)
    # the resurrected old primary: reads serve, writes fence
    primary.set_down(False)
    try:
        primary.stage(
            "r0",
            PodRow(
                pod="probe/p", node="n0000", zone="z0",
                namespace="probe", labels=(("app", "probe"),),
            ),
        )
        stale_write_rejected = False
    except HubDeposed:
        stale_write_rejected = True
    for adapter in adapters:
        try:
            adapter.close()
        except Exception:
            pass
    assert t_kill is not None and t_promote is not None
    assert t_first_after is not None, (
        "no admit ever committed after the promotion — the fleet "
        "never healed"
    )
    # placement-completeness CORRECTNESS is the sim's job (zero lost
    # rows/handoffs under invariants); the ladder's bar is that the
    # failover cost no real capacity: every plain pod binds and the
    # hard-spread cohort stays effectively complete (a straggler
    # waiting out a real-clock backoff at the deadline is latency,
    # not loss)
    unbound = [k for k in enq_t if k not in bind_t]
    assert all(
        int(k.rsplit("p", 1)[-1]) % 64 == 0 for k in unbound
    ), f"plain pods unbound after heal: {unbound[:5]}"
    assert len(bind_t) >= n_pods * 0.99, (
        f"only {len(bind_t)}/{n_pods} pods bound — the failover lost "
        "real capacity"
    )
    assert stale_write_rejected, (
        "the deposed old primary accepted a write probe"
    )
    assert standby.hub_epoch == 2 and standby.role == "primary"
    blackout_s = t_first_after - t_kill
    assert blackout_s < 60.0, f"unbounded blackout: {blackout_s:.1f}s"
    # rate before / after, and the e2e p99 of pods bound in the window
    t0 = min(enq_t.values())
    pre = [t for t in bind_t.values() if t <= t_kill]
    post = [t for t in bind_t.values() if t >= t_first_after]
    pre_rate = len(pre) / max(max(pre) - t0, 1e-9) if pre else 0.0
    post_rate = (
        len(post) / max(max(post) - t_first_after, 1e-9)
        if len(post) > 1
        else 0.0
    )
    window = sorted(
        bound_at - enq_t[pod]
        for pod, bound_at in bind_t.items()
        if t_kill <= bound_at <= t_first_after
    )
    p99_window = (
        window[min(int(len(window) * 0.99), len(window) - 1)]
        if window
        else 0.0
    )
    return {
        "config": (
            f"hub-failover blackout: 2 replicas x {n_pods} pods "
            "(required-anti-affinity cohort for the cross-shard admit "
            f"path, {wave_pods}/wave drip) x "
            f"{n_nodes} nodes over a replicated hub pair (real-time "
            f"lease {lease_s}s, op-log replication, endpoint-failover "
            f"client); primary killed at wave {kill_wave}; staleness "
            "bound 2s (< blackout) so conservative admission engages "
            "mid-blackout"
        ),
        "hub_failover_blackout_s": round(blackout_s, 3),
        "hub_failover_p99_latency_s": round(p99_window, 3),
        "promotion_s": round(t_promote - t_kill, 3),
        "lease_s": lease_s,
        "pods_bound": len(bind_t),
        "pods_unbound_at_deadline": len(unbound),
        "bound_in_window": len(window),
        "pre_kill_pods_per_sec": round(pre_rate, 1),
        "post_heal_pods_per_sec": round(post_rate, 1),
        "stale_rejections": stale_rejections,
        "client_failovers": client_failovers,
        "flush_dedup_hits": (
            primary.flush_dedup_hits + standby.flush_dedup_hits
        ),
        "stale_primary_write_rejected": stale_write_rejected,
        "replication_ops": replicator.ops_applied,
    }


def ladder15_gang() -> dict:
    """#15: gang throughput + time-to-full-gang (ISSUE 17) — a
    DL-training backlog of pod GROUPS (gangs) over an accelerator-
    heterogeneous cluster, driven through the gang gate's park /
    assemble / atomic-commit machinery. Members of every gang arrive
    SPLIT across two waves on purpose: wave 0 parks every half-gang
    (gang_incomplete, zero binds — the all-or-nothing invariant under
    load), wave 1 completes them and the gate re-pulls the parked
    halves via take_for_gang, so the measured window covers the whole
    assembly lifecycle, not just a lucky same-batch arrival. Measures
    gang-member binds/sec end to end, the per-gang time from first
    member creation to the atomic commit (p50/p99 — the number the
    gang gate exists to bound), and the fraction of workload-classed
    pods the heterogeneity throughput term steered onto their fastest
    accelerator class. Asserts zero partial gangs at every
    observation point and exactly one atomic commit per gang. Hoists
    gang_pods_per_sec and gang_time_to_full_p99_s to the JSON top
    level."""
    from kubernetes_tpu import metrics
    from kubernetes_tpu.gang import ACCEL_CLASS_LABEL, GangConfig
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.sim.generators import make_node, make_pod
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    n_nodes, n_gangs, gang_size = 48, 40, 4
    warm_gangs = 24
    classes = ("tpu-v5e", "tpu-v4", "gpu-a100")
    # transformer gangs run fastest on v5e, resnet on v4 — the Gavel-
    # style objective should steer each class to its best accelerator
    # since capacity is deliberately nowhere near binding
    table = {
        "transformer": {"tpu-v5e": 1.0, "tpu-v4": 0.7, "gpu-a100": 0.4},
        "resnet": {"tpu-v5e": 0.7, "tpu-v4": 1.0, "gpu-a100": 0.4},
    }
    best = {"transformer": "tpu-v5e", "resnet": "tpu-v4"}
    cluster = ClusterState()
    accel_of = {}
    for i in range(n_nodes):
        accel = classes[i % len(classes)]
        accel_of[f"n{i:03d}"] = accel
        cluster.create_node(
            make_node(
                f"n{i:03d}", "64", "256Gi", {ACCEL_CLASS_LABEL: accel}
            )
        )
    sched = Scheduler(
        cluster,
        SchedulerConfig(
            batch_size=64,
            mesh_devices=1,
            solver=ExactSolverConfig(tie_break="first", group_size=8),
            gang=GangConfig(
                # assembly gaps here are batch-cadence, not operator
                # timescale: keep the timeout/quarantine machinery far
                # out of the measurement's way
                min_member_timeout=600.0,
                quarantine_after=1_000,
                throughput_weight=8,
                class_throughput=table,
            ),
        ),
    )
    clock = sched.clock
    wc_of: dict[str, str] = {}
    gang_of_pod: dict[str, str] = {}
    created_at: dict[str, float] = {}
    bind_t: dict[str, float] = {}
    seq = {"n": 0}

    def arrive_members(gid: str, wc: str, count: int):
        if gid not in created_at:
            created_at[gid] = clock.now()
        for _ in range(count):
            i = seq["n"]
            seq["n"] += 1
            pod = make_pod(
                f"{gid}-m{i:04d}", "500m",
                gang=gid, gang_min=gang_size, workload_class=wc,
            )
            cluster.create_pod(pod)
            wc_of[pod.key] = wc
            gang_of_pod[pod.key] = f"default/{gid}"

    def drive():
        for r in sched.run_until_settled(max_batches=16):
            now = clock.now()
            for pod, _node in r.scheduled:
                bind_t[pod] = now
        # all-or-nothing at every observation point: a gang is either
        # fully bound or fully pending, never split
        by_gid: dict[str, int] = {}
        for k in bind_t:
            by_gid[gang_of_pod[k]] = by_gid.get(gang_of_pod[k], 0) + 1
        partial = {
            g: c for g, c in by_gid.items() if c != gang_size
        }
        assert not partial, f"partially bound gangs: {partial}"

    # warmup: complete gangs, same 64-batch pad shapes the measured
    # waves produce, so the window isn't polluted by CPU-backend
    # recompiles
    for g in range(warm_gangs):
        arrive_members(f"warm{g:03d}", "transformer", gang_size)
    drive()
    assert len(bind_t) == warm_gangs * gang_size, (
        f"warmup never settled: {len(bind_t)} bound"
    )
    commits0 = metrics.gang_commits_total._value.get()
    bound0 = metrics.gang_bound_pods_total._value.get()
    warm_keys = set(bind_t)
    t0 = clock.now()
    # wave 0: HALF of every gang — the gate must park all of them
    for g in range(n_gangs):
        wc = "transformer" if g % 2 == 0 else "resnet"
        arrive_members(f"g{g:03d}", wc, gang_size // 2)
    drive()
    assert len(bind_t) == len(warm_keys), (
        "a half-assembled gang bound pods"
    )
    # wave 1: the completing halves — take_for_gang re-pulls the
    # parked members and every gang commits atomically
    for g in range(n_gangs):
        wc = "transformer" if g % 2 == 0 else "resnet"
        arrive_members(f"g{g:03d}", wc, gang_size // 2)
    drive()
    wall_s = max(clock.now() - t0, 1e-9)
    n_pods = n_gangs * gang_size
    measured = {k: t for k, t in bind_t.items() if k not in warm_keys}
    assert len(measured) == n_pods, (
        f"only {len(measured)}/{n_pods} gang pods bound"
    )
    commits = int(metrics.gang_commits_total._value.get() - commits0)
    assert commits == n_gangs, (
        f"{commits} atomic commits for {n_gangs} gangs"
    )
    assert (
        metrics.gang_bound_pods_total._value.get() - bound0 == n_pods
    )
    # heterogeneity steering: fraction of measured pods whose node
    # carries their workload class's fastest accelerator
    on_best = sum(
        1
        for k in measured
        if accel_of[cluster.get_pod(*k.split("/")).node_name]
        == best[wc_of[k]]
    )
    best_frac = on_best / n_pods
    assert best_frac > 0.5, (
        f"throughput term never steered: {best_frac:.2f} on best class"
    )
    ttf = sorted(
        max(
            measured[k]
            for k in measured
            if gang_of_pod[k] == f"default/g{g:03d}"
        )
        - created_at[f"g{g:03d}"]
        for g in range(n_gangs)
    )
    p50 = ttf[len(ttf) // 2]
    p99 = ttf[min(int(len(ttf) * 0.99), len(ttf) - 1)]
    return {
        "config": (
            f"{n_gangs} gangs x {gang_size} members over {n_nodes} "
            f"nodes in {len(classes)} accelerator classes; members "
            "split across two arrival waves (park -> assemble -> "
            "atomic commit); heterogeneity throughput term weight "
            f"{sched.config.gang.throughput_weight}"
        ),
        "gang_pods_per_sec": round(n_pods / wall_s, 1),
        "gang_time_to_full_p50_s": round(p50, 3),
        "gang_time_to_full_p99_s": round(p99, 3),
        "gangs_committed": commits,
        "gang_pods_bound": len(measured),
        "partial_gangs": 0,  # asserted after every drive above
        "best_accel_fraction": round(best_frac, 3),
    }


def pallas_microbench() -> dict:
    """The tpuSolver.pallas ladder micro-bench (ISSUE 13 satellite):
    the InterPodAffinity (term, domain) aggregation — jitted
    segment_sum reference vs the wired Pallas kernel
    (domain_counts_padded) — at a zone-topology production shape. On a
    TPU backend this measures the compiled MXU kernel; on CPU the
    kernel necessarily runs in INTERPRET mode, which measures the
    wiring's correctness cost, not kernel speed — reported as such
    (ops/pallas_kernels.py says why the default stays off)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.ops.pallas_kernels import (
        domain_counts_padded,
        domain_counts_reference,
    )

    t, n, d_pad = 16, 2_048, 16
    rng = np.random.default_rng(5)
    dom = jnp.asarray(
        rng.integers(-1, d_pad, size=(t, n)).astype(np.int32)
    )
    cnt = jnp.asarray(rng.integers(0, 5, size=(t, n)).astype(np.int32))
    ref = jax.jit(domain_counts_reference, static_argnames=("d_pad",))
    pal = jax.jit(domain_counts_padded, static_argnames=("d_pad",))
    out_ref = np.asarray(ref(dom, cnt, d_pad=d_pad))
    out_pal = np.asarray(pal(dom, cnt, d_pad=d_pad))
    np.testing.assert_array_equal(out_ref, out_pal)

    def best_of(fn, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(dom, cnt, d_pad=d_pad)[0].block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    ref_s = best_of(ref)
    pal_s = best_of(pal)
    backend = jax.default_backend()
    return {
        "shape": f"[{t} terms x {n} nodes] -> [{t} x {d_pad}]",
        "backend": backend,
        "mode": "compiled" if backend == "tpu" else "interpret",
        "segment_sum_s": round(ref_s, 6),
        "pallas_s": round(pal_s, 6),
        "pallas_vs_segment_sum": round(ref_s / max(pal_s, 1e-9), 3),
        "parity": True,  # asserted above
        "note": (
            "wired behind tpuSolver.pallas (default off): see "
            "ops/pallas_kernels.py for what keeps the default"
        ),
    }


def ladder7_multichip() -> dict:
    """#7: multichip A/B — the exact-parity grouped SESSION solve at the
    north-star shape (51,200 x 10,240) on 1 device vs the full node-axis
    mesh, plus the 8x-node shape (~81,920 nodes — the HBM-growth target)
    on the full mesh only. Each timed rep is a fresh device session
    (upload + solve + assignment read), symmetric across both arms; the
    sharded arm must pick bit-identical nodes (the device-count
    invariance contract). Skips cleanly when only one device is
    visible."""
    import jax
    import numpy as np

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {
            "skipped": (
                f"only {n_dev} device visible; the multichip A/B needs a "
                "multi-device mesh (virtual-CPU variant: "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
            )
        }

    from kubernetes_tpu.parallel.sharding import node_mesh
    from kubernetes_tpu.server.bulk import columnar_pod_batch
    from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
    from kubernetes_tpu.tensorize.schema import ResourceVocab, pad_to

    mesh = node_mesh()
    vocab = ResourceVocab(("cpu", "memory", "ephemeral-storage"))
    cfg = ExactSolverConfig(tie_break="random", group_size=1024)

    def run(n_nodes, n_pods, use_mesh, reps=3):
        npad = pad_to(n_nodes)
        alloc = np.zeros((3, npad), dtype=np.int64)
        alloc[0, :n_nodes] = 16_000
        alloc[1, :n_nodes] = 64 << 30
        cpu = np.full(n_pods, 1000, np.int64)
        mem = np.full(n_pods, 2 << 30, np.int64)
        pb = columnar_pod_batch(cpu, mem, None, vocab)
        m = mesh if use_mesh else None
        cv = np.ones(npad, dtype=np.int64)
        # compile warm (untimed); the timed reps then pay a fresh
        # session upload + solve + read each
        ExactSolver(cfg).solve(
            _synthetic_node_batch(vocab, n_nodes, alloc), pb,
            col_versions=cv, mesh=m,
        )
        best = float("inf")
        a = None
        for _ in range(reps):
            batch = _synthetic_node_batch(vocab, n_nodes, alloc)
            solver = ExactSolver(cfg)
            t0 = time.perf_counter()
            a = solver.solve(batch, pb, col_versions=cv, mesh=m)
            best = min(best, time.perf_counter() - t0)
        a = np.asarray(a)
        placed = int((a >= 0).sum())
        assert placed == n_pods, (
            f"multichip {n_pods}x{n_nodes}: placed {placed}/{n_pods}"
        )
        assert int(a.max()) < n_nodes  # no padding-row bindings
        return best, a

    t1, a1 = run(NS_NODES, NS_PODS, False)
    tn, an = run(NS_NODES, NS_PODS, True)
    # the device-count-invariance contract AT SCALE: the sharded arm must
    # pick bit-identical nodes, or the speedup below is meaningless
    assert np.array_equal(a1, an), (
        "multichip: sharded solve diverged from the 1-device solve"
    )
    t8x, _ = run(NS_NODES * 8, NS_PODS, True, reps=2)
    return {
        "config": (
            "exact grouped session solve, fresh session per rep "
            "(upload+solve+read), min over reps; A/B at the north-star "
            "shape, 8x-node shape on the full mesh"
        ),
        "devices": n_dev,
        "pods": NS_PODS,
        "nodes": NS_NODES,
        "solve_1dev_s": round(t1, 3),
        "solve_mesh_s": round(tn, 3),
        "multichip_pods_per_sec": round(NS_PODS / tn, 1),
        "multichip_speedup": round(t1 / tn, 2),
        "bit_invariant_vs_1dev": True,  # asserted above
        "nodes_8x": NS_NODES * 8,
        "solve_8x_nodes_mesh_s": round(t8x, 3),
        "latency_ratio_8x_vs_1x": round(t8x / tn, 2),
    }


def served_grpc() -> dict:
    """Ladder #2's workload THROUGH THE WIRE: columnar pod batch over the
    bulk gRPC boundary (SyncNodes + Solve), measuring end-to-end wire
    pods/s including framing, transport, tensorize, and the device solve."""
    import numpy as np

    from kubernetes_tpu.server.bulk import BulkClient, BulkCore, make_grpc_server
    from kubernetes_tpu.state.cluster import ClusterState

    cs = ClusterState()
    core = BulkCore(cs)
    server, port = make_grpc_server(core, port=0)
    server.start()
    try:
        client = BulkClient(f"127.0.0.1:{port}")
        client.sync_nodes(
            names=[f"n{i:05}" for i in range(1_000)],
            cpu_milli=[16_000] * 1_000,
            mem_bytes=[64 << 30] * 1_000,
            max_pods=[110] * 1_000,
        )
        cpu = np.full(5_000, 250, np.int64)
        mem = np.full(5_000, 512 << 20, np.int64)
        client.solve(cpu_milli=cpu, mem_bytes=mem)  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            meta, arrays = client.solve(cpu_milli=cpu, mem_bytes=mem)
            best = min(best, time.perf_counter() - t0)
        placed = int((arrays["assignments"] >= 0).sum())
        assert placed == 5_000, f"served: {placed}/5000 placed"
        client.close()
    finally:
        server.stop(grace=None)
    return {
        "config": "ladder #2 workload over the bulk gRPC boundary",
        "pods": 5_000,
        "nodes": 1_000,
        "wire_round_trip_s": round(best, 3),
        "pods_per_sec": round(5_000 / best, 1),
    }


MP_PODS = 2_000_000
MP_NODES = 200_000


def _megaplan_tensors(n_nodes: int, n_pods: int, seed: int = 12):
    """The _backlog_auction synthetic recipe with a heterogeneous node
    preload: the pack objective needs a fill gradient (an empty cluster
    scores every node identically and the objective ratio would be
    0/0). Returns the raw solver tensors + the per-node integer pack
    score both engines' placements are valued under."""
    import numpy as np

    rng = np.random.default_rng(seed)
    k, c, rc = 3, 8, 8
    alloc = np.zeros((k, n_nodes), dtype=np.int64)
    alloc[0] = 16_000
    alloc[1] = 64 * 1024**3
    load = rng.integers(0, 9, n_nodes)
    used = np.zeros((k, n_nodes), dtype=np.int64)
    used[0] = load * 1_000
    used[1] = load * (2 * 1024**3)
    cnt = load.astype(np.int32)
    rc_req = np.zeros((rc, k), dtype=np.int64)
    rc_req[:, 0] = rng.integers(1, 9, rc) * 250
    rc_req[:, 1] = rng.integers(1, 5, rc) * 1024**3
    rc_static = (np.arange(rc) % c).astype(np.int32)
    rc_of = rng.integers(0, rc, n_pods).astype(np.int32)
    priority = rng.integers(0, 10, n_pods).astype(np.int32)
    headroom = (
        100.0
        * (
            (alloc[0] - used[0]) / np.maximum(alloc[0], 1)
            + (alloc[1] - used[1]) / np.maximum(alloc[1], 1)
        )
        / 2.0
    ).astype(np.int64)
    pack_score = 100 - headroom
    return {
        "alloc": alloc,
        "used": used,
        "cnt": cnt,
        "max_pods": np.full(n_nodes, 110, np.int32),
        "node_valid": np.ones(n_nodes, bool),
        "static_mask": np.ones((c, n_nodes), bool),
        "rc_req": rc_req,
        "rc_static": rc_static,
        "rc_of": rc_of,
        "priority": priority,
        "pod_valid": np.ones(n_pods, bool),
        "pack_score": pack_score,
    }


def ladder16_megaplan(
    n_nodes: int = BD_NODES, n_pods: int = BD_PODS
) -> dict:
    """#16: the convex-relaxation mega-planner (ISSUE 19) vs the
    auction at the PLAN posture (plan_auction_config: pack objective,
    top_t=8, no repair phase — exactly what rebalance/planner.py
    dispatches), on one preloaded heterogeneous 512k x 102.4k shape:

    - wall time: the relaxed solve (dual ascent + deterministic
      rounding, one jitted program) must beat the auction's plan solve
      by >= 10x — the headline the planner's "auto" engine routing is
      justified by;
    - quality: the relax+round plan, tail-repaired through the SAME
      plan auction config, must value >= 0.95 of the auction plan
      under the shared integer pack score;
    - scale: a 2M-pod x 200k-node relaxed solve, pre-checked against
      the solver/budget.py HBM model (relax_estimate under the device
      budget, assert_index_headroom with the relax rc lane), completes
      with end-state validity asserted — the shape past the auction's
      planning ceiling."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.rebalance.planner import plan_auction_config
    from kubernetes_tpu.solver import budget as hbm
    from kubernetes_tpu.solver.budget import assert_index_headroom
    from kubernetes_tpu.solver.relax import RelaxConfig, _relax_jit
    from kubernetes_tpu.solver.single_shot import _single_shot_jit

    rcfg = RelaxConfig(objective="pack")
    acfg = plan_auction_config()
    akw = dict(
        max_rounds=acfg.max_rounds,
        price_step=acfg.price_step,
        top_t=acfg.top_t,
        repair_rounds=acfg.repair_rounds,
        pack=True,
    )

    def relax_call(ts):
        # used/pod_count are donated — fresh device arrays per call
        return _relax_jit(
            jnp.asarray(ts["alloc"]),
            jnp.asarray(ts["used"]),
            jnp.asarray(ts["cnt"]),
            jnp.asarray(ts["max_pods"]),
            jnp.asarray(ts["node_valid"]),
            jnp.asarray(ts["static_mask"]),
            jnp.asarray(ts["rc_req"]),
            jnp.asarray(ts["rc_static"]),
            jnp.asarray(ts["rc_of"]),
            jnp.asarray(ts["priority"]),
            jnp.asarray(ts["pod_valid"]),
            jnp.float32(rcfg.tol),
            jnp.float32(rcfg.temp),
            jnp.float32(rcfg.step),
            max_iters=rcfg.max_iters,
            pack=True,
        )

    def auction_call(ts):
        return _single_shot_jit(
            jnp.asarray(ts["alloc"]),
            jnp.asarray(ts["used"]),
            jnp.asarray(ts["cnt"]),
            jnp.asarray(ts["max_pods"]),
            jnp.asarray(ts["node_valid"]),
            jnp.asarray(ts["static_mask"]),
            jnp.asarray(ts["rc_req"]),
            jnp.asarray(ts["rc_static"]),
            jnp.asarray(ts["rc_of"]),
            jnp.asarray(ts["priority"]),
            jnp.asarray(ts["pod_valid"]),
            **akw,
        )

    def timed(fn, ts):
        fn(ts)[0].block_until_ready()  # compile
        best, out = float("inf"), None
        for _ in range(2):
            t0 = time.perf_counter()
            out = fn(ts)
            out[0].block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best, out

    ts = _megaplan_tensors(n_nodes, n_pods)
    auction_s, a_out = timed(auction_call, ts)
    relax_s, r_out = timed(relax_call, ts)
    a_assigned = np.asarray(a_out[0])
    r_assigned = np.asarray(r_out[0])

    # tail repair through the SAME plan auction config, against the
    # post-rounding occupancy (the RelaxSolver wiring, on raw tensors)
    tail = r_assigned < 0
    repair_s = 0.0
    if tail.any():
        t0 = time.perf_counter()
        rep = _single_shot_jit(
            jnp.asarray(ts["alloc"]),
            r_out[1],  # used after rounding (donated onward)
            r_out[2],  # pod_count after rounding
            jnp.asarray(ts["max_pods"]),
            jnp.asarray(ts["node_valid"]),
            jnp.asarray(ts["static_mask"]),
            jnp.asarray(ts["rc_req"]),
            jnp.asarray(ts["rc_static"]),
            jnp.asarray(ts["rc_of"]),
            jnp.asarray(ts["priority"]),
            jnp.asarray(tail),
            **akw,
        )
        rep[0].block_until_ready()
        repair_s = time.perf_counter() - t0
        r_assigned = np.where(tail, np.asarray(rep[0]), r_assigned)

    def objective(assigned):
        placed = assigned >= 0
        return int(ts["pack_score"][assigned[placed]].sum()), int(
            placed.sum()
        )

    obj_a, placed_a = objective(a_assigned)
    obj_r, placed_r = objective(r_assigned)
    ratio = obj_r / max(obj_a, 1)
    speedup = auction_s / max(relax_s, 1e-9)
    # the perf bar is defined AT the ladder shape (the auction's round
    # count — and so the gap — grows with scale); debug downscales
    # still report both numbers but only the real shape enforces them
    if n_pods >= BD_PODS and n_nodes >= BD_NODES:
        assert speedup >= 10.0, (
            f"relax plan solve only {speedup:.1f}x faster than the "
            f"auction's ({relax_s:.3f}s vs {auction_s:.3f}s)"
        )
    assert ratio >= 0.95, (
        f"post-repair pack objective ratio {ratio:.4f} < 0.95 "
        f"({obj_r} vs {obj_a})"
    )

    # -- the 2M-pod arm: budget-model pre-check, then the solve --
    n_dev = len(jax.devices())
    est = hbm.relax_estimate(
        MP_NODES, MP_PODS, rc=8, mesh_devices=n_dev
    )
    budget = hbm.device_budget_bytes(0)
    assert est.per_device_bytes <= budget, (
        f"2M-pod relax shape over budget: {est.per_device_bytes} B "
        f"per device vs {budget} B"
    )
    assert_index_headroom(est.pod_pad, est.node_pad, rc_pad=est.rc_pad)
    ts2 = _megaplan_tensors(MP_NODES, MP_PODS, seed=13)
    mp_s, mp_out = timed(relax_call, ts2)
    mp_assigned = np.asarray(mp_out[0])
    placed_mp = mp_assigned >= 0
    # end-state validity at 2M: every placement on a real node, no
    # resource or pod-count overcommit (weighted bincounts over the
    # actual per-class request vectors)
    assert mp_assigned[placed_mp].min(initial=0) >= 0
    assert mp_assigned.max() < MP_NODES
    req_pod = ts2["rc_req"][ts2["rc_of"]]
    for kk in range(2):
        load_k = np.bincount(
            mp_assigned[placed_mp],
            weights=req_pod[placed_mp, kk].astype(np.float64),
            minlength=MP_NODES,
        )
        free_k = (ts2["alloc"][kk] - ts2["used"][kk]).astype(np.float64)
        assert (load_k <= free_k + 0.5).all(), f"resource {kk} overcommit"
    cnt_load = np.bincount(mp_assigned[placed_mp], minlength=MP_NODES)
    assert (
        cnt_load + ts2["cnt"] <= ts2["max_pods"]
    ).all(), "pod-count overcommit"
    mp_rate = MP_PODS / max(mp_s, 1e-9)

    return {
        "config": (
            f"plan posture A/B at {n_pods} pods x {n_nodes} preloaded "
            "nodes: pack-objective plan auction (top_t=8, no repair "
            "phase) vs the convex relaxation (dual ascent + "
            "deterministic rounding, one jitted program) with the "
            "same auction config repairing the integrality tail; "
            f"then a {MP_PODS}-pod x {MP_NODES}-node relaxed solve "
            "under the HBM budget model with end-state validity"
        ),
        "pods": n_pods,
        "nodes": n_nodes,
        "auction_plan_seconds": round(auction_s, 3),
        "relax_plan_seconds": round(relax_s, 3),
        "relax_plan_speedup": round(speedup, 1),
        "relax_repair_seconds": round(repair_s, 3),
        "relax_objective_ratio": round(ratio, 4),
        "auction_placed": placed_a,
        "relax_placed": placed_r,
        "relax_iterations": int(r_out[6]),
        "relax_residual": round(float(r_out[7]), 5),
        # converged duals, aggregated: the autoscaler cost signal —
        # nonzero mean = the shape is contended somewhere
        "dual_price_mean": round(
            float(
                (np.asarray(r_out[4]).sum(axis=0) + np.asarray(r_out[5]))
                .mean()
            ),
            3,
        ),
        "megaplan": {
            "pods": MP_PODS,
            "nodes": MP_NODES,
            "relax_solve_seconds": round(mp_s, 3),
            "megaplan_pods_per_sec": round(mp_rate, 1),
            "placed": int(placed_mp.sum()),
            "placed_ratio": round(float(placed_mp.mean()), 4),
            "iterations": int(mp_out[6]),
            "residual": round(float(mp_out[7]), 5),
            "estimated_per_device_bytes": est.per_device_bytes,
            "budget_bytes": budget,
            "end_state_valid": True,  # asserted above
        },
        "megaplan_pods_per_sec": round(mp_rate, 1),
    }


def _fleet_drain_worker(
    rid: str,
    universe: tuple,
    n_nodes: int,
    pod_idx,
    chunk: int,
    start_at: float,
    out_q,
    hub_addr: str = "",
    total_devices: int = 8,
) -> None:
    """One fleet-drain replica as its own OS process (spawn target).

    B arm (len(universe) > 1): builds its replica of the state service
    holding ONLY the pods the coordinator's plan routed near it (its
    base partition + the whole residual cohort — any replica may end up
    the residual's serialized claimant), then loops
    ``Scheduler.fleet_drain_backlog`` — claim a hub drain lease, drain
    it through this replica's own slot ring, complete it — until the
    hub ledger reports the global drain complete. A arm (singleton
    universe): the classic sole-owner ``drain_backlog`` over the whole
    backlog in one process with the whole device set — same worker,
    same env/affinity/warmup idiom, so the A/B is process-shape only.

    Reports its (pod_index, node_index) binds so the parent can merge
    the fleet's end state and assert validity: every pod bound exactly
    once (no pod lost, none double-drained), no node overcommitted."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={total_devices}"
        ).strip()
    if len(universe) > 1:
        # disjoint core slices per replica (the ladder-#8 fairness
        # rule): a real fleet runs replicas on separate hosts, so the
        # same-box A/B is a hardware split, not oversubscription
        try:
            cores = sorted(os.sched_getaffinity(0))
            n = len(universe)
            rank = universe.index(rid)
            share = max(len(cores) // n, 1)
            mine = cores[rank * share : (rank + 1) * share] or cores
            os.sched_setaffinity(0, mine)
        except (AttributeError, OSError):
            pass  # non-Linux: let the OS schedule
    import jax

    jax.config.update("jax_enable_x64", True)
    from kubernetes_tpu.fleet import FleetConfig
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.solver import budget as hbm
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    rank = universe.index(rid)
    fleet_mode = len(universe) > 1
    cs = ClusterState()
    for i in range(n_nodes):
        cs.create_node(_mk_node(i))
    fleet = (
        FleetConfig(
            replica=rid,
            replicas=universe,
            hub_address=hub_addr,
            cas_domain=True,  # leg c: domain-scoped CAS opt-in
        )
        if fleet_mode
        else None
    )
    sched = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=chunk,
            mesh_slice=(rank, len(universe)),
            solver=ExactSolverConfig(tie_break="random", group_size=512),
            fleet=fleet,
        ),
    )
    # multi-process replicas own EXCLUSIVE device slices, so each
    # replica's drain should plan against the full per-device budget;
    # fleet_drain_backlog splits by fleet size unconditionally (the
    # co-hosted sim/test shape), so pre-multiply to undo the split
    budget = hbm.device_budget_bytes(0) * max(len(universe), 1)

    # warmup: compile the chunk-size drain bucket on the SAME node
    # padding bucket. In fleet mode the ring routes only ~1/N of
    # created pods to this replica's queue, so seed chunk*N*2 pods
    # (indices offset past the measured backlog — warmup keys must
    # never collide with the hub ledger's), then delete them all
    base = 10_000_000  # offset: never a backlog index
    warm = chunk * (2 * len(universe) if fleet_mode else 1)
    for j in range(warm):
        cs.create_pod(_mk_pod(base + j, "plain"))
    sched.drain_backlog(chunk_pods=chunk, budget_bytes=budget)
    for p in list(cs.list_pods()):
        cs.delete_pod(p.namespace, p.name)

    # the measured backlog: ONLY this worker's plan slice (plus the
    # shared residual cohort in fleet mode) — the coordinator already
    # partitioned the 512k backlog, shipping every pod to every
    # replica is exactly the redundancy the fleet drain removes
    my_keys = set()
    for i in pod_idx:
        pod = _mk_pod(i, "plain")
        my_keys.add(f"{pod.namespace}/{pod.name}")
        cs.create_pod(pod)

    while time.time() < start_at:
        time.sleep(0.001)
    t_last = time.time()
    drained = 0
    cas_conflicts0 = _bench_counter_value("fleet_admit_cas_conflict_total")
    stalled = ""
    if fleet_mode:
        idle = 0
        while True:
            out = sched.fleet_drain_backlog(
                chunk_pods=chunk, budget_bytes=budget, plan_keys=my_keys
            )
            if out["drained"]:
                drained += out["drained"]
                t_last = time.time()
                idle = 0
            if any(x["remaining"] for x in out["leases"]):
                stalled = f"lease stranded {out['leases']}"
                break
            st = sched.fleet.exchange.drain_status()
            if st.get("complete"):
                break
            idle += 1
            if idle > 600:  # ~30 s of claim-nothing polls: deadlock
                stalled = f"no claimable lease, ledger {st}"
                break
            time.sleep(0.05)
    else:
        rep = sched.drain_backlog(chunk_pods=chunk, budget_bytes=budget)
        drained = rep.drained
        t_last = time.time()
    binds = [
        (int(p.name[4:]), int(p.node_name[5:]))
        for p in cs.list_pods()
        if p.node_name and p.name.startswith("pod-")
    ]
    out_q.put(
        {
            "rid": rid,
            "drained": drained,
            "t_done": t_last,
            "binds": binds,
            "stalled": stalled,
            "cas_conflicts": (
                _bench_counter_value("fleet_admit_cas_conflict_total")
                - cas_conflicts0
            ),
        }
    )


def _bench_counter_value(name: str) -> float:
    """Best-effort read of a kubernetes_tpu counter metric's current
    value (0.0 when the metric does not exist or the registry backend
    hides samples) — bench reporting only, never an assertion input."""
    try:
        from kubernetes_tpu import metrics as m

        counter = getattr(m, name)
        return float(counter._value.get())  # prometheus_client Counter
    except Exception:
        return 0.0


def _domain_cas_ab(n_admits: int = 4_096, zones: int = 8) -> dict:
    """Leg-c measure-first micro A/B: the SAME interleaving — every
    admit races one label-free peer write in a DIFFERENT zone — under
    the hub-wide CAS vs the domain-scoped CAS
    (``compare_and_stage(..., domain_scope=True)``). The hub-wide
    compare charges every one of these admits a re-fetch round for an
    interleaving that provably cannot touch its admission; the domain
    compare charges none of them."""
    from kubernetes_tpu.fleet import (
        AdmitConflict,
        NodeRow,
        OccupancyExchange,
        PENDING,
        PodRow,
    )

    def row(pod: str, z: int, state=PENDING) -> PodRow:
        return PodRow(
            pod=pod, node=f"n{z}", zone=f"z{z}", namespace="default",
            labels=(), state=state,
        )

    out = {}
    for scope in (False, True):
        hub = OccupancyExchange()
        hub.publish_nodes(
            "r0", [NodeRow(f"n{z}", f"z{z}") for z in range(zones)]
        )
        hub.publish_nodes("r1", [NodeRow(f"nx{zones}", "z0")])
        conflicts = 0
        t0 = time.perf_counter()
        for i in range(n_admits):
            z = i % zones
            v = hub.version
            # the interleaved peer write: label-free, NEXT zone over
            hub.stage("r1", row(f"default/peer-{i}", (z + 1) % zones))
            try:
                hub.compare_and_stage(
                    "r0", row(f"default/adm-{i}", z), v,
                    domain_scope=scope,
                )
            except AdmitConflict:
                conflicts += 1
                hub.stage("r0", row(f"default/adm-{i}", z))
        dt = time.perf_counter() - t0
        out["domain" if scope else "full"] = {
            "admits": n_admits,
            "cas_conflicts": conflicts,
            "seconds": round(dt, 3),
        }
    out["conflict_rounds_avoided"] = (
        out["full"]["cas_conflicts"] - out["domain"]["cas_conflicts"]
    )
    return out


def ladder17_fleet_drain(
    n_replicas: int = 4,
    n_nodes: int = BD_NODES,
    n_pods: int = BD_PODS,
    chunk: int = 16_384,
) -> dict:
    """#17: the FLEET-tier backlog drain (ISSUE 20) at the ladder-#11
    shape — the same 512k-pod backlog against 102,400 nodes, drained
    by 1 process vs N replica processes coordinated through the hub's
    drain-lease ledger. The parent plays coordinator: one global relax
    plan (ISSUE 19) over the backlog, partitioned by planned-node ring
    owner (``fleet/drain.py``) with every 512th pod forced cross-shard
    into the serialized residual cohort, registered at a REAL gRPC
    occupancy hub via ``drain_init``. Each B-arm replica process
    builds only its slice of the backlog, claims epoch-fenced drain
    leases, and drains them through its own slot ring under its own
    HBM budget (``cas_domain`` on — leg c). The parent merges every
    worker's binds and asserts fleet-wide end-state validity: all
    ``n_pods`` bound exactly once (lost=0, double_bind=0), no node
    overcommitted. The >= 1.5x fleet speedup bar is enforced AT the
    ladder shape (debug downscales report, full scale gates)."""
    import multiprocessing

    import numpy as np

    from kubernetes_tpu.fleet import OccupancyExchange, drain
    from kubernetes_tpu.fleet.ring import HashRing, ring_nodes_from
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.server.bulk import BulkCore, make_grpc_server
    from kubernetes_tpu.solver.exact import ExactSolverConfig
    from kubernetes_tpu.state.cluster import ClusterState

    universe = tuple(f"r{i}" for i in range(n_replicas))

    # -- the coordinator's planning half: one global relax plan ------
    cs = ClusterState()
    for i in range(n_nodes):
        cs.create_node(_mk_node(i))
    planner = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=chunk,
            solver=ExactSolverConfig(tie_break="random", group_size=512),
        ),
    )
    keys = []
    for i in range(n_pods):
        pod = _mk_pod(i, "plain")
        keys.append(f"{pod.namespace}/{pod.name}")
        cs.create_pod(pod)
    t0 = time.perf_counter()
    plan = planner.relax_plan_backlog()
    plan_s = time.perf_counter() - t0
    assignment = HashRing(universe).assign(
        ring_nodes_from(cs.list_nodes())
    )
    # every 512th pod plays the constrained cross-shard shape: the
    # partitioner sends it to the residual cohort, whose ONE
    # serialized lease keeps the fenced-CAS admit semantics intact
    partitions, residual = drain.partition_backlog(
        keys, plan, assignment,
        cross_shard=lambda k: int(k.rsplit("-", 1)[1]) % 512 == 0,
    )
    key_to_idx = {k: i for i, k in enumerate(keys)}
    part_idx = {
        rid: [key_to_idx[k] for k in ks]
        for rid, ks in partitions.items()
    }
    residual_idx = [key_to_idx[k] for k in residual]
    del cs, planner, plan, key_to_idx  # free before the fleet runs

    # -- the hub: a real gRPC occupancy exchange, ledger installed ---
    exchange = OccupancyExchange()
    core = BulkCore(ClusterState(), exchange=exchange)
    server, hub_port = make_grpc_server(core, port=0)
    server.start()
    hub_addr = f"127.0.0.1:{hub_port}"
    exchange.drain_init("r0", partitions, residual)

    ctx = multiprocessing.get_context("spawn")
    out_q = ctx.Queue()

    def run_arm(arm_universe: tuple) -> list:
        start_at = time.time() + 40.0  # clear every warmup compile
        procs = []
        for rid in arm_universe:
            idx = (
                sorted(part_idx.get(rid, []) + residual_idx)
                if len(arm_universe) > 1
                else list(range(n_pods))
            )
            procs.append(
                ctx.Process(
                    target=_fleet_drain_worker,
                    args=(
                        rid, arm_universe, n_nodes, idx, chunk,
                        start_at, out_q, hub_addr, 8,
                    ),
                )
            )
        for p in procs:
            p.start()
        try:
            results = [out_q.get(timeout=1_800.0) for _ in procs]
        finally:
            for p in procs:
                p.join(timeout=30.0)
        return [start_at, results]

    try:
        # B first (the ledger is armed and single-use per drain_init);
        # then the A arm reuses the same worker with a singleton
        # universe — no fleet, no hub, whole backlog, whole device set
        b_start, b_results = run_arm(universe)
        a_start, a_results = run_arm(("r0",))
    finally:
        server.stop(grace=None)

    for r in b_results + a_results:
        assert not r["stalled"], f"{r['rid']}: {r['stalled']}"

    # -- merged fleet end state: every pod bound EXACTLY once --------
    merged = [b for r in b_results for b in r["binds"]]
    a = np.array([b[0] for b in merged], dtype=np.int64)
    nd = np.array([b[1] for b in merged], dtype=np.int64)
    assert len(np.unique(a)) == len(a), "a pod drained twice (double bind)"
    lost = n_pods - len(a)
    assert lost == 0, f"{lost} backlog pod(s) ended unbound fleet-wide"
    cnt = np.bincount(nd, minlength=n_nodes)
    assert int(cnt.max()) <= 110, "pod-count overcommit"
    assert np.bincount(nd, weights=np.full(len(nd), 250.0)).max() <= 16_000
    assert (
        np.bincount(nd, weights=np.full(len(nd), 512.0 * 1024**2)).max()
        <= 64 * 1024**3
    )

    st = exchange.drain_status()
    b_done = max(r["t_done"] for r in b_results)
    b_wall = max(b_done - b_start, 1e-9)
    fleet_rate = n_pods / b_wall
    a_wall = max(a_results[0]["t_done"] - a_start, 1e-9)
    single_rate = a_results[0]["drained"] / a_wall
    speedup = fleet_rate / max(single_rate, 1e-9)
    # the perf bar is defined AT the ladder shape (ladder-#16 rule):
    # debug downscales report both arms but only full scale enforces
    if n_pods >= BD_PODS and n_nodes >= BD_NODES:
        assert speedup >= 1.5, (
            f"fleet drain only {speedup:.2f}x over the sole-owner "
            f"drain ({fleet_rate:.0f} vs {single_rate:.0f} pods/s)"
        )
    return {
        "config": (
            f"{n_pods}-pod backlog x {n_nodes} nodes: one global "
            "relax plan partitioned by planned-node ring owner, "
            f"drained by {n_replicas} replica processes claiming "
            "epoch-fenced hub drain leases (gRPC hub, domain-scoped "
            "CAS on, every 512th pod serialized through the residual "
            "cohort) vs the same backlog through one sole-owner "
            "drain_backlog process; merged end-state validity "
            "asserted fleet-wide"
        ),
        "replicas": n_replicas,
        "pods": n_pods,
        "nodes": n_nodes,
        "chunk_pods": chunk,
        "plan_seconds": round(plan_s, 3),
        "partition_sizes": {
            rid: len(ix) for rid, ix in sorted(part_idx.items())
        },
        "residual_pods": len(residual_idx),
        "single": {
            "drained": a_results[0]["drained"],
            "wall_s": round(a_wall, 3),
            "pods_per_sec": round(single_rate, 1),
        },
        "fleet": {
            "drained": sum(r["drained"] for r in b_results),
            "bound": len(a),
            "wall_s": round(b_wall, 3),
            "fleet_drain_pods_per_sec": round(fleet_rate, 1),
            "leases": st.get("leases", 0),
            "leases_reassigned": st.get("reassigned", 0),
            "ledger_complete": bool(st.get("complete")),
            "cas_conflicts": sum(
                r["cas_conflicts"] for r in b_results
            ),
            "per_replica_drained": {
                r["rid"]: r["drained"] for r in b_results
            },
        },
        "fleet_drain_pods_per_sec": round(fleet_rate, 1),
        "fleet_drain_speedup": round(speedup, 3),
        "lost": lost,
        "double_bind": 0,  # asserted above (unique pod indices)
        "domain_cas": _domain_cas_ab(),
        "end_state_valid": True,  # asserted above
    }


def main() -> None:
    import jax

    # resource arithmetic is int64 (memory bytes overflow int32)
    jax.config.update("jax_enable_x64", True)
    from kubernetes_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    # dispatch/read canary: the time of a trivial dispatch-and-wait
    # before and after this process's first device->host read, so what
    # one sync cost on the machine every number below came from is in
    # the record.
    import numpy as _np
    import jax.numpy as _jnp

    _triv = jax.jit(lambda x: x * 3 + 1)
    _x = _jnp.arange(8)
    _triv(_x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        _triv(_x).block_until_ready()
    pre_read_ms = (time.perf_counter() - t0) / 5 * 1e3
    _np.asarray(_triv(_x))  # first D2H read
    t0 = time.perf_counter()
    for _ in range(5):
        _triv(_x).block_until_ready()
    rtt_ms = (time.perf_counter() - t0) / 5 * 1e3

    ladders = {}
    ladders["1_basic_500x500"] = {
        "config": "SchedulingBasic, default plugins, YAML-runner path",
        **ladder1_basic(),
    }
    # batch sizes: measured sweet spots — every ladder runs as ONE solve
    # call (pods per sync amortise the blocking read; since the
    # compact-wire rework the padding chunks of a 16384 bucket cost
    # nearly nothing, so 1x16384 beats 3x4096 for the 10k-pod spread row
    # by ~1.5x)
    ladders["2_fit_5kx1k"] = {
        "config": "Fit+BalancedAllocation, homogeneous",
        **_run_ladder(1_000, 5_000, "plain", batch=8_192, warm_pods=5_000),
    }
    ladders["3_spread_10kx5k"] = {
        "config": "PodTopologySpread hard maxSkew=1, 3 zones",
        **_run_ladder(5_000, 10_000, "spread", batch=16_384, warm_pods=10_000),
    }
    ladders["4_interpod_5kx5k"] = {
        "config": "InterPodAffinity required hostname anti-affinity",
        **_run_ladder(5_000, 5_000, "anti", batch=8_192, warm_pods=5_000),
    }
    ladders["5_rebalance_50kx10k"] = {
        "config": "global rebalance, single batched auction solve",
        **ladder5_north_star(),
    }
    sustained = ladder_sustained()
    ladders["6_sustained_arrival"] = {
        "config": (
            "open-loop sustained arrival, sync-vs-pipelined-vs-"
            "streaming A/B/C per shape; hard shapes (ports/spread/"
            "anti) run through run_pipelined's occupancy-carrying "
            "sub-batch split AND run_streaming's device-resident "
            "cross-batch chain; rtt_attribution rows break deferred "
            "reads into hidden vs paid"
        ),
        **sustained,
    }
    multichip = ladder7_multichip()
    ladders["7_multichip"] = multichip
    fleet = ladder8_fleet()
    ladders["8_fleet"] = fleet
    degraded = ladder9_degraded()
    ladders["9_degraded"] = degraded
    backlog = ladder11_backlog_drain()
    ladders["11_backlog_drain"] = backlog
    autotune = ladder12_autotune()
    ladders["12_autotune"] = autotune
    obs_overhead = ladder13_obs_overhead()
    ladders["13_obs_overhead"] = obs_overhead
    hub_failover = ladder14_hub_failover()
    ladders["14_hub_failover"] = hub_failover
    gang = ladder15_gang()
    ladders["15_gang"] = gang
    megaplan = ladder16_megaplan()
    ladders["16_megaplan"] = megaplan
    fleet_drain = ladder17_fleet_drain()
    ladders["17_fleet_drain"] = fleet_drain
    ladders["pallas_domain_counts"] = pallas_microbench()
    rebalance = ladder10_rebalance_loop()
    ladders["10_rebalance_loop"] = {
        "config": (
            "continuous rebalancer A/B on a seeded fragmented "
            "51.2k x 10.24k cluster: detector + drain gather + "
            "pack-auction plan + budget/gain/PDB-bounded selection "
            "per cycle, loop run to detector convergence"
        ),
        **rebalance,
    }
    ladders["served_grpc_5kx1k"] = served_grpc()
    ladders["dispatch_read_canary"] = {
        "pre_first_read_dispatch_ms": round(pre_read_ms, 4),
        "post_first_read_dispatch_ms": round(rtt_ms, 4),
        "note": (
            "trivial jitted dispatch + block_until_ready, mean of 5, "
            "before and after this process's first device->host read. "
            "All ladder numbers above include per-batch assignment "
            "reads."
        ),
    }

    headline = ladders["2_fit_5kx1k"]["pods_per_sec"]
    # headline sustained pair (the pipelined open-loop plain shape):
    # sustained pods/s and per-pod e2e p99 under queueing
    sus_head = sustained["plain"]["pipelined"]
    print(
        json.dumps(
            {
                "metric": (
                    "pods scheduled/sec, BASELINE ladder #2 (5k pods x 1k "
                    "nodes, full default plugin pipeline, warm start, "
                    "end-to-end); all six ladder rows in `ladders`"
                ),
                "value": headline,
                "unit": "pods/s",
                "sustained_pods_per_sec": sus_head[
                    "sustained_pods_per_sec"
                ],
                "sustained_p99_pod_latency_s": sus_head[
                    "sustained_p99_pod_latency_s"
                ],
                # ladder #6 streaming hoist (ISSUE 10): the streaming
                # dispatcher's plain-shape sustained p99 and its p99
                # speedup over the PR 4 pipelined arm (the >= 2x gate),
                # plus the amortized un-hidden reads per batch (the
                # per-event-fence RTT floor; < 1.0 means the per-batch
                # floor fell)
                "streaming_p99_pod_latency_s": sustained["plain"][
                    "streaming"
                ]["sustained_p99_pod_latency_s"],
                "streaming_speedup": sustained["plain"][
                    "streaming_p99_speedup_vs_pipelined"
                ],
                "streaming_unhidden_reads_per_batch": sustained[
                    "plain"
                ]["streaming_unhidden_reads_per_batch"],
                # ladder #7 hoist: real numbers when a mesh ran, the skip
                # reason string when only one device is visible
                "multichip_pods_per_sec": multichip.get(
                    "multichip_pods_per_sec",
                    multichip.get("skipped"),
                ),
                "multichip_speedup": multichip.get(
                    "multichip_speedup", multichip.get("skipped")
                ),
                # ladder #8 hoist: N-replica fleet sustained throughput
                # and its speedup over the 1-replica arm
                "fleet_pods_per_sec": fleet["fleet_pods_per_sec"],
                "fleet_speedup": fleet["fleet_speedup"],
                # ladder #9 hoist: sustained pods/s on the fallback
                # ladder's pure-host floor — what degraded mode costs
                "degraded_pods_per_sec": degraded[
                    "degraded_pods_per_sec"
                ],
                # ladder #10 hoist: packed-utilization gain the
                # rebalance loop recovered on the seeded fragmented
                # north-star cluster, and its steady-state plan solve
                "rebalance_utilization_gain": rebalance[
                    "rebalance_utilization_gain"
                ],
                "rebalance_plan_solve_s": rebalance[
                    "rebalance_plan_solve_s"
                ],
                # ladder #11 hoist (ISSUE 12): the 10x-scale backlog
                # drain — 512k pods against 102,400 nodes through the
                # HBM-budget-planned chunked streaming path — end-to-
                # end drain rate and wall time (mesh arm when a mesh
                # ran, 1-device otherwise)
                "backlog_drain_pods_per_sec": backlog[
                    "backlog_drain_pods_per_sec"
                ],
                "backlog_drain_seconds": backlog[
                    "backlog_drain_seconds"
                ],
                # ladder #12 hoist (ISSUE 13): the auto-tuned sustained
                # streaming arm — tuned >= static asserted inside the
                # ladder (revert-on-regression makes the static config
                # the tuned arm's floor), convergence in batches, zero
                # guardrail breaches asserted
                "tuned_pods_per_sec": autotune["tuned_pods_per_sec"],
                "tuning_convergence_batches": autotune[
                    "tuning_convergence_batches"
                ],
                # ladder #13 hoist (ISSUE 14): what the whole obs
                # layer (fleet-wide tracing + journal + SLO engine)
                # costs on the sustained stream, asserted <= 5% inside
                # the ladder, and the SLO engine's own live p99 from
                # the obs-on arm
                "slo_p99_pod_latency_s": obs_overhead[
                    "slo_p99_pod_latency_s"
                ],
                "obs_overhead_fraction": obs_overhead[
                    "obs_overhead_fraction"
                ],
                # ladder #13 refresh (ISSUE 18): the full flight-
                # telemetry loop's cost on the same stream — profiler +
                # sentinel on top of the obs layer, asserted <= 5%
                # inside the ladder — the profiler's marginal cost over
                # the plain obs arm, and how many batches the
                # production-window sentinel needs to flag a 50%
                # sustained-throughput collapse (scripted offline)
                "profiler_overhead_fraction": obs_overhead[
                    "profiler_overhead_fraction"
                ],
                "anomaly_detection_lag_batches": obs_overhead[
                    "anomaly_detection_lag_batches"
                ],
                # ladder #14 hoist (ISSUE 15): the hub-failover
                # blackout window — wall seconds from the primary-hub
                # kill to the first post-promotion committed admit
                # (conservative admission engaged during it, full-rate
                # admit after it, asserted inside the ladder) — and
                # the e2e p99 of pods bound inside that window
                "hub_failover_blackout_s": hub_failover[
                    "hub_failover_blackout_s"
                ],
                "hub_failover_p99_latency_s": hub_failover[
                    "hub_failover_p99_latency_s"
                ],
                # ladder #15 hoist (ISSUE 17): gang-member binds/sec
                # through the gang gate's park/assemble/atomic-commit
                # path (split-wave arrivals, zero partial gangs and
                # one commit per gang asserted inside the ladder) and
                # the per-gang first-member-to-commit p99
                "gang_pods_per_sec": gang["gang_pods_per_sec"],
                "gang_time_to_full_p99_s": gang[
                    "gang_time_to_full_p99_s"
                ],
                # ladder #16 hoist (ISSUE 19): the convex-relaxation
                # mega-planner — relaxed plan solve wall time at the
                # 512k x 102.4k plan shape (>= 10x over the auction's
                # plan solve asserted inside the ladder), the post-
                # repair pack objective ratio vs the auction plan
                # (>= 0.95 asserted), and the 2M-pod global plan rate
                # under the HBM budget with end-state validity
                "relax_plan_seconds": megaplan["relax_plan_seconds"],
                "relax_objective_ratio": megaplan[
                    "relax_objective_ratio"
                ],
                "megaplan_pods_per_sec": megaplan[
                    "megaplan_pods_per_sec"
                ],
                # ladder #17 hoist (ISSUE 20): the fleet-tier backlog
                # drain — the 512k backlog partitioned by the global
                # relax plan and drained by N replica processes
                # claiming epoch-fenced hub drain leases — the merged
                # fleet drain rate and its speedup over the
                # sole-owner drain_backlog arm (>= 1.5x asserted
                # inside the ladder, with fleet-wide end-state
                # validity: every pod bound exactly once)
                "fleet_drain_pods_per_sec": fleet_drain[
                    "fleet_drain_pods_per_sec"
                ],
                "fleet_drain_speedup": fleet_drain[
                    "fleet_drain_speedup"
                ],
                "vs_baseline": round(headline / BAND_TOP_PODS_PER_SEC, 2),
                "baseline_note": (
                    "vs_baseline divides by the TOP of the reference's "
                    "in-proc band (5k pods/s); vs_api_bound uses the "
                    "~300 pods/s sustained API-bound figure"
                ),
                "vs_api_bound": round(headline / API_BOUND_PODS_PER_SEC, 2),
                "ladders": ladders,
            }
        )
    )


if __name__ == "__main__":
    main()
