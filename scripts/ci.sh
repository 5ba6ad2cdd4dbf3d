#!/usr/bin/env bash
# CI gate: the correctness/perf layers in order of cost —
#   1. static analysis (full Analyzer v2: per-module TPU001..MET001 plus
#      the project rules LOCK002/FENCE001/RETRY001/TPU004/MET002, the
#      suppression-debt ratchet, and the lock-order artifact drift
#      check; findings uploaded as SARIF + JSON artifacts; budgeted at
#      < 10 s wall so the gate stays instant)
#   2. tier-1 tests   (the driver's selection: tests/ minus the soak
#      marker, six xdist workers, one file per worker at a time)
#   3. sim smokes     (one fixed-seed run per scenario profile, plus a
#      determinism self-check on the flagship churn profile)
#   4. obs smoke      (journaled fixed-seed sim -> JSONL schema check ->
#      explain one pod from the recorded trace)
#
# Usage: scripts/ci.sh            # everything
#        SKIP_TESTS=1 scripts/ci.sh   # lint + sim only (fast local loop)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== lint: static analyzer (project rules + ratchet + lock-order) =="
# one invocation does everything: findings as JSON (stdout -> artifact),
# SARIF artifact, suppression-debt ratchet, lock-order drift check.
# Wall-time budget: the analyzer must stay under 10 s or it stops being
# the gate everyone runs first.
mkdir -p artifacts
SECONDS=0
python scripts/lint.py --json --sarif artifacts/analysis.sarif \
    --ratchet --check-lock-order > artifacts/analysis.json
lint_elapsed=$SECONDS
echo "-- analyzer wall time: ${lint_elapsed}s (budget 10s) --"
if [ "$lint_elapsed" -ge 10 ]; then
    echo "LINT BUDGET: analyzer took ${lint_elapsed}s (>= 10s)"
    exit 1
fi

if [ -z "${SKIP_TESTS:-}" ]; then
    echo "== tier-1 tests =="
    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p xdist -n 6 --dist loadfile -p no:randomly
fi

echo "== sim smokes (fixed seed, every profile) =="
for profile in churn_heavy bind_storms node_flaps preemption_pressure \
               extender_flaky permit_stalls; do
    echo "-- $profile --"
    python -m kubernetes_tpu.sim --seed 0 --cycles 6 --profile "$profile"
done

echo "== sim determinism self-check =="
python -m kubernetes_tpu.sim --seed 0 --cycles 6 --profile churn_heavy \
    --selfcheck

echo "== pipelined hard-shape sim smoke =="
# churn_heavy now generates spread/anti/ports arrivals, so this fixed-seed
# run drives the occupancy-carrying pipelined path (hard shapes no longer
# drain to the synchronous loop) under delete/label churn; --selfcheck
# re-runs it and asserts byte-identical traces + journal digest. The
# preemption_pressure run covers the pipelined loop under PostFilter/
# nominated-pod traffic the same way.
python -m kubernetes_tpu.sim --seed 1 --cycles 8 --profile churn_heavy \
    --selfcheck
python -m kubernetes_tpu.sim --seed 1 --cycles 8 \
    --profile preemption_pressure --selfcheck

echo "== streaming dispatcher smoke =="
# sustained_stream: the high-arrival profile driving run_streaming —
# the device-resident solve loop with cross-batch occupancy chaining,
# per-slot fence epochs, and the completion thread; --selfcheck proves
# the whole loop byte-deterministic (the completion thread only warms
# transfers). churn_heavy re-driven through --dispatcher streaming
# covers slot discards + the livelock backstop under delete/label
# churn, and its trace digest is byte-compared at --mesh-devices 8 vs
# 1 (the PR 5 device-count-invariance convention, now through the
# chained stream dispatch). Greps pin the discard machinery within
# bounds: sustained_stream must never engage the livelock backstop
# (fallbacks=0 — the backstop is a last resort, not the steady state),
# and the churn run must actually exercise per-slot discards
# (stream_discards >= 1) while staying fallback-bounded (single
# digit). solver_flaky / crash_restart / fleet_mixed re-drive through
# the streaming dispatcher so degraded mode, restart recovery, and the
# fleet tier are proven to survive the refactor.
stream_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 8 \
    --profile sustained_stream --selfcheck)
echo "$stream_out"
echo "$stream_out" | grep -qE "fallbacks=0 " \
    || { echo "STREAM SMOKE: sustained_stream engaged the livelock backstop"; exit 1; }
churn_stream=$(python -m kubernetes_tpu.sim --seed 1 --cycles 8 \
    --profile churn_heavy --dispatcher streaming --selfcheck)
echo "$churn_stream"
echo "$churn_stream" | grep -qE "stream_discards=[1-9][0-9]* " \
    || { echo "STREAM SMOKE: churn never discarded a stream slot (vacuous fences)"; exit 1; }
echo "$churn_stream" | grep -qE "fallbacks=[0-9] " \
    || { echo "STREAM SMOKE: churn backstop out of bounds"; exit 1; }
stream_mesh_digest=$(python -m kubernetes_tpu.sim --seed 0 --cycles 6 \
    --profile sustained_stream --mesh-devices 8 | grep -o 'trace_digest=[0-9a-f]*')
stream_one_digest=$(python -m kubernetes_tpu.sim --seed 0 --cycles 6 \
    --profile sustained_stream | grep -o 'trace_digest=[0-9a-f]*')
if [ "$stream_mesh_digest" != "$stream_one_digest" ] || [ -z "$stream_mesh_digest" ]; then
    echo "STREAM MULTICHIP DIVERGENCE: mesh=$stream_mesh_digest vs 1-device=$stream_one_digest"
    exit 1
fi
echo "-- streaming mesh-vs-1-device trace digests identical: $stream_mesh_digest --"
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile solver_flaky \
    --dispatcher streaming --selfcheck
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile crash_restart \
    --dispatcher streaming --selfcheck
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile fleet_mixed \
    --fleet 2 --dispatcher streaming --selfcheck

echo "== backlog drain smoke: HBM-budget-planned chunked streaming =="
# backlog_drain: a seeded mega-backlog (sim-relative) drained at cycle 0
# through Scheduler.drain_backlog — chunk size planned by the HBM budget
# model (solver/budget.py), chunks streamed down the ring with cross-
# batch occupancy chaining, then delete churn + fresh arrivals. The
# profile forces the budget planner to auto-split (budget one byte
# below the base chunk's own estimate), so the grep pins the split
# path engaging non-vacuously (budget_splits >= 1); the drain must
# never trip the livelock backstop (fallbacks=0). --selfcheck proves
# the whole budget-plan -> chunk -> chain pipeline byte-deterministic.
backlog_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 4 \
    --profile backlog_drain --selfcheck)
echo "$backlog_out"
echo "$backlog_out" | grep -qE "budget_splits=[1-9]" \
    || { echo "BACKLOG SMOKE: the budget auto-split never engaged"; exit 1; }
echo "$backlog_out" | grep -qE "fallbacks=0 " \
    || { echo "BACKLOG SMOKE: the drain engaged the livelock backstop"; exit 1; }
echo "$backlog_out" | grep -qE "stream_chained=[0-9]+" \
    || { echo "BACKLOG SMOKE: no chain accounting in the footer"; exit 1; }

echo "== megaplan smoke: convex-relaxation warm-started drain =="
# megaplan: the backlog drain warm-starts — one relaxed global solve
# (solver/relax.py: dual ascent + deterministic rounding) ranks the
# whole active queue before the first chunk pops — and the harness's
# probe replays the relax+repair plan against the sequential oracle.
# check_megaplan asserts engagement, feasibility, and the objective-
# ratio floor; the greps pin each leg non-vacuously off the footer so
# a silently-disconnected warm-start (ranked=0) or a never-iterating
# relaxation cannot pass. --selfcheck proves the probe + warm-start +
# drain pipeline byte-deterministic (counts and rounded ratios only
# ride the footer).
mega_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 4 \
    --profile megaplan --selfcheck)
echo "$mega_out"
echo "$mega_out" | grep -qE "megaplan: pods=[1-9].* ranked=[1-9]" \
    || { echo "MEGAPLAN SMOKE: warm-start ranked no backlog pods"; exit 1; }
echo "$mega_out" | grep -qE "megaplan: .*iterations=[1-9]" \
    || { echo "MEGAPLAN SMOKE: the relaxation never iterated"; exit 1; }
echo "$mega_out" | grep -qE "megaplan: .*plan_valid=True" \
    || { echo "MEGAPLAN SMOKE: relaxed plan failed oracle feasibility"; exit 1; }

echo "== tuning smoke: closed-loop auto-tuning convergence =="
# tuning_convergence: the hill-climb controllers (stream_depth /
# pipeline_split, sim-sized evaluation windows) must probe both
# directions, settle, detect the mid-drive workload shift (arrivals
# roughly double at cycle 12), and re-settle — all under the tuning
# invariant (engaged / settled / zero guardrail breaches / bounded
# moves / shift detected). The greps pin settled=1 and
# guardrail_breaches=0 non-vacuously; --selfcheck proves the whole
# controller stack byte-deterministic (pure host python over the
# virtual clock). The backlog_drain --tuning run exercises the
# drain-chunk controller under the HBM budget guardrail.
tune_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 24 \
    --profile tuning_convergence --selfcheck)
echo "$tune_out"
echo "$tune_out" | grep -qE "settled=1 " \
    || { echo "TUNING SMOKE: controllers never settled"; exit 1; }
echo "$tune_out" | grep -qE "guardrail_breaches=0 " \
    || { echo "TUNING SMOKE: a tuner-applied value breached its guardrail"; exit 1; }
echo "$tune_out" | grep -qE "shifts=[1-9]" \
    || { echo "TUNING SMOKE: the workload shift was never detected"; exit 1; }
python -m kubernetes_tpu.sim --seed 0 --cycles 16 --profile backlog_drain \
    --tuning --selfcheck

echo "== chaos smoke: solver fallback ladder + poison quarantine =="
# solver_flaky: every device-tier solve fails during the fault window
# (virtual t in [2,5)), then heals. The run's resilience invariant
# asserts the fallback ladder engaged (breaker tripped, batches kept
# binding at degraded tiers down to the pure-host greedy), zero pods
# were lost (lost-pod + journal-completeness invariants), and the
# breaker RE-CLOSED to the top tier after the window — the footer's
# breaker-state summary is the assertion target. poison_pods: a
# fraction of arrivals deterministically break the solve at EVERY
# tier; the bisection must isolate exactly them into terminal
# quarantine while the rest of each batch proceeds. --selfcheck
# re-runs each drive and byte-compares traces + journal digest.
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile solver_flaky \
    --selfcheck
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile poison_pods \
    --selfcheck

echo "== crash smoke: restart recovery + partition-safe fencing =="
# crash_restart: the scheduler is killed mid-batch (pods assumed +
# approved, nothing bound) and a fresh incarnation recovers on the
# same ClusterState. The run's invariants assert zero lost pods
# (bounded recovery runs the lost-pod check the moment the new
# incarnation constructs), cross-incarnation journal completeness
# (terminal `recovered` records close the dead incarnation's dangling
# histories), and zero double-binds; --selfcheck proves the whole
# crash/restart boundary byte-deterministic. The greps pin the faults
# actually engaging — a run that never crashed or never recovered
# would pass the invariants vacuously.
crash_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 8 \
    --profile crash_restart --selfcheck)
echo "$crash_out"
echo "$crash_out" | grep -q "incarnations=2 crashes=1" \
    || { echo "CRASH SMOKE: the mid-batch kill never fired"; exit 1; }
echo "$crash_out" | grep -qE "recovered_records=[1-9]" \
    || { echo "CRASH SMOKE: recovery journaled no recovered records"; exit 1; }
# hub_partition: the last replica is partitioned from the occupancy
# hub with its lease observed stale — survivors revoke its commit
# fence and 100% of the zombie's bind attempts must reject with
# Conflict (the all-zombie-commits-fenced invariant), while
# conservative admission under aged-out rows rejects cross-shard-risky
# placements instead of risking overcommit. The grep pins >= 1 fenced
# zombie commit (and zero landed).
part_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 8 \
    --profile hub_partition --fleet 2 --selfcheck)
echo "$part_out"
echo "$part_out" | grep -qE "fenced_commits=[1-9][0-9]* zombie_binds_while_fenced=0" \
    || { echo "CRASH SMOKE: no fenced zombie commit (or one landed)"; exit 1; }

echo "== rebalance smoke: fragmentation profile =="
# fragmentation: heavy plain arrivals + heavy deletes carve the cluster
# into a sparse scatter; the idle-cycle rebalancer must detect it, plan
# through the pack-objective auction, and migrate pods through the REAL
# evict -> requeue -> re-bind path under the churn budget and the PDB
# gate. The run's rebalance invariant asserts budget-never-exceeded,
# zero PDB overruns, and packing-non-regressing across passes;
# --selfcheck proves the whole loop byte-deterministic. The greps pin
# the loop actually engaging — a run with no migrations would pass the
# invariants vacuously.
reb_out=$(python -m kubernetes_tpu.sim --seed 1234 --profile fragmentation \
    --selfcheck)
echo "$reb_out"
echo "$reb_out" | grep -qE "migrations_completed=[1-9]" \
    || { echo "REBALANCE SMOKE: no completed migration"; exit 1; }
echo "$reb_out" | grep -qE "over_budget=0" \
    || { echo "REBALANCE SMOKE: a cycle exceeded the churn budget"; exit 1; }
echo "$reb_out" | grep -qE "pdb_overruns=0" \
    || { echo "REBALANCE SMOKE: an eviction violated a PDB"; exit 1; }

echo "== gang smoke: all-or-nothing pod groups + heterogeneity =="
# the gang profile mixes pod-group arrivals (sizes 2-3, heterogeneous
# accelerator/workload classes feeding the effective-throughput
# objective) with one deliberately SHORT gang (min-member one above
# what ever arrives) under delete churn. The run's invariant layer
# asserts no pod group is EVER partially bound (check_no_partial_gangs
# after every drive) plus journal completeness through the
# gang_incomplete/quarantined outcomes; the greps pin the machinery
# engaging non-vacuously — >= 1 atomic gang commit, zero partial
# gangs at finish, and the short gang quarantined as a unit.
# --selfcheck proves the whole gate/round/commit pipeline
# byte-deterministic. gang_crash kills the scheduler at the exact
# assumed+staged-but-uncommitted window (crash between stage and
# commit): the fresh incarnation's rollback must reassemble
# half-staged gangs with zero partial binds. gang_replica_loss drives
# the same arrivals through a 2-replica fleet (every member stages
# through the fenced hub CAS) and kills one replica mid-drive — the
# survivor re-owns the shard with the partial-gang invariant still
# fleet-wide.
gang_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 12 \
    --profile gang --selfcheck)
echo "$gang_out"
echo "$gang_out" | grep -qE "gang: commits=[1-9]" \
    || { echo "GANG SMOKE: no atomic gang commit ever landed"; exit 1; }
echo "$gang_out" | grep -qE "partial_gangs=0 " \
    || { echo "GANG SMOKE: a pod group was partially bound"; exit 1; }
echo "$gang_out" | grep -qE "quarantined_gangs=[1-9]" \
    || { echo "GANG SMOKE: the short gang was never quarantined"; exit 1; }
python -m kubernetes_tpu.sim --seed 0 --cycles 12 --profile gang_crash \
    --selfcheck
gang_fleet=$(python -m kubernetes_tpu.sim --seed 0 --cycles 12 \
    --profile gang_replica_loss --fleet 2 --selfcheck)
echo "$gang_fleet"
echo "$gang_fleet" | grep -qE "partial_gangs=0 " \
    || { echo "GANG SMOKE: fleet replica loss left a partial gang"; exit 1; }

echo "== telemetry smoke: anomaly storm -> capture -> offline replay =="
# anomaly_storm: healthy warmup cycles, then a solver-fault window
# trips the breaker and collapses pods/s — the sentinel must fire
# (edge + regression rules), every fire must capture a replay bundle,
# and each carry-clean bundle must re-execute offline to BIT-IDENTICAL
# assignments (the run's telemetry invariant loads + replays every
# written bundle). --selfcheck re-runs WITHOUT the bundle dir and
# byte-compares summaries: capture EVENTS are part of the
# deterministic record, bundle writing is a pure side effect. The
# greps pin the loop engaging non-vacuously off the footer line; the
# explicit `obs replay` exercises the operator CLI end-to-end.
tele_dir=$(mktemp -d)
tele_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 12 \
    --profile anomaly_storm --bundle-dir "$tele_dir" --selfcheck)
echo "$tele_out"
echo "$tele_out" | grep -qE "telemetry: anomalies=[1-9]" \
    || { echo "TELEMETRY SMOKE: the sentinel never fired"; exit 1; }
echo "$tele_out" | grep -qE "bundles_captured=[1-9]" \
    || { echo "TELEMETRY SMOKE: no anomaly captured a bundle"; exit 1; }
tele_bundle=$(ls -d "$tele_dir"/bundle-* | head -1)
replay_out=$(python -m kubernetes_tpu.obs replay "$tele_bundle")
echo "$replay_out"
echo "$replay_out" | grep -q "assignments bit-identical" \
    || { echo "TELEMETRY SMOKE: offline replay diverged"; exit 1; }
rm -rf "$tele_dir"

echo "== fleet smoke: 2-replica sharded drive =="
# two active replicas sharding one cluster (shard-filtered watches,
# cross-shard occupancy exchange, handoff protocol) under the
# fleet_mixed hard-shape churn, with the no-global-overcommit and
# fleet journal-completeness invariants enabled; --selfcheck re-runs
# the drive and byte-compares the per-replica journal digests. The
# replica_loss run kills one replica mid-drive and requires its shard
# re-owned with every orphaned pod reaching a terminal outcome.
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile fleet_mixed \
    --fleet 2 --selfcheck
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile replica_loss \
    --fleet 2

echo "== fleet drain smoke: hub-coordinated backlog drain (ISSUE 20) =="
# fleet_backlog_drain: a seeded backlog partitioned by the coordinator's
# global relax plan into per-replica drain leases (hub ledger), drained
# by a 3-replica fleet with ONE replica killed mid-drain at cycle 1 —
# its outstanding lease must RETURN to the ledger (retire runs
# return_leases) and be re-claimed by a survivor, so no backlog pod
# drains twice and none is lost. The greps pin the fault engaging
# non-vacuously off the `fleet_drain:` footer line (the header's
# lost= field is the killed REPLICA, so every grep anchors on the
# footer key): >= 1 lease reassigned, zero pods lost fleet-wide, zero
# double-binds. --selfcheck proves the whole coordinator -> lease ->
# drain -> kill -> reassign pipeline byte-deterministic.
fdrain_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 12 \
    --profile fleet_backlog_drain --fleet 3 --selfcheck)
echo "$fdrain_out"
echo "$fdrain_out" | grep -qE "fleet_drain:.* leases_reassigned=[1-9]" \
    || { echo "FLEET DRAIN SMOKE: the mid-drain kill never returned a lease"; exit 1; }
echo "$fdrain_out" | grep -qE "fleet_drain:.* lost=0" \
    || { echo "FLEET DRAIN SMOKE: a backlog pod was lost fleet-wide"; exit 1; }
echo "$fdrain_out" | grep -qE "fleet_drain:.* double_bind=0" \
    || { echo "FLEET DRAIN SMOKE: a pod drained through two leases"; exit 1; }
echo "$fdrain_out" | grep -qE "fleet_drain:.* residual=[1-9]" \
    || { echo "FLEET DRAIN SMOKE: the serialized residual cohort never engaged"; exit 1; }

echo "== fleet smoke: gRPC-backed occupancy hub =="
# the same fault profiles re-driven with the hub served behind a
# localhost bulk gRPC server (--hub-grpc): every stage / fenced
# compare-and-stage / view crosses a real socket with the tensorcodec
# wire framing and the typed status-code conflict mapping
# (ABORTED/FAILED_PRECONDITION never retried). replica_loss proves
# shard re-owning + orphan adoption survive the wire; hub_partition
# re-pins the PR 8 contract over it — 100% of the fenced zombie's
# commits reject (zombie_binds_while_fenced=0) AND conservative
# admission under aged-out rows engages (stale_rejections >= 1).
# --selfcheck byte-compares per-replica journals across two runs (RPC
# wall time never enters the virtual clock; the write-behind row
# buffer re-times hub version bumps vs the in-process drive, so the
# cross-transport contract is invariants, not byte equality).
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile replica_loss \
    --fleet 2 --hub-grpc --selfcheck
part_grpc=$(python -m kubernetes_tpu.sim --seed 0 --cycles 8 \
    --profile hub_partition --fleet 2 --hub-grpc --selfcheck)
echo "$part_grpc"
echo "$part_grpc" | grep -qE "fenced_commits=[1-9][0-9]* zombie_binds_while_fenced=0" \
    || { echo "GRPC HUB SMOKE: no fenced zombie commit (or one landed)"; exit 1; }
echo "$part_grpc" | grep -qE "stale_rejections=[1-9]" \
    || { echo "GRPC HUB SMOKE: conservative admission never engaged"; exit 1; }

echo "== hub HA smoke: epoch-fenced failover chaos (ISSUE 15) =="
# the hub_failover profile kills the PRIMARY occupancy hub mid-drive:
# a standby replicated from the primary's op log must promote at the
# next lease epoch WITHOUT operator action, replicas must fail over
# (endpoint rotation + epoch-advance detection + forced wholesale
# republish), conservative admission must cover the blackout, and the
# resurrected OLD primary must keep serving reads while 100% of its
# replica-facing writes reject with the typed HubDeposed. A
# deterministic reply-loss-after-apply injection proves the idempotent
# flush dedup inside the chaos loop (the write-behind double-apply
# hazard's regression). Greps pin each fault engaging non-vacuously:
# failovers==1, stale-primary writes rejected >= 1, dedup hits >= 1,
# zero journal lines lost; zero lost rows/handoffs ride the run's own
# overcommit/lost-pod/journal invariants. Driven over the REAL gRPC
# hub pair; --selfcheck proves byte-determinism across runs.
ha_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 12 \
    --profile hub_failover --fleet 2 --hub-grpc --selfcheck)
echo "$ha_out"
echo "$ha_out" | grep -qE "hub_ha: failovers=1 epoch=2" \
    || { echo "HUB HA SMOKE: expected exactly one failover to epoch 2"; exit 1; }
echo "$ha_out" | grep -qE "stale_writes_rejected=[1-9]" \
    || { echo "HUB HA SMOKE: the deposed primary never rejected a write"; exit 1; }
echo "$ha_out" | grep -qE "dedup_hits=[1-9]" \
    || { echo "HUB HA SMOKE: the idempotent flush dedup never engaged"; exit 1; }
echo "$ha_out" | grep -qE "journal_missing=0" \
    || { echo "HUB HA SMOKE: the failover lost hub journal lines"; exit 1; }
echo "$ha_out" | grep -qE "stale_rejections=[1-9]" \
    || { echo "HUB HA SMOKE: conservative admission never covered the blackout"; exit 1; }

echo "== multichip: 8-device forced-host mesh smoke =="
# sharded-vs-unsharded exact-path equivalence on an 8-way virtual CPU
# mesh (conftest.py forces the device count before jax initializes):
# ExactSolver.solve(mesh=...) standalone + the full Scheduler session
# path must be bit-identical to the single-device solve, and padding
# rows must never take a binding.
XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
    python -m pytest tests/test_sharding.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
# one fixed-seed sim drive against the sharded solve, its trace digest
# byte-compared against the single-device run with identical flags —
# the device-count-invariance contract end to end through the sim
mesh_out=$(python -m kubernetes_tpu.sim --seed 0 --cycles 6 \
    --profile churn_heavy --mesh-devices 8)
echo "$mesh_out"
mesh_digest=$(echo "$mesh_out" | grep -o 'trace_digest=[0-9a-f]*')
one_digest=$(python -m kubernetes_tpu.sim --seed 0 --cycles 6 \
    --profile churn_heavy | grep -o 'trace_digest=[0-9a-f]*')
if [ "$mesh_digest" != "$one_digest" ] || [ -z "$mesh_digest" ]; then
    echo "MULTICHIP DIVERGENCE: mesh=$mesh_digest vs 1-device=$one_digest"
    exit 1
fi
echo "-- mesh-vs-1-device trace digests identical: $mesh_digest --"

echo "== obs smoke: journaled sim -> schema check -> explain =="
obs_journal=$(mktemp /tmp/ktpu_obs_journal.XXXXXX.jsonl)
python -m kubernetes_tpu.sim --seed 0 --cycles 6 --profile churn_heavy \
    --journal "$obs_journal"
python -m kubernetes_tpu.obs validate "$obs_journal"
obs_pod=$(python -c "import json,sys; print(json.loads(open(sys.argv[1]).readline())['pod'])" "$obs_journal")
python -m kubernetes_tpu.obs explain "$obs_pod" --trace "$obs_journal"
rm -f "$obs_journal"

echo "== obs fleet smoke: cross-replica explain over the gRPC hub =="
# the handoff-FORCING fleet profile drives a 2-replica fleet against
# the gRPC-served occupancy hub: replicas ship bounded journal
# segments to the hub's aggregation surface piggybacked on their
# write-behind flushes, handoff rows carry each pod's journey trace
# across the wire, and `obs explain --fleet` reconstructs the full
# enqueue→handoff→re-admit→bind chain with the PR 8 merge rules.
# --selfcheck proves the hub-aggregated journal (and therefore the
# explain output, a pure function of it) byte-identical across runs.
# The greps pin the tentpole non-vacuously: a handed-off pod must
# exist, its history must span >= 2 replicas under ONE journey trace,
# and it must reach a terminal outcome.
fleet_journal=$(mktemp /tmp/ktpu_fleet_journal.XXXXXX.jsonl)
python -m kubernetes_tpu.sim --seed 0 --cycles 8 --profile fleet_handoff \
    --fleet 2 --hub-grpc --journal "$fleet_journal" --selfcheck
python -m kubernetes_tpu.obs validate "$fleet_journal"
handoff_pod=$(python - "$fleet_journal" <<'PYEOF'
import collections, json, sys
by_pod = collections.defaultdict(set)
for ln in open(sys.argv[1]):
    rec = json.loads(ln)
    by_pod[rec["pod"]].add(rec.get("replica"))
crossed = sorted(p for p, reps in by_pod.items() if len(reps) > 1)
if not crossed:
    sys.exit("OBS FLEET SMOKE: no pod was handed off between replicas")
print(crossed[0])
PYEOF
)
explain_out=$(python -m kubernetes_tpu.obs explain "$handoff_pod" \
    --fleet --trace "$fleet_journal")
echo "$explain_out"
echo "$explain_out" | grep -qE "replicas: r[0-9]+ -> r[0-9]+" \
    || { echo "OBS FLEET SMOKE: history does not span >= 2 replicas"; exit 1; }
echo "$explain_out" | grep -q "one journey trace" \
    || { echo "OBS FLEET SMOKE: the journey shattered into multiple traces"; exit 1; }
echo "$explain_out" | grep -q "terminal outcome:" \
    || { echo "OBS FLEET SMOKE: the handed-off pod never reached a terminal outcome"; exit 1; }
rm -f "$fleet_journal" "$fleet_journal".r*

echo "== metrics doc drift gate =="
python -m kubernetes_tpu.metrics --check

echo "CI gate: OK"
