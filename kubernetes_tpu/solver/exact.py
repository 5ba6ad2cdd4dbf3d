"""Exact-parity solver: a lax.scan over pods in queue order (SURVEY.md §8.4
mode 1).

This replaces the reference's scheduleOne hot path
(pkg/scheduler/schedule_one.go#schedulePod -> findNodesThatFitPod ->
prioritizeNodes -> selectHost) with one compiled program: each scan step is a
dense filter-mask + score over ALL nodes at once (the per-(pod,node) Go
interface-call overhead becomes one fused XLA loop body), and the
assume-pod state mutation (cache.AssumePod) becomes an in-carry scatter so
the next step sees updated node state — preserving the reference's strict
pod-by-pod sequential semantics, which is what "binding parity" means.

Filter pipeline per step (runtime/framework.go#RunFilterPlugins, fused):
  NodeResourcesFit ∧ static class mask (NodeName ∧ NodeUnschedulable ∧
  TaintToleration ∧ NodeAffinity, precompiled per pod class) ∧ NodePorts
  (occupancy matvec over the port vocab) ∧ PodTopologySpread hard
  constraints (segment reductions over domain ids).

Score pipeline (runtime/framework.go#RunScorePlugins: score, normalize,
weight — default-profile weights from apis/config/v1/default_plugins.go):
  1·LeastAllocated + 1·BalancedAllocation + 3·TaintToleration(norm reverse)
  + 2·NodeAffinity(norm) + 1·ImageLocality + 2·PodTopologySpread(norm).

selectHost tie-break: the reference reservoir-samples uniformly among
max-score ties with an unseeded RNG (schedule_one.go#selectHost). Bit-parity
is impossible; we offer:
- "random": uniform among ties from a seeded PRNG key (documented divergence)
- "first":  lowest node index among ties (deterministic, used by parity tests)
Either way the pick is provably inside the reference's tie set, which is the
parity definition from SURVEY.md §8.8.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics
from ..ops import fastmath
from ..ops import interpod as ip
from ..ops import noderesources as nr
from ..ops import plugins as pl
from ..ops import spread as sp
from ..ops.domains import dense_form, domain_max, domain_sum
from ..parallel.sharding import (
    REPLICATED_TABLE_NAMES,
    mesh_fingerprint,
    placers,
    replicated,
)
from ..tensorize.interpod import InterpodTensors, trivial_interpod_tensors
from ..tensorize.plugins import (
    PortTensors,
    StaticPluginTensors,
    trivial_port_tensors,
    trivial_static_tensors,
)
from ..tensorize.spread import SpreadTensors, trivial_spread_tensors
from ..tensorize.schema import MEM_IDX, NodeBatch, PodBatch

TIE_RANDOM = "random"
TIE_FIRST = "first"

# jax.named_scope names on the served path's device programs, in one
# place: the filter/score parts carry upstream's plugin names (they line
# up with scheduler_plugin_execution_duration_seconds{plugin}), the rest
# name the scan step's and the wire's own parts. A scope changes op_name
# metadata only; the benchmark's scan_* metrics read it off the device
# trace (benchmarks/lib/span_attrib.py).
SCOPES = (
    "NodeResourcesFit",
    "NodePorts",
    "PodTopologySpread",
    "InterPodAffinity",
    "Score",
    "select",
    "assume",
    "grouped_fast",
    "grouped_slow",
    "unpack",
    "pack",
)


@dataclass(frozen=True)
class ExactSolverConfig:
    tie_break: str = TIE_RANDOM
    seed: int = 0
    # Score-plugin weights; defaults mirror the default profile
    # (apis/config/v1/default_plugins.go): TaintToleration 3, NodeAffinity 2,
    # PodTopologySpread 2, Fit/Balanced/ImageLocality 1.
    fit_weight: int = 1
    balanced_weight: int = 1
    # NodeResourcesFitArgs.scoringStrategy.type: LeastAllocated (default) |
    # MostAllocated | RequestedToCapacityRatio (shape + per-resource
    # weights below)
    scoring_strategy: str = "LeastAllocated"
    # NodeResourcesFitArgs.scoringStrategy.resources weights for the two
    # scoring resources the NonZero pipeline tracks (cpu milli, memory
    # bytes); other resources are rejected with a config warning
    cpu_weight: int = 1
    mem_weight: int = 1
    # RequestedToCapacityRatio shape: ((utilization, score), ...) ascending,
    # scores 0..10 (requested_to_capacity_ratio.go)
    rtc_shape: tuple = ()
    taint_weight: int = 3
    node_affinity_weight: int = 2
    image_weight: int = 1
    spread_weight: int = 2
    interpod_weight: int = 2
    # InterPodAffinityArgs.hardPodAffinityWeight (default 1) — consumed by
    # the interpod tensorizer when building m_w rows (the scheduler passes
    # it through to build_interpod_tensors)
    hard_pod_affinity_weight: int = 1
    balanced_fdtype: str = "float32"  # float64 for bit-parity on CPU tests
    # Grouped fast path (§8.4 batched variant): chunk size for runs of
    # identical pods; 0/1 disables. Engages for plain batches and — via
    # the kind-2/3 quota chunks — for hard-only spread and anti-only
    # interpod batches (grouped_eligible + _chunk_kinds hold the exact
    # conditions); soft spread / preferred terms / nominated pods route
    # through the per-pod scan. With tie_break="random" the grouped path
    # places q DISTINCT tie-set nodes per iteration (without replacement)
    # while the per-pod scan samples ties with replacement: every grouped
    # result is a sequentially valid outcome, but the placement
    # DISTRIBUTION differs from the ungrouped solver for the same seed, so
    # random-mode runs are not reproducible across group_size settings.
    # tie_break="first" is bit-identical either way.
    group_size: int = 64
    # Compact wire mode: when every chunk of a grouped batch is uniform
    # (host-verified; see _solve_grouped), upload one representative row
    # per chunk instead of [P, *] per-pod arrays. Results are bit-identical
    # to the full upload; this knob exists as an escape hatch and for the
    # equivalence tests.
    compact_wire: bool = True
    # plugins.filter.disabled for this profile (runtime/framework.go):
    # names whose Filter stage is skipped. Static-mask plugins are handled
    # by the tensorizer; these flags gate the in-scan filters. A non-empty
    # set also disables the grouped fast path (rare config; keep it exact).
    disabled_filters: tuple = ()
    # NodeAffinityArgs.addedAffinity, parsed into an api.objects.NodeAffinity
    # (consumed by the tensorizer via the scheduler; kept here so profile
    # construction is one object)
    added_affinity: object = None
    # PodTopologySpreadArgs.defaultingType: System (upstream default —
    # service-selected pods without explicit constraints get soft
    # zone/hostname spreading) | List (no cluster defaults)
    spread_defaulting: str = "System"
    # Pallas-kernel tier (config key tpuSolver.pallas, VERDICT r5
    # missing #8): route the InterPodAffinity (term, domain) count
    # aggregation through ops/pallas_kernels.domain_counts_pallas (the
    # MXU one-hot-contraction kernel) instead of the flattened
    # segment_sum, inside the production per-pod scan. The kernel
    # compiles on a TPU with x64 on (pallas_kernels.py module
    # docstring); default OFF because the identity fast path already
    # removed the hot hostname case and no benchmark cell has measured
    # the scan step with the flag on. On non-TPU backends the kernel
    # runs in interpret mode, which is what the tier-1 parity tests
    # exercise. The ident fast path still wins when the tensorizer
    # proves unique domains.
    pallas: bool = False


def grouped_eligible(
    cfg: "ExactSolverConfig",
    pod_pad: int,
    node_pad: int,
    use_spread: bool,
    use_interpod: bool,
    use_nominated: bool = False,
    spread_groupable: bool = False,
    interpod_groupable: bool = False,
) -> bool:
    """Single source of truth for the grouped fast path's dispatch
    condition — the scheduler consults it when choosing the pod-axis
    padding bucket, and ExactSolver.solve when picking the executable, so
    the two can never drift into padding-without-grouping. Nominated-pod
    load (rare, preemption aftermath) routes through the per-pod scan.

    ``spread_groupable``/``interpod_groupable``: the batch-level facts
    that make the kind-2/3 quota chunks possible (hard-only spread with no
    soft constraints; anti-affinity-only interpod). Solve derives them
    from the tensors; the scheduler mirrors them from the pods for its
    padding decision — a mismatch degrades to padded-slow, never to a
    wrong result (unqualified chunks replay the full pipeline)."""
    return (
        cfg.group_size > 1
        and not cfg.disabled_filters
        and (not use_spread or spread_groupable)
        and (not use_interpod or interpod_groupable)
        and not use_nominated
        and pod_pad % cfg.group_size == 0
        and node_pad >= cfg.group_size  # order[:group] gather needs N >= G
    )


def _fit_scorer(scoring_strategy, rtc_shape):
    """Scoring-strategy dispatch shared by the per-pod pipeline and the
    grouped fast path (resource_allocation.go scorer selection). All
    callers evaluate per-step-class shapes ([R, N] / [R, 2N]) where the
    kernels' default float-estimate exact division wins
    (ops/fastmath.py)."""
    if scoring_strategy == "RequestedToCapacityRatio" and rtc_shape:
        # ktpu: ignore[TPU001]: rtc_shape is a static argname, coerced once at trace time on Python ints
        sx = jnp.asarray([int(p[0]) for p in rtc_shape], dtype=jnp.int64)
        # ktpu: ignore[TPU001]: rtc_shape is a static argname, coerced once at trace time on Python ints
        sy = jnp.asarray([int(p[1]) for p in rtc_shape], dtype=jnp.int64)
        return lambda requested, alloc, w: nr.rtc_score(
            requested, alloc, w, sx, sy
        )
    if scoring_strategy == "MostAllocated":
        return nr.most_allocated_score
    return nr.least_allocated_score


def _mask_and_score(
    tables,
    st,
    x,
    *,
    scoring_strategy: str,
    w_cpu: int,
    w_mem: int,
    rtc_shape: tuple,
    disabled: tuple,
    w_fit: int,
    w_balanced: int,
    w_taint: int,
    w_nodeaff: int,
    w_image: int,
    w_spread: int,
    w_interpod: int,
    use_spread: bool,
    use_interpod: bool,
    d_pad: int,
    ipa_d_pad: int,
    fdtype,
    spread_soft: bool = True,
    ipa_ident: bool = False,
    ipa_score: bool = True,
    use_nominated: bool = False,
    use_nominated_ports: bool = False,
    use_extra_score: bool = False,
    pallas: bool = False,
):
    """One pod's full filter+score pipeline over all nodes against node
    state ``st`` (runtime/framework.go#RunFilterPlugins + #RunScorePlugins,
    fused). Returns ``score`` [N] int32 with -1 on infeasible lanes (the
    mask is recoverable as ``score >= 0``). Shared by the sequential scan
    step (which adds tie-break + assume scatter) and the stateless batch
    evaluator behind the extender boundary (solver/evaluate.py).

    ``spread_soft``/``ipa_ident``/``ipa_score`` are batch-static facts the
    tensorizers proved (no soft constraints; unique-domain topologies; no
    preferred terms): each one statically removes work from the compiled
    step — the measured difference is large (SURVEY §8.8: the per-pod scan
    budget is per-step microseconds, not milliseconds)."""
    alloc = tables["alloc"]
    alloc2 = alloc[: MEM_IDX + 1]  # cpu, memory rows for scoring
    weights2 = jnp.asarray([w_cpu, w_mem], dtype=alloc.dtype)
    fit_scorer = _fit_scorer(scoring_strategy, rtc_shape)
    spr = tables.get("spr")
    ipa = tables.get("ipa")
    cls = x["class_of"]

    mask = tables["static_mask"][cls] & tables["node_valid"]
    used = st["used"]
    pod_count = st["pod_count"]
    port_used = st["port_used"]
    if use_nominated:
        # addNominatedPods: nominated pods with priority >= this pod's
        # count as placed for the monotone filters; the pod's own
        # nomination (always inside its own level row) is subtracted out
        lvl = x["nom_level"]
        s = x["nominated_slot"]
        is_nom = s >= 0
        ss = jnp.maximum(s, 0)
        # nom_corr_* carries the load of nominated pods already PLACED by
        # earlier scan steps (the nominator-map removal on assume) so their
        # requests aren't counted twice — once as real used, once as
        # nominated load
        extra_u = tables["nom_used"][lvl] - st["nom_corr_used"][lvl]
        extra_c = tables["nom_cnt"][lvl] - st["nom_corr_cnt"][lvl]
        extra_u = extra_u.at[:, ss].add(-x["req"] * is_nom.astype(extra_u.dtype))
        extra_c = extra_c.at[ss].add(-is_nom.astype(extra_c.dtype))
        used = used + extra_u
        pod_count = pod_count + extra_c
        if use_nominated_ports:
            # NodePorts is as monotone as resources: nominated hostPorts
            # occupy their reserved node for lower-priority pods too
            extra_p = tables["nom_ports"][lvl] - st["nom_corr_ports"][lvl]
            extra_p = extra_p.at[:, ss].add(
                -x["pod_takes"] * is_nom.astype(extra_p.dtype)
            )
            port_used = port_used + extra_p
    if "NodeResourcesFit" not in disabled:
        with jax.named_scope("NodeResourcesFit"):
            mask = mask & nr.fit_mask(
                x["req"], x["req_mask"], alloc, used,
                pod_count, tables["max_pods"],
            )
    if "NodePorts" not in disabled:
        with jax.named_scope("NodePorts"):
            mask = mask & ~pl.ports_conflict_mask(
                x["pod_conflict"], port_used
            )
    if use_spread and "PodTopologySpread" not in disabled:
        with jax.named_scope("PodTopologySpread"):
            mask = mask & ~sp.hard_violations(
                spr, st["spr_cnt"], cls, d_pad
            )
    if use_interpod:
        with jax.named_scope("InterPodAffinity"):
            ipa_allowed, ipa_raw = ip.filter_and_score(
                ipa, st["ipa_in"], st["ipa_ex"], cls, x, ipa_d_pad,
                tables["node_valid"],
                ident=ipa_ident, score=ipa_score and w_interpod > 0,
                pallas=pallas,
            )
            if "InterPodAffinity" not in disabled:
                mask = mask & ipa_allowed

    with jax.named_scope("Score"):
        requested = nr.scoring_requested(
            x["nonzero_req"], st["nonzero_used"]
        )
        score = w_fit * fit_scorer(requested, alloc2, weights2)
        score = score + w_balanced * nr.balanced_allocation_score(
            requested, alloc2, fdtype=fdtype
        )
        score = score.astype(jnp.int32)
        if w_taint:
            score = score + w_taint * pl.normalize_score(
                tables["taint_cnt"][cls], mask, reverse=True
            )
        if w_nodeaff:
            score = score + w_nodeaff * pl.normalize_score(
                tables["nodeaff_pref"][cls], mask, reverse=False
            )
        if w_image:
            score = score + w_image * tables["image_score"][cls]
        if use_extra_score:
            # out-of-tree ScorePlugins + the gang heterogeneity objective
            # (gang/throughput.py's workload-class x accelerator-class
            # effective-throughput term), folded per class with weights
            # pre-applied — the kernel stays objective-agnostic
            score = score + tables["extra_score"][cls]
    if use_spread and w_spread and spread_soft:
        with jax.named_scope("PodTopologySpread"):
            score = score + w_spread * sp.soft_scores(
                spr, st["spr_cnt"], cls, mask, d_pad, fdtype=fdtype
            )
    if use_interpod and w_interpod and ipa_score:
        with jax.named_scope("InterPodAffinity"):
            score = score + w_interpod * ip.normalize(ipa_raw, mask)
    with jax.named_scope("Score"):
        return jnp.where(mask, score, -1)


def _make_step(
    tables,
    *,
    tie_break: str,
    **pipe_kw,
):
    """Builds the per-pod scan step (one full filter+score pipeline over all
    nodes + assume scatter). Shared by the per-pod scan and the grouped
    solver's non-uniform fallback branch."""
    alloc = tables["alloc"]
    use_spread = pipe_kw["use_spread"]
    use_interpod = pipe_kw["use_interpod"]

    def step(carry, x):
        st, k = carry
        score = _mask_and_score(tables, st, x, **pipe_kw)
        with jax.named_scope("select"):
            mask = score >= 0

            best = jnp.max(score)
            feasible = best >= 0
            ties = (score == best) & mask
            csum = jnp.cumsum(ties)
            if tie_break == TIE_RANDOM:
                k, sub = jax.random.split(k)
                n_ties = csum[-1]
                pick_rank = jax.random.randint(sub, (), 0, jnp.maximum(n_ties, 1))
            else:
                pick_rank = 0
            pick = jnp.argmax(csum > pick_rank).astype(jnp.int32)
            if pipe_kw.get("use_nominated"):
                # schedule_one.go#evaluateNominatedNode: a pod carrying a
                # nomination takes that node if it is feasible, before any
                # scoring of alternatives
                s = x["nominated_slot"]
                nom_ok = (s >= 0) & mask[jnp.maximum(s, 0)]
                pick = jnp.where(nom_ok, jnp.maximum(s, 0).astype(jnp.int32), pick)

        with jax.named_scope("assume"):
            found = feasible & x["pod_valid"]
            d = found.astype(alloc.dtype)
            di = found.astype(jnp.int32)
            new_st = dict(
                used=st["used"].at[:, pick].add(x["req"] * d),
                nonzero_used=st["nonzero_used"].at[:, pick].add(x["nonzero_req"] * d),
                pod_count=st["pod_count"].at[pick].add(di),
                port_used=st["port_used"].at[:, pick].add(x["pod_takes"] * di),
                spr_cnt=(
                    st["spr_cnt"].at[:, pick].add(x["spr_placed"].astype(jnp.int32) * di)
                    if use_spread
                    else st["spr_cnt"]
                ),
                ipa_in=(
                    st["ipa_in"].at[:, pick].add(x["ipa_in_match"] * di)
                    if use_interpod
                    else st["ipa_in"]
                ),
                ipa_ex=(
                    st["ipa_ex"].at[:, pick].add(x["ipa_ex_owned"] * di)
                    if use_interpod
                    else st["ipa_ex"]
                ),
            )
            if pipe_kw.get("use_nominated"):
                # a placed nominated pod leaves the nominator map: accumulate
                # its load (at its NOMINATED slot, where nom_used counted it)
                # into the correction rows its priority contributed to
                s_nom = x["nominated_slot"]
                placed_nom = found & (s_nom >= 0)
                ssn = jnp.maximum(s_nom, 0)
                rows = st["nom_corr_cnt"].shape[0]
                lev_mask = (
                    jnp.arange(rows, dtype=jnp.int32) >= x["nom_level"]
                ) & placed_nom
                new_st["nom_corr_used"] = st["nom_corr_used"].at[:, :, ssn].add(
                    lev_mask[:, None].astype(alloc.dtype) * x["req"][None, :]
                )
                new_st["nom_corr_cnt"] = st["nom_corr_cnt"].at[:, ssn].add(
                    lev_mask.astype(jnp.int32)
                )
                if pipe_kw.get("use_nominated_ports"):
                    new_st["nom_corr_ports"] = st["nom_corr_ports"].at[
                        :, :, ssn
                    ].add(
                        lev_mask[:, None].astype(jnp.int32)
                        * x["pod_takes"][None, :]
                    )
            st = new_st
            assignment = jnp.where(found, pick, -1).astype(jnp.int32)
        return (st, k), assignment

    return step


def _solve_scan(
    tables,  # dict of read-only node/class tables (see ExactSolver.solve)
    state0,  # dict of carried node state (donated)
    xs,  # dict of per-pod scanned inputs, leading axis P
    key,  # PRNG key
    **kw,  # pipeline shape/weight params, see _make_step
):
    step = _make_step(tables, **kw)
    (state, _), assignments = jax.lax.scan(step, (state0, key), xs)
    return assignments, state


def _solve_grouped(
    tables,
    state0,
    xs,  # per-pod scanned inputs: leading axis P (P % group == 0), or —
    #      compact mode — one representative row per chunk, leading axis C
    kinds,  # [C] int32 chunk dispatch (see _chunk_kinds)
    key,
    *,
    group: int,
    vcnt=None,  # [C] int32 valid-pod count per chunk (compact mode)
    compact: bool = False,
    **kw,
):
    """Grouped exact scan (SURVEY §8.4 'batched variant').

    The pod axis is cut into chunks of ``group`` consecutive pods; a
    host-computed per-chunk KIND picks the executable branch:

      0  slow: inner per-pod scan with the full pipeline — bit-identical
         to the ungrouped solver (mixed chunks, anything unproven).
      1  plain fast: identical pods whose class is spread/interpod-NEUTRAL
         (host-verified zero involvement) — node-local frontier stepping
         with multi-placement, as before.
      2  spread fast: identical pods with exactly ONE hard topology-spread
         constraint (no soft, no min_domains, zero taint/nodeaff
         preference rows, interpod-neutral). Domain-quota multi-placement:
         per iteration, up to quota_d = globalMin + maxSkew - count_d pods
         may land in domain d on distinct eligible nodes. Each placement
         is sequentially valid: counts only grow within quota (its own
         skew check holds at its turn since globalMin can only rise), and
         with zero preference rows every score is placement-count
         independent, so a chosen tie node is still an argmax tie at its
         turn even if other nodes leave the mask.
      3  anti fast: identical pods with exactly ONE required anti-affinity
         term (self-selecting, symmetric ex term on the same topology,
         no affinity/preferred, zero preference rows, spread-neutral).
         Same machinery with quota_d = 1 while the domain is empty — on
         hostname topology every node is its own domain, so a whole chunk
         places in ~one iteration (the scheduler_perf
         SchedulingPodAntiAffinity shape).

    Random-mode multi-placement (all fast kinds) produces a sequentially
    VALID outcome whose distribution differs from the per-pod scan for the
    same seed (ExactSolverConfig.group_size documents this); "first" mode
    places one pod per iteration and is bit-identical to the scan.

    COMPACT mode (host-verified precondition: within every chunk, validity
    is a prefix and all valid rows are identical): ``xs`` carries ONE
    representative row per chunk plus ``vcnt`` valid counts instead of P
    per-pod rows — the fast branches only ever read row 0, and the slow
    branch replays the representative broadcast ``group`` times with
    ``pod_valid = iota < vcnt``, which is bit-identical to the full-row
    replay for uniform chunks. It cuts the per-pod upload of a uniform
    50k-pod solve to one row per chunk.
    """
    tie_break = kw["tie_break"]
    w_cpu = kw["w_cpu"]
    w_mem = kw["w_mem"]
    rtc_shape = kw["rtc_shape"]
    w_fit = kw["w_fit"]
    w_balanced = kw["w_balanced"]
    w_taint = kw["w_taint"]
    w_nodeaff = kw["w_nodeaff"]
    w_image = kw["w_image"]
    fdtype = kw["fdtype"]
    scoring_strategy = kw["scoring_strategy"]

    alloc = tables["alloc"]
    alloc2 = alloc[: MEM_IDX + 1]
    weights2 = jnp.asarray([w_cpu, w_mem], dtype=alloc.dtype)
    fit_scorer = _fit_scorer(scoring_strategy, rtc_shape)
    n = alloc.shape[1]
    step = _make_step(tables, **kw)

    use_spread = kw["use_spread"]
    use_interpod = kw["use_interpod"]
    use_extra = kw.get("use_extra_score", False)
    d_pad = kw["d_pad"]
    ipa_d_pad = kw["ipa_d_pad"]
    iota_n = jnp.arange(n, dtype=jnp.int32)

    iota_group = jnp.arange(group, dtype=jnp.int32)

    def row(a):
        """Chunk-representative row: leading pod axis already stripped in
        compact mode."""
        return a if compact else a[0]

    def slow_chunk(st, k, cxs, vc):
        if compact:
            cxs = {
                n: jnp.broadcast_to(a[None], (group,) + a.shape)
                for n, a in cxs.items()
            }
            cxs["pod_valid"] = iota_group < vc
        (st, k), asg = jax.lax.scan(step, (st, k), cxs)
        return st, k, asg

    def make_fast(mode):
        """mode: None (plain) | "spread" | "anti" — the quota machinery is
        shared; mode picks the domain model (host preconditions in
        _chunk_kinds guarantee each branch only sees chunks it is exact
        for)."""

        def fast_chunk(st, k, cxs, vc):
            req = row(cxs["req"])  # [K] int64
            req_mask = row(cxs["req_mask"])
            nz = row(cxs["nonzero_req"])  # [2] int64
            takes = row(cxs["pod_takes"])
            conflict_row = row(cxs["pod_conflict"])
            cls = row(cxs["class_of"])
            # number of pods to place: `group` for a uniform chunk, 0 for
            # an all-padding chunk (kinds marks both; this makes
            # fixed-bucket pod padding nearly free)
            vcnt = (
                vc
                if compact
                else jnp.sum(cxs["pod_valid"].astype(jnp.int32)).astype(
                    jnp.int32
                )
            )

            # capacity: how many MORE identical pods each node can take.
            # floor_div_exact is only exact below 2^23 quotients, but the
            # result is clamped to [0, group] right after: a true quotient
            # >= 2^23 has relative f32 error ~2^-23, so the estimate stays
            # >> group and clamps identically; below 2^23 it is exact.
            with jax.named_scope("NodeResourcesFit"):
                free = alloc - st["used"]
                cap_res = jnp.where(
                    req_mask[:, None],
                    fastmath.floor_div_exact(
                        jnp.maximum(free, 0), jnp.maximum(req, 1)[:, None]
                    ),
                    group,
                )
                cap = jnp.min(cap_res, axis=0)
                cap = jnp.minimum(
                    cap, (tables["max_pods"] - st["pod_count"]).astype(cap.dtype)
                )
            with jax.named_scope("NodePorts"):
                conflict_now = pl.ports_conflict_mask(
                    conflict_row, st["port_used"]
                )
                has_ports = jnp.any(takes > 0)
                self_conf = jnp.any((takes > 0) & conflict_row)
                cap = jnp.where(conflict_now & has_ports, 0, cap)
                cap = jnp.where(
                    self_conf & ~conflict_now, jnp.minimum(cap, 1), cap
                )
            base_mask = tables["static_mask"][cls] & tables["node_valid"]
            cap = jnp.clip(jnp.where(base_mask, cap, 0), 0, group).astype(
                jnp.int32
            )

            # Frontier scores are computed LAZILY per iteration instead of
            # precomputing the full [group, N] table: the multi-placement
            # loop typically runs 1-3 iterations per chunk and reads only
            # the current and next frontier rows, so the eager table wasted
            # ~group/2x the division work (measured 13 ms vs 0.5 ms per
            # chunk at group=256 x 10k nodes on this device — it WAS the
            # exact-parity north star's dominant cost).
            static_row = jnp.zeros((n,), dtype=jnp.int32)
            if w_image:
                static_row = static_row + w_image * tables["image_score"][cls]
            if use_extra:
                # out-of-tree scores are per-(class, node) constants, same
                # shape as ImageLocality: fold into the frontier rows
                static_row = static_row + tables["extra_score"][cls]

            @jax.named_scope("Score")
            def frontier_rows(m, rows):
                """fit+balanced (+static rows) score of placing the
                (m+1)-th .. (m+rows)-th identical pod per node:
                [rows, N] int32 — same kernels as the per-pod pipeline,
                evaluated only at the frontier rows the loop body reads
                (rows=2 for the random multi-place body, rows=1 for the
                deterministic one-per-iteration body)."""
                jj = jnp.stack(
                    [m + 1 + i for i in range(rows)]
                ).astype(alloc.dtype)  # [rows, N]
                req_g = (
                    st["nonzero_used"][:, None, :]
                    + nz[:, None, None] * jj[None, :, :]
                ).reshape(2, rows * n)
                alloc_g = jnp.broadcast_to(
                    alloc2[:, None, :], (2, rows, n)
                ).reshape(2, rows * n)
                s = w_fit * fit_scorer(req_g, alloc_g, weights2)
                s = s + w_balanced * nr.balanced_allocation_score(
                    req_g, alloc_g, fdtype=fdtype
                )
                return (
                    s.astype(jnp.int32).reshape(rows, n)
                    + static_row[None, :]
                )

            taint_row = tables["taint_cnt"][cls]
            nodeaff_row = tables["nodeaff_pref"][cls]

            # -- domain model (mode-static), under its plugin's name --
            domain_scope = {
                "spread": "PodTopologySpread", "anti": "InterPodAffinity",
            }.get(mode, "grouped_fast")
            with jax.named_scope(domain_scope):
                if mode == "spread":
                    spr = tables["spr"]
                    jj = jnp.maximum(spr["hard"][cls, 0], 0)
                    dom_row = spr["dom"][jj]  # [N] (-1 = key missing)
                    hk = dom_row >= 0
                    dd = jnp.where(hk, dom_row, 0)
                    counted = spr["elig"][jj] & hk
                    base_cnt = st["spr_cnt"][jj]
                    skew_lim = spr["max_skew"][jj]
                    dom_present = (
                        domain_sum(counted.astype(jnp.int32), dd, d_pad) > 0
                    )
                    dpad_local = d_pad
                elif mode == "anti":
                    ipa = tables["ipa"]
                    jj = jnp.maximum(ipa["cls_req_anti"][cls, 0], 0)
                    dom_row = ipa["in_dom"][jj]
                    hk = dom_row >= 0
                    dd = jnp.where(hk, dom_row, 0)
                    # own symmetric ex term (host precondition: exactly one,
                    # same topology/domain row): its counts also block
                    ex_owned_row = row(cxs["ipa_ex_owned"])  # [Te]
                    ee = jnp.argmax(ex_owned_row > 0).astype(jnp.int32)
                    v_in = row(cxs["ipa_in_match"])[jj]
                    v_ex = ex_owned_row[ee]
                    base_cnt = st["ipa_in"][jj] + st["ipa_ex"][ee]
                    dpad_local = ipa_d_pad

            @jax.named_scope(domain_scope)
            def domain_eval(m):
                """(extra feasibility mask [N], quota_d [D], charged [N],
                dc [D] current domain counts). charged=False nodes
                (missing key / not counted) affect no domain totals and
                bypass quotas."""
                if mode == "spread":
                    cnt_now = jnp.where(counted, base_cnt + m, 0)
                    dc = domain_sum(cnt_now, dd, dpad_local)
                    mn = jnp.min(
                        jnp.where(dom_present, dc, jnp.int32(2**30))
                    )
                    node_dc = dc[dd]
                    ok = hk & (node_dc + 1 - mn <= skew_lim)
                    quota_d = jnp.clip(mn + skew_lim - dc, 0, group)
                    return ok, quota_d, counted, dc
                if mode == "anti":
                    cnt_now = jnp.where(
                        hk, base_cnt + (v_in + v_ex) * m, 0
                    )
                    dc = domain_sum(cnt_now, dd, dpad_local)
                    node_dc = dc[dd]
                    ok = (~hk) | (node_dc == 0)
                    quota_d = jnp.where(dc == 0, 1, 0).astype(jnp.int32)
                    return ok, quota_d, hk, dc
                ones_d = jnp.ones(1, dtype=jnp.int32)
                return (
                    jnp.ones(n, dtype=bool),
                    ones_d,
                    jnp.zeros(n, dtype=bool),
                    ones_d,
                )

            @jax.named_scope("Score")
            def scores_at(m, extra_ok, f):
                """Total score at frontier row ``f``
                (= frontier_rows(m, ...)[0])."""
                mask_t = (m < cap) & extra_ok
                total = f
                # DefaultNormalizeScore, recomputed per iteration because
                # the feasible mask shifts as nodes saturate. In quota
                # modes the host precondition makes these rows all-zero,
                # so the terms are the same constant on every node — they
                # cannot move an argmax and are skipped at trace time
                # (normalize costs a real per-iteration int division).
                if mode is None:
                    if w_taint:
                        total = total + w_taint * pl.normalize_score(
                            taint_row, mask_t, reverse=True
                        )
                    if w_nodeaff:
                        total = total + w_nodeaff * pl.normalize_score(
                            nodeaff_row, mask_t, reverse=False
                        )
                return jnp.where(mask_t, total, -1), mask_t

            m0 = jnp.zeros(n, dtype=jnp.int32)
            asg0 = jnp.full(group, -1, dtype=jnp.int32)
            iota_g = jnp.arange(group, dtype=jnp.int32)

            if tie_break == TIE_RANDOM:
                # Multi-placement (see _solve_grouped docstring for the
                # validity argument per mode). Terminates: each iteration
                # places >= 1 pod or proves infeasibility.
                def cond(state):
                    m, asg, placed, k = state
                    return placed < vcnt

                # what is not one of the named parts above is the pick
                # among ties (keys, quotas, water-fill): select
                @jax.named_scope("select")
                def body(state):
                    m, asg, placed, k = state
                    extra_ok, quota_d, charged, dc_now = domain_eval(m)
                    # anti mode never reads the next frontier row
                    # (eligible = tie): score only the row consumed
                    n_rows = 1 if mode == "anti" else 2
                    fr = frontier_rows(m, n_rows)
                    f_now, next_f = fr[0], fr[n_rows - 1]
                    total, mask_t = scores_at(m, extra_ok, f_now)
                    best = jnp.max(total)
                    feasible = best >= 0
                    tie = (total == best) & mask_t
                    # Node-local multi-place eligibility differs by mode:
                    # - plain: a chosen node must stay in the mask with a
                    #   non-increasing frontier, else DefaultNormalizeScore
                    #   and the tie set shift for later pods this iteration.
                    # - anti: a placed node's domain becomes quota-blocked,
                    #   removing it from the mask — its risen frontier can
                    #   never out-tie later pods, so tie alone suffices.
                    # - spread: a placed node may STAY in the mask (domain
                    #   quota remaining), so the frontier-rise exclusion is
                    #   still required; saturation is harmless (constant
                    #   normalize rows by host precondition).
                    if mode is None:
                        eligible = tie & ((m + 1) < cap) & (next_f <= f_now)
                    elif mode == "spread":
                        eligible = tie & (next_f <= f_now)
                    else:  # anti
                        eligible = tie

                    k, s1 = jax.random.split(k)
                    if mode is None:
                        r = jax.random.uniform(s1, (n,))
                        pick_key = jnp.where(tie, r, 2.0)
                        accept = eligible
                        order = jnp.argsort(
                            jnp.where(accept, r, 2.0)
                        ).astype(jnp.int32)
                        n_acc = jnp.sum(accept.astype(jnp.int32))
                        q = jnp.minimum(n_acc, vcnt - placed)
                    else:
                        ec = eligible & charged
                        rb = (
                            jax.random.randint(
                                s1, (n,), 0, 1 << 20, dtype=jnp.int32
                            ).astype(jnp.int64)
                            * n
                            + iota_n
                        )  # unique per-node random keys
                        if mode == "spread":
                            # WATER-FILL: when every present domain sits at
                            # the same count (totally balanced — the steady
                            # state of a spread workload) and no
                            # skew-blocked node could strictly out-score
                            # today's best after re-entering, k full
                            # ROUNDS are sequentially valid at once: the
                            # round-robin replay keeps the profile within
                            # 1 of balanced at every step, so each
                            # placement's skew bound holds for any
                            # maxSkew >= 1, and mask changes can only add
                            # ties or remove non-chosen nodes.
                            seg_elig = domain_sum(
                                ec.astype(jnp.int32), dd, dpad_local
                            )
                            d_present = jnp.sum(
                                dom_present.astype(jnp.int32)
                            )
                            # dc_now comes from this iteration's
                            # domain_eval — no second domain_sum
                            mx_dc = jnp.max(
                                jnp.where(dom_present, dc_now, -1)
                            )
                            mn_dc = jnp.min(
                                jnp.where(dom_present, dc_now, 2**30)
                            )
                            blocked_over = jnp.any(
                                (m < cap)
                                & hk
                                & ~extra_ok
                                & (f_now > best)
                            )
                            kk = jnp.minimum(
                                jnp.min(
                                    jnp.where(
                                        dom_present, seg_elig, 2**30
                                    )
                                ),
                                (vcnt - placed)
                                // jnp.maximum(d_present, 1),
                            )
                            waterfill = (
                                (mx_dc == mn_dc)
                                & ~blocked_over
                                & (kk >= 1)
                            )
                        else:
                            waterfill = jnp.bool_(False)
                            kk = jnp.int32(0)

                        def wf_accept(_):
                            # one sort per iteration, amortized over k*D
                            # placements: rank eligible nodes within their
                            # domain by random key, accept rank < k.
                            # POSITIONS interleave domains round-robin
                            # (round r of every present domain before
                            # round r+1 of any) — the emitted assignment
                            # order IS the sequential replay order, and
                            # only the interleaved order keeps every
                            # step's skew bound valid.
                            keyf = jnp.where(
                                ec,
                                dd.astype(jnp.float32) * 2.0
                                + jax.random.uniform(s1, (n,)),
                                jnp.float32(jnp.inf),
                            )
                            si = jnp.argsort(keyf)
                            sd = dd[si]
                            elig_s = ec[si]
                            is_start = elig_s & (
                                (iota_n == 0) | (sd != jnp.roll(sd, 1))
                            )
                            start_pos = jax.lax.associative_scan(
                                jnp.maximum,
                                jnp.where(is_start, iota_n, -1),
                            )
                            rank = iota_n - start_pos
                            accept_s = elig_s & (rank < kk)
                            accept = (
                                jnp.zeros(n, dtype=bool)
                                .at[si]
                                .set(accept_s)
                            )
                            d_rank = (
                                jnp.cumsum(dom_present.astype(jnp.int32))
                                - 1
                            )
                            # clamp the scattered rank to `group` before
                            # the position product: accepted lanes have
                            # rank < kk <= group (values unchanged), and
                            # unaccepted lanes' positions are never read
                            # — without the clamp, rank_n * d_present
                            # reaches node_pad * d_pad (~1.7e10 at the
                            # 512k x 102k hostname-domain shape) and
                            # wraps int32 (solver/budget.py
                            # assert_index_headroom polices the clamped
                            # bound host-side)
                            rank_n = (
                                jnp.zeros(n, dtype=jnp.int32)
                                .at[si]
                                .set(
                                    jnp.minimum(rank, group).astype(
                                        jnp.int32
                                    )
                                )
                            )
                            pos = rank_n * d_present + d_rank[dd]
                            return accept, pos.astype(jnp.int32)

                        def winner_accept(_):
                            # sort-free single-round selection: one
                            # domain_max winner per domain with quota
                            # (TPU sorts cost ~1 ms per [5k] vector; the
                            # 1-3 placements of an unbalanced iteration
                            # can't amortize one)
                            seg_key = domain_max(
                                jnp.where(ec, rb, -1), dd, dpad_local
                            )
                            if mode == "spread":
                                # re-entry gate for maxSkew > 1 (min may
                                # rise mid-iteration; maxSkew == 1 places
                                # only into distinct current-min domains)
                                blocked_high = jnp.any(
                                    (m < cap)
                                    & hk
                                    & ~extra_ok
                                    & (f_now >= best)
                                )
                                quota_eff = jnp.where(
                                    (skew_lim > 1) & blocked_high,
                                    0,
                                    quota_d,
                                )
                            else:
                                quota_eff = quota_d
                            win = (
                                ec
                                & (rb == seg_key[dd])
                                & (quota_eff[dd] >= 1)
                            )
                            # uncharged nodes affect no totals: no quota.
                            # Single-round placements are order-free (each
                            # accepted node sits in a distinct domain
                            # within old-min quota), so index-order
                            # positions via prefix sums are fine.
                            acc = win | (eligible & ~charged)
                            return acc, (
                                jnp.cumsum(acc.astype(jnp.int32)) - 1
                            ).astype(jnp.int32)

                        # waterfill accepts EXACTLY k per present domain —
                        # quota-free nodes would let the q-truncation cut
                        # into the charged set unevenly, breaking the
                        # round-robin replay; they place in later
                        # iterations instead
                        if mode == "spread":
                            accept, pos_iter = jax.lax.cond(
                                waterfill, wf_accept, winner_accept, None
                            )
                        else:
                            accept, pos_iter = winner_accept(None)
                        q = jnp.minimum(
                            jnp.sum(accept.astype(jnp.int32)),
                            vcnt - placed,
                        )

                    # q == 0 but feasible: single placement on one tie node
                    # (possibly saturating — next iteration recomputes).
                    # Picked by extremal random key among ties (uniform,
                    # since the keys are iid): min of `r` (non-ties padded
                    # to 2.0) in plain mode, max of `rb` (non-ties -1) in
                    # quota modes — reusing this iteration's draw instead
                    # of a second [N] cumsum + randint.
                    if mode is None:
                        pick = jnp.argmin(pick_key).astype(jnp.int32)
                    else:
                        pick = jnp.argmax(
                            jnp.where(tie, rb, jnp.int64(-1))
                        ).astype(jnp.int32)

                    multi = q > 0
                    n_placed = jnp.where(
                        feasible, jnp.where(multi, q, 1), 0
                    ).astype(jnp.int32)

                    with jax.named_scope("assume"):
                        if mode is None:
                            chosen = jnp.where(
                                multi,
                                jnp.where(iota_g < q, order[:group], -1),
                                jnp.where(iota_g < 1, pick, -1),
                            )  # [G] node ids for this iteration's pods, -1 pad
                            chosen = jnp.where(feasible, chosen, -1)
                            pos = jnp.where(chosen >= 0, placed + iota_g, group)
                            asg = asg.at[pos].set(chosen, mode="drop")
                            m = m.at[jnp.where(chosen >= 0, chosen, n)].add(
                                jnp.int32(1), mode="drop"
                            )
                        else:
                            take = accept & (pos_iter < q) & multi & feasible
                            idx_multi = jnp.where(
                                take, placed + pos_iter, group
                            )
                            # asg[idx_multi[n]] = n: the positions are
                            # distinct, so the write is a max per slot
                            # (lanes at `group` land in none)
                            lane = domain_max(iota_n, idx_multi, group)
                            asg = jnp.where(lane >= 0, lane, asg)
                            single = (~multi) & feasible
                            asg = asg.at[
                                jnp.where(single, placed, group)
                            ].set(pick, mode="drop")
                            delta_m = take.astype(jnp.int32) + (
                                jnp.zeros(n, dtype=jnp.int32)
                                .at[pick]
                                .set(jnp.int32(1))
                                * single.astype(jnp.int32)
                            )
                            m = m + delta_m
                    placed = jnp.where(feasible, placed + n_placed, vcnt)
                    return m, asg, placed, k

                m, asg, _, k = jax.lax.while_loop(
                    cond, body, (m0, asg0, jnp.int32(0), k)
                )
            else:
                # Deterministic lowest-index tie-break: one placement per
                # iteration, exactly the per-pod pipeline's argmax.
                @jax.named_scope("select")
                def body(t, acc):
                    m, asg = acc
                    extra_ok, _, _, _ = domain_eval(m)
                    total, _ = scores_at(
                        m, extra_ok, frontier_rows(m, 1)[0]
                    )
                    best = jnp.max(total)
                    feasible = (best >= 0) & (t < vcnt)
                    pick = jnp.argmax(total).astype(jnp.int32)
                    m = m.at[pick].add(feasible.astype(jnp.int32))
                    asg = asg.at[t].set(jnp.where(feasible, pick, -1))
                    return m, asg

                m, asg = jax.lax.fori_loop(0, group, body, (m0, asg0))

            with jax.named_scope("assume"):
                d = m.astype(alloc.dtype)
                st = dict(
                    st,
                    used=st["used"] + req[:, None] * d[None, :],
                    nonzero_used=st["nonzero_used"] + nz[:, None] * d[None, :],
                    pod_count=st["pod_count"] + m,
                    port_used=st["port_used"] + takes[:, None] * m[None, :],
                )
                # family occupancy updates (rows are zero for neutral chunks,
                # making these no-ops for kind-1 chunks in active batches)
                if use_spread:
                    st["spr_cnt"] = st["spr_cnt"] + row(
                        cxs["spr_placed"]
                    ).astype(jnp.int32)[:, None] * m[None, :]
                if use_interpod:
                    st["ipa_in"] = st["ipa_in"] + row(cxs["ipa_in_match"])[
                        :, None
                    ] * m[None, :]
                    st["ipa_ex"] = st["ipa_ex"] + row(cxs["ipa_ex_owned"])[
                        :, None
                    ] * m[None, :]
            return st, k, asg

        return fast_chunk

    slow, fast = jax.named_scope("grouped_slow"), jax.named_scope("grouped_fast")
    branches = [slow(slow_chunk), fast(make_fast(None))]
    branches.append(fast(make_fast("spread")) if use_spread else branches[1])
    branches.append(fast(make_fast("anti")) if use_interpod else branches[1])

    def chunk_step(carry, x):
        st, k = carry
        cxs, kind, vc = x
        st, k, asg = jax.lax.switch(kind, branches, st, k, cxs, vc)
        return (st, k), asg

    c = kinds.shape[0]
    if compact:
        cxs_all = xs  # already one representative row per chunk
    else:
        cxs_all = jax.tree.map(
            lambda a: a.reshape((c, group) + a.shape[1:]), xs
        )
        vcnt = jnp.zeros(c, dtype=jnp.int32)  # unread by the branches
    (state, _), assignments = jax.lax.scan(
        chunk_step, (state0, key), (cxs_all, kinds, vcnt)
    )
    return assignments.reshape(c * group), state


# -- packed transfer layer ---------------------------------------------------
#
# Every host<->device transfer is its own PJRT call, so the per-solve wire
# protocol is collapsed to a handful of arrays:
#   xi64 / xi32 / xbool — per-pod inputs concatenated along the trailing axis
#                         per dtype class, unpacked by a static slice spec
#                         inside the compiled program (free on device);
#   bstate              — per-batch node-state rows (ports/spread/interpod
#                         occupancy) stacked into one int32 [B, N], uploaded
#                         fresh each batch (its dims differ per batch, so
#                         donation would never reuse the buffer);
#   persist             — used/nonzero_used/pod_count, DEVICE-RESIDENT between
#                         batches in session mode (donated through each call);
#   assignments         — the only per-batch download in session mode.


def _run_packed(
    nt,  # node tables {alloc, max_pods, node_valid}
    ct,  # class tables {static_mask, taint_cnt, nodeaff_pref, image_score, spr, ipa}
    persist,  # {used, nonzero_used, pod_count} — donated; with chain_in it
    #           ALSO carries the batch-state rows from the previous
    #           chained sub-solve (BatchCarriedUsage)
    bstate,  # [B, N] int32 packed per-batch state ([1, 1] dummy with chain_in)
    xi64,  # [P, *] int64 packed per-pod inputs ([C, *] in compact mode)
    xi32,  # [P, *] int32
    xbool,  # [P, *] bool
    kinds,  # [P // group] int32 chunk kinds (grouped) or [1] dummy
    vcnt,  # [C] int32 per-chunk valid counts (compact mode) or [1] dummy
    nom_used,  # [L+1, K, N] int64 cumulative nominated load ([1,1,1] unused)
    nom_ports,  # [L+1, B, N] int32 nominated hostPort occupancy ([1,1,1] unused)
    key,
    *,
    bspec,  # tuple of (name, start, width)
    xspec,  # tuple of (name, src, start, width, squeeze)
    grouped: bool,
    group: int,
    **kw,
):
    pack_result = kw.pop("pack_result", False)
    compact = kw.pop("compact", False)
    # chained sub-batch dispatch (run_pipelined's RTT-hiding batch split):
    # chain_in consumes the previous sub-solve's carried batch-state rows
    # (port/spread/interpod occupancy) straight from the donated persist
    # dict instead of re-uploading host bstate — the occupancy the earlier
    # sub-batches placed stays device-resident. chain_out returns the full
    # carried state so the next sub-solve can chain on it.
    chain_in = kw.pop("chain_in", False)
    chain_out = kw.pop("chain_out", False)
    tables = {**nt, **ct}
    state0 = dict(persist)
    if not chain_in:
        with jax.named_scope("unpack"):
            for name, s, w in bspec:
                state0[name] = bstate[s : s + w]
    if kw.get("use_nominated"):
        tables["nom_used"] = nom_used
        tables["nom_cnt"] = state0.pop("nom_cnt")
        # placed-nominated correction carry (starts empty each batch)
        state0["nom_corr_used"] = jnp.zeros_like(nom_used)
        state0["nom_corr_cnt"] = jnp.zeros(
            (nom_used.shape[0], nom_used.shape[2]), dtype=jnp.int32
        )
        if kw.get("use_nominated_ports"):
            tables["nom_ports"] = nom_ports
            state0["nom_corr_ports"] = jnp.zeros_like(nom_ports)
    srcs = {"i64": xi64, "i32": xi32, "bool": xbool}
    xs = {}
    with jax.named_scope("unpack"):
        for name, src, s, w, squeeze in xspec:
            a = srcs[src][:, s : s + w]
            xs[name] = a[:, 0] if squeeze else a
    if grouped:
        assignments, state = _solve_grouped(
            tables, state0, xs, kinds, key, group=group, vcnt=vcnt,
            compact=compact, **kw,
        )
    else:
        assignments, state = _solve_scan(tables, state0, xs, key, **kw)
    if chain_out:
        # the whole carried state rides to the next chained sub-solve
        # (fit rows AND the batch occupancy rows)
        out_state = dict(state)
    else:
        out_state = {
            k: state[k] for k in ("used", "nonzero_used", "pod_count")
        }
    if pack_result:
        # Standalone mode downloads everything host-side: the four
        # result arrays are flattened into ONE int64 buffer so the host
        # blocks on a single device->host read. Session mode keeps the
        # dict (state stays device-resident; only assignments download).
        with jax.named_scope("pack"):
            return jnp.concatenate(
                [
                    out_state["used"].reshape(-1),
                    out_state["nonzero_used"].reshape(-1),
                    out_state["pod_count"].astype(jnp.int64),
                    assignments.astype(jnp.int64),
                ]
            )
    return assignments, out_state


_RUN_PACKED_STATICS = (
    "bspec",
    "xspec",
    "grouped",
    "group",
    "tie_break",
    "scoring_strategy",
    "w_cpu",
    "w_mem",
    "rtc_shape",
    "disabled",
    "w_fit",
    "w_balanced",
    "w_taint",
    "w_nodeaff",
    "w_image",
    "w_spread",
    "w_interpod",
    "use_spread",
    "use_interpod",
    "d_pad",
    "ipa_d_pad",
    "fdtype",
    "spread_soft",
    "ipa_ident",
    "ipa_score",
    "pallas",
    "use_nominated",
    "use_nominated_ports",
    "use_extra_score",
    "pack_result",
    "compact",
    "chain_in",
    "chain_out",
)

# Session mode donates the device-resident persist buffers through each call.
_run_packed_jit = jax.jit(
    _run_packed, static_argnames=_RUN_PACKED_STATICS, donate_argnums=(2,)
)

# Standalone (pack_result) solves flatten the result, so the donated persist
# buffers could never be reused as outputs — a non-donating wrapper avoids
# the spurious donation warning on every standalone call.
_run_packed_jit_nodonate = jax.jit(
    _run_packed, static_argnames=_RUN_PACKED_STATICS
)


def _heal(nt, persist, cols_i64, cols_i32, cols_bool, idx):
    """Scatter dirty snapshot columns onto the device-resident node tables
    and carried state (cache.go#UpdateSnapshot's O(changed) contract, device
    side). idx may contain repeats (shape bucketing pads with idx[0]) —
    set-scatter with identical payload is idempotent."""
    k = nt["alloc"].shape[0]
    nt = dict(
        nt,
        alloc=nt["alloc"].at[:, idx].set(cols_i64[:k]),
        max_pods=nt["max_pods"].at[idx].set(cols_i32[0]),
        node_valid=nt["node_valid"].at[idx].set(cols_bool[0]),
    )
    persist = dict(
        persist,
        used=persist["used"].at[:, idx].set(cols_i64[k : 2 * k]),
        nonzero_used=persist["nonzero_used"].at[:, idx].set(
            cols_i64[2 * k : 2 * k + 2]
        ),
        pod_count=persist["pod_count"].at[idx].set(cols_i32[1]),
    )
    return nt, persist


_heal_jit = jax.jit(_heal, donate_argnums=(0, 1))


def _pack_cols(arrs: list[np.ndarray]) -> np.ndarray:
    """Stack row-blocks (each [*, D] or [D]) into one array for upload."""
    rows = [a[None, :] if a.ndim == 1 else a for a in arrs]
    return np.concatenate(rows, axis=0)


class SessionDrainRequired(Exception):
    """Raised by a deferred-heal sync (allow_heal=False) when the device
    session would need a FULL re-upload (node/vocab shape change): a full
    upload from host truth while an earlier solve is still unapplied
    would erase that solve's carried placements. The pipelined driver
    catches this BEFORE any device mutation, drains the in-flight solve,
    and re-dispatches with healing allowed."""


class DeferredAssignments:
    """Handle to a dispatched-but-unread session solve (VERDICT r4 #1).

    The device→host copy is initiated asynchronously at construction
    (``copy_to_host_async``), so the transfer overlaps whatever host
    work happens before ``get()``. ``get()`` blocks until the transfer
    lands and returns the trimmed int32 assignment vector.

    ``lo``/``count`` locate a chained sub-batch's pods within the popped
    batch (solve(..., split=K)): this handle covers batch pods
    [lo, lo + count). An unsplit solve is the trivial chain lo=0,
    count=num_pods."""

    __slots__ = ("_dev", "_num_pods", "lo")

    def __init__(self, dev, num_pods: int, lo: int = 0) -> None:
        self._dev = dev
        self._num_pods = num_pods
        self.lo = lo
        dev.copy_to_host_async()

    @property
    def count(self) -> int:
        return self._num_pods

    # sanctioned deferred-read point (analysis/registry.py) — the async
    # D2H copy started in __init__ makes this read post-overlap: ktpu: hot
    def get(self) -> np.ndarray:
        return np.asarray(self._dev)[: self._num_pods]

    # sanctioned deferred-read point (analysis/registry.py): the
    # streaming dispatcher's COMPLETION THREAD parks here so the wait
    # for the solve is paid off the driver thread — it only waits for
    # the async D2H started in __init__ to land, it never converts the
    # value (the driver's get() stays the one read): ktpu: hot
    def wait(self) -> None:
        try:
            self._dev.block_until_ready()
        except Exception:
            pass  # get() surfaces any real transfer death to the driver


class BatchCarriedUsage:
    """Device-resident occupancy carry between chained sub-batch solves
    of ONE popped batch (the RTT-hiding batch split): the port-vocab
    occupancy rows, spread domain counts, and interpod term counts the
    earlier sub-batches' placements advanced, alongside the fit rows —
    everything ``_run_packed`` needs as ``state0`` for the next chained
    dispatch. Sub-batches of one batch share one tensorize (one
    occupancy vocab / domain id space / class table), which is exactly
    what makes the device-side carry well-defined; the carry dies with
    the chain (the next popped batch re-tensorizes a fresh vocab from
    host truth)."""

    __slots__ = ("state",)

    def __init__(self, state: dict) -> None:
        self.state = state  # device arrays, donated through the chain


def _class_table_arrays(static, spread, interpod) -> list:
    """The flat array list behind one class-table upload — the content
    hash AND the transfer-byte accounting both walk exactly this."""
    arrays = [
        static.mask, static.taint_cnt, static.nodeaff_pref,
        static.image_score, spread.dom, spread.elig, spread.max_skew,
        spread.min_domains, spread.self_match, spread.is_hostname,
        spread.hard, spread.soft, interpod.in_dom, interpod.in_pref_w,
        interpod.cls_req_aff, interpod.cls_req_anti, interpod.cls_pref,
        interpod.ex_dom, interpod.ex_anti,
    ]
    if static.extra_score is not None:
        arrays.append(static.extra_score)
    return arrays


def _class_table_digest(static, spread, interpod) -> bytes:
    """Content hash of the class-table arrays — the one digest both the
    session's class-table cache key AND the streaming dispatcher's
    stream_chain_key are built from, so a streaming dispatch hashes the
    tables once (stream_chain_key computes it, solve hands it to
    class_tables via the chain key) instead of twice per batch."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for a in _class_table_arrays(static, spread, interpod):
        arr = np.ascontiguousarray(a)
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.digest()


def _place_class_tables(static, spread, interpod, mesh, node_pad: int):
    """Device placement for the per-batch class tables: tables with a
    trailing node axis ([*, N]) shard over the mesh's node axis, the
    per-class / per-instance scalar tables replicate BY NAME
    (parallel.sharding.REPLICATED_TABLE_NAMES — a shape test alone
    could collide when an instance-axis pow2 pad happens to equal the
    node pad). mesh=None is the plain single-device upload."""
    dev, dev_n = placers(mesh, node_pad)

    def put(name, a):
        return dev(a) if name in REPLICATED_TABLE_NAMES else dev_n(a)

    names_arrays = {
        "static_mask": static.mask,
        "taint_cnt": static.taint_cnt,
        "nodeaff_pref": static.nodeaff_pref,
        "image_score": static.image_score,
        **(
            {"extra_score": static.extra_score}
            if static.extra_score is not None
            else {}
        ),
        "spr": {
            "dom": spread.dom,
            "elig": spread.elig,
            "max_skew": spread.max_skew,
            "min_domains": spread.min_domains,
            "self_match": spread.self_match,
            "is_hostname": spread.is_hostname,
            "hard": spread.hard,
            "soft": spread.soft,
        },
        "ipa": {
            "in_dom": interpod.in_dom,
            "in_pref_w": interpod.in_pref_w,
            "cls_req_aff": interpod.cls_req_aff,
            "cls_req_anti": interpod.cls_req_anti,
            "cls_pref": interpod.cls_pref,
            "ex_dom": interpod.ex_dom,
            "ex_anti": interpod.ex_anti,
        },
    }
    return {
        name: (
            {n: put(n, a) for n, a in v.items()}
            if isinstance(v, dict)
            else put(name, v)
        )
        for name, v in names_arrays.items()
    }


class _DeviceSession:
    """Device-resident mirror of one snapshot's node tensors (SURVEY §8.3).

    Engaged by Scheduler-driven solves (col_versions provided): node tables
    and the carried used/nonzero_used/pod_count live in HBM across batches;
    dirty snapshot columns heal by scatter; class-table uploads dedupe by
    content hash. Standalone solves (tests, one-shot callers) bypass it.
    """

    def __init__(self) -> None:
        self.padded = -1
        self.k = -1
        self.nt = None
        self.persist = None
        self.seen_versions: np.ndarray | None = None
        self.class_cache: dict[tuple, object] = {}
        # node-axis mesh the resident tables are sharded over (None =
        # single-device). A mesh change is a full re-upload: the resident
        # buffers' shardings no longer match the dispatch's expectations.
        self.mesh = None
        self.mesh_key: tuple | None = None
        # cross-BATCH occupancy carry (the streaming dispatcher): the
        # FULL carried state of the last stream solve — fit rows plus
        # the port/spread/interpod occupancy rows — kept device-resident
        # so the next batch with an identical occupancy vocabulary
        # (stream_key) chains on it instead of re-uploading host bstate.
        # Its fit buffers are the SAME objects as ``persist``'s, so any
        # donation of persist (ordinary solves, heals) invalidates it —
        # every such path must null it out. ``stream_versions`` is the
        # carry's own host-column baseline: the scheduler advances it
        # after each CLEAN ring-slot apply (the device assumed those
        # placements at solve time, so host truth catching up is not
        # drift), which is what keeps chaining alive past the first
        # ring fill — ``seen_versions`` stays the heal baseline.
        self.stream_carry: dict | None = None
        self.stream_key: tuple | None = None
        self.stream_versions: np.ndarray | None = None

    def sync(
        self,
        nodes: NodeBatch,
        col_versions: np.ndarray,
        allow_heal: bool = True,
        mesh=None,
    ) -> int:
        """Bring resident node tables/state up to date with the snapshot.

        ``allow_heal=False`` (pipelined dispatch with an EARLIER solve
        still unapplied): dirty columns are NOT healed and seen_versions
        is NOT advanced, so the next healing sync picks them up. Host
        truth can only understate device usage under the pipeline's
        conflict fence (external usage-increasing events discard the
        in-flight solve; own-apply effects are either already in the
        device carry or usage-decreasing rollbacks), so deferring the
        heal is conservative — never a capacity violation. A shape
        change in this mode raises SessionDrainRequired instead of
        re-uploading over the in-flight solve's carried state.

        With ``mesh`` set, the resident node tables and carried state
        live SHARDED over the mesh's node axis (node axis last); dirty-
        column heals scatter into the sharded residents, so only the
        owning shard's slice actually changes. Returns the host->device
        bytes this sync uploaded (the per-solve transfer counters)."""
        mesh_key = mesh_fingerprint(mesh)
        if (
            self.padded != nodes.padded
            or self.k != nodes.allocatable.shape[0]
            or self.mesh_key != mesh_key
        ):
            if not allow_heal and self.padded != -1:
                raise SessionDrainRequired()
            self.padded = nodes.padded
            self.k = nodes.allocatable.shape[0]
            self.mesh = mesh
            self.mesh_key = mesh_key
            # a full re-upload replaces the resident state wholesale:
            # any cross-batch occupancy carry is gone with it
            self.stream_carry = None
            self.stream_key = None
            self.stream_versions = None
            _, put = placers(mesh, nodes.padded)
            self.nt = {
                "alloc": put(nodes.allocatable),
                "max_pods": put(nodes.max_pods),
                "node_valid": put(nodes.valid),
            }
            self.persist = {
                "used": put(nodes.used),
                "nonzero_used": put(nodes.nonzero_used),
                "pod_count": put(nodes.pod_count),
            }
            self.seen_versions = col_versions[: nodes.padded].copy()
            return sum(
                a.nbytes
                for a in (
                    nodes.allocatable, nodes.max_pods, nodes.valid,
                    nodes.used, nodes.nonzero_used, nodes.pod_count,
                )
            )
        dirty = np.nonzero(
            col_versions[: self.padded] > self.seen_versions
        )[0]
        if dirty.size and not allow_heal:
            return 0  # defer: seen_versions untouched, a later sync heals
        if dirty.size:
            d_pad = 1
            while d_pad < dirty.size:
                d_pad *= 2
            idx = np.full(d_pad, dirty[0], dtype=np.int32)
            idx[: dirty.size] = dirty
            cols_i64 = _pack_cols(
                [
                    nodes.allocatable[:, idx],
                    nodes.used[:, idx],
                    nodes.nonzero_used[:, idx],
                ]
            )
            cols_i32 = _pack_cols(
                [nodes.max_pods[idx], nodes.pod_count[idx]]
            )
            cols_bool = _pack_cols([nodes.valid[idx]])
            # heal payloads replicate (every shard scatters; GSPMD keeps
            # only the owning shard's columns — the others are out of its
            # index range)
            put_r, _ = placers(self.mesh)
            # the heal donates persist's fit buffers, which the stream
            # carry shares — a dirty-column heal therefore breaks any
            # cross-batch chain (the streaming dispatcher refuses to
            # chain over dirty columns for exactly this reason:
            # ExactSolver.can_chain checks seen_versions first)
            self.stream_carry = None
            self.stream_key = None
            self.stream_versions = None
            self.nt, self.persist = _heal_jit(
                self.nt,
                self.persist,
                put_r(cols_i64),
                put_r(cols_i32),
                put_r(cols_bool),
                put_r(idx),
            )
        self.seen_versions = col_versions[: self.padded].copy()
        return (
            cols_i64.nbytes + cols_i32.nbytes + cols_bool.nbytes + idx.nbytes
            if dirty.size
            else 0
        )

    def class_tables(self, static, spread, interpod, mesh=None, digest=None):
        """Content-addressed device cache of the per-batch class tables.
        Returns (tables, bytes_uploaded) — 0 bytes on a cache hit. The
        cache key includes the mesh fingerprint: the same content placed
        for a different topology is a different device resident.
        ``digest`` short-circuits the content hash with a precomputed
        _class_table_digest (the streaming path already computed it for
        the chain key)."""
        arrays = _class_table_arrays(static, spread, interpod)
        if digest is None:
            digest = _class_table_digest(static, spread, interpod)
        key = (digest, mesh_fingerprint(mesh))
        ct = self.class_cache.pop(key, None)
        if ct is not None:
            self.class_cache[key] = ct  # re-insert: LRU refresh on hit
            return ct, 0
        ct = _place_class_tables(static, spread, interpod, mesh, self.padded)
        if len(self.class_cache) >= 8:
            self.class_cache.pop(next(iter(self.class_cache)))
        self.class_cache[key] = ct
        return ct, sum(np.asarray(a).nbytes for a in arrays)


# dispatch_counts key -> the /metrics child that counts the same thing
_TALLY_SERIES = {
    "grouped": metrics.solves_total.labels("grouped"),
    "scan": metrics.solves_total.labels("scan"),
    "kind0": metrics.solve_chunks_total.labels("slow"),
    "kind1": metrics.solve_chunks_total.labels("plain"),
    "kind2": metrics.solve_chunks_total.labels("spread"),
    "kind3": metrics.solve_chunks_total.labels("anti"),
    "spread_instances": metrics.spread_instances_total,
    "interpod_incoming": metrics.interpod_terms_total.labels("incoming"),
    "interpod_existing": metrics.interpod_terms_total.labels("existing"),
    "domains_dense": metrics.domain_reductions_total.labels("dense"),
    "domains_scatter": metrics.domain_reductions_total.labels("scatter"),
    "class_table_uploads": metrics.class_table_uploads_total,
}


def _capture_config_fingerprint(cfg: "ExactSolverConfig") -> dict:
    """JSON-safe config snapshot for the telemetry capture hook (lazy
    import: the solver must not pull the obs layer in at module load)."""
    from ..obs.bundle import config_fingerprint

    return config_fingerprint(cfg)


class ExactSolver:
    """Host-facing wrapper: NodeBatch/PodBatch (+ plugin tensors) in,
    assignments out, node state written back (the device-side 'assume')."""

    def __init__(self, config: ExactSolverConfig | None = None, mesh=None):
        self.config = config or ExactSolverConfig()
        # default jax.sharding.Mesh for every solve (node axis sharded over
        # its devices); solve(mesh=...) overrides per call. None = the
        # single-device path. The scheduler threads its
        # SchedulerConfig.mesh_devices mesh through here.
        self.mesh = mesh
        self._step_count = 0
        self._session = _DeviceSession()
        # flight-telemetry input snapshot hook (obs/bundle.py): when
        # set, solve() hands over its resolved inputs — pre-PRNG-
        # increment, pre-default-filling — so a capture-on-anomaly
        # bundle can re-execute the exact solve offline. Host-side
        # callable, never touches device state.
        self.capture_hook = None
        # Cumulative executable-dispatch histogram: "scan" counts whole
        # per-pod-scan solves and "grouped" grouped ones, "kindK" counts
        # grouped chunks that hold a pod by the _chunk_kinds dispatch
        # (0 slow replay / 1 plain / 2 spread quota / 3 anti quota),
        # "padding" those that hold none, "domains_dense|scatter" the
        # domain tables by the form ops/domains.py reduces them in,
        # "interpod_incoming|existing" the inter-pod terms a solve carries.
        # _tally is the one increment;
        # /metrics exports the same counts (_TALLY_SERIES). Benchmarks
        # report THIS instead of asserting which path a workload takes
        # (PERF.md §4: the spread cell was assumed to take the per-pod
        # scan until these counts showed the quota chunks engaged).
        from collections import Counter

        self.dispatch_counts: Counter = Counter()
        # int64 resource arithmetic is non-negotiable (memory bytes overflow
        # int32): enable it here rather than trusting the embedding
        # application to have set JAX_ENABLE_X64.
        if not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        # SURVEY §6.4: the XLA executable cache is the solver's only durable
        # warm state — restarts deserialize instead of recompiling.
        from ..utils.compile_cache import enable_persistent_cache

        enable_persistent_cache()

    def _tally(self, key: str, n: int = 1) -> None:
        """The one increment behind ``dispatch_counts`` and the /metrics
        series that export it (``_TALLY_SERIES``)."""
        if n:
            self.dispatch_counts[key] += n
            series = _TALLY_SERIES.get(key)
            if series is not None:
                series.inc(n)

    def reset_session(self) -> None:
        """Drop the device-resident session so the next solve re-uploads
        node tables and carried state from the host snapshot. Called when
        a deferred solve is DISCARDED (run_pipelined's fence): the
        discarded scan already advanced the carried used/pod_count on
        device, and host cache truth no longer matches it. The per-class
        table cache is content-addressed — it cannot be stale — so it
        survives the reset (only node tables + carry are invalidated)."""
        fresh = _DeviceSession()
        fresh.class_cache = self._session.class_cache
        self._session = fresh

    # -- cross-batch occupancy chaining (the streaming dispatcher) --

    def stream_chain_key(
        self,
        nodes: NodeBatch,
        pods: PodBatch,
        static: StaticPluginTensors,
        ports: PortTensors | None = None,
        spread: SpreadTensors | None = None,
        interpod: InterpodTensors | None = None,
        mesh=None,
    ) -> tuple:
        """Fingerprint of everything that makes one batch's device-
        resident occupancy carry (BatchCarriedUsage) semantically AND
        shape-compatible with the next batch's dispatch: the class-table
        content (spread instance/domain tables, interpod term tables,
        static masks — the index spaces the carried rows are keyed by),
        the ordered port vocabulary, the bstate row layout, the node
        padding/resource-vocab width, the domain paddings, and the mesh
        topology. Two consecutive batches with equal keys may chain: the
        occupancy rows the earlier batch's placements advanced stay
        device-resident instead of round-tripping through host
        tensorize. Conservative by construction — any difference falls
        back to the drain-then-retensorize path, never to a wrong
        chain. ``spread``/``interpod``/``ports`` may be None — the same
        trivial tensors ``solve`` would build are keyed then, so a
        plain batch's key matches the dispatch it fingerprints."""
        if mesh is None:
            mesh = self.mesh
        if ports is None:
            ports = trivial_port_tensors(pods, nodes.padded)
        if spread is None:
            spread = trivial_spread_tensors(pods, nodes.padded, static.c_pad)
        if interpod is None:
            interpod = trivial_interpod_tensors(
                pods, nodes.padded, static.c_pad
            )
        import hashlib

        # component 0 is exactly the class-table cache digest, so the
        # dispatch can hand it to _DeviceSession.class_tables instead of
        # hashing the same arrays a second time in the hot loop
        return (
            _class_table_digest(static, spread, interpod),
            hashlib.blake2b(
                repr(ports.vocab).encode(), digest_size=16
            ).digest(),
            mesh_fingerprint(mesh),
            nodes.padded,
            nodes.allocatable.shape[0],
            ports.used.shape[0],
            spread.cnt0.shape[0],
            interpod.in_cnt0.shape[0],
            interpod.ex_cnt0.shape[0],
            spread.d_pad,
            interpod.d_pad,
        )

    def can_chain(self, key: tuple, col_versions: np.ndarray) -> bool:
        """True when the next solve may consume the resident stream
        carry: a carry exists, its key matches, and NO snapshot column
        went dirty past the carry's OWN baseline (``stream_versions``) —
        unexplained dirt means host truth moved under the carry (node
        table change, assume-failure touch), and healing it would
        donate the carry's fit buffers, so the chain refuses instead
        (the caller drains and re-tensorizes, which is always correct).
        The baseline starts at the carry's dispatch and is advanced by
        ``note_stream_applied`` after each clean ring-slot apply: the
        scheduler's own applies only write usage the device already
        assumed at solve time, so they must not kill the chain —
        without the advance, chaining would die permanently the moment
        the stream ring first fills (every apply dirties columns, and
        in-flight dispatches defer heals, so ``seen_versions`` never
        catches up)."""
        s = self._session
        if s.stream_carry is None or s.stream_key != key:
            return False
        if s.padded == -1 or s.stream_versions is None:
            return False
        if col_versions is None or s.padded > len(col_versions):
            return False
        return not bool(
            np.any(col_versions[: s.padded] > s.stream_versions)
        )

    def note_stream_applied(self, col_versions: np.ndarray) -> None:
        """Advance the stream carry's column baseline after the
        scheduler applied a ring slot CLEANLY (no fence discard, no
        assume/bind failure): the apply wrote exactly the usage the
        device session assumed at that slot's solve, so host truth
        catching up is not drift — the carry stays chainable. Any
        UNCLEAN apply skips this call; its assume-failure ``touch``
        then trips ``can_chain`` and the next dispatch drains + heals
        the phantom placement."""
        s = self._session
        if s.stream_carry is None or s.padded == -1:
            return
        if col_versions is None or s.padded > len(col_versions):
            return
        s.stream_versions = col_versions[: s.padded].copy()

    def invalidate_stream_carry(self) -> None:
        """Drop the resident stream carry. Called by the scheduler when
        a ring-slot apply was UNCLEAN (fence discard, assume/bind
        failure): the session persist may hold a phantom placement, and
        a later clean apply must not advance the baseline past the
        failure's ``touch`` — with the carry gone, the next dispatch
        takes the drain-then-heal path, which clears the phantom."""
        s = self._session
        s.stream_carry = None
        s.stream_key = None
        s.stream_versions = None

    def solve(
        self,
        nodes: NodeBatch,
        pods: PodBatch,
        static: StaticPluginTensors | None = None,
        ports: PortTensors | None = None,
        spread: SpreadTensors | None = None,
        interpod: InterpodTensors | None = None,
        col_versions: np.ndarray | None = None,
        nominated=None,  # NominatedTensors | None
        nominated_slot: np.ndarray | None = None,  # [num_pods] int32, -1 none
        defer_read: bool = False,
        allow_heal: bool = True,
        split: int = 1,
        mesh=None,
        chain_occupancy: bool = False,
        stream_carry_out: bool = False,
        chain_key: tuple | None = None,
    ) -> np.ndarray | DeferredAssignments | list[DeferredAssignments]:
        """Returns assignments [num_pods] of node indices (-1 = unschedulable).

        Standalone mode (col_versions=None): uploads everything, downloads
        the updated node state and writes it back into ``nodes`` in place.

        Session mode (col_versions from a Snapshot): node tables and the
        carried used/nonzero_used/pod_count stay device-resident between
        calls; only columns whose snapshot version advanced re-upload, and
        ONLY the assignments download — ``nodes`` is NOT written back (the
        cache/snapshot generation path is authoritative host-side).

        ``defer_read`` (session mode only): return a DeferredAssignments
        handle instead of blocking on the device→host read. The carried
        device state advances immediately either way, so a later solve may
        be dispatched before the handle is read — the double-buffered
        scheduling loop's overlap point (the caller is responsible for
        discarding/fencing stale handles; see Scheduler.run_pipelined).

        ``split`` (session + defer_read only): chop the padded pod axis
        into up to ``split`` contiguous sub-batches dispatched
        back-to-back, each chained on the previous one's device-resident
        carried state (fit rows AND the batch occupancy rows —
        BatchCarriedUsage), and return one DeferredAssignments per
        sub-batch. The assignment read of sub-batch i then overlaps the
        solve of i+1 — only the LAST read is not overlapped.
        Sequential semantics are identical to the unsplit solve (same
        scan order over the same carried state); with tie_break="first"
        the assignments are bit-identical, with "random" each sub-batch
        draws its own fold_in(key, i) stream, so placements are a valid
        sequential outcome whose distribution differs from the unsplit
        solve (the grouped-path caveat, ExactSolverConfig.group_size).
        The requested split is clamped to the largest feasible divisor
        of the padded pod axis (group-aligned when the grouped path
        engages); nominated-pod batches always dispatch unsplit (their
        correction carry is per-solve). When ``split > 1`` the return
        value is ALWAYS a list, even if the clamp lands on one
        sub-batch.

        ``stream_carry_out`` (session + defer_read only): after the
        solve, keep the FULL carried state — fit rows AND the batch
        occupancy rows — device-resident as the session's stream carry,
        tagged with ``chain_key`` (stream_chain_key). The next solve
        whose key matches may pass ``chain_occupancy=True`` to consume
        it: its first dispatch chains on the resident carry instead of
        uploading host bstate, so the occupancy the earlier batch's
        placements advanced never round-trips. This is the streaming
        dispatcher's cross-BATCH extension of the within-batch
        ``split`` chain; the caller is responsible for only chaining
        when its fences prove no conflicting event landed in between
        (``can_chain`` re-checks the vocabulary + dirty columns).
        Nominated batches never stream (their correction carry is
        per-solve).

        ``mesh`` (default: the constructor's mesh): a jax.sharding.Mesh
        with a "nodes" axis — every node-resident table/state array
        shards over its trailing node axis (which must be a multiple of
        the device count; Snapshot.pad_multiple guarantees this on the
        scheduler path), per-pod/per-class inputs replicate, and GSPMD
        inserts the cross-shard collectives. Assignments are bit-
        identical to the single-device solve for any device count
        (integer scores, stable reductions — tests/test_sharding.py).

        Without ``static``/``ports``/``spread``/``interpod`` tensors, a
        trivial single-class mask (valid ∧ schedulable) reproduces the
        resources-only pipeline.
        """
        cfg = self.config
        if mesh is None:
            mesh = self.mesh
        if self.capture_hook is not None:
            # BEFORE the PRNG derivation and the trivial-tensor default
            # filling: step_count is exactly what a replay must restore,
            # and None containers stay None (the replayed solve
            # re-derives the identical trivial tensors, and the bundle
            # stays small). Raw references — the hook copies host-side.
            self.capture_hook(
                nodes=nodes,
                pods=pods,
                static=static,
                ports=ports,
                spread=spread,
                interpod=interpod,
                nominated=nominated,
                nominated_slot=nominated_slot,
                step_count=self._step_count,
                split=split,
                defer_read=defer_read,
                session=col_versions is not None,
                allow_heal=allow_heal,
                chain_occupancy=chain_occupancy,
                config=_capture_config_fingerprint(cfg),
            )
        fdtype = jnp.float64 if cfg.balanced_fdtype == "float64" else jnp.float32
        key = jax.random.PRNGKey(cfg.seed + self._step_count)
        self._step_count += 1
        if static is None:
            static = trivial_static_tensors(pods, nodes.padded, nodes.schedulable)
        if ports is None:
            ports = trivial_port_tensors(pods, nodes.padded)
        if spread is None:
            spread = trivial_spread_tensors(pods, nodes.padded, static.c_pad)
        if interpod is None:
            interpod = trivial_interpod_tensors(pods, nodes.padded, static.c_pad)
        use_spread = not spread.empty
        use_interpod = not interpod.empty
        use_nominated = nominated is not None and not nominated.empty
        session = col_versions is not None

        # index-dtype audit (solver/budget.py): the flattened-index
        # products this dispatch's compiled program forms must fit
        # their container dtypes — a 2^31-scale shape fails loudly
        # here instead of silently wrapping on device. Host ints, ~ns.
        from .budget import assert_index_headroom

        assert_index_headroom(
            pods.padded,
            nodes.padded,
            d_pad=max(spread.d_pad, interpod.d_pad),
            group=max(cfg.group_size, 1),
        )

        h2d_bytes = 0
        if session:
            h2d_bytes += self._session.sync(
                nodes, col_versions, allow_heal=allow_heal, mesh=mesh
            )
            nt = self._session.nt
            persist = self._session.persist
            ct, ct_bytes = self._session.class_tables(
                static, spread, interpod, mesh=mesh,
                digest=chain_key[0] if chain_key is not None else None,
            )
            h2d_bytes += ct_bytes
            if ct_bytes:  # a cache miss: the tables were placed anew
                self._tally("class_table_uploads")
        else:
            _, put = placers(mesh, nodes.padded)
            nt = {
                "alloc": put(nodes.allocatable),
                "max_pods": put(nodes.max_pods),
                "node_valid": put(nodes.valid),
            }
            persist = {
                "used": put(nodes.used),
                "nonzero_used": put(nodes.nonzero_used),
                "pod_count": put(nodes.pod_count),
            }
            ct = _place_class_tables(
                static, spread, interpod, mesh, nodes.padded
            )
            h2d_bytes += sum(
                a.nbytes
                for a in (
                    nodes.allocatable, nodes.max_pods, nodes.valid,
                    nodes.used, nodes.nonzero_used, nodes.pod_count,
                )
            ) + sum(
                np.asarray(a).nbytes
                for a in _class_table_arrays(static, spread, interpod)
            )

        # per-batch node-state rows, one int32 upload
        b_arrs = [ports.used]
        bspec = [("port_used", 0, ports.used.shape[0])]
        off = ports.used.shape[0]
        for name, arr in (
            ("spr_cnt", spread.cnt0),
            ("ipa_in", interpod.in_cnt0),
            ("ipa_ex", interpod.ex_cnt0),
        ):
            b_arrs.append(arr)
            bspec.append((name, off, arr.shape[0]))
            off += arr.shape[0]
        if use_nominated:
            b_arrs.append(nominated.count)
            bspec.append(("nom_cnt", off, nominated.count.shape[0]))
            off += nominated.count.shape[0]
        bstate = np.concatenate(b_arrs, axis=0)
        nom_used = (
            nominated.used if use_nominated else np.zeros((1, 1, 1), np.int64)
        )
        use_nominated_ports = (
            use_nominated and nominated.port_takes is not None
        )
        nom_ports = (
            nominated.port_takes
            if use_nominated_ports
            else np.zeros((1, 1, 1), np.int32)
        )

        # per-pod inputs, one upload per dtype class
        pod_valid = (pods.valid & pods.feasible_static)[:, None]
        i64_cols = [("req", pods.req), ("nonzero_req", pods.nonzero_req)]
        i32_cols = [
            ("class_of", np.asarray(static.class_of)[:, None]),
            ("pod_takes", np.asarray(ports.pod_takes)),
        ]
        if use_nominated:
            slots = np.full(pods.padded, -1, dtype=np.int32)
            if nominated_slot is not None:
                slots[: len(nominated_slot)] = nominated_slot
            levels = nominated.level_of(
                np.asarray(pods.priority, dtype=np.int32)
            )
            i32_cols += [
                ("nom_level", levels[:, None]),
                ("nominated_slot", slots[:, None]),
            ]
        bool_cols = [
            ("req_mask", pods.req_mask),
            ("pod_valid", pod_valid),
            ("pod_conflict", np.asarray(ports.pod_conflict)),
        ]
        if use_spread:
            bool_cols.append(("spr_placed", np.asarray(spread.placed_match)))
        if use_interpod:
            i32_cols += [
                ("ipa_in_match", np.asarray(interpod.in_match)),
                ("ipa_ex_owned", np.asarray(interpod.ex_owned)),
                ("ipa_m_w", np.asarray(interpod.m_w)),
            ]
            bool_cols += [
                ("ipa_m_anti", np.asarray(interpod.m_anti)),
                ("ipa_self_aff", np.asarray(interpod.self_aff)[:, None]),
            ]
        squeeze_names = {
            "class_of", "pod_valid", "ipa_self_aff", "nom_level",
            "nominated_slot",
        }

        def pack_x(cols):
            spec = []
            off = 0
            for name, arr in cols:
                spec.append((name, off, arr.shape[1], name in squeeze_names))
                off += arr.shape[1]
            return np.concatenate([a for _, a in cols], axis=1), spec

        xi64, spec64 = pack_x(i64_cols)
        xi32, spec32 = pack_x(i32_cols)
        xbool, specb = pack_x(bool_cols)
        xspec = tuple(
            [(n, "i64", s, w, sq) for n, s, w, sq in spec64]
            + [(n, "i32", s, w, sq) for n, s, w, sq in spec32]
            + [(n, "bool", s, w, sq) for n, s, w, sq in specb]
        )

        kw = dict(
            tie_break=cfg.tie_break,
            scoring_strategy=cfg.scoring_strategy,
            w_cpu=cfg.cpu_weight,
            w_mem=cfg.mem_weight,
            rtc_shape=tuple(tuple(p) for p in cfg.rtc_shape),
            disabled=tuple(sorted(cfg.disabled_filters)),
            w_fit=cfg.fit_weight,
            w_balanced=cfg.balanced_weight,
            # batch-static dead-weight elimination: an all-zero preference
            # row normalizes to the SAME value on every feasible node, and
            # a constant term can't move an argmax or its tie set — so the
            # plugin's weight is dropped at trace time, removing two [N]
            # integer-division normalizes from every scan step / grouped
            # iteration. Assignments are bit-identical either way; only
            # internal (never returned) score values shift by a constant.
            w_taint=cfg.taint_weight if np.any(static.taint_cnt) else 0,
            w_nodeaff=(
                cfg.node_affinity_weight
                if np.any(static.nodeaff_pref)
                else 0
            ),
            w_image=cfg.image_weight if np.any(static.image_score) else 0,
            w_spread=cfg.spread_weight,
            w_interpod=cfg.interpod_weight,
            use_spread=use_spread,
            use_interpod=use_interpod,
            d_pad=spread.d_pad,
            ipa_d_pad=interpod.d_pad,
            fdtype=fdtype,
            spread_soft=spread.has_soft,
            ipa_ident=interpod.ident,
            ipa_score=interpod.has_score,
            pallas=cfg.pallas,
            use_nominated=use_nominated,
            use_nominated_ports=use_nominated_ports,
            use_extra_score=static.extra_score is not None,
        )
        group = cfg.group_size
        grouped = grouped_eligible(
            cfg, pods.padded, nodes.padded, use_spread, use_interpod,
            use_nominated,
            spread_groupable=not spread.has_soft,
            interpod_groupable=interpod.anti_only,
        )
        compact = False
        vcnt_host = np.zeros(1, dtype=np.int32)
        if grouped:
            kinds_host = self._chunk_kinds(
                pods, static, ports, spread, interpod, group,
                use_spread, use_interpod,
            )
            c = pods.padded // group
            pvc = pod_valid[:, 0].reshape(c, group)
            vc = pvc.sum(axis=1).astype(np.int32)
            self._tally("grouped")
            # chunks that hold no pod are kind 1 by construction
            # (_chunk_kinds) and place nothing: not a plain chunk
            self._tally("padding", int((vc == 0).sum()))
            for v, cnt in zip(
                *np.unique(kinds_host[vc > 0], return_counts=True)
            ):
                self._tally(f"kind{int(v)}", int(cnt))
            kinds = jnp.asarray(kinds_host)
            # COMPACT eligibility (wire-cost fast path, _solve_grouped
            # docstring): every chunk's validity is a prefix and its valid
            # per-pod rows are identical — then one representative row per
            # chunk + a valid count replaces the [P, *] uploads, and even
            # kind-0 chunks replay bit-identically from the broadcast.
            if cfg.compact_wire and bool(
                (pvc == (np.arange(group)[None, :] < vc[:, None])).all()
            ):
                pv_off = next(
                    s for n, s, w, _ in specb if n == "pod_valid"
                )
                xb_cmp = xbool.copy()
                xb_cmp[:, pv_off] = True  # reconstructed from vcnt on device

                def _uniform(x):
                    a = x.reshape(c, group, -1)
                    return bool(
                        ((a == a[:, :1]) | ~pvc[:, :, None]).all()
                    )

                if _uniform(xi64) and _uniform(xi32) and _uniform(xb_cmp):
                    compact = True
                    vcnt_host = vc
                    xi64 = np.ascontiguousarray(
                        xi64.reshape(c, group, -1)[:, 0]
                    )
                    xi32 = np.ascontiguousarray(
                        xi32.reshape(c, group, -1)[:, 0]
                    )
                    xbool = np.ascontiguousarray(
                        xbool.reshape(c, group, -1)[:, 0]
                    )
                    self._tally("compact_batches")
        else:
            group = 1
            kinds = jnp.zeros(1, dtype=jnp.int32)
            kinds_host = None
            self._tally("scan")
        self._tally("spread_instances", int(spread.num_instances))
        self._tally("interpod_incoming", int(interpod.num_in))
        self._tally("interpod_existing", int(interpod.num_ex))
        # the domain tables whose reductions go through ops/domains.py:
        # the spread table's in every program, the inter-pod table's only
        # in the grouped program's anti branch (the per-pod step counts
        # inter-pod domains over T * d_pad slots, ops/interpod.py)
        for carried, slots in (
            (use_spread, spread.d_pad),
            (use_interpod and grouped, interpod.d_pad),
        ):
            if carried:
                self._tally(
                    "domains_dense" if dense_form(slots) else "domains_scatter"
                )

        # streaming chain eligibility: session + deferred + un-nominated
        stream = (
            session
            and defer_read
            and not use_nominated
            and (chain_occupancy or stream_carry_out)
        )
        chain_occupancy = chain_occupancy and stream
        if chain_occupancy and not self.can_chain(
            chain_key, col_versions
        ):
            # the caller's pre-dispatch check and this one race nothing
            # (single driver thread); a mismatch here is a logic error
            # upstream — refuse loudly rather than chain wrongly
            raise ValueError(
                "chain_occupancy requested but the session carry does "
                "not match (stale key or dirty columns)"
            )

        # per-solve transfer accounting + mesh placement: per-pod packed
        # arrays and scalars replicate; node-axis rows (bstate, nominated
        # load) shard over the mesh's node axis. A chained dispatch
        # consumes the resident carry instead of uploading bstate.
        h2d_bytes += (
            (0 if chain_occupancy else bstate.nbytes)
            + xi64.nbytes + xi32.nbytes + xbool.nbytes
            + vcnt_host.nbytes + np.asarray(nom_used).nbytes
            + np.asarray(nom_ports).nbytes
        )
        if grouped:
            h2d_bytes += kinds_host.nbytes
        metrics.h2d_bytes_total.inc(int(h2d_bytes))
        if session:
            # the only per-batch download: the (padded) assignment vector
            metrics.d2h_bytes_total.inc(int(pods.padded) * 4)
        else:
            metrics.d2h_bytes_total.inc(
                ((nodes.allocatable.shape[0] + 3) * nodes.padded
                 + pods.padded) * 8
            )
        dev, dev_n = placers(mesh, nodes.padded)
        if mesh is not None:
            _repl = replicated(mesh)
            key = jax.device_put(key, _repl)
            kinds = jax.device_put(kinds, _repl)

        want_chain = split > 1 and session and defer_read
        if (want_chain or stream) and not use_nominated:
            k_split = self._feasible_split(
                max(split, 1), pods.padded, grouped, group
            )
            if k_split > 1 or stream:
                # stream solves route through the chain dispatcher even
                # unsplit (k_split == 1): it is the one path that can
                # consume/produce the cross-batch occupancy carry
                handles = self._solve_chain(
                    k_split, nt, ct, bstate, xi64, xi32, xbool,
                    kinds_host if grouped else None, vcnt_host, compact,
                    nom_used, nom_ports, key, pods, mesh,
                    bspec=tuple(bspec), xspec=xspec, grouped=grouped,
                    group=group,
                    chain_start=(
                        self._session.stream_carry
                        if chain_occupancy
                        else None
                    ),
                    carry_out=stream_carry_out,
                    chain_key=chain_key,
                    **kw,
                )
                if self._session.stream_carry is not None:
                    # the kept carry's chain baseline: host columns as
                    # of this dispatch (note_stream_applied advances it
                    # as ring-slot applies land cleanly)
                    self._session.stream_versions = col_versions[
                        : self._session.padded
                    ].copy()
                return handles

        if session:
            # this dispatch donates the session persist, whose fit
            # buffers any saved stream carry shares: the carry cannot
            # survive a non-streaming solve
            self._session.stream_carry = None
            self._session.stream_key = None
            self._session.stream_versions = None
        run = _run_packed_jit if session else _run_packed_jit_nodonate
        out = run(
            nt,
            ct,
            persist,
            dev_n(bstate),
            dev(xi64),
            dev(xi32),
            dev(xbool),
            kinds,
            dev(vcnt_host),
            dev_n(nom_used),
            dev_n(nom_ports),
            key,
            bspec=tuple(bspec),
            xspec=xspec,
            grouped=grouped,
            group=group,
            # packed single-buffer download only on the unsharded path:
            # the SPMD partitioner rejects the flatten+concat of the
            # sharded state with a dtype-mixed dynamic_update_slice
            # (s64 index vs s32 shard offset, XLA verifier error), and a
            # sharded standalone solve is a dryrun/smoke/test context
            # where four reads instead of one is acceptable
            pack_result=not session and mesh is None,
            compact=compact,
            **kw,
        )
        if session:
            assignments, new_persist = out
            self._session.persist = new_persist
            if defer_read:
                handle = DeferredAssignments(assignments, pods.num_pods)
                # split requested but clamped/ineligible (nominated batch,
                # indivisible padding): the contract stays "list in, list
                # out" so the pipelined caller never type-switches
                return [handle] if want_chain else handle
            return np.asarray(assignments)[: pods.num_pods]
        if mesh is not None:
            # sharded standalone: unpacked results (see pack_result above)
            assignments, out_state = out
            nodes.used = np.array(out_state["used"])
            nodes.nonzero_used = np.array(out_state["nonzero_used"])
            nodes.pod_count = np.array(out_state["pod_count"]).astype(
                np.int32
            )
            return np.asarray(assignments).astype(np.int32)[: pods.num_pods]
        # standalone: ONE packed download (np.array = writable copy; the
        # unpacked slices below are views of it, so later in-place
        # dirty-column writes to ``nodes`` stay legal)
        flat = np.array(out)
        k = nodes.allocatable.shape[0]
        npad = nodes.padded
        o = 0
        nodes.used = flat[o : o + k * npad].reshape(k, npad)
        o += k * npad
        nodes.nonzero_used = flat[o : o + 2 * npad].reshape(2, npad)
        o += 2 * npad
        nodes.pod_count = flat[o : o + npad].astype(np.int32)
        o += npad
        return flat[o:].astype(np.int32)[: pods.num_pods]

    @staticmethod
    def _feasible_split(
        split: int, pod_pad: int, grouped: bool, group: int
    ) -> int:
        """Largest K <= split such that the padded pod axis cuts into K
        equal sub-batches the dispatch machinery can chain: K divides
        pod_pad, and — when the grouped path engages — each sub-batch
        stays a whole number of group chunks (the chunk-kind dispatch
        and the compact-wire representative rows both slice along the
        chunk axis)."""
        for k in range(min(split, pod_pad), 1, -1):
            if pod_pad % k:
                continue
            if grouped and (pod_pad // k) % group:
                continue
            return k
        return 1

    def _solve_chain(
        self,
        k_split: int,
        nt,
        ct,
        bstate,
        xi64,
        xi32,
        xbool,
        kinds_host,  # [C] int32 (grouped) | None (per-pod scan)
        vcnt_host,
        compact: bool,
        nom_used,
        nom_ports,
        key,
        pods: PodBatch,
        mesh=None,
        *,
        bspec,
        xspec,
        grouped: bool,
        group: int,
        chain_start: dict | None = None,
        carry_out: bool = False,
        chain_key: tuple | None = None,
        **kw,
    ) -> list[DeferredAssignments]:
        """Dispatch one tensorized batch as ``k_split`` chained
        sub-solves (see ``solve``'s ``split`` doc). The per-pod packed
        arrays slice along the (chunk-aligned) pod axis; sub-solve i+1's
        ``state0`` is sub-solve i's full carried state
        (BatchCarriedUsage) donated straight through — no host sync
        anywhere in the chain. Trailing all-padding sub-batches are
        never dispatched.

        ``chain_start`` (the streaming dispatcher's cross-batch chain):
        the PREVIOUS batch's full carried state — the first sub-solve
        chains on it exactly like a mid-chain sub-solve would, so the
        occupancy rows the previous batch's placements advanced never
        re-upload from host. ``carry_out`` keeps the final carried
        state resident as the session's stream carry under
        ``chain_key`` for the next batch to consume."""
        sub = pods.padded // k_split
        cpk = sub // group  # chunks per sub-batch (grouped/compact axes)
        handles: list[DeferredAssignments] = []
        carry: BatchCarriedUsage | None = (
            BatchCarriedUsage(chain_start)
            if chain_start is not None
            else None
        )
        if chain_start is not None:
            self._tally("stream_chained")
            # the carry is consumed (donated) by the first dispatch —
            # it can no longer be offered to anyone else
            self._session.stream_carry = None
            self._session.stream_key = None
            self._session.stream_versions = None
        dummy_b = np.zeros((1, 1), dtype=np.int32)
        # node pad = bstate's trailing axis (chained solves are
        # session-mode only; nominated dummies replicate)
        dev, dev_n = placers(mesh, bstate.shape[1])
        nom_used_j = dev_n(nom_used)
        nom_ports_j = dev_n(nom_ports)
        try:
            for i in range(k_split):
                lo = i * sub
                if lo >= pods.num_pods:
                    break
                sl = slice(i * cpk, (i + 1) * cpk) if compact else slice(
                    lo, lo + sub
                )
                first = carry is None
                out = _run_packed_jit(
                    nt,
                    ct,
                    self._session.persist if first else carry.state,
                    dev_n(bstate) if first else dev(dummy_b),
                    dev(xi64[sl]),
                    dev(xi32[sl]),
                    dev(xbool[sl]),
                    dev(kinds_host[i * cpk : (i + 1) * cpk])
                    if grouped
                    else dev(np.zeros(1, dtype=np.int32)),
                    dev(vcnt_host[i * cpk : (i + 1) * cpk])
                    if compact
                    else dev(np.zeros(1, dtype=np.int32)),
                    nom_used_j,
                    nom_ports_j,
                    jax.random.fold_in(key, i),
                    bspec=bspec,
                    xspec=xspec,
                    grouped=grouped,
                    group=group,
                    pack_result=False,
                    compact=compact,
                    chain_in=not first,
                    chain_out=True,
                    **kw,
                )
                assignments, st = out
                carry = BatchCarriedUsage(st)
                handles.append(
                    DeferredAssignments(
                        assignments, min(sub, pods.num_pods - lo), lo=lo
                    )
                )
        except Exception:
            # the chain donated session buffers before dying: the resident
            # state is unusable — drop it so the next solve re-uploads
            self.reset_session()
            raise
        self._session.persist = {
            name: carry.state[name]
            for name in ("used", "nonzero_used", "pod_count")
        }
        if carry_out and chain_key is not None:
            # keep the FULL carried state resident for the next batch's
            # chain (its fit entries are the same buffers as persist's;
            # every donating path nulls this out before reusing them)
            self._session.stream_carry = carry.state
            self._session.stream_key = chain_key
        else:
            self._session.stream_carry = None
            self._session.stream_key = None
            self._session.stream_versions = None
        self._tally("chained_subbatches", len(handles))
        return handles

    @staticmethod
    def _chunk_kinds(
        pods: PodBatch,
        static: StaticPluginTensors,
        ports: PortTensors,
        spread: SpreadTensors,
        interpod: InterpodTensors,
        group: int,
        use_spread: bool,
        use_interpod: bool,
    ) -> np.ndarray:
        """[P // group] int32 chunk dispatch for _solve_grouped:
        0 slow / 1 plain fast / 2 spread fast / 3 anti fast.

        A fast kind requires `group` consecutive IDENTICAL valid pods
        (class, requests, port rows, and — when active — the spread/
        interpod per-pod rows). Kind 2/3 additionally require the single-
        constraint, zero-preference-row shapes whose sequential validity
        the device branches prove (see _solve_grouped); anything else is
        kind 0 and replays the full per-pod pipeline."""
        gn = pods.padded // group

        def same(arr: np.ndarray) -> np.ndarray:
            a = arr.reshape(gn, group, -1)
            return (a == a[:, :1]).all(axis=(1, 2))

        valid = pods.valid & pods.feasible_static
        vchunk = valid.reshape(gn, group)
        uniform = vchunk.all(axis=1)
        arrays = [
            np.asarray(static.class_of),
            pods.req,
            pods.req_mask,
            pods.nonzero_req,
            np.asarray(ports.pod_conflict),
            np.asarray(ports.pod_takes),
        ]
        if use_spread:
            arrays.append(np.asarray(spread.placed_match))
        if use_interpod:
            arrays += [
                np.asarray(interpod.in_match),
                np.asarray(interpod.ex_owned),
                np.asarray(interpod.m_anti),
                np.asarray(interpod.m_w),
                np.asarray(interpod.self_aff)[:, None],
            ]
        for arr in arrays:
            uniform &= same(arr)
        padding = ~vchunk.any(axis=1)

        kinds = np.zeros(gn, dtype=np.int32)
        # all-padding chunks are trivially fast: vcnt == 0 places nothing
        kinds[padding] = 1
        if not (use_spread or use_interpod):
            kinds[uniform] = 1
            return kinds

        class_of = np.asarray(static.class_of)
        taint = np.asarray(static.taint_cnt)
        nodeaff = np.asarray(static.nodeaff_pref)
        # hoist tensor->ndarray conversions out of the per-chunk loop
        if use_spread:
            spr_hard = np.asarray(spread.hard)
            spr_soft = np.asarray(spread.soft)
            spr_placed = np.asarray(spread.placed_match)
            spr_min_dom = np.asarray(spread.min_domains)
        if use_interpod:
            ipa_anti = np.asarray(interpod.cls_req_anti)
            ipa_aff = np.asarray(interpod.cls_req_aff)
            ipa_pref = np.asarray(interpod.cls_pref)
            ipa_in_m = np.asarray(interpod.in_match)
            ipa_ex_o = np.asarray(interpod.ex_owned)
            ipa_m_anti = np.asarray(interpod.m_anti)
            ipa_m_w = np.asarray(interpod.m_w)
            ipa_ex_anti = np.asarray(interpod.ex_anti)
            ipa_in_dom = np.asarray(interpod.in_dom)
            ipa_ex_dom = np.asarray(interpod.ex_dom)
        first = np.arange(gn) * group  # first pod index per chunk
        for g in np.nonzero(uniform & ~padding)[0]:
            i = int(first[g])
            c = int(class_of[i])
            no_pref_rows = not taint[c].any() and not nodeaff[c].any()

            if use_spread:
                hard_row = spr_hard[c]
                soft_row = spr_soft[c]
                placed_row = spr_placed[i]
                spr_neutral = (
                    (hard_row < 0).all()
                    and (soft_row < 0).all()
                    and not placed_row.any()
                )
                j = int(hard_row[0])
                spr_fast = (
                    j >= 0
                    and (hard_row[1:] < 0).all()
                    and (soft_row < 0).all()
                    and no_pref_rows
                    and bool(placed_row[j])
                    and not placed_row[np.arange(len(placed_row)) != j].any()
                    and int(spr_min_dom[j]) < 0
                )
            else:
                spr_neutral, spr_fast = True, False

            if use_interpod:
                anti_row = ipa_anti[c]
                aff_row = ipa_aff[c]
                pref_row = ipa_pref[c]
                in_m = ipa_in_m[i]
                ex_o = ipa_ex_o[i]
                m_anti = ipa_m_anti[i]
                m_w = ipa_m_w[i]
                ipa_neutral = (
                    (anti_row < 0).all()
                    and (aff_row < 0).all()
                    and (pref_row < 0).all()
                    and not in_m.any()
                    and not ex_o.any()
                    and not m_anti.any()
                    and not m_w.any()
                )
                j = int(anti_row[0])
                ex_idx = np.nonzero(ex_o)[0]
                ipa_fast = (
                    j >= 0
                    and (anti_row[1:] < 0).all()
                    and (aff_row < 0).all()
                    and (pref_row < 0).all()
                    and no_pref_rows
                    and not m_w.any()
                    and in_m[j] > 0
                    and not in_m[np.arange(len(in_m)) != j].any()
                    and len(ex_idx) == 1
                    and bool(m_anti[ex_idx[0]])
                    and m_anti.sum() == 1
                    and bool(ipa_ex_anti[ex_idx[0]])
                    and np.array_equal(
                        ipa_in_dom[j], ipa_ex_dom[ex_idx[0]]
                    )
                )
            else:
                ipa_neutral, ipa_fast = True, False

            if spr_fast and ipa_neutral:
                kinds[g] = 2
            elif ipa_fast and spr_neutral:
                kinds[g] = 3
            elif spr_neutral and ipa_neutral:
                kinds[g] = 1
        return kinds
