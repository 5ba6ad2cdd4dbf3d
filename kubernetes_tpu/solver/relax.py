"""Convex-relaxation mega-planner — fractional assignment by mirror
descent + dual ascent, TPU-native (ISSUE 19, ROADMAP item #3).

The single-shot auction prices capacity through SEQUENTIAL rounds:
top-T bids, segmented admission, price escalation on rejection. Each
round is dense, but the round chain is inherently serial and the top-T
window caps how much of the price surface one round can explore — at
1M+ pods the plan solve stops fitting a planning cycle. The CvxCluster
line of work (PAPERS.md) shows the road past it: RELAX the integral
assignment to a fractional one, solve the relaxation with first-order
iterations that are pure matmul + softmax — natively batched, node-axis
mesh-shardable, exactly the arithmetic the TPU is built for — then
round and repair the integrality gap.

The relaxation, in request-class space (never [P, N] — the same memory
move that makes the auction fit, `single_shot.request_classes`):

  maximize   sum_{rc,n} score[rc,n] * x[rc,n]  +  temp * H(x)
  s.t.       sum_rc x[rc,n] * req[rc,k] <= free[k,n]     (lam[k,n])
             sum_rc x[rc,n]             <= cnt_free[n]   (mu[n])
             sum_n  x[rc,n]              = mass[rc]
             x >= 0,  x[rc,n] = 0 where statically infeasible

H is the entropy regularizer that makes the primal step closed-form:
holding the duals fixed, the optimal x is a temperature-``temp``
softmax over (score - penalty) per class, scaled to the class mass —
one [RC,K]x[K,N] matmul for the penalty, one softmax. The duals then
take a projected ascent step on the normalized overcommit
(load/capacity - 1). Iterations run in one jitted
``lax.while_loop`` with residual-based early exit: converged solves
stop paying for the remaining iteration budget.

Rounding is deterministic and device-side: per-class quotas
(round-to-nearest of x, clamped per node against remaining integer
capacity by a scan over the small RC axis, mass-clamped per class),
then pods map to quota slots by priority rank through one
searchsorted over the flattened [RC*N] quota prefix — higher-priority
pods take the quota slots, the tail stays unassigned. The tail then
repairs through the EXISTING single-shot auction (scarcity repair and
all), so end states carry the auction's feasibility guarantees and
pass ``validate_assignments``: the relaxation proposes, the auction
disposes.

The converged duals are exported as PRICES: ``lam[k, n]`` is the
marginal score cost of one normalized unit of resource k on node n
(``mu`` the pod-slot analog) — aggregated per node group they are the
cost signal ROADMAP item #2's autoscaler consumes: a group whose price
stays pinned at zero has slack; a group whose price climbs is worth
growing.

Scope mirrors the auction: NodeResourcesFit + folded static plugin
masks + headroom scoring, ``"spread"``/``"pack"`` objectives with the
same integer base score. HBM discipline: ``solver/budget.py``'s
``relax_estimate`` byte model + ``assert_index_headroom`` (with the
relaxation's own flattened-index lanes audited) run before dispatch.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..tensorize.plugins import StaticPluginTensors, trivial_static_tensors
from ..tensorize.schema import CPU_IDX, MEM_IDX, NodeBatch, PodBatch
from .single_shot import (
    SingleShotConfig,
    _cumsum0,
    _segmented_prefix,
    _single_shot_jit,
    request_classes,
)

NEG_F = jnp.float32(-1e30)


@dataclass(frozen=True)
class RelaxConfig:
    # iteration budget for the dual-ascent loop; the residual early
    # exit means converged shapes pay only what they use
    max_iters: int = 128
    # convergence tolerance on the relative overcommit residual:
    # max over (k, n) of load/capacity - 1, clipped at 0. 0.01 = the
    # fractional plan overcommits no node by more than 1% before
    # rounding (rounding itself is exact — the clamp admits only
    # integer quotas that fit).
    tol: float = 0.01
    # softmax temperature in score points: lower = harder argmax
    # (faster commitment, worse exploration), higher = smoother mass
    # spreading. Score range is 0..100; 8 measured a good balance.
    temp: float = 8.0
    # dual ascent step in score points per unit of relative overcommit
    step: float = 4.0
    # "spread" = prefer high-headroom nodes; "pack" = prefer full
    # nodes (the planner posture) — same integer base score as the
    # auction, so objectives are directly comparable
    objective: str = "spread"


def _relax(
    alloc,  # [K, N] int
    used0,  # [K, N] int
    pod_count0,  # [N] int32
    max_pods,  # [N] int32
    node_valid,  # [N] bool
    static_mask,  # [C, N] bool
    rc_req,  # [RC, K] int — request per request-class
    rc_static,  # [RC] int32 — static-plugin class of the request-class
    rc_of,  # [P] int32
    priority,  # [P] int32
    pod_valid,  # [P] bool
    tol,  # f32 scalar
    temp,  # f32 scalar
    step,  # f32 scalar
    *,
    max_iters: int,
    pack: bool = False,
):
    p = rc_of.shape[0]
    n = alloc.shape[1]
    rc = rc_req.shape[0]

    pod_idx = jnp.arange(p, dtype=jnp.int32)
    mass = jax.ops.segment_sum(
        pod_valid.astype(jnp.float32), rc_of, num_segments=rc
    )  # [RC] valid pods per class

    # -- capacities and the static feasibility mask (fixed across
    # iterations: the relaxation prices the SNAPSHOT, like one auction
    # solve) --
    free_i = jnp.maximum(alloc - used0, 0)  # [K, N] int64
    cnt_free_i = jnp.maximum(
        (max_pods - pod_count0).astype(jnp.int32), 0
    )  # [N] int32
    free_f = free_i.astype(jnp.float32)
    cnt_free_f = cnt_free_i.astype(jnp.float32)
    req_f = rc_req.astype(jnp.float32)  # [RC, K]

    # single-pod fit at snapshot free capacity + folded static masks:
    # a cell that cannot host even one pod of the class carries no
    # fractional mass, ever
    fit = jnp.all(rc_req[:, :, None] <= free_i[None, :, :], axis=1)
    ok = (
        fit
        & static_mask[rc_static]
        & node_valid[None, :]
        & (cnt_free_i >= 1)[None, :]
    )  # [RC, N]
    feas_any = jnp.any(ok, axis=1)  # [RC]

    # same integer base score as the auction (headroom at snapshot,
    # pack flips the sense) so relax-vs-auction objectives compare
    alloc2 = alloc[: MEM_IDX + 1].astype(jnp.float32)
    used2 = used0[: MEM_IDX + 1].astype(jnp.float32)
    free_frac = jnp.where(
        alloc2 > 0, (alloc2 - used2) / jnp.maximum(alloc2, 1.0), 0.0
    )
    headroom = (
        100.0 * (free_frac[CPU_IDX] + free_frac[MEM_IDX]) / 2.0
    ).astype(jnp.int32)
    base_score = (jnp.int32(100) - headroom) if pack else headroom
    score_f = base_score.astype(jnp.float32)  # [N]

    inv_free = 1.0 / jnp.maximum(free_f, 1.0)  # [K, N]
    inv_cnt = 1.0 / jnp.maximum(cnt_free_f, 1.0)  # [N]

    def primal(lam, mu):
        """Closed-form entropic primal: x = mass * softmax over the
        penalized score. Penalty = the duals paired with the
        NORMALIZED constraint coefficients req/free — one matmul."""
        pen = req_f @ (lam * inv_free)  # [RC, N]
        logits = (score_f[None, :] - pen - (mu * inv_cnt)[None, :]) / temp
        logits = jnp.where(ok, logits, NEG_F)
        m = jnp.max(logits, axis=1, keepdims=True)
        z = jnp.where(ok, jnp.exp(logits - m), 0.0)
        denom = jnp.maximum(jnp.sum(z, axis=1, keepdims=True), 1e-30)
        x = mass[:, None] * z / denom
        return jnp.where(feas_any[:, None], x, 0.0)

    def residual_of(x):
        load = req_f.T @ x  # [K, N]
        over_res = jnp.max(
            jnp.where(node_valid[None, :], load * inv_free - 1.0, 0.0)
        )
        cnt_load = jnp.sum(x, axis=0)
        over_cnt = jnp.max(
            jnp.where(node_valid, cnt_load * inv_cnt - 1.0, 0.0)
        )
        return jnp.maximum(jnp.maximum(over_res, over_cnt), 0.0)

    def cond(state):
        it, _, _, res = state
        return (it < max_iters) & (res > tol)

    def body(state):
        it, lam, mu, _ = state
        x = primal(lam, mu)
        load = req_f.T @ x  # [K, N]
        cnt_load = jnp.sum(x, axis=0)  # [N]
        # projected dual ascent on relative overcommit: prices rise
        # where the fractional plan overbooks, decay toward 0 where it
        # leaves slack — the converged lam/mu ARE the exported prices
        lam = jnp.maximum(lam + step * (load * inv_free - 1.0), 0.0)
        mu = jnp.maximum(mu + step * (cnt_load * inv_cnt - 1.0), 0.0)
        return it + 1, lam, mu, residual_of(primal(lam, mu))

    k = alloc.shape[0]
    lam0 = jnp.zeros((k, n), dtype=jnp.float32)
    mu0 = jnp.zeros(n, dtype=jnp.float32)
    iters, lam, mu, res = jax.lax.while_loop(
        cond, body, (jnp.int32(0), lam0, mu0, jnp.float32(jnp.inf))
    )
    x = primal(lam, mu)

    # -- deterministic rounding: fractional mass -> integer per-class
    # quotas, clamped against remaining integer capacity (scan over the
    # small RC axis — the only sequential chain, length RC not P) --
    q_des = jnp.floor(x + 0.5).astype(jnp.int32)  # [RC, N]
    mass_i = mass.astype(jnp.int32)

    def round_class(carry, inp):
        free_c, cnt_c = carry  # [K, N] int64, [N] int32
        qd, req_row, ok_row, m_rc = inp
        safe_req = jnp.maximum(req_row, 1)  # [K]
        cap_k = free_c // safe_req[:, None]  # [K, N] int64
        cap_k = jnp.where(req_row[:, None] > 0, cap_k, jnp.int64(1 << 31))
        # per-node admissible count for this class, bounded by the pod
        # axis (mass <= P < 2^31) so the narrowing below cannot wrap
        cap = jnp.minimum(
            jnp.min(cap_k, axis=0), cnt_c.astype(jnp.int64)
        )
        cap = jnp.clip(cap, 0, jnp.int64(m_rc)).astype(jnp.int32)
        q = jnp.where(ok_row, jnp.minimum(qd, cap), 0)
        # mass clamp: cumulative quota along the node axis never
        # exceeds the class's pod count (round-to-nearest can
        # overshoot). The prefix accumulates in int64 — N * per-node
        # quota passes 2^31 at mega shapes — then narrows: the clamped
        # value is bounded by q (int32) by construction.
        q64 = q.astype(jnp.int64)
        cq = jnp.cumsum(q64)
        q = jnp.clip(
            m_rc.astype(jnp.int64) - (cq - q64), 0, q64
        ).astype(jnp.int32)
        free_c = free_c - q.astype(jnp.int64)[None, :] * req_row[:, None]
        cnt_c = cnt_c - q
        return (free_c, cnt_c), q

    (_, _), quotas = jax.lax.scan(
        round_class,
        (free_i, cnt_free_i),
        (q_des, rc_req, ok, mass_i),
    )  # quotas [RC, N] int32

    # -- pods -> quota slots by priority rank within their class --
    inv_prio = jnp.int64((1 << 31) - 1) - priority.astype(jnp.int64)
    key = jnp.where(
        pod_valid,
        rc_of.astype(jnp.int64) * (1 << 32) + inv_prio,
        jnp.int64(1) << 62,
    )
    order = jnp.argsort(key)  # stable: pod index is the final tiebreak
    rc_sorted = rc_of[order]
    # ranks only matter for valid pods (invalid all sort to the tail
    # under the 2^62 key and are masked out of `placed` below)
    seg_start = jnp.concatenate(
        [
            jnp.array([True], dtype=jnp.bool_),
            rc_sorted[1:] != rc_sorted[:-1],
        ]
    )
    seg_id = _cumsum0(seg_start.astype(jnp.int32)) - 1
    rank_sorted = (
        _segmented_prefix(
            jnp.ones(p, dtype=jnp.int32), seg_start, seg_id, p
        )
        - 1
    )
    rank = jnp.zeros(p, dtype=jnp.int32).at[order].set(rank_sorted)

    flat_q = quotas.reshape(-1).astype(jnp.int64)  # [RC * N]
    gcum = jnp.cumsum(flat_q)  # monotone quota prefix over flat cells
    gcum0 = jnp.concatenate([jnp.zeros(1, dtype=jnp.int64), gcum])
    # class offsets into the flat prefix: int64 product — rc * N can
    # pass 2^31 at mega shapes (the audited relax flat-cell lane)
    cell_base = rc_of.astype(jnp.int64) * n
    offs = gcum0[cell_base]
    tot = quotas.sum(axis=1).astype(jnp.int64)  # [RC] placed per class
    placed = pod_valid & (rank.astype(jnp.int64) < tot[rc_of])
    g = jnp.where(placed, offs + rank.astype(jnp.int64), jnp.int64(0))
    flat_cell = jnp.searchsorted(gcum, g, side="right")
    # node id within the class's row: bounded by the node pad (< 2^31)
    node64 = flat_cell.astype(jnp.int64) - cell_base
    assigned_to = jnp.where(placed, node64, -1).astype(jnp.int32)

    req_add = jnp.where(placed[:, None], rc_req[rc_of], 0)
    park = jnp.where(placed, assigned_to, n)
    used = used0 + jax.ops.segment_sum(
        req_add, park, num_segments=n + 1
    )[:n].T
    pod_count = pod_count0 + jax.ops.segment_sum(
        placed.astype(jnp.int32), park, num_segments=n + 1
    )[:n]
    placed_total = jnp.sum(placed.astype(jnp.int32))

    return assigned_to, used, pod_count, placed_total, lam, mu, iters, res


_relax_jit = jax.jit(
    _relax,
    static_argnames=("max_iters", "pack"),
    donate_argnums=(1, 2),
)


@dataclass
class RelaxStats:
    """Host-side record of the last RelaxSolver.solve, the source for
    the ``scheduler_relax_*`` metric family and the sim footer."""

    iterations: int = 0
    residual: float = 0.0
    placed_relaxed: int = 0  # pods the rounded relaxation seated
    placed_total: int = 0  # after the auction tail repair
    repaired_pods: int = 0  # tail size handed to the auction
    repair_rounds: int = 0  # auction rounds the repair actually ran
    # per-node aggregate dual price (sum_k lam[k, n] + mu[n]), score
    # points per normalized capacity unit — 0 on uncontended nodes
    node_prices: np.ndarray | None = None


class RelaxSolver:
    """Host wrapper mirroring ``SingleShotSolver.solve``'s contract
    (fit + static mask scope, mutates nodes.used/pod_count, returns the
    per-pod assignment), with the relaxation as the engine and the
    auction as the integrality-tail repair."""

    def __init__(
        self,
        config: RelaxConfig | None = None,
        repair: SingleShotConfig | None = None,
    ):
        self.config = config or RelaxConfig()
        # the tail repair runs the EXISTING auction at the same
        # objective; None disables (planning callers that simply drop
        # the unplaced tail pass repair=None and keep the narrow plan)
        self.repair = repair
        self.last = RelaxStats()
        if not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        from ..utils.compile_cache import enable_persistent_cache

        enable_persistent_cache()

    def solve(
        self,
        nodes: NodeBatch,
        pods: PodBatch,
        static: StaticPluginTensors | None = None,
        mesh=None,
    ) -> np.ndarray:
        """``mesh``: optional jax.sharding.Mesh with a "nodes" axis —
        node-resident arrays shard over their trailing node axis,
        class/pod arrays replicate, and GSPMD inserts the collectives
        the matmul/softmax iterations need (the same contract as
        ``SingleShotSolver.solve``)."""
        if static is None:
            static = trivial_static_tensors(
                pods, nodes.padded, nodes.schedulable
            )
        from .budget import assert_index_headroom

        rc_req, rc_static, rc_of = request_classes(pods, static)
        # index-dtype audit including the relaxation's own flat-cell
        # lane (rc * node_pad quota prefix) — typed failure at dispatch
        assert_index_headroom(
            pods.padded, nodes.padded, rc_pad=rc_req.shape[0]
        )
        args = [
            nodes.allocatable,
            nodes.used,
            nodes.pod_count,
            nodes.max_pods,
            nodes.valid,
            static.mask,
            rc_req,
            rc_static,
            rc_of,
            pods.priority,
            pods.valid & pods.feasible_static,
        ]
        if mesh is not None:
            from ..parallel.sharding import node_sharding, replicated

            node_axis_args = {0, 1, 2, 3, 4, 5}  # node-resident inputs
            args = [
                jax.device_put(
                    jnp.asarray(a),
                    node_sharding(mesh, np.ndim(a))
                    if i in node_axis_args
                    else replicated(mesh),
                )
                for i, a in enumerate(args)
            ]
        else:
            args = [jnp.asarray(a) for a in args]
        cfg = self.config
        pod_valid = args[10]
        assigned, used, pod_count, placed, lam, mu, iters, res = _relax_jit(
            *args,
            jnp.float32(cfg.tol),
            jnp.float32(cfg.temp),
            jnp.float32(cfg.step),
            max_iters=cfg.max_iters,
            pack=cfg.objective == "pack",
        )
        stats = RelaxStats(
            iterations=int(iters),
            residual=float(res),
            placed_relaxed=int(placed),
            placed_total=int(placed),
            node_prices=np.asarray(
                jnp.sum(lam, axis=0) + mu, dtype=np.float32
            ),
        )

        tail = np.asarray(pod_valid & (np.asarray(assigned) < 0))
        n_tail = int(tail.sum())
        if self.repair is not None and n_tail > 0:
            # the integrality tail repairs through the EXISTING auction
            # against the post-rounding occupancy: only the still-
            # unassigned pods bid, everything the rounding seated is
            # fixed load. End states inherit the auction's feasibility.
            rep = self.repair
            rep_assigned, used, pod_count, _, rounds = _single_shot_jit(
                args[0],
                used,
                pod_count,
                args[3],
                args[4],
                args[5],
                args[6],
                args[7],
                args[8],
                args[9],
                jnp.asarray(tail),
                max_rounds=rep.max_rounds,
                price_step=rep.price_step,
                top_t=rep.top_t,
                repair_rounds=rep.repair_rounds,
                pack=rep.objective == "pack",
            )
            assigned = jnp.where(
                jnp.asarray(tail), rep_assigned, assigned
            )
            stats.repaired_pods = n_tail
            stats.repair_rounds = int(rounds)
            stats.placed_total = int(
                jnp.sum((assigned >= 0) & jnp.asarray(pod_valid))
            )
        self.last = stats
        nodes.used = np.array(used)
        nodes.pod_count = np.array(pod_count)
        return np.asarray(assigned)[: pods.num_pods]


def group_prices(
    stats: RelaxStats,
    node_groups: list[str],
    valid: np.ndarray | None = None,
) -> dict[str, float]:
    """Aggregate the per-node dual prices into per-node-group means —
    the autoscaler-facing cost signal (ROADMAP item #2): a group priced
    at 0 has slack at the converged plan; a rising price is demand the
    group cannot absorb. ``node_groups`` names a group per UNPADDED
    node slot (e.g. the zone label); padded slots never contribute."""
    if stats.node_prices is None:
        return {}
    out: dict[str, list[float]] = {}
    for i, grp in enumerate(node_groups):
        if valid is not None and not bool(valid[i]):
            continue
        out.setdefault(grp, []).append(float(stats.node_prices[i]))
    return {g: float(np.mean(v)) for g, v in sorted(out.items())}
