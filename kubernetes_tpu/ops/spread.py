"""Device kernels for PodTopologySpread (the in-scan pieces).

Domain bookkeeping that the reference keeps in hash maps
(podtopologyspread/filtering.go: TpPairToMatchNum, TpKeyToCriticalPaths) is
recomputed per scan step as reductions over the node axis: counts per
domain = per-node match counts summed by domain id (ops/domains.py picks
the form by the domain axis's size), the "critical path" minimum = masked
min over registered domains. This is the TPU-shaped tradeoff — O(N) fused
vector work per constraint per step beats maintaining device-side sorted
structures, and the node axis is already lane-resident.

Sentinel: INF_COUNT stands in for the reference's math.MaxInt32 initial
criticalPaths value — an empty domain set means the constraint cannot be
violated (skew is hugely negative), matching filtering.go#minMatchNum.
"""

from __future__ import annotations

import jax.numpy as jnp

from .domains import domain_sum

MAX_NODE_SCORE = 100
INF_COUNT = jnp.int32(2**30)


def _domain_aggregate(dom_row, elig_row, cnt_row, d_pad: int):
    """Returns (per-node domain count, #registered domains, min over
    registered domains). dom_row: [N] int32 (-1 missing), elig_row: [N] bool,
    cnt_row: [N] int32 per-node match counts."""
    hk = dom_row >= 0
    dd = jnp.where(hk, dom_row, 0)
    counted = elig_row & hk
    dom_counts = domain_sum(jnp.where(counted, cnt_row, 0), dd, d_pad)
    dom_present = domain_sum(counted.astype(jnp.int32), dd, d_pad) > 0
    n_dom = jnp.sum(dom_present.astype(jnp.int32))
    min_match = jnp.min(jnp.where(dom_present, dom_counts, INF_COUNT))
    node_cnt = dom_counts[dd]  # [N]
    return node_cnt, n_dom, min_match, hk


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def hard_violations(spr, cnt, cls, d_pad: int):
    """[N] bool — any hard spread constraint of class ``cls`` violated.

    spr: dict of spread tables (dom, elig, max_skew, min_domains, self_match,
    hard [C, Sh]); cnt: [J, N] carried per-node match counts.
    """
    n = spr["dom"].shape[1]
    viol = jnp.zeros(n, dtype=bool)
    sh = spr["hard"].shape[1]
    for s in range(sh):  # static unroll over the class's constraint slots
        j = spr["hard"][cls, s]
        active = j >= 0
        jj = jnp.maximum(j, 0)
        node_cnt, n_dom, min_match, hk = _domain_aggregate(
            spr["dom"][jj], spr["elig"][jj], cnt[jj], d_pad
        )
        md = spr["min_domains"][jj]
        min_match = jnp.where((md >= 0) & (n_dom < md), 0, min_match)
        skew = node_cnt + spr["self_match"][jj].astype(jnp.int32) - min_match
        v = (~hk) | (skew > spr["max_skew"][jj])
        viol = viol | (v & active)
    return viol


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def soft_scores(spr, cnt, cls, mask, d_pad: int, fdtype=jnp.float32):
    """[N] int32 — normalized 0-100 PodTopologySpread score over the
    feasible set ``mask`` (scoring.go#Score + #NormalizeScore).

    ``fdtype`` mirrors the solver's balanced_fdtype knob: float64 matches the
    oracle's Go-float64 math bit-for-bit in CPU parity tests."""
    n = spr["dom"].shape[1]
    ss = spr["soft"].shape[1]
    raw = jnp.zeros(n, dtype=fdtype)
    ignored = jnp.zeros(n, dtype=bool)
    has_soft = spr["soft"][cls, 0] >= 0
    n_feasible = jnp.sum(mask.astype(jnp.int32))
    for s in range(ss):
        j = spr["soft"][cls, s]
        active = j >= 0
        jj = jnp.maximum(j, 0)
        node_cnt, n_dom, _, hk = _domain_aggregate(
            spr["dom"][jj], spr["elig"][jj], cnt[jj], d_pad
        )
        hostname = spr["is_hostname"][jj]
        c = jnp.where(hostname, cnt[jj], node_cnt).astype(fdtype)
        size = jnp.where(hostname, n_feasible, n_dom).astype(fdtype)
        contrib = c * jnp.log(size + 2.0) + (
            spr["max_skew"][jj].astype(fdtype) - 1.0
        )
        raw = raw + jnp.where(active & hk, contrib, 0.0)
        ignored = ignored | (active & ~hk)
    raw_i = jnp.round(raw).astype(jnp.int32)

    considered = mask & ~ignored
    mx = jnp.max(jnp.where(considered, raw_i, -INF_COUNT))
    mn = jnp.min(jnp.where(considered, raw_i, INF_COUNT))
    any_considered = jnp.any(considered)
    norm = MAX_NODE_SCORE * (mx + mn - raw_i) // jnp.maximum(mx, 1)
    norm = jnp.where(mx == 0, MAX_NODE_SCORE, norm)
    out = jnp.where(considered & any_considered, norm, 0)
    return jnp.where(has_soft, out, 0)
