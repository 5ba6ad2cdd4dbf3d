"""Device kernels for InterPodAffinity (the in-scan pieces).

The reference's topologyToMatchedTermCount hash maps
(interpodaffinity/filtering.go) become one flattened segment-sum over
(term, domain) pairs per step: per-node owner/match counts [T, N] aggregate
to [T, D] domain totals, then gather back per node. All four directions
(incoming aff/anti, existing-anti symmetry, scored preferred/hard symmetry)
read those two aggregates; the per-pod "does existing term u concern pod p"
bits arrive as dense rows (m_anti / m_w), so the inner product over the
existing-term axis is a masked matvec.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import ops as jops

MAX_NODE_SCORE = 100
INF = jnp.int32(2**30)


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def domain_counts(
    dom, cnt, d_pad: int, ident: bool = False, pallas: bool = False
):
    """dom, cnt: [T, N] -> (per-node domain totals [T, N], has_key [T, N]).

    ``ident=True`` (static): every valid node has a UNIQUE domain in every
    row — the hostname-topology case, verified numerically by the
    tensorizer — so the per-node total IS the per-node count and no
    aggregation runs at all. This matters: the flattened segment_sum costs
    ~0.8 ms per scan step at N=5k (measured), and hostname anti-affinity
    is the canonical interpod workload (scheduler_perf
    SchedulingPodAntiAffinity).

    Otherwise one segment_sum over T*d_pad flattened segments replaces T
    hash maps — unless ``pallas=True`` (static; config
    ``tpuSolver.pallas``), which routes the [T, D] aggregation through
    the MXU one-hot-contraction kernel
    (ops/pallas_kernels.domain_counts_padded) and gathers back per node.
    Bit-identical to the segment_sum (integer adds in both); off by
    default (pallas_kernels.py says why)."""
    t, n = dom.shape
    hk = dom >= 0
    if ident:
        return jnp.where(hk, cnt, 0), hk
    dd = jnp.where(hk, dom, 0)
    if pallas:
        from .pallas_kernels import domain_counts_padded

        seg = domain_counts_padded(dom, cnt, d_pad)
    else:
        seg_ids = (
            dd + jnp.arange(t, dtype=jnp.int32)[:, None] * d_pad
        ).reshape(-1)
        seg = jops.segment_sum(
            jnp.where(hk, cnt, 0).reshape(-1),
            seg_ids,
            num_segments=t * d_pad,
        ).reshape(t, d_pad)
    node_counts = jnp.take_along_axis(seg, dd, axis=1)
    return node_counts, hk


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def filter_and_score(
    ipa, in_cnt, ex_cnt, cls, x, d_pad: int, node_valid,
    ident: bool = False, score: bool = True, pallas: bool = False,
):
    """Returns (allowed [N] bool, raw_score [N] int32).

    ipa: table dict; in_cnt/ex_cnt: carried [T, N] counts; cls: pod class;
    x: per-pod xs dict (ipa_m_anti, ipa_m_w, ipa_self_aff). Raw scores are
    returned unnormalized — normalization runs over the FINAL feasible mask
    (which includes this function's `allowed`). ``ident``: unique-domain
    fast path (see domain_counts). ``score=False`` (static): the batch has
    no preferred terms and no symmetry weights — skip the scoring section
    (raw is all-zero then anyway)."""
    in_counts, in_hk = domain_counts(
        ipa["in_dom"], in_cnt, d_pad, ident, pallas
    )
    ex_counts, ex_hk = domain_counts(
        ipa["ex_dom"], ex_cnt, d_pad, ident, pallas
    )
    n = in_counts.shape[1]

    # 1. existing pods' required anti-affinity vs this pod (symmetry)
    concerns = ipa["ex_anti"] & x["ipa_m_anti"]  # [Te]
    blocked = jnp.any(concerns[:, None] & ex_hk & (ex_counts > 0), axis=0)

    # 2. incoming required anti-affinity (missing key -> passes)
    viol = jnp.zeros(n, dtype=bool)
    sb = ipa["cls_req_anti"].shape[1]
    for s in range(sb):
        j = ipa["cls_req_anti"][cls, s]
        active = j >= 0
        jj = jnp.maximum(j, 0)
        viol = viol | (active & in_hk[jj] & (in_counts[jj] > 0))

    # 3. incoming required affinity + first-pod special case
    sa = ipa["cls_req_aff"].shape[1]
    all_ok = jnp.ones(n, dtype=bool)
    has_all_keys = jnp.ones(n, dtype=bool)
    total_any = jnp.int32(0)
    has_aff = ipa["cls_req_aff"][cls, 0] >= 0
    for s in range(sa):
        j = ipa["cls_req_aff"][cls, s]
        active = j >= 0
        jj = jnp.maximum(j, 0)
        ok_t = in_hk[jj] & (in_counts[jj] > 0)
        all_ok = all_ok & jnp.where(active, ok_t, True)
        has_all_keys = has_all_keys & jnp.where(active, in_hk[jj], True)
        total_any = total_any + jnp.where(
            active,
            jnp.sum(jnp.where(in_hk[jj] & node_valid, in_cnt[jj], 0)),
            0,
        )
    # first-pod special case never admits a node missing a topology key
    # (filtering.go#satisfyPodAffinity)
    first_pod = (total_any == 0) & x["ipa_self_aff"] & has_all_keys
    aff_ok = jnp.where(has_aff, all_ok | first_pod, True)

    allowed = ~blocked & ~viol & aff_ok

    # score: incoming preferred terms + existing-side symmetry matvec
    raw = jnp.zeros(n, dtype=jnp.int32)
    if score:
        sp = ipa["cls_pref"].shape[1]
        for s in range(sp):
            j = ipa["cls_pref"][cls, s]
            active = j >= 0
            jj = jnp.maximum(j, 0)
            w = ipa["in_pref_w"][jj]
            raw = raw + jnp.where(active & in_hk[jj], w * in_counts[jj], 0)
        raw = raw + x["ipa_m_w"] @ jnp.where(ex_hk, ex_counts, 0)
    return allowed, raw


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def normalize(raw, mask):
    """scoring.go#NormalizeScore: 100*(s-min)/(max-min) over the feasible
    set; all-equal -> 0."""
    mx = jnp.max(jnp.where(mask, raw, -INF))
    mn = jnp.min(jnp.where(mask, raw, INF))
    diff = mx - mn
    norm = MAX_NODE_SCORE * (raw - mn) // jnp.maximum(diff, 1)
    return jnp.where(mask & (diff > 0), norm, 0)
