"""PodTopologySpread tensorizer: compile each pod class's spread constraints
into "constraint instances" evaluated on-device with segment reductions.

Per instance j (one (class, constraint) pair, hard or soft):
- dom[j, n]   : domain id of node n under the instance's topologyKey
                (-1 = node lacks the key). Ids are per-topologyKey vocabs.
- elig[j, n]  : counting eligibility (common.go#calPreFilterState — node has
                ALL the class's keys + nodeAffinityPolicy/nodeTaintsPolicy).
- max_skew[j], min_domains[j] (-1 = nil), self_match[j], is_hostname[j].

The per-node match counts cnt[j, n] are SOLVE STATE: they start from the
already-placed pods and are incremented in-scan when a batch pod lands on a
node and matches instance j's selector+namespace (placed_match[p, j],
precompiled host-side). Domain aggregation (counts per domain, min over
registered domains, #domains) runs on device per step as segment sums over
the node axis — the tensor equivalent of the reference's
TpPairToMatchNum/criticalPaths bookkeeping (filtering.go#preFilterState).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

from ..api.objects import Node, Pod
from ..ops.oracle import spread as osp
from ..state.spread_counts import SpreadCounts
from .schema import PodBatch, bucket_pow2

INST_PAD = 8  # instance-axis quantum
DOM_PAD = 8


@dataclass
class SpreadTensors:
    num_instances: int
    d_pad: int  # static segment count for domain reductions
    # per-instance tables
    dom: np.ndarray  # [Jp, Np] int32, -1 = key missing
    elig: np.ndarray  # [Jp, Np] bool
    max_skew: np.ndarray  # [Jp] int32
    min_domains: np.ndarray  # [Jp] int32, -1 = nil
    self_match: np.ndarray  # [Jp] bool
    is_hostname: np.ndarray  # [Jp] bool
    # class -> instance tables (-1 pad)
    hard: np.ndarray  # [Cp, Sh] int32
    soft: np.ndarray  # [Cp, Ss] int32
    # state + per-pod
    cnt0: np.ndarray  # [Jp, Np] int32 — matching placed pods per node
    placed_match: np.ndarray  # [Pp, Jp] bool

    @property
    def empty(self) -> bool:
        return self.num_instances == 0

    @property
    def has_soft(self) -> bool:
        """False when no class has a soft constraint: soft_scores is
        statically zero and the scan can skip it."""
        return bool((self.soft >= 0).any())


def trivial_spread_tensors(pbatch: PodBatch, padded_n: int, c_pad: int) -> SpreadTensors:
    z = np.zeros((INST_PAD, padded_n), dtype=np.int32)
    return SpreadTensors(
        num_instances=0,
        d_pad=DOM_PAD,
        dom=z - 1,
        elig=np.zeros((INST_PAD, padded_n), dtype=bool),
        max_skew=np.ones(INST_PAD, dtype=np.int32),
        min_domains=np.full(INST_PAD, -1, dtype=np.int32),
        self_match=np.zeros(INST_PAD, dtype=bool),
        is_hostname=np.zeros(INST_PAD, dtype=bool),
        hard=np.full((c_pad, 1), -1, dtype=np.int32),
        soft=np.full((c_pad, 1), -1, dtype=np.int32),
        cnt0=z.copy(),
        placed_match=np.zeros((pbatch.padded, INST_PAD), dtype=bool),
    )


def build_spread_tensors(
    pods: Sequence[Pod],
    class_reps: Sequence[Pod],
    pbatch: PodBatch,
    slot_nodes: Sequence[Node | None],
    placed_by_slot: Mapping[int, Sequence[Pod]],
    padded_n: int,
    c_pad: int,
    services: Sequence | None = None,
    defaulting: str = "System",
    nominated: Sequence[tuple[Pod, int]] = (),
    counts: SpreadCounts | None = None,
    slot_of: Mapping[str, int] | None = None,
) -> SpreadTensors:
    """class_reps comes from the static tensorizer so all per-class tables
    share one class id space (xs carries class_of for the gather).

    ``services`` + ``defaulting`` feed PodTopologySpreadArgs.defaultingType
    =System: classes with no explicit constraints get the soft
    zone/hostname system defaults when a service selects them.

    ``nominated`` carries (pod, node slot) pairs for unbound pods whose
    ``status.nominatedNodeName`` resolved to a live slot: they count in
    ``cnt0`` exactly like placed pods (the
    RunFilterPluginsWithNominatedPods convention the synchronous filter
    path already applies via the ports tensorizer) so a spread
    constraint sees a nominated peer as occupying its slot.

    The placed pods come from ONE of two places. A caller with a
    scheduler cache passes ``counts``, its per-selector node counts kept by
    node name, with ``slot_of``, the name -> slot map of this batch, and an
    empty ``placed_by_slot``. A caller with only lists passes
    ``placed_by_slot`` and neither of the two: the same index is built over
    it here and dropped. Any other combination is refused."""
    # collect instances per class
    per_class: list[tuple[list, list]] = []  # (hard ECs, soft ECs)
    insts: list[tuple[int, osp.EffectiveConstraint, bool, Pod]] = []
    for c, rep in enumerate(class_reps):
        defaults = (
            osp.system_default_constraints(rep, services)
            if defaulting == "System" and services
            else ()
        )
        hard = osp.effective_constraints(rep, hard=True)
        soft = osp.effective_constraints(rep, hard=False, defaults=defaults)
        per_class.append((hard, soft))
        for ec in hard:
            insts.append((c, ec, True, rep))
        for ec in soft:
            insts.append((c, ec, False, rep))

    if not insts:
        return trivial_spread_tensors(pbatch, padded_n, c_pad)

    j_pad = bucket_pow2(len(insts), floor=INST_PAD)
    sh = max(max((len(h) for h, _ in per_class), default=0), 1)
    ss = max(max((len(s) for _, s in per_class), default=0), 1)
    hard_tbl = np.full((c_pad, sh), -1, dtype=np.int32)
    soft_tbl = np.full((c_pad, ss), -1, dtype=np.int32)

    # dom is a function of the topology key alone: one [N] row per key of
    # the batch (domain ids per key in slot order, over all live nodes),
    # shared by every instance that names the key
    key_vocab: dict[str, dict[str, int]] = {}
    dom_rows: dict[str, np.ndarray] = {}
    for key in {ec.topology_key for _, ec, _, _ in insts}:
        vocab = key_vocab[key] = {}
        row = dom_rows[key] = np.full(padded_n, -1, dtype=np.int32)
        for n_i, node in enumerate(slot_nodes):
            v = None if node is None else node.labels.get(key)
            if v is not None:
                d = vocab.setdefault(v, len(vocab))
                if n_i < padded_n:
                    row[n_i] = d
    max_domains = max((len(v) for v in key_vocab.values()), default=1)
    d_pad = bucket_pow2(max_domains, floor=DOM_PAD)

    dom = np.full((j_pad, padded_n), -1, dtype=np.int32)
    elig = np.zeros((j_pad, padded_n), dtype=bool)
    max_skew = np.ones(j_pad, dtype=np.int32)
    min_domains = np.full(j_pad, -1, dtype=np.int32)
    self_match = np.zeros(j_pad, dtype=bool)
    is_hostname = np.zeros(j_pad, dtype=bool)
    cnt0 = np.zeros((j_pad, padded_n), dtype=np.int32)
    placed_match = np.zeros((pbatch.padded, j_pad), dtype=bool)

    # counting eligibility is shared by every instance of one (class,
    # hardness) bucket (upstream counts one node set per bucket), and by
    # every bucket _node_counted cannot tell apart: the row is keyed on
    # exactly what it reads of the bucket and the rep
    elig_rows: dict[tuple, np.ndarray] = {}

    def bucket_elig(c: int, is_hard: bool) -> np.ndarray:
        bucket = per_class[c][0] if is_hard else per_class[c][1]
        rep = class_reps[c]
        na = rep.affinity.node_affinity if rep.affinity else None
        reads = (
            frozenset(ec.topology_key for ec in bucket),
            (
                tuple(sorted((rep.node_selector or {}).items())),
                None if na is None else na.required,
            )
            if any(ec.node_affinity_policy == "Honor" for ec in bucket)
            else None,
            tuple(rep.tolerations)
            if any(ec.node_taints_policy == "Honor" for ec in bucket)
            else None,
        )
        row = elig_rows.get(reads)
        if row is None:
            row = elig_rows[reads] = np.zeros(padded_n, dtype=bool)
            for n_i, node in enumerate(slot_nodes[:padded_n]):
                if node is not None:
                    row[n_i] = osp._node_counted(rep, node, bucket)
        return row

    # matching placed pods per node: from the counts the scheduler cache
    # keeps, or from an index built here over placed_by_slot and dropped
    if counts is None and slot_of is None:
        counts = SpreadCounts(placed_by_slot.items)
    elif counts is None or slot_of is None or placed_by_slot:
        raise ValueError(
            "placed pods come from placed_by_slot alone, or from counts "
            "with slot_of and an empty placed_by_slot"
        )
    cnt0[: len(insts)] = counts.rows(
        [(rep.namespace, ec.selector) for _, ec, _, rep in insts],
        padded_n,
        slot_of,
    )

    hard_fill: dict[int, int] = {}
    soft_fill: dict[int, int] = {}
    for j, (c, ec, is_hard, rep) in enumerate(insts):
        tbl, fill = (hard_tbl, hard_fill) if is_hard else (soft_tbl, soft_fill)
        s = fill.get(c, 0)
        tbl[c, s] = j
        fill[c] = s + 1

        max_skew[j] = ec.max_skew
        if ec.min_domains is not None:
            min_domains[j] = ec.min_domains
        self_match[j] = osp._sel_matches(ec.selector, rep.labels)
        is_hostname[j] = ec.topology_key == osp.HOSTNAME_KEY
        elig[j] = bucket_elig(c, is_hard)
        dom[j] = dom_rows[ec.topology_key]

        for p, n_i in nominated:
            # nominated-pod parity: count a matching nominated pod at
            # its slot exactly like a placed pod
            if 0 <= n_i < padded_n and (
                p.namespace == rep.namespace
                and osp._sel_matches(ec.selector, p.labels)
            ):
                cnt0[j, n_i] += 1

        for p_i, pod in enumerate(pods):
            placed_match[p_i, j] = pod.namespace == rep.namespace and (
                osp._sel_matches(ec.selector, pod.labels)
            )

    return SpreadTensors(
        num_instances=len(insts),
        d_pad=d_pad,
        dom=dom,
        elig=elig,
        max_skew=max_skew,
        min_domains=min_domains,
        self_match=self_match,
        is_hostname=is_hostname,
        hard=hard_tbl,
        soft=soft_tbl,
        cnt0=cnt0,
        placed_match=placed_match,
    )
