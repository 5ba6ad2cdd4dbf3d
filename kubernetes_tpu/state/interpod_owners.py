"""Owner pods per node of every inter-pod term a placed pod owns: the
existing side of InterPodAffinity.

An existing pod's required anti-affinity blocks an incoming pod it
selects, and its preferred terms and required affinity score one
(filtering.go#satisfyExistingPodsAntiAffinity, scoring.go). Every batch
starts that side from "pods on each node that own term u"
(``InterpodTensors.ex_cnt0``). Counting that from a walk over every placed
pod grows with the cluster; the counts themselves change only where an
owner enters or leaves a node. So the scheduler cache keeps them
(``SchedulerCache.interpod_owners``), and a caller with no cache behind its
pod lists builds the same index on the spot and throws it away.

A term is kept as its owner made it effective (matchLabelKeys merged from
the owner's labels) and filed by its selector (``SelectorDispatch``), so
``selecting(pod)`` looks only at the terms that can select the pod: the
terms that act on a batch are found from the batch, not from the placed
pods.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Hashable

from ..api.objects import Pod, PodAffinityTerm
from ..ops.oracle import interpod as oip
from .spread_counts import SelectorDispatch

# existing-term kinds
K_REQ_ANTI = 0
K_PREF_AFF = 1
K_PREF_ANTI = 2
K_REQ_AFF = 3


def owned_terms(pod: Pod) -> list[tuple[int, PodAffinityTerm, int]]:
    """(kind, term, weight) triples owned by ``pod`` that the symmetry
    machinery needs. Terms are made EFFECTIVE here (matchLabelKeys merged
    from the owner's labels) because the dedup key and the per-pod match
    rows depend on the owner-resolved selector, not the raw spec."""
    out = []
    for t in oip._required_anti_terms(pod):
        out.append((K_REQ_ANTI, oip.effective_term(t, pod), 0))
    for wt in oip._preferred_terms(pod, anti=False):
        out.append((K_PREF_AFF, oip.effective_term(wt.term, pod), wt.weight))
    for wt in oip._preferred_terms(pod, anti=True):
        out.append((K_PREF_ANTI, oip.effective_term(wt.term, pod), -wt.weight))
    for t in oip._required_aff_terms(pod):
        out.append((K_REQ_AFF, oip.effective_term(t, pod), 0))
    return out


@dataclass
class Owned:
    key: tuple  # (kind, effective term, weight, owner namespace)
    by_node: dict = field(default_factory=dict)  # node key -> owners (> 0)


class OwnerTerms:
    """Every term some counted pod owns, keyed ``(kind, effective term,
    weight, owner namespace)``, with its owners per node key. A term
    leaves with its last owner. Not thread safe: the owner's lock guards
    it."""

    def __init__(self) -> None:
        self._owned: dict[tuple, Owned] = {}
        self._dispatch = SelectorDispatch()

    def __len__(self) -> int:
        return len(self._owned)

    def counts(self, key: tuple) -> dict | None:
        """node key -> owner pods of a term; None if no counted pod owns it."""
        owned = self._owned.get(key)
        return None if owned is None else owned.by_node

    def pod_added(self, pod: Pod, node: Hashable) -> None:
        for kind, term, w in owned_terms(pod):
            key = (kind, term, w, pod.namespace)
            owned = self._owned.get(key)
            if owned is None:
                owned = self._owned[key] = Owned(key)
                if term.label_selector is not None:
                    self._dispatch.add(None, term.label_selector, owned)
            owned.by_node[node] = owned.by_node.get(node, 0) + 1

    def pod_removed(self, pod: Pod, node: Hashable) -> None:
        for kind, term, w in owned_terms(pod):
            key = (kind, term, w, pod.namespace)
            owned = self._owned[key]
            n = owned.by_node[node] - 1
            if n:
                owned.by_node[node] = n
                continue
            del owned.by_node[node]
            if not owned.by_node:
                del self._owned[key]
                if term.label_selector is not None:
                    self._dispatch.remove(term.label_selector, owned)

    def selecting(self, pod: Pod) -> list[Owned]:
        """The kept terms that select ``pod``: the selector filed them,
        the namespace rule has the last word."""
        namespace = pod.namespace
        return [
            owned
            for owned in self._dispatch.matching(pod)
            if owned.key[1].matches_namespace(owned.key[3], namespace)
        ]
