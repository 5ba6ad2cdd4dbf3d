"""Per-selector node counts: how many pods on each node match a
(namespace, label selector) that a spread constraint asked about.

PodTopologySpread starts every batch from "matching pods already on each
node" (``SpreadTensors.cnt0``). Counting that from a walk over every placed
pod, once per constraint instance per batch, grows with the cluster; the
counts themselves change only where a pod enters or leaves a node. So the
scheduler cache keeps them (``SchedulerCache.spread_counts``), and a caller
with no cache behind its pod lists (solver/evaluate.py, the extender
webhook) builds the same index on the spot and throws it away.
InterPodAffinity asks the same question of an incoming term ("pods of
namespace ns matching selector S, per node") and reads the same counts.

A pod is checked only against the selectors that can match it: each
selector is filed under one label pair it requires (a ``matchLabels`` entry,
or an ``In`` with one value), and ``Selector.matches`` has the final word. A
selector that requires no such pair (only ``Exists`` / ``NotIn`` /
``DoesNotExist``, or empty) is checked against every pod.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Collection, Hashable, Iterable, Mapping, Sequence

import numpy as np

from ..api.labels import IN, Selector
from ..api.objects import Pod

# Batches a selector stays tracked after the last one that asked for it.
# Keeping one costs a dict of the nodes that hold a matching pod, and an
# update reaches it only through a pod that carries its label pair;
# dropping one too early costs a pass over every placed pod when it comes
# back. A rollout's pods arrive in consecutive batches, so one that 256
# batches (some 260,000 pods at the default batch size) have not named is
# over, and the index stays under 256 x (instances a batch) selectors.
KEEP_BATCHES = 256


def _required_pair(selector: Selector) -> tuple[str, str] | None:
    for r in selector.requirements:
        if r.operator == IN and len(r.values) == 1:
            return r.key, r.values[0]
    return None


@dataclass
class _Kept:
    by_node: dict = field(default_factory=dict)  # node key -> count (> 0)
    asked: int = 0  # the last batch that asked for the selector


class SelectorDispatch:
    """(namespace, selector, item) triples, filed so that ``matching(pod)``
    looks only at the selectors that can match the pod. A None namespace
    matches a pod of any namespace."""

    def __init__(self) -> None:
        # label key -> label value -> [(namespace, selector, item)]
        self._by_key: dict[str, dict[str, list]] = {}
        # no required pair: [(namespace, selector, item)]
        self._every_pod: list[tuple[str, Selector, object]] = []

    def add(self, namespace: str, selector: Selector, item) -> None:
        pair = _required_pair(selector)
        if pair is None:
            filed = self._every_pod
        else:
            filed = self._by_key.setdefault(pair[0], {}).setdefault(pair[1], [])
        filed.append((namespace, selector, item))

    def remove(self, selector: Selector, item) -> None:
        pair = _required_pair(selector)
        if pair is None:
            self._every_pod[:] = [e for e in self._every_pod if e[2] is not item]
            return
        by_value = self._by_key[pair[0]]
        by_value[pair[1]] = [e for e in by_value[pair[1]] if e[2] is not item]
        if not by_value[pair[1]]:
            del by_value[pair[1]]
            if not by_value:
                del self._by_key[pair[0]]

    def matching(self, pod: Pod) -> Sequence:
        """The items of every selector that matches ``pod``."""
        labels = pod.labels
        out = None
        for key, by_value in self._by_key.items():
            # a pod without the key looks up None, which files nothing
            filed = by_value.get(labels.get(key))
            if filed:
                out = self._matched(filed, pod, out)
        if self._every_pod:
            out = self._matched(self._every_pod, pod, out)
        return out or ()

    @staticmethod
    def _matched(filed: list, pod: Pod, out: list | None) -> list | None:
        namespace, labels = pod.namespace, pod.labels
        for ns, selector, item in filed:
            if (ns == namespace or ns is None) and selector.matches(labels):
                if out is None:
                    out = []
                out.append(item)
        return out


def _count(dispatch: SelectorDispatch, pod: Pod, node: Hashable, delta: int) -> None:
    """One pod entering (+1) or leaving (-1) ``node``: the one-pod update
    and the whole-cluster first count are both this."""
    for by_node in dispatch.matching(pod):
        n = by_node.get(node, 0) + delta
        if n:
            by_node[node] = n
        else:
            del by_node[node]


class SpreadCounts:
    """``placed()`` yields (node key, pods on it) for every node that
    counts: it is walked only for selectors not tracked yet.
    ``rows_total`` maps the family that asks (``spread`` | ``interpod``)
    to a counter labelled by source (kept | walk) that tallies the rows
    handed to it; an index built on the spot and dropped has no hit rate
    to report and leaves it out. Not thread safe: the owner's lock guards
    it."""

    def __init__(
        self,
        placed: Callable[[], Iterable[tuple[Hashable, Collection[Pod]]]],
        rows_total: Mapping[str, object] | None = None,
    ) -> None:
        self._placed = placed
        self._rows_total = rows_total or {}
        self._tracked: dict[tuple[str, Selector], _Kept] = {}
        self._dispatch = SelectorDispatch()
        self._batch = 0

    def __len__(self) -> int:
        return len(self._tracked)

    def counts(self, namespace: str, selector: Selector) -> dict | None:
        """node key -> count of a tracked selector; None if not tracked."""
        kept = self._tracked.get((namespace, selector))
        return None if kept is None else kept.by_node

    def pod_added(self, pod: Pod, node: Hashable) -> None:
        if self._tracked:
            _count(self._dispatch, pod, node, 1)

    def pod_removed(self, pod: Pod, node: Hashable) -> None:
        if self._tracked:
            _count(self._dispatch, pod, node, -1)

    def _tally(self, family: str, kept: int, walk: int) -> None:
        rows_total = self._rows_total.get(family)
        if rows_total is not None:
            rows_total.labels("walk").inc(walk)
            rows_total.labels("kept").inc(kept)

    def rows(
        self,
        wanted: Sequence[tuple[str, Selector | None]],
        padded_n: int,
        slot_of: Mapping[Hashable, int] | None = None,
        family: str = "spread",
        visits=None,
    ) -> np.ndarray:
        """[len(wanted), padded_n] int32: row i holds, per node slot, the
        pods that match ``wanted[i]`` (a None selector matches nothing).
        ``slot_of`` maps a node key to its slot (None: the keys are the
        slots); a key it lacks, or a slot past ``padded_n``, is left out.
        ``family`` names the tally; ``visits`` (a counter with ``inc``) is
        given the placed pods the first count walked. One call is one
        batch of the ``KEEP_BATCHES`` bound: a batch that both families
        ask makes two."""
        self._batch += 1
        fresh: dict[tuple[str, Selector], _Kept] = {}
        for key in wanted:
            if key[1] is None:
                continue
            kept = self._tracked.get(key) or fresh.get(key)
            if kept is None:
                fresh[key] = _Kept(asked=self._batch)
            else:
                kept.asked = self._batch
        if fresh:
            # every selector new to this batch, in ONE pass over the pods
            first = SelectorDispatch()
            for (namespace, selector), kept in fresh.items():
                first.add(namespace, selector, kept.by_node)
                self._dispatch.add(namespace, selector, kept.by_node)
            walked = 0
            for node, pods in self._placed():
                walked += len(pods)
                for pod in pods:
                    _count(first, pod, node, 1)
            self._tracked.update(fresh)
            if visits is not None:
                visits.inc(walked)

        out = np.zeros((len(wanted), padded_n), dtype=np.int32)
        n_kept = n_walk = 0
        for i, key in enumerate(wanted):
            if key[1] is None:
                continue
            if key in fresh:
                n_walk += 1
            else:
                n_kept += 1
            row = out[i]
            for node, n in self._tracked[key].by_node.items():
                slot = node if slot_of is None else slot_of.get(node, -1)
                if 0 <= slot < padded_n:
                    row[slot] = n
        self._tally(family, n_kept, n_walk)

        stale = [
            key
            for key, kept in self._tracked.items()
            if self._batch - kept.asked >= KEEP_BATCHES
        ]
        for key in stale:
            self._dispatch.remove(key[1], self._tracked.pop(key).by_node)
        return out

    def walked(
        self,
        preds: Sequence[Callable[[Pod], bool]],
        padded_n: int,
        slot_of: Mapping[Hashable, int] | None,
        family: str,
        visits=None,
    ) -> np.ndarray:
        """[len(preds), padded_n] int32: row i holds, per node slot, the
        pods for which ``preds[i]`` holds, by one pass over the placed
        pods, kept nowhere: for a question the index does not file (an
        inter-pod term with a namespaceSelector). Each row is tallied as
        walked; ``slot_of`` and ``visits`` as in ``rows``."""
        out = np.zeros((len(preds), padded_n), dtype=np.int32)
        walked = 0
        for node, pods in self._placed():
            walked += len(pods)
            slot = node if slot_of is None else slot_of.get(node, -1)
            if not 0 <= slot < padded_n:
                continue
            for pod in pods:
                for i, pred in enumerate(preds):
                    if pred(pod):
                        out[i, slot] += 1
        if visits is not None:
            visits.inc(walked)
        self._tally(family, 0, len(preds))
        return out
