"""What held a thread of ``serve``: the collector's pauses and the waits
for ``cluster.lock``.

Each is a series on ``/metrics`` and, with telemetry on, an annotation
on the profiler's clock beside the ``stage:*`` ones of
``Telemetry.stage``. The annotations are deliberately not
``stage:``-prefixed: they cut across the stages, they are not stages.

- ``scheduler_gc_pause_seconds{generation}``: one ``gc.callbacks``
  entry, on in every ``serve`` (as kube-scheduler exports
  ``go_gc_duration_seconds``); with telemetry each collection is also a
  ``gc:gen<N>`` annotation on whichever thread ran it.
- ``scheduler_cluster_lock_wait_seconds_total{thread}`` and
  ``scheduler_cluster_lock_contended_total{thread}``: with telemetry
  only, ``cluster.lock`` is wrapped in ``TimedRLock``, and an acquire
  that has to wait is a ``wait:cluster.lock`` annotation around the
  wait. ``thread`` is ``loop`` (inside ``Scheduler.run_pipelined``),
  ``ingest`` (the server's event-loop thread) or ``other``.

``cli.py cmd_serve`` installs both through ``instrument_serve`` before
any thread starts; nothing here runs at import, so the sim and the
tests that build a ``Scheduler`` are untouched.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import sys
import threading
import time

from .. import metrics

GC_NAMES = ("gc:gen0", "gc:gen1", "gc:gen2")
FLUSH_EVERY_S = 1.0
LOCK_WAIT = "wait:cluster.lock"
THREADS = ("loop", "ingest", "other")


class CollectorPauses:
    """The ``gc.callbacks`` entry: each collection's pause, and with
    ``annotation`` (``jax.profiler.TraceAnnotation``) a ``gc:gen<N>``
    event from its start to its stop.

    A collection starts at a bytecode boundary of any thread, possibly
    inside a metric's ``with self._lock`` (prometheus_client's locks are
    not re-entrant), so the callback takes no lock: it queues the pause
    and ``flush`` observes it, called first by ``metrics.render`` and
    every ``FLUSH_EVERY_S`` by a thread of its own, so that the queue
    stays short in a process nobody scrapes. Collections never overlap,
    so one slot holds the open one."""

    def __init__(self, annotation=None) -> None:
        self.annotation = annotation
        self._pending: collections.deque = collections.deque()
        self._t0: float | None = None
        self._ann = None
        # every generation exported from the start, at 0
        self._hist = [metrics.gc_pause_seconds.labels(str(g)) for g in range(3)]
        self._stop = threading.Event()
        self._flusher = threading.Thread(
            target=self._flush_every, name="gc-pauses", daemon=True
        )

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if self.annotation is not None:
                self._ann = self.annotation(GC_NAMES[info["generation"]])
            self._t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0 is not None:
            self._pending.append((info["generation"], t1 - self._t0))
            self._t0 = None

    def flush(self) -> None:
        while True:
            try:  # two flushes may race for the last one
                generation, seconds = self._pending.popleft()
            except IndexError:
                return
            self._hist[generation].observe(seconds)

    def _flush_every(self) -> None:
        while not self._stop.wait(FLUSH_EVERY_S):
            self.flush()


_collector: CollectorPauses | None = None


def watch_collector(annotation=None) -> CollectorPauses:
    """Install the collector's callback once a process; a second call
    only sets whether collections are annotated."""
    global _collector
    if _collector is None:
        _collector = CollectorPauses()
        gc.callbacks.append(_collector)
        metrics.before_render.append(_collector.flush)
        _collector._flusher.start()
    _collector.annotation = annotation
    return _collector


def unwatch_collector() -> None:
    global _collector
    if _collector is not None:
        gc.callbacks.remove(_collector)
        metrics.before_render.remove(_collector.flush)
        _collector._stop.set()
        _collector._flusher.join(timeout=10)
        _collector.flush()
        _collector = None


class TimedRLock:
    """A re-entrant lock (the ``threading.RLock`` it wraps) whose
    contended acquires book their wait.

    An acquire first tries without blocking: a free lock, and every
    re-entry of a held run, books nothing. One that has to wait is an
    ``annotation(LOCK_WAIT)`` around the blocking acquire, and adds its
    seconds and one contended acquire to the series of its thread."""

    __slots__ = ("_lock", "_annotation", "_loop_code", "_wait_s", "_contended")

    def __init__(self, lock, annotation, loop_code) -> None:
        self._lock = lock
        self._annotation = annotation
        self._loop_code = loop_code
        self._wait_s = {
            t: metrics.cluster_lock_wait_seconds_total.labels(t) for t in THREADS
        }
        self._contended = {
            t: metrics.cluster_lock_contended_total.labels(t) for t in THREADS
        }

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(False):
            return True
        if not blocking:
            return False
        ann = self._annotation(LOCK_WAIT)
        t0 = time.perf_counter()
        got = self._lock.acquire(True, timeout)
        seconds = time.perf_counter() - t0
        ann.__exit__(None, None, None)
        thread = self._thread()
        self._wait_s[thread].inc(seconds)
        self._contended[thread].inc()
        return got

    __enter__ = acquire

    def release(self) -> None:
        self._lock.release()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._lock.release()

    def _thread(self) -> str:
        """Which thread waited: asked only after a contended acquire."""
        frame = sys._getframe(2)
        while frame is not None:
            if frame.f_code is self._loop_code:
                return "loop"
            frame = frame.f_back
        return "ingest" if asyncio._get_running_loop() is not None else "other"


def instrument_serve(cluster, telemetry: bool) -> None:
    """``serve``'s wiring, before any of its threads starts: the
    collector's pauses always; with telemetry both annotations and the
    timed ``cluster.lock``. Without telemetry the lock stays the plain
    ``threading.RLock`` and no annotation is ever built."""
    annotation = None
    if telemetry:
        from jax.profiler import TraceAnnotation

        from ..scheduler import Scheduler

        annotation = TraceAnnotation
        cluster.lock = TimedRLock(
            cluster.lock, annotation, Scheduler.run_pipelined.__code__
        )
    watch_collector(annotation)
