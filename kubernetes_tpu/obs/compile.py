"""Compile observability: make XLA compilations visible per cache key,
so a streaming-hot-path recompile — the silent killer at sustained
stream / mega-drain scale, where one retracing shape turns a ~2 ms
dispatch into a multi-second compile stall — shows up in metrics and on
the dispatch span instead of only in a wall-clock mystery.

Mechanism: one process-wide listener on ``jax.monitoring``'s events.
``/jax/core/compile/backend_compile_duration`` fires per executable
BUILT — an XLA backend compile, or a retrieval from the persistent disk
cache (JAX times both under the one event; the second is much shorter)
— and ``/jax/compilation_cache/cache_hits`` fires for the retrievals,
so compiles minus hits is what XLA actually compiled.
``/jax/core/compile/jaxpr_trace_duration`` fires per retrace (a
persistent-cache hit still pays the retrace).
Attribution: the scheduler brackets each solver dispatch with
``CompileWatcher.scope(key)`` — ``key`` is the dispatch's shape/static
fingerprint — and any compile event firing inside the bracket counts
against that key; events outside any bracket count under ``"other"``
(eager ops, warmup, tensorizer helpers).

The watcher is always on (installed at the first Scheduler
construction): the listener is a few dict updates per *compile*, which
only happens when the expensive thing already happened. Span
attribution additionally lands on the dispatch span when tracing is
enabled: ``compiles=N compile_s=...`` — absent on the (steady-state)
batches that compiled nothing.

Exported as the gauge pair ``scheduler_xla_compile_cache_keys`` (how
many distinct compile scopes this process has paid for) and
``scheduler_xla_recompilations`` (compiles beyond the first per scope —
the hot-path regression signal a known-shape test pins at zero), plus
the raw ``scheduler_xla_compilations_total`` /
``scheduler_xla_compile_seconds_total`` counters.
"""

from __future__ import annotations

import threading

from .. import metrics

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

OTHER_SCOPE = "other"


class CompileWatcher:
    """Process-wide compile counter with scope attribution. All state
    is lock-guarded: compiles fire on whichever thread dispatched."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        # scope key -> [compiles, retraces, seconds]
        self.by_scope: dict[str, list] = {}
        self.compiles = 0
        self.retraces = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0  # compiles served by the persistent cache
        self._installed = False

    # -- scope bracketing --

    def scope(self, key: str):
        return _Scope(self, key)

    def _current(self) -> str:
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else OTHER_SCOPE

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # -- the jax.monitoring listener --

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.compile_seconds += duration
                row = self.by_scope.setdefault(
                    self._current(), [0, 0, 0.0]
                )
                row[0] += 1
                row[2] += duration
            metrics.xla_compilations_total.inc()
            metrics.xla_compile_seconds_total.inc(duration)
            self._export()
        elif event == _TRACE_EVENT:
            with self._lock:
                self.retraces += 1
                self.by_scope.setdefault(
                    self._current(), [0, 0, 0.0]
                )[1] += 1

    def _on_cache_hit(self, event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
            metrics.xla_persistent_cache_hits_total.inc()

    def _export(self) -> None:
        with self._lock:
            keys = len(self.by_scope)
            compiled = sum(r[0] for r in self.by_scope.values())
            known = sum(1 for r in self.by_scope.values() if r[0])
        metrics.xla_compile_cache_keys.set(keys)
        # recompilations = compiles beyond the first per scope: a
        # steady-state loop re-paying a compile for a shape it already
        # compiled is exactly the silent hot-path killer
        metrics.xla_recompilations.set(max(compiled - known, 0))

    def install(self) -> None:
        """Register the jax.monitoring listener once (idempotent).
        Guarded: an environment without the monitoring surface keeps
        the watcher as a no-op counter."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                self._on_event
            )
            monitoring.register_event_listener(self._on_cache_hit)
        except Exception:  # pragma: no cover - jax surface drift
            pass

    # -- reads (tests, spans, /debug) --

    def totals(self) -> tuple[int, int, float]:
        with self._lock:
            return self.compiles, self.retraces, self.compile_seconds

    def scope_counts(self) -> dict[str, tuple]:
        with self._lock:
            return {k: tuple(v) for k, v in self.by_scope.items()}


class _Scope:
    __slots__ = ("_w", "_key", "compiles0", "seconds0")

    def __init__(self, watcher: CompileWatcher, key: str) -> None:
        self._w = watcher
        self._key = key
        self.compiles0 = 0
        self.seconds0 = 0.0

    def __enter__(self) -> "_Scope":
        self._w._stack().append(self._key)
        self.compiles0, _, self.seconds0 = self._w.totals()
        return self

    def __exit__(self, *exc) -> bool:
        stack = self._w._stack()
        if stack and stack[-1] == self._key:
            stack.pop()
        return False

    def delta(self) -> tuple[int, float]:
        """(compiles, seconds) attributed since __enter__ — the
        dispatch span's attribution read."""
        c, _, s = self._w.totals()
        return c - self.compiles0, s - self.seconds0


WATCHER = CompileWatcher()
