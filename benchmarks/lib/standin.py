"""The reference put in the program's place: an object with the served
scheduler's client-side surface (``lib.serve.Serve``'s methods) whose
placements come from ``reference.ReferenceScheduler``. It reads the pods
from the POSTed manifests themselves, schedules them batch by batch on a
worker thread and streams a decision journal in the program's format.

Used by ``control.py`` (the control: a batch solved against the
occupancy it started with), by ``tests/test_faults.py`` (faults planted
under the harness) and by ``tests/test_marks.py`` (the window's marks:
``captures`` are recorded profiler captures the stand-in hands out, one
a ``trace_start``). Never by a measured run.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time

from . import gen, reference, span_attrib

FAULTS = (None, "stale_state", "drop_half", "alter_answers")


def write_capture(trace_dir: str, planes: dict) -> str:
    """A capture where the profiler would leave it, from plain data as
    ``trace_reduce.load_xplane`` gives it back:
    {plane: {line: [(event name, start ns, duration ns)]}}."""
    space = span_attrib._xspace_class()()
    for plane_name, lines in planes.items():
        plane = space.planes.add(name=plane_name)
        ids: dict = {}
        for line_name, events in lines.items():
            line = plane.lines.add(name=line_name)
            for name, start, dur in events:
                if name not in ids:
                    ids[name] = len(ids) + 1
                    meta = plane.event_metadata.add(key=ids[name])
                    meta.value.id, meta.value.name = ids[name], name
                line.events.add(
                    metadata_id=ids[name], offset_ps=start * 1000, duration_ps=dur * 1000
                )
    d = os.path.join(trace_dir, "plugins", "profile", "recorded")
    os.makedirs(d)
    path = os.path.join(d, "standin.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return path


def spec_of(manifest: dict) -> gen.PodSpec:
    """What a scheduler reads off a wire-shape pod."""
    meta, spec = manifest["metadata"], manifest["spec"]
    if spec.get("topologySpreadConstraints"):
        kind = "spread"
    elif (spec.get("affinity") or {}).get("podAntiAffinity"):
        kind = "anti"
    else:
        kind = "plain"
    (key, app), = meta["labels"].items()  # a pod carries one label
    return gen.PodSpec(meta["name"], kind, app, key)


class StandIn:
    def __init__(
        self, cfg: dict, workdir: str, fault: str | None = None,
        batch: int = 1024, platform: str = "cpu",
        pace_pods_per_s: float = 2000.0, fault_after: int = 0,
        device_kind: str = "reference", captures: tuple = (),
        stop_seconds: float = 0.0,
    ) -> None:
        """``captures``: what the n-th ``trace_start`` records, each
        ``{"planes": <write_capture's plain data>, "stall": bool}`` (the
        last one again once they run out; none: no file is written). A
        capture that stalls holds the scheduler still while it is on, as
        a compile inside the window does. ``stop_seconds``: how long a
        ``trace_stop`` takes to answer."""
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.fault = fault
        self.batch = batch
        # a closed loop offers as fast as it is served: without a pace the
        # stand-in would fill the cluster before the window opened
        self.pace = pace_pods_per_s
        self.fault_after = fault_after  # pods decided soundly first
        self.platform, self.device_kind = platform, device_kind
        self.captures, self.stop_seconds = list(captures), stop_seconds
        self.asked: list = []  # the first word of every request, in order
        self._gate = threading.Lock()  # decisions are published under it;
        self._stalled = False  # a capture that stalls holds it
        self.journal = os.path.join(workdir, "journal.jsonl")
        self._sched = reference.ReferenceScheduler(cfg)
        self._queue: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._solves = 0
        self._step = 0  # batches published, as the program's ``step``
        self._decided = 0  # pods decided
        self._file = open(self.journal, "w")
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # -- the scheduler ------------------------------------------------------

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                batch = [self._queue.get(timeout=0.01)]
            except queue.Empty:
                continue
            while len(batch) < self.batch:
                try:
                    batch.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            t_batch = time.monotonic()
            n_in = len(batch)
            fault = self.fault if self._decided >= self.fault_after else None
            if fault == "drop_half":
                batch = batch[::2]  # the other half is never looked at
            self._sched.carry = fault != "stale_state"
            placed = self._sched.schedule(batch)
            if fault == "alter_answers" and placed:
                first = placed[0][1]
                placed = [(spec, first) for spec, _ in placed]
            t_next = t_batch + max(n_in / self.pace, 0.1)
            time.sleep(max(0.0, t_next - time.monotonic()))
            with self._gate:
                self._publish(placed)

    def _publish(self, placed: list) -> None:
        self._solves += 1
        self._step += 1
        self._decided += len(placed)
        for spec, node in placed:
            rec = {
                "k": "dec", "step": self._step, "pod": spec.key,
                "outcome": "bound" if node else "unschedulable",
                "t": time.monotonic(),
            }
            if node:
                rec["node"] = node
            self._file.write(json.dumps(rec) + "\n")
        self._file.flush()

    def _stall(self, on: bool) -> None:
        if on and not self._stalled:
            self._gate.acquire()
        elif self._stalled and not on:
            self._gate.release()
        self._stalled = on

    # -- the client-side surface of lib.serve.Serve -------------------------

    def wait_healthy(self, timeout: float = 0.0) -> float:
        return 0.0

    def alive(self) -> bool:
        return self._worker.is_alive()

    def require_alive(self) -> None:
        if not self.alive() and not self._stop.is_set():
            raise RuntimeError("the stand-in's worker died")

    def post_pods(self, body: bytes) -> int:
        items = json.loads(body)["items"]
        for m in items:
            self._queue.put(spec_of(m))
        return len(items)

    def scrape(self) -> dict:
        return {
            ("scheduler_tpu_device_info",
             (("device_kind", self.device_kind), ("platform", self.platform))): 1.0,
            ("scheduler_tpu_host_to_device_bytes_total", ()): float(self._solves),
            ("scheduler_tpu_solve_batch_size_count", ()): float(self._solves),
            ("scheduler_tpu_solve_batch_size_sum", ()): float(self._decided),
        }

    def ask(self, *words, timeout: float = 0.0) -> dict:
        """The wrapper's answers (lib/serve_child.py). Without
        ``captures`` nothing is written, and the device readers find
        nothing to read."""
        t_ask = time.monotonic()
        if words[0] == "trace_start" and self.captures:
            n = sum(1 for w in self.asked if w == "trace_start")
            capture = self.captures[min(n, len(self.captures) - 1)]
            write_capture(words[1], capture["planes"])
            self._stall(bool(capture.get("stall")))
        elif words[0] == "trace_stop":
            time.sleep(self.stop_seconds)
            self._stall(False)
        self.asked.append(words[0])
        now = time.monotonic()
        return {"t_ask": t_ask, "t_anchor": t_ask, "t_on": now, "t_off": now}

    def memory_peak_bytes(self):
        return None

    def close(self) -> None:
        if not self._stop.is_set():
            self._stop.set()
            self._stall(False)
            self._worker.join(timeout=10.0)
            self._file.close()
