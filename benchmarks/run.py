#!/usr/bin/env python3
"""One run of one cell, from the client's side of ``python -m
kubernetes_tpu serve --mode scheduler`` on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (+ ``breakdown`` with ``--trace 1``)
and, last, ``compared``: each number the comparison held, beside its
limit. See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import (  # noqa: E402
    files, gen, journal, loops, reference, serve as serve_mod, solve_work,
    trace_reduce,
)

TRACE_START_SHARE = 0.3  # of the window, before the first capture starts
TRACE_SECONDS = 3.0  # length of a profiler capture (at most a third of the window)
# the sign that the device worked inside a capture: batches applied
# between a scrape at its start and one this long before its stop (the
# stop itself takes seconds in which the loop goes on)
SOLVES = "scheduler_tpu_solve_batch_size_count"
SIGN_LEAD_S = 0.2
# a further capture is asked only while it would stop this long before t1
RETAKE_MARGIN_S = 1.0
# after t1 the loop goes on until a pod of the batch after the one that
# straddles t1 is bound, so that batch's end is known: two batches at most,
# ~4 s in the slowest cell read (a 1,024-pod batch every 2.06 s, PR 36), and
# a build inside a window has held the loop 21.6 s (PR 32). A run that waits
# this long has no pods_bound_per_s (its reader says why), never the count.
BATCH_AFTER_T1_WAIT_S = 60.0
# a backlog cell's queue is first-in first-out: a pod offered more than
# this share of the queue's depth ahead of the newest bound one and still
# undecided at the close is lost
LOST_MARGIN_SHARE = 0.5


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def apply_rehearsal(cell: dict, cfg: dict) -> None:
    """--rehearse-cpu: the cell's own ``rehearse`` overrides (a tiny
    cluster the CPU backend can serve), never used by the driver."""
    r = cell["rehearse"]
    scale = r["nodes"] / cfg["nodes"]["count"]
    cfg["nodes"]["count"] = r["nodes"]
    cfg["initPods"]["count"] = r["initPods"]
    cfg["validWhile"]["maxPodsOffered"] = int(
        cfg["validWhile"]["maxPodsOffered"] * scale
    )
    cfg["stream"]["deploymentReplicas"] = r.get(
        "deploymentReplicas", cfg["stream"]["deploymentReplicas"]
    )
    cell["loop"].update(r.get("loop", {}))
    cell["warmup"] = r.get("warmup", cell["warmup"])


def sleep_until(t: float) -> None:
    while True:
        wait = t - time.monotonic()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.05))


class NoCapture(serve_mod.ServeError):
    """A --trace 1 run none of whose captures holds device work under
    its anchor: the run prints no result."""


class _Mark(threading.Thread):
    """One thread of the window's marks; what it raises is re-raised by
    the main thread at ``Marks.finish``."""

    def __init__(self, body):
        super().__init__(daemon=True)
        self.body = body
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            self.body()
        except BaseException as e:
            self.error = e


class Marks:
    """The window's marks, off the posting thread and each kind on a
    thread (so on an HTTP connection) of its own: the two scrapes at
    ``t0`` and ``t1``, whatever the profiler is doing, and with
    ``--trace 1`` the profiler captures."""

    def __init__(self, system, t0: float, seconds: float, trace: bool, workdir: str):
        self.system, self.t0, self.t1 = system, t0, t0 + seconds
        self.workdir = workdir
        self.trace_s = min(TRACE_SECONDS, seconds / 3.0)
        self.t_trace = t0 + TRACE_START_SHARE * seconds
        self.out: dict = {}  # log0, m0, m1, log1 and when m0 / m1 were asked and answered
        self.captures: list = []  # one dict a capture, in the order taken
        self._threads = [_Mark(self._scrapes)] + ([_Mark(self._captures)] if trace else [])

    def start(self) -> None:
        for th in self._threads:
            th.start()

    def _scrapes(self) -> None:
        sleep_until(self.t0)
        self.out["log0"] = _log_size(self.system)
        self._scrape("m0")
        sleep_until(self.t1)
        self._scrape("m1")
        self.out["log1"] = _log_size(self.system)

    def _scrape(self, key: str) -> None:
        asked = time.monotonic()
        self.out[key] = self.system.scrape()
        self.out["t_" + key] = (asked, time.monotonic())

    def _captures(self) -> None:
        """The first capture is asked at a fixed place of the window; one
        whose sign says the device did nothing inside it is followed by
        another, while the window holds one."""
        sleep_until(self.t_trace)
        while True:
            cap: dict = {"dir": os.path.join(self.workdir, f"trace-{len(self.captures)}")}
            self.captures.append(cap)
            cap["on"] = self.system.ask("trace_start", cap["dir"])
            t_stop = time.monotonic() + self.trace_s
            s_on = self.system.scrape()
            sleep_until(t_stop - SIGN_LEAD_S)
            s_off = self.system.scrape()
            sleep_until(t_stop)
            cap["off"] = self.system.ask("trace_stop", timeout=240.0)
            cap["solves_inside"] = (
                serve_mod.metric_sum(s_off, SOLVES) - serve_mod.metric_sum(s_on, SOLVES)
            )
            if (
                cap["solves_inside"] > 0
                or self.t1 - time.monotonic() < self.trace_s + RETAKE_MARGIN_S
            ):
                return

    def finish(self) -> dict:
        for th in self._threads:
            th.join(timeout=300.0)
            if th.is_alive():
                raise serve_mod.ServeError("window marks did not finish")
            if th.error is not None:
                raise th.error
        return self.out


def pick_capture(captures: list) -> dict | None:
    """The reduction of the first capture, in the order taken, that holds
    a device operation AND the anchor that places it on the host's clock
    (with ``xplane``, the file it was read from, and ``capture``, which
    one it was). Each capture looked at is marked ``reduced`` and, where
    it does not qualify, ``why_not``."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # the child is gone; read, don't grab
    for n, cap in enumerate(captures):
        cap["reduced"] = False
        if "off" not in cap:
            cap["why_not"] = "no answer to trace_stop"
        elif (path := trace_reduce.find_xplane(cap["dir"])) is None:
            cap["why_not"] = "no *.xplane.pb was written"
        else:
            t_r = time.monotonic()
            trace = trace_reduce.reduce(trace_reduce.load_xplane(path))
            log(f"trace {os.path.getsize(path)} B reduced in {time.monotonic() - t_r:.1f}s")
            if trace is None or not trace["busy_s"] > 0:
                cap["why_not"] = "no device plane holds an operation"
            elif trace["anchor_ns"] is None:
                cap["why_not"] = "the anchor event is missing"
            else:
                cap["reduced"] = True
                return {**trace, "xplane": path, "capture": n}
    return None


def _capture_rows(captures: list, t0: float) -> list:
    """The captures as ``{"info": "window"}`` and a refusal print them."""
    rows = []
    for cap in captures:
        on, off = cap.get("on"), cap.get("off")
        rows.append({
            "from_t0": on["t_ask"] - t0 if on else None,
            "asked_seconds": off["t_ask"] - on["t_on"] if on and off else None,
            "stop_seconds": off["t_off"] - off["t_ask"] if off else None,
            "solves_inside": cap.get("solves_inside"),
            "reduced": cap.get("reduced"),
            "why_not": cap.get("why_not"),
        })
    return rows


def drive(
    args, cell: dict, cfg: dict, system, workdir: str,
    batch_wait_s: float = BATCH_AFTER_T1_WAIT_S,
) -> dict:
    """Everything of a run but the look for a chip and the spawn:
    ``system`` is the served scheduler (``lib.serve.Serve``, or a
    stand-in in control.py and the tests). Returns the result line."""
    rehearse = bool(args.rehearse_cpu)
    seconds = float(args.seconds)
    loop = cell["loop"]
    ready_s = system.wait_healthy()
    tail = journal.JournalTail(system.journal)
    device = serve_mod.device_of(system.scrape())
    log(f"serve up in {ready_s:.2f}s on {device}")
    if not rehearse and device["platform"] != "tpu":
        raise serve_mod.ServeError(f"the child runs on {device}, not on a TPU")
    if device["count"] < int(cell["chips"]):
        raise serve_mod.ServeError(
            f"the cell asks for {cell['chips']} chips, the child has {device['count']}"
        )
    peaks = None if rehearse else solve_work.load_peaks(device["kind"])

    offer = loops.Offer(cfg, system)
    stream = gen.RolloutStream(cfg, args.seed)
    max_offered = int(cfg["validWhile"]["maxPodsOffered"])

    # -- set-up: initPods, then this cell's shapes -------------------------
    # initPods, then the cell's warm-up bursts: each is posted whole and
    # waited to bound, so the next dispatch finds the pipeline drained
    # and syncs every node column the burst touched in one go
    for specs in [gen.init_pods(cfg)] + [
        stream.take(int(n)) for n in cell["warmup"].get("bursts", [])
    ]:
        for lo in range(0, len(specs), 1000):
            offer.post(specs[lo : lo + 1000])
        loops.wait_bound(system, tail, offer.n_posted, timeout=1500.0)
        log(f"{offer.n_posted} pods bound at {time.monotonic() - T_PROCESS:.1f}s")
    if loop["kind"] != "backlog":
        raise ValueError(f"unknown loop kind {loop['kind']!r}")
    depth, chunk = int(loop["depth"]), int(loop["chunk"])
    loops.backlog(
        offer, tail, stream, depth, chunk,
        until_bound=offer.n_posted + int(cell["warmup"]["until_bound"]),
        max_offered=max_offered,
    )
    t0 = time.monotonic() + 0.05
    n_setup_posted = offer.n_posted
    marks = Marks(system, t0, seconds, bool(args.trace), workdir)
    marks.start()

    # -- the window ---------------------------------------------------------
    sleep_until(t0)
    t1 = t0 + seconds
    loops.backlog(
        offer, tail, stream, depth, chunk, stop_at=t1, max_offered=max_offered,
    )
    setup_s = t0 - T_PROCESS
    sleep_until(t1)

    # -- after the close: late answers are late, not wrong -------------------
    def t1_batch_done() -> bool:
        return journal.batch_done_after(tail.times, tail.steps, t1)

    loops.backlog(
        offer, tail, stream, depth, chunk, stop_at=time.monotonic() + batch_wait_s,
        until=t1_batch_done, max_offered=max_offered,
    )
    waited_s = time.monotonic() - t1
    if not t1_batch_done():
        log(f"no pod of a batch after the one at t1 was bound in {batch_wait_s:.0f}s")
    child_alive = system.alive()
    seen = marks.finish() if child_alive else dict(marks.out)
    m_end = system.scrape() if child_alive else seen.get("m1", {})
    mem_peak = system.memory_peak_bytes() if child_alive else None
    system.close()
    tail.poll()

    # -- what the window produced -------------------------------------------
    bound_at = dict(zip(tail.keys, tail.times))
    in_window = [t for t in tail.times if t0 <= t < t1]
    numbers: dict = {}  # name -> (value, op, limit)
    # decisions about offered pods (the telemetry sentinel journals its
    # own anomaly records under the same kind)
    other = [
        r for r in tail.other
        if t0 <= r["t"] < t1 and r.get("pod") in offer.specs
    ]
    newest = max(
        (i for i, k in enumerate(offer.order) if k in bound_at), default=-1
    )
    lost = sum(
        1
        for k in offer.order[: max(0, newest - int(LOST_MARGIN_SHARE * depth))]
        if k not in bound_at
    )
    attempted = len(in_window) + len(other)
    not_bound = len(other) + lost
    replay = reference.replay(cfg, offer.specs, list(zip(tail.keys, tail.nodes)))
    fallbacks = _fallback_events(m_end)
    numbers["child_exits"] = (0 if child_alive else 1, "<=", 0)
    numbers["fallback_events"] = (fallbacks, "<=", 0)
    numbers["pods_not_bound"] = (not_bound, "<=", 0)
    for name in (
        "unknown_bindings", "bound_twice", "infeasible_at_commit",
        "nodes_over_capacity",
    ):
        numbers[name] = (replay[name], "<=", 0)
    # a guarantee of a pod kind is compared where the configuration has it
    kinds = cfg["stream"]["kinds"]
    if "anti" in kinds:
        numbers["anti_affinity_clashes"] = (replay["anti_affinity_clashes"], "<=", 0)
    if "spread" in kinds:
        numbers["max_zone_skew"] = (
            replay["max_zone_skew"], "<=", int(kinds["spread"]["maxSkew"])
        )
    numbers["window_pods_bound"] = (len(in_window), ">=", 1)
    numbers["device_h2d_bytes"] = (
        serve_mod.metric_sum(m_end, "scheduler_tpu_host_to_device_bytes_total"),
        ">=", 1,
    )
    correct = all(
        (v <= lim) if op == "<=" else (v >= lim) for v, op, lim in numbers.values()
    )
    failed = (
        not_bound + replay["unknown_bindings"] + replay["bound_twice"]
        + replay["infeasible_at_commit"]
    )
    for note in replay["notes"]:
        log(f"reference: {note}")

    # -- metrics -------------------------------------------------------------
    trace = pick_capture(marks.captures) if args.trace else None
    m0, m1 = seen.get("m0"), seen.get("m1")

    def delta(name: str, **labels):
        if m0 is None or m1 is None:
            return None
        s = serve_mod.metric_sum
        return s(m1, name, **labels) - s(m0, name, **labels)

    traced = None
    if trace is not None:
        # the span of the device's own events, placed on the host's
        # monotonic clock by the wrapper's anchor event; the pods bound
        # inside it, and the solves at the window's solves per pod
        cap = marks.captures[trace["capture"]]
        off = cap["on"]["t_anchor"] - trace["anchor_ns"] / 1e9
        lo, hi = trace["lo_ns"] / 1e9 + off, trace["hi_ns"] / 1e9 + off
        pods = sum(1 for t in tail.times if lo <= t < hi)
        solves = delta("scheduler_tpu_solve_batch_size_count")
        traced = {
            "pods": pods,
            "solves": (
                pods * solves / len(in_window) if solves and in_window else None
            ),
            "seconds": hi - lo,
            "asked_seconds": cap["off"]["t_ask"] - cap["on"]["t_on"],
            "from_t0": lo - t0,
            "capture": trace["capture"],
            "programs": trace["programs"],
        }
    ctx = {
        "cell": cell, "config": cfg, "seconds": seconds, "t0": t0, "t1": t1,
        "setup_s": setup_s, "serve_ready_s": ready_s,
        "m0": m0, "m1": m1, "delta": delta,
        "trace": trace, "traced": traced, "peaks": peaks,
        "bound_times": tail.times, "bound_steps": tail.steps, "bound_at": bound_at,
        "bound_in_window": len(in_window),
        "posts": offer.posts, "metric_sum": serve_mod.metric_sum,
        "solve_work": solve_work,
    }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name, m in sorted(files.metrics_of_cell(cell, kind).items()):
        value = files.load_reader(m)(ctx, **m.get("args", {}))
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    captures = _capture_rows(marks.captures, t0)
    if m0 and m1:
        say(info="window", stage_seconds=_stage_seconds(m0, m1),
            compile=_compile_counts(m0, m1), traced=traced,
            captures=captures,
            scrapes={
                "m0_from_t0": [t - t0 for t in seen["t_m0"]],
                "m1_from_t1": [t - t1 for t in seen["t_m1"]],
            },
            posts=len(offer.posts), offered=offer.n_posted, bound=tail.n_bound,
            bound_in_window=len(in_window),
            window_count_per_s=len(in_window) / seconds,
            waited_after_t1_s=waited_s,
            bound_steps_out_of_order=sum(
                1 for a, b in zip(tail.steps, tail.steps[1:]) if b < a
            ),
            setup_posted=n_setup_posted,
            other_decisions=[
                {k: r.get(k) for k in ("pod", "outcome", "reason", "t")}
                for r in tail.other[:5]
            ],
            compiled_in_window=_compiled_in_window(system, seen),
            discarded_solves_in_window=delta("scheduler_tpu_solves_discarded_total"),
            bound_per_s=_timeline(tail.times, t0, -5, int(seconds) + 10),
            offered_per_s=_timeline(
                [p[0] for p in offer.posts for _ in range(p[2])], t0, -5,
                int(seconds) + 10,
            ),
            largest_commit_gap_s=max(
                (b - a for a, b in zip(in_window, in_window[1:])), default=None
            ))

    if args.trace and not rehearse and trace is None:
        raise NoCapture(
            "no capture of this --trace 1 run holds device work under its "
            f"anchor: {json.dumps(captures)}"
        )

    dev = dict(device)
    if not rehearse:
        dev["memory_peak_bytes"] = mem_peak
        if trace is not None:
            dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
    line: dict = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": dev,
    }
    if rehearse:
        line["rehearsal"] = "cpu: no number here is a device number"
    if trace is not None:
        line["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    line["compared"] = {
        n: {"value": v, "limit": f"{op} {lim}"} for n, (v, op, lim) in numbers.items()
    }
    for n, (v, op, lim) in numbers.items():
        print(f"compared {n}: {v} (limit {op} {lim})", file=sys.stderr)
    sys.stderr.flush()
    return line


def _log_size(system) -> int:
    path = getattr(system, "log_path", None)
    return os.path.getsize(path) if path and os.path.exists(path) else 0


def _compiled_in_window(system, seen: dict) -> list:
    """Names of the programs JAX logged as compiling (or fetching)
    between the window's two marks (JAX_LOG_COMPILES)."""
    path = getattr(system, "log_path", None)
    if not path or "log1" not in seen:
        return []
    with open(path, "rb") as f:
        f.seek(seen["log0"])
        text = f.read(seen["log1"] - seen["log0"]).decode(errors="replace")
    return [
        row.split("Compiling ", 1)[1].split(" with ", 1)[0]
        for row in text.splitlines()
        if "Compiling " in row
    ][:20]


def _timeline(times: list, t0: float, lo: int, hi: int) -> list:
    """Events per second of the run, from ``lo`` to ``hi`` seconds
    after the window's start."""
    out = [0] * (hi - lo)
    for t in times:
        k = int((t - t0) // 1) - lo
        if 0 <= k < len(out):
            out[k] += 1
    return out


def _fallback_events(m: dict) -> float:
    s = serve_mod.metric_sum
    return (
        s(m, "scheduler_tpu_fallback_solves_total")
        + s(m, "scheduler_tpu_breaker_transitions_total", transition="trip")
        + s(m, "scheduler_tpu_breaker_transitions_total", transition="rebuild")
        + s(m, "scheduler_tpu_breaker_state")
        + s(m, "scheduler_tpu_quarantined_pods_total")
        + s(m, "scheduler_pipeline_mode_total", mode="sync")
    )


def _stage_seconds(m0: dict, m1: dict) -> dict:
    out = {}
    for (name, labels), v in m1.items():
        if name == "scheduler_profile_stage_seconds_total":
            stage = dict(labels).get("stage")
            out[stage] = v - m0.get((name, labels), 0.0)
    return out


def _compile_counts(m0: dict, m1: dict) -> dict:
    s = serve_mod.metric_sum

    def d(name: str) -> float:
        return s(m1, name) - s(m0, name)

    return {
        "executables_before_window": s(m0, "scheduler_xla_compilations_total"),
        "cache_hits_before_window": s(m0, "scheduler_xla_persistent_cache_hits_total"),
        "executables_in_window": d("scheduler_xla_compilations_total"),
        "cache_hits_in_window": d("scheduler_xla_persistent_cache_hits_total"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="plumbing rehearsal on the CPU backend at the cell's tiny "
        "'rehearse' size; the result carries no device metric",
    )
    args = ap.parse_args(argv)
    cell = files.load_workload(args.workload)
    cfg = files.load_config(cell["config"])
    if args.rehearse_cpu:
        apply_rehearsal(cell, cfg)
    if not os.path.isfile(os.path.join(files.ROOT, "kubernetes_tpu", "cli.py")):
        log(f"no program in {files.ROOT}: kubernetes_tpu/cli.py is missing")
        return 2
    workdir = os.path.join(files.ROOT, ".bench_work", cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    state_path = os.path.join(workdir, "state.json")
    gen.write_state_file(cfg, state_path)
    system = serve_mod.Serve(
        files.ROOT, workdir, state_path,
        platforms="cpu" if args.rehearse_cpu else "tpu",
        telemetry=bool(args.trace),
    )
    try:
        line = drive(args, cell, cfg, system, workdir)
    except (serve_mod.ServeError, TimeoutError, RuntimeError) as e:
        log(f"no result: {type(e).__name__}: {e}")
        return 1
    finally:
        system.close()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
