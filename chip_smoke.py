#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that serve -> solve -> bind still
starts on the chip.

    python chip_smoke.py            # needs a TPU; exits non-zero without one

The PARENT process never imports JAX: it generates the cluster and the
pods from ``--seed`` (``api/wrappers.py``), drives the children over
their own wire surfaces, and checks what comes back with the NumPy /
pure-Python oracle (``ops/oracle/``). Each phase is ONE child process
that owns the chip, run one after another, started with
``JAX_PLATFORMS=tpu,cpu`` so that backend initialisation RAISES when
there is no chip instead of falling back (the CPU platform stays
listed because the degraded-mode ladder's third rung needs it; the
default backend is the first one named).

Phase A  ``python -m kubernetes_tpu serve --mode scheduler`` at 5,000
         nodes x 10,000 pods (upstream scheduler_perf's 5000Nodes node
         and pod templates): webhook answers against the oracle, a
         plain wave (grouped fast path) and a host-port / hard zone
         spread / hostname anti-affinity wave (occupancy carry) bound
         through /api/pods, one bulk gRPC SyncNodes + Solve round trip,
         bindings read from the decision journal and replayed through
         the oracle, and the no-fallback proof from /metrics. On more
         than one device it runs on the default mesh AND with
         ``tpuSolver.meshDevices: 1`` and requires identical bindings.
Phase B  every other compiled program once, in one child, at 51,200
         pods x 10,240 nodes: exact session solve, single-shot auction,
         relax + auction repair, ``Scheduler.drain_backlog`` on the
         hard-spread shape, a preemption dry run, a dirty-column heal,
         and the Pallas kernel compiled with x64 on.
Phase C  Phase A's server again, in a new process on the same compile
         cache: compilations and time-to-first-bind, cold versus warm.

Every stdout line is one JSON object naming the device as the child
that held the chip reported it. Times are observations, not claims.
The line before the last is the summary, ending ``"claim": null``; the
last is ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
exactly those keys.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
ZONES = 3
# upstream scheduler_perf templates (config/templates/node-default.yaml,
# pod-default.yaml): the 5000Nodes workloads create these
NODE_CAPACITY = {"cpu": "4", "memory": "32Gi", "pods": "110"}
POD_REQUEST = {"cpu": "100m", "memory": "500Mi"}
PORT_POOL = 8
# feasibility-replay sample: every step is replayed, this many are run
# through the oracle's full filter pipeline (the cheap invariants cover
# every pod)
REPLAY_SAMPLE = 600


class SmokeFailure(AssertionError):
    """A phase's check failed: the run exits non-zero."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def emit(device: dict, **fields) -> None:
    """One stdout line; every line names the device its child reported."""
    print(json.dumps({**fields, "device": device}), flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- data, from --seed -------------------------------------------------------


def make_nodes(n_nodes: int) -> list[dict]:
    from kubernetes_tpu.api.wrappers import MakeNode

    return [
        MakeNode()
        .name(f"node-{i:05}")
        .capacity(NODE_CAPACITY)
        .label(ZONE, f"z{i % ZONES}")
        .label(HOST, f"node-{i:05}")
        .obj()
        .to_dict()
        for i in range(n_nodes)
    ]


def make_pods(seed: int, n_plain: int, n_mixed: int) -> tuple[list, list]:
    """(plain wave, mixed wave) of wire-shape pod dicts. The mixed wave
    interleaves host-port, hard zone-spread (maxSkew=1) and hostname
    anti-affinity pods in a seeded order."""
    import random

    from kubernetes_tpu.api.wrappers import MakePod

    def base(name: str, app: str):
        return MakePod().name(name).label("app", app).req(POD_REQUEST)

    plain = [
        base(f"plain-{i:05}", "plain").obj().to_dict()
        for i in range(n_plain)
    ]
    rng = random.Random(seed)
    kinds = ["ports", "spread", "anti"] * (n_mixed // 3 + 1)
    kinds = kinds[:n_mixed]
    rng.shuffle(kinds)
    mixed = []
    for i, kind in enumerate(kinds):
        b = base(f"{kind}-{i:05}", kind)
        if kind == "ports":
            b = b.host_port(8000 + rng.randrange(PORT_POOL))
        elif kind == "spread":
            b = b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": kind})
        else:
            b = b.pod_anti_affinity(HOST, {"app": kind})
        mixed.append(b.obj().to_dict())
    return plain, mixed


def webhook_probe_pods() -> list[dict]:
    """The handful of pods /filter and /prioritize are asked about: one
    per filter family the cluster can exercise."""
    from kubernetes_tpu.api.wrappers import MakePod

    def base(name: str, app: str = "probe"):
        return MakePod().name(name).label("app", app).req(POD_REQUEST)

    return [
        p.obj().to_dict()
        for p in (
            base("probe-plain"),
            base("probe-zone").node_affinity_in(ZONE, ["z1"]),
            base("probe-host").node_selector({HOST: "node-00003"}),
            MakePod().name("probe-big").req({"cpu": "64", "memory": "1Gi"}),
            base("probe-ports", "ports").host_port(8000),
            base("probe-spread", "spread").spread_constraint(
                1, ZONE, "DoNotSchedule", {"app": "spread"}
            ),
            base("probe-anti", "anti").pod_anti_affinity(
                HOST, {"app": "anti"}
            ),
        )
    ]


# -- wire helpers ------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        ctype = resp.headers.get("Content-Type", "")
    return json.loads(raw) if "json" in ctype else raw.decode()


def scrape(base: str) -> dict:
    """/metrics -> {(name, sorted label items): value}."""
    from prometheus_client.parser import text_string_to_metric_families

    out = {}
    for fam in text_string_to_metric_families(http("GET", f"{base}/metrics")):
        for s in fam.samples:
            out[(s.name, tuple(sorted(s.labels.items())))] = s.value
    return out


def metric_sum(samples: dict, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(
        v for (n, ls), v in samples.items() if n == name and want <= set(ls)
    )


def device_of(samples: dict) -> dict:
    """The device identity the serve child exported at start-up
    (scheduler_tpu_device_info, utils/device.init_backend)."""
    rows = [
        (dict(ls), v)
        for (n, ls), v in samples.items()
        if n == "scheduler_tpu_device_info"
    ]
    require(len(rows) == 1, f"expected one device_info series, got {rows}")
    labels, count = rows[0]
    return {
        "platform": labels["platform"],
        "kind": labels["device_kind"],
        "count": int(count),
    }


# -- children ----------------------------------------------------------------


def child_env(platforms: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platforms
    return env


def cache_dir() -> str:
    """Where the children's compile cache must land: placed from outside
    by JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    (utils/compile_cache.py) — never a path of this script's making."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )


def cache_entries() -> int:
    d = cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


class Serve:
    """One ``serve --mode scheduler`` child and its wire addresses."""

    def __init__(
        self, tag: str, workdir: str, state_path: str, platforms: str,
        mesh_devices: int | None = None,
    ) -> None:
        self.journal = os.path.join(workdir, f"journal-{tag}.jsonl")
        self.log_path = os.path.join(workdir, f"serve-{tag}.log")
        for stale in (self.journal, self.log_path):
            if os.path.exists(stale):
                os.remove(stale)
        port, self.grpc_port = free_port(), free_port()
        self.base = f"http://127.0.0.1:{port}"
        argv = [sys.executable, "-m", "kubernetes_tpu"]
        if mesh_devices is not None:
            cfg_path = os.path.join(workdir, f"config-{tag}.yaml")
            with open(cfg_path, "w") as f:
                # JSON is YAML
                json.dump(
                    {
                        "apiVersion": "kubescheduler.config.k8s.io/v1",
                        "kind": "KubeSchedulerConfiguration",
                        "tpuSolver": {"meshDevices": mesh_devices},
                    },
                    f,
                )
            argv += ["--config", cfg_path]
        argv += [
            "serve", "--mode", "scheduler", "--state", state_path,
            "--port", str(port), "--grpc-port", str(self.grpc_port),
            "--obs-journal", self.journal,
        ]
        self.t_spawn = time.perf_counter()
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=child_env(platforms),
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_healthy(self, timeout: float = 300.0) -> float:
        """Seconds from spawn to the first /healthz answer. The child
        initialises its backend before it listens, so a missing chip
        shows here as an exited process."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"serve exited with code {self.proc.returncode} "
                    f"before /healthz answered:\n{self.log_tail()}"
                )
            try:
                http("GET", f"{self.base}/healthz", timeout=2.0)
                return time.perf_counter() - self.t_spawn
            except (urllib.error.URLError, OSError):
                time.sleep(0.1)
        raise SmokeFailure(f"serve not healthy after {timeout}s")

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path) as f:
            return "".join(f.readlines()[-n:])

    def close(self) -> None:
        stop(self.proc)
        self._log.close()


# -- phase A: the main path --------------------------------------------------


def webhook_answers(ask, pods: list[dict], nodes: list[dict]) -> list:
    """[(filter result, prioritize result)] per probe pod over the FULL
    node list, normalised so two backends compare with ==."""
    out = []
    for pod in pods:
        args = {"pod": pod, "nodes": {"items": nodes}}
        f = ask("filter", args)
        out.append(
            (
                {
                    "passed": [
                        n["metadata"]["name"] for n in f["nodes"]["items"]
                    ],
                    "failed": f["failedNodes"],
                    "unresolvable": f["failedAndUnresolvableNodes"],
                },
                ask("prioritize", args),
            )
        )
    return out


def check_webhook(tag: str, served: list, cluster, pods, nodes) -> dict:
    """The served /filter and /prioritize answers equal the scalar
    oracle's over the same state."""
    from kubernetes_tpu.server.extender import ExtenderCore

    oracle = ExtenderCore(cluster, backend="oracle")
    want = webhook_answers(
        lambda verb, args: getattr(oracle, verb)(args), pods, nodes
    )
    passed = []
    for pod, (gf, gp), (wf, wp) in zip(pods, served, want):
        name = pod["metadata"]["name"]
        require(gf == wf, f"{tag}: /filter for {name} differs from oracle")
        if gp != wp:
            diffs = [(g, w) for g, w in zip(gp, wp) if g != w]
            raise SmokeFailure(
                f"{tag}: /prioritize for {name} differs from oracle on "
                f"{len(diffs)} of {len(wp)} nodes, e.g. {diffs[:3]}"
            )
        passed.append(len(gf["passed"]))
    return {"pods": len(pods), "passed_nodes": passed}


def wait_bound(serve: Serve, target: int, timeout: float) -> tuple[float, float]:
    """Poll /api/state until ``target`` pods are bound. Returns the
    perf_counter stamps of the first progress past the starting count
    and of completion."""
    deadline = time.perf_counter() + timeout
    start = first = None
    while time.perf_counter() < deadline:
        if serve.proc.poll() is not None:
            raise SmokeFailure(
                f"serve died (code {serve.proc.returncode}):\n"
                f"{serve.log_tail()}"
            )
        st = http("GET", f"{serve.base}/api/state")
        bound = st["pods"] - st["unscheduled"]
        now = time.perf_counter()
        if start is None:
            start = bound
        if first is None and bound > start:
            first = now
        if bound >= target and st["unscheduled"] == 0:
            return first or now, now
        # each poll lists every pod inside the server's event loop:
        # often enough to time a first bind, not enough to load it
        time.sleep(0.05)
    raise SmokeFailure(
        f"only {bound}/{target} pods bound after {timeout}s:\n"
        f"{serve.log_tail()}"
    )


def post_pods(serve: Serve, pods: list[dict], chunk: int = 1000) -> float:
    t0 = time.perf_counter()
    for lo in range(0, len(pods), chunk):
        got = http(
            "POST", f"{serve.base}/api/pods",
            {"items": pods[lo : lo + chunk]},
        )
        require(
            got["applied"] == len(pods[lo : lo + chunk]),
            f"/api/pods applied {got}",
        )
    return t0


def read_bindings(
    journal: str, expect: int, timeout: float = 30.0
) -> tuple[list, dict]:
    """([(pod key, node)] in commit order, {other outcome: count}) from
    the decision journal. /api/state can turn over a moment before the
    last record's line is flushed, so wait for the count."""
    deadline = time.perf_counter() + timeout
    while True:
        bound, other = [], {}
        with open(journal) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("k") != "dec":
                    continue
                if rec["outcome"] == "bound":
                    bound.append((rec["pod"], rec["node"]))
                else:
                    other[rec["outcome"]] = other.get(rec["outcome"], 0) + 1
        if len(bound) >= expect or time.perf_counter() > deadline:
            return bound, other
        time.sleep(0.2)


def check_no_fallback(before: dict, after: dict, pods_sent: int) -> list[str]:
    """The proof that the resilience ladder never engaged between two
    /metrics scrapes, and that the device did the work. Returns the
    violations (empty = proven)."""
    def delta(name: str, **labels) -> float:
        return metric_sum(after, name, **labels) - metric_sum(
            before, name, **labels
        )

    bad = []
    for tier in ("single", "cpu", "host"):
        n = delta("scheduler_tpu_fallback_solves_total", tier=tier)
        if n:
            bad.append(f"{n:g} solves fell back to tier {tier!r}")
    for kind in ("rebuild", "trip"):
        n = delta(
            "scheduler_tpu_breaker_transitions_total", transition=kind
        )
        if n:
            bad.append(f"{n:g} breaker {kind} transitions")
    if metric_sum(after, "scheduler_tpu_breaker_state"):
        bad.append("a solve breaker is not closed")
    n = delta("scheduler_tpu_quarantined_pods_total")
    if n:
        bad.append(f"{n:g} pods quarantined")
    n = delta("scheduler_pipeline_mode_total", mode="sync")
    if n:
        bad.append(f"{n:g} batches rerouted to the synchronous cycle")
    n = delta("scheduler_schedule_attempts_total", result="scheduled")
    if n != pods_sent:
        bad.append(f"{n:g} scheduled attempts for {pods_sent} pods sent")
    for result in ("unschedulable", "error"):
        n = delta("scheduler_schedule_attempts_total", result=result)
        if n:
            bad.append(f"{n:g} {result} attempts")
    if delta("scheduler_tpu_host_to_device_bytes_total") <= 0:
        bad.append("no host->device bytes: the device solved nothing")
    return bad


def check_bindings(
    seed: int, nodes: list[dict], pods: list[dict], bindings: list
) -> dict:
    """Bindings against the oracle: every pod bound exactly once, the
    oracle's feasibility replay in commit order (sampled filter runs
    over an exact state replay), and — over EVERY pod — no node over
    allocatable or pod count, zone skew <= 1, host-port and hostname
    anti-affinity exclusivity."""
    import random
    from collections import Counter

    from kubernetes_tpu.api.objects import Node, Pod
    from kubernetes_tpu.ops.oracle.profile import (
        FullOracle,
        make_oracle_nodes,
    )

    by_key = {}
    for d in pods:
        p = Pod.from_dict(d)
        by_key[p.key] = p
    keys = [k for k, _ in bindings]
    require(
        len(set(keys)) == len(keys) == len(by_key)
        and set(keys) == set(by_key),
        f"{len(keys)} bound records for {len(by_key)} pods "
        f"({len(set(keys))} distinct)",
    )
    node_objs = [Node.from_dict(d) for d in nodes]
    ordered = [by_key[k] for k in keys]
    names = [n for _, n in bindings]
    sample = set(
        random.Random(seed).sample(
            range(len(ordered)), min(REPLAY_SAMPLE, len(ordered))
        )
    )
    oracle = FullOracle(make_oracle_nodes(node_objs))
    errors = oracle.validate_feasible(
        ordered, [0] * len(ordered), names=names, sample=sample
    )
    require(not errors, f"oracle feasibility replay: {errors[:5]}")

    # the replayed oracle state IS the end state: exhaustive invariants
    zone_of = {n.name: n.labels[ZONE] for n in node_objs}
    spread = Counter({f"z{z}": 0 for z in range(min(ZONES, len(node_objs)))})
    for on in oracle.nodes:
        alloc = on.node.allocatable
        require(
            len(on.pods) <= on.node.allowed_pod_number,
            f"{on.node.name}: {len(on.pods)} pods over the pods limit",
        )
        for r in ("cpu", "memory"):
            used = sum(p.resource_request().get(r, 0) for p in on.pods)
            require(
                used <= alloc[r], f"{on.node.name}: {r} {used} > {alloc[r]}"
            )
        ports = [hp for p in on.pods for hp in p.host_ports()]
        require(
            len(ports) == len(set(ports)),
            f"{on.node.name}: host port bound twice: {sorted(ports)}",
        )
        kinds = Counter(p.labels["app"] for p in on.pods)
        require(
            kinds["anti"] <= 1,
            f"{on.node.name}: {kinds['anti']} anti-affinity pods",
        )
        spread[zone_of[on.node.name]] += kinds["spread"]
    skew = max(spread.values()) - min(spread.values())
    require(skew <= 1, f"zone skew {skew}: {dict(spread)}")
    return {
        "bound": len(keys),
        "replayed": len(ordered),
        "oracle_filter_runs": len(sample),
        "zone_skew": skew,
    }


def bulk_round_trip(serve: Serve, nodes: list[dict], free_cpu_milli) -> dict:
    """One SyncNodes + Solve (exact, then single_shot) round trip
    through the bulk gRPC boundary at the served node count. Advisory
    solves: nothing is committed."""
    import numpy as np

    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.server.bulk import BulkClient

    objs = [Node.from_dict(d) for d in nodes]
    n = len(objs)
    # the first Solve compiles: the default 30 s deadline is a tunable
    # of the client, not of the wire
    client = BulkClient(
        f"127.0.0.1:{serve.grpc_port}", retries=0, deadline_s=900.0
    )
    try:
        t0 = time.perf_counter()
        reply = client.sync_nodes(
            names=[o.name for o in objs],
            cpu_milli=[o.allocatable["cpu"] for o in objs],
            mem_bytes=[o.allocatable["memory"] for o in objs],
            max_pods=[o.allowed_pod_number for o in objs],
            labels=[o.labels for o in objs],
        )
        sync_s = time.perf_counter() - t0
        require(reply.get("applied") == n, f"SyncNodes applied {reply}")
        p = n
        cpu = np.full(p, 250, np.int64)
        mem = np.full(p, 256 << 20, np.int64)
        out = {"sync_nodes_s": round(sync_s, 4), "pods": p, "nodes": n}
        for mode in ("exact", "single_shot"):
            walls = []
            for _ in range(2):  # first = set-up (compiles), second = steady
                t0 = time.perf_counter()
                meta, arrays = client.solve(
                    cpu_milli=cpu, mem_bytes=mem, mode=mode
                )
                walls.append(time.perf_counter() - t0)
            require("error" not in meta, f"bulk {mode}: {meta}")
            a = arrays["assignments"]
            require(
                a.shape == (p,) and int(a.min()) >= 0,
                f"bulk {mode}: {int((a < 0).sum())}/{p} unplaced",
            )
            require(
                len(set(meta["nodes"])) == n,
                f"bulk {mode}: {len(meta['nodes'])} node names",
            )
            load = np.bincount(a, minlength=n) * 250
            free = np.asarray(
                [free_cpu_milli[name] for name in meta["nodes"]]
            )
            require(
                bool((load <= free).all()),
                f"bulk {mode}: cpu overcommit on "
                f"{int((load > free).sum())} nodes",
            )
            out[mode] = {
                "first_round_trip_s": round(walls[0], 4),
                "steady_round_trip_s": round(walls[1], 4),
                "placed": p,
            }
        return out
    finally:
        client.close()


def run_serve_phase(
    tag: str, args, workdir: str, platforms: str,
    nodes: list[dict], state_path: str,
    mesh_devices: int | None = None,
) -> dict:
    """Start one serve child, drive it end to end, stop it, check what
    it did. Returns the observations plus the ordered bindings."""
    from kubernetes_tpu.server.extender import _load_state_file
    from kubernetes_tpu.state.cluster import ClusterState

    plain, mixed = make_pods(args.seed, args.pods // 2, args.pods // 2)
    all_pods = plain + mixed
    probes = webhook_probe_pods()
    entries0 = cache_entries()
    serve = Serve(tag, workdir, state_path, platforms, mesh_devices)
    try:
        startup_s = serve.wait_healthy()
        m0 = scrape(serve.base)
        device = device_of(m0)
        log(f"{tag}: serve up in {startup_s:.1f}s on {device}")

        def ask(verb: str, body: dict):
            return http("POST", f"{serve.base}/{verb}", body)

        # the parent's oracle view of the same state file
        mirror = ClusterState()
        _load_state_file(mirror, state_path)
        t0 = time.perf_counter()
        served = webhook_answers(ask, probes, nodes)
        webhook_s = time.perf_counter() - t0
        web_empty = check_webhook(
            f"{tag}/empty", served, mirror, probes, nodes
        )

        t_plain = post_pods(serve, plain)
        first, done = wait_bound(serve, len(plain), args.wave_timeout)
        plain_obs = {
            "first_bind_s": round(first - t_plain, 4),
            "wall_s": round(done - t_plain, 4),
        }
        m1 = scrape(serve.base)
        t_mixed = post_pods(serve, mixed)
        first, done = wait_bound(serve, len(all_pods), args.wave_timeout)
        mixed_obs = {
            "first_bind_s": round(first - t_mixed, 4),
            "wall_s": round(done - t_mixed, 4),
        }
        m2 = scrape(serve.base)
        bindings, other = read_bindings(serve.journal, len(all_pods))

        # webhook again, now over a cluster with every occupancy
        # family live; the parent's mirror binds what the journal says
        from kubernetes_tpu.api.objects import Pod

        for d in all_pods:
            mirror.create_pod(Pod.from_dict(d))
        for key, node in bindings:
            ns, _, name = key.partition("/")
            mirror.bind(ns, name, node)
        served = webhook_answers(ask, probes, nodes)
        web_bound = check_webhook(
            f"{tag}/bound", served, mirror, probes, nodes
        )

        free_cpu = {}
        for n in mirror.list_nodes():
            free_cpu[n.name] = n.allocatable["cpu"]
        for p in mirror.list_pods():
            free_cpu[p.node_name] -= p.resource_request().get("cpu", 0)
        bulk = bulk_round_trip(serve, nodes, free_cpu)
        m3 = scrape(serve.base)
    finally:
        serve.close()

    def compiles(a: dict, b: dict) -> dict:
        built = metric_sum(b, "scheduler_xla_compilations_total") - (
            metric_sum(a, "scheduler_xla_compilations_total")
        )
        hits = metric_sum(
            b, "scheduler_xla_persistent_cache_hits_total"
        ) - metric_sum(a, "scheduler_xla_persistent_cache_hits_total")
        secs = metric_sum(b, "scheduler_xla_compile_seconds_total") - (
            metric_sum(a, "scheduler_xla_compile_seconds_total")
        )
        return {
            "executables_built": int(built),
            "from_persistent_cache": int(hits),
            "compiled": int(built - hits),
            "build_seconds": round(secs, 3),
        }

    violations = check_no_fallback(m0, m2, len(all_pods))
    require(not violations, f"{tag}: fallback engaged: {violations}")
    require(
        not other, f"{tag}: non-bound journal outcomes: {other}"
    )
    mesh = int(metric_sum(m2, "scheduler_mesh_devices"))
    want_mesh = device["count"] if mesh_devices is None else mesh_devices
    require(
        mesh == want_mesh,
        f"{tag}: scheduler_mesh_devices {mesh}, expected {want_mesh}",
    )
    t0 = time.perf_counter()
    checked = check_bindings(args.seed, nodes, all_pods, bindings)
    obs = {
        "phase": tag,
        "nodes": len(nodes),
        "pods": len(all_pods),
        "mesh_devices": mesh,
        "startup_s": round(startup_s, 3),
        "webhook": {
            "empty": web_empty, "bound": web_bound,
            "first_pass_s": round(webhook_s, 3),
        },
        "plain_wave": plain_obs,
        "mixed_wave": mixed_obs,
        "pipeline_modes": {
            mode: int(metric_sum(m2, "scheduler_pipeline_mode_total", mode=mode))
            for mode in ("overlap", "carry", "stream", "sync")
        },
        "bulk_grpc": bulk,
        "bindings": checked,
        "oracle_check_s": round(time.perf_counter() - t0, 3),
        "no_fallback": True,
        "h2d_bytes": int(
            metric_sum(m2, "scheduler_tpu_host_to_device_bytes_total")
        ),
        # set-up: everything the two waves built; the second half of
        # the run (mixed wave onward) reuses what the first compiled
        # only where the shapes repeat — arrival timing picks the pod
        # buckets, so the mixed-wave count is an observation
        "compile_setup": compiles({}, m2),
        "compile_mixed_wave": compiles(m1, m2),
        "compile_bulk": compiles(m2, m3),
        "cache_dir": cache_dir(),
        "cache_entries_before": entries0,
        "cache_entries_after": cache_entries(),
    }
    emit(device, **obs)
    obs["device"] = device
    obs["ordered_bindings"] = bindings
    return obs


# -- phase B: every other compiled program, one child -----------------------


def phase_b_child(args) -> int:
    """Runs INSIDE the child that owns the chip. No step's exception is
    caught: a refusal kills the child and the parent fails the run."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.utils.device import init_backend

    device = init_backend()
    dev0 = jax.devices()[0]
    n_nodes, n_pods = args.ns_nodes, args.ns_pods

    def med_us(fn, reps: int = 200) -> float:
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6)

    # -- canary: trivial dispatch and a small device->host read, before
    # and after this process's first read --
    f = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.zeros(8, np.int32))
    f(x).block_until_ready()
    y = f(x)
    before = {
        "dispatch_us": med_us(lambda: f(x)),
        "dispatch_and_wait_us": med_us(lambda: f(x).block_until_ready()),
    }
    y.block_until_ready()
    t0 = time.perf_counter()
    np.asarray(y)
    first_read_us = (time.perf_counter() - t0) * 1e6
    ready = f(x)
    ready.block_until_ready()
    after = {
        "dispatch_us": med_us(lambda: f(x)),
        "dispatch_and_wait_us": med_us(lambda: f(x).block_until_ready()),
        "read_ready_8xint32_us": med_us(lambda: np.asarray(f(x))),
    }
    emit(
        device, phase="B", step="canary", before_first_read=before,
        first_read_us=first_read_us, after_first_read=after,
        note="host-clock medians of 200; dispatch_us is the enqueue only",
    )

    from kubernetes_tpu.obs.compile import WATCHER
    from kubernetes_tpu.parallel.sharding import node_mesh
    from kubernetes_tpu.server.bulk import columnar_pod_batch
    from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
    from kubernetes_tpu.tensorize.schema import (
        NodeBatch,
        ResourceVocab,
        pad_to,
    )

    WATCHER.install()
    mesh = node_mesh() if device["count"] > 1 else None
    vocab = ResourceVocab(("cpu", "memory", "ephemeral-storage"))
    npad = pad_to(n_nodes, 128 * device["count"])
    live = np.arange(npad) < n_nodes

    def node_batch(load=None) -> NodeBatch:
        alloc = np.zeros((3, npad), np.int64)
        alloc[0, :n_nodes] = 16_000
        alloc[1, :n_nodes] = 64 << 30
        used = np.zeros((3, npad), np.int64)
        cnt = np.zeros(npad, np.int32)
        if load is not None:
            used[0, :n_nodes] = load * 1_000
            used[1, :n_nodes] = load * (2 << 30)
            cnt[:n_nodes] = load
        return NodeBatch(
            vocab=vocab, names=[f"n{i}" for i in range(n_nodes)],
            num_nodes=n_nodes, padded=npad, allocatable=alloc, used=used,
            nonzero_used=used[:2].copy(), pod_count=cnt,
            max_pods=np.where(live, 110, 0).astype(np.int32),
            valid=live, schedulable=live.copy(),
        )

    def timed(step: str, fn, **fields):
        """fn() twice: the first call is set-up (it compiles), the
        second is the steady call and must build nothing."""
        c0, _, s0 = WATCHER.totals()
        h0 = WATCHER.cache_hits
        t0 = time.perf_counter()
        fn()
        first_s = time.perf_counter() - t0
        c1, _, s1 = WATCHER.totals()
        h1 = WATCHER.cache_hits
        t0 = time.perf_counter()
        out = fn()
        steady_s = time.perf_counter() - t0
        c2, _, _ = WATCHER.totals()
        emit(
            device, phase="B", step=step, nodes=n_nodes, pods=n_pods,
            first_call_s=round(first_s, 3),
            steady_call_s=round(steady_s, 4),
            setup_executables_built=c1 - c0,
            setup_from_persistent_cache=h1 - h0,
            setup_build_seconds=round(s1 - s0, 3),
            steady_compiles=c2 - c1,
            **fields, **(out or {}),
        )
        require(c2 == c1, f"{step}: {c2 - c1} compiles in the steady call")

    def capacity_ok(step, a, cpu, mem, nb0) -> None:
        """No node over cpu / memory / pod count under the actual
        request vectors (weighted bincounts)."""
        placed = a >= 0
        require(int(a.max()) < n_nodes, f"{step}: bound to a padding row")
        for name, w, used0, cap in (
            ("cpu", cpu, nb0.used[0], nb0.allocatable[0]),
            ("memory", mem, nb0.used[1], nb0.allocatable[1]),
        ):
            load = np.bincount(
                a[placed], weights=w[placed].astype(np.float64),
                minlength=npad,
            )
            require(
                bool((load + used0 <= cap + 0.5).all()),
                f"{step}: {name} overcommit",
            )
        cnt = np.bincount(a[placed], minlength=npad) + nb0.pod_count
        require(bool((cnt <= nb0.max_pods).all()), f"{step}: pods overcommit")

    # -- exact session solve (the north star: 51,200 nodes x 10,240 pods) --
    cfg = ExactSolverConfig(tie_break="random", group_size=1024)
    cpu1 = np.full(n_pods, 1000, np.int64)
    mem1 = np.full(n_pods, 2 << 30, np.int64)
    pb1 = columnar_pod_batch(cpu1, mem1, None, vocab)
    cv = np.ones(npad, np.int64)
    exact_out = {}

    def exact(use_mesh=True):
        a = np.asarray(
            ExactSolver(cfg).solve(
                node_batch(), pb1, col_versions=cv,
                mesh=mesh if use_mesh else None,
            )
        )
        require(int((a >= 0).sum()) == n_pods, "exact: not all placed")
        capacity_ok("exact", a, cpu1, mem1, node_batch())
        # sequential-parity replay: identical pods on identical nodes
        # make the reference tie set the minimum-count nodes
        counts = np.zeros(n_nodes, np.int64)
        for k, node in enumerate(a):
            require(
                counts[node] == k // n_nodes,
                f"exact: step {k} outside the reference tie set",
            )
            counts[node] += 1
        exact_out["a"] = a
        return {"placed": n_pods, "tie_set_replay": "ok"}

    timed("exact_session_solve", exact, mesh_devices=device["count"])
    if mesh is not None:
        sharded = exact_out["a"]
        exact(use_mesh=False)
        require(
            bool(np.array_equal(sharded, exact_out["a"])),
            "exact: sharded solve diverged from the 1-device solve",
        )
        emit(
            device, phase="B", step="exact_mesh_vs_one_device",
            bit_identical=True,
        )

    # -- single-shot auction, relax + auction repair: one preloaded
    # heterogeneous cluster, 8 request classes (BASELINE.json
    # configuration 5's rebalance shape) --
    from kubernetes_tpu.solver.relax import RelaxConfig, RelaxSolver
    from kubernetes_tpu.solver.single_shot import (
        SingleShotConfig,
        SingleShotSolver,
    )

    rng = np.random.default_rng(args.seed)
    load = rng.integers(0, 9, n_nodes)
    rc_cpu = rng.integers(1, 9, 8) * 250
    rc_mem = rng.integers(1, 5, 8) * (1 << 30)
    rc_of = rng.integers(0, 8, n_pods)
    cpu2, mem2 = rc_cpu[rc_of], rc_mem[rc_of]
    prio = rng.integers(0, 10, n_pods).astype(np.int32)
    pb2 = columnar_pod_batch(cpu2, mem2, prio, vocab)

    def auction():
        a = SingleShotSolver().solve(node_batch(load), pb2, mesh=mesh)
        capacity_ok("auction", a, cpu2, mem2, node_batch(load))
        require(int((a >= 0).sum()) == n_pods, "auction: not all placed")
        return {"placed": int((a >= 0).sum())}

    timed("single_shot_auction", auction)

    def relax():
        solver = RelaxSolver(RelaxConfig(), repair=SingleShotConfig())
        a = solver.solve(node_batch(load), pb2, mesh=mesh)
        capacity_ok("relax", a, cpu2, mem2, node_batch(load))
        st = solver.last
        require(st.placed_total == n_pods, f"relax: placed {st.placed_total}")
        return {
            "placed": st.placed_total,
            "placed_by_relaxation": st.placed_relaxed,
            "repaired_by_auction": st.repaired_pods,
            "iterations": st.iterations,
            "residual": round(st.residual, 5),
        }

    timed("relax_with_auction_repair", relax)

    # -- Scheduler.drain_backlog through run_streaming's ring on the
    # hard-spread shape (every pod maxSkew / DoNotSchedule over zones),
    # then a preemption dry run
    # and a dirty-column heal on the same resident session --
    from kubernetes_tpu import metrics
    from kubernetes_tpu.api.wrappers import MakeNode, MakePod
    from kubernetes_tpu.ops.oracle import preemption as opr
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.solver import budget as hbm
    from kubernetes_tpu.solver import exact as exact_mod
    from kubernetes_tpu.state.cluster import ClusterState

    cs = ClusterState()
    for i in range(n_nodes):
        cs.create_node(
            MakeNode().name(f"node-{i:05}")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"})
            .label(ZONE, f"z{i % ZONES}").label(HOST, f"node-{i:05}").obj()
        )
    chunk = min(args.drain_chunk, n_pods)
    sched = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=chunk,
            solver=ExactSolverConfig(tie_break="random", group_size=512),
        ),
    )

    def spread_pod(i: int):
        return (
            MakePod().name(f"pod-{i:05}").label("app", "spread")
            .req({"cpu": "250m", "memory": "512Mi"})
            .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "spread"})
            .obj()
        )

    # set-up: a first drain of two chunks plus the odd remainder
    # compiles the opening, the chained and the partial-chunk programs
    # on this cluster, so the measured drain is whole chunks only
    n_warm = min(2 * chunk + n_pods % chunk, n_pods // 2)
    c0, _, s0 = WATCHER.totals()
    for i in range(n_warm):
        cs.create_pod(spread_pod(i))
    t0 = time.perf_counter()
    warm = sched.drain_backlog(chunk_pods=chunk)
    warm_s = time.perf_counter() - t0
    require(warm.drained == n_warm, f"drain set-up bound {warm.drained}")
    c1, _, s1 = WATCHER.totals()
    t0 = time.perf_counter()
    for i in range(n_warm, n_pods):
        cs.create_pod(spread_pod(i))
    enqueue_s = time.perf_counter() - t0
    report = sched.drain_backlog(chunk_pods=chunk)
    c2, _, _ = WATCHER.totals()
    require(
        report.drained == n_pods - n_warm,
        f"drain bound {report.drained}/{n_pods - n_warm}",
    )
    require(
        report.chain_fraction >= 0.5,
        f"stream chain engaged on {report.chain_fraction:.0%} of chunks",
    )
    nodes_list = cs.list_nodes()
    slot = {n.name: i for i, n in enumerate(nodes_list)}
    pods_now = cs.list_pods()
    a = np.fromiter(
        (slot[p.node_name] for p in pods_now), np.int64, count=len(pods_now)
    )
    require(len(pods_now) == n_pods, f"{len(pods_now)} pods in the store")
    require(int(np.bincount(a, minlength=n_nodes).max()) * 250 <= 16_000,
            "drain: cpu overcommit")
    require(int(np.bincount(a, minlength=n_nodes).max()) <= 110,
            "drain: pods overcommit")
    zone_idx = np.asarray([int(n.labels[ZONE][1:]) for n in nodes_list])
    zones = np.bincount(zone_idx[a], minlength=ZONES)
    require(
        int(zones.max() - zones.min()) <= 1,
        f"drain: zone skew {zones.tolist()}",
    )
    stats = dev0.memory_stats() or {}
    emit(
        device, phase="B", step="drain_backlog_streaming",
        nodes=n_nodes, pods=n_pods, drained_pods=report.drained,
        chunk_pods=report.chunk_pods,
        chunks=report.chunks, chain_fraction=round(report.chain_fraction, 4),
        stream_chained_batches=report.stream_chained_batches,
        budget_splits=report.budget_splits,
        setup_pods=n_warm, setup_drain_s=round(warm_s, 3),
        setup_executables_built=c1 - c0,
        setup_build_seconds=round(s1 - s0, 3),
        enqueue_s=round(enqueue_s, 3),
        drain_s=round(report.drain_seconds, 3),
        steady_compiles=c2 - c1,
        mesh_devices=sched._mesh_devices,
        hbm_budget_bytes=report.budget_bytes,
        hbm_bytes_limit=stats.get("bytes_limit"),
        hbm_peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        hbm_estimated_per_device_bytes=report.estimated_per_device_bytes,
        zone_skew=int(zones.max() - zones.min()),
    )
    if device["platform"] != "cpu":
        require(
            report.budget_bytes == hbm.device_budget_bytes(0)
            == stats["bytes_limit"],
            "drain budget is not the limit the chip gave",
        )

    # preemption dry run: a pod no node can hold without evictions
    c0, _, s0 = WATCHER.totals()
    cs.create_pod(
        MakePod().name("preemptor").priority(1000)
        .req({"cpu": "15500m", "memory": "1Gi"}).obj()
    )
    t0 = time.perf_counter()
    res = sched.schedule_batch()
    preempt_s = time.perf_counter() - t0
    require(
        len(res.preemptions) == 1,
        f"preemption dry run nominated {res.preemptions}",
    )
    _pod_key, nominated, victims = res.preemptions[0]
    node = cs.get_node(nominated)
    on_node = [p for p in pods_now if p.node_name == nominated]
    want = opr.select_victims_on_node(
        cs.get_pod("default", "preemptor"), node.allocatable,
        node.allowed_pod_number, on_node, [],
    )
    on_keys = {p.key for p in on_node}
    require(
        want is not None
        and len(victims) == len(want.victims)
        and set(victims) <= on_keys,
        f"preemption victims {victims} on {nominated}: the oracle "
        f"evicts {want and [v.key for v in want.victims]}",
    )
    c1, _, s1 = WATCHER.totals()
    emit(
        device, phase="B", step="preemption_dry_run", nodes=n_nodes,
        nominated=nominated, victims=len(victims), wall_s=round(preempt_s, 3),
        setup_executables_built=c1 - c0,
        setup_build_seconds=round(s1 - s0, 3),
    )

    # dirty-column heal: grow ONE node past every other, then send a
    # pod only that node can hold — it binds only if the scatter
    # reached the resident tables
    grown = "node-00007" if n_nodes > 7 else "node-00000"
    heals0 = exact_mod._heal_jit._cache_size()
    h2d0 = metrics.h2d_bytes_total._value.get()
    cs.update_node(
        MakeNode().name(grown)
        .capacity({"cpu": "64", "memory": "256Gi", "pods": "110"})
        .label(ZONE, cs.get_node(grown).labels[ZONE])
        .label(HOST, grown).obj()
    )
    cs.create_pod(
        MakePod().name("needs-heal").priority(2000)
        .req({"cpu": "40", "memory": "1Gi"}).obj()
    )
    t0 = time.perf_counter()
    sched.run_until_settled()
    heal_s = time.perf_counter() - t0
    healed = cs.get_pod("default", "needs-heal")
    require(
        healed.node_name == grown,
        f"heal: pod bound to {healed.node_name!r}, not {grown}",
    )
    emit(
        device, phase="B", step="dirty_column_heal", nodes=n_nodes,
        bound_to=healed.node_name, wall_s=round(heal_s, 3),
        heal_programs_compiled=exact_mod._heal_jit._cache_size() - heals0,
        h2d_bytes=int(metrics.h2d_bytes_total._value.get() - h2d0),
    )
    require(
        exact_mod._heal_jit._cache_size() > 0, "heal program never ran"
    )

    # -- the Pallas kernel, compiled (not interpreted), x64 on, at the
    # zone-topology shape the scan hands it --
    from kubernetes_tpu.ops.pallas_kernels import (
        domain_counts_padded,
        domain_counts_reference,
    )

    require(jax.config.jax_enable_x64, "x64 is off")
    interpret = jax.default_backend() != "tpu"
    t_terms, d_pad = 8, 8
    dom = rng.integers(-1, ZONES, (t_terms, npad)).astype(np.int32)
    cnt = rng.integers(0, 5, (t_terms, npad)).astype(np.int32)
    kernel = jax.jit(domain_counts_padded, static_argnames=("d_pad",))
    reference = jax.jit(domain_counts_reference, static_argnames=("d_pad",))
    dd, cc = jnp.asarray(dom), jnp.asarray(cnt)
    got = np.asarray(kernel(dd, cc, d_pad=d_pad))
    want = np.asarray(reference(dd, cc, d_pad=d_pad))
    require(bool(np.array_equal(got, want)), "pallas kernel != reference")
    lowered = kernel.lower(dd, cc, d_pad=d_pad).as_text()
    require(
        interpret or "tpu_custom_call" in lowered,
        "pallas kernel did not lower to a Mosaic custom call",
    )
    emit(
        device, phase="B", step="pallas_domain_counts",
        shape=[t_terms, npad, d_pad], x64=True, interpret=interpret,
        matches_reference=True,
    )
    emit(
        device, phase="B", step="done",
        cache_dir=jax.config.jax_compilation_cache_dir,
    )
    return 0


def run_phase_b(args, platforms: str) -> dict:
    """Spawn the phase B child, relay its lines, collect its steps."""
    argv = [
        sys.executable, os.path.abspath(__file__), "--phase-b-child",
        "--seed", str(args.seed), "--ns-nodes", str(args.ns_nodes),
        "--ns-pods", str(args.ns_pods),
        "--drain-chunk", str(args.drain_chunk),
    ]
    proc = subprocess.Popen(
        argv, cwd=REPO, env=child_env(platforms),
        stdout=subprocess.PIPE, text=True,
    )
    steps, device = {}, None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if not line.startswith("{"):
                log(f"B: {line}")
                continue
            rec = json.loads(line)
            print(line, flush=True)
            steps[rec["step"]] = rec
            device = rec["device"]
        code = proc.wait()
    finally:
        stop(proc)
    require(code == 0, f"phase B child exited with code {code}")
    expected = (
        "canary", "exact_session_solve", "single_shot_auction",
        "relax_with_auction_repair", "drain_backlog_streaming",
        "preemption_dry_run", "dirty_column_heal", "pallas_domain_counts",
        "done",
    )
    missing = [s for s in expected if s not in steps]
    require(not missing, f"phase B steps missing: {missing}")
    require(
        steps["done"]["cache_dir"] == cache_dir(),
        f"phase B cached in {steps['done']['cache_dir']}, "
        f"expected {cache_dir()}",
    )
    return {"device": device, "steps": steps}


# -- the run -----------------------------------------------------------------


def run(args, platforms: str = "tpu,cpu") -> dict:
    """All phases, in order; raises on the first failure. ``platforms``
    is what the children get as JAX_PLATFORMS — only the tier-1 test,
    which drives this at a tiny size, passes "cpu"."""
    workdir = args.workdir
    os.makedirs(workdir, exist_ok=True)
    nodes = make_nodes(args.nodes)
    state_path = os.path.join(workdir, "state.json")
    with open(state_path, "w") as f:
        json.dump({"nodes": nodes}, f)
    log(f"state: {args.nodes} nodes -> {state_path}; cache {cache_dir()}")
    summary: dict = {"phases": {}}
    phases = args.phases.split(",")

    def serve_phase(tag: str, mesh_devices=None) -> dict:
        return run_serve_phase(
            tag, args, workdir, platforms, nodes, state_path, mesh_devices
        )

    def brief(obs: dict) -> dict:
        return {
            "startup_s": obs["startup_s"],
            "first_bind_s": obs["plain_wave"]["first_bind_s"],
            "waves_wall_s": round(
                obs["plain_wave"]["wall_s"] + obs["mixed_wave"]["wall_s"], 3
            ),
            "compiled": obs["compile_setup"]["compiled"],
            "from_persistent_cache": obs["compile_setup"][
                "from_persistent_cache"
            ],
            "build_seconds": obs["compile_setup"]["build_seconds"],
        }

    cold = None
    if "A" in phases:
        cold = serve_phase("A")
        summary["device"] = cold["device"]
        summary["phases"]["A"] = brief(cold)
        if cold["device"]["count"] > 1:
            one = serve_phase("A-mesh1", mesh_devices=1)
            require(
                one["ordered_bindings"] == cold["ordered_bindings"],
                "bindings on the default mesh differ from meshDevices: 1",
            )
            emit(
                cold["device"], phase="A", step="mesh_vs_one_device",
                identical_bindings=True, pods=len(cold["ordered_bindings"]),
            )
            summary["phases"]["A-mesh1"] = {
                **brief(one), "identical_bindings": True
            }
    if "B" in phases:
        b = run_phase_b(args, platforms)
        summary.setdefault("device", b["device"])
        require(
            b["device"] == summary["device"],
            f"phase B ran on {b['device']}, phase A on {summary['device']}",
        )
        canary = b["steps"]["canary"]
        drain = b["steps"]["drain_backlog_streaming"]
        summary["phases"]["B"] = {
            "canary_dispatch_us": canary["after_first_read"]["dispatch_us"],
            "canary_read_us": canary["after_first_read"][
                "read_ready_8xint32_us"
            ],
            "chain_fraction": drain["chain_fraction"],
            "hbm_bytes_limit": drain["hbm_bytes_limit"],
            "hbm_peak_bytes_in_use": drain["hbm_peak_bytes_in_use"],
            "pallas_interpret": b["steps"]["pallas_domain_counts"][
                "interpret"
            ],
        }
    if "C" in phases:
        require(cold is not None, "phase C needs phase A's cold start")
        warm = serve_phase("C")
        require(
            warm["device"] == cold["device"],
            f"phase C ran on {warm['device']}",
        )
        require(cache_entries() > 0, f"{cache_dir()} is empty")
        c_cold = cold["compile_setup"]["compiled"]
        c_warm = warm["compile_setup"]["compiled"]
        # fewer compilations warm than cold — or none at all, when the
        # cache directory came already warm from an earlier run
        require(
            c_warm < c_cold or c_warm == 0,
            f"warm start compiled {c_warm} programs, cold {c_cold}",
        )
        require(
            warm["compile_setup"]["from_persistent_cache"] > 0,
            "warm start took nothing from the persistent cache",
        )
        summary["phases"]["C"] = {
            "cold": brief(cold), "warm": brief(warm),
            "cache_dir": cache_dir(), "cache_entries": cache_entries(),
        }
        emit(
            warm["device"], phase="C", step="cold_vs_warm",
            **summary["phases"]["C"],
        )
    return summary


def report(args, summary: dict, wall_s: float) -> None:
    """The last two stdout lines: the summary, then the verdict the
    driver reads — exactly the keys "ok" and "device", nothing after."""
    device = summary["device"]
    print(
        json.dumps(
            {
                "step": "summary", "device": device, "seed": args.seed,
                "sizes": {
                    "serve": [args.pods, args.nodes],
                    "north_star": [args.ns_pods, args.ns_nodes],
                },
                "wall_s": round(wall_s, 1),
                "phases": summary["phases"], "claim": None,
            }
        ),
        flush=True,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=5_000)
    ap.add_argument("--pods", type=int, default=10_000)
    ap.add_argument("--ns-nodes", type=int, default=10_240)
    ap.add_argument("--ns-pods", type=int, default=51_200)
    ap.add_argument("--drain-chunk", type=int, default=4_096)
    ap.add_argument("--wave-timeout", type=float, default=600.0)
    ap.add_argument("--phases", default="A,B,C")
    ap.add_argument(
        "--workdir", default=os.path.join(REPO, "chiprun_out", "chip_smoke")
    )
    ap.add_argument("--phase-b-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase_b_child:
        return phase_b_child(args)
    asked = os.environ.get("JAX_PLATFORMS")
    require(
        not asked or asked.split(",")[0] == "tpu",
        f"JAX_PLATFORMS={asked!r} does not ask for a TPU; this run proves "
        "the program on the chip or fails",
    )
    t0 = time.perf_counter()
    summary = run(args)
    require("jax" not in sys.modules, "the parent imported jax")
    device = summary["device"]
    require(
        device["platform"] == "tpu",
        f"ran on {device['platform']!r}, not on a TPU",
    )
    report(args, summary, time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
