"""Driver of one ``serve --mode scheduler`` child: spawn, wire helpers,
requests to the wrapper's side thread, stop. The parent never touches
JAX while the child lives."""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time


class ServeError(RuntimeError):
    """The child died, refused or timed out: the run prints no result."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {(name, sorted label items): value}."""
    from prometheus_client.parser import text_string_to_metric_families

    out = {}
    for fam in text_string_to_metric_families(text):
        for s in fam.samples:
            out[(s.name, tuple(sorted(s.labels.items())))] = s.value
    return out


def metric_sum(samples: dict, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(
        v for (n, ls), v in samples.items() if n == name and want <= set(ls)
    )


def device_of(samples: dict) -> dict:
    """The device the child initialised, as it exported it at start-up
    (scheduler_tpu_device_info)."""
    rows = [
        (dict(ls), v)
        for (n, ls), v in samples.items()
        if n == "scheduler_tpu_device_info"
    ]
    if len(rows) != 1:
        raise ServeError(f"expected one device_info series, got {rows}")
    labels, count = rows[0]
    return {
        "platform": labels["platform"],
        "kind": labels["device_kind"],
        "count": int(count),
    }


class Serve:
    def __init__(
        self, root: str, workdir: str, state_path: str, platforms: str,
        telemetry: bool = False,
    ) -> None:
        self.workdir = workdir
        self.journal = os.path.join(workdir, "journal.jsonl")
        self.log_path = os.path.join(workdir, "serve.log")
        self.port = free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platforms
        # the program places its compile cache by this variable, else at
        # <checkout>/.jax_cache: the same fixed path, said out loud
        env.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache")
        )
        # one log line per executable built: which program a compile
        # inside the window was (run.py reads the names off the log)
        env.setdefault("JAX_LOG_COMPILES", "1")
        argv = [
            sys.executable,
            os.path.join(root, "benchmarks", "lib", "serve_child.py"),
            root,
            "serve", "--mode", "scheduler", "--state", state_path,
            "--port", str(self.port), "--obs-journal", self.journal,
        ]
        if telemetry:
            argv.append("--telemetry")
        self._log = open(self.log_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=root, env=env, stdin=subprocess.PIPE,
            stdout=self._log, stderr=subprocess.STDOUT,
        )
        self._local = threading.local()  # one connection to a thread
        self._acks = 0
        self._ask_lock = threading.Lock()

    # -- wire -------------------------------------------------------------

    def alive(self) -> bool:
        return self.proc.poll() is None

    def require_alive(self) -> None:
        if not self.alive():
            raise ServeError(
                f"serve exited with code {self.proc.returncode}:\n"
                f"{self.log_tail()}"
            )

    def request(self, method: str, path: str, body: bytes | None = None,
                timeout: float = 600.0) -> bytes:
        for attempt in (0, 1):
            conn = getattr(self._local, "conn", None)
            if conn is None:
                conn = self._local.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=timeout
                )
            try:
                conn.request(
                    method, path, body=body,
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                data = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                self._local.conn = None
                self.require_alive()
                if attempt:
                    raise
                continue
            if resp.status != 200:
                raise ServeError(f"{method} {path}: HTTP {resp.status} {data[:200]!r}")
            return data
        raise AssertionError("unreachable")

    def wait_healthy(self, timeout: float = 300.0) -> float:
        """Seconds from spawn to the first /healthz answer. The child
        initialises its backend before it listens, so a missing chip
        shows here as an exited process."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.require_alive()
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=2.0)
                conn.request("GET", "/healthz")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return time.monotonic() - self.t_spawn
            except OSError:
                pass
            time.sleep(0.05)
        raise ServeError(f"serve not healthy after {timeout}s:\n{self.log_tail()}")

    def post_pods(self, body: bytes) -> int:
        return int(json.loads(self.request("POST", "/api/pods", body))["applied"])

    def scrape(self) -> dict:
        return parse_metrics(self.request("GET", "/metrics").decode())

    # -- requests to the wrapper's side thread ----------------------------

    def ask(self, *words: str, timeout: float = 120.0) -> dict:
        with self._ask_lock:
            self._acks += 1
            ack = os.path.join(self.workdir, f"ack-{self._acks}.json")
            self.proc.stdin.write((" ".join([*words, ack]) + "\n").encode())
            self.proc.stdin.flush()
        deadline = time.monotonic() + timeout
        while not os.path.exists(ack):
            self.require_alive()
            if time.monotonic() > deadline:
                raise ServeError(f"no answer to {words[0]} after {timeout}s")
            time.sleep(0.01)
        with open(ack) as f:
            doc = json.load(f)
        if "error" in doc:
            raise ServeError(f"{words[0]}: {doc['error']}")
        return doc

    def memory_peak_bytes(self) -> int | None:
        peaks = [p for p in self.ask("mem")["peak_bytes_in_use"] if p is not None]
        return max(peaks) if peaks else None

    # -- lifetime ---------------------------------------------------------

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def close(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            conn.close()
            self._local.conn = None
        if self.proc.poll() is None:
            # a graceful stop first drains up to 64 queued batches; by
            # now everything a run needs is on disk or read, so a short
            # grace and then the kill
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=2)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self.proc.stdin:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
        self._log.close()
