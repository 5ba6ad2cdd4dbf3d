"""Process-level smoke of the serve binary: `python -m kubernetes_tpu
serve` in a real subprocess — the operator's actual entry point — must
come up, answer verbs, ingest, schedule, and die cleanly."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.api.wrappers import MakeNode, MakePod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _req(port, method, path, payload=None, timeout=120):
    # generous default: the first device-backed verb compiles the evaluator
    data = json.dumps(payload).encode() if payload is not None else None
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=data,
        method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_serve_process_end_to_end(tmp_path):
    state = {
        "nodes": [
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .obj().to_dict()
            for i in range(4)
        ],
    }
    state_file = tmp_path / "state.json"
    state_file.write_text(json.dumps(state))
    port = _free_port()

    env = dict(os.environ)
    # the server subprocess runs on CPU in tests (the installed JAX
    # honours JAX_PLATFORMS)
    env["JAX_PLATFORMS"] = "cpu"
    log = open(tmp_path / "serve.log", "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kubernetes_tpu", "serve",
            "--state", str(state_file),
            "--mode", "scheduler",
            "--port", str(port),
        ],
        cwd=_REPO,
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
    )

    def server_log() -> str:
        log.flush()
        return (tmp_path / "serve.log").read_text()

    try:
        last_err = None
        for _ in range(240):
            try:
                # healthz is plain text ("ok"), not JSON
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5
                ) as resp:
                    assert resp.read() == b"ok"
                break
            except Exception as e:
                last_err = e
                if proc.poll() is not None:
                    pytest.fail(
                        "serve exited during startup:\n" + server_log()
                    )
                time.sleep(0.5)
        else:
            pytest.fail(
                f"serve never became healthy (last: {last_err!r}):\n"
                + server_log()
            )

        st = _req(port, "GET", "/api/state")
        assert st["nodes"] == 4

        # webhook verb over the real socket
        pod = MakePod().name("probe").req({"cpu": "4"}).obj()
        out = _req(
            port, "POST", "/filter",
            {"pod": pod.to_dict(), "nodenames": ["n0", "n1", "ghost"]},
        )
        assert out["nodenames"] == ["n0", "n1"]
        assert out["failedAndUnresolvableNodes"] == {"ghost": "node not found"}

        # ingest + background scheduling
        pods = {
            "items": [
                MakePod().name(f"w{i}").req({"cpu": "1"}).obj().to_dict()
                for i in range(6)
            ]
        }
        assert _req(port, "POST", "/api/pods", pods) == {"applied": 6}
        for _ in range(120):
            st = _req(port, "GET", "/api/state")
            if st["unscheduled"] == 0:
                break
            time.sleep(0.5)
        assert st["unscheduled"] == 0

        # metrics exposition is live
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "scheduler_schedule_attempts_total" in raw
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


def _wait_healthy(proc, port, server_log):
    last_err = None
    for _ in range(240):
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5
            ) as resp:
                assert resp.read() == b"ok"
            return
        except Exception as e:
            last_err = e
            if proc.poll() is not None:
                pytest.fail(
                    "serve exited during startup:\n" + server_log()
                )
            time.sleep(0.5)
    pytest.fail(
        f"serve never became healthy (last: {last_err!r}):\n"
        + server_log()
    )


def _get_status(port, path):
    """(status, parsed-JSON body) — urllib raises on 4xx/5xx, but the
    debug surfaces' disabled contracts ARE json bodies with status."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_serve_debug_surfaces_end_to_end(tmp_path):
    """ISSUE 18 satellite: the operator debug surfaces — /debug/slo,
    /debug/hub, /debug/profile — over a real serve subprocess with the
    full telemetry stack on: status codes, response schema, and one
    consistent-snapshot read of /debug/profile under concurrent
    scheduling traffic."""
    from kubernetes_tpu.obs.profile import STAGES

    state = {
        "nodes": [
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "40"})
            .obj().to_dict()
            for i in range(4)
        ],
    }
    state_file = tmp_path / "state.json"
    state_file.write_text(json.dumps(state))
    port = _free_port()

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    log = open(tmp_path / "serve.log", "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kubernetes_tpu", "serve",
            "--state", str(state_file),
            "--mode", "scheduler",
            "--port", str(port),
            "--obs", "--slo", "30", "--telemetry",
        ],
        cwd=_REPO,
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
    )

    def server_log() -> str:
        log.flush()
        return (tmp_path / "serve.log").read_text()

    try:
        _wait_healthy(proc, port, server_log)

        # /debug/slo: enabled (serve --slo 30), serves the engine's
        # live snapshot schema
        status, slo = _get_status(port, "/debug/slo")
        assert status == 200, slo
        for key in ("healthy", "p99_pod_latency_s", "burn_rates"):
            assert key in slo, sorted(slo)

        # /debug/hub: this serve is not a fleet replica — the disabled
        # contract is a 404 WITH a json error body, not a bare error
        status, hub = _get_status(port, "/debug/hub")
        assert status == 404
        assert "occupancy hub" in hub["error"]

        # /debug/profile: enabled (serve --telemetry) even before any
        # batch ran — the schema must hold at zero
        status, prof = _get_status(port, "/debug/profile")
        assert status == 200, prof
        assert prof["enabled"] is True
        assert set(prof["profile"]["stage_seconds"]) == set(STAGES)
        assert "degraded" in prof["sentinel"]
        assert "captures" in prof["bundles"]

        # consistent snapshots under concurrent traffic: ingest pods
        # (the drain task schedules them in the background) while
        # polling the profile surface — every poll must parse against
        # the schema and the batch counter must be monotone
        pods = {
            "items": [
                MakePod().name(f"w{i}").req({"cpu": "1"}).obj().to_dict()
                for i in range(24)
            ]
        }
        assert _req(port, "POST", "/api/pods", pods) == {"applied": 24}
        last_batches = 0
        for _ in range(120):
            status, prof = _get_status(port, "/debug/profile")
            assert status == 200
            batches = prof["profile"]["batches"]
            assert batches >= last_batches, (
                "profiler batch counter went backwards under "
                f"concurrent reads: {last_batches} -> {batches}"
            )
            assert set(prof["profile"]["stage_seconds"]) == set(STAGES)
            last_batches = batches
            st = _req(port, "GET", "/api/state")
            if st["unscheduled"] == 0 and batches > 0:
                break
            time.sleep(0.5)
        assert st["unscheduled"] == 0
        assert last_batches > 0, "no batch ever closed a ledger entry"
        # the scheduled batches must have attributed stage time
        assert sum(prof["profile"]["stage_seconds"].values()) > 0.0

        # ?capture=1: a manual forensic capture counts (no bundle_dir,
        # so nothing hits disk — captures counts regardless)
        status, cap = _get_status(port, "/debug/profile?capture=1")
        assert status == 200
        assert cap["captured"] is True
        assert cap["bundles"]["captures"] >= 1
        assert cap["bundles"]["by_trigger"].get("manual", 0) >= 1
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
