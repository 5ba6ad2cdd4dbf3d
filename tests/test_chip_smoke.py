"""chip_smoke.py, driven where there is no chip (on-chip guide §1: make
the command run end to end here first, at a tiny size on the CPU
backend, then send the same command at the real size).

- the whole run — serve children, the phase B child, the oracle checks,
  the multi-device branch on virtual CPU devices — at a tiny size;
- the no-fallback proof FAILS when a solve runs below the top tier;
- the default invocation exits non-zero without a chip, and refuses a
  JAX_PLATFORMS that names no TPU.
"""

import argparse
import json
import os
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import chip_smoke  # noqa: E402


def test_all_phases_tiny_on_cpu(tmp_path, monkeypatch, capsys):
    """Phases A (default mesh AND meshDevices: 1, identical bindings), B
    and C through the real children with JAX_PLATFORMS=cpu and four
    virtual devices; every check function runs on what they return."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    # the cache placed from outside, and empty: phase A is a true cold
    # start and everything the children cache must land HERE
    cache = tmp_path / "cache-from-outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    args = argparse.Namespace(
        seed=3, nodes=48, pods=192, ns_nodes=256, ns_pods=1024,
        drain_chunk=256, wave_timeout=300.0, phases="A,B,C",
        workdir=str(tmp_path / "work"),
    )
    summary = chip_smoke.run(args, platforms="cpu")
    assert summary["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert set(summary["phases"]) == {"A", "A-mesh1", "B", "C"}
    assert summary["phases"]["A-mesh1"]["identical_bindings"]
    c = summary["phases"]["C"]
    assert c["warm"]["compiled"] < c["cold"]["compiled"]
    assert c["cold"]["from_persistent_cache"] == 0
    assert c["cache_dir"] == str(cache) and c["cache_entries"] > 0
    # on the CPU the kernel can only be interpreted; on a TPU
    # domain_counts_padded compiles it or raises
    assert summary["phases"]["B"]["pallas_interpret"] is True
    # every stdout line is JSON and names the device the child reported
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) > 10
    for line in lines:
        assert json.loads(line)["device"]["platform"] == "cpu"
    # the last two lines main() prints: the summary ends "claim": null,
    # and the verdict has exactly the keys the driver's contract names
    chip_smoke.report(args, summary, 1.0)
    tail = capsys.readouterr().out.strip().splitlines()
    assert tail[-2].endswith('"claim": null}')
    verdict = json.loads(tail[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert type(verdict["device"]["count"]) is int


def test_no_fallback_check_fails_below_the_top_tier():
    """A scheduler pinned to the host rung binds every pod — exactly the
    outcome that made a dead device invisible — and the proof rejects
    it; the same drive at the top tier passes."""
    from kubernetes_tpu import metrics
    from kubernetes_tpu.api.wrappers import MakeNode, MakePod
    from kubernetes_tpu.resilience import ResilienceConfig
    from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.state.cluster import ClusterState

    def samples() -> dict:
        from prometheus_client.parser import text_string_to_metric_families

        # the registry is process-wide: keep this drive's profile only
        # (other tests leave their own profiles' breaker gauges behind)
        return {
            (s.name, tuple(sorted(s.labels.items()))): s.value
            for fam in text_string_to_metric_families(
                metrics.render().decode()
            )
            for s in fam.samples
            if s.labels.get("profile", "default-scheduler")
            == "default-scheduler"
        }

    def drive(resilience) -> list[str]:
        cs = ClusterState()
        for i in range(4):
            cs.create_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi", "pods": "32"}).obj()
            )
        sched = Scheduler(cs, SchedulerConfig(resilience=resilience))
        before = samples()
        for i in range(12):
            cs.create_pod(MakePod().name(f"p{i}").req({"cpu": "100m"}).obj())
        while sched.pending:
            if not any(r.progressed for r in sched.run_pipelined()):
                break
        assert all(p.node_name for p in cs.list_pods())  # "all bound"
        return chip_smoke.check_no_fallback(before, samples(), 12)

    assert drive(None) == []
    bad = drive(ResilienceConfig(force_tier="host"))
    assert any("tier 'host'" in b for b in bad), bad
    assert any("host->device" in b for b in bad), bad


def _run_default(env_platforms: str | None) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    if env_platforms is not None:
        env["JAX_PLATFORMS"] = env_platforms
    return subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "chip_smoke.py")],
        cwd=_REPO_ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("platforms", ["cpu", None])
def test_default_invocation_fails_without_a_chip(platforms):
    """No chip here: whatever JAX_PLATFORMS says, the default invocation
    exits non-zero and prints no result."""
    proc = _run_default(platforms)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
