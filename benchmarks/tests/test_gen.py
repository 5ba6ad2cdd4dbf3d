"""The generator is a pure function of --seed and the cell's files."""

import collections
import json

import pytest

from benchmarks.lib import files, gen, standin
from conftest import mixed_cfg

CONFIGS = files.names("configs")


@pytest.mark.parametrize("config", CONFIGS)
def test_same_seed_same_stream(config):
    cfg = files.load_config(config)
    a = gen.RolloutStream(cfg, 2**31 + 11).take(3000)
    b = gen.RolloutStream(cfg, 2**31 + 11).take(3000)
    c = gen.RolloutStream(cfg, 12).take(3000)
    assert a == b
    assert a != c
    assert len({s.name for s in a}) == len(a)
    assert gen.pods_body(cfg, a[:50]) == gen.pods_body(cfg, b[:50])


def test_mixed_shares_hold_for_every_seed():
    cfg = mixed_cfg()
    for seed in (1, 2, 3_000_000_019):
        pods = gen.RolloutStream(cfg, seed).take(40_000)
        share = collections.Counter(s.kind for s in pods)
        assert abs(share["plain"] / 40_000 - 0.5) < 0.03, share
        for kind in ("spread", "anti"):
            assert abs(share[kind] / 40_000 - 0.25) < 0.03, share
        apps = collections.defaultdict(set)
        for s in pods:
            apps[s.kind].add(s.app)
        assert {k: len(v) for k, v in apps.items()} == {
            "plain": 8, "spread": 2, "anti": 5,
        }


def test_spread_pods_are_upstreams_template():
    cfg = files.load_config("sched-perf-spread-5000n")
    pods = gen.RolloutStream(cfg, 7).take(500)
    assert {(s.kind, s.app, s.label_key) for s in pods} == {("spread", "blue", "color")}
    m = gen.pod_manifest(cfg, pods[0])
    assert m["metadata"]["labels"] == {"color": "blue"}
    assert m["spec"]["topologySpreadConstraints"] == [{
        "maxSkew": 5, "topologyKey": "topology.kubernetes.io/zone",
        "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"color": "blue"}},
    }]
    nodes = gen.make_nodes(cfg)
    assert [n["metadata"]["labels"] for n in nodes[:4]] == [
        {"topology.kubernetes.io/zone": z}
        for z in ("moon-1", "moon-2", "moon-3", "moon-1")
    ]
    init = gen.init_pods(cfg)
    assert len(init) == 5000 and {s.kind for s in init} == {"plain"}


def test_manifest_carries_what_the_spec_says():
    cfg = mixed_cfg()
    for spec in gen.RolloutStream(cfg, 5).take(2000):
        assert standin.spec_of(gen.pod_manifest(cfg, spec)) == spec
    cfg = files.load_config("sched-perf-basic-5000n")
    node = gen.make_nodes(cfg)[4321]
    assert node["status"]["allocatable"] == {
        "cpu": "4", "memory": str(32 * 2**30), "pods": "110",
    }
    assert node["metadata"]["labels"] == {
        "topology.kubernetes.io/zone": "z1",
        "kubernetes.io/hostname": "node-04321",
    }
    assert len(gen.make_nodes(cfg)) == 5000
    json.dumps(node)
