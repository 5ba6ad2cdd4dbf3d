"""The plain reference's predicates, on cases worked by hand."""

from benchmarks.lib import files, gen, reference
from conftest import mixed_cfg


def small_cfg():
    return mixed_cfg(nodes=6)  # zones 0 1 2 0 1 2, maxSkew 1


def test_capacity_is_forty_pods_of_100m():
    ref = reference.ClusterRef(small_cfg())
    pod = gen.PodSpec("p", "plain", "plain-0")
    for _ in range(40):
        assert ref.violations(pod, 0) == []
        ref.bind(pod, 0)
    assert ref.violations(pod, 0) == ["cpu"]
    assert ref.violations(pod, 1) == []
    assert ref.end_state()["nodes_over_capacity"] == 0
    ref.bind(pod, 0)
    assert ref.end_state()["nodes_over_capacity"] == 1


def test_anti_affinity_is_per_node():
    ref = reference.ClusterRef(small_cfg())
    x = gen.PodSpec("x", "anti", "anti-0")
    y = gen.PodSpec("y", "anti", "anti-1")
    ref.bind(x, 2)
    assert ref.violations(x, 2) == ["anti_affinity"]
    assert ref.violations(y, 2) == [] and ref.violations(x, 5) == []


def test_zone_skew_counts_the_incoming_pod():
    ref = reference.ClusterRef(small_cfg())
    s = gen.PodSpec("s", "spread", "spread-0")
    ref.bind(s, 0)  # z0: 1, z1: 0, z2: 0
    assert ref.violations(s, 3) == ["zone_skew"]  # z0 again: 2 - 0 > 1
    assert ref.violations(s, 1) == []
    ref.bind(s, 1)
    ref.bind(s, 2)  # 1 1 1
    assert ref.violations(s, 3) == []
    other = gen.PodSpec("o", "spread", "spread-1")
    assert ref.violations(other, 0) == []  # its own app's counts


def test_upstreams_max_skew_five_is_read_from_the_configuration():
    cfg = files.load_config("sched-perf-spread-5000n")
    cfg["nodes"]["count"] = 6
    ref = reference.ClusterRef(cfg)
    s = gen.PodSpec("s", "spread", "blue", "color")
    for _ in range(5):
        assert ref.violations(s, 0) == []
        ref.bind(s, 0)  # moon-1: 5, the others 0
    assert ref.violations(s, 3) == ["zone_skew"]  # 6 - 0 > 5
    assert ref.violations(s, 1) == []
    assert ref.end_state()["max_zone_skew"] == 5


def test_replay_counts_each_breach():
    cfg = small_cfg()
    specs = {
        s.key: s
        for s in (
            gen.PodSpec("a", "anti", "anti-0"),
            gen.PodSpec("b", "anti", "anti-0"),
            gen.PodSpec("c", "plain", "plain-0"),
        )
    }
    got = reference.replay(
        cfg, specs,
        [("default/a", "node-00000"), ("default/b", "node-00000"),
         ("default/c", "node-00001"), ("default/c", "node-00002"),
         ("default/zzz", "node-00001"), ("default/a", "node-99999")],
    )
    assert got["infeasible_at_commit"] == 1
    assert got["anti_affinity_clashes"] == 1
    assert got["bound_twice"] == 1
    assert got["unknown_bindings"] == 2
    assert got["bound"] == 3


def test_reference_scheduler_holds_its_own_guarantees():
    cfg = small_cfg()
    cfg["stream"]["deploymentReplicas"] = 6
    pods = gen.RolloutStream(cfg, 9).take(60)
    sched = reference.ReferenceScheduler(cfg)
    placed = sched.schedule(pods)
    bindings = [(s.key, n) for s, n in placed if n]
    got = reference.replay(cfg, {s.key: s for s in pods}, bindings)
    assert got["infeasible_at_commit"] == 0 and got["max_zone_skew"] <= 1
    stale = reference.ReferenceScheduler(cfg, carry=False).schedule(pods)
    got = reference.replay(
        cfg, {s.key: s for s in pods}, [(s.key, n) for s, n in stale if n]
    )
    assert got["infeasible_at_commit"] > 0
