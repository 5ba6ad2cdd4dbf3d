"""The many-selector cell (spread-5k.rollouts, PR 27): the reference on a
stream in which every rollout spreads under its own label, the control
of that cell's ``correct``, and the four readers that came with it.

The counters' readers are held to "None where the program has no such
counter (the parent), 0.0 where it has and nothing was counted (a cell
that takes no slow chunk)"; the path reader to a capture worked by hand.
"""

import json

import pytest

from benchmarks import control
from benchmarks.lib import files, gen, reference
from test_span_attrib import ctx_for

CELL = "spread-5k.rollouts"
LABEL = "pod-template-hash"
NEW = (
    "y_slow_chunk_pct.backlog", "y_slow_chunk_us_per_pod.backlog",
    "y_spread_instances_per_solve.backlog", "y_tensorize_spread_s_per_kpod.backlog",
)


def rollouts_cfg(nodes=6):
    cfg = files.load_config(files.load_workload(CELL)["config"])
    cfg["nodes"]["count"] = nodes  # zones 0 1 2 0 1 2
    return cfg


def pod(name, app):
    return gen.PodSpec(name, "spread", app, LABEL)


# -- the reference ------------------------------------------------------------


def test_the_configuration_is_its_sibling_with_the_selector_per_rollout():
    mine, sib = rollouts_cfg(5000), files.load_config("sched-perf-spread-5000n")
    spread = mine["stream"]["kinds"]["spread"]
    assert spread["labelKey"] == LABEL and spread["apps"] == 900
    assert spread["apps"] * mine["stream"]["deploymentReplicas"] == (
        mine["validWhile"]["maxPodsOffered"]
    )
    differ = {k for k in mine if mine[k] != sib.get(k)}
    assert differ == {"name", "source", "why", "what", "stream", "guarantees", "assumed"}
    for k in ("labelKey", "apps"):
        spread.pop(k), sib["stream"]["kinds"]["spread"].pop(k)
    assert mine["stream"] == sib["stream"] and mine["reduced"] == []


def test_the_stream_interleaves_eight_rollouts_and_turns_labels_over():
    specs = gen.RolloutStream(rollouts_cfg(), seed=2147483659).take(4096)
    assert {s.kind for s in specs} == {"spread"} and {s.label_key for s in specs} == {LABEL}
    labels = [len({s.app for s in specs[i:i + 1024]}) for i in range(0, 4096, 1024)]
    assert all(9 <= n <= 16 for n in labels), labels  # 8 in flight + turnovers
    assert max(
        sum(1 for s in specs if s.app == app) for app in {s.app for s in specs}
    ) <= 200
    # no 64 neighbours alike: what sends every chunk down the slow branch
    assert all(len({s.app for s in specs[i:i + 64]}) > 1 for i in range(0, 4096, 64))
    manifest = gen.pod_manifest(rollouts_cfg(), specs[0])
    (tsc,) = manifest["spec"]["topologySpreadConstraints"]
    assert tsc["labelSelector"] == {"matchLabels": {LABEL: specs[0].app}}
    assert manifest["metadata"]["labels"] == {LABEL: specs[0].app}


def test_skew_is_counted_under_the_pods_own_label():
    ref = reference.ClusterRef(rollouts_cfg())
    a, b = pod("a", "spread-1"), pod("b", "spread-2")
    for _ in range(5):
        assert ref.violations(a, 0) == []
        ref.bind(a, 0)  # spread-1: zone 0 holds 5, the others none
    assert ref.violations(a, 3) == ["zone_skew"]  # 6 - 0 > 5, under its own label
    assert ref.violations(b, 3) == []  # the same node under another label
    assert ref.feasible(a).tolist() == [False, True, True, False, True, True]
    assert ref.feasible(b).all()


def test_replay_catches_the_breach_under_one_label_among_many():
    cfg = rollouts_cfg()
    names = gen.node_names(cfg)
    specs, bindings = {}, []
    for app in range(12):  # twelve rollouts, each sound: 2 2 2 over the zones
        for i in range(6):
            s = pod(f"r{app}-{i}", f"spread-{app}")
            specs[s.key] = s
            bindings.append((s.key, names[i]))
    sound = reference.replay(cfg, specs, bindings)
    assert sound["infeasible_at_commit"] == 0 and sound["max_zone_skew"] == 0
    assert sound["bound"] == 72
    # five more of rollout 7 into zone 0 (7 2 2: skew 5, sound), rollout 3 onto
    # the same node (sound under ITS label), then a sixth of rollout 7
    extra = [pod(f"r7-x{i}", "spread-7") for i in range(6)]
    other = pod("r3-x", "spread-3")
    for s in extra + [other]:
        specs[s.key] = s
    bindings += [(s.key, names[0]) for s in extra[:5]] + [(other.key, names[0])]
    bindings += [(extra[5].key, names[3])]
    got = reference.replay(cfg, specs, bindings)
    # the sixth: 8 - 2 > 5 under spread-7, and only that one
    assert got["infeasible_at_commit"] == 1
    assert got["max_zone_skew"] == 6
    assert got["notes"] == [f"{extra[5].key} -> {names[3]}: zone_skew"]
    assert got["nodes_over_capacity"] == 0 and got["bound"] == 79


# -- the control --------------------------------------------------------------


def run_control(capsys, fault):
    code = control.main(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--fault", fault, "--rehearse-size"]
    )
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_control_of_the_cell_is_not_correct_and_the_reference_is(capsys):
    line = run_control(capsys, "stale_state")
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["infeasible_at_commit"]["value"] > 0
    # a batch solved against the counts it started with piles each label into one zone
    assert line["compared"]["max_zone_skew"]["value"] > 5
    assert line["compared"]["max_zone_skew"]["limit"] == "<= 5"
    line = run_control(capsys, "none")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["max_zone_skew"]["value"] <= 5


# -- the readers ----------------------------------------------------------------

SLOW = "jit(_run_packed)/while/body/grouped_slow/while/body/"
FAST = "jit(_run_packed)/while/body/grouped_fast/while/body/"
# (HLO line, op_name or None, start ns, duration ns): a slow chunk's scan
# with two plugin scopes inside, a fast chunk, and an unpack outside both
OPS = [
    ("%while.74 = (s32[]) while(%t), body=%b", None, 0, 500),
    ("%fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1",
     SLOW + "PodTopologySpread/scatter-add", 50, 200),
    ("%fusion.2 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.2", SLOW + "select/reduce", 300, 100),
    ("%fusion.3 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.3", SLOW + "dynamic_slice", 410, 40),
    ("%fusion.4 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.4", FAST + "select/scatter-max", 600, 100),
    ("%fusion.5 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.5", "jit(_run_packed)/unpack/slice", 700, 60),
]


def read_new(ctx):
    ms = files.load_metrics()
    return {n: files.load_reader(ms[n])(ctx, **ms[n].get("args", {})) for n in NEW}


def test_the_four_readers_on_a_run_of_the_change(tmp_path, capsys):
    ctx = ctx_for(tmp_path, ops=OPS)
    hist = "scheduler_plugin_execution_duration_seconds_sum"
    spread_pre = (("extension_point", "PreFilter"), ("plugin", "PodTopologySpread"))
    counters = {
        ("scheduler_tpu_solve_chunks_total", (("kind", "slow"),)): 90.0,
        ("scheduler_tpu_solve_chunks_total", (("kind", "spread"),)): 10.0,
        ("scheduler_tpu_solves_total", (("path", "grouped"),)): 8.0,
        ("scheduler_tpu_spread_instances_total", ()): 104.0,
        (hist, spread_pre): 3.0,
        (hist, (("extension_point", "Filter"), ("plugin", "NodeResourcesFit"))): 1.0,
    }

    def delta(name, **labels):
        want = set(labels.items())
        return sum(v for (n, ls), v in counters.items() if n == name and want <= set(ls))

    ctx.update(m1=counters, delta=delta, bound_in_window=2000)
    got = read_new(ctx)
    assert got == {
        "y_slow_chunk_pct.backlog": pytest.approx(90.0),
        # under grouped_slow: 200 + 100 + 40 ns of 50 pods; the while's own
        # 160 ns and the unpack carry neither outer scope
        "y_slow_chunk_us_per_pod.backlog": pytest.approx(340e-9 / 50 * 1e6),
        "y_spread_instances_per_solve.backlog": pytest.approx(13.0),
        "y_tensorize_spread_s_per_kpod.backlog": pytest.approx(1.5),
    }
    (line,) = [json.loads(r) for r in capsys.readouterr().out.splitlines()
               if '"grouped_paths"' in r]
    # slow + fast + neither = busy: a cut across the innermost-scope table
    assert line["seconds"] == {
        "grouped_fast": pytest.approx(100e-9), "grouped_slow": pytest.approx(340e-9),
        "neither": pytest.approx(220e-9),
    }
    assert sum(line["seconds"].values()) == pytest.approx(660e-9)


def test_a_cell_that_takes_no_slow_chunk_reads_zero_and_the_parent_none(tmp_path):
    fast_only = [op for op in OPS if op[1] is None or "grouped_slow" not in op[1]]
    ctx = ctx_for(tmp_path, ops=fast_only)
    counters = {
        ("scheduler_tpu_solve_chunks_total", (("kind", "slow"),)): 0.0,
        ("scheduler_tpu_solve_chunks_total", (("kind", "plain"),)): 16.0,
        ("scheduler_tpu_solves_total", (("path", "grouped"),)): 8.0,
        ("scheduler_tpu_spread_instances_total", ()): 0.0,
        ("scheduler_plugin_execution_duration_seconds_sum",
         (("extension_point", "Filter"), ("plugin", "NodeResourcesFit"))): 1.0,
    }
    ctx.update(
        m1=counters, bound_in_window=2000,
        delta=lambda name, **labels: sum(
            v for (n, ls), v in counters.items()
            if n == name and set(labels.items()) <= set(ls)
        ),
    )
    assert read_new(ctx) == dict.fromkeys(NEW, 0.0)
    # the parent: none of the counters, no histogram child, scopes as they were
    ctx.update(m1={("scheduler_tpu_solve_batch_size_count", ()): 8.0},
               delta=lambda name, **labels: 8.0 if "batch_size" in name else 0.0)
    got = read_new(ctx)
    assert got.pop("y_slow_chunk_us_per_pod.backlog") == 0.0  # the scopes are PR 25's
    assert set(got.values()) == {None}
    # an untraced run: no look at the capture at all
    ctx["trace"] = None
    assert read_new(ctx)["y_slow_chunk_us_per_pod.backlog"] is None


def test_new_metrics_are_owed_by_every_backlog_cell():
    ms = files.load_metrics()
    assert {ms[n]["reader"] for n in NEW} == {
        "counter_share_pct.py", "scope_path_us_per_pod.py", "counter_ratio.py",
        "labelled_s_per_kpod.py",
    }
    for n in NEW:
        assert ms[n]["moves"] == "pods_bound_per_s" and "workloads" not in ms[n]
    for cell in files.names("workloads"):
        mine = files.metrics_of_cell(files.load_workload(cell), "per_layer")
        assert set(NEW) <= set(mine)
