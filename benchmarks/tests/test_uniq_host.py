"""The one-replica-a-host cell (uniq-host-5k.rollouts): the configuration
against its sibling, the stream it generates, the control of its
``correct``, and the five inter-pod readers, including the family's
share of its roofline (metrics/scope_roofline.py)."""

import json

import pytest

from benchmarks import control
from benchmarks.lib import files, gen, solve_work
from test_span_attrib import ctx_for

CELL = "uniq-host-5k.rollouts"
CONFIG = "sched-perf-uniq-host-rollouts-5000n"
HOST = "kubernetes.io/hostname"
LABEL = "pod-template-hash"
NEW = (
    "interpod_tensorize_s_per_kpod.backlog", "interpod_scan_us_per_pod.backlog",
    "interpod_existing_terms_per_solve.backlog", "interpod_placed_visits_per_solve.backlog",
    "interpod_roofline.backlog",
)


# -- the configuration and its stream ----------------------------------------


def test_the_configuration_is_the_spread_rollouts_one_with_the_anti_kind():
    mine, sib = files.load_config(CONFIG), files.load_config("sched-perf-spread-rollouts-5000n")
    differ = {k for k in mine if mine[k] != sib.get(k)}
    assert differ == {
        "name", "source", "why", "what", "nodes", "stream", "guarantees", "assumed",
    }
    assert mine["stream"]["kinds"] == {"anti": {
        "share": 1.0, "labelKey": LABEL, "apps": 900, "topologyKey": HOST,
    }}
    mine["stream"].pop("kinds"), sib["stream"].pop("kinds")
    assert mine["stream"] == sib["stream"] and mine["reduced"] == []
    assert mine["nodes"]["labels"].pop(HOST) == "{name}"
    assert mine["nodes"] == sib["nodes"]
    assert 900 * mine["stream"]["deploymentReplicas"] == mine["validWhile"]["maxPodsOffered"]
    assert "no node holds two pods of one pod-template-hash value" in mine["guarantees"]
    assert not any("skew" in g for g in mine["guarantees"])
    cell = files.load_workload(CELL)
    assert cell["chips"] == 1 and cell["warmup"] == {"until_bound": 28000}
    assert cell["loop"] == {"kind": "backlog", "depth": 16384, "chunk": 1000}


def test_nodes_are_labelled_by_their_name():
    nodes = gen.make_nodes(files.load_config(CONFIG))
    assert len(nodes) == 5000
    for n in nodes[:5] + nodes[-5:]:
        assert n["metadata"]["labels"][HOST] == n["metadata"]["name"]
    assert len({n["metadata"]["labels"][HOST] for n in nodes}) == 5000


def test_every_pod_is_anti_affine_on_hostname_under_its_own_revision():
    cfg = files.load_config(CONFIG)
    # 178,000 pods reach nearly every value; the first waves start part-way
    # (staggered slots), so the first value comes again only after ~178,500
    # (a run offers under 70,000: set-up 33,000, the queue, the window)
    specs = gen.RolloutStream(cfg, seed=2**31 + 38).take(178_000)
    assert {(s.kind, s.label_key) for s in specs} == {("anti", LABEL)}
    waves: dict = {}
    for s in specs:
        waves.setdefault(s.app, set()).add(s.name.rsplit("-", 2)[1])
    # one wave a value: no value comes twice, so no rollout's replicas
    # meet another's under one selector
    assert len(waves) >= 895 and all(len(w) == 1 for w in waves.values())
    assert {s.app for s in specs} <= {f"anti-{i}" for i in range(900)}
    for s in specs[:50]:
        m = gen.pod_manifest(cfg, s)
        assert m["metadata"]["labels"] == {LABEL: s.app}
        assert set(m["spec"]) == {"containers", "affinity"}
        assert m["spec"]["affinity"] == {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": HOST, "labelSelector": {"matchLabels": {LABEL: s.app}},
            }],
        }}


# -- the control --------------------------------------------------------------


def run_control(capsys, fault):
    code = control.main(
        ["--workload", CELL, "--seed", "3800000017", "--seconds", "2",
         "--fault", fault, "--rehearse-size"]
    )
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault, correct", [("stale_state", False), ("none", True)])
def test_control_of_the_cell(capsys, fault, correct):
    line = run_control(capsys, fault)
    clashes = line["compared"]["anti_affinity_clashes"]
    assert line["correct"] is correct and clashes["limit"] == "<= 0"
    if correct:
        assert line["failed"] == 0 and clashes["value"] == 0 and line["attempted"] > 0
    else:
        # a batch solved against the occupancy it started with puts a
        # rollout's replicas on one node
        assert clashes["value"] > 0 and line["failed"] > 0


# -- the readers ----------------------------------------------------------------

SLOW = "jit(_run_packed)/while/body/grouped_slow/while/body/"
# (HLO line, op_name or None, start ns, duration ns)
OPS = [
    ("%while.7 = (s32[]) while(%t), body=%b", None, 0, 1000),
    ("%fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1",
     SLOW + "InterPodAffinity/reduce", 100, 400),
    ("%fusion.2 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.2", SLOW + "select/reduce", 600, 100),
]
TERMS = "scheduler_tpu_interpod_terms_total"
VISITS = "scheduler_tpu_interpod_placed_visits_total"


def read_new(ctx):
    ms = files.load_metrics()
    return {n: files.load_reader(ms[n])(ctx, **ms[n].get("args", {})) for n in NEW}


def with_counters(ctx, counters, bound=2000):
    ctx.update(
        m1=counters, bound_in_window=bound,
        delta=lambda name, **labels: sum(
            v for (n, ls), v in counters.items()
            if n == name and set(labels.items()) <= set(ls)
        ),
    )
    return ctx


def test_the_five_readers_on_a_run_of_the_change(tmp_path):
    ctx = with_counters(ctx_for(tmp_path, ops=OPS), {
        ("scheduler_tpu_solves_total", (("path", "grouped"),)): 8.0,
        (TERMS, (("side", "incoming"),)): 104.0,
        (TERMS, (("side", "existing"),)): 1600.0,
        (VISITS, ()): 8.0 * 14 * 40_000,
        ("scheduler_plugin_execution_duration_seconds_sum",
         (("extension_point", "PreFilter"), ("plugin", "InterPodAffinity"))): 3.0,
        ("scheduler_plugin_execution_duration_seconds_sum",
         (("extension_point", "PreFilter"), ("plugin", "NodeResourcesFit"))): 1.0,
    })
    ctx.update(config=files.load_config(CONFIG),
               peaks=solve_work.load_peaks("TPU v5 lite"))
    ctx["traced"]["solves"] = 2.0
    got = read_new(ctx)
    # 2 solves x 5,000 nodes + 50 pods, 4 B each, at 819 GB/s, over the
    # 400 ns under the scope
    least = 4 * (2 * 5000 + 50) / 819e9
    assert got == {
        "interpod_tensorize_s_per_kpod.backlog": pytest.approx(1.5),
        "interpod_scan_us_per_pod.backlog": pytest.approx(400e-9 / 50 * 1e6),
        "interpod_existing_terms_per_solve.backlog": pytest.approx(200.0),
        "interpod_placed_visits_per_solve.backlog": pytest.approx(14 * 40_000),
        "interpod_roofline.backlog": pytest.approx(100 * least / 400e-9),
    }
    assert 0 < got["interpod_roofline.backlog"] <= 100


def test_no_interpod_time_and_the_parent_read_none(tmp_path):
    spread_only = [(h, op and op.replace("InterPodAffinity", "PodTopologySpread"), s, d)
                   for h, op, s, d in OPS]
    ctx = with_counters(ctx_for(tmp_path, ops=spread_only), {
        ("scheduler_tpu_solves_total", (("path", "grouped"),)): 8.0,
    })
    ctx.update(config=files.load_config(CONFIG),
               peaks=solve_work.load_peaks("TPU v5 lite"))
    ctx["traced"]["solves"] = 2.0
    got = read_new(ctx)
    # no InterPodAffinity time: no share; the scope's own time reads 0.0
    assert got["interpod_roofline.backlog"] is None
    assert got["interpod_scan_us_per_pod.backlog"] == 0.0
    # the parent: no counter of the terms nor of the walk, no histogram child
    assert got["interpod_existing_terms_per_solve.backlog"] is None
    assert got["interpod_placed_visits_per_solve.backlog"] is None
    assert got["interpod_tensorize_s_per_kpod.backlog"] is None
    # an untraced run: no look at the capture at all
    ctx["trace"], ctx["traced"] = None, None
    got = read_new(ctx)
    assert got["interpod_roofline.backlog"] is None
    assert got["interpod_scan_us_per_pod.backlog"] is None


def test_the_new_metrics_are_owed_by_this_cell_alone():
    ms = files.load_metrics()
    for n in NEW:
        assert ms[n]["moves"] == "pods_bound_per_s" and ms[n]["workloads"] == [CELL]
    assert ms["interpod_roofline.backlog"]["unit"] == "%"
    for cell in files.names("workloads"):
        mine = set(files.metrics_of_cell(files.load_workload(cell), "per_layer"))
        assert (set(NEW) <= mine) if cell == CELL else not set(NEW) & mine
