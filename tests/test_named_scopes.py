"""jax.named_scope names on the served path's device programs
(solver/exact.py SCOPES): every name a path uses is in the compiled
HLO's op_name metadata, where a device trace reads it, and the scopes
change nothing but metadata."""

import contextlib
import re

import jax
import pytest

from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.solver import exact
from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
from kubernetes_tpu.tensorize.interpod import build_interpod_tensors
from kubernetes_tpu.tensorize.plugins import build_port_tensors, build_static_tensors
from kubernetes_tpu.tensorize.schema import (
    ResourceVocab,
    build_node_batch,
    build_pod_batch,
)
from kubernetes_tpu.tensorize.spread import build_spread_tensors

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
GROUP = 8


def mk_pods(n, kind):
    out = []
    for i in range(n):
        b = MakePod().name(f"{kind}-{i:03}").label("app", kind).req(
            {"cpu": "250m", "memory": "512Mi"}
        )
        if kind == "spread":
            b = b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": kind})
        elif kind == "anti":
            b = b.pod_anti_affinity(HOST, {"app": kind})
        out.append(b.obj())
    return out


def packed_call(pods, group):
    """The (args, kwargs) ExactSolver.solve hands the jitted _run_packed
    for a small cluster and these pods."""
    nodes = [
        MakeNode().name(f"n-{i:03}")
        .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"})
        .label(ZONE, f"z{i % 3}").label(HOST, f"n-{i:03}").obj()
        for i in range(12)
    ]
    vocab = ResourceVocab.build(pods, nodes)
    nbatch = build_node_batch(nodes, vocab=vocab)
    pad = -(-len(pods) // GROUP) * GROUP
    pbatch = build_pod_batch(pods, vocab, pad=pad)
    slots = list(nodes) + [None] * (nbatch.padded - len(nodes))
    static = build_static_tensors(pods, pbatch, slots, nbatch.padded)
    ports = build_port_tensors(pods, pbatch, slots, {}, nbatch.padded)
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slots, {}, nbatch.padded, static.c_pad
    )
    interpod = build_interpod_tensors(
        pods, static.reps, pbatch, slots, {}, nbatch.padded, static.c_pad
    )
    seen = []
    real = exact._run_packed_jit_nodonate

    def recording(*args, **kwargs):
        seen.append((args, kwargs))
        return real(*args, **kwargs)

    exact._run_packed_jit_nodonate = recording
    try:
        ExactSolver(ExactSolverConfig(tie_break="first", group_size=group)).solve(
            nbatch, pbatch, static, ports, spread, interpod
        )
    finally:
        exact._run_packed_jit_nodonate = real
    assert len(seen) == 1
    return seen[0]


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """The persistent compile cache's key leaves metadata out
    (cache_key: strip-debuginfo), so with it on a compile hands back
    whatever names the executable was first built with: the stale-name
    hazard PERF.md describes. These tests read names, so they compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def optimized_hlo(call) -> str:
    args, kwargs = call
    jax.clear_caches()  # a trace made under other scopes must not be reused
    fn = jax.jit(exact._run_packed, static_argnames=exact._RUN_PACKED_STATICS)
    return fn.lower(*args, **kwargs).compile().as_text()


def scopes_in(hlo: str) -> set:
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(part for part in op_name.split("/") if part in exact.SCOPES)
    return found


@pytest.fixture(scope="module")
def spread_scan_call():
    # every pod hard zone-spread, grouping off: the spread cell's path
    return packed_call(mk_pods(12, "spread"), group=0)


def test_per_pod_scan_carries_its_scopes(spread_scan_call):
    assert scopes_in(optimized_hlo(spread_scan_call)) == {
        "NodeResourcesFit", "NodePorts", "PodTopologySpread", "Score",
        "select", "assume", "unpack", "pack",
    }


@pytest.fixture(scope="module")
def grouped_call():
    # uniform chunks take the fast branches (the served cells' path: plain
    # in the basic cell, spread in the spread cell), the mixed chunk the
    # slow one
    pods = (
        mk_pods(GROUP, "plain") + mk_pods(GROUP, "spread") + mk_pods(GROUP, "anti")
        + mk_pods(GROUP // 2, "plain") + mk_pods(GROUP // 2, "spread")
    )
    return packed_call(pods, group=GROUP)


def test_grouped_solve_carries_its_scopes(grouped_call):
    assert scopes_in(optimized_hlo(grouped_call)) == set(exact.SCOPES)


@pytest.mark.parametrize("path", ["spread_scan_call", "grouped_call"])
def test_scopes_change_metadata_only(path, request, monkeypatch):
    """The optimised HLO with scopes is the HLO without them, debug
    information aside: op metadata, the stack-frame table, and the
    NUMBERS in two instruction labels (the scope named ``select`` shares
    XLA's name-uniquifier prefix with the ``select`` opcode, so
    ``%select.21`` becomes ``%select.27``; with that scope under any
    other name the two texts are byte-identical). Labels are therefore
    renumbered by first appearance before the comparison."""

    def canonical(hlo):
        hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
        hlo = "\n".join(
            row for row in hlo.splitlines() if "file_name_id=" not in row
        )
        seen = {}
        return re.sub(
            r"%[\w.\-]+",
            lambda m: seen.setdefault(
                m.group(0), f"%{m.group(0)[1:].split('.')[0]}#{len(seen)}"
            ),
            hlo,
        )

    call = request.getfixturevalue(path)
    with_scopes = optimized_hlo(call)
    monkeypatch.setattr(jax, "named_scope", contextlib.contextmanager(lambda name: (yield)))
    without = optimized_hlo(call)
    assert scopes_in(with_scopes) and not scopes_in(without)
    assert canonical(with_scopes).encode() == canonical(without).encode()


def test_the_benchmark_reads_the_same_names():
    from benchmarks.lib import span_attrib

    assert span_attrib.SCOPES == exact.SCOPES
    assert span_attrib.scope_of(
        "jit(_run_packed)/while/body/grouped_slow/while/body/closed_call/Score/mul"
    ) == "Score"
    assert span_attrib.scope_of("jit(_run_packed)/while/body/dynamic_slice") is None
