"""Pallas kernel parity vs the jax.lax reference (interpret mode on CPU;
the compiled TPU path is exercised by chip_smoke.py phase B and
scripts/pallas_smoke.py), its TPU lowering under x64, plus the
PRODUCTION wiring behind ``tpuSolver.pallas`` (ISSUE 13 satellite): the
per-pod scan's InterPodAffinity domain aggregation routed through the
kernel must produce bit-identical assignments to the segment_sum path,
end to end through ``ExactSolver.solve``."""

import numpy as np
import pytest

from kubernetes_tpu.ops.pallas_kernels import (
    N_TILE,
    T_TILE,
    domain_counts_padded,
    domain_counts_pallas,
    domain_counts_reference,
)


@pytest.mark.parametrize("t,n_tiles,d_pad", [(8, 1, 8), (8, 2, 16), (16, 4, 32)])
def test_domain_counts_parity(t, n_tiles, d_pad):
    rng = np.random.default_rng(42 + t)
    n = n_tiles * N_TILE
    dom = rng.integers(-1, d_pad, size=(t, n)).astype(np.int32)
    cnt = rng.integers(0, 5, size=(t, n)).astype(np.int32)
    got = np.asarray(domain_counts_pallas(dom, cnt, d_pad, interpret=True))
    want = np.asarray(domain_counts_reference(dom, cnt, d_pad))
    np.testing.assert_array_equal(got, want)


def test_lowers_for_tpu_with_x64_on():
    """The solver runs with jax_enable_x64 process-wide, and Mosaic has
    no 64-bit integers: a bare Python literal in the kernel body or in a
    BlockSpec index map becomes an i64 and the kernel stops lowering
    (body: RecursionError in the 64->32 convert rule) or compiling
    (index map: "failed to legalize operation 'func.return'" on the
    chip). Cross-lowering needs no chip, so neither can come back."""
    import jax

    from kubernetes_tpu.ops import pallas_kernels as pk

    assert jax.config.jax_enable_x64  # conftest: the solver's regime
    spec = jax.ShapeDtypeStruct((16, 4 * N_TILE), np.int32)
    lowered = domain_counts_pallas.trace(spec, spec, d_pad=8).lower(
        lowering_platforms=("tpu",)
    )
    assert "tpu_custom_call" in lowered.as_text()
    # the part of the program Mosaic compiles later, on the chip: every
    # block index must already be 32-bit
    i32 = jax.ShapeDtypeStruct((), np.int32)
    for index_map in (pk._in_block, pk._out_block):
        for out in jax.eval_shape(index_map, i32, i32):
            assert out.dtype == np.int32, (index_map.__name__, out.dtype)


def test_domain_counts_excludes_missing_key():
    dom = np.full((8, N_TILE), -1, dtype=np.int32)
    cnt = np.ones((8, N_TILE), dtype=np.int32)
    out = np.asarray(domain_counts_pallas(dom, cnt, 8, interpret=True))
    assert out.sum() == 0


@pytest.mark.parametrize(
    "t,n", [(5, 200), (T_TILE, N_TILE), (9, N_TILE + 1), (1, 130)]
)
def test_padded_adapter_parity_on_untiled_shapes(t, n):
    """The production adapter pads arbitrary (term, node) shapes to the
    kernel tiles (pad lanes carry dom=-1) and slices back — parity with
    the reference on the UNpadded inputs."""
    rng = np.random.default_rng(100 + t + n)
    dom = rng.integers(-1, 6, size=(t, n)).astype(np.int32)
    cnt = rng.integers(0, 5, size=(t, n)).astype(np.int32)
    got = np.asarray(domain_counts_padded(dom, cnt, 8))
    want = np.asarray(domain_counts_reference(dom, cnt, 8))
    np.testing.assert_array_equal(got, want)


def _interpod_cluster():
    """A zone-topology interpod mix whose domains are SHARED across
    nodes (ident=False), so the wired aggregation actually runs inside
    the scan."""
    from kubernetes_tpu.api.wrappers import MakeNode, MakePod

    nodes = [
        MakeNode()
        .name(f"node-{i:03}")
        .capacity({"cpu": "8", "memory": "32Gi", "pods": "50"})
        .label("zone", f"z{i % 2}")
        .label("kubernetes.io/hostname", f"node-{i:03}")
        .obj()
        for i in range(8)
    ]
    be = (
        MakePod().name("be").label("app", "backend").node("node-000").obj()
    )
    rng = np.random.default_rng(7)
    pods = []
    for i in range(16):
        b = MakePod().name(f"m{i:02}").req({"cpu": "200m"})
        r = rng.random()
        if r < 0.35:
            b = b.label("app", "frontend").pod_affinity(
                "zone", match_labels={"app": "backend"}
            )
        elif r < 0.6:
            b = b.label("team", "red").pod_anti_affinity(
                "zone", match_labels={"team": "red"}
            )
        elif r < 0.8:
            b = b.label("app", "web").preferred_pod_affinity(
                int(rng.integers(1, 100)), "zone",
                match_labels={"app": "backend"},
            )
        else:
            b = b.label("app", "plain")
        pods.append(b.obj())
    return nodes, pods, {"node-000": [be]}


def _solve(nodes, pods, placed_by_node, pallas: bool):
    from kubernetes_tpu.solver.exact import ExactSolver, ExactSolverConfig
    from kubernetes_tpu.tensorize.interpod import build_interpod_tensors
    from kubernetes_tpu.tensorize.plugins import (
        build_port_tensors,
        build_static_tensors,
    )
    from kubernetes_tpu.tensorize.schema import (
        ResourceVocab,
        build_node_batch,
        build_pod_batch,
    )
    from kubernetes_tpu.tensorize.spread import build_spread_tensors

    all_pods = pods + [p for ps in placed_by_node.values() for p in ps]
    vocab = ResourceVocab.build(all_pods, nodes)
    nbatch = build_node_batch(nodes, placed_by_node, vocab=vocab)
    pbatch = build_pod_batch(pods, vocab)
    slot_nodes = list(nodes) + [None] * (nbatch.padded - len(nodes))
    placed_by_slot = {
        i: placed_by_node[n.name]
        for i, n in enumerate(nodes)
        if n.name in placed_by_node
    }
    static = build_static_tensors(pods, pbatch, slot_nodes, nbatch.padded)
    ports = build_port_tensors(
        pods, pbatch, slot_nodes, placed_by_slot, nbatch.padded
    )
    spread = build_spread_tensors(
        pods, static.reps, pbatch, slot_nodes, placed_by_slot,
        nbatch.padded, static.c_pad,
    )
    interpod = build_interpod_tensors(
        pods, static.reps, pbatch, slot_nodes, placed_by_slot,
        nbatch.padded, static.c_pad,
    )
    solver = ExactSolver(
        ExactSolverConfig(tie_break="first", pallas=pallas)
    )
    return solver.solve(
        nbatch, pbatch, static, ports, spread, interpod
    )


def test_production_solve_parity_flag_on_vs_off():
    """tpuSolver.pallas wired into the production scan: the exact same
    interpod batch solved with the kernel aggregation and with the
    segment_sum must pick bit-identical nodes (integer adds either way;
    the f32 MXU contraction is exact far below 2^24 counts)."""
    nodes, pods, placed = _interpod_cluster()
    base = np.asarray(_solve(nodes, pods, placed, pallas=False))
    wired = np.asarray(_solve(nodes, pods, placed, pallas=True))
    np.testing.assert_array_equal(base, wired)
    # non-vacuous: at least one interpod-constrained pod actually placed
    assert (base >= 0).sum() >= len(pods) - 2
