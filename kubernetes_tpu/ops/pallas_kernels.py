"""Pallas TPU kernels — the native-code tier of this framework.

SURVEY.md §3.4: the reference implements its hot loops in pure Go; the
"native equivalent" obligation here maps to Pallas TPU kernels with jax.lax
reference implementations for parity (the parity tests ARE the sanitizer).
First kernel: the (term, domain) count aggregation that InterPodAffinity
runs every scan step (ops/interpod.py#domain_counts lowers it through
jax.ops.segment_sum; PodTopologySpread's, ops/spread.py#_domain_aggregate,
goes through ops/domains.py).

domain_counts_pallas computes, for T term rows at once,

    out[t, d] = sum_n  cnt[t, n] * (dom[t, n] == d)

by materializing the one-hot domain matrix PER TILE in VMEM and contracting
it on the MXU: each (t, n-tile) grid step does a [1, NT] x [NT, D] matmul
accumulated into the [T, D] output block — the blockwise-attention trick
applied to scatter-free segment reduction (guide §4, §7). Grid iterates the
n-tile axis innermost so the output block stays resident and accumulates
(@pl.when zero-init on the first tile).

Works in interpret mode on CPU (tests) and compiled by Mosaic on a TPU.

**Production wiring (ISSUE 13):** the kernel is wired behind
``tpuSolver.pallas`` (default OFF). ``ops/interpod.domain_counts``
routes its [T, D] aggregation through ``domain_counts_padded`` below
when ``ExactSolverConfig.pallas`` is set, inside the production per-pod
scan, with parity pinned end to end by tests/test_pallas_kernels.py
(production ExactSolver.solve, flag on vs off, bit-identical
assignments); chip_smoke.py compiles it with Mosaic on the chip.

**x64.** The solver REQUIRES ``jax_enable_x64`` process-wide (int64
resource arithmetic; memory bytes overflow int32), and under x64 every
bare Python ``0`` in a kernel or an index map is a 64-bit value. Mosaic
has no 64-bit integers, so two spots are pinned to int32 and must stay
so:

- the ``jnp.where(dom >= 0, cnt, 0)`` literal in the kernel body: as
  an ``i64[]`` operand it sent the 64->32 convert lowering into
  unbounded recursion (a RecursionError at lowering, no chip needed);
- the ``(i, 0)`` block index of the output BlockSpec: as an ``i64``
  result Mosaic refuses the index map at compile time ("failed to
  legalize operation 'func.return'", on the chip only).

With both pinned the kernel compiles on a TPU v5e with x64 on and
matches ``domain_counts_reference`` at zone- and hostname-scale domain
paddings (``chip_smoke.py`` phase B checks the compiled kernel on every
run; tests/test_pallas_kernels.py cross-lowers it for TPU under x64 so
neither literal can regress).

**Why the default is still off.** No benchmark cell has measured the
scan step with the flag on, on the chip. The workload that made this
aggregation expensive — hostname-topology terms, where d_pad ~ N — is
served by ``ops/interpod.domain_counts``' IDENTITY mode (unique-domain
rows need no aggregation at all), so what remains for the kernel is the
small-d_pad zone-topology case. ROADMAP Speed item 3 asked whether the
one-hot form wins there, and PR 30 answered it for the spread path
without this kernel: ``ops/domains.py`` reduces the per-domain sums and
winners of ``ops/spread.py`` and ``_solve_grouped`` as masked reductions
over the node axis (2 us against the scatter's 74 us at 8 slots on a
v5e; PERF.md section 5), plain XLA that fuses with its producers. This
kernel still serves only the inter-pod scan's ``[T, D]`` aggregation,
which no cell runs; until one does the flag stays a flag, and
``ops/domains.py`` is where ``domain_counts`` would go first.

On non-TPU backends ``domain_counts_padded`` selects interpret mode at
trace time, which is how the tier-1 parity tests exercise the wired
path under the x64-everywhere test config; on a TPU it compiles or
raises — there is no interpreted fallback there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_TILE = 512  # lanes per grid step (multiple of 128)
T_TILE = 8  # term rows per grid step (sublane quantum for int32-as-f32)


def _domain_counts_kernel(dom_ref, cnt_ref, out_ref, *, d_pad: int):
    j = pl.program_id(1)  # n-tile index (innermost)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dom = dom_ref[...]  # [T_TILE, NT] int32
    cnt = cnt_ref[...]  # [T_TILE, NT] int32
    masked = jnp.where(dom >= 0, cnt, jnp.int32(0)).astype(jnp.float32)
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (N_TILE, d_pad), 1)
    rows = []
    for s in range(T_TILE):  # static unroll: each row has its own one-hot
        onehot = (dom[s].reshape(N_TILE, 1) == iota_d).astype(jnp.float32)
        rows.append(
            jax.lax.dot_general(
                masked[s].reshape(1, N_TILE),
                onehot,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [1, D]
        )
    out_ref[...] += jnp.concatenate(rows, axis=0).astype(jnp.int32)


def _in_block(i, j):
    return i, j


def _out_block(i, j):
    # the output block stays on column 0 while j walks the n-tiles. The
    # index is an int32 VALUE, not a bare 0: under x64 a Python literal
    # is an i64 result, which Mosaic refuses at compile time ("failed
    # to legalize operation 'func.return'")
    return i, jnp.int32(0)


@functools.partial(jax.jit, static_argnames=("d_pad", "interpret"))
def domain_counts_pallas(dom, cnt, d_pad: int, interpret: bool = False):
    """[T, D] domain totals from per-node counts.

    dom: [T, N] int32 domain ids (-1 = node lacks the key, excluded);
    cnt: [T, N] int32. T must be a multiple of T_TILE and N of N_TILE (the
    tensorizers pad instance axes to 8s and the node axis to 128s; callers
    pad up to these tiles).
    """
    t, n = dom.shape
    assert n % N_TILE == 0, f"node axis {n} not a multiple of {N_TILE}"
    assert t % T_TILE == 0, f"term axis {t} not a multiple of {T_TILE}"
    grid = (t // T_TILE, n // N_TILE)
    return pl.pallas_call(
        functools.partial(_domain_counts_kernel, d_pad=d_pad),
        grid=grid,
        in_specs=[
            pl.BlockSpec((T_TILE, N_TILE), _in_block),
            pl.BlockSpec((T_TILE, N_TILE), _in_block),
        ],
        out_specs=pl.BlockSpec((T_TILE, d_pad), _out_block),
        out_shape=jax.ShapeDtypeStruct((t, d_pad), jnp.int32),
        interpret=interpret,
    )(dom, cnt)


def domain_counts_padded(dom, cnt, d_pad: int):
    """Production adapter for the per-pod scan (``tpuSolver.pallas``):
    pad the term axis to T_TILE and the node axis to N_TILE (pad lanes
    carry dom = -1, which the kernel masks out), run the MXU kernel,
    slice the pad rows back off. Returns the [T, D] domain totals the
    dispatcher gathers per node.

    Interpret mode is selected AT TRACE TIME on non-TPU backends (the
    tier-1 suite runs the wired path this way under x64); a TPU backend
    lowers the compiled kernel. Called from exact.py's jit scope —
    padding is trace-time reshaping, not a host sync: ktpu: hot"""
    import jax as _jax

    t, n = dom.shape
    tp = -t % T_TILE
    np_ = -n % N_TILE
    if tp or np_:
        dom = jnp.pad(dom, ((0, tp), (0, np_)), constant_values=-1)
        cnt = jnp.pad(cnt, ((0, tp), (0, np_)))
    interpret = _jax.default_backend() != "tpu"
    out = domain_counts_pallas(
        dom.astype(jnp.int32), cnt.astype(jnp.int32), d_pad,
        interpret=interpret,
    )
    return out[:t]


def domain_counts_reference(dom, cnt, d_pad: int):
    """jax.lax reference implementation (parity anchor): the segment_sum
    formulation the solver currently uses."""
    t = dom.shape[0]
    hk = dom >= 0
    dd = jnp.where(hk, dom, 0)
    seg_ids = (dd + jnp.arange(t, dtype=jnp.int32)[:, None] * d_pad).reshape(-1)
    return jax.ops.segment_sum(
        jnp.where(hk, cnt, 0).reshape(-1), seg_ids, num_segments=t * d_pad
    ).reshape(t, d_pad)
