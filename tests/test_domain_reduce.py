"""ops/domains.py against jax.ops.segment_sum / segment_max: the dense form
and the scatter are equal element for element, dtype and shape included,
on both sides of the limit, and the rule that picks the form reads the
static d_pad alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import ops as jops

from kubernetes_tpu.ops import domains

LIMIT = domains.DENSE_MAX_SLOTS
D_PADS = sorted({8, 16, 64, LIMIT, 2 * LIMIT})


def _dd(layout, n, d_pad, rng):
    """Domain id per node. ``gaps``: three of the slots used (the cells'
    three zones), the rest empty; ``one``: every lane in the last slot;
    ``all``: every slot used."""
    if layout == "gaps":
        return rng.choice(np.array([0, 2, d_pad - 1]), n).astype(np.int32)
    if layout == "one":
        return np.full(n, d_pad - 1, dtype=np.int32)
    return (rng.permutation(n) % d_pad).astype(np.int32)


def _values(kind, n, rng):
    """The three rows the programs reduce: int32 match counts with masked
    lanes at 0, the unique int64 random keys of winner_accept with
    ineligible lanes at -1, and a bool mask counted as int32."""
    masked = rng.random(n) < 0.3
    if kind == "sum_i32":
        return np.where(masked, 0, rng.integers(0, 1000, n)).astype(np.int32)
    if kind == "max_i64":
        keys = rng.integers(0, 1 << 20, n).astype(np.int64) * n + np.arange(n)
        return np.where(masked, -1, keys).astype(np.int64)
    return (~masked).astype(np.int32)  # sum_bool


@pytest.mark.parametrize("layout", ["gaps", "one", "all"])
@pytest.mark.parametrize("n", [128, 8192])
@pytest.mark.parametrize("kind", ["sum_i32", "max_i64", "sum_bool"])
@pytest.mark.parametrize("d_pad", D_PADS)
def test_equals_the_segment_reduction(d_pad, kind, n, layout):
    rng = np.random.default_rng(d_pad * 7919 + n)
    dd = jnp.asarray(_dd(layout, n, d_pad, rng))
    values = jnp.asarray(_values(kind, n, rng))
    if kind == "max_i64":
        got = jax.jit(lambda v, d: domains.domain_max(v, d, d_pad))(values, dd)
        want = jops.segment_max(values, dd, num_segments=d_pad)
        if layout != "all":  # an empty slot holds what the scatter leaves
            assert int(got[1]) == np.iinfo(np.int64).min
    else:
        got = jax.jit(lambda v, d: domains.domain_sum(v, d, d_pad))(values, dd)
        want = jops.segment_sum(values, dd, num_segments=d_pad)
    assert got.dtype == want.dtype == values.dtype
    assert got.shape == want.shape == (d_pad,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_all_masked_row_reduces_to_the_identities():
    dd = jnp.zeros(128, dtype=jnp.int32)
    zeros = jnp.zeros(128, dtype=jnp.int32)
    minus = jnp.full(128, -1, dtype=jnp.int64)
    np.testing.assert_array_equal(
        domains.domain_sum(zeros, dd, 8), np.zeros(8, dtype=np.int32)
    )
    want = np.full(8, np.iinfo(np.int64).min)
    want[0] = -1
    np.testing.assert_array_equal(domains.domain_max(minus, dd, 8), want)


def _hlo(fn, dtype, d_pad):
    v = jax.ShapeDtypeStruct((8192,), dtype)
    d = jax.ShapeDtypeStruct((8192,), jnp.int32)
    return jax.jit(lambda a, b: fn(a, b, d_pad)).lower(v, d).as_text()


@pytest.mark.parametrize(
    "fn,dtype", [(domains.domain_sum, jnp.int32), (domains.domain_max, jnp.int64)]
)
def test_the_rule_is_the_static_d_pad(fn, dtype):
    """Over the limit the lowered program still holds a scatter, at the
    cells' 8 slots and at the limit itself it holds none: the rule cannot
    flip in silence."""
    assert domains.dense_form(8) and domains.dense_form(LIMIT)
    assert not domains.dense_form(LIMIT + 1)
    assert "scatter" not in _hlo(fn, dtype, 8)
    assert "scatter" not in _hlo(fn, dtype, LIMIT)
    assert "scatter" in _hlo(fn, dtype, 2 * LIMIT)


def test_the_limit_is_read_at_trace_time(monkeypatch):
    """The end-to-end parity tests patch the constant to 0 to get the
    scatter at every d_pad; that only works while the rule reads it when a
    program is traced."""
    monkeypatch.setattr(domains, "DENSE_MAX_SLOTS", 0)
    assert not domains.dense_form(8)
    assert "scatter" in _hlo(domains.domain_sum, jnp.int32, 8)


@pytest.mark.parametrize("group", [16, LIMIT, 2 * LIMIT])
def test_a_write_of_distinct_positions_is_a_max_per_slot(group):
    """_solve_grouped's placement write: ``asg.at[idx].set(lane,
    mode="drop")`` with distinct positions, lanes at ``group`` dropped,
    as ``domain_max`` over the positions: the slot's one lane, or the
    minimum where no lane lands and the old value stays."""
    n = 1024
    rng = np.random.default_rng(group)
    idx = np.full(n, group, dtype=np.int32)
    lanes = rng.choice(n, group // 2, replace=False)
    idx[lanes] = rng.permutation(group)[: group // 2]  # half the slots
    idx, lane = jnp.asarray(idx), jnp.arange(n, dtype=jnp.int32)
    asg = jnp.full(group, -1, dtype=jnp.int32)
    want = asg.at[idx].set(lane, mode="drop")
    got = domains.domain_max(lane, idx, group)
    got = jnp.where(got >= 0, got, asg)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
