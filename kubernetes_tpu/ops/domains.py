"""Per-domain reductions over the node axis.

PodTopologySpread and the inter-pod quota branch keep their domain
bookkeeping as reductions of an ``[N]`` row into ``d_pad`` slots keyed by
a domain id per node (ops/spread.py module docstring). ``jax.ops.segment_*``
lowers that to a scatter, which a TPU runs one element at a time; with a
handful of slots the same answer is ``where(dd == d, v, identity)`` reduced
over the node axis once a slot, lane-resident and fused with its producers.

Which form a program gets is decided at trace time from the static
``d_pad`` alone (``dense_form``): integer sums and a max do not depend on
order, so the two forms are equal bit for bit and nothing downstream (keys,
quotas, tie sets, assignments) can tell them apart.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import ops as jops

# Largest slot count that takes the dense form. Read on a TPU v5e with the
# helper alone under jit at N = 8,192 (PERF.md section 5, PR 30): the
# scatter takes 74 us for an int32 sum and 492 us for an int64 max whatever
# d_pad; the dense form 2.0 / 2.3 us at 8 slots and 2.9 / 5.2 us at 256
# (25x and 94x), and it stays ahead to 2,048 slots (13 / 33 us) before the
# sum crosses between 4,096 and 8,192. The limit stays at 256 all the same:
# no domain axis between there and the node count is known (zones, regions
# and racks are under it, hostnames go with N), and a [d_pad, N]
# intermediate is only this cheap while XLA keeps it inside one fusion,
# which at 2,048 x 512k lanes would be 8 GB if it ever did not.
DENSE_MAX_SLOTS = 256


def dense_form(d_pad: int) -> bool:
    """True where ``d_pad`` slots are reduced densely, False for a scatter.
    The one rule: the helpers below and ExactSolver's counter both ask it."""
    return d_pad <= DENSE_MAX_SLOTS


def _slot_hit(dd, d_pad: int):
    """[d_pad, N] bool: node n belongs to slot d."""
    return dd[None, :] == jnp.arange(d_pad, dtype=dd.dtype)[:, None]


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def domain_sum(values, dd, d_pad: int):
    """``jax.ops.segment_sum(values, dd, num_segments=d_pad)`` for an [N]
    row: [d_pad], values' dtype (a bare ``sum`` would widen int32 under
    x64)."""
    if not dense_form(d_pad):
        return jops.segment_sum(values, dd, num_segments=d_pad)
    return jnp.sum(
        jnp.where(_slot_hit(dd, d_pad), values[None, :], 0),
        axis=-1,
        dtype=values.dtype,
    )


# traced-region kernel, called from exact.py's jit scope: ktpu: hot
def domain_max(values, dd, d_pad: int):
    """``jax.ops.segment_max(values, dd, num_segments=d_pad)`` for an [N]
    integer row: an empty slot holds the dtype's minimum, as the scatter
    leaves it."""
    if not dense_form(d_pad):
        return jops.segment_max(values, dd, num_segments=d_pad)
    lowest = jnp.iinfo(values.dtype).min
    return jnp.max(
        jnp.where(_slot_hit(dd, d_pad), values[None, :], lowest), axis=-1
    )
