"""Test configuration: force JAX onto CPU with 8 virtual devices BEFORE any
test imports jax, so sharding tests exercise a multi-chip mesh without TPU
hardware (SURVEY.md §6.7) and resource arithmetic stays int64.

The installed JAX honours JAX_PLATFORMS / JAX_ENABLE_X64 (the tier-1
command sets JAX_PLATFORMS=cpu); conftest pins both through jax.config as
well, so a bare ``pytest tests/`` on a machine with a chip still runs the
suite on the 8 virtual CPU devices it was written for. This is the one
place outside the virtual-time sim that sets jax_platforms in code.
"""

import contextlib
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
# int64 resource arithmetic (memory bytes overflow int32) — parity requires it
jax.config.update("jax_enable_x64", True)


@pytest.fixture
def all_scatter(monkeypatch):
    """A context manager under which ops/domains.py takes the scatter at
    every d_pad (its limit patched to 0: a test's patch, not an option of
    the program), for the parity tests that solve one seeded batch in both
    forms. The rule is read when a program is traced, so the jit caches
    are dropped on the way in and on the way out."""
    from kubernetes_tpu.ops import domains

    @contextlib.contextmanager
    def scatter():
        jax.clear_caches()
        with monkeypatch.context() as m:
            m.setattr(domains, "DENSE_MAX_SLOTS", 0)
            yield
        jax.clear_caches()

    return scatter
