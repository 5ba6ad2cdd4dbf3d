"""Backend identity: initialise JAX at start-up and say what it found.

``serve`` and ``perf`` call ``init_backend`` before they accept work, so
a missing or broken accelerator fails the process at start instead of
surfacing as a solve error the degraded-mode ladder would absorb. The
backend is chosen by ``JAX_PLATFORMS`` alone — nothing here (or
anywhere outside tests/conftest.py and the virtual-time sim) sets
``jax_platforms`` in code. With ``JAX_PLATFORMS`` unset, stock JAX
falls back to the CPU when it finds no accelerator; the identity logged
and exported here is how an operator sees that it did.
"""

from __future__ import annotations

import logging

from .. import metrics

_log = logging.getLogger("kubernetes_tpu.device")


def init_backend() -> dict:
    """Initialise the default JAX backend (raises if ``JAX_PLATFORMS``
    names a platform that cannot start), log its identity once, export
    it as ``scheduler_tpu_device_info``, and return it in the shape
    JAX reports: ``{"platform", "kind", "count"}``."""
    import jax

    devices = jax.devices()
    ident = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    metrics.device_info.labels(ident["platform"], ident["kind"]).set(
        ident["count"]
    )
    # the identity rides as structured fields (utils/logging.py appends
    # them as k=v in text mode, top-level keys in json mode)
    _log.info(
        "jax backend up",
        extra={
            "platform": ident["platform"],
            "device_kind": ident["kind"],
            "devices": ident["count"],
        },
    )
    return ident
