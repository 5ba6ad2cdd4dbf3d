"""Compiled-path smoke test and micro-timing for the Pallas kernels on a
TPU, with x64 ON as the solver runs them (the CPU test suite runs them
in interpret mode only; chip_smoke.py phase B checks the compiled kernel
on every run). Run on the chip:
    python scripts/pallas_smoke.py
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def main() -> None:
    import jax

    jax.config.update("jax_enable_x64", True)  # the solver's regime

    from kubernetes_tpu.ops.pallas_kernels import (
        N_TILE,
        domain_counts_pallas,
        domain_counts_reference,
    )

    print(f"devices: {jax.devices()}")
    rng = np.random.default_rng(0)
    t, n, d_pad = 16, 20 * N_TILE, 32
    dom = rng.integers(-1, d_pad, size=(t, n)).astype(np.int32)
    cnt = rng.integers(0, 5, size=(t, n)).astype(np.int32)

    got = np.asarray(domain_counts_pallas(dom, cnt, d_pad))
    want = np.asarray(domain_counts_reference(dom, cnt, d_pad))
    np.testing.assert_array_equal(got, want)

    # timing: compiled kernel vs segment_sum lowering (device-resident)
    import jax.numpy as jnp

    dom_d, cnt_d = jnp.asarray(dom), jnp.asarray(cnt)
    ref_jit = jax.jit(domain_counts_reference, static_argnames=("d_pad",))
    for name, fn in (
        ("pallas", lambda: domain_counts_pallas(dom_d, cnt_d, d_pad)),
        ("segment_sum", lambda: ref_jit(dom_d, cnt_d, d_pad)),
    ):
        fn().block_until_ready()  # warm
        t0 = time.perf_counter()
        for _ in range(50):
            out = fn()
        out.block_until_ready()
        print(f"{name}: {(time.perf_counter() - t0) / 50 * 1e6:.0f}us/call")
    print("pallas smoke OK: compiled kernel matches reference")


if __name__ == "__main__":
    main()
