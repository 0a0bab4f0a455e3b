"""Time the JAX search kernels (pallas / gather / fdmt) on the live device.

Usage: python tools/kernel_probe.py [nchan nsamp ndm [kernels...]]

Generates the data ON DEVICE (no host upload — this probe measures
kernel time, not host-to-device bandwidth), warms each kernel once,
then reports steady-state seconds and DM-trials/s.
"""
import os
import sys
import time

import numpy as np


def main(argv):
    nchan = int(argv[1]) if len(argv) > 1 else 1024
    nsamp = int(argv[2]) if len(argv) > 2 else 262144
    ndm = int(argv[3]) if len(argv) > 3 else 512
    kernels = argv[4:] or ["fdmt", "pallas"]

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.search import dedispersion_search

    print(f"platform={jax.default_backend()} "
          f"config: {nchan} chan x {nsamp} samp, {ndm} trials",
          flush=True)

    start_freq, bandwidth, tsamp = 1200.0, 200.0, 0.0005
    from pulsarutils_tpu.ops.plan import dmmax_for_trials
    dmmin = 100.0
    dmmax = dmmax_for_trials(dmmin, ndm, start_freq, bandwidth, tsamp)

    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (nchan, nsamp), dtype=jnp.float32)
    data = jnp.abs(data) * 0.5
    data.block_until_ready()

    for kernel in kernels:
        try:
            t0 = time.time()
            table = dedispersion_search(
                data, dmmin, dmmax, start_freq, bandwidth, tsamp,
                backend="jax", kernel=kernel)
            n_tr = table.nrows
            t_first = time.time() - t0
            t0 = time.time()
            table = dedispersion_search(
                data, dmmin, dmmax, start_freq, bandwidth, tsamp,
                backend="jax", kernel=kernel)
            dt = time.time() - t0
            print(f"{kernel:8s} ntrials={n_tr} first={t_first:.2f}s "
                  f"steady={dt:.3f}s -> {n_tr / dt:.1f} DM-trials/s",
                  flush=True)
        except Exception as e:
            print(f"{kernel:8s} FAILED: {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main(sys.argv)
