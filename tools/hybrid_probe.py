"""Time the hybrid on a device-generated pulse chunk (no host upload).

The full bench pays a host simulate + a 4 GB upload per invocation;
this probe reproduces its hybrid-vs-exact comparison with
the data built ON DEVICE — the kernel-iteration loop for hybrid tuning.

Usage: python tools/hybrid_probe.py [nchan nsamp ndm [reps]]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    nchan = int(argv[1]) if len(argv) > 1 else 1024
    nsamp = int(argv[2]) if len(argv) > 2 else 1 << 20
    ndm = int(argv[3]) if len(argv) > 3 else 512
    reps = int(argv[4]) if len(argv) > 4 else 3

    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.plan import (
        dedispersion_shifts, dmmax_for_trials)
    from pulsarutils_tpu.ops.search import dedispersion_search

    geom = (1200.0, 200.0, 0.0005)
    dmmin = 300.0
    dmmax = dmmax_for_trials(dmmin, ndm, *geom)
    inject_dm = 350.0

    key = jax.random.PRNGKey(0)
    data = jnp.abs(jax.random.normal(key, (nchan, nsamp), jnp.float32)) * 0.5
    shifts = np.rint(np.asarray(dedispersion_shifts(
        nchan, inject_dm, *geom))).astype(np.int64)
    idx = (nsamp // 2 + shifts) % nsamp
    data = data.at[jnp.arange(nchan), jnp.asarray(idx)].add(4.0)
    data.block_until_ready()
    print(f"platform={jax.default_backend()} {nchan}x{nsamp} "
          f"DM {dmmin:.0f}-{dmmax:.0f}", flush=True)

    t0 = time.time()
    tb = dedispersion_search(data, dmmin, dmmax, *geom, backend="jax",
                             kernel="hybrid")
    print(f"first={time.time() - t0:.1f}s", flush=True)
    best = np.inf
    for _ in range(reps):
        t0 = time.time()
        tb = dedispersion_search(data, dmmin, dmmax, *geom, backend="jax",
                                 kernel="hybrid")
        best = min(best, time.time() - t0)
    nex = int(tb["exact"].sum())
    print(f"hybrid steady={best:.3f}s -> {tb.nrows / best:.1f} tr/s  "
          f"best_dm={float(tb.best_row()['DM']):.2f} exact_rows={nex}",
          flush=True)

    # exact argbest check vs the pallas sweep
    tp = dedispersion_search(data, dmmin, dmmax, *geom, backend="jax",
                             kernel="pallas")
    ok = tb.argbest() == tp.argbest()
    print(f"argbest match vs pallas: {ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
