"""Sweep the fused FDMT head's (t_slice, n_levels) on the live TPU.

Each combination is timed head-only at the benchmark config; invalid
combinations (VMEM overflow, eligibility) are reported and skipped.
Usage: python tools/head_sweep.py [t_slices...] e.g. 2048 4096 8192
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    t_slices = [int(a) for a in argv[1:]] or [2048, 4096, 8192]
    levels = [int(x) for x in
              (os.environ.get("SWEEP_LEVELS") or "7,8").split(",")]

    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.fdmt import fdmt_trial_dms
    from pulsarutils_tpu.ops.fdmt_resident import _build_head_kernel
    from pulsarutils_tpu.ops.plan import dmmax_for_trials

    nchan, t = 1024, 1 << 20
    geom = (1200.0, 200.0, 0.0005)
    dmmax = dmmax_for_trials(300.0, 512, *geom)
    _, n_lo, n_hi = fdmt_trial_dms(nchan, 300.0, dmmax, *geom)
    print(f"platform={jax.default_backend()} {nchan}x{t} n={n_lo}..{n_hi}",
          flush=True)

    key = jax.random.PRNGKey(0)
    data = jnp.abs(jax.random.normal(key, (nchan, t), jnp.float32)) * 0.5
    data.block_until_ready()

    ref = None
    for n_levels in levels:
        for t_slice in t_slices:
            tag = f"levels={n_levels} t_slice={t_slice}"
            try:
                run, head = _build_head_kernel(
                    nchan, geom[0], geom[1], n_hi, n_lo, n_levels, t,
                    t_slice, False)
                jrun = jax.jit(run)
                out = jrun(data)
                np.asarray(out[0, :1])
                best = np.inf
                for _ in range(3):
                    t0 = time.time()
                    out = jrun(data)
                    np.asarray(out[0, :1])
                    best = min(best, time.time() - t0)
                # correctness vs the reference combo (first success)
                note = ""
                if ref is None:
                    ref = (n_levels, np.asarray(out[:8, :4096]))
                elif ref[0] == n_levels:
                    ok = np.array_equal(ref[1], np.asarray(out[:8, :4096]))
                    note = " BITMATCH" if ok else " MISMATCH!"
                print(f"{tag}: {best:.3f}s halo={head.halo}{note}",
                      flush=True)
            except Exception as exc:
                msg = str(exc).split("\n")[0][:140]
                print(f"{tag}: FAILED {type(exc).__name__}: {msg}",
                      flush=True)


if __name__ == "__main__":
    main(sys.argv)
