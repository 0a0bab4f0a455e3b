"""Stage-level timing of the FDMT coarse sweep on the live TPU.

Times, at the benchmark config (1024 x 2^20, DM 300-635):
  head   — the fused VMEM-resident head alone (levels 0..HEAD_LEVELS-1)
  deep   — the remaining per-level merges alone (fed a level-N state)
  xform  — the full transform (head + deep, no scoring)
  score  — scoring alone on a captured final state
  full   — transform + fused scoring (the production program)

This separates instruction-bound from traffic-bound stages: the plan's
HBM traffic per stage is printed next to the measured time so achieved
GB/s is read off directly (VERDICT r3 #2: make "fast" quantitative).
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, n=2):
    out = fn(*args)
    out = out[0] if isinstance(out, tuple) else out
    np.asarray(out[0, :1] if out.ndim > 1 else out[:1])  # force
    best = np.inf
    for _ in range(n):
        t0 = time.time()
        prev = out
        out = fn(*args)
        out = out[0] if isinstance(out, tuple) else out
        np.asarray(out[0, :1] if out.ndim > 1 else out[:1])
        best = min(best, time.time() - t0)
        if prev is not out and hasattr(prev, "delete"):
            prev.delete()  # keep one live copy: HBM is 16 GB
    return best, out


def main():
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.fdmt import (
        _build_transform, _pick_fdmt_tile, fdmt_plan, fdmt_trial_dms)
    from pulsarutils_tpu.ops.fdmt_resident import (
        HEAD_LEVELS, _build_head_kernel, _head_plan_cached,
        pick_head_t_slice)
    from pulsarutils_tpu.ops.plan import dmmax_for_trials

    nchan = int(os.environ.get("PROBE_NCHAN", 1024))
    t = int(os.environ.get("PROBE_T", 1 << 20))
    geom = (1200.0, 200.0, 0.0005)
    dmmin = 300.0
    dmmax = dmmax_for_trials(dmmin, 512, *geom)
    _, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, *geom)
    plan = fdmt_plan(nchan, geom[0], geom[1], n_hi, n_lo)
    rows = [len(it["idx_low"]) for it in plan.iterations]
    B = t * 4 / 1e9
    print(f"platform={jax.default_backend()} {nchan}x{t} n={n_lo}..{n_hi} "
          f"rows/level={rows}", flush=True)

    key = jax.random.PRNGKey(0)
    data = jnp.abs(jax.random.normal(key, (nchan, t), jnp.float32)) * 0.5
    data.block_until_ready()
    t_tile = _pick_fdmt_tile(t)

    # head alone (same t_slice the production transform picks)
    t_slice = pick_head_t_slice(
        _head_plan_cached(nchan, geom[0], geom[1], n_hi, n_lo,
                          HEAD_LEVELS), t)
    print(f"head t_slice={t_slice}", flush=True)
    head_run, head = _build_head_kernel(nchan, *geom[:2], n_hi, n_lo,
                                        HEAD_LEVELS, t, t_slice, False)
    jhead = jax.jit(head_run)
    dt, state = timed(jhead, data)
    head_gb = 2 * nchan * B + rows[HEAD_LEVELS - 1] * B
    print(f"head   {dt:7.3f}s  (naive traffic {head_gb:5.1f} GB -> "
          f"{head_gb / dt:6.0f} GB/s)", flush=True)

    # deep levels alone (jit the per-level tail on the head's output)
    from pulsarutils_tpu.ops.fdmt import _merge_pallas

    def deep_fn(st):
        for it in plan.iterations[HEAD_LEVELS:]:
            st = _merge_pallas(st, it, t_tile, False)
        return st

    jdeep = jax.jit(deep_fn)
    dt, final = timed(jdeep, state)
    state.delete()
    deep_gb = sum(3 * rows[i] * B for i in range(HEAD_LEVELS, len(rows)))
    print(f"deep   {dt:7.3f}s  (naive traffic {deep_gb:5.1f} GB -> "
          f"{deep_gb / dt:6.0f} GB/s)", flush=True)

    # scoring alone
    from pulsarutils_tpu.ops.search import score_profiles_chunked

    jscore = jax.jit(lambda p: score_profiles_chunked(p, jnp, with_cert=True))
    dt, _ = timed(jscore, final)
    final.delete()
    print(f"score  {dt:7.3f}s  (plane {rows[-1] * B:5.1f} GB)", flush=True)

    # full production program
    run = _build_transform(nchan, geom[0], geom[1], n_hi, t, t_tile, True,
                           False, n_lo=n_lo, with_scores=True,
                           with_plane=False, with_cert=True, use_head=True)
    dt, _ = timed(run, data)
    print(f"full   {dt:7.3f}s  -> {rows[-1] / dt:7.1f} tr/s", flush=True)

    run0 = _build_transform(nchan, geom[0], geom[1], n_hi, t, t_tile, True,
                            False, n_lo=n_lo, with_scores=True,
                            with_plane=False, with_cert=True, use_head=False)
    dt, _ = timed(run0, data)
    print(f"full(no head) {dt:7.3f}s", flush=True)


if __name__ == "__main__":
    main()
