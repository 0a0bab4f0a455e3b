"""Sweep FDMT merge-kernel tuning knobs on the live device.

Usage: python tools/fdmt_tune.py [nchan nsamp ndm]
Times a full search per (MERGE_ROW_BLOCK, tile preference) combination.
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

    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.plan import dmmax_for_trials
    from pulsarutils_tpu.ops.search import dedispersion_search

    geom = (1200.0, 200.0, 0.0005)
    dmmin = 300.0
    dmmax = dmmax_for_trials(dmmin, ndm, *geom)
    key = jax.random.PRNGKey(0)
    data = jnp.abs(jax.random.normal(key, (nchan, nsamp), dtype=jnp.float32))
    np.asarray(data[0, :1])
    print(f"config: {nchan} x {nsamp}, {ndm} trials", flush=True)

    tiles_default = (8192, 4096, 2048, 1024)
    row_block_orig = fdmt.MERGE_ROW_BLOCK
    for row_block in (8, 16, 32, 64):
        for tiles in (tiles_default, (4096, 2048, 1024), (2048, 1024)):
            fdmt.MERGE_ROW_BLOCK = row_block
            orig = fdmt._pick_fdmt_tile
            fdmt._pick_fdmt_tile = lambda t, _tiles=tiles: next(
                (tt for tt in _tiles if t % tt == 0), 0)
            # drop caches so the knobs take effect
            fdmt._build_transform.cache_clear()
            fdmt._build_merge_kernel.cache_clear()
            try:
                t0 = time.time()
                table = dedispersion_search(data, dmmin, dmmax, *geom,
                                            backend="jax", kernel="fdmt")
                t_compile = time.time() - t0
                t0 = time.time()
                table = dedispersion_search(data, dmmin, dmmax, *geom,
                                            backend="jax", kernel="fdmt")
                dt = time.time() - t0
                print(f"row_block={row_block:3d} tile_max={tiles[0]:5d}: "
                      f"steady {dt:.3f}s ({table.nrows / dt:.0f} tr/s, "
                      f"compile {t_compile:.1f}s)", flush=True)
            except Exception as exc:
                print(f"row_block={row_block:3d} tile_max={tiles[0]:5d}: "
                      f"FAILED {type(exc).__name__}: {exc}", flush=True)
            finally:
                fdmt._pick_fdmt_tile = orig
    # restore module state for long-lived importers
    fdmt.MERGE_ROW_BLOCK = row_block_orig
    fdmt._build_transform.cache_clear()
    fdmt._build_merge_kernel.cache_clear()


if __name__ == "__main__":
    main(sys.argv)
