"""The VMEM-resident fused FDMT head: bit-identity with the per-level
merges, and the full transform/search with the head enabled."""

import numpy as np
import pytest

from pulsarutils_tpu.ops.fdmt import _merge_xla, fdmt_plan
from pulsarutils_tpu.ops.fdmt_resident import (
    HEAD_LEVELS,
    HeadPlan,
    head_supported,
    head_transform,
)

GARGS = (1200.0, 200.0)


def _unfused_head(plan, data, n_levels):
    import jax.numpy as jnp

    state = jnp.asarray(np.concatenate(
        [data, np.zeros((plan.nchan_padded - data.shape[0],
                         data.shape[1]), np.float32)]))
    for it in plan.iterations[:n_levels]:
        sh = (jnp.asarray(it["shift_high"])
              if it["shift_high"] is not None else None)
        state = _merge_xla(state, jnp.asarray(it["idx_low"]),
                           jnp.asarray(it["idx_high"]),
                           jnp.asarray(it["shift"]), sh)
    return np.asarray(state)


class TestHead:
    @pytest.mark.parametrize("nchan,t,lo,hi", [
        (256, 4096, 100, 250),
        # T == t_slice: n_slices == 1, every staggered input block maps
        # to slice 0 — the circular-wrap path a review caught reading
        # uninitialised VMEM (the last `halo` samples were NaN); the
        # 128-chan case that LOOKED like it covered this skipped via
        # head_supported (exactly 7 iterations)
        (256, 2048, 40, 180),
        (200, 4096, 40, 180),   # non-power-of-two channels (zero pad)
    ])
    def test_bit_identical_to_per_level(self, nchan, t, lo, hi):
        plan = fdmt_plan(nchan, *GARGS, hi, lo)
        if not head_supported(plan.nchan_padded, len(plan.iterations), t,
                              t_slice=2048):
            pytest.skip("geometry below head size")
        rng = np.random.default_rng(1)
        data = rng.standard_normal((nchan, t)).astype(np.float32)
        ref = _unfused_head(plan, data, HEAD_LEVELS)
        out = np.asarray(head_transform(data, hi, *GARGS, min_delay=lo,
                                        t_slice=2048, interpret=True))
        assert out.shape == ref.shape
        assert np.array_equal(out, ref), float(np.abs(out - ref).max())

    def test_head_plan_row_accounting(self):
        plan = fdmt_plan(256, *GARGS, 250, 100)
        hp = HeadPlan(plan)
        # groups partition the level-7 state exactly
        assert hp.rows_total == sum(plan.iterations[HEAD_LEVELS - 1]
                                    ["ndelay"])
        assert (hp.row_starts[1:]
                == np.cumsum(hp.rows_valid)[:-1]).all()
        # halo equals the sum of per-level worst shifts
        assert hp.halo == sum(hp.max_shift_per_level)

    def test_full_transform_with_head_matches(self):
        """End-to-end: the whole transform with the head forced on must
        equal the per-level transform bit for bit."""
        from pulsarutils_tpu.ops import fdmt

        nchan, t, lo, hi = 256, 4096, 100, 250
        assert fdmt.head_active(nchan, *GARGS, hi, lo, t)
        data = np.random.default_rng(3).standard_normal(
            (nchan, t)).astype(np.float32)
        head, per_level = (np.asarray(fdmt._build_transform(
            nchan, *GARGS, hi, t, fdmt._pick_fdmt_tile(t), False, True,
            n_lo=lo, t_orig=t, use_head=use_head)(data))
            for use_head in (True, False))
        assert np.array_equal(head, per_level), float(
            np.abs(head - per_level).max())

    def test_head_supported_gates(self):
        assert not head_supported(64, 10, 1 << 14)      # too few chans
        assert not head_supported(1024, 7, 1 << 14)     # too few levels
        assert not head_supported(1024, 10, 1000)       # t not divisible
        assert head_supported(1024, 10, 1 << 14)
        assert not head_supported(1024, 10, 1 << 14, halo=2000)
