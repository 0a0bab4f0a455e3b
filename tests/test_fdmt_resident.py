"""The VMEM-resident fused FDMT head: bit-identity with the per-level
merges, and the full transform/search with the head enabled."""

import numpy as np
import pytest

from pulsarutils_tpu.ops.fdmt import _merge_xla, fdmt_plan
from pulsarutils_tpu.ops.fdmt_resident import (
    _VMEM_HEADROOM,
    HEAD_LEVELS,
    HeadPlan,
    head_scratch_bytes,
    head_smem_bytes,
    head_smem_limit,
    head_supported,
    head_tables,
    head_tile_counts,
    head_transform,
    head_vmem_limit,
    pick_head_t_slice,
)

GARGS = (1200.0, 200.0)
#: MeerKAT's L band in 4,096 channels (chipbench/configs/
#: meertrap_lband_8bit.json): 32 groups of 128
MEERTRAP = (856.0, 856.0)
#: HTRU/BPSR's band (chipbench/configs/htru_bpsr_lowdm.json)
HTRU = (1182.0, 400.0)
#: the sweeps of the benchmark's cells, (band, max_delay, min_delay, T):
#: HTRU's six tiers (1-4 share a plan) and the rehearsal (PERF.md 4)
SURVEY_PLANS = {
    "htru_tier0": (HTRU, 1068, 0, 1 << 19),
    "htru_tier1": (HTRU, 1068, 535, 1 << 18),
    "htru_tier5": (HTRU, 641, 535, 1 << 14),
    "rehearsal": (GARGS, 612, 458, 1 << 20),
}


def _survey_head(name):
    band, hi, lo, t = SURVEY_PLANS[name]
    return HeadPlan(fdmt_plan(1024, *band, hi, lo)), t


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


class TestSliceAndExtents:
    """ISSUE 33: the slice sized to the core's VMEM, the row loop to each
    group's own rows."""

    NCHAN, T, HI = 256, 1 << 14, 267   # HTRU's band, unpruned from DM 0

    @pytest.fixture(scope="class")
    def case(self):
        plan = fdmt_plan(self.NCHAN, *HTRU, self.HI, 0)
        hp = HeadPlan(plan)
        # the groups differ in row count at every level past the first
        assert len(set(hp.tables[-1]["counts"])) == hp.n_groups > 1
        assert hp.row_blocks[-1].min() < hp.row_blocks[-1].max()
        data = np.random.default_rng(5).standard_normal(
            (self.NCHAN, self.T)).astype(np.float32)
        return data, _unfused_head(plan, data, HEAD_LEVELS)

    @pytest.mark.parametrize("row_extents", [True, False])
    @pytest.mark.parametrize("t_slice", [2048, 4096, 8192, 1 << 14],
                             ids=["2048", "4096", "8192", "T"])
    def test_bit_identical_at_every_slice(self, case, t_slice, row_extents):
        """``t_slice == T`` is tier 5's shape: one slice whose window
        laps the whole axis, every copy a wrap segment."""
        data, ref = case
        out = np.asarray(head_transform(
            data, self.HI, *HTRU, t_slice=t_slice, interpret=True,
            row_extents=row_extents))
        assert out.shape == ref.shape
        assert np.array_equal(out, ref), float(np.abs(out - ref).max())

    @pytest.mark.parametrize("name", sorted(SURVEY_PLANS))
    def test_slice_leaves_the_floor_on_the_survey_plans(self, name):
        hp, t = _survey_head(name)
        assert head_vmem_limit() == 96 << 20  # no TPU here: a v5e's share
        # 72 / 45 / 20 / 36 MiB of scratch: the largest slice dividing T
        assert pick_head_t_slice(hp, t) == min(t, 32768)
        assert pick_head_t_slice(hp, 8192) == 8192  # T allows no more

    @pytest.mark.parametrize("limit_mib", [21, 24, 32, 48, 64, 96])
    @pytest.mark.parametrize("name", sorted(SURVEY_PLANS))
    def test_slice_never_exceeds_the_budget_given(self, name, limit_mib):
        hp, t = _survey_head(name)
        t_slice = pick_head_t_slice(hp, t, vmem_limit=limit_mib << 20)
        assert (head_scratch_bytes(hp, t_slice)
                <= (limit_mib << 20) - _VMEM_HEADROOM)
        # and it is the largest that fits
        if 2 * t_slice <= min(t, 32768):
            assert (head_scratch_bytes(hp, 2 * t_slice)
                    > (limit_mib << 20) - _VMEM_HEADROOM)

    def test_scratch_of_the_tier0_plan(self):
        hp, _ = _survey_head("htru_tier0")
        assert [head_scratch_bytes(hp, ts) >> 20 for ts in
                (2048, 4096, 8192, 16384, 32768)] == [12, 16, 24, 40, 72]

    @pytest.mark.parametrize("t_slice,computed,extents", [
        (2048, 5685248, 4171776),
        (4096, 4366336, 3200000),
        (8192, 3706880, 2714112),
        (16384, 3377152, 2471168),
        (32768, 3212288, 2349696),
    ])
    def test_tile_count_of_the_tier0_plan(self, t_slice, computed, extents):
        """PERF.md 6 (PR 33): 2.615 tiles computed per useful one at the
        2,048 floor, 1.553 at 16,384 and 1.478 at 32,768; 1.137 and 1.081
        with the groups' own row extents."""
        hp, t = _survey_head("htru_tier0")
        assert head_tile_counts(hp, t, t_slice, row_extents=False) == (
            computed, 2173952)
        assert head_tile_counts(hp, t, t_slice) == (extents, 2173952)

    @pytest.mark.parametrize("name,ratio", [
        ("htru_tier1", 1.975), ("htru_tier5", 1.611), ("rehearsal", 1.429)])
    def test_tile_ratio_at_the_parents_slice(self, name, ratio):
        hp, t = _survey_head(name)
        parents = {"htru_tier1": 4096, "htru_tier5": 8192,
                   "rehearsal": 8192}[name]
        computed, useful = head_tile_counts(hp, t, parents,
                                            row_extents=False)
        assert round(computed / useful, 3) == ratio
        now, _ = head_tile_counts(hp, t, pick_head_t_slice(hp, t))
        assert now < computed


class TestSweepCounter:
    """``putpu_fdmt_head_tiles_total``: one count per coarse sweep."""

    #: cell 2's sweep: 1,024 x 2^19 at 64 us, DM 0-52
    SWEEP = ((1024, 1 << 19), 0.0, 52.0, 1182.0, 400.0, 6.4e-05)

    @staticmethod
    def _total():
        from pulsarutils_tpu.obs.metrics import REGISTRY

        return sum(s["value"] for s in REGISTRY.snapshot()
                   if s["name"] == "putpu_fdmt_head_tiles_total")

    def test_counts_the_sweeps_that_run_the_head(self, monkeypatch):
        import jax

        from pulsarutils_tpu.pipeline.search_pipeline import (
            _count_head_tiles,
        )

        n0 = self._total()
        # off the TPU the Pallas merges are off: no head, nothing counted
        assert _count_head_tiles({}, ("jax", "hybrid", None),
                                 *self.SWEEP) == 0
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        from pulsarutils_tpu.ops.fdmt import fdmt_trial_dms

        (nchan, t), dmmin, dmmax, f0, bw, tsamp = self.SWEEP
        _, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, f0, bw, tsamp)
        hp = HeadPlan(fdmt_plan(nchan, f0, bw, n_hi, n_lo))
        tiles = head_tile_counts(hp, t, pick_head_t_slice(hp, t))[0]
        assert 2.3e6 < tiles < 2.4e6  # tier 0's 2,350 k, but for a trial
        assert _count_head_tiles({}, ("jax", "hybrid", None),
                                 *self.SWEEP) == tiles
        assert _count_head_tiles({}, ("jax", "fdmt", None),
                                 *self.SWEEP) == tiles
        # another kernel, a mesh, a run that fell back to the host: none
        assert _count_head_tiles({}, ("jax", "auto", None),
                                 *self.SWEEP) == 0
        assert _count_head_tiles({}, ("jax", "hybrid", object()),
                                 *self.SWEEP) == 0
        assert _count_head_tiles({"backend": "numpy", "kernel": "auto"},
                                 ("jax", "hybrid", None), *self.SWEEP) == 0
        assert self._total() - n0 == 2 * tiles


class TestTablesAndPlane:
    """ISSUE 35: a grid step holds its own group's tables, not every
    group's, and the head writes each group's own rows."""

    #: (max_delay, min_delay) of MeerTRAP's tier 0 and of its pruned tiers
    #: 1-2, and what 28 whole tables of 32 groups took of a v5e's 1 MiB
    #: (each ``s32[32, rows]`` padded to 128-word lines; the compiler's
    #: "Used 1.75M of 1.00M smem", PERF.md section 6, PR 34)
    PLANS = {"tier0": (5182, 0, 1.75, 512),
             "tier1": (5182, 2592, 0.9375, 384)}

    @staticmethod
    def _whole_tables_bytes(hp):
        return sum(4 * hp.n_groups * (-(-rows // 128) * 128) * 4
                   for rows in hp.rows_out)

    @pytest.mark.parametrize("name", sorted(PLANS))
    def test_smem_need_of_the_meertrap_plans(self, name):
        hi, lo, old_mib, width = self.PLANS[name]
        hp = HeadPlan(fdmt_plan(4096, *MEERTRAP, hi, lo))
        assert hp.n_groups == 32
        assert self._whole_tables_bytes(hp) == old_mib * 2 ** 20
        assert head_smem_limit() == 512 << 10  # no TPU here: half a v5e's
        # one group's slice, 29 rows in (8, 128)-word tiles, twice
        assert head_smem_bytes(hp) == 2 * 32 * width * 4 <= head_smem_limit()
        tables = head_tables(hp)
        assert tables.shape == (32, 4 * HEAD_LEVELS + 1, max(hp.rows_out))
        assert width - 128 < tables.shape[2] <= width
        # indices <= 475, shifts <= 201: only a plane start is larger
        assert tables[:, :-1].max() < 512
        assert tables[:, -1, HEAD_LEVELS].max() == hp.plane_starts[-1]

    @pytest.mark.parametrize("name", sorted(SURVEY_PLANS))
    def test_smem_need_of_the_survey_plans(self, name):
        hp, _ = _survey_head(name)
        assert head_smem_bytes(hp) <= 64 << 10

    def test_plane_is_the_groups_own_rows(self):
        hp = HeadPlan(fdmt_plan(4096, *MEERTRAP, 5182, 0))
        assert hp.rows_out[-1] * hp.n_groups == 13312  # the old plane
        assert hp.rows_total <= hp.rows_plane <= hp.rows_total + 7 * 32
        assert hp.rows_plane < 0.42 * 13312
        assert (np.diff(hp.plane_starts) % 8 == 0).all()
        assert (np.diff(hp.plane_starts) >= hp.rows_valid[:-1]).all()

    def test_bit_identical_on_the_meertrap_band_from_dm0(self):
        """4,096 channels, 32 groups of 56 to 413 rows, band delays
        0-5,182: the plan the v5e compiler refused (interpret mode, one
        slice of 2,048 samples)."""
        t = 2048
        plan = fdmt_plan(4096, *MEERTRAP, 5182, 0)
        hp = HeadPlan(plan)
        assert hp.rows_valid.min() < 64 and hp.rows_valid.max() > 400
        data = np.random.default_rng(35).standard_normal(
            (4096, t)).astype(np.float32)
        ref = _unfused_head(plan, data, HEAD_LEVELS)
        out = np.asarray(head_transform(data, 5182, *MEERTRAP, t_slice=t,
                                        interpret=True))
        assert out.shape == ref.shape == (hp.rows_total, t)
        assert np.array_equal(out, ref), float(np.abs(out - ref).max())

    #: the head's plane handed to the next kernel as the head leaves it
    #: (ISSUE 37): (nchan, max_delay, the kernels after the head)
    HANDED_ON = {
        # 8 levels: the head, then ONE merge reading the padded plane
        "head_then_merge": (256, 267, {"fdmt_merge"}),
        # 9 levels: no single merge, the paired pass reads the plane
        "head_then_pair": (512, 300, {"fdmt_deep_pair"}),
    }

    @pytest.mark.parametrize("name", sorted(HANDED_ON))
    def test_next_kernel_reads_the_plane_through_rebased_tables(self, name):
        """``rows_plane != rows_total``: the stage after the head indexes
        plane rows (tables rebased on the host), no gather or slice of
        the state in the program, and the sweep is the flat per-level
        path's bit for bit."""
        import jax

        from pulsarutils_tpu.ops import fdmt
        from pulsarutils_tpu.ops.fdmt_resident import head_plane_rows

        nchan, hi, after = self.HANDED_ON[name]
        t = 4096
        hp = HeadPlan(fdmt_plan(nchan, *HTRU, hi, 0))
        rows = head_plane_rows(hp)
        assert hp.rows_plane > hp.rows_total == len(rows)
        assert (np.diff(rows) >= 1).all() and rows[-1] < hp.rows_plane
        assert (rows[hp.row_starts] == hp.plane_starts).all()

        def build(builder, use_pallas):
            return getattr(fdmt, builder)(
                nchan, *HTRU, hi, t, fdmt._pick_fdmt_tile(t), use_pallas,
                True, n_lo=0, t_orig=t)

        data = np.random.default_rng(37).standard_normal(
            (nchan, t)).astype(np.float32)
        jaxpr = jax.make_jaxpr(build("_transform_fn", True))(data)
        prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
        calls = [i for i, p in enumerate(prims) if p == "pallas_call"]
        assert len(calls) == 2 and "gather" not in prims
        # the head's lines to the merges' tiles: one reshape, no slice
        assert prims[calls[0] + 1:calls[1]] == ["reshape"]
        text = str(jaxpr)
        assert {k for k in ("fdmt_merge", "fdmt_deep_pair")
                if k in text} == after
        got = np.asarray(build("_build_transform", True)(data))
        want = np.asarray(build("_build_transform", False)(data))
        assert got.shape == want.shape == (hi + 1, t)
        assert np.array_equal(got, want), float(np.abs(got - want).max())

    def test_head_declines_where_the_tables_do_not_fit(self, monkeypatch):
        """A plan too wide for the core's SMEM takes the per-level
        merges: `_head_choice` says so, nothing raises."""
        from pulsarutils_tpu.ops import fdmt, fdmt_resident

        nchan, t, lo, hi = 256, 4096, 100, 250
        args = (nchan, *GARGS, hi, lo, t)
        choice, declined, smem = fdmt._head_verdict(*args)
        assert choice is not None and declined is None and smem > 0
        monkeypatch.setattr(fdmt_resident, "head_smem_limit",
                            lambda: smem - 1)
        assert fdmt._head_verdict(*args) == (None, "smem", smem)
        assert fdmt._head_choice(*args) is None
        assert not fdmt.head_active(*args)
        fdmt._transform_fn.cache_clear()
        data = np.random.default_rng(4).standard_normal(
            (nchan, t)).astype(np.float32)
        declined_out, per_level = (np.asarray(fdmt._build_transform(
            nchan, *GARGS, hi, t, fdmt._pick_fdmt_tile(t), False, True,
            n_lo=lo, t_orig=t, use_head=use_head)(data))
            for use_head in (True, False))
        fdmt._transform_fn.cache_clear()
        assert np.array_equal(declined_out, per_level)

    def test_verdict_names_the_other_reasons(self):
        from pulsarutils_tpu.ops import fdmt

        assert fdmt._head_verdict(64, *GARGS, 40, 0, 4096)[1:] == (
            "shape", 0)
        # a band whose early levels shift a row by more than one lane row
        verdicts = {fdmt._head_verdict(1024, 300.0, 100.0, hi, 0, 1 << 15)[1]
                    for hi in (3000, 30000)}
        assert verdicts <= {"halo", "shift"} and verdicts
