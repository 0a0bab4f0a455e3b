"""FDMT tree dedispersion: track correctness, round-trip DM recovery,
and agreement with the exact kernels.

The FDMT's per-channel delays are tree-rounded (each merge rounds the
track's sub-band crossing), so planes are compared against a brute-force
summation along the SAME tree-rounded tracks (exact equality), while
search results are compared statistically (recovered DM within one trial
of the exact backend).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from pulsarutils_tpu.models.simulate import simulate_test_data
from pulsarutils_tpu.ops.fdmt import (
    fdmt_plan,
    fdmt_transform,
    fdmt_trial_dms,
    max_band_delay,
)
from pulsarutils_tpu.ops.search import dedispersion_search

GEOM = (1200.0, 200.0, 0.0005)  # start_freq, bandwidth, tsamp


def _kernels(run, shape):
    """The coarse sweep's Pallas kernels a program calls, by their
    declared names (traced only: nothing is lowered or run)."""
    import jax

    text = str(jax.make_jaxpr(run)(jax.ShapeDtypeStruct(shape, np.float32)))
    return {name for name in ("fdmt_head", "fdmt_merge", "fdmt_deep_pair",
                              "score_rows") if name in text}


def brute_force_tracks(data, plan, max_delay):
    """Recompute every row by walking the plan's merge tables on the host.

    Returns the per-(row, channel) sample delays the tree encodes, then
    sums ``data`` along them — the ground truth for the transform.
    """
    nchan, t = data.shape
    nch2 = plan.nchan_padded
    # delays[row] = {channel: sample delay}; init: raw channels
    state_delays = [{c: 0} for c in range(nch2)]
    for it in plan.iterations:
        new = []
        for r in range(len(it["idx_low"])):
            low = state_delays[it["idx_low"][r]]
            high = state_delays[it["idx_high"][r]]
            s = int(it["shift"][r])
            sh = int(it["shift_high"][r]) if it["shift_high"] is not None \
                else 0
            merged = {c: d + s for c, d in low.items()}
            merged.update({c: d + sh for c, d in high.items()})
            new.append(merged)
        state_delays = new
    out = np.zeros((max_delay + 1, t))
    for n in range(max_delay + 1):
        for c, d in state_delays[n].items():
            if c < nchan:
                out[n] += np.roll(data[c], -d)
    return out


class TestTransform:
    def test_matches_tree_tracks_exactly(self):
        rng = np.random.default_rng(0)
        nchan, t = 16, 512
        data = rng.normal(0, 1, (nchan, t)).astype(np.float32)
        max_delay = 40
        plan = fdmt_plan(nchan, GEOM[0], GEOM[1], max_delay)
        ref = brute_force_tracks(data, plan, max_delay)
        out = np.asarray(fdmt_transform(data, max_delay, GEOM[0], GEOM[1]))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)

    def test_pallas_merge_matches_xla_merge(self):
        rng = np.random.default_rng(1)
        nchan, t = 8, 2048  # t divisible by 1024 -> pallas path possible
        data = rng.normal(0, 1, (nchan, t)).astype(np.float32)
        a = np.asarray(fdmt_transform(data, 30, GEOM[0], GEOM[1],
                                      use_pallas=False))
        b = np.asarray(fdmt_transform(data, 30, GEOM[0], GEOM[1],
                                      use_pallas=True))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("min_delay", [0, 17])
    def test_deep_pair_bit_identical(self, min_delay):
        # the composed 4-parent pass must be BIT-identical to the two
        # per-level merges it replaces: same floats, same pairwise add
        # tree (ops/fdmt.py:_build_merge4_kernel); 17 prunes the plan
        from pulsarutils_tpu.ops import fdmt

        nchan, t = 16, 2048  # pallas path; >= 2 deep iterations
        data = np.random.default_rng(9).normal(
            0, 1, (nchan, t)).astype(np.float32)

        def side(deep_pair):
            run = fdmt._build_transform(
                nchan, GEOM[0], GEOM[1], 40, t, fdmt._pick_fdmt_tile(t),
                True, True, n_lo=min_delay, t_orig=t, deep_pair=deep_pair)
            return _kernels(run, (nchan, t)), np.asarray(run(data))

        per_level_kernels, per_level = side(False)
        paired_kernels, paired = side(True)
        assert per_level_kernels == {"fdmt_merge"}
        assert paired_kernels == {"fdmt_merge", "fdmt_deep_pair"}
        np.testing.assert_array_equal(per_level, paired)

    @pytest.mark.parametrize("use_pallas,interpret,scored", [
        (True, False, {"score_rows"}),   # a TPU
        (True, True, set()),             # Pallas forced on off the chip
        (False, True, None),             # the CPU: XLA merges and scorer
        (False, False, None),            # XLA merges forced on a TPU
    ])
    @pytest.mark.parametrize("nchan,t,n_lo,n_hi,merges", [
        # ten levels, the head fits: head, one merge, the deep pair
        (1024, 4096, 100, 250, {"fdmt_head", "fdmt_merge",
                                "fdmt_deep_pair"}),
        # four levels, below the head's size: two merges, the deep pair
        (16, 2048, 0, 40, {"fdmt_merge", "fdmt_deep_pair"}),
    ])
    def test_sweep_decides_its_own_shape(self, use_pallas, interpret,
                                         scored, nchan, t, n_lo, n_hi,
                                         merges):
        # what production builds (no variant keyword): the kernels of
        # the traced program follow from use_pallas, interpret and the
        # geometry alone
        from pulsarutils_tpu.ops import fdmt

        assert fdmt.head_active(nchan, GEOM[0], GEOM[1], n_hi, n_lo, t) == (
            "fdmt_head" in merges)
        run = fdmt._build_transform(
            nchan, GEOM[0], GEOM[1], n_hi, t, fdmt._pick_fdmt_tile(t),
            use_pallas, interpret, n_lo=n_lo, with_scores=True,
            with_plane=False, t_orig=t, with_cert=True)
        want = set() if scored is None else merges | scored
        assert _kernels(run, (nchan, t)) == want

    #: plans that meet the edges of the chained sweep (ISSUE 37): the
    #: state goes from kernel to kernel in the merges' own tiles, the
    #: rows each kernel pads to left in place.  (nchan, hi, lo, forced
    #: variant, rows of every level)
    CHAINS = {
        # four merges, each padded to its 32-row block (48 -> 64, 44 ->
        # 64, ...): every stage reads a state of more rows than are real
        "padded_rows_carried": (16, 40, 0, {"deep_pair": False},
                                [48, 44, 42, 41]),
        # the pair pads 41 rows to its 16-row block; 41 = 5 x 8 + 1
        "pair_pads_its_rows": (16, 40, 0, {}, [48, 44, 42, 41]),
        # MeerTRAP tier 0's shape in small: three merges, then the pair
        "three_merges_then_pair": (32, 45, 0, {}, [61, 53, 49, 47, 46]),
        # a pruned plan, fewer rows than a block at every level
        "pruned": (16, 40, 17, {}, [31, 27, 25, 24]),
    }

    @staticmethod
    def _chain(name, t=2048, builder="_build_transform", **kw):
        from pulsarutils_tpu.ops import fdmt

        nchan, hi, lo, variant, rows = TestTransform.CHAINS[name]
        plan = fdmt_plan(nchan, GEOM[0], GEOM[1], hi, lo)
        assert [len(it["idx_low"]) for it in plan.iterations] == rows

        def build(use_pallas, **more):
            return getattr(fdmt, builder)(
                nchan, GEOM[0], GEOM[1], hi, t, fdmt._pick_fdmt_tile(t),
                use_pallas, True, n_lo=lo, **dict(kw, **more))

        data = np.random.default_rng(37).normal(
            0, 1, (nchan, t)).astype(np.float32)
        return build(True, **variant), build(False), data, rows

    @pytest.mark.parametrize("name", sorted(CHAINS))
    def test_chained_sweep_bit_identical_to_flat_path(self, name):
        chained, flat, data, rows = self._chain(name, t_orig=2048)
        got, want = np.asarray(chained(data)), np.asarray(flat(data))
        assert got.shape == want.shape == (rows[-1], 2048)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("name", sorted(CHAINS))
    def test_nothing_between_two_kernels(self, name):
        """From the first kernel to the last the traced program holds
        kernels alone: no reshape, slice or gather of the state."""
        import jax

        chained, _, data, rows = self._chain(name, builder="_transform_fn",
                                             t_orig=2048)
        prims = [e.primitive.name for e in
                 jax.make_jaxpr(chained)(data).jaxpr.eqns]
        calls = [i for i, p in enumerate(prims) if p == "pallas_call"]
        paired = self.CHAINS[name][3].get("deep_pair", True)
        assert len(calls) == len(rows) - paired  # the pair is two levels
        assert prims[calls[0]:calls[-1] + 1] == ["pallas_call"] * len(calls)
        # one relayout in, one out (and the cut to the true rows)
        assert prims[:calls[0]].count("reshape") == 1
        assert prims[calls[-1] + 1:].count("reshape") == 1

    def test_chained_sweep_cuts_plane_to_true_rows_and_samples(self):
        """``with_plane`` and ``t_orig < t``: the scorer's plane and the
        captured one are the true rows and samples, whatever the last
        kernel padded."""
        chained, flat, data, rows = self._chain(
            "pair_pads_its_rows", t_orig=1900, with_scores=True,
            with_plane=True, with_cert=True)
        (got_s, got_p), (want_s, want_p) = chained(data), flat(data)
        assert got_p.shape == (rows[-1], 1900)
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))

    def test_merge_rows_traced_keeps_its_flat_contract(self):
        """``parallel/sharded_fdmt.py`` hands flat states and traced
        tables: flat in, flat out, equal to the XLA merge."""
        from pulsarutils_tpu.ops.fdmt import (MERGE_ROW_BLOCK, _merge_xla,
                                              merge_rows_traced)

        t, t_tile = 2048, 1024
        it = fdmt_plan(32, GEOM[0], GEOM[1], 45, 0).iterations[1]
        pad = (-len(it["idx_low"])) % MERGE_ROW_BLOCK
        il, ih, sh = (jnp.asarray(np.concatenate([it[k], it[k][-1:].repeat(
            pad)])) for k in ("idx_low", "idx_high", "shift"))
        state = jnp.asarray(np.random.default_rng(2).normal(
            0, 1, (61, t)).astype(np.float32))
        out = merge_rows_traced(
            state, il, ih, sh, jnp.zeros_like(sh),
            k_tiles=(int(it["shift"].max()) // (t_tile // 8) + 23) // 8,
            k_tiles_h=0, t_tile=t_tile, interpret=True)
        assert out.shape == (53 + pad, t)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_merge_xla(state, il, ih, sh)))

    def test_row_zero_is_plain_channel_sum(self):
        rng = np.random.default_rng(2)
        data = rng.normal(0, 1, (8, 256)).astype(np.float32)
        out = np.asarray(fdmt_transform(data, 10, GEOM[0], GEOM[1]))
        np.testing.assert_allclose(out[0], data.sum(axis=0), rtol=1e-5,
                                   atol=1e-4)

    def test_dm_range_pruning_matches_full_transform(self):
        # a min_delay-pruned plan must reproduce the corresponding rows
        # of the classic 0-anchored transform exactly (same tracks, same
        # summation order) while allocating fewer rows per iteration
        rng = np.random.default_rng(4)
        nchan, t, max_delay, min_delay = 16, 512, 40, 17
        data = rng.normal(0, 1, (nchan, t)).astype(np.float32)
        full = np.asarray(fdmt_transform(data, max_delay, GEOM[0], GEOM[1]))
        pruned = np.asarray(fdmt_transform(data, max_delay, GEOM[0],
                                           GEOM[1], min_delay=min_delay))
        assert pruned.shape == (max_delay - min_delay + 1, t)
        np.testing.assert_array_equal(pruned, full[min_delay:])
        plan_full = fdmt_plan(nchan, GEOM[0], GEOM[1], max_delay)
        plan_pruned = fdmt_plan(nchan, GEOM[0], GEOM[1], max_delay,
                                min_delay)
        rows = lambda p: sum(len(it["idx_low"]) for it in p.iterations)  # noqa: E731
        assert rows(plan_pruned) < rows(plan_full)

    def test_nonpow2_channels_padded(self):
        rng = np.random.default_rng(3)
        data = rng.normal(0, 1, (12, 256)).astype(np.float32)
        out = np.asarray(fdmt_transform(data, 10, GEOM[0], GEOM[1]))
        np.testing.assert_allclose(out[0], data.sum(axis=0), rtol=1e-5,
                                   atol=1e-4)


class TestSearch:
    def test_roundtrip_recovers_injected_dm(self):
        array, header = simulate_test_data(150, nchan=64, nsamples=4096,
                                           rng=7)
        args = (100, 200.0, header["fbottom"], header["bandwidth"],
                header["tsamp"])
        t_np = dedispersion_search(array, *args, backend="numpy")
        t_fd = dedispersion_search(array, *args, backend="jax",
                                   kernel="fdmt")
        dm_np = float(t_np["DM"][t_np.argbest()])
        dm_fd = float(t_fd["DM"][t_fd.argbest()])
        spacing = float(t_fd["DM"][1] - t_fd["DM"][0])
        assert abs(dm_fd - dm_np) <= 1.5 * spacing
        assert abs(dm_fd - 150.0) <= 2 * spacing

    def test_trial_grid_matches_plan_spacing(self):
        trial_dms, n_lo, n_hi = fdmt_trial_dms(64, 100, 200.0, *GEOM)
        assert n_hi > n_lo
        assert len(trial_dms) == n_hi - n_lo + 1
        # integer band-delay grid: delta_delay(dm)/tsamp is integral
        from pulsarutils_tpu.ops.plan import delta_delay

        n = delta_delay(trial_dms, GEOM[0], GEOM[0] + GEOM[1]) / GEOM[2]
        np.testing.assert_allclose(n, np.round(n), atol=1e-6)

    def test_capture_plane_shape(self):
        array, header = simulate_test_data(150, nchan=32, nsamples=2048,
                                           rng=8)
        t_fd, plane = dedispersion_search(
            array, 120, 180.0, header["fbottom"], header["bandwidth"],
            header["tsamp"], backend="jax", kernel="fdmt", show=True)
        assert plane.shape == (t_fd.nrows, array.shape[1])

    def test_odd_length_time_axis(self):
        # exercises the XLA-fallback / t_orig slicing for chunk lengths
        # no power-of-two tile divides
        array, header = simulate_test_data(150, nchan=32, nsamples=1900,
                                           rng=11)
        t_fd, plane = dedispersion_search(
            array, 120, 180.0, header["fbottom"], header["bandwidth"],
            header["tsamp"], backend="jax", kernel="fdmt", show=True)
        assert plane.shape == (t_fd.nrows, 1900)

    def test_pipeline_accepts_fdmt_kernel(self, tmp_path):
        from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
        from pulsarutils_tpu.models.simulate import disperse_array
        from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

        rng = np.random.default_rng(12)
        nchan, nsamples = 32, 8192
        array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
        array[:, 5000] += 4.0
        array = disperse_array(array, 150, 1200., 200., 0.0005)
        header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
                  "nsamples": nsamples, "tsamp": 0.0005,
                  "foff": 200. / nchan}
        fname = str(tmp_path / "t.fil")
        write_simulated_filterbank(fname, array, header, descending=True)
        hits, store = search_by_chunks(
            fname, dmmin=100, dmmax=200, backend="jax", kernel="fdmt",
            make_plots=False, output_dir=str(tmp_path))
        assert any(abs(info.dm - 150) < 5 for _, _, info, _ in hits)

    def test_pipeline_accepts_hybrid_kernel(self, tmp_path):
        # the streaming driver must run the hybrid end-to-end (exact
        # hits at coarse-sweep cost) just like any other kernel
        from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
        from pulsarutils_tpu.models.simulate import disperse_array
        from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

        rng = np.random.default_rng(14)
        nchan, nsamples = 32, 8192
        array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
        array[:, 5000] += 4.0
        array = disperse_array(array, 150, 1200., 200., 0.0005)
        header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
                  "nsamples": nsamples, "tsamp": 0.0005,
                  "foff": 200. / nchan}
        fname = str(tmp_path / "h.fil")
        write_simulated_filterbank(fname, array, header, descending=True)
        hits, store = search_by_chunks(
            fname, dmmin=100, dmmax=200, backend="jax", kernel="hybrid",
            make_plots=False, output_dir=str(tmp_path))
        assert any(abs(info.dm - 150) < 5 for _, _, info, _ in hits)

    def test_fdmt_requires_jax_backend(self):
        array, header = simulate_test_data(150, nchan=16, nsamples=512,
                                           rng=9)
        with pytest.raises(ValueError):
            dedispersion_search(array, 100, 200.0, header["fbottom"],
                                header["bandwidth"], header["tsamp"],
                                backend="numpy", kernel="fdmt")


class TestPlanTables:
    def test_indices_in_range(self):
        plan = fdmt_plan(64, GEOM[0], GEOM[1], 100)
        rows_in = plan.nchan_padded
        for it in plan.iterations:
            assert it["idx_low"].max() < rows_in
            assert it["idx_high"].max() < rows_in
            assert (it["shift"] >= 0).all()
            rows_in = len(it["idx_low"])

    def test_max_band_delay(self):
        n = max_band_delay(64, 200.0, *GEOM)
        from pulsarutils_tpu.ops.plan import delta_delay

        assert n == int(np.ceil(delta_delay(200.0, GEOM[0],
                                            GEOM[0] + GEOM[1]) / GEOM[2]))


@pytest.mark.parametrize("nchan,start_freq,bandwidth,dmmin,dmmax", [
    (32, 1200.0, 200.0, 50.0, 250.0),
    (64, 400.0, 100.0, 20.0, 120.0),    # low-frequency band, steep delays
    (48, 1500.0, 300.0, 100.0, 400.0),  # non-power-of-two channels
    (128, 800.0, 50.0, 10.0, 60.0),     # narrow band
])
def test_fdmt_hit_within_one_trial_across_geometries(nchan, start_freq,
                                                     bandwidth, dmmin, dmmax):
    """The tree's rounded tracks must localise a strong injection to
    within one trial spacing of the exact kernel, for varied band
    geometries (Zackay & Ofek bound the per-channel deviation)."""
    tsamp = 0.0005
    dm = 0.5 * (dmmin + dmmax)
    array, header = simulate_test_data(
        dm, tsamp=tsamp, nchan=nchan, nsamples=4096, start_freq=start_freq,
        bandwidth=bandwidth, signal=3.0, noise=0.3, rng=int(nchan))
    args = (dmmin, dmmax, header["fbottom"], header["bandwidth"], tsamp)
    t_exact = dedispersion_search(array, *args, backend="numpy")
    t_fdmt = dedispersion_search(array, *args, backend="jax", kernel="fdmt")
    best_exact = float(t_exact.best_row()["DM"])
    best_fdmt = float(t_fdmt.best_row()["DM"])
    dms = np.asarray(t_fdmt["DM"])
    spacing = float(dms[1] - dms[0]) if dms.size > 1 else 1.0
    assert abs(best_fdmt - best_exact) <= 1.5 * spacing, (
        best_fdmt, best_exact, spacing)
