"""A chunk too long for the device is searched in time tiles and the
answer is the untiled search's (ISSUE 40): the same small chunk searched
whole and with a planner budget that forces 2 and 4 tiles gives the same
table; the planner gives MeerTRAP's whole DM range tiles [4, 2, 1, 1, 1]
on a v5e and every accepted configuration none; the partial scorer is the
untiled scorers' at every ladder level."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import dispersion, generate  # noqa: E402

#: S/N of a tiled search against the untiled one: the same float32 values
#: summed in another order (a tile's partial sums folded in float32, the
#: tiles' in float64, against one float32 reduction over the row), so a
#: few units of 2^-24 x sqrt(samples) and no more
SNR_RTOL = 2e-6

NCHAN, T, TSAMP = 64, 1 << 14, 0.0005
BAND = {"fch1_mhz": 1398.4375, "foff_mhz": -3.125}


def _write(path, nbits, pulses, seed=5, hops=3):
    """A SIGPROC file of ``hops`` half-chunks of noise with the
    ``pulses`` ``(sample, dm, S/N)``, four samples wide, at the places
    given (``chipbench/generate.py`` draws its own)."""
    cfg = dict(BAND, nchans=NCHAN, nbits=nbits, tsamp_s=TSAMP)
    rng = np.random.default_rng(seed)
    n = hops * T // 2
    top = (1 << nbits) - 1
    mean, sd = (96.0, 16.0) if nbits == 8 else (1.5, 0.9)
    data = rng.normal(mean, sd, size=(n, NCHAN))
    fbottom, bandwidth = dispersion.band_edges(BAND["fch1_mhz"],
                                               BAND["foff_mhz"], NCHAN)
    for sample, dm, snr in pulses:
        shifts = dispersion.channel_shifts(dm, NCHAN, fbottom, bandwidth,
                                           TSAMP)
        amp = snr * sd * 2.0 / np.sqrt(NCHAN) / 4
        for k in range(4):
            data[sample + shifts + k, np.arange(NCHAN)] += amp
    codes = np.clip(np.rint(data), 0, top).astype(np.uint8)[:, ::-1]
    if nbits < 8:
        from pulsarutils_tpu.io.lowbit import pack_numpy

        codes = pack_numpy(codes.reshape(-1), nbits).reshape(n, -1)
    with open(path, "wb") as f:
        f.write(generate.sigproc_header(cfg))
        f.write(codes.tobytes())
    return path


PLANS = {
    "flat": {"dmmin": 20.0, "dmmax": 42.0},
    "tiered": {"dmmin": 20.0, "dmmax": 80.0, "dm_tiers": "smearing"},
    "tiered_boxcar": {"dmmin": 20.0, "dmmax": 80.0, "dm_tiers": "smearing",
                      "boxcar_max": 256},
}


def _kw(plan):
    return dict(chunk_length=T // 2 * TSAMP, backend="jax", kernel="hybrid",
                snr_threshold="certifiable", zero_dm=True, make_plots=False,
                resume=False, **PLANS[plan])


def _search(path, out, kw):
    from pulsarutils_tpu.obs import metrics
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    names = ("putpu_certified_chunks_total", "putpu_time_tiles_total",
             "putpu_tile_halo_samples_total", "putpu_host_fallbacks_total")
    before = [metrics.counter(n).value for n in names]
    hits, _ = search_by_chunks(path, output_dir=str(out), **kw)
    moved = [metrics.counter(n).value - b for n, b in zip(names, before)]
    return hits, dict(zip(names, moved))


def _same(whole, tiled):
    """The tiled run's hits are the untiled run's: trial DMs, peak
    samples, boxcars and exact flags equal, S/N within SNR_RTOL, the
    persisted cut-out the same window of the same values."""
    assert [h[:2] for h in whole] == [h[:2] for h in tiled]
    for (_, _, info0, table0), (_, _, info1, table1) in zip(whole, tiled):
        assert table0.colnames == table1.colnames
        for name in ("DM", "rebin", "peak", "exact", "downsample"):
            if name in table0.colnames:
                assert np.array_equal(table0[name], table1[name]), name
        for name in ("snr", "max", "std", "cert"):
            np.testing.assert_allclose(table1[name], table0[name],
                                       rtol=SNR_RTOL, atol=1e-6)
        assert (info0.dm, info0.width) == (info1.dm, info1.width)
        assert info0.snr == pytest.approx(info1.snr, rel=SNR_RTOL)
        assert info0.cutout_start == info1.cutout_start
        np.testing.assert_allclose(info1.allprofs, info0.allprofs,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(info1.disp_profile, info0.disp_profile,
                                   rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def untiled(tmp_path_factory):
    """``(path, hits, counters)`` of the untiled search, once per file."""
    cache = {}

    def get(nbits, plan, pulses):
        key = (nbits, plan, tuple(pulses))
        if key not in cache:
            work = tmp_path_factory.mktemp("whole")
            path = _write(str(work / "f.fil"), nbits, pulses)
            cache[key] = (path,) + _search(path, work / "out", _kw(plan))
        return cache[key]

    return get


#: one pulse in the second chunk's first hop, at a DM of the flat plan
#: and of the tiered plan's native tier
MID = ((T // 2 + 3000, 33.0, 30.0),)


@pytest.mark.parametrize("tiles", [2, 4])
@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("nbits", [8, 2])
def test_tiled_search_is_the_untiled_one(untiled, force_time_tiles,
                                         tmp_path, nbits, plan, tiles):
    path, whole, counted = untiled(nbits, plan, MID)
    assert whole and counted["putpu_time_tiles_total"] == 0
    tile_plan = force_time_tiles(path, _kw(plan), tiles)
    tiled, moved = _search(path, tmp_path / "out", _kw(plan))
    _same(whole, tiled)
    # two chunks, each tier's tiles swept once; certified chunks certify
    # in both; nothing left the device path
    assert moved["putpu_time_tiles_total"] == 2 * sum(
        t.tiles for t in tile_plan if t.tiles > 1)
    assert moved["putpu_tile_halo_samples_total"] == 2 * sum(
        t.tiles * t.halo for t in tile_plan)
    assert moved["putpu_certified_chunks_total"] == \
        counted["putpu_certified_chunks_total"]
    assert moved["putpu_host_fallbacks_total"] == 0


@pytest.mark.parametrize("what,pulses,tiles", [
    # the track (53 samples at DM 35) lies across the edge of the native
    # tier's second and third tile of 4,096 in the first chunk
    ("crosses_a_tile_edge", ((2 * 4096 - 20, 35.0, 30.0),), 4),
    # the track runs over the first chunk's end into its start: the last
    # tile's halo is the chunk's first samples
    ("wraps_the_chunks_end", ((T - 25, 35.0, 30.0),), 4),
    # DM 60 is the 2x tier's, which this budget leaves whole beside a
    # native tier in two tiles
    ("in_a_whole_deep_tier", ((T // 2 + 3000, 60.0, 30.0),), 2),
])
def test_where_the_pulse_lies(untiled, force_time_tiles, tmp_path, what,
                              pulses, tiles):
    path, whole, _ = untiled(8, "tiered_boxcar", pulses)
    assert whole, what
    tile_plan = force_time_tiles(path, _kw("tiered_boxcar"), tiles)
    if what == "in_a_whole_deep_tier":
        assert [t.tiles for t in tile_plan] == [2, 1]
    tiled, _ = _search(path, tmp_path / "out", _kw("tiered_boxcar"))
    _same(whole, tiled)


def test_the_tile_plan_is_the_searching_devices_business(untiled):
    """A planner that states no device (the fleet coordinator) plans no
    tiles and touches none; one that states a small device plans them,
    and both meet on one resume ledger: the fingerprint does not name
    the tiles."""
    from pulsarutils_tpu.pipeline import search_pipeline as sp

    path, _, _ = untiled(8, "tiered", MID)
    kw = {k: v for k, v in _kw("tiered").items()
          if k not in ("make_plots", "resume")}
    plain = sp.plan_survey(path, **kw)
    assert plain["tile_plan"] is None
    roomy = sp.plan_survey(path, device_memory_bytes=1 << 34, **kw)
    assert roomy["tile_plan"] is None
    small = sp.plan_survey(path, device_memory_bytes=1 << 22, **kw)
    assert small["tile_plan"][0].tiles > 1
    assert (plain["fingerprint"] == roomy["fingerprint"]
            == small["fingerprint"])


def test_a_tiled_chunk_has_no_plane_to_plot(untiled, force_time_tiles,
                                            tmp_path):
    path, _, _ = untiled(8, "flat", MID)
    force_time_tiles(path, _kw("flat"), 2)
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    with pytest.raises(ValueError, match="time tiles"):
        search_by_chunks(path, output_dir=str(tmp_path),
                         **dict(_kw("flat"), make_plots="hits"))


def test_a_tiled_tiers_cleans_lay_the_tiers_below_it(rng):
    """The deepest tiled tier's tile cleans carry the chain on and lay
    the deeper tiers' whole arrays: bit for bit what each of those tiers
    cleaned from the bytes on its own is, in whatever order the tiles are
    swept, handed over once and only when every tile has been."""
    import jax.numpy as jnp

    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.pipeline.time_tiles import (TiledTierArray,
                                                     chunk_stats_program,
                                                     wrap_rows_program)

    raw = jnp.asarray(rng.integers(60, 140, (T, NCHAN), dtype=np.uint8))
    mask = jnp.zeros(NCHAN, bool).at[5].set(True)
    unpack = (device_unpack_block, 8, NCHAN, True)
    stats = chunk_stats_program(unpack, T)(raw, mask)
    wrapped = wrap_rows_program()(raw)

    def source(chain, tiles, halo, lay=()):
        return TiledTierArray(wrapped, T, stats, mask, unpack, True, chain,
                              tiles, halo, lay=lay)

    layer = source((2,), 4, 256, lay=(4, 16))
    plain = source((2,), 4, 256)
    for i in (2, 0, 3):
        assert np.array_equal(np.asarray(layer.tile(i)),
                              np.asarray(plain.tile(i)))
    with pytest.raises(RuntimeError, match=r"tiles \[1\]"):
        layer.laid()
    layer.tile(1)
    for arr, chain in zip(layer.laid(), ((2, 4), (2, 4, 16))):
        whole = np.asarray(source(chain, 1, 0).tile(0))
        assert np.array_equal(np.asarray(arr), whole)
    assert layer.laid() is None


# -- a hit's cut-out, summed where it lies (ISSUE 50) ----------------------

CUTOUT_COUNTERS = ("putpu_cutout_readback_bytes_total",
                   "putpu_bytes_readback_total",
                   "putpu_cutout_device_decim_total")


def _counted(run):
    from pulsarutils_tpu.obs import metrics

    before = [metrics.counter(n).value for n in CUTOUT_COUNTERS]
    out = run()
    return out, [metrics.counter(n).value - b
                 for n, b in zip(CUTOUT_COUNTERS, before)]


@pytest.mark.parametrize("where,peak", [("inside_a_tile", 5000),
                                        ("wraps_the_chunks_end", T - 50)])
def test_a_tiled_tiers_cutout_is_summed_where_it_lies(rng, tmp_path, where,
                                                      peak):
    """A window of a tiled tier over the store's budget (6,536 samples x
    64 channels for 2^15 elements: sums of 13) is what the host path
    makes of the whole tier read back: same start, decimation, shape and
    dtype, the values within the tiles' tolerance; the sums are what is
    counted as read back, not the window."""
    import dataclasses

    import jax.numpy as jnp

    from pulsarutils_tpu.io.candidates import CandidateStore
    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.pipeline.pulse_info import PulseInfo
    from pulsarutils_tpu.pipeline.time_tiles import (TiledTierArray,
                                                     chunk_stats_program,
                                                     wrap_rows_program)
    from pulsarutils_tpu.utils.table import ResultTable

    raw = jnp.asarray(rng.integers(60, 140, (T, NCHAN), dtype=np.uint8))
    mask = jnp.zeros(NCHAN, bool).at[5].set(True)
    unpack = (device_unpack_block, 8, NCHAN, True)
    stats = chunk_stats_program(unpack, T)(raw, mask)
    tier = TiledTierArray(wrap_rows_program()(raw), T, stats, mask, unpack,
                          True, (), 2, 4096)
    # DM 350 crosses 400-500 MHz in 3,268 samples of 1 ms
    info = PulseInfo(allprofs=tier, nbin=T, nchan=NCHAN, start_freq=400.0,
                     bandwidth=100.0, pulse_freq=1.0 / (T * 1e-3))
    table = ResultTable({"DM": np.array([350.0]), "snr": np.array([20.0]),
                         "peak": np.array([peak]), "rebin": np.array([1])})
    store = CandidateStore(str(tmp_path), None)
    store.WATERFALL_BUDGET = 1 << 15
    cut, moved = _counted(lambda: store.trim_waterfall(info, table))
    host, nothing = _counted(lambda: store.trim_waterfall(
        dataclasses.replace(info, allprofs=np.asarray(tier)), table))
    assert nothing == [0, 0, 0]
    assert (cut.cutout_start, cut.cutout_decim) \
        == (host.cutout_start, host.cutout_decim) == ((peak - 1634) % T, 13)
    assert isinstance(cut.allprofs, np.ndarray)
    assert (cut.allprofs.shape, cut.allprofs.dtype) \
        == (host.allprofs.shape, host.allprofs.dtype) \
        == ((NCHAN, 6536 // 13), np.float32)
    np.testing.assert_allclose(cut.allprofs, host.allprofs,
                               rtol=1e-5, atol=1e-6)
    assert moved == [cut.allprofs.nbytes, cut.allprofs.nbytes, 1]
    assert cut.allprofs.nbytes * 13 <= NCHAN * 6536 * 4


@pytest.mark.parametrize("tiles", [1, 2])
def test_a_searchs_cutouts_over_the_budget(untiled, force_time_tiles,
                                           tmp_path, monkeypatch, tiles):
    """Through ``search_by_chunks`` with the store's budget under the
    hit's window: the whole chunk's record (a device array, cut and
    summed in one program) and the tiled chunk's (a re-cleaned stretch,
    then summed) are NumPy's block sums of the same window of the
    untiled chunk, which the default budget reads back whole."""
    from pulsarutils_tpu.io.candidates import CandidateStore
    from pulsarutils_tpu.ops.rebin import quick_resample

    path, whole, _ = untiled(8, "flat", MID)
    monkeypatch.setattr(CandidateStore, "WATERFALL_BUDGET", 1 << 12)
    if tiles > 1:
        force_time_tiles(path, _kw("flat"), tiles)
    (hits, _), moved = _counted(
        lambda: _search(path, tmp_path / "out", _kw("flat")))
    assert [h[:2] for h in hits] == [h[:2] for h in whole] and hits
    for (_, _, info, _), (_, _, info0, _) in zip(hits, whole):
        decim, n = info.cutout_decim, info.allprofs.shape[1]
        assert decim > 1 and info0.cutout_start is None
        cols = (info.cutout_start + np.arange(n * decim)) % T
        want = quick_resample(info0.allprofs[:, cols], decim)
        assert (info.allprofs.shape, info.allprofs.dtype) \
            == (want.shape, want.dtype)
        np.testing.assert_allclose(info.allprofs, want, rtol=1e-5,
                                   atol=1e-6)
    total = sum(h[2].allprofs.nbytes for h in hits)
    assert moved == [total, total, len(hits)]


# -- the planner ----------------------------------------------------------

V5E_BYTES = int(15.75 * 2**30)


def _config_geometry(name):
    """``plan_time_tiles``' arguments for a configuration of the
    benchmark, from the program's own planners."""
    from pulsarutils_tpu.ops.plan import dedispersion_plan, dm_tier_plan
    from pulsarutils_tpu.ops.search import boxcar_ladder

    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    nchan, tsamp = cfg["nchans"], cfg["tsamp_s"]
    fbottom, bandwidth = dispersion.band_edges(cfg["fch1_mhz"],
                                               cfg["foff_mhz"], nchan)
    boxcar = cfg.get("boxcar_max")
    if "--dm-tiers" in cfg["cli_flags"]:
        tiers = dm_tier_plan(nchan, cfg["dmmin"], cfg["dmmax"], fbottom,
                             bandwidth, tsamp, abs(cfg["foff_mhz"]),
                             boxcar_max=boxcar)
        geometry = [(t.downsample, t.sample_time, t.trial_dms, t.windows)
                    for t in tiers]
    else:
        geometry = [(1, tsamp, dedispersion_plan(
            nchan, cfg["dmmin"], cfg["dmmax"], fbottom, bandwidth, tsamp),
            boxcar_ladder(boxcar))]
    # as ``search_pipeline._tile_geometry`` states it: the packed chunk
    # with its first 2^15 frames once more at its end, and the prefetch
    resident = ((2 * cfg["chunk_samples"] + min(1 << 15, cfg["chunk_samples"]))
                * nchan * cfg["nbits"] // 8)
    return (nchan, cfg["chunk_samples"], fbottom, bandwidth, geometry,
            resident)


def test_meertraps_whole_range_on_a_v5e():
    from pulsarutils_tpu.parallel.stream import plan_time_tiles

    *args, resident = _config_geometry("meertrap_lband_8bit_fulldm")
    assert [len(g[2]) for g in args[4]] == [5183, 2591, 2591, 2591, 1846]
    budget = V5E_BYTES * 15 // 16
    plan = plan_time_tiles(*args, budget, resident)
    assert [t.tiles for t in plan] == [4, 2, 1, 1, 1]
    assert [(t.own, t.halo) for t in plan[:2]] == [(1 << 17, 8192)] * 2
    assert all(t.halo == 0 for t in plan[2:])
    assert max(t.bytes for t in plan) <= budget
    # a tile's axis is one the kernels' time tiles divide
    from pulsarutils_tpu.ops.fdmt import _pick_fdmt_tile

    assert _pick_fdmt_tile(plan[0].own + plan[0].halo) == 8192


@pytest.mark.parametrize("name", [
    "rehearsal_1024ch_2bit", "htru_bpsr_lowdm", "htru_bpsr_fulldm",
    "htru_bpsr_fulldm_boxcar4096", "meertrap_lband_8bit"])
def test_accepted_configurations_plan_no_tiles(name):
    from pulsarutils_tpu.parallel.stream import plan_time_tiles

    *args, resident = _config_geometry(name)
    plan = plan_time_tiles(*args, V5E_BYTES * 15 // 16, resident)
    assert [t.tiles for t in plan] == [1] * len(plan)
    assert all(t.halo == 0 for t in plan)


def test_a_tier_that_cannot_be_tiled_is_refused_at_plan_time():
    from pulsarutils_tpu.parallel.stream import plan_time_tiles

    *args, resident = _config_geometry("tiny_cpu_8bit")
    with pytest.raises(ValueError, match=r"DM tier \d .* bytes"):
        plan_time_tiles(*args, resident + 100_000, resident)
    assert [t.tiles for t in plan_time_tiles(*args, None, resident)] == [1, 1]


# -- the partial scorer -----------------------------------------------------

@pytest.mark.parametrize("tiles", [1, 3, 5])
@pytest.mark.parametrize("boxcar", [None, 64, 256])
def test_partial_scores_are_the_whole_rows(boxcar, tiles):
    from pulsarutils_tpu.ops.score_partials import (combine_partials,
                                                    score_partials)
    from pulsarutils_tpu.ops.search import (boxcar_ladder,
                                            cert_profile_scores,
                                            score_profiles_stacked)

    rng = np.random.default_rng(boxcar or 1)
    total = 15 * 2048
    windows = boxcar_ladder(boxcar)
    plane = (rng.normal(size=(9, total)) * 3 + 50).astype(np.float32)
    own = total // tiles
    plane[2, own - 2:own + 2] += 20      # across a tile's edge
    plane[4, -2:] += 15                  # across the row's end
    plane[4, :2] += 15
    plane[6, 5 * 512:5 * 512 + 300] += 4  # a wide one
    want = score_profiles_stacked(plane.astype(np.float64), xp=np,
                                  windows=windows)
    cert = cert_profile_scores(plane.astype(np.float64), xp=np,
                               windows=windows)
    parts = [score_partials(plane[:, i * own:(i + 1) * own], np, windows,
                            total, with_cert=True) for i in range(tiles)]
    got = combine_partials(parts, own, windows, total, with_cert=True)
    np.testing.assert_allclose(got[:3], want[:3], rtol=SNR_RTOL)
    assert np.array_equal(got[3], want[3])   # the window
    assert np.array_equal(got[4], want[4])   # the peak's place
    np.testing.assert_allclose(got[5], cert, rtol=SNR_RTOL)


def test_the_one_pass_kernels_partials_are_the_xla_scorers():
    """``score_rows`` in partial mode (interpreted here) emits what
    ``score_partials`` does, column for column."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.score_pallas import score_partials_pallas
    from pulsarutils_tpu.ops.score_partials import (combine_partials,
                                                    score_partials)
    from pulsarutils_tpu.ops.search import boxcar_ladder

    rng = np.random.default_rng(11)
    total, own, halo = 1 << 14, 1 << 13, 1 << 11
    windows = boxcar_ladder(128)
    plane = (rng.normal(size=(11, total + halo)) + 5).astype(np.float32)
    parts, wants = [], []
    for i in range(2):
        tile = plane[:, i * own:i * own + own + halo]  # the halo is not read
        parts.append(np.asarray(score_partials_pallas(
            jnp.asarray(tile), own, total, with_cert=True, interpret=True,
            windows=windows)))
        wants.append(score_partials(tile[:, :own], np, windows, total,
                                    with_cert=True))
    got = combine_partials(parts, own, windows, total, with_cert=True)
    want = combine_partials(wants, own, windows, total, with_cert=True)
    np.testing.assert_allclose(got[:3], want[:3], rtol=SNR_RTOL)
    assert np.array_equal(got[3:5], want[3:5])
    np.testing.assert_allclose(got[5], want[5], rtol=SNR_RTOL)
