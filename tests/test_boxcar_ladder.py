"""The boxcar ladder beyond 8 samples (``PUsearchfrb --boxcar-max``, ISSUE
32): the ladder rule; the scorers against a straight restatement of the
equations (``chipbench/reference_boxcar.py:score_row``, which imports
nothing of the program) for every ladder length 4..13, NumPy, XLA and the
one-pass Pallas kernel in interpret mode; the search against the plain
reference, flat and tiered; tiers x ladder through ``PUsearchfrb``; a wide
hit's products.
"""
import json
import os

import numpy as np
import pytest

from chipbench import generate, reference_boxcar
from chipbench import run as harness
from pulsarutils_tpu.ops.search import (SEARCH_WINDOWS, boxcar_ladder,
                                        cert_profile_scores,
                                        cert_wide_windows, check_windows,
                                        score_profiles,
                                        score_profiles_chunked,
                                        scored_windows)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LENGTHS = list(range(4, 14))


def _ladder(length):
    return tuple(1 << j for j in range(length))


# -- the rule ---------------------------------------------------------------

def test_the_ladder_rule():
    assert boxcar_ladder() == boxcar_ladder(None, 16) == SEARCH_WINDOWS
    assert boxcar_ladder(8) == SEARCH_WINDOWS
    assert boxcar_ladder(4096) == _ladder(13)
    # HTRU's six tiers at Heimdall's boxcar_max: 63 windows a chunk
    per_tier = [len(boxcar_ladder(4096, 2 ** k)) for k in range(6)]
    assert per_tier == [13, 12, 11, 10, 9, 8] and sum(per_tier) == 63
    # no tier loses a width of the default ladder
    assert boxcar_ladder(64, 32) == SEARCH_WINDOWS
    for bad in (0, 4, 12, 100, 4096.5):
        with pytest.raises(ValueError, match="boxcar_max"):
            boxcar_ladder(bad)
    for bad in ((1, 2, 4), (1, 2, 4, 8, 32), (2, 4, 8, 16), (1, 2, 3, 4)):
        with pytest.raises(ValueError, match="windows"):
            check_windows(bad)
    assert check_windows(None) == check_windows([1, 2, 4, 8]) \
        == SEARCH_WINDOWS


def test_the_tiers_carry_their_ladder():
    from pulsarutils_tpu.ops.plan import dm_tier_plan

    args = (1024, 0.0, 1000.0, 1182.0, 400.0, 64e-6, 0.390625)
    assert all(t.windows == SEARCH_WINDOWS for t in dm_tier_plan(*args))
    tiers = dm_tier_plan(*args, boxcar_max=4096)
    assert [t.windows for t in tiers] == [boxcar_ladder(4096, t.downsample)
                                          for t in tiers]
    # every tier reaches 4,096 samples of the file
    assert {t.windows[-1] * t.downsample for t in tiers} == {4096}


def test_a_level_with_under_64_blocks_is_not_scored():
    t = 32 * 16  # the width-16 level would have 32 blocks
    assert scored_windows(_ladder(5), t) == SEARCH_WINDOWS
    assert scored_windows(_ladder(5), 64 * 16) == _ladder(5)
    # the default four are scored whatever the length is
    assert scored_windows(None, 16) == SEARCH_WINDOWS
    assert cert_wide_windows(None, 1 << 20) == ()
    assert cert_wide_windows(_ladder(4), 1 << 20) == ()
    assert cert_wide_windows(_ladder(7), 1 << 20) == (8, 16, 32, 64)
    assert cert_wide_windows(_ladder(7), 64 * 16) == (8, 16)
    rng = np.random.default_rng(5)
    for t_, scored in ((t, False), (64 * 16, True)):
        plane = rng.standard_normal((2, t_))
        plane[0, 160:176] += 3.0  # a 16-sample pulse on a width-16 block
        long = score_profiles(plane, windows=_ladder(5))
        short = score_profiles(plane)
        assert all(np.array_equal(a, b)
                   for a, b in zip(long, short)) == (not scored)
        assert (long[3][0] == 16) == scored


# -- the scorers, every ladder length --------------------------------------

def _plane(length, rows=8, seed=0, dtype=np.float64, tiles=1):
    """``rows`` noise series of ``64 * 2^(length-1)`` samples (the widest
    level has just 64 blocks; ``tiles`` = 3: three times that and at least
    three of the one-pass kernel's tiles), on a DC offset, with pulses of
    the widest window, of three quarters of it, of a sixth of it and
    none."""
    t = 64 << (length - 1)
    if tiles > 1:
        t = tiles * max(t, 1024)
    widest = 1 << (length - 1)
    rng = np.random.default_rng(1000 * length + seed)
    plane = rng.standard_normal((rows, t)) + 50.0
    for r, (width, phase) in enumerate([
            (widest, 0), (widest, widest // 2), (3 * widest // 4, 3),
            (max(widest // 6, 1), 1), (2 * widest, widest // 4)]):
        pos = 5 * widest + phase + 7 * widest * r
        plane[r, pos:pos + width] += 12.0 / np.sqrt(width)
    return plane.astype(dtype)


@pytest.mark.parametrize("length", LENGTHS)
def test_numpy_scorer_is_the_restated_equations(length):
    ladder = _ladder(length)
    plane = _plane(length)
    maxv, std, snr, win, peak = score_profiles(plane, windows=ladder)
    for r in range(plane.shape[0]):
        want = reference_boxcar.score_row(plane[r], list(ladder))
        assert (int(win[r]), int(peak[r])) == want[1:], (length, r)
        assert snr[r] == pytest.approx(want[0], rel=1e-12)
    # row 0's pulse is as wide as the widest window, on a block of it (with
    # 64 blocks the pulse's own share of the level's std can hand the win
    # to the level below)
    assert win[0] in (ladder[-1], ladder[-1] // 2)
    # the default ladder through the same argument is the default call
    for a, b in zip(score_profiles(plane, windows=SEARCH_WINDOWS),
                    score_profiles(plane)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("length", LENGTHS)
def test_xla_scorer_picks_the_reference_window_and_peak(length):
    import jax.numpy as jnp

    ladder = _ladder(length)
    plane = _plane(length, dtype=np.float32)
    got = np.asarray(score_profiles_chunked(jnp.asarray(plane), jnp,
                                            windows=ladder))
    want = score_profiles(plane.astype(np.float64), windows=ladder)
    assert np.array_equal(got[3], want[3]) and np.array_equal(got[4],
                                                               want[4])
    assert np.allclose(got[2], want[2], rtol=2e-4)


@pytest.mark.parametrize("length", LENGTHS)
def test_pallas_scorer_is_exact_in_window_and_peak(length):
    """Interpret mode, against ``score_profiles`` + ``cert_profile_scores``:
    window and peak exact, values to float32 reduction order."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.score_pallas import (pick_score_tile,
                                                  score_plane_pallas)

    ladder = _ladder(length)
    plane = _plane(length, dtype=np.float32, tiles=3)
    tile = pick_score_tile(plane.shape[1], ladder[-1])
    # several tiles: the half-stride captures cross their boundaries
    assert tile % ladder[-1] == 0 and plane.shape[1] // tile >= 3
    got = np.asarray(score_plane_pallas(jnp.asarray(plane), with_cert=True,
                                        interpret=True, windows=ladder))
    want = np.asarray(score_profiles_chunked(jnp.asarray(plane), jnp,
                                             with_cert=True,
                                             windows=ladder))
    assert got.shape == want.shape == (6, plane.shape[0])
    assert np.array_equal(got[3], want[3]), (got[3], want[3])
    assert np.array_equal(got[4], want[4])
    assert np.allclose(got[[0, 1, 2, 5]], want[[0, 1, 2, 5]], rtol=2e-4)


def test_pallas_tiles_are_multiples_of_the_widest_window():
    from pulsarutils_tpu.ops.score_pallas import pick_score_tile

    t = 1 << 19
    assert pick_score_tile(t) == pick_score_tile(t, 4096) == 16384
    assert pick_score_tile(3 * 4096, 4096) == 4096
    assert pick_score_tile(3 * 4096) == 4096
    assert pick_score_tile(3 * 2048, 4096) == 0  # the XLA scorer runs
    assert pick_score_tile(3 * 2048, 1024) == 2048


@pytest.mark.parametrize("length", [5, 9, 13])
def test_wide_capture_is_never_below_the_block_score(length):
    """On one series the half-stride capture of width w holds every block
    of width w, over the same denominator."""
    ladder = _ladder(length)
    plane = _plane(length, seed=3)
    *_, snr, win, _ = score_profiles(plane, windows=ladder)
    cert = cert_profile_scores(plane, windows=ladder)
    wide = win >= 8
    assert wide.any()
    assert np.all(cert[wide] >= snr[wide] * (1 - 1e-12))
    # and the default ladder's certificate is the one it was
    assert np.array_equal(cert_profile_scores(plane),
                          cert_profile_scores(plane, windows=SEARCH_WINDOWS))


# -- the search -------------------------------------------------------------

GEOM = (1200.0, 200.0, 0.0005)


def _dispersed(nchan, t, dm, width, amp, seed):
    from pulsarutils_tpu.ops.plan import dedispersion_shifts

    rng = np.random.default_rng(seed)
    data = (np.abs(rng.standard_normal((nchan, t))) * 0.5).astype(np.float32)
    shifts = np.rint(np.asarray(dedispersion_shifts(nchan, dm,
                                                    *GEOM))).astype(int)
    pos = t // 2 + 3
    for c in range(nchan):
        idx = (pos + shifts[c] + np.arange(width)) % t
        data[c, idx] += amp / width
    return data


@pytest.mark.parametrize("length", LENGTHS)
def test_hybrid_search_is_the_numpy_backend(length):
    """A flat search with the ladder as an argument: the hybrid's best row
    is the float64 backend's, exact, at the pulse's width."""
    from pulsarutils_tpu.ops.search import dedispersion_search

    ladder = _ladder(length)
    nchan, t = 16, 256 << (length - 1)  # 256 blocks at the widest level
    width = ladder[-1]
    data = _dispersed(nchan, t, 110.0, width, 0.9 * np.sqrt(width), length)
    ref = dedispersion_search(data, 100.0, 120.0, *GEOM, backend="numpy",
                              windows=ladder)
    hyb = dedispersion_search(data, 100.0, 120.0, *GEOM, backend="jax",
                              kernel="hybrid", windows=ladder)
    j = ref.argbest()
    assert hyb.argbest() == j and bool(hyb["exact"][j])
    assert int(ref["rebin"][j]) in (width, width // 2, 2 * width)
    for name in ("rebin", "peak"):
        assert int(hyb[name][j]) == int(ref[name][j])
    assert float(hyb["snr"][j]) == pytest.approx(float(ref["snr"][j]),
                                                 rel=1e-4)
    exact = np.asarray(hyb["exact"])
    assert np.array_equal(np.asarray(hyb["rebin"])[exact],
                          np.asarray(ref["rebin"])[exact])


def test_fourier_kernel_refuses_a_longer_ladder():
    from pulsarutils_tpu.ops.search import dedispersion_search

    data = np.zeros((8, 256), np.float32)
    with pytest.raises(ValueError, match="fourier"):
        dedispersion_search(data, 100.0, 120.0, *GEOM, backend="jax",
                            kernel="fourier", windows=_ladder(6))


# -- through the driver, against the plain reference -------------------------

def _load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


def _cell(dmmax, fraction, width, boxcar_max, **cfg_kw):
    """The CPU rehearsal's geometry (64 ch x 2^14, 0.5 ms, 1,200-1,400 MHz:
    smearing one sample at DM 42.35) from DM 0, and one pulse.  A tier
    has about 32 trials here and a wide pulse's S/N is flat over as many
    rows as it is samples wide: the reference compares the whole tier
    (``reference_half_rows`` 64), and a tiered cell's pulse sits in the
    middle of the x4 tier (DM 84.7-160), no wider than the way to its
    edge."""
    cfg = dict(_load("configs", "tiny_cpu_rehearsal.json"), dmmin=0.0,
               dmmax=dmmax, boxcar_max=boxcar_max, reference_half_rows=64,
               **cfg_kw)
    traffic = dict(_load("traffic", "backlog_sparse.json"),
                   pulse_dm_fraction=fraction, pulse_widths=[width])
    return cfg, traffic


def _search(tmp_path, cfg, traffic, backend, seed=3000000019, name="o",
            **kw):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, seed)
    flags = (dict(kernel="hybrid", snr_threshold="certifiable")
             if backend == "jax" else dict(snr_threshold=8.0))
    hits, store = search_by_chunks(
        path, chunk_length=info["hop"] * cfg["tsamp_s"], dmmin=cfg["dmmin"],
        dmmax=cfg["dmmax"], backend=backend,
        output_dir=str(tmp_path / name), make_plots=False, progress=False,
        boxcar_max=cfg["boxcar_max"], **dict(flags, **kw))
    return info, path, hits


def _against_reference(tmp_path, cfg, info, path, tolerance):
    pulse, hop = info["pulses"][0], info["hop"]
    istart = 2 * hop
    ref = reference_boxcar.best_row(path, cfg, istart, pulse["dm"],
                                    control=True)
    _, tables, done, _ = harness.persisted(str(tmp_path / "o"))
    assert done == {0, hop, 2 * hop}
    table = tables[istart]
    best = table.best_row()
    assert table.argbest() == ref["row"]
    assert (float(best["DM"]), int(best["peak"]), int(best["rebin"])) == (
        ref["DM"], ref["peak"], ref["rebin"])
    rms, nrows = harness.rms_gap(table, ref["rows"])
    assert nrows >= 3 and rms <= tolerance
    ctl_rms, _ = harness.rms_gap(harness.rows_as_table(
        ref["control"]["rows"]), ref["rows"])
    assert ctl_rms > 1e-5
    return ref, best


@pytest.mark.parametrize("tiers", [None, "smearing"])
@pytest.mark.parametrize("length", LENGTHS)
def test_numpy_search_is_the_plain_reference(tmp_path, length, tiers):
    """Flat (DM 0-40, one tier) and tiered (DM 0-160: x1, x2, x4), every
    ladder length; at 2^14-sample chunks the cut-off ends a flat ladder at
    256, in the program and in the reference alike."""
    boxcar_max = 1 << (length - 1)
    width = min(boxcar_max, 64 if tiers is None else 32)
    cfg, traffic = (_cell(40.0, [0.49, 0.51], width, boxcar_max)
                    if tiers is None else
                    _cell(160.0, [0.77, 0.79], width, boxcar_max))
    info, path, hits = _search(tmp_path, cfg, traffic, "numpy",
                               dm_tiers=tiers)
    ref, best = _against_reference(tmp_path, cfg, info, path, 1e-9)
    assert ref["windows"][-1] == max(8, boxcar_max // ref["downsample"])
    found = int(best["rebin"]) * ref["downsample"]
    assert found in (width // 2, width, 2 * width), (found, width)


@pytest.mark.parametrize("tiers,boxcar_max", [(None, 256), ("smearing", 256),
                                              ("smearing", 32)])
def test_hybrid_search_is_the_plain_reference(tmp_path, tiers, boxcar_max):
    cfg, traffic = (_cell(40.0, [0.49, 0.51], 64, boxcar_max)
                    if tiers is None else
                    _cell(160.0, [0.77, 0.79], 32, boxcar_max))
    info, path, hits = _search(tmp_path, cfg, traffic, "jax",
                               dm_tiers=tiers)
    ref, best = _against_reference(tmp_path, cfg, info, path, 1e-5)
    assert bool(best["exact"])
    # the two signal-free chunks certified under the longer ladder
    assert [h[0] for h in hits] == [2 * info["hop"]]


def test_without_the_flag_nothing_changes(tmp_path):
    """``boxcar_max=8`` is the default ladder in every tier: the same
    tables byte for byte, under another fingerprint; absent, the plan and
    its fingerprint are the ones they were."""
    from pulsarutils_tpu.pipeline.search_pipeline import plan_survey

    cfg, traffic = _cell(160.0, [0.60, 0.62], 4, None)
    trees = {}
    for name, boxcar_max in (("absent", None), ("eight", 8)):
        cfg["boxcar_max"] = boxcar_max
        info, path, hits = _search(tmp_path, cfg, traffic, "jax", name=name,
                                   dm_tiers="smearing")
        _, tables, done, _ = harness.persisted(str(tmp_path / name))
        trees[name] = {k: {c: np.asarray(t[c]).tobytes()
                           for c in t.colnames} for k, t in tables.items()}
        assert len(done) == 3 and len(tables) >= 1
    assert trees["absent"] == trees["eight"]

    def plan(**kw):
        return plan_survey(path, chunk_length=4.096, dmmin=0.0, dmmax=160.0,
                           snr_threshold=8.0, dm_tiers="smearing", **kw)

    absent, eight, wide = plan(), plan(boxcar_max=8), plan(boxcar_max=64)
    assert absent["windows"] is None
    assert eight["windows"] == SEARCH_WINDOWS
    assert len({absent["fingerprint"], eight["fingerprint"],
                wide["fingerprint"]}) == 3
    assert [t["tier"].windows[-1] for t in wide["tiers"]] == [64, 32, 16]


def test_each_tier_resolves_its_threshold_for_its_ladder(tmp_path):
    from pulsarutils_tpu.pipeline.search_pipeline import plan_survey

    cfg, traffic = _cell(160.0, [0.60, 0.62], 4, None)
    path = str(tmp_path / "f.fil")
    generate.generate(path, cfg, traffic, 5)

    def thresholds(**kw):
        sp = plan_survey(path, chunk_length=4.096, dmmin=0.0, dmmax=160.0,
                         kernel="hybrid", snr_threshold="certifiable",
                         dm_tiers="smearing", **kw)
        assert [t["search_snr_floor"] for t in sp["tiers"]] == [
            t["snr_threshold"] for t in sp["tiers"]]
        return [t["snr_threshold"] for t in sp["tiers"]]

    short, wide = thresholds(), thresholds(boxcar_max=256)
    # the bound's minimum sits at widths 1-3 under either ladder, so the
    # certifiable floors do not move (docs/hybrid_calibration.md)
    assert wide == short


@pytest.mark.parametrize("kw,named", [
    (dict(mesh=object()), "mesh"),
    (dict(period_search=True), "period_search"),
    (dict(plane_consumer=lambda *a: None), "plane_consumer"),
])
def test_boxcar_max_refuses_what_it_cannot_carry(tmp_path, kw, named):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    with pytest.raises(ValueError, match=f"boxcar_max.*{named}"):
        search_by_chunks(str(tmp_path / "absent.fil"), boxcar_max=64, **kw)


# -- tiers x ladder through PUsearchfrb --------------------------------------

#: 256 ch x 0.78125 MHz over 1,200-1,400 MHz at 0.5 ms: the smearing
#: reaches one sample at DM 169.4, so DM 0-600 is three tiers (x1, x2 to
#: 338.8, x4) of about 259, 130 and 100 trials: wide enough in band delay
#: that a pulse of 64 tier samples belongs to one of them
WIDE_BAND = dict(nchans=256, fch1_mhz=1399.609375, foff_mhz=-0.78125)


@pytest.mark.parametrize("factor,tier_width,fraction", [
    (1, 64, [0.14, 0.15]), (2, 16, [0.41, 0.42]), (4, 1, [0.83, 0.84])])
def test_pulses_found_at_their_width_in_their_tier(tmp_path, caplog, factor,
                                                   tier_width, fraction):
    """Widths of 64, 16 and 1 tier samples, each in another tier.  The
    widest goes to the tier with the most samples: ``max / std`` of a level
    of n blocks cannot pass sqrt(n), so at these sizes (4,096 samples in
    the x4 tier) a 64-sample pulse there stays under the threshold."""
    import logging

    from pulsarutils_tpu.cli import search_main

    cfg, traffic = _cell(600.0, fraction, factor * tier_width, 256,
                         **WIDE_BAND)
    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, 2147483659)
    out = str(tmp_path / "o")
    with caplog.at_level(logging.INFO, logger="pulsarutils_tpu"):
        rc = search_main.main(
            [path, "--dmmin", "0", "--dmmax", "600", "--chunk-length",
             repr(info["hop"] * cfg["tsamp_s"]), "--output-dir", out,
             "--plots", "none", "--kernel", "hybrid", "--snr-threshold",
             "certifiable", "--dm-tiers", "smearing", "--boxcar-max", "256"])
    assert rc == 0
    _, tables, done, _ = harness.persisted(out)
    assert len(done) == 3
    best = tables[2 * info["hop"]].best_row()
    assert int(best["downsample"]) == factor
    # its own width, or where the block phase splits it the level above
    # (all of it at 1/sqrt(2)) or below (half of it at sqrt(2))
    assert int(best["rebin"]) in (max(tier_width // 2, 1), tier_width,
                                  2 * tier_width)
    assert bool(best["exact"])
    assert abs(float(best["DM"]) - info["pulses"][0]["dm"]) <= 0.05 * 600
    budget = [json.loads(r.getMessage()[len("BUDGET_JSON "):])
              for r in caplog.records
              if r.getMessage().startswith("BUDGET_JSON ")][-1]
    hit = budget["per_chunk"][2]
    assert [t["windows"] for t in hit["tiers"]] == [9, 8, 7]
    assert hit["best_window_samples"] == int(best["rebin"]) * factor
    assert [t.get("best_window") for t in hit["tiers"]] == [
        hit["best_window_samples"] if t["downsample"] == factor else None
        for t in hit["tiers"]]
    assert all("best_window_samples" not in c
               for c in budget["per_chunk"][:2])
    with pytest.raises(SystemExit):
        search_main.main([path, "--boxcar-max", "wide"])


def test_counter_counts_the_levels_scored(tmp_path):
    from pulsarutils_tpu.obs.metrics import REGISTRY

    def total():
        return sum(s["value"] for s in REGISTRY.snapshot()
                   if s["name"] == "putpu_boxcar_windows_total")

    cfg, traffic = _cell(160.0, [0.60, 0.62], 4, None)
    n0 = total()
    _search(tmp_path, cfg, traffic, "numpy", name="a", dm_tiers="smearing")
    n1 = total()
    cfg["boxcar_max"] = 64
    _search(tmp_path, cfg, traffic, "numpy", name="b", dm_tiers="smearing")
    n2 = total()
    _search(tmp_path, cfg, traffic, "numpy", name="c")
    n3 = total()
    # three chunks of three tiers: 4 + 4 + 4, then 7 + 6 + 5; flat: 7
    assert (n1 - n0, n2 - n1, n3 - n2) == (3 * 12, 3 * 18, 3 * 7)


# -- a wide hit's products ----------------------------------------------------

def test_cutout_holds_a_wide_hit_and_its_track(tmp_path):
    from pulsarutils_tpu.io.candidates import CandidateStore
    from pulsarutils_tpu.ops.plan import delta_delay
    from pulsarutils_tpu.pipeline.pulse_info import PulseInfo
    from pulsarutils_tpu.utils.table import ResultTable

    nchan, nbin, tsamp = 128, 1 << 16, 1.024e-3
    wf = np.random.default_rng(0).standard_normal((nchan, nbin)).astype(
        np.float32)
    store = CandidateStore(str(tmp_path), None)
    sizes = {}
    for rebin in (8, 32, 512):
        peak = 30000 // rebin * rebin
        table = ResultTable({"DM": np.array([100.0, 120.0]),
                             "snr": np.array([5.0, 20.0]),
                             "rebin": np.array([1, rebin]),
                             "peak": np.array([0, peak])})
        info = PulseInfo(allprofs=wf, start_freq=1182.0, bandwidth=400.0,
                         nbin=nbin, nchan=nchan, t0=0.0, istart=0,
                         pulse_freq=1.0 / (nbin * tsamp))
        cut = store.trim_waterfall(info, table)
        span = int(delta_delay(120.0, 1182.0, 1582.0) / tsamp) + 1
        lo = cut.cutout_start
        hi = lo + cut.allprofs.shape[1] * cut.cutout_decim
        # the whole boxcar and the whole track after it
        assert lo <= peak and hi >= peak + rebin + span, (rebin, lo, hi)
        sizes[rebin] = cut.allprofs.nbytes
    # a 32-sample hit's record is the default ladder's; the widest stays
    # within twice of it
    assert sizes[32] == sizes[8] and sizes[512] <= 2 * sizes[8]


def test_one_wide_pulse_in_two_overlapping_chunks_is_one_candidate(tmp_path):
    from pulsarutils_tpu.pipeline.sift import hit_fields, sift_hits

    cfg, traffic = _cell(600.0, [0.83, 0.84], 64, 256, **WIDE_BAND)
    traffic["pulse_hops"] = [1]  # chunks 0 and 1 both hold the hop
    info, path, hits = _search(tmp_path, cfg, traffic, "jax",
                               dm_tiers="smearing")
    assert sorted(h[0] for h in hits) == [0, info["hop"]]
    fields = [hit_fields(*h) for h in hits]
    tier_tsamp = 4 * cfg["tsamp_s"]
    for f, h in zip(fields, hits):
        # the width is the row's boxcar in seconds of its own tier
        assert f["width"] == pytest.approx(
            int(h[3].best_row()["rebin"]) * tier_tsamp)
        assert 8 * tier_tsamp <= f["width"] <= 32 * tier_tsamp
        assert h[2].width == f["width"]
    sifted = sift_hits(hits)
    assert len(sifted) == 1 and sifted[0]["n_members"] == 2
