"""CHIME/FRB's beam as a deployment (ISSUE 49): 400-800 MHz in 16,384
channels of 8 bits at 0.98304 ms, ``chipbench/configs/
chime_frb_16k_8bit.json``.  Its native tier's smallest legal time tile (a
halo of 24,576 samples) does not fit a v5e beside the 70,000 rows of its
sweep's state, so the tier is swept in **delay bands**.  Here on the CPU:
the tiny rehearsal (``tiny_cpu_chime``: the same band in 128 channels)
against the plain reference, banded and unbanded; banded and unbanded
tables of one file; the planner at the configuration's own numbers and at
cells 6 and 7's; the head's verdict in every band; the three counters."""

import ast
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import dispersion, generate  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_time_tiles import SNR_RTOL, _config_geometry  # noqa: E402

REHEARSAL = "tiny_cpu_chime.backlog_sparse_chime"
V5E_BYTES = 16909336064        # bytes_limit a v5e reports
CHIME = (16384, 400.0, 400.0)  # nchan, fbottom, bandwidth (MHz)
COUNTERS = ("putpu_tier_delay_bands_total", "putpu_sweep_calls_total",
            "putpu_sweep_samples_total", "putpu_time_tiles_total",
            "putpu_tier_sweeps_total", "putpu_tier_certified_total",
            "putpu_host_fallbacks_total")


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


def _search_kw(cfg):
    return dict(chunk_length=cfg["chunk_samples"] // 2 * cfg["tsamp_s"],
                dmmin=cfg["dmmin"], dmmax=cfg["dmmax"], backend="jax",
                kernel="hybrid", snr_threshold="certifiable", zero_dm=True,
                dm_tiers="smearing", boxcar_max=cfg["boxcar_max"])


def _counters():
    from pulsarutils_tpu.obs import metrics

    return {n: metrics.counter(n).value for n in COUNTERS}


def _moved(before):
    return {n: v - before[n] for n, v in _counters().items()}


def _band_spans(tracer):
    """``(tier, band, n_lo, n_hi, tiles)`` of every ``search/band`` span."""
    return [tuple(e["args"][k] for k in ("tier", "band", "n_lo", "n_hi",
                                          "tiles"))
            for e in tracer.events_since(0)[0]
            if e.get("name") == "search/band"]


def _expected(plan, chunks):
    """What the three counters move by over ``chunks`` chunks of a tile
    plan: one band beyond a tier's first, one call of the sweep program a
    tile a band, its ``own + halo`` samples."""
    calls = sum(t.tiles * max(len(t.bands), 1) for t in plan)
    return {"putpu_tier_delay_bands_total":
            chunks * sum(max(len(t.bands) - 1, 0) for t in plan),
            "putpu_sweep_calls_total": chunks * calls,
            "putpu_sweep_samples_total": chunks * sum(
                t.tiles * max(len(t.bands), 1) * (t.own + t.halo)
                for t in plan),
            "putpu_time_tiles_total":
            chunks * sum(t.tiles for t in plan if t.tiles > 1)}


# -- (a) the rehearsal, banded and unbanded --------------------------------

@pytest.mark.parametrize("bands", [0, 2], ids=["unbanded", "banded"])
def test_rehearsal_is_the_references_row(capsys, tmp_path, force_delay_bands,
                                         bands):
    """``PUsearchfrb`` on a ``tiny_cpu_chime`` file as ``chipbench/run.py``
    drives it: the persisted best row is ``reference_boxcar``'s (tier,
    trial DM, peak sample, boxcar, S/N within the file's limit, flagged
    exact), the bfloat16 control is not; with a device so small that the
    native tier, which holds the pulse, is swept in time tiles and two
    delay bands, the same, and a band is no tier in what the run
    reports."""
    cfg = _load("configs", "tiny_cpu_chime")
    traffic = _load("traffic", "backlog_sparse_chime")
    seed = 3400002048
    plan = None
    if bands:
        path = str(tmp_path / "plan.fil")
        generate.generate(path, cfg, traffic, seed)
        plan = force_delay_bands(path, _search_kw(cfg), bands)
        assert [len(t.bands) for t in plan] == [2, 0]
        assert plan[0].tiles > 1
    before = _counters()
    rc = harness.main(["--workload", REHEARSAL, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0", "--rehearsal",
                       "--control", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc != 0  # a rehearsal never exits 0
    assert line["correct"] is True and line["control_correct"] is False
    assert all(c["ok"] for name, c in line["compared"].items()
               if name != "snr_rel_gap_rms.control")
    ref = next(ln for ln in out if ln.startswith("reference chipbench."))
    assert "reference_boxcar" in ref
    # the pulse (DM 4.8-5.12 of 0-16) is the native tier's, the first of
    # two: its row counts from 0
    best = json.loads(ref[ref.index("): {") + 3:])
    row = ast.literal_eval(next(
        ln for ln in out if ln.startswith("program, chunk "
                                          )).split(": ", 1)[1])[1]
    assert best["row"] < 162 and row["DM"] == best["DM"]
    assert (row["peak"], row["rebin"], row["exact"]) == (
        best["peak"], best["rebin"], True)
    budget = json.loads(next(ln for ln in out if ln.startswith(
        "budget cold: "))[len("budget cold: "):])
    tiers = [ch["tiers"] for ch in budget["per_chunk"]]
    assert [[t["downsample"] for t in ch] for ch in tiers] == [[1, 2]] * 3
    assert [[t["trials"] for t in ch] for ch in tiers] == [[162, 79]] * 3
    assert all((len(ch[0].get("bands", ())), "bands" in ch[1])
               == (bands, False) for ch in tiers)
    moved = _moved(before)
    assert moved["putpu_host_fallbacks_total"] == 0
    chunks = 3 * (1 + line["attempted"] // 3)   # the cold pass + the window
    assert moved["putpu_tier_sweeps_total"] == 2 * chunks
    assert moved["putpu_tier_certified_total"] == 2 * (chunks // 3) * 2
    if bands:
        for name, want in _expected(plan, chunks).items():
            assert moved[name] == want, name
        assert [[b["n_lo"], b["n_hi"]] for b in tiers[0][0]["bands"]] == [
            [b.n_lo, b.n_hi] for b in plan[0].bands]
    else:
        assert moved["putpu_tier_delay_bands_total"] == 0
        assert moved["putpu_sweep_calls_total"] == 2 * chunks
        assert moved["putpu_sweep_samples_total"] == chunks * (
            cfg["chunk_samples"] * 3 // 2)


# -- (b) banded and unbanded tables of one file ----------------------------

def _write(path, cfg, pulses, seed=5, hops=3):
    """A SIGPROC file of ``hops`` half-chunks of 8-bit noise with the
    ``pulses`` ``(sample, dm, S/N)``, four samples wide, at the places
    given (``chipbench/generate.py`` draws its own)."""
    nchan, t, tsamp = cfg["nchans"], cfg["chunk_samples"], cfg["tsamp_s"]
    rng = np.random.default_rng(seed)
    n = hops * t // 2
    data = rng.normal(96.0, 16.0, size=(n, nchan))
    fbottom, bandwidth = dispersion.band_edges(cfg["fch1_mhz"],
                                               cfg["foff_mhz"], nchan)
    for sample, dm, snr in pulses:
        shifts = dispersion.channel_shifts(dm, nchan, fbottom, bandwidth,
                                           tsamp)
        amp = snr * 16.0 * 2.0 / np.sqrt(nchan) / 4
        for k in range(4):
            data[sample + shifts + k, np.arange(nchan)] += amp
    codes = np.clip(np.rint(data), 0, 255).astype(np.uint8)[:, ::-1]
    with open(path, "wb") as f:
        f.write(generate.sigproc_header(cfg))
        f.write(codes.tobytes())
    return path


@pytest.mark.parametrize("what,sample,dm", [
    # band delay 80.5 of tier 0's 0-161: the DM curve lies across the edge
    # of its two bands (0-80, 81-161), its peak rows on both sides
    ("on_a_bands_edge", 8192 + 3000, 80.5 / 19.785),
    # band delay 121 is the second band's, and the track lies across the
    # edge of two of the tier's time tiles
    ("in_a_tiled_band_across_a_tile_edge", 8192 + 4096 - 60, 121 / 19.785),
    # DM 12 is the 2x tier's, which is not banded
    ("in_the_unbanded_deep_tier", 8192 + 3000, 12.0),
])
def test_banded_and_unbanded_tables_agree(request, tmp_path,
                                          force_delay_bands, what, sample,
                                          dm):
    """The two searches of one ``tiny_cpu_chime`` file persist the same:
    trial DMs, peaks, boxcars, ``downsample`` and exact flags equal, S/N to
    2e-6 (``tests/test_time_tiles.py``'s measure); the three counters move
    by what the plan says and stay 0 on the unbanded search, which opens
    no ``search/band`` span where the banded one opens one a sweep."""
    from pulsarutils_tpu.obs import trace
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    cfg = _load("configs", "tiny_cpu_chime")
    path = _write(str(tmp_path / "f.fil"), cfg, [(sample, dm, 30.0)])
    kw = dict(_search_kw(cfg), make_plots=False, resume=False)
    before = _counters()
    tracer = trace.start_tracing()
    request.addfinalizer(trace.stop_tracing)
    whole, _ = search_by_chunks(path, output_dir=str(tmp_path / "a"), **kw)
    assert not _band_spans(tracer)
    moved = _moved(before)
    assert moved["putpu_tier_delay_bands_total"] == 0
    assert moved["putpu_time_tiles_total"] == 0
    assert moved["putpu_sweep_calls_total"] == 2 * 2
    plan = force_delay_bands(path, kw, 2)
    own = plan[0].own
    if what == "on_a_bands_edge":
        assert (plan[0].bands[0].n_hi, plan[0].bands[1].n_lo) == (80, 81)
    elif what.startswith("in_a_tiled_band"):
        assert plan[0].bands[1].n_lo <= 121 <= plan[0].bands[1].n_hi
        assert (sample % 8192) // own != (sample % 8192 + 121) // own
    before = _counters()
    banded, _ = search_by_chunks(path, output_dir=str(tmp_path / "b"), **kw)
    spans = _band_spans(trace.stop_tracing())
    moved = _moved(before)
    # one span a band a tile a chunk, inside the tier that is banded
    assert sorted(spans) == sorted(
        (0, b, band.n_lo, band.n_hi, plan[0].tiles)
        for b, band in enumerate(plan[0].bands)
        for _ in range(2 * plan[0].tiles))
    for name, want in _expected(plan, 2).items():
        assert moved[name] == want, name
    assert moved["putpu_tier_sweeps_total"] == 4
    assert moved["putpu_host_fallbacks_total"] == 0
    assert whole and [h[:2] for h in whole] == [h[:2] for h in banded]
    for (_, _, info0, t0), (_, _, info1, t1) in zip(whole, banded):
        assert t0.colnames == t1.colnames
        for name in ("DM", "rebin", "peak", "exact", "downsample"):
            assert np.array_equal(t0[name], t1[name]), name
        for name in ("snr", "max", "std", "cert"):
            np.testing.assert_allclose(t1[name], t0[name], rtol=SNR_RTOL,
                                       atol=1e-6)
        assert (info0.dm, info0.width) == (info1.dm, info1.width)
        assert abs(info0.dm - dm) < 0.2


# -- (c) the deployment's files and the planner ------------------------------

def test_the_files_state_the_deployment_the_issue_names():
    """``chime_frb_16k_8bit.json`` and ``backlog_sparse_chime.json`` as
    ISSUE 49 states them: published widths, nothing cut but the DM
    ceiling, cell 6's flags with the ladder at 128, and
    ``backlog_sparse_8bit``'s loop and levels to the letter.  The ceiling
    is 1,100 where the issue has 1,600: the largest round one whose file
    the accepted generator writes at chunks of 2^16 (``reduced_why``)."""
    cfg = _load("configs", "chime_frb_16k_8bit")
    assert (cfg["nchans"], cfg["nbits"], cfg["tsamp_s"], cfg["fch1_mhz"],
            cfg["foff_mhz"]) == (16384, 8, 0.00098304, 799.98779296875,
                                 -0.0244140625)
    assert dispersion.band_edges(cfg["fch1_mhz"], cfg["foff_mhz"],
                                 cfg["nchans"]) == CHIME[1:]
    assert (cfg["dmmin"], cfg["dmmax"], cfg["chunk_samples"]) == (
        0.0, 1100.0, 65536)
    assert cfg["reduced"] == ["dmmax"] and set(cfg["reduced_why"]) == {
        "dmmax"}
    assert len(cfg["source"]) <= 200
    assert cfg["reference"] == "reference_boxcar"
    assert cfg["cli_flags"] == _load(
        "configs", "meertrap_lband_8bit_fulldm")["cli_flags"][:-1] + ["128"]
    assert cfg["limits"] == _load("configs",
                                  "meertrap_lband_8bit_fulldm")["limits"]
    traffic = _load("traffic", "backlog_sparse_chime")
    eight_bit = _load("traffic", "backlog_sparse_8bit")
    for key in ("loop", "files_in_flight", "first_pass", "hops_per_file",
                "pulse_hops", "pulse_widths", "pulse_snr",
                "noise_mean_levels", "noise_sd_levels", "hot_channels"):
        assert traffic[key] == eight_bit[key], key
    assert traffic["pulse_dm_fraction"] == [0.30, 0.32]
    assert traffic["comb"] == {"hz": 60.0, "amp_levels": 6.0}
    assert traffic["hit_seed"] == 3400001011
    # the tiny rehearsal is the same band and the same flags
    tiny = _load("configs", "tiny_cpu_chime")
    assert dispersion.band_edges(tiny["fch1_mhz"], tiny["foff_mhz"],
                                 tiny["nchans"]) == CHIME[1:]
    assert (tiny["tsamp_s"], tiny["nbits"], tiny["cli_flags"]) == (
        cfg["tsamp_s"], cfg["nbits"], cfg["cli_flags"])


def _as_lists(plan):
    return [{"tiles": t.tiles, "own": t.own, "halo": t.halo,
             "bytes": t.bytes, "keep": t.keep,
             "bands": [[b.n_lo, b.n_hi, b.bytes] for b in t.bands]}
            for t in plan]


def test_chimes_beam_on_a_v5e_is_the_files_plan():
    """At the configuration's numbers the planner raises nothing and
    gives the band and tile plan the file states, every band's reckoned
    bytes under the budget; a budget no band fits is still refused, with
    the tier, the band and the bytes named."""
    from pulsarutils_tpu.parallel.stream import plan_time_tiles

    cfg = _load("configs", "chime_frb_16k_8bit")
    *args, resident = _config_geometry("chime_frb_16k_8bit")
    assert [len(g[2]) for g in args[4]] == [20732, 517]
    assert cfg["tile_plan"]["device_bytes"] == V5E_BYTES
    budget = V5E_BYTES * 15 // 16
    plan = plan_time_tiles(*args, budget, resident)
    assert _as_lists(plan) == cfg["tile_plan"]["tiers"]
    assert [len(t.bands) for t in plan] == [4, 0]
    assert all(b.bytes <= budget for t in plan for b in t.bands)
    assert max(t.bytes for t in plan) <= budget
    bands = plan[0].bands
    assert (bands[0].n_lo, bands[-1].n_hi) == (0, 20731)
    assert all(a.n_hi + 1 == b.n_lo for a, b in zip(bands, bands[1:]))
    assert {b.n_hi - b.n_lo + 1 for b in bands} == {5183}
    with pytest.raises(ValueError, match=(
            r"DM tier 1 \(x2, band delays 10366-10882\) .* delay band 0 of 1 "
            r".* needs \d+ bytes of \d+, .* a band of one delay needs \d+")):
        plan_time_tiles(*args, resident + (3 << 30), resident)
    # without the deeper tier beside it, tier 0 alone: bands are tried,
    # and the refusal names the last split's worst band
    with pytest.raises(ValueError, match=(
            r"DM tier 0 \(x1, band delays 0-20731\) .* delay band \d+ of "
            r"\d+ \(band delays \d+-\d+\) on a time tile of 32768 \+ 24576 "
            r"samples needs \d+ bytes")):
        plan_time_tiles(*args[:4], args[4][:1], resident + (6 << 30),
                        resident)


@pytest.mark.parametrize("name,want", [
    ("meertrap_lband_8bit_fulldm", [
        (4, 131072, 8192, 14061273088, 4), (2, 131072, 8192, 15200092160, 2),
        (1, 131072, 0, 12632195072, 0), (1, 65536, 0, 8195145728, 0),
        (1, 32768, 0, 5878972416, 0)]),
    ("parkes_uwl_2bit", [
        (2, 65536, 16384, 12133072896, 2), (1, 65536, 0, 5735186432, 0),
        (1, 32768, 0, 1459617792, 0)]),
])
def test_plans_that_fit_without_bands_are_unchanged(name, want):
    """Cells 6 and 7 get the tiles, halos and keeps PR 47's planner gave
    them, to the letter, and no band.  Each tier's reckoned bytes are PR
    47's and the 2^15 frames the tiled chunk carries twice, which its
    caller left out of what is resident until PR 49 (134,217,728 and
    27,262,976 bytes here): every sweep still fits."""
    from pulsarutils_tpu.parallel.stream import TierTiles, plan_time_tiles

    cfg = _load("configs", name)
    wrap = (1 << 15) * cfg["nchans"] * cfg["nbits"] // 8
    *args, resident = _config_geometry(name)
    assert resident == 2 * cfg["chunk_samples"] * cfg["nchans"] * cfg[
        "nbits"] // 8 + wrap
    budget = V5E_BYTES * 15 // 16
    plan = plan_time_tiles(*args, budget, resident)
    assert plan == [TierTiles(tiles, own, halo, bytes_ + wrap, keep)
                    for tiles, own, halo, bytes_, keep in want]
    assert all(t.bands == () and t.bytes <= budget for t in plan)


def test_the_survey_states_the_frames_a_tiled_chunk_carries_twice(tmp_path):
    """``_tile_geometry`` (the owner of what the chunk loop holds) counts
    the packed chunk, its first ``MAX_BLOCK`` frames once more and the
    prefetch; the planner charges that to sweeps and ``keep`` alike and
    knows nothing of the pipeline."""
    import inspect

    from pulsarutils_tpu.parallel import stream
    from pulsarutils_tpu.pipeline import search_pipeline as sp
    from pulsarutils_tpu.pipeline.time_tiles import MAX_BLOCK

    cfg = _load("configs", "tiny_cpu_chime")
    path = _write(str(tmp_path / "f.fil"), cfg, [])
    survey = sp.plan_survey(path, **_search_kw(cfg))
    *_, resident = sp._tile_geometry(
        survey["reader"].header, survey["plan"], survey["tiers"], None,
        survey["reader"].packed_bits)
    step = survey["plan"].step
    assert resident == (2 * step + min(MAX_BLOCK, step)) * cfg["nchans"]
    assert "from ..pipeline" not in inspect.getsource(stream)


# -- (d) the head in every band ---------------------------------------------

def test_the_head_runs_in_every_band_at_the_smem_the_file_states():
    """128 groups, fourteen levels: ``_head_verdict`` takes the fused head
    in each of tier 0's four bands on its tile's axis and in the 2x
    tier's tiles, with the scalar memory the configuration's file states."""
    from pulsarutils_tpu.ops.fdmt import _head_verdict

    cfg = _load("configs", "chime_frb_16k_8bit")
    tiers = cfg["tile_plan"]["tiers"]
    axis = tiers[0]["own"] + tiers[0]["halo"]
    got = []
    for lo, hi, _ in tiers[0]["bands"]:
        choice, reason, smem = _head_verdict(*CHIME, hi, lo, axis)
        assert choice is not None and reason is None
        got.append(smem)
    assert got == cfg["head"]["tier0_band_smem_bytes"]
    choice, _, smem = _head_verdict(*CHIME, 10882, 10366,
                                    tiers[1]["own"] + tiers[1]["halo"])
    assert choice is not None and smem == cfg["head"]["tier1_smem_bytes"]
