"""The smearing-tiered DM plan (``dm_tiers="smearing"``, ISSUE 28): the tier
rule against hand-worked numbers, a one-tier plan as the flat path byte for
byte, and the tiered search against the benchmark's plain reference
(``chipbench/reference_tiered.py``, which imports nothing of the program)
on seeded 64-channel files, in the native tier and in a downsampled one, on
both back ends.
"""
import glob
import json
import logging
import os

import numpy as np
import pytest

from chipbench import generate, reference_tiered
from chipbench import run as harness
from pulsarutils_tpu.ops.plan import (dedispersion_plan, delta_delay,
                                      dm_tier_plan)
from pulsarutils_tpu.parallel.stream import plan_chunks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Parkes HTRU / BPSR (chipbench/configs/htru_bpsr_*.json)
HTRU = dict(nchan=1024, start_freq=1182.0, bandwidth=400.0,
            sample_time=64e-6, foff=-0.390625)


def _htru(dmmin, dmmax):
    return dm_tier_plan(HTRU["nchan"], dmmin, dmmax, HTRU["start_freq"],
                        HTRU["bandwidth"], HTRU["sample_time"], HTRU["foff"])


def test_htru_tiers_by_hand():
    """Smearing at 1,382 MHz: 8300 x 0.390625 / 1382^3 = 1.22833 us per DM
    unit, one 64 us sample at DM 52.103; band delay 4149 x (1182^-2 -
    1582^-2) = 1.31188 ms = 20.498 samples per DM unit."""
    tiers = _htru(0.0, 1000.0)
    assert [t.downsample for t in tiers] == [1, 2, 4, 8, 16, 32]
    assert [round(t.dm_hi, 1) for t in tiers] == [52.1, 104.2, 208.4, 416.8,
                                                  833.7, 1000.0]
    assert [len(t.trial_dms) for t in tiers] == [1069, 534, 534, 534, 534,
                                                 107]
    assert sum(len(t.trial_dms) for t in tiers) == 3312
    f0, f1 = 1182.0, 1582.0
    for t, (first, last) in zip(tiers, [(0, 1068)] + [(535, 1068)] * 4
                                + [(535, 641)]):
        n = delta_delay(t.trial_dms, f0, f1) / t.sample_time
        assert t.sample_time == t.downsample * 64e-6
        assert np.allclose(n, np.arange(first, last + 1), atol=1e-6)
    assert tiers[-1].trial_dms[-2] < 1000.0 <= tiers[-1].trial_dms[-1]


@pytest.mark.parametrize("args", [
    # the benchmark's two cells: rehearsal_1024ch_2bit, htru_bpsr_lowdm
    (1024, 300.0, 400.0, 1200.0, 200.0, 5e-4, -0.1953125),
    (1024, 0.0, 52.0, 1182.0, 400.0, 64e-6, -0.390625),
])
def test_existing_cells_plan_one_tier_to_the_bit(args):
    tiers = dm_tier_plan(*args)
    assert len(tiers) == 1 and tiers[0].downsample == 1
    assert np.array_equal(tiers[0].trial_dms, dedispersion_plan(*args[:6]))


@pytest.mark.parametrize("dmmin,dmmax", [(0.0, 1000.0), (60.0, 300.0),
                                         (0.0, 104.3)])
def test_no_dm_twice_none_skipped(dmmin, dmmax):
    tiers = _htru(dmmin, dmmax)
    dms = np.concatenate([t.trial_dms for t in tiers])
    assert np.all(np.diff(dms) > 0)
    assert dms[0] <= dmmin + 1e-9 and dms[-1] >= dmmax
    unit = delta_delay(1.0, 1182.0, 1582.0)  # s of band delay per DM
    for a, b in zip(tiers, tiers[1:]):
        assert b.downsample == 2 * a.downsample and b.dm_lo == a.dm_hi
        # inside a tier one sample of its own band delay; across an edge
        # less than one step of each side
        assert np.allclose(np.diff(a.trial_dms), a.sample_time / unit)
        gap = b.trial_dms[0] - a.trial_dms[-1]
        assert 0 < gap <= (a.sample_time + b.sample_time) / unit
        assert a.trial_dms[-1] <= a.dm_hi < b.trial_dms[0]


def test_chunk_step_keeps_every_tier_tile_divisible():
    plan = plan_chunks(10**7, 64e-6, 0.0, 1000.0, 1182.0, 1582.0, -0.390625,
                       chunk_length=16.7, tile_factor=32)
    assert plan.step % (1024 * 32) == 0 and plan.hop * 2 == plan.step
    flat = plan_chunks(10**7, 64e-6, 0.0, 1000.0, 1182.0, 1582.0, -0.390625,
                       chunk_length=16.7)
    assert flat.step % 1024 == 0 and flat.step <= plan.step


# -- the search, on seeded 64-channel files --------------------------------

def _load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


def _cell(dmmax, fraction, width):
    """The CPU rehearsal's geometry (64 ch x 2^14, 0.5 ms, 1,200-1,400 MHz:
    smearing one sample at DM 42.35) from DM 0, and one pulse."""
    cfg = dict(_load("configs", "tiny_cpu_rehearsal.json"), dmmin=0.0,
               dmmax=dmmax)
    traffic = dict(_load("traffic", "backlog_sparse.json"),
                   pulse_dm_fraction=fraction, pulse_widths=[width])
    return cfg, traffic


CELLS = {
    # tiers x1 (DM 0-42.35), x2 (-80); pulse at DM 24-25.6, one sample
    "native": _cell(80.0, [0.30, 0.32], 1),
    # tiers x1, x2 (-84.7), x4 (-160); pulse at DM 96-99.2, four samples
    "downsampled": _cell(160.0, [0.60, 0.62], 4),
}


def _search(tmp_path, which, backend, seed=3000000019, **kw):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    cfg, traffic = CELLS[which]
    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, seed)
    flags = (dict(kernel="hybrid", snr_threshold="certifiable")
             if backend == "jax" else dict(snr_threshold=8.0))
    hits, store = search_by_chunks(
        path, chunk_length=info["hop"] * cfg["tsamp_s"], dmmin=cfg["dmmin"],
        dmmax=cfg["dmmax"], backend=backend, output_dir=str(tmp_path / "o"),
        make_plots=False, dm_tiers="smearing", progress=False,
        **dict(flags, **kw))
    return cfg, info, path, hits, store


#: relative S/N gap, rms over the reference's rows, that a sound search
#: stays under and the reference computed from a bfloat16 chunk does not.
#: NumPy back end: float64 against float64, summation order only.  JAX back
#: end: the cleaned chunk is float32 (rounding 6e-8 a value) and so are the
#: pair sums and the channel sum; over 64 channels the S/N moves by a few
#: 1e-7 (measured 2e-7 - 4e-7), while bfloat16 storage (rounding 4e-3 a
#: value) moves it by 1e-4 or more.
TOLERANCE = {"numpy": 1e-9, "jax": 1e-5}


@pytest.mark.parametrize("backend", ["jax", "numpy"])
@pytest.mark.parametrize("which", ["native", "downsampled"])
def test_tiered_search_is_the_plain_reference(tmp_path, which, backend):
    cfg, info, path, hits, store = _search(tmp_path, which, backend)
    pulse, hop = info["pulses"][0], info["hop"]
    istart = 2 * hop
    ref = reference_tiered.best_row(path, cfg, istart, pulse["dm"],
                                    control=True)
    assert ref["downsample"] == (1 if which == "native" else 4)
    rows, tables, done, _ = harness.persisted(str(tmp_path / "o"))
    assert done == {0, hop, 2 * hop}
    table = tables[istart]
    best = table.best_row()
    # the chunk's hit is the reference's row of the concatenated table:
    # same tier, same trial DM to the bit, peak and boxcar in that tier's
    # samples
    assert table.argbest() == ref["row"]
    assert int(best["downsample"]) == ref["downsample"]
    assert (float(best["DM"]), int(best["peak"]), int(best["rebin"])) == (
        ref["DM"], ref["peak"], ref["rebin"])
    assert len(table["DM"]) == ref["ntrials"]
    assert sorted(set(table["downsample"])) == (
        [1, 2] if which == "native" else [1, 2, 4])
    rms, nrows = harness.rms_gap(table, ref["rows"])
    assert nrows >= 3 and rms <= TOLERANCE[backend]
    ctl_rms, _ = harness.rms_gap(harness.rows_as_table(
        ref["control"]["rows"]), ref["rows"])
    assert ctl_rms > TOLERANCE["jax"]
    # the hit record is built at the hit's tier
    (lo, hi, hit_info, hit_table), = [h for h in hits if h[0] == istart]
    assert hit_info.nbin == cfg["chunk_samples"] // ref["downsample"]
    assert hit_info.dm == ref["DM"]


def test_hit_fields_of_a_downsampled_tier_hit(tmp_path):
    from pulsarutils_tpu.pipeline.sift import hit_fields

    cfg, info, _, hits, _ = _search(tmp_path, "downsampled", "numpy")
    pulse = info["pulses"][0]
    best = max(hits, key=lambda h: h[2].snr)
    fields = hit_fields(*best)
    tsamp = cfg["tsamp_s"]
    # the pulse is injected at its band-centre arrival time, four samples
    # wide: one sample of the 4x tier
    assert abs(fields["time"] - pulse["sample"] * tsamp) <= 2 * 4 * tsamp
    assert 4 * tsamp <= fields["width"] <= 2 * 4 * tsamp
    assert fields["span"] == pytest.approx(cfg["chunk_samples"] * tsamp)
    assert fields["dm"] == pytest.approx(pulse["dm"], abs=2.0)


def test_budget_spans_and_counters_say_what_each_tier_did(tmp_path, caplog):
    from pulsarutils_tpu.obs import trace as ptrace
    from pulsarutils_tpu.obs.metrics import REGISTRY

    def totals():
        return {s["name"]: s["value"] for s in REGISTRY.snapshot()
                if s["name"].startswith(("putpu_tier_", "putpu_certified"))}

    before = totals()
    tracer = ptrace.start_tracing()
    try:
        with caplog.at_level(logging.INFO, logger="pulsarutils_tpu"):
            _search(tmp_path, "downsampled", "jax")
    finally:
        ptrace.stop_tracing()
    after = totals()
    moved = {k: after[k] - before.get(k, 0) for k in after}
    budget = json.loads(next(
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("BUDGET_JSON "))[len("BUDGET_JSON "):])
    per_chunk = budget["per_chunk"]
    assert [[t["downsample"] for t in c["tiers"]] for c in per_chunk] \
        == [[1, 2, 4]] * 3
    assert moved["putpu_tier_sweeps_total"] == 9
    ncert = sum(t["certified"] for c in per_chunk for t in c["tiers"])
    assert moved["putpu_tier_certified_total"] == ncert >= 6
    # a chunk is certified once, when every tier of it was
    assert moved["putpu_certified_chunks_total"] == sum(
        all(t["certified"] for t in c["tiers"]) for c in per_chunk) == 2
    for c in per_chunk:
        assert "search/tier_downsample" in c["buckets"]
        assert all(set(t) - {"best_window"} == {
            "downsample", "trials", "coarse_s", "certified", "windows"}
            for t in c["tiers"])
        assert all(t["windows"] == 4 for t in c["tiers"])
    events, _ = tracer.events_since(0)
    spans = [e for e in events if e.get("name") == "search/tier"]
    assert len(spans) == 9
    assert {(e["args"]["tier"], e["args"]["downsample"]) for e in spans} \
        == {(0, 1), (1, 2), (2, 4)}
    assert sum(e["args"]["certified"] for e in spans) == ncert
    assert all(e["args"]["trials"] > 0 for e in spans)


# -- off, and a one-tier plan: today's path --------------------------------

def _tree(directory):
    out = {}
    for path in sorted(glob.glob(os.path.join(directory, "*"))):
        with open(path, "rb") as f:
            out[os.path.basename(path)] = f.read()
    return out


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_one_tier_plan_is_the_flat_path_byte_for_byte(tmp_path, backend):
    """DM 0-40 at this geometry stays under the first edge (42.35): the
    flag changes neither a persisted byte nor the ledger's name (its
    fingerprint)."""
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    cfg, traffic = _cell(40.0, [0.49, 0.51], 1)
    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, 11)
    flags = (dict(kernel="hybrid", snr_threshold="certifiable")
             if backend == "jax" else dict(snr_threshold=8.0))
    for name, tiers in (("flat", None), ("tiered", "smearing")):
        hits, _ = search_by_chunks(
            path, chunk_length=info["hop"] * cfg["tsamp_s"], dmmin=0.0,
            dmmax=40.0, backend=backend, output_dir=str(tmp_path / name),
            make_plots=False, dm_tiers=tiers, progress=False, **flags)
        assert len(hits) >= 1
        assert "downsample" not in hits[0][3].colnames
    flat, tiered = _tree(str(tmp_path / "flat")), _tree(str(tmp_path /
                                                           "tiered"))
    assert list(flat) == list(tiered) and len(flat) >= 3
    assert flat == tiered


def test_fingerprint_carries_the_tiers_only_when_there_are_several(tmp_path):
    from pulsarutils_tpu.pipeline.search_pipeline import plan_survey

    cfg, traffic = _cell(160.0, [0.60, 0.62], 4)
    path = str(tmp_path / "f.fil")
    generate.generate(path, cfg, traffic, 5)

    def fp(dmmax, tiers):
        sp = plan_survey(path, chunk_length=4.096, dmmin=0.0, dmmax=dmmax,
                         snr_threshold=8.0, dm_tiers=tiers)
        return sp["fingerprint"], sp["tiers"], sp["plan"].step

    assert fp(40.0, None) == fp(40.0, "smearing")
    flat, tiered = fp(160.0, None), fp(160.0, "smearing")
    assert flat[1] is None and flat[0] != tiered[0]
    assert [t["tier"].downsample for t in tiered[1]] == [1, 2, 4]
    assert all(t["snr_threshold"] == 8.0 for t in tiered[1])
    with pytest.raises(ValueError, match="dm_tiers"):
        plan_survey(path, dm_tiers="log")


def test_each_tier_resolves_its_own_threshold(tmp_path, caplog):
    from pulsarutils_tpu.pipeline.search_pipeline import plan_survey

    cfg, traffic = _cell(160.0, [0.60, 0.62], 4)
    path = str(tmp_path / "f.fil")
    generate.generate(path, cfg, traffic, 5)
    with caplog.at_level(logging.INFO, logger="pulsarutils_tpu"):
        sp = plan_survey(path, chunk_length=4.096, dmmin=0.0, dmmax=160.0,
                         kernel="hybrid", snr_threshold="certifiable",
                         dm_tiers="smearing")
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("snr_threshold resolved")]
    assert len(said) == 3  # one line per tier
    thresholds = [t["snr_threshold"] for t in sp["tiers"]]
    # fewer samples and fewer trials: a lower floor, forwarded as that
    # tier's certificate floor
    assert thresholds[0] > thresholds[1] > thresholds[2]
    assert [t["search_snr_floor"] for t in sp["tiers"]] == thresholds
    assert sp["snr_threshold"] == thresholds[0]


@pytest.mark.parametrize("kw,named", [
    (dict(mesh=object()), "mesh"),
    (dict(canary=0.5), "canary"),
    (dict(period_search=True), "period_search"),
    (dict(plane_consumer=lambda *a: None), "plane_consumer"),
])
def test_dm_tiers_refuses_what_it_cannot_carry(tmp_path, kw, named):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    with pytest.raises(ValueError, match=f"dm_tiers.*{named}"):
        search_by_chunks(str(tmp_path / "absent.fil"), dm_tiers="smearing",
                         **kw)


def test_cli_flag_reaches_the_driver(tmp_path):
    from pulsarutils_tpu.cli import search_main

    cfg, traffic = CELLS["native"]
    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, 3)
    out = str(tmp_path / "o")
    rc = search_main.main(
        [path, "--dmmin", "0", "--dmmax", "80", "--chunk-length",
         repr(info["hop"] * cfg["tsamp_s"]), "--output-dir", out, "--plots",
         "none", "--kernel", "hybrid", "--snr-threshold", "certifiable",
         "--dm-tiers", "smearing"])
    assert rc == 0
    _, tables, done, _ = harness.persisted(out)
    assert len(done) == 3
    assert set(tables[2 * info["hop"]]["downsample"]) == {1, 2}
    with pytest.raises(SystemExit):
        search_main.main([path, "--dm-tiers", "log"])


def test_a_higher_row_under_its_own_threshold_is_not_the_hit(tmp_path,
                                                             monkeypatch):
    """Tier thresholds differ under ``"certifiable"``.  A tier-0 row that
    outscores the hit without reaching tier 0's higher threshold is no
    detection: the hit is the lower tier's row, and the persisted table's
    best row is that hit (sift and cutout read it there)."""
    from pulsarutils_tpu.pipeline import search_pipeline
    from pulsarutils_tpu.pipeline.sift import hit_fields
    from pulsarutils_tpu.utils.table import ResultTable

    cfg, traffic = CELLS["native"]
    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, 17)
    chunk_length = info["hop"] * cfg["tsamp_s"]
    sp = search_pipeline.plan_survey(
        path, chunk_length=chunk_length, dmmin=0.0, dmmax=80.0,
        kernel="hybrid", snr_threshold="certifiable", dm_tiers="smearing")
    thr = [t["snr_threshold"] for t in sp["tiers"]]
    assert thr[0] > thr[1] + 0.2

    def fake(array, *a, trial_dms=None, **kw):
        n = len(trial_dms)
        tier = 0 if array.shape[1] == cfg["chunk_samples"] else 1
        snr = np.full(n, 5.0)
        # tier 0: just under its threshold; tier 1: just over its own
        snr[3] = thr[0] - 0.05 if tier == 0 else thr[1] + 0.05
        assert snr[3] > thr[1]
        cols = {"DM": trial_dms, "max": snr, "std": np.ones(n), "snr": snr,
                "rebin": np.full(n, 2), "peak": np.full(n, 100 + tier),
                "exact": np.ones(n, bool), "cert": np.zeros(n)}
        return ResultTable(cols, meta={"certified": False})

    monkeypatch.setattr(search_pipeline, "_search_with_fallback", fake)
    hits, _ = search_pipeline.search_by_chunks(
        path, chunk_length=chunk_length, dmmin=0.0, dmmax=80.0,
        kernel="hybrid", snr_threshold="certifiable", dm_tiers="smearing",
        output_dir=str(tmp_path / "o"), make_plots=False, max_chunks=1,
        progress=False)
    (istart, iend, hit_info, table), = hits
    n0 = len(sp["tiers"][0]["tier"].trial_dms)
    best = table.best_row()
    assert int(best["downsample"]) == 2 and int(best["peak"]) == 101
    assert float(best["snr"]) == pytest.approx(thr[1] + 0.05)
    assert table.nrows == n0 + len(sp["tiers"][1]["tier"].trial_dms) - 1
    assert hit_info.nbin == cfg["chunk_samples"] // 2
    fields = hit_fields(istart, iend, hit_info, table)
    assert fields["time"] == pytest.approx(101 * 2 * cfg["tsamp_s"])
    assert fields["width"] == pytest.approx(2 * 2 * cfg["tsamp_s"])
