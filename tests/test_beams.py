"""Multi-beam subsystem tests (ISSUE 8).

The load-bearing pins:

* batched N-beam dispatch is BIT-IDENTICAL per beam to N sequential
  single-beam dispatches (kernel level and end-to-end: tables, ledgers,
  persisted candidate bytes) — the PR 2 discipline at the beam axis;
* one device dispatch serves N beam-chunks (the counters prove the Nx
  amortisation);
* cross-beam coincidence verdicts: all-beam same-(DM, t) detections are
  RFI-vetoed, single/adjacent-beam detections confirmed;
* beam provenance (sigproc ``ibeam``/``nbeams``) rides the reader, the
  PulseInfo record, and the sift's candidate dicts;
* per-beam canary controllers inject disjoint deterministic chunk
  subsets and label their metric series by beam.
"""

import os

import numpy as np
import pytest

from pulsarutils_tpu.beams.batcher import BeamBatcher, BeamGeometryError
from pulsarutils_tpu.beams.coincidence import (AMBIGUOUS, CONFIRMED, RFI,
                                               coincidence_sift)
from pulsarutils_tpu.beams.multibeam import multibeam_search, open_beams
from pulsarutils_tpu.io.sigproc import (FilterbankReader,
                                        write_simulated_filterbank)
from pulsarutils_tpu.models.simulate import simulate_test_data
from pulsarutils_tpu.tuning.geometry import geometry_key
from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

GEOM = {"bandwidth": 200.0, "fbottom": 1200.0, "tsamp": 0.0005}


def write_beam(path, nchan, nsamples, seed, pulse_dm=None, nbeams=None,
               ibeam=None, rfi_impulse_at=None):
    rng = np.random.default_rng(seed)
    arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 10.0
    if pulse_dm is not None:
        pulse, _ = simulate_test_data(
            dm=pulse_dm, nchan=nchan, nsamples=nsamples,
            tsamp=GEOM["tsamp"], start_freq=GEOM["fbottom"],
            bandwidth=GEOM["bandwidth"], signal=8.0, noise=0.0, rng=99)
        arr = arr + pulse
    if rfi_impulse_at is not None:
        arr[:, rfi_impulse_at:rfi_impulse_at + 2] += 40.0
    header = {"bandwidth": GEOM["bandwidth"], "fbottom": GEOM["fbottom"],
              "nchans": nchan, "nsamples": nsamples,
              "tsamp": GEOM["tsamp"],
              "foff": GEOM["bandwidth"] / nchan}
    extra = {}
    if nbeams is not None:
        extra = {"nbeams": nbeams, "ibeam": ibeam}
    write_simulated_filterbank(path, arr, header, descending=True, **extra)
    return path


# ---------------------------------------------------------------------------
# batcher kernel bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["roll", "gather"])
def test_batched_search_bit_identical_per_beam(kernel, rng):
    nchan, nsamples, ndm = 32, 2048, 16
    blocks = [rng.normal(size=(nchan, nsamples)).astype(np.float32)
              for _ in range(3)]
    dms = np.linspace(100.0, 200.0, ndm)
    batcher = BeamBatcher(nchan, nsamples, dms, 1200.0, 200.0, 5e-4,
                          kernel=kernel)
    batched = batcher.search(blocks)
    for blk, table in zip(blocks, batched):
        single = batcher.search_single(blk)
        for col in table.colnames:
            assert np.array_equal(table[col], single[col]), \
                f"column {col} diverged between batched and single"


def test_batched_ragged_tail_geometry(rng):
    """A shorter final chunk gets its own offset table (gather wraps mod
    T) — results still match the single-beam dispatch at that length."""
    nchan = 32
    dms = np.linspace(100.0, 200.0, 8)
    batcher = BeamBatcher(nchan, 2048, dms, 1200.0, 200.0, 5e-4,
                          kernel="roll")
    short = [rng.normal(size=(nchan, 1024)).astype(np.float32)
             for _ in range(2)]
    tables = batcher.search(short)
    ref = batcher.search_single(short[1])
    for col in ref.colnames:
        assert np.array_equal(tables[1][col], ref[col])


def test_batcher_rejects_mixed_shapes(rng):
    batcher = BeamBatcher(32, 4096, np.linspace(100, 200, 8), 1200.0,
                          200.0, 5e-4, kernel="roll")
    with pytest.raises(BeamGeometryError):
        batcher.search([np.zeros((32, 4096), np.float32),
                        np.zeros((32, 2048), np.float32)])
    with pytest.raises(ValueError):
        BeamBatcher(32, 4096, np.linspace(100, 200, 8), 1200.0, 200.0,
                    5e-4, kernel="pallas")


def test_geometry_key_batch_axis():
    base = geometry_key("cpu", 64, 8192, 128)
    assert geometry_key("cpu", 64, 8192, 128, batch=1) == base, \
        "batch=1 must leave pre-batch tune-cache keys untouched"
    batched = geometry_key("cpu", 64, 8192, 128, batch=8)
    assert batched == base + "|b8"


# ---------------------------------------------------------------------------
# end-to-end: batched vs sequential byte identity + dispatch amortisation
# ---------------------------------------------------------------------------

def test_multibeam_batched_equals_sequential(tmp_path):
    nchan, nsamples = 64, 4096
    fnames = [
        write_beam(str(tmp_path / f"beam{b}.fil"), nchan, nsamples,
                   seed=b, pulse_dm=150.0 if b == 1 else None,
                   nbeams=3, ibeam=b + 1)
        for b in range(3)]
    accb, accs = BudgetAccountant(), BudgetAccountant()
    rb = multibeam_search(fnames, 100, 200, snr_threshold=7.0,
                          output_dir=str(tmp_path / "ob"), budget=accb,
                          batched=True, keep_tables=True)
    rs = multibeam_search(fnames, 100, 200, snr_threshold=7.0,
                          output_dir=str(tmp_path / "os"), budget=accs,
                          batched=False, keep_tables=True)

    # per-beam tables bit-identical, every chunk
    for bb, bs in zip(rb["beams"], rs["beams"]):
        assert len(bb["tables"]) == len(bs["tables"]) > 0
        for (i1, t1), (i2, t2) in zip(bb["tables"], bs["tables"]):
            assert i1 == i2
            for col in t1.colnames:
                assert np.array_equal(t1[col], t2[col])

    # ledgers and persisted candidates byte-identical
    batched_files = sorted(os.listdir(tmp_path / "ob"))
    assert batched_files == sorted(os.listdir(tmp_path / "os"))
    assert any(f.endswith(".table.npz") for f in batched_files)
    for name in batched_files:
        a = (tmp_path / "ob" / name).read_bytes()
        b = (tmp_path / "os" / name).read_bytes()
        assert a == b, f"{name} differs between batched and sequential"

    # the amortisation: one dispatch per epoch vs one per beam-chunk
    epochs = len(accb.chunks)
    assert accb.counters_total["dispatches"] == epochs
    assert accs.counters_total["dispatches"] == 3 * epochs

    # the injected pulse is found only in beam 2 and confirmed
    hits = {b["beam"]: len(b["hits"]) for b in rb["beams"]}
    assert hits[2] > 0 and hits[1] == 0 and hits[3] == 0
    verdicts = rb["coincidence"]["stats"]["verdicts"]
    assert verdicts[CONFIRMED] >= 1 and verdicts[RFI] == 0


def test_multibeam_resume_skips_done_chunks(tmp_path):
    nchan, nsamples = 64, 4096
    fnames = [write_beam(str(tmp_path / f"b{b}.fil"), nchan, nsamples,
                         seed=10 + b, pulse_dm=150.0 if b == 0 else None)
              for b in range(2)]
    out = str(tmp_path / "out")
    acc1 = BudgetAccountant()
    r1 = multibeam_search(fnames, 100, 200, snr_threshold=7.0,
                          output_dir=out, budget=acc1, max_chunks=3)
    assert all(b["chunks_done"] == 3 for b in r1["beams"])
    acc2 = BudgetAccountant()
    r2 = multibeam_search(fnames, 100, 200, snr_threshold=7.0,
                          output_dir=out, budget=acc2)
    # session 2 searched only the remaining chunks...
    total = len(r2["beams"][0]["store"].done_chunks)
    assert all(b["chunks_done"] == total - 3 for b in r2["beams"])
    # ...and still reports the COMPLETE per-beam hit list (restored from
    # the store), identical to an uninterrupted run
    ref = multibeam_search(fnames, 100, 200, snr_threshold=7.0,
                           output_dir=str(tmp_path / "ref"), resume=False)
    assert [len(b["hits"]) for b in r2["beams"]] \
        == [len(b["hits"]) for b in ref["beams"]]


def test_multibeam_rejects_mismatched_geometry(tmp_path):
    a = write_beam(str(tmp_path / "a.fil"), 64, 4096, seed=0)
    rng = np.random.default_rng(1)
    arr = np.abs(rng.normal(0, 0.5, (32, 4096))) + 10.0
    header = {"bandwidth": GEOM["bandwidth"], "fbottom": GEOM["fbottom"],
              "nchans": 32, "nsamples": 4096, "tsamp": GEOM["tsamp"],
              "foff": GEOM["bandwidth"] / 32}
    b = str(tmp_path / "b.fil")
    write_simulated_filterbank(b, arr, header, descending=True)
    with pytest.raises(BeamGeometryError):
        open_beams([a, b])


# ---------------------------------------------------------------------------
# coincidence verdicts
# ---------------------------------------------------------------------------

def cand(beam, t, dm, snr, width=0.002):
    return {"beam": beam, "time": t, "dm": dm, "snr": snr, "width": width}


def test_coincidence_all_beam_rfi_vetoed():
    # the same (DM, t) in every one of 8 beams: terrestrial
    cands = [cand(b, 10.0, 150.0, 12.0 + 0.1 * b) for b in range(8)]
    stats = {}
    groups = coincidence_sift(cands, nbeams=8, stats=stats)
    assert len(groups) == 1
    assert groups[0]["verdict"] == RFI
    assert groups[0]["n_beams"] == 8
    assert stats["vetoed_members"] == 8


def test_coincidence_single_beam_confirmed():
    cands = [cand(3, 42.0, 300.0, 15.0)]
    groups = coincidence_sift(cands, nbeams=8)
    assert groups[0]["verdict"] == CONFIRMED


def test_coincidence_adjacent_pair_confirmed_nonadjacent_ambiguous():
    near = coincidence_sift([cand(3, 5.0, 200.0, 12.0),
                             cand(4, 5.0, 200.2, 9.0)], nbeams=8)
    assert near[0]["verdict"] == CONFIRMED
    far = coincidence_sift([cand(1, 5.0, 200.0, 12.0),
                            cand(6, 5.0, 200.2, 9.0)], nbeams=8)
    assert far[0]["verdict"] == AMBIGUOUS


def test_coincidence_no_veto_below_three_beams():
    # two beams cannot anti-coincide: a both-beam detection stays a
    # candidate question, never an automatic veto
    groups = coincidence_sift([cand(0, 1.0, 100.0, 10.0),
                               cand(1, 1.0, 100.0, 10.5)], nbeams=2)
    assert groups[0]["verdict"] != RFI


def test_coincidence_distinct_events_stay_separate():
    groups = coincidence_sift(
        [cand(0, 10.0, 150.0, 12.0), cand(5, 600.0, 150.0, 11.0)],
        nbeams=8)
    assert len(groups) == 2
    assert all(g["verdict"] == CONFIRMED for g in groups)


def test_coincidence_adjacency_map_overrides_labels():
    # a 2-D beam layout: beams "1" and "7" are physical neighbours
    adjacency = {1: {7}, 7: {1}}
    groups = coincidence_sift(
        [cand(1, 5.0, 200.0, 12.0), cand(7, 5.0, 200.1, 9.0)],
        nbeams=8, adjacency=adjacency)
    assert groups[0]["verdict"] == CONFIRMED


# ---------------------------------------------------------------------------
# beam provenance plumbing
# ---------------------------------------------------------------------------

def test_sigproc_beam_headers_roundtrip(tmp_path):
    path = write_beam(str(tmp_path / "b.fil"), 32, 1024, seed=0,
                      nbeams=13, ibeam=7)
    reader = FilterbankReader(path)
    assert reader.nbeams == 13 and reader.ibeam == 7
    plain = write_beam(str(tmp_path / "p.fil"), 32, 1024, seed=0)
    reader2 = FilterbankReader(plain)
    assert reader2.nbeams is None and reader2.ibeam is None


def test_beam_label_in_candidate_record(tmp_path):
    nchan, nsamples = 64, 4096
    fname = write_beam(str(tmp_path / "b.fil"), nchan, nsamples, seed=1,
                       pulse_dm=150.0, nbeams=4, ibeam=2)
    out = str(tmp_path / "out")
    result = multibeam_search([fname], 100, 200, snr_threshold=7.0,
                              output_dir=out)
    beam = result["beams"][0]
    assert beam["beam"] == 2
    assert len(beam["hits"]) > 0
    istart, iend, info, table = beam["hits"][0]
    assert info.ibeam == 2 and info.nbeams == 4
    assert table.meta["ibeam"] == 2
    # the persisted record carries it too (reload from disk)
    info2, _ = beam["store"].load_candidate(beam["root"], istart, iend)
    assert info2.ibeam == 2 and info2.nbeams == 4
    # and hit_fields exposes it to the coincidence sift
    from pulsarutils_tpu.pipeline.sift import hit_fields

    assert hit_fields(istart, iend, info2, table)["beam"] == 2


# ---------------------------------------------------------------------------
# per-beam canary
# ---------------------------------------------------------------------------

def test_canary_beam_subsets_disjoint_and_deterministic():
    from pulsarutils_tpu.obs.canary import CanaryController

    chunks = list(range(0, 4000, 100))
    plain = CanaryController(rate=0.3, seed=5)
    plain2 = CanaryController(rate=0.3, seed=5)
    assert [plain.selects(c) for c in chunks] \
        == [plain2.selects(c) for c in chunks]
    b1 = CanaryController(rate=0.3, seed=5, beam=1)
    b2 = CanaryController(rate=0.3, seed=5, beam=2)
    s1 = [b1.selects(c) for c in chunks]
    s2 = [b2.selects(c) for c in chunks]
    assert s1 != s2, "beams at one seed must inject different subsets"
    b1b = CanaryController(rate=0.3, seed=5, beam=1)
    assert s1 == [b1b.selects(c) for c in chunks]


def test_canary_beam_label_on_gauges_and_json():
    from pulsarutils_tpu.obs import metrics as m
    from pulsarutils_tpu.obs.canary import CanaryController
    from pulsarutils_tpu.utils.table import ResultTable

    ctl = CanaryController(rate=1.0, seed=3, beam=9)
    ctl.bind(nchan=16, start_freq=1200.0, bandwidth=200.0, tsamp=5e-4,
             dmmin=100, dmmax=200)
    block = np.random.default_rng(0).normal(0, 1, (16, 2048))
    injected = ctl.maybe_inject(block, 0)
    assert injected is not block
    table = ResultTable({"DM": [150.0], "max": [1.0], "std": [1.0],
                         "snr": [1.0], "rebin": [1], "peak": [5]})
    ctl.observe(0, table, snr_threshold=6.0)  # a miss — still labelled
    snap = m.REGISTRY.snapshot()
    rows = [r for r in snap if r["name"] == "putpu_canary_recall"
            and r["labels"].get("beam") == "9"]
    assert rows, "recall gauge must carry the beam label"
    assert ctl.summary()["beam"] == 9
    assert ctl.to_json()["beam"] == 9
