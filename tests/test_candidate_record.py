"""A hit's record on disk (ISSUE 26).

``PulseInfo.save`` writes stored npz members (no deflate) holding the
same arrays bit for bit; records written the old way (deflated) still
read through every reader; the bytes a run writes are counted.

The record's cut-out of a chunk that lies on the device (ISSUE 50): the
window is cut there, and a window over the store's budget is block-summed
there too, so the record alone crosses the link; a NumPy chunk is cut and
summed by NumPy, to the bit what it always was.
"""
import dataclasses
import os
import zipfile

import numpy as np
import pytest

from pulsarutils_tpu.cli import cands_main
from pulsarutils_tpu.faults.audit import audit_run
from pulsarutils_tpu.io.candidates import CandidateStore, config_fingerprint
from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
from pulsarutils_tpu.models.simulate import disperse_array
from pulsarutils_tpu.obs.metrics import REGISTRY
from pulsarutils_tpu.pipeline.pulse_info import _ARRAY_FIELDS, PulseInfo
from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks
from pulsarutils_tpu.utils.logging_utils import BudgetAccountant
from pulsarutils_tpu.utils.table import ResultTable

NCHAN, NBIN, TSAMP = 16, 512, 0.0005
BYTES_WRITTEN = "putpu_candidate_bytes_written_total"


def make_info(case):
    """A record with the arrays a single-pulse hit, a periodic hit or a
    hit-less ``PulseInfo`` carries."""
    rng = np.random.default_rng(26)
    info = PulseInfo(nbin=NBIN, nchan=NCHAN, start_freq=1200.0,
                     bandwidth=200.0, pulse_freq=1.0 / (NBIN * TSAMP),
                     date=60000.5, t0=1.25, istart=2560, dm=150.0,
                     snr=12.5, width=TSAMP, cutout_start=7, cutout_decim=1)
    if case == "bare":
        return info
    info.allprofs = rng.normal(size=(NCHAN, NBIN)).astype(np.float32)
    info.disp_profile = info.allprofs.mean(0)
    info.dedisp_profile = rng.normal(size=4 * NBIN).astype(np.float32)
    info.compute_stats()
    if case == "periodic":
        info.period_freq, info.period_sigma = 60.06, 9.5
        info.fold_profile = rng.normal(size=64)  # float64 stays float64
    return info


def make_table():
    return ResultTable({"DM": np.array([149.0, 150.0, 151.0]),
                        "snr": np.array([7.0, 12.5, 8.0]),
                        "peak": np.array([100, 101, 102]),
                        "rebin": np.array([1, 1, 2])})


def deflate(path):
    """Rewrite a record as ``PulseInfo.save`` wrote it before ISSUE 26:
    the same members through ``np.savez_compressed``."""
    with np.load(path, allow_pickle=False) as data:
        np.savez_compressed(path, **{k: data[k] for k in data.files})
    return path


def assert_same_record(got, want):
    for f in dataclasses.fields(PulseInfo):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if f.name in _ARRAY_FIELDS and b is not None:
            assert a.dtype == b.dtype and a.shape == b.shape, f.name
            assert np.array_equal(a, b) and a.tobytes() == b.tobytes(), \
                f.name
        else:
            assert a == b, f.name


CASES = ("single_pulse", "periodic", "bare")


@pytest.mark.parametrize("case", CASES)
def test_save_load_round_trip_is_bit_identical(case, tmp_path):
    info = make_info(case)
    path = info.save(str(tmp_path / "c.info.npz"))
    assert_same_record(PulseInfo.load(path), info)


@pytest.mark.parametrize("case", CASES)
def test_every_member_is_stored_not_deflated(case, tmp_path):
    info = make_info(case)
    path = info.save(str(tmp_path / "c.info.npz"))
    with zipfile.ZipFile(path) as z:
        members = z.infolist()
    arrays = [n for n in _ARRAY_FIELDS if getattr(info, n) is not None]
    assert sorted(m.filename for m in members) == sorted(
        n + ".npy" for n in ["__scalars__"] + arrays)
    for m in members:
        assert m.compress_type == zipfile.ZIP_STORED, m.filename
        assert m.compress_size == m.file_size
    # the same bytes per array as the deflated encoding held
    with zipfile.ZipFile(deflate(path)) as z:
        assert {m.filename: m.file_size for m in z.infolist()} \
            == {m.filename: m.file_size for m in members}
        assert all(m.compress_type == zipfile.ZIP_DEFLATED
                   for m in z.infolist())


def _listing(directory, out):
    assert cands_main.main([str(directory), "--no-sift", "--csv",
                            str(out)]) == 0
    with open(out) as f:
        return f.read()


@pytest.mark.parametrize("reader", ("PulseInfo.load", "load_candidate",
                                    "PUcands", "audit"))
def test_a_record_written_deflated_still_reads(reader, tmp_path):
    """An ``.info.npz`` as every commit before ISSUE 26 wrote it goes
    through each reader exactly as one written now."""
    info, table = make_info("single_pulse"), make_table()
    fp = config_fingerprint(x="issue26")
    dirs = {}
    for how in ("old", "new"):
        d = tmp_path / how
        store = CandidateStore(str(d), fp)
        base = store.save_candidate("obs", 2560, 2560 + NBIN, info, table)
        if how == "old":
            deflate(base + ".info.npz")
        store.mark_done(2560)
        dirs[how] = d
    old = str(dirs["old"] / f"obs_2560-{2560 + NBIN}.info.npz")
    with zipfile.ZipFile(old) as z:
        assert {m.compress_type for m in z.infolist()} \
            == {zipfile.ZIP_DEFLATED}
    if reader == "PulseInfo.load":
        assert_same_record(PulseInfo.load(old), info)
    elif reader == "load_candidate":
        got, got_table = CandidateStore(str(dirs["old"]), fp) \
            .load_candidate("obs", 2560, 2560 + NBIN)
        assert_same_record(got, info)
        assert all(np.array_equal(got_table[c], table[c])
                   for c in table.colnames)
    elif reader == "PUcands":
        listing = _listing(dirs["old"], tmp_path / "old.csv")
        assert listing == _listing(dirs["new"], tmp_path / "new.csv")
        assert "obs," in listing and ",150.0,12.5," in listing
    else:
        reports = {how: audit_run(str(d), fp, root="obs")
                   for how, d in dirs.items()}
        assert reports["old"]["ok"] and not reports["old"]["orphans"]
        assert reports["old"] == reports["new"]


def _counter(name):
    return sum(rec["value"] for rec in REGISTRY.snapshot()
               if rec["name"] == name)


def test_store_counts_the_bytes_of_each_pair_it_writes(tmp_path):
    store = CandidateStore(str(tmp_path), None)
    before = _counter(BYTES_WRITTEN)
    total = 0
    for lo, case in ((0, "single_pulse"), (NBIN, "periodic")):
        base = store.save_candidate("obs", lo, lo + NBIN, make_info(case),
                                    make_table())
        pair = (os.path.getsize(base + ".info.npz")
                + os.path.getsize(base + ".table.npz"))
        assert store.pair_bytes(base) == pair
        total += pair
        assert _counter(BYTES_WRITTEN) == before + total
    # a stored record is its arrays plus headers: within 1 % of raw
    raw = sum(np.asarray(getattr(make_info("periodic"), n)).nbytes
              for n in _ARRAY_FIELDS)
    assert raw < os.path.getsize(base + ".info.npz") < 1.01 * raw + 4096


@pytest.fixture(scope="module")
def survey_file(tmp_path_factory):
    """Noise and one bright dispersed pulse: three chunks, two hits."""
    tmp = tmp_path_factory.mktemp("record")
    nchan, nsamples = 64, 32768
    rng = np.random.default_rng(0)
    array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
    array[:, 20000] += 4.0
    array = disperse_array(array, 150, 1200., 200., TSAMP)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
              "nsamples": nsamples, "tsamp": TSAMP, "foff": 200. / nchan}
    path = str(tmp / "survey.fil")
    write_simulated_filterbank(path, array, header, descending=True)
    return path


@pytest.mark.parametrize("overlap_persist", (True, False),
                         ids=("persist_worker", "serial"))
def test_run_counts_bytes_written_per_hit(survey_file, tmp_path,
                                          overlap_persist):
    """The counter rises by exactly the pairs' sizes on disk, and each
    hit chunk's ``BUDGET_JSON`` record carries its own as ``save_bytes``."""
    budget = BudgetAccountant()
    before = _counter(BYTES_WRITTEN)
    hits, store = search_by_chunks(
        survey_file, output_dir=str(tmp_path), dmmin=100, dmmax=200,
        backend="jax", chunk_length=8192 * TSAMP, make_plots=False,
        progress=False, snr_threshold=6.5, budget=budget,
        overlap_persist=overlap_persist)
    assert len(hits) == 2
    sizes = {}
    for root, lo, hi in store.candidates():
        base = os.path.join(str(tmp_path), f"{root}_{lo}-{hi}")
        sizes[lo] = (os.path.getsize(base + ".info.npz")
                     + os.path.getsize(base + ".table.npz"))
        with zipfile.ZipFile(base + ".info.npz") as z:
            assert {m.compress_type for m in z.infolist()} \
                == {zipfile.ZIP_STORED}
    assert sorted(sizes) == [h[0] for h in hits]
    assert _counter(BYTES_WRITTEN) == before + sum(sizes.values())
    per_chunk = {c["chunk"]: c for c in budget.to_json()["per_chunk"]}
    assert {lo: c["save_bytes"] for lo, c in per_chunk.items()
            if "save_bytes" in c} == sizes
    assert len(per_chunk) == 3  # the noise chunk persisted nothing


# -- the cut-out of a chunk on the device (ISSUE 50) -----------------------

CUT_NCHAN, CUT_NBIN = 32, 1 << 15
READBACK = ("putpu_cutout_readback_bytes_total", "putpu_bytes_readback_total")
DEVICE_DECIM = "putpu_cutout_device_decim_total"


def _cut_case(where, budget, tmp_path):
    """A chunk of noise, a hit whose track (DM 350 over 400-500 MHz:
    3,268 samples of 1 ms, 6,536 with the pads) lies where ``where``
    says, and a store whose budget holds ``budget`` elements."""
    rng = np.random.default_rng(50)
    wf = rng.normal(3.0, 1.0, (CUT_NCHAN, CUT_NBIN)).astype(np.float32)
    peak = {"inside": 9000, "wraps_the_end": CUT_NBIN - 50}[where]
    info = PulseInfo(allprofs=wf, nbin=CUT_NBIN, nchan=CUT_NCHAN,
                     start_freq=400.0, bandwidth=100.0,
                     pulse_freq=1.0 / (CUT_NBIN * 1e-3), dm=350.0, snr=20.0)
    table = ResultTable({"DM": np.array([350.0]), "snr": np.array([20.0]),
                         "peak": np.array([peak]), "rebin": np.array([1])})
    store = CandidateStore(str(tmp_path), None)
    store.WATERFALL_BUDGET = budget
    return wf, info, table, store, peak


def _moved(names, run):
    before = [_counter(n) for n in names]
    out = run()
    return out, [_counter(n) - b for n, b in zip(names, before)]


def _window(wf, peak):
    """The window ``trim_waterfall`` takes about ``peak``, written out."""
    span, pad = 3268, 1634
    cols = np.arange(peak - pad, peak + span + pad) % wf.shape[1]
    return cols[0], np.take(wf, cols, axis=1)


@pytest.mark.parametrize("where", ("inside", "wraps_the_end"))
def test_a_numpy_cutout_is_the_parents_to_the_bit(where, tmp_path):
    from pulsarutils_tpu.ops.rebin import quick_resample

    wf, info, table, store, peak = _cut_case(where, 1 << 14, tmp_path)
    cut, moved = _moved(READBACK + (DEVICE_DECIM,),
                        lambda: store.trim_waterfall(info, table))
    start, window = _window(wf, peak)
    decim = -(-window.size // (1 << 14))
    want = quick_resample(window, decim)
    assert (cut.cutout_start, cut.cutout_decim) == (start, decim) \
        and decim == 13
    assert cut.allprofs.dtype == want.dtype == np.float32
    assert cut.allprofs.tobytes() == want.tobytes()
    assert info.allprofs is wf and moved == [0, 0, 0]  # nothing crossed


@pytest.mark.parametrize("where", ("inside", "wraps_the_end"))
def test_a_device_cutout_is_summed_where_it_lies(where, tmp_path):
    """Same window, decimation, shape and dtype as the host path on the
    same values; the float32 sums of ``decim`` neighbours in another
    order; and what is counted as read back is the sums, not the window."""
    import jax.numpy as jnp

    wf, info, table, store, peak = _cut_case(where, 1 << 14, tmp_path)
    host = store.trim_waterfall(info, table)
    on_device = dataclasses.replace(info, allprofs=jnp.asarray(wf))
    cut, moved = _moved(READBACK + (DEVICE_DECIM,),
                        lambda: store.trim_waterfall(on_device, table))
    assert (cut.cutout_start, cut.cutout_decim) \
        == (host.cutout_start, host.cutout_decim)
    assert isinstance(cut.allprofs, np.ndarray)
    assert (cut.allprofs.shape, cut.allprofs.dtype) \
        == (host.allprofs.shape, host.allprofs.dtype)
    np.testing.assert_allclose(cut.allprofs, host.allprofs,
                               rtol=1e-5, atol=1e-6)
    _, window = _window(wf, peak)
    n = cut.allprofs.shape[1]
    exact = window[:, :n * 13].astype(np.float64).reshape(-1, n, 13).sum(2)
    # 13 float32 additions of values near 3: a few units of 2^-24 x 39,
    # and no further from the float64 sum than the host's are
    worst = np.abs(host.allprofs - exact).max()
    assert np.abs(cut.allprofs - exact).max() <= max(2 * worst, 1e-5)
    assert moved == [cut.allprofs.nbytes, cut.allprofs.nbytes, 1]
    assert cut.allprofs.nbytes * 12 < window.nbytes
    assert on_device.allprofs.shape == wf.shape  # the chunk is untouched


@pytest.mark.parametrize("what", ("window", "chunk"))
def test_a_device_cutout_under_the_budget_crosses_as_it_is(what, tmp_path):
    """A window (or a whole chunk) the budget holds is read back value
    for value, as ever, and its own bytes are what is counted."""
    import jax.numpy as jnp

    wf, info, table, store, peak = _cut_case(
        "wraps_the_end",
        (1 << 18) if what == "window" else CUT_NCHAN * CUT_NBIN, tmp_path)
    on_device = dataclasses.replace(info, allprofs=jnp.asarray(wf))
    cut, moved = _moved(READBACK + (DEVICE_DECIM,),
                        lambda: store.trim_waterfall(on_device, table))
    start, window = _window(wf, peak)
    want = window if what == "window" else wf
    assert isinstance(cut.allprofs, np.ndarray)
    assert cut.allprofs.tobytes() == want.tobytes()
    assert (cut.cutout_start, cut.cutout_decim) \
        == ((start, 1) if what == "window" else (None, None))
    assert moved == [want.nbytes, want.nbytes, 0]
    # the record, now on the host and under the budget, is what is saved
    assert store.trim_waterfall(cut, table) is cut


@pytest.mark.parametrize("length,factor,starts", [
    (1561, 13, (0, 8610)),       # 120 sums: one block, under a lane's width
    (13184, 52, (40, 19000)),    # 253: the second block overlaps the first
    (6940, 7, (1, 25828)),       # 991 sums in eight blocks
    (512, 2, (32256,)),          # 256: two whole blocks, at the array's end
])
def test_window_resample_program_is_quick_resample(length, factor, starts):
    """``jit_window_resample`` against NumPy's block sums of the same
    window, a trailing fragment truncated; one program whatever the
    start."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.rebin import (quick_resample,
                                           window_resample_program)

    rng = np.random.default_rng(length)
    counts = rng.normal(0.0, 1.0, (8, CUT_NBIN)).astype(np.float32)
    program = window_resample_program(length, factor)
    assert program is window_resample_program(length, factor)
    for start in starts:
        got = np.asarray(program(jnp.asarray(counts), np.int32(start)))
        want = quick_resample(counts[:, start:start + length], factor)
        assert got.dtype == np.float32 and got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
    assert program._cache_size() == 1


def test_a_run_reads_back_its_records_and_nothing_more(survey_file, tmp_path,
                                                       monkeypatch):
    """Through ``search_by_chunks`` with the store's budget lowered under
    both hits' windows: each record came summed off the device, and the
    read-back counters moved by the records' bytes."""
    monkeypatch.setattr(CandidateStore, "WATERFALL_BUDGET", 1 << 12)
    (hits, _), moved = _moved(
        READBACK + (DEVICE_DECIM,),
        lambda: search_by_chunks(
            survey_file, output_dir=str(tmp_path), dmmin=100, dmmax=200,
            backend="jax", chunk_length=8192 * TSAMP, make_plots=False,
            progress=False, snr_threshold=6.5))
    assert len(hits) == 2
    records = [info.allprofs for _, _, info, _ in hits]
    assert all(isinstance(r, np.ndarray) and r.size <= 1 << 12
               for r in records)
    assert all(info.cutout_decim > 1 for _, _, info, _ in hits)
    total = sum(r.nbytes for r in records)
    assert moved == [total, total, 2]
