"""A chunk's upload is the continuation of its read (ISSUE 46).

The reader thread puts a chunk's bytes on the device when its read ends
and waits the transfer out before it reads on; the main thread takes
that buffer, or uploads itself where the reader did not or could not.
CPU, ``backend="jax"``: who uploaded what is counted, never timed.
"""
import threading
import time

import jax
import numpy as np
import pytest

from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
from pulsarutils_tpu.io.sigproc import (
    FilterbankReader,
    write_simulated_filterbank,
)
from pulsarutils_tpu.models.simulate import disperse_array
from pulsarutils_tpu.obs import trace
from pulsarutils_tpu.obs.metrics import REGISTRY
from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks
from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

TSAMP = 0.0005
NCHAN = 64
NSAMPLES = 32768
STEP = 16384                        # three chunks: 0, 8192, 16384
NCHUNKS = 3
PULSE_T = 20000                     # chunk 0 is noise, the other two hit
#: a chunk as the reader hands it over: the host's float64 decode of a
#: 32-bit file, the packed frames of a 2-bit one
CHUNK_BYTES = {32: NCHAN * STEP * 8, 2: NCHAN * STEP // 4}
SEARCH_KW = dict(dmmin=100, dmmax=200, chunk_length=8192 * TSAMP,
                 make_plots=False, progress=False, snr_threshold=6.5)


def _counter(name):
    for rec in REGISTRY.snapshot():
        if rec["name"] == name and not rec["labels"]:
            return rec["value"]
    return 0


def _write(path, nbits):
    rng = np.random.default_rng(0)
    if nbits == 32:
        array = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
        array[:, PULSE_T] += 4.0
    else:
        array = rng.normal(1.6, 0.6, (NCHAN, NSAMPLES))
        array[:, PULSE_T] += 2.2
    array = disperse_array(array, 150, 1200., 200., TSAMP)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
              "nsamples": NSAMPLES, "tsamp": TSAMP, "foff": 200. / NCHAN}
    write_simulated_filterbank(path, array, header, descending=True,
                               nbits=nbits)


@pytest.fixture(scope="module")
def survey_files(tmp_path_factory):
    """The same sky as floats and as packed 2-bit frames, bad-channel
    side-cars written so no armed plan fires in the pre-scan."""
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    tmp = tmp_path_factory.mktemp("upload_overlap")
    paths = {}
    for nbits in (32, 2):
        paths[nbits] = str(tmp / f"survey{nbits}.fil")
        _write(paths[nbits], nbits)
        get_bad_chans(paths[nbits])
    return paths


@pytest.fixture
def puts(monkeypatch):
    """Every ``jax.device_put`` of a host chunk, by the thread that made
    it: ``{"reader": [nbytes...], "main": [...]}``.  Only chunks are
    NumPy arrays with a chunk's length along one axis."""
    seen = {"reader": [], "main": []}
    orig = jax.device_put

    def spy(x, *a, **k):
        if isinstance(x, np.ndarray) and STEP in x.shape:
            main = threading.current_thread() is threading.main_thread()
            seen["main" if main else "reader"].append(int(x.nbytes))
        return orig(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    return seen


def _slow_reads(monkeypatch, seconds):
    """Reads after a call's first take ``seconds``: longer than a tiny
    chunk's whole clean and search, so the read of chunk k+1 ends after
    chunk k has nothing left to hide it behind."""
    for name in ("read_block", "read_block_packed"):
        orig = getattr(FilterbankReader, name)

        def slow(self, istart, *a, _orig=orig, **k):
            if istart:
                time.sleep(seconds)
            return _orig(self, istart, *a, **k)

        monkeypatch.setattr(FilterbankReader, name, slow)


def _run(path, outdir, **kw):
    acct = BudgetAccountant()
    before = _counter("putpu_bytes_uploaded_total")
    hits, store = search_by_chunks(path, backend="jax", budget=acct,
                                   output_dir=str(outdir),
                                   **{**SEARCH_KW, **kw})
    budget = acct.to_json()
    budget["bytes_uploaded"] = (_counter("putpu_bytes_uploaded_total")
                                - before)
    return hits, store, budget


def _assert_same_hits(hits, ref):
    """As ``tests/test_pipeline.py`` compares two backends' runs."""
    assert len(hits) == len(ref) >= 1
    for h, r in zip(hits, ref):
        assert h[:2] == r[:2]
        assert np.isclose(h[2].dm, r[2].dm, atol=1e-6)
        assert np.isclose(h[2].snr, r[2].snr, rtol=1e-4)


@pytest.fixture(scope="module")
def numpy_hits(survey_files, tmp_path_factory):
    out = tmp_path_factory.mktemp("upload_overlap_numpy")
    return {nbits: search_by_chunks(path, backend="numpy",
                                    output_dir=str(out / str(nbits)),
                                    **SEARCH_KW)[0]
            for nbits, path in survey_files.items()}


@pytest.mark.parametrize("nbits", [32, 2])
@pytest.mark.parametrize("read_s", [0.0, 0.4], ids=["instant", "slow"])
def test_every_chunk_goes_up_behind_its_read(survey_files, numpy_hits, puts,
                                             monkeypatch, tmp_path, nbits,
                                             read_s):
    """Whether the read ends inside the pre-search window or long after
    it, the reader uploads every chunk and the main thread none."""
    if read_s:
        _slow_reads(monkeypatch, read_s)
    hits, _, budget = _run(survey_files[nbits], tmp_path)
    assert budget["counters"]["prefetch_uploads"] == NCHUNKS
    assert 0 <= budget["counters"].get("uploads_ready", 0) <= NCHUNKS
    assert len(puts["reader"]) == NCHUNKS and puts["main"] == []
    # each chunk's bytes counted once, wherever it went up
    assert budget["bytes_uploaded"] == sum(puts["reader"])
    assert budget["bytes_uploaded"] == NCHUNKS * CHUNK_BYTES[nbits]
    assert budget["async_s"]["upload"] >= 0
    _assert_same_hits(hits, numpy_hits[nbits])
    for rec in budget["per_chunk"]:
        assert rec["counters"]["prefetch_uploads"] == 1


@pytest.mark.parametrize("frac,verdict", [(0.02, "sanitized"),
                                          (0.9, "quarantine")])
def test_gated_chunk_never_goes_up_from_the_reader(survey_files, puts,
                                                   tmp_path, frac, verdict):
    plan = FaultPlan([FaultSpec(site="corrupt", kind="nan", chunks=(0,),
                                frac=frac, times=1)])
    ref, _, _ = _run(survey_files[32], tmp_path / "ref")
    puts["reader"].clear()
    with plan.armed():
        hits, store, budget = _run(survey_files[32], tmp_path / "gated")
    assert plan.fired() == 1
    chunk_bytes = CHUNK_BYTES[32]
    assert puts["reader"] == [chunk_bytes] * (NCHUNKS - 1)
    assert budget["counters"]["prefetch_uploads"] == NCHUNKS - 1
    if verdict == "sanitized":
        # the imputed block, once, from the main path
        assert puts["main"] == [chunk_bytes]
        assert budget["bytes_uploaded"] == NCHUNKS * chunk_bytes
        assert store.quarantined_chunks == {}
    else:
        assert puts["main"] == []
        assert budget["bytes_uploaded"] == (NCHUNKS - 1) * chunk_bytes
        assert store.quarantined_chunks == {"0": "integrity:nan_frac"}
    _assert_same_hits(hits, ref)


def test_read_failure_never_goes_up_and_keeps_the_next_chunks_buffer(
        survey_files, puts, tmp_path):
    """A chunk that cannot be read is quarantined; the chunk after it
    still comes with its own buffer."""
    plan = FaultPlan([FaultSpec(site="read", kind="error", chunks=(8192,),
                                times=3)])
    with plan.armed():
        hits, store, budget = _run(survey_files[32], tmp_path)
    assert plan.fired() == 3
    assert store.quarantined_chunks == {"8192": "read_error"}
    assert len(puts["reader"]) == NCHUNKS - 1 and puts["main"] == []
    assert [rec["counters"].get("prefetch_uploads", 0)
            for rec in budget["per_chunk"]] == [1, 0, 1]
    assert any(lo <= PULSE_T < hi for lo, hi, _, _ in hits)


def test_put_that_raises_on_the_reader_leaves_the_result(survey_files, puts,
                                                         monkeypatch,
                                                         tmp_path):
    ref, _, _ = _run(survey_files[32], tmp_path / "ref")
    spy = jax.device_put

    def reader_cannot(x, *a, **k):
        if isinstance(x, np.ndarray) and STEP in x.shape \
                and threading.current_thread() is not threading.main_thread():
            raise RuntimeError("injected: the reader's put fails")
        return spy(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", reader_cannot)
    for seen in puts.values():
        seen.clear()
    hits, _, budget = _run(survey_files[32], tmp_path / "main_path")
    assert puts["reader"] == [] and len(puts["main"]) == NCHUNKS
    assert budget["counters"].get("prefetch_uploads", 0) == 0
    assert budget["counters"].get("uploads_ready", 0) == 0
    assert budget["bytes_uploaded"] == sum(puts["main"])
    assert "upload" not in budget["async_s"]
    assert budget["counters"].get("host_sweeps", 0) == 0
    _assert_same_hits(hits, ref)
    assert [(h[2].dm, h[2].snr) for h in hits] \
        == [(r[2].dm, r[2].snr) for r in ref]


def test_cancel_mid_file_leaves_no_reader_task_pending(survey_files,
                                                       monkeypatch,
                                                       tmp_path):
    calls = {"in": 0, "out": 0}
    orig = FilterbankReader.read_block

    def counted(self, *a, **k):
        calls["in"] += 1
        try:
            time.sleep(0.2)  # still reading when the cancel lands
            return orig(self, *a, **k)
        finally:
            calls["out"] += 1

    monkeypatch.setattr(FilterbankReader, "read_block", counted)
    threads = set(threading.enumerate())
    polls = iter([False, True])
    hits, store, budget = _run(survey_files[32], tmp_path,
                               cancel_cb=lambda: next(polls))
    assert budget["chunks"] == 1 and store.is_done(0)
    assert not store.is_done(8192)
    # the read submitted behind chunk 0 ran to its end, upload and all
    assert calls["in"] == calls["out"] == 2
    left = [t for t in set(threading.enumerate()) - threads if t.is_alive()]
    assert left == []


def test_upload_span_follows_its_read_on_the_reader_track(survey_files,
                                                          tmp_path):
    """``upload`` is an async span of the reader's track with its chunk,
    begun where that chunk's ``read_decode`` ended and over before the
    next chunk's begins: two transfers never share the link."""
    tracer = trace.start_tracing()
    try:
        with trace.trace_context(trace.new_trace_id()), \
                trace.span("call") as root:
            _run(survey_files[2], tmp_path)
    finally:
        trace.stop_tracing()
    events = tracer.events_since(0)[0]
    reader_tid = tracer.tracks()["reader"]

    def marks(name, ph):
        return {e["args"]["chunk"] if ph == "b" else e["id"]: e
                for e in events if e["name"] == name and e["ph"] == ph}

    reads_b, uploads_b = marks("read_decode", "b"), marks("upload", "b")
    assert sorted(uploads_b) == sorted(reads_b) == [0, 8192, 16384]
    ends = {e["id"]: e["ts"] for e in events if e["ph"] == "e"}
    by_id = {e["args"]["span_id"]: e for e in events
             if "span_id" in e.get("args", {})}
    for chunk, up in uploads_b.items():
        read = reads_b[chunk]
        assert up["tid"] == read["tid"] == reader_tid
        assert up["args"]["trace_id"] == read["args"]["trace_id"]
        assert up["args"]["parent_id"] == read["args"]["parent_id"]
        assert ends[read["id"]] <= up["ts"] <= ends[up["id"]]
        # the parent chain ends at the call's root
        node = up
        while "parent_id" in node["args"]:
            node = by_id[node["args"]["parent_id"]]
        assert node["args"]["span_id"] == root.span_id
    order = sorted(reads_b)
    for before, after in zip(order, order[1:]):
        assert ends[uploads_b[before]["id"]] <= uploads_b[after]["ts"]
