"""End-to-end packed low-bit data path (ISSUE 11).

The house proof rule, applied to every scaled dispatch surface: a run
fed RAW packed 1/2/4-bit bytes (device unpack, integer sweep
accumulation where exact) must produce candidates, ledgers and tables
BYTE-identical to the same run fed the host-unpacked float codes —
single-device stream, shard_map mesh, batched-beam, incl. ragged tails
and descending bands.  Plus: the packed canary injection is
deterministic and canary-off stays byte-inert, and the code-domain
integrity gate actually fires on broken low-bit chunks.
"""

import os

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from pulsarutils_tpu.io.lowbit import (  # noqa: E402
    PackedFrames,
    accum_dtype,
    pack_numpy,
)
from pulsarutils_tpu.io.sigproc import (  # noqa: E402
    FilterbankReader,
    FilterbankWriter,
)

GEOM = (1200.0, 200.0, 0.0005)  # start_freq, bandwidth, tsamp


def make_codes(nchan, nsamps, nbits, seed=0, pulse_t=None, pulse_amp=3):
    """Quantized survey codes with an optional dispersed pulse."""
    from pulsarutils_tpu.models.simulate import disperse_array

    rng = np.random.default_rng(seed)
    arr = rng.integers(0, (1 << nbits), (nchan, nsamps)).astype(np.float64)
    if pulse_t is not None:
        base = np.zeros((nchan, nsamps))
        base[:, pulse_t] = pulse_amp
        arr = arr + disperse_array(base, 150.0, GEOM[0], GEOM[1], GEOM[2])
    return np.clip(np.rint(arr), 0, (1 << nbits) - 1).astype(np.float32)


def pack_codes(codes, nbits, descending=True):
    """Codes -> raw SIGPROC frames (file order) + the PackedFrames."""
    file_order = codes[::-1] if descending else codes
    frames = np.stack([pack_numpy(file_order[:, t], nbits)
                       for t in range(codes.shape[1])])
    return frames, PackedFrames(frames, nbits, codes.shape[0],
                                band_descending=descending)


def write_lowbit(path, codes, nbits, descending=True, **extra):
    nchan = codes.shape[0]
    header = {"nchans": nchan, "nbits": nbits, "nifs": 1, "tsamp": GEOM[2],
              "fch1": (GEOM[0] + GEOM[1]) if descending else GEOM[0],
              "foff": (-GEOM[1] / nchan) if descending else GEOM[1] / nchan,
              "tstart": 60000.0, **extra}
    with FilterbankWriter(path, header) as w:
        w.write_block(codes[::-1] if descending else codes)


def assert_tables_equal(a, b, msg=""):
    assert a.colnames == b.colnames
    for c in a.colnames:
        np.testing.assert_array_equal(np.asarray(a[c]), np.asarray(b[c]),
                                      err_msg=f"{msg}:{c}")


# ---------------------------------------------------------------------------
# Integer sweep accumulation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_int_accumulation_exact_vs_float(nbits):
    """int16/int32-accumulated dedispersion plane == float32 plane,
    value for value (every sum is an exact integer below 2^24)."""
    from pulsarutils_tpu.ops.dedisperse import dedisperse_block_chunked_jax
    from pulsarutils_tpu.ops.search import score_profiles_stacked

    nchan, nsamps = 64, 2048
    codes = make_codes(nchan, nsamps, nbits, seed=nbits)
    acc = accum_dtype(nbits, nchan)
    assert acc in ("int16", "int32")
    rng = np.random.default_rng(1)
    offsets = rng.integers(0, nsamps, (8, nchan)).astype(np.int32)
    for formulation in ("gather", "roll"):
        plane_f = np.asarray(dedisperse_block_chunked_jax(
            jnp.asarray(codes, jnp.float32), jnp.asarray(offsets),
            None, formulation=formulation))
        plane_i = np.asarray(dedisperse_block_chunked_jax(
            jnp.asarray(codes, getattr(jnp, acc)), jnp.asarray(offsets),
            None, formulation=formulation))
        assert plane_i.dtype == np.dtype(acc)
        np.testing.assert_array_equal(plane_i.astype(np.float32), plane_f)
        # scores off the integer plane == scores off the float plane
        np.testing.assert_array_equal(
            np.asarray(score_profiles_stacked(jnp.asarray(plane_i),
                                              xp=jnp)),
            np.asarray(score_profiles_stacked(jnp.asarray(plane_f),
                                              xp=jnp)))


# ---------------------------------------------------------------------------
# Single-device + streaming driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbits,descending", [(1, True), (2, True),
                                              (2, False), (4, True)])
def test_stream_packed_vs_host_unpack_identity(tmp_path, nbits, descending):
    """stream_search fed PackedFrames == fed host-unpacked float codes,
    every chunk's table byte for byte — incl. a ragged final chunk —
    and the uploaded-bytes ratio shows the packed link win."""
    from pulsarutils_tpu.obs import metrics as m
    from pulsarutils_tpu.parallel.stream import stream_search

    nchan, step = 32, 4096
    nsamps = 2 * step + step // 2  # ragged tail
    codes = make_codes(nchan, nsamps, nbits, seed=3, pulse_t=step + 100,
                       pulse_amp=(1 << nbits))
    path = str(tmp_path / f"s{nbits}{descending}.fil")
    write_lowbit(path, codes, nbits, descending)
    r = FilterbankReader(path)

    def chunks_packed():
        return [(s, PackedFrames.read(r, s, step))
                for s in range(0, nsamps, step)]

    def chunks_host():
        return [(s, r.read_block(s, step,
                                 band_ascending=True).astype(np.float32))
                for s in range(0, nsamps, step)]

    dms = np.linspace(100., 200., 32)
    up = m.counter("putpu_bytes_uploaded_total")
    before = up.value
    res_h, hits_h = stream_search(chunks_host(), 100, 200, *GEOM,
                                  trial_dms=dms)
    host_bytes = up.value - before
    before = up.value
    res_p, hits_p = stream_search(chunks_packed(), 100, 200, *GEOM,
                                  trial_dms=dms)
    packed_bytes = up.value - before
    assert len(res_h) == len(res_p) == 3
    for (i1, t1), (i2, t2) in zip(res_h, res_p):
        assert i1 == i2
        assert_tables_equal(t1, t2, msg=f"chunk {i1}")
    assert len(hits_h) == len(hits_p)
    # float32 upload is 32/nbits the packed bytes
    assert packed_bytes > 0
    assert host_bytes / packed_bytes >= 8


def test_packed_chunk_counters(tmp_path):
    from pulsarutils_tpu.obs import metrics as m
    from pulsarutils_tpu.parallel.stream import stream_search

    nchan, step = 32, 2048
    codes = make_codes(nchan, 2 * step, 2, seed=5)
    path = str(tmp_path / "c.fil")
    write_lowbit(path, codes, 2, True)
    r = FilterbankReader(path)
    chunks = [(s, PackedFrames.read(r, s, step))
              for s in range(0, 2 * step, step)]
    n0 = m.counter("putpu_lowbit_packed_chunks_total").value
    b0 = m.counter("putpu_lowbit_bytes_saved_total").value
    stream_search(chunks, 100, 200, *GEOM,
                  trial_dms=np.linspace(100., 200., 16))
    assert m.counter("putpu_lowbit_packed_chunks_total").value - n0 == 2
    # 2-bit: each chunk saves nchan*step*(4 - 1/4) bytes
    assert (m.counter("putpu_lowbit_bytes_saved_total").value - b0
            == 2 * nchan * step * 4 - 2 * nchan * step // 4)


# ---------------------------------------------------------------------------
# Mesh surfaces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbits", [2, 4])
def test_mesh_packed_identity(nbits):
    """Packed input through the fused mesh hybrid, the sharded FDMT and
    the (dm, chan) exact sweep == the float-block run, byte for byte."""
    from pulsarutils_tpu.parallel.mesh import make_mesh
    from pulsarutils_tpu.parallel.sharded import sharded_dedispersion_search
    from pulsarutils_tpu.parallel.sharded_fdmt import (
        sharded_fdmt_search,
        sharded_hybrid_search,
    )

    nchan, nsamps = 32, 8192
    codes = make_codes(nchan, nsamps, nbits, seed=7, pulse_t=5000,
                       pulse_amp=(1 << nbits))
    _, pf = pack_codes(codes, nbits, descending=True)
    mesh = make_mesh((4, 2), ("dm", "chan"))

    t_h = sharded_hybrid_search(codes, 100, 200, *GEOM, mesh=mesh)
    t_p = sharded_hybrid_search(pf, 100, 200, *GEOM, mesh=mesh)
    assert_tables_equal(t_h, t_p, msg="hybrid")

    t_h = sharded_fdmt_search(codes, 100, 200, *GEOM, mesh=mesh)
    t_p = sharded_fdmt_search(pf, 100, 200, *GEOM, mesh=mesh)
    assert_tables_equal(t_h, t_p, msg="fdmt")

    t_h = sharded_dedispersion_search(codes, 100, 200, *GEOM, mesh=mesh)
    t_p = sharded_dedispersion_search(pf, 100, 200, *GEOM, mesh=mesh)
    assert_tables_equal(t_h, t_p, msg="sweep")


# ---------------------------------------------------------------------------
# Batched-beam surface
# ---------------------------------------------------------------------------

def test_batched_beam_packed_identity():
    """Packed BeamBatcher (per-beam in-jit unpack, integer
    accumulation) == float batcher == the packed sequential arm, for
    interior and ragged-tail lengths."""
    from pulsarutils_tpu.beams.batcher import BeamBatcher

    nchan, nsamps, nbits = 32, 4096, 2
    dms = np.linspace(100., 200., 24)
    beams = [make_codes(nchan, nsamps, nbits, seed=20 + b,
                        pulse_t=2000 if b == 1 else None, pulse_amp=4)
             for b in range(3)]
    packed = [pack_codes(c, nbits, descending=True)[0] for c in beams]

    plain = BeamBatcher(nchan, nsamps, dms, *GEOM, kernel="roll")
    pb = BeamBatcher(nchan, nsamps, dms, *GEOM, kernel="roll",
                     packed=(nbits, True))
    # integer accumulation is actually engaged on the packed batcher
    assert pb.packed_meta[3] == accum_dtype(nbits, nchan)
    for length in (nsamps, nsamps - 513):  # interior + ragged tail
        t_f = plain.search([c[:, :length] for c in beams])
        t_p = pb.search([f[:length] for f in packed])
        for b, (tf, tp) in enumerate(zip(t_f, t_p)):
            assert_tables_equal(tf, tp, msg=f"beam {b} len {length}")
        t_s = [pb.search_single(f[:length]) for f in packed]
        for b, (tp, ts) in enumerate(zip(t_p, t_s)):
            assert_tables_equal(tp, ts, msg=f"seq beam {b} len {length}")


def test_multibeam_driver_packed_modes(tmp_path):
    """multibeam_search packed='device' vs packed='host': per-beam
    tables and every persisted candidate/ledger file byte-identical."""
    from pulsarutils_tpu.beams.multibeam import multibeam_search

    nbeams, nchan, nsamps, nbits = 3, 32, 6144, 2
    fnames = []
    for b in range(nbeams):
        codes = make_codes(nchan, nsamps, nbits, seed=40 + b,
                           pulse_t=4000 if b == 1 else None, pulse_amp=5)
        path = str(tmp_path / f"beam{b}.fil")
        write_lowbit(path, codes, nbits, True, nbeams=nbeams, ibeam=b + 1)
        fnames.append(path)

    def run(arm, packed):
        return multibeam_search(fnames, 100, 200, snr_threshold=7.0,
                                output_dir=str(tmp_path / arm),
                                keep_tables=True, resume=True,
                                packed=packed)

    r_dev = run("dev", "device")
    r_host = run("host", "host")
    for bd, bh in zip(r_dev["beams"], r_host["beams"]):
        assert len(bd["tables"]) == len(bh["tables"])
        for (i1, t1), (i2, t2) in zip(bd["tables"], bh["tables"]):
            assert i1 == i2
            assert_tables_equal(t1, t2, msg=f"beam {bd['beam']} chunk {i1}")
    names = (set(os.listdir(tmp_path / "dev"))
             | set(os.listdir(tmp_path / "host")))
    assert names  # at least the ledgers exist
    for name in sorted(names):
        a = tmp_path / "dev" / name
        b = tmp_path / "host" / name
        assert a.exists() and b.exists(), name
        if name.endswith(".json"):
            assert a.read_bytes() == b.read_bytes(), name
        elif name.endswith(".npz"):
            with np.load(a, allow_pickle=False) as za, \
                    np.load(b, allow_pickle=False) as zb:
                assert set(za.files) == set(zb.files)
                for k in za.files:
                    assert za[k].tobytes() == zb[k].tobytes(), (name, k)


# ---------------------------------------------------------------------------
# Packed canary
# ---------------------------------------------------------------------------

def _canary_survey(tmp_path, arm, canary, codes, nbits=2):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    path = str(tmp_path / f"{arm}.fil")
    write_lowbit(path, codes, nbits, True)
    out = str(tmp_path / f"out_{arm}")
    hits, store = search_by_chunks(
        path, dmmin=100, dmmax=200, backend="jax", output_dir=out,
        make_plots=False, snr_threshold=6.0, progress=False,
        canary=canary)
    return hits, out


def test_packed_canary_measured_and_deterministic(tmp_path):
    """Canary recall is MEASURED (not auto-disabled) on a packed run,
    the injection is deterministic across repeats, and the science
    candidate set matches the canary-off run."""
    from pulsarutils_tpu.obs import metrics as m
    from pulsarutils_tpu.obs.canary import CanaryController

    codes = make_codes(64, 3 * 4096, 2, seed=50, pulse_t=9000,
                       pulse_amp=4)
    hits_off, out_off = _canary_survey(tmp_path, "off", None, codes)

    before = m.counter("putpu_canary_packed_injections_total").value
    c1 = CanaryController(rate=1.0, snr=14.0, seed=9)
    hits_a, out_a = _canary_survey(tmp_path, "a", c1, codes)
    injected = (m.counter("putpu_canary_packed_injections_total").value
                - before)
    assert injected > 0
    assert c1.injected == injected  # observed, not discarded
    assert c1.recovered > 0  # the quantized bump is detectable

    c2 = CanaryController(rate=1.0, snr=14.0, seed=9)
    hits_b, out_b = _canary_survey(tmp_path, "b", c2, codes)
    assert c1.injected == c2.injected
    assert c1.recovered == c2.recovered
    assert [p[:2] for p in c1.curve] == [p[:2] for p in c2.curve]

    # science candidate SET: canary-on == canary-off (canaries are
    # tagged/excluded, the real pulse persists; its per-trial table may
    # legitimately carry canary-lit rows — the documented
    # "contaminated table" case — so the pin is set-level, and full
    # byte determinism is pinned between the two canary-on repeats)
    spans_off = {(h[0], h[1]) for h in hits_off}
    assert {(h[0], h[1]) for h in hits_a} == spans_off
    assert {(h[0], h[1]) for h in hits_b} == spans_off
    for h_a, h_b in zip(sorted(hits_a), sorted(hits_b)):
        assert_tables_equal(h_a[3], h_b[3], msg=f"chunk {h_a[0]}")


def test_packed_canary_quantizes_onto_code_grid():
    """Injected packed bytes decode to codes on the 0..2^nbits-1 grid —
    the device signature is exact by construction."""
    from pulsarutils_tpu.obs.canary import CanaryController

    nchan, nsamps, nbits = 32, 4096, 2
    codes = make_codes(nchan, nsamps, nbits, seed=60)
    frames, pf = pack_codes(codes, nbits, descending=True)
    c = CanaryController(rate=1.0, snr=20.0, seed=1)
    c.bind(nchan=nchan, start_freq=GEOM[0], bandwidth=GEOM[1],
           tsamp=GEOM[2], dmmin=100, dmmax=200)
    out = c.maybe_inject_packed(frames, 0, nbits=nbits, nchan=nchan,
                                band_descending=True)
    assert out is not frames  # selected -> a modified copy
    decoded = PackedFrames(out, nbits, nchan,
                           band_descending=True).to_host()
    assert decoded.min() >= 0 and decoded.max() <= (1 << nbits) - 1
    diff = decoded - codes
    assert np.any(diff != 0)  # the bump landed
    assert np.all(diff >= 0)  # additive, clipped at the rail
    # un-selected chunk: byte-inert
    c2 = CanaryController(rate=0.5, snr=20.0, seed=1)
    c2.bind(nchan=nchan, start_freq=GEOM[0], bandwidth=GEOM[1],
            tsamp=GEOM[2], dmmin=100, dmmax=200)
    unselected = next(k for k in range(64) if not c2.selects(k))
    assert c2.maybe_inject_packed(frames, unselected, nbits=nbits,
                                  nchan=nchan,
                                  band_descending=True) is frames


# ---------------------------------------------------------------------------
# Packed integrity gate
# ---------------------------------------------------------------------------

def test_packed_gate_verdicts():
    from pulsarutils_tpu.faults.policy import (
        IntegrityPolicy,
        gate_chunk_lowbit,
        gate_chunk_packed,
    )

    nchan, nsamps, nbits = 32, 2048, 2
    policy = IntegrityPolicy()

    healthy = make_codes(nchan, nsamps, nbits, seed=70)
    frames, _ = pack_codes(healthy, nbits, descending=True)
    _, info = gate_chunk_packed(frames, nbits, nchan, policy)
    assert info["verdict"] == "clean"

    # dropped-packet chunk: all zero codes -> quarantined
    zeros = np.zeros_like(frames)
    _, info = gate_chunk_packed(zeros, nbits, nchan, policy)
    assert info["verdict"] == "quarantine"
    assert "zero_frac" in info["reasons"]
    assert "dead_frac" in info["reasons"]

    # clipped digitiser: every code at the top rail -> quarantined
    rails = np.full_like(frames, 0xFF)
    _, info = gate_chunk_packed(rails, nbits, nchan, policy)
    assert info["verdict"] == "quarantine"
    assert "rail_frac" in info["reasons"]

    # host-decoded code block: same rule
    _, info = gate_chunk_lowbit(healthy, nbits, policy)
    assert info["verdict"] == "clean"
    _, info = gate_chunk_lowbit(np.zeros_like(healthy), nbits, policy)
    assert info["verdict"] == "quarantine"


def test_packed_gate_quarantines_in_pipeline(tmp_path):
    """An all-zero packed low-bit file no longer silently passes: the
    code-domain gate quarantines every chunk under the default
    policy (the float gate used to skip low-bit data entirely)."""
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    nchan, nsamps = 32, 2 * 4096
    codes = np.zeros((nchan, nsamps), dtype=np.float32)
    path = str(tmp_path / "dead.fil")
    write_lowbit(path, codes, 2, True)
    hits, store = search_by_chunks(
        path, dmmin=100, dmmax=200, backend="jax",
        output_dir=str(tmp_path / "out"), make_plots=False,
        snr_threshold=6.0, progress=False)
    assert hits == []
    assert len(store.quarantined_chunks) > 0


# ---------------------------------------------------------------------------
# 8-bit files through search_by_chunks (PR 35)
# ---------------------------------------------------------------------------

def _survey_8bit(tmp_path, arm, codes, **kw):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    path = str(tmp_path / f"{arm}.fil")
    write_lowbit(path, codes, 8, True)
    hits, store = search_by_chunks(
        path, dmmin=100, dmmax=200, backend="jax",
        output_dir=str(tmp_path / f"out_{arm}"), make_plots=False,
        snr_threshold=6.0, progress=False, **kw)
    return path, hits, store


def test_8bit_raw_upload_equals_host_decode(tmp_path, monkeypatch):
    """An 8-bit file searched from its raw bytes (device decode, packed
    pre-scan, code-domain gate) persists the tables of the host-decoded
    run bit for bit, with the same ``.badchans``; the packed path's
    counters move for it as for a 2-bit file."""
    from pulsarutils_tpu.obs import metrics as m

    from pulsarutils_tpu.models.simulate import disperse_array

    nchan, nsamps = 64, 3 * 4096
    rng = np.random.default_rng(8)
    pulse = np.zeros((nchan, nsamps))
    pulse[:, 9000] = 40.0
    codes = 96 + 16 * rng.standard_normal((nchan, nsamps)) \
        + disperse_array(pulse, 150.0, *GEOM)
    codes[5] += 48  # a hot channel for the pre-scan to flag
    codes = np.clip(np.rint(codes), 0, 255).astype(np.float32)

    names = ("putpu_lowbit_packed_chunks_total",
             "putpu_lowbit_bytes_saved_total",
             "putpu_prescan_packed_bytes_total")
    before = [m.counter(n).value for n in names]
    path_p, hits_p, _ = _survey_8bit(tmp_path, "raw", codes)
    moved = [m.counter(n).value - b for n, b in zip(names, before)]
    assert moved[0] > 0             # chunks uploaded as raw bytes,
    assert moved[1] > 3 * nchan * nsamps  # each a quarter of its floats
    assert moved[2] == nchan * nsamps     # the pre-scan read every byte

    monkeypatch.setattr(FilterbankReader, "packed_bits",
                        property(lambda self: 0))
    before = [m.counter(n).value for n in names]
    path_h, hits_h, _ = _survey_8bit(tmp_path, "host", codes)
    assert [m.counter(n).value - b for n, b in zip(names, before)] \
        == [0, 0, 0]

    assert len(hits_p) == len(hits_h) > 0
    for h_p, h_h in zip(sorted(hits_p), sorted(hits_h)):
        assert h_p[:2] == h_h[:2]
        assert_tables_equal(h_p[3], h_h[3], msg=f"chunk {h_p[0]}")
    with open(path_p + ".badchans", "rb") as f, \
            open(path_h + ".badchans", "rb") as g:
        assert f.read() == g.read()


def test_8bit_gate_quarantines_a_railed_chunk(tmp_path):
    """The code-domain gate reads the 8-bit rails off the raw bytes: a
    file pinned at 255 is quarantined, a healthy one is not."""
    from pulsarutils_tpu.faults.policy import (
        IntegrityPolicy,
        gate_chunk_packed,
    )

    nchan = 32
    rng = np.random.default_rng(9)
    healthy = np.clip(np.rint(96 + 16 * rng.standard_normal(
        (2048, nchan))), 0, 255).astype(np.uint8)
    _, info = gate_chunk_packed(healthy, 8, nchan, IntegrityPolicy())
    assert info["verdict"] == "clean" and info["stats"]["nbits"] == 8
    # the gate's sample is a view of the bytes, equal to their decode
    from pulsarutils_tpu.io.lowbit import sample_codes, unpack_numpy

    codes = sample_codes(healthy, 8, nchan, max_rows=512)
    assert codes.dtype == np.uint8 and np.shares_memory(codes, healthy)
    assert np.array_equal(codes, unpack_numpy(healthy[::4], 8).reshape(
        -1, nchan).T)

    railed = np.full((nchan, 2 * 4096), 255, dtype=np.float32)
    _, hits, store = _survey_8bit(tmp_path, "railed", railed)
    assert hits == []
    assert len(store.quarantined_chunks) > 0


def test_8bit_canary_quantizes_into_the_bytes():
    """The packed canary needs no 8-bit branch: a byte a channel, the
    bump rounded and clipped onto 0..255."""
    from pulsarutils_tpu.obs.canary import CanaryController

    nchan, nsamps = 32, 4096
    rng = np.random.default_rng(10)
    frames = np.clip(np.rint(96 + 16 * rng.standard_normal(
        (nsamps, nchan))), 0, 255).astype(np.uint8)
    c = CanaryController(rate=1.0, snr=20.0, seed=1)
    c.bind(nchan=nchan, start_freq=GEOM[0], bandwidth=GEOM[1],
           tsamp=GEOM[2], dmmin=100, dmmax=200)
    out = c.maybe_inject_packed(frames, 0, nbits=8, nchan=nchan,
                                band_descending=True)
    diff = out.astype(int) - frames
    assert np.any(diff > 0) and np.all(diff >= 0)
    # one lit sample per channel and sample of the pulse's width
    assert (diff > 0).sum(axis=0).max() <= c._width
