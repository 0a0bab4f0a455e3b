"""Property tests for the packed low-bit upload path (VERDICT r4 #8).

The decode triangle — device-jit unpack (``device_unpack_block``),
C++-or-numpy host unpack (``FilterbankReader.unpack_frames``), and the
pure-numpy oracle (``unpack_numpy``) — must agree BIT-EXACTLY on one
file across nbits x band order x nchan x truncated-final-frame, and a
mid-stream device-clean failure must force the packed host fallback
without losing the detection.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from pulsarutils_tpu.io.lowbit import (  # noqa: E402
    accum_dtype,
    device_unpack_block,
    unpack_numpy,
)
from pulsarutils_tpu.io.sigproc import (  # noqa: E402
    FilterbankReader,
    FilterbankWriter,
)

PER = {1: 8, 2: 4, 4: 2}


def _write_lowbit(path, nbits, nchan, nsamps, descending, seed):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, (1 << nbits), (nchan, nsamps)).astype(np.float32)
    header = {"nchans": nchan, "nbits": nbits, "nifs": 1, "tsamp": 1e-3,
              "fch1": 1400.0 if descending else 1200.0,
              "foff": -1.0 if descending else 1.0, "tstart": 60000.0}
    with FilterbankWriter(path, header) as w:
        w.write_block(data[::-1] if descending else data)
    return data


@pytest.mark.parametrize("nbits", [1, 2, 4])
@pytest.mark.parametrize("descending", [True, False])
@pytest.mark.parametrize("nchan_mult", [3, 5])
def test_decode_triangle_bit_exact(tmp_path, nbits, descending, nchan_mult):
    # nchan: an odd multiple of the per-byte packing factor (the format
    # requires nchan*nbits % 8 == 0, so "not divisible by per-byte" is
    # structurally impossible — pinned below in test_misaligned_rejected)
    nchan = PER[nbits] * nchan_mult * (8 // (PER[nbits] * nbits) or 1)
    nchan = max(nchan, 8 // nbits)
    if (nchan * nbits) % 8:
        nchan *= 8 // ((nchan * nbits) % 8)
    nsamps = 37  # not a multiple of anything relevant
    path = str(tmp_path / f"tri_{nbits}_{descending}.fil")
    data = _write_lowbit(path, nbits, nchan, nsamps, descending,
                         seed=nbits * 10 + nchan_mult)

    r = FilterbankReader(path)
    raw = r.read_block_packed(0, nsamps)

    # 1. device-jit unpack (ascending-band convention)
    dev = np.asarray(device_unpack_block(
        jnp.asarray(raw), nbits, nchan, band_descending=descending,
        xp=jnp))
    # 2. host unpack (native C++ when built, else numpy)
    host = np.asarray(r.read_block(0, nsamps, band_ascending=True))
    # 3. pure-numpy oracle, decoded by hand from the same raw bytes
    per_frame = nchan * nbits // 8
    oracle = unpack_numpy(raw.reshape(nsamps, per_frame), nbits)
    oracle = oracle.reshape(nsamps, -1)[:, :nchan].T
    if descending:
        oracle = oracle[::-1]

    np.testing.assert_array_equal(dev, host.astype(np.float32))
    np.testing.assert_array_equal(dev, oracle)
    np.testing.assert_array_equal(dev, data)  # and the ground truth


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
@pytest.mark.parametrize("integer", [False, True],
                         ids=["float32", "accum_int"])
@pytest.mark.parametrize("part_filled", [False, True],
                         ids=["whole_bytes", "part_filled_last_byte"])
@pytest.mark.parametrize("descending", [False, True],
                         ids=["ascending", "descending"])
@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_device_unpack_equals_numpy_unpack_then_host_flip(
        nbits, descending, part_filled, integer, xp):
    """``device_unpack_block`` flips a descending band on the packed
    bytes (ISSUE 42); what it returns is still ``unpack_numpy`` followed
    by the host's flip, bit for bit and in the dtype asked for, in both
    array namespaces.

    ``read_block_packed`` cannot produce a frame whose last byte is
    part-filled (the reader and the writer refuse an ``nchan * nbits``
    that is no whole number of bytes:
    ``test_misaligned_nchan_rejected``), so that case hands the function
    such frames directly: every bit random, the padding codes included,
    so that a slice taken from the wrong end shows."""
    nchan = 5 * PER[nbits] - (1 if part_filled else 0)
    nsamps = 37
    raw = np.random.default_rng(100 * nbits + nchan).integers(
        0, 256, (nsamps, 5), dtype=np.uint8)
    name = accum_dtype(nbits, nchan) if integer else "float32"

    got = device_unpack_block(xp.asarray(raw), nbits, nchan,
                              band_descending=descending, xp=xp,
                              dtype=getattr(xp, name))

    want = unpack_numpy(raw, nbits).reshape(nsamps, -1)[:, :nchan].T
    if descending:
        want = want[::-1]
    assert got.shape == (nchan, nsamps) and got.dtype == np.dtype(name)
    np.testing.assert_array_equal(np.asarray(got), want.astype(name))


def test_misaligned_nchan_rejected(tmp_path):
    # nchan * nbits not a byte multiple cannot be written (SIGPROC
    # frames are byte-aligned); the guard is the writer's
    header = {"nchans": 10, "nbits": 2, "nifs": 1, "tsamp": 1e-3,
              "fch1": 1400.0, "foff": -1.0}
    with pytest.raises(ValueError):
        FilterbankWriter(str(tmp_path / "bad.fil"), header)


def test_truncated_final_frame(tmp_path):
    nbits, nchan, nsamps = 2, 16, 50
    path = str(tmp_path / "trunc.fil")
    data = _write_lowbit(path, nbits, nchan, nsamps, True, seed=3)
    # chop the file mid-frame: reader must clamp to whole frames
    size = None
    with open(path, "rb") as f:
        buf = f.read()
    per_frame = nchan * nbits // 8
    with open(path, "wb") as f:
        f.write(buf[:-(per_frame + 3)])
    r = FilterbankReader(path)
    assert r.nsamples == nsamps - 2  # one whole + one partial frame lost
    size = r.nsamples
    raw = r.read_block_packed(0, nsamps)  # over-ask: clamps
    assert raw.shape[0] == size
    dev = np.asarray(device_unpack_block(jnp.asarray(raw), nbits, nchan,
                                         band_descending=True, xp=jnp))
    host = np.asarray(r.read_block(0, nsamps, band_ascending=True))
    np.testing.assert_array_equal(dev, host.astype(np.float32))
    np.testing.assert_array_equal(dev, data[:, :size])


def test_device_clean_failure_forces_packed_host_fallback(
        tmp_path, monkeypatch, caplog):
    # a failing device unpack/clean mid-stream must fall back to the
    # HOST decode of the PACKED chunk (C++/numpy) and keep searching
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.pipeline import search_pipeline
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    rng = np.random.default_rng(11)
    nchan, nsamples = 64, 16384
    array = rng.normal(1.6, 0.5, (nchan, nsamples)).astype(np.float32)
    array[:, 9000] += 2.5
    array = disperse_array(array, 150, 1200., 200., 0.0005)
    header = {"nchans": nchan, "nbits": 2, "nifs": 1, "tsamp": 0.0005,
              "fch1": 1400.0, "foff": -200.0 / nchan, "tstart": 60000.0}
    path = str(tmp_path / "fail.fil")
    with FilterbankWriter(path, header) as w:
        w.write_block(array[::-1])

    import jax

    from pulsarutils_tpu.io import lowbit

    real_unpack = lowbit.device_unpack_block

    def boom(raw, *a, **k):
        # fails when the compiled program RUNS, like a device fault: a
        # failure while the program is traced/lowered/compiled is
        # deterministic and propagates instead (ISSUE 22,
        # tests/test_faults.py pins that side)
        out = real_unpack(raw, *a, **k)

        def fail(_):
            raise RuntimeError("injected device unpack failure")

        return jax.pure_callback(
            fail, jax.ShapeDtypeStruct(out.shape, out.dtype), out)

    monkeypatch.setattr(lowbit, "device_unpack_block", boom)
    import logging

    with caplog.at_level(logging.WARNING,
                         logger=search_pipeline.logger.name):
        hits, _ = search_by_chunks(
            path, dmmin=100, dmmax=200, backend="jax",
            output_dir=str(tmp_path / "out"), make_plots=False,
            snr_threshold=6.0)
    assert any("device clean failed" in r.message for r in caplog.records)
    assert len(hits) >= 1
    best = max(hits, key=lambda h: h[2].snr)
    assert np.isclose(best[2].dm, 150, atol=3)
