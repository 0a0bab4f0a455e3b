"""1/2/4-bit filterbank support: native C unpacker vs numpy oracle,
file round trips, and DM recovery through a quantised file."""
import numpy as np
import pytest

from pulsarutils_tpu.io import lowbit
from pulsarutils_tpu.io.sigproc import FilterbankReader, write_filterbank


@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_pack_unpack_numpy_round_trip(nbits, rng):
    maxval = (1 << nbits) - 1
    values = rng.integers(0, maxval + 1, size=512).astype(np.float32)
    packed = lowbit.pack_numpy(values, nbits)
    assert packed.dtype == np.uint8
    assert packed.size == values.size * nbits // 8
    out = lowbit.unpack_numpy(packed, nbits)
    assert np.array_equal(out, values)


@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_native_matches_numpy(nbits, rng):
    if not lowbit.native_available():
        pytest.skip("native unpacker did not build")
    values = rng.integers(0, (1 << nbits), size=4096).astype(np.float32)
    p_np = lowbit.pack_numpy(values, nbits)
    p_c = lowbit.pack(values, nbits)
    assert np.array_equal(p_np, p_c)
    assert np.array_equal(lowbit.unpack_numpy(p_c, nbits),
                          lowbit.unpack(p_c, nbits))


def test_pack_clips_out_of_range():
    vals = np.array([-3.0, 0.0, 1.4, 1.6, 99.0, 3.0, 2.0, 1.0],
                    dtype=np.float32)
    out = lowbit.unpack_numpy(lowbit.pack_numpy(vals, 2), 2)
    assert np.array_equal(out, [0, 0, 1, 2, 3, 3, 2, 1])


@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_filterbank_lowbit_round_trip(tmp_path, rng, nbits):
    nchan, nsamp = 16, 64
    maxval = (1 << nbits) - 1
    data = rng.integers(0, maxval + 1, size=(nchan, nsamp)).astype(float)
    path = str(tmp_path / f"lb{nbits}.fil")
    write_filterbank(path, data, tsamp=1e-3, fch1=1400.0, foff=-1.0,
                     nbits=nbits)
    r = FilterbankReader(path)
    assert r.header["nbits"] == nbits
    assert r.nsamples == nsamp
    block = r.read_block(0, nsamp)
    assert np.array_equal(block, data)
    # partial read from an offset
    assert np.array_equal(r.read_block(10, 7), data[:, 10:17])


def test_search_through_2bit_file(tmp_path):
    # quantise a simulated dispersed pulse to 2 bits and recover the DM
    from pulsarutils_tpu.models.simulate import simulate_test_data
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.ops.search import dedispersion_search

    array, header = simulate_test_data(150, nchan=64, nsamples=4096,
                                       signal=3.0, noise=0.4, rng=11)
    # scale to use the 0..3 range
    q = np.clip(np.rint(array / array.max() * 3), 0, 3)
    path = str(tmp_path / "q2.fil")
    write_simulated_filterbank(path, q, header, nbits=2)
    r = FilterbankReader(path)
    block = r.read_block(0, r.nsamples, band_ascending=True)
    table = dedispersion_search(block, 100, 200.0, header["fbottom"],
                                header["bandwidth"], header["tsamp"],
                                backend="numpy")
    assert abs(table.best_row()["DM"] - 150) <= 2.0


def test_native_pack_half_values_match_numpy():
    # exact halves round half-to-even in BOTH paths (np.rint semantics)
    if not lowbit.native_available():
        pytest.skip("native unpacker did not build")
    vals = np.array([0.5, 1.5, 2.5, 3.5, -0.5, 0.0, 1.0, 2.0],
                    dtype=np.float32)
    assert np.array_equal(lowbit.pack(vals, 2), lowbit.pack_numpy(vals, 2))
    assert np.array_equal(lowbit.pack(vals, 4), lowbit.pack_numpy(vals, 4))
    assert np.array_equal(lowbit.pack(vals, 1), lowbit.pack_numpy(vals, 1))


# ---------------------------------------------------------------------------
# 8-bit files: the raw bytes are the packed frames (PR 35)
# ---------------------------------------------------------------------------

def _write_8bit(path, rng, nchan=16, nsamp=96, descending=True, **extra):
    data = rng.integers(0, 256, size=(nchan, nsamp)).astype(float)
    write_filterbank(str(path), data, tsamp=1e-3,
                     fch1=1400.0 if descending else 1200.0,
                     foff=-1.0 if descending else 1.0, nbits=8, **extra)
    return data


@pytest.mark.parametrize("descending", [True, False])
def test_8bit_read_block_packed_equals_read_block(tmp_path, rng, descending):
    data = _write_8bit(tmp_path / "u8.fil", rng, descending=descending)
    r = FilterbankReader(str(tmp_path / "u8.fil"))
    assert r.packed_bits == 8
    frames = r.read_block_packed(10, 50)
    assert frames.dtype == np.uint8 and frames.shape == (50, 16)
    assert np.array_equal(frames.T, r.read_block(10, 50))
    assert np.array_equal(frames.T, data[:, 10:60])


@pytest.mark.parametrize("descending", [True, False])
def test_8bit_device_decode_equals_host_exactly(tmp_path, rng, descending):
    _write_8bit(tmp_path / "u8.fil", rng, descending=descending)
    r = FilterbankReader(str(tmp_path / "u8.fil"))
    host = r.read_block(0, 96, band_ascending=True)
    packed = lowbit.PackedFrames.read(r, 0, 96)
    assert packed.nbytes * 4 == packed.float_nbytes
    dev = np.asarray(lowbit.device_unpack_block(
        packed.frames, 8, r.nchans, band_descending=r.band_descending))
    assert dev.dtype == np.float32 and host.dtype == np.float32
    assert np.array_equal(dev, host)
    assert np.array_equal(packed.to_host(), host)
    assert np.array_equal(np.asarray(packed.to_device()), host)


@pytest.mark.parametrize("nbits,nifs,extra", [
    (8, 1, {"signed": 1}), (8, 2, {}), (16, 1, {}), (32, 1, {})],
    ids=["signed8", "8bit_2if", "16bit", "float32"])
def test_other_files_keep_the_host_path(tmp_path, rng, nbits, nifs, extra):
    from pulsarutils_tpu.io.sigproc import FilterbankWriter

    header = {"nchans": 8, "nbits": nbits, "nifs": nifs, "tsamp": 1e-3,
              "fch1": 1400.0, "foff": -1.0, "tstart": 0.0, **extra}
    data = rng.integers(0, 100, size=(nifs, 8, 32))
    with FilterbankWriter(str(tmp_path / "o.fil"), header) as w:
        w.write_block(data if nifs > 1 else data[0])
    r = FilterbankReader(str(tmp_path / "o.fil"))
    assert r.packed_bits == 0
    with pytest.raises(ValueError, match="read_block_packed"):
        r.read_block_packed(0, 8)
    assert np.array_equal(r.read_block(0, 32), data.sum(axis=0))


@pytest.mark.parametrize("signed,nifs,if_mode", [
    (False, 1, "sum"), (True, 1, "sum"), (False, 2, 1), (False, 2, "sum")],
    ids=["unsigned", "signed", "plane1_of_2", "sum_of_2"])
def test_8bit_host_fallback_values(tmp_path, rng, signed, nifs, if_mode):
    """``unpack_frames`` transposes the bytes and widens after: the values
    are those of the float64 block it used to make first."""
    from pulsarutils_tpu.io.sigproc import FilterbankWriter

    nchan, nsamp = 8, 40
    header = {"nchans": nchan, "nbits": 8, "nifs": nifs, "tsamp": 1e-3,
              "fch1": 1400.0, "foff": -1.0, "tstart": 0.0}
    if signed:
        header["signed"] = 1
    data = rng.integers(-128 if signed else 0, 128 if signed else 256,
                        size=(nifs, nchan, nsamp))
    with FilterbankWriter(str(tmp_path / "f.fil"), header) as w:
        w.write_block(data if nifs > 1 else data[0])
    r = FilterbankReader(str(tmp_path / "f.fil"), if_mode=if_mode)
    raw = np.asarray(r._mmap[:])
    old = raw.reshape(nsamp, nifs, nchan).astype(float)
    old = (old.sum(axis=1) if if_mode == "sum" else old[:, if_mode]).T
    for ascending in (False, True):
        block = r.unpack_frames(raw, band_ascending=ascending)
        assert np.array_equal(block, old[::-1] if ascending else old)
        if if_mode != "sum" or nifs == 1:
            assert block.dtype == np.float32
