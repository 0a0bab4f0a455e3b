"""Streaming moments + bad-channel cache (host float64 loop and the
packed pre-scan on the device)."""
import numpy as np
import pytest

from pulsarutils_tpu.io.sigproc import (
    FilterbankReader,
    FilterbankWriter,
    header_from_simulated,
    write_filterbank,
)
from pulsarutils_tpu.models.simulate import inject_rfi, simulate_test_data
from pulsarutils_tpu.obs import metrics as obs_metrics
from pulsarutils_tpu.pipeline import spectral_stats
from pulsarutils_tpu.pipeline.spectral_stats import (
    flag_bad_channels,
    get_bad_chans,
    get_spectral_stats,
)


@pytest.fixture()
def rfi_file(tmp_path):
    array, sim_header = simulate_test_data(0, nchan=64, nsamples=8192,
                                           signal=0.0, rng=0)
    array += 100.0  # realistic positive baseline
    bad = (5, 30, 31)
    array = inject_rfi(array, bad_channels=bad, bad_channel_scale=15, rng=1)
    path = tmp_path / "rfi.fil"
    write_filterbank(path, array, **header_from_simulated(sim_header))
    return str(path), array, bad


def test_streaming_stats_match_direct(rfi_file):
    path, array, _ = rfi_file
    mean_s, std_s = get_spectral_stats(path, chunksize=1000)
    assert np.allclose(mean_s, array.mean(1), rtol=1e-5)
    assert np.allclose(std_s, array.std(1), rtol=1e-4)


def test_stats_on_array_input(rfi_file):
    _, array, _ = rfi_file
    mean_s, std_s = get_spectral_stats(array)
    assert np.allclose(mean_s, array.mean(1))
    assert np.allclose(std_s, array.std(1))


def test_get_bad_chans_finds_and_caches(rfi_file, tmp_path):
    path, _, bad = rfi_file
    mask = get_bad_chans(path)
    assert set(np.flatnonzero(mask)) >= set(bad)
    # cache file written next to the data
    import os
    assert os.path.exists(path + ".badchans")
    # cache round trip gives the same mask without recomputation
    mask2 = get_bad_chans(path)
    assert np.array_equal(mask, mask2)


def test_get_bad_chans_surelybad_and_refresh(rfi_file):
    path, _, bad = rfi_file
    mask = get_bad_chans(path, surelybad=[0, 63])
    assert mask[0] and mask[63]
    mask3 = get_bad_chans(path, refresh=True)
    assert set(np.flatnonzero(mask3)) >= set(bad)


def test_flag_bad_channels_jax():
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    mean_spec = rng.normal(100, 1, 64)
    std_spec = rng.normal(10, 0.1, 64)
    mean_spec[17] += 50
    bad_np = flag_bad_channels(mean_spec, std_spec)
    bad_j = flag_bad_channels(jnp.asarray(mean_spec), jnp.asarray(std_spec),
                              xp=jnp)
    assert bad_np[17]
    assert np.array_equal(np.asarray(bad_j), bad_np)


# -- the packed pre-scan (ISSUE 31) ----------------------------------------

NCHAN, NSAMP, HOT = 64, 1000, (5, 30, 31)
COUNTER = "putpu_prescan_packed_bytes_total"


def lowbit_file(path, nbits, nifs=1, seed=0, signed=False):
    """A file of ``nbits``-wide codes with hot channels: noise in the
    lower half of the code range, the hot channels spread over all of it
    (a higher mean and a larger scatter)."""
    rng = np.random.default_rng(seed)
    top = (1 << nbits) - 1 if nbits <= 8 and not signed else 120
    data = rng.integers(0, top // 2 + 1, size=(nifs, NCHAN, NSAMP))
    data[:, HOT, :] = rng.integers(0, top + 1, size=(nifs, len(HOT), NSAMP))
    data[:, HOT, ::2] = top
    header = {"nchans": NCHAN, "nbits": nbits, "nifs": nifs,
              "tsamp": 1e-3, "fch1": 1400.0, "foff": -1.0, "tstart": 0.0,
              "source_name": "t", "machine_id": 0, "telescope_id": 0,
              "data_type": 1}
    if signed:
        header["signed"] = 1
    with FilterbankWriter(str(path), header) as w:
        w.write_block(data if nifs > 1 else data[0])
    return str(path)


def packed_bytes():
    return obs_metrics.counter(COUNTER).value


@pytest.fixture()
def tiny_blocks(monkeypatch):
    """Blocks of 96 frames of the 64-channel test files: NSAMP is 10
    blocks and 40 frames, so the last block is padded."""
    def force(nbits):
        monkeypatch.setattr(spectral_stats, "_PACKED_BLOCK_BYTES",
                            96 * NCHAN * nbits // 8)
    return force


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
def test_packed_prescan_spectra_equal_float_loop(tmp_path, tiny_blocks,
                                                 nbits):
    path = lowbit_file(tmp_path / "p.fil", nbits)
    tiny_blocks(nbits)
    reader = FilterbankReader(path)
    assert NSAMP % spectral_stats._packed_block_frames(
        nbits, NCHAN * nbits // 8, NSAMP) != 0
    mean_p, std_p = get_spectral_stats(path)
    mean_f, std_f = spectral_stats.moments_to_spectra(
        *spectral_stats._float_moments(reader, 130))
    # the same integers through the same function: equal, not close
    assert np.array_equal(mean_p, mean_f)
    assert np.array_equal(std_p, std_f)
    assert std_p[list(HOT)].min() > np.delete(std_p, HOT).max()


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
def test_packed_prescan_sidecar_bytes_equal_float_loop(tmp_path, tiny_blocks,
                                                       nbits):
    path = lowbit_file(tmp_path / "p.fil", nbits)
    tiny_blocks(nbits)
    mask = get_bad_chans(path)
    assert set(np.flatnonzero(mask)) >= set(HOT)
    float_spectra = spectral_stats.moments_to_spectra(
        *spectral_stats._float_moments(FilterbankReader(path), 10000))
    get_bad_chans(path, cache=str(tmp_path / "float.badchans"),
                  spectra=float_spectra)
    with open(path + ".badchans", "rb") as f, \
            open(tmp_path / "float.badchans", "rb") as g:
        assert f.read() == g.read()


@pytest.mark.parametrize("nbits,nifs,signed", [
    (8, 1, True), (32, 1, False), (2, 2, False), (8, 2, False),
    (16, 1, False)], ids=["signed8", "float32", "2bit_2if", "8bit_2if",
                          "16bit"])
def test_other_sources_keep_float_loop(tmp_path, nbits, nifs, signed):
    path = lowbit_file(tmp_path / "f.fil", nbits, nifs=nifs, signed=signed)
    assert not FilterbankReader(path).packed_bits
    before = packed_bytes()
    mean_s, std_s = get_spectral_stats(path, chunksize=300)
    assert packed_bytes() == before
    block = FilterbankReader(path).read_block(0, NSAMP)
    assert np.allclose(mean_s, block.mean(1))
    assert np.allclose(std_s, block.std(1))


def test_packed_prescan_counts_the_files_data_bytes(tmp_path, tiny_blocks):
    path = lowbit_file(tmp_path / "p.fil", 2)
    tiny_blocks(2)
    before = packed_bytes()
    get_spectral_stats(path)
    assert packed_bytes() - before == NSAMP * NCHAN * 2 // 8


def test_packed_prescan_second_file_builds_no_second_program(tmp_path):
    first = lowbit_file(tmp_path / "a.fil", 2, seed=1)
    second = lowbit_file(tmp_path / "b.fil", 2, seed=2)
    get_spectral_stats(first)
    built = spectral_stats._prescan_program.cache_info()
    get_spectral_stats(second)
    after = spectral_stats._prescan_program.cache_info()
    assert after.misses == built.misses
    assert after.hits == built.hits + 1


def test_packed_block_frames_keeps_int32_exact():
    # 4-bit codes reach 15: 2^31 / 225 frames bound a narrow file's block
    # where the byte budget would allow more
    frames = spectral_stats._packed_block_frames(4, 4, 2 ** 30)
    assert frames * 15 ** 2 < 2 ** 31 <= (frames + 1) * 15 ** 2
    # a wide file is bounded by bytes, a short one by its length
    assert spectral_stats._packed_block_frames(2, 256, 2 ** 30) \
        == spectral_stats._PACKED_BLOCK_BYTES // 256
    assert spectral_stats._packed_block_frames(2, 256, 1000) == 1024
    # MeerTRAP's 4,096-byte frames of 8-bit samples: 64 MiB blocks of
    # 16,384, under the 33,025 frames that keep a sum of 255^2 exact
    assert spectral_stats._packed_block_frames(8, 4096, 2 ** 18) == 16384
    assert spectral_stats._packed_block_frames(8, 64, 2 ** 30) \
        == (2 ** 31 - 1) // 255 ** 2 == 33025
