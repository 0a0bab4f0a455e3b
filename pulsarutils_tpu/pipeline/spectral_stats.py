"""Streaming bandpass statistics and bad-channel detection.

Capability-equivalents of the reference's L2 stats layer
(``pulsarutils/stats.py:35-90``):

* :func:`get_spectral_stats` — one-pass mean & std bandpass spectra via
  running ``sum(x)`` / ``sum(x^2)`` moment accumulation over chunks
  (reference ``stats.py:35-60``).  One algorithm, two representations,
  chosen from the file's header alone: a packed 1/2/4-bit single-IF file
  stays packed — its frames go to the device in large blocks and one
  jitted program (``jit_prescan_moments``) unpacks them and sums the
  codes and their squares per channel as exact int32; every other source
  is unpacked to float64 on the host, block by block
  (:func:`moment_accumulate`).  Both hand the same integers to
  :func:`moments_to_spectra`, so the spectra, and the mask made from
  them, do not depend on which one ran.
* :func:`get_bad_chans` — flag channels above ``medfilt(spec, 11) +
  4 * ref_mad(spec)`` on both the mean and std spectra, with a
  ``.badchans`` text-cache making the computation restartable
  (reference ``stats.py:63-90``; the deprecated ``np.bool`` alias is
  simply not an issue here).

Input flexibility: all entry points accept a path to a SIGPROC file, an
open :class:`~pulsarutils_tpu.io.sigproc.FilterbankReader`, or an in-memory
``(nchans, nsamples)`` array.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from ..io.sigproc import FilterbankReader
from ..obs import metrics as obs_metrics
from ..ops.robust import median_filter_1d, ref_mad

#: packed bytes one block of the packed pre-scan uploads and reduces.
#: On one v5e a 512 MiB file takes 47 / 53 / 62 / 74 / 102 ms in blocks of
#: 32 / 64 / 128 / 256 / 512 MiB (PERF.md section 6, PR 31): smaller
#: blocks overlap upload and reduction better and hold less of the device
_PACKED_BLOCK_BYTES = 64 * 2 ** 20
#: blocks the packed pre-scan keeps on their way to the device at once: an
#: upload allocates its device buffer when it is asked for, so a loop left
#: to run ahead of the link holds as much of a multi-GB file on the device
#: as the link is behind (2.2 GB of a 6 GiB file; 0.27 GB at 4 in flight).
#: 512 MiB: 106 / 77 / 63 / 54 ms at 1 / 2 / 4 / 8 in flight, 55 unbounded
_PACKED_BLOCKS_IN_FLIGHT = 4


def _as_reader(source):
    if isinstance(source, FilterbankReader):
        return source
    if isinstance(source, (str, os.PathLike)):
        return FilterbankReader(source)
    return None


def moment_accumulate(carry, block):
    """Fold one ``(nchans, n)`` block into running ``(sum, sumsq, count)``.

    Pure function of its carry; the host's float64 loop folds every
    block through it.
    """
    s, sq, n = carry
    block_f = block.astype(s.dtype) if hasattr(block, "astype") else block
    return (s + block_f.sum(axis=1),
            sq + (block_f ** 2).sum(axis=1),
            n + block.shape[1])


def moments_to_spectra(s, sq, n, xp=np):
    """Running moments -> (mean spectrum, std spectrum).

    ``std = sqrt(E[x^2] - E[x]^2)`` (reference ``stats.py:55-57``).
    """
    mean = s / n
    var = xp.maximum(sq / n - mean ** 2, 0.0)
    return mean, xp.sqrt(var)


def _float_moments(reader, chunksize):
    """Whole-file ``(sum, sumsq, n)`` per channel through the host's
    float64 blocks."""
    s = np.zeros(reader.nchans)
    sq = np.zeros(reader.nchans)
    n = 0
    for _, block in reader.iter_blocks(chunksize):
        s, sq, n = moment_accumulate((s, sq, n), block)
    return s, sq, n


def _packed_block_frames(nbits, frame_bytes, nsamples):
    """Frames in one block of the packed pre-scan, from the header alone:
    at most ``_PACKED_BLOCK_BYTES`` of packed bytes, few enough that a
    channel's sum of squared codes stays below 2^31, and no more than the
    file's length rounded up to a power of two (short files of about one
    length then share a program)."""
    top = (1 << nbits) - 1
    return max(1, min(_PACKED_BLOCK_BYTES // frame_bytes,
                      (2 ** 31 - 1) // top ** 2,
                      1 << max(nsamples - 1, 0).bit_length()))


@functools.lru_cache(maxsize=16)
def _prescan_program(nbits, frame_bytes, block_frames):
    """The jitted reduction of one packed block, kept across calls like
    ``search_pipeline._device_clean_program``: ``(block_frames,
    frame_bytes)`` uint8 -> ``(2, nchans)`` int32, the per-channel sum of
    the codes and of their squares in file channel order.  The function's
    name is the program's in a device trace (``jit_prescan_moments``;
    obs/names.py KERNEL_NAMES).

    The shift-and-mask is ``io/lowbit.py:device_unpack_block``'s, LSB
    first, taken one bit position at a time: position ``k`` of every
    byte is one ``(block_frames, frame_bytes)`` plane summed over the
    frames, and channel ``byte * per + k`` is interleaved from the
    ``per`` results.  The block is read once, in its packed layout, with
    no unpacked copy on the device (``device_unpack_block``'s ``(frames,
    bytes, per)`` array costs the v5e compiler a transposed copy and
    eight times the block in temporaries here)."""
    import jax
    import jax.numpy as jnp

    per = 8 // nbits
    mask = np.uint8((1 << nbits) - 1)

    def prescan_moments(frames):
        sums, squares = [], []
        for k in range(per):
            codes = ((frames >> np.uint8(k * nbits)) & mask).astype(jnp.int32)
            sums.append(codes.sum(axis=0))
            squares.append((codes * codes).sum(axis=0))
        return jnp.stack([jnp.stack(sums, axis=1).reshape(-1),
                          jnp.stack(squares, axis=1).reshape(-1)])

    return jax.jit(prescan_moments)


def _packed_moments(reader):
    """Whole-file ``(sum, sumsq, n)`` per channel of a packed low-bit
    single-IF file, never expanded on the host: equal blocks of packed
    frames are uploaded and reduced by :func:`_prescan_program` while the
    host reads the next one, the per-block int32 results are fetched
    once at the end and totalled in int64.  The last block is padded
    with zero bytes (code 0 adds nothing to either sum; ``n`` counts
    real frames), so a file runs one program.  The totals are the
    integers the float64 loop holds, so the spectra are bit-identical.
    """
    import jax

    nbits = reader._nbits
    nsamples = reader.nsamples
    frame_bytes = reader.nchans * nbits // 8
    block_frames = _packed_block_frames(nbits, frame_bytes, nsamples)
    program = _prescan_program(nbits, frame_bytes, block_frames)
    reduced = obs_metrics.counter("putpu_prescan_packed_bytes_total")
    parts = []
    n = 0
    for istart in range(0, nsamples, block_frames):
        if len(parts) >= _PACKED_BLOCKS_IN_FLIGHT:
            parts[-_PACKED_BLOCKS_IN_FLIGHT].block_until_ready()
        frames = reader.read_block_packed(istart, block_frames)
        n += frames.shape[0]
        reduced.inc(frames.nbytes)
        if frames.shape[0] < block_frames:
            padded = np.zeros((block_frames, frame_bytes), dtype=np.uint8)
            padded[:frames.shape[0]] = frames
            frames = padded
        parts.append(program(frames))
    totals = np.zeros((2, reader.nchans), dtype=np.int64)
    for part in jax.device_get(parts):
        totals += part
    return totals[0].astype(float), totals[1].astype(float), n


def get_spectral_stats(source, chunksize=10000):
    """One-pass mean & std bandpass spectra of a filterbank.

    Reference ``stats.py:35-60`` (diagnostic plotting lives in
    :mod:`..pipeline.diagnostics`, not here).  A packed 1/2/4-bit or
    unsigned 8-bit single-IF file (``FilterbankReader.packed_bits``)
    is reduced on the device in blocks sized from its header;
    ``chunksize`` is the block of the host's float64 loop, which every
    other source takes.
    """
    reader = _as_reader(source)
    if reader is None:
        data = np.asarray(source, dtype=float)
        return data.mean(axis=1), data.std(axis=1)

    if reader.packed_bits:
        return moments_to_spectra(*_packed_moments(reader))
    return moments_to_spectra(*_float_moments(reader, chunksize))


def flag_bad_channels(mean_spec, std_spec, medfilt_size=11, nsigma=4.0,
                      xp=np):
    """Threshold both spectra against their median-filtered baselines.

    Reference ``stats.py:70-77``.  Pure / jit-compatible.
    """
    nchan = mean_spec.shape[0]
    bad = xp.zeros(nchan, dtype=bool)
    for spec in (mean_spec, std_spec):
        smooth = median_filter_1d(spec, medfilt_size, xp=xp)
        sigma = ref_mad(spec, xp=xp)
        bad = bad | (spec > smooth + nsigma * sigma)
    return bad


def get_bad_chans(source, cache=None, surelybad=(), refresh=False,
                  spectra=None):
    """Bad-channel mask for a filterbank, with a restartable text cache.

    Reference ``stats.py:63-90`` (cache file ``<fname>.badchans``) plus the
    ``surelybad`` user override that the reference applied in its chunk
    driver (``clean.py:280-282``).  Pass ``refresh=True`` to ignore a stale
    cache, or ``spectra=(mean, std)`` to reuse already-computed bandpass
    spectra instead of streaming the file again.
    """
    path = source if isinstance(source, (str, os.PathLike)) else None
    if cache is None and path is not None:
        cache = f"{path}.badchans"

    if spectra is None and cache is not None and os.path.exists(cache) \
            and not refresh:
        bad = np.loadtxt(cache).astype(bool)
    else:
        mean_spec, std_spec = spectra if spectra is not None \
            else get_spectral_stats(source)
        bad = np.asarray(flag_bad_channels(mean_spec, std_spec))
        if cache is not None:
            np.savetxt(cache, [bad.astype(int)], fmt="%d")

    bad = np.array(bad, dtype=bool)
    for chan in surelybad:
        bad[int(chan)] = True
    return bad
