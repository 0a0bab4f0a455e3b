"""A chunk too long for the device, searched in time tiles (ISSUE 40).

MeerTRAP's beam to DM 5,118.4 asks for chunks of 2^19 samples: the cleaned
chunk is 8.6 GB of float32 and the native-resolution sweep's state ~29 GiB
of a chip's 15.75.  What does fit is the chunk as the file stores it (2 GiB
of bytes), so the packed chunk stays resident and everything downstream is
made from it a **time tile** at a time:

* the clean normalises by the **chunk's** smoothed light curve and
  per-channel mean (:func:`~pulsarutils_tpu.ops.clean_ops.renormalize_data`),
  so it becomes two steps: :func:`chunk_stats_program` reduces the resident
  bytes to those two vectors (``jit_chunk_stats``), and
  :func:`tile_clean_program` unpacks, normalises, zero-DM-filters and
  downsamples any stretch of the chunk from them (``jit_tile_clean``),
  block by block, the same element-wise operations in the same order as
  the untiled clean;
* :class:`TiledTierArray` is one tier's array that never exists whole: the
  search asks it for tile ``i`` (its own samples + a halo that holds the
  longest dispersion track, circular over the chunk), the hit's products
  for the band average and for the window around the peak.  The deepest
  tier that is tiled **lays** the tiers below it, which fit whole: its
  tile cleans run the downsample chain on and write each deeper tier's
  piece into that tier's whole array, so the chunk is cleaned once per
  tiled tier and no more.

The tile plan itself (how many tiles of how many samples, from the
device's memory) is :func:`~pulsarutils_tpu.parallel.stream.plan_time_tiles`.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.clean_ops import (_masked_channel_mean, gaussian_filter_1d,
                             zero_dm_filter)
from ..ops.rebin import downsample_chain

#: native samples a block of the two programs holds in float32 at most
#: (4,096 channels x 2^15 x 4 B = 0.5 GiB a temporary)
MAX_BLOCK = 1 << 15


#: samples of a tier that :class:`TiledTierArray` still hands out whole
#: (256 MiB of float32)
WHOLE_ARRAY_MAX = 1 << 26


def _pow2_divisor(n, cap):
    """Largest power of two that divides ``n``, at most ``cap``."""
    return min(n & -n, cap)


@functools.lru_cache(maxsize=8)
def chunk_stats_program(unpack, nsamples, baseline_window=101):
    """``jit_chunk_stats``: the packed chunk ``(nsamples, bytes)`` and the
    bad-channel mask -> ``(factor, spec)``, what
    :func:`~pulsarutils_tpu.ops.clean_ops.renormalize_data` derives from
    the whole chunk: the light curve's flattening factor per sample and
    the per-channel mean of the flattened chunk.  Two passes over the
    resident bytes, a block at a time; no float copy of the chunk exists.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    unpack_block, nbits, nchan, descending = unpack
    block = _pow2_divisor(nsamples, MAX_BLOCK)
    nblocks = nsamples // block

    def floats(raw, b):
        frames = lax.dynamic_slice_in_dim(raw, b * block, block, axis=0)
        return unpack_block(frames, nbits, nchan, band_descending=descending,
                            xp=jnp).astype(float)

    def chunk_stats(raw, mask):
        good = ~mask

        def light(b, lc):
            part = _masked_channel_mean(floats(raw, b), good, jnp)
            return lax.dynamic_update_slice_in_dim(lc, part, b * block, 0)

        lc = lax.fori_loop(0, nblocks, light,
                           jnp.zeros(nsamples, jnp.result_type(float)))
        window = min(int(baseline_window), nsamples // 100 * 2 + 1)
        lc_smooth = gaussian_filter_1d(lc, window, xp=jnp)
        lc_smooth = jnp.where(lc_smooth == 0, 1.0, lc_smooth)
        factor = jnp.median(lc_smooth) / lc_smooth

        def spectrum(b, acc):
            fac = lax.dynamic_slice_in_dim(factor, b * block, block)
            return acc + (floats(raw, b) * fac[None, :]).sum(axis=1)

        spec = lax.fori_loop(0, nblocks, spectrum,
                             jnp.zeros(nchan, factor.dtype)) / nsamples
        return factor, spec

    return jax.jit(chunk_stats)


@functools.lru_cache(maxsize=2)
def wrap_rows_program():
    """``jit_wrap_rows``: the packed chunk with its first
    :data:`MAX_BLOCK` frames once more at its end, so that any block of
    :func:`tile_clean_program` is ONE contiguous slice, the chunk's end
    included (a row gather over the wrap ran as a loop on the v5e: 0.79 s
    a chunk, more than the sweeps' head; my chip run, PR 40)."""
    import jax
    import jax.numpy as jnp

    def wrap_rows(raw):
        return jnp.concatenate([raw, raw[:min(MAX_BLOCK, raw.shape[0])]])

    return jax.jit(wrap_rows)


@functools.lru_cache(maxsize=32)
def tile_clean_program(unpack, zero_dm, nsamples, length, chain,
                       band_mean=False, lay=()):
    """``jit_tile_clean``: ``length`` samples of a tier's array, cleaned,
    from native sample ``start`` of the resident packed chunk on (circular
    over its ``nsamples``).  ``chain`` is the downsample chain up to the
    tier's factor (``()``: native resolution): each array the one before
    summed in pairs, as the untiled ``jit_tier_downsample`` sums.  One
    program serves every tile of a tier, whatever its start.  With
    ``band_mean`` the program returns the mean over channels instead
    (``jit_tile_band_mean``, the hit's dispersed profile).

    ``lay`` continues the chain to deeper tiers' factors: the program
    then takes those tiers' whole arrays after its other arguments
    (donated on an accelerator) and returns them after the tile, every
    block's pieces written at their place.  A halo block writes what the
    next tile's first block writes, the same values from the same
    slice, so the tiles of one chunk fill each array exactly once over.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    unpack_block, nbits, nchan, descending = unpack
    factor_k = chain[-1] if chain else 1
    out_block = _pow2_divisor(length, max(MAX_BLOCK // factor_k, 1))
    block = out_block * factor_k
    nblocks = length // out_block
    if lay and block % lay[-1]:
        raise ValueError(f"a block of {block} samples holds no whole "
                         f"sample of a tier at {lay[-1]}x")

    def clean_block(raw, first, factor, spec, denom, mask):
        # ``raw`` and ``factor`` carry their first MAX_BLOCK entries once
        # more at the end (wrap_rows_program): a block that starts before
        # the chunk's end is one slice
        frames = lax.dynamic_slice_in_dim(raw, first, block, axis=0)
        x = unpack_block(frames, nbits, nchan, band_descending=descending,
                         xp=jnp).astype(float)
        renorm = x * lax.dynamic_slice_in_dim(factor, first, block)[None, :]
        renorm = (renorm - spec[:, None]) / denom[:, None]
        renorm = jnp.where(mask[:, None], 0.0, renorm)
        if zero_dm:
            renorm = zero_dm_filter(renorm, badchans_mask=mask, xp=jnp)
        # the tier's own array, then the deeper tiers' pieces
        return ([renorm] + downsample_chain(renorm, chain + lay,
                                            xp=jnp))[len(chain):]

    def run(raw, start, factor, spec, mask, *deep):
        denom = jnp.where(spec == 0, 1.0, spec)
        factor = jnp.concatenate([factor, factor[:block]])

        def one(b, carry):
            out, deep = carry
            first = (start + b * block) % nsamples
            piece, *rest = clean_block(raw, first, factor, spec, denom, mask)
            deep = tuple(
                lax.dynamic_update_slice_in_dim(
                    whole, part.astype(whole.dtype), first // f, 1)
                for whole, part, f in zip(deep, rest, lay))
            if band_mean:
                piece, axis = piece.mean(0), 0
            else:
                axis = 1
            return lax.dynamic_update_slice_in_dim(
                out, piece.astype(out.dtype), b * out_block, axis), deep

        shape = (length,) if band_mean else (nchan, length)
        out, deep = lax.fori_loop(0, nblocks, one,
                                  (jnp.zeros(shape, jnp.float32), deep))
        return (out,) + deep if lay else out

    run.__name__ = "tile_band_mean" if band_mean else "tile_clean"
    donate = (tuple(range(5, 5 + len(lay)))
              if jax.default_backend() in ("tpu", "gpu") else ())
    return jax.jit(run, donate_argnums=donate)


class TiledTierArray:
    """One tier's cleaned array ``(nchan, nsamples)``, made a time tile at
    a time from the resident packed chunk.

    ``raw`` is the packed chunk as :func:`wrap_rows_program` extends it,
    ``nsamples`` its true length.  The search
    (:func:`~pulsarutils_tpu.ops.search.time_tiles_of`) reads
    ``time_tiles``, ``own``, ``halo``, ``keep`` and :meth:`tile`; the
    hit's products read ``shape``/``size``, ``mean(0)`` and a window
    ``[:, lo:hi]`` (or ``[:, cols]``, consecutive mod the axis), each
    computed from the bytes on demand.  With ``time_tiles == 1`` and no
    halo, ``tile(0)`` IS the whole array, the untiled chain's tier.

    ``lay`` names the chain factors of the deeper tiers this tier lays
    (:func:`tile_clean_program`): the first clean of each tile at its own
    place writes their pieces, and :meth:`laid` hands the whole arrays
    over once every tile has been cleaned.

    ``bands`` are the ``(n_lo, n_hi)`` band delays of a tier swept in delay
    bands (:func:`~pulsarutils_tpu.parallel.stream.plan_time_tiles`; empty:
    one sweep a tile, as ever): each tile is still cleaned once, and the
    coarse sweep runs once a band on it, leaving the seconds each band's
    sweeps and readbacks took in ``band_seconds``.
    """

    def __init__(self, raw, nsamples, stats, mask, unpack, zero_dm, chain,
                 tiles, halo, tier=0, keep=0, lay=(), bands=()):
        self.raw, self.mask = raw, mask
        self.factor, self.spec = stats
        self._key = (unpack, bool(zero_dm), int(nsamples))
        self.chain = tuple(chain)
        self.downsample = self.chain[-1] if self.chain else 1
        nsamples = int(nsamples) // self.downsample
        self.shape = (unpack[2], nsamples)
        self.size = self.shape[0] * self.shape[1]
        self.time_tiles = int(tiles)
        self.own = nsamples // self.time_tiles
        self.halo = int(halo)
        self.tier = tier
        self.keep = int(keep)
        self.lay = tuple(lay)
        self.bands = tuple(bands)
        self.band_seconds = [0.0] * len(self.bands)
        self._laid = None
        self._to_lay = set(range(self.time_tiles)) if self.lay else set()

    def _run(self, start, length, band_mean=False, lay=False):
        program = tile_clean_program(*self._key, int(length), self.chain,
                                     band_mean, self.lay if lay else ())
        native = (int(start) * self.downsample) % self._key[2]
        args = (self.raw, np.int32(native), self.factor, self.spec,
                self.mask)
        if not lay:
            return program(*args)
        if self._laid is None:
            import jax.numpy as jnp

            self._laid = tuple(
                jnp.zeros((self.shape[0], self._key[2] // f), jnp.float32)
                for f in self.lay)
        tile, *self._laid = program(*args, *self._laid)
        return tile

    def tile(self, i, shift=0):
        """Tile ``i``'s ``own + halo`` samples, from ``shift`` samples
        after its first own sample on (device array)."""
        lay = shift == 0 and i in self._to_lay
        self._to_lay.discard(i)
        return self._run(i * self.own + shift, self.own + self.halo, lay=lay)

    def laid(self):
        """The deeper tiers' whole arrays, in ``lay``'s order, given up by
        this object: whole once every tile has been cleaned at its own
        place, which a sweep of the tier does."""
        if self._to_lay:
            raise RuntimeError(f"tiles {sorted(self._to_lay)} of tier "
                               f"{self.tier} were not swept: the tiers it "
                               "lays are not whole")
        laid, self._laid = self._laid, None
        return laid

    def mean(self, axis):
        if axis != 0:
            raise ValueError("a tiled tier gives its band average only")
        return self._run(0, self.shape[1], band_mean=True)

    def __getitem__(self, key):
        rows, cols = key
        if rows != slice(None):
            raise IndexError("a tiled tier is cut along time only")
        if isinstance(cols, slice):
            lo, hi, step = cols.indices(self.shape[1])
            if step != 1:
                raise IndexError("a tiled tier is cut in whole stretches")
        else:
            cols = np.asarray(cols)
            lo, hi = int(cols[0]), int(cols[0]) + len(cols)
            if not np.array_equal(cols, np.arange(lo, hi) % self.shape[1]):
                raise IndexError("a tiled tier is cut in whole stretches")
        length = self.own + self.halo
        if hi - lo > length:
            raise IndexError(f"a window of {hi - lo} samples is longer "
                             f"than a tile of {length}")
        return self._run(lo, length)[:, :hi - lo]

    def __array__(self, dtype=None, copy=None):
        """The whole array, for a tier small enough to be asked for whole
        (a hit's record under the store's waterfall budget).  A tier of
        survey size is tiled because it does NOT fit: asking for it whole
        is refused rather than left to end in an out-of-memory or a
        host-side crawl."""
        if self.size > WHOLE_ARRAY_MAX:
            raise TypeError("a tier searched in time tiles has no whole "
                            f"array ({self.size} samples would not fit the "
                            "device it is tiled for)")
        whole = np.asarray(self._run(0, self.shape[1]))
        return whole if dtype is None else whole.astype(dtype)
