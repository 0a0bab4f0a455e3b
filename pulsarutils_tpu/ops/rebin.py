"""Rebinning ops: block-sum down-sampling along channel and time axes.

Capability-equivalents of the reference's ``quick_chan_rebin``
(``pulsarutils/dedispersion.py:15-35``) and numba-jitted ``quick_resample``
(``pulsarutils/dedispersion.py:38-57``).  Both are pure reshape+sum, which
XLA lowers to a tiny fused reduction — no loops needed on any backend.

Both truncate trailing elements that do not fill a whole block, exactly like
the reference.
"""

from __future__ import annotations

import functools

import numpy as np


def quick_chan_rebin(counts, factor, xp=np):
    """Rebin along the **channel** (first) axis by an integer factor.

    Reference: ``pulsarutils/dedispersion.py:15-35``.  Trailing channels
    that do not fill a block are truncated:

    >>> quick_chan_rebin(np.ones((5, 3)), 2)
    array([[2., 2., 2.],
           [2., 2., 2.]])
    >>> quick_chan_rebin(np.arange(8).reshape(4, 2), 2)
    array([[ 2,  4],
           [10, 12]])
    """
    nchan, nbin = counts.shape
    n = int(nchan // factor)
    return counts[: n * factor, :].reshape(n, factor, nbin).sum(axis=1)


def quick_resample(counts, factor, xp=np):
    """Rebin along the **time** (last) axis by an integer factor.

    Returns a float array like the reference's njit loop accumulation
    (``pulsarutils/dedispersion.py:38-57``).  Works on 1-D or 2-D input
    (the reference requires 2-D; 1-D is accepted here for convenience and
    treated as a single channel).

    >>> quick_resample(np.ones((2, 6)), 3)
    array([[3., 3.],
           [3., 3.]])
    >>> quick_resample(np.arange(5.0), 2)  # trailing sample truncated
    array([1., 5.])
    """
    counts = xp.asarray(counts)
    squeeze = counts.ndim == 1
    if squeeze:
        counts = counts[None, :]
    nchan, nbin = counts.shape
    n = int(nbin // factor)
    out = (
        counts[:, : n * factor]
        .reshape(nchan, n, factor)
        .astype(_float_dtype(counts, xp))
        .sum(axis=2)
    )
    return out[0] if squeeze else out


#: blocks of the output one product of :func:`window_resample_program` makes
#: (the lane width of the accelerator's matrix unit)
_RESAMPLE_BLOCK = 128


@functools.lru_cache(maxsize=8)
def window_resample_program(length, factor):
    """``jit_window_resample``: ``(counts, start)`` ->
    ``quick_resample(counts[:, start:start + length], factor)`` in float32,
    for a window that is not worth reading back whole (a hit's cut-out
    over the store's budget, :meth:`~pulsarutils_tpu.io.candidates.
    CandidateStore.trim_waterfall`).  ``start`` is traced, so one program
    serves every window of a length.

    The block sums are products with a 0/1 matrix at the highest
    precision, ``_RESAMPLE_BLOCK`` sums at a time: a float32 is three
    bfloat16 pieces exactly, each times 1.0, accumulated in float32, so
    the values are float32 sums of ``factor`` neighbours in the matrix
    unit's order.  The v5e compiler reads the slice in place for it (no
    temporary at 16,384 x 13,184 by 52); for ``lax.reduce_window`` or a
    reshape over as many lanes it makes two window-sized relayout copies
    first (1,646 MiB there; compiler here, PR 50).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = length // factor
    block = min(_RESAMPLE_BLOCK, n)

    def window_resample(counts, start):
        nchan = counts.shape[0]
        ones = (jnp.arange(block * factor)[:, None] // factor
                == jnp.arange(block)[None, :]).astype(jnp.float32)

        def sums(b, out):
            # the last block may begin inside the one before: the same
            # sums from the same samples, written twice
            first = jnp.minimum(b * block, n - block)
            piece = lax.dynamic_slice(counts, (0, start + first * factor),
                                      (nchan, block * factor))
            piece = jnp.dot(piece.astype(jnp.float32), ones,
                            precision=lax.Precision.HIGHEST)
            return lax.dynamic_update_slice(out, piece, (0, first))

        return lax.fori_loop(0, -(-n // block), sums,
                             jnp.zeros((nchan, n), jnp.float32))

    return jax.jit(window_resample)


def downsample_chain(counts, factors, xp=np):
    """``counts`` rebinned along time by each of ``factors``, ascending and
    each a multiple of the one before: every array is the previous one
    block-summed (:func:`quick_resample` semantics, a trailing fragment is
    truncated), so a chain of doublings sums in pairs.

    Under ``jax.numpy`` a step is a strided window sum
    (``lax.reduce_window``), not a reshape: the v5e compiler gives the
    reshape of a 1,024 x 2^19 chunk to ``(..., 2)`` 4 GiB of relayout
    copies, the window sum none.

    >>> [a.tolist() for a in downsample_chain(np.arange(9.0), (2, 4))]
    [[1.0, 5.0, 9.0, 13.0], [6.0, 22.0]]
    """
    out, have = [], 1
    for factor in factors:
        step = factor // have
        if xp is np:
            counts = quick_resample(counts, step)
        else:
            from jax import lax

            counts = lax.reduce_window(counts, 0.0, lax.add, (1, step),
                                       (1, step), "VALID")
        have = factor
        out.append(counts)
    return out


def stretch_resample(x, indices, xp=np):
    """Resample along the time (last) axis at precomputed sample indices.

    The **fractional-stretch generalisation** of :func:`quick_resample`
    (the reference's resampling primitive only ever rebinned by an
    integer factor): ``out[..., n] = x[..., indices[n]]`` for any
    monotone index map, so a caller can stretch the time axis by a
    *non-integer, even time-varying* rate — the acceleration-search
    resample (:mod:`~pulsarutils_tpu.periodicity.accel`) maps
    ``n -> n - kappa n^2``.  ``indices`` must be integer, precomputed
    on the host in float64 (index arithmetic in float32 drifts by
    whole samples past ``n ~ 2^24``) and already clipped to the axis.

    >>> stretch_resample(np.arange(6.0), np.array([0, 2, 4]))
    array([0., 2., 4.])
    """
    x = xp.asarray(x)
    return xp.take(x, indices, axis=-1)


def block_sum_time(x, factor, xp=np):
    """Block-sum a batch of series ``(..., T)`` along the last axis.

    Generalised form of :func:`quick_resample` used by the batched S/N
    scorer: keeps whatever leading (trial) axes exist, truncates ``T`` to a
    multiple of ``factor``.
    """
    t = x.shape[-1]
    n = t // factor
    lead = x.shape[:-1]
    return x[..., : n * factor].reshape(*lead, n, factor).sum(axis=-1)


def _float_dtype(arr, xp):
    if arr.dtype in (np.dtype("float32"),):
        return arr.dtype
    if xp is np:
        return np.float64
    # keep accumulation in f32 on accelerator backends
    return np.float32
