"""The dedispersion search: plan -> dedisperse every trial -> boxcar S/N.

Public entry point :func:`dedispersion_search` is the capability-equivalent
of the reference's fast/slow search façade
(``pulsarutils/dedispersion.py:205-251``) with its numba ``prange`` sweep
(``pulsarutils/dedispersion.py:174-202``), unified:

* one search implementation, optional dedispersed-plane capture (the
  reference had a second, older copy of the slow path in
  ``pulsarutils/clean.py:136-180`` — intentionally not reproduced);
* ``backend="numpy"`` keeps exact reference semantics (float64, same
  rounding, same scoring) and is the correctness/benchmark baseline;
* ``backend="jax"`` runs the whole sweep as one jitted program: the trial
  axis is processed in blocks via ``lax.map``, each block dedispersed by a
  batched gather (see :mod:`..ops.dedisperse`) and scored on device.  All
  shift/plan math is computed host-side in float64 and shipped as int32
  gather offsets (2 MB for 512 trials x 1024 chans) so hit detection is
  bit-identical to the NumPy path regardless of device precision.

Scoring (reference ``dedispersion.py:186-201``): for each trial, subtract
the mean, then for boxcar block-sums of width 1, 2, 4, 8 compute
``snr = max / std`` and keep the best; also record the peak and std of the
unbinned series.
"""

from __future__ import annotations

import functools
import logging
import os

import numpy as np

logger = logging.getLogger("pulsarutils_tpu")

from .dedisperse import dedisperse_batch_numpy, dedisperse_block_chunked_jax
from .plan import (
    dedispersion_plan,
    dedispersion_shifts_batch,
    normalize_shifts,
)
from .rebin import block_sum_time
from ..tuning.geometry import PLAN_CACHE_SIZE, counted_plan_cache
from ..utils.logging_utils import budget_bucket, budget_count
from ..utils.table import ResultTable

#: boxcar widths tried by the scorer (reference ``dedispersion.py:190-191``):
#: the default ladder.  ``--boxcar-max`` continues it in doubling steps
#: (:func:`boxcar_ladder`); every scorer takes the ladder as a static
#: tuple, ``None`` meaning this one.
SEARCH_WINDOWS = (1, 2, 4, 8)

#: a level above the default four is scored only where it has at least
#: this many blocks: its ``std`` would otherwise be an estimate from too
#: few values.  The default four are scored whatever the length is.
MIN_WIDE_BLOCKS = 64

#: sliding windows of the hybrid's certificate scorer.  SOUNDNESS
#: COUPLING: :func:`cert_profile_scores` unrolls exactly these widths
#: structurally (plus :func:`cert_wide_windows` of a longer ladder), the
#: one-pass kernel (``ops/score_pallas.py``) captures the same set, and
#: ``certify._cert_retention_from_offsets`` computes the retention bound
#: over it — change all of them together or the noise certificate's bound
#: no longer describes the scorer (``tests/test_certify.py`` pins the
#: coupling).
CERT_WINDOWS = (2, 3, 4)


def boxcar_ladder(boxcar_max=None, downsample=1):
    """The detection ladder of a plan (or tier) that works at
    ``downsample`` x the file's sample time.

    ``boxcar_max`` (Heimdall's name and meaning: the widest boxcar, a
    power of two in samples of the file) absent gives
    :data:`SEARCH_WINDOWS`.  Given, the ladder is ``1, 2, 4, ...,
    max(8, boxcar_max / downsample)`` samples of the working sample
    time: every tier reaches the same width in samples of the file and
    none loses a width of the default ladder.

    >>> boxcar_ladder()
    (1, 2, 4, 8)
    >>> boxcar_ladder(64), boxcar_ladder(64, 4), boxcar_ladder(64, 32)
    ((1, 2, 4, 8, 16, 32, 64), (1, 2, 4, 8, 16), (1, 2, 4, 8))
    """
    if boxcar_max is None:
        return SEARCH_WINDOWS
    n = int(boxcar_max)
    if n != boxcar_max or n < SEARCH_WINDOWS[-1] or n & (n - 1):
        raise ValueError(f"boxcar_max={boxcar_max!r}: expected a power of "
                         f"two of at least {SEARCH_WINDOWS[-1]} samples")
    widest = max(SEARCH_WINDOWS[-1], n // max(int(downsample), 1))
    return tuple(1 << j for j in range(widest.bit_length()))


def check_windows(windows):
    """``windows`` as the scorers take it: ``None`` is the default
    ladder; anything else must continue it in doubling steps (the
    incremental pyramid, the one-pass kernel's tiles and the
    certificate's bound all assume so)."""
    if windows is None:
        return SEARCH_WINDOWS
    windows = tuple(int(w) for w in windows)
    if (len(windows) < len(SEARCH_WINDOWS)
            or windows != tuple(1 << j for j in range(len(windows)))):
        raise ValueError(f"windows={windows!r}: expected 1, 2, 4, 8 "
                         "continued in doubling steps")
    return windows


def scored_windows(windows, nsamples):
    """The levels of ``windows`` a series of ``nsamples`` is scored at
    (the :data:`MIN_WIDE_BLOCKS` cut-off) — one rule for the scorers,
    the certificate's capture and its bound."""
    return tuple(w for w in check_windows(windows)
                 if w <= SEARCH_WINDOWS[-1]
                 or int(nsamples) // w >= MIN_WIDE_BLOCKS)


def cert_wide_windows(windows, nsamples):
    """Widths of the certificate's half-stride captures for a ladder.

    The default ladder has none (its certificate is the sliding
    :data:`CERT_WINDOWS` alone, as ever).  A longer ladder adds one
    capture per scored level from 8 up: windows of that width at
    strides of half of it, i.e. adjacent pairs of the level below —
    the set whose retention ``certify`` bounds below for every pulse
    width the ladder can match.
    """
    scored = scored_windows(windows, nsamples)
    if len(scored) <= len(SEARCH_WINDOWS):
        return ()
    return tuple(w for w in scored if w >= SEARCH_WINDOWS[-1])


def score_profiles(plane, xp=np, windows=None):
    """Score a block of dedispersed series ``(ndm, T)``.

    Returns ``(maxvalues, stds, best_snrs, best_windows, best_peaks)`` per
    trial, reproducing the reference's per-trial loop
    (``pulsarutils/dedispersion.py:186-201``) in batched form, plus the
    peak's sample index in the unbinned series (``argmax`` of the best
    window's block sums, scaled back by the window — the reference threw
    the arrival time away; candidate sifting needs it).

    HBM-traffic transform (round 4), algebraically neutral — every
    backend shares this function, so cross-backend hit parity is
    untouched: the block-sum pyramid is incremental — width 4 sums
    width 2's output, width 8 sums width 4's — reading ~1.8 GB instead
    of 6.3 GB at the 513 x 1M coarse plane (identical sample coverage
    for any T: ``floor(floor(T/2)/2) == floor(T/4)``; only the float
    ASSOCIATION of the in-block adds changes).  The mean subtraction
    stays materialised up front: folding it into the reductions read
    catastrophically-cancelling raw block sums on planes with a large
    DC offset (measured S/N errors of several units at baseline ~1e7
    in float32 — code-review r4).

    ``windows`` is the ladder (static; ``None`` =
    :data:`SEARCH_WINDOWS`): level ``j`` holds the block sums of width
    ``2^j`` at offsets that are multiples of it, ``snr_j = max / std`` of
    the level, the smallest ``j`` winning ties; levels cut off by
    :func:`scored_windows` are not scored.
    """
    plane = xp.asarray(plane)
    windows = scored_windows(windows, plane.shape[1])
    if not xp.issubdtype(plane.dtype, xp.floating):
        # integer-accumulated sweep plane (packed low-bit path): every
        # value is an exact integer below 2^24 (io/lowbit.accum_dtype's
        # bound), so this float32 view is exact and the scores are
        # bit-identical to a float32-accumulated plane's
        plane = plane.astype(xp.float32)
    x = plane - plane.mean(axis=1, keepdims=True)
    maxvalues = x.max(axis=1)
    stds = x.std(axis=1)

    best_snrs = xp.zeros(x.shape[0], dtype=x.dtype)
    best_windows = xp.zeros(x.shape[0], dtype=xp.int32)
    best_peaks = xp.zeros(x.shape[0], dtype=xp.int32)
    reb = x
    for window in windows:
        if window > 1:
            reb = block_sum_time(reb, 2, xp=xp)
        snr = reb.max(axis=1) / reb.std(axis=1)
        peak = xp.argmax(reb, axis=1).astype(xp.int32) * window
        better = snr > best_snrs
        best_snrs = xp.where(better, snr, best_snrs)
        best_windows = xp.where(better, window, best_windows)
        best_peaks = xp.where(better, peak, best_peaks)
    return maxvalues, stds, best_snrs, best_windows, best_peaks


def warn_peak_exactness(nsamples, stacklevel=3):
    """Warn when float32 peak-index accumulation loses exactness.

    Stacked score packs carry the peak sample index as float32, exact
    only below 2^24; every scorer that emits such a pack (the XLA
    :func:`score_profiles_stacked` and the one-pass Pallas
    :func:`..ops.score_pallas.score_plane_pallas`) shares this check so
    no path silently accepts an over-long series (ADVICE r5).  The
    bound itself is owned by :func:`..precision.exactness_domain`
    (ISSUE 17) — this is a consumer, not a second copy of 2^24.
    """
    from ..precision import exactness_domain

    dom = exactness_domain(1, nsamples=nsamples)
    if not dom.peak_index_exact:
        import warnings

        warnings.warn(
            f"series length {nsamples} exceeds 2^24: float32 peak "
            "indices lose exactness (off by up to "
            f"{dom.index_error_samples:.1f} samples)",
            stacklevel=stacklevel)


def score_profiles_stacked(plane, xp=np, windows=None):
    """:func:`score_profiles` packed into ONE ``(5, ndm)`` float array.

    Every array fetched is a host sync with the device; stacking the
    per-trial score vectors device-side makes the whole search's host
    readback a single transfer.  Row order:
    ``max, std, snr, window, peak`` (windows are powers of two up to
    the ladder's widest and peaks are sample indices < 2^24 — both
    exact in float32).
    """
    warn_peak_exactness(plane.shape[1])
    scores = score_profiles(plane, xp=xp, windows=windows)
    dtype = scores[0].dtype
    return xp.stack([s.astype(dtype) for s in scores])


def cert_profile_scores(plane, xp=np, windows=None):
    """Sliding-window certificate score per row of a (coarse) plane.

    ``max_t (x * box_w)(t) / (std * sqrt(w))`` for ``w`` in (2, 3, 4)
    over ALL alignments (sliding, circular) — unlike the detection scorer's
    non-sliding block sums, this capture is pulse-phase-invariant, which
    is what makes the hybrid's structural bounds usable: a pulse whose
    energy the tree scatters over a few adjacent bins always shows a
    sliding-window capture near its full mass, whereas a block boxcar at
    the worst phase splits it (the difference between a worst-case
    retention of ~0.6 and ~0.44 at the benchmark config — see
    :mod:`.certify`).  Used only on the hybrid's coarse plane; detection
    scores keep the reference's block convention.

    A ladder longer than the default (``windows``) adds, per width ``w``
    of :func:`cert_wide_windows`, the windows of width ``w`` at strides
    of ``w / 2`` — adjacent pairs of the level below in the scorer's own
    pyramid — over the ``std`` of the scorer's level ``w``: a superset
    of that level's blocks over the same denominator, so on one series
    this capture is never below the block score at ``w``, and a pulse of
    any width up to twice the ladder's widest keeps a bounded share of
    its exact score whatever its phase (:mod:`.certify`).
    """
    assert CERT_WINDOWS == (2, 3, 4), \
        "cert_profile_scores structurally unrolls widths 2/3/4"
    plane = xp.asarray(plane)
    wide = cert_wide_windows(windows, plane.shape[1])
    # the mean subtraction is materialised (NOT folded into the maxima):
    # raw sliding sums cancel catastrophically at large DC offsets in
    # float32 — see score_profiles
    x = plane - plane.mean(axis=1, keepdims=True)
    std = x.std(axis=1)
    s2 = x + xp.roll(x, -1, axis=1)
    best = s2.max(axis=1) / (std * np.float32(np.sqrt(2.0)))
    s3 = s2 + xp.roll(x, -2, axis=1)
    best = xp.maximum(best, s3.max(axis=1) / (std * np.float32(np.sqrt(3.0))))
    s4 = s2 + xp.roll(s2, -2, axis=1)
    best = xp.maximum(best, s4.max(axis=1) / (std * np.float32(2.0)))
    level, width = x, 1
    while wide and width < wide[-1]:
        below, width = level, 2 * width
        level = block_sum_time(below, 2, xp=xp)
        if width in wide:
            pairs = below[:, :-1] + below[:, 1:]
            best = xp.maximum(best, pairs.max(axis=1) / level.std(axis=1))
    return best


def score_profiles_chunked(plane, xp, chunk=512, with_cert=False,
                           windows=None):
    """:func:`score_profiles_stacked` over row chunks of a large plane.

    Whole-plane scoring materialises the mean-subtracted copy plus four
    boxcar block-sum arrays (~1.9x the plane) all at once — an HBM OOM
    at multi-thousand-trial x long-T shapes on a 16 GB chip.  The
    statically-unrolled chunk loop bounds the scorer's live temps to
    ~``chunk/ndm`` of that, still emitting ONE ``(5, ndm)`` array (one
    host readback round trip) — ``(6, ndm)`` with ``with_cert`` (the
    hybrid's sliding certificate row appended).  The cert row's three
    sliding sums add ~3 more plane-sized temps, so its chunk is capped
    at 128 rows: at 512 x 1M the uncapped 512-row chunk pushed the
    coarse program to a measured 16.25 GB HBM compile-OOM.
    """
    if with_cert:
        chunk = min(chunk, 128)
    rows = plane.shape[0]

    def one(sub):
        stacked = score_profiles_stacked(sub, xp=xp, windows=windows)
        if with_cert:
            stacked = xp.concatenate(
                [stacked,
                 cert_profile_scores(sub, xp=xp, windows=windows)[None]])
        return stacked

    return xp.concatenate(
        [one(plane[lo:min(lo + chunk, rows)])
         for lo in range(0, rows, chunk)], axis=1)


def unstack_scores(stacked):
    """Host-side inverse of :func:`score_profiles_stacked` (one readback).

    Accepts the 5-row pack or the 6-row ``with_cert`` pack; the cert row
    (when present) is returned as-is as a sixth element.
    """
    stacked = np.asarray(stacked)
    maxvalues, stds, best_snrs, wins, peaks = stacked[:5]
    out = (maxvalues, stds, best_snrs, np.rint(wins).astype(np.int32),
           np.rint(peaks).astype(np.int64))
    if stacked.shape[0] > 5:
        out = out + (stacked[5],)
    return out


#: soft cap on the gather workspace (elements) a single trial-block may
#: materialise; keeps the kernel HBM-resident at 1M-sample configs
GATHER_BUDGET_ELEMENTS = 1 << 28


def auto_chan_block(nchan, nsamples, dm_block):
    """Largest power-of-two channel block that (a) divides ``nchan`` and
    (b) keeps ``dm_block * chan_block * nsamples`` under the gather budget.

    Returns ``None`` (no chunking) when the whole channel axis fits.
    """
    if dm_block * nchan * nsamples <= GATHER_BUDGET_ELEMENTS:
        return None
    block = 1
    candidate = 2
    while candidate <= nchan:
        if (nchan % candidate == 0
                and dm_block * candidate * nsamples <= GATHER_BUDGET_ELEMENTS):
            block = candidate
        candidate *= 2
    return block


def _offsets_for(trial_dms, nchan, start_freq, bandwidth, sample_time, nsamples):
    """Host-side float64 shift table -> int32 gather offsets in ``[0, T)``."""
    shifts = dedispersion_shifts_batch(
        np.asarray(trial_dms, dtype=np.float64), nchan, start_freq, bandwidth,
        sample_time)
    return normalize_shifts(shifts, nsamples)


@counted_plan_cache("hybrid_offsets", maxsize=PLAN_CACHE_SIZE)
def _hybrid_offsets_by_key(grid_bytes, nchan, start_freq, bandwidth,
                           sample_time, nsamples):
    from .pallas_dedisperse import rebase_offsets

    # a miss is the one place this host time can come back: it has a
    # name in BUDGET_JSON and in the span tree
    with budget_bucket("search/offsets"):
        offsets = _offsets_for(np.frombuffer(grid_bytes, dtype=np.float64),
                               nchan, start_freq, bandwidth, sample_time,
                               nsamples)
        # ONE rebase over the full table: every subset then shares the
        # same static max_off (one compiled program per bucket) and the
        # same host-side peak correction constant
        rebased, roll_k, max_off = rebase_offsets(offsets, nsamples)
    rebased.setflags(write=False)  # shared cache object: fail loudly
    return rebased, roll_k, max_off


def _hybrid_offsets(trial_dms, nchan, start_freq, bandwidth, sample_time,
                    nsamples):
    """The exact kernels' rebased offset table for one (trial grid,
    geometry): ``rebase_offsets(_offsets_for(...))``, built when a
    rescore first asks and kept by what it is a function of.

    The float64 ``(ndm, nchan)`` delay table depends on the plan, never
    on the chunk; rebuilding it in every search call cost 45 ms a chunk
    at 1,067 trials and 145 ms over a tiered chunk's six grids, on the
    host, before the coarse sweep was dispatched.  The key holds the
    grid's own bytes (8.5 KB at 1,067 trials), not its end points: a
    tier's grid is not ``dedispersion_plan``'s.  Size and hit/miss
    counters (``cache="hybrid_offsets"``) come from
    :mod:`..tuning.geometry`.  Returns ``(rebased, roll_k, max_off)``;
    ``rebased`` is the shared cache object, read-only — callers index
    (``rebased[rows]`` copies), never mutate.
    """
    grid = np.ascontiguousarray(trial_dms, dtype=np.float64)
    return _hybrid_offsets_by_key(grid.tobytes(), int(nchan),
                                  float(start_freq), float(bandwidth),
                                  float(sample_time), int(nsamples))


def block_offsets(offsets, dm_block):
    """Pad the trial axis to a multiple of ``dm_block`` (duplicating the
    last trial — sliced off after the kernel) and reshape to the
    ``(nblocks, dm_block, nchan)`` layout :func:`search_kernel_fn` takes."""
    ndm, nchan = offsets.shape
    npad = (-ndm) % dm_block
    if npad:
        offsets = np.concatenate([offsets, offsets[-1:].repeat(npad, axis=0)])
    return offsets.reshape(-1, dm_block, nchan)


# ---------------------------------------------------------------------------
# NumPy backend
# ---------------------------------------------------------------------------

def _search_numpy(data, trial_dms, start_freq, bandwidth, sample_time,
                  capture_plane, windows=None):
    data = np.asarray(data, dtype=np.float64)
    nchan, nsamples = data.shape
    ndm = len(trial_dms)
    offsets = _offsets_for(trial_dms, nchan, start_freq, bandwidth,
                           sample_time, nsamples)

    if capture_plane == "memmap":
        plane = plane_memmap(ndm, nsamples)  # float32 on disk (16 GB at
        # 4096 x 1M in float64 would double the spill for scores the
        # jax paths keep in float32 anyway); scoring stays float64
    elif capture_plane:
        plane = np.empty((ndm, nsamples), dtype=np.float64)
    else:
        plane = None
    maxvalues = np.empty(ndm)
    stds = np.empty(ndm)
    best_snrs = np.empty(ndm)
    best_windows = np.empty(ndm, dtype=np.int32)
    best_peaks = np.empty(ndm, dtype=np.int64)

    budget_count("host_sweeps")
    block = 16  # score in small batches to bound the workspace
    work = np.empty((block, nsamples))
    for lo in range(0, ndm, block):
        hi = min(lo + block, ndm)
        sub = work[:hi - lo]
        dedisperse_batch_numpy(data, offsets[lo:hi], out=sub)
        if capture_plane:
            plane[lo:hi] = sub
        m, s, b, w, p = score_profiles(sub, windows=windows)
        maxvalues[lo:hi] = m
        stds[lo:hi] = s
        best_snrs[lo:hi] = b
        best_windows[lo:hi] = w
        best_peaks[lo:hi] = p

    return maxvalues, stds, best_snrs, best_windows, best_peaks, plane


# ---------------------------------------------------------------------------
# JAX backend
# ---------------------------------------------------------------------------

def search_kernel_fn(data, offset_blocks, capture_plane=False,
                     chan_block=None, formulation=None, policy=None,
                     windows=None):
    """The pure, jittable forward step of the search (flagship kernel).

    ``data`` is ``(nchan, T)``; ``offset_blocks`` is
    ``(nblocks, dm_block, nchan)`` int32 gather offsets.  Returns the
    per-block stacked scores ``(nblocks, 5, dm_block)`` (see
    :func:`score_profiles_stacked`) — plus the dedispersed plane blocks
    when ``capture_plane``.  Traceable under ``jit``/``shard_map``; the
    blocks are processed by ``lax.map`` so the compiled program is
    independent of the trial count.  ``formulation`` forces the
    dedisperse formulation (``"gather"``/``"roll"``; ``None`` =
    backend-resolved) — the axis the autotuner measures.  ``policy``
    names a :mod:`..precision` accumulation strategy for the channel
    reduction (``None`` = the byte-identical ``f32`` default) — the
    second axis the autotuner measures (ISSUE 17).  ``windows`` is the
    scorer's ladder (static).
    """
    import jax
    import jax.numpy as jnp

    def per_block(offs):
        plane = dedisperse_block_chunked_jax(data, offs, chan_block,
                                             formulation=formulation,
                                             policy=policy)
        scores = score_profiles_stacked(plane, xp=jnp, windows=windows)
        if capture_plane:
            return scores, plane
        return scores

    return jax.lax.map(per_block, offset_blocks)


@functools.lru_cache(maxsize=32)
def _jax_search_kernel(capture_plane, chan_block, formulation=None,
                       packed=None, policy=None, windows=None):
    """The direct-sweep program.  ``packed`` (a
    :meth:`~pulsarutils_tpu.io.lowbit.PackedFrames.meta` tuple) makes
    ``data`` the RAW packed uint8 frames: the bit-unpack runs inside
    this jit, so the host->device link carries 1/8-1/16th the bytes and
    — when the meta names an integer dtype — the sweep accumulates in
    int16/int32 (exact; converted to float32 only at scoring)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def direct_sweep(data, offset_blocks):
        if packed is not None:
            from ..io.lowbit import unpack_from_meta

            data = unpack_from_meta(data, packed, jnp)
        return search_kernel_fn(data, offset_blocks,
                                capture_plane=capture_plane,
                                chan_block=chan_block,
                                formulation=formulation,
                                policy=policy, windows=windows)

    return direct_sweep


#: alignment of the exact kernels' rebase rotation
#: (``pallas_dedisperse.rebase_offsets``): block sums of windows up to it
#: are a rotation of the reference's
REBASE_ALIGN = 128

#: trials dedispersed per Pallas pass — bounds the live plane to
#: superblock * nsamples floats (512 x 1M = 2 GB) regardless of ndm
PALLAS_SUPERBLOCK = 512


def plane_memmap(ndm, nsamples, directory=None, delete=False):
    """A disk-backed ``(ndm, nsamples)`` float32 plane (``.npy`` memmap).

    The reference spills its dedispersed plane to a disk memmap so
    ``show=True`` works at any size (``pulsarutils/dedispersion.py:
    215-218``); this is the equivalent for ``capture_plane="memmap"`` —
    a 4096-trial x 1M-sample capture is 16 GB, beyond host RAM on many
    driver nodes.  The file is a valid ``.npy`` (``np.load(...,
    mmap_mode=...)`` reopens it); its path is ``plane.filename``.
    Directory: ``directory`` arg, else ``$PUTPU_PLANE_DIR``, else the
    system temp dir (size that directory for ndm*nsamples*4 bytes per
    concurrent capture).  Deletion: by default the file persists so
    diagnostics can outlive the search — free it with
    :func:`release_plane` (or ``os.unlink(plane.filename)``) when done;
    ``delete=True`` instead ties the file's lifetime to the returned
    memmap (``weakref.finalize`` unlinks it at garbage collection), so
    repeated captures cannot silently fill the temp dir.
    """
    import tempfile
    import weakref

    directory = directory or os.environ.get("PUTPU_PLANE_DIR") or None
    fd, path = tempfile.mkstemp(suffix=".npy", prefix="putpu_plane_",
                                dir=directory)
    os.close(fd)
    mm = np.lib.format.open_memmap(path, mode="w+", dtype=np.float32,
                                   shape=(int(ndm), int(nsamples)))
    if delete:
        weakref.finalize(mm, _unlink_quiet, path)
    return mm


def _unlink_quiet(path):
    try:
        os.unlink(path)
    except OSError:
        pass


def release_plane(plane):
    """Unlink the disk file behind a :func:`plane_memmap` capture.

    Accepts any plane a search returned: a plain ndarray (no-op) or a
    ``np.memmap``-backed capture, whose ``.npy`` file is removed.  Safe
    to call twice.
    """
    path = getattr(plane, "filename", None)
    if path:
        _unlink_quiet(path)


@functools.lru_cache(maxsize=8)
def _jitted_scorer(windows=None):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(plane):
        return score_profiles_stacked(plane, xp=jnp, windows=windows)

    return score


def _search_jax_pallas(data, offsets, capture_plane, dm_block=None,
                       chan_block=None, windows=None):
    """Pallas-kernel sweep: dedisperse in trial superblocks, score each."""
    from .pallas_dedisperse import dedisperse_plane_pallas

    ndm = offsets.shape[0]
    nsamples = int(np.shape(data)[1])
    scorer = _jitted_scorer(windows)
    mm = plane_memmap(ndm, nsamples) if capture_plane == "memmap" else None
    outs, planes = [], []
    for lo in range(0, ndm, PALLAS_SUPERBLOCK):
        sub = offsets[lo:lo + PALLAS_SUPERBLOCK]
        with budget_bucket("search/dispatch"):
            plane = dedisperse_plane_pallas(data, sub, dm_block=dm_block,
                                            chan_block=chan_block)
            scored = scorer(plane)
            budget_count("dispatches", 2)
        with budget_bucket("search/readback"):
            outs.append(unstack_scores(scored))  # one readback
            budget_count("readbacks")
        if mm is not None:
            # disk spill (reference memmap parity, dedispersion.py:
            # 215-218): host RAM holds one superblock transiently, disk
            # holds the plane — any ndm x T capture in bounded memory.
            # The spill is the LARGEST single transfer in a capture run,
            # so it gets its own bucket + trip count
            with budget_bucket("search/plane_spill"):
                mm[lo:lo + plane.shape[0]] = np.asarray(plane)
                budget_count("readbacks")
        elif capture_plane:
            # single superblock: keep the plane device-resident so
            # downstream consumers (plane period search, diagnostics)
            # pull only what they need over the slow host link.  Multiple
            # superblocks: spill each to host as it completes — device
            # concatenation would hold all blocks plus the result (2x the
            # full plane) in HBM, breaking the PALLAS_SUPERBLOCK bound.
            if ndm <= PALLAS_SUPERBLOCK:
                planes.append(plane)
            else:
                with budget_bucket("search/plane_spill"):
                    planes.append(np.asarray(plane))
                    budget_count("readbacks")
    maxvalues, stds, best_snrs, best_windows, best_peaks = (
        np.concatenate([o[i] for o in outs]) for i in range(5))
    if mm is not None:
        mm.flush()
        plane = mm
    elif not capture_plane:
        plane = None
    elif len(planes) == 1:
        plane = planes[0]
    else:
        plane = np.concatenate(planes)
    return maxvalues, stds, best_snrs, best_windows, best_peaks, plane


def _search_jax_fdmt(data, dmmin, dmmax, start_freq, bandwidth, sample_time,
                     capture_plane, with_cert=False, windows=None):
    """FDMT sweep: every integer-delay trial in one log-depth transform.

    Trial grid is the FDMT's natural (= the reference plan's) integer
    band-delay grid on ``[dmmin, dmmax]`` — see
    :func:`pulsarutils_tpu.ops.fdmt.fdmt_trial_dms`.  ``with_cert``
    appends the sliding certificate row (hybrid's coarse stage).
    """
    import jax.numpy as jnp

    from .fdmt import _build_transform, _transform_setup, fdmt_trial_dms

    nchan = data.shape[0]
    trial_dms, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, start_freq,
                                           bandwidth, sample_time)
    data = jnp.asarray(data, jnp.float32)
    data, t_run, t_tile, use_pallas, interpret, t_orig = _transform_setup(
        data, None)
    # scoring (and the row slice) run inside the transform's jit: only
    # the per-trial score vectors (and optionally the plane) leave the
    # device, keeping back-to-back searches within HBM
    run = _build_transform(nchan, float(start_freq), float(bandwidth),
                           n_hi, t_run, t_tile, use_pallas, interpret,
                           n_lo=n_lo, with_scores=True,
                           with_plane=capture_plane, t_orig=t_orig,
                           with_cert=with_cert, windows=windows)
    with budget_bucket("search/coarse"):
        out = run(data)
        budget_count("dispatches")
        budget_count("sweep_calls")
        budget_count("sweep_samples", t_orig)
    if capture_plane:
        stacked, plane_out = out  # plane stays device-resident
    else:
        stacked, plane_out = out, None
    with budget_bucket("search/coarse_readback"):
        scores = unstack_scores(stacked)
        budget_count("readbacks")
    (maxvalues, stds, best_snrs, best_windows, best_peaks) = scores[:5]
    out = (trial_dms, maxvalues, stds, best_snrs, best_windows, best_peaks,
           plane_out)
    if with_cert:
        out = out + (scores[5],)
    return out


def time_tiles_of(data):
    """How many time tiles ``data`` is searched in: 1 for an array, more
    for a tier the device cannot hold whole
    (:class:`~pulsarutils_tpu.pipeline.time_tiles.TiledTierArray`: a
    ``shape``, its ``tier``, ``own`` samples a tile, a ``halo``, how many
    cleaned tiles a rescore may ``keep``, the delay ``bands`` a tile is
    swept in (empty: one sweep) with their ``band_seconds``, and
    ``tile(i, shift)``, the cleaned ``own + halo`` samples from ``i * own
    + shift`` on, circular over the chunk)."""
    return int(getattr(data, "time_tiles", 1))


def _search_jax_fdmt_tiled(src, dmmin, dmmax, start_freq, bandwidth,
                           sample_time, windows=None):
    """:func:`_search_jax_fdmt` (``with_cert``, no plane) of a tier that
    exists a time tile at a time: the unchanged transform on each tile's
    axis of ``own + halo`` samples, the scorer's partials over the tile's
    own outputs (the last ``halo`` are the circular transform's wrap), and
    the rows' scores from the tiles' partials
    (:func:`~.score_partials.combine_partials`).  The last tile's halo is
    the chunk's start, so the row stays the circular series of the whole
    chunk, which is what the certificate's bound and the reference
    assume.  A tile's coarse values are the untiled sweep's bit for bit
    (the same tree of adds); the scores agree to float32 summation order.

    A tier in delay bands (``src.bands``) runs one such transform a band
    on each cleaned tile, over the band's delays alone: a row's tree of
    adds does not depend on which other rows are computed, so the bands'
    rows, one after the other, are the one sweep's.
    """
    import jax

    from ..obs.trace import span as trace_span
    from .fdmt import _build_transform, _pick_fdmt_tile, fdmt_trial_dms
    from .score_partials import combine_partials

    nchan, total = src.shape
    trial_dms, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, start_freq,
                                           bandwidth, sample_time)
    length = src.own + src.halo
    use_pallas = jax.default_backend() == "tpu"
    t_tile = _pick_fdmt_tile(length)
    if src.halo < n_hi or (use_pallas and not t_tile):
        raise ValueError(f"a time tile of {src.own} + {src.halo} samples "
                         f"cannot hold band delays to {n_hi} (or no FDMT "
                         "tile divides it): the tile plan is not this "
                         "tier's")
    bands = src.bands or ((n_lo, n_hi),)
    if (bands[0][0], bands[-1][1]) != (n_lo, n_hi) or any(
            a[1] + 1 != b[0] for a, b in zip(bands, bands[1:])):
        raise ValueError(f"delay bands {bands} do not cover band delays "
                         f"{n_lo}-{n_hi}: the tile plan is not this tier's")
    runs = [_build_transform(nchan, float(start_freq), float(bandwidth), hi,
                             length, t_tile, use_pallas, not use_pallas,
                             n_lo=lo, with_scores=True, with_plane=False,
                             t_orig=length, with_cert=True, windows=windows,
                             partial=(src.own, total)) for lo, hi in bands]
    parts = [[] for _ in bands]

    def sweep(b, tile):
        with budget_bucket("search/coarse"):
            out = runs[b](tile)
            budget_count("dispatches")
            budget_count("sweep_calls")
            budget_count("sweep_samples", length)
        with budget_bucket("search/coarse_readback"):
            parts[b].append(np.asarray(out))
            budget_count("readbacks")

    for i in range(src.time_tiles):
        with trace_span("search/tile", tier=src.tier, tile=i,
                        samples=src.own, halo=src.halo):
            with budget_bucket("search/tile_clean"):
                tile = src.tile(i)
                budget_count("dispatches")
            if not src.bands:   # one sweep a tile: no band to name
                sweep(0, tile)
            for b, (lo, hi) in enumerate(src.bands):
                with trace_span("search/band", tier=src.tier, band=b,
                                n_lo=lo, n_hi=hi,
                                tiles=src.time_tiles) as band_span:
                    sweep(b, tile)
                src.band_seconds[b] += band_span.dur
            del tile
    scores = unstack_scores(np.concatenate(
        [combine_partials(p, src.own, windows, total, with_cert=True)
         for p in parts], axis=1))
    return (trial_dms,) + tuple(scores[:5]) + (None, scores[5])


@functools.lru_cache(maxsize=16)
def _tiled_rescore_kernel(max_off, dm_block, windows, own, total):
    """One jitted program: exact dedispersion of a row bucket on one time
    tile + the partials of its own samples.  The tile starts ``roll_k``
    (the rebase's rotation, :func:`~.pallas_dedisperse.rebase_offsets`)
    before its own first sample, so output ``t`` of the rebased kernel IS
    sample ``t`` of the reference's row: no rotation is left to undo,
    whatever the ladder."""
    import jax
    import jax.numpy as jnp

    from .score_partials import score_partials

    on_tpu = jax.default_backend() == "tpu"

    @jax.jit
    def rescore_tile(data, offs):
        if on_tpu:
            from .pallas_dedisperse import dedisperse_plane_pallas_traced

            plane = dedisperse_plane_pallas_traced(data, offs, max_off,
                                                   dm_block=dm_block)
        else:
            from .dedisperse import dedisperse_block_jax

            plane = dedisperse_block_jax(data, offs)
        return score_partials(plane[:, :own], jnp, windows, total)

    return rescore_tile


def _search_jax(data, trial_dms, start_freq, bandwidth, sample_time,
                capture_plane, dm_block, chan_block, dtype, kernel="auto",
                precision=None, windows=None):
    import jax
    import jax.numpy as jnp

    from ..io.lowbit import PackedFrames, accum_dtype
    from ..precision import engage as _engage
    from ..precision import resolve_policy as _resolve_policy

    # explicit precision wins; else PUTPU_PRECISION; else "f32".  "auto"
    # defers to the autotuner once the formulation is known (below).
    eff_policy = _resolve_policy(precision)
    packed = data if isinstance(data, PackedFrames) else None
    nchan, nsamples = np.shape(data)  # PackedFrames reports its logical shape
    ndm = len(trial_dms)
    if packed is not None and dtype not in (None, jnp.float32):
        raise ValueError("packed low-bit input unpacks to float32 (or an "
                         "exact integer accumulator); pass dtype=None")

    if kernel == "fourier":
        from .fourier import search_fourier

        if windows is not None and tuple(windows) != SEARCH_WINDOWS:
            raise ValueError("kernel='fourier' scores with the default "
                             "boxcar ladder only")
        if eff_policy not in ("f32", "auto"):
            raise ValueError("precision policies apply to the gather/roll "
                             "channel reductions; kernel='fourier' is "
                             "float32-only")
        if capture_plane == "memmap":
            raise ValueError("capture_plane='memmap' requires "
                             "kernel='pallas'/'auto' or backend='numpy'")
        if dtype not in (None, jnp.float32):
            raise ValueError("kernel='fourier' supports float32 only")
        if packed is not None:
            # FDD wants the float block: packed upload + cached device
            # unpack (the link still carries the packed bytes)
            data = packed.to_device()
        # before the integer-offset table: the FDD uses un-rounded delays
        # (and data passes through untouched — converting a
        # device-resident chunk would bounce it over the slow link)
        return search_fourier(data, trial_dms, start_freq, bandwidth,
                              sample_time, capture_plane=capture_plane,
                              dm_block=dm_block, chan_block=chan_block)

    offsets = _offsets_for(trial_dms, nchan, start_freq, bandwidth,
                           sample_time, nsamples)

    if kernel == "auto":
        # measured per-(backend, geometry) selection with a persistent
        # tune cache (the PAPERS.md auto-tuning survey's lesson, made
        # operational).  The static heuristic — Pallas on TPU, roll-scan
        # on CPU (PR 1's measured 14x), gather elsewhere — stays as the
        # zero-measurement fallback and the PUTPU_AUTOTUNE=off escape
        # hatch; a winner is only ever cached after passing the
        # exact-hit-match equivalence harness.
        from ..tuning import autotune as _autotune

        kernel = _autotune.resolve_search_kernel(
            nchan, nsamples, ndm, dtype, capture_plane, start_freq,
            bandwidth, sample_time, trial_dms, dm_block=dm_block,
            chan_block=chan_block)
    if kernel in ("gather", "roll") and capture_plane == "memmap":
        raise ValueError("capture_plane='memmap' requires the Pallas "
                         "spill path (kernel='pallas'/'auto' with the "
                         "default float32 dtype) or backend='numpy' — "
                         "the gather/roll kernels hold the full plane in "
                         "device memory, and the Pallas kernel is "
                         "float32-only")
    if kernel == "pallas":
        if eff_policy not in ("f32", "auto"):
            raise ValueError("precision policies apply to the gather/roll "
                             "channel reductions; kernel='pallas' declares "
                             "its own f32 accumulation")
        if dtype not in (None, jnp.float32):
            raise ValueError("kernel='pallas' supports float32 only; use "
                             "kernel='gather' for other dtypes")
        if packed is not None:
            data = packed.to_device()  # packed upload, unpack on HBM
        data = jnp.asarray(data, dtype=jnp.float32)
        return _search_jax_pallas(data, offsets, capture_plane, dm_block,
                                  chan_block, windows=windows)
    packed_meta = None
    if packed is not None:
        # in-jit unpack for the traceable formulations: the RAW bytes
        # are the program's operand.  Integer accumulation only when
        # the plane never leaves the program (capture consumers expect
        # a float plane) and the exactness bound holds.
        acc = (None if capture_plane
               else accum_dtype(packed.nbits, nchan)) or "float32"
        packed_meta = packed.meta(acc)
        data = packed.frames
    dtype = dtype or jnp.float32
    data = (jnp.asarray(data) if packed_meta is not None
            else jnp.asarray(data, dtype=dtype))

    if dm_block is None:
        dm_block = max(1, min(ndm, 32))
    if chan_block is None:
        chan_block = auto_chan_block(nchan, nsamples, dm_block)
    offset_blocks = block_offsets(offsets, dm_block)

    # both spellings force their formulation (an auto-resolving
    # "gather" would make the CPU tuner measure the same program twice
    # and never reproduce PR 1's 14x) — pre-tuner "auto" callers are
    # unaffected because the static fallback names the formulation the
    # old backend switch picked ("roll" on CPU, the gather elsewhere)
    from ..resilience import ladder as _ladder
    from ..resilience import memory_budget as _membudget

    formulation = (kernel if kernel in ("gather", "roll")
                   else ("roll" if jax.default_backend() == "cpu"
                         else "gather"))
    if eff_policy == "auto":
        # measured (kernel, policy)-pair selection (ISSUE 17): a
        # non-default strategy only ever wins after the exact-hit-match
        # harness passes at its stated bound; the static fallback is
        # the formulation's plain f32 pairing.
        from ..tuning import autotune as _autotune

        pair = _autotune.resolve_search_policy(
            formulation, nchan, nsamples, ndm, start_freq, bandwidth,
            sample_time, trial_dms, dm_block=dm_block,
            chan_block=chan_block)
        eff_policy = pair.split("+", 1)[1]
    policy_arg = None if eff_policy == "f32" else eff_policy
    if policy_arg is not None:
        _engage(policy_arg)
    nblocks = len(offset_blocks)
    # preflight (ISSUE 12): a dispatch whose footprint estimate exceeds
    # measured headroom splits BEFORE compiling — no-op when headroom
    # is unknown (the CPU default), so the default path is byte-inert
    _membudget.preflight_direct(
        formulation, nchan, nsamples, ndm, dm_block=dm_block,
        chan_block=chan_block, capture_plane=bool(capture_plane),
        nblocks=nblocks,
        packed_nbits=packed_meta[0] if packed_meta else 0)
    while True:
        passes = _ladder.direct_plan(formulation, nblocks)
        try:
            stacked, plane_blocks = _dispatch_direct(
                data, offset_blocks, capture_plane, chan_block, kernel,
                packed_meta, passes, policy=policy_arg, windows=windows)
            break
        except (ValueError, TypeError):
            raise  # deterministic configuration error, never OOM
        except Exception as exc:  # jax errors share no base class
            if not _ladder.is_resource_exhausted(exc) \
                    or _ladder.direct_maxed(formulation, nblocks):
                raise
            # RESOURCE_EXHAUSTED: descend the ladder and re-dispatch
            # smaller — byte-identical by construction (per-trial rows
            # are independent sums; gather columns are independent)
            _ladder.oom_event("direct_sweep")
            step = _ladder.direct_step(formulation)
            logger.warning("direct sweep OOM (%r); ladder step %r",
                           exc, step)
            _ladder.descend(step)
            _ladder.count_split("ladder")
    if _membudget.allocator_reports_limit():
        # calibration loop (ISSUE 12): fold this dispatch's allocator
        # high-water mark against the model's estimate into the
        # persisted per-geometry offset.  Gated on a REAL allocator
        # limit — the CPU live-array fallback has no watermark to
        # learn from (and must not pay a live_arrays sweep here).
        _membudget.observe(nchan, nsamples, ndm, _membudget.estimate_direct(
            nchan, nsamples, ndm, dm_block=dm_block,
            chan_block=chan_block, formulation=formulation,
            capture_plane=bool(capture_plane), dm_passes=passes,
            packed_nbits=packed_meta[0] if packed_meta else 0)["total"])
    stacked = stacked.transpose(1, 0, 2).reshape(5, -1)[:, :ndm]
    (maxvalues, stds, best_snrs, best_windows,
     best_peaks) = unstack_scores(stacked)
    if capture_plane:  # keep device-resident (see _search_jax_pallas)
        plane = plane_blocks.reshape(-1, *plane_blocks.shape[2:])
        if plane.shape[0] != ndm:  # slicing outside jit is a real copy
            plane = plane[:ndm]
    else:
        plane = None
    return maxvalues, stds, best_snrs, best_windows, best_peaks, plane


def _dispatch_direct(data, offset_blocks, capture_plane, chan_block,
                     formulation, packed_meta, passes, policy=None,
                     windows=None):
    """One direct-sweep dispatch at the given degradation level.

    ``passes == 1`` is the exact pre-resilience path (single dispatch,
    plane kept device-resident).  Degraded levels split the trial-block
    axis into ``passes`` dispatches of the SAME compiled per-block body
    — each pass's buffers die before the next dispatch, which is the
    footprint reduction, and because only the ``lax.map``-ed outer axis
    shrinks (every per-block shape is unchanged) the concatenated score
    packs and captured plane are byte-identical to the unsplit run
    (``tests/test_resilience.py`` pins it; splitting the *inner* time
    axis was tested and rejected — XLA reassociates the channel
    reduction when the column extent changes, see docs/robustness.md).
    """
    import jax.numpy as jnp

    kernel_fn = _jax_search_kernel(capture_plane, chan_block, formulation,
                                   packed_meta, policy, windows)
    if passes <= 1:
        with budget_bucket("search/dispatch"):
            offs_dev = jnp.asarray(offset_blocks)  # attributed
            out = kernel_fn(data, offs_dev)
            budget_count("dispatches")
        stacked = out[0] if capture_plane else out  # (nblocks, 5, dmb)
        with budget_bucket("search/readback"):
            stacked = np.asarray(stacked)
            budget_count("readbacks")
        return stacked, (out[1] if capture_plane else None)
    parts = []
    planes = []
    for sub in np.array_split(offset_blocks, passes):
        if not len(sub):
            continue
        with budget_bucket("search/dispatch"):
            offs_dev = jnp.asarray(sub)
            out = kernel_fn(data, offs_dev)
            budget_count("dispatches")
        with budget_bucket("search/readback"):
            parts.append(np.asarray(out[0] if capture_plane else out))
            budget_count("readbacks")
            if capture_plane:
                # degraded mode trades plane residency for footprint:
                # each pass's plane blocks spill to host so at most one
                # pass's worth of plane lives in HBM
                planes.append(np.asarray(out[1]))
                budget_count("readbacks")
    stacked = np.concatenate(parts, axis=0)
    return stacked, (np.concatenate(planes, axis=0) if capture_plane
                     else None)


#: rescore-call row buckets (requested rows pad up to the next bucket);
#: a small set of static shapes keeps compiles bounded while not paying
#: the biggest block's VPU cost for a handful of rows.  The 32-row top
#: bucket matters for LARGE rescans (the round-budget fallback rescores
#: every remaining row — halving the top bucket would double its
#: dispatches); the fused seed uses its own smaller
#: :data:`HYBRID_SEED_BUCKET`.
HYBRID_RESCORE_BUCKETS = (8, 16, 32)

#: hard cap on guarantee-loop iterations before the hybrid falls back to
#: rescoring every remaining candidate row (correctness is then trivial)
HYBRID_MAX_ROUNDS = 20

#: structural bound on how much of a real pulse's S/N the coarse (FDMT)
#: sweep can lose to tree track rounding: every unrescored row whose
#: coarse S/N is within this fraction of the exact best gets rescored
#: regardless of the adaptively-observed error (guards against the
#: observed-error sample being biased toward the peak, where the coarse
#: score tracks well).  MEASURED (round 3, ops/certify.py — worst-case
#: retention computed exactly from the transform's own merge tables):
#: at the 1024-chan / 1M-sample / DM 300-635 headline config the block
#: detection scorer retains >= 0.436 of a worst-phase width-1 pulse's
#: exact S/N (mean 0.60), so the matching margin fraction is
#: 1 - 0.436 = 0.564 — the round-2 hand value of 0.45 was slightly
#: optimistic at the worst phase and is corrected here.  This constant
#: is only the FALLBACK for callers that do not supply the sliding
#: certificate scores; the hybrid itself now uses the per-config
#: phase-invariant bound (``certify.cert_retention``) — computed rather
#: than hand-set, and tighter (~0.56 retention; sound up to the noise
#: cross-term, see certify's *Miss risk* section).
HYBRID_COARSE_TRUST = 0.60


def iter_rescore_buckets(rows):
    """Yield ``(rows_block, padded_block)`` per fixed-shape bucket.

    Splits a rescore request into :data:`HYBRID_RESCORE_BUCKETS`-sized
    blocks, each padded (repeating the last row) up to the next bucket —
    a small set of static shapes keeps compiles bounded while not paying
    the biggest block's cost for a handful of rows.  Shared by the
    single-device and sharded hybrids.
    """
    rows = np.asarray(rows)
    top = HYBRID_RESCORE_BUCKETS[-1]
    for blk_lo in range(0, len(rows), top):
        blk = rows[blk_lo:blk_lo + top]
        bucket = next(b for b in HYBRID_RESCORE_BUCKETS if b >= len(blk))
        yield blk, np.concatenate(
            [blk, blk[-1:].repeat(bucket - len(blk))])


def nearest_rows(sorted_grid, targets):
    """Index of the nearest ``sorted_grid`` entry for each target value.

    Maps plan-grid trial DMs onto the coarse integer-band-delay grid
    (both sorted, one-sample spacing, offset < 1 trial apart) — shared
    by the single-device and sharded hybrid searches.
    """
    sorted_grid = np.asarray(sorted_grid)
    targets = np.asarray(targets)
    pos = np.searchsorted(sorted_grid, targets)
    lo = np.clip(pos - 1, 0, len(sorted_grid) - 1)
    hi = np.clip(pos, 0, len(sorted_grid) - 1)
    return np.where(np.abs(sorted_grid[lo] - targets)
                    <= np.abs(sorted_grid[hi] - targets), lo, hi)


def hybrid_guarantee_loop(coarse_snrs, snrs, exact, rescore,
                          snr_floor=None, seed_done=False,
                          cert_scores=None, rho_cert=None,
                          cert_slack=None):
    """The hybrid's seed + guarantee iteration (see
    :func:`_search_jax_hybrid` for the full rationale).

    ``snrs``/``exact`` are mutated in place by ``rescore(rows)``.

    With ``cert_scores``/``rho_cert`` supplied (the sliding certificate
    row and the per-config retention bound, :mod:`.certify`), the loop
    uses the cert-based skip criterion: row ``j`` is left unrescored
    only when ``(cert_j + HYBRID_CERT_SLACK) / rho_cert < best_exact``
    — an impulsive signal beating the exact best would show a
    certificate score above that line, so skipped rows cannot hold the
    best hit *under the stated signal model, up to the Gaussian noise
    cross-term the slack absorbs* (sd <= 1 S/N unit; at the default
    slack an at-worst-phase row whose true S/N exactly ties the best
    retains a ``Phi(-0.5)`` ~ 31% chance of evading rescoring — see
    :mod:`.certify`'s *Miss risk* section; the probability collapses as
    the true gap grows, and such a tie is score-equivalent anyway).
    This replaces the round-2 heuristic margins (1.5x the *observed*
    underestimate — a peak-biased sample — and the hand-set
    :data:`HYBRID_COARSE_TRUST` fraction), which the round-3 worst-case
    analysis showed could in principle skip a worst-phase width-1
    pulse deterministically.  Consequence worth knowing: on chunks
    whose best is barely above the noise (no certificate, no bright
    pulse) the cert-based criterion rescans honestly toward a full
    exact sweep — the noise-certificate fast path, not the margin, is
    what makes signal-free chunks cheap.

    Without cert scores the legacy margins apply (conservative fallback
    for callers that only have block coarse scores).  ``seed_done=True``
    skips the seeding round (the fused TPU program already rescored it).
    ``cert_slack`` overrides :data:`~.certify.HYBRID_CERT_SLACK` in the
    skip criterion (derive it from a target miss probability with
    :func:`~.certify.cert_slack_for_miss_p`).
    """
    from .certify import HYBRID_CERT_SLACK

    if cert_slack is None:
        cert_slack = HYBRID_CERT_SLACK
    ndm = len(coarse_snrs)
    if not seed_done:
        seed = (coarse_snrs >= coarse_snrs.max() - 0.5)
        if snr_floor is not None:
            seed |= coarse_snrs >= snr_floor - 0.75
        seed_idx = np.flatnonzero(seed)
        grown = np.unique(np.clip(seed_idx[:, None]
                                  + np.arange(-1, 2)[None, :], 0, ndm - 1))
        rescore(grown)
    cert_based = cert_scores is not None and rho_cert is not None
    for _round in range(HYBRID_MAX_ROUNDS):
        best_exact = snrs[exact].max()
        if cert_based:
            need = (~exact) & (cert_scores
                               >= rho_cert * best_exact - cert_slack)
            # consistency guard (mirrors certify_noise_only's): a row
            # whose DISPLAYED coarse block score already beats the exact
            # best must be rescored even if its sliding cert score is
            # low (single-spike-with-negative-dips junk outside the
            # impulsive model) — otherwise argbest could land on a
            # non-exact row, breaking the exact-argbest contract
            need |= (~exact) & (coarse_snrs >= best_exact)
            if snr_floor is not None:
                need |= (~exact) & (cert_scores >= rho_cert * snr_floor
                                    - cert_slack)
                # same consistency guard for the floor contract: a row
                # DISPLAYING an above-floor coarse score must be exact
                need |= (~exact) & (coarse_snrs >= snr_floor)
        else:
            under = (snrs[exact] - coarse_snrs[exact]).max(initial=0.0)
            margin = max(1.5 * under, HYBRID_COARSE_TRUST * best_exact, 0.25)
            need = (~exact) & (coarse_snrs >= best_exact - margin)
            if snr_floor is not None:
                need |= (~exact) & (coarse_snrs >= snr_floor - 0.75)
        todo = np.flatnonzero(need)
        if todo.size == 0:
            break
        rescore(todo)
    else:
        # round budget exhausted: rescore EVERY remaining row, exactly as
        # documented at HYBRID_MAX_ROUNDS — a narrower criterion here
        # (e.g. best_exact - 0.25) could leave a row whose coarse score
        # understates the true best unrescored, silently voiding the
        # exact-hit guarantee in precisely the pathological cases this
        # cap exists for
        todo = np.flatnonzero(~exact)
        if todo.size:
            rescore(todo)


def hybrid_certificate_gate(cert_scores, coarse_snrs, snrs, exact, rescore,
                            *, nchan, trial_dms, start_freq, bandwidth,
                            sample_time, nsamples, snr_floor,
                            noise_certificate, seed_done=False,
                            rho_cert=None, cert_slack=None, windows=None):
    """The certificate check + guarantee loop, shared VERBATIM by the
    single-device and sharded hybrids (their docstrings promise an
    identical contract — this helper is what makes that true).

    Owns the PAD-FREE soundness guard: on TPU a time axis no
    power-of-two tile divides gets zero-padded inside the transform
    (``fdmt._transform_setup``), gathers wrap through the pad instead
    of circularly mod ``nsamples``, and the retention bound's circular
    model no longer applies — neither the certificate nor the
    cert-based skip proof may run, so the loop falls back to the
    legacy conservative margins (and the retention bound is not even
    computed — it could inform nothing).

    Otherwise computes the per-config retention bound, certifies the
    chunk signal-free when permitted (skipping the loop entirely), and
    runs :func:`hybrid_guarantee_loop` with the cert-based skip
    criterion (sound under the stated signal model up to the Gaussian
    noise cross-term — :mod:`.certify`, *Miss risk*).  Returns
    ``(certified, rho_cert_min)`` — ``rho_cert_min`` is ``None`` on
    padded runs.

    ``rho_cert`` pre-empts the bound computation: a float is used
    verbatim (callers cycling many distinct geometries can precompute
    ``certify.cert_retention(...).min()`` off the hot path — the
    first-call cost is multi-second at multi-thousand-trial configs,
    lru-cached per config afterwards); ``False`` opts out of the
    cert-based machinery entirely, dropping the loop to the legacy
    conservative margins (no certificate, no bound computation).
    ``cert_slack`` overrides the default
    :data:`~.certify.HYBRID_CERT_SLACK` in both the certificate
    threshold and the skip criterion.  ``windows`` is the ladder the
    scores came from: the bound is the one for that ladder.
    """
    import jax

    from .certify import certify_noise_only, retention_bound
    from .fdmt import _pick_fdmt_tile

    if rho_cert is False or (jax.default_backend() == "tpu"
                             and _pick_fdmt_tile(int(nsamples)) == 0):
        cert_scores = None
        noise_certificate = False

    rho_cert_min = None
    certified = False
    if cert_scores is not None:
        if rho_cert is not None:
            rho_cert_min = float(rho_cert)
        else:
            # multi-second host computation on first call per config
            # (lru-cached after) — a named budget bucket so a cache miss
            # cannot hide inside the search stage (VERDICT r5 #2 listed
            # "floor computation" among the uninstrumented suspects)
            with budget_bucket("search/cert_floor"):
                rho_cert_min = retention_bound(nchan, trial_dms,
                                               start_freq, bandwidth,
                                               sample_time, nsamples,
                                               cert=True, windows=windows)
        certified = bool(noise_certificate
                         and certify_noise_only(cert_scores, snr_floor,
                                                rho_cert_min,
                                                coarse_snrs=coarse_snrs,
                                                slack=cert_slack))
    if not certified:
        hybrid_guarantee_loop(coarse_snrs, snrs, exact, rescore,
                              snr_floor=snr_floor, seed_done=seed_done,
                              cert_scores=cert_scores,
                              rho_cert=rho_cert_min,
                              cert_slack=cert_slack)
    return certified, rho_cert_min


#: top-k coarse rows the fused seed program rescores device-side (plus
#: grid neighbours, padded to one HYBRID_SEED_BUCKET)
HYBRID_SEED_TOPK = 2

#: rows the fused first-round program rescores.  Round-3 A/B (v5e 1M
#: headline) picked bucket 16 with top-5 (0.489 s): smaller seeds
#: regressed because every miss cost a host-loop ROUND TRIP.  Round 4's
#: in-dispatch need stage (HYBRID_NEED_BUCKET) absorbs those misses on
#: the device, flipping the trade — re-swept with the need stage on:
#: (top-5, 16): 0.512 s; (top-2, 8): 0.451 s, same exact argbest.  The
#: exact rescore costs ~6 ms/row regardless of batch, so every padded
#: slot is real money.  Deliberately decoupled from
#: HYBRID_RESCORE_BUCKETS so shrinking the seed does not shrink the
#: max block of large guarantee-loop rescans.
HYBRID_SEED_BUCKET = 8

#: rows the fused program's SECOND stage rescores (round 4, VERDICT r3
#: #4): after the seed's exact scores, the device evaluates the
#: guarantee loop's own cert-based need mask against the seed's
#: best_exact and rescores the top-scoring flagged rows in the same
#: dispatch — on typical hit chunks the host loop then finds nothing
#: left and the whole search costs ONE round trip (each trip is a host
#: sync).  Sized 8, measured (v5e 1M headline, round 4):
#: the exact rescore costs ~6 ms/row regardless of batch (VPU-bound),
#: so padding slots are pure waste — kernel-only A/B: bucket2 0/8/32 =
#: 0.396/0.449/0.591 s with n_need = 1 flagged row.  Chunks flagging
#: more than 8 rows fall through to the host loop (which was the only
#: path for ALL of them before round 4).
HYBRID_NEED_BUCKET = 8


def fused_masked_topk(score, mask, bucket):
    """Device-side selection of up to ``bucket`` rows of ``mask``.

    Shared by the single-device and mesh fused hybrid kernels:
    ``top_k`` over ``score`` restricted to ``mask``, with slots beyond
    the flagged count (``n = mask.sum()``) repeating the top selected
    row — every returned index names a flagged row (or a duplicate of
    one, whose exact scores are equally valid), so the host may apply
    the whole selection unconditionally.  Returns ``(sel, n)`` with
    ``sel`` int32 of length ``bucket``.
    """
    import jax
    import jax.numpy as jnp

    ndm = score.shape[0]
    k = min(bucket, ndm)
    _, sel = jax.lax.top_k(jnp.where(mask, score, -jnp.inf), k)
    if bucket > k:
        sel = jnp.concatenate(
            [sel, jnp.broadcast_to(sel[:1], (bucket - k,))])
    n = mask.sum()
    return jnp.where(jnp.arange(bucket) < n, sel, sel[0]), n


def fused_need_stage(coarse, best_exact, rescored, cert_params, bucket2):
    """The guarantee loop's round-1 need mask, evaluated device-side.

    Mirrors :func:`hybrid_guarantee_loop`'s cert-based criterion exactly
    — including both consistency guards and the floor terms — against
    the seed stage's ``best_exact``.  ``coarse`` is the ``(6, ndm)``
    plan-grid score pack (row 2 the block S/N, row 5 the sliding
    certificate score); ``cert_params = (rho, slack, floor)`` arrives as
    a runtime array so one compiled program serves any bound/floor
    (``+inf`` disables the respective terms — see
    :func:`~.certify.fused_cert_params`).  Returns ``(sel2, n_need)``:
    the top-``bucket2`` flagged rows cert-descending (the rows hardest
    to rule out; overflow slots duplicate the top row) and the total
    flagged count.  Shared by the single-device and mesh fused kernels
    so the two programs can never drift from the host loop or from each
    other.
    """
    rho, slack, floor = cert_params[0], cert_params[1], cert_params[2]
    snr_c, cert = coarse[2], coarse[5]
    need = cert >= rho * best_exact - slack
    need |= snr_c >= best_exact          # consistency guard
    need |= cert >= rho * floor - slack  # floor contract
    need |= snr_c >= floor               # its consistency guard
    need &= ~rescored
    return fused_masked_topk(cert, need, bucket2)


def unpack_fused_hybrid(packed, ndm, bucket, bucket2):
    """Host-side inverse of the fused hybrid kernels' packed layout.

    ``[coarse (6*ndm) | sel (bucket) | exact (5*bucket) | n_seed (1) |
    sel2 (bucket2) | exact2 (5*bucket2) | n_need (1)]`` — the trailing
    four parts absent when ``bucket2 == 0`` (indices < 2^24 are exact in
    float32).  Returns ``(coarse, sel, seed_scores, n_seed, sel2,
    need_scores, n_need)`` with ``coarse`` float64 ``(6, ndm)``.
    """
    coarse = packed[:6 * ndm].reshape(6, ndm).astype(np.float64)
    pos = 6 * ndm
    sel = np.rint(packed[pos:pos + bucket]).astype(np.int64)
    pos += bucket
    seed_scores = packed[pos:pos + 5 * bucket].reshape(5, bucket)
    pos += 5 * bucket
    n_seed = int(np.rint(packed[pos]))
    pos += 1
    if not bucket2:
        return coarse, sel, seed_scores, n_seed, None, None, 0
    sel2 = np.rint(packed[pos:pos + bucket2]).astype(np.int64)
    pos += bucket2
    need_scores = packed[pos:pos + 5 * bucket2].reshape(5, bucket2)
    n_need = int(np.rint(packed[pos + 5 * bucket2]))
    return coarse, sel, seed_scores, n_seed, sel2, need_scores, n_need


def fused_scores_to_host(scores, roll_k, nsamples):
    """Float32 ``(5, n)`` score pack -> host column tuple
    ``(max, std, snr, window, peak)``, the rebase rotation undone on the
    peak index (shared by the fused hybrids' seed/need-stage unpacks)."""
    m, s, b, w, p = (scores[i].astype(np.float64) for i in range(5))
    w = np.rint(w).astype(np.int32)
    p = (np.rint(p).astype(np.int64) - roll_k) % nsamples
    return m, s, b, w, p


@functools.lru_cache(maxsize=8)
def _fused_hybrid_seed_kernel(nchan, start_freq, bandwidth, n_hi, t_run,
                              t_tile, n_lo, t_orig, max_off, ndm_plan,
                              bucket, bucket2=0, windows=None):
    """ONE jitted program for the hybrid's first round on TPU:

    FDMT coarse sweep -> plan-grid score mapping -> device-side top-k
    seed selection (+/-1 grid neighbours) -> exact Pallas rescore of the
    seed bucket -> (round 4) the guarantee loop's OWN cert-based need
    mask evaluated against the seed's best exact S/N, with the
    top-``bucket2`` flagged rows exactly rescored in the same program ->
    everything packed into a single flat float32 array.

    Collapses the device round trips (coarse readback, seed offsets
    upload [cached instead], rescore readbacks) into one dispatch + one
    readback — each trip is a host sync.  With
    the fused need stage a typical hit chunk's guarantee loop finds
    nothing left to rescore and the whole search is ONE round trip
    (VERDICT r3 #4).
    Packing layout: the shared fused-hybrid pack
    (:func:`unpack_fused_hybrid`); the ``n_seed`` slot is the constant
    ``bucket`` here (the top-k seed always fills its slots — the mesh
    kernel's mask-based seed is the variable-count case).  Coarse row 5
    is the sliding certificate score (:func:`cert_profile_scores`).

    The need mask mirrors :func:`hybrid_guarantee_loop`'s cert-based
    criterion exactly (including both consistency guards and the floor
    terms); ``cert_params = (rho_cert, slack, floor)`` arrives as a
    runtime array so one compiled program serves any bound/floor —
    ``rho_cert = +inf`` disables the cert terms (legacy-margin callers:
    the device then pre-rescores only rows whose DISPLAYED coarse score
    beats the seed best, a correct subset; the host loop backstops),
    ``floor = +inf`` disables the floor terms.
    """
    import jax
    import jax.numpy as jnp

    from .fdmt import _transform_fn
    from .pallas_dedisperse import dedisperse_plane_pallas_traced

    coarse_fn = _transform_fn(nchan, start_freq, bandwidth, n_hi, t_run,
                              t_tile, True, False, n_lo=n_lo,
                              with_scores=True, with_plane=False,
                              t_orig=t_orig, with_cert=True,
                              windows=windows)
    k = min(HYBRID_SEED_TOPK, ndm_plan)  # top_k requires k <= axis size

    @jax.jit
    def rescore_fused(data, idx_map, offsets_rebased, cert_params):
        stacked_f = coarse_fn(data)               # (6, ndm_fdmt)
        coarse = stacked_f[:, idx_map]            # (6, ndm_plan)
        _, top = jax.lax.top_k(coarse[2], k)
        sel = jnp.concatenate([top - 1, top, top + 1])
        sel = jnp.clip(sel, 0, ndm_plan - 1)
        sel = jnp.concatenate(
            [sel, jnp.broadcast_to(sel[:1], (bucket - 3 * k,))])
        offs = offsets_rebased[sel]               # (bucket, nchan) rows
        plane = dedisperse_plane_pallas_traced(data, offs, max_off,
                                               dm_block=bucket)
        exact = score_profiles_stacked(plane, xp=jnp,
                                       windows=windows)  # (5, bucket)
        parts = [coarse.reshape(-1), sel.astype(jnp.float32),
                 exact.reshape(-1),
                 jnp.full((1,), bucket, jnp.float32)]  # n_seed slot
        if bucket2:
            best_exact = exact[2].max()
            rescored = jnp.zeros(ndm_plan, bool).at[sel].set(True)
            # rescore the strongest flagged rows (fused_need_stage:
            # cert-descending — the rows hardest to rule out; overflow
            # slots duplicate the top flagged row).  The whole stage is
            # SKIPPED (lax.cond) when nothing is flagged — the common
            # bright-pulse case converges on the seed alone, and an
            # unconditional 32-row rescore measured 1069 -> 806 tr/s on
            # the benchmark (the host applies sel2 only when n_need > 0,
            # so the skip branch's zeros are never consumed).
            sel2, n_need = fused_need_stage(coarse, best_exact, rescored,
                                            cert_params, bucket2)

            def rescore2(rows):
                plane2 = dedisperse_plane_pallas_traced(
                    data, offsets_rebased[rows], max_off,
                    dm_block=bucket2)
                return score_profiles_stacked(plane2, xp=jnp,
                                              windows=windows)

            exact2 = jax.lax.cond(
                n_need > 0, rescore2,
                lambda _: jnp.zeros((5, bucket2), jnp.float32), sel2)
            parts += [sel2.astype(jnp.float32), exact2.reshape(-1),
                      n_need.astype(jnp.float32)[None]]
        return jnp.concatenate(parts)

    return rescore_fused


@functools.lru_cache(maxsize=4)
def _device_offsets_cache(offsets_bytes, shape):
    """Device-resident rebased-offset table, cached across searches.

    The 2 MB int32 table is deterministic in (geometry, trial grid,
    nsamples); re-uploading it per search is one more host->device
    transfer on the critical path.
    Keyed by the host bytes — the lru holds the device buffer alive.
    """
    import jax.numpy as jnp

    return jnp.asarray(
        np.frombuffer(offsets_bytes, dtype=np.int32).reshape(shape))


@functools.lru_cache(maxsize=16)
def _fused_rescore_kernel(max_off, dm_block, windows=None, roll_k=0):
    """One jitted program: Pallas dedisperse (un-rebased output) + score.

    The hybrid's exact-rescore hot path on TPU.  ``max_off`` is the
    *full* offset table's rebased bound — static and identical for every
    subset, so all guarantee-loop rounds (and warm/timed bench runs) hit
    one compiled program per row bucket.  The plane is scored WITHOUT
    undoing the rebase rotation: max/std/snr/window are
    rotation-invariant (the rebase constant is 128-aligned, a multiple
    of every boxcar width of the default ladder, so block sums are a
    rotation of the reference ones), and the peak index is corrected
    host-side (``(peak - roll_k) mod T``) — saving a full-plane roll pass
    and two dispatch round trips per call.  A ladder wider than the
    alignment would score other blocks than the reference's, so with one
    the rotation is undone on the device before scoring (``roll_k`` is
    then static) and the peak needs no correction.
    """
    import jax
    import jax.numpy as jnp

    from .pallas_dedisperse import dedisperse_plane_pallas_traced

    @jax.jit
    def rescore_rows(data, offs):
        plane = dedisperse_plane_pallas_traced(data, offs, max_off,
                                               dm_block=dm_block,
                                               roll_k=roll_k)
        return score_profiles_stacked(plane, xp=jnp, windows=windows)

    return rescore_rows


def _search_jax_hybrid(data, trial_dms, start_freq, bandwidth, sample_time,
                       capture_plane, dm_block, chan_block,
                       snr_floor=None, noise_certificate=True,
                       rho_cert=None, cert_slack=None, windows=None):
    """FDMT coarse sweep + exact rescore of the hit region.

    The throughput/exactness trade (VERDICT round 1): the FDMT computes
    every trial in O(nchan log nchan) passes but its tree-rounded tracks
    make scores approximate (within ~a trial of the exact kernels); the
    direct kernels are bit-exact-vs-NumPy but O(ndm * nchan).  This path
    delivers both at once:

    1. coarse-score ALL plan trials with the FDMT (each plan row takes
       the S/N of its nearest integer-band-delay FDMT row);
    2. exactly rescore — same offsets, same scorer, same summation order
       as the direct kernels — every row whose coarse estimate could be
       the global best;
    3. iterate with a margin bound derived from the *observed* coarse
       error on already-rescored rows until no unrescored row's coarse
       estimate reaches ``best_exact - margin``.  On exhaustion of the
       round budget, rescore everything still in question.

    Hit detection (``argbest`` row: DM, snr, rebin, peak) is therefore
    the exact kernel's — byte-equal to ``kernel="pallas"`` and matching
    ``backend="numpy"`` wherever the direct kernel does — at a cost of
    one FDMT pass plus a few dozen exact trials instead of the full
    O(ndm) sweep.  The returned table carries an ``exact`` bool column
    marking which rows hold exact scores.

    Cost note: the rescore count adapts to the data.  With a real
    candidate the loop converges in ~10-50 rows; on signal-free noise
    every trial's score is statistically equivalent, so pinning down the
    exact argbest correctly degenerates toward a full exact sweep — the
    hybrid is never *wrong*, just no faster than ``kernel="pallas"``
    when there is nothing to find in the chunk.

    ``snr_floor`` (opt-in): additionally rescore every row that could
    hold an above-floor detection (sliding certificate score within the
    per-config retention bound of the floor, :mod:`.certify`), making
    *all* above-threshold detections exact, not just the best — and,
    with ``noise_certificate`` (default on), enabling the noise
    certificate: when NO trial's certificate score reaches
    ``rho_cert * snr_floor - HYBRID_CERT_SLACK``, the chunk holds no
    impulsive signal detectable at the floor (sound under the stated
    signal model up to the Gaussian noise cross-term the slack absorbs
    — residual at-floor miss risk recorded in
    ``meta["cert_miss_p_at_floor"]``, see :mod:`.certify` *Miss risk*),
    the guarantee loop is skipped entirely, and the coarse table is
    returned with ``meta["certified"] = True`` (its rows are then
    coarse scores, NOT exact — the certificate's claim is strictly the
    absence of detections).  On survey data this is the difference between the
    hybrid degenerating to a full exact sweep on every signal-free
    chunk and paying one tree transform per such chunk.  Note the floor
    must sit at ``certify.certifiable_snr_floor`` (~12 at 1M-sample
    chunks) for the certificate to actually fire on typical noise;
    lower floors remain correct but uncertifiable — at T = 2^20 the
    reference's ``snr > 6`` floor (``clean.py:349``) is a mere 0.5
    above the noise max, and pinning down exactness that close to the
    noise genuinely costs a full sweep.

    ``capture_plane`` returns the *coarse* (FDMT) plane: the plane is a
    diagnostics product and the tree rows agree with the exact series up
    to track rounding and a small circular rotation (:mod:`.fdmt`).
    """
    import jax

    from .fdmt import _pick_fdmt_tile, fdmt_trial_dms

    ndm = len(trial_dms)
    tiled = time_tiles_of(data) > 1
    nchan, nsamples = data.shape if tiled else np.shape(data)
    if tiled and capture_plane:
        raise ValueError("a tier searched in time tiles has no plane to "
                         "capture")
    dmmin = float(np.min(trial_dms))
    dmmax = float(np.max(trial_dms))
    ladder = check_windows(windows)  # ``windows`` below: the rows' best
    # the exact kernels' rebase rotation is 128-aligned: blocks of a
    # wider window are no rotation of the reference's, so such a ladder
    # undoes the rotation on the device before it scores
    rotation_free = ladder[-1] <= REBASE_ALIGN

    use_fused = jax.default_backend() == "tpu" and not tiled
    # (the pad-free soundness guard — disabling certificate + cert-proof
    # on zero-padded TPU time axes — lives in hybrid_certificate_gate;
    # the streaming driver sizes chunks so the post-resample axis is a
    # tile multiple precisely so it never triggers there, and 50%
    # overlap re-contains edge pulses in the neighbouring chunk)
    if use_fused:
        import jax.numpy as jnp

        data32 = jnp.asarray(data, jnp.float32)

    def offsets_table():
        """``(rebased_full, roll_k, max_off)`` of the exact kernels,
        asked for where a rescore needs it: a call the certificate ends
        builds nothing and looks nothing up (:func:`_hybrid_offsets`)."""
        return _hybrid_offsets(trial_dms, nchan, start_freq, bandwidth,
                               sample_time, nsamples)

    # nearest coarse (integer band-delay) row for each plan row —
    # host-computable before any device work
    fdmt_dms, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, start_freq,
                                          bandwidth, sample_time)
    idx = nearest_rows(fdmt_dms, trial_dms)

    plane = None
    # the fused program earns its keep on wide sweeps; narrow grids
    # (fewer trials than the seed bucket) take the two-stage path, which
    # also avoids top_k k > ndm edge cases.  With a detection floor set
    # (streaming mode) the two-stage path is preferred even on TPU: a
    # noise-certified chunk then pays ONE coarse dispatch and readback —
    # the fused program would burn a full seed-bucket exact rescore on
    # every chunk the certificate is about to skip (the survey majority),
    # while a non-certified chunk only pays one extra round trip.
    from ..resilience import ladder as _ladder

    fused_seed = (use_fused and not capture_plane
                  and ndm >= 3 * HYBRID_SEED_TOPK
                  and _pick_fdmt_tile(nsamples) > 0
                  and rotation_free
                  and (snr_floor is None or not noise_certificate)
                  # OOM ladder "unfuse" rung (ISSUE 12): under memory
                  # pressure the one-dispatch program splits back into
                  # coarse + rescore (bit-identity already pinned)
                  and not _ladder.unfuse_engaged())
    if fused_seed:
        # 1+2 fused: coarse sweep, device-side top-k seed selection and
        # exact seed rescore in ONE dispatch + ONE packed readback (each
        # round trip is a host sync).  Requires the unpadded time
        # axis (a pad would shift the rescore's circular wrap off the
        # exact kernels' convention).
        bucket = HYBRID_SEED_BUCKET
        assert bucket >= 3 * HYBRID_SEED_TOPK
        bucket2 = min(HYBRID_NEED_BUCKET, ndm)
        t_tile = _pick_fdmt_tile(nsamples)
        # the need stage wants the retention bound BEFORE the dispatch;
        # same lru-cached computation the gate performs, so no extra
        # cost — rho_cert=False (cert opt-out) sends +inf, which
        # disables the device's cert terms (the consistency guards
        # still flag displayed-score beats).  fused_cert_params is the
        # one constructor of this operand, shared with the mesh kernel.
        from .certify import fused_cert_params

        cert_params = fused_cert_params(nchan, trial_dms, start_freq,
                                        bandwidth, sample_time, nsamples,
                                        snr_floor=snr_floor,
                                        rho_cert=rho_cert,
                                        cert_slack=cert_slack,
                                        windows=ladder)

        rebased_full, roll_k, max_off = offsets_table()
        kernel = _fused_hybrid_seed_kernel(
            nchan, float(start_freq), float(bandwidth), n_hi, nsamples,
            t_tile, n_lo, None, max_off, ndm, bucket, bucket2=bucket2,
            windows=ladder)
        offs_dev = _device_offsets_cache(rebased_full.tobytes(),
                                         rebased_full.shape)
        with budget_bucket("search/fused"):
            idx_dev = jnp.asarray(idx.astype(np.int32))
            cert_dev = jnp.asarray(cert_params)
            packed = np.asarray(kernel(data32, idx_dev, offs_dev, cert_dev))
            budget_count("dispatches")
            budget_count("readbacks")
        (coarse, sel, seed_scores, _, sel2, need_scores,
         n_need) = unpack_fused_hybrid(packed, ndm, bucket, bucket2)
        maxvalues, stds, snrs = coarse[0], coarse[1], coarse[2]
        windows = np.rint(coarse[3]).astype(np.int32)
        peaks = np.rint(coarse[4]).astype(np.int64)
        cert_scores = coarse[5]
    else:
        # two-stage path (CPU, plane capture, awkward time axes, or a
        # tier in time tiles): coarse sweep first, scores mapped host-side
        (_, c_max, c_std, c_snr, c_win, c_peak, plane,
         c_cert) = (_search_jax_fdmt_tiled(
            data, dmmin, dmmax, start_freq, bandwidth, sample_time,
            windows=ladder) if tiled else _search_jax_fdmt(
            data, dmmin, dmmax, start_freq, bandwidth, sample_time,
            capture_plane, with_cert=True, windows=ladder))
        if plane is not None and plane.shape[0] != ndm:
            # align the coarse plane with the plan grid (row gather —
            # cheap, and row-major on TPU unlike the scalarising lane
            # gather)
            plane = plane[idx]
        # the coarse score vectors come back from the device here — the
        # fused path's readback is bucketed above, and this two-stage
        # path must attribute the same trip (putpu-lint device-trip)
        with budget_bucket("search/coarse_readback"):
            maxvalues = np.asarray(c_max, np.float64)[idx]
            stds = np.asarray(c_std, np.float64)[idx]
            snrs = np.asarray(c_snr, np.float64)[idx]
            windows = np.asarray(c_win, np.int32)[idx]
            peaks = np.asarray(c_peak, np.int64)[idx]
            cert_scores = np.asarray(c_cert, np.float64)[idx]
            budget_count("readbacks")

    coarse_snrs = snrs.copy()
    exact = np.zeros(ndm, dtype=bool)

    def _apply(blk, scored):
        m, s, b, w, p = scored
        k = len(blk)
        maxvalues[blk] = m[:k]
        stds[blk] = s[:k]
        snrs[blk] = b[:k]
        windows[blk] = w[:k]
        peaks[blk] = p[:k]
        exact[blk] = True

    _rescore_kernel = {}

    def rescore_kernel():
        """ONE tuner resolution at the CHUNK geometry (full plan ndm),
        shared by every rescore bucket and resolved lazily on the first
        actual rescore (a certified chunk never pays it).  Passing
        ``kernel="auto"`` per bucket would tune independent
        (ndm=8/16/32) keys — repeated mid-loop synthetic-chunk
        measurements, and a bucket whose winner differed from its
        neighbour's would diverge at float level from the
        ``PUTPU_AUTOTUNE=off`` run.  The sharded hybrid pins its
        ``rescore_kernel`` for the same reason."""
        if "k" not in _rescore_kernel:
            from ..tuning.autotune import resolve_search_kernel

            _rescore_kernel["k"] = resolve_search_kernel(
                nchan, nsamples, ndm, None, False, start_freq, bandwidth,
                sample_time, trial_dms, dm_block=dm_block,
                chan_block=chan_block)
        return _rescore_kernel["k"]

    kept_tiles = {}

    def rescore_tiled(rows):
        """:func:`rescore` of a tier in time tiles: each tile is cleaned
        once more, ``roll_k`` early, every row bucket dedispersed on it
        and scored into partials, and the buckets' scores combined over
        the tiles.  As many cleaned tiles as the tile plan says fit
        (``data.keep``) are held for the guarantee loop's next rounds."""
        from .score_partials import combine_partials

        rebased_full, roll_k, max_off = offsets_table()
        if data.halo < max_off:
            raise ValueError(f"a halo of {data.halo} samples is short of "
                             f"the exact kernels' {max_off}")
        buckets = list(iter_rescore_buckets(rows))
        parts = [[] for _ in buckets]
        with budget_bucket("search/rescore"):
            for i in range(data.time_tiles):
                tile = kept_tiles.get(i)
                if tile is None:
                    tile = data.tile(i, shift=roll_k)
                    budget_count("dispatches")
                    if len(kept_tiles) < data.keep:
                        kept_tiles[i] = tile
                for part, (_, padded) in zip(parts, buckets):
                    run = _tiled_rescore_kernel(max_off, len(padded), ladder,
                                                data.own, nsamples)
                    part.append(np.asarray(
                        run(tile, np.asarray(rebased_full[padded]))))
                    budget_count("dispatches")
                    budget_count("readbacks")
                del tile
        for part, (blk, _) in zip(parts, buckets):
            _apply(blk, unstack_scores(combine_partials(
                part, data.own, ladder, nsamples)))

    def rescore(rows):
        """Exact scores for ``rows`` — fused Pallas+score program on TPU
        (one dispatch + one readback per bucketed call), the portable
        direct kernel elsewhere (whose own budget buckets attribute the
        dispatch/readback time; here only the call/row counters)."""
        budget_count("rescore_calls")
        budget_count("rescore_rows", len(rows))
        if tiled:
            return rescore_tiled(rows)
        if use_fused:
            rebased_full, roll_k, max_off = offsets_table()
        for blk, padded in iter_rescore_buckets(rows):
            if use_fused:
                run = _fused_rescore_kernel(
                    max_off, len(padded), ladder,
                    0 if rotation_free else roll_k)
                with budget_bucket("search/rescore"):
                    stacked = run(data32,
                                  jnp.asarray(rebased_full[padded]))
                    budget_count("dispatches")
                    m, s, b_, w, p = unstack_scores(stacked)
                    budget_count("readbacks")
                if rotation_free:
                    p = (p - roll_k) % nsamples  # undo the rebase rotation
                _apply(blk, (m, s, b_, w, p))
            else:
                m, s, b_, w, p, _ = _search_jax(
                    data, trial_dms[padded], start_freq, bandwidth,
                    sample_time, capture_plane=False, dm_block=dm_block,
                    chan_block=chan_block, dtype=None,
                    kernel=rescore_kernel(), windows=ladder)
                _apply(blk, (m, s, b_, w, p))

    # 2. seed (plausible-best rows + grid neighbours; the coarse grid
    # sits up to one trial off the plan) and 3. guarantee loop — shared
    # with the sharded hybrid (see hybrid_guarantee_loop).  An
    # unrescored row j can only beat the exact best if its coarse score
    # understated it (exact_j <= coarse_j + U, U the true max
    # underestimate), so the margin is one-sided: the overestimate side
    # (coarse > exact, typical of wing rows whose nearest coarse
    # neighbour is the peak) must NOT widen it.  U is estimated two
    # ways and the wider wins: adaptively (1.5x the worst underestimate
    # observed on rescored rows — a biased, peak-clustered sample) and
    # structurally (the HYBRID_COARSE_TRUST bound: tree track rounding
    # deviates <= ~2 samples/channel, Zackay & Ofek 2017 sec 2.3,
    # costing a boxcar-scored pulse at most ~1/sqrt(3) of its S/N).
    if fused_seed:
        # the device already rescored the top-k neighbourhood: unpack it
        # (kept even when certified — the scores are already computed and
        # exact rows are strictly more informative).  The need-stage
        # scores exist only when the device's mask flagged rows
        # (n_need > 0; the skipped branch emits zeros, never applied)
        blocks = [(sel, seed_scores)]
        if n_need > 0:
            blocks.append((sel2, need_scores))
        for rows, scores in blocks:
            _apply(rows, fused_scores_to_host(scores, roll_k, nsamples))
    # the cert-based criterion covers the snr_floor rows directly
    # (every row that could hold an above-floor detection is flagged
    # per-row), so no separate floor pre-pass is needed
    certified, rho_cert_min = hybrid_certificate_gate(
        cert_scores, coarse_snrs, snrs, exact, rescore, nchan=nchan,
        trial_dms=trial_dms, start_freq=start_freq, bandwidth=bandwidth,
        sample_time=sample_time, nsamples=nsamples, snr_floor=snr_floor,
        noise_certificate=noise_certificate, seed_done=fused_seed,
        rho_cert=rho_cert, cert_slack=cert_slack, windows=ladder)
    logger.debug("hybrid: %d/%d rows rescored exactly%s%s", exact.sum(), ndm,
                 f" (device need stage flagged {n_need})" if fused_seed
                 else "",
                 " (noise-certified)" if certified else "")

    return (maxvalues, stds, snrs, windows, peaks, exact, plane,
            cert_scores, certified, rho_cert_min)


# ---------------------------------------------------------------------------
# Public façade
# ---------------------------------------------------------------------------

def dedispersion_search(data, dmmin, dmmax, start_freq, bandwidth, sample_time,
                        show=False, *, backend="numpy", capture_plane=None,
                        trial_dms=None, dm_block=None, chan_block=None,
                        dtype=None, kernel="auto", snr_floor=None,
                        noise_certificate=True, rho_cert=None,
                        cert_slack=None, precision=None, windows=None):
    """Sweep trial DMs over ``data`` and score each dedispersed series.

    Parameters mirror the reference façade
    (``pulsarutils/dedispersion.py:205``); ``show=True`` additionally
    returns the dedispersed plane, like the reference's slow path (but
    computed by the same fast kernel — no duplicate implementation).

    Extra keyword-only parameters select and tune the execution backend:

    backend : ``"numpy"`` (reference semantics, float64, single core) or
        ``"jax"`` (jitted batched gather kernel; TPU/CPU).
    capture_plane : override for plane capture (defaults to ``show``).
        ``"memmap"`` spills the plane to a disk-backed ``.npy``
        (:func:`plane_memmap` — the reference's memmap behaviour,
        ``dedispersion.py:215-218``): host RAM holds one superblock at
        a time, so ``show=True``-class diagnostics work at any
        ``ndm x T``.  Requires the superblocked kernels —
        ``backend="numpy"`` or the Pallas path (``kernel="pallas"``, or
        ``"auto"``, which then resolves to Pallas even off-TPU); the
        fdmt/hybrid/fourier/gather kernels hold the full plane in
        device memory by construction and reject it.
    trial_dms : explicit trial grid; default is the reference plan
        (one trial per integer sample of band-crossing delay).
    dm_block, chan_block : JAX blocking factors (memory/speed trade-off).
    dtype : device dtype for the JAX path (default float32).
    snr_floor : ``kernel="hybrid"`` only — when set, every row that
        could hold an above-floor detection is exactly rescored (all
        above-threshold detections exact, not just the best), and the
        noise certificate becomes available; see
        :func:`_search_jax_hybrid`.
    noise_certificate : ``kernel="hybrid"`` with ``snr_floor`` only —
        allow the certified fast path on signal-free chunks (default
        on); the verdict lands in ``table.meta["certified"]``, with the
        certificate's operating assumptions (``cert_slack``,
        ``cert_miss_p_at_floor`` — see :mod:`.certify` *Miss risk*)
        alongside.
    rho_cert : ``kernel="hybrid"`` only — the per-config certificate
        retention bound.  ``None`` (default) computes it from the
        transform's merge tables; NOTE this is a multi-second host
        computation on the FIRST call at a multi-thousand-trial config
        (lru-cached per config afterwards, 32 entries).  Pass a
        precomputed ``certify.cert_retention(...).min()`` to move that
        cost off the hot path (one-shot calls at large configs,
        workloads cycling > 32 geometries), or ``False`` to skip the
        certificate machinery entirely (the guarantee loop then uses
        the legacy conservative margins — still exact-argbest, no
        certified fast path).
    cert_slack : ``kernel="hybrid"`` only — override the certificate
        slack (default :data:`~.certify.HYBRID_CERT_SLACK`).  Derive it
        from a target at-floor miss probability with
        :func:`~.certify.cert_slack_for_miss_p`; a larger slack
        tightens the miss risk at the cost of a higher
        :func:`~.certify.certifiable_snr_floor` and more rescoring.
        The value used is recorded in ``meta["cert_slack"]``.
    kernel : JAX-path kernel selector: ``"auto"`` (measured per-
        (backend, geometry) selection among the exact direct-sweep
        variants via the plan-level autotuner with a persistent tune
        cache — see :mod:`pulsarutils_tpu.tuning`; the static heuristic
        — Pallas on TPU, roll-scan on CPU, gather elsewhere — is the
        zero-measurement fallback and the ``PUTPU_AUTOTUNE=off`` escape
        hatch), ``"pallas"`` (hand-written tiled TPU kernel, see
        :mod:`.pallas_dedisperse`), ``"gather"`` (portable XLA
        ``take_along_axis`` formulation), ``"roll"`` (the roll-scan
        scan/roll-accumulate formulation — the measured CPU winner,
        14x over the scalarising CPU gather at the PR 1 rescore
        geometry), ``"fdmt"`` (tree dedispersion,
        O(nchan log nchan) instead of O(ndm * nchan) — fastest for dense
        DM sweeps; uses its own integer band-delay trial grid and tree-
        rounded tracks, so hits agree with the exact kernels to within a
        trial but not bit-identically; see :mod:`.fdmt`), ``"hybrid"``
        (FDMT coarse sweep + exact rescore of the hit region: exact hit
        detection on the plan grid at near-FDMT throughput; adds an
        ``exact`` bool column, see :func:`_search_jax_hybrid`) or
        ``"fourier"``
        (Fourier-domain dedispersion: exact *fractional*-sample delays —
        the precision option for narrow pulses at high time resolution;
        O(ndm * nchan * T) with transcendentals, see :mod:`.fourier`).
    precision : accumulation-precision policy for the gather/roll
        channel reductions (:mod:`pulsarutils_tpu.precision`):
        ``None``/``"f32"`` (the byte-identical default), a strategy
        name (``"f32_compensated"``, ``"split_f32"``,
        ``"bf16_operand_f32_accum"``), or ``"auto"`` — the measured
        (kernel, policy)-pair selection, where a non-default strategy
        only ever wins after the exact-hit-match equivalence harness
        passes at its documented error bound.  ``PUTPU_PRECISION``
        sets the default when the argument is omitted.
    windows : the scorer's boxcar ladder, a tuple continuing
        ``1, 2, 4, 8`` in doubling steps (:func:`boxcar_ladder`);
        ``None`` (default) is :data:`SEARCH_WINDOWS`.  Every backend and
        kernel takes it but ``"fourier"``; with ``kernel="hybrid"`` the
        certificate's capture and retention bound follow it.

    Returns
    -------
    :class:`~pulsarutils_tpu.utils.table.ResultTable` with columns
    ``DM, max, std, snr, rebin, peak`` (``peak`` = sample index of the
    best-window maximum — arrival time within the chunk) — plus the
    ``(ndm, nsamples)`` plane if ``show``/``capture_plane``.
    """
    from ..io.lowbit import PackedFrames

    if isinstance(data, PackedFrames):
        # packed low-bit input (ISSUE 11).  The traceable direct-sweep
        # formulations unpack INSIDE their jit (handled in _search_jax);
        # every other consumer gets the decode it can use while the
        # link still carries only the packed bytes: a cached device
        # unpack program for the jax tree/hybrid kernels, the C++/numpy
        # host decode for the reference backend.
        if backend == "numpy":
            data = data.to_host()
        elif kernel in ("fdmt", "hybrid"):
            data = data.to_device()

    if precision not in (None, "f32", "auto") and (
            backend != "jax" or kernel in ("fdmt", "hybrid")):
        raise ValueError("precision policies apply to the jax gather/roll "
                         f"channel reductions; got precision={precision!r} "
                         f"with backend={backend!r}, kernel={kernel!r}")

    nchan = data.shape[0]
    if capture_plane is None:
        capture_plane = bool(show)
    windows = check_windows(windows)

    if kernel == "fdmt":
        # the FDMT computes its own trial grid: the plan's one-sample
        # spacing snapped to integer band delays (the plan itself sits at
        # a fractional offset, so values/count can differ by one trial);
        # an explicit trial_dms only bounds the DM range.  dm_block /
        # chan_block do not apply to the tree transform.
        if backend != "jax":
            raise ValueError("kernel='fdmt' requires backend='jax'")
        if capture_plane == "memmap":
            raise ValueError("capture_plane='memmap' requires kernel="
                             "'pallas'/'auto' or backend='numpy' (the "
                             "tree transform is one whole-plane program)")
        import jax.numpy as _jnp

        if dtype not in (None, _jnp.float32):
            raise ValueError("kernel='fdmt' supports float32 only")
        if trial_dms is not None:
            dmmin = float(np.min(trial_dms))
            dmmax = float(np.max(trial_dms))
        (trial_dms, maxvalues, stds, best_snrs, best_windows, best_peaks,
         plane) = _search_jax_fdmt(data, dmmin, dmmax, start_freq,
                                   bandwidth, sample_time, capture_plane,
                                   windows=windows)
        table = ResultTable({
            "DM": trial_dms,
            "max": maxvalues,
            "std": stds,
            "snr": best_snrs,
            "rebin": best_windows,
            "peak": best_peaks,
        })
        return (table, plane) if (capture_plane or show) else table

    if trial_dms is None:
        with budget_bucket("search/plan"):
            trial_dms = dedispersion_plan(nchan, dmmin, dmmax, start_freq,
                                          bandwidth, sample_time)
    trial_dms = np.asarray(trial_dms, dtype=np.float64)

    if kernel == "hybrid":
        if backend != "jax":
            raise ValueError("kernel='hybrid' requires backend='jax'")
        if capture_plane == "memmap":
            raise ValueError("capture_plane='memmap' requires kernel="
                             "'pallas'/'auto' or backend='numpy' (the "
                             "hybrid's coarse plane is one whole-plane "
                             "program)")
        import jax.numpy as _jnp

        if dtype not in (None, _jnp.float32):
            raise ValueError("kernel='hybrid' supports float32 only")
        from .certify import cert_meta

        (maxvalues, stds, best_snrs, best_windows, best_peaks, exact,
         plane, cert_scores, certified,
         rho_out) = _search_jax_hybrid(data, trial_dms, start_freq,
                                       bandwidth, sample_time,
                                       capture_plane, dm_block,
                                       chan_block, snr_floor=snr_floor,
                                       noise_certificate=noise_certificate,
                                       rho_cert=rho_cert,
                                       cert_slack=cert_slack,
                                       windows=windows)
        table = ResultTable({
            "DM": trial_dms,
            "max": maxvalues,
            "std": stds,
            "snr": best_snrs,
            "rebin": best_windows,
            "peak": best_peaks,
            "exact": exact,
            "cert": cert_scores,
            # meta records the certificate's operating assumptions
            # wherever its verdict is (ADVICE r3): the slack is a
            # z-score against the Gaussian noise cross-term, not a hard
            # bound — see certify's *Miss risk* section
        }, meta=cert_meta(certified, rho_out, snr_floor, cert_slack))
        return (table, plane) if (capture_plane or show) else table

    if backend == "numpy":
        (maxvalues, stds, best_snrs, best_windows, best_peaks,
         plane) = _search_numpy(data, trial_dms, start_freq, bandwidth,
                                sample_time, capture_plane, windows)
    elif backend == "jax":
        (maxvalues, stds, best_snrs, best_windows, best_peaks,
         plane) = _search_jax(data, trial_dms, start_freq, bandwidth,
                              sample_time, capture_plane, dm_block,
                              chan_block, dtype, kernel,
                              precision=precision, windows=windows)
    else:
        raise ValueError(f"unknown backend {backend!r}")

    table = ResultTable({
        "DM": trial_dms,
        "max": maxvalues,
        "std": stds,
        "snr": best_snrs,
        "rebin": best_windows,
        "peak": best_peaks,
    })
    if capture_plane or show:
        return table, plane
    return table
