"""Per-config soundness bounds for the hybrid search: a computed lower
bound on how much of a real pulse's exact S/N the coarse (FDMT) sweep
retains (exact for the *deterministic* track scatter; the stochastic
noise cross-term is handled separately — see *Miss risk* below), and the
noise certificate built on it.

The hybrid search (:func:`~pulsarutils_tpu.ops.search._search_jax_hybrid`)
screens every trial with the tree transform and exactly rescores the rows
that could hold the best hit.  Both its stopping margin and its
noise-certificate fast path rest on ONE quantity: a lower bound on the
ratio ``coarse_snr / exact_snr`` for an impulsive signal.  Round 2 carried
that bound as a hand-set constant (``HYBRID_COARSE_TRUST = 0.45``, citing
the Zackay & Ofek 2017 §2.3 track-deviation argument); this module
*computes* it per search configuration, exactly, from the transform's own
merge tables:

1. :func:`~pulsarutils_tpu.ops.fdmt.fdmt_tracks` reconstructs the
   effective per-channel track of every coarse row — no data, no noise;
2. for each plan trial, the deviation of its mapped coarse row's track
   from the exact kernel's integer offsets gives the *exact* per-channel
   scatter a pulse's energy suffers in the coarse sweep;
3. the worst-case retention over pulse phase follows combinatorially from
   that scatter histogram and the scorer's block-boxcar geometry
   (widths 1, 2, 4, 8, non-sliding block sums — reference
   ``pulsarutils/dedispersion.py:190-196`` — or the longer ladder of
   ``--boxcar-max``, ``ops/search.py:boxcar_ladder``).

A ladder of any length (ISSUE 32)
---------------------------------
With the default ladder the exact score of a pulse wider than 8 samples
decays like the sliding capture's, and the certificate's bound has its
minimum at widths 1-3.  A longer ladder matches wide pulses at full
strength, so the certificate's capture grows with it — windows of every
scored level from 8 up at strides of half a window
(:func:`~pulsarutils_tpu.ops.search.cert_wide_windows`, the one set the
XLA scorer, the one-pass kernel and this module share) — and the bound
is minimised over every pulse width up to twice the ladder's widest
window (:func:`_cert_retention_from_offsets`: numerically to width 16, by
a closed form beyond).  The guarantee is the one it was: under the
signal model below, a pulse of any width up to the ladder's widest whose
exact score reaches the floor shows a certificate score of at least
``rho * floor - HYBRID_CERT_SLACK``.  At the HTRU plan the bound reads
0.5554 (0.5728 in the last tier) for the default ladder and for
``--boxcar-max 4096`` alike: the wide widths' minimum is 0.73
(``docs/hybrid_calibration.md`` has the table), so the ``certifiable``
floors do not move.

Signal model (stated, not hidden): the bound covers **impulsive signals**
— one coherent pulse per channel riding a dispersion track, width >=
``min_width`` samples, any alignment — which is the signal class the
search exists to find (and the same class the reference's own integer
rounding is analysed for).  Arbitrary adversarial inputs can defeat any
coarse screen; they can also defeat the reference's rounding.

Noise certificate
-----------------
For a detection floor ``s`` (the pipeline's ``snr > s`` hit criterion,
reference ``clean.py:349``), any pulse with exact S/N >= ``s`` must show
coarse S/N >= ``rho * s - HYBRID_CERT_SLACK``.  Contrapositive: when no
coarse row reaches that level, **no detectable pulse exists in the
chunk** and the costly exact-argbest localisation can be skipped
entirely — the chunk is certified signal-free at floor ``s``.  On survey
data (overwhelmingly noise) this converts the hybrid's worst case (the
degenerate full exact sweep on signal-free chunks, VERDICT r2) into its
best case: one coarse sweep per noise chunk.

A certified table does NOT carry an exact argbest (its best row holds
coarse scores); the certificate's claim is strictly about the absence of
detections above the floor.  A pure-noise fluctuation that would have
crossed the floor on the exact grid can be suppressed by the certificate
— that is a false alarm the exact pipeline would have flagged, not a
missed signal.

Miss risk (the honest fine print)
---------------------------------
The retention bound covers the *deterministic* part of the coarse score
exactly, but the coarse row also carries a stochastic cross-term: the
noise already sitting in the bins the pulse's scattered energy lands in.
In S/N units that cross-term is (sub-)Gaussian with standard deviation
<= 1 — the certificate's best capture window of width ``w`` holds ``w``
iid noise samples whose normalised sum has unit variance, and the
max-over-windows selection can only push the realised score *up* (see
:func:`cert_slack_for_miss_p` for the derivation).  The certificate
inequality absorbs it with the absolute allowance
:data:`HYBRID_CERT_SLACK`; the inequality is therefore **sound under the
stated impulsive-signal model up to this Gaussian cross-term**, not
adversarially absolute.  Quantitatively: a worst-case-phase,
worst-case-width pulse sitting *exactly* at the floor evades the
certificate with probability at most ``Phi(-slack)`` (~0.31 at the
default 0.5), decaying as ``Phi(-(slack + rho * (s - floor)))`` for a
pulse of exact S/N ``s`` — ``Phi(-1.1)`` ~ 14% one S/N unit above a
rho=0.6 floor via the deterministic surplus alone, ~2% three units
above, and far smaller at typical phases,
where the realised retention exceeds the worst-case ``rho`` by enough
to absorb several cross-term sigmas (empirically the cross-term never
exceeded ~0.3 across the seeded calibration sweeps).  Callers that need
a stated at-floor miss probability should pass
``cert_slack=cert_slack_for_miss_p(p)`` to ``dedispersion_search`` /
``sharded_hybrid_search``; the operating assumption is recorded in
``table.meta`` (``cert_slack``, ``cert_miss_p_at_floor``) wherever
``certified`` is reported.

Detection floors at long chunks
-------------------------------
The reference's ``snr > 6`` criterion was tuned for its physics-sized
chunks (a few thousand samples, noise max ~ 4).  At this framework's
million-sample device-resident chunks the expected signal-free maximum is
~ 5.3-5.6, so a fixed 6.0 floor false-alarms on a few percent of pure
noise chunks *regardless of kernel* — and sits too close to the noise for
the certificate to clear it.  :func:`expected_noise_max_snr` /
:func:`matched_snr_floor` compute the statistically matched floor for a
given chunk geometry (the same false-alarm philosophy as the reference's
6, adapted to the chunk size).
"""

from __future__ import annotations

import functools

import numpy as np

from ..obs.trace import build_span_name, span


def _windows(windows=None):
    """The detection scorer's boxcar widths — imported lazily from the
    single source of truth so the bounds can never silently diverge
    from the scorer.  ``None`` is the default ladder; a longer one is
    checked by the scorer's own rule."""
    from .search import check_windows

    return check_windows(windows)

#: absolute S/N slack in the certificate inequality
#: ``coarse >= rho * exact - HYBRID_CERT_SLACK``: the allowance for the
#: stochastic noise cross-term (the pulse's scattered energy interacting
#: with the noise already in its bins) and sub-sample pulse phase.  The
#: cross-term is Gaussian-tailed with sd <= 1 in S/N units, so this
#: value IS a z-score, not a hard bound: an at-floor worst-case-phase
#: pulse evades the certificate with probability up to ``Phi(-slack)``
#: (~0.31 at 0.5) — see the module docstring's *Miss risk* section and
#: :func:`cert_slack_for_miss_p` to derive the slack from a target miss
#: probability instead.  The 0.5 default is an empirically supported
#: operating point (worst observed cross-term ~< 0.3 over hundreds of
#: seeded draws in ``tests/test_certify.py``/``tools/hybrid_calibrate.py``
#: — typical-phase retention surplus absorbs the dips), chosen to keep
#: ``certifiable_snr_floor`` low; it is NOT a proof.
HYBRID_CERT_SLACK = 0.5

#: upper bound on the certificate noise cross-term's standard deviation
#: in S/N units (see :func:`cert_slack_for_miss_p` for the argument)
CERT_CROSS_TERM_SD = 1.0


def cert_slack_for_miss_p(miss_p):
    """Certificate slack achieving an at-floor miss probability <= ``miss_p``.

    Derivation: write the coarse row's certificate score for a pulse of
    exact S/N ``s`` as ``cert = rho_realised * s + Z`` where
    ``rho_realised >= rho`` (the computed deterministic retention bound)
    and ``Z`` is the noise already in the certificate's best capture
    window.  For a width-``w`` sliding window, ``Z`` is a sum of ``w``
    iid unit-variance noise samples divided by ``std * sqrt(w)`` — unit
    variance; taking the max over windows and alignments only *raises*
    the realised score, so ``P(cert < rho * s - slack) <=
    P(Z < -slack) = Phi(-slack / CERT_CROSS_TERM_SD)``.  Hence
    ``slack = CERT_CROSS_TERM_SD * Phi^{-1}(1 - miss_p)`` guarantees an
    at-floor miss probability <= ``miss_p`` *for the worst-case phase
    and width*; pulses above the floor gain ``rho * (s - floor)`` extra
    margin on top.

    Note the cost: a 1e-3 target needs slack ~3.1, which raises
    :func:`certifiable_snr_floor` by ``(3.1 - 0.5) / rho`` (~4.3 S/N at
    rho = 0.6) over the default operating point — the price of a stated
    guarantee instead of an empirical allowance.
    """
    from statistics import NormalDist

    if not 0.0 < miss_p < 1.0:
        raise ValueError(f"miss_p={miss_p!r}: expected a probability in "
                         "(0, 1)")
    return CERT_CROSS_TERM_SD * NormalDist().inv_cdf(1.0 - float(miss_p))


def cert_miss_p_at_floor(slack=None):
    """At-floor worst-case miss probability implied by ``slack``
    (``Phi(-slack / CERT_CROSS_TERM_SD)``, the inverse of
    :func:`cert_slack_for_miss_p`) — the residual-risk number recorded
    in ``table.meta`` alongside ``certified``."""
    from statistics import NormalDist

    if slack is None:
        slack = HYBRID_CERT_SLACK
    return NormalDist().cdf(-float(slack) / CERT_CROSS_TERM_SD)


def cert_meta(certified, rho_cert, snr_floor, cert_slack=None):
    """The hybrid searches' certificate block of ``table.meta`` — ONE
    place constructs it so the single-device and sharded hybrids (whose
    docstrings promise an identical contract) can never drift.

    ``cert_miss_p_at_floor`` is recorded only when there was actually a
    floor for the number to refer to (``snr_floor`` set and the bound
    computed); ``cert_slack`` is always recorded — the skip criterion
    uses it even on floorless runs.
    """
    slack_used = (HYBRID_CERT_SLACK if cert_slack is None
                  else float(cert_slack))
    return {"certified": certified, "rho_cert": rho_cert,
            "snr_floor": snr_floor, "cert_slack": slack_used,
            "cert_miss_p_at_floor": (
                round(cert_miss_p_at_floor(slack_used), 4)
                if rho_cert is not None and snr_floor is not None
                else None)}


def _retention_from_offsets(offsets, weights=None, min_width=1):
    """Worst-case coarse/exact S/N ratio given per-channel track offsets.

    ``offsets`` is the signed per-channel deviation (samples) of the
    coarse track from the exact track for one trial.  A width-``W`` pulse
    (amplitude spread uniformly over ``W`` samples per channel) that the
    exact kernel sees as a clean ``W``-sample box becomes, in the coarse
    row, the box convolved with the offset histogram.  Both series are
    scored identically (block sums of widths 1/2/4/8, ``max/std``), so
    the retention at pulse phase ``p`` is the ratio of the best
    block-capture of the scattered mass to the best block-capture of the
    clean box; the bound takes the worst phase.  Noise std is identical
    in both series (each channel contributes exactly one sample per bin
    in either kernel), so S/N ratio == capture ratio.
    """
    offsets = np.asarray(offsets, dtype=np.int64)
    offsets = offsets - offsets.min()
    if weights is None:
        weights = np.full(offsets.shape, 1.0 / len(offsets))
    span = int(offsets.max()) + 1
    h = np.zeros(span)
    np.add.at(h, offsets, weights)
    h /= h.sum()
    w_pulse = int(min_width)
    # mass distributions over absolute bins, pulse starting at phase p:
    # exact = box of width W at [p, p+W); coarse = same box convolved
    # with h -> support [p, p + W + span - 1)
    box = np.full(w_pulse, 1.0 / w_pulse)
    coarse_mass = np.convolve(h, box)
    worst = np.inf
    for p in range(8):  # lcm of the window widths
        def best_score(mass):
            best = 0.0
            for w in _windows():
                bins = p + np.arange(len(mass))
                blocks = bins // w
                cap = np.zeros(blocks[-1] + 1)
                np.add.at(cap, blocks, mass)
                best = max(best, cap.max() / np.sqrt(w))
            return best

        exact_score = best_score(box)
        coarse_score = best_score(coarse_mass)
        worst = min(worst, coarse_score / exact_score)
    return float(worst)


@functools.lru_cache(maxsize=256)
def _exact_best_phase(width, windows=None):
    """Best block-boxcar score of a clean width-``width`` box (in total-
    mass units), over all windows AND phases — the soundness-relevant
    denominator of the certificate ratio.  The best phase starts the box
    on a boundary of every block: a window ``w`` then captures
    ``min(w, width)`` of its ``width`` equal parts, and no window of
    that width captures more at any phase.  Depends on ``width`` and
    the ladder alone, so it is memoised (the bound asks for each width
    once a tier, the tests and tools once a trial)."""
    parts = np.cumsum(np.full(width, 1.0 / width))
    return max(parts[min(w, width) - 1] / np.sqrt(w)
               for w in _windows(windows))


def _wide_capture_worst_phase(mass, wide):
    """Score of the half-stride captures (``search.cert_wide_windows``)
    on ``mass`` at the pulse phase that serves them worst; ``mass`` is
    one trial's ``(n,)`` or, for trials whose mass spans the same ``n``
    bins, ``(trials, n)``.

    A window of width ``w`` starts at every multiple of ``w / 2``.  One
    at least twice as long as the mass holds all of it whatever the
    phase; a shorter one holds, at phase ``p``, the best of the sliding
    sums whose start is ``-p`` modulo ``w / 2``.  The phases are those
    of the widest such window's stride, which every narrower stride
    divides.
    """
    mass = np.asarray(mass, dtype=np.float64)
    n = mass.shape[-1]
    total = mass.sum(axis=-1)
    whole = [w for w in wide if w >= 2 * n]
    floor = total / np.sqrt(whole[0]) if whole else np.zeros_like(total)
    partial = [w for w in wide if w < 2 * n]
    if not partial:
        return floor
    csum = np.concatenate([np.zeros(mass.shape[:-1] + (1,)),
                           np.cumsum(mass, axis=-1)], axis=-1)
    phases = np.arange(partial[-1] // 2)
    scores = np.asarray(floor)[..., None]
    for w in partial:
        half = w // 2
        # whole strides of starts from -w on: the sums of one residue
        # modulo the stride are a column, and the windows added at either
        # end (before -(w - 1), from n on) hold nothing
        strides = -(-(n + w) // half)
        start = np.arange(-w, -w + strides * half)
        sums = (csum[..., np.clip(start + w, 0, n)]
                - csum[..., np.clip(start, 0, n)])
        best = sums.reshape(sums.shape[:-1] + (strides, half)).max(axis=-2)
        scores = np.maximum(scores, best[..., (-phases) % half] / np.sqrt(w))
    return scores.min(axis=-1)


@functools.lru_cache(maxsize=32)
def _wide_retention_table(windows, wide, first_width):
    """What the closed-form bound of :func:`_cert_retention_from_offsets`
    needs for the pulse widths ``first_width .. 2 x windows[-1]``, per
    width ``W`` (rows) and capture window ``w`` (columns): the capture's
    guaranteed score ``cap / sqrt(w)``, the score one sample of mean
    track deviation costs it, ``1 / (W sqrt(w))``, and the exact
    ladder's best-phase score of the box.

    ``cap`` is the share of a width-``W`` box that SOME window of width
    ``w`` holds at every phase: all of it from ``w >= 2W`` (starts every
    ``w / 2``), ``w / W`` where a window fits inside the box at any
    phase (``3w <= 2W``), and in between ``1/2 + w / (4W)`` — the two
    windows that straddle the box hold ``W - u`` and ``u + w / 2`` of
    its samples for an offset ``u``, and the larger of the two is least
    where they are equal.  The sliding :data:`~.search.CERT_WINDOWS`
    hold ``min(w, W) / W`` at any phase.
    """
    from .search import CERT_WINDOWS

    widths = np.arange(first_width, 2 * windows[-1] + 1, dtype=np.float64)
    W = widths[:, None]
    w = np.asarray(wide, dtype=np.float64)[None, :]
    cap = np.where(w >= 2 * W, 1.0,
                   np.where(3 * w <= 2 * W, w / W, 0.5 + w / (4 * W)))
    ws = np.asarray(CERT_WINDOWS, dtype=np.float64)[None, :]
    cap = np.concatenate([cap, np.minimum(ws, W) / W], axis=1)
    w_all = np.concatenate([w, ws], axis=1)
    ladder = np.asarray(windows, dtype=np.float64)[None, :]
    exact = (np.minimum(ladder, W) / W / np.sqrt(ladder)).max(axis=1)
    table = (cap / np.sqrt(w_all), 1.0 / (W * np.sqrt(w_all)), exact)
    for arr in table:  # shared cache objects: fail loudly
        arr.setflags(write=False)
    return table


def _cert_retention_from_offsets(offsets, max_width=16, windows=None,
                                 wide=()):
    """Worst-case ``cert_score / exact_snr`` ratio for one trial's track
    (``offsets`` of ``(nchan,)``: a float), or for each of a tier's
    trials at once (``(trials, nchan)``: an array, trial by trial the
    same numbers; :func:`_cert_retention_from_histograms` is the
    arithmetic of both).

    The denominator is the exact kernel's best detection score of the
    pulse over the ladder ``windows``, taken at the pulse's *best* phase
    (the soundness-relevant worst case: the exact sweep scoring the
    pulse as well as it possibly can while the coarse row still must
    flag it).  The numerator is the certificate's capture
    (:func:`~pulsarutils_tpu.ops.search.cert_profile_scores`) of the
    same pulse scattered by the track's deviations: the *sliding*
    window-2/3/4 capture — phase invariant, so no worst-phase
    minimisation applies to it — and, for a ladder longer than the
    default, the half-stride captures ``wide``
    (:func:`~pulsarutils_tpu.ops.search.cert_wide_windows`) at the phase
    that serves them worst.

    **The default ladder** (no ``wide``): minimised over pulse widths
    1..``max_width``.  Beyond the scorer's largest block (8) both sides
    decay ~1/W and the ratio tends to a constant ~0.7, so the minimum
    sits at small widths.

    **A longer ladder**: the exact score no longer decays beyond 8, and
    a sliding capture of at most 4 samples alone would keep ``2 /
    sqrt(W)`` of a width-``W`` pulse (0.09 at 512); the half-stride
    captures are what keeps it bounded.  Minimised over every pulse
    width the ladder can match and twice beyond, ``1 .. 2 x
    windows[-1]``: widths up to ``max_width`` numerically from the
    track's own scatter as above; wider ones from the closed form of
    :func:`_wide_retention_table`, which gives every capture window the
    share of a clean box it holds at ANY phase, less what the scatter
    can cost it.  A box of ``W`` equal parts moved by one sample changes
    any window's share by at most ``1 / W``, and the scattered pulse is
    a mixture of such boxes, so a window holds at least its clean share
    less ``D / W``, ``D`` the mean absolute deviation of the track about
    its median.  Without scatter the closed form's minimum is 0.75, at
    widths that are powers of two (the box straddles two windows of its
    own width and the next level holds all of it at ``1/sqrt(2)``);
    with the tree's scatter (``D`` about a sample) the overall minimum
    still sits at widths 1-3, where it sat before.
    """
    offsets = np.asarray(offsets)
    rho = _cert_retention_from_histograms(
        _offset_histograms([np.atleast_2d(offsets)]), max_width=max_width,
        windows=windows, wide=wide)
    return float(rho[0]) if offsets.ndim == 1 else rho


def _cert_retention_from_histograms(hist, max_width=16, windows=None,
                                    wide=()):
    """:func:`_cert_retention_from_offsets` of every trial at once, from
    the ``(trials, span)`` histograms of their offsets
    (:func:`_offset_histograms`): each step of that bound as arithmetic
    over the trials' axis.  Returns ``(trials,)``."""
    from .search import CERT_WINDOWS

    spans = hist.shape[1] - (hist[:, ::-1] > 0).argmax(axis=1)
    nchan = int(hist[0].sum())
    # a bin's mass is 1 / nchan added once a channel, as np.add.at adds it
    bin_mass = np.concatenate([[0.0],
                               np.cumsum(np.full(nchan, 1.0 / nchan))])

    ladder = _windows(windows)
    if wide:
        max_width = min(max_width, 2 * ladder[-1])
    worst = np.full(len(hist), np.inf)
    # trials whose scatter spans as many bins share every shape below
    # (and _wide_capture_worst_phase's split of the windows)
    for span in np.unique(spans):
        rows = np.flatnonzero(spans == span)
        h = bin_mass[hist[rows, :span]]
        low = np.full(len(rows), np.inf)
        for width in range(1, max_width + 1):
            n = span + width - 1
            mass = np.zeros((len(rows), n))  # h convolved with the box
            part = h * (1.0 / width)
            for k in range(span):
                mass[:, k:k + width] += part[:, k:k + 1]
            cert = np.zeros(len(rows))
            for w in CERT_WINDOWS:  # sliding: the best w adjacent bins
                starts = max(n - w, 0) + 1
                held = mass[:, :starts].copy()
                for k in range(1, min(w, n)):
                    held += mass[:, k:k + starts]
                cert = np.maximum(cert, held.max(axis=1) / np.sqrt(w))
            if wide:
                cert = np.maximum(cert,
                                  _wide_capture_worst_phase(mass, wide))
            low = np.minimum(low, cert / _exact_best_phase(width, windows))
        worst[rows] = low
    if wide and max_width < 2 * ladder[-1]:
        score, per_sample, exact = _wide_retention_table(
            ladder, tuple(wide), max_width + 1)
        # the closed form is a function of the deviation alone: once a
        # distinct value, a block at a time ((values, widths, windows))
        deviation, trial = np.unique(_mean_abs_deviation(hist),
                                     return_inverse=True)
        closed = np.empty(len(deviation))
        block = max(1, (1 << 22) // score.size)
        for lo in range(0, len(deviation), block):
            d = deviation[lo:lo + block, None, None]
            cert = (score - d * per_sample).max(axis=2)
            closed[lo:lo + block] = (cert / exact).min(axis=1)
        worst = np.minimum(worst, closed[trial])
    return worst


def _offset_histograms(blocks):
    """``(trials, span)`` counts of each trial's offsets above its
    smallest, ``span`` the widest trial's; ``blocks`` yields the trials'
    ``(rows, nchan)`` offsets some rows at a time."""
    hists = []
    for offsets in blocks:
        offsets = offsets - offsets.min(axis=1, keepdims=True)
        span = int(offsets.max()) + 1
        rows = np.arange(len(offsets), dtype=np.int64)[:, None]
        hists.append(np.bincount(
            (rows * span + offsets).ravel(),
            minlength=len(offsets) * span).reshape(-1, span))
    span = max(h.shape[1] for h in hists)
    return np.concatenate([np.pad(h, ((0, 0), (0, span - h.shape[1])))
                           for h in hists])


def _mean_abs_deviation(hist):
    """Mean absolute deviation about the median (``np.median``'s: the
    mean of the two middle values) of each trial's offsets, from their
    histogram."""
    n = int(hist[0].sum())
    below = np.cumsum(hist, axis=1)
    # the i-th smallest offset is the first bin whose running count passes i
    median = ((below <= (n - 1) // 2).sum(axis=1)
              + (below <= n // 2).sum(axis=1)) / 2.0
    return (hist * np.abs(np.arange(hist.shape[1]) - median[:, None])
            ).sum(axis=1) / n


def _track_deviations(nchan, trial_dms, start_freq, bandwidth, sample_time,
                      nsamples):
    """Signed per-channel deviation of each plan trial's mapped coarse
    row from the exact kernel's integer offsets: the rows of ``(ndm,
    nchan)`` in trial order, about 2^20 elements at a time (the float64
    shift arithmetic then works in buffers the allocator hands back)."""
    from .fdmt import fdmt_plan, fdmt_tracks, fdmt_trial_dms
    from .plan import dedispersion_shifts_batch, normalize_shifts
    from .search import nearest_rows

    trial_dms = np.asarray(trial_dms, dtype=np.float64)
    fdmt_dms, n_lo, n_hi = fdmt_trial_dms(
        nchan, float(trial_dms.min()), float(trial_dms.max()), start_freq,
        bandwidth, sample_time)
    plan = fdmt_plan(nchan, float(start_freq), float(bandwidth), n_hi, n_lo)
    # a band delay and a chunk's sample index, with room to add two
    dtype = np.int32 if max(n_hi, nsamples) < 2 ** 30 else np.int64
    tracks = fdmt_tracks(plan, dtype)
    idx = nearest_rows(fdmt_dms, trial_dms)
    period, half = dtype(nsamples), dtype(nsamples // 2)
    block = max(1, (1 << 20) // nchan)
    for lo in range(0, len(trial_dms), block):
        shifts = dedispersion_shifts_batch(
            trial_dms[lo:lo + block], nchan, start_freq, bandwidth,
            sample_time)
        exact = normalize_shifts(shifts, nsamples).astype(dtype, copy=False)
        dev = tracks[idx[lo:lo + block], :nchan] % period - exact
        # wrap to signed: a track and an offset that agree mod T are the
        # same gather; centre the deviation on the dominant branch
        yield (dev + half) % period - half


@functools.lru_cache(maxsize=32)
def _retention_cached(nchan, dms_key, start_freq, bandwidth, sample_time,
                      nsamples, min_width, cert, windows=None):
    trial_dms = np.frombuffer(dms_key, dtype=np.float64)
    # host work of a process's first call with this geometry: array
    # arithmetic over the tier's trials at once (a Python loop over them
    # until PR 45: 57 s of MeerTRAP's cold pass, PERF.md PR 38)
    with span(build_span_name("plan", "cert_retention"), nchan=nchan,
              trials=len(trial_dms), t=nsamples):
        blocks = _track_deviations(nchan, trial_dms, start_freq, bandwidth,
                                   sample_time, nsamples)
        if not cert:
            return np.asarray([
                _retention_from_offsets(d, min_width=min_width)
                for dev in blocks for d in dev])
        hist = _offset_histograms(blocks)
        if windows is None:
            return _cert_retention_from_histograms(hist)
        from ..utils.logging_utils import budget_bucket
        from .search import cert_wide_windows

        # the host's share of a longer ladder: its captures at the
        # worst phase and the closed form beyond max_width
        with budget_bucket("search/cert_wide"):
            return _cert_retention_from_histograms(
                hist, windows=windows,
                wide=cert_wide_windows(windows, nsamples))


def coarse_retention(nchan, trial_dms, start_freq, bandwidth, sample_time,
                     nsamples, min_width=1):
    """Per-trial worst-case ``coarse_snr / exact_snr`` retention (block
    detection scorer on both sides).

    Computed exactly from the transform's merge tables (no data, no
    noise); see the module docstring for the signal model.  ``min_width``
    is the narrowest pulse width (samples) the bound must cover — wider
    pulses always retain more, so 1 is fully conservative.  This is the
    quantity that justifies (and per-config recalibrates)
    ``search.HYBRID_COARSE_TRUST``.

    Returns a ``(ndm,)`` float array in ``(0, 1]``.
    """
    trial_dms = np.ascontiguousarray(trial_dms, dtype=np.float64)
    return _retention_cached(int(nchan), trial_dms.tobytes(),
                             float(start_freq), float(bandwidth),
                             float(sample_time), int(nsamples),
                             int(min_width), False)


def cert_retention(nchan, trial_dms, start_freq, bandwidth, sample_time,
                   nsamples, windows=None):
    """Per-trial worst-case ``cert_score / exact_snr`` retention (the
    sliding certificate scorer as numerator — phase-invariant, so much
    tighter than :func:`coarse_retention` at the same track scatter:
    ~0.6 vs ~0.44 at the benchmark config).  ``windows`` is the
    detection ladder (``None``: the default four): the exact score in
    the denominator, the half-stride captures in the numerator and the
    pulse widths minimised over all follow it, cut off for ``nsamples``
    as the scorer is.  Returns ``(ndm,)``."""
    from .search import SEARCH_WINDOWS, scored_windows

    trial_dms = np.ascontiguousarray(trial_dms, dtype=np.float64)
    ladder = scored_windows(windows, nsamples)
    return _retention_cached(int(nchan), trial_dms.tobytes(),
                             float(start_freq), float(bandwidth),
                             float(sample_time), int(nsamples), 1, True,
                             None if ladder == SEARCH_WINDOWS else ladder)


def retention_bound(nchan, trial_dms, start_freq, bandwidth, sample_time,
                    nsamples, min_width=1, cert=False, windows=None):
    """``min`` over trials of :func:`coarse_retention` (or
    :func:`cert_retention` with ``cert=True``, for the ladder
    ``windows``) — the single per-config constant the hybrid's margin
    and certificate use."""
    fn = (functools.partial(cert_retention, windows=windows) if cert
          else functools.partial(coarse_retention, min_width=min_width))
    return float(fn(nchan, trial_dms, start_freq, bandwidth, sample_time,
                    nsamples).min())


def fused_cert_params(nchan, trial_dms, start_freq, bandwidth, sample_time,
                      nsamples, snr_floor=None, rho_cert=None,
                      cert_slack=None, windows=None):
    """The ``(rho, slack, floor)`` float32 runtime operand of the fused
    hybrid programs — ONE place constructs it so the single-device
    (``ops/search.py:_fused_hybrid_seed_kernel``) and mesh
    (``parallel/sharded_fdmt.py``) fused kernels share the need stage's
    contract: ``rho = +inf`` disables the device's cert terms (the
    consistency guards still fire), ``floor = +inf`` disables the floor
    terms.  ``rho_cert=None`` computes the retention bound — the same
    lru-cached computation :func:`~..ops.search.hybrid_certificate_gate`
    performs, under the same ``search/cert_floor`` budget bucket so a
    cache miss cannot hide inside the fused dispatch.
    """
    from ..utils.logging_utils import budget_bucket

    if rho_cert is False:
        rho_val = np.inf
    elif rho_cert is not None:
        rho_val = float(rho_cert)
    else:
        with budget_bucket("search/cert_floor"):
            rho_val = retention_bound(nchan, trial_dms, start_freq,
                                      bandwidth, sample_time, nsamples,
                                      cert=True, windows=windows)
    slack_val = (HYBRID_CERT_SLACK if cert_slack is None
                 else float(cert_slack))
    floor_val = np.inf if snr_floor is None else float(snr_floor)
    return np.asarray([rho_val, slack_val, floor_val], np.float32)


def certify_noise_only(cert_scores, snr_floor, rho_cert_min,
                       coarse_snrs=None, slack=None):
    """True iff the coarse sweep certifies no pulse reaches ``snr_floor``
    (under the stated impulsive-signal model, up to the Gaussian noise
    cross-term the ``slack`` absorbs — see the module docstring's *Miss
    risk* section for the residual probability).

    The certificate inequality: an impulsive signal with exact S/N ``s``
    shows a sliding certificate score ``>= rho_cert_min * s - slack``
    (up to the cross-term); when every trial's certificate score sits
    below ``rho_cert_min * snr_floor - slack``, no trial's exact S/N
    reaches the floor.  ``slack`` defaults to :data:`HYBRID_CERT_SLACK`;
    derive it from a target miss probability with
    :func:`cert_slack_for_miss_p`.

    ``coarse_snrs`` (the block detection scores), when given, add a
    consistency guard: a chunk whose coarse BLOCK score already reaches
    the floor is never certified, whatever the sliding scores say.  For
    impulsive signals the sliding capture dominates and the guard is
    redundant; for non-impulsive junk (e.g. a single-sample spike
    flanked by negative dips after aggressive RFI filtering — outside
    the signal model) it prevents the absurd state of a chunk counted
    signal-free while its own table shows an above-floor score.
    """
    if snr_floor is None:
        return False
    if slack is None:
        slack = HYBRID_CERT_SLACK
    threshold = rho_cert_min * float(snr_floor) - float(slack)
    ok = bool(np.max(cert_scores) < threshold)
    if ok and coarse_snrs is not None:
        ok = bool(np.max(coarse_snrs) < float(snr_floor))
    return ok


def certifiable_snr_floor(nsamples, ndm, rho_cert_min, margin=0.75,
                          slack=None):
    """The smallest detection floor whose noise certificate actually
    fires on typical signal-free chunks of this geometry.

    The certificate threshold ``rho * floor - slack`` must clear the
    chunk's expected signal-free certificate-score maximum (plus
    ``margin`` Gumbel spread); below this floor the certificate is still
    *valid* but never triggers, and the hybrid pays the full
    exact-argbest localisation on every chunk.  ``slack`` defaults to
    :data:`HYBRID_CERT_SLACK`; a slack derived from a stricter miss
    probability (:func:`cert_slack_for_miss_p`) raises the floor
    proportionally.
    """
    if slack is None:
        slack = HYBRID_CERT_SLACK
    ceiling = expected_noise_max_snr(nsamples, ndm) + float(margin)
    return (ceiling + float(slack)) / float(rho_cert_min)


# ---------------------------------------------------------------------------
# Matched detection floors for long chunks
# ---------------------------------------------------------------------------

def expected_noise_max_snr(nsamples, ndm=1):
    """Expected maximum certificate score of a signal-free chunk.

    Gumbel location for an effective count ``m = 6 * nsamples * ndm``.
    The multiplier was FIT to seeded half-normal-noise simulation of the
    full hybrid coarse+cert scorer; it bundles the sliding-window
    multiplicity, the boxcar family, and the noise skew.  The Gumbel
    scale is ``1 / sqrt(2 ln m)`` (~0.15-0.19 at these sizes), so
    chunk-to-chunk maxima spread by a few tenths.

    FIT DOMAIN (extrapolate with care): half-normal iid noise after the
    pipeline's renormalisation, T = 4k-32k, ndm ~ 60-300 (original fit
    T = 8k/16k/32k x 154 trials, measured means 5.17/5.21/5.40 vs this
    formula's 5.16/5.28/5.41; re-validated in
    ``tests/test_certify.py::TestNoiseCeiling`` at a second trial count).
    Outside it — strongly correlated channels after aggressive RFI
    cleaning, non-Gaussian residuals, very large ndm — the effective
    count ``m`` drifts and the location can be off by a few tenths;
    ``snr_threshold="auto"`` additionally clamps to the reference's 6.0
    floor so small chunks never resolve below the reference default.
    """
    m = 6.0 * float(nsamples) * max(1.0, float(ndm))
    a = np.sqrt(2.0 * np.log(m))
    return float(a - (np.log(np.log(m)) + np.log(4.0 * np.pi)) / (2.0 * a))


def matched_snr_floor(nsamples, ndm=1, margin=1.0):
    """A detection floor matched to the chunk's noise statistics.

    ``expected_noise_max_snr + margin``: the same "clearly above the
    noise maximum" philosophy as the reference's fixed ``snr > 6``
    (tuned for its ~1e3-sample chunks), adapted to the chunk geometry.
    ``margin = 1.0`` puts the per-chunk false-alarm probability at the
    sub-percent level (Gumbel scale ``1/sqrt(2 ln m)`` ~ 0.19 at 2^20
    samples).
    """
    return expected_noise_max_snr(nsamples, ndm) + float(margin)
