"""Fourier-domain dedispersion (FDD): exact fractional-sample delays.

Every other kernel in this framework (and the whole reference,
``pulsarutils/dedispersion.py:125-139``) quantises per-channel dispersion
delays to integer samples — ``rint(delay // tsamp)`` — which smears
pulses narrower than a sample and dithers arrival times by up to half a
sample per channel.  Fourier-domain dedispersion (Bassa, Pleunis &
Hessels 2022, A&C 38:100549 — PAPERS.md) applies each channel's *exact*
delay as a phase ramp on its spectrum:

    out(t) = sum_c  F^-1[ F[data_c] * exp(+2pi i f tau_c(DM)) ](t)

— a circular *advance* by the un-rounded ``tau_c`` (the positive sign
matches the integer kernels' gather convention ``out[t] = x[(t + shift)
mod T]``, module :mod:`.dedisperse`), so results line up with them
bin-for-bin.

Cost model (why this is the *precision* option, not the survey kernel):
``O(ndm * nchan * T)`` complex multiply-adds — asymptotically the direct
sweep's cost, vs the FDMT's ``O(nchan * T * log nchan)``.  The rFFT of
the input is computed once and reused by every trial.

TPU notes — two device paths:

* **uniform-grid incremental rotation** (the fast path; every standard
  plan grid is uniform in DM, and dispersion delay is *linear* in DM, so
  consecutive trials differ by one constant per-channel phase ramp):
  trials are processed in anchored superblocks — the anchor trial's
  phase comes from the exact integer-limb table, then each next trial is
  one complex multiply by the (constant) step ramp via ``lax.scan``.
  This removes the transcendental from the inner loop entirely: ``exp``
  runs once per (superblock, channel) instead of once per (trial,
  channel, bin) — a ~``superblock``-fold cut of the dominant cost.
  Phase error: anchors are exact to the 36-bit limb quantisation
  (~2.4e-5 rad at T=2^20); the 48-bit step limbs accumulate
  < ~1e-5 rad across a superblock.
* **arbitrary-grid fallback**: the phase table is built on the fly from
  an outer product (``f x tau``) and consumed immediately — XLA fuses
  exp + complex multiply + channel reduction into one pass over the
  spectrum block.
"""

from __future__ import annotations

import functools
import os
import warnings

import numpy as np

from .plan import channel_frequencies, dm_delay

#: trials per device block in the arbitrary-grid fallback (bounds the
#: phase/workspace to dm_block * chan_block * (T/2+1) complex64)
FOURIER_DM_BLOCK = 4
FOURIER_CHAN_BLOCK = 128

#: trials per anchored segment in the uniform-grid incremental path; the
#: scan's rotation carry is chan_block * (T/2+1) complex64 and each
#: superblock materialises a (superblock, T/2+1) spectrum accumulator
FOURIER_SUPERBLOCK = 64

#: HBM budget (bytes) the FDD's live-set estimate must fit in; oversized
#: blocking requests are auto-shrunk (with a warning) instead of
#: compile-OOMing the chip — the FDD analogue of the Pallas kernel's
#: VMEM_BUDGET.  Default 12 GB leaves headroom on a 16 GB chip for the
#: allocator and XLA's FFT temporaries; override via PUTPU_FDD_HBM.
FDD_HBM_BUDGET = 12 << 30


def _fdd_hbm_budget():
    raw = os.environ.get("PUTPU_FDD_HBM")
    try:
        value = int(float(raw or 0))
    except (ValueError, OverflowError):  # "8GB", "inf", ...
        value = 0
    if raw and value <= (1 << 28):
        # a rejected override must not silently budget for the 12 GB
        # default on a smaller chip (the compile-OOM this knob exists
        # to prevent)
        warnings.warn(
            f"PUTPU_FDD_HBM={raw!r} ignored (needs a byte count "
            "> 2^28, e.g. 8589934592 for 8 GB); using the "
            f"{FDD_HBM_BUDGET >> 30} GB default", stacklevel=2)
    return value if value > (1 << 28) else FDD_HBM_BUDGET


def _fdd_live_bytes(nchan, t, superblock, chan_block, cross=False):
    """Conservative live-set estimate of an FDD program.

    Counts the resident spectrum (complex64, the irreducible term), the
    float32 input, the per-channel-block phasors (anchor, step, carry,
    spectrum slice), the superblock accumulators, and a 2x allowance on
    the superblock-sized irfft for XLA's FFT temporaries.  ``cross=True``
    adds the arbitrary-grid fallback's dominant
    ``dm_block x chan_block x nbin`` complex phase tensor (the
    uniform-grid kernel never materialises that cross term).
    """
    nbin = t // 2 + 1
    nchan_p = -(-nchan // chan_block) * chan_block
    spec = 8 * nchan_p * nbin
    data = 4 * nchan_p * t
    phasors = 8 * nbin * 4 * chan_block
    acc = 8 * nbin * 3 * superblock
    fft = 2 * 4 * superblock * t
    phase_cross = 2 * 8 * superblock * chan_block * nbin if cross else 0
    return spec + data + phasors + acc + fft + phase_cross


def _auto_fdd_blocks(nchan, t, superblock, chan_block, cross=False):
    """Shrink (superblock, chan_block) until the estimate fits the HBM
    budget; returns the (possibly reduced) pair."""
    budget = _fdd_hbm_budget()
    req = (superblock, chan_block)
    min_s = 1 if cross else 8
    while (_fdd_live_bytes(nchan, t, superblock, chan_block, cross)
           > budget and (superblock > min_s or chan_block > 32)):
        # shrink whichever block contributes more shrinkable bytes
        # (uniform path: superblock terms ~ 20*S*t vs chan terms
        # ~ 16*C*t; with the cross term both shrink it equally, so the
        # same dominance rule still picks the bigger contributor)
        if chan_block <= 32 or (superblock > min_s
                                and 20 * superblock >= 16 * chan_block):
            superblock //= 2
        else:
            chan_block //= 2
    if (superblock, chan_block) != req:
        warnings.warn(
            f"FDD blocking {req} exceeds the HBM budget "
            f"({_fdd_live_bytes(nchan, t, *req, cross) >> 30} GB est. > "
            f"{budget >> 30} GB); shrunk to "
            f"({superblock}, {chan_block}) — set PUTPU_FDD_HBM to raise",
            stacklevel=3)
    return superblock, chan_block


def fractional_delays(trial_dms, nchan, start_freq, bandwidth):
    """Un-rounded per-channel delays (seconds) for each trial DM.

    Same band-centre reference convention as the integer path
    (``dedispersion_shifts``, reference ``dedispersion.py:128-135``) so
    the two kernels dedisperse to the same epoch: the delay of channel
    ``c`` is relative to the band centre frequency.
    """
    trial_dms = np.atleast_1d(np.asarray(trial_dms, dtype=np.float64))
    freqs = channel_frequencies(nchan, start_freq, bandwidth)
    center = start_freq + bandwidth / 2.0
    # (ndm, nchan): positive = channel lags the band centre
    return (dm_delay(trial_dms[:, None], freqs[None, :])
            - dm_delay(trial_dms, center)[:, None])


def _dedisperse_fourier_numpy(data, delays, sample_time):
    data = np.asarray(data, dtype=np.float64)
    nchan, t = data.shape
    spec = np.fft.rfft(data, axis=1)
    f = np.fft.rfftfreq(t, d=sample_time)
    out = np.empty((delays.shape[0], t))
    for d in range(delays.shape[0]):
        phase = np.exp(2j * np.pi * f[None, :] * delays[d][:, None])
        out[d] = np.fft.irfft((spec * phase).sum(axis=0), n=t)
    return out


@functools.lru_cache(maxsize=16)
def _jitted_fourier(t, dm_block, chan_block, with_scores, with_plane=True):
    """One compiled FDD program.

    Memory: when the plane is not requested (``with_scores`` and not
    ``with_plane``), each dm block is scored inside the loop and only the
    ``(5, ndm)`` score array accumulates — the live set is one
    ``dm_block x T`` block regardless of trial count, matching the other
    kernels' bounded-plane behaviour.
    """
    import jax
    import jax.numpy as jnp

    def one_block(spec_b, limbs_b, k, kf):
        # spec_b (C_b, F) complex; limbs_b (3, D_b, C_b) int32 12-bit
        # limbs of the per-(trial, channel) phase slope (see
        # _phase_limbs).  The phase at rfft bin k is k * M / 2^36 cycles
        # with M = M1*2^24 + M2*2^12 + M3; each k*Mi fits the wrapping
        # int32 product's congruence class, so the phase error is bounded
        # by the 36-bit quantisation of the slope (~2.4e-5 rad at
        # T = 2^20) — float32 `f * tau` would be off by ~0.1 rad at the
        # 1M-sample sizes this kernel exists to serve.
        m1, m2, m3 = (limbs_b[i][:, :, None] for i in range(3))
        th = (((k * m1) & 0xFFF).astype(jnp.float32) / (1 << 12)
              + ((k * m2) & 0xFFFFFF).astype(jnp.float32) / (1 << 24)
              + kf * m3.astype(jnp.float32) / np.float32(1 << 36))
        phase = jnp.exp((2j * jnp.pi) * th)
        return (spec_b[None, :, :] * phase).sum(axis=1)  # (D_b, F)

    keep_plane = with_plane or not with_scores

    @jax.jit
    def run(data, limbs):
        from .search import score_profiles_stacked

        nbin = t // 2 + 1
        k = jnp.arange(nbin, dtype=jnp.int32)[None, None, :]
        kf = k.astype(jnp.float32)
        nchan = data.shape[0]
        ndm = limbs.shape[1]
        nc = -(-nchan // chan_block)
        nd = -(-ndm // dm_block)
        data_p = jnp.pad(data, ((0, nc * chan_block - nchan), (0, 0)))
        spec = _blocked_rfft(data_p, chan_block, nbin)
        limbs_p = jnp.pad(limbs, ((0, 0), (0, nd * dm_block - ndm),
                                  (0, nc * chan_block - nchan)))

        def series_block(i):
            dl = jax.lax.dynamic_slice_in_dim(limbs_p, i * dm_block,
                                              dm_block, axis=1)

            def chan_step(j, acc_spec):
                sp = jax.lax.dynamic_slice_in_dim(spec, j * chan_block,
                                                  chan_block, axis=0)
                db = jax.lax.dynamic_slice_in_dim(dl, j * chan_block,
                                                  chan_block, axis=2)
                return acc_spec + one_block(sp, db, k, kf)

            out_spec = jax.lax.fori_loop(
                0, nc, chan_step,
                jnp.zeros((dm_block, t // 2 + 1), jnp.complex64))
            return jnp.fft.irfft(out_spec, n=t, axis=1).astype(jnp.float32)

        def dm_step(i, carry):
            plane_acc, score_acc = carry
            series = series_block(i)
            if keep_plane:
                plane_acc = jax.lax.dynamic_update_slice_in_dim(
                    plane_acc, series, i * dm_block, axis=0)
            if with_scores:
                score_acc = jax.lax.dynamic_update_slice_in_dim(
                    score_acc, score_profiles_stacked(series, xp=jnp),
                    i * dm_block, axis=1)
            return plane_acc, score_acc

        plane0 = jnp.zeros((nd * dm_block if keep_plane else 1, t),
                           jnp.float32)
        score0 = jnp.zeros((5, nd * dm_block if with_scores else 1),
                           jnp.float32)
        plane, scores = jax.lax.fori_loop(0, nd, dm_step, (plane0, score0))
        plane = plane[:ndm]
        scores = scores[:, :ndm]
        if not with_scores:
            return plane
        return (scores, plane) if with_plane else scores

    return run


def _blocked_rfft(data, chan_block, nbin):
    """rFFT of ``data`` row-blocks via ``fori_loop``.

    XLA's TPU FFT lowering materialises convolution temps proportional
    to the *batch* size — a single rfft over (1024, 1M) data wants
    ~20 GB of HLO temps and fails to compile on a 16 GB chip.  Rows are
    independent, so filling the spectrum ``chan_block`` rows at a time
    is bit-identical and caps the temps at ``chan_block/nchan`` of that.
    """
    import jax
    import jax.numpy as jnp

    nchan_p, t = data.shape
    nc = nchan_p // chan_block

    def fill(j, spec):
        sp = jnp.fft.rfft(
            jax.lax.dynamic_slice_in_dim(data, j * chan_block, chan_block,
                                         axis=0), axis=1)
        return jax.lax.dynamic_update_slice_in_dim(spec, sp, j * chan_block,
                                                   axis=0)

    return jax.lax.fori_loop(
        0, nc, fill, jnp.zeros((nchan_p, nbin), jnp.complex64))


def _uniform_spacing(trial_dms):
    """The constant DM step of a uniform grid, or ``None`` if non-uniform.

    Every standard plan grid (one trial per integer band-delay sample,
    ``dedispersion_plan``) is uniform: DM is linear in the delay index.
    """
    dms = np.asarray(trial_dms, dtype=np.float64)
    if dms.size < 2:
        return 0.0
    d = np.diff(dms)
    step = d.mean()
    scale = max(abs(step), abs(dms).max() * 1e-12, 1e-300)
    if np.abs(d - step).max() <= 1e-8 * scale:
        return float(step)
    return None


def _step_limbs(delays_step, sample_time, t):
    """48-bit phase-slope limbs for the per-trial increment ramp.

    Same congruence scheme as :func:`_phase_limbs` but quantised to 48
    bits (four 12-bit limbs): the step's phase error is *accumulated*
    over a superblock of trials, so it gets 12 more bits than the
    anchors (64 * 2pi * (T/2) * 2^-49 ~ 1e-5 rad at T = 2^20).
    """
    a = np.asarray(delays_step, dtype=np.float64) / (sample_time * t)
    m = np.rint((a % 1.0) * (1 << 48)).astype(np.int64) & ((1 << 48) - 1)
    return np.stack([(m >> 36).astype(np.int32),
                     ((m >> 24) & 0xFFF).astype(np.int32),
                     ((m >> 12) & 0xFFF).astype(np.int32),
                     (m & 0xFFF).astype(np.int32)])


@functools.lru_cache(maxsize=16)
def _jitted_fourier_uniform(t, superblock, chan_block, with_scores,
                            with_plane=True, use_pallas=False,
                            interpret=False):
    """One compiled uniform-grid FDD program (incremental rotation).

    Inputs: ``data (nchan, T)``, ``anchor_limbs (3, nblocks, nchan)`` —
    exact phase limbs of each superblock's first trial — and
    ``step_limbs (4, nchan)`` — 48-bit limbs of the constant per-trial
    increment ramp.  Trials covered: ``nblocks * superblock`` (callers
    pad the grid and slice).

    ``use_pallas`` routes the rotate-accumulate recurrence through the
    VMEM-resident kernel (:mod:`.fourier_pallas`): same anchors, same
    step ramp, same recurrence, but the per-trial rotation state never
    round-trips HBM — measured 18 s -> ~2.9 s at the canonical
    513-trial 1024 x 1M sweep (the ``lax.scan`` form carries ~1 TB of
    rotation state through HBM and runs at ~6% of the VPU).  Float sum
    order over channels differs (per-channel accumulation instead of
    the scan's per-chan-block contribution sums), so results agree to
    float32 tolerance, not bitwise.
    """
    import jax
    import jax.numpy as jnp

    nbin = t // 2 + 1
    keep_plane = with_plane or not with_scores

    def limb_phase(limbs, k, kf, nlimb):
        # limbs (nlimb, C) int32 -> (C, nbin) complex64 unit phasor.
        # k * m1 / m2 wrap in int32: int32 wrap is mod 2^32, a multiple
        # of each masked modulus, so the congruence classes are exact.
        m = [limbs[i][:, None] for i in range(nlimb)]
        th = ((k * m[0]) & 0xFFF).astype(jnp.float32) / (1 << 12)
        th = th + ((k * m[1]) & 0xFFFFFF).astype(jnp.float32) / (1 << 24)
        th = th + kf * m[2].astype(jnp.float32) / np.float32(1 << 36)
        if nlimb > 3:
            # k * m4 / 2^48 < 2^-16: no wrap possible, float32 is ample
            th = th + kf * m[3].astype(jnp.float32) / np.float32(2.0 ** 48)
        return jnp.exp((2j * jnp.pi) * th)

    @jax.jit
    def run(data, anchor_limbs, step_limbs):
        from .search import score_profiles_stacked

        nchan = data.shape[0]
        nblocks = anchor_limbs.shape[1]
        nc = -(-nchan // chan_block)
        data_p = jnp.pad(data, ((0, nc * chan_block - nchan), (0, 0)))
        spec = _blocked_rfft(data_p, chan_block, nbin)
        anchor_p = jnp.pad(anchor_limbs,
                           ((0, 0), (0, 0), (0, nc * chan_block - nchan)))
        step_p = jnp.pad(step_limbs, ((0, 0), (0, nc * chan_block - nchan)))
        k = jnp.arange(nbin, dtype=jnp.int32)[None, :]
        kf = k.astype(jnp.float32)
        ndm_p = nblocks * superblock

        def super_step(i, carry):
            plane_acc, score_acc = carry

            def chan_step(j, acc):
                sp = jax.lax.dynamic_slice_in_dim(spec, j * chan_block,
                                                  chan_block, axis=0)
                al = jax.lax.dynamic_slice_in_dim(
                    anchor_p[:, i], j * chan_block, chan_block, axis=1)
                sl = jax.lax.dynamic_slice_in_dim(step_p, j * chan_block,
                                                  chan_block, axis=1)
                rot0 = limb_phase(al, k, kf, 3)
                step = limb_phase(sl, k, kf, 4)

                if use_pallas:
                    from .fourier_pallas import fdd_superblock_spectra

                    return acc + fdd_superblock_spectra(
                        sp * rot0, step, superblock, interpret=interpret)

                def trial(rot, _):
                    # rot IS trial d's total phasor; emit its channel
                    # sum, advance to trial d+1 by the constant ramp
                    return rot * step, (sp * rot).sum(axis=0)

                _, contribs = jax.lax.scan(trial, rot0, None,
                                           length=superblock)
                return acc + contribs  # (superblock, nbin)

            out_spec = jax.lax.fori_loop(
                0, nc, chan_step,
                jnp.zeros((superblock, nbin), jnp.complex64))
            series = jnp.fft.irfft(out_spec, n=t, axis=1).astype(jnp.float32)
            if keep_plane:
                plane_acc = jax.lax.dynamic_update_slice_in_dim(
                    plane_acc, series, i * superblock, axis=0)
            if with_scores:
                score_acc = jax.lax.dynamic_update_slice_in_dim(
                    score_acc, score_profiles_stacked(series, xp=jnp),
                    i * superblock, axis=1)
            return plane_acc, score_acc

        plane0 = jnp.zeros((ndm_p if keep_plane else 1, t), jnp.float32)
        score0 = jnp.zeros((5, ndm_p if with_scores else 1), jnp.float32)
        plane, scores = jax.lax.fori_loop(0, nblocks, super_step,
                                          (plane0, score0))
        if not with_scores:
            return plane
        return (scores, plane) if with_plane else scores

    return run


def _uniform_fourier_inputs(trial_dms, dm_step, nchan, start_freq,
                            bandwidth, sample_time, t, superblock):
    """Host-side limb tables for the uniform-grid kernel.

    Returns ``(anchor_limbs, step_limbs, ndm)``; the grid is extended to
    a whole number of superblocks (extra trials are sliced off).
    """
    dms = np.asarray(trial_dms, dtype=np.float64)
    ndm = dms.size
    nblocks = -(-ndm // superblock)
    anchors = dms[0] + dm_step * superblock * np.arange(nblocks)
    anchor_delays = fractional_delays(anchors, nchan, start_freq, bandwidth)
    anchor_limbs = _phase_limbs(anchor_delays, sample_time, t)
    # dispersion delay is linear in DM: the step ramp is dm_step times
    # the unit-DM delay curve
    step_delays = dm_step * fractional_delays(
        np.array([1.0]), nchan, start_freq, bandwidth)[0]
    step_limbs = _step_limbs(step_delays, sample_time, t)
    return anchor_limbs, step_limbs, ndm


def _phase_limbs(delays, sample_time, t):
    """Host-side exact phase-slope limbs for the device kernel.

    The phase at rfft bin ``k`` is ``k * A mod 1`` cycles with
    ``A = tau / (tsamp * T)``.  ``A mod 1`` is quantised to 36 bits
    (float64 is exact here) and split into three 12-bit limbs so the
    device can form ``k * A mod 1`` with wrapping int32 products —
    phase error <= 2pi * (T/2) * 2^-37 cycles-rounding ~ 2.4e-5 rad at
    T = 2^20 (it grows linearly with T: ~1.5e-3 rad by T = 2^26).

    Returns int32 ``(3, ndm, nchan)``.
    """
    a = np.asarray(delays, dtype=np.float64) / (sample_time * t)
    m = np.rint((a % 1.0) * (1 << 36)).astype(np.int64) & ((1 << 36) - 1)
    return np.stack([(m >> 24).astype(np.int32),
                     ((m >> 12) & 0xFFF).astype(np.int32),
                     (m & 0xFFF).astype(np.int32)])


def _fourier_device_run(data, trial_dms, start_freq, bandwidth, sample_time,
                        with_scores, with_plane, dm_block, chan_block):
    """Shared device dispatch: uniform-grid incremental kernel when the
    trial grid allows it, arbitrary-grid exp fallback otherwise."""
    import jax.numpy as jnp

    import jax

    nchan, t = data.shape[0], data.shape[1]
    chan_block = chan_block or FOURIER_CHAN_BLOCK
    dm_step = _uniform_spacing(trial_dms)
    if dm_step is not None:
        # the VMEM-resident rotation kernel: default on TPU;
        # PUTPU_FDD_PALLAS=0|1 overrides (1 off-TPU = interpret mode,
        # the CPU test path); garbage values warn via the shared parser
        from ..utils.knobs import tristate_env

        knob = tristate_env("PUTPU_FDD_PALLAS")
        on_tpu = jax.default_backend() == "tpu"
        use_pallas = on_tpu if knob is None else knob
        superblock = dm_block or FOURIER_SUPERBLOCK
        # clamp to the trial count BEFORE the budget check: a 512-block
        # request over 8 trials would otherwise warn and shrink
        # chan_block for a program that was never going to be built
        superblock = max(1, min(superblock, len(np.atleast_1d(trial_dms))))
        superblock, chan_block = _auto_fdd_blocks(nchan, t, superblock,
                                                  chan_block)
        if use_pallas:
            from .fourier_pallas import FDD_L, FDD_N_UNROLL

            # the kernel's revisited output block pair is
            # 2 * superblock * 8 * FDD_L * 4 bytes of VMEM (plus ~2 MB
            # of input staging) — clamp so it stays well inside the
            # ~16 MB chip budget (the scan form had no such ceiling;
            # dm_block=512 would otherwise compile a 32 MB block and
            # fail where the old path worked — code-review r4)
            vmem_cap = (10 << 20) // (2 * 8 * FDD_L * 4)
            superblock = min(superblock, max(FDD_N_UNROLL, vmem_cap))
            # the kernel's trial loop is unrolled in FDD_N_UNROLL steps
            superblock = -(-superblock // FDD_N_UNROLL) * FDD_N_UNROLL
        anchor_limbs, step_limbs, ndm = _uniform_fourier_inputs(
            trial_dms, dm_step, nchan, start_freq, bandwidth, sample_time,
            t, superblock)
        run = _jitted_fourier_uniform(t, superblock, chan_block,
                                      with_scores, with_plane,
                                      use_pallas=use_pallas,
                                      interpret=not on_tpu)
        out = run(jnp.asarray(data, jnp.float32),
                  jnp.asarray(anchor_limbs), jnp.asarray(step_limbs))
    else:
        delays = fractional_delays(trial_dms, nchan, start_freq, bandwidth)
        ndm = delays.shape[0]
        dm_block, chan_block = _auto_fdd_blocks(
            nchan, t, min(dm_block or FOURIER_DM_BLOCK, max(1, ndm)),
            chan_block, cross=True)
        run = _jitted_fourier(t, dm_block, chan_block,
                              with_scores, with_plane)
        out = run(jnp.asarray(data, jnp.float32),
                  jnp.asarray(_phase_limbs(delays, sample_time, t)))
    # slice off superblock/dm_block padding
    if with_scores and with_plane:
        return out[0][:, :ndm], out[1][:ndm]
    if with_scores:
        return out[:, :ndm], None
    return out[:ndm], None


def dedisperse_fourier(data, trial_dms, start_freq, bandwidth, sample_time,
                       xp=np, dm_block=None, chan_block=None):
    """Dedisperse ``data`` at exact (fractional-sample) delays per trial.

    Returns the ``(ndm, T)`` dedispersed plane.  ``xp=np`` is the float64
    reference implementation; ``xp=jax.numpy`` runs blocked on device
    (``dm_block`` is the trial superblock of the uniform-grid kernel, or
    the phase-table block of the arbitrary-grid fallback).
    """
    if xp is np:
        delays = fractional_delays(trial_dms, data.shape[0], start_freq,
                                   bandwidth)
        return _dedisperse_fourier_numpy(data, delays, sample_time)
    plane, _ = _fourier_device_run(data, trial_dms, start_freq, bandwidth,
                                   sample_time, with_scores=False,
                                   with_plane=True, dm_block=dm_block,
                                   chan_block=chan_block)
    return plane


def search_fourier(data, trial_dms, start_freq, bandwidth, sample_time,
                   capture_plane=False, dm_block=None, chan_block=None):
    """FDD sweep + standard boxcar scoring (jax path; used by
    ``dedispersion_search(kernel="fourier")``)."""
    from .search import unstack_scores

    stacked, plane = _fourier_device_run(
        data, trial_dms, start_freq, bandwidth, sample_time,
        with_scores=True, with_plane=bool(capture_plane),
        dm_block=dm_block, chan_block=chan_block)
    return unstack_scores(stacked) + (plane,)
