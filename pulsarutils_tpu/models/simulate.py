"""Synthetic filterbank generation — the framework's fake backend.

Capability-equivalent of the reference's ``simulate_test_data``
(``pulsarutils/simulate.py:6-28``): an impulse of a given amplitude at the
midpoint of every channel, folded-normal noise, then each channel rolled
*forward* by its DM delay (the inverse of what ``dedisperse`` undoes —
opposite sign conventions pinned by tests).

Extended for the TPU build:

* ``backend="jax"`` builds the array on device with ``jax.random`` so the
  whole simulate -> clean -> dedisperse loop stays in HBM (no host round
  trip);
* periodic-pulsar injection (:func:`simulate_pulsar_data`) for the folding /
  H-test periodicity stack;
* optional RFI injection (:func:`inject_rfi`) to exercise the excision ops.
"""

from __future__ import annotations

import numpy as np

from ..ops.plan import dedispersion_shifts


def _sigpyproc_style_header(nchan, nsamples, tsamp, start_freq, bandwidth):
    """Header dict with the field names the reference pipeline consumes
    (``pulsarutils/simulate.py:21-26``, ``clean.py:284-294``)."""
    return {
        "bandwidth": bandwidth,
        "fbottom": start_freq,
        "ftop": start_freq + bandwidth,
        "foff": bandwidth / nchan,
        "nchans": nchan,
        "nsamples": nsamples,
        "tsamp": tsamp,
    }


def disperse_array(array, dm, start_freq, bandwidth, tsamp, xp=np):
    """Roll each channel *forward* by its DM delay (reference
    ``simulate.py:17-19`` applies ``+shifts``; ``dedisperse`` undoes it)."""
    array = xp.asarray(array)
    nchan, nsamples = array.shape
    shifts = dedispersion_shifts(nchan, dm, start_freq, bandwidth, tsamp)
    sh = np.rint(np.asarray(shifts)).astype(np.int64) % nsamples
    idx = (np.arange(nsamples)[None, :] - sh[:, None]) % nsamples
    idx = xp.asarray(idx)
    if xp is np:
        return np.take_along_axis(array, idx, axis=1)
    return xp.take_along_axis(array, idx, axis=1)


def simulate_test_data(dm=150, tsamp=0.0005, nsamples=1024, nchan=128,
                       start_freq=1200., bandwidth=200., signal=1., noise=0.5,
                       rng=None, backend="numpy"):
    """Simulate a dispersed single pulse in a noisy filterbank.

    Defaults and semantics match the reference fixture
    (``pulsarutils/simulate.py:6-28``): impulse at ``nsamples // 2`` in every
    channel, ``abs(Normal(impulse, noise))`` noise, channels rolled by their
    DM delays.  Returns ``(array, header)`` where header uses
    sigpyproc-style keys.

    ``backend="jax"`` generates the array on the default JAX device and
    returns a device array (the north-star "device-resident simulator").
    """
    if backend == "jax":
        return _simulate_test_data_jax(dm, tsamp, nsamples, nchan, start_freq,
                                       bandwidth, signal, noise, rng)

    rng = np.random.default_rng(rng) if not isinstance(rng, np.random.Generator) else rng
    array = np.zeros((nchan, nsamples))
    array[:, nsamples // 2] = signal
    array = np.abs(rng.normal(array, noise))
    array = disperse_array(array, dm, start_freq, bandwidth, tsamp)
    header = _sigpyproc_style_header(nchan, nsamples, tsamp, start_freq,
                                     bandwidth)
    return array, header


def _simulate_test_data_jax(dm, tsamp, nsamples, nchan, start_freq, bandwidth,
                            signal, noise, seed):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0 if seed is None else int(seed))
    base = jnp.zeros((nchan, nsamples), dtype=jnp.float32)
    base = base.at[:, nsamples // 2].set(signal)
    array = jnp.abs(base + noise * jax.random.normal(key, base.shape))
    array = disperse_array(array, dm, start_freq, bandwidth, tsamp, xp=jnp)
    header = _sigpyproc_style_header(nchan, nsamples, tsamp, start_freq,
                                     bandwidth)
    return array, header


def simulate_pulsar_data(period=0.033, dm=56.77, tsamp=0.0005, nsamples=16384,
                         nchan=128, start_freq=1200., bandwidth=200.,
                         signal=1., noise=0.5, duty_cycle=0.05, rng=None):
    """Simulate a *periodic* dispersed pulsar (for folding / H-test).

    A pulse train with Gaussian profile of fractional width ``duty_cycle``
    at period ``period`` seconds, dispersed at ``dm``.  This extends the
    reference's single-pulse fixture to the periodicity-search stack
    (the reference scores periodicity with the H-test in
    ``pulsarutils/clean.py:252-255`` but has no periodic simulator).
    """
    rng = np.random.default_rng(rng) if not isinstance(rng, np.random.Generator) else rng
    t = np.arange(nsamples) * tsamp
    phase = (t / period) % 1.0
    # wrapped distance from phase 0
    dist = np.minimum(phase, 1.0 - phase)
    profile = signal * np.exp(-0.5 * (dist / duty_cycle) ** 2)
    array = np.abs(rng.normal(np.broadcast_to(profile, (nchan, nsamples)),
                              noise))
    array = disperse_array(array, dm, start_freq, bandwidth, tsamp)
    header = _sigpyproc_style_header(nchan, nsamples, tsamp, start_freq,
                                     bandwidth)
    return array, header


#: speed of light (m/s) — kept equal to periodicity.accel.C_M_S (the
#: search-side constant) so injected and searched accelerations agree
_C_M_S = 299792458.0


def simulate_accel_pulsar_data(freq=60.0, dm=150.0, accel=0.0,
                               tsamp=0.0005, nsamples=16384, nchan=32,
                               start_freq=1200., bandwidth=200.,
                               signal=1.0, noise=0.5, duty_cycle=0.05,
                               floor=20.0, jerk=0.0, rng=None):
    """Simulate a dispersed **accelerated** (binary) pulsar.

    Apparent phase ``phi(t) = f0 (t + a t^2 / (2 c) + j t^3 / (6 c))``
    — the constant line-of-sight-acceleration (+``jerk``) Doppler track
    the acceleration search straightens with trial ``(a, j) == (accel,
    jerk)`` (sign convention pinned by
    ``tests/test_period_backend.py``).  ``floor`` adds a constant
    offset so unsigned-integer quantisation in a written filterbank
    keeps the noise floor.  One generator serves the chaos drill and
    the tests — the injection physics must never fork (drifting ground
    truths between the two would hold them to different claims).
    """
    rng = np.random.default_rng(rng) \
        if not isinstance(rng, np.random.Generator) else rng
    t = np.arange(nsamples) * tsamp
    phase = freq * (t + accel * t * t / (2.0 * _C_M_S)
                    + jerk * t ** 3 / (6.0 * _C_M_S))
    dist = np.minimum(phase % 1.0, 1.0 - (phase % 1.0))
    profile = signal * np.exp(-0.5 * (dist / duty_cycle) ** 2)
    array = np.abs(rng.normal(np.broadcast_to(profile,
                                              (nchan, nsamples)),
                              noise)) + floor
    array = disperse_array(array, dm, start_freq, bandwidth, tsamp)
    header = _sigpyproc_style_header(nchan, nsamples, tsamp, start_freq,
                                     bandwidth)
    return array, header


def inject_rfi(array, bad_channels=(), bad_channel_scale=10.0,
               impulse_times=(), impulse_scale=20.0, rng=None):
    """Contaminate a filterbank with narrowband and impulsive broadband RFI.

    ``bad_channels`` get their noise multiplied by ``bad_channel_scale``;
    ``impulse_times`` (sample indices) get a broadband spike added across
    all channels.  Exercises the excision stack (capability parity with the
    RFI the reference's ``stats.py``/``clean.py`` ops were written to
    remove).
    """
    rng = np.random.default_rng(rng) if not isinstance(rng, np.random.Generator) else rng
    out = np.array(array, dtype=float, copy=True)
    nchan, nsamples = out.shape
    for c in bad_channels:
        out[c] += np.abs(rng.normal(0, bad_channel_scale, nsamples))
    for t in impulse_times:
        out[:, int(t) % nsamples] += impulse_scale
    return out
