"""Robust stats: MAD/medfilt vs scipy, H-test / Z^2_n sanity + jit parity."""
import numpy as np
import pytest
from scipy.signal import medfilt

from pulsarutils_tpu.ops.robust import (
    MAD_SCALE,
    digitize,
    h_test,
    h_test_batch,
    mad,
    median_filter_1d,
    ref_mad,
    z_n_and_h,
    z_n_test,
)


def test_mad_gaussian_estimates_sigma():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 3.0, 100000)
    assert mad(x) == pytest.approx(3.0, rel=0.02)


def test_mad_matches_definition():
    x = np.array([1.0, 2.0, 3.0, 100.0])
    med = np.median(x)
    assert mad(x) == pytest.approx(np.median(np.abs(x - med)) / MAD_SCALE)


def test_mad_axis():
    x = np.arange(12.0).reshape(3, 4)
    per_row = mad(x, axis=1)
    assert per_row.shape == (3,)
    assert per_row[0] == pytest.approx(mad(x[0]))


def test_ref_mad_ignores_smooth_trend():
    rng = np.random.default_rng(1)
    t = np.linspace(0, 1, 10000)
    x = 100 * np.sin(2 * np.pi * t) + rng.normal(0, 0.5, t.size)
    # direct MAD is dominated by the trend; ref_mad recovers the noise
    assert ref_mad(x) == pytest.approx(0.5, rel=0.1)
    assert mad(x) > 10


def test_ref_mad_window_minimum():
    rng = np.random.default_rng(2)
    x = rng.normal(0, 1.0, 4000)
    x[2000:] += rng.normal(0, 20.0, 2000)  # second half much noisier
    windowed = ref_mad(x, window=500)
    assert windowed == pytest.approx(1.0, rel=0.25)


def test_median_filter_matches_scipy():
    rng = np.random.default_rng(3)
    x = rng.normal(size=101)
    for size in (3, 5, 11):
        assert np.allclose(median_filter_1d(x, size), medfilt(x, size))


def test_median_filter_jax_matches():
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    x = rng.normal(size=64)
    out = median_filter_1d(jnp.asarray(x), 11, xp=jnp)
    assert np.allclose(np.asarray(out), medfilt(x, 11), atol=1e-6)


def _pulsed_profile(nbin=64, counts=5000, width=0.05, rng=None):
    rng = np.random.default_rng(rng)
    phases = rng.normal(0.3, width, counts) % 1.0
    prof, _ = np.histogram(phases, bins=nbin, range=(0, 1))
    return prof


def test_h_test_detects_pulse():
    prof = _pulsed_profile(rng=5)
    h, m = h_test(prof)
    assert h > 50  # decisively periodic
    flat = np.full(64, 5000 // 64)
    h_flat, _ = h_test(flat)
    assert h_flat < 10


def test_h_test_flat_noise_calibration():
    # for pure Poisson noise H should be small on average (E[H] ~ 2.5)
    rng = np.random.default_rng(6)
    hs = []
    for _ in range(50):
        prof = rng.poisson(100, 64)
        hs.append(h_test(prof)[0])
    assert np.mean(hs) < 10


def test_h_test_batch_matches_scalar():
    rng = np.random.default_rng(7)
    profs = np.stack([_pulsed_profile(rng=10 + i) for i in range(4)] +
                     [rng.poisson(100, 64)])
    h_b, m_b = h_test_batch(profs)
    for i in range(profs.shape[0]):
        h_s, m_s = h_test(profs[i])
        assert h_b[i] == pytest.approx(h_s)
        assert m_b[i] == m_s


def test_h_test_jax_matches_numpy():
    import jax.numpy as jnp

    prof = _pulsed_profile(rng=8)
    h_np, m_np = h_test(prof)
    h_j, m_j = h_test(jnp.asarray(prof), xp=jnp)
    assert float(h_j) == pytest.approx(float(h_np), rel=1e-4)
    assert int(m_j) == m_np


def test_z_n_test_positive_and_increasing_info():
    prof = _pulsed_profile(rng=9)
    z2 = z_n_test(prof, 2)
    z8 = z_n_test(prof, 8)
    assert z2 > 0
    assert z8 >= z2  # harmonics only add power


def test_digitize():
    rng = np.random.default_rng(10)
    x = rng.normal(100, 5, (8, 256))
    d = digitize(x)
    assert d.dtype == np.int32
    assert d.min() == 0
    # median maps to 0, +1 MAD-sigma maps to ~3
    assert np.median(d) == 0
    ints = np.arange(10)
    assert digitize(ints) is ints  # integer passthrough


def test_digitize_jax():
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    x = rng.normal(0, 1, (4, 64))
    d_np = digitize(x)
    d_j = digitize(jnp.asarray(x), xp=jnp)
    assert np.array_equal(np.asarray(d_j), d_np)


def test_digitize_integer_passthrough_jax():
    import jax.numpy as jnp

    ints = jnp.arange(10)
    out = digitize(ints, xp=jnp)
    assert np.array_equal(np.asarray(out), np.arange(10))


def test_z_n_test_rejects_unresolvable_harmonics():
    prof = np.ones(16)
    with pytest.raises(ValueError, match="harmonics"):
        z_n_test(prof, 10)


@pytest.mark.parametrize("nbin", [3, 4, 9, 40, 64, 1000, 1 << 14])
def test_z_n_and_h_equals_the_separate_tests_bit_for_bit(nbin):
    """One transform for a candidate's four Z^2_n and its H: the floats
    of ``z_n_test`` and ``h_test`` exactly, whatever the profile's length
    leaves of the harmonics (``PulseInfo.compute_stats``'s rule)."""
    prof = np.random.default_rng(nbin).poisson(40, nbin)
    nmax = nbin // 2
    ns = [n for n in (2, 6, 12, 20) if n <= nmax]
    z, h, m = z_n_and_h(prof, ns, nmax=min(20, max(nmax, 1)))
    assert list(z) == ns
    for n in ns:
        assert z[n] == z_n_test(prof, n)
    h_ref, m_ref = h_test(prof, nmax=min(20, max(nmax, 1)))
    assert (h, m) == (h_ref, m_ref)
    with pytest.raises(ValueError):
        z_n_and_h(prof, [nmax + 1])
