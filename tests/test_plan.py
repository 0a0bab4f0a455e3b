"""Plan-math correctness anchors.

Ports the reference's kernel doctests (``pulsarutils/dedispersion.py``) and
pins the sign/rounding conventions the S/N recovery depends on.
"""
import numpy as np
import pytest

from pulsarutils_tpu.ops.plan import (
    DM_DELAY_CONST,
    dedispersion_plan,
    dedispersion_shifts,
    dedispersion_shifts_batch,
    delta_delay,
    dm_broadening,
    dm_tier_plan,
    normalize_shifts,
    plan_size,
)


def test_normalize_shifts_doctest():
    # reference doctest, dedispersion.py:105-109
    a = np.array([-1, 0, 2, 4])
    b = normalize_shifts(a, 3)
    assert np.all(b == np.array([2, 0, 2, 1]))
    assert b.dtype == np.int32


def test_normalize_shifts_rounds_then_wraps():
    # rint uses round-half-to-even, then wrap into [0, N)
    a = np.array([-0.5, 0.5, 1.5, 2.5, -7.2])
    b = normalize_shifts(a, 5)
    assert list(b) == [0, 0, 2, 2, 3]


def test_dedispersion_plan_doctest():
    # reference doctest, dedispersion.py:154-158
    t_dm = dedispersion_plan(10, 0, 10, 1400, 128, 0.0005)
    assert np.isclose(t_dm[0], 0)
    assert np.isclose(t_dm[-1], 10.0, atol=1)


def test_plan_one_sample_spacing():
    t_dm = dedispersion_plan(64, 100, 200, 1200, 200, 0.0005)
    f0, f1 = 1200.0, 1400.0
    n = delta_delay(t_dm, f0, f1) / 0.0005
    # consecutive trials differ by exactly one sample of band-crossing delay
    assert np.allclose(np.diff(n), 1.0)
    assert plan_size(64, 100, 200, 1200, 200, 0.0005) == len(t_dm)


def test_delta_delay_formula():
    assert np.isclose(delta_delay(100, 1200, 1400),
                      4149 * 100 * (1200.0 ** -2 - 1400.0 ** -2))


def test_dm_broadening_formula():
    assert np.isclose(dm_broadening(150, 1200, 200 / 1024),
                      8300 * 150 * (200 / 1024) / 1200 ** 3)


def test_shifts_sign_convention():
    # channels below band centre are delayed (positive shift), above are
    # early (negative shift); centre channel ~0
    shifts = dedispersion_shifts(128, 150, 1200., 200., 0.0005)
    assert shifts[0] > 0
    assert shifts[-1] < 0
    mid = 64  # channel at the centre frequency
    assert abs(shifts[mid]) <= 1


def test_shifts_rounding_is_floordiv_then_rint():
    # shift = rint(delay // tsamp): integer-valued floats
    shifts = dedispersion_shifts(128, 150, 1200., 200., 0.0005)
    assert np.all(shifts == np.rint(shifts))
    # reproduce one value by hand
    dfreq = 200.0 / 128
    center = 1300.0
    f5 = 1200.0 + 5 * dfreq
    delay = DM_DELAY_CONST * 150 * (f5 ** -2 - center ** -2)
    assert shifts[5] == np.rint(delay // 0.0005)


def test_batched_shifts_match_scalar():
    dms = dedispersion_plan(128, 100, 200, 1200., 200., 0.0005)
    batch = dedispersion_shifts_batch(dms, 128, 1200., 200., 0.0005)
    for i in [0, 7, len(dms) // 2, len(dms) - 1]:
        single = dedispersion_shifts(128, dms[i], 1200., 200., 0.0005)
        assert np.array_equal(batch[i], single)


def test_batched_shifts_jax_offsets_close_to_numpy():
    """The device-side (float32) shift variant may round off-by-one near
    half-sample boundaries; the search therefore ships host-computed float64
    offsets to the device.  The jnp variant still has to agree within one
    sample everywhere (it is used for on-device plan *previews* only)."""
    import jax.numpy as jnp

    dms = dedispersion_plan(64, 100, 200, 1200., 200., 0.0005)
    np_off = normalize_shifts(
        dedispersion_shifts_batch(dms, 64, 1200., 200., 0.0005), 1024)
    j_off = np.asarray(normalize_shifts(
        dedispersion_shifts_batch(jnp.asarray(dms), 64, 1200., 200., 0.0005,
                                  xp=jnp), 1024, xp=jnp))
    diff = (j_off.astype(int) - np_off.astype(int)) % 1024
    diff = np.minimum(diff, 1024 - diff)
    assert diff.max() <= 1
    assert (diff == 0).mean() > 0.95


# -- smearing-tiered plan (ISSUE 28) ----------------------------------------

def test_tier_plan_doctest():
    import doctest

    from pulsarutils_tpu.ops import plan

    assert doctest.testmod(plan).failed == 0


@pytest.mark.parametrize("k,edge", [(0, 52.10), (1, 104.21), (2, 208.41),
                                    (3, 416.83), (4, 833.65)])
def test_tier_edges_are_where_the_smearing_reaches_a_sample(k, edge):
    """HTRU/BPSR by hand: 8300 x 0.390625 MHz / (1382 MHz)^3 = 1.22833 us
    of smearing per DM unit; tier k ends where that is 2^k x 64 us."""
    tiers = dm_tier_plan(1024, 0.0, 1000.0, 1182.0, 400.0, 64e-6, -0.390625)
    assert tiers[k].dm_hi == pytest.approx(edge, abs=5e-3)
    assert dm_broadening(tiers[k].dm_hi, 1382.0, 0.390625) == pytest.approx(
        2 ** k * 64e-6)
    assert tiers[k + 1].dm_lo == tiers[k].dm_hi
    # the same DM is band delay n in tier k and n / 2 in tier k + 1
    n = delta_delay(tiers[k].dm_hi, 1182.0, 1582.0) / tiers[k].sample_time
    assert len(tiers[k].trial_dms) == (int(n) + 1 if k == 0
                                       else int(n) - int(n / 2))


def test_a_tiered_range_from_above_the_first_edge_starts_downsampled():
    tiers = dm_tier_plan(1024, 60.0, 300.0, 1182.0, 400.0, 64e-6, -0.390625)
    assert [t.downsample for t in tiers] == [2, 4, 8]
    # the first tier is the flat grid's spelling at its own sample time
    flat = dedispersion_plan(1024, 60.0, 300.0, 1182.0, 400.0, 128e-6)
    n = len(tiers[0].trial_dms)
    assert np.array_equal(tiers[0].trial_dms, flat[:n])
    assert tiers[-1].trial_dms[-1] >= 300.0 > tiers[-1].trial_dms[-2]
