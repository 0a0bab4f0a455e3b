"""Parity of the one-pass Pallas scorer vs the XLA chunked scorer.

The kernel must reproduce ``score_profiles`` + ``cert_profile_scores``
semantics exactly for window/peak selection and to f32 reduction order
for float values (see ``ops/score_pallas.py``'s docstring) — including
sliding-certificate windows that straddle time-tile boundaries and the
circular wrap at the row end.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from pulsarutils_tpu.ops.score_pallas import (  # noqa: E402
    pick_score_tile,
    score_plane_pallas,
)
from pulsarutils_tpu.ops.search import score_profiles_chunked  # noqa: E402


def _reference(plane, with_cert):
    return np.asarray(score_profiles_chunked(jnp.asarray(plane), jnp,
                                             with_cert=with_cert))


def _pallas(plane, with_cert):
    return np.asarray(score_plane_pallas(jnp.asarray(plane),
                                         with_cert=with_cert,
                                         interpret=True))


def _check(plane, with_cert=True, rtol=2e-4):
    got = _pallas(plane, with_cert)
    want = _reference(plane, with_cert)
    assert got.shape == want.shape
    # float rows: max, std, snr (and cert) to f32 reduction order
    for row, name in ((0, "max"), (1, "std"), (2, "snr")):
        np.testing.assert_allclose(got[row], want[row], rtol=rtol,
                                   atol=1e-5, err_msg=name)
    # selection rows: EXACT (same tie-breaking, same argmax convention)
    np.testing.assert_array_equal(got[3], want[3], err_msg="window")
    np.testing.assert_array_equal(got[4], want[4], err_msg="peak")
    if with_cert:
        np.testing.assert_allclose(got[5], want[5], rtol=rtol,
                                   atol=1e-5, err_msg="cert")


def test_single_tile_rows_split():
    # 13 rows: 8 through the kernel, 5 through the XLA remainder path
    rng = np.random.default_rng(0)
    plane = rng.standard_normal((13, 2048)).astype(np.float32)
    assert pick_score_tile(2048) == 2048
    _check(plane)


def test_under_eight_rows_all_remainder():
    rng = np.random.default_rng(8)
    plane = rng.standard_normal((5, 2048)).astype(np.float32)
    _check(plane)


def test_multi_tile():
    rng = np.random.default_rng(1)
    plane = rng.standard_normal((16, 3072)).astype(np.float32)
    assert pick_score_tile(3072) == 1024  # forces n_t = 3
    _check(plane)


def test_without_cert_row():
    rng = np.random.default_rng(2)
    plane = rng.standard_normal((8, 1024)).astype(np.float32)
    got = _pallas(plane, with_cert=False)
    assert got.shape == (5, 8)
    _check(plane, with_cert=False)


def test_pulse_at_tile_boundary():
    # a width-3 pulse straddling the lane-1023/1024 tile boundary: the
    # sliding cert windows that capture it live in the boundary pass
    rng = np.random.default_rng(3)
    plane = 0.1 * rng.standard_normal((8, 3072)).astype(np.float32)
    plane[2, 1023:1026] += 5.0
    plane[5, 2047:2049] += 4.0
    _check(plane)


def test_circular_wrap_at_row_end():
    # pulse split across the row end: circular sliding windows must see
    # its full mass (reference semantics are circular via np.roll)
    rng = np.random.default_rng(4)
    plane = 0.1 * rng.standard_normal((8, 2048)).astype(np.float32)
    plane[1, 2046:] += 5.0
    plane[1, :1] += 5.0
    _check(plane)


def test_large_dc_offset():
    # the round-4 mean-fold lesson: raw block sums cancel at large DC;
    # the centered accumulation must stay accurate.  Tolerance note: at
    # DC 1e5 the XLA reference ITSELF quantises — float32 ``x - mean``
    # with x ~ 1e5 rounds to 1/128 steps (visible in its outputs), while
    # the kernel's centered accumulation keeps full precision — so the
    # two agree only to the reference's own quantisation (~3e-3
    # relative), and float64 NumPy scoring confirms the kernel is the
    # closer of the two
    rng = np.random.default_rng(5)
    plane = (1e5 + rng.standard_normal((8, 2048))).astype(np.float32)
    got = _pallas(plane, True)
    want = _reference(plane, True)
    for row, name in ((0, "max"), (1, "std"), (2, "snr"), (5, "cert")):
        np.testing.assert_allclose(got[row], want[row], rtol=6e-3,
                                   atol=1e-5, err_msg=name)
    # float64 ground truth: the kernel's width-1 max must beat the XLA
    # scorer's distance to it
    x64 = plane.astype(np.float64)
    true_max = (x64 - x64.mean(axis=1, keepdims=True)).max(axis=1)
    assert (np.abs(got[0] - true_max).mean()
            <= np.abs(want[0] - true_max).mean() + 1e-6)


def test_injected_pulse_scores_and_peak():
    rng = np.random.default_rng(6)
    plane = rng.standard_normal((24, 4096)).astype(np.float32)
    plane[7, 1000:1004] += 6.0  # width-4 pulse, block-aligned at 1000
    got = _pallas(plane, True)
    assert got[2, 7] > 10
    assert got[3, 7] in (4.0, 8.0)
    assert abs(got[4, 7] - 1000) <= 8
    _check(plane)


@pytest.mark.parametrize("with_cert", [False, True])
@pytest.mark.parametrize("rows,padded", [(41, 48), (24, 32), (5, 16),
                                         (46, 46)])
def test_rows_scores_a_prefix_of_a_padded_plane(rows, padded, with_cert):
    """The FDMT sweep hands its last kernel's output with the padded rows
    in place (ISSUE 37): the first ``rows`` rows score exactly as the
    plane cut to them does, 8-aligned prefix through the kernel, the
    remainder through the XLA scorer; the rows after them are not read
    (NaN there changes nothing)."""
    rng = np.random.default_rng(rows)
    plane = rng.standard_normal((padded, 2048)).astype(np.float32)
    plane[rows:] = np.nan
    want = _pallas(plane[:rows], with_cert)
    got = np.asarray(score_plane_pallas(jnp.asarray(plane),
                                        with_cert=with_cert,
                                        interpret=True, rows=rows))
    assert got.shape == (6 if with_cert else 5, rows)
    np.testing.assert_array_equal(got, want)


def test_chained_sweep_scores_remainder_rows_as_the_flat_path():
    """A sweep whose final row count is 5 x 8 + 1, its last kernel padded
    to 48 rows: the coarse scores, remainder row included, and the
    captured plane are those of the same scorer behind the flat per-level
    merges, bit for bit (whole programs both: two programs compiled apart
    may contract a multiply-add differently on the CPU)."""
    from pulsarutils_tpu.ops import fdmt

    nchan, t, hi = 16, 2048, 40
    data = np.random.default_rng(41).standard_normal(
        (nchan, t)).astype(np.float32)

    def build(use_pallas, **kw):
        return fdmt._build_transform(
            nchan, 1200.0, 200.0, hi, t, fdmt._pick_fdmt_tile(t),
            use_pallas, True, n_lo=0, t_orig=t, **kw)

    kw = dict(with_scores=True, with_plane=True, with_cert=True,
              use_score=True)
    (got, got_plane), (want, want_plane) = build(True, **kw)(data), build(
        False, **kw)(data)
    assert got_plane.shape == (41, t) and got.shape == (6, 41)
    np.testing.assert_array_equal(np.asarray(got_plane),
                                  np.asarray(want_plane))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unsupported_tile_raises():
    plane = np.zeros((8, 1000), np.float32)
    with pytest.raises(ValueError):
        score_plane_pallas(jnp.asarray(plane), interpret=True)


def test_wired_into_transform():
    # use_score routes the coarse sweep's scoring through the kernel
    # (interpret mode here); the coarse scores must match the
    # XLA-scored program on selection rows and to f32 order on floats
    import jax

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.search import unstack_scores

    rng = np.random.default_rng(7)
    data = rng.standard_normal((64, 2048)).astype(np.float32)
    data[:, 700] += 3.0
    nchan, t = data.shape
    _, n_lo, n_hi = fdmt.fdmt_trial_dms(nchan, 20.0, 80.0, 1200.0, 200.0,
                                        0.001)

    def scores(use_score):
        run = fdmt._build_transform(
            nchan, 1200.0, 200.0, n_hi, t, fdmt._pick_fdmt_tile(t), False,
            True, n_lo=n_lo, with_scores=True, with_plane=False, t_orig=t,
            with_cert=True, use_score=use_score)
        assert ("score_rows" in str(jax.make_jaxpr(run)(data))) == use_score
        return unstack_scores(run(jnp.asarray(data)))

    got, want = scores(True), scores(False)
    for i in (0, 1, 2, 5):  # max, std, snr, cert
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(want[i]), rtol=2e-4,
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))


def test_over_2pow24_series_warns_peak_inexact(monkeypatch):
    # ADVICE r5: float32 peak accumulation in the kernel is exact only
    # below 2^24 samples; the XLA scorer warned, the Pallas path
    # silently accepted any tile-divisible T.  The kernel invocation is
    # stubbed (a real 8 x 2^25 plane is a 1 GiB allocation) — the
    # warning must fire in the wrapper BEFORE any kernel work.
    from pulsarutils_tpu.ops import score_pallas

    t = 1 << 25
    calls = []

    def fake_kernel(rows_p, t_, t_blk, with_cert, interpret, sub, **ladder):
        calls.append((rows_p, t_, t_blk))
        return jnp.zeros((rows_p, 128), jnp.float32)

    monkeypatch.setattr(score_pallas, "_kernel_scores", fake_kernel)
    plane = np.broadcast_to(np.float32(0.0), (8, t))  # zero-strided view
    with pytest.warns(UserWarning, match="2\\^24"):
        out = score_plane_pallas(plane, with_cert=False)
    assert calls and calls[0][1] == t  # the stub ran (wrapper reached it)
    assert out.shape == (5, 8)

    # under the limit: no warning
    import warnings as _warnings

    small = np.zeros((8, 2048), np.float32)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        score_plane_pallas(jnp.asarray(small), with_cert=False,
                           interpret=True)
