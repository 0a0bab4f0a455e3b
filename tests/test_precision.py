"""Precision-policy engine (ISSUE 17): strategy registry, exactness
domain, compensated/split accumulation vs a float64 oracle, the f32
byte-identity escape hatch and the (kernel, policy) autotune ledger.

The property tests feed the classical adversaries of naive f32
summation — a large DC pedestal, alternating-sign cancellation, and a
uniform stream longer than 2^24 samples (where ``x + 1.0 == x`` at
f32) — and assert each strategy lands inside its DOCUMENTED bound
(``Strategy.error_bound``), not merely "close".
"""

import warnings

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from pulsarutils_tpu.io.lowbit import accum_dtype  # noqa: E402
from pulsarutils_tpu.ops.search import (  # noqa: E402
    dedispersion_search,
    warn_peak_exactness,
)
from pulsarutils_tpu.precision import (  # noqa: E402
    EPS_F32,
    F32_EXACT_INT_BOUND,
    STRATEGIES,
    cast_operand,
    engage,
    exactness_domain,
    neumaier_sum,
    policy_name,
    resolve_policy,
    split_sum,
)
from pulsarutils_tpu.tuning import autotune  # noqa: E402
from pulsarutils_tpu.tuning.cache import TuneCache  # noqa: E402


# -- exactness domain: the ONE 2^24 rule --------------------------------------

def test_integer_ladder_matches_lowbit_accum_dtype():
    # satellite (a): io/lowbit.py delegates — the two sites can't drift
    for nbits in (1, 2, 4, 8):
        for nchan in (16, 64, 1024, 4096, 1 << 22):
            dom = exactness_domain(nchan, nbits=nbits)
            assert accum_dtype(nbits, nchan) == dom.accum_dtype
            assert dom.code_peak == ((1 << nbits) - 1) * nchan


def test_integer_ladder_boundaries():
    # int16 while peak < 2^15, int32 while peak < 2^24, else float
    assert exactness_domain(1, nbits=15).accum_dtype == "int16"  # 2^15-1
    assert exactness_domain(1, nbits=16).accum_dtype == "int32"  # 2^16-1
    assert exactness_domain((1 << 15) - 1, nbits=1).accum_dtype == "int16"
    assert exactness_domain(1 << 15, nbits=1).accum_dtype == "int32"
    assert exactness_domain((1 << 24) - 1, nbits=1).accum_dtype == "int32"
    assert exactness_domain(1 << 24, nbits=1).accum_dtype is None


def test_peak_index_domain_and_warning_agree():
    n_ok = F32_EXACT_INT_BOUND
    n_bad = F32_EXACT_INT_BOUND + 1
    assert exactness_domain(1, nsamples=n_ok).peak_index_exact
    dom = exactness_domain(1, nsamples=n_bad)
    assert not dom.peak_index_exact
    assert dom.index_error_samples > 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        warn_peak_exactness(n_ok)  # must not raise
    with pytest.warns(UserWarning, match="2\\^24"):
        warn_peak_exactness(n_bad)


def test_overflow_averted_metric_counts():
    from pulsarutils_tpu.obs.metrics import REGISTRY

    def count():
        return sum(r["value"] for r in REGISTRY.snapshot()
                   if r["name"] == "putpu_precision_overflow_averted_total")

    before = count()
    exactness_domain(1 << 24, nbits=1)
    assert count() == before + 1


# -- the strategy registry ----------------------------------------------------

def test_registry_names_and_bounds():
    assert set(STRATEGIES) == {"f32", "f32_compensated", "split_f32",
                               "bf16_operand_f32_accum"}
    n = 4096
    plain = STRATEGIES["f32"].error_bound(n)
    comp = STRATEGIES["f32_compensated"].error_bound(n)
    split = STRATEGIES["split_f32"].error_bound(n)
    # the compensated strategies beat plain f32 by orders of magnitude
    # (Neumaier's n^2*eps^2 second-order term caps the win at large n),
    # and split's bound is tighter than Neumaier's
    assert comp < plain / 100
    assert split <= comp
    # bf16 trades operand precision: worse than plain f32's bound at
    # small n, bounded by ~half a bf16 ulp
    assert STRATEGIES["bf16_operand_f32_accum"].error_bound(2) > plain
    assert STRATEGIES["bf16_operand_f32_accum"].score_rtol > \
        STRATEGIES["f32"].score_rtol


def test_policy_name_validation():
    assert policy_name(None) == "f32"
    assert policy_name("auto") == "auto"
    assert policy_name("split_f32") == "split_f32"
    with pytest.raises(ValueError, match="unknown precision policy"):
        policy_name("f16_fast")


def test_resolve_policy_env_and_explicit(monkeypatch):
    monkeypatch.delenv("PUTPU_PRECISION", raising=False)
    assert resolve_policy() == "f32"
    monkeypatch.setenv("PUTPU_PRECISION", "f32_compensated")
    assert resolve_policy() == "f32_compensated"
    # explicit beats env
    assert resolve_policy("bf16_operand_f32_accum") == \
        "bf16_operand_f32_accum"
    monkeypatch.setenv("PUTPU_PRECISION", "not-a-policy")
    with pytest.raises(ValueError):
        resolve_policy()


def test_engage_counts_compensated_only():
    from pulsarutils_tpu.obs.metrics import REGISTRY

    def count():
        return sum(r["value"] for r in REGISTRY.snapshot()
                   if r["name"]
                   == "putpu_precision_compensated_engagements_total")

    before = count()
    engage("f32")
    engage("bf16_operand_f32_accum")  # plain accumulator: no count
    assert count() == before
    engage("split_f32")
    assert count() == before + 1


def test_cast_operand_is_noop_for_f32_strategies():
    x = jnp.arange(8, dtype=jnp.float32)
    assert cast_operand(x, "f32", jnp) is x
    assert cast_operand(x, "f32_compensated", jnp) is x
    y = cast_operand(x, "bf16_operand_f32_accum", jnp)
    assert y.dtype == jnp.bfloat16


# -- property tests vs the float64 oracle -------------------------------------

def _rel_err(approx, x64):
    exact = x64.sum()
    scale = np.abs(x64).sum()
    return abs(float(approx) - float(exact)) / float(scale)


def _adversaries():
    rng = np.random.default_rng(171)
    n = 1 << 16
    # large DC pedestal: every addend rounds against a ~1e7 partial
    dc = (1e7 + rng.standard_normal(n)).astype(np.float32)
    # alternating-sign cancellation: huge sum(|x|), tiny true sum
    alt = rng.standard_normal(n).astype(np.float32)
    alt[::2] *= -1.0
    alt *= 1e4
    return {"dc_offset": dc, "alternating": alt}


@pytest.mark.parametrize("case", sorted(_adversaries()))
@pytest.mark.parametrize("xp_name", ["np", "jnp"])
def test_compensated_and_split_meet_bounds(case, xp_name):
    x = _adversaries()[case]
    xp = np if xp_name == "np" else jnp
    x64 = x.astype(np.float64)
    n = x.size
    for name, fn in (("f32_compensated", neumaier_sum),
                     ("split_f32", split_sum)):
        got = np.asarray(fn(xp.asarray(x), axis=-1, xp=xp))
        err = _rel_err(got, x64)
        # documented bound + the final f32 store (result rounds once)
        bound = STRATEGIES[name].error_bound(n) + EPS_F32
        assert err <= bound, (case, name, err, bound)


def test_compensated_beats_plain_on_dc_offset():
    x = _adversaries()["dc_offset"]
    x64 = x.astype(np.float64)
    # sequential f32 (what a scan carry does — np.sum's pairwise tree
    # would hide the failure)
    plain = x.cumsum(dtype=np.float32)[-1]
    comp = neumaier_sum(x, axis=-1, xp=np)
    assert _rel_err(comp, x64) < _rel_err(plain, x64) / 10


@pytest.mark.slow
def test_split_sum_exact_on_beyond_2pow24_stream():
    # 2^24 + 8192 ones: plain f32 accumulation stagnates at 2^24
    # (1.0 vanishes against the partial); the two-float tree is exact
    n = (1 << 24) + 8192
    x = np.ones(n, dtype=np.float32)
    plain = np.empty((), np.float32)
    plain = x.cumsum(dtype=np.float32)[-1]
    assert float(plain) == float(1 << 24)  # the failure being fixed
    assert float(split_sum(x, axis=-1, xp=np)) == float(n)


def test_neumaier_blockwise_on_beyond_2pow24_partials():
    # the roll-scan shape of the same failure: 4096 block partials of
    # 4096.0 each (total 2^24) plus a tail block of 1.0s — a plain f32
    # reduction of the partials loses the tail; Neumaier keeps it
    partials = np.full(4098, 4096.0, dtype=np.float32)
    partials[-2:] = 1.0
    exact = 4096.0 * 4096 + 2.0
    plain = np.float32(0.0)
    for p in partials:
        plain = np.float32(plain + p)
    assert float(plain) == float(1 << 24)  # tail lost
    assert float(neumaier_sum(partials, axis=-1, xp=np)) == exact
    got = np.asarray(neumaier_sum(jnp.asarray(partials), axis=-1, xp=jnp))
    assert float(got) == exact


# -- dispatch-surface integration --------------------------------------------

def _problem(seed=5, nchan=32, nsamples=4096, ndm=12):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((nchan, nsamples)).astype(np.float32)
    dms = np.linspace(300.0, 330.0, ndm)
    return data, dms, (1200.0, 200.0, 0.0005)


COLS = ("DM", "max", "std", "snr", "rebin", "peak")


def test_default_is_byte_identical_to_explicit_f32(monkeypatch):
    # THE escape hatch: with tuning off, precision=None (pre-PR code
    # path: policy never threads in), precision="f32" and
    # precision="auto" all produce byte-identical columns
    monkeypatch.setenv("PUTPU_AUTOTUNE", "off")
    monkeypatch.delenv("PUTPU_PRECISION", raising=False)
    data, dms, geom = _problem()
    ref = dedispersion_search(data, None, None, *geom, backend="jax",
                              trial_dms=dms)
    for pol in ("f32", "auto"):
        got = dedispersion_search(data, None, None, *geom, backend="jax",
                                  trial_dms=dms, precision=pol)
        for col in COLS:
            np.testing.assert_array_equal(np.asarray(got[col]),
                                          np.asarray(ref[col]), err_msg=col)


@pytest.mark.parametrize("formulation", ["roll", "gather"])
@pytest.mark.parametrize("policy", ["f32_compensated", "split_f32",
                                    "bf16_operand_f32_accum"])
def test_policies_preserve_discrete_hits(formulation, policy, monkeypatch):
    monkeypatch.setenv("PUTPU_AUTOTUNE", "off")
    data, dms, geom = _problem()
    # inject a pulse so the peak is physical, not a noise razor edge
    data[:, 1000:1003] += 6.0
    ref = dedispersion_search(data, None, None, *geom, backend="jax",
                              trial_dms=dms, kernel=formulation)
    got = dedispersion_search(data, None, None, *geom, backend="jax",
                              trial_dms=dms, kernel=formulation,
                              precision=policy)
    np.testing.assert_array_equal(np.asarray(got["rebin"]),
                                  np.asarray(ref["rebin"]))
    np.testing.assert_array_equal(np.asarray(got["peak"]),
                                  np.asarray(ref["peak"]))
    rtol = STRATEGIES[policy].score_rtol
    np.testing.assert_allclose(np.asarray(got["snr"]),
                               np.asarray(ref["snr"]), rtol=rtol)


def test_bf16_sweep_meets_its_bound_against_a_float64_oracle():
    """``bf16_operand_f32_accum`` end to end through the gather sweep: its
    best candidate is plain f32's, at the injected trial, and its
    dedispersed profile there stays inside the strategy's documented
    bound (relative to the per-sample sum of absolute operands) of a
    float64 roll-and-add."""
    from pulsarutils_tpu.ops.search import _offsets_for
    from pulsarutils_tpu.tuning.autotune import synthetic_chunk

    nchan, nsamples, ndm = 16, (1 << 16) + 512, 8
    geom = (1400.0, 400.0, 5e-4)
    dms = np.linspace(40.0, 80.0, ndm)
    offsets = _offsets_for(dms, nchan, *geom, nsamples)
    inj = ndm // 2
    data = synthetic_chunk(nchan, nsamples, offsets[inj], seed=21)

    def run(policy, capture=False):
        return dedispersion_search(data, None, None, *geom, backend="jax",
                                   trial_dms=dms, kernel="gather",
                                   precision=policy, capture_plane=capture)

    def best(tbl):
        i = tbl.argbest("snr")
        return i, int(tbl["rebin"][i]), int(tbl["peak"][i])

    t_bf16, plane = run("bf16_operand_f32_accum", capture=True)
    assert best(t_bf16) == best(run("f32")) and best(t_bf16)[0] == inj

    prof64 = np.zeros(nsamples)
    abs64 = np.zeros(nsamples)
    for c in range(nchan):
        rolled = np.roll(data[c].astype(np.float64), -int(offsets[inj, c]))
        prof64 += rolled
        abs64 += np.abs(rolled)
    bound = STRATEGIES["bf16_operand_f32_accum"].error_bound(nchan)
    got = np.asarray(plane[inj], dtype=np.float64)
    assert (np.abs(got - prof64) <= bound * abs64 + 1e-6).all()


def test_policy_rejected_on_non_policy_backends():
    data, dms, geom = _problem()
    with pytest.raises(ValueError, match="precision"):
        dedispersion_search(data, None, None, *geom, backend="numpy",
                            trial_dms=dms, precision="split_f32")
    with pytest.raises(ValueError, match="precision"):
        dedispersion_search(data, None, None, *geom, backend="jax",
                            trial_dms=dms, kernel="fdmt",
                            precision="f32_compensated")


def test_autotuned_policy_ledger_names_kernel_policy_pair(monkeypatch):
    # PR 7 contract: the ledger/BUDGET_JSON names the winning
    # (kernel, policy) PAIR, and a winner is cached only after the
    # exact-hit-match harness passed (resolve() enforces equiv before
    # caching; a cached decision implies a passed harness)
    monkeypatch.delenv("PUTPU_AUTOTUNE", raising=False)
    prev = autotune.set_tuner(autotune.KernelTuner(
        cache=TuneCache(None), mode="on", min_elements=0))
    try:
        mark = len(autotune.decisions_since(0))
        data, dms, geom = _problem()
        pair = autotune.resolve_search_policy(
            "roll", data.shape[0], data.shape[1], len(dms), *geom, dms)
        kern, pol = pair.split("+", 1)
        assert kern == "roll"
        assert pol in STRATEGIES
        recs = autotune.decisions_since(mark)
        assert any(r["kernel"] == pair and "-precision|" in r["key"]
                   for r in recs)
        # measured walls cover the full candidate set
        rec = next(r for r in recs if r["kernel"] == pair)
        assert set(rec["measured_s"]) == {
            f"roll+{name}" for name in STRATEGIES}
    finally:
        autotune.set_tuner(prev)


def test_autotune_off_resolves_static_f32_pair(monkeypatch):
    monkeypatch.setenv("PUTPU_AUTOTUNE", "off")
    prev = autotune.set_tuner(autotune.KernelTuner(cache=TuneCache(None)))
    try:
        data, dms, geom = _problem()
        pair = autotune.resolve_search_policy(
            "gather", data.shape[0], data.shape[1], len(dms), *geom, dms)
        assert pair == "gather+f32"
    finally:
        autotune.set_tuner(prev)
