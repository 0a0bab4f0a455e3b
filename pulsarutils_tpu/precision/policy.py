"""Named accumulation-precision strategies and the exactness-domain rule.

This module is the single owner of every dtype/accumulation decision that
used to be hard-coded across the dispatch surfaces:

* the exact-integer accumulator ladder (``io/lowbit.py:accum_dtype``),
* the 2^24 float32 peak-index exactness bound (``ops/search.py``
  ``warn_peak_exactness`` and the ``score_plane_pallas`` wrapper),
* the float32-everywhere default of the dedispersion and periodicity
  reductions.

Strategies
----------
``f32``
    Plain float32 operands + float32 accumulation.  The byte-identical
    default: every dispatch surface treats ``policy=None`` and
    ``policy="f32"`` as "run the pre-existing code path unchanged".
``f32_compensated``
    Neumaier (improved Kahan) compensated summation: a two-float
    (sum, compensation) carry threaded through the roll-scan and gather
    reductions.  Error is O(eps) independent of n.
``split_f32``
    Two-float pairwise summation: a tree reduction whose nodes combine
    with Knuth TwoSum and carry the rounding error in a second float.
    Built for >2^24-sample regimes where even the reduction *depth*
    matters; error is O(eps) with an O(n·eps²) tail.
``bf16_operand_f32_accum``
    Operands cast to bfloat16 (halving memory traffic on bandwidth-bound
    sweeps), accumulated in float32.  Error is dominated by the bf16
    half-ulp (2^-8) per operand.

Every non-default strategy is registered as an autotuner candidate and
only ever wins after passing the exact-hit-match harness — discrete
fields exact, scores within the strategy's stated ``score_rtol``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

__all__ = [
    "EPS_BF16",
    "EPS_F32",
    "F32_EXACT_INT_BOUND",
    "STRATEGIES",
    "ExactnessDomain",
    "Strategy",
    "cast_operand",
    "engage",
    "exactness_domain",
    "neumaier_sum",
    "policy_name",
    "resolve_policy",
    "split_sum",
]

# Machine epsilons (unit roundoff is eps/2 under round-to-nearest).
EPS_F32 = float(np.finfo(np.float32).eps)  # 2^-23
# bfloat16 significand is 8 bits (incl. hidden), so machine epsilon is
# 2^(1-8); the per-operand rounding bound below uses the unit roundoff
# eps/2 = 2^-8.  (``tests/test_precision.py`` checks a real sweep
# against this bound — a too-tight value fails there, not in production.)
EPS_BF16 = 2.0 ** -7

# Largest contiguous integer range float32 represents exactly.  This is
# THE 2^24 bound: both ``exactness_domain`` consumers (the low-bit
# accumulator ladder and the peak-index warning) derive from it.
F32_EXACT_INT_BOUND = 1 << 24

_ENV_POLICY = "PUTPU_PRECISION"


class ExactnessDomain(NamedTuple):
    """Where a reduction stays *exact*, for a given geometry.

    ``accum_dtype``
        Narrowest exact integer accumulator for summing ``nchan``
        ``nbits``-bit channel codes (``None`` when no integer dtype in
        the ladder holds the peak — callers fall back to float32).
    ``code_peak``
        Worst-case integer channel sum, ``((1 << nbits) - 1) * nchan``
        (0 when ``nbits`` is not given).
    ``peak_index_exact``
        True while float32 represents every sample index in
        ``[0, nsamples)`` exactly, i.e. ``nsamples <= 2^24``.
    ``index_error_samples``
        Worst-case peak-index slip in samples once exactness is lost
        (0.0 while ``peak_index_exact``).
    """

    accum_dtype: Optional[str]
    code_peak: int
    peak_index_exact: bool
    index_error_samples: float


def exactness_domain(nchan: int, nsamples: int = 0,
                     nbits: Optional[int] = None) -> ExactnessDomain:
    """Single-owner exactness rule replacing both hard-coded 2^24 sites.

    ``io/lowbit.py:accum_dtype`` consumes ``accum_dtype`` /
    ``code_peak``; ``ops/search.py:warn_peak_exactness`` (and through it
    the ``score_plane_pallas`` wrapper) consumes ``peak_index_exact`` /
    ``index_error_samples``.
    """
    acc = None
    peak = 0
    if nbits is not None:
        peak = ((1 << int(nbits)) - 1) * int(nchan)
        if peak < (1 << 15):
            acc = "int16"
        elif peak < F32_EXACT_INT_BOUND:
            acc = "int32"
        else:
            acc = None
            counter("putpu_precision_overflow_averted_total").inc()
    exact = int(nsamples) <= F32_EXACT_INT_BOUND
    err = 0.0 if exact else float(nsamples) / F32_EXACT_INT_BOUND
    return ExactnessDomain(acc, peak, exact, err)


@dataclass(frozen=True)
class Strategy:
    """One named accumulation strategy.

    ``error_bound(n)`` returns the documented worst-case error of
    summing ``n`` terms, *relative to* ``sum(|x_i|)`` — the classical
    normalisation under which compensated-summation bounds are stated.
    ``score_rtol`` is the tolerance the autotuner equivalence harness
    grants this strategy's float score columns (discrete fields must
    always match exactly regardless).
    """

    name: str
    operand_dtype: str  # "float32" | "bfloat16"
    accumulator: str  # "plain" | "compensated" | "split"
    score_rtol: float
    summary: str

    def error_bound(self, n: int) -> float:
        """Worst-case |sum_strategy - sum_exact| / sum(|x_i|)."""
        n = max(int(n), 1)
        if self.name == "f32":
            return (n - 1) * EPS_F32
        if self.name == "f32_compensated":
            # Neumaier: 2*eps + O(n^2 * eps^2)  (Higham, ASNA thm 4.3).
            return 2.0 * EPS_F32 + (n ** 2) * EPS_F32 ** 2
        if self.name == "split_f32":
            # TwoSum-carrying pairwise tree: the hi+lo pair is exact at
            # every node; only the final renormalisation and the lo-sum
            # rounding contribute.
            return 2.0 * EPS_F32 + n * EPS_F32 ** 2
        if self.name == "bf16_operand_f32_accum":
            # Half-ulp bf16 operand rounding + plain f32 accumulation.
            return 0.5 * EPS_BF16 + (n - 1) * EPS_F32
        raise ValueError(f"unknown strategy {self.name!r}")


STRATEGIES = {
    s.name: s
    for s in (
        Strategy(
            name="f32",
            operand_dtype="float32",
            accumulator="plain",
            score_rtol=1e-4,
            summary="plain float32 operands + accumulation (default)",
        ),
        Strategy(
            name="f32_compensated",
            operand_dtype="float32",
            accumulator="compensated",
            score_rtol=1e-4,
            summary="Neumaier compensated carry through scan/gather sums",
        ),
        Strategy(
            name="split_f32",
            operand_dtype="float32",
            accumulator="split",
            score_rtol=1e-4,
            summary="two-float pairwise tree for >2^24-sample regimes",
        ),
        Strategy(
            name="bf16_operand_f32_accum",
            operand_dtype="bfloat16",
            accumulator="plain",
            score_rtol=5e-2,
            summary="bfloat16 operands, float32 accumulation (bandwidth)",
        ),
    )
}


def policy_name(policy: Optional[str]) -> str:
    """Canonicalise ``policy``: ``None`` means the default ``f32``."""
    name = policy or "f32"
    if name != "auto" and name not in STRATEGIES:
        raise ValueError(
            f"unknown precision policy {policy!r}; expected one of "
            f"{sorted(STRATEGIES)} or 'auto'"
        )
    return name


def resolve_policy(policy: Optional[str] = None) -> str:
    """Resolve the effective policy name for a dispatch surface.

    Explicit ``policy`` wins; otherwise the ``PUTPU_PRECISION``
    environment variable; otherwise ``f32``.  The returned name may be
    ``"auto"``, in which case the caller consults the autotuner
    (``tuning.autotune.resolve_search_policy``).
    """
    name = policy_name(policy if policy else os.environ.get(_ENV_POLICY))
    counter("putpu_precision_policy_resolutions_total", policy=name).inc()
    return name


def engage(policy: Optional[str]) -> str:
    """Record that a dispatch surface engaged a non-plain strategy."""
    name = policy_name(policy)
    if name != "auto" and STRATEGIES[name].accumulator != "plain":
        counter("putpu_precision_compensated_engagements_total",
                policy=name).inc()
    return name


def cast_operand(data, policy, xp):
    """The sanctioned bf16 seam: device layers never spell jnp.bfloat16.

    Returns ``data`` cast to the strategy's operand dtype (a no-op for
    float32-operand strategies).  putpu-lint's bf16-cast checker flags
    any mixed-precision cast in ``ops/``/``parallel/`` outside this
    function, so bandwidth-motivated narrowing always flows through the
    policy engine.
    """
    name = policy_name(policy)
    strat = STRATEGIES[name]
    if strat.operand_dtype == "float32":
        return data
    return data.astype(xp.dtype(strat.operand_dtype))


def _two_sum(a, b):
    """Knuth TwoSum: s = fl(a + b) and the exact rounding error."""
    s = a + b
    bp = s - a
    err = (a - (s - bp)) + (b - bp)
    return s, err


def neumaier_sum(x, axis=-1, xp=np):
    """Compensated (Neumaier) reduction along ``axis``.

    Sequential over the reduced axis with a two-float (sum, comp)
    carry; vectorised over every other axis.  Traceable under jit when
    ``xp`` is jax.numpy (the sequential walk lowers to ``lax.scan``).
    """
    x = xp.moveaxis(xp.asarray(x), axis, 0)
    if x.shape[0] == 0:
        return xp.zeros(x.shape[1:], dtype=x.dtype)
    if xp is np:
        acc = np.array(x[0], copy=True)
        comp = np.zeros_like(acc)
        for v in x[1:]:
            s, err = _two_sum(acc, v)
            comp = comp + err
            acc = s
        return acc + comp

    import jax

    def body(carry, v):
        acc, comp = carry
        s, err = _two_sum(acc, v)
        return (s, comp + err), None

    (acc, comp), _ = jax.lax.scan(body, (x[0], x[0] - x[0]), x[1:])
    return acc + comp


def split_sum(x, axis=-1, xp=np):
    """Two-float pairwise reduction along ``axis``.

    A tree reduction whose nodes combine with TwoSum and carry rounding
    errors in a parallel "lo" array — the ``split_f32`` strategy.  The
    tree has ceil(log2 n) vectorised passes, so it stays cheap even for
    >2^24-element axes.  Traceable (loop bounds are static).
    """
    x = xp.moveaxis(xp.asarray(x), axis, 0)
    if x.shape[0] == 0:
        return xp.zeros(x.shape[1:], dtype=x.dtype)
    hi = x
    lo = xp.zeros_like(x)
    while hi.shape[0] > 1:
        n = hi.shape[0]
        even = (n // 2) * 2
        s, err = _two_sum(hi[0:even:2], hi[1:even:2])
        l = lo[0:even:2] + lo[1:even:2] + err
        if n % 2:
            s = xp.concatenate([s, hi[n - 1:n]], axis=0)
            l = xp.concatenate([l, lo[n - 1:n]], axis=0)
        hi, lo = s, l
    return hi[0] + lo[0]


class _NullCounter:
    def inc(self, n=1):
        return None


def counter(name: str, **labels):
    """Lazily fetch the obs counter (keeps precision/ import-light).

    Named ``counter`` so emission sites read as the standard facade —
    the putpu-lint name-drift checker verifies their literal metric
    names against the ``obs/names.py`` manifest.
    """
    try:
        from ..obs.metrics import counter as _obs_counter
    except ImportError:  # pragma: no cover - obs always importable in-tree
        return _NullCounter()
    return _obs_counter(name, **labels)
