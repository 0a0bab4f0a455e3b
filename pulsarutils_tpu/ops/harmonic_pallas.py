"""One-pass Pallas kernel for the periodicity hot loop (ISSUE 17).

The XLA chain (:func:`..ops.periodicity.normalize_power` ->
:func:`..ops.periodicity.score_normalized_power`) is the memory-bound
half of the periodicity search (the PulsarX point, arxiv 2309.02544):
the median-normalise materialises a normalised copy of the spectrum and
every harmonic depth re-reads it through a strided gather.  This kernel
fuses the whole chain for one 8-row block of spectra: the raw power rows
are read into VMEM ONCE, median-normalised in place, and the incremental
harmonic stack accumulates VMEM-resident partials in the accumulation
dtype the active :mod:`..precision` policy declares (plain f32,
TwoSum-compensated f32 pairs, or bf16 operands with an f32 accumulator).
Only the per-depth (peak value, peak bin) pairs leave the kernel — the
host-side wrapper reconstructs the false-alarm/sigma chain with the
IDENTICAL XLA ops.  Discrete fields (peak bin, frequency bin, harmonic
depth) match the XLA scorer exactly: the harmonic addends are generated
in the same order with the same values (the stride-``j`` slice
``norm[:, ::j]`` zero-padded to ``nbins`` IS ``_add_harmonic``'s
gather).  Score floats agree to within one f32 ulp — XLA may fuse the
``p / (med / ln2)`` normalise differently across the two programs
(reciprocal-multiply vs true divide), a data-dependent last-bit
difference that uniformly scales a row and does not move an argmax
(the equivalence harness gates the razor-edge tie case anyway) — so
the identity tests pin discrete fields exactly and scores at tight
``allclose`` tolerance, the same contract the autotuner harness gates.

Like :mod:`.pallas_dedisperse`, the kernel is developed and tested in
interpret mode on CPU (``tests/test_harmonic_pallas.py`` pins identity
on host, under jit, and on the (4,2)/(2,4) CPU meshes); on TPU it runs
compiled.  The in-kernel ``jnp.median`` (a per-row sort of the spectrum)
is the part most likely to need a Mosaic workaround on real hardware —
it is deliberately kept at the top of the kernel so a TPU-side rewrite
(bucketed histogram median) swaps in without touching the stack.

Registered as a scoring candidate through
:func:`~pulsarutils_tpu.tuning.autotune.resolve_harmonic_kernel`
(``kernel="auto"`` in ``_spectral_chunk``): a Pallas win is only ever
cached after the identity harness passes — discrete top-cell fields
exact, scores within the declared tolerance.
"""

from __future__ import annotations

import functools

import numpy as np

from .periodicity import (HARMONIC_SUMS, _LN2, power_sf_log, power_spectrum,
                          sf_log_to_sigma)

#: rows per grid cell (the f32 sublane width — one VMEM tile of rows)
_ROW_BLK = 8


def _pallas_modules():
    from jax.experimental import pallas as pl

    return pl


@functools.lru_cache(maxsize=64)
def _build_harmonic_kernel(rows_p, nbins, depths, lo, hi, policy, interpret):
    """Compile (or interpret) the fused normalize+stack kernel.

    Static key: padded row count, spectrum width, harmonic depth
    schedule, band ``[lo, hi)``, precision policy name and interpret
    flag.  Outputs per 8-row block: ``(8, 128)`` f32 peak values and
    ``(8, 128)`` int32 peak bins, lane ``k`` = depth ``depths[k]``.
    """
    import jax
    import jax.numpy as jnp

    pl = _pallas_modules()

    compensated = policy in ("f32_compensated", "split_f32")
    bf16 = policy == "bf16_operand_f32_accum"

    def kernel(p_ref, val_ref, idx_ref):
        p = p_ref[...]  # (8, nbins) raw power, DC bin already zeroed
        # normalize_power, verbatim: median over bins [1:], ln2 scaling
        med = jnp.median(p[:, 1:], axis=-1, keepdims=True)
        norm = p / jnp.where(med > 0, med / _LN2, 1.0)
        # the bf16_operand_f32_accum strategy's cast, inside the traced
        # kernel body where the host-side cast_operand seam cannot reach
        gath = (norm.astype(jnp.bfloat16)  # putpu-lint: disable=bf16-cast — policy-gated (bf16_operand_f32_accum)
                if bf16 else norm)

        col = jax.lax.broadcasted_iota(jnp.int32, (_ROW_BLK, nbins), 1)
        band = ((col >= lo) & (col < hi)).astype(norm.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, (_ROW_BLK, 128), 1)

        acc = jnp.zeros_like(norm)
        comp = jnp.zeros_like(norm) if compensated else None
        vals = jnp.zeros((_ROW_BLK, 128), jnp.float32)
        idxs = jnp.zeros((_ROW_BLK, 128), jnp.int32)

        depth = 0
        for k, h in enumerate(depths):
            for j in range(depth + 1, h + 1):
                # harmonic j of fundamental i is bin i*j: the stride-j
                # slice zero-padded to nbins — same addends, same
                # order, as _add_harmonic's gather
                g = gath[:, ::j]
                v = jnp.pad(g.astype(jnp.float32),
                            ((0, 0), (0, nbins - g.shape[1])))
                if compensated:
                    s = acc + v
                    bp = s - acc
                    comp = comp + ((acc - (s - bp)) + (v - bp))
                    acc = s
                else:
                    acc = acc + v
            depth = h
            hsum = (acc + comp if compensated else acc) * band
            peak = jnp.argmax(hsum, axis=-1)
            pval = jnp.take_along_axis(hsum, peak[:, None], axis=-1)[:, 0]
            vals = jnp.where(lane == k, pval[:, None], vals)
            idxs = jnp.where(lane == k, peak.astype(jnp.int32)[:, None],
                             idxs)
        val_ref[...] = vals
        idx_ref[...] = idxs

    n_rb = rows_p // _ROW_BLK
    return pl.pallas_call(
        kernel,
        grid=(n_rb,),
        in_specs=[pl.BlockSpec((_ROW_BLK, nbins), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((_ROW_BLK, 128), lambda i: (i, 0)),
                   pl.BlockSpec((_ROW_BLK, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, 128), jnp.float32),
                   jax.ShapeDtypeStruct((rows_p, 128), jnp.int32)],
        interpret=bool(interpret),
        name="harmonic_sum",
    )


def score_power_pallas(power, nsamples, tsamp, max_harmonics=16, fmin=None,
                       fmax=None, policy=None, interpret=None):
    """Pallas analogue of ``normalize_power`` -> ``score_normalized_power``.

    ``power`` is the RAW ``(rows, nbins)`` power spectrum (DC zeroed,
    un-normalised — normalisation happens inside the kernel, one VMEM
    pass).  Returns the same dict as
    :func:`..ops.periodicity.score_normalized_power`: ``freq, power,
    nharm, log_sf, sigma`` per row.  ``interpret=None`` auto-selects
    interpret mode off-TPU, like :mod:`.pallas_dedisperse`.
    """
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    power = jnp.asarray(power, dtype=jnp.float32)
    rows, nbins = power.shape
    t = int(nsamples)

    # band edges: verbatim score_normalized_power
    lo = 1 if fmin is None else max(1, int(np.ceil(fmin * t * tsamp)))
    hi = (nbins if fmax is None
          else min(nbins, int(fmax * t * tsamp) + 1))  # putpu-lint: disable=device-trip — host band-edge scalars
    depths = tuple(h for h in HARMONIC_SUMS if h <= int(max_harmonics))

    name = "f32"
    if policy not in (None, "f32"):
        from ..precision import policy_name

        name = policy_name(policy)

    rows_p = -(-rows // _ROW_BLK) * _ROW_BLK
    if rows_p != rows:
        # benign padding rows: all-ones spectra (positive median, so
        # the normalise never divides by zero); sliced off below
        pad = jnp.ones((rows_p - rows, nbins), jnp.float32)
        power_p = jnp.concatenate([power, pad], axis=0)
    else:
        power_p = power
    run = _build_harmonic_kernel(rows_p, nbins, depths, lo, hi, name,
                                 bool(interpret))
    vals, idxs = run(power_p)
    vals, idxs = vals[:rows], idxs[:rows]

    # best-depth selection with the IDENTICAL XLA ops (bit-parity with
    # score_normalized_power's loop under the same policy)
    freqs = jnp.arange(nbins) / (t * tsamp)
    best_logsf = jnp.full((rows,), jnp.inf)
    best_freq = jnp.zeros((rows,))
    best_power = jnp.zeros((rows,))
    best_nharm = jnp.zeros((rows,), dtype=jnp.int32)
    for k, h in enumerate(depths):
        pval = vals[:, k]
        peak = idxs[:, k]
        log_sf = power_sf_log(pval, nsum=h, xp=jnp)
        better = log_sf < best_logsf
        best_logsf = jnp.where(better, log_sf, best_logsf)
        best_freq = jnp.where(better, jnp.take(freqs, peak), best_freq)
        best_power = jnp.where(better, pval, best_power)
        best_nharm = jnp.where(better, h, best_nharm)
    return {
        "freq": best_freq,
        "power": best_power,
        "nharm": best_nharm,
        "log_sf": best_logsf,
        "sigma": sf_log_to_sigma(best_logsf, xp=jnp),
    }


def spectral_search_pallas(plane, tsamp, max_harmonics=16, fmin=None,
                           fmax=None, policy=None, interpret=None):
    """Pallas counterpart of :func:`..ops.periodicity.spectral_search`.

    The batched rFFT stays on XLA (it is MXU/FFT-library territory);
    the normalise+harmonic-stack scoring runs in the fused kernel.
    """
    import jax.numpy as jnp

    plane = jnp.asarray(plane, dtype=jnp.float32)
    t = plane.shape[-1]
    power = power_spectrum(plane, xp=jnp)
    return score_power_pallas(power, t, tsamp,
                              max_harmonics=max_harmonics, fmin=fmin,
                              fmax=fmax, policy=policy,
                              interpret=interpret)
