"""One-pass Pallas scorer for coarse (FDMT) planes (round 5).

VERDICT r4 #3 named the fused one-pass scorer as the next FDMT lever:
round 4's stage probe (``docs/performance.md``)
measured the XLA chunked scorer at ~0.17 s standalone on the 513 x 1M
coarse plane — instruction/materialisation-bound, not traffic-bound
(the mean-subtracted copy plus the boxcar pyramid and three sliding
cert sums materialise ~9 GB of effective HBM temps against a ~2 GB
plane).  This kernel reads the plane ONCE: a grid of (8-row block,
time tile) cells accumulates per-row partial statistics in VMEM
scratch across the time tiles and emits the finished score vectors at
each row block's last tile — no plane-sized temporary ever exists.

Scoring semantics are :func:`..ops.search.score_profiles` +
:func:`..ops.search.cert_profile_scores` (reference per-trial loop,
``pulsarutils/dedispersion.py:186-201``, plus the hybrid's sliding
certificate row):

* window/peak selection is EXACT (same strict-inequality tie-breaking,
  same first-occurrence argmax, same ``peak = block_index * window``
  convention) — pinned by ``tests/test_score_pallas.py``;
* float values (max, std, snr, cert) agree to f32 reduction order: the
  kernel accumulates per-tile partials sequentially where the XLA
  scorer reduces whole rows, so sums associate differently (same
  floats, different trees).  Coarse scores feed seed selection and
  guarantee-loop margins, both of which already absorb
  within-one-trial coarse error; the hybrid's EXACT rescore path
  (``_fused_rescore_kernel`` -> ``score_profiles_stacked``) is
  untouched, so exact-hit parity vs the reference is unaffected.

Numerical safety (the round-4 mean-fold lesson): raw block sums cancel
catastrophically at large DC offsets in float32, so nothing here
reduces raw values.  Each row block is CENTERED on the first tile's
mean ``c`` (within ~std/sqrt(T_BLK) of the row mean) before any
reduction; the exact residual mean ``m = mean(x - c)`` is recovered
from the accumulated centered sum and folded back analytically
(``max(blocksum(x - mean)) = max(blocksum(x - c)) - w*m`` — subtracting
a constant moves every block sum equally, so maxima/argmaxima are
computed on well-centered values and the correction is exact algebra,
not a cancelling subtraction of large floats).
"""

from __future__ import annotations

import functools

import numpy as np

#: scratch slot indices (each slot is one (8, 128) f32 tile per row block)
_C, _SUM, _SSQ = 0, 1, 2
_MAX1, _ARG1 = 3, 4
_SQ2, _MAX2, _ARG2 = 5, 6, 7
_SQ4, _MAX4, _ARG4 = 8, 9, 10
_SQ8, _MAX8, _ARG8 = 11, 12, 13
_CM2, _CM3, _CM4 = 14, 15, 16
_FIRST3, _LAST3 = 17, 18
_NSLOT = 19

#: preferred time-tile widths (largest dividing T wins; all multiples of
#: 8 so width-8 blocks never cross a tile boundary)
_T_BLKS = (16384, 8192, 4096, 2048, 1024)


def pick_score_tile(t):
    """Largest supported time tile dividing ``t`` (0 if none)."""
    for t_blk in _T_BLKS:
        if t % t_blk == 0:
            return t_blk
    return 0


@functools.lru_cache(maxsize=16)
def _build_score_kernel(rows_p, t, t_blk, with_cert, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_t = t // t_blk
    n_rb = rows_p // 8
    BIG = np.float32(1e18)
    NEG = np.float32(-1e30)

    def lroll(v, s):
        # left-rotate by s lanes: result[i] = v[(i + s) mod L]
        length = v.shape[-1]
        return pltpu.roll(v, (length - s) % length, 1)

    def rroll(v, s):
        return pltpu.roll(v, s % v.shape[-1], 1)

    def kernel(x_ref, out_ref, st_ref):
        i_t = pl.program_id(1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, t_blk), 1)
        lane_f = lane.astype(jnp.float32)
        lane128 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

        raw = x_ref[:]

        @pl.when(i_t == 0)
        def _init():
            c = jnp.sum(raw, axis=1, keepdims=True) / jnp.float32(t_blk)
            st_ref[_C] = jnp.broadcast_to(c, (8, 128))
            zero = jnp.zeros((8, 128), jnp.float32)
            for s in (_SUM, _SSQ, _ARG1, _ARG2, _ARG4, _ARG8,
                      _SQ2, _SQ4, _SQ8):
                st_ref[s] = zero
            for s in (_MAX1, _MAX2, _MAX4, _MAX8, _CM2, _CM3, _CM4):
                st_ref[s] = jnp.full((8, 128), NEG)

        c = st_ref[_C][:, 0:1]
        x = raw - c

        if with_cert:
            @pl.when(i_t == 0)
            def _first3():
                # centered first 3 samples at lanes 3..5 (the final
                # circular boundary pass reads them there)
                st_ref[_FIRST3] = rroll(x[:, :128], 3)

        # ---- sliding-window boundary pass for the PREVIOUS tile -------
        # (windows starting in the previous tile's last 3 lanes reach
        # into this tile; st[_LAST3] holds those lanes at positions 0..2)
        def boundary(prev3, cur3):
            m0_2 = lane128 < 3
            m3_5 = (lane128 >= 3) & (lane128 < 6)
            seq = (jnp.where(m0_2, prev3, 0.0)
                   + jnp.where(m3_5, cur3, 0.0))
            s2 = seq + lroll(seq, 1)
            s3 = s2 + lroll(seq, 2)
            s4 = s2 + lroll(s2, 2)
            st_ref[_CM2] = jnp.maximum(
                st_ref[_CM2],
                jnp.max(jnp.where(lane128 == 2, s2, NEG), axis=1,
                        keepdims=True))
            st_ref[_CM3] = jnp.maximum(
                st_ref[_CM3],
                jnp.max(jnp.where((lane128 >= 1) & (lane128 < 3), s3,
                                  NEG), axis=1, keepdims=True))
            st_ref[_CM4] = jnp.maximum(
                st_ref[_CM4],
                jnp.max(jnp.where(lane128 < 3, s4, NEG), axis=1,
                        keepdims=True))

        if with_cert:
            @pl.when(i_t > 0)
            def _bnd_prev():
                boundary(st_ref[_LAST3], rroll(x[:, :128], 3))

        # ---- in-tile partials ----------------------------------------
        st_ref[_SUM] += jnp.sum(x, axis=1, keepdims=True)
        st_ref[_SSQ] += jnp.sum(x * x, axis=1, keepdims=True)

        s2 = x + lroll(x, 1)
        s4 = s2 + lroll(s2, 2)
        s8 = s4 + lroll(s4, 4)

        def upd(vals, mask, max_slot, arg_slot, sq_slot):
            v = jnp.where(mask, vals, NEG)
            tile_max = jnp.max(v, axis=1, keepdims=True)
            tile_arg = jnp.min(
                jnp.where(v == tile_max, lane_f, BIG), axis=1,
                keepdims=True)
            run_max = st_ref[max_slot][:, 0:1]
            better = tile_max > run_max
            st_ref[max_slot] = jnp.broadcast_to(
                jnp.where(better, tile_max, run_max), (8, 128))
            run_arg = st_ref[arg_slot][:, 0:1]
            g_arg = tile_arg + jnp.float32(t_blk) * i_t.astype(jnp.float32)
            st_ref[arg_slot] = jnp.broadcast_to(
                jnp.where(better, g_arg, run_arg), (8, 128))
            if sq_slot is not None:
                st_ref[sq_slot] += jnp.sum(
                    jnp.where(mask, vals * vals, 0.0), axis=1,
                    keepdims=True)

        true_mask = lane >= 0
        upd(x, true_mask, _MAX1, _ARG1, None)
        upd(s2, lane % 2 == 0, _MAX2, _ARG2, _SQ2)
        upd(s4, lane % 4 == 0, _MAX4, _ARG4, _SQ4)
        upd(s8, lane % 8 == 0, _MAX8, _ARG8, _SQ8)

        if with_cert:
            # sliding cert maxima over windows fully inside this tile
            s3 = s2 + lroll(x, 2)
            st_ref[_CM2] = jnp.maximum(
                st_ref[_CM2],
                jnp.max(jnp.where(lane <= t_blk - 2, s2, NEG), axis=1,
                        keepdims=True))
            st_ref[_CM3] = jnp.maximum(
                st_ref[_CM3],
                jnp.max(jnp.where(lane <= t_blk - 3, s3, NEG), axis=1,
                        keepdims=True))
            st_ref[_CM4] = jnp.maximum(
                st_ref[_CM4],
                jnp.max(jnp.where(lane <= t_blk - 4, s4, NEG), axis=1,
                        keepdims=True))

            # centered last 3 samples -> lanes 0..2 for the next boundary
            st_ref[_LAST3] = lroll(x, t_blk - 3)[:, :128]

        # ---- finish the row block ------------------------------------
        @pl.when(i_t == n_t - 1)
        def _emit():
            if with_cert:
                # circular wrap: windows starting in the row's last 3
                # samples
                boundary(st_ref[_LAST3], st_ref[_FIRST3])

            tt = jnp.float32(t)
            m = st_ref[_SUM][:, 0:1] / tt
            var = st_ref[_SSQ][:, 0:1] / tt - m * m
            std = jnp.sqrt(jnp.maximum(var, 0.0))
            maxv = st_ref[_MAX1][:, 0:1] - m

            best_snr = jnp.zeros((8, 1), jnp.float32)
            best_w = jnp.zeros((8, 1), jnp.float32)
            best_p = jnp.zeros((8, 1), jnp.float32)
            for w, max_slot, arg_slot, sq_slot in (
                    (1, _MAX1, _ARG1, None),
                    (2, _MAX2, _ARG2, _SQ2),
                    (4, _MAX4, _ARG4, _SQ4),
                    (8, _MAX8, _ARG8, _SQ8)):
                wm = jnp.float32(w) * m
                if sq_slot is None:
                    var_w, mx = var, maxv
                else:
                    nb = tt / jnp.float32(w)
                    var_w = st_ref[sq_slot][:, 0:1] / nb - wm * wm
                    mx = st_ref[max_slot][:, 0:1] - wm
                snr_w = mx / jnp.sqrt(jnp.maximum(var_w, 1e-30))
                better = snr_w > best_snr
                best_snr = jnp.where(better, snr_w, best_snr)
                best_w = jnp.where(better, jnp.float32(w), best_w)
                best_p = jnp.where(better, st_ref[arg_slot][:, 0:1],
                                   best_p)

            cols = [maxv, std, best_snr, best_w, best_p]
            if with_cert:
                denom = jnp.maximum(std, 1e-30)
                cert = (st_ref[_CM2][:, 0:1] - 2.0 * m) / (
                    denom * jnp.float32(np.sqrt(2.0)))
                cert = jnp.maximum(
                    cert, (st_ref[_CM3][:, 0:1] - 3.0 * m) / (
                        denom * jnp.float32(np.sqrt(3.0))))
                cert = jnp.maximum(
                    cert, (st_ref[_CM4][:, 0:1] - 4.0 * m) / (
                        denom * jnp.float32(2.0)))
                cols.append(cert)

            out = jnp.zeros((8, 128), jnp.float32)
            for k, v in enumerate(cols):
                out = out + jnp.where(lane128 == k, v, 0.0)
            out_ref[:] = out

    call = pl.pallas_call(
        kernel,
        grid=(n_rb, n_t),
        in_specs=[pl.BlockSpec((8, t_blk), lambda i_r, i_t: (i_r, i_t))],
        out_specs=pl.BlockSpec((8, 128), lambda i_r, i_t: (i_r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_NSLOT, 8, 128), jnp.float32)],
        interpret=bool(interpret),
        name="score_rows",
    )
    return call


def _kernel_scores(rows_p, t, t_blk, with_cert, interpret, sub):
    """Run the one-pass kernel on the 8-aligned row block ``sub``.

    Split out of :func:`score_plane_pallas` so tests can stub the
    (expensive) kernel invocation while exercising the wrapper's
    checks (the 2^24 peak-exactness warning below).
    """
    import jax.numpy as jnp

    return _build_score_kernel(rows_p, t, t_blk, with_cert, interpret)(
        jnp.asarray(sub, jnp.float32))


def score_plane_pallas(plane, with_cert=False, interpret=False):
    """One-pass scores of ``plane`` — drop-in for
    :func:`..ops.search.score_profiles_chunked` on tile-friendly shapes.

    Returns the stacked ``(5, rows)`` float32 array (``(6, rows)`` with
    ``with_cert``: the sliding certificate row appended).  Raises
    ``ValueError`` when no supported tile divides the time axis — the
    caller falls back to the XLA scorer.

    Peak indices are accumulated as float32 in the kernel (the global
    argmax slot is ``tile_arg + t_blk * i_t``), exact only below 2^24
    samples — the same float32-pack limit as
    :func:`..ops.search.score_profiles_stacked`, and the same warning
    fires above it (ADVICE r5: this path previously accepted e.g. a
    tile-divisible 2^25 silently while the XLA scorer warned).

    Row counts are handled without any plane-sized copy (the motivating
    coarse plane is 513 x 1M — an odd row count; padding it would
    re-materialise ~2 GB per search, code-review r5): the 8-aligned
    row prefix goes through the kernel and the <= 7 remainder rows
    through the XLA scorer (same per-row semantics, independent rows).
    """
    import jax.numpy as jnp

    from .search import warn_peak_exactness

    rows, t = plane.shape
    t_blk = pick_score_tile(t)
    if t_blk == 0:
        raise ValueError(f"no supported score tile divides T={t}")
    rows8 = (rows // 8) * 8
    if rows8 == rows:
        # remainder rows (below) route through the XLA stacked scorer,
        # whose own warn_peak_exactness covers the call — warning here
        # too would fire twice for one call (code-review r6)
        warn_peak_exactness(t)
    parts = []
    if rows8:
        out = _kernel_scores(rows8, t, t_blk, bool(with_cert),
                             bool(interpret), plane[:rows8])
        parts.append(out[:, :6 if with_cert else 5].T)
    if rows8 != rows:
        from .search import score_profiles_chunked

        parts.append(score_profiles_chunked(plane[rows8:], jnp,
                                            with_cert=with_cert))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
