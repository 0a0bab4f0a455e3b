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

from ..utils.logging_utils import kernel_build_span

#: fixed scratch slots (each slot is one (8, 128) f32 tile per row block);
#: the per-level and per-capture slots follow, see :func:`_slots`
_C, _SUM, _SSQ = 0, 1, 2
_MAX1, _ARG1 = 3, 4

#: preferred time-tile widths (largest dividing T wins; all multiples of
#: 8 so width-8 blocks never cross a tile boundary — a longer ladder
#: takes the tiles that are multiples of its widest window)
_T_BLKS = (16384, 8192, 4096, 2048, 1024)


def pick_score_tile(t, widest=8):
    """Largest supported time tile dividing ``t`` that is a multiple of
    the ladder's widest window (0 if none)."""
    for t_blk in _T_BLKS:
        if t % t_blk == 0 and t_blk % widest == 0:
            return t_blk
    return 0


def _slots(n_levels, n_wide, partial=False):
    """The scratch layout of a ladder of ``n_levels`` windows with
    ``n_wide`` half-stride certificate captures.

    Returns ``(level, cert, wide, n_slots)``: ``level[j - 1]`` is the
    ``(sumsq, max, argmax)`` triple of level ``j >= 1`` (width ``2^j``),
    ``cert`` the sliding certificate's ``(cm2, cm3, cm4, first3,
    last3)``, ``wide[i]`` the ``(max, last half block of the previous
    tile)`` pair of the i-th capture — with ``partial`` a triple, the
    row's first half block after them.  For the default ladder the first
    19 slots are the ones this kernel has always had.
    """
    base = _ARG1 + 1
    level = [tuple(base + 3 * j + k for k in range(3))
             for j in range(n_levels - 1)]
    base += 3 * (n_levels - 1)
    cert = tuple(range(base, base + 5))
    base += 5
    per = 3 if partial else 2
    wide = [tuple(base + per * i + k for k in range(per))
            for i in range(n_wide)]
    return level, cert, wide, base + per * n_wide


@functools.lru_cache(maxsize=16)
def _build_score_kernel(rows_p, t, t_blk, with_cert, interpret,
                        n_levels=4, wide_from=None, partial=False):
    """The one-pass kernel for a ladder of ``n_levels`` doubling windows.

    ``wide_from`` (``None``: no such capture) is the level of the first
    half-stride certificate capture; every level from it to the last has
    one (:func:`..ops.search.cert_wide_windows`).

    ``partial``: the row block's last tile emits what the scratch holds,
    in :func:`..ops.score_partials.partial_layout`'s columns, in place of
    the finished scores: ``t`` is then one time tile's own samples of a
    longer row (the array may be longer still: its halo is never read),
    and :func:`..ops.score_partials.combine_partials` finishes the row
    over its tiles.  Nothing wraps inside such a call: the windows across
    the row's end are the combiner's.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_t = t // t_blk
    n_rb = rows_p // 8
    BIG = np.float32(1e18)
    NEG = np.float32(-1e30)
    wide_levels = (list(range(wide_from, n_levels))
                   if with_cert and wide_from is not None else [])
    level_slots, cert_slots, wide_slots, n_slot = _slots(
        n_levels, len(wide_levels), partial)
    _CM2, _CM3, _CM4, _FIRST3, _LAST3 = cert_slots
    assert n_levels >= 4 and t_blk % (1 << (n_levels - 1)) == 0

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
            for s in ([_SUM, _SSQ, _ARG1]
                      + [sl[k] for sl in level_slots for k in (0, 2)]
                      + [sl[1] for sl in wide_slots]):
                st_ref[s] = zero
            for s in ([_MAX1, _CM2, _CM3, _CM4]
                      + [sl[1] for sl in level_slots]
                      + [sl[0] for sl in wide_slots]):
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

        def wide_capture(below, sums, w, max_slot, last_slot,
                         first_slot=None):
            """Windows of width ``w`` at strides of ``w / 2``: the ones
            inside this tile from ``sums`` (every lane's width-``w``
            sum), the one across the tile's start from the previous
            tile's last half block and this tile's first."""
            half = w // 2
            if first_slot is not None:
                @pl.when(i_t == 0)
                def _first_half():
                    st_ref[first_slot] = jnp.broadcast_to(below[:, 0:1],
                                                          (8, 128))

            inside = (lane % half == 0) & (lane <= t_blk - w)
            run = jnp.maximum(
                st_ref[max_slot][:, 0:1],
                jnp.max(jnp.where(inside, sums, NEG), axis=1,
                        keepdims=True))
            across = st_ref[last_slot][:, 0:1] + below[:, 0:1]
            run = jnp.where(i_t > 0, jnp.maximum(run, across), run)
            st_ref[max_slot] = jnp.broadcast_to(run, (8, 128))
            # the last half block sits on a 128-lane boundary, or in the
            # tile's last 128 lanes
            if half >= 128:
                last = below[:, t_blk - half:t_blk - half + 128][:, 0:1]
            else:
                last = jnp.max(
                    jnp.where(lane128 == 128 - half,
                              below[:, t_blk - 128:], NEG), axis=1,
                    keepdims=True)
            st_ref[last_slot] = jnp.broadcast_to(last, (8, 128))

        # level j's sums of width 2^j at every lane: the sums of width
        # 2^(j-1) plus themselves half a window on; at lanes that are
        # multiples of 2^j these are the pyramid's block sums, same adds.
        # The default ladder's three doublings come before any reduction,
        # as this kernel has always had them: with a reduction between
        # two of them the v5e compiler gives each its own pass over the
        # tile (17.4 against 13.5 ms a call at 1,069 x 2^19, PR 32)
        levels = [x]
        for j in range(1, 4):
            levels.append(levels[-1] + lroll(levels[-1], 1 << (j - 1)))
        s2, s4 = levels[1], levels[2]
        true_mask = lane >= 0
        upd(x, true_mask, _MAX1, _ARG1, None)
        for j in range(1, n_levels):
            w = 1 << j
            if j == len(levels):
                levels.append(levels[-1] + lroll(levels[-1], w // 2))
            sq_slot, max_slot, arg_slot = level_slots[j - 1]
            upd(levels[j], lane % w == 0, max_slot, arg_slot, sq_slot)
            if j in wide_levels:
                wide_capture(levels[j - 1], levels[j], w,
                             *wide_slots[wide_levels.index(j)])

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
        if partial:
            @pl.when(i_t == n_t - 1)
            def _emit_partials():
                from .score_partials import partial_layout

                cols, ncol = partial_layout(n_levels, len(wide_levels),
                                            with_cert)
                assert ncol <= 128
                scalars = [(cols["c"], _C), (cols["sum"], _SUM),
                           (cols["ssq"], _SSQ), (cols[("max", 0)], _MAX1),
                           (cols[("arg", 0)], _ARG1)]
                for j in range(1, n_levels):
                    sq_slot, max_slot, arg_slot = level_slots[j - 1]
                    scalars += [(cols[("ssq", j)], sq_slot),
                                (cols[("max", j)], max_slot),
                                (cols[("arg", j)], arg_slot)]
                if with_cert:
                    scalars += [(cols["cm2"], _CM2), (cols["cm3"], _CM3),
                                (cols["cm4"], _CM4)]
                    for i, (max_slot, last_slot, first_slot) in enumerate(
                            wide_slots):
                        scalars += [(cols[("wmax", i)], max_slot),
                                    (cols[("wlast", i)], last_slot),
                                    (cols[("wfirst", i)], first_slot)]
                out = jnp.zeros((8, 128), jnp.float32)
                for col, slot in scalars:
                    out = out + jnp.where(lane128 == col,
                                          st_ref[slot][:, 0:1], 0.0)
                if with_cert:
                    # first3 sits at lanes 3..5 of its slot, last3 at 0..2
                    for col, slot, at in ((cols["first3"], _FIRST3, 3),
                                          (cols["last3"], _LAST3, 0)):
                        moved = rroll(st_ref[slot], col - at)
                        out = out + jnp.where(
                            (lane128 >= col) & (lane128 < col + 3), moved,
                            0.0)
                out_ref[:] = out

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
            level_std = {}
            for j in range(n_levels):
                w = 1 << j
                wm = jnp.float32(w) * m
                if j == 0:
                    var_w, mx, arg_slot = var, maxv, _ARG1
                else:
                    sq_slot, max_slot, arg_slot = level_slots[j - 1]
                    nb = tt / jnp.float32(w)
                    var_w = st_ref[sq_slot][:, 0:1] / nb - wm * wm
                    mx = st_ref[max_slot][:, 0:1] - wm
                level_std[j] = jnp.sqrt(jnp.maximum(var_w, 1e-30))
                snr_w = mx / level_std[j]
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
                for j, (max_slot, _) in zip(wide_levels, wide_slots):
                    cert = jnp.maximum(
                        cert, (st_ref[max_slot][:, 0:1]
                               - jnp.float32(1 << j) * m) / level_std[j])
                cols.append(cert)

            out = jnp.zeros((8, 128), jnp.float32)
            for k, v in enumerate(cols):
                out = out + jnp.where(lane128 == k, v, 0.0)
            out_ref[:] = out

        if not partial:
            pl.when(i_t == n_t - 1)(_emit)

    call = pl.pallas_call(
        kernel,
        grid=(n_rb, n_t),
        in_specs=[pl.BlockSpec((8, t_blk), lambda i_r, i_t: (i_r, i_t))],
        out_specs=pl.BlockSpec((8, 128), lambda i_r, i_t: (i_r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_slot, 8, 128), jnp.float32)],
        interpret=bool(interpret),
        name="score_rows",
    )
    return call


def _kernel_scores(rows_p, t, t_blk, with_cert, interpret, sub,
                   n_levels=4, wide_from=None, partial=False):
    """Run the one-pass kernel on the first ``rows_p`` (8-aligned) rows
    of ``sub``: the grid visits them, rows past them are never read.

    Split out of :func:`score_plane_pallas` so tests can stub the
    (expensive) kernel invocation while exercising the wrapper's
    checks (the 2^24 peak-exactness warning below).
    """
    import jax.numpy as jnp

    return _build_score_kernel(rows_p, t, t_blk, with_cert, interpret,
                               n_levels, wide_from, partial)(
        jnp.asarray(sub, jnp.float32))


def score_plane_pallas(plane, with_cert=False, interpret=False,
                       windows=None, rows=None):
    """One-pass scores of ``plane`` — drop-in for
    :func:`..ops.search.score_profiles_chunked` on tile-friendly shapes.
    ``rows`` (default: all) scores the first ``rows`` rows alone: the
    FDMT sweep hands its last kernel's output with the padded rows left
    in place after the real ones.

    Returns the stacked ``(5, rows)`` float32 array (``(6, rows)`` with
    ``with_cert``: the sliding certificate row appended).  Raises
    ``ValueError`` when no supported tile divides the time axis — the
    caller falls back to the XLA scorer.  ``windows`` is the ladder
    (static; ``None`` = the default four): the kernel is built for its
    number of scored levels, on a tile that is a multiple of the widest.

    Peak indices are accumulated as float32 in the kernel (the global
    argmax slot is ``tile_arg + t_blk * i_t``), exact only below 2^24
    samples — the same float32-pack limit as
    :func:`..ops.search.score_profiles_stacked`, and the same warning
    fires above it (ADVICE r5: this path previously accepted e.g. a
    tile-divisible 2^25 silently while the XLA scorer warned).

    Row counts are handled without any plane-sized copy (the motivating
    coarse plane is 513 x 1M — an odd row count; padding it would
    re-materialise ~2 GB per search, code-review r5): the 8-aligned
    row prefix goes through the kernel, by its grid, and the <= 7
    remainder rows through the XLA scorer (same per-row semantics,
    independent rows).
    """
    import jax.numpy as jnp

    from .search import (cert_wide_windows, scored_windows,
                         warn_peak_exactness)

    t = plane.shape[1]
    if rows is None:
        rows = plane.shape[0]
    scored = scored_windows(windows, t)
    wide = cert_wide_windows(windows, t)
    t_blk = pick_score_tile(t, scored[-1])
    if t_blk == 0:
        raise ValueError(f"no supported score tile divides T={t} in "
                         f"multiples of the widest window {scored[-1]}")
    rows8 = (rows // 8) * 8
    if rows8 == rows:
        # remainder rows (below) route through the XLA stacked scorer,
        # whose own warn_peak_exactness covers the call — warning here
        # too would fire twice for one call (code-review r6)
        warn_peak_exactness(t)
    parts = []
    if rows8:
        with kernel_build_span("score_rows", rows=rows8, t=t, t_tile=t_blk):
            out = _kernel_scores(
                rows8, t, t_blk, bool(with_cert), bool(interpret),
                plane, n_levels=len(scored),
                wide_from=scored.index(wide[0]) if wide else None)
        parts.append(out[:, :6 if with_cert else 5].T)
    if rows8 != rows:
        from .search import score_profiles_chunked

        parts.append(score_profiles_chunked(plane[rows8:rows], jnp,
                                            with_cert=with_cert,
                                            windows=windows))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def pick_partial_tile(own, length, widest=8):
    """The scorer's time tile for one time tile of a row: the largest
    supported one that divides both its ``own`` samples and the array's
    ``length`` (own + halo: no block of the grid is cut short) and is a
    multiple of the widest window; 0 if none."""
    for t_blk in _T_BLKS:
        if own % t_blk == 0 and length % t_blk == 0 and t_blk % widest == 0:
            return t_blk
    return 0


def score_partials_pallas(plane, own, nsamples_total, with_cert=False,
                          interpret=False, windows=None, rows=None):
    """One time tile's partials (:mod:`.score_partials`) of ``plane``'s
    first ``rows`` rows over its first ``own`` samples, through the
    one-pass kernel: ``(rows, ncol)`` float32.  The ladder's levels follow
    ``nsamples_total``, the whole row.  The rows past the last whole block
    of eight go through the XLA :func:`.score_partials.score_partials`.
    Raises ``ValueError`` where no tile fits (the caller takes the XLA
    scorer for all rows)."""
    import jax.numpy as jnp

    from .score_partials import partial_layout, score_partials, tile_ladder

    if rows is None:
        rows = plane.shape[0]
    scored, wide = tile_ladder(windows, nsamples_total)
    t_blk = pick_partial_tile(own, plane.shape[1], scored[-1])
    if t_blk == 0:
        raise ValueError(f"no supported score tile divides a tile of {own} "
                         f"of {plane.shape[1]} samples in multiples of the "
                         f"widest window {scored[-1]}")
    _, ncol = partial_layout(len(scored), len(wide), with_cert)
    rows8 = (rows // 8) * 8
    parts = []
    if rows8:
        with kernel_build_span("score_rows", rows=rows8, t=own, t_tile=t_blk):
            out = _kernel_scores(
                rows8, own, t_blk, bool(with_cert), bool(interpret), plane,
                n_levels=len(scored),
                wide_from=scored.index(wide[0]) if wide else None,
                partial=True)
        parts.append(out[:, :ncol])
    if rows8 != rows:
        parts.append(score_partials(plane[rows8:rows, :own], jnp, windows,
                                    nsamples_total, with_cert=with_cert))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
