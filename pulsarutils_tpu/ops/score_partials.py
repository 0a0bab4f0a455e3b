"""Row scores of a plane that exists a time tile at a time.

A row's S/N is ``max / std`` over the **whole** row at every level of the
boxcar ladder (:func:`..ops.search.score_profiles`), and its certificate
score a maximum over sliding windows of the whole row
(:func:`..ops.search.cert_profile_scores`).  A tier swept in time tiles
(:mod:`..parallel.stream`, ``plan_time_tiles``) never holds a whole row:
MeerTRAP's native-resolution coarse plane is 5,183 x 2^19 x 4 B = 10.9 GB.
So each tile's scorer emits **partials** per row — centre, sum, sum of
squares, maximum and its place at every level, the certificate's maxima
inside the tile and the few samples and half blocks at its two edges —
and :func:`combine_partials` gives the row's scores on the host, in
float64, from a few dozen numbers a tile a row.

One layout (:func:`partial_layout`) serves both producers: the XLA
:func:`score_partials` here (every backend; the exact rescore's few rows
on the chip too) and the one-pass kernel's partial mode
(``ops/score_pallas.py``, the coarse plane on the chip).

What agrees with the untiled scorers and what does not.  Window, peak and
place selection are the same rules (strict ``>``, the smallest level and
the first place win ties).  The float values come from other summation
trees: a tile's sums are folded in float32 and the tiles' in float64,
where the untiled scorer reduces a whole row in float32, so S/N agrees to
float32 reduction error of one row (a few 1e-7 relative at 2^19 samples),
never bit for bit.  Every reduction is over values centred on the tile's
own mean (the round-4 lesson of ``score_profiles``: raw block sums cancel
at a large DC offset), and the shift to the row's mean is exact algebra
on the host.
"""

from __future__ import annotations

import numpy as np

from .rebin import block_sum_time


def partial_layout(n_levels, n_wide, with_cert):
    """Column of every partial in a tile's ``(rows, ncol)`` array.

    ``n_levels`` scored levels (widths ``2^j``), ``n_wide`` half-stride
    certificate captures (the last ``n_wide`` levels).  Returns ``(cols,
    ncol)``; ``cols`` maps ``"c"``, ``"sum"``, ``"ssq"``, ``("max", j)``,
    ``("arg", j)``, ``("ssq", j)`` for ``j >= 1``, and with the
    certificate ``"cm2"``, ``"cm3"``, ``"cm4"``, ``"first3"``, ``"last3"``
    (three columns each, the first given) and per capture ``("wmax", i)``,
    ``("wfirst", i)``, ``("wlast", i)``.
    """
    cols = {"c": 0, "sum": 1, "ssq": 2, ("max", 0): 3, ("arg", 0): 4}
    n = 5
    for j in range(1, n_levels):
        cols[("ssq", j)], cols[("max", j)], cols[("arg", j)] = n, n + 1, n + 2
        n += 3
    if with_cert:
        for name in ("cm2", "cm3", "cm4"):
            cols[name] = n
            n += 1
        cols["first3"], cols["last3"] = n, n + 3
        n += 6
        for i in range(n_wide):
            cols[("wmax", i)] = n
            cols[("wfirst", i)] = n + 1
            cols[("wlast", i)] = n + 2
            n += 3
    return cols, n


def tile_ladder(windows, nsamples_total):
    """``(scored, wide)`` of a tile of a row of ``nsamples_total``
    samples: the levels and captures follow the WHOLE row's length
    (:func:`..ops.search.scored_windows`), not the tile's."""
    from .search import cert_wide_windows, scored_windows

    return (scored_windows(windows, nsamples_total),
            cert_wide_windows(windows, nsamples_total))


def score_partials(plane, xp, windows, nsamples_total, with_cert=False):
    """Partials of one tile's rows ``(rows, own)``: the tile's own samples
    of each row and nothing else (the caller drops the halo).  ``own``
    must be a multiple of the widest scored window, so that no block of
    any level lies across two tiles."""
    scored, wide = tile_ladder(windows, nsamples_total)
    plane = xp.asarray(plane)
    own = plane.shape[1]
    if own % scored[-1]:
        raise ValueError(f"a tile of {own} samples holds no whole number "
                         f"of windows of {scored[-1]}")
    if not xp.issubdtype(plane.dtype, xp.floating):
        plane = plane.astype(xp.float32)
    cols, ncol = partial_layout(len(scored), len(wide), with_cert)
    out = [None] * ncol
    c = plane.mean(axis=1)
    x = plane - c[:, None]
    out[cols["c"]] = c
    out[cols["sum"]] = x.sum(axis=1)
    levels = [x]
    for j, w in enumerate(scored):
        if j:
            levels.append(block_sum_time(levels[-1], 2, xp=xp))
        reb = levels[-1]
        out[cols["ssq" if j == 0 else ("ssq", j)]] = (reb * reb).sum(axis=1)
        out[cols[("max", j)]] = reb.max(axis=1)
        out[cols[("arg", j)]] = (xp.argmax(reb, axis=1) * w).astype(x.dtype)
    if with_cert:
        s2 = x[:, :-1] + x[:, 1:]
        out[cols["cm2"]] = s2.max(axis=1)
        out[cols["cm3"]] = (s2[:, :-1] + x[:, 2:]).max(axis=1)
        out[cols["cm4"]] = (s2[:, :-2] + s2[:, 2:]).max(axis=1)
        for k in range(3):
            out[cols["first3"] + k] = x[:, k]
            out[cols["last3"] + k] = x[:, own - 3 + k]
        for i, w in enumerate(wide):
            below = levels[scored.index(w) - 1]
            out[cols[("wmax", i)]] = (below[:, :-1] + below[:, 1:]).max(axis=1)
            out[cols[("wfirst", i)]] = below[:, 0]
            out[cols[("wlast", i)]] = below[:, -1]
    return xp.stack([o.astype(x.dtype) for o in out], axis=1)


def combine_partials(tiles, own, windows, nsamples_total, with_cert=False):
    """The rows' scores from their tiles' partials: the stacked ``(5,
    rows)`` array of :func:`..ops.search.score_profiles_stacked` (``max,
    std, snr, window, peak``), ``(6, rows)`` with the certificate row, in
    float64.  ``tiles`` are the ``(rows, ncol)`` arrays in time order,
    each over ``own`` samples; the row is circular over all of them."""
    scored, wide = tile_ladder(windows, nsamples_total)
    cols, ncol = partial_layout(len(scored), len(wide), with_cert)
    p = np.stack([np.asarray(t, np.float64) for t in tiles])  # (n, rows, ncol)
    if p.shape[2] != ncol or p.shape[0] * own != nsamples_total:
        raise ValueError(f"partials {p.shape} do not tile a row of "
                         f"{nsamples_total} samples by {own} ({ncol} columns)")
    n, rows = p.shape[:2]
    total = float(nsamples_total)
    mean = (p[:, :, cols["c"]] * own + p[:, :, cols["sum"]]).sum(0) / total
    delta = p[:, :, cols["c"]] - mean[None, :]     # tile centre - row mean
    tsum = p[:, :, cols["sum"]]

    def level_max(j, w):
        """Maximum of level ``j``'s blocks of the mean-subtracted row and
        its place: the first tile that holds it, the first block there."""
        vals = p[:, :, cols[("max", j)]] + w * delta
        tile = np.argmax(vals, axis=0)             # first occurrence
        take = (tile, np.arange(rows))
        return vals[take], tile * own + p[:, :, cols[("arg", j)]][take]

    best_snr = np.zeros(rows)
    best_w = np.zeros(rows)
    best_p = np.zeros(rows)
    level_std = {}
    maxv = std = None
    for j, w in enumerate(scored):
        ssq = p[:, :, cols["ssq" if j == 0 else ("ssq", j)]]
        nblocks = total / w
        # sum over the row of (block + w*delta)^2; the row's blocks have
        # mean zero by construction of ``mean``
        var = (ssq + 2.0 * w * delta * tsum
               + (own / w) * (w * delta) ** 2).sum(0) / nblocks
        level_std[w] = np.sqrt(np.maximum(var, 1e-300))
        mx, place = level_max(j, w)
        if j == 0:
            maxv, std = mx, np.sqrt(np.maximum(var, 0.0))
        snr = mx / level_std[w]
        better = snr > best_snr
        best_snr = np.where(better, snr, best_snr)
        best_w = np.where(better, float(w), best_w)
        best_p = np.where(better, place, best_p)
    out = [maxv, std, best_snr, best_w, best_p]
    if with_cert:
        denom = np.maximum(std, 1e-300)
        cm = [(p[:, :, cols[name]] + k * delta).max(0)
              for name, k in (("cm2", 2), ("cm3", 3), ("cm4", 4))]
        # windows that start in a tile's last three samples and end in the
        # next tile's first three; the last tile's run on into the first
        first = p[:, :, cols["first3"]:cols["first3"] + 3] + delta[:, :, None]
        last = p[:, :, cols["last3"]:cols["last3"] + 3] + delta[:, :, None]
        seq = np.concatenate([last, np.roll(first, -1, axis=0)], axis=2)
        for k, width in enumerate((2, 3, 4)):
            for start in range(4 - width, 3):
                cm[k] = np.maximum(
                    cm[k], seq[:, :, start:start + width].sum(2).max(0))
        cert = np.maximum(np.maximum(cm[0] / (denom * np.sqrt(2.0)),
                                     cm[1] / (denom * np.sqrt(3.0))),
                          cm[2] / (denom * 2.0))
        for i, w in enumerate(wide):
            half = w / 2.0
            wmax = (p[:, :, cols[("wmax", i)]] + w * delta).max(0)
            if n > 1:  # the pair across two tiles; none across the end
                across = (p[:-1, :, cols[("wlast", i)]] + half * delta[:-1]
                          + p[1:, :, cols[("wfirst", i)]] + half * delta[1:])
                wmax = np.maximum(wmax, across.max(0))
            cert = np.maximum(cert, wmax / level_std[w])
        out.append(cert)
    return np.stack(out)
