"""The plain reference of a search with a boxcar ladder beyond 8 samples
(``PUsearchfrb --boxcar-max N``), flat or smearing-tiered: what the pulse
chunk's best row has to be.

NumPy and SciPy only; imports nothing of the program and reads nothing the
program made.  From ``reference.py`` it takes the header parser, the packed
reader, the bad-channel mask and the bfloat16 rounding; from
``reference_tiered.py`` the tier rule (``tier_table``; a range that stays
in the first tier is the flat grid); from ``dispersion.py`` the delays.  The
clean and the roll-and-sum are ``reference_tiered.best_row``'s, restated
here because that function does not expose them.

The ladder, restated from the configuration's ``boxcar_max`` alone (a power
of two, in samples of the file):

* a tier that works at ``2^k`` samples scores the boxcars ``1, 2, 4, ...,
  max(8, boxcar_max / 2^k)`` of its own samples;
* for a dedispersed series ``x`` of ``T`` samples, ``x0 = x - mean(x)``;
  level 0 is ``x0``; level ``j`` is the sum of adjacent pairs of level
  ``j - 1`` (a trailing odd element dropped), so it holds the block sums of
  width ``w = 2^j`` at offsets that are multiples of ``w``;
* ``snr_j = max(level j) / std(level j)``; the row's score is the largest,
  the smallest ``j`` winning ties; ``rebin = w``, ``peak = argmax(level j)
  * w`` (first occurrence);
* a level above the first four (``w > 8``) with fewer than 64 blocks is
  not scored.

One thing differs from ``reference_tiered.best_row``: a wide pulse's S/N is
flat over as many trial rows as it is samples wide, so noise picks the best
row and five rows do not hold it.  Every trial within
``cfg["reference_half_rows"]`` of the one nearest the injected DM is
compared, and all are returned under ``rows``.

The control stores the cleaned chunk in bfloat16 **before** the block sum.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import dispersion, reference, reference_tiered

DEFAULT_LEVELS = 4
MIN_WIDE_BLOCKS = 64


def ladder(boxcar_max, factor):
    """The boxcar widths of a tier that works at ``factor`` samples of the
    file, in its own samples."""
    widest = max(8, int(boxcar_max) // int(factor))
    return [1 << j for j in range(widest.bit_length())]


def score_row(row, windows):
    """``(snr, width, peak)`` of one dedispersed series over ``windows``."""
    level = row - row.mean()
    best = (0.0, 0, 0)
    for j, w in enumerate(windows):
        if j:
            n = level.shape[0] // 2
            level = level[: 2 * n].reshape(n, 2).sum(axis=1)
        if j >= DEFAULT_LEVELS and level.shape[0] < MIN_WIDE_BLOCKS:
            break
        snr = float(level.max() / level.std())
        if snr > best[0]:
            best = (snr, w, int(np.argmax(level)) * w)
    return best


def best_row(path, cfg, chunk_start, near_dm, half_rows=None, control=False,
             threads=None):
    """``reference_tiered.best_row``'s contract with the ladder of
    ``cfg["boxcar_max"]``: the best of the ``2 * half_rows + 1`` trials
    nearest ``near_dm`` on the grid of the tier that holds it
    (``half_rows`` from ``cfg["reference_half_rows"]``), ``row`` counted in
    the concatenated table, ``rebin`` and ``peak`` in that tier's samples."""
    t0 = time.perf_counter()
    if half_rows is None:
        half_rows = int(cfg["reference_half_rows"])
    packed_T, hdr = reference.read_packed(path)
    nchan, nbits, tsamp = hdr["nchans"], hdr["nbits"], hdr["tsamp"]
    descending = hdr["foff"] < 0
    fbottom, bandwidth = dispersion.band_edges(hdr["fch1"], hdr["foff"],
                                               nchan)
    T = cfg["chunk_samples"]
    lo, hi = chunk_start, chunk_start + T
    if hi > packed_T.shape[1]:
        raise ValueError("the chunk leaves the file")
    bad_file = reference.bad_channels(packed_T, nbits)

    tiers = reference_tiered.tier_table(cfg["dmmin"], cfg["dmmax"], fbottom,
                                        bandwidth, tsamp, hdr["foff"])
    held = [i for i, t in enumerate(tiers)
            if t["dm_lo"] <= near_dm < t["dm_hi"]]
    it = held[0] if held else int(np.argmin(
        [min(abs(near_dm - t["dm_lo"]), abs(near_dm - t["dm_hi"]))
         for t in tiers]))
    tier = tiers[it]
    row0 = sum(len(t["dms"]) for t in tiers[:it])
    factor, trials = tier["factor"], tier["dms"]
    Tk = T // factor
    windows = ladder(cfg["boxcar_max"], factor)

    def fchan(c):  # ascending-band channel -> file channel
        return nchan - 1 - c if descending else c

    good = [c for c in range(nchan) if not bad_file[fchan(c)]]
    ngood = len(good)

    # the clean of reference.best_row, at the file's own resolution
    total = np.zeros(T, dtype=np.uint32)
    for c in good:
        total += reference._file_channel(packed_T, nbits, fchan(c), lo, hi)
    lc = total.astype(np.float64) / max(ngood, 1)
    from scipy.ndimage import gaussian_filter1d

    window = min(101, T // 100 * 2 + 1)
    smooth = gaussian_filter1d(lc, window, mode="reflect", truncate=4.0)
    smooth = np.where(smooth == 0, 1.0, smooth)
    flat = np.median(smooth) / smooth

    centre = int(np.argmin(np.abs(trials - near_dm)))
    rows = list(range(max(centre - half_rows, 0),
                      min(centre + half_rows + 1, len(trials))))
    offs = dispersion.channel_shifts(trials[rows], nchan, fbottom, bandwidth,
                                     tier["tsamp"]) % Tk

    threads = threads or min(8, os.cpu_count() or 1)
    parts = [good[i::threads] for i in range(threads)]
    zero_dm = bool(cfg.get("clean", {}).get("zero_dm", False))

    def spectrum(chans):
        spec, msum = {}, np.zeros(T) if zero_dm else None
        for c in chans:
            u = reference._file_channel(packed_T, nbits, fchan(c), lo, hi) * flat
            s = float(u.mean())
            spec[c] = s if s != 0 else 1.0
            if zero_dm:
                msum += u / spec[c] - 1.0
        return spec, msum

    def block_sum(v):
        return v[: Tk * factor].reshape(Tk, factor).sum(axis=1)

    with ThreadPoolExecutor(threads) as pool:
        got = list(pool.map(spectrum, parts))
        spec = {c: s for part, _ in got for c, s in part.items()}
        mean_t = (sum(m for _, m in got) / max(ngood, 1)) if zero_dm \
            else None

        def accumulate(chans):
            acc = np.zeros((len(rows), Tk))
            ctl = np.zeros((len(rows), Tk)) if control else None
            for c in chans:
                u = reference._file_channel(packed_T, nbits, fchan(c), lo, hi) * flat
                v = (u - spec[c]) / spec[c]
                if zero_dm:
                    v -= mean_t
                vs = [(block_sum(v), acc)]
                if control:
                    stored = reference._bf16(v.astype(np.float32))
                    vs.append((block_sum(stored.astype(np.float64)), ctl))
                for val, out in vs:
                    for r in range(len(rows)):
                        o = int(offs[r, c])
                        out[r, : Tk - o] += val[o:]
                        out[r, Tk - o:] += val[:o]
            return acc, ctl

        sums = list(pool.map(accumulate, parts))
    plane = sum(a for a, _ in sums)

    def pick(pl):
        scored = [score_row(pl[r], windows) for r in range(len(rows))]
        i = max(range(len(rows)), key=lambda r: (scored[r][0], -r))
        return {"DM": float(trials[rows[i]]), "row": int(row0 + rows[i]),
                "snr": scored[i][0], "rebin": scored[i][1],
                "peak": scored[i][2],
                "rows": [{"row": int(row0 + rows[r]),
                          "DM": float(trials[rows[r]]),
                          "snr": scored[r][0], "rebin": scored[r][1],
                          "peak": scored[r][2]} for r in range(len(rows))]}

    out = pick(plane)
    out["ntrials"] = int(sum(len(t["dms"]) for t in tiers))
    out["tier"] = it
    out["downsample"] = factor
    out["windows"] = windows
    out["bad_channels_file_order"] = np.flatnonzero(bad_file).tolist()
    if control:
        out["control"] = pick(sum(c for _, c in sums))
    out["seconds"] = time.perf_counter() - t0
    return out
