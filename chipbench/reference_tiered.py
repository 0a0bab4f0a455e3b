"""The plain reference of a smearing-tiered search: what the pulse chunk's
best row has to be when the DM range is searched in tiers.

NumPy and SciPy only; imports nothing of the program and reads nothing the
program made.  From ``reference.py`` it takes the header parser, the
packed reader (1, 2, 4 and 8 bits), the bad-channel mask, ``score_row`` and
the bfloat16 rounding; from ``dispersion.py`` the delays.  The clean is
``reference.best_row``'s, restated here because that function does not
expose it.

The tier rule, restated from the header alone:

* the intra-channel smearing at the band centre is ``8300 * |foff| /
  centre^3`` seconds per DM unit (MHz); tier ``k`` works at ``2^k *
  tsamp`` and ends at the DM where the smearing reaches that sample time
  (tiers that end at or below ``dmmin`` are left out);
* the first tier's trials are the flat grid's, band delays ``n(dmmin) +
  0, 1, 2, ...`` samples; every later tier searches the integer band
  delays of its own sample time above ``n_k(lower edge)``; every tier but
  the last stops at ``n_k(upper edge)``, the last runs to the first trial
  at or past ``dmmax``; ``DM_n = n * tsamp_k / 4149 / (f0^-2 - f1^-2)``;
* a tier's data is the cleaned chunk summed in blocks of ``2^k`` samples
  (a trailing fragment is dropped); dedispersion is the circular
  roll-and-sum of ``reference.py`` at the tier's sample time, scored with
  boxcars 1, 2, 4, 8 of the tier's samples;
* the program's table is the tiers' rows one after the other: ``row`` is
  the index into that concatenation, ``peak`` and ``rebin`` are in the
  samples of the row's own tier.

The control stores the cleaned chunk in bfloat16 **before** the block sum.
"""

from __future__ import annotations

import math
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import dispersion, reference

DM_SMEARING_CONST = 8300.0


def tier_table(dmmin, dmmax, fbottom, bandwidth, tsamp, foff):
    """``[{"factor", "tsamp", "dm_lo", "dm_hi", "dms"}]``, one per tier."""
    f0, f1 = float(fbottom), float(fbottom) + float(bandwidth)
    centre = f0 + float(bandwidth) / 2.0
    smear = DM_SMEARING_CONST * abs(float(foff)) / centre ** 3
    unit = f0 ** -2.0 - f1 ** -2.0
    dmmin, dmmax = float(dmmin), float(dmmax)
    k = 0
    while (2 ** k) * tsamp / smear <= dmmin:
        k += 1
    tiers, lo = [], dmmin
    while True:
        edge = (2 ** k) * tsamp / smear
        last = edge >= dmmax
        hi = dmmax if last else edge
        ts = (2 ** k) * tsamp
        n_lo = dispersion.band_delay_samples(lo, f0, bandwidth, ts)
        n_hi = dispersion.band_delay_samples(hi, f0, bandwidth, ts)
        if tiers:
            n_lo = math.floor(n_lo) + 1.0
        if last:
            n = np.arange(n_lo, n_hi + 1)
        else:
            n = n_lo + np.arange(math.floor(n_hi - n_lo) + 1)
        tiers.append({"factor": 2 ** k, "tsamp": ts, "dm_lo": lo,
                      "dm_hi": hi,
                      "dms": n * ts / dispersion.DM_DELAY_CONST / unit})
        if last:
            return tiers
        lo, k = hi, k + 1


def best_row(path, cfg, chunk_start, near_dm, half_rows=2, control=False,
             threads=None):
    """``reference.best_row``'s contract for a tiered plan: the best of the
    ``2 * half_rows + 1`` trials nearest ``near_dm`` on the grid of the
    tier that holds it, ``row`` counted in the concatenated table."""
    t0 = time.perf_counter()
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

    tiers = tier_table(cfg["dmmin"], cfg["dmmax"], fbottom, bandwidth, tsamp,
                       hdr["foff"])
    held = [i for i, t in enumerate(tiers)
            if t["dm_lo"] <= near_dm < t["dm_hi"]]
    it = held[0] if held else int(np.argmin(
        [min(abs(near_dm - t["dm_lo"]), abs(near_dm - t["dm_hi"]))
         for t in tiers]))
    tier = tiers[it]
    row0 = sum(len(t["dms"]) for t in tiers[:it])
    factor, trials = tier["factor"], tier["dms"]
    Tk = T // factor

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
        scored = [reference.score_row(pl[r]) for r in range(len(rows))]
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
    out["bad_channels_file_order"] = np.flatnonzero(bad_file).tolist()
    if control:
        out["control"] = pick(sum(c for _, c in sums))
    out["seconds"] = time.perf_counter() - t0
    return out
