"""The plain reference: what the pulse chunk's best row has to be.

NumPy and SciPy only; imports nothing of the program and reads nothing the
program made (its own header parser, its own reader of 1-, 2-, 4- and 8-bit
samples, its own bad-channel mask from its own pass over the file, its own
dispersion arithmetic in ``dispersion.py``).

It restates the upstream semantics (SURVEY.md: ``clean.py:70-111``,
``dedispersion.py:173-201``, ``stats.py:63-90``) in float64:

* bad channels: per-channel mean and standard deviation over the whole
  file; a channel is bad where either exceeds ``medfilt(spec, 11) +
  4 * mad(diff(spec)) / sqrt(2)``;
* clean: divide out the Gaussian-smoothed (sigma 101 samples, reflect,
  truncate 4) mean light curve of the good channels, normalise every
  channel to fractional deviation from its own mean, zero the bad ones;
  optionally subtract the per-sample mean over good channels (zero-DM);
* dedisperse at a trial: circular roll of every channel by its integer
  delay, summed over channels;
* score: subtract the row mean; for boxcar widths 1, 2, 4, 8 (non-
  overlapping block sums) S/N = max / std, the first strictly largest wins;
  the peak is the block's index times the width.

It evaluates the whole pulse chunk, but only the few trial rows next to the
injected DM (a row is 1,024 slice-adds over the chunk).  The control is the
same computation with the cleaned chunk stored in bfloat16, the precision
below the float32 the configurations state.
"""

from __future__ import annotations

import os
import struct
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import dispersion

WINDOWS = (1, 2, 4, 8)
_INT = {"machine_id", "telescope_id", "data_type", "barycentric",
        "pulsarcentric", "nbits", "nsamples", "nchans", "nifs", "nbeams",
        "ibeam"}
_DBL = {"az_start", "za_start", "src_raj", "src_dej", "tstart", "tsamp",
        "fch1", "foff", "refdm", "period"}
_STR = {"source_name", "rawdatafile"}


def read_header(path):
    """SIGPROC header -> ``(dict, data offset)``."""
    hdr = {}
    with open(path, "rb") as f:
        def string():
            (n,) = struct.unpack("<i", f.read(4))
            if not 0 < n < 128:
                raise ValueError("corrupt SIGPROC header")
            return f.read(n).decode("ascii")

        if string() != "HEADER_START":
            raise ValueError(f"{path}: not a SIGPROC file")
        while True:
            key = string()
            if key == "HEADER_END":
                return hdr, f.tell()
            if key in _INT:
                (hdr[key],) = struct.unpack("<i", f.read(4))
            elif key in _DBL:
                (hdr[key],) = struct.unpack("<d", f.read(8))
            elif key in _STR:
                hdr[key] = string()
            else:
                raise ValueError(f"unknown SIGPROC key {key!r}")


def read_packed(path):
    """The file's 1-, 2-, 4- or 8-bit samples as ``(nchan * nbits / 8,
    nsamples)`` packed bytes, FILE channel order (at ``per = 8 / nbits``
    channels a byte, byte row j holds file channels ``per * j .. per * j +
    per - 1``, the lowest in the low bits), plus the header, whose
    ``nbits`` says how to take a row apart."""
    hdr, off = read_header(path)
    if hdr["nbits"] not in (1, 2, 4, 8) or hdr.get("nifs", 1) != 1:
        raise ValueError("the reference reads single-IF 1-, 2-, 4- and "
                         "8-bit files")
    nrow = hdr["nchans"] * hdr["nbits"] // 8
    raw = np.fromfile(path, dtype=np.uint8, offset=off)
    nsamples = raw.size // nrow
    raw = raw[: nsamples * nrow].reshape(nsamples, nrow)
    return np.ascontiguousarray(raw.T), hdr


def _file_channel(packed_T, nbits, fc, lo=None, hi=None):
    per = 8 // nbits
    row = packed_T[fc // per, lo:hi]
    if per == 1:
        return row
    return (row >> np.uint8(nbits * (fc % per))) & np.uint8((1 << nbits) - 1)


def medfilt_zero_padded(x, size):
    from scipy.signal import medfilt

    return medfilt(np.asarray(x, dtype=np.float64), size)


def mad(x):
    x = np.asarray(x, dtype=np.float64)
    return np.median(np.abs(x - np.median(x))) / 0.6744897501960817


def bad_channels(packed_T, nbits):
    """Boolean mask in FILE channel order, from whole-file statistics.
    The per-channel moments are exact: they come from the counts of each
    byte value in each packed row."""
    nrow, nsamples = packed_T.shape
    per = 8 // nbits
    b = np.arange(256)
    mean = np.empty(nrow * per)
    std = np.empty(nrow * per)
    for j in range(nrow):
        hist = np.bincount(packed_T[j], minlength=256).astype(np.float64)
        for k in range(per):
            lv = (b >> (nbits * k)) & ((1 << nbits) - 1)
            s1 = float((hist * lv).sum())
            s2 = float((hist * lv * lv).sum())
            m = s1 / nsamples
            mean[per * j + k] = m
            std[per * j + k] = np.sqrt(max(s2 / nsamples - m * m, 0.0))
    bad = np.zeros(nrow * per, dtype=bool)
    for spec in (mean, std):
        sigma = mad(np.diff(spec)) / np.sqrt(2)
        bad |= spec > medfilt_zero_padded(spec, 11) + 4.0 * sigma
    return bad


def score_row(row):
    """``(snr, width, peak)`` of one dedispersed series."""
    x = row - row.mean()
    best = (0.0, 0, 0)
    reb = x
    for w in WINDOWS:
        if w > 1:
            n = reb.shape[0] // 2
            reb = reb[: 2 * n].reshape(n, 2).sum(axis=1)
        snr = float(reb.max() / reb.std())
        if snr > best[0]:
            best = (snr, w, int(np.argmax(reb)) * w)
    return best


def _bf16(x):
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def best_row(path, cfg, chunk_start, near_dm, half_rows=2, control=False,
             threads=None):
    """The reference's best row of the chunk ``[chunk_start, chunk_start +
    chunk_samples)`` among the ``2 * half_rows + 1`` trials nearest
    ``near_dm``.  Returns a dict; with ``control`` it also holds the best
    row of the bfloat16-stored computation under ``"control"``."""
    t0 = time.perf_counter()
    packed_T, hdr = read_packed(path)
    nchan, nbits, tsamp = hdr["nchans"], hdr["nbits"], hdr["tsamp"]
    descending = hdr["foff"] < 0
    fbottom, bandwidth = dispersion.band_edges(hdr["fch1"], hdr["foff"],
                                               nchan)
    T = cfg["chunk_samples"]
    lo, hi = chunk_start, chunk_start + T
    if hi > packed_T.shape[1]:
        raise ValueError("the chunk leaves the file")
    bad_file = bad_channels(packed_T, nbits)

    def fchan(c):  # ascending-band channel -> file channel
        return nchan - 1 - c if descending else c

    good = [c for c in range(nchan) if not bad_file[fchan(c)]]
    ngood = len(good)

    # mean light curve of the good channels (exact integer sums)
    total = np.zeros(T, dtype=np.uint32)
    for c in good:
        total += _file_channel(packed_T, nbits, fchan(c), lo, hi)
    lc = total.astype(np.float64) / max(ngood, 1)
    from scipy.ndimage import gaussian_filter1d

    window = min(101, T // 100 * 2 + 1)
    smooth = gaussian_filter1d(lc, window, mode="reflect", truncate=4.0)
    smooth = np.where(smooth == 0, 1.0, smooth)
    factor = np.median(smooth) / smooth

    trials = dispersion.trial_dms(cfg["dmmin"], cfg["dmmax"], fbottom,
                                  bandwidth, tsamp)
    centre = int(np.argmin(np.abs(trials - near_dm)))
    rows = list(range(max(centre - half_rows, 0),
                      min(centre + half_rows + 1, len(trials))))
    offs = dispersion.channel_shifts(trials[rows], nchan, fbottom, bandwidth,
                                     tsamp) % T

    threads = threads or min(8, os.cpu_count() or 1)
    parts = [good[i::threads] for i in range(threads)]
    zero_dm = bool(cfg.get("clean", {}).get("zero_dm", False))

    def spectrum(chans):
        """Per-channel mean of the flattened chunk, and (zero-DM) this
        part's share of the per-sample sum of normalised channels."""
        spec, msum = {}, np.zeros(T) if zero_dm else None
        for c in chans:
            u = _file_channel(packed_T, nbits, fchan(c), lo, hi) * factor
            s = float(u.mean())
            spec[c] = s if s != 0 else 1.0
            if zero_dm:
                msum += u / spec[c] - 1.0
        return spec, msum

    with ThreadPoolExecutor(threads) as pool:
        got = list(pool.map(spectrum, parts))
        spec = {c: s for part, _ in got for c, s in part.items()}
        mean_t = (sum(m for _, m in got) / max(ngood, 1)) if zero_dm \
            else None

        def accumulate(chans):
            acc = np.zeros((len(rows), T))
            ctl = np.zeros((len(rows), T)) if control else None
            for c in chans:
                u = _file_channel(packed_T, nbits, fchan(c), lo, hi) * factor
                v = (u - spec[c]) / spec[c]
                if zero_dm:
                    v -= mean_t
                vs = [(v, acc)]
                if control:
                    vs.append((_bf16(v.astype(np.float32)), ctl))
                for val, out in vs:
                    for r in range(len(rows)):
                        o = int(offs[r, c])
                        out[r, : T - o] += val[o:]
                        out[r, T - o:] += val[:o]
            return acc, ctl

        sums = list(pool.map(accumulate, parts))
    plane = sum(a for a, _ in sums)

    def pick(pl):
        scored = [score_row(pl[r]) for r in range(len(rows))]
        i = max(range(len(rows)), key=lambda r: (scored[r][0], -r))
        return {"DM": float(trials[rows[i]]), "row": int(rows[i]),
                "snr": scored[i][0], "rebin": scored[i][1],
                "peak": scored[i][2],
                "rows": [{"row": int(rows[r]), "DM": float(trials[rows[r]]),
                          "snr": scored[r][0], "rebin": scored[r][1],
                          "peak": scored[r][2]} for r in range(len(rows))]}

    out = pick(plane)
    out["ntrials"] = int(len(trials))
    out["bad_channels_file_order"] = np.flatnonzero(bad_file).tolist()
    if control:
        out["control"] = pick(sum(c for _, c in sums))
    out["seconds"] = time.perf_counter() - t0
    return out
