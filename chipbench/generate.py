"""Seeded SIGPROC files for the benchmark, written packed.

One general generator: everything that distinguishes one traffic mix from
another (file length in hops, which hops hold a pulse, its S/N, width and
DM range, the RFI) is a parameter of the traffic file; everything that
distinguishes one geometry from another (channels, band, sample time, hop)
is a parameter of the configuration file.

Speed is the point (the program's own rehearsal generator writes 6 MiB/s):
the radiometer noise is never drawn as floats.  A sample's quantised level
is a function of one uniform draw (a table built from the normal
distribution's level probabilities).  At 1 and 2 bits a byte of randomness
a sample resolves the levels: two channels are looked up at once from one
uniform 16-bit draw, and two (four) such pairs make a packed byte.  At 4
and 8 bits a byte would leave 256 levels unresolved, so every sample has a
16-bit draw of its own.  Samples are packed ``8 // nbits`` channels a byte
in SIGPROC's order, the lowest channel in the lowest bits.  Pulses, hot
channels and the mains comb shift the *mean* of the underlying normal, i.e.
they select another table; the traffic's ``*_levels`` are levels of the
file's own quantiser.  Blocks are drawn from independent child seeds, so
the bytes do not depend on the number of threads.

What a search does with a chunk that holds a pulse follows the chunk's
content (the rows it rescores, the record it persists), so where every
seed has to bring the same work a traffic file names a ``hit_seed``: the
pulses and every block of the hops a pulse chunk covers (a pulse's hop and
its two neighbours) are drawn from it, the other hops' noise from the
run's seed.  Without the key everything is drawn from the run's seed.
"""

from __future__ import annotations

import math
import os
import struct
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import dispersion

BLOCK = 1 << 16  # samples per generation block, at most
BLOCK_CELLS = 1 << 26  # ... and channels x samples per block, at most
NBITS = (1, 2, 4, 8)


def _phi(x):
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def level_probabilities(mean, sd, nlevels=4):
    """P(level k) of ``clip(rint(N(mean, sd)), 0, nlevels-1)``."""
    edges = [-math.inf] + [k + 0.5 for k in range(nlevels - 1)] + [math.inf]
    cdf = [0.0 if e == -math.inf else 1.0 if e == math.inf
           else _phi((e - mean) / sd) for e in edges]
    return np.diff(np.array(cdf))


class LevelTables:
    """Uniform draw -> level tables, by mean: a byte of randomness a sample
    at 1 and 2 bits (and a 16-bit draw -> two levels), 16 bits a sample at
    4 and 8."""

    def __init__(self, sd, nbits=2):
        self.sd = sd
        self.nbits = nbits
        self.nlevels = 1 << nbits
        self.draw_bits = 8 if nbits <= 2 else 16
        self.draw_dtype = np.dtype(f"uint{self.draw_bits}")
        self._lut = {}
        self._lut16 = {}

    def draw(self, rng, size):
        """One uniform draw a sample, of the width :meth:`lut` indexes."""
        return rng.integers(0, 1 << self.draw_bits, size,
                            dtype=self.draw_dtype)

    def lut(self, mean):
        key = round(float(mean), 9)
        if key not in self._lut:
            cum = np.cumsum(level_probabilities(key, self.sd, self.nlevels))
            n = 1 << self.draw_bits
            u = (np.arange(n) + 0.5) / n
            self._lut[key] = np.minimum(
                np.searchsorted(cum, u), self.nlevels - 1).astype(np.uint8)
        return self._lut[key]

    def lut16(self, mean):
        """16-bit draw -> two neighbouring channels' levels (1 and 2 bits)."""
        key = round(float(mean), 9)
        if key not in self._lut16:
            l8 = self.lut(key)
            v = np.arange(1 << 16)
            self._lut16[key] = (l8[v & 255] | (l8[v >> 8] << self.nbits)
                                ).astype(np.uint8)
        return self._lut16[key]

    def moments(self, mean):
        """Mean and standard deviation of the quantised level, as the
        table realises them."""
        lv = self.lut(mean).astype(np.float64)
        return float(lv.mean()), float(lv.std())


def sigproc_header(cfg, source_name="CHIPBENCH"):
    def s(x):
        b = x.encode("ascii")
        return struct.pack("<i", len(b)) + b

    out = s("HEADER_START")
    out += s("source_name") + s(source_name)
    for key, val in (("data_type", 1), ("nchans", cfg["nchans"]),
                     ("nbits", cfg["nbits"]), ("nifs", 1)):
        out += s(key) + struct.pack("<i", int(val))
    for key, val in (("tsamp", cfg["tsamp_s"]), ("fch1", cfg["fch1_mhz"]),
                     ("foff", cfg["foff_mhz"]), ("tstart", 60000.0)):
        out += s(key) + struct.pack("<d", float(val))
    return out + s("HEADER_END")


def hit_seed(traffic, seed):
    """The seed of the pulses and of the hops their chunks cover."""
    return int(traffic.get("hit_seed", seed))


def hit_hops(traffic):
    """The hops a chunk that holds a pulse covers."""
    return sorted({h + k for h in traffic["pulse_hops"] for k in (-1, 0, 1)
                   if 0 <= h + k < traffic["hops_per_file"]})


def draw_pulses(cfg, traffic, seed, tables):
    """``[(sample, dm, amp_levels_total, width, target_snr)]``: one pulse
    in each hop the traffic names, its whole dispersion track inside that
    hop, so that exactly the chunks covering the hop hold it."""
    nchan, tsamp = cfg["nchans"], cfg["tsamp_s"]
    hop = cfg["chunk_samples"] // 2
    fbottom, bandwidth = dispersion.band_edges(
        cfg["fch1_mhz"], cfg["foff_mhz"], nchan)
    rng = np.random.default_rng(
        np.random.SeedSequence([hit_seed(traffic, seed), 1]))
    reach = int(np.abs(dispersion.channel_shifts(
        cfg["dmmax"], nchan, fbottom, bandwidth, tsamp)).max())
    margin = reach + max(traffic["pulse_widths"]) + 64
    if 2 * margin >= hop:
        raise ValueError("a hop cannot hold a whole track at dmmax")
    lo_f, hi_f = traffic["pulse_dm_fraction"]
    span = cfg["dmmax"] - cfg["dmmin"]
    _, sigma_q = tables.moments(traffic["noise_mean_levels"])
    ngood = nchan - len(traffic["hot_channels"])
    pulses = []
    for h in traffic["pulse_hops"]:
        pos = h * hop + int(rng.integers(margin, hop - margin))
        dm = float(rng.uniform(cfg["dmmin"] + lo_f * span,
                               cfg["dmmin"] + hi_f * span))
        width = int(rng.choice(traffic["pulse_widths"]))
        snr = float(rng.uniform(*traffic["pulse_snr"]))
        amp = snr * sigma_q * math.sqrt(width) / math.sqrt(ngood)
        pulses.append((pos, dm, amp, width, snr))
    return pulses


def generate(path, cfg, traffic, seed, threads=None):
    """Write the file; returns a dict describing what is in it."""
    t0 = time.perf_counter()
    nbits = cfg["nbits"]
    if nbits not in NBITS:
        raise ValueError(f"the generator packs {NBITS}-bit samples")
    per = 8 // nbits  # channels a byte
    nchan, tsamp = cfg["nchans"], cfg["tsamp_s"]
    if nchan % per:
        raise ValueError("nchans must pack to whole bytes")
    hop = cfg["chunk_samples"] // 2
    nsamples = traffic["hops_per_file"] * hop
    descending = cfg["foff_mhz"] < 0
    fbottom, bandwidth = dispersion.band_edges(
        cfg["fch1_mhz"], cfg["foff_mhz"], nchan)
    tables = LevelTables(traffic["noise_sd_levels"], nbits)
    mu = traffic["noise_mean_levels"]
    comb = traffic.get("comb") or None
    comb_amp = comb["amp_levels"] if comb else 0.0
    # hot channels are given for a 1,024-channel band and scale with it
    hot = {(int(c["channel_of_1024"]) * nchan) // 1024: c["excess_levels"]
           for c in traffic["hot_channels"]}
    pulses = draw_pulses(cfg, traffic, seed, tables)

    def file_chan(c):
        return nchan - 1 - c if descending else c

    # every (channel, sample) a pulse raises, with the mean it gets
    p_rows, p_chan, p_mean, p_u = [], [], [], []
    prng = np.random.default_rng(
        np.random.SeedSequence([hit_seed(traffic, seed), 2]))
    for pos, dm, amp, width, _ in pulses:
        sh = dispersion.channel_shifts(dm, nchan, fbottom, bandwidth, tsamp)
        for k in range(width):
            p_rows.append(pos + sh + k)
            p_chan.append(np.arange(nchan))
            p_mean.append(np.full(nchan, mu + amp / width))
            p_u.append(tables.draw(prng, nchan))
    p_rows = np.concatenate(p_rows) if p_rows else np.zeros(0, np.int64)
    p_chan = np.concatenate(p_chan) if p_chan else np.zeros(0, np.int64)
    p_mean = np.concatenate(p_mean) if p_mean else np.zeros(0)
    p_u = np.concatenate(p_u) if p_u else np.zeros(0, tables.draw_dtype)
    keep = ~np.isin(p_chan, list(hot))
    p_rows, p_chan, p_mean, p_u = (p_rows[keep], p_chan[keep], p_mean[keep],
                                   p_u[keep])
    if p_rows.size and (p_rows.min() < 0 or p_rows.max() >= nsamples):
        raise ValueError("a pulse track leaves the file")

    def comb_on(idx):
        if not comb:
            return np.zeros(idx.shape, dtype=bool)
        return np.sin(2 * np.pi * comb["hz"] * idx * tsamp) > 0

    def put(packed, rows, chan, levels):
        fc = file_chan(np.asarray(chan))
        rows, levels = np.asarray(rows), np.asarray(levels)
        # one bit position at a time: neighbouring channels share a byte,
        # and a fancy assignment keeps only the last write to an element
        for k in range(per):
            pick = fc % per == k
            r, col = rows[pick], fc[pick] // per
            packed[r, col] = ((packed[r, col]
                               & ~np.uint8((1 << nbits) - 1 << nbits * k))
                              | (levels[pick] << np.uint8(nbits * k)))

    def noise(rng, n, mean, rows_on, mean_on):
        """``(n, nchan / per)`` packed bytes of noise about ``mean``, about
        ``mean_on`` in the rows the comb raises."""
        if nbits <= 2:
            # a byte of randomness a sample: one 16-bit draw, two channels
            r = rng.integers(0, 1 << 16, size=(n, nchan // 2),
                             dtype=np.uint16)
            lut, lut_on = tables.lut16(mean), tables.lut16(mean_on)
            width, group = 2 * nbits, per // 2
        else:
            r = tables.draw(rng, (n, nchan))
            lut, lut_on = tables.lut(mean), tables.lut(mean_on)
            width, group = nbits, per
        t = lut[r]
        if rows_on.size:
            t[rows_on] = lut_on[r[rows_on]]
        del r
        if group == 1:
            return t
        packed = t[:, 0::group]
        for k in range(1, group):
            packed = packed | (t[:, k::group] << np.uint8(width * k))
        return packed

    block_len = min(BLOCK, max(256, BLOCK_CELLS // nchan))
    nblocks = -(-nsamples // block_len)
    seeds = np.random.SeedSequence([int(seed), 3]).spawn(nblocks)
    if hit_seed(traffic, seed) != int(seed):
        # a block that reaches into a pulse chunk's hops is the hit seed's
        fixed = np.random.SeedSequence(
            [hit_seed(traffic, seed), 3]).spawn(nblocks)
        held = hit_hops(traffic)
        for i in range(nblocks):
            lo, hi = i * block_len, min((i + 1) * block_len, nsamples) - 1
            if any(lo // hop <= h <= hi // hop for h in held):
                seeds[i] = fixed[i]

    def block(i):
        lo = i * block_len
        n = min(block_len, nsamples - lo)
        rng = np.random.default_rng(seeds[i])
        on = comb_on(lo + np.arange(n))
        packed = noise(rng, n, mu, np.flatnonzero(on), mu + comb_amp)
        for c, excess in sorted(hot.items()):
            u = tables.draw(rng, n)
            lv = np.where(on, tables.lut(mu + excess + comb_amp)[u],
                          tables.lut(mu + excess)[u])
            put(packed, np.arange(n), np.full(n, c), lv)
        sel = np.flatnonzero((p_rows >= lo) & (p_rows < lo + n))
        if sel.size:
            rows = p_rows[sel] - lo
            means = p_mean[sel] + np.where(on[rows], comb_amp, 0.0)
            lv = np.empty(sel.size, dtype=np.uint8)
            for m in np.unique(means):
                pick = means == m
                lv[pick] = tables.lut(m)[p_u[sel][pick]]
            put(packed, rows, p_chan[sel], lv)
        return packed

    threads = threads or min(8, os.cpu_count() or 1)
    with open(path, "wb") as f, ThreadPoolExecutor(threads) as pool:
        f.write(sigproc_header(cfg))
        # bounded look-ahead: at most ``threads`` blocks are alive
        pending = []
        for i in range(nblocks):
            pending.append(pool.submit(block, i))
            if len(pending) >= threads:
                pending.pop(0).result().tofile(f)
        for fut in pending:
            fut.result().tofile(f)
    return {
        "path": path, "nsamples": nsamples, "hop": hop,
        "bytes": os.path.getsize(path), "seconds": time.perf_counter() - t0,
        "duration_s": nsamples * tsamp,
        "hit_seed": hit_seed(traffic, seed),
        "pulses": [{"sample": p, "dm": d, "amp_levels": a, "width": w,
                    "target_snr": s} for p, d, a, w, s in pulses],
        "hot_channels": sorted(hot),
    }
