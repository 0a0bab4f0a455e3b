"""Operations and bytes a kernel needs, from its shapes alone.

Kept with the benchmark so that a roofline share means the same thing in
every PR.  Each function returns what the *algorithm* needs for one call,
never what an implementation happens to move: recomputation and spills
count against the kernel, not for it.
"""

from __future__ import annotations

import math

from . import dispersion


def fdmt_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth, tsamp,
                itemsize=4):
    """The tree dedispersion (FDMT) of one ``nchan x nsamples`` float32
    chunk over the band delays of ``dmmin..dmmax``.

    bytes: the chunk read once.  Every implementation must do that; the
    output plane (a few hundred rows) may be scored in fast memory and
    never written, so it is not counted.  adds: at each of the
    ``log2(nchan)`` merges every output row of every sub-band costs one
    add per sample; a sub-band keeps the rows between its share of the
    lowest and of the highest band delay.
    """
    f0, f1 = float(fbottom), float(fbottom) + float(bandwidth)
    n_lo = math.floor(dispersion.band_delay_samples(dmmin, f0, bandwidth,
                                                    tsamp))
    n_hi = math.ceil(dispersion.band_delay_samples(dmmax, f0, bandwidth,
                                                   tsamp))
    full = f0 ** -2.0 - f1 ** -2.0
    adds = 0
    nsub = nchan
    while nsub > 1:
        nsub = -(-nsub // 2)
        width = bandwidth / nsub
        for b in range(nsub):
            lo = f0 + b * width
            share = (lo ** -2.0 - (lo + width) ** -2.0) / full
            rows = math.ceil(n_hi * share) - math.floor(n_lo * share) + 1
            adds += rows * nsamples
    return {"bytes": nchan * nsamples * itemsize, "flops": adds,
            "rows_out": n_hi - n_lo + 1}


def roofline_seconds(counts, peaks):
    """Least time the chip could take, and which roof sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_cmp = counts["flops"] / peaks["flops_per_s_bf16"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
