"""Operations and bytes of the 8-bit decode-and-clean of one chunk, from
the cell's shapes alone (``kernel_counts.py``'s conventions: what the
algorithm needs for one chunk, never what an implementation moves)."""

from __future__ import annotations


def unpack8_clean_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                         tsamp, itemsize=4):
    """One ``nsamples x nchan`` chunk of 8-bit samples widened, transposed
    and conditioned into the ``nchan x nsamples`` float32 chunk the sweeps
    read.  bytes: one byte read and ``itemsize`` written a sample; the
    per-channel and per-sample statistics of the clean fit fast memory
    beside a tile, so a second pass over the chunk is the implementation's
    and is not counted.  adds: one subtraction and one scaling a sample for
    the channel's baseline, one subtraction for the zero-DM series."""
    samples = nchan * nsamples
    return {"bytes": samples * (1 + itemsize), "flops": 3 * samples}
