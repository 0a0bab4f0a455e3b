"""Native SIGPROC filterbank I/O.

The reference reads filterbank files through the third-party
``sigpyproc.Readers.FilReader`` (``pulsarutils/clean.py:18,284-294``,
``pulsarutils/stats.py:6,37``).  This framework implements the format
natively: a binary header of length-prefixed keyword/value records between
``HEADER_START`` and ``HEADER_END``, followed by time-major sample frames
of ``nifs * nchans`` values at 8/16/32 bits.

Provided:

* :class:`FilterbankReader` — memory-mapped reader with the
  ``read_block(istart, nsamples) -> (nchans, n)`` access pattern the
  pipeline drivers use, plus a sigpyproc-compatible ``header`` dict
  (``fbottom``/``ftop``/``bandwidth``/``foff``/``nchans``/``tsamp``/
  ``nsamples``/``tstart`` — the exact keys the reference pipeline consumes,
  ``clean.py:284-294``).
* :class:`FilterbankWriter` / :func:`write_filterbank` — streaming writer,
  which also makes ``PUclean`` a real tool (the reference's
  ``cleanup_data`` was a stub, ``clean.py:354-357``).

Byte order is little-endian (SIGPROC convention on all modern hardware).
"""

from __future__ import annotations

import os
import struct

import numpy as np

from ..faults import inject as fault_inject

_INT_KEYS = {
    "machine_id", "telescope_id", "data_type", "barycentric",
    "pulsarcentric", "nbits", "nsamples", "nchans", "nifs", "nbeams",
    "ibeam",
}
_DOUBLE_KEYS = {
    "az_start", "za_start", "src_raj", "src_dej", "tstart", "tsamp",
    "fch1", "foff", "refdm", "period",
}
_STR_KEYS = {"source_name", "rawdatafile"}
#: single-byte keys (sigproc's ``signed`` flag for 8-bit data)
_CHAR_KEYS = {"signed"}

_DTYPES = {8: np.uint8, 16: np.uint16, 32: np.float32}


def _pack_string(s):
    b = s.encode("ascii")
    return struct.pack("<i", len(b)) + b


def _pack_record(key, value):
    rec = _pack_string(key)
    if key in _INT_KEYS:
        rec += struct.pack("<i", int(value))
    elif key in _DOUBLE_KEYS:
        rec += struct.pack("<d", float(value))
    elif key in _STR_KEYS:
        rec += _pack_string(str(value))
    elif key in _CHAR_KEYS:
        rec += struct.pack("<b", int(value))
    else:
        raise KeyError(f"unknown SIGPROC header key {key!r}")
    return rec


def _read_exact(f, n, path, what):
    """Read exactly ``n`` bytes or raise a clean ``ValueError`` naming
    the byte offset and expected length (a file truncated mid-header
    used to surface as a raw ``struct.error`` from ``struct.unpack``)."""
    offset = f.tell()
    data = f.read(n)
    if len(data) != n:
        raise ValueError(
            f"{path}: truncated SIGPROC header — expected {n} bytes for "
            f"{what} at byte offset {offset}, got {len(data)}")
    return data


def read_header(path):
    """Parse a SIGPROC header.  Returns ``(header_dict, data_offset)``."""
    header = {}
    with open(path, "rb") as f:
        def read_string():
            (n,) = struct.unpack(
                "<i", _read_exact(f, 4, path, "a string length"))
            if not 0 < n < 128:
                raise ValueError(f"corrupt SIGPROC header string length {n}")
            return _read_exact(f, n, path,
                               "a header string").decode("ascii")

        if read_string() != "HEADER_START":
            raise ValueError(f"{path}: not a SIGPROC filterbank file")
        while True:
            key = read_string()
            if key == "HEADER_END":
                break
            if key in _INT_KEYS:
                (header[key],) = struct.unpack(
                    "<i", _read_exact(f, 4, path, f"int key {key!r}"))
            elif key in _DOUBLE_KEYS:
                (header[key],) = struct.unpack(
                    "<d", _read_exact(f, 8, path, f"double key {key!r}"))
            elif key in _STR_KEYS:
                header[key] = read_string()
            elif key in _CHAR_KEYS:
                (header[key],) = struct.unpack(
                    "<b", _read_exact(f, 1, path, f"char key {key!r}"))
            else:
                # unknown keys cannot be skipped (their payload length is
                # key-specific), so fail loudly with the offending name
                raise ValueError(f"{path}: unknown header key {key!r}")
        return header, f.tell()


def derived_header(header, data_size_bytes):
    """Add the derived fields the pipeline consumes (band edges, size).

    Channel ``i`` has centre frequency ``fch1 + i * foff``; band edges
    extend half a channel beyond the extreme centres.  ``foff < 0``
    (descending band) is the common convention; both signs are handled.
    """
    h = dict(header)
    nchans = h["nchans"]
    nifs = h.get("nifs", 1)
    nbits = h.get("nbits", 32)
    fch1, foff = h["fch1"], h["foff"]
    centres = fch1 + np.arange(nchans) * foff
    h["bandwidth"] = abs(foff) * nchans
    h["fbottom"] = float(centres.min() - abs(foff) / 2)
    h["ftop"] = float(centres.max() + abs(foff) / 2)
    bytes_per_sample = nchans * nifs * nbits // 8
    available = int(data_size_bytes // bytes_per_sample)
    if "nsamples" not in h or h["nsamples"] <= 0:
        h["nsamples"] = available
    else:
        # a truncated data section (interrupted write / partial transfer)
        # must not crash the memmap — read what is actually present
        h["nsamples"] = min(int(h["nsamples"]), available)
    h.setdefault("tstart", 0.0)
    return h


class FilterbankReader:
    """Memory-mapped SIGPROC filterbank reader.

    ``read_block(istart, n)`` returns a float ``(nchans, n)`` array in
    **ascending frequency order** when ``band_ascending=True`` (default
    False returns file order) — the reference flips descending bands by
    hand in its chunk loop (``clean.py:332-333``); the flag folds that in.

    Multi-IF files (``nifs > 1`` — polarisation/IF planes interleaved
    per time frame as ``[t][if][chan]``, the SIGPROC layout) are
    supported natively (the reference inherited this from sigpyproc's
    ``FilReader``, used at ``clean.py:284-294`` / ``stats.py:37``):
    ``if_mode`` selects what ``read_block`` returns —

    * ``"sum"`` (default): total intensity, the IF planes summed — what
      a single-pulse search wants from e.g. dual-polarisation data;
    * an integer ``k``: IF plane ``k`` alone.
    """

    def __init__(self, path, if_mode="sum"):
        self.path = path
        raw_header, offset = read_header(path)
        data_size = os.path.getsize(path) - offset
        self.header = derived_header(raw_header, data_size)
        nbits = self.header.get("nbits", 32)
        self._nbits = nbits
        nifs = self.header.get("nifs", 1)
        self.nifs = nifs
        if if_mode != "sum":
            k = int(if_mode)
            if not 0 <= k < nifs:
                raise ValueError(f"if_mode={if_mode!r}: file has {nifs} "
                                 "IF planes")
        self.if_mode = if_mode
        nchans = self.header["nchans"]
        width = nifs * nchans  # values per time frame
        if nbits in (1, 2, 4):
            # packed low-bit samples: mmap the raw bytes, unpack per block
            # (native C loop when available — io/lowbit.py)
            if (width * nbits) % 8:
                raise ValueError(
                    f"nchans={nchans} x nifs={nifs} at nbits={nbits} does "
                    "not pack to whole bytes")
            self._mmap = np.memmap(
                path, dtype=np.uint8, mode="r", offset=offset,
                shape=(self.header["nsamples"], width * nbits // 8))
        elif nbits in _DTYPES:
            self._dtype = _DTYPES[nbits]
            if nbits == 8 and self.header.get("signed"):
                self._dtype = np.int8  # sigproc ``signed`` char flag
            self._mmap = np.memmap(path, dtype=self._dtype, mode="r",
                                   offset=offset,
                                   shape=(self.header["nsamples"], width))
        else:
            raise ValueError(f"unsupported nbits={nbits}")

    @property
    def nsamples(self):
        return self.header["nsamples"]

    @property
    def nchans(self):
        return self.header["nchans"]

    @property
    def band_descending(self):
        return self.header["foff"] < 0

    @property
    def packed_bits(self):
        """Bit depth of a file whose raw frames :meth:`read_block_packed`
        serves — 1, 2 or 4 bits packed, or 8 bits unsigned, one IF — and
        0 for every other (signed 8-bit, 16- and 32-bit, multi-IF), which
        :meth:`read_block` decodes on the host.  THE rule of the packed
        path: the search's upload and the bad-channel pre-scan ask it."""
        raw_bytes = self._mmap.dtype == np.uint8
        return self._nbits if raw_bytes and self.nifs == 1 else 0

    @property
    def nbeams(self):
        """Total beams of the observation this file belongs to (sigproc
        ``nbeams`` header key; ``None`` when the header omits it)."""
        n = self.header.get("nbeams")
        return int(n) if n is not None else None

    @property
    def ibeam(self):
        """This file's beam number (sigproc ``ibeam``, conventionally
        1-based; ``None`` when absent).  The multi-beam driver uses it
        to label per-beam candidates, canaries and coincidence groups
        without re-opening files."""
        b = self.header.get("ibeam")
        return int(b) if b is not None else None

    def read_block(self, istart, nsamps, band_ascending=False):
        istart = int(istart)
        fault_inject.fire("read", chunk=istart)
        nsamps = int(min(nsamps, self.nsamples - istart))
        nsamps = fault_inject.truncated_length("read", istart, nsamps)
        raw = np.asarray(self._mmap[istart:istart + nsamps])
        return self.unpack_frames(raw, band_ascending=band_ascending)

    def read_block_packed(self, istart, nsamps):
        """Raw packed frames ``(nsamps, bytes_per_frame)`` uint8 — the
        low-bit fast path: callers ship THESE over the host->device
        link (1/16th the bytes of float32 at 2 bits, a quarter at 8) and
        unpack in the device-clean jit
        (:func:`..io.lowbit.device_unpack_block`);
        :meth:`unpack_frames` is the matching host-side decode for
        fallback paths.  Files with :attr:`packed_bits` only — 1/2/4-bit
        and unsigned 8-bit, single-IF: the device-side unpack takes the
        first ``nchans`` values of each frame, which on a multi-IF file
        would silently decode IF 0 instead of honouring ``if_mode`` the
        way :meth:`read_block` does."""
        if self.nifs != 1:
            raise ValueError(
                f"read_block_packed is single-IF only (nifs={self.nifs}); "
                "use read_block, which honours if_mode")
        if not self.packed_bits:
            raise ValueError(
                f"read_block_packed needs a packed low-bit or unsigned "
                f"8-bit file (nbits={self._nbits}, "
                f"{self._mmap.dtype.name} samples)")
        istart = int(istart)
        fault_inject.fire("read", chunk=istart)
        nsamps = int(min(nsamps, self.nsamples - istart))
        nsamps = fault_inject.truncated_length("read", istart, nsamps)
        return np.asarray(self._mmap[istart:istart + nsamps])

    def unpack_frames(self, raw, band_ascending=False):
        """Decode raw frames (packed low-bit or plain) to the
        ``(nchan, nsamps)`` float block ``read_block`` returns."""
        nsamps = raw.shape[0]
        if self._nbits in (1, 2, 4):
            from .lowbit import unpack

            frames = unpack(raw, self._nbits).reshape(
                nsamps, self.nifs, self.nchans).astype(float)
        elif self._nbits == 8 and (self.nifs == 1 or self.if_mode != "sum"):
            # one plane of bytes: transposed as stored, widened after (to
            # float32, which holds every 8-bit value) — a chunk of
            # 131,072 x 4,096 takes 6 s so, 18 + 18 s widened to float64
            # first and copied transposed later (PERF.md section 6, PR 34)
            plane = 0 if self.nifs == 1 else int(self.if_mode)
            block = np.ascontiguousarray(
                raw.reshape(nsamps, self.nifs, self.nchans)[:, plane].T)
            if band_ascending and self.band_descending:
                block = block[::-1]
            return block.astype(np.float32)
        else:
            frames = raw.reshape(nsamps, self.nifs,
                                 self.nchans).astype(float)
        if self.nifs == 1:
            block = frames[:, 0].T
        elif self.if_mode == "sum":
            block = frames.sum(axis=1).T
        else:
            block = frames[:, int(self.if_mode)].T
        if band_ascending and self.band_descending:
            block = block[::-1]
        return block

    def readBlock(self, istart, nsamps, as_filterbankBlock=False,
                  band_ascending=False):
        """sigpyproc-compatible alias: the reference calls
        ``readBlock(istart, size, as_filterbankBlock=False)``
        (reference ``stats.py:44``, ``clean.py:327``); the flag is accepted
        and ignored (plain arrays are always returned)."""
        return self.read_block(istart, nsamps, band_ascending=band_ascending)

    def iter_blocks(self, chunksize, band_ascending=False):
        """Yield ``(istart, block)`` over the whole file."""
        for istart in range(0, self.nsamples, chunksize):
            yield istart, self.read_block(istart, chunksize,
                                          band_ascending=band_ascending)


class FilterbankWriter:
    """Streaming SIGPROC filterbank writer (time-major frames).

    With ``nifs > 1`` in the header, :meth:`write_block` takes
    ``(nifs, nchans, n)`` blocks and interleaves the IF planes per time
    frame (the SIGPROC ``[t][if][chan]`` layout the reader expects).
    """

    def __init__(self, path, header):
        self.path = path
        self.header = dict(header)
        self.nchans = int(self.header["nchans"])
        self.nifs = int(self.header.get("nifs", 1))
        self.nbits = int(self.header.get("nbits", 32))
        if self.nbits in (1, 2, 4):
            if (self.nifs * self.nchans * self.nbits) % 8:
                raise ValueError(
                    f"nchans={self.nchans} x nifs={self.nifs} at "
                    f"nbits={self.nbits} does not pack to whole bytes")
            self._dtype = np.uint8
        elif self.nbits in _DTYPES:
            self._dtype = _DTYPES[self.nbits]
            if self.nbits == 8 and self.header.get("signed"):
                self._dtype = np.int8  # sigproc ``signed`` char flag
        else:
            raise ValueError(f"unsupported nbits={self.nbits}")
        self._file = open(path, "wb")
        self._nsamples_written = 0
        self._file.write(_pack_string("HEADER_START"))
        for key in sorted(set(self.header) & (_INT_KEYS | _DOUBLE_KEYS |
                                              _STR_KEYS | _CHAR_KEYS)):
            if key == "nsamples":
                continue  # computed from data size on read
            self._file.write(_pack_record(key, self.header[key]))
        self._file.write(_pack_string("HEADER_END"))

    def write_block(self, block):
        """Write a ``(nchans, n)`` block (channel-major in, time-major
        out), or ``(nifs, nchans, n)`` for a multi-IF file."""
        block = np.asarray(block)
        if self.nifs > 1:
            if block.ndim != 3 or block.shape[:2] != (self.nifs,
                                                      self.nchans):
                raise ValueError(
                    f"multi-IF block must be ({self.nifs}, {self.nchans}, "
                    f"n); got {block.shape}")
            nsamps = block.shape[2]
            frames = np.ascontiguousarray(
                block.transpose(2, 0, 1)).reshape(nsamps,
                                                  self.nifs * self.nchans)
        else:
            if block.shape[0] != self.nchans:
                raise ValueError(f"block has {block.shape[0]} channels, "
                                 f"expected {self.nchans}")
            nsamps = block.shape[1]
            frames = np.ascontiguousarray(block.T)
        if self.nbits in (1, 2, 4):
            from .lowbit import pack

            frames = pack(frames, self.nbits)  # clips to [0, 2^nbits - 1]
            self._file.write(frames.tobytes())
            self._nsamples_written += nsamps
            return
        if self.nbits < 32:
            info = np.iinfo(self._dtype)
            frames = np.clip(np.rint(frames), info.min, info.max)
        self._file.write(frames.astype(self._dtype).tobytes())
        self._nsamples_written += nsamps

    def close(self):
        if not self._file.closed:
            self._file.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def write_filterbank(path, data, tsamp, fch1, foff, nbits=32, tstart=0.0,
                     source_name="pulsarutils_tpu", **extra):
    """Write a whole ``(nchans, nsamples)`` array as a filterbank file."""
    data = np.asarray(data)
    header = {
        "nchans": data.shape[0],
        "nbits": nbits,
        "nifs": 1,
        "tsamp": tsamp,
        "fch1": fch1,
        "foff": foff,
        "tstart": tstart,
        "source_name": source_name,
        "machine_id": 0,
        "telescope_id": 0,
        "data_type": 1,
    }
    header.update(extra)
    with FilterbankWriter(path, header) as w:
        w.write_block(data)
    return header


def write_simulated_filterbank(path, array, sim_header, descending=False,
                               **extra):
    """Write a simulator-convention array (ascending band, row i = lowest
    frequency first) as a filterbank file, handling the row flip a
    descending-band header requires.

    Use this instead of composing :func:`write_filterbank` +
    :func:`header_from_simulated` by hand — forgetting the row flip for
    ``descending=True`` silently corrupts the band orientation and ruins
    DM recovery.
    """
    data = np.asarray(array)[::-1] if descending else array
    kw = header_from_simulated(sim_header, descending=descending)
    kw.update(extra)
    return write_filterbank(path, data, **kw)


def header_from_simulated(sim_header, descending=False):
    """Map a simulator header (ascending-band, band-edge keys) onto writer
    kwargs (``fch1``/``foff`` channel-centre convention)."""
    nchan = sim_header["nchans"]
    df = sim_header["bandwidth"] / nchan
    if descending:
        fch1 = sim_header["fbottom"] + sim_header["bandwidth"] - df / 2
        foff = -df
    else:
        fch1 = sim_header["fbottom"] + df / 2
        foff = df
    return {"tsamp": sim_header["tsamp"], "fch1": fch1, "foff": foff}
