"""1/2/4-bit sample packing/unpacking for SIGPROC filterbanks, and the
raw-byte carrier of unsigned 8-bit ones.

The reference delegates filterbank decoding to the third-party
``sigpyproc`` (``clean.py:18``, ``stats.py:6``), which supports 1-32 bit
samples; this module provides the low-bit half of that capability
natively.  Bit order is LSB-first within each byte (lowest channel index
in the least-significant bits — the sigproc ecosystem convention).

Two implementations:

* a C++ lookup-table loop (``native/unpack.cpp``) compiled on demand
  with the system toolchain and loaded via ``ctypes`` — 3-5x faster
  than numpy on the streaming driver's hundreds-of-MB chunks.  The
  shared library is ALWAYS built from ``native/unpack.cpp`` on first
  use (``native/_unpack.<abi>.so``, git-ignored) and never shipped: a
  fresh checkout holds the source only, so what decodes the data is
  what this tree's source says, on every machine;
* a pure-numpy shift-and-mask fallback, always available, and the
  correctness oracle in the tests.

Use :func:`unpack` / :func:`pack`; they pick the native path when it
loads, unless ``PUTPU_NO_NATIVE=1``.
"""

from __future__ import annotations

import ctypes
import functools
import logging
import os
import subprocess
import sys
import tempfile

import numpy as np

logger = logging.getLogger("pulsarutils_tpu")

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native", "unpack.cpp")

#: values per byte for each width whose raw frames go to the device as
#: they are stored (8: one unsigned byte a sample, nothing to shift)
_PER_BYTE = {1: 8, 2: 4, 4: 2, 8: 1}

_lib = None
_lib_tried = False


def _build_library():
    """Compile unpack.cpp to a cached shared library; return its path.

    The cache lives next to the source (``native/_unpack.<abi>.so``) when
    writable, else in a per-user temp dir.  Rebuilds when the source is
    newer than the cached binary.
    """
    tag = f"cpython{sys.version_info.major}{sys.version_info.minor}"
    # per-user temp dir: os.getuid does not exist on Windows — fall
    # back to USERNAME there (the windows CI leg must reach the numpy
    # fallback through the normal probe chain, not an AttributeError)
    uid = (os.getuid() if hasattr(os, "getuid")
           else os.environ.get("USERNAME", "user"))
    build_dirs = [os.path.dirname(_SRC),
                  os.path.join(tempfile.gettempdir(),
                               f"pulsarutils_tpu_native_{uid}")]
    for d in build_dirs:
        try:
            os.makedirs(d, exist_ok=True)
            out = os.path.join(d, f"_unpack.{tag}.so")
            if (os.path.exists(out)
                    and os.path.getmtime(out) >= os.path.getmtime(_SRC)):
                return out
            # compile to a unique temp path and rename into place: rename
            # is atomic on POSIX, so a concurrent process never CDLLs a
            # half-written (yet ELF-parsable) library
            tmp = f"{out}.tmp{os.getpid()}"
            try:
                _compile(tmp)
                os.replace(tmp, out)
            finally:
                if os.path.exists(tmp):  # failed build: no orphan files
                    os.unlink(tmp)
            return out
        except (OSError, subprocess.SubprocessError) as exc:
            logger.debug("native unpack build failed in %s: %s", d, exc)
    logger.info("native low-bit unpacker unavailable (no working C++ "
                "toolchain); using the numpy fallback — correct but "
                "slower on multi-GB low-bit files")
    return None


def _compile(out):
    """Build ``unpack.cpp`` with the first working compiler.

    ``$CXX`` wins when set; otherwise g++ then clang++ then c++ — on
    macOS ``g++`` is usually a clang shim and all three take the same
    ``-shared -fPIC`` flags (the library is self-contained, so no
    ``-undefined dynamic_lookup`` is needed).  Raises the last failure
    when none work (the caller logs and falls back to numpy).
    """
    compilers = ([os.environ["CXX"]] if os.environ.get("CXX")
                 else ["g++", "clang++", "c++"])
    last = None
    for cxx in compilers:
        try:
            subprocess.run([cxx, "-O3", "-shared", "-fPIC", "-o", out,
                            _SRC], check=True, capture_output=True,
                           timeout=120)
            return
        except (OSError, subprocess.SubprocessError) as exc:
            last = exc
    raise last


def _load():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("PUTPU_NO_NATIVE") == "1":
        return None
    try:
        path = _build_library()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        for name in ("unpack1", "unpack2", "unpack4"):
            getattr(lib, name).argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        for name in ("pack1", "pack2", "pack4"):
            getattr(lib, name).argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        _lib = lib
    except OSError as exc:
        logger.debug("native unpack unavailable: %s", exc)
        _lib = None
    return _lib


def native_available():
    """True when the C++ unpacker compiled and loaded."""
    return _load() is not None


def accum_dtype(nbits, nchan):
    """Name of the smallest integer dtype that EXACTLY holds a
    full-channel dedispersion sum of ``nbits``-bit codes — the
    integer-sweep-accumulation contract (ISSUE 11):

    * a worst-case sum is ``(2^nbits - 1) * nchan`` (every channel at
      the top rail);
    * below 2^15 the whole ``(ndm, T)`` plane accumulates in **int16**
      (half the HBM traffic of float32 on the memory-bound sweep);
    * below 2^24 it accumulates in **int32** AND its float32 view is
      still exact (float32 represents every integer < 2^24), so the
      scores computed from the integer plane are bit-identical to the
      float-accumulated reference — float32 addition of exact integers
      with an exact-representable running sum never rounds;
    * at or above 2^24 the exactness argument breaks and callers must
      stay on the float32 path (``None`` is returned).

    The ladder itself lives in :func:`..precision.exactness_domain`,
    the single owner of the 2^24 bound (ISSUE 17) — this wrapper keeps
    the historic call signature.
    """
    from ..precision import exactness_domain

    return exactness_domain(nchan, nbits=nbits).accum_dtype


def device_unpack_block(frames, nbits, nchan, band_descending=False,
                        xp=None, dtype=None):
    """Jittable device unpack: packed frames -> ``(nchan, n)`` float32.

    ``frames`` is the raw ``(nsamps, nbytes_per_frame)`` uint8 block a
    low-bit or unsigned 8-bit filterbank stores
    (``FilterbankReader.read_block_packed``), single-IF.  Same LSB-first convention as :func:`unpack_numpy`; the
    returned block is ASCENDING-band (``band_descending=True`` flips
    the file's channel order, mirroring ``read_block(band_ascending=
    True)``).

    Why this exists (round 4): the streaming pipeline used to unpack on
    the host and upload float32 — 16x the bytes of a 2-bit file over
    the host->device link.
    Uploading the packed bytes and unpacking in the device-clean jit
    moves the inflation to HBM, where it is free by comparison.

    ``dtype`` (round 11) overrides the output dtype: an integer dtype
    (see :func:`accum_dtype`) keeps the unpacked codes integral so the
    dedispersion sweep can accumulate in int16/int32 — same values,
    half the HBM traffic — converting to float only at scoring.

    Where the flip happens (PR 42): on the smallest array that has the
    band's order, the stored bytes — at 8 bits the transposed bytes, at
    1/2/4 bits each frame's bytes reversed and the shifts applied
    highest first, so the shift-and-mask itself emits ascending codes.
    The TPU compiler fuses a ``reverse`` into nothing; one on the
    widened ``(nchan, n)`` plane made every consumer of this block write
    that plane, reverse it in a pass of its own and read it back (29.5
    ms of a 75.7 ms clean at 1,024 x 2^20).  With none there the
    widening fuses into whatever reads the block.
    """
    if xp is None:
        import jax.numpy as xp
    frames = xp.asarray(frames)
    if nbits == 8:
        # nothing to shift: the BYTES are transposed (and flipped), the
        # widening comes after — a quarter of the traffic of widening
        # first, and none of the shift-and-mask's (frames, bytes, per)
        # temporaries
        block = frames[:, :nchan].T
        if band_descending:
            block = block[::-1]
        return block.astype(dtype if dtype is not None else xp.float32)
    per = _PER_BYTE[nbits]
    mask = (1 << nbits) - 1
    shifts = xp.arange(per, dtype=xp.uint8) * np.uint8(nbits)
    if band_descending:
        # highest file channel first; nothing wider than a byte is reversed
        frames, shifts = frames[:, ::-1], shifts[::-1]
    vals = (frames[:, :, None] >> shifts[None, None, :]) & np.uint8(mask)
    codes = vals.reshape(frames.shape[0], -1)
    # a part-filled last byte's padding codes come first in a reversed
    # frame, last in one left as stored
    block = codes[:, -nchan:] if band_descending else codes[:, :nchan]
    return block.astype(dtype if dtype is not None else xp.float32).T


def unpack_from_meta(data, meta, xp):
    """In-jit unpack from a :meth:`PackedFrames.meta` tuple.

    The ONE traceable body every surface embeds (direct-sweep kernel,
    batched beam body, both shard_map programs) — so the meta's dtype
    element is always honored and the bit-identity-critical unpack
    cannot drift between copies.
    """
    nbits, nchan, descending, dtype_name = meta
    return device_unpack_block(data, nbits, nchan,
                               band_descending=descending, xp=xp,
                               dtype=getattr(xp, dtype_name))


def sample_codes(frames, nbits, nchan, max_rows=4096):
    """Bounded strided decode of packed frames -> ``(nchan, k)`` codes
    in FILE channel order.

    Shared by the reader-thread consumers that need statistics, not the
    whole chunk (the packed canary's noise scale, the code-domain
    integrity gate): at most ``max_rows`` frames are decoded regardless
    of chunk size.
    """
    frames = np.asarray(frames)
    stride = max(1, frames.shape[0] // int(max_rows))
    if nbits == 8:  # the bytes are the codes: a view, nothing decoded
        return frames[::stride, :int(nchan)].T
    per_frame = frames.shape[1] * _PER_BYTE[nbits]
    return unpack_numpy(frames[::stride], nbits).reshape(
        -1, per_frame)[:, :int(nchan)].T


@functools.lru_cache(maxsize=16)
def _unpack_program(nbits, nchan, band_descending, dtype_name):
    """ONE compiled device-unpack program per (geometry, dtype): raw
    packed bytes in, ``(nchan, n)`` block out.  Shared by every surface
    that uploads packed frames but runs a kernel that cannot unpack
    in-program (Pallas/FDMT/fourier, the mesh exact sweep): the link
    still carries 1/8-1/16th the bytes, the shift/mask inflation
    happens on HBM."""
    import jax
    import jax.numpy as jnp

    dtype = getattr(jnp, dtype_name)

    @jax.jit
    def run(frames):
        return device_unpack_block(frames, nbits, nchan,
                                   band_descending=band_descending,
                                   xp=jnp, dtype=dtype)

    return run


class PackedFrames:
    """A packed low-bit chunk in transit: raw SIGPROC frames plus the
    metadata needed to decode them.

    This is the carrier every scaled dispatch surface accepts in place
    of a float ``(nchan, n)`` block (ISSUE 11): the streaming driver
    (``parallel/stream.py``), the mesh searches
    (``parallel/sharded_fdmt.py`` / ``parallel/sharded.py``), the
    batched beam dispatcher (``beams/batcher.py``) and the single-device
    facade (``ops/search.py``).  ``frames`` is exactly what
    ``FilterbankReader.read_block_packed`` returns — ``(nsamps,
    bytes_per_frame)`` uint8 — so shipping it to the device costs
    ``nbits/32`` of the float32 upload.  ``.shape`` reports the LOGICAL
    ``(nchan, nsamps)`` block shape so geometry-planning code
    (``np.shape(data)``) works unchanged.
    """

    __slots__ = ("frames", "nbits", "nchan", "band_descending")

    def __init__(self, frames, nbits, nchan, band_descending=False):
        if nbits not in _PER_BYTE:
            raise ValueError(f"unsupported nbits={nbits}")
        self.frames = np.asarray(frames)
        if self.frames.ndim != 2 or self.frames.dtype != np.uint8:
            raise ValueError(
                "PackedFrames wants the raw (nsamps, bytes_per_frame) "
                f"uint8 frames; got {self.frames.dtype} "
                f"{self.frames.shape}")
        self.nbits = int(nbits)
        self.nchan = int(nchan)
        self.band_descending = bool(band_descending)

    @classmethod
    def read(cls, reader, istart, nsamps):
        """Read one packed chunk off a low-bit single-IF
        :class:`~pulsarutils_tpu.io.sigproc.FilterbankReader`."""
        return cls(reader.read_block_packed(istart, nsamps),
                   reader._nbits, reader.nchans,
                   band_descending=reader.band_descending)

    @property
    def shape(self):
        """Logical decoded shape ``(nchan, nsamps)``."""
        return (self.nchan, int(self.frames.shape[0]))

    @property
    def nsamps(self):
        return int(self.frames.shape[0])

    @property
    def nbytes(self):
        """Bytes actually shipped over the link (the packed bytes)."""
        return int(self.frames.nbytes)

    @property
    def float_nbytes(self):
        """Bytes the host-unpack path would have shipped (float32)."""
        return self.nchan * self.nsamps * 4

    def meta(self, dtype_name="float32"):
        """Hashable unpack descriptor ``(nbits, nchan, descending,
        dtype)`` — the static operand in-jit unpackers key on."""
        return (self.nbits, self.nchan, self.band_descending,
                str(dtype_name))

    def to_device(self, dtype_name="float32"):
        """Upload the PACKED bytes and unpack on device.

        Returns the device-resident ``(nchan, nsamps)`` ascending-band
        block (float32 by default, or an :func:`accum_dtype` integer
        dtype) — one cached compiled program per geometry, so steady
        state never retraces.
        """
        return _unpack_program(self.nbits, self.nchan,
                               self.band_descending,
                               str(dtype_name))(self.frames)

    def to_host(self):
        """Host decode (C++ when built, numpy otherwise) to the float32
        ``(nchan, nsamps)`` ascending-band block — the fallback path and
        the byte-identity oracle the device unpack is pinned against."""
        per_frame = self.frames.shape[1] * _PER_BYTE[self.nbits]
        block = unpack(self.frames, self.nbits).reshape(
            self.nsamps, per_frame)[:, :self.nchan].T
        if self.band_descending:
            block = block[::-1]
        return np.ascontiguousarray(block)


def unpack_numpy(packed, nbits):
    """Numpy reference: packed uint8 -> float32, LSB-first."""
    packed = np.ascontiguousarray(packed, dtype=np.uint8).ravel()
    per = _PER_BYTE[nbits]
    mask = (1 << nbits) - 1
    shifts = np.arange(per, dtype=np.uint8) * nbits
    out = (packed[:, None] >> shifts[None, :]) & mask
    return out.astype(np.float32).ravel()


def pack_numpy(values, nbits):
    """Numpy reference: float32 -> packed uint8 (clipped, LSB-first)."""
    per = _PER_BYTE[nbits]
    maxval = (1 << nbits) - 1
    v = np.asarray(values, dtype=np.float32).ravel()
    if v.size % per:
        raise ValueError(f"value count {v.size} not a multiple of {per}")
    q = np.clip(np.rint(v), 0, maxval).astype(np.uint8).reshape(-1, per)
    shifts = np.arange(per, dtype=np.uint8) * nbits
    return np.bitwise_or.reduce(q << shifts[None, :], axis=1).astype(np.uint8)


def unpack(packed, nbits):
    """Packed uint8 buffer -> float32 values (native path when available)."""
    if nbits not in _PER_BYTE:
        raise ValueError(f"unsupported nbits={nbits}")
    lib = _load() if nbits < 8 else None  # a byte a sample: a plain cast
    if lib is None:
        return unpack_numpy(packed, nbits)
    packed = np.ascontiguousarray(packed, dtype=np.uint8).ravel()
    out = np.empty(packed.size * _PER_BYTE[nbits], dtype=np.float32)
    getattr(lib, f"unpack{nbits}")(
        packed.ctypes.data, out.ctypes.data, packed.size)
    return out


def pack(values, nbits):
    """Float values -> packed uint8 (native path when available)."""
    if nbits not in _PER_BYTE:
        raise ValueError(f"unsupported nbits={nbits}")
    lib = _load() if nbits < 8 else None
    if lib is None:
        return pack_numpy(values, nbits)
    per = _PER_BYTE[nbits]
    v = np.ascontiguousarray(values, dtype=np.float32).ravel()
    if v.size % per:
        raise ValueError(f"value count {v.size} not a multiple of {per}")
    out = np.empty(v.size // per, dtype=np.uint8)
    getattr(lib, f"pack{nbits}")(v.ctypes.data, out.ctypes.data, out.size)
    return out
