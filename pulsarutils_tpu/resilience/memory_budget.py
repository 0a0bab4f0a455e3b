"""Preflight HBM footprint estimator + persisted calibration offsets.

The footprint of a dedispersion dispatch is a strong function of its
geometry — the memory-bound roll/sum over ``nchan x nsamples x nDM``
(arxiv 1201.5380) — so OOM is *predictable* before dispatch:
:func:`estimate_direct` models the per-dispatch bytes (operands, packed
unpack intermediates, gather/scan workspace, scoring temporaries,
plane/score outputs) and :func:`preflight_direct` splits a dispatch
whose estimate exceeds measured headroom **before compiling** — the
same discipline an inference server applies to batch size.

The model is deliberately first-order; what makes it honest is the
**calibration loop**: :func:`observe` compares each estimate against
the allocator watermark :mod:`~pulsarutils_tpu.obs.memory` already
records per chunk, and persists a per-:func:`~pulsarutils_tpu.tuning.
geometry.geometry_key` measured/estimated ratio beside the tune cache
(``membudget_calib.json``, same atomic-write/torn-file rules as
:mod:`~pulsarutils_tpu.tuning.cache`).  Backends that report no
allocator stats (CPU's ``live_arrays`` fallback) skip calibration and,
with no ``PUTPU_MEM_LIMIT``, skip preflight entirely — the default
data path is byte-inert.

``PUTPU_MEM_LIMIT`` (bytes) overrides the allocator's ``bytes_limit``:
the test/drill knob, and the operator's way to fence a shared device.
"""

from __future__ import annotations

import json
import os
import threading

__all__ = ["MEM_LIMIT_ENV", "SAFETY_FRACTION", "device_budget_bytes",
           "headroom_bytes", "estimate_direct", "estimate_chunk_bytes",
           "max_beam_batch", "preflight_direct", "observe",
           "calibration_path", "calibration_offset", "record_calibration"]

#: env override (bytes) for the device memory budget
MEM_LIMIT_ENV = "PUTPU_MEM_LIMIT"

#: fraction of measured headroom a preflighted dispatch may plan into —
#: the slack absorbs allocator fragmentation and the model's first-order
#: blindness (XLA fusion, donation timing) until calibration tightens it
SAFETY_FRACTION = 0.8

_CALIB_VERSION = 1
_lock = threading.Lock()
_calib_cache = {"path": None, "offsets": None}


# -- budget / headroom -------------------------------------------------------

#: one-shot allocator-limit probe (the limit is static per process;
#: the preflight sits on the per-dispatch hot path and must not pay a
#: live_arrays() sweep on backends that report no limit at all)
_limit_probe = []


def device_budget_bytes():
    """The device memory budget in bytes: ``PUTPU_MEM_LIMIT`` when set,
    else the allocator's reported ``bytes_limit``; ``None`` when
    neither exists (CPU live-array fallback) — callers must treat
    ``None`` as "no budget known", never as infinite."""
    env = os.environ.get(MEM_LIMIT_ENV)
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    if not _limit_probe:
        from ..obs.memory import device_memory_snapshot

        snap = device_memory_snapshot()
        _limit_probe.append(int(snap["bytes_limit"])
                            if snap and snap.get("bytes_limit") else None)
    return _limit_probe[0]


def allocator_reports_limit():
    """True when the device allocator itself reports ``bytes_limit``
    (TPU/GPU ``memory_stats``) — the precondition for watermark
    calibration.  The ``PUTPU_MEM_LIMIT`` env override is deliberately
    ignored here: it is a fence, not a measurement, and calibrating
    the footprint model against it would teach the estimator the
    operator's policy instead of the hardware."""
    if not _limit_probe:
        from ..obs.memory import device_memory_snapshot

        snap = device_memory_snapshot()
        _limit_probe.append(int(snap["bytes_limit"])
                            if snap and snap.get("bytes_limit") else None)
    return _limit_probe[0] is not None


def headroom_bytes():
    """Budget minus bytes currently in use (``None`` = unknown).  With
    no budget known this returns WITHOUT touching the allocator — the
    preflight's no-op path costs one env read."""
    budget = device_budget_bytes()
    if budget is None:
        return None
    from ..obs.memory import device_memory_snapshot

    snap = device_memory_snapshot()
    in_use = int(snap["bytes_in_use"]) if snap else 0
    return max(budget - in_use, 0)


# -- the footprint model -----------------------------------------------------

def estimate_direct(nchan, nsamples, ndm, *, dm_block=32, chan_block=None,
                    formulation="gather", capture_plane=False, batch=1,
                    dm_passes=1, packed_nbits=0, dtype_bytes=4):
    """Per-dispatch HBM byte estimate for the direct sweep.

    Returns a dict of named terms plus ``total``:

    * ``operand`` — the resident chunk(s): ``batch x nchan x T`` floats,
      plus the raw packed frames when ``packed_nbits`` (the in-jit
      unpack briefly holds both);
    * ``workspace`` — the dedisperse working set of ONE live trial
      block: gather materialises an index + gathered pair of
      ``dm_block x chan_block x T`` elements; the roll-scan's carry +
      rolled rows are ``O(dm_block x T)``;
    * ``scoring`` — the mean-subtracted copy and block-sum pyramid of
      one block's plane (~2x ``dm_block x T``);
    * ``outputs`` — score packs (small) plus, under ``capture_plane``,
      the per-pass slice of the full ``ndm x T`` plane.

    ``dm_passes`` scales only the capture-plane output term — the
    lax.map'd blocks of one pass share one live workspace — which is
    exactly why the ladder's ``split_dm`` rung helps most where capture
    or batching inflates the output side, while ``halve_time`` attacks
    the gather workspace directly.
    """
    nchan = int(nchan)
    nsamples = int(nsamples)
    ndm = max(int(ndm), 1)
    batch = max(int(batch), 1)
    dm_block = max(min(int(dm_block or 32), ndm), 1)
    cb = int(chan_block) if chan_block else nchan

    operand = batch * nchan * nsamples * dtype_bytes
    if packed_nbits:
        operand += batch * nchan * nsamples * packed_nbits // 8
    if formulation == "gather":
        workspace = 2 * dm_block * cb * nsamples * dtype_bytes
    else:
        workspace = 3 * dm_block * nsamples * dtype_bytes
    scoring = 2 * dm_block * nsamples * dtype_bytes
    nblocks = -(-ndm // dm_block)
    per_pass_blocks = -(-nblocks // max(int(dm_passes), 1))
    outputs = per_pass_blocks * 5 * dm_block * dtype_bytes
    if capture_plane:
        outputs += per_pass_blocks * dm_block * nsamples * dtype_bytes
    total = operand + workspace + scoring + outputs
    return {"operand": operand, "workspace": workspace,
            "scoring": scoring, "outputs": outputs, "total": total}


def estimate_chunk_bytes(nchan, nsamples_searched, ndm, **kw):
    """One chunk search's calibrated total — the coordinator's
    lease-sizing and the service's admission unit."""
    est = estimate_direct(nchan, nsamples_searched, ndm, **kw)["total"]
    return calibrated(_direct_key(nchan, nsamples_searched, ndm), est)


def max_beam_batch(nchan, nsamples, ndm, *, dm_block=None, chan_block=None,
                   formulation="gather", packed_nbits=0, budget=None):
    """Largest beam-batch width the budget admits (``None`` = unknown
    budget, no cap).  The batch axis multiplies the operand term only
    (``lax.map`` serialises the per-beam bodies, so one beam's
    workspace is live at a time); admission caps the batch so the
    estimate fits ``SAFETY_FRACTION`` of the budget instead of
    co-batching tenants into an OOM."""
    if budget is None:
        budget = headroom_bytes()
    if budget is None:
        return None
    one = estimate_direct(nchan, nsamples, ndm, dm_block=dm_block,
                          chan_block=chan_block, formulation=formulation,
                          packed_nbits=packed_nbits, batch=1)
    fixed = one["workspace"] + one["scoring"] + one["outputs"]
    per_beam = max(one["operand"], 1)
    usable = SAFETY_FRACTION * budget - fixed
    return max(int(usable // per_beam), 1)


# -- preflight ---------------------------------------------------------------

def preflight_direct(formulation, nchan, nsamples, ndm, *, dm_block,
                     chan_block, capture_plane, nblocks, packed_nbits=0):
    """Descend the ladder BEFORE compiling until the estimate fits
    measured headroom (no-op when headroom is unknown).  Returns the
    resulting global level."""
    from . import ladder as _ladder

    head = headroom_bytes()
    if head is None:
        return _ladder.level()
    key = _direct_key(nchan, nsamples, ndm)
    while not _ladder.direct_maxed(formulation, nblocks):
        dm_passes = _ladder.direct_plan(formulation, nblocks)
        est = calibrated(key, estimate_direct(
            nchan, nsamples, ndm, dm_block=dm_block, chan_block=chan_block,
            formulation=formulation, capture_plane=capture_plane,
            dm_passes=dm_passes,
            packed_nbits=packed_nbits)["total"])
        if est <= SAFETY_FRACTION * head:
            break
        _ladder.descend(_ladder.direct_step(formulation))
        _ladder.count_split("preflight")
    return _ladder.level()


# -- calibration: persisted beside the tune cache ----------------------------

def _direct_key(nchan, nsamples, ndm):
    """The estimator's calibration key: the tuner's geometry axes.

    The backend axis is the one THIS process dispatches to.  A process
    that never loaded JAX — the fleet coordinator, which sizes leases
    with this estimate — dispatches nowhere and gets the generic key:
    asking ``jax.default_backend()`` there would initialise a backend,
    and on a TPU host take the chip the workers need.
    """
    import sys

    from ..tuning.geometry import geometry_key

    jax = sys.modules.get("jax")
    backend = jax.default_backend() if jax is not None else "any"
    return geometry_key(backend, nchan, nsamples, ndm)


def calibration_path():
    """``membudget_calib.json`` in the tune cache's directory — the
    estimator's offsets live (and are isolated/overridden) exactly
    where the tuner's measurements do."""
    from ..tuning.cache import default_cache_path

    return os.path.join(os.path.dirname(default_cache_path()),
                        "membudget_calib.json")


def _load_offsets():
    path = calibration_path()
    with _lock:
        if _calib_cache["path"] == path \
                and _calib_cache["offsets"] is not None:
            return dict(_calib_cache["offsets"])
    offsets = {}
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if isinstance(doc, dict) \
                and doc.get("version") == _CALIB_VERSION \
                and isinstance(doc.get("offsets"), dict):
            offsets = {str(k): float(v)
                       for k, v in doc["offsets"].items()}
    except (OSError, ValueError, TypeError):
        # missing / torn / unreadable calibration degrades to the raw
        # model — estimates get less sharp, nothing fails (the tune
        # cache's own durability rule)
        offsets = {}
    with _lock:
        _calib_cache["path"] = path
        _calib_cache["offsets"] = dict(offsets)
    return offsets


def calibration_offset(key):
    """The persisted measured/estimated ratio for ``key`` (1.0 when
    uncalibrated)."""
    return _load_offsets().get(str(key), 1.0)


def calibrated(key, estimate):
    """Apply the persisted calibration offset to a raw estimate."""
    return estimate * calibration_offset(key)


def record_calibration(key, estimated, measured):
    """Persist ``measured/estimated`` for ``key`` (EWMA over the stored
    value so one outlier chunk cannot swing the offset).  Atomic write;
    an OSError is logged-and-dropped — calibration must never fail a
    search."""
    if not estimated or measured is None or measured <= 0:
        return None
    ratio = float(measured) / float(estimated)
    offsets = _load_offsets()
    prev = offsets.get(str(key))
    value = ratio if prev is None else 0.7 * prev + 0.3 * ratio
    offsets[str(key)] = round(value, 4)
    path = calibration_path()
    try:
        from ..io.atomic import atomic_write_json

        atomic_write_json(path,
                          {"version": _CALIB_VERSION, "offsets": offsets},
                          indent=1, sort_keys=True, trailing_newline=True)
    except OSError as exc:
        import logging

        logging.getLogger("pulsarutils_tpu").warning(
            "membudget calibration persist failed (%r); offset kept "
            "in-memory only", exc)
    with _lock:
        _calib_cache["path"] = path
        _calib_cache["offsets"] = dict(offsets)
    return value


def observe(nchan, nsamples, ndm, estimated):
    """Validate one dispatch's estimate against the allocator watermark
    (the per-chunk ``obs.memory`` snapshot) and fold the ratio into the
    persisted calibration.  Backends without allocator stats (CPU
    live-array fallback) return ``None`` — nothing to calibrate
    against."""
    from ..obs.memory import device_memory_snapshot

    snap = device_memory_snapshot()
    if not snap or snap.get("source") != "memory_stats" \
            or not snap.get("peak_bytes_in_use"):
        return None
    return record_calibration(_direct_key(nchan, nsamples, ndm),
                              estimated, snap["peak_bytes_in_use"])
