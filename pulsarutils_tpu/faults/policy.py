"""Failure policy: dispatch deadlines, the data-integrity gate, the
quarantine manifest.

This module holds the *decisions* the hardened survey loop makes when
:mod:`pulsarutils_tpu.faults.inject` (or reality) misbehaves:

* :class:`DispatchPolicy` + :func:`call_with_deadline` — a wedged device
  dispatch was an infinite stall; now it runs on a watchdog thread with
  a configurable deadline, bounded retry and exponential backoff before
  the existing numpy fallback;
* :func:`gate_chunk` + :class:`IntegrityPolicy` — the pre-search
  data-integrity gate: NaN/Inf fraction, dead-channel fraction,
  saturation and zero-run fractions against configurable thresholds.
  Recoverable chunks are **sanitized** (non-finite values imputed with
  the per-channel median, counted); unrecoverable ones are
  **quarantined** instead of poisoning the S/N statistics or crashing.
  Low-bit (1/2/4-bit) data gets the CODE-domain gate instead
  (:func:`gate_chunk_packed` / :func:`gate_chunk_lowbit`, ISSUE 11):
  rail/zero/dead-channel fractions computed from the raw packed bytes
  with thresholds rescaled onto the quantization floor — strict/
  sanitize policies now work on low-bit files instead of silently
  passing;
* :class:`QuarantineManifest` — the ``quarantine_<fingerprint>.jsonl``
  record of every quarantined chunk and persist dead-letter (chunk
  span, reason, stats), the artifact the end-of-run audit
  (:mod:`.audit`) cross-checks against the resume ledger.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import warnings

import numpy as np

from ..obs import metrics as _metrics
from ..utils.frame_reserve import reserve_frames


class DispatchTimeoutError(RuntimeError):
    """A device dispatch exceeded its deadline.  Deliberately a
    ``RuntimeError`` (not ``TimeoutError``/``OSError``): the fallback
    ladder in ``_search_with_fallback`` treats it like any other
    device-side failure — retry, then numpy."""


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """Deadline + retry policy for one chunk's device dispatch.

    The defaults reproduce the pre-hardening behaviour exactly (one
    same-backend retry, no backoff, no deadline — dispatch runs inline
    on the calling thread).  ``timeout_s`` arms the watchdog: the
    dispatch runs on a daemon thread and a hang is bounded by
    ``timeout_s`` per attempt instead of stalling the stream forever.
    Caveats (``docs/robustness.md``): the watchdog dispatches from a
    non-main thread, which a device client may not tolerate — test
    before enabling; an abandoned hung attempt
    keeps running in the background (its late budget/trace writes may
    land in a later chunk's buckets, and a retry briefly overlaps it
    on the device).
    """

    timeout_s: float | None = None
    retries: int = 1          # same-backend re-attempts before fallback
    backoff_s: float = 0.0    # base for exponential backoff between them


def call_with_deadline(fn, timeout_s=None):
    """Run ``fn()`` bounded by ``timeout_s`` seconds.

    ``timeout_s=None``/``0`` calls inline (zero overhead, identical
    thread — the production default).  Otherwise ``fn`` runs on a fresh
    daemon thread carrying a copy of the caller's context (so budget /
    trace attribution keeps working) and :class:`DispatchTimeoutError`
    is raised when the deadline passes; the abandoned thread is left to
    finish and its result is discarded.
    """
    if not timeout_s:
        return fn()
    import contextvars

    box = {}
    ctx = contextvars.copy_context()

    @reserve_frames
    def target():
        try:
            box["value"] = ctx.run(fn)
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            box["exc"] = exc

    t = threading.Thread(target=target, daemon=True,
                         name="putpu-dispatch-watchdog")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise DispatchTimeoutError(
            f"device dispatch exceeded the {timeout_s}s deadline "
            "(wedged device? the attempt was abandoned).  NOTE: XLA "
            "compile time counts against the deadline — if this fired "
            "on a first chunk, size the timeout above the cold compile "
            "or warm up first, or every retry times out too and the "
            "run stickily degrades to the numpy path")
    if "exc" in box:
        raise box["exc"]
    return box["value"]


# ---------------------------------------------------------------------------
# Data-integrity gate
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IntegrityPolicy:
    """Thresholds for the pre-search chunk gate.  A chunk breaching any
    ``max_*`` fraction is quarantined; a chunk with a *sub-threshold*
    non-finite fraction is sanitized when ``sanitize`` is set (the
    ``"sanitize"`` policy) or quarantined when not (``"strict"``)."""

    max_nan_frac: float = 0.25
    max_dead_frac: float = 0.5
    max_sat_frac: float = 0.5
    max_zero_frac: float = 0.75
    sanitize: bool = True


def resolve_integrity_policy(policy):
    """``"sanitize"`` / ``"strict"`` / ``"off"`` / an
    :class:`IntegrityPolicy` / ``None`` -> policy instance or ``None``."""
    if policy is None or policy == "off" or policy is False:
        return None
    if isinstance(policy, IntegrityPolicy):
        return policy
    if policy == "sanitize":
        return IntegrityPolicy()
    if policy == "strict":
        return IntegrityPolicy(sanitize=False)
    raise ValueError(f"quarantine policy {policy!r}: expected 'sanitize', "
                     "'strict', 'off' or an IntegrityPolicy")


def chunk_stats(block, finite=None):
    """Integrity statistics of a ``(nchan, nsamp)`` float block.

    ``finite`` accepts a precomputed ``np.isfinite(block)`` mask so a
    caller that needs the mask afterwards (the sanitize path) pays the
    pass and the full-size boolean temporary once.

    A few host passes: non-finite fraction, dead-channel fraction
    (zero variance over the finite values — a flat channel carries no
    signal and divides to garbage downstream), exact-zero fraction
    (dropped-packet runs) and saturation fraction (values pinned at the
    block maximum — a clipped digitiser rail repeats its max, noise
    does not).  Fractions are returned at FULL precision — verdicts
    must never hinge on display rounding (two NaNs in a 2^26-sample
    chunk round to 0.0 at six decimals but still poison every DM trial
    they touch).  Variance is two-pass with float64 accumulation: the
    one-pass ``E[x²] − mean²`` form cancels catastrophically on float32
    blocks with a large DC offset (ordinary uncalibrated power levels)
    and falsely classified healthy channels dead.
    """
    block = np.asarray(block)
    if finite is None:
        finite = np.isfinite(block)
    n = block.size
    nfinite = int(finite.sum())
    nan_frac = (n - nfinite) / n
    safe = np.where(finite, block, 0.0)
    cnt = finite.sum(axis=1)
    denom = np.maximum(cnt, 1)
    mean = safe.sum(axis=1, dtype=np.float64) / denom
    # deviations stay in the block's dtype — centered values cannot
    # cancel catastrophically, and a survey-scale float32 chunk must
    # not materialize full-size float64 temporaries on the reader
    # thread (code-review r8); only the ACCUMULATIONS are float64
    # (einsum: no full-size product temporary either)
    mean_s = mean.astype(safe.dtype, copy=False)
    dev = np.where(finite, safe - mean_s[:, None], 0.0)
    var = np.einsum("ct,ct->c", dev, dev, dtype=np.float64) / denom
    dead_frac = float(((var <= 0) | (cnt == 0)).mean())
    zero_frac = float(((block == 0) & finite).sum() / n)
    if nfinite:
        vmax = float(safe.max())
        sat_frac = float(((block == vmax) & finite).sum() / n)
    else:
        sat_frac = 0.0
    return {"nan_frac": float(nan_frac), "dead_frac": dead_frac,
            "zero_frac": zero_frac, "sat_frac": sat_frac}


def gate_chunk(block, policy):
    """Gate one chunk.  Returns ``(block, info)`` with ``info`` =
    ``{"verdict": "clean"|"sanitized"|"quarantine", "stats": {...},
    "reasons": [...]}``.

    A clean chunk is returned **as the same object** — the gate must
    never perturb the byte-identical production path.  Sanitization
    imputes non-finite values with the per-channel median of the finite
    values (0 for a fully dead channel) — deliberately signal-free, so
    a sanitized noise chunk stays below any sane detection floor.

    Verdicts are decided on the RAW fractions; the six-decimal rounding
    in the returned ``stats`` is display-only (a handful of NaNs in a
    survey-scale chunk rounds to 0.0 but must still be sanitized).
    """
    block_arr = np.asarray(block)
    finite = np.isfinite(block_arr)
    raw = chunk_stats(block_arr, finite=finite)
    stats = {k: round(v, 6) for k, v in raw.items()}
    reasons = [name for name, frac, lim in (
        ("nan_frac", raw["nan_frac"], policy.max_nan_frac),
        ("dead_frac", raw["dead_frac"], policy.max_dead_frac),
        ("zero_frac", raw["zero_frac"], policy.max_zero_frac),
        ("sat_frac", raw["sat_frac"], policy.max_sat_frac),
    ) if frac > lim]
    if reasons:
        return block, {"verdict": "quarantine", "stats": stats,
                       "reasons": reasons}
    if raw["nan_frac"] == 0.0:
        return block, {"verdict": "clean", "stats": stats, "reasons": []}
    if not policy.sanitize:
        return block, {"verdict": "quarantine", "stats": stats,
                       "reasons": ["nan_frac(strict)"]}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # all-NaN channel median
        med = np.nanmedian(np.where(finite, block_arr, np.nan), axis=1)
    med = np.where(np.isfinite(med), med, 0.0)
    out = np.where(finite, block_arr, med[:, None])
    return out, {"verdict": "sanitized", "stats": stats, "reasons": []}


def lowbit_code_stats(codes, nbits):
    """Integrity statistics of a low-bit CODE block (ISSUE 11).

    ``codes`` is ``(nchan, n)`` quantization codes (integer values
    ``0..2^nbits - 1``, any numeric dtype — the decoded floats a host
    unpack yields are exact codes too).  The float-domain
    :func:`chunk_stats` is meaningless here — low-bit data cannot hold
    NaN/Inf, and its zero/saturation fractions sit at the quantization
    levels *by construction* (a healthy 1-bit chunk is ~50% at each
    rail), which is why the gate used to skip quantized data entirely
    (PR 4) and silently passed genuinely broken low-bit chunks.  These
    are the code-domain equivalents:

    * ``zero_frac`` — codes at the bottom rail (dropped packets, a dead
      digitiser leg);
    * ``rail_frac`` — codes pinned at the TOP rail (clipped digitiser,
      persistent broadband RFI saturating the quantizer);
    * ``dead_frac`` — channels whose codes never change over the
      sample (a flat channel carries no signal and biases the
      renormalisation).
    """
    codes = np.asarray(codes)
    mask = (1 << int(nbits)) - 1
    zero_frac = float((codes == 0).mean())
    rail_frac = float((codes == mask).mean())
    dead_frac = float((codes.max(axis=1) == codes.min(axis=1)).mean())
    return {"zero_frac": zero_frac, "rail_frac": rail_frac,
            "dead_frac": dead_frac, "nbits": int(nbits)}


def _lowbit_verdict(raw, nbits, policy):
    """Code-domain gate rule shared by the packed and host-decoded
    low-bit paths.  The zero/rail thresholds are RESCALED onto the
    quantization floor: a uniform healthy code distribution already
    puts ``2^-nbits`` of the samples on each rail, so the policy's
    float-domain fraction limits are interpreted as *how far toward
    100% the excess may go* — ``limit' = expected + (1 - expected) *
    limit``.  At 2 bits with the default ``max_zero_frac=0.75`` that is
    0.8125 (healthy ~0.25 passes, a dropped-packet chunk at ~1.0
    trips); at 1 bit the default saturation limit resolves to 0.75
    (healthy ~0.5 passes, a clipped chunk at ~1.0 trips).
    ``dead_frac`` needs no rescale — channel flatness is
    rate-independent.  There is nothing to sanitize in integer codes
    (no NaN to impute), so ``"strict"`` and ``"sanitize"`` behave
    identically here: clean or quarantine.
    """
    expected = 2.0 ** -int(nbits)
    zero_lim = expected + (1.0 - expected) * policy.max_zero_frac
    rail_lim = expected + (1.0 - expected) * policy.max_sat_frac
    stats = {k: (round(v, 6) if isinstance(v, float) else v)
             for k, v in raw.items()}
    reasons = [name for name, frac, lim in (
        ("zero_frac", raw["zero_frac"], zero_lim),
        ("rail_frac", raw["rail_frac"], rail_lim),
        ("dead_frac", raw["dead_frac"], policy.max_dead_frac),
    ) if frac > lim]
    if reasons:
        return {"verdict": "quarantine", "stats": stats,
                "reasons": reasons}
    return {"verdict": "clean", "stats": stats, "reasons": []}


def gate_chunk_packed(frames, nbits, nchan, policy, max_rows=4096):
    """Gate one PACKED low-bit chunk from its raw bytes (ISSUE 11).

    ``frames`` is the raw ``(nsamps, bytes_per_frame)`` uint8 block the
    packed fast path ships to the device.  A bounded strided row
    subsample (``max_rows`` frames) is decoded with cheap shift/mask
    stats — the reader thread never pays a full-chunk unpack — and the
    code-domain verdict rule (:func:`_lowbit_verdict`) applies.  The
    frames are returned untouched either way: the gate must never
    perturb the byte-exact upload.
    """
    from ..io.lowbit import sample_codes

    frames = np.asarray(frames)
    codes = sample_codes(frames, nbits, nchan, max_rows=max_rows)
    return frames, _lowbit_verdict(lowbit_code_stats(codes, nbits),
                                   nbits, policy)


def gate_chunk_lowbit(block, nbits, policy, max_cols=4096):
    """Gate one host-DECODED low-bit chunk (the numpy-backend path):
    same code-domain rule as :func:`gate_chunk_packed`, computed from a
    strided column subsample of the float code block."""
    block = np.asarray(block)
    stride = max(1, block.shape[1] // int(max_cols))
    return block, _lowbit_verdict(
        lowbit_code_stats(block[:, ::stride], nbits), nbits, policy)


# ---------------------------------------------------------------------------
# Quarantine manifest
# ---------------------------------------------------------------------------

class QuarantineManifest:
    """Append-only ``quarantine_<fingerprint>.jsonl`` next to the
    candidate store: one JSON record per quarantined chunk or persist
    dead-letter (``{"chunk", "end", "reason", "stats"?}``).  Created
    lazily on first record, so a clean run's output directory is
    byte-identical to pre-hardening.  Thread-safe (records arrive from
    the main loop and the persist worker)."""

    def __init__(self, directory, fingerprint=None):
        self.directory = str(directory)
        self.fingerprint = fingerprint
        self.path = os.path.join(
            self.directory, f"quarantine_{fingerprint or 'noresume'}.jsonl")
        self._lock = threading.Lock()

    def record(self, chunk, end, reason, stats=None):
        rec = {"chunk": int(chunk), "end": int(end), "reason": str(reason)}
        if stats:
            rec["stats"] = stats
        line = json.dumps(rec, sort_keys=True)
        with self._lock:
            os.makedirs(self.directory, exist_ok=True)
            with open(self.path, "a") as f:
                f.write(line + "\n")
        _metrics.counter("putpu_quarantine_records_total").inc()
        return rec

    def records(self):
        """Every record in file order (``[]`` when no manifest exists)."""
        if not os.path.exists(self.path):
            return []
        out = []
        with open(self.path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    # a torn final line (crash mid-append): the manifest
                    # is advisory — a torn record must never take down
                    # the audit or the run that triggers it
                    continue
        return out

    def chunks(self, reason_prefix=None):
        """Set of quarantined chunk starts, optionally filtered by a
        reason prefix (e.g. ``"persist_dead_letter"``)."""
        return {r["chunk"] for r in self.records()
                if reason_prefix is None
                or str(r["reason"]).startswith(reason_prefix)}
