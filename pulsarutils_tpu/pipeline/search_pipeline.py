"""The main streaming search driver: file -> clean -> sweep -> candidates.

Capability-equivalent of the reference's ``search_by_chunks``
(``pulsarutils/clean.py:276-351``), rebuilt around the TPU execution model:

* one place owns band orientation (everything downstream sees an
  *ascending* band — the reference flipped inline at ``clean.py:332-333``);
* physics-driven chunk/hop/resample sizing via
  :func:`..parallel.stream.plan_chunks` (reference ``clean.py:296-316``);
* every interior chunk has the same shape, so ONE compiled search
  executable serves the entire file; candidates above the S/N threshold
  (reference's ``snr > 6``, ``clean.py:349``) are persisted through the
  :class:`..io.candidates.CandidateStore` with a crash-safe resume ledger
  (replacing the reference's manual ``tmin`` restart);
* diagnostics are rendered from the plane the search already computed —
  never recomputed (the reference re-ran its slow search per chunk,
  ``clean.py:204-205``, and plotted unconditionally with ``show=True``,
  ``clean.py:347``; here plotting is opt-in and hit-gated by default).
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import time
import zipfile
import zlib

import numpy as np

from ..faults import compile_phase
from ..faults import inject as fault_inject
from ..faults import reasons as fault_reasons
from ..faults.policy import (DispatchPolicy, QuarantineManifest,
                             call_with_deadline, gate_chunk,
                             gate_chunk_lowbit, gate_chunk_packed,
                             resolve_integrity_policy)
from ..io.candidates import CandidateStore, config_fingerprint
from ..io.sigproc import FilterbankReader
from ..obs import memory as obs_memory
from ..obs import metrics as obs_metrics
from ..obs.canary import CanaryController
from ..obs.capacity import EwmaThroughput
from ..obs.health import HealthEngine
from ..obs.server import start_obs_server
from ..obs.lineage import LineageRecorder
from ..obs.push import AlertBroker
from ..obs.trace import begin_span, span as trace_span
from ..ops.clean_ops import (fft_zap_time, renormalize_data, zero_dm_filter)
from ..ops.rebin import downsample_chain, quick_resample
from ..ops.search import dedispersion_search, time_tiles_of
from ..parallel.stream import (iter_chunk_starts, plan_chunks,
                               plan_time_tiles)
from ..pipeline.pulse_info import PulseInfo
from ..pipeline.spectral_stats import get_bad_chans
from ..resilience import ladder as _resilience_ladder
from ..utils.frame_reserve import reserve_frames
from ..utils.logging_utils import (BudgetAccountant, logger,
                                   measure_device_rtt)
from ..utils.table import ResultTable


def _search_with_fallback(array, dmmin, dmmax, start_freq, bandwidth,
                          eff_tsamp, *, backend, kernel, capture_plane,
                          state=None, mesh=None, snr_floor=None,
                          chunk=None, policy=None, trial_dms=None,
                          windows=None):
    """One chunk's search with failure containment.

    The reference has no failure handling at all (SURVEY §5).  Policy:

    - configuration errors (ValueError/TypeError) propagate immediately —
      they are deterministic and would fail identically on every chunk;
    - so does any error raised while a program is traced, lowered or
      compiled (a Mosaic refusal, a compile-time out-of-memory —
      :mod:`..faults.compile_phase` tells them from run-time failures):
      the same program fails the same way on every retry and every
      later chunk, and the NumPy path is ~1000x slower at survey width,
      so "fall back" would mean a hang or an empty run that exits 0;
    - a device-side failure at run time (worker crash, wedged
      dispatch, OOM) is retried
      on the same backend (``policy.retries`` times, default once, with
      exponential ``policy.backoff_s`` between attempts), then the chunk
      falls back to the NumPy reference path (a ``mesh`` run falls back
      the same way: the mesh route is dropped along with the jax
      backend).  With ``policy.timeout_s`` set, every device attempt
      runs on a watchdog thread (:func:`..faults.policy.
      call_with_deadline`) so a *wedged* dispatch — previously an
      infinite stall — is bounded by ``timeout_s × (retries + 1)``
      before the fallback;
    - the fallback decision is remembered in ``state`` (a mutable dict
      shared across the chunk loop), so a persistently broken device is
      discovered once — not re-discovered with two doomed attempts per
      chunk — and every subsequent chunk runs on the same backend/kernel
      (one consistent trial grid in the candidate store).

    Retries are counted (``putpu_dispatch_retries_total``) and each
    retry attempt is a ``dispatch_retry`` span, so a flaky device is
    visible in the metrics snapshot and the Chrome trace.

    ``mesh`` routes the chunk through the sharded multi-device searches
    (``kernel="hybrid"`` -> :func:`..parallel.sharded_fdmt.sharded_hybrid_search`,
    ``"fdmt"`` -> :func:`..parallel.sharded_fdmt.sharded_fdmt_search`,
    anything else -> the DM x chan sharded exact sweep).  ``snr_floor``
    reaches the hybrid searches (single- and multi-device) so the noise
    certificate can fire on signal-free chunks.  Round 6: a floorless
    mesh hybrid chunk (the common streaming configuration — thresholds
    below the certifiable floor resolve to ``snr_floor=None``) runs its
    whole first round as ONE fused ``shard_map`` dispatch, with the
    guarantee loop as the escape hatch; with a certificate-mode floor
    the two-stage composition is kept deliberately, so a certified
    chunk pays one coarse dispatch and no seed rescore — the same
    gating as the single-device fused path.

    ``trial_dms`` is a tier's explicit trial grid and ``windows`` a
    plan's or tier's boxcar ladder (single-device routes only: a tiered
    plan and ``boxcar_max`` refuse a mesh before they get here).
    """
    from ..resilience import ladder as _ladder

    policy = policy if policy is not None else DispatchPolicy()
    state = state if state is not None else {}
    bk = state.get("backend", backend)
    kern = state.get("kernel", kernel)
    # attempt tuples carry an oom_retry flag: a RESOURCE_EXHAUSTED is
    # NOT one of the transient faults the retry budget exists for
    # (retrying the identical dispatch would OOM identically) — it gets
    # ladder descents instead, counted as putpu_oom_* rather than
    # putpu_dispatch_retries_total (ISSUE 12)
    attempts = [(bk, kern, False)] * (1 + max(int(policy.retries), 0))
    if bk != "numpy":
        attempts.append(("numpy", "auto", False))
    last = None
    oom_descents = 0

    def run_one(b, k):
        if b != "numpy":
            # the numpy reference path is the last-resort fallback this
            # ladder exists to reach: injecting there too would make a
            # *persistent* dispatch fault (FaultSpec times=None) crash
            # the run through the very fallback the harness must prove
            # (code-review r8)
            fault_inject.fire("dispatch", chunk=chunk, backend=b)
        else:
            # the OOM drill's floor seam: only kind="oom" specs target
            # the "host" site, so every pre-existing dispatch-fault
            # class still proves the numpy fallback un-injected
            fault_inject.fire("host", chunk=chunk, backend=b)
        if mesh is not None and b == "jax":
            fault_inject.fire("mesh", chunk=chunk)
            # plane capture on the mesh path stays DM-sharded and
            # device-resident (a ShardedPlane handle; the downstream
            # period search and diagnostics consume shard-local products
            # instead of a gathered plane — see parallel/sharded_plane)
            from ..parallel.sharded import sharded_dedispersion_search
            from ..parallel.sharded_fdmt import (
                sharded_fdmt_search,
                sharded_hybrid_search,
            )

            if k == "hybrid":
                return sharded_hybrid_search(
                    array, dmmin, dmmax, start_freq, bandwidth, eff_tsamp,
                    mesh=mesh, snr_floor=snr_floor,
                    capture_plane=capture_plane)
            if k == "fdmt":
                return sharded_fdmt_search(
                    array, dmmin, dmmax, start_freq, bandwidth, eff_tsamp,
                    mesh=mesh, capture_plane=capture_plane)
            return sharded_dedispersion_search(
                array, dmmin, dmmax, start_freq, bandwidth, eff_tsamp,
                mesh=mesh, capture_plane=capture_plane, plane_handle=True)
        return dedispersion_search(
            array, dmmin, dmmax, start_freq, bandwidth, eff_tsamp,
            backend=b, kernel=k, capture_plane=capture_plane,
            **({"trial_dms": trial_dms} if trial_dms is not None else {}),
            **({"windows": windows} if windows is not None else {}),
            **({"snr_floor": snr_floor} if k == "hybrid" else {}))

    i = 0
    while i < len(attempts):
        b, k, oom_retry = attempts[i]
        try:
            # the numpy reference path is the reliability floor: no
            # watchdog (a deadline there would turn the last-resort
            # fallback into another way to fail)
            timeout = policy.timeout_s if b != "numpy" else None
            if i and (b, k) == (bk, kern) and not oom_retry:
                # a same-backend RETRY: counted, backed off, and traced
                # as one — the numpy fallback attempt is neither (span
                # and counter must agree; code-review r8), and an OOM
                # ladder re-dispatch is counted under putpu_oom_*
                obs_metrics.counter("putpu_dispatch_retries_total").inc()
                if policy.backoff_s:
                    time.sleep(policy.backoff_s * (2 ** (i - 1)))
                with trace_span("dispatch_retry", chunk=chunk, attempt=i,
                                backend=b):
                    result = call_with_deadline(
                        lambda: run_one(b, k), timeout)
            else:
                result = call_with_deadline(lambda: run_one(b, k), timeout)
            if (b, k) != (bk, kern):
                logger.error(
                    "device search failed persistently; the rest of this "
                    "run uses backend=%s kernel=%s (reference path)", b, k)
                obs_metrics.counter("putpu_host_fallbacks_total",
                                    stage="search").inc()
                state["backend"], state["kernel"] = b, k
            return result
        except (ValueError, TypeError):
            raise  # deterministic configuration error
        except _ladder.OOMFloorError:
            raise  # already classified at a deeper rung
        except Exception as exc:  # jax runtime errors share no base class
            if compile_phase.failed_phase(exc):
                raise  # deterministic: the program could not be built
            last = exc
            if _ladder.is_resource_exhausted(exc):
                # RESOURCE_EXHAUSTED — distinguished from the transient
                # dispatch faults above (ISSUE 12).  On a device rung:
                # descend the degradation ladder and re-dispatch
                # smaller (byte-identical by construction).  On the
                # numpy floor: the chunk cannot be searched on this
                # host at all — quarantine it (oom_floor), never wedge
                # or kill the survey.
                _ladder.oom_event("chunk_search")
                if b == "numpy":
                    raise _ladder.OOMFloorError(
                        f"chunk {chunk}: the numpy reliability floor "
                        f"itself ran out of memory ({exc!r}); "
                        "quarantining the chunk as oom_floor") from exc
                step = ("unfuse" if k == "hybrid" else "split_dm")
                _ladder.descend(step)
                if oom_descents < 2 * len(_ladder.STEPS):
                    oom_descents += 1
                    attempts.insert(i + 1, (b, k, True))
                logger.warning(
                    "chunk %s search hit RESOURCE_EXHAUSTED on "
                    "backend=%s kernel=%s (%r); degradation ladder "
                    "step %r, re-dispatching smaller", chunk, b, k,
                    exc, step)
            elif i + 1 < len(attempts):
                nxt = attempts[i + 1]
                logger.warning(
                    "chunk search failed on backend=%s kernel=%s (%r); "
                    "retrying with backend=%s kernel=%s", b, k, exc,
                    nxt[0], nxt[1])
            i += 1
            continue
    raise last


def _count_windows(windows, nsamples):
    """Counts one sweep's scored boxcar levels
    (``putpu_boxcar_windows_total``: one per level per tier sweep or flat
    sweep) and returns their number."""
    from ..ops.search import scored_windows

    n = len(scored_windows(windows, nsamples))
    obs_metrics.counter("putpu_boxcar_windows_total").inc(n)
    return n


def _count_head_tiles(state, route, shape, dmmin, dmmax, start_freq,
                      bandwidth, tsamp, delays=None):
    """Counts the (8, 256) tiles the FDMT's fused head computes in one
    sweep (``putpu_fdmt_head_tiles_total``: halo chunks and padded rows
    included; ``ops/fdmt.py:coarse_head_tiles``) and returns their
    number: 0 where the sweep runs no head.  Beside it the SMEM the
    head's tables take (gauge ``putpu_fdmt_head_smem_bytes``), where
    the geometry declined the head, why
    (``putpu_fdmt_head_declined_total``), and the all-zero channels the
    sweep carries to a power of two (``putpu_fdmt_pad_channels_total``,
    on any backend: the tree pads wherever it runs).  ``route`` is the
    call's ``(backend, kernel, mesh)``, ``state`` what a fall-back made
    of it; ``delays`` the ``(n_lo, n_hi)`` of a sweep over one delay band
    of the DM range."""
    backend, kernel, mesh = route
    if (mesh is not None or state.get("backend", backend) != "jax"
            or state.get("kernel", kernel) not in ("hybrid", "fdmt")):
        return 0
    from ..ops.fdmt import coarse_head_tiles, pad_channels

    obs_metrics.counter("putpu_fdmt_pad_channels_total").inc(
        pad_channels(shape[0]))
    n, _, declined, smem = coarse_head_tiles(
        shape[0], shape[1], dmmin, dmmax, start_freq, bandwidth, tsamp,
        delays=delays)
    obs_metrics.counter("putpu_fdmt_head_tiles_total").inc(n)
    obs_metrics.gauge("putpu_fdmt_head_smem_bytes").set(smem)
    if declined:
        obs_metrics.counter("putpu_fdmt_head_declined_total",
                            reason=declined).inc()
    return n


def _clean_block(block, m, xp, cut_outliers, zero_dm, fft_zap, resample):
    """The conditioning of one chunk, parameterised by array namespace:
    the device (jitted) and host (fallback) paths call this one
    function."""
    cleaned = renormalize_data(block, badchans_mask=m,
                               cut_outliers=cut_outliers, xp=xp)
    if zero_dm:
        cleaned = zero_dm_filter(cleaned, badchans_mask=m, xp=xp)
    if fft_zap:
        cleaned, _ = fft_zap_time(cleaned, xp=xp)
    if resample > 1:
        cleaned = quick_resample(cleaned, resample, xp=xp)
    return cleaned


@functools.lru_cache(maxsize=16)
def _device_clean_program(unpack, donate, clean_options):
    """The jitted device clean, kept across calls (ROADMAP S4): a
    ``jax.jit`` built inside ``search_by_chunks`` was a new function to
    JAX in every call, re-traced and its executable read back from the
    persistent cache on every call's first chunk.  It closes over what
    keys it here and nothing else: the clean's options and, for a packed
    low-bit file, ``unpack = (device_unpack_block, nbits, nchans,
    band_descending)``.  The function's name is the program's in a device
    trace (``jit_unpack_clean`` / ``jit_clean``; obs/names.py
    KERNEL_NAMES)."""
    import jax
    import jax.numpy as jnp

    if unpack is not None:
        unpack_block, nbits, nchan_file, descending = unpack

        def unpack_clean(raw, m):
            return _clean_block(
                unpack_block(raw, nbits, nchan_file,
                             band_descending=descending, xp=jnp),
                m, jnp, *clean_options)

        return jax.jit(unpack_clean, donate_argnums=donate)

    def clean(block, m):
        return _clean_block(block, m, jnp, *clean_options)

    return jax.jit(clean, donate_argnums=donate)


@functools.lru_cache(maxsize=8)
def _tier_downsample_program(chain_factors):
    """The jitted downsample chain of a tiered search: each array the one
    before summed in pairs.  ``jit_tier_downsample`` in a device trace
    (``obs/names.py`` KERNEL_NAMES).  Kept across calls: it closes over
    the factors alone, so a second call in one process neither re-traces
    it nor reads its executable back from the persistent cache."""
    import jax
    import jax.numpy as jnp

    def tier_downsample(cleaned):
        return tuple(downsample_chain(cleaned, chain_factors, xp=jnp))

    return jax.jit(tier_downsample)


def _clean_on_host(what, exc):
    """Record that the device ``what`` (upload / clean) failed at run
    time and cleaning moves to the host for the rest of the run; returns
    the new ``device_clean`` (``None``)."""
    logger.warning("device %s failed (%r); cleaning on host from here on",
                   what, exc)
    obs_metrics.counter("putpu_host_fallbacks_total", stage="clean").inc()
    return None


class _TiledChunk:
    """A chunk searched in time tiles, between the clean stage and the
    search: its packed bytes on the device and its chunk-wide moments
    (``pipeline/time_tiles.py``)."""

    __slots__ = ("raw", "stats")

    def __init__(self, raw, stats):
        self.raw, self.stats = raw, stats


def _sweep_shape(arr):
    """The shape one coarse sweep of ``arr`` runs on: a tiled tier's is
    a tile's, halo included."""
    if time_tiles_of(arr) > 1:
        return (arr.shape[0], arr.own + arr.halo)
    return arr.shape


class _ReadFailure:
    """Sentinel from the reader thread: a chunk's read failed even after
    the bounded retries.  The chunk loop quarantines that one chunk
    (done-with-reason in the ledger, a manifest record) instead of the
    whole stream dying on one bad disk region."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


def _device_memory_bytes():
    """What the first local accelerator can hold (``bytes_limit`` of its
    ``memory_stats``); ``None`` where the backend does not say (the CPU):
    nothing is then tiled."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except (RuntimeError, NotImplementedError):  # a backend without them
        return None
    return (stats or {}).get("bytes_limit")


def _tile_geometry(header, plan, tiers, flat, packed_bits):
    """:func:`~..parallel.stream.plan_time_tiles`' arguments but the
    budget, for a survey's plan: ``tiers`` is the tiered plan's list
    (``None``: the flat plan, ``flat`` its ``(dmmin, dmmax, windows)``),
    the last element what the chunk loop holds beside a sweep (the packed
    chunk, the first :data:`~.time_tiles.MAX_BLOCK` frames a chunk
    searched in tiles carries once more at its end, and the next one's
    prefetch)."""
    from ..ops.plan import dedispersion_plan
    from .time_tiles import MAX_BLOCK

    nchan = header["nchans"]
    if tiers:
        geometry = [(t["tier"].downsample, t["tier"].sample_time,
                     t["tier"].trial_dms, t["tier"].windows) for t in tiers]
    else:
        dmmin, dmmax, windows = flat
        geometry = [(1, plan.sample_time, dedispersion_plan(
            nchan, dmmin, dmmax, header["fbottom"], header["bandwidth"],
            plan.sample_time), windows)]
    return (nchan, plan.step // plan.resample, header["fbottom"],
            header["bandwidth"], geometry,
            (2 * plan.step + min(MAX_BLOCK, plan.step))
            * (nchan * packed_bits // 8))


def _plan_tiles(device_memory_bytes, *survey):
    """The tile plan of a survey (:func:`_tile_geometry`'s arguments) on a
    device of ``device_memory_bytes``, a sixteenth of it left to what the
    reckoning does not see; ``None`` where every tier is swept whole."""
    *args, resident = _tile_geometry(*survey)
    tile_plan = plan_time_tiles(*args, int(device_memory_bytes) * 15 // 16,
                                resident)
    return tile_plan if any(t.tiles > 1 for t in tile_plan) else None


def plan_survey(fname, chunk_length=None, new_sample_time=None, tmin=0,
                dmmin=200, dmmax=800, surelybad=(), *, backend="jax",
                kernel="auto", snr_threshold=6.0, fft_zap=False,
                cut_outliers=False, zero_dm=False, mesh=None,
                exact_floor="auto", quarantine_policy="sanitize",
                period_search=False, period_sigma_threshold=8.0,
                fingerprint_extra=None, dm_tiers=None, boxcar_max=None,
                device_memory_bytes=None):
    """Resolve a survey's geometry, threshold and resume fingerprint
    WITHOUT searching anything.

    ``boxcar_max`` (a power of two in samples of the file, Heimdall's
    name and meaning; default off) continues the scorer's boxcar ladder
    (:func:`~pulsarutils_tpu.ops.search.boxcar_ladder`): the returned
    dict's ``windows`` is the flat plan's ladder (``None``: the default
    four), each entry of ``tiers`` carries its own under
    ``tier.windows``, every threshold and certificate floor is resolved
    for its ladder, and the fingerprint names ``boxcar_max``.  Absent,
    nothing changes.

    ``dm_tiers="smearing"`` plans the DM range in tiers
    (:func:`~pulsarutils_tpu.ops.plan.dm_tier_plan`): the returned dict's
    ``tiers`` is then a list with, per tier, its
    :class:`~pulsarutils_tpu.ops.plan.DMTier` (``tier``), its own resolved
    ``snr_threshold`` and its own ``search_snr_floor``.  A plan that
    resolves to one tier at the plan's own sample time is today's flat
    plan: ``tiers`` is ``None`` and threshold, chunk grid and fingerprint
    are those of ``dm_tiers=None``, so no existing ledger is orphaned.

    ``fingerprint_extra`` (a flat JSON-safe dict) is folded into the
    resume-ledger fingerprint when non-empty — the workload seam
    (ISSUE 13): a periodicity job over a file must not share a ledger
    with a single-pulse survey of the same physics (its accumulation
    snapshot advances in lockstep with *its* ledger), and ``None``
    keeps every pre-existing fingerprint byte-identical.  Keys must
    not collide with the driver's own fingerprint fields.

    This is the single source of truth :func:`search_by_chunks` plans
    from, split out (ISSUE 9) so the fleet coordinator
    (:mod:`..fleet.coordinator`) can shard a file into the *exact* chunk
    grid — and read the *exact* resume-ledger fingerprint — that a
    worker's ``search_by_chunks`` run will use.  Any drift between the
    two would silently orphan ledgers across the fleet, so there is
    deliberately no second copy of this logic anywhere.

    Returns a dict: ``reader`` (the open
    :class:`~pulsarutils_tpu.io.sigproc.FilterbankReader`), ``plan``
    (the :class:`~pulsarutils_tpu.parallel.stream.ChunkPlan`),
    ``chunk_starts`` (every planned chunk ``istart``, before any resume
    filtering), ``snr_threshold`` (the resolved float — ``"auto"`` /
    ``"certifiable"`` strings are resolved here), ``search_snr_floor``
    (the hybrid's forwarded floor, or ``None``), ``fingerprint`` (the
    resume-ledger key), ``root`` (the candidate filename stem) and
    ``nsamples``/``sample_time``, and ``tile_plan``: ``None`` where every
    tier is swept whole (every chunk that fits its device: today's
    programs and bits), else one
    :class:`~pulsarutils_tpu.parallel.stream.TierTiles` per tier (one for
    a flat plan), chosen from the geometry and ``device_memory_bytes``
    (:func:`_plan_tiles`).  Only the process that searches states its
    device's memory (``search_by_chunks`` asks its first accelerator); a
    planner that searches nothing (the fleet coordinator) leaves it
    ``None``, touches no device and plans no tiles.  The fingerprint does
    not name the tiles: they are the device's business, they change the
    floats' summation order and not the search, and a coordinator and
    its workers, or a resume on another device, must meet on one ledger.
    """
    logger.info("opening %s", fname)
    # strip only the final extension: "obs.day1.fil" and "obs.day2.fil"
    # must keep distinct candidate roots in a shared output directory
    root = os.path.splitext(os.path.basename(str(fname)))[0]
    reader = FilterbankReader(fname)
    header = reader.header
    nsamples = header["nsamples"]
    sample_time = header["tsamp"]
    start_freq = header["fbottom"]
    stop_freq = header["ftop"]
    bandwidth = header["bandwidth"]
    foff = header["foff"]

    if dm_tiers not in (None, "smearing"):
        raise ValueError(f"dm_tiers={dm_tiers!r}: expected None or "
                         "'smearing'")
    plan = plan_chunks(nsamples, sample_time, dmmin, dmmax, start_freq,
                       stop_freq, foff, chunk_length=chunk_length,
                       new_sample_time=new_sample_time)
    flat_windows = None
    if boxcar_max is not None:
        from ..ops.search import boxcar_ladder

        flat_windows = boxcar_ladder(boxcar_max, plan.resample)
    dm_plan = None
    if dm_tiers is not None:
        from ..ops.plan import dm_tier_plan

        dm_plan = dm_tier_plan(header["nchans"], dmmin, dmmax, start_freq,
                               bandwidth, plan.sample_time, foff,
                               # the plan's samples are `resample` of the
                               # file's
                               boxcar_max=(None if boxcar_max is None else
                                           flat_windows[-1]))
        if len(dm_plan) == 1 and dm_plan[0].downsample == 1:
            dm_plan = None  # the flat plan, to the bit
        else:
            # every tier's time axis stays tile-divisible
            plan = plan_chunks(nsamples, sample_time, dmmin, dmmax,
                               start_freq, stop_freq, foff,
                               chunk_length=chunk_length,
                               new_sample_time=new_sample_time,
                               tile_factor=dm_plan[-1].downsample)
    eff_tsamp = plan.sample_time
    logger.info("chunk plan: step=%d hop=%d resample=%d -> tsamp=%g s",
                plan.step, plan.hop, plan.resample, eff_tsamp)

    def _resolve(threshold, tsamp, t_eff, grid, windows=None):
        """``(snr_threshold, search_snr_floor)`` of one searched geometry:
        ``t_eff`` samples of ``tsamp`` over the trial DMs ``grid()``,
        scored with the ladder ``windows``."""
        from ..ops.certify import (certifiable_snr_floor, matched_snr_floor,
                                   retention_bound)

        def cert_floor():
            """Certifiable floor for this geometry (lazy: the retention
            bound walks the tier's merge tables and its trials' shift
            table on the host, seconds at ten thousand trials of a few
            thousand channels, and only two configurations need it —
            snr_threshold='certifiable', and the hybrid's
            exact_floor='auto' comparison)."""
            trial_dms = grid()
            rho = retention_bound(header["nchans"], trial_dms, start_freq,
                                  bandwidth, tsamp, t_eff, cert=True,
                                  windows=windows)
            return certifiable_snr_floor(t_eff, len(trial_dms), rho)

        if isinstance(threshold, str):
            if threshold == "auto":
                # clamped to the reference default (clean.py:349): at short
                # chunks the matched floor resolves BELOW 6 and "auto" must
                # never be more permissive than the reference's criterion
                # (the Gumbel fit is also least validated at small m —
                # certify.expected_noise_max_snr's stated fit domain)
                threshold = max(matched_snr_floor(t_eff, len(grid())), 6.0)
            elif threshold == "certifiable":
                threshold = cert_floor()
            else:
                raise ValueError(
                    f"snr_threshold={threshold!r}: expected a number, "
                    "'auto' or 'certifiable'")
            threshold = round(float(threshold), 2)
            logger.info("snr_threshold resolved to %.2f for %d-sample "
                        "chunks", threshold, t_eff)

        # the hybrid gets the threshold as its snr_floor ONLY when the
        # noise certificate can actually fire at that level: forwarding a
        # sub-certifiable floor (e.g. the reference default 6.0 on
        # million-sample chunks) would make the rigorous all-detections-
        # exact criterion rescan toward a full exact sweep on EVERY chunk —
        # the round-2 behaviour this round removed.  Below the certifiable
        # level the hybrid runs floorless (exact-argbest-only contract, the
        # round-2 streaming semantics), which is both faster and what the
        # fixed thresholds historically meant.
        floor = None
        if kernel == "hybrid" and exact_floor is not False:
            cert = None if exact_floor is True else cert_floor()
            if exact_floor is True or threshold >= round(cert, 2) - 1e-9:
                floor = threshold
            else:
                logger.info(
                    "snr_threshold %.2f sits below the certifiable floor "
                    "%.2f for this chunk geometry: hybrid runs without "
                    "snr_floor (exact best row only; pass exact_floor=True "
                    "to force the all-detections-exact contract, or "
                    "snr_threshold='certifiable' for the noise-certificate "
                    "fast path)", threshold, cert)
        return threshold, floor

    def _flat_grid():
        from ..ops.plan import dedispersion_plan

        return dedispersion_plan(header["nchans"], dmmin, dmmax, start_freq,
                                 bandwidth, eff_tsamp)

    t_flat = max(plan.step // plan.resample, 2)
    tiers = None
    if dm_plan is None:
        snr_threshold, search_snr_floor = _resolve(
            snr_threshold, eff_tsamp, t_flat, _flat_grid, flat_windows)
    else:
        tiers = []
        for tier in dm_plan:
            logger.info("DM tier x%d: DM %.2f-%.2f, %d trials at %g s",
                        tier.downsample, tier.dm_lo, tier.dm_hi,
                        len(tier.trial_dms), tier.sample_time)
            thr, floor = _resolve(
                snr_threshold, tier.sample_time,
                max(t_flat // tier.downsample, 2),
                lambda tier=tier: tier.trial_dms,
                None if boxcar_max is None else tier.windows)
            tiers.append({"tier": tier, "snr_threshold": thr,
                          "search_snr_floor": floor})
        # what the run reports as "the" threshold is the first tier's
        snr_threshold = tiers[0]["snr_threshold"]
        search_snr_floor = tiers[0]["search_snr_floor"]

    # a chunk the device cannot hold is searched in time tiles: only the
    # packed single-device hybrid path knows how (the bytes stay resident
    # and every tile is cleaned from them), and only the plain clean
    tile_plan = None
    if (device_memory_bytes and backend == "jax" and kernel == "hybrid"
            and mesh is None and reader.packed_bits and plan.resample == 1
            and not (fft_zap or cut_outliers)):
        tile_plan = _plan_tiles(device_memory_bytes, header, plan, tiers,
                                (dmmin, dmmax, flat_windows),
                                reader.packed_bits)
    if tile_plan:
        logger.info("tile plan: %s", "; ".join(
            f"tier {k}: {t.tiles} x ({t.own} + {t.halo} halo)"
            + (f" x {len(t.bands)} delay bands ("
               + ", ".join(f"{b.n_lo}-{b.n_hi}" for b in t.bands) + ")"
               if t.bands else "")
            + f", {t.bytes / 2**30:.2f} GiB reckoned, keeps {t.keep}"
            for k, t in enumerate(tile_plan)))
    else:
        logger.info("tile plan: every tier whole")

    fingerprint = config_fingerprint(
        fname=os.path.abspath(str(fname)), dmmin=dmmin, dmmax=dmmax,
        step=plan.step, resample=plan.resample, backend=backend,
        kernel=kernel, snr_threshold=snr_threshold, fft_zap=fft_zap,
        cut_outliers=cut_outliers,
        # only fingerprint zero_dm when it changes the result: adding the
        # key unconditionally would orphan every pre-existing resume
        # ledger for plain runs
        **({"zero_dm": True} if zero_dm else {}),
        # same orphan-avoidance rule for the mesh route (device count
        # changes the f32 reduction shapes, not the science)
        **({"mesh": list(mesh.shape.values())} if mesh is not None else {}),
        # and for the integrity gate: a non-default policy changes what
        # gets searched on flagged data, so its ledger must not be
        # interchangeable with the default's (a default-policy run
        # keeps the pre-hardening fingerprint — no orphaned ledgers)
        **({"quarantine_policy": str(quarantine_policy)}
           if quarantine_policy != "sanitize" else {}),
        surelybad=sorted(int(c) for c in surelybad),
        period_search=bool(period_search),
        period_sigma_threshold=float(period_sigma_threshold),
        # a tiered plan searches another grid against other thresholds;
        # the flat plan (dm_tiers off, or one tier) keeps its fingerprint
        **({"dm_tiers": [[t["tier"].downsample, len(t["tier"].trial_dms),
                          t["snr_threshold"]] for t in tiers]}
           if tiers else {}),
        # another ladder scores other windows: a resume across a changed
        # --boxcar-max is refused like any changed plan
        **({"boxcar_max": int(boxcar_max)} if boxcar_max is not None
           else {}),
        # workload-distinct ledgers (ISSUE 13): merged LAST so a
        # collision with a driver field fails loudly in review, and
        # absent entirely when unset — every pre-existing ledger
        # fingerprint is unchanged
        **(fingerprint_extra or {}))

    return {
        "reader": reader, "plan": plan, "root": root,
        "nsamples": nsamples, "sample_time": sample_time,
        "snr_threshold": snr_threshold,
        "search_snr_floor": search_snr_floor,
        "tiers": tiers,
        "windows": flat_windows,
        "tile_plan": tile_plan,
        "fingerprint": fingerprint,
        "chunk_starts": list(iter_chunk_starts(nsamples, plan, tmin=tmin,
                                               sample_time=sample_time)),
    }


@reserve_frames
def search_by_chunks(fname, chunk_length=None, new_sample_time=None, tmin=0,
                     dmmin=200, dmmax=800, surelybad=(), *, backend="jax",
                     kernel="auto", snr_threshold=6.0, output_dir=None,
                     make_plots="hits", resume=True, fft_zap=False,
                     cut_outliers=False, zero_dm=False, max_chunks=None,
                     progress=True, period_search=False,
                     period_sigma_threshold=8.0, show_plots=False,
                     mesh=None, exact_floor="auto", overlap_persist=True,
                     budget=None, dispatch_timeout=None, dispatch_retries=1,
                     dispatch_backoff=0.0, quarantine_policy="sanitize",
                     persist_retries=2, persist_backoff=0.05,
                     http_port=None, http_host="127.0.0.1", canary=None,
                     health=None, report_out=None, chunks=None,
                     cancel_cb=None, plane_consumer=None,
                     fingerprint_extra=None, fence=None, lineage=None,
                     push=None, dm_tiers=None, boxcar_max=None):
    """Search a filterbank file for dispersed single pulses.

    Parameters follow the reference driver (``clean.py:276``) plus the
    TPU-framework knobs (keyword-only).  ``make_plots``: ``"hits"``
    (diagnostic JPEG per candidate), ``"all"``, or ``False``.

    ``snr_threshold`` is the reference's hit criterion (``snr > 6``,
    ``clean.py:349``).  Besides a number it accepts two strings that
    adapt the floor to the chunk geometry (the fixed 6 was tuned for the
    reference's ~1e3-sample chunks; at million-sample chunks the
    signal-free maximum alone is ~5.5 — see :mod:`..ops.certify`):

    * ``"auto"`` — the statistically matched floor
      (:func:`~pulsarutils_tpu.ops.certify.matched_snr_floor`): noise
      ceiling + 1, sub-percent false alarms per chunk;
    * ``"certifiable"`` — the lowest floor whose noise certificate fires
      on typical signal-free chunks
      (:func:`~pulsarutils_tpu.ops.certify.certifiable_snr_floor`):
      with ``kernel="hybrid"`` the streaming cost of a signal-free chunk
      drops to one coarse sweep (the survey fast path).

    ``exact_floor`` controls whether ``snr_threshold`` is also forwarded
    as the hybrid kernel's ``snr_floor`` (the all-above-threshold-
    detections-exact contract + the noise certificate):

    * ``"auto"`` (default) — forwarded only when the threshold sits at
      or above the chunk geometry's certifiable floor; below it the
      hybrid runs floorless (exact best row only — the fast behaviour
      the fixed reference thresholds historically got), with an
      info-level log stating so;
    * ``True`` — always forwarded: every above-threshold detection is
      exact, accepting that below the certifiable floor this honestly
      costs up to a full exact sweep per chunk;
    * ``False`` — never forwarded.

    ``mesh`` (a ``jax.sharding.Mesh``) routes every chunk through the
    multi-device sharded searches — the same device-resident chunk is
    searched by all devices (for ``kernel="hybrid"`` the DM-sliced
    coarse stage, seed selection and exact seed/need rescore run as ONE
    fused ``shard_map`` dispatch on floorless chunks, round 6; the
    per-chunk dispatch/readback trip counts land in the chunk budget
    exactly as on the single-device path, so the ``BUDGET_JSON`` footer
    prices the mesh route's device trips honestly).
    ``make_plots``/``period_search`` work on the mesh path too: the
    captured plane stays DM-sharded and device-resident, the
    periodicity spectra and the figure's per-row H curve are computed
    shard-locally, and only per-row score vectors, a decimated image
    and single rows are gathered (:mod:`..parallel.sharded_plane`).

    ``show_plots=True`` additionally displays each diagnostic figure in
    an interactive window (the reference's ``show=True`` behaviour,
    ``clean.py:347``) — a no-op under a non-interactive matplotlib
    backend, so headless runs are unaffected.

    ``period_search=True`` adds the folded period search
    (:func:`..ops.periodicity.period_search_plane`) on every chunk's
    dedispersed plane: a chunk whose best periodic candidate exceeds
    ``period_sigma_threshold`` is persisted as a hit even without a
    single-pulse detection, with the folded profile and H statistics on
    its :class:`~.pulse_info.PulseInfo`.

    ``overlap_persist`` (default on, round 6) moves each chunk's
    candidate persist + ledger write onto a single-worker executor so
    the host-side npz write of chunk ``k`` overlaps the device
    search of chunk ``k+1``.  The worker is FIFO, ``save_candidate``
    precedes ``mark_done`` inside one task, and every task is drained
    before the function returns — ledger ordering, crash-safe resume
    semantics and the persisted candidate set are identical to the
    serial loop (pinned by ``tests/test_budget.py``).
    ``overlap_persist=False`` restores the strictly serial loop.

    ``budget`` accepts a caller-owned
    :class:`~pulsarutils_tpu.utils.logging_utils.BudgetAccountant`; by
    default one is created internally.  Either way every chunk's wall
    clock is attributed to named buckets (read/upload_wait/clean/search
    with the kernel facade's sub-buckets/trim/persist/...), with the
    residual reported as ``unattributed`` per chunk and in the run
    footer, a measured device RTT pricing the dispatch+readback trip
    counters, and a one-line ``BUDGET_JSON`` record logged for
    artifact parsers (the round-5 rehearsal's stage table explained ~6%
    of its wall clock; this layer exists so that can never happen
    silently again).

    Robustness knobs (ISSUE 4; see ``docs/robustness.md``).  On clean
    (all-finite) input the defaults reproduce the pre-hardening data
    path exactly — pinned by test; on data the integrity gate flags,
    the defaults *deliberately* diverge (sanitize or quarantine where
    the old path searched garbage); pass ``quarantine_policy="off"``
    for the literal pre-hardening behaviour:

    * ``dispatch_timeout`` (seconds, default off) bounds each device
      dispatch on a watchdog thread — a wedged device used to stall the
      stream forever; with a deadline the chunk proceeds to retry /
      numpy fallback within ``dispatch_timeout × (dispatch_retries +
      1)``.  Off by default (inline dispatch, byte-identical path);
      when arming it, note the watchdog dispatches from a non-main
      thread — device clients that require main-thread dispatch must
      be tested first (``docs/robustness.md``).  ``dispatch_retries``
      / ``dispatch_backoff`` shape the same-backend retry ladder
      before the numpy fallback;
    * ``quarantine_policy`` (``"sanitize"`` default / ``"strict"`` /
      ``"off"``) arms the pre-search data-integrity gate: chunks whose
      NaN/Inf, dead-channel, zero-run or saturation fractions breach
      the :class:`~pulsarutils_tpu.faults.policy.IntegrityPolicy`
      thresholds are **quarantined** — recorded in
      ``quarantine_<fingerprint>.jsonl`` and marked done-with-reason in
      the ledger (exact resume semantics) instead of poisoning the S/N
      statistics or crashing; sub-threshold NaN chunks are sanitized
      (non-finite values imputed, counted) under ``"sanitize"``.  The
      gate runs on the reader thread (overlapped, not on the chunk's
      serial critical path); low-bit (1/2/4-bit) chunks — packed fast
      path or host-decoded — are gated in the CODE domain instead
      (rail/zero/dead-channel fractions off the raw packed bytes, with
      thresholds rescaled onto the quantization floor, round 11 — the
      float gate used to skip them entirely, leaving low-bit runs
      health-blind);
    * persist failures retry ``persist_retries`` times with exponential
      ``persist_backoff`` and then **dead-letter** the chunk into the
      quarantine manifest instead of failing the whole run on one bad
      write; an end-of-run integrity audit
      (:func:`~pulsarutils_tpu.faults.audit.audit_run`) cross-checks
      ledger vs candidate files vs manifest and logs any inconsistency.

    Live observability knobs (ISSUE 5; ``docs/observability.md``) —
    all default-off, and when off the data path is byte-identical to
    the pre-PR driver:

    * ``http_port`` starts the live HTTP surface
      (:mod:`~pulsarutils_tpu.obs.server`): ``/metrics`` (live
      Prometheus scrape), ``/healthz`` (engine verdict, HTTP 503 on
      CRITICAL), ``/progress`` (chunks done/total of *this session's*
      work list, ETA, canary recall).  ``0`` binds an ephemeral port;
      ``http_host`` picks the bind address — the loopback default
      keeps the surface on-machine, ``"0.0.0.0"`` exposes it to a
      remote Prometheus scrape job or fleet ``/healthz`` probe;
    * ``canary`` arms continuous synthetic-pulse injection-recovery
      (:class:`~pulsarutils_tpu.obs.canary.CanaryController`, or a bare
      float taken as the injection rate): known-(DM, width, S/N)
      dispersed pulses on the reader thread, matched against the
      emitted tables into live recall / S/N-recovery / DM-error
      metrics.  Canary-matched best rows are tagged and **excluded**
      from the hits list, candidate files and ledger — when the canary
      outranks a genuine weaker pulse in the same chunk, that pulse is
      promoted (persisted with the canary rows masked out of its
      table) so the science candidate set matches the canary-off run;
      on the packed low-bit fast path the bump is quantized into the
      low-bit codes and re-packed on the reader thread (round 11), so
      recall is measured there too — the old auto-disable is gone;
    * ``health`` accepts a caller-owned
      :class:`~pulsarutils_tpu.obs.health.HealthEngine` (the chaos
      drill passes one); with ``http_port`` set and no engine given,
      one is created internally.  The engine receives one update per
      chunk (wall, candidate count, quarantines, retries, retraces,
      headroom, canary recall) and folds them into the OK / DEGRADED /
      CRITICAL verdict ``/healthz`` serves;
    * ``report_out`` writes the end-of-run survey report (markdown +
      single-file HTML, :mod:`~pulsarutils_tpu.obs.report`) stitching
      budget, canary recall curve, health incidents, sift counters and
      the quarantine manifest into one artifact.

    Fleet knobs (ISSUE 9; ``docs/fleet.md``) — default-off, byte-inert
    when unset:

    * ``chunks`` restricts the session to the given chunk ``istart``
      values (an iterable; chunk starts not in the plan are ignored).
      This is the fleet worker's lease seam: a leased work unit is a
      subset of one file's chunk grid, and each chunk's persisted
      candidate/ledger bytes are independent of which session searches
      it — the byte-identity contract bench config 14 gates.  Chunks
      outside the subset are neither searched nor marked done;
    * ``cancel_cb`` (zero-arg callable) is checked before each chunk:
      once it returns True the session finishes nothing further — the
      in-flight chunk completes, its persist/ledger write drains, and
      the remaining chunks stay un-marked so a resumed (or re-leased)
      session picks up exactly there.  This is the worker's graceful
      drain seam.

    Periodicity seams (ISSUE 13; ``docs/periodicity.md``) — both
    byte-inert when unset:

    * ``plane_consumer`` (a ``fn(istart, plane, table)`` callable)
      forces plane capture and hands each searched chunk's dedispersed
      plane — a device array, or a DM-sharded
      :class:`~pulsarutils_tpu.parallel.sharded_plane.ShardedPlane`
      handle on the mesh route — downstream before it is dropped.
      Called BEFORE the chunk's ledger mark, so a crash window at
      worst re-delivers a chunk on resume; consumers must de-duplicate
      by ``istart`` (the
      :class:`~pulsarutils_tpu.periodicity.accumulate.
      DMTimeAccumulator` does).  With the single-pulse ``canary``
      armed, injected chunks' planes carry the synthetic track — the
      periodicity driver runs canary-off on this leg and injects its
      own periodic canary downstream;
    * ``fingerprint_extra`` rides to :func:`plan_survey` so a
      different *workload* over the same file keeps its own resume
      ledger.

    Candidate lifecycle observability (ISSUE 18), both ``None``-gated
    (off keeps the output directory byte-identical):

    * ``lineage`` — ``True`` (or a
      :class:`~pulsarutils_tpu.obs.lineage.LineageRecorder`) stamps
      every hit with monotone stage timestamps (read → dispatch →
      device ready → sift → persist → alert), persists a
      ``.lineage.json`` doc beside the candidate npz pair, feeds the
      ``putpu_candidate_stage_seconds`` /
      ``putpu_candidate_latency_seconds`` histograms (the
      candidate-latency p95 SLO) and opens a ``candidate`` span on the
      chunk's Perfetto track;
    * ``push`` — an :class:`~pulsarutils_tpu.obs.push.AlertBroker` (or
      a list of subscriber specs, which builds a driver-owned broker
      dead-lettering into the output directory and closes it, bounded,
      at the tail) fans each hit out to webhook subscribers on a
      bounded-queue daemon thread; a slow or dead subscriber can only
      fill the queue (drop-oldest, counted), never stall this loop.
      Canary-tagged rows are excluded before the publish site.

    ``dm_tiers="smearing"`` (default off) searches the DM range in tiers
    (:func:`~pulsarutils_tpu.ops.plan.dm_tier_plan`): the chunk is cleaned
    once at the plan's sample time, summed in pairs tier by tier on the
    device (``jit_tier_downsample``), and every tier goes through the
    same search call as the flat path with its own array, trial grid,
    sample time and threshold.  A chunk's table is the tiers' rows
    concatenated in tier order, with an integer ``downsample`` column;
    ``peak`` and ``rebin`` stay in the samples of the row's own tier.  A
    row is a detection against its own tier's threshold, the chunk's hit
    is the best such row, and its :class:`~.pulse_info.PulseInfo` is
    built from that tier's array.  A plan of one tier at the plan's own
    sample time is the flat path: same bytes, same fingerprint.  A mesh,
    the canary, the period search and a ``plane_consumer`` are refused
    with it (``ValueError``).

    ``boxcar_max`` (default off; ``PUsearchfrb --boxcar-max``) continues
    the scorer's boxcar ladder beyond 8 samples, in doubling steps up to
    that many samples of the file
    (:func:`~pulsarutils_tpu.ops.search.boxcar_ladder`): a flat plan
    scores ``1 .. boxcar_max``, tier ``k`` of a tiered plan ``1 ..
    max(8, boxcar_max / 2^k)`` of its own samples.  The certificate's
    capture and bound, the exact rescore, each tier's threshold and the
    fingerprint follow the ladder; ``rebin`` and ``peak`` stay in the
    row's own samples.  A mesh, the period search and a
    ``plane_consumer`` are refused with it (``ValueError``).

    Returns ``(hits, store)`` where hits is a list of
    ``(istart, iend, PulseInfo, ResultTable)``.  NOTE (round 6): when
    plotting is off, a hit's retained/persisted ``info.allprofs`` is the
    self-describing pulse **cutout** (``cutout_start``/``cutout_decim``
    set, device-sliced before readback), not the full chunk waterfall —
    pulling multi-GB cleaned chunks back over a slow link per hit was
    the survey rehearsal's single largest unattributed cost.
    """
    # identity checks on purpose: exact_floor=1 must NOT silently pass
    # as True (the floor-forwarding branches use `is True`/`is not
    # False`); validated before any file IO so config errors fail fast
    if exact_floor is not True and exact_floor is not False \
            and exact_floor != "auto":
        raise ValueError(f"exact_floor={exact_floor!r}: expected True, "
                         "False or 'auto'")
    if dm_tiers is not None:
        # S9's four-chip tier is its own PR; the other three read one
        # plane or one time axis per chunk
        refused = [name for name, on in (
            ("mesh", mesh is not None), ("canary", bool(canary)),
            ("period_search", period_search),
            ("plane_consumer", plane_consumer is not None)) if on]
        if refused:
            raise ValueError(f"dm_tiers={dm_tiers!r} does not run with "
                             + ", ".join(refused))
    if boxcar_max is not None:
        # the mesh kernels and the plane's period search score with the
        # default ladder only
        refused = [name for name, on in (
            ("mesh", mesh is not None), ("period_search", period_search),
            ("plane_consumer", plane_consumer is not None)) if on]
        if refused:
            raise ValueError(f"boxcar_max={boxcar_max!r} does not run with "
                             + ", ".join(refused))
    if mesh is not None:
        # fail fast: a missing axis would otherwise surface as a KeyError
        # inside the first chunk's search, which the failure-containment
        # path misreads as a transient device fault and silently retries
        # into the numpy fallback.  kernel="fdmt" routes to the DM-sliced
        # sharded FDMT only, so a dm-only mesh is valid there; every
        # other kernel reaches sharded_dedispersion_search, which indexes
        # both axes.
        needed = {"dm"} if kernel == "fdmt" else {"dm", "chan"}
        if not needed <= set(mesh.shape):
            raise ValueError(
                f"mesh axes {tuple(mesh.shape)} must include "
                f"{sorted(needed)} for kernel={kernel!r} (build one with "
                "make_mesh((d, c), ('dm', 'chan')))")
    # resolved before any file IO so a bogus policy string fails fast
    integrity = resolve_integrity_policy(quarantine_policy)
    dispatch_policy = DispatchPolicy(timeout_s=dispatch_timeout,
                                     retries=dispatch_retries,
                                     backoff_s=dispatch_backoff)
    # canary normalisation fails fast too: a bare number is the rate
    if canary is not None and not isinstance(canary, CanaryController):
        canary = CanaryController(rate=float(canary))
    if canary is not None and canary.rate <= 0.0:
        canary = None  # rate 0 is the documented spelled-out "off"
    output_dir = output_dir or os.path.dirname(os.path.abspath(str(fname)))

    if make_plots:
        try:
            import matplotlib  # noqa: F401 — optional [plot] extra
        except ImportError:
            logger.warning("matplotlib not installed: diagnostic plots "
                           "disabled (install the [plot] extra)")
            make_plots = False

    timer = budget if budget is not None else BudgetAccountant()
    timer.begin_stream()  # reused accountants: retrace baseline per run
    # each survey session starts undegraded: within a run OOM descents
    # are sticky (a measured slowdown, not a crash loop); a fresh run
    # rediscovers pressure through the preflight estimator (ISSUE 12)
    _resilience_ladder.reset()

    with_timer = timer.bucket
    # what the call costs outside its chunks (ISSUE 25): ``call/setup``
    # runs from here to the first chunk, ``call/finish`` from the persist
    # drain to the return; both reach BUDGET_JSON's ``call_s`` and, under
    # a tracer, the timeline.  Opened and closed by hand because each
    # encloses most of this function: when set-up raises the span goes
    # unrecorded with the call it would have described.
    call_phase = contextlib.ExitStack()
    call_phase.enter_context(with_timer("call/setup"))
    with with_timer("badchans"):
        # the pre-scan streams the whole file through the same reader
        # seam the chunk loop uses, but BEFORE the hardened loop
        # exists: injection is suppressed here so an env-armed read
        # fault targets the search chunks (and cannot crash the run at
        # startup or silently eat a times=1 budget); the scan has its
        # own resilience story (.badchans cache, restartable)
        with fault_inject.suppressed():
            mask_fileorder = get_bad_chans(fname, surelybad=surelybad)

    with with_timer("call/plan"):
        # geometry, resolved threshold and ledger fingerprint all come from
        # the ONE planning function the fleet coordinator also calls — any
        # second copy of this logic would let coordinator and worker drift
        # onto different ledgers (ISSUE 9)
        sp = plan_survey(fname, chunk_length=chunk_length,
                         new_sample_time=new_sample_time, tmin=tmin,
                         dmmin=dmmin, dmmax=dmmax, surelybad=surelybad,
                         backend=backend, kernel=kernel,
                         snr_threshold=snr_threshold, fft_zap=fft_zap,
                         cut_outliers=cut_outliers, zero_dm=zero_dm,
                         mesh=mesh, exact_floor=exact_floor,
                         quarantine_policy=quarantine_policy,
                         period_search=period_search,
                         period_sigma_threshold=period_sigma_threshold,
                         fingerprint_extra=fingerprint_extra,
                         dm_tiers=dm_tiers, boxcar_max=boxcar_max,
                         device_memory_bytes=(
                             _device_memory_bytes()
                             if backend == "jax" and kernel == "hybrid"
                             and mesh is None else None))
        reader = sp["reader"]
        root = sp["root"]
        header = reader.header
        nsamples = sp["nsamples"]
        sample_time = sp["sample_time"]
        start_freq = header["fbottom"]
        bandwidth = header["bandwidth"]
        date = header.get("tstart", None)

        # single place that owns band orientation: ascending everywhere
        # below
        mask = (mask_fileorder[::-1] if reader.band_descending
                else mask_fileorder)

        plan = sp["plan"]
        eff_tsamp = plan.sample_time
        snr_threshold = sp["snr_threshold"]
        search_snr_floor = sp["search_snr_floor"]
        tiers = sp["tiers"]  # None: the flat plan
        flat_windows = sp["windows"]  # None: the default ladder
        tile_plan = sp["tile_plan"]  # None: every tier swept whole
        fingerprint = sp["fingerprint"]
        # fence (ISSUE 15): the fleet worker's lease epoch — candidate
        # artifact writes stamped with a higher epoch are refused (see
        # CandidateStore).  None (every non-fleet caller) is byte-inert.
        store = CandidateStore(output_dir, fingerprint if resume else None,
                               fence=fence)
        # quarantine manifest: created lazily on first record, so a clean
        # run's output directory is byte-identical to pre-hardening
        manifest = QuarantineManifest(output_dir,
                                      fingerprint if resume else None)

        # candidate lifecycle observability (ISSUE 18).  ``lineage=True``
        # builds a per-run recorder (or pass a LineageRecorder to share one
        # across files); ``push`` accepts an AlertBroker or a list of
        # subscriber specs (urls/dicts) — specs build a driver-owned broker
        # dead-lettering into the output directory, closed (bounded) at the
        # tail.  Both are None-gated: off is the pre-PR code path and the
        # output directory is byte-identical.
        if lineage is True:
            lineage = LineageRecorder(fingerprint=fingerprint,
                                      source="search_by_chunks")
        elif not lineage:
            lineage = None          # accept False/0/"" as "off" (CLI flag)
        push_owned = False
        if not push:
            push = None
        elif not isinstance(push, AlertBroker):
            push = AlertBroker(
                push, health=health,
                dead_letter_path=os.path.join(
                    output_dir, f"push_dead_letter_{fingerprint}.jsonl"))
            push_owned = True

    hits = []
    nproc = 0
    ncertified = 0
    capture = bool(make_plots) or bool(period_search) \
        or plane_consumer is not None
    if tile_plan and capture:
        raise ValueError(
            "this chunk is searched in time tiles (it does not fit the "
            "device whole): no plane exists to plot, fold or hand on; run "
            "with make_plots=False")
    if tile_plan:
        for k, t in enumerate(tile_plan):
            obs_metrics.gauge("putpu_time_tile_samples", tier=str(k)).set(
                t.own)
    fallback_state = {}

    # one conditioning pipeline, parameterised by array namespace — the
    # device (jitted) and host (fallback) paths must never diverge
    clean_options = (bool(cut_outliers), bool(zero_dm), bool(fft_zap),
                     int(plan.resample))

    def _clean(block, m):
        return _clean_block(block, m, np, *clean_options)

    # device-side cleaning: with backend="jax" the chunk is uploaded raw
    # and conditioned on the accelerator (one jitted program reused for
    # every chunk) — the host, often a single core, only reads/decodes,
    # and the cleaned chunk is already device-resident for the search.
    # Low-bit and unsigned 8-bit single-IF files go further (round 4;
    # 8 bits PR 35): the bytes are uploaded as the file stores them and
    # the unpack runs inside the same jit — 1/16th the link traffic at 2
    # bits, a quarter at 8, and no float block on the host at all (the
    # host decoders stay as the fallback).  ``reader.packed_bits`` is
    # the rule.
    packed_bits = reader.packed_bits if backend == "jax" else 0
    if canary is not None:
        # the packed fast path injects too (round 11): the bump is
        # quantized into the low-bit codes and re-packed on the reader
        # thread (CanaryController.maybe_inject_packed), so the device
        # signature is exact and recall is measured on packed runs —
        # the old auto-disable seam is gone
        canary.bind(nchan=header["nchans"], start_freq=start_freq,
                    bandwidth=bandwidth, tsamp=sample_time,
                    dmmin=dmmin, dmmax=dmmax,
                    resample=plan.resample)
    device_clean = None
    device_downsample = None
    chain_factors = ()
    if tiers:
        # tier k's array is tier k-1's summed in pairs; a first tier at the
        # plan's own sample time searches the cleaned chunk itself
        chain_factors = tuple(t["tier"].downsample for t in tiers
                              if t["tier"].downsample > 1)
    if backend == "jax":
        with with_timer("call/device_setup"):
            import jax
            import jax.numpy as jnp

            compile_phase.install()
            mask_dev = jnp.asarray(np.asarray(mask))
            # donate the raw chunk buffer into the clean program on
            # accelerators: it is never touched again (the host copy backs
            # the fallback), so the cleaned output can reuse its HBM — one
            # fewer live chunk-sized buffer during the double-buffered
            # stream.  CPU ignores donation with a per-call warning, so the
            # flag is backend-gated rather than unconditional.
            donate = ((0,) if jax.default_backend() in ("tpu", "gpu") else ())
            unpack = None
            if packed_bits:
                from ..io.lowbit import device_unpack_block

                unpack = (device_unpack_block, packed_bits,
                          header["nchans"], bool(reader.band_descending))
            device_clean = _device_clean_program(unpack, donate,
                                                 clean_options)
            if tiers:
                device_downsample = _tier_downsample_program(chain_factors)
            if tile_plan:
                from .time_tiles import (TiledTierArray,
                                         chunk_stats_program,
                                         wrap_rows_program)

                chunk_stats = chunk_stats_program(unpack, plan.step)
            if timer.rtt_s is None:  # keep a caller-calibrated RTT
                timer.rtt_s = measure_device_rtt()
            if timer.rtt_s is not None:
                logger.info("device round-trip floor: %.4fs per "
                            "dispatch+readback trip", timer.rtt_s)

    # the chunk list is known upfront, so the NEXT chunk's read/decode
    # and then its upload overlap the current chunk's device compute
    # (single reader thread, one chunk ahead — the driver host is often
    # one core doing nothing during the search; see ``read_at``)
    todo = [s for s in sp["chunk_starts"]
            if not (resume and store.is_done(s))]
    if chunks is not None:
        # fleet lease subset: only the leased chunk starts are searched
        # (or marked done) this session; unknown starts are ignored so a
        # stale lease over a replanned file degrades to a no-op, not a
        # crash
        wanted = {int(c) for c in chunks}
        todo = [s for s in todo if s in wanted]
    if max_chunks is not None:
        todo = todo[:max_chunks]

    # -- live surface (ISSUE 5): health engine + HTTP endpoints ---------
    if http_port is not None and health is None:
        health = HealthEngine()
    t_run0 = time.time()
    # EWMA chunk throughput (ISSUE 20): the /progress ETA follows the
    # CURRENT rate, so one slow warm-up/compile chunk stops poisoning
    # the estimate after a few folds.  The lifetime mean stays as the
    # fallback until the model has evidence.
    eta_model = EwmaThroughput()

    def _progress_snapshot():
        """The ``/progress`` document (read from the scrape thread —
        plain reads of ints/lists under the GIL)."""
        done = nproc
        total = len(todo)
        elapsed = time.time() - t_run0
        eta = eta_model.eta_s(max(total - done, 0))
        if eta is None and done and elapsed > 0:
            eta = (total - done) * elapsed / done
        doc = {"fname": os.path.basename(str(fname)),
               "chunks_done": done, "chunks_total": total,
               "elapsed_s": round(elapsed, 1),
               "eta_s": None if eta is None else round(eta, 1),
               "hits": len(hits), "certified": ncertified,
               "quarantined": len(store.quarantined_chunks)}
        if canary is not None:
            doc["canary"] = canary.summary()
        return doc

    obs_server = None
    if http_port is not None:
        obs_server = start_obs_server(http_port, health=health,
                                      progress_fn=_progress_snapshot,
                                      host=http_host, push=push)

    # health consumes per-chunk DELTAS of process-wide counters (other
    # runs in this process may have bumped them already).  OOM events
    # arrive per surface label, so the delta is over the labelled sum.
    def _oom_events_total():
        return sum(
            m.get("value", 0)
            for m in obs_metrics.REGISTRY.snapshot()
            if m.get("name") == "putpu_oom_events_total")

    health_base = {}
    if health is not None:
        for key, name in (("dead", "putpu_persist_dead_letter_total"),
                          ("retry", "putpu_dispatch_retries_total"),
                          ("retrace", "putpu_retraces_total")):
            health_base[key] = obs_metrics.counter(name).value
        health_base["oom"] = _oom_events_total()

    def _health_update(istart, wall_s, candidates=None, quarantined=False,
                       headroom_frac=None, oom_floor=False):
        # every completion path lands here, so this is where the ETA
        # model folds — quarantined chunks count too (they drain the
        # backlog just the same).  wall_s is None on the tail flush:
        # nothing completed, nothing to fold.
        if wall_s is not None:
            eta_model.note(1, wall_s)
        if health is None:
            return
        deltas = {}
        for key, name in (("dead", "putpu_persist_dead_letter_total"),
                          ("retry", "putpu_dispatch_retries_total"),
                          ("retrace", "putpu_retraces_total")):
            v = obs_metrics.counter(name).value
            deltas[key] = v - health_base[key]
            health_base[key] = v
        oom_now = _oom_events_total()
        oom_delta = oom_now - health_base["oom"]
        health_base["oom"] = oom_now
        health.update(
            istart, wall_s=wall_s, candidates=candidates,
            quarantined=quarantined, dead_letter=deltas["dead"] > 0,
            dispatch_retries=deltas["retry"],
            retraces=deltas["retrace"], headroom_frac=headroom_frac,
            oom_events=oom_delta, oom_floor=oom_floor,
            fallback=bool(backend != "numpy"
                          and fallback_state.get("backend") == "numpy"),
            canary=canary.summary() if canary is not None else None)

    from concurrent.futures import ThreadPoolExecutor

    def read_gated(s, rspan):
        """Read (and gate) one chunk on the reader thread.

        Returns ``(block, gate_info)`` — ``gate_info`` is ``None`` when
        the integrity gate is off or the packed fast path is in use,
        else the verdict/stats dict from :func:`..faults.policy.
        gate_chunk`.  A transient read error is retried (bounded,
        counted); a persistent one returns a ``_ReadFailure`` sentinel
        so the chunk loop quarantines the chunk instead of the whole
        stream dying.  SCOPE: this contains read failures that surface
        as ``OSError`` (network filesystems, injected faults); a bad
        sector under the mmapped file raises SIGBUS, which no except
        clause can catch — pread-based reads would be needed at the
        sigproc seam to contain that class.
        """
        t0 = time.perf_counter()
        if lineage is not None:
            lineage.mark(s, "read")
        try:
            nread = min(plan.step, nsamples - s)
            block = None
            for attempt in range(3):
                try:
                    if packed_bits:
                        # packed bytes straight off the mmap: decode
                        # happens on device (or in the host fallback
                        # below on demand)
                        block = reader.read_block_packed(s, nread)
                    else:
                        block = reader.read_block(s, nread,
                                                  band_ascending=True)
                    break
                except OSError as exc:
                    if attempt == 2:
                        logger.error("chunk %d read failed after %d "
                                     "attempts (%r)", s, attempt + 1, exc)
                        return _ReadFailure(exc), None
                    obs_metrics.counter("putpu_read_retries_total").inc()
                    logger.warning("chunk %d read error (%r); retrying",
                                   s, exc)
                    # backoff before re-reading (reader thread — off the
                    # critical path): immediate retries would exhaust
                    # the budget in microseconds and quarantine a chunk
                    # over a sub-second I/O blip (code-review r8)
                    time.sleep(0.1 * (2 ** attempt))
            if packed_bits:
                # packed fast path (round 11): the canary bump is
                # quantized into the low-bit codes and re-packed here —
                # whatever unpacks these bytes (device jit, host
                # fallback) sees an exact signature — and the
                # code-domain integrity gate reads cheap shift/mask
                # stats off the raw bytes (the float gate was skipped
                # on quantized data since PR 4, leaving low-bit runs
                # health-blind)
                if canary is not None:
                    block = canary.maybe_inject_packed(
                        block, s, nbits=packed_bits,
                        nchan=header["nchans"],
                        band_descending=reader.band_descending)
                if integrity is not None:
                    block, gate_info = gate_chunk_packed(
                        block, packed_bits, header["nchans"], integrity)
                    return block, gate_info
            else:
                block = fault_inject.corrupt("corrupt", block, chunk=s)
                if canary is not None:
                    # canary rides AFTER any armed fault corruption: it
                    # is injected into exactly the bytes the search
                    # will see, so an RFI storm that masks real pulses
                    # masks canaries too — which is the point
                    block = canary.maybe_inject(block, s)
                if integrity is not None \
                        and reader._nbits in (1, 2, 4):
                    # host-decoded low-bit chunk (numpy backend): the
                    # float-domain gate is meaningless on quantized
                    # codes (a healthy 1-bit chunk is ~50% at the
                    # rail, code-review r8) — the CODE-domain rule
                    # applies instead
                    block, gate_info = gate_chunk_lowbit(
                        np.asarray(block), reader._nbits, integrity)
                    return block, gate_info
                if integrity is not None:
                    # gated HERE, on the reader thread: the stats pass
                    # overlaps the previous chunk's device work instead
                    # of sitting on the chunk's serial critical path
                    block, gate_info = gate_chunk(np.asarray(block),
                                                  integrity)
                    return block, gate_info
            return block, None
        finally:
            # reader-thread seconds: overlapped with the previous
            # chunk's device work, so accounted but not in any chunk's
            # serial budget
            timer.add_async("read_decode", time.perf_counter() - t0)
            rspan.end()

    def upload(host):
        """Start the (asynchronous) host->device transfer of a chunk."""
        import jax

        buf = jax.device_put(host)
        obs_metrics.counter("putpu_bytes_uploaded_total").inc(
            int(getattr(host, "nbytes", 0)))
        return buf

    def read_at(s, rspan):
        """One chunk on the reader thread: read and gated
        (:func:`read_gated`), then its upload started.

        Returns ``(block, gate_info, buf)``.  The transfer of a chunk is
        the continuation of its read: where there is a device clean and
        the chunk may go up as it is, the device buffer ``buf`` comes
        back with the block, so the link works under the chunk BEFORE's
        search whenever the read ends, and the reader's next task waits
        for the transfer (span ``upload`` on track ``reader``,
        ``async_s.upload``) before it reads on — two transfers never
        share the link.  A failed, sanitized or quarantined chunk never
        goes up from here (the main path handles it, and must never
        upload the un-sanitized bytes), and a put that fails is
        non-fatal: ``buf`` is ``None`` and the main path uploads.  COST:
        peak HBM carries one extra raw chunk.  The budget's counters are
        taken on the main thread, where it takes the buffer.
        """
        block, gate_info = read_gated(s, rspan)
        buf = None
        if device_clean is not None \
                and not isinstance(block, _ReadFailure) \
                and (gate_info is None or gate_info["verdict"] == "clean"):
            uspan = rspan.follow("upload", chunk=s)
            t0 = time.perf_counter()
            try:
                buf = upload(block)
                # queued before this read's future resolves, so ahead of
                # the next read
                reader_pool.submit(await_upload, buf, uspan, t0)
            except Exception:
                # the put failed, or the pool is already shut down (a
                # cancel): the main path uploads, or nobody needs it
                buf = None
                uspan.end()
        return block, gate_info, buf

    def await_upload(buf, uspan, t0):
        try:
            buf.block_until_ready()
        except Exception:
            pass  # surfaces on the main thread's forced read-back
        finally:
            timer.add_async("upload", time.perf_counter() - t0)
            uspan.end()

    def submit_read(s):
        # begun here, on the main thread: the pool's thread inherits
        # neither the trace id nor the open span
        # putpu-lint: disable=span-leak — ends in read_gated on the reader thread (cross-thread by design)
        rspan = begin_span("read_decode", track="reader", chunk=s)
        return reader_pool.submit(read_at, s, rspan)

    # persist executor (round 6): one FIFO worker absorbs the per-chunk
    # candidate write + ledger write so it overlaps the NEXT
    # chunk's device search.  Single worker + save-before-mark inside
    # one task = ledger order and crash-resume semantics byte-identical
    # to the serial loop.
    persist_pool = (ThreadPoolExecutor(max_workers=1) if overlap_persist
                    else None)
    persist_futures = []

    def _persist_and_mark(payload, istart_, iend_, ck, reason=None):
        """Persist + mark done, with bounded retry and a dead-letter.

        A write failure used to fail the whole run (the overlap only
        deferred the raise).  Now: ``persist_retries`` bounded retries
        with exponential backoff, then a ``persist_dead_letter`` record
        in the quarantine manifest and done-with-reason in the ledger —
        the run continues, the audit knows the candidate is missing on
        purpose.  Only ``OSError`` is retried: anything else is a bug,
        not a disk hiccup, and still propagates.  ``ck`` (the chunk's
        entry, see ``budget_chunk``) receives ``t_disk``, the clock when
        ``mark_done`` returned — candidates and mark are on disk from
        then on — the seconds of the save and of the mark, and for a
        hit ``save_bytes``, the size on disk of its record.
        """
        t_save = time.perf_counter()
        if payload is not None:
            for attempt in range(max(int(persist_retries), 0) + 1):
                try:
                    ck["save_bytes"] = store.pair_bytes(
                        store.save_candidate(root, istart_, iend_, *payload))
                    break
                except OSError as exc:
                    if attempt < persist_retries:
                        obs_metrics.counter(
                            "putpu_persist_retries_total").inc()
                        logger.warning(
                            "persist of chunk %d-%d failed (%r); "
                            "retry %d/%d", istart_, iend_, exc,
                            attempt + 1, persist_retries)
                        time.sleep(persist_backoff * (2 ** attempt))
                    else:
                        obs_metrics.counter(
                            "putpu_persist_dead_letter_total").inc()
                        logger.error(
                            "persist of chunk %d-%d failed %d times "
                            "(%r): dead-letter recorded, run continues",
                            istart_, iend_, attempt + 1, exc)
                        manifest.record(istart_, iend_,
                                        fault_reasons.PERSIST_DEAD_LETTER,
                                        {"error": repr(exc)})
                        reason = fault_reasons.PERSIST_DEAD_LETTER
        t_mark = time.perf_counter()
        store.mark_done(istart_, reason=reason)
        ck["t_disk"] = time.perf_counter()
        ck["save_s"] = t_mark - t_save
        ck["mark_s"] = ck["t_disk"] - t_mark
        return reason

    def _lineage_finish(cl, istart_, iend_, payload, reason_out):
        """Stamp persist-complete on a hit's lineage and write its doc
        beside the npz pair (ISSUE 18).  A dead-lettered persist has no
        artifact to sit beside — the candidate span still ends so the
        trace never shows an unterminated bar."""
        if cl is None:
            return
        if payload is not None and reason_out is None:
            try:
                lineage.persisted(
                    cl, writer=lambda doc, a=istart_, b=iend_:
                    store.save_lineage(root, a, b, doc))
            except OSError as exc:
                # the doc is observability riding beside the candidate:
                # a full disk here must not fail a persisted hit
                logger.warning("lineage doc for chunk %d-%d failed "
                               "(%r); candidate unaffected",
                               istart_, iend_, exc)
                cl.span.end()
        else:
            cl.span.end()

    def _persist_async(payload, istart_, iend_, ck, t_submit, pspan=None,
                       reason=None, cl=None):
        t0 = time.perf_counter()
        try:
            out = _persist_and_mark(payload, istart_, iend_, ck,
                                    reason=reason)
            _lineage_finish(cl, istart_, iend_, payload, out)
        finally:
            timer.add_async("persist", time.perf_counter() - t0)
            # the same seconds by phase: waiting for the FIFO worker
            # (not in the total, which starts with the worker), the
            # candidate save, the ledger mark
            timer.add_async("persist/queued", t0 - t_submit)
            timer.add_async("persist/save", ck.get("save_s", 0.0))
            timer.add_async("persist/mark", ck.get("mark_s", 0.0))
            if pspan is not None:
                # async completion: submitted on the main thread inside
                # the chunk, finished here on the worker — the trace
                # shows the overlap the serial budget deliberately omits
                pspan.end()

    def _submit_persist(payload, istart_, iend_, ck, **kw):
        persist_futures.append((persist_pool.submit(
            _persist_async, payload, istart_, iend_, ck,
            time.perf_counter(), **kw), ck))

    def _stamp_on_disk(ck):
        """``on_disk_lag_s`` on the chunk's own record: seconds from the
        end of its span to the return of ``mark_done`` (0 when that came
        first), and a hit's ``save_bytes``.  Main thread, as soon as
        both are known."""
        if ck["t_end"] is not None and ck["t_disk"] is not None:
            ck["rec"]["on_disk_lag_s"] = round(
                max(ck["t_disk"] - ck["t_end"], 0.0), 4)
            if "save_bytes" in ck:
                ck["rec"]["save_bytes"] = ck["save_bytes"]

    def _pop_persist():
        future, ck = persist_futures.pop(0)
        future.result()
        _stamp_on_disk(ck)

    def _drain_persist(block=False):
        # serial semantics: a persist failure that survives the retry +
        # dead-letter policy (i.e. a bug, not a disk hiccup) must fail
        # the run — the overlap only defers the raise to the next drain
        while persist_futures and (block or persist_futures[0][0].done()):
            _pop_persist()

    @contextlib.contextmanager
    def budget_chunk(istart_):
        """``timer.chunk`` plus the chunk's entry ``ck``: its record,
        when its span ended (``t_end``) and when its persist was on disk
        (``t_disk``, set by ``_persist_and_mark``) — what
        ``on_disk_lag_s`` needs."""
        with timer.chunk(istart_) as rec:
            ck = {"rec": rec, "t_end": None, "t_disk": None}
            yield ck
        ck["t_end"] = timer.last_chunk_end
        _stamp_on_disk(ck)

    def _pulse_info(arr, tsamp, istart_, t0_):
        return PulseInfo(
            allprofs=arr, start_freq=start_freq, bandwidth=bandwidth,
            nbin=arr.shape[1], nchan=arr.shape[0], date=date, t0=t0_,
            istart=istart_, pulse_freq=1.0 / (arr.shape[1] * tsamp),
            # beam provenance from the sigproc header (ISSUE 8):
            # None on single-beam files, so their persisted bytes
            # are unchanged — beam-labelled files carry it into the
            # candidate record for the cross-beam coincidence sift
            ibeam=reader.ibeam, nbeams=reader.nbeams)

    # the deepest tier in tiles lays the whole arrays of the tiers below
    # it from its own tile cleans (time_tiles.py): one clean of the chunk
    # per tiled tier, none per whole one
    laying_tier = max((k for k, t in enumerate(tile_plan or ())
                       if t.tiles > 1), default=None)

    def tier_source(chunk, k):
        """Tier ``k``'s array of a chunk searched in time tiles, made
        from the resident bytes on demand (``k`` 0 of a flat plan)."""
        factor = tiers[k]["tier"].downsample if tiers else 1
        return TiledTierArray(
            chunk.raw, plan.step, chunk.stats, mask_dev, unpack, zero_dm,
            tuple(f for f in chain_factors if f <= factor),
            tile_plan[k].tiles, tile_plan[k].halo, tier=k,
            keep=tile_plan[k].keep,
            lay=(tuple(f for f in chain_factors if f > factor)
                 if k == laying_tier else ()),
            bands=[(b.n_lo, b.n_hi) for b in tile_plan[k].bands])

    def _count_tiles(t):
        obs_metrics.counter("putpu_time_tiles_total").inc(t.tiles)
        obs_metrics.counter("putpu_tile_halo_samples_total").inc(
            t.tiles * t.halo)
        obs_metrics.counter("putpu_tier_delay_bands_total").inc(
            max(len(t.bands) - 1, 0))

    def _count_sweeps(arr, mesh_, dm_lo, dm_hi, tsamp):
        """:func:`_count_head_tiles` once for every sweep of ``arr``: a
        tile, and a delay band of a tile.  Returns the bands' records for
        ``BUDGET_JSON`` (none where the tier is not banded)."""
        bands = getattr(arr, "bands", ())
        for _ in range(time_tiles_of(arr)):
            for delays in bands or (None,):
                _count_head_tiles(fallback_state, (backend, kernel, mesh_),
                                  _sweep_shape(arr), dm_lo, dm_hi,
                                  start_freq, bandwidth, tsamp,
                                  delays=delays)
        return [{"n_lo": lo, "n_hi": hi, "coarse_s": round(sec, 4)}
                for (lo, hi), sec in zip(bands, arr.band_seconds)] \
            if bands else None

    def _search_tiers(cleaned, istart_, rec):
        """The tiered search of one cleaned chunk: downsample chain, then
        every tier through the flat path's own search call.

        Returns ``(table, top)``: the tiers' rows concatenated in tier
        order with their ``downsample`` column (``meta["certified"]``
        when every tier certified), and the tier that holds the chunk's
        best detection, or with no detection its best row: its index
        ``k``, its own ``table`` and ``plane``, its ``array`` (the only
        tier array still referenced), ``best``, ``row`` (the best row's
        index in the concatenated table), ``detection`` and ``n_above``
        (rows of all tiers above their own tier's threshold)."""
        if isinstance(cleaned, _TiledChunk):
            # nothing is made ahead: each tier's array, or its tiles,
            # comes from the resident bytes at its turn
            arrays = [None] * len(tiers)
        else:
            with with_timer("search/tier_downsample"):
                if isinstance(cleaned, np.ndarray):
                    rest = downsample_chain(cleaned, chain_factors)
                else:
                    import jax as _jax

                    rest = device_downsample(cleaned)
                    timer.count("dispatches")
                    _jax.block_until_ready(rest)
                    timer.count("readbacks")
            arrays = ([cleaned] * (len(tiers) - len(chain_factors))
                      + list(rest))
            del rest  # a tier's array goes once searched, unless on top
        tables, top, n_above, nrows = [], None, 0, 0
        rec["tiers"] = []

        def coarse_s():  # the chunk's coarse-sweep seconds so far
            return sum(rec["buckets"].get(key, 0.0) for key in
                       ("search/coarse", "search/coarse_readback"))

        for k, t in enumerate(tiers):
            tier = t["tier"]
            arr, arrays[k] = arrays[k], None
            if isinstance(cleaned, _TiledChunk) and arr is None:
                arr = tier_source(cleaned, k)
                if arr.time_tiles == 1:
                    # a whole tier above every tiled one: the untiled
                    # search of its array, laid from the bytes
                    with with_timer("search/tier_downsample"):
                        import jax as _jax

                        arr = arr.tile(0)
                        timer.count("dispatches")
                        _jax.block_until_ready(arr)
                        timer.count("readbacks")
            if time_tiles_of(arr) > 1:
                _count_tiles(tile_plan[k])
            coarse0 = coarse_s()
            with trace_span("search/tier", tier=k,
                            downsample=tier.downsample,
                            trials=len(tier.trial_dms),
                            certified=False) as tspan:
                result = _search_with_fallback(
                    arr, tier.dm_lo, tier.dm_hi, start_freq, bandwidth,
                    tier.sample_time, backend=backend, kernel=kernel,
                    capture_plane=capture, state=fallback_state,
                    snr_floor=t["search_snr_floor"], chunk=istart_,
                    policy=dispatch_policy, trial_dms=tier.trial_dms,
                    windows=tier.windows if flat_windows else None)
                ttable, tplane = result if capture else (result, None)
                certified = bool(ttable.meta.get("certified"))
                tspan.attrs["certified"] = certified
            obs_metrics.counter("putpu_tier_sweeps_total").inc()
            if getattr(arr, "lay", ()):
                # the tiers below, whole, as this tier's tile cleans laid
                # them: each is searched at its turn and goes like any
                arrays[k + 1:] = arr.laid()
            if certified:
                obs_metrics.counter("putpu_tier_certified_total").inc()
            nwindows = _count_windows(tier.windows, arr.shape[1])
            bands = _count_sweeps(arr, None, tier.dm_lo, tier.dm_hi,
                                  tier.sample_time)
            rec["tiers"].append({
                "downsample": tier.downsample, "trials": ttable.nrows,
                "coarse_s": round(coarse_s() - coarse0, 4),
                "certified": certified, "windows": nwindows,
                **({"tiles": tile_plan[k].tiles} if tile_plan else {}),
                **({"bands": bands} if bands else {})})
            snr = np.asarray(ttable["snr"], dtype=np.float64)
            n_above += int(np.count_nonzero(snr > t["snr_threshold"]))
            best = ttable.best_row()
            detection = bool(best["snr"] > t["snr_threshold"])
            # a detection outranks any row that is none; ties go to the
            # earlier tier
            if top is None or ((detection, float(best["snr"]))
                               > (top["detection"],
                                  float(top["best"]["snr"]))):
                top = {"k": k, "table": ttable, "plane": tplane,
                       "array": arr, "best": best, "detection": detection,
                       "row": nrows + ttable.argbest()}
            nrows += ttable.nrows
            tables.append(ttable)
        names = [n for n in tables[0].colnames
                 if all(n in tt.colnames for tt in tables)]
        cols = {n: np.concatenate([np.asarray(tt[n]) for tt in tables])
                for n in names}
        cols["downsample"] = np.concatenate(
            [np.full(tt.nrows, t["tier"].downsample, dtype=np.int32)
             for tt, t in zip(tables, tiers)])
        meta = dict(tables[0].meta)
        meta["certified"] = all(r["certified"] for r in rec["tiers"])
        top["best"] = dict(top["best"],
                           downsample=tiers[top["k"]]["tier"].downsample)
        top["n_above"] = n_above
        if top["detection"]:
            # the hit's boxcar, in samples of the file
            rec["tiers"][top["k"]]["best_window"] = int(
                top["best"]["rebin"]) * top["best"]["downsample"] \
                * plan.resample
        return ResultTable(cols, meta=meta), top

    reader_pool = ThreadPoolExecutor(max_workers=1)
    next_read = submit_read(todo[0]) if todo else None
    call_phase.close()  # call/setup ends where the first chunk starts
    try:
        for ichunk, istart in enumerate(todo):
          if cancel_cb is not None and cancel_cb():
              # graceful drain (fleet workers, service cancel): nothing
              # further starts; completed chunks are already persisted +
              # marked, the rest stay un-marked for the next session
              logger.info("search cancelled before chunk %d: %d of %d "
                          "chunks left for a resumed session", istart,
                          len(todo) - ichunk, len(todo))
              break
          with budget_chunk(istart) as ck:
            t_chunk = time.perf_counter()
            chunk_size = min(plan.step, nsamples - istart)
            iend = istart + chunk_size
            t0 = istart * sample_time

            with with_timer("read"):
                # array_dev: the device buffer the reader started, if any
                array, gate_info, array_dev = next_read.result()
            next_read = (submit_read(todo[ichunk + 1])
                         if ichunk + 1 < len(todo) else None)

            # -- failure containment: quarantine, never poison/crash --
            # an unreadable, truncated or unrecoverably corrupt chunk is
            # recorded (manifest + done-with-reason in the ledger, so
            # resume never retries it) and the stream moves on
            quarantine_reason = q_stats = None
            if isinstance(array, _ReadFailure):
                quarantine_reason = fault_reasons.READ_ERROR
                q_stats = {"error": repr(array.exc)}
            else:
                got = array.shape[0] if packed_bits else array.shape[1]
                if got < chunk_size:
                    quarantine_reason = fault_reasons.SHORT_READ
                    q_stats = {"expected": int(chunk_size),
                               "got": int(got)}
                elif gate_info is not None:
                    if gate_info["verdict"] == "quarantine":
                        quarantine_reason = \
                            fault_reasons.INTEGRITY_PREFIX + ",".join(
                                gate_info["reasons"])
                        q_stats = gate_info["stats"]
                    elif gate_info["verdict"] == "sanitized":
                        obs_metrics.counter(
                            "putpu_chunks_sanitized_total").inc()
                        logger.warning(
                            "chunk %d-%d sanitized (non-finite values "
                            "imputed): %s", istart, iend,
                            gate_info["stats"])
            if quarantine_reason is not None:
                obs_metrics.counter(
                    "putpu_chunks_quarantined_total").inc()
                logger.error("chunk %d-%d QUARANTINED (%s): %s -> %s",
                             istart, iend, quarantine_reason, q_stats,
                             manifest.path)
                manifest.record(istart, iend, quarantine_reason, q_stats)
                if persist_pool is not None:
                    _submit_persist(None, istart, iend, ck,
                                    reason=quarantine_reason)
                else:
                    with with_timer("persist"):
                        _persist_and_mark(None, istart, iend, ck,
                                          reason=quarantine_reason)
                nproc += 1
                if canary is not None:
                    # the chunk never reaches the search: its pending
                    # injection must not count as a recall miss
                    canary.discard(istart)
                _health_update(istart,
                               wall_s=time.perf_counter() - t_chunk,
                               quarantined=True)
                continue

            src = None
            if device_clean is not None:
                if packed_bits:
                    # the acceptance metric of the packed path: chunks
                    # served from raw bytes, and the link bytes the
                    # float32 upload would have cost on top
                    obs_metrics.counter(
                        "putpu_lowbit_packed_chunks_total").inc()
                    obs_metrics.counter(
                        "putpu_lowbit_bytes_saved_total").inc(
                        int(header["nchans"] * array.shape[0] * 4
                            - array.nbytes))
                with with_timer("upload_wait"):
                    try:
                        if array_dev is None:
                            src = upload(array)
                        else:
                            # no second name keeps the raw bytes alive
                            # past the clean that consumes them
                            src, array_dev = array_dev, None
                            timer.count("prefetch_uploads")
                            if src.is_ready():
                                # hidden whole under the chunk before
                                timer.count("uploads_ready")
                        # force the async host->device transfer HERE so
                        # link time has its own bucket, which holds what
                        # is left of it: un-forced, the wait surfaces
                        # inside whatever device op blocks next (the
                        # round-5 rehearsal's "search" stage silently
                        # absorbed the next chunk's upload)
                        np.asarray(src[:1, :1])
                        timer.count("readbacks")
                    except Exception as exc:
                        device_clean = _clean_on_host("upload", exc)
            with with_timer("clean"):
                if tile_plan and device_clean is not None:
                    # the chunk does not fit cleaned: its bytes stay on
                    # the device, the chunk-wide moments are taken from
                    # them here, and every tile is cleaned from both where
                    # it is swept.  Nothing falls back to the host: what
                    # does not fit the device does not fit a float64 copy
                    with trace_span("clean/chunk_stats"):
                        stats = chunk_stats(src, mask_dev)
                        # the bytes every tile is cut from: the chunk
                        # with its start once more at its end
                        src = wrap_rows_program()(src)
                        timer.count("dispatches", 2)
                        jax.block_until_ready((stats, src))
                        timer.count("readbacks")
                    array = _TiledChunk(src, stats)
                elif device_clean is not None:
                    try:
                        cleaned = device_clean(src, mask_dev)
                        timer.count("dispatches")
                        # wait here: dispatch is async, so a device
                        # failure would otherwise surface as a poisoned
                        # array later, past both fallbacks.  ``array``
                        # still holds the raw host chunk until the wait
                        # succeeds, so the host fallback below never
                        # touches a poisoned device array.
                        cleaned.block_until_ready()
                        # a host wait on the device is one trip of the
                        # budget's trips x RTT floor, like a readback
                        timer.count("readbacks")
                        array = cleaned
                    except Exception as exc:
                        if compile_phase.failed_phase(exc):
                            raise  # the clean program cannot be built
                        device_clean = _clean_on_host("clean", exc)
                if device_clean is None:
                    if tile_plan:
                        raise RuntimeError(
                            "the upload of a chunk searched in time tiles "
                            "failed, and it has no host path")
                    host_raw = np.asarray(array)
                    if packed_bits and host_raw.dtype == np.uint8:
                        # fallback decode of a packed chunk (C++/numpy
                        # host unpacker; same result as the device jit)
                        host_raw = reader.unpack_frames(
                            host_raw, band_ascending=True)
                    array = _clean(host_raw, mask)

            if lineage is not None:
                lineage.mark(istart, "dispatch")
            try:
                with with_timer("search"):
                    if tiers:
                        table, top = _search_tiers(array, istart,
                                                   ck["rec"])
                        # from here on the chunk is its top tier's: the
                        # hit's record is built from that tier's array
                        array = top["array"]
                        hit_tsamp = tiers[top["k"]]["tier"].sample_time
                        result = ((table, top["plane"]) if capture
                                  else table)
                    else:
                        top, hit_tsamp = None, eff_tsamp
                        if isinstance(array, _TiledChunk):
                            array = tier_source(array, 0)
                            ck["rec"]["tiles"] = array.time_tiles
                            _count_tiles(tile_plan[0])
                        result = _search_with_fallback(
                            array, dmmin, dmmax, start_freq, bandwidth,
                            eff_tsamp, backend=backend, kernel=kernel,
                            capture_plane=capture, state=fallback_state,
                            mesh=mesh, snr_floor=search_snr_floor,
                            chunk=istart, policy=dispatch_policy,
                            windows=flat_windows)
                        _count_windows(flat_windows, array.shape[1])
                        bands = _count_sweeps(array, mesh, dmmin, dmmax,
                                              eff_tsamp)
                        if bands:
                            ck["rec"]["bands"] = bands
            except _resilience_ladder.OOMFloorError as exc:
                # the degradation ladder's floor itself OOMed: this
                # chunk cannot be searched on this host at ANY geometry
                # — quarantine it (manifest + done-with-reason, exact
                # resume) and keep the survey alive (ISSUE 12)
                obs_metrics.counter("putpu_oom_floor_total").inc()
                obs_metrics.counter(
                    "putpu_chunks_quarantined_total").inc()
                logger.error("chunk %d-%d QUARANTINED (oom_floor): %r "
                             "-> %s", istart, iend, exc, manifest.path)
                manifest.record(istart, iend, fault_reasons.OOM_FLOOR,
                                {"error": repr(exc)})
                if persist_pool is not None:
                    _submit_persist(None, istart, iend, ck,
                                    reason=fault_reasons.OOM_FLOOR)
                else:
                    with with_timer("persist"):
                        _persist_and_mark(None, istart, iend, ck,
                                          reason=fault_reasons.OOM_FLOOR)
                nproc += 1
                if canary is not None:
                    canary.discard(istart)
                if lineage is not None:
                    lineage.discard(istart)
                _health_update(istart,
                               wall_s=time.perf_counter() - t_chunk,
                               quarantined=True, oom_floor=True)
                continue
            table, plane = result if capture else (result, None)
            info = _pulse_info(array, hit_tsamp, istart, t0)
            if lineage is not None:
                # device ready/readback: the search result is host-
                # visible from here on
                lineage.mark(istart, "ready")
            if plane_consumer is not None and plane is not None:
                # the periodicity accumulation seam: the consumer sees
                # the plane (device array or ShardedPlane handle)
                # before the sift/persist machinery drops it, and
                # before mark_done — so the consumer's own durable
                # state can never be AHEAD of the ledger in the
                # direction that loses data
                with with_timer("plane_consume"):
                    plane_consumer(istart, plane, table)
            if reader.ibeam is not None:
                # chunk metadata rides the in-process table (meta is not
                # persisted; the PulseInfo fields are the durable copy)
                table.meta["ibeam"] = reader.ibeam
                table.meta["nbeams"] = reader.nbeams

            canary_obs = (canary.observe(istart, table, snr_threshold)
                          if canary is not None else None)
            ncand_above = None
            if health is not None:
                # candidate RATE (table rows above threshold), not the
                # 0/1 hit decision: the engine's RFI-storm detector
                # needs the many-DM-trials-at-once signature
                ncand_above = (top["n_above"] if top else int(
                    np.count_nonzero(
                        np.asarray(table["snr"], dtype=np.float64)
                        > float(snr_threshold))))
                if canary_obs is not None:
                    # rows the injection lit must not feed the storm
                    # detector: an injected chunk's canary sidelobes
                    # would inflate the candidate-rate baseline
                    ncand_above = max(
                        ncand_above - canary_obs["n_above_near"], 0)

            best = table.best_row()
            is_hit = bool(best["snr"] > snr_threshold)
            # sci_table is what downstream consumers see (persist, sift,
            # cutout window, plots); best_plane_idx indexes the DM-trial
            # plane for the dedispersed profile.  Both shift only when a
            # canary tops the chunk and a genuine weaker pulse is
            # promoted in its place.
            sci_table = table
            best_plane_idx = None
            plot_table = table
            if top:
                # a row is a detection against its own tier's threshold
                # and the hit is the best such row; the plane and the
                # figure's table are its tier's own
                best, is_hit = top["best"], top["detection"]
                best_plane_idx = top["table"].argbest()
                plot_table = top["table"]
                if is_hit and table.argbest() != top["row"]:
                    # rows of other tiers that score higher without
                    # reaching their own tier's threshold: consumers take
                    # a table's best row for the hit (sift, cutout), so
                    # they leave the persisted table
                    keep = np.asarray(table["snr"]) < best["snr"]
                    keep[top["row"]] = True
                    sci_table = ResultTable(
                        {name: table[name][keep]
                         for name in table.colnames}, meta=table.meta)
                    logger.info(
                        "chunk %d-%d: %d row(s) above the hit's S/N but "
                        "under their own tier's threshold dropped from "
                        "the persisted table", istart, iend,
                        int(np.count_nonzero(~keep)))
            if is_hit and canary_obs is not None \
                    and canary_obs["best_is_canary"]:
                # the chunk's best row IS this chunk's injected canary
                # (DM *and* dedispersed-time matched): tag it — canaries
                # must never become candidates, ledger payloads, or sift
                # input.  A genuine weaker pulse in the same chunk must
                # persist exactly as the canary-off run would: promote
                # the strongest row OUTSIDE the canary track, with the
                # track's rows masked out of the persisted table so
                # sift/cutout/plots see the real detection as best
                canary.tag_hit(istart)
                sci_idx = canary_obs["science_idx"]
                sci_snr = canary_obs["science_snr"]
                if sci_idx is not None and sci_snr > float(snr_threshold):
                    keep = ~canary_obs["canary_rows"]
                    sci_table = ResultTable(
                        {name: table[name][keep]
                         for name in table.colnames}, meta=table.meta)
                    best = {name: table[name][sci_idx]
                            for name in table.colnames}
                    best_plane_idx = int(sci_idx)
                    obs_metrics.counter(
                        "putpu_canary_promoted_hits_total").inc()
                    logger.info(
                        "chunk %d-%d: canary outranked a genuine pulse "
                        "— promoted the science best row (DM=%.2f "
                        "snr=%.2f), canary rows dropped from the "
                        "persisted table", istart, iend,
                        float(best["DM"]), float(best["snr"]))
                else:
                    is_hit = False
            elif is_hit and canary_obs is not None \
                    and canary_obs["recovered"]:
                # a REAL pulse outranked this chunk's canary: the hit
                # is genuine and persists, but the per-trial table
                # saved with it still contains the canary-lit rows —
                # counted and logged so consumers of the full table
                # know synthetic rows ride along (the candidate's own
                # best row is real; see docs/observability.md)
                obs_metrics.counter(
                    "putpu_canary_contaminated_tables_total").inc()
                logger.info(
                    "chunk %d-%d: real hit persisted alongside a "
                    "recovered canary — trial rows near DM %.1f in "
                    "the persisted table include synthetic signal",
                    istart, iend, canary.dm)
            if getattr(table, "meta", {}).get("certified"):
                # hybrid noise certificate: the chunk holds no detection
                # above snr_threshold (up to the certificate's stated
                # miss risk, table.meta["cert_miss_p_at_floor"] — so
                # is_hit is False by construction) and no exact
                # rescoring was paid
                if ncertified == 0:
                    # state the operating assumption once, where
                    # certification is consumed: the certificate is
                    # probabilistic, and the at-floor miss risk is a
                    # tunable (cert_slack / cert_slack_for_miss_p), not
                    # fine print (ADVICE r4)
                    logger.info(
                        "noise certificate active: certified chunks skip "
                        "exact rescoring; worst-case at-floor miss "
                        "probability %.3g (tune via cert_slack, see "
                        "ops.certify.cert_slack_for_miss_p)",
                        table.meta.get("cert_miss_p_at_floor", float("nan")))
                ncertified += 1
                obs_metrics.counter("putpu_certified_chunks_total").inc()

            if period_search and plane is not None \
                    and canary_obs is not None:
                # the folded plane carries the injected canary's track:
                # a synthetic single pulse must neither resurrect a
                # tagged canary as a periodicity "hit" (is_hit was set
                # False above; best still points at the canary row) nor
                # decorate a real one with its DM — injected chunks
                # skip the period stage (the injection rate bounds the
                # loss; canary-off runs are untouched)
                obs_metrics.counter(
                    "putpu_canary_period_skips_total").inc()
                logger.debug("chunk %d-%d: period search skipped on a "
                             "canary-injected chunk", istart, iend)
            elif period_search and plane is not None:
                from ..ops.periodicity import period_search_plane

                # key off the EFFECTIVE backend: a device failure flips
                # _search_with_fallback to numpy permanently, and the
                # period stage must follow it off the dead device
                if fallback_state.get("backend", backend) == "jax":
                    import jax.numpy as _xp
                else:
                    _xp = np
                with with_timer("period"):
                    pres = period_search_plane(
                        plane, eff_tsamp,
                        fmin=4.0 / (plane.shape[1] * eff_tsamp),
                        refine_top=1, xp=_xp)
                if pres["best_sigma"] > period_sigma_threshold:
                    info.period_freq = float(pres["best_freq"])
                    info.period_dm = float(
                        table["DM"][pres["best_dm_index"]])
                    info.period_sigma = float(pres["best_sigma"])
                    info.period_H = float(pres["best_h"])
                    info.period_M = int(pres["best_m"])
                    if pres["best_profile"] is not None:
                        info.fold_profile = np.asarray(pres["best_profile"])
                    is_hit = True
                    logger.info("PERIODIC chunk %d-%d: f=%.4f Hz DM=%.2f "
                                "sigma=%.1f", istart, iend,
                                info.period_freq, info.period_dm,
                                info.period_sigma)

            cl = None
            if is_hit:
                info.dm = float(best["DM"])
                info.snr = float(best["snr"])
                info.width = float(best["rebin"]) * hit_tsamp
                # the boxcar the hit was matched at, in samples of the file
                ck["rec"]["best_window_samples"] = int(round(
                    info.width / sample_time))
                with with_timer("hit_products"):
                    # readback counters only for DEVICE sources: after a
                    # fallback to the numpy backend these are host
                    # arrays and counting them would inflate the
                    # trips x RTT floor the budget exists to make honest
                    n_rb = (not isinstance(array, np.ndarray)) \
                        + (plane is not None
                           and not isinstance(plane, np.ndarray))
                    info.disp_profile = np.asarray(array.mean(0))
                    if plane is not None:
                        info.dedisp_profile = np.asarray(
                            plane[best_plane_idx
                                  if best_plane_idx is not None
                                  else table.argbest()])
                    n_rb += not isinstance(info.allprofs, np.ndarray)
                    if make_plots:
                        # the diagnostic figure needs the full waterfall:
                        # convert device arrays to host now (retained in
                        # the hits list — an un-pulled hit would pin the
                        # whole chunk's HBM until the search ends)
                        if not isinstance(info.allprofs, np.ndarray):
                            info.allprofs = np.asarray(info.allprofs)
                            obs_metrics.counter(
                                "putpu_bytes_readback_total").inc(
                                int(info.allprofs.nbytes))
                    else:
                        # the record's cut-out, on the host: the store
                        # cuts the pulse's window where the chunk lies and
                        # sums a window over its budget (hundreds of MB at
                        # thousands of channels) there too, so the record
                        # alone crosses the link; it counts those bytes
                        info = store.trim_waterfall(info, sci_table)
                    if n_rb:
                        timer.count("readbacks", int(n_rb))
                info.compute_stats()
                hits.append((istart, iend, info, sci_table))
                obs_metrics.counter("putpu_hits_total").inc()
                logger.info("HIT chunk %d-%d: DM=%.2f snr=%.2f width=%gs",
                            istart, iend, info.dm, info.snr, info.width)
                if lineage is not None:
                    # sift verdict: freeze the chunk's stage marks into
                    # this candidate's lineage doc + open its span
                    cl = lineage.candidate(
                        istart, iend, name=f"{root}_{istart}-{iend}",
                        dm=info.dm, snr=info.snr, width=info.width)
                if push is not None:
                    # fan-out at the hit-append site: canary best rows
                    # were tagged/promoted above, so the broker only
                    # ever sees genuine science candidates.  Enqueue-
                    # only — a wedged subscriber cannot touch the loop.
                    push.publish(
                        {"schema_version": 1, "kind": "candidate",
                         "fname": os.path.basename(str(fname)),
                         "root": root, "chunk": int(istart),
                         "iend": int(iend), "t_start_s": float(t0),
                         "dm": info.dm, "snr": info.snr,
                         "width_s": info.width,
                         "fingerprint": fingerprint},
                        on_delivered=(
                            None if cl is None else
                            lambda sub, _lat, _cl=cl:
                            lineage.delivered(_cl, sub)))

            if make_plots == "all" or (make_plots == "hits" and is_hit):
                from .diagnostics import plot_diagnostics

                # the figure gets the FULL table: its plane panel is
                # labeled by the table's DM trials row-for-row, so the
                # canary-masked sci_table cannot back it (a promoted
                # chunk's figure therefore renders the canary track —
                # diagnostics, not a candidate artifact)
                with with_timer("plot"):
                    plot_diagnostics(
                        info, plot_table, plane,
                        outname=os.path.join(output_dir,
                                             f"{root}_{istart}-{iend}.jpg"),
                        t0=t0, show=show_plots)

            # candidate persist + ledger write: overlapped with the NEXT
            # chunk's device work (FIFO worker), or inline when
            # overlap_persist=False — identical order and bytes either
            # way.  Submitted AFTER the plot so mark_done cannot precede
            # the chunk's diagnostic figure: a crash mid-plot leaves the
            # chunk un-marked and the resumed run re-renders it, exactly
            # like the serial loop (code-review r6)
            payload = (info, sci_table) if is_hit else None
            if persist_pool is not None:
                # putpu-lint: disable=span-leak — ends in _persist_async on the FIFO persist worker (cross-thread by design; the drain barrier guarantees completion)
                pspan = begin_span("persist", track="persist-worker",
                                   chunk=istart)
                _submit_persist(payload, istart, iend, ck, pspan=pspan,
                                cl=cl)
                # backpressure: each queued payload retains its cutout +
                # table on the host, so an unbounded backlog on a
                # hit-dense stream would grow without limit (the serial
                # loop had natural backpressure); two in flight keeps
                # the overlap win while bounding retained memory
                while len(persist_futures) > 2:
                    with with_timer("persist_backpressure"):
                        _pop_persist()
            else:
                with with_timer("persist"):
                    reason_out = _persist_and_mark(payload, istart, iend,
                                                   ck)
                    _lineage_finish(cl, istart, iend, payload,
                                    reason_out)
            mem_snap = None
            if fallback_state.get("backend", backend) == "jax":
                # per-chunk device-memory watermark: HBM headroom is a
                # tracked gauge, not an OOM surprise (obs.memory)
                mem_snap = obs_memory.record_watermark()
            nproc += 1
            headroom_frac = None
            if mem_snap and mem_snap.get("bytes_limit"):
                headroom_frac = ((mem_snap["bytes_limit"]
                                  - mem_snap["bytes_in_use"])
                                 / mem_snap["bytes_limit"])
            _health_update(istart, wall_s=time.perf_counter() - t_chunk,
                           candidates=ncand_above,
                           headroom_frac=headroom_frac)
            if lineage is not None:
                # any candidate froze its marks at the sift verdict;
                # dropping them here bounds the recorder's memory
                lineage.discard(istart)
            if progress and nproc % 50 == 0:
                logger.info("processed %d chunks (through sample %d/%d)",
                            nproc, iend, nsamples)
            # the chunk's resident bytes go with its iteration, not when
            # the next chunk's search rebinds these names: a device short
            # of room (a chunk searched in tiles) has none for both, and a
            # hit keeps its trimmed record, nothing else refers to them
            array = src = info = top = None
          _drain_persist()
    except BaseException:
        reader_pool.shutdown(wait=False, cancel_futures=True)
        if persist_pool is not None:
            persist_pool.shutdown(wait=False, cancel_futures=True)
        if obs_server is not None:
            obs_server.close()
        raise
    reader_pool.shutdown(wait=True)
    if persist_pool is not None:
        # the tail of the persist queue is the only persist time left on
        # the critical path — everything else overlapped chunk k+1
        with timer.bucket("persist_drain"):
            persist_pool.shutdown(wait=True)
            _drain_persist(block=True)
    call_phase.enter_context(with_timer("call/finish"))
    if push is not None and push_owned:
        # bounded drain: a wedged subscriber journals to the dead
        # letter and cannot stall the driver's exit.  PUSH_JSON is the
        # one-line machine-readable delivery ledger, BUDGET_JSON-style.
        logger.info("PUSH_JSON %s", json.dumps(push.close()))
    if health is not None and nproc:
        # tail flush: a persist dead-letter from the final drain (the
        # last chunk's write overlaps nothing) would otherwise never
        # reach the engine — one post-drain update folds it in
        _health_update("drain", wall_s=None)
    timer.report()
    timer.footer()
    logger.info("BUDGET_JSON %s", json.dumps(timer.to_json()))
    if canary is not None:
        # one-line machine-readable canary ledger, BUDGET_JSON-style
        logger.info("CANARY_JSON %s", json.dumps(canary.to_json()))
    if health is not None:
        logger.info("health verdict at end of run: %s%s", health.verdict,
                    " (" + ", ".join(health.reasons()) + ")"
                    if health.reasons() else "")
    logger.info("done: %d chunks processed, %d hits, %d noise-certified",
                nproc, len(hits), ncertified)
    if resume:
        # a resumed run must report the COMPLETE result, not just this
        # session's chunks: candidates persisted by interrupted runs are
        # restored from the store so downstream sifting/reporting sees
        # every detection (round-5 survey rehearsal: the injected pulse
        # was found before the interrupt and then absent from the
        # resumed run's report)
        with with_timer("call/restore"):
            seen = {(h[0], h[1]) for h in hits}
            restored = 0
            for cand_root, lo, hi in store.candidates():
                # only chunks this fingerprint's ledger marks done: the
                # store directory may hold same-named candidates persisted
                # by other configurations
                if (cand_root != root or (lo, hi) in seen
                        or not store.is_done(lo)):
                    continue
                try:
                    info, table = store.load_candidate(root, lo, hi)
                # the actual load failure modes of a partial/corrupt npz
                # pair (missing file, truncated zip, bad json, a stored
                # member whose CRC no longer matches, the bit-rotted deflate
                # stream of an older record) — anything else is a bug and
                # must propagate, and every skip is counted so silent
                # skips show in the metrics snapshot (ISSUE 4 satellite)
                except (OSError, ValueError, KeyError, EOFError,
                        zipfile.BadZipFile, zlib.error) as exc:
                    obs_metrics.counter(
                        "putpu_resume_pairs_skipped_total").inc()
                    logger.warning("could not restore candidate %s_%d-%d: %r",
                                   root, lo, hi, exc)
                    continue
                hits.append((lo, hi, info, table))
                restored += 1
            if restored:
                hits.sort(key=lambda h: h[0])
                logger.info("restored %d persisted candidate(s) from the "
                            "resume ledger", restored)
        # end-of-run integrity audit: ledger vs candidate files vs
        # quarantine manifest (read-only; inconsistencies are logged
        # and counted, never fatal — observability must not take down
        # a survey run)
        from ..faults.audit import audit_run

        with with_timer("call/audit"):
            try:
                report = audit_run(output_dir, fingerprint, root=root)
            except Exception as exc:  # never fatal — by contract
                logger.warning("integrity audit failed (%r); run result is "
                               "unaffected", exc)
            else:
                if report["issues"]:
                    logger.warning("integrity audit: %d inconsistencies: %s",
                                   len(report["issues"]), report["issues"])
                else:
                    logger.info("integrity audit: ok %s", report["checked"])
    if report_out:
        from ..obs import report as obs_report

        with with_timer("call/report"):
            try:  # never fatal — observability must not take down a run
                md_path, html_path = obs_report.write_report(
                    str(report_out),
                    meta={"root": root,
                          "fname": os.path.abspath(str(fname)),
                          "fingerprint": fingerprint,
                          "chunks_processed": nproc, "hits": len(hits),
                          "certified": ncertified, "backend": backend,
                          "kernel": kernel,
                          "snr_threshold": snr_threshold},
                    budget=timer.to_json(max_per_chunk=0),
                    health=health.snapshot() if health is not None else None,
                    canary=canary.to_json() if canary is not None else None,
                    quarantine=manifest.records(),
                    metrics=obs_metrics.REGISTRY.snapshot(),
                    lineage=(lineage.summary()
                             if lineage is not None else None),
                    push=push.stats() if push is not None else None)
            except Exception as exc:
                logger.warning("survey report failed (%r); run result is "
                               "unaffected", exc)
            else:
                logger.info("survey report -> %s + %s", md_path, html_path)
    if obs_server is not None:
        obs_server.close()
    call_phase.close()
    return hits, store
