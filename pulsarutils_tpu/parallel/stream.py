"""Streaming long-series search: overlap-save chunking and the
time-sharded ring dedispersion step.

The reference's "long-context" mechanism is a host-side 50%-overlap chunk
loop sized by the physics — chunk length = band-crossing delay at ``dmmax``,
hop = half the chunk (reference ``pulsarutils/clean.py:296-301,318``) — so
every pulse is fully contained, un-wrapped, in at least one chunk.  This
module keeps that overlap-save logic but makes it device-resident:

* :func:`plan_chunks` — the physics-driven chunk/hop/resample sizing rule;
* :func:`stream_search` — jit-once, stream-many driver: every chunk reuses
  one compiled search executable; JAX's async dispatch overlaps the
  host->device copy of chunk ``k+1`` with the compute of chunk ``k``
  (double buffering for free);
* :func:`ring_dedisperse` — the sequence-parallel analogue: the time axis
  is sharded over a ``"time"`` mesh axis and each device pulls a halo of
  ``max_offset`` samples from its right neighbour with ONE
  ``lax.ppermute`` per step, reproducing the exact global circular-shift
  semantics of :func:`~pulsarutils_tpu.ops.dedisperse.dedisperse` on a
  sequence no single device could hold.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ..ops.plan import delta_delay, dm_broadening
from ..ops.search import dedispersion_search
from ..tuning.geometry import PLAN_CACHE_SIZE, counted_plan_cache
from ..utils.frame_reserve import reserve_frames
from ..utils.logging_utils import budget_bucket


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """Physics-driven streaming geometry (reference ``clean.py:296-316``)."""
    step: int            # samples per chunk
    hop: int             # chunk advance (step // 2 -> 50% overlap)
    resample: int        # time-rebin factor applied to each chunk
    sample_time: float   # post-resample sample time


def plan_chunks(nsamples, sample_time, dmmin, dmmax, start_freq, stop_freq,
                foff, chunk_length=None, new_sample_time=None, min_step=128,
                tile_factor=1):
    """Choose chunk size / hop / resampling from the search physics.

    * chunk length defaults to the band-crossing delay at ``dmmax`` and the
      chunk holds twice that, so a pulse entering at any phase of the hop
      is fully contained once (reference ``clean.py:296-301``);
    * data are resampled so the new sample time is ~1/10 of the minimum
      intra-channel DM smearing (reference ``clean.py:304-316``);
    * ``tile_factor`` is the largest further downsampling a tiered search
      (:func:`~pulsarutils_tpu.ops.plan.dm_tier_plan`) applies after the
      resampling: the chunk is rounded so that axis stays tile-divisible
      too.
    """
    if chunk_length is None:
        chunk_length = delta_delay(dmmax, start_freq, stop_freq)
    step = max(int(chunk_length / sample_time) * 2, min_step)

    dm_dt = dm_broadening(dmmin, start_freq, abs(foff))
    if new_sample_time is None:
        new_sample_time = max(dm_dt / 10, sample_time)
    ratio = new_sample_time / sample_time
    resample = int(np.rint(ratio)) if ratio >= 2 else 1

    if step >= 1024 * resample * tile_factor:
        # round the chunk up so the POST-RESAMPLE time axis is a
        # multiple of the FDMT/Pallas tile size: a non-tile-divisible
        # searched axis forces the TPU transform to zero-pad (slower,
        # and it disables the hybrid's noise certificate — the pad
        # breaks the circular-gather model its soundness bound
        # assumes).  A slightly larger chunk keeps the physics
        # guarantee (chunk >= 2x the band-crossing delay).
        quantum = 1024 * resample * tile_factor
        step = -(-step // quantum) * quantum
    return ChunkPlan(step=step, hop=step // 2, resample=resample,
                     sample_time=resample * sample_time)


@dataclasses.dataclass(frozen=True)
class DelayBand:
    """One delay band of a tier swept in bands (:func:`plan_time_tiles`)."""
    n_lo: int       # its first band delay, in the tier's own samples
    n_hi: int       # its last (inclusive)
    bytes: int      # device bytes reckoned while one of its sweeps runs


@dataclasses.dataclass(frozen=True)
class TierTiles:
    """One tier's share of a tile plan (:func:`plan_time_tiles`)."""
    tiles: int      # time tiles its axis is swept in (1: whole, as ever)
    own: int        # its own samples a tile answers for
    halo: int       # samples of the next tile swept again (0 when whole)
    bytes: int      # device bytes reckoned while one of its tiles is swept
    keep: int = 0   # cleaned tiles an exact rescore may hold across rounds
    bands: tuple = ()   # its DelayBands, in order, where one sweep of a
    #                     tile over all its band delays does not fit (else
    #                     empty: every configuration before CHIME's)


def sweep_state_rows(nchan, start_freq, bandwidth, n_hi, n_lo):
    """Rows of the two largest consecutive states of the FDMT's merge
    schedule for band delays ``n_lo..n_hi``: what one sweep holds at once
    beside its input, per sample of its time axis.  An upper bound on
    the chip, where the fused head keeps the first levels in VMEM (the
    v5e compiler: 5.51 GiB of temporaries for MeerTRAP's tier 0 on a tile
    of 139,264 samples against 6.97 GiB reckoned here, PR 40)."""
    from ..ops.fdmt import fdmt_plan

    plan = fdmt_plan(int(nchan), float(start_freq), float(bandwidth),
                     int(n_hi), int(n_lo))
    rows = [plan.nchan_padded] + [int(sum(it["ndelay"]))
                                  for it in plan.iterations]
    return max(a + b for a, b in zip(rows, rows[1:]))


def plan_time_tiles(nchan, nsamples, start_freq, bandwidth, tiers,
                    budget_bytes, resident_bytes=0):
    """How many time tiles, and how many delay bands, each tier of a chunk
    is swept in, from the device's memory.

    ``tiers`` is one ``(downsample, sample_time, trial_dms, windows)`` per
    tier (a flat plan is one tier at ``downsample`` 1) over a chunk of
    ``nsamples`` samples of the plan; ``resident_bytes`` is what the chunk
    loop holds beside a sweep (the packed chunk, the frames a chunk
    searched in tiles carries twice, and the next one's prefetch: its
    caller's to state).  A tier's sweep is reckoned at its input array plus
    :func:`sweep_state_rows` rows of float32 over its time axis, beside
    the whole arrays of the tiers below it that exist by then: an upper
    bound, not a footprint (the planner's job is a plan that cannot run
    out of memory).  A tier that does not fit ``budget_bytes`` whole is
    halved until a tile plus its halo does.  The halo holds the tier's
    longest track (the FDMT's highest band delay and the exact kernels'
    rebased offsets) and keeps a tile's axis divisible by the kernels'
    time tiles.  A tile cannot be shorter than its halo or than the
    ladder's widest window.

    A tier whose smallest tile still does not fit (CHIME's 16,384 channels:
    a halo of 24,576 samples beside 70,000 rows of state) is swept in
    **delay bands**: its band delays halved into contiguous runs of equal
    count, in order, until every band's sweep fits on one tile plan of the
    tier (:class:`DelayBand`).  The bands share the tier's tiles: a tile
    is cleaned once and every band is swept on it, halo and all (a band
    of low delays that read less of the halo would read a copy, and the
    copy does not fit beside the tile).  A banded tier is swept in two
    tiles at least: the sweep that did not fit was a tile's.  A tier that
    no number of bands fits raises ``ValueError`` naming the tier, the
    band and the bytes, at plan time.

    The tiers are planned from the deepest up.  The deepest tier that is
    tiled lays the whole arrays of the tiers below it from its own tile
    cleans (``pipeline/time_tiles.py``), so it is reckoned beside all of
    them; a tiled tier above it is swept before they exist.  ``keep`` is
    how many cleaned tiles fit beside that and one more tile: what an
    exact rescore may hold across its rounds (CHIME's tiles of 3.5 GiB:
    one; with the frames the chunk carries twice left out of
    ``resident_bytes`` it was two, and the third tile's clean found 3.48
    GiB free).

    ``budget_bytes=None`` (no accelerator to ask) plans every tier whole.
    Returns a list of :class:`TierTiles`, one per tier.
    """
    from ..ops.fdmt import fdmt_trial_dms
    from ..ops.pallas_dedisperse import rebase_offsets
    from ..ops.search import _offsets_for, scored_windows

    out = [None] * len(tiers)
    below = 0       # whole arrays of deeper tiers, resident by then
    for k in reversed(range(len(tiers))):
        downsample, sample_time, trial_dms, windows = tiers[k]
        axis = int(nsamples) // int(downsample)
        if budget_bytes is None:
            out[k] = TierTiles(1, axis, 0, 0)
            continue
        dm_lo, dm_hi = float(np.min(trial_dms)), float(np.max(trial_dms))
        _, n_lo, n_hi = fdmt_trial_dms(nchan, dm_lo, dm_hi, start_freq,
                                       bandwidth, sample_time)
        # the exact kernels' span is the highest trial's, rebased
        _, _, max_off = rebase_offsets(_offsets_for(
            [dm_lo, dm_hi], nchan, start_freq, bandwidth, sample_time, axis),
            axis)
        widest = scored_windows(windows, axis)[-1]
        held = int(resident_bytes) + below
        # the tier's legal tile plans, the whole axis first
        shapes, tiles = [], 1
        while True:
            own, halo = axis // tiles, 0
            if tiles > 1:
                quantum = min(8192, max(128, 1 << ((own // 8).bit_length()
                                                   - 1)))
                halo = -(-max(n_hi, max_off) // quantum) * quantum
            shapes.append((tiles, own, halo))
            if (axis % (2 * tiles) or (own // 2) % widest
                    or own // 2 < max(halo, n_hi, max_off)):
                break   # no shorter tile holds its own halo
            tiles *= 2
        # one sweep a tile over all the tier's delays, else its delays
        # halved into bands until every band's sweep fits on one plan
        count = n_hi - n_lo + 1
        nbands = 1
        while True:
            edges = [n_lo + count * b // nbands for b in range(nbands + 1)]
            spans = [(lo, hi - 1) for lo, hi in zip(edges, edges[1:])]
            rows = [int(nchan) + sweep_state_rows(nchan, start_freq,
                                                  bandwidth, hi, lo)
                    for lo, hi in spans]
            # the sweep that did not fit was a tile's: a banded tier is
            # not swept whole
            fit = next((shape for shape in shapes
                        if (shape[0] > 1 or nbands == 1)
                        and held + max(rows) * (shape[1] + shape[2]) * 4
                        <= budget_bytes), None)
            if fit is not None:
                break
            # what no band can go under: one delay, the tier's last, on
            # the shortest tile
            _, own, halo = shapes[-1]
            least = held + (int(nchan) + sweep_state_rows(
                nchan, start_freq, bandwidth, n_hi, n_hi)) * (own + halo) * 4
            if 2 * nbands > count or least > budget_bytes:
                worst = int(np.argmax(rows))
                raise ValueError(
                    f"DM tier {k} (x{downsample}, band delays {n_lo}-{n_hi}) "
                    f"cannot be searched on this device: delay band {worst} "
                    f"of {nbands} (band delays {spans[worst][0]}-"
                    f"{spans[worst][1]}) on a time tile of {own} + {halo} "
                    f"samples needs {held + rows[worst] * (own + halo) * 4} "
                    f"bytes of {budget_bytes}, a shorter tile would not "
                    "hold its own halo, and a band of one delay needs "
                    f"{least}")
            nbands *= 2
        tiles, own, halo = fit
        needs = [held + r * (own + halo) * 4 for r in rows]
        need = max(needs)
        keep = 0
        if tiles > 1:
            tile_bytes = int(nchan) * (own + halo) * 4
            keep = int(max(0, min(tiles, (budget_bytes - held)
                                  // tile_bytes - 1)))
            below = 0   # tiers above are swept before anything is laid
        else:
            below += int(nchan) * axis * 4
        bands = () if nbands == 1 else tuple(
            DelayBand(lo, hi, n) for (lo, hi), n in zip(spans, needs))
        out[k] = TierTiles(tiles, own, halo, need, keep, bands)
    return out


def iter_chunk_starts(nsamples, plan, tmin=0, sample_time=None):
    """Chunk start indices with 50% overlap, skipping a final fragment
    shorter than half a chunk (reference ``clean.py:318-325``) — and,
    round 5, a final fragment *wholly contained* in the previous chunk
    (``istart - hop + step >= nsamples``): it re-reads data the previous
    full-length chunk already searched with MORE context (the short time
    axis only worsens circular-wrap artifacts) while costing a complete
    extra compile set for the odd shape (~minutes on the 1M-sample
    configs — measured in the round-5 survey rehearsal)."""
    prev = None
    for istart in range(0, nsamples, plan.hop):
        if sample_time is not None and istart * sample_time < tmin:
            continue
        if min(plan.step, nsamples - istart) < plan.hop:
            continue
        if (prev is not None and istart - plan.hop == prev
                and prev + plan.step >= nsamples):
            continue
        prev = istart
        yield istart


def _iter_lookahead(chunks):
    """Pull-lazy iteration with exactly ONE chunk of lookahead.

    ``stream_search`` must consume its producer as a true iterator
    (ISSUE 19: a live feed cannot hold an observation in RAM), but a
    strict lock-step pull would serialize chunk production behind the
    device search.  Pre-pulling a single item keeps the classic
    double-buffer overlap — the producer builds chunk ``k+1`` while
    chunk ``k`` computes — with bounded memory by construction: at most
    two produced-but-unconsumed chunks exist at any moment (the pending
    slot plus the producer's in-flight ``next``).  A list producer
    degrades gracefully (iteration order and results are identical).
    """
    it = iter(chunks)
    try:
        pending = next(it)
    except StopIteration:
        return
    for item in it:
        yield pending
        pending = item
    yield pending


@reserve_frames
def stream_search(chunks, dmmin, dmmax, start_freq, bandwidth, sample_time,
                  *, backend="jax", snr_threshold=6.0, trial_dms=None,
                  dm_block=None, chan_block=None, budget=None, mesh=None,
                  kernel="auto", dispatch_timeout=None, dispatch_retries=0,
                  skip_failed=False, health=None, http_port=None,
                  http_host="127.0.0.1", canary=None,
                  plane_consumer=None, lineage=None, push=None):
    """Search an iterable of ``(istart, (nchan, step))`` chunks.

    ``chunks`` is consumed as a true lazy iterator with one chunk of
    lookahead (ISSUE 19): a generator producer — a file reader or the
    live-ingest assembler — is pulled at most one chunk ahead of the
    chunk being searched, so memory stays bounded by two chunks no
    matter how long the observation runs, while production still
    overlaps compute.  Lists keep working unchanged (and still
    provide the progress total via ``len``).

    One compiled executable serves every distinct chunk shape; interior
    chunks share one shape by construction, so at most one extra compile
    happens for a ragged final chunk (which the reference also processes,
    ``clean.py:319-325``).  Returns a list of per-chunk hits:
    ``(istart, table, best_row)`` for chunks whose best S/N clears
    ``snr_threshold`` (the reference's candidate criterion,
    ``clean.py:349``), plus the full tables for diagnostics.

    ``mesh`` (with ``backend="jax"``) routes every chunk through the
    sharded multi-device searches, the same routing rule as the full
    pipeline driver (``kernel="hybrid"`` -> the fused
    :func:`~.sharded_fdmt.sharded_hybrid_search` — one ``shard_map``
    dispatch per typical hit chunk, round 6 — ``"fdmt"`` -> the
    DM-sliced tree, anything else -> the ``(dm, chan)`` exact sweep).
    The sharded searches re-derive the chunk-geometry plan from a
    per-geometry cache, so interior chunks share one compiled program
    AND one host-side offset table.

    ``budget`` (a
    :class:`~pulsarutils_tpu.utils.logging_utils.BudgetAccountant`)
    opens one chunk budget per chunk: the search's dispatch/readback
    buckets land per chunk — on the mesh route too, attributed by the
    sharded searches exactly as single-device — and a compile observed
    on any chunk after the first is flagged as a retrace (the
    one-executable contract above is *checked*, not assumed — round 6).

    Robustness (ISSUE 4 — defaults reproduce the pre-hardening path):
    ``dispatch_timeout`` bounds each chunk's search on a watchdog
    thread (a wedged dispatch was an infinite stall),
    ``dispatch_retries`` re-attempts a failed/timed-out chunk, and
    ``skip_failed=True`` drops a chunk that still fails (logged +
    ``putpu_stream_chunks_failed_total``) instead of killing the whole
    stream.  ValueError/TypeError always propagate, even under
    ``skip_failed`` — they are treated as configuration errors (which
    would fail identically on every chunk), so a producer feeding
    malformed per-chunk arrays must validate shapes upstream rather
    than rely on containment.

    Live surface (ISSUE 5, same contract as ``search_by_chunks``):
    ``http_port`` serves ``/metrics`` / ``/healthz`` / ``/progress``
    for the duration of the stream (``http_host`` picks the bind
    address — loopback by default, ``"0.0.0.0"`` to let a remote
    Prometheus scrape job or fleet probe reach it); ``health`` accepts
    a caller-owned
    :class:`~pulsarutils_tpu.obs.health.HealthEngine` (created
    internally when ``http_port`` is set), updated per chunk with wall
    time, candidate rate and containment events; ``canary`` (a
    :class:`~pulsarutils_tpu.obs.canary.CanaryController` or a bare
    rate float) injects synthetic pulses into selected chunks before
    the search and matches them against the emitted tables — canary
    best rows are excluded from the returned ``hits``, and when the
    canary outranks a genuine weaker pulse in the same chunk that
    pulse's row is promoted as the chunk's ``best_row`` instead.  All
    are ``None``-gated: off means the pre-PR code path,
    byte-identical.

    Packed low-bit chunks (ISSUE 11): a chunk may be a
    :class:`~pulsarutils_tpu.io.lowbit.PackedFrames` instead of a float
    block — the RAW 1/2/4-bit bytes ship to the device and the
    bit-unpack runs inside the search jit (integer sweep accumulation
    where exact), cutting host->device traffic 8-16x with candidates
    byte-identical to the host-unpacked run (``tests/test_lowbit_e2e.py``
    pins the identity and the ``putpu_bytes_uploaded_total`` ratio).  Canaries
    are quantized into the packed codes on the same seam
    (:meth:`~pulsarutils_tpu.obs.canary.CanaryController.
    maybe_inject_packed`), so recall is measured on packed runs too.

    ``plane_consumer`` (ISSUE 13, same contract as
    ``search_by_chunks``): a ``fn(istart, plane, table)`` callable
    that forces plane capture on every chunk's search and receives the
    dedispersed plane (device array, or a sharded handle on the mesh
    route) before it is dropped — the periodicity accumulation seam.
    ``None`` (default) keeps the pre-seam code path byte-identical.

    ``lineage`` / ``push`` (ISSUE 18, same contract as
    ``search_by_chunks``): lineage stamps each hit with monotone stage
    timestamps and feeds the candidate latency histograms — a stream
    has no persist store, so the hit-emit point is its "persist
    complete" stage and no ``.lineage.json`` doc is written; ``push``
    (an :class:`~pulsarutils_tpu.obs.push.AlertBroker` or subscriber
    specs) fans hits out to webhook subscribers on a bounded queue
    that can never block this loop.  Canary best rows are excluded
    before the publish site.  Both ``None``-gated, byte-identical off.
    """
    import contextlib
    import json as _json
    import time as _time

    from ..faults import inject as fault_inject
    from ..faults.policy import call_with_deadline
    from ..io.lowbit import PackedFrames
    from ..obs import metrics as _metrics
    from ..obs.canary import CanaryController
    from ..obs.health import HealthEngine
    from ..obs.lineage import LineageRecorder
    from ..obs.push import AlertBroker
    from ..obs.server import start_obs_server
    from ..obs.trace import set_track, span
    from ..resilience import ladder as _ladder
    from ..utils.logging_utils import logger

    # each stream session starts undegraded (OOM descents within the
    # stream are sticky — a measured slowdown; ISSUE 12)
    _ladder.reset()

    @contextlib.contextmanager
    def traced_chunk(istart):
        # budget-less analogue of BudgetAccountant.chunk's tracing: the
        # chunk span AND its nested spans (search, kernel buckets) land
        # on this chunk's own Perfetto track
        with set_track(f"chunk {istart}"):
            with span("chunk", chunk=istart):
                yield

    if budget is not None:
        budget.begin_stream()

    # the plane-consumer seam forces capture; the kwarg is only passed
    # when armed so the seam-off dispatch signature (and its compiled
    # programs) stays byte-identical to the pre-seam driver
    capture_kw = {"capture_plane": True} if plane_consumer is not None \
        else {}

    def run_one(istart, chunk):
        fault_inject.fire("dispatch", chunk=istart, backend=backend)
        if mesh is not None and backend == "jax":
            if kernel == "hybrid":
                from .sharded_fdmt import sharded_hybrid_search

                return sharded_hybrid_search(
                    chunk, dmmin, dmmax, start_freq, bandwidth,
                    sample_time, mesh=mesh, **capture_kw)
            if kernel == "fdmt":
                from .sharded_fdmt import sharded_fdmt_search

                return sharded_fdmt_search(
                    chunk, dmmin, dmmax, start_freq, bandwidth,
                    sample_time, mesh=mesh, **capture_kw)
            from .sharded import sharded_dedispersion_search

            return sharded_dedispersion_search(
                chunk, dmmin, dmmax, start_freq, bandwidth, sample_time,
                mesh=mesh, trial_dms=trial_dms, chan_block=chan_block,
                # the documented consumer contract: a DM-sharded
                # device-resident handle, never an eagerly-gathered
                # host plane (search_by_chunks' mesh seam rule)
                **(dict(capture_kw, plane_handle=True) if capture_kw
                   else {}))
        return dedispersion_search(
            chunk, dmmin, dmmax, start_freq, bandwidth, sample_time,
            backend=backend, trial_dms=trial_dms, dm_block=dm_block,
            chan_block=chan_block, **capture_kw,
            **({} if kernel == "auto" else {"kernel": kernel}))

    def run_guarded(istart, chunk):
        last = None
        attempt = 0
        oom_descents = 0
        budget_attempts = max(int(dispatch_retries), 0) + 1
        while attempt < budget_attempts:
            try:
                return call_with_deadline(lambda: run_one(istart, chunk),
                                          dispatch_timeout)
            except (ValueError, TypeError):
                raise  # deterministic configuration error
            except Exception as exc:  # jax errors share no base class
                last = exc
                if _ladder.is_resource_exhausted(exc) \
                        and oom_descents < 2 * len(_ladder.STEPS):
                    # RESOURCE_EXHAUSTED is not a transient dispatch
                    # fault (ISSUE 12): descend the degradation ladder
                    # — the re-dispatch runs smaller (split trial
                    # passes; unfused mesh hybrid) and byte-identical —
                    # without burning the transient retry budget
                    _ladder.oom_event("stream")
                    _ladder.descend("unfuse" if kernel == "hybrid"
                                    else "split_dm")
                    oom_descents += 1
                    logger.warning(
                        "stream chunk %s hit RESOURCE_EXHAUSTED (%r); "
                        "ladder level %d, re-dispatching smaller",
                        istart, exc, _ladder.level())
                    continue
                attempt += 1
                if attempt < budget_attempts:
                    _metrics.counter("putpu_dispatch_retries_total").inc()
                logger.warning("stream chunk %s search failed (%r); "
                               "%s", istart, exc,
                               "retrying" if attempt < budget_attempts
                               else "giving up")
        raise last

    if canary is not None and not isinstance(canary, CanaryController):
        canary = CanaryController(rate=float(canary))
    if canary is not None and canary.rate <= 0.0:
        canary = None
    if http_port is not None and health is None:
        health = HealthEngine()
    if lineage is True:
        lineage = LineageRecorder(source="stream_search")
    elif not lineage:
        lineage = None          # accept False/0/"" as "off" (CLI flag)
    push_owned = False
    if not push:
        push = None
    elif not isinstance(push, AlertBroker):
        push = AlertBroker(push, health=health)
        push_owned = True

    results = []
    hits = []
    total = len(chunks) if hasattr(chunks, "__len__") else None
    t_run0 = _time.time()

    def _progress_snapshot():
        done = len(results)
        elapsed = _time.time() - t_run0
        rate = done / elapsed if elapsed > 0 and done else None
        doc = {"chunks_done": done, "chunks_total": total,
               "elapsed_s": round(elapsed, 1),
               "eta_s": (round((total - done) / rate, 1)
                         if rate and total is not None else None),
               "hits": len(hits)}
        if canary is not None:
            doc["canary"] = canary.summary()
        return doc

    obs_server = (start_obs_server(http_port, health=health,
                                   progress_fn=_progress_snapshot,
                                   host=http_host, push=push)
                  if http_port is not None else None)

    def _oom_events_total():
        return sum(m.get("value", 0)
                   for m in _metrics.REGISTRY.snapshot()
                   if m.get("name") == "putpu_oom_events_total")

    health_oom_base = [_oom_events_total()] if health is not None else None

    def _health_update(istart, wall_s, candidates=None, contained=False):
        if health is not None:
            oom_now = _oom_events_total()
            oom_delta = oom_now - health_oom_base[0]
            health_oom_base[0] = oom_now
            health.update(istart, wall_s=wall_s, candidates=candidates,
                          quarantined=contained, oom_events=oom_delta,
                          canary=canary.summary()
                          if canary is not None else None)

    def _emit_candidate(istart, chunk, best):
        """Lineage + push at a hit-append site (ISSUE 18; canary best
        rows are tagged/promoted before this point and never reach
        it).  A stream has no persist store, so the emit point doubles
        as the "persist complete" stage: the hit is durable in the
        caller's hands and the end-to-end latency histogram closes
        here."""
        if lineage is None and push is None:
            return
        dm = float(best["DM"])
        snr = float(best["snr"])
        width = float(best["rebin"]) * float(sample_time)
        iend = istart + int(chunk.shape[1])
        cl = None
        if lineage is not None:
            cl = lineage.candidate(istart, iend, dm=dm, snr=snr,
                                   width=width)
            lineage.persisted(cl, writer=None)
        if push is not None:
            push.publish(
                {"schema_version": 1, "kind": "candidate",
                 "source": "stream_search", "chunk": int(istart),
                 "iend": int(iend), "dm": dm, "snr": snr,
                 "width_s": width},
                on_delivered=(None if cl is None else
                              lambda sub, _lat, _cl=cl:
                              lineage.delivered(_cl, sub)))

    try:
      for istart, chunk in _iter_lookahead(chunks):
        # with a budget, the chunk/search spans come from the accountant
        # itself (one timing primitive); without one, emit them directly
        # so a trace-only stream still renders per-chunk tracks
        ctx = (budget.chunk(istart) if budget is not None
               else traced_chunk(istart))
        with ctx:
            t_chunk = _time.perf_counter()
            is_packed = isinstance(chunk, PackedFrames)
            if lineage is not None:
                # a stream has no reader thread: chunk receipt is its
                # "read" seam
                lineage.mark(istart, "read")
            if canary is not None:
                if not canary._bound:
                    canary.bind(nchan=chunk.shape[0],
                                start_freq=start_freq,
                                bandwidth=bandwidth, tsamp=sample_time,
                                dmmin=dmmin, dmmax=dmmax)
                if is_packed:
                    # quantized into the low-bit codes, re-packed on
                    # this thread: the device signature is exact and
                    # recall is measured on packed runs too (ISSUE 11)
                    chunk = PackedFrames(
                        canary.maybe_inject_packed(
                            chunk.frames, istart, nbits=chunk.nbits,
                            nchan=chunk.nchan,
                            band_descending=chunk.band_descending),
                        chunk.nbits, chunk.nchan,
                        band_descending=chunk.band_descending)
                else:
                    chunk = canary.maybe_inject(chunk, istart)
            if backend == "jax":
                # bytes shipped for this chunk's search: the packed
                # fast path's 8-16x link win is a METRIC, not a claim
                # (tests/test_lowbit_e2e.py pins the ratio).  The float arm
                # counts the float32 bytes the search actually uploads
                # (not the host array's nbytes — a float64 producer
                # would over-report 2x and inflate the ratio)
                _metrics.counter("putpu_bytes_uploaded_total").inc(
                    int(chunk.nbytes) if is_packed
                    else 4 * int(np.prod(np.shape(chunk))))
                if is_packed:
                    _metrics.counter(
                        "putpu_lowbit_packed_chunks_total").inc()
                    _metrics.counter(
                        "putpu_lowbit_bytes_saved_total").inc(
                        chunk.float_nbytes - chunk.nbytes)
            if lineage is not None:
                lineage.mark(istart, "dispatch")
            try:
                with (budget.bucket("search") if budget is not None
                      else span("search")):
                    result = run_guarded(istart, chunk)
                if plane_consumer is not None:
                    table, _plane = result
                    plane_consumer(istart, _plane, table)
                else:
                    table = result
            except (ValueError, TypeError):
                raise
            except Exception:
                if not skip_failed:
                    raise
                # containment: one broken chunk must not kill a long
                # stream — counted, logged above, and absent from the
                # results (callers see exactly which chunks made it)
                _metrics.counter("putpu_stream_chunks_failed_total").inc()
                if canary is not None:
                    canary.discard(istart)
                if lineage is not None:
                    lineage.discard(istart)
                _health_update(istart,
                               wall_s=_time.perf_counter() - t_chunk,
                               contained=True)
                continue
            if lineage is not None:
                lineage.mark(istart, "ready")
            canary_obs = (canary.observe(istart, table, snr_threshold)
                          if canary is not None else None)
            results.append((istart, table))
            best = table.best_row()
            _metrics.counter("putpu_stream_chunks_total").inc()
            if best["snr"] > snr_threshold:
                if canary_obs is not None \
                        and canary_obs["best_is_canary"]:
                    # the chunk's best row is the injected canary:
                    # excluded from the science hits.  A genuine weaker
                    # pulse in the same chunk is promoted in its place
                    # — the hit list must match the canary-off run's
                    canary.tag_hit(istart)
                    sci_idx = canary_obs["science_idx"]
                    sci_snr = canary_obs["science_snr"]
                    if sci_idx is not None \
                            and sci_snr > float(snr_threshold):
                        # same contract as search_by_chunks: the
                        # promoted hit's table has the canary-lit rows
                        # masked out, so consumers sifting/persisting
                        # stream hits never ingest synthetic rows
                        keep = ~canary_obs["canary_rows"]
                        sci_table = type(table)(
                            {name: table[name][keep]
                             for name in table.colnames},
                            meta=table.meta)
                        best = {name: table[name][sci_idx]
                                for name in table.colnames}
                        hits.append((istart, sci_table, best))
                        _metrics.counter(
                            "putpu_stream_hits_total").inc()
                        _metrics.counter(
                            "putpu_canary_promoted_hits_total").inc()
                        _emit_candidate(istart, chunk, best)
                else:
                    if canary_obs is not None \
                            and canary_obs["recovered"]:
                        # a real pulse outranked this chunk's canary:
                        # the hit is genuine but its table still holds
                        # the canary-lit rows — counted + logged, as in
                        # search_by_chunks
                        _metrics.counter(
                            "putpu_canary_contaminated_tables_total").inc()
                        logger.info(
                            "stream chunk %d: real hit persisted "
                            "alongside a recovered canary — trial rows "
                            "near DM %.1f include synthetic signal",
                            istart, canary.dm)
                    hits.append((istart, table, best))
                    _metrics.counter("putpu_stream_hits_total").inc()
                    _emit_candidate(istart, chunk, best)
            if health is not None:
                ncand = int(np.count_nonzero(
                    np.asarray(table["snr"], dtype=np.float64)
                    > float(snr_threshold)))
                if canary_obs is not None:
                    # canary-lit rows are excluded from the storm signal
                    ncand = max(ncand - canary_obs["n_above_near"], 0)
                _health_update(istart,
                               wall_s=_time.perf_counter() - t_chunk,
                               candidates=ncand)
            if lineage is not None:
                # hit lineage froze at the sift verdict; dropping the
                # chunk marks bounds the recorder's memory
                lineage.discard(istart)
    finally:
        if push is not None and push_owned:
            # bounded drain — a wedged subscriber cannot stall the
            # stream's exit (undelivered alerts are counted)
            logger.info("PUSH_JSON %s", _json.dumps(push.close()))
        if obs_server is not None:
            obs_server.close()
    return results, hits


# ---------------------------------------------------------------------------
# Time-sharded ring dedispersion (sequence parallelism)
# ---------------------------------------------------------------------------

@counted_plan_cache("_ring_kernel", maxsize=PLAN_CACHE_SIZE)
def _ring_kernel(mesh, n_hops, rotation):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_time = mesh.shape["time"]
    # each device receives its RIGHT neighbour's block (ring, wraps)
    perm = [(i, (i - 1) % n_time) for i in range(n_time)]

    def local_step(data_local, offsets):
        # data_local (C, T_loc): this device's contiguous time slice.
        # offsets (D, C): rebased gather offsets in [0, n_hops * T_loc).
        t_loc = data_local.shape[1]
        ndm = offsets.shape[0]
        tidx = jnp.arange(t_loc, dtype=jnp.int32)

        def hop(h, carry):
            acc, cur, nxt = carry
            # out[d, t] += sum_{c : off in window h} ext[c, t + off - base]
            ext = jnp.concatenate([cur, nxt], axis=1)
            rel = offsets - h * t_loc
            valid = (rel >= 0) & (rel < t_loc)
            relc = jnp.clip(rel, 0, t_loc)
            idx = tidx[None, None, :] + relc[:, :, None]  # < 2 * t_loc
            gathered = jnp.take_along_axis(
                jnp.broadcast_to(ext[None], (ndm,) + ext.shape), idx, axis=2)
            acc = acc + jnp.where(valid[:, :, None], gathered, 0.0).sum(axis=1)
            # rotate the ring: this device's view advances one block right
            return acc, nxt, jax.lax.ppermute(nxt, "time", perm=perm)

        # jax tracks varying-mesh-axes: a zeros-constant carry is
        # UNVARYING while the body's sum varies over the mesh, and
        # fori_loop rejects the carry-type mismatch
        acc0 = jax.lax.pcast(jnp.zeros((ndm, t_loc),
                                       dtype=data_local.dtype),
                             "time", to="varying")
        nxt0 = jax.lax.ppermute(data_local, "time", perm=perm)
        acc, _, _ = jax.lax.fori_loop(0, n_hops, hop,
                                      (acc0, data_local, nxt0))
        return acc

    from .mesh import shard_map_compat

    fn = shard_map_compat(
        local_step,
        mesh=mesh,
        in_specs=(P(None, "time"), P(None, None)),
        out_specs=P(None, "time"),
    )

    @jax.jit
    def run(data, offsets):
        plane = fn(data, offsets)
        # undo the constant global rotation introduced by offset rebasing:
        # ring_result[d, t] = dedisp[d, (t - base) mod T], so rolling by
        # rotation = (-base) mod T restores dedisp
        return jnp.roll(plane, rotation, axis=1)

    return run


def ring_dedisperse(data, trial_dms, start_freq, bandwidth, sample_time,
                    mesh):
    """Globally-circular dedispersion of a time-sharded sequence.

    The sequence-parallel path (ring-attention-style): ``data`` is
    ``(nchan, T)`` with ``T`` divisible by the ``"time"`` mesh axis size and
    each device holds a contiguous slice.  Fixed-size blocks rotate around
    the ring (one ``ppermute`` per hop); every device accumulates, for its
    own output slice, the channels whose delay lands in the currently-held
    window.  Raw per-channel shifts are rebased by the global minimum so
    gather offsets sit in ``[0, span]`` (span = intra-band delay range at
    ``dmmax``), and the resulting constant time rotation is undone at the
    end — the output equals the single-device
    :func:`~pulsarutils_tpu.ops.dedisperse.dedisperse_batch_numpy` plane up
    to float32 summation order, for ANY shift magnitude (the ring wraps).

    Hop count = ``ceil(span / (T / n_time))``; total gather work equals the
    single-device kernel — it is only distributed, with one ICI block
    transfer per hop overlapping the local gather.
    """
    import jax.numpy as jnp

    # host normalisation of the input: for a device-resident array this
    # is a full-chunk readback — attribute it instead of letting it land
    # in the unattributed residual (putpu-lint device-trip)
    with budget_bucket("search/readback"):
        data = np.asarray(data)
    nchan, nsamples = data.shape
    n_time = mesh.shape["time"]
    if nsamples % n_time:
        raise ValueError(f"T={nsamples} not divisible by time axis {n_time}")
    t_loc = nsamples // n_time

    trial_dms = np.asarray(  # putpu-lint: disable=device-trip — host DM plan list
        trial_dms, dtype=np.float64)
    from ..ops.plan import dedispersion_shifts_batch
    shifts = np.rint(dedispersion_shifts_batch(
        trial_dms, nchan, start_freq, bandwidth,
        sample_time)).astype(np.int64)
    base = int(shifts.min()) if shifts.size else 0
    offsets = (shifts - base).astype(np.int32)
    span = int(offsets.max()) if offsets.size else 0
    if span >= nsamples:
        raise ValueError(
            f"intra-band delay span {span} exceeds the sequence length "
            f"{nsamples}; enlarge the chunk (plan_chunks sizes it correctly)")
    n_hops = max(1, -(-(span + 1) // t_loc))
    # rotation: out[d, tau] = ring_result[d, (tau - base) mod T]
    rotation = (-base) % nsamples

    kernel = _ring_kernel(mesh, n_hops, rotation)
    return kernel(jnp.asarray(data, dtype=jnp.float32),
                  jnp.asarray(offsets))
