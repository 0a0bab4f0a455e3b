"""The N-filterbank multi-beam survey driver.

``multibeam_search`` opens N same-geometry filterbanks (the beams of
one receiver, or the files of N co-batched tenant jobs), plans ONE
chunk grid from the shared physics, and walks it with every beam's
chunk searched in a single batched dispatch
(:class:`~.batcher.BeamBatcher`).  Per beam it keeps the single-beam
driver's contracts:

* **exact resume** — one :class:`~pulsarutils_tpu.io.candidates.
  CandidateStore` ledger per beam, fingerprinted by the beam's own
  (file, physics) config — NOT by the batch composition, so a chunk
  searched in an 8-beam batch, a 3-beam batch or a sequential
  single-beam run marks done identically, and a killed run resumes
  exactly regardless of who else was in its batch;
* **bit-identity** — per-beam candidate tables (and therefore ledgers
  and persisted candidates) are byte-identical between
  ``batched=True`` and the sequential arm (``batched=False`` searches
  beam-by-beam through the same single-beam compiled kernel) — the
  PR 2 discipline, pinned in ``tests/test_beams.py``;
* **per-beam canary** — ``canary_rate`` arms one
  :class:`~pulsarutils_tpu.obs.canary.CanaryController` per beam with
  the beam's label, so each beam injects its own deterministic chunk
  subset and owns its own recall gauges: one silently-dead beam is
  caught by ITS recall floor instead of hiding in a fleet average.

After the chunk loop the per-beam hits run through the cross-beam
coincidence sift (:mod:`.coincidence`): same-(DM, time) detections
across all/most beams are vetoed as RFI, 1-2-adjacent-beam detections
confirmed.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from ..io.candidates import CandidateStore, config_fingerprint
from ..io.sigproc import FilterbankReader
from ..obs import metrics as obs_metrics
from ..obs.canary import CanaryController
from ..ops.clean_ops import renormalize_data
from ..ops.plan import dedispersion_plan
from ..ops.rebin import quick_resample
from ..parallel.stream import iter_chunk_starts, plan_chunks
from ..pipeline.pulse_info import PulseInfo
from ..pipeline.sift import hit_fields
from ..utils.frame_reserve import reserve_frames
from ..utils.logging_utils import BudgetAccountant, logger
from ..utils.table import ResultTable
from .batcher import BeamBatcher, BeamGeometryError
from .coincidence import coincidence_sift

__all__ = ["multibeam_search", "open_beams"]

#: header keys every co-batched beam must agree on (the chunk plan and
#: the shared offset table are derived from exactly these)
_GEOMETRY_KEYS = ("nchans", "tsamp", "fbottom", "ftop", "bandwidth", "foff")


def open_beams(fnames):
    """Open N filterbanks as the beams of one batch; returns
    ``(readers, labels)``.

    Geometry (channel count, sample time, band) must agree across all
    files — a mismatched beam raises :class:`~.batcher.
    BeamGeometryError` naming the offending key.  Labels come from the
    sigproc ``ibeam`` header where present and unique (satellite: the
    reader parses ``nbeams``/``ibeam`` natively); otherwise the
    positional index labels the beam.
    """
    readers = [FilterbankReader(f) for f in fnames]
    ref = readers[0].header
    for r in readers[1:]:
        for key in _GEOMETRY_KEYS:
            if not np.isclose(float(r.header.get(key, 0.0)),
                              float(ref.get(key, 0.0)), rtol=1e-9):
                raise BeamGeometryError(
                    f"{r.path}: header {key}={r.header.get(key)!r} does "
                    f"not match {readers[0].path}'s {ref.get(key)!r} — "
                    "beams batch only at one shared geometry")
    ibeams = [r.ibeam for r in readers]
    if all(b is not None for b in ibeams) \
            and len(set(ibeams)) == len(ibeams):
        labels = [int(b) for b in ibeams]
    else:
        labels = list(range(len(readers)))
    return readers, labels


def _clean_block(block, resample):
    """Per-beam host-side conditioning — IDENTICAL in the batched and
    sequential arms by construction (same numpy ops per beam), which is
    what lets the bit-identity pin cover the whole pipeline, not just
    the kernel."""
    cleaned = renormalize_data(block, xp=np)
    if resample > 1:
        cleaned = quick_resample(cleaned, resample, xp=np)
    return np.asarray(cleaned, dtype=np.float32)


@reserve_frames
def multibeam_search(fnames, dmmin=200, dmmax=800, *, snr_threshold=6.0,
                     output_dir=None, resume=True, max_chunks=None,
                     chunk_length=None, new_sample_time=None,
                     batched=True, kernel=None, canary_rate=0.0,
                     canary_seed=0, coincidence=True, veto_frac=0.7,
                     max_real_beams=2, adjacency=None, budget=None,
                     progress_cb=None, cancel_cb=None, keep_tables=False,
                     store_factory=None, packed="auto"):
    """Search N same-geometry filterbanks as one batched survey.

    Returns a result dict::

        {"beams": [{"fname", "beam", "hits": [(istart, iend, info,
                    table), ...], "store", "cancelled", "chunks_done",
                    "tables": [...] when keep_tables}],
         "coincidence": {"groups": [...], "stats": {...}} or None,
         "plan": ChunkPlan, "snr_threshold": float}

    ``batched=False`` is the sequential arm: the same per-beam pipeline
    dispatched beam-by-beam (the A/B baseline and the bit-identity
    reference).  ``progress_cb(beam_index, istart, wall_s, ncand)`` and
    ``cancel_cb(beam_index) -> bool`` are the job-service hooks: a
    cancelled beam stops being batched (its remaining chunks stay
    un-marked, so resubmitting the same spec resumes exactly from the
    ledger) while the other beams keep going.  ``store_factory(i,
    fname, fingerprint)`` overrides per-beam store construction (the
    service roots each job's store in the job's own output directory).

    ``packed`` (ISSUE 11) selects the low-bit data path:

    * ``"auto"`` (default) — ``"device"`` when every beam file is a
      packed 1/2/4-bit single-IF filterbank, ``"off"`` otherwise;
    * ``"device"`` / ``True`` — each beam's RAW packed bytes are read,
      canary-injected in the packed domain, stacked and unpacked **per
      beam inside the one batched program**, with the per-beam
      conditioning (renormalise + resample) in the same jit: an N-beam
      chunk epoch uploads 1/8-1/16th the float32 bytes;
    * ``"host"`` — the byte-identity A/B arm: the same in-jit
      conditioning fed host-unpacked float codes (identical floats, at
      float32 upload cost);
    * ``"off"`` / ``False`` — the legacy host-side clean (the only
      mode for 8/16/32-bit files, whose path is unchanged).

    ``"device"`` and ``"host"`` produce byte-identical per-beam tables,
    ledgers and candidates (pinned in ``tests/test_lowbit_e2e.py``);
    both differ from ``"off"`` on low-bit files, whose conditioning
    used to run host-side in float64 — the packed path is the default
    there now, which is the point of ISSUE 11.
    """
    if not fnames:
        raise ValueError("multibeam_search needs at least one filterbank")
    from ..resilience import ladder as _resilience_ladder

    # each batched survey session starts undegraded, exactly like the
    # single-file drivers: a transient OOM in one tenant batch must not
    # permanently degrade every later job of a long-lived service
    # process (ISSUE 12; code-review r16)
    _resilience_ladder.reset()
    readers, labels = open_beams(fnames)
    nbeams = len(readers)
    header = readers[0].header
    nchan = header["nchans"]
    sample_time = header["tsamp"]
    start_freq = header["fbottom"]
    stop_freq = header["ftop"]
    bandwidth = header["bandwidth"]
    foff = header["foff"]
    nsamples = min(r.nsamples for r in readers)
    if any(r.nsamples != nsamples for r in readers):
        logger.warning(
            "beam files differ in length (%s samples): batching the "
            "common %d-sample prefix",
            sorted({r.nsamples for r in readers}), nsamples)

    # -- low-bit data-path resolution (ISSUE 11) ------------------------
    lowbit_ok = (all(r._nbits in (1, 2, 4) and r.nifs == 1
                     for r in readers)
                 and len({r._nbits for r in readers}) == 1)
    if packed == "auto":
        mode = "device" if lowbit_ok else "off"
    elif packed in (True, "device"):
        mode = "device"
    elif packed == "host":
        mode = "host"
    elif packed in (False, "off", None):
        mode = "off"
    else:
        raise ValueError(f"packed={packed!r}: expected 'auto', 'device', "
                         "'host' or 'off'")
    if mode in ("device", "host") and not lowbit_ok:
        raise ValueError(
            "packed mode needs every beam file packed at one shared "
            "1/2/4-bit single-IF format; pass packed='off' for mixed "
            "or full-rate files")
    nbits = readers[0]._nbits if lowbit_ok else 0
    descending = readers[0].band_descending

    plan = plan_chunks(nsamples, sample_time, dmmin, dmmax, start_freq,
                       stop_freq, foff, chunk_length=chunk_length,
                       new_sample_time=new_sample_time)
    eff_tsamp = plan.sample_time
    trial_dms = dedispersion_plan(nchan, dmmin, dmmax, start_freq,
                                  bandwidth, eff_tsamp)
    nsamp_eff = plan.step // plan.resample
    batcher = BeamBatcher(
        nchan, nsamp_eff, trial_dms, start_freq, bandwidth, eff_tsamp,
        kernel=kernel, batch_hint=nbeams,
        # device mode ships raw packed bytes (per-beam in-jit unpack);
        # both packed modes move the per-beam conditioning into the
        # batched program so the two arms share one float pipeline
        packed=(nbits, descending) if mode == "device" else None,
        prep=(True, plan.resample) if mode != "off" else None)
    logger.info("multibeam: %d beams, chunk plan step=%d hop=%d "
                "resample=%d, %d trials, kernel=%s, %s dispatch, "
                "data path=%s",
                nbeams, plan.step, plan.hop, plan.resample, len(trial_dms),
                batcher.kernel, "batched" if batched else "sequential",
                mode if mode != "off" else "host-clean")

    timer = budget if budget is not None else BudgetAccountant()
    timer.begin_stream()

    beams = []
    for i, (reader, label) in enumerate(zip(readers, labels)):
        fname = reader.path
        root = os.path.splitext(os.path.basename(str(fname)))[0]
        out_i = output_dir or os.path.dirname(os.path.abspath(str(fname)))
        # fingerprint = the beam's OWN science config; deliberately no
        # batch width / co-tenant names — ledgers must be interchangeable
        # between batched, sequential and differently-batched runs
        fingerprint = config_fingerprint(
            fname=os.path.abspath(str(fname)), dmmin=dmmin, dmmax=dmmax,
            step=plan.step, resample=plan.resample, backend="jax",
            kernel="multibeam", snr_threshold=snr_threshold)
        if store_factory is not None:
            store = store_factory(i, fname, fingerprint if resume else None)
        else:
            store = CandidateStore(out_i, fingerprint if resume else None)
        controller = None
        if canary_rate and float(canary_rate) > 0.0:
            controller = CanaryController(rate=float(canary_rate),
                                          seed=canary_seed, beam=label)
            controller.bind(nchan=nchan, start_freq=start_freq,
                            bandwidth=bandwidth, tsamp=sample_time,
                            dmmin=dmmin, dmmax=dmmax,
                            resample=plan.resample)
        beams.append({"fname": str(fname), "beam": label, "root": root,
                      # provenance prefers the header's observation-level
                      # nbeams (a 4-beam receiver batched 1 file at a
                      # time is still a 4-beam observation); the batch
                      # width is the coincidence denominator instead
                      "nbeams": (reader.nbeams if reader.nbeams is not None
                                 else nbeams),
                      "reader": reader, "store": store, "hits": [],
                      "canary": controller, "cancelled": False,
                      "chunks_done": 0, "tables": [] if keep_tables
                      else None})

    todo = list(iter_chunk_starts(nsamples, plan))
    if max_chunks is not None:
        todo = todo[:max_chunks]
    date = header.get("tstart", None)

    for istart in todo:
        chunk_size = min(plan.step, nsamples - istart)
        iend = istart + chunk_size
        t0 = istart * sample_time
        pending = []
        for i, b in enumerate(beams):
            if b["cancelled"]:
                continue
            if cancel_cb is not None and cancel_cb(i):
                b["cancelled"] = True
                logger.info("beam %s cancelled at chunk %d", b["beam"],
                            istart)
                continue
            if resume and b["store"].is_done(istart):
                continue
            pending.append(i)
        if not pending:
            continue

        # one budget chunk per batch epoch: the dispatch/readback trip
        # counters land per epoch (config 13's dispatches-per-beam-chunk
        # evidence), and wall is attributed exactly as in the single-beam
        # driver
        with timer.chunk(istart):
            blocks = {}
            with timer.bucket("read"):
                for i in pending:
                    b = beams[i]
                    if mode != "off":
                        # packed low-bit path: raw bytes off the mmap,
                        # canary quantized into the codes on this
                        # thread; "host" decodes here (the identity
                        # A/B arm), "device" ships the bytes as-is
                        raw = b["reader"].read_block_packed(istart,
                                                            chunk_size)
                        if b["canary"] is not None:
                            raw = b["canary"].maybe_inject_packed(
                                raw, istart, nbits=nbits, nchan=nchan,
                                band_descending=descending)
                        if mode == "host":
                            from ..io.lowbit import PackedFrames

                            blocks[i] = PackedFrames(
                                raw, nbits, nchan,
                                band_descending=descending).to_host()
                        else:
                            blocks[i] = raw
                        continue
                    block = b["reader"].read_block(istart, chunk_size,
                                                   band_ascending=True)
                    if b["canary"] is not None:
                        block = b["canary"].maybe_inject(block, istart)
                    blocks[i] = block
            if mode == "off":
                # packed modes condition INSIDE the batched program
                # (BeamBatcher prep); the legacy path cleans host-side
                with timer.bucket("clean"):
                    for i in pending:
                        blocks[i] = _clean_block(blocks[i], plan.resample)

            t_chunk = time.perf_counter()
            with timer.bucket("search"):
                if batched:
                    tables = batcher.search([blocks[i] for i in pending])
                    obs_metrics.counter("putpu_multibeam_batches_total").inc()
                else:
                    tables = [batcher.search_single(blocks[i])
                              for i in pending]
            wall = time.perf_counter() - t_chunk

            for i, table in zip(pending, tables):
                b = beams[i]
                table.meta["ibeam"] = b["beam"]
                table.meta["nbeams"] = b["nbeams"]
                if keep_tables:
                    b["tables"].append((istart, table))
                canary_obs = (b["canary"].observe(istart, table, snr_threshold)
                              if b["canary"] is not None else None)
                best = table.best_row()
                is_hit = bool(best["snr"] > snr_threshold)
                sci_table = table
                ncand = int(np.count_nonzero(
                    np.asarray(table["snr"], dtype=np.float64)
                    > float(snr_threshold)))
                if canary_obs is not None:
                    ncand = max(ncand - canary_obs["n_above_near"], 0)
                if is_hit and canary_obs is not None \
                        and canary_obs["best_is_canary"]:
                    # the beam's best row is its own injected canary: tag it,
                    # promote the strongest unlit row when it still clears
                    # the threshold (stream_search's contract, per beam)
                    b["canary"].tag_hit(istart)
                    sci_idx = canary_obs["science_idx"]
                    sci_snr = canary_obs["science_snr"]
                    if sci_idx is not None \
                            and sci_snr > float(snr_threshold):
                        keep = ~canary_obs["canary_rows"]
                        sci_table = ResultTable(
                            {name: table[name][keep]
                             for name in table.colnames}, meta=table.meta)
                        best = {name: table[name][sci_idx]
                                for name in table.colnames}
                        obs_metrics.counter(
                            "putpu_canary_promoted_hits_total").inc()
                    else:
                        is_hit = False
                elif is_hit and canary_obs is not None \
                        and canary_obs["recovered"]:
                    obs_metrics.counter(
                        "putpu_canary_contaminated_tables_total").inc()
                    logger.info(
                        "beam %s chunk %d: real hit persisted alongside a "
                        "recovered canary (synthetic rows near DM %.1f ride "
                        "in its table)", b["beam"], istart, b["canary"].dm)

                payload = None
                if is_hit:
                    if mode == "device":
                        # diagnostics waterfall for the (rare) hit:
                        # host decode + host clean of exactly the bytes
                        # the device searched — identical across the
                        # device/host arms, so candidate files stay
                        # byte-identical
                        from ..io.lowbit import PackedFrames

                        array = _clean_block(PackedFrames(
                            blocks[i], nbits, nchan,
                            band_descending=descending).to_host(),
                            plan.resample)
                    elif mode == "host":
                        array = _clean_block(blocks[i], plan.resample)
                    else:
                        array = blocks[i]
                    info = PulseInfo(
                        allprofs=array, start_freq=start_freq,
                        bandwidth=bandwidth, nbin=array.shape[1],
                        nchan=array.shape[0], date=date, t0=t0, istart=istart,
                        pulse_freq=1.0 / (array.shape[1] * eff_tsamp),
                        ibeam=b["beam"], nbeams=b["nbeams"],
                        dm=float(best["DM"]), snr=float(best["snr"]),
                        width=float(best["rebin"]) * eff_tsamp)
                    info.disp_profile = np.asarray(array.mean(0))
                    info.compute_stats()
                    payload = (info, sci_table)
                    obs_metrics.counter("putpu_beam_hits_total",
                                        beam=str(b["beam"])).inc()
                    logger.info("HIT beam %s chunk %d-%d: DM=%.2f snr=%.2f",
                                b["beam"], istart, iend, info.dm, info.snr)
                with timer.bucket("persist"):
                    if payload is not None:
                        b["store"].save_candidate(b["root"], istart, iend,
                                                  *payload)
                        b["hits"].append((istart, iend) + payload)
                    b["store"].mark_done(istart)
                b["chunks_done"] += 1
                obs_metrics.counter("putpu_beam_chunks_total",
                                    beam=str(b["beam"])).inc()
                if progress_cb is not None:
                    progress_cb(i, istart, wall / len(pending), ncand)

    # resumed sessions must report the COMPLETE per-beam result (the
    # single-beam driver's round-5 rule): restore candidates persisted
    # by interrupted runs
    for b in beams:
        if not resume:
            continue
        seen = {(h[0], h[1]) for h in b["hits"]}
        for cand_root, lo, hi in b["store"].candidates():
            if (cand_root != b["root"] or (lo, hi) in seen
                    or not b["store"].is_done(lo)):
                continue
            try:
                info, table = b["store"].load_candidate(b["root"], lo, hi)
            except (OSError, ValueError, KeyError) as exc:
                obs_metrics.counter(
                    "putpu_resume_pairs_skipped_total").inc()
                logger.warning("beam %s: could not restore candidate "
                               "%s_%d-%d: %r", b["beam"], b["root"], lo,
                               hi, exc)
                continue
            b["hits"].append((lo, hi, info, table))
        b["hits"].sort(key=lambda h: h[0])

    coinc = None
    if coincidence:
        cands = []
        for b in beams:
            for h in b["hits"]:
                c = hit_fields(*h)
                c["beam"] = b["beam"]
                cands.append(c)
        stats = {}
        groups = coincidence_sift(
            cands, nbeams=nbeams, veto_frac=veto_frac,
            max_real_beams=max_real_beams, adjacency=adjacency,
            stats=stats) if cands else []
        if not cands:
            stats = {"in": 0, "nbeams": nbeams, "groups": 0,
                     "verdicts": {}, "vetoed_members": 0}
        coinc = {"groups": groups, "stats": stats}

    timer.report()
    timer.footer()
    logger.info("BUDGET_JSON %s", json.dumps(timer.to_json()))
    for b in beams:
        if b["canary"] is not None:
            logger.info("CANARY_JSON %s", json.dumps(b["canary"].to_json()))
    logger.info("multibeam done: %d beams, %s chunks/beam, hits per "
                "beam %s", nbeams, len(todo),
                {b["beam"]: len(b["hits"]) for b in beams})
    result_beams = []
    for b in beams:
        result_beams.append({
            "fname": b["fname"], "beam": b["beam"], "root": b["root"],
            "hits": b["hits"], "store": b["store"],
            "cancelled": b["cancelled"], "chunks_done": b["chunks_done"],
            "canary": (b["canary"].to_json() if b["canary"] is not None
                       else None),
            **({"tables": b["tables"]} if keep_tables else {})})
    return {"beams": result_beams, "coincidence": coinc, "plan": plan,
            "snr_threshold": float(snr_threshold)}
