"""The full-observation periodicity job: accumulate -> acceleration
search -> sift -> fold -> persist.

``periodicity_search`` is the workload driver behind the
``workload="periodicity"`` service job type, the fleet lease and the
``PUperiod`` CLI.  It rides the hardened single-pulse driver as its
transport: :func:`~pulsarutils_tpu.pipeline.search_pipeline.
search_by_chunks` streams, cleans and dedisperses every chunk exactly
as a single-pulse survey would (same ledger, quarantine, retry and
resume machinery — single-pulse candidates are persisted as a bonus),
and the ``plane_consumer`` seam hands each chunk's dedispersed plane to
the :class:`~.accumulate.DMTimeAccumulator` before it is dropped.

Resume contract: the chunk ledger records completion (under a
periodicity-specific fingerprint via ``fingerprint_extra``, so a
single-pulse run over the same file never collides), and the
accumulator snapshots its partial plane beside it after every consumed
chunk.  A chunk the ledger marks done but the snapshot lost (a crash in
the one-chunk window, a deleted snapshot) is detected after the
streaming pass and re-searched explicitly — accumulation can never
silently hole.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from ..faults import inject as fault_inject
from ..obs import metrics as _metrics
from ..utils.frame_reserve import reserve_frames
from ..utils.logging_utils import logger
from .accel import accel_grid, accel_search, jerk_grid
from .accumulate import DMTimeAccumulator
from .candidates import (ZapList, candidate_list, fold_candidates,
                         harmonic_ratio, save_candidates, sift_candidates)

__all__ = ["periodicity_search"]

#: keyword subset forwarded to ``plan_survey`` (the rest of
#: ``search_kwargs`` only shapes the session, not the plan/fingerprint)
_PLAN_KEYS = ("chunk_length", "new_sample_time", "tmin", "surelybad",
              "fft_zap", "cut_outliers", "zero_dm", "exact_floor",
              "quarantine_policy")

#: periodic-canary shape: a Gaussian pulse train of this duty cycle,
#: injected at this fraction of the spectral band and this DM-row
#: fraction — all deterministic, so recall failures are signal, not luck
_CANARY_DUTY = 0.08
_CANARY_BIN_FRAC = 0.12
_CANARY_ROW_FRAC = 1 / 3


def _inject_canary(plane, tsamp):
    """Inject the synthetic pulsar into a COPY of the plane; returns
    ``(plane_copy, row, freq)``.  Amplitude is ``canary snr`` row-noise
    standard deviations at every sample of the train's Gaussian peak —
    far above any folding threshold, so a miss means the trial search
    (not the injection) failed."""
    ndm, nout = plane.shape
    row = max(int(ndm * _CANARY_ROW_FRAC), 0)
    bin_c = max(int(round(_CANARY_BIN_FRAC * (nout // 2))), 4)
    freq = bin_c / (nout * tsamp)
    out = np.array(plane, copy=True)
    std = float(np.std(out[row])) or 1.0
    phase = (np.arange(nout) * tsamp * freq) % 1.0
    dist = np.minimum(phase, 1.0 - phase)
    out[row] += (10.0 * std
                 * np.exp(-0.5 * (dist / _CANARY_DUTY) ** 2)
                 ).astype(out.dtype)
    return out, row, freq


def _canary_is_recovered(cand, freq, freq_tol):
    """True when a canary-row candidate is the injection itself (or an
    integer harmonic of it) — the recall signal.  Candidates on the
    canary row that fail this are still *excluded* from the science
    list: a nonzero-accel trial smears the unaccelerated canary into a
    shifted, weakened peak whose frequency no simple window can name,
    so the canary owns its DM-row neighbourhood outright (the
    contamination bound is stated in ``docs/periodicity.md`` — the row
    is deterministic, ``ndm // 3``)."""
    return (abs(cand["freq"] - freq) <= freq_tol
            or harmonic_ratio(freq, cand["freq"]) > 0)


@reserve_frames
def periodicity_search(fname, dmmin=200, dmmax=800, *, accel_max=0.0,
                       n_accel=None, jerk_max=0.0, n_jerk=None,
                       accel_backend="auto",
                       sigma_threshold=8.0, topk=64,
                       max_harmonics=16, fmin=None, fmax=None, nbin=32,
                       zap=None, zap_path=None, rebin="auto",
                       budget_bytes=None, snapshot_every=1,
                       backend="jax", kernel="auto", mesh=None,
                       snr_threshold=6.0, output_dir=None, resume=True,
                       canary=False, health=None, http_port=None,
                       report_out=None, cancel_cb=None, chunk_cb=None,
                       progress=True, fence=None, **search_kwargs):
    """Search one filterbank for (accelerated) pulsars at survey scale.

    Stages:

    1. **accumulate** — stream the file through ``search_by_chunks``
       (all its hardening knobs pass through ``search_kwargs``) and
       fold every chunk's dedispersed plane into one rebinned
       full-observation DM–time plane, sized by the memory budget;
    2. **trial search** — the (DM, accel[, jerk]) sweep over
       ``accel_grid(accel_max, ...)`` x ``jerk_grid(jerk_max, ...)``
       (``n_accel``/``n_jerk`` override the grid sizes; ``accel_max=0``
       searches the single zero-acceleration trial and ``jerk_max=0``
       adds no jerk axis), on the ``backend``/``mesh`` the single-pulse
       leg used, with a host-numpy fallback on device failure.
       ``accel_backend`` picks the trial formulation: ``"time_stretch"``
       (:func:`~.accel.accel_search`, one rfft per trial),
       ``"fdas"`` (:func:`~.fdas.fdas_search`, one rfft per DM +
       batched z/w-response correlation) or ``"auto"`` (the measured
       autotuner contender pair, :func:`~pulsarutils_tpu.tuning.
       autotune.resolve_accel_backend` — below the tune floor this
       resolves statically to ``time_stretch``, the pre-FDAS path);
    3. **candidates** — threshold at ``sigma_threshold``, zap-list /
       DM-grouping / harmonic sift (:mod:`~.candidates`), batched
       phase-folding of survivors;
    4. **persist** — folded candidates land in
       ``period_cands_<root>_<fingerprint>.npz`` beside the chunk
       ledger; a ``PERIOD_JSON`` summary line is logged and the survey
       report (``report_out``) gains a Periodicity section.

    ``canary=True`` injects a synthetic pulsar (deterministic P at a
    known DM row, ``ndm // 3``) into a *copy* of the accumulated plane
    before the trial search; its recovery sets the
    ``putpu_period_canary_recall`` gauge and feeds ``health`` (when
    given).  The canary owns its DM-row neighbourhood (±2 trials):
    every candidate there is excluded from the science list — nonzero-
    accel trials smear the injection into sidelobe peaks no frequency
    window can name — so a real source inside that neighbourhood is
    the stated contamination bound of a canary-on run
    (``docs/periodicity.md``); outside it the persisted candidates are
    pinned identical to a canary-off run.

    Returns a dict: ``candidates`` (sifted + folded), ``sift`` stats,
    ``table`` (raw trial-search top-k), ``accumulator``, ``accels``,
    ``fingerprint``, ``candidates_path``, ``snapshot_path``,
    ``complete`` (False when cancelled before every chunk was
    accumulated — resubmit/resume to continue), ``canary`` summary and
    the single-pulse leg's ``hits``/``store``.
    """
    from ..ops.plan import dedispersion_plan
    from ..pipeline.search_pipeline import plan_survey, search_by_chunks

    for k in ("period_search", "period_sigma_threshold", "make_plots",
              "plane_consumer", "fingerprint_extra"):
        if k in search_kwargs:
            raise ValueError(
                f"{k} is owned by the periodicity driver: the "
                "full-observation stage replaces the per-chunk rescue "
                "seam (use sigma_threshold for the candidate floor)")
    if accel_backend not in ("auto", "time_stretch", "fdas"):
        raise ValueError(
            f"accel_backend must be 'auto', 'time_stretch' or 'fdas', "
            f"got {accel_backend!r}")
    output_dir = output_dir or os.path.dirname(os.path.abspath(str(fname)))
    extra = {"workload": "periodicity", "accel_max": float(accel_max)}
    if jerk_max:
        # conditional on purpose: a jerk-less run's fingerprint (and so
        # its ledger/snapshot/artifact names) stays byte-identical to
        # every pre-jerk release — the driver-fingerprint rule
        extra["jerk_max"] = float(jerk_max)
    plan_kw = {k: search_kwargs[k] for k in _PLAN_KEYS
               if k in search_kwargs}
    sp = plan_survey(fname, dmmin=dmmin, dmmax=dmmax, backend=backend,
                     kernel=kernel, snr_threshold=snr_threshold,
                     mesh=mesh, fingerprint_extra=extra, **plan_kw)
    header = sp["reader"].header
    trial_dms = dedispersion_plan(header["nchans"], dmmin, dmmax,
                                  header["fbottom"], header["bandwidth"],
                                  sp["plan"].sample_time)
    acc = DMTimeAccumulator(sp["plan"], sp["nsamples"],
                            sp["chunk_starts"], len(trial_dms),
                            rebin=rebin, budget_bytes=budget_bytes,
                            trial_dms=trial_dms)
    snap_path = os.path.join(output_dir,
                             f"period_accum_{sp['fingerprint']}.npz")
    if resume:
        acc.restore(snap_path)
    logger.info(
        "periodicity job: %d DM trials x %d chunks -> %d x %d plane "
        "(rebin %d, tsamp %.4gs, T_obs %.1fs)", len(trial_dms),
        len(sp["chunk_starts"]), acc.ndm, acc.nout, acc.rebin, acc.tsamp,
        acc.nout * acc.tsamp)

    state = {"since_snap": 0}

    def consumer(istart, plane, table):
        if acc.consume(istart, plane, table):
            state["since_snap"] += 1
            if snapshot_every and state["since_snap"] >= snapshot_every:
                acc.save(snap_path)
                state["since_snap"] = 0
        if chunk_cb is not None:
            chunk_cb(istart)

    common = dict(dmmin=dmmin, dmmax=dmmax, backend=backend,
                  kernel=kernel, snr_threshold=snr_threshold, mesh=mesh,
                  output_dir=output_dir, make_plots=False,
                  progress=progress, fingerprint_extra=extra,
                  plane_consumer=consumer, **search_kwargs)
    hits, store = search_by_chunks(fname, resume=resume, health=health,
                                   http_port=http_port,
                                   cancel_cb=cancel_cb, fence=fence,
                                   **common)
    if state["since_snap"] or not os.path.exists(snap_path):
        acc.save(snap_path)
        state["since_snap"] = 0

    quarantined = set(store.quarantined_chunks)
    missing = set(acc.chunk_starts) - acc.seen - quarantined
    cancelled = cancel_cb is not None and cancel_cb()
    if missing and not cancelled:
        # ledger-done chunks whose planes never reached the snapshot
        # (crash inside the snapshot_every window, lost snapshot file):
        # re-search exactly those chunks, ledger-less, so accumulation
        # cannot hole silently
        logger.warning(
            "periodicity accumulation is missing %d ledger-done "
            "chunk(s); re-searching them for their planes", len(missing))
        search_by_chunks(fname, resume=False, chunks=sorted(missing),
                         **common)
        acc.save(snap_path)
        missing = set(acc.chunk_starts) - acc.seen - quarantined
    if missing:
        logger.info("periodicity job incomplete: %d chunk(s) not yet "
                    "accumulated — resume to continue", len(missing))
        return {"complete": False, "candidates": None, "sift": None,
                "table": None, "accumulator": acc, "accels": None,
                "fingerprint": sp["fingerprint"],
                "candidates_path": None, "snapshot_path": snap_path,
                "canary": None, "hits": hits, "store": store}
    if quarantined:
        logger.warning(
            "periodicity plane carries %d quarantined chunk(s) as "
            "zeros — bounded sensitivity loss, see the quarantine "
            "manifest", len(quarantined))

    # -- stage 2: the (DM, accel) trial sweep ---------------------------------
    tsamp_out = acc.tsamp
    nout = acc.nout
    if n_accel is not None:
        # odd and >= 3, so the grid ALWAYS contains the exact zero
        # trial (n_accel=1 would linspace to the single trial
        # -accel_max and an unaccelerated pulsar could be missed
        # outright); n_accel <= 1 means "no acceleration axis"
        n_accel = int(n_accel)
        if accel_max <= 0 or n_accel <= 1:
            accels = np.zeros(1)
        else:
            accels = np.linspace(-accel_max, accel_max,
                                 max(n_accel, 3) | 1)
    else:
        accels = accel_grid(accel_max, tsamp_out, nout)
    if n_jerk is not None:
        # same odd-grid rule as n_accel: the exact zero-jerk trial is
        # always present, n_jerk <= 1 means "no jerk axis"
        n_jerk = int(n_jerk)
        if jerk_max <= 0 or n_jerk <= 1:
            jerks = np.zeros(1)
        else:
            jerks = np.linspace(-jerk_max, jerk_max, max(n_jerk, 3) | 1)
    else:
        jerks = jerk_grid(jerk_max, tsamp_out, nout)
    # the single zero trial is "no jerk axis": the table layout, the
    # trial count and the resume artifacts stay exactly the pre-jerk
    # ones
    jerks_axis = jerks if len(jerks) > 1 else None
    fmin_eff = fmin if fmin is not None else 4.0 / (nout * tsamp_out)
    freq_tol = 1.5 / (nout * tsamp_out)

    chosen_backend = accel_backend
    if chosen_backend == "auto":
        chosen_backend = "time_stretch"
        if backend == "jax":
            try:
                from ..tuning.autotune import resolve_accel_backend

                chosen_backend = resolve_accel_backend(
                    acc.ndm, nout, tsamp_out, accels, jerks=jerks_axis,
                    max_harmonics=max_harmonics, fmin=fmin_eff,
                    fmax=fmax, mesh=mesh)
            except Exception as exc:  # putpu-lint: disable=broad-except — backend tuning must degrade to the static choice, never fail the job
                logger.warning("accel backend resolution failed (%r); "
                               "using time_stretch", exc)

    canary_info = None
    plane_search = acc.plane
    if canary:
        plane_search, c_row, c_freq = _inject_canary(acc.plane, tsamp_out)
        canary_info = {"dm_index": c_row, "freq": c_freq,
                       "recovered": False}

    if chosen_backend == "fdas":
        from .fdas import fdas_search as search_fn
    else:
        search_fn = accel_search

    def run_trials():
        t0 = time.perf_counter()
        if backend == "jax":
            try:
                fault_inject.fire("period", backend="jax")
                import jax.numpy as jnp

                return search_fn(
                    plane_search, tsamp_out, accels, jerks=jerks_axis,
                    max_harmonics=max_harmonics, fmin=fmin_eff,
                    fmax=fmax, topk=topk, xp=jnp, mesh=mesh), t0, "jax"
            except (ValueError, TypeError):
                raise
            except Exception as exc:  # jax errors share no base class — the workload's numpy floor
                logger.warning(
                    "periodicity trial dispatch failed (%r); falling "
                    "back to the host path", exc)
        # the host fallback keeps the CHOSEN formulation — both
        # backends have a pure-numpy reference path, and switching
        # formulations mid-job would change the table's float fields
        return search_fn(plane_search, tsamp_out, accels,
                         jerks=jerks_axis,
                         max_harmonics=max_harmonics, fmin=fmin_eff,
                         fmax=fmax, topk=topk, xp=np), t0, "numpy"

    # trial_backend remembers an actual fallback: the fold stage below
    # must follow the sweep off a dead device, not re-enter jax and
    # crash the job after all the accumulation+sweep work succeeded
    table, t_trials, trial_backend = run_trials()
    _metrics.counter("putpu_period_trials_total").inc(
        int(acc.ndm * len(accels) * len(jerks)))
    logger.info("periodicity trial sweep: %d DM x %d accel%s trials in "
                "%.2fs [%s]", acc.ndm, len(accels),
                f" x {len(jerks)} jerk" if len(jerks) > 1 else "",
                time.perf_counter() - t_trials, chosen_backend)

    raw = candidate_list(table, acc.trial_dms, sigma_threshold)
    _metrics.counter("putpu_period_candidates_total").inc(len(raw))

    if canary_info is not None:
        on_row = [c for c in raw
                  if abs(c["dm_index"] - canary_info["dm_index"]) <= 2]
        matched = [c for c in on_row
                   if _canary_is_recovered(c, canary_info["freq"],
                                           freq_tol)]
        canary_info["recovered"] = bool(matched)
        canary_info["best_sigma"] = max(
            (c["sigma"] for c in matched), default=0.0)
        matched = on_row  # the whole neighbourhood is excluded
        recall = 1.0 if matched else 0.0
        _metrics.gauge("putpu_period_canary_recall").set(recall)
        if health is not None:
            health.update("periodicity", canary={"injected": 1,
                                                 "window_recall": recall})
        if not matched:
            logger.error(
                "PERIODIC CANARY MISSED: injected pulsar at DM row %d, "
                "f=%.4f Hz not recovered by the trial search",
                canary_info["dm_index"], canary_info["freq"])
        raw = [c for c in raw if c not in matched]

    zap_obj = zap if isinstance(zap, ZapList) else (
        ZapList.load(zap_path) if zap_path else zap)
    kept, sift_stats = sift_candidates(raw, zap=zap_obj,
                                       freq_tol=freq_tol)
    fold_xp = np
    if trial_backend == "jax":
        import jax.numpy as fold_xp  # noqa: F811
    fold_candidates(acc, kept, nbin=nbin, xp=fold_xp)

    meta = {"fname": os.path.abspath(str(fname)),
            "fingerprint": sp["fingerprint"],
            "dmmin": float(dmmin), "dmmax": float(dmmax),
            "accel_max": float(accel_max), "n_accel": len(accels),
            "jerk_max": float(jerk_max), "n_jerk": len(jerks),
            "accel_backend": chosen_backend,
            "rebin": acc.rebin, "tsamp": acc.tsamp, "nout": acc.nout,
            "sigma_threshold": float(sigma_threshold),
            "max_harmonics": int(max_harmonics),
            "sift": sift_stats,
            "quarantined_chunks": sorted(int(c) for c in quarantined),
            "canary": canary_info}
    cands_path = os.path.join(
        output_dir, f"period_cands_{sp['root']}_{sp['fingerprint']}.npz")
    # the candidates artifact gets the SAME epoch fence as the
    # single-pulse npz (ISSUE 15): a periodicity unit is the whole
    # observation, so a partitioned zombie finishing a long sweep
    # after its lease was stolen is the likeliest clobber of all.
    # store carries the lease's fence= (threaded through the
    # accumulation transport above); fence-off runs write directly.
    if not store.fenced_write(cands_path,
                              lambda: save_candidates(cands_path, kept,
                                                      meta=meta)):
        logger.warning(
            "periodicity candidates write fenced off: %s is stamped "
            "with a higher lease epoch (this session's lease was "
            "stolen; the new owner's artifact stands)", cands_path)
    _metrics.counter("putpu_period_jobs_total").inc()

    summary = {
        "n_dm": acc.ndm, "n_accel": len(accels), "n_jerk": len(jerks),
        "accel_backend": chosen_backend, "nout": acc.nout,
        "rebin": acc.rebin, "tsamp": acc.tsamp,
        "t_obs_s": round(acc.nout * acc.tsamp, 3),
        "raw_candidates": sift_stats["in"],
        "kept": sift_stats["kept"],
        "rejected": sift_stats["rejected"],
        "canary": canary_info,
        "top": [{k: c[k] for k in ("dm", "accel", "jerk", "freq",
                                   "sigma", "nharm")}
                for c in kept[:5]],
    }
    logger.info("PERIOD_JSON %s", json.dumps(summary, default=float))
    if kept:
        best = kept[0]
        logger.info(
            "periodicity: best candidate f=%.6f Hz (P=%.6f s) DM=%.2f "
            "accel=%.2f m/s^2 sigma=%.1f nharm=%d", best["freq"],
            1.0 / best["freq"], best["dm"], best["accel"],
            best["sigma"], best["nharm"])
    else:
        logger.info("periodicity: no candidates above sigma %.1f",
                    float(sigma_threshold))

    if report_out:
        from ..obs import report as obs_report

        try:  # observability must never take down the job
            obs_report.write_report(
                str(report_out),
                meta={"root": sp["root"], "workload": "periodicity",
                      "fname": os.path.abspath(str(fname)),
                      "fingerprint": sp["fingerprint"]},
                periodicity=dict(summary,
                                 candidates=[
                                     {k: c.get(k) for k in
                                      ("dm", "accel", "jerk", "freq",
                                       "freq_refined", "sigma", "nharm",
                                       "h", "m")}
                                     for c in kept]),
                health=health.snapshot() if health is not None else None,
                metrics=_metrics.REGISTRY.snapshot())
        except Exception as exc:
            logger.warning("periodicity report failed (%r); job result "
                           "is unaffected", exc)

    return {"complete": True, "candidates": kept, "sift": sift_stats,
            "table": table, "accumulator": acc, "accels": accels,
            "jerks": jerks, "accel_backend": chosen_backend,
            "fingerprint": sp["fingerprint"],
            "candidates_path": cands_path, "snapshot_path": snap_path,
            "canary": canary_info, "hits": hits, "store": store}
