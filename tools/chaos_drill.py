"""Chaos drill: the survey loop's failure policy, proven end-to-end.

Runs the full ``search_by_chunks`` survey (small synthetic file, CPU)
under a fault matrix — every fault class from
:mod:`pulsarutils_tpu.faults.inject` x recoverable/unrecoverable — and
asserts the contracts ``docs/robustness.md`` documents:

* every **recoverable** class (transient dispatch error, bounded hang,
  transient persist error, transient read error, sanitizable NaN chunk,
  dead channels, torn ledger at resume) completes with candidates and
  ledger **byte-identical** to the fault-free baseline run (candidate
  npz files are compared member-by-member on raw array bytes — zip
  timestamps are the only allowed difference);
* every **unrecoverable** class (hard-corrupt chunk, truncated read,
  persist dead-letter) completes the run with the affected chunks
  recorded in the quarantine manifest + marked done-with-reason in the
  ledger, the *unaffected* chunks' outputs still byte-identical, and
  the integrity audit reporting zero inconsistencies;
* the **health engine** (ISSUE 5) sees every run: the fault-free
  baseline and every recoverable class must end OK, every
  unrecoverable class must reach DEGRADED/CRITICAL while the fault is
  live and — when clean chunks follow the last affected one — recover
  back to OK.  Each class's verdict transitions land in the drill
  record (``classes.<name>.health.transitions``);
* the **fleet control plane** (ISSUE 15) survives its own failure
  matrix: ``killed_coordinator`` (journal replay + ledger re-derive +
  epoch-fenced re-steal), ``partitioned_worker`` (a zombie computing
  through a steal has its late artifact writes fenced and its
  completion stale-rejected, audit clean) and ``torn_journal`` (torn
  tail truncated to a ``.corrupt`` backup) all finish byte-identical
  to the baseline;
* the **alert fan-out** (ISSUE 18) is wedge-proof: ``dead_subscriber``
  runs the survey with push armed at a webhook that accepts but never
  answers — every delivery dead-letters, the bounded queue
  drops-oldest, health flags ``push`` DEGRADED then resolves at close,
  and the survey outputs stay byte-identical;
* the **live ingest frontend** (ISSUE 19) contains every feed-failure
  mode: ``lossy_feed`` (drop/corrupt/reorder/duplicate — sub-threshold
  loss sanitized byte-exactly, heavy loss quarantined as ``feed_gap``),
  ``disconnected_feed`` (torn TCP connection re-established, all
  chunks byte-identical to disk) and ``overrun_feed`` (wedged search:
  the socket reader never blocks, oldest chunks shed as
  ``shed_overrun``, sustained overrun reaches CRITICAL) — each class
  ends with the quarantine manifest mirroring the ingest ledger's
  journal exactly and **zero unaccounted samples**;
* the **capacity advice engine** (ISSUE 20) reads load in both
  directions: ``starved_fleet`` (more worker capacity than work —
  the ``/fleet/capacity`` advice scales **down**) and
  ``saturated_fleet`` (backlog growing under busy workers — advice
  scales **up**, the ``fleet_saturated`` condition flashes DEGRADED
  and decays back to OK at drain), both with survey outputs
  byte-identical to the capacity-off baseline.

The same matrix runs as a ``slow``+``chaos`` pytest in
``tests/test_faults.py``.

Usage: JAX_PLATFORMS=cpu python tools/chaos_drill.py [--out drill.json]
"""

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TSAMP = 0.0005
NCHAN = 64
NSAMPLES = 32768
CHUNK_LEN_S = 8192 * TSAMP
DM = 150.0
PULSE_T = 20000
#: chunk starts for this geometry (step 16384, hop 8192); the pulse
#: (and its ~230-sample dispersed track) lives entirely in the two
#: overlapping chunks starting at 8192/16384 — chunk 0 is pure noise,
#: so corruption injected there must not change the candidate set
NOISE_CHUNK = 0
CHUNKS = (0, 8192, 16384)
#: the two overlapping chunks that contain the pulse — the only ones
#: that persist a candidate, hence the only ones a persist dead-letter
#: can affect
HIT_CHUNKS = (8192, 16384)

#: snr_threshold 6.5, not the reference 6.0: this geometry's noise
#: ceiling grazes 6.0 (chunk 0 produced a marginal 6.02 noise
#: "candidate"), and the drill needs its noise chunk genuinely
#: candidate-free so corruption injected there cannot perturb a
#: borderline detection — the byte-identical contract is about failure
#: handling, not about pinning noise-floor coin flips
SEARCH_KW = dict(dmmin=100, dmmax=200, backend="jax",
                 chunk_length=CHUNK_LEN_S, make_plots=False,
                 progress=False, snr_threshold=6.5)


def make_survey_file(path):
    """Deterministic small survey: noise + ONE bright dispersed pulse."""
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array

    rng = np.random.default_rng(0)
    array = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    array[:, PULSE_T] += 4.0
    array = disperse_array(array, DM, 1200., 200., TSAMP)
    sim_header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
                  "nsamples": NSAMPLES, "tsamp": TSAMP,
                  "foff": 200. / NCHAN}
    write_simulated_filterbank(path, array, sim_header, descending=True)
    return path


def run_search(path, outdir, plan=None, **kw):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    params = dict(SEARCH_KW, output_dir=outdir, **kw)
    ctx = plan.armed() if plan is not None else contextlib.nullcontext()
    with ctx:
        return search_by_chunks(path, **params)


def _health_record(engine):
    """Condense a run's HealthEngine into the drill record: every
    verdict transition, the worst verdict reached, and the final one."""
    rank = {"OK": 0, "DEGRADED": 1, "CRITICAL": 2}
    transitions = [
        {"chunk": t["chunk"], "from": t["from"], "to": t["to"],
         "reasons": t["reasons"]} for t in engine.transitions]
    worst = "OK"
    for t in transitions:
        if rank[t["to"]] > rank[worst]:
            worst = t["to"]
    return {"transitions": transitions, "worst": worst,
            "final": engine.verdict}


def snapshot_outputs(outdir, fingerprint):
    """Byte-level snapshot of a run's durable outputs.

    The ledger is raw file bytes.  Candidate npz files are snapshotted
    member-by-member (name, dtype, shape, raw array bytes): the zip
    container embeds write timestamps, so whole-file byte comparison
    would be flaky by construction while the *content* comparison is
    exact.
    """
    ledger_path = os.path.join(outdir, f"progress_{fingerprint}.json")
    with open(ledger_path, "rb") as f:
        ledger = f.read()
    cands = {}
    for name in sorted(os.listdir(outdir)):
        if not name.endswith(".npz"):
            continue
        with np.load(os.path.join(outdir, name),
                     allow_pickle=False) as data:
            cands[name] = {k: (str(data[k].dtype), data[k].shape,
                               data[k].tobytes()) for k in data.files}
    return {"ledger": ledger, "cands": cands}


def diff_outputs(base, fresh, ignore_ledger=False):
    """Human-readable list of differences (empty = byte-identical)."""
    diffs = []
    if not ignore_ledger and base["ledger"] != fresh["ledger"]:
        diffs.append(f"ledger bytes differ: {base['ledger']!r} != "
                     f"{fresh['ledger']!r}")
    missing = set(base["cands"]) - set(fresh["cands"])
    extra = set(fresh["cands"]) - set(base["cands"])
    if missing:
        diffs.append(f"candidate files missing: {sorted(missing)}")
    if extra:
        diffs.append(f"unexpected candidate files: {sorted(extra)}")
    for name in sorted(set(base["cands"]) & set(fresh["cands"])):
        b, f = base["cands"][name], fresh["cands"][name]
        if set(b) != set(f):
            diffs.append(f"{name}: member sets differ")
            continue
        for k in sorted(b):
            if b[k] != f[k]:
                diffs.append(f"{name}:{k}: bytes differ")
    return diffs


def _fault_classes():
    """The drill matrix: name -> (recoverable, plan specs, extra search
    kwargs, affected chunks for unrecoverable classes)."""
    from pulsarutils_tpu.faults.inject import FaultSpec

    return {
        # -- recoverable: outputs must be byte-identical to baseline --
        "transient_dispatch": (True, [FaultSpec(
            site="dispatch", kind="error", chunks=(8192,), times=1)],
            {}, None),
        # timeout 5s, not sub-second: the deadline must sit comfortably
        # above a LOADED machine's healthy chunk search (the baseline
        # run already warmed the jit cache, but shared CPU runners
        # stretch the search wall), or legitimate retries time out too
        # and the run stickily degrades to numpy — breaking the
        # byte-identity contract for the wrong reason (code-review r8).
        # The sub-second-bounded-hang pin lives in tests/test_faults.py.
        "transient_hang": (True, [FaultSpec(
            site="dispatch", kind="hang", seconds=30.0, chunks=(0,),
            times=1)],
            {"dispatch_timeout": 5.0, "dispatch_retries": 2,
             "dispatch_backoff": 0.01}, None),
        "transient_persist": (True, [FaultSpec(
            site="persist", kind="error", times=1)],
            {"persist_backoff": 0.01}, None),
        "transient_read": (True, [FaultSpec(
            site="read", kind="error", chunks=(8192,), times=1)],
            {}, None),
        "sanitizable_nan": (True, [FaultSpec(
            site="corrupt", kind="nan", chunks=(NOISE_CHUNK,),
            frac=0.02, times=1)],
            {}, None),
        "dead_channels": (True, [FaultSpec(
            site="corrupt", kind="dead_channels", chunks=(NOISE_CHUNK,),
            frac=0.1, times=1)],
            {}, None),
        # -- resource exhaustion (ISSUE 12): transient OOM descends the
        # degradation ladder (split trial passes) and recovers with
        # candidates byte-identical; the chunks searched AFTER the
        # descent run degraded too — the identity contract covers them
        "oom_transient": (True, [FaultSpec(
            site="dispatch", kind="oom", chunks=(NOISE_CHUNK,),
            times=1)],
            {}, None),
        # -- unrecoverable: contained, quarantined, audited ------------
        # persistent floor-OOM (ISSUE 12): every device rung OOMs AND
        # the numpy reliability floor itself raises MemoryError (the
        # "host" site) — the chunk must land in the quarantine
        # manifest as oom_floor with the audit clean, never wedge or
        # kill the survey
        "oom_floor": (False, [
            FaultSpec(site="dispatch", kind="oom", chunks=(NOISE_CHUNK,),
                      times=None),
            FaultSpec(site="host", kind="oom", chunks=(NOISE_CHUNK,),
                      times=None)],
            {}, {NOISE_CHUNK}),
        "hard_corrupt": (False, [FaultSpec(
            site="corrupt", kind="nan", chunks=(NOISE_CHUNK,), frac=0.9,
            times=1)],
            {}, {NOISE_CHUNK}),
        "truncated_read": (False, [FaultSpec(
            site="read", kind="truncate", chunks=(NOISE_CHUNK,),
            frac=0.5, times=3)],
            {}, {NOISE_CHUNK}),
        "dead_letter": (False, [FaultSpec(
            site="persist", kind="error", times=None)],
            {"persist_backoff": 0.01}, set(HIT_CHUNKS)),
    }


def run_drill(log=print, workdir=None, keep=False):
    """Run the whole matrix; returns the result record."""
    from pulsarutils_tpu.faults.audit import audit_run
    from pulsarutils_tpu.faults.inject import FaultPlan
    from pulsarutils_tpu.obs.health import HealthEngine
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    t_start = time.time()
    base_dir = workdir or tempfile.mkdtemp(prefix="chaos_drill_")
    os.makedirs(base_dir, exist_ok=True)
    path = os.path.join(base_dir, "survey.fil")
    make_survey_file(path)
    # warm the bad-channel cache BEFORE any plan is armed: its streaming
    # scan shares the reader seam, and the drill targets search chunks,
    # not the scan's blocks
    get_bad_chans(path)

    log("chaos drill: fault-free baseline run")
    base_engine = HealthEngine()
    hits, store = run_search(path, os.path.join(base_dir, "baseline"),
                             health=base_engine)
    assert base_engine.verdict == "OK", (
        f"health engine flagged the fault-free baseline run: "
        f"{base_engine.snapshot()}")
    fingerprint = store.fingerprint
    assert hits, "baseline run found no candidates — drill is vacuous"
    assert any(lo <= PULSE_T < hi for lo, hi, _, _ in hits)
    baseline = snapshot_outputs(os.path.join(base_dir, "baseline"),
                                fingerprint)

    classes = {}
    for name, (recoverable, specs, kw, affected) in _fault_classes().items():
        outdir = os.path.join(base_dir, name)
        plan = FaultPlan(specs)
        engine = HealthEngine()
        log(f"chaos drill: class {name} "
            f"({'recoverable' if recoverable else 'unrecoverable'})")
        t0 = time.time()
        hits_f, store_f = run_search(path, outdir, plan=plan,
                                     health=engine, **kw)
        fresh = snapshot_outputs(outdir, fingerprint)
        rec = {"recoverable": recoverable, "fired": plan.fired(),
               "hits": len(hits_f), "wall_s": round(time.time() - t0, 2),
               "health": _health_record(engine)}
        if recoverable:
            diffs = diff_outputs(baseline, fresh)
            rec["byte_identical"] = not diffs
            rec["diffs"] = diffs
            # a transient fault must not leave the run flagged: whatever
            # flashed during containment, the engine ends the run OK
            rec["health_ok"] = rec["health"]["final"] == "OK"
            rec["ok"] = (bool(plan.fired()) and not diffs
                         and rec["health_ok"])
        else:
            report = audit_run(outdir, fingerprint, root="survey")
            quarantined = {int(k) for k in
                           store_f.quarantined_chunks}
            rec["quarantined"] = sorted(quarantined)
            rec["audit_ok"] = report["ok"]
            rec["audit_issues"] = report["issues"]
            # the unaffected chunks' outputs must still match baseline
            sub_base = {"ledger": b"", "cands": {
                n: v for n, v in baseline["cands"].items()
                if not any(f"_{c}-" in n for c in affected)}}
            sub_fresh = {"ledger": b"", "cands": {
                n: v for n, v in fresh["cands"].items()
                if not any(f"_{c}-" in n for c in affected)}}
            diffs = diff_outputs(sub_base, sub_fresh, ignore_ledger=True)
            rec["diffs"] = diffs
            # the health engine must SEE every unrecoverable class
            # (DEGRADED or CRITICAL at some point), and — when the
            # fault's last affected chunk precedes the end of the run —
            # recover back to OK with clean chunks behind it
            recovery_due = max(affected) < CHUNKS[-1]
            rec["health_ok"] = (rec["health"]["worst"]
                                in ("DEGRADED", "CRITICAL")
                                and (rec["health"]["final"] == "OK"
                                     or not recovery_due))
            rec["ok"] = (bool(plan.fired()) and report["ok"]
                         and affected <= quarantined and not diffs
                         and rec["health_ok"])
        classes[name] = rec
        log(f"chaos drill: class {name}: "
            f"{'PASS' if rec['ok'] else 'FAIL ' + str(rec)}")

    # periodicity workload (ISSUE 13): a transient device fault during
    # full-observation accumulation, plus an interrupt-and-resume,
    # must both leave the periodicity candidate artifact byte-identical
    # to the fault-free job — the ledger records chunk completion and
    # the accumulator snapshot advances in lockstep with it
    log("chaos drill: class period_accumulation (recoverable)")
    classes["period_accumulation"] = run_period_class(base_dir, log)
    log(f"chaos drill: class period_accumulation: "
        f"{'PASS' if classes['period_accumulation']['ok'] else 'FAIL'}")

    # torn ledger at resume: no FaultPlan — the fault is a truncated
    # progress file between two resumed sessions
    log("chaos drill: class torn_ledger (recoverable)")
    outdir = os.path.join(base_dir, "torn_ledger")
    t0 = time.time()
    run_search(path, outdir, max_chunks=2)
    ledger_path = os.path.join(outdir, f"progress_{fingerprint}.json")
    with open(ledger_path, "rb") as f:
        blob = f.read()
    with open(ledger_path, "wb") as f:
        f.write(blob[: len(blob) // 2])  # torn mid-file
    hits_t, _ = run_search(path, outdir)
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    classes["torn_ledger"] = {
        "recoverable": True, "fired": 1, "hits": len(hits_t),
        "wall_s": round(time.time() - t0, 2),
        "byte_identical": not diffs, "diffs": diffs,
        "backup_kept": os.path.exists(ledger_path + ".corrupt"),
        "ok": not diffs and os.path.exists(ledger_path + ".corrupt")}
    log(f"chaos drill: class torn_ledger: "
        f"{'PASS' if classes['torn_ledger']['ok'] else 'FAIL'}")

    # coordinator-crash / partition classes (ISSUE 15): the fleet
    # control plane under the same byte-identity contract
    for name, fn in (("killed_coordinator", run_killed_coordinator_class),
                     ("partitioned_worker", run_partitioned_worker_class),
                     ("torn_journal", run_torn_journal_class)):
        log(f"chaos drill: class {name} (recoverable)")
        classes[name] = fn(base_dir, path, baseline, fingerprint, log)
        log(f"chaos drill: class {name}: "
            f"{'PASS' if classes[name]['ok'] else 'FAIL ' + str(classes[name])}")

    # wedged alert subscriber (ISSUE 18): candidate push fan-out under a
    # dead endpoint — the driver must never stall, outputs stay
    # byte-identical, and the drops land in the dead-letter journal
    log("chaos drill: class dead_subscriber (recoverable)")
    classes["dead_subscriber"] = run_dead_subscriber_class(
        base_dir, path, baseline, fingerprint, log)
    log(f"chaos drill: class dead_subscriber: "
        f"{'PASS' if classes['dead_subscriber']['ok'] else 'FAIL ' + str(classes['dead_subscriber'])}")

    # live ingest frontend (ISSUE 19): the feed-failure containment
    # matrix — loss accounted, disconnects survived byte-identical,
    # overrun shed bounded — each ending with zero unaccounted samples
    for name, fn in (("lossy_feed", run_lossy_feed_class),
                     ("disconnected_feed", run_disconnected_feed_class),
                     ("overrun_feed", run_overrun_feed_class)):
        log(f"chaos drill: class {name}")
        classes[name] = fn(base_dir, path, baseline, fingerprint, log)
        log(f"chaos drill: class {name}: "
            f"{'PASS' if classes[name]['ok'] else 'FAIL ' + str(classes[name])}")

    # fleet capacity observability (ISSUE 20): the scaling-advice
    # engine must read synthetic load in BOTH directions — starved
    # scales down, saturated scales up with fleet_saturated flashing
    # DEGRADED then decaying — and capacity-armed runs stay
    # byte-identical (observability, never policy)
    for name, fn in (("starved_fleet", run_starved_fleet_class),
                     ("saturated_fleet", run_saturated_fleet_class)):
        log(f"chaos drill: class {name} (recoverable)")
        classes[name] = fn(base_dir, path, baseline, fingerprint, log)
        log(f"chaos drill: class {name}: "
            f"{'PASS' if classes[name]['ok'] else 'FAIL ' + str(classes[name])}")

    recovered = sum(1 for r in classes.values()
                    if r["recoverable"] and r["ok"])
    contained = sum(1 for r in classes.values()
                    if not r["recoverable"] and r["ok"])
    result = {
        "survey": {"nchan": NCHAN, "nsamples": NSAMPLES,
                   "chunks": list(CHUNKS), "pulse_dm": DM},
        "n_classes": len(classes),
        "recovered_identical": recovered,
        "contained": contained,
        "health_ok": all(r.get("health_ok", True)
                         for r in classes.values()),
        "all_ok": all(r["ok"] for r in classes.values()),
        "classes": classes,
        "wall_s": round(time.time() - t_start, 2),
    }
    if not keep and workdir is None:
        shutil.rmtree(base_dir, ignore_errors=True)
    return result


# ---------------------------------------------------------------------------
# coordinator-crash / partition chaos classes (ISSUE 15)
# ---------------------------------------------------------------------------

#: strip the driver-session knobs off SEARCH_KW: leases carry only the
#: protocol whitelist
_FLEET_CONFIG_KEYS = ("make_plots", "progress")


def _fleet_config():
    return {k: v for k, v in SEARCH_KW.items()
            if k not in _FLEET_CONFIG_KEYS}


def _drain_after_first(worker):
    """Wrap a worker's unit runner to drain after its first unit — the
    deterministic 'mid-survey' state every crash class needs."""
    orig = worker._run_unit

    def wrapped(lease):
        result = orig(lease)
        worker.drain()
        return result

    worker._run_unit = wrapped


def run_killed_coordinator_class(base_dir, path, baseline, fingerprint,
                                 log=print):
    """**killed_coordinator**: one unit completes, one lease is left in
    flight, then the coordinator is killed (its in-memory state
    dropped — every journal record was already flushed at append, so
    this is exactly what a SIGKILL leaves behind).  ``recover()``
    replays the journal, re-derives outstanding units from the
    ledgers, re-steals the stranded lease under a bumped epoch, and a
    fresh worker finishes the survey byte-identical to the
    uninterrupted baseline."""
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs.server import start_obs_server

    outdir = os.path.join(base_dir, "killed_coordinator")
    t0 = time.time()
    first = FleetCoordinator(outdir, lease_ttl_s=60.0,
                             chunks_per_unit=1, auto_sweep=False)
    server = start_obs_server(0, fleet=first)
    first.add_survey([path], **_fleet_config())
    worker = FleetWorker(f"http://127.0.0.1:{server.port}",
                         http_port=None)
    _drain_after_first(worker)
    worker.run()
    ghost = first.register({})["worker"]
    stranded = first.lease({"worker": ghost, "max_units": 1})["leases"]
    server.close()
    first.close()
    del first      # the kill: nothing beyond the journal survives

    second = FleetCoordinator.recover(outdir, lease_ttl_s=60.0,
                                      chunks_per_unit=1,
                                      auto_sweep=False)
    # the stranded lease was re-stolen with a bumped fencing epoch
    restolen = [u for u in second._units.values()
                if stranded and u.id == stranded[0]["unit"]]
    epoch_bumped = bool(restolen) and stranded \
        and restolen[0].epoch > stranded[0]["epoch"]
    server2 = start_obs_server(0, fleet=second)
    finisher = FleetWorker(f"http://127.0.0.1:{server2.port}",
                           http_port=None)
    finisher.run(max_idle_s=60.0)
    done = second.survey_done
    server2.close()
    second.close()
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    return {"recoverable": True, "fired": 1,
            "units_before_kill": worker.units_done,
            "stranded_leases": len(stranded),
            "epoch_bumped": bool(epoch_bumped),
            "survey_done": done,
            "byte_identical": not diffs, "diffs": diffs,
            "wall_s": round(time.time() - t0, 2),
            "ok": (done and not diffs and bool(stranded)
                   and bool(epoch_bumped)
                   and worker.units_done == 1)}


def run_partitioned_worker_class(base_dir, path, baseline, fingerprint,
                                 log=print):
    """**partitioned_worker**: a zombie worker hangs mid-dispatch far
    past its lease TTL (the compute side of a partition: it keeps
    working while unreachable), the unit is stolen and finished at a
    bumped epoch, and when the zombie wakes its late artifact writes
    are rejected by the epoch fence, its completion is rejected as
    stale, and the audit shows zero inconsistencies — with the survey
    output byte-identical to the baseline."""
    from pulsarutils_tpu.faults.audit import audit_run
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs import metrics as obs_metrics
    from pulsarutils_tpu.obs.server import start_obs_server

    outdir = os.path.join(base_dir, "partitioned_worker")
    t0 = time.time()
    fenced_before = obs_metrics.counter(
        "putpu_fleet_fenced_writes_total").value
    # the zombie wedges inside the HIT chunk's dispatch: after the
    # steal it will still compute the chunk and try to persist the
    # candidate — the exact write the fence exists to reject
    plan = FaultPlan([FaultSpec(site="dispatch", kind="hang",
                                seconds=10.0, chunks=(HIT_CHUNKS[0],),
                                times=1)])
    coordinator = FleetCoordinator(outdir, lease_ttl_s=2.5,
                                   chunks_per_unit=1,
                                   probe_interval_s=0.25)
    server = start_obs_server(0, fleet=coordinator)
    url = f"http://127.0.0.1:{server.port}"
    coordinator.add_survey([path], **_fleet_config())
    try:
        import threading

        with plan.armed():
            zombie = FleetWorker(url, http_port=None, max_units=1)
            zt = threading.Thread(target=zombie.run,
                                  kwargs={"max_idle_s": 60.0})
            zt.start()
            stolen = _wait_for(
                lambda: coordinator.progress_doc()["stats"]["expired"]
                >= 1, timeout_s=60)
            rescuer = FleetWorker(url, http_port=None)
            rescuer.run(max_idle_s=30.0)
            zt.join(timeout=120.0)
        done = coordinator.survey_done
        stats = coordinator.progress_doc()["stats"]
    finally:
        server.close()
        coordinator.close()
    fenced = obs_metrics.counter(
        "putpu_fleet_fenced_writes_total").value - fenced_before
    audit = audit_run(outdir, fingerprint, root="survey")
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    return {"recoverable": True, "fired": plan.fired(),
            "stolen": stolen, "survey_done": done,
            "fenced_writes": int(fenced),
            "stale_epochs": stats["stale_epochs"],
            "audit_ok": audit["ok"], "audit_issues": audit["issues"],
            "byte_identical": not diffs, "diffs": diffs,
            "wall_s": round(time.time() - t0, 2),
            "ok": (bool(plan.fired()) and stolen and done and not diffs
                   and fenced >= 1 and stats["stale_epochs"] >= 1
                   and audit["ok"])}


def run_torn_journal_class(base_dir, path, baseline, fingerprint,
                           log=print):
    """**torn_journal**: the coordinator dies AND its final journal
    append was torn mid-line.  Replay truncates the tail to a
    ``.corrupt`` backup and recovers from the good prefix + the
    ledgers; the survey still finishes byte-identical."""
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.journal import JOURNAL_NAME
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs.server import start_obs_server

    outdir = os.path.join(base_dir, "torn_journal")
    t0 = time.time()
    first = FleetCoordinator(outdir, lease_ttl_s=60.0,
                             chunks_per_unit=1, auto_sweep=False)
    server = start_obs_server(0, fleet=first)
    first.add_survey([path], **_fleet_config())
    worker = FleetWorker(f"http://127.0.0.1:{server.port}",
                         http_port=None)
    _drain_after_first(worker)
    worker.run()
    server.close()
    first.close()
    del first
    journal_path = os.path.join(outdir, JOURNAL_NAME)
    with open(journal_path, "rb") as f:
        blob = f.read()
    with open(journal_path, "wb") as f:
        f.write(blob[: len(blob) - 7])   # torn mid-append
    second = FleetCoordinator.recover(outdir, lease_ttl_s=60.0,
                                      chunks_per_unit=1,
                                      auto_sweep=False)
    backup_kept = os.path.exists(journal_path + ".corrupt")
    server2 = start_obs_server(0, fleet=second)
    finisher = FleetWorker(f"http://127.0.0.1:{server2.port}",
                           http_port=None)
    finisher.run(max_idle_s=60.0)
    done = second.survey_done
    server2.close()
    second.close()
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    return {"recoverable": True, "fired": 1, "backup_kept": backup_kept,
            "survey_done": done,
            "byte_identical": not diffs, "diffs": diffs,
            "wall_s": round(time.time() - t0, 2),
            "ok": done and not diffs and backup_kept}


# ---------------------------------------------------------------------------
# alert fan-out chaos class (ISSUE 18)
# ---------------------------------------------------------------------------


def run_dead_subscriber_class(base_dir, path, baseline, fingerprint,
                              log=print):
    """**dead_subscriber**: an armed push subscriber accepts the TCP
    connection but never answers.  Every delivery times out onto the
    dead-letter journal, the 1-slot broker queue drops-oldest when
    detections keep arriving, the health engine flags ``push`` DEGRADED
    and resolves it at close — and the survey's durable outputs stay
    byte-identical to the fault-free baseline: a wedged alert endpoint
    can never stall or perturb the search itself."""
    import http.server
    import threading

    from pulsarutils_tpu.obs.health import HealthEngine
    from pulsarutils_tpu.obs.push import AlertBroker

    outdir = os.path.join(base_dir, "dead_subscriber")
    os.makedirs(outdir, exist_ok=True)

    class _Hung(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            time.sleep(5.0)     # outlives every client timeout below

        def log_message(self, *a):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Hung)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_port}/hook"
    engine = HealthEngine()
    dead_letter = os.path.join(outdir, "push_dead_letter.jsonl")
    broker = AlertBroker([url], queue_max=1, timeout_s=0.5, retries=0,
                         dead_letter_path=dead_letter, health=engine)
    t0 = time.time()
    try:
        hits_f, _ = run_search(path, outdir, health=engine, push=broker)
        # three rapid publishes against a wedged worker (in-flight
        # delivery blocks 0.5 s) guarantee the 1-slot queue overflows:
        # drop-oldest must fire and land in the dead-letter journal
        for i in range(3):
            broker.publish({"kind": "candidate", "chunk": -1 - i,
                            "snr": 99.0, "fingerprint": fingerprint})
        stats = broker.close(timeout_s=3.0)
    finally:
        server.shutdown()
        server.server_close()
    wall = round(time.time() - t0, 2)
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    with open(dead_letter) as f:
        reasons = {json.loads(line).get("reason")
                   for line in f if line.strip()}
    health = _health_record(engine)
    rec = {"recoverable": True, "fired": 1, "hits": len(hits_f),
           "wall_s": wall, "byte_identical": not diffs, "diffs": diffs,
           "delivered": stats["delivered"], "dropped": stats["dropped"],
           "dead_lettered": stats["dead_lettered"],
           "dead_letter_reasons": sorted(str(r) for r in reasons),
           "health": health,
           "health_ok": (health["worst"] in ("DEGRADED", "CRITICAL")
                         and health["final"] == "OK")}
    rec["ok"] = (not diffs and stats["delivered"] == 0
                 and stats["dropped"] >= 1
                 and stats["dead_lettered"] >= len(hits_f)
                 and "dropped_oldest" in reasons and rec["health_ok"])
    return rec


# ---------------------------------------------------------------------------
# live ingest chaos classes (ISSUE 19)
# ---------------------------------------------------------------------------

#: feed geometry: non-overlapping chunks (the assembler's contract),
#: 256-sample packets -> 32 packets per 8192-sample chunk, 128 total
INGEST_STEP = 8192
INGEST_SPP = 256


def _audit_feed(manifest_path, asm):
    """The feed frontend's audit: every loss-bearing manifest record
    mirrors a ledger journal entry (both directions, exact spans) and
    the disposition axis balances.  Returns a list of issues (empty =
    clean)."""
    records = []
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            records = [json.loads(line) for line in f if line.strip()]
    man = sorted((int(r["chunk"]), int(r["end"]), r["reason"])
                 for r in records)
    led = sorted((int(r["chunk"]), int(r["end"]), r["reason"])
                 for r in asm.ledger.journal)
    issues = []
    if man != led:
        issues.append(f"manifest records != ledger journal: "
                      f"{man} != {led}")
    unaccounted = asm.ledger.unaccounted()
    if unaccounted:
        issues.append(f"{unaccounted} samples unaccounted for")
    return issues


def _feed_harness(outdir, path, plan=None, *, step=INGEST_STEP, shed=8,
                  pace_s=0.0, consume_during_feed=True, recover_after=1):
    """One feed session over the drill survey file: packetize, serve a
    TCPSource + assembler, feed under ``plan``, drain.  Returns the
    session record every feed class asserts against."""
    import threading

    from pulsarutils_tpu.faults.policy import QuarantineManifest
    from pulsarutils_tpu.ingest import ChunkAssembler, TCPSource, feed_tcp
    from pulsarutils_tpu.io.packets import packetize_array
    from pulsarutils_tpu.io.sigproc import FilterbankReader
    from pulsarutils_tpu.obs.health import HealthEngine

    os.makedirs(outdir, exist_ok=True)
    reader = FilterbankReader(path)
    wire = reader.read_block(0, reader.nsamples).astype(np.float32)
    encoded = packetize_array(wire, samples_per_packet=INGEST_SPP,
                              band_descending=reader.band_descending)
    # the assembler delivers search-ready ascending chunks whatever
    # the wire order: expectations compare against the ascending view
    block = (np.ascontiguousarray(wire[::-1])
             if reader.band_descending else wire)
    manifest = QuarantineManifest(outdir, "feed")
    health = HealthEngine(recover_after=recover_after)
    asm = ChunkAssembler(nchan=reader.nchans, step=step,
                         band_descending=reader.band_descending,
                         policy="sanitize", shed=shed,
                         manifest=manifest, health=health,
                         wait_poll_s=0.05)
    delivered = {}

    def consume():
        for istart, chunk in asm.chunks():
            delivered[istart] = np.asarray(chunk)

    consumer = threading.Thread(target=consume, daemon=True)
    ctx = plan.armed() if plan is not None else contextlib.nullcontext()
    with TCPSource(asm, port=0, idle_timeout_s=0.5) as src:
        if consume_during_feed:
            consumer.start()
        t0 = time.time()
        with ctx:
            feed_tcp(src.host, src.port, encoded, pace_s=pace_s)
        feed_wall = time.time() - t0
        # the reader drains every byte already on the wire, goes idle,
        # then flushes the assembler itself — close() after wait() is
        # a no-op shutdown, not a data race
        assert src.wait(timeout_s=60), "ingest reader failed to drain"
    # the idle flush closed the assembler; a wedged-consumer class
    # starts draining only now
    if not consume_during_feed:
        consumer.start()
    consumer.join(timeout=60)
    return {"asm": asm, "health": health, "delivered": delivered,
            "block": block, "feed_wall_s": feed_wall,
            "manifest_path": manifest.path}


def _chunks_identical(delivered, block, starts, step):
    """Byte-compare delivered chunks against the disk block."""
    bad = []
    for s in starts:
        got = delivered.get(s)
        want = np.ascontiguousarray(block[:, s:s + step])
        if got is None or got.tobytes() != want.tobytes():
            bad.append(s)
    return bad


def run_lossy_feed_class(base_dir, path, baseline, fingerprint,
                         log=print):
    """**lossy_feed**: the feed drops, corrupts, reorders and
    duplicates packets.  Sub-threshold loss is sanitized (delivered
    zero-filled, byte-exact against the disk block with the gaps
    zeroed), unrecoverable loss quarantines the chunk as ``feed_gap``,
    reorder/duplicate lose nothing — and the ledger accounts for every
    observed sample with the manifest mirroring the journal exactly."""
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec

    outdir = os.path.join(base_dir, "lossy_feed")
    t0 = time.time()
    # chunk 0 (seqs 0-31): one dropped + one CRC-corrupted packet ->
    # 512/8192 samples gap-filled, sanitized.  chunk 1 (seqs 32-63):
    # 28/32 packets dropped -> 87.5% loss > max_zero_frac 0.75 ->
    # feed_gap quarantine.  chunk 2: swap + duplicate, lossless.
    # chunk 3: untouched.
    plan = FaultPlan([
        FaultSpec(site="ingest", kind="drop", chunks=(5,), times=1),
        FaultSpec(site="ingest", kind="corrupt", chunks=(20,), times=1),
        FaultSpec(site="ingest", kind="drop",
                  chunks=tuple(range(36, 64)), times=None),
        FaultSpec(site="ingest", kind="reorder", chunks=(70,), times=1),
        FaultSpec(site="ingest", kind="duplicate", chunks=(80,),
                  times=1),
    ])
    sess = _feed_harness(outdir, path, plan)
    asm, health, block = sess["asm"], sess["health"], sess["block"]
    delivered = sess["delivered"]
    led = asm.ledger

    expected = block.copy()
    for seq in (5, 20):                       # dropped + CRC-rejected
        expected[:, seq * INGEST_SPP:(seq + 1) * INGEST_SPP] = 0.0
    sanitized_bad = _chunks_identical(
        delivered, expected, (0, 2 * INGEST_STEP, 3 * INGEST_STEP),
        INGEST_STEP)
    audit_issues = _audit_feed(sess["manifest_path"], asm)
    hrec = _health_record(health)
    rec = {"recoverable": False, "fired": plan.fired(),
           "wall_s": round(time.time() - t0, 2),
           "delivered_chunks": sorted(delivered),
           "gap_filled": led.gap_filled,
           "quarantined_samples": led.quarantined,
           "journal_reasons": sorted({r["reason"]
                                      for r in led.journal}),
           "unaccounted": led.unaccounted(),
           "audit_ok": not audit_issues, "audit_issues": audit_issues,
           "diffs": [f"chunk {s} differs" for s in sanitized_bad],
           "health": hrec,
           "health_ok": (hrec["worst"] in ("DEGRADED", "CRITICAL")
                         and hrec["final"] == "OK")}
    rec["ok"] = (bool(plan.fired()) and not audit_issues
                 and not sanitized_bad
                 and INGEST_STEP not in delivered       # quarantined
                 and led.quarantined == INGEST_STEP
                 and rec["journal_reasons"] == ["feed_gap"]
                 and led.unaccounted() == 0
                 and asm.invalid >= 1 and rec["health_ok"])
    return rec


def run_disconnected_feed_class(base_dir, path, baseline, fingerprint,
                                log=print):
    """**disconnected_feed**: the feeder's TCP connection is torn
    mid-stream and re-established.  Nothing is lost: every chunk is
    byte-identical to the disk block, the reconnect is counted and
    flagged (``feed_disconnect`` DEGRADED) and health recovers to OK
    with clean chunks behind it."""
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec

    outdir = os.path.join(base_dir, "disconnected_feed")
    t0 = time.time()
    plan = FaultPlan([FaultSpec(site="ingest", kind="disconnect",
                                chunks=(64,), times=1)])
    sess = _feed_harness(outdir, path, plan)
    asm, health = sess["asm"], sess["health"]
    bad = _chunks_identical(
        sess["delivered"], sess["block"],
        range(0, NSAMPLES, INGEST_STEP), INGEST_STEP)
    audit_issues = _audit_feed(sess["manifest_path"], asm)
    hrec = _health_record(health)
    rec = {"recoverable": True, "fired": plan.fired(),
           "wall_s": round(time.time() - t0, 2),
           "reconnects": asm.reconnects,
           "byte_identical": not bad,
           "diffs": [f"chunk {s} differs" for s in bad],
           "unaccounted": asm.ledger.unaccounted(),
           "audit_ok": not audit_issues, "audit_issues": audit_issues,
           "health": hrec,
           "health_ok": (hrec["worst"] == "DEGRADED"
                         and hrec["final"] == "OK")}
    rec["ok"] = (bool(plan.fired()) and not bad
                 and asm.reconnects == 1
                 and asm.ledger.unaccounted() == 0
                 and not audit_issues and rec["health_ok"])
    return rec


def run_overrun_feed_class(base_dir, path, baseline, fingerprint,
                           log=print):
    """**overrun_feed**: the consumer is wedged while the feed bursts.
    ``push()`` must stay bounded (the socket reader never blocks on
    search), the 2-chunk admission bound drops the OLDEST queued
    chunks journaled as ``shed_overrun``, sustained overrun reaches
    CRITICAL, and after the wedge lifts the survivors are
    byte-identical with every shed sample accounted."""

    outdir = os.path.join(base_dir, "overrun_feed")
    t0 = time.time()
    # 4096-sample chunks -> 8 chunks; a 2-chunk queue bound with a
    # wedged consumer sheds 6 of them, all journaled
    step = 4096
    sess = _feed_harness(outdir, path, plan=None, step=step, shed=2,
                         consume_during_feed=False)
    asm, health = sess["asm"], sess["health"]
    delivered = sess["delivered"]
    led = asm.ledger
    shed_chunks = sorted(r["chunk"] for r in led.journal
                         if r["reason"] == "shed_overrun")
    bad = _chunks_identical(delivered, sess["block"],
                            sorted(delivered), step)
    audit_issues = _audit_feed(sess["manifest_path"], asm)
    hrec = _health_record(health)
    rec = {"recoverable": False, "fired": len(shed_chunks),
           "wall_s": round(time.time() - t0, 2),
           "feed_wall_s": round(sess["feed_wall_s"], 3),
           "shed_chunks": shed_chunks,
           "delivered_chunks": sorted(delivered),
           "shed_samples": led.shed,
           "unaccounted": led.unaccounted(),
           "audit_ok": not audit_issues, "audit_issues": audit_issues,
           "diffs": [f"chunk {s} differs" for s in bad],
           "health": hrec,
           "health_ok": hrec["worst"] == "CRITICAL"}
    rec["ok"] = (len(shed_chunks) == 6 and not bad
                 and led.shed == 6 * step
                 and led.delivered == 2 * step
                 and led.unaccounted() == 0
                 and sess["feed_wall_s"] < 10.0      # reader never wedged
                 and not audit_issues and rec["health_ok"])
    return rec


# ---------------------------------------------------------------------------
# fleet capacity observability chaos classes (ISSUE 20)
# ---------------------------------------------------------------------------


def _get_capacity_doc(port):
    """``GET /fleet/capacity`` over real HTTP — the drill checks the
    served document, not the in-process object."""
    from urllib.request import urlopen

    with urlopen(f"http://127.0.0.1:{port}/fleet/capacity",
                 timeout=10.0) as resp:
        return json.loads(resp.read().decode())


def run_starved_fleet_class(base_dir, path, baseline, fingerprint,
                            log=print):
    """**starved_fleet**: a capacity-armed fleet with far more worker
    capacity than work.  A worker whose clocks say it spent ~300s
    polling for every few seconds of searching (the injected fault:
    idleness) reports a tiny busy fraction; with the queue drained the
    detector must classify ``starved`` and the advice at
    ``/fleet/capacity`` must point **down** — while the survey outputs
    stay byte-identical to the capacity-off baseline (capacity is
    observability, never policy)."""
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs.capacity import SaturationDetector
    from pulsarutils_tpu.obs.server import start_obs_server

    outdir = os.path.join(base_dir, "starved_fleet")
    t0 = time.time()
    coordinator = FleetCoordinator(outdir, lease_ttl_s=60.0,
                                   chunks_per_unit=1, auto_sweep=False,
                                   capacity=True)
    # drill-scale hysteresis (one sweep confirms/decays) — the same
    # time-compression every fleet class applies to lease TTLs
    coordinator.saturation = SaturationDetector(confirm=1, decay=1)
    server = start_obs_server(0, fleet=coordinator)
    url = f"http://127.0.0.1:{server.port}"
    try:
        coordinator.add_survey([path], **_fleet_config())
        worker = FleetWorker(url, http_port=None)
        # the starvation injection: the worker's own idle clock says it
        # waited ~300s for leases around its one real unit
        worker.util.note_idle(300.0)
        _drain_after_first(worker)
        worker.run()
        # park the remaining units on a ghost worker: queue depth 0
        # with leases in flight is the starved fleet's steady state
        ghost = coordinator.register({})["worker"]
        parked = coordinator.lease({"worker": ghost,
                                    "max_units": 16})["leases"]
        coordinator.sweep()
        doc = _get_capacity_doc(server.port)
        advice = doc.get("advice") or {}
        # hand the parked units back and finish the survey for real
        coordinator.release({"worker": ghost,
                             "leases": [l["lease"] for l in parked],
                             "reason": "drill"})
        finisher = FleetWorker(url, http_port=None)
        finisher.run(max_idle_s=60.0)
        done = coordinator.survey_done
    finally:
        server.close()
        coordinator.close()
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    return {"recoverable": True, "fired": 1,
            "state": doc.get("state"),
            "utilization": doc.get("utilization"),
            "advice": advice, "survey_done": done,
            "byte_identical": not diffs, "diffs": diffs,
            "wall_s": round(time.time() - t0, 2),
            "ok": (done and not diffs
                   and doc.get("enabled") is True
                   and doc.get("state") == "starved"
                   and advice.get("direction") == "down"
                   and advice.get("desired_workers", 99)
                   < doc.get("workers_alive", 0))}


def run_saturated_fleet_class(base_dir, path, baseline, fingerprint,
                              log=print):
    """**saturated_fleet**: the backlog grows while the only worker is
    flat-out busy (a second survey lands mid-run).  The detector must
    classify ``worker-bound``, the advice must point **up**, the
    ``fleet_saturated`` health condition must flash DEGRADED — and
    decay back to OK once the fleet drains, with the first survey's
    outputs byte-identical to baseline."""
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs.capacity import SaturationDetector
    from pulsarutils_tpu.obs.health import HealthEngine
    from pulsarutils_tpu.obs.server import start_obs_server
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    outdir = os.path.join(base_dir, "saturated_fleet")
    t0 = time.time()
    path2 = os.path.join(base_dir, "survey2.fil")
    if not os.path.exists(path2):
        make_survey_file(path2)
    get_bad_chans(path2)
    health = HealthEngine()
    coordinator = FleetCoordinator(outdir, lease_ttl_s=60.0,
                                   chunks_per_unit=1, auto_sweep=False,
                                   capacity=True, health=health)
    coordinator.saturation = SaturationDetector(confirm=1, decay=1)
    server = start_obs_server(0, fleet=coordinator)
    url = f"http://127.0.0.1:{server.port}"
    try:
        coordinator.add_survey([path], **_fleet_config())
        # one busy worker seeds the throughput model + a high busy
        # fraction, then drains (still registered, still alive)
        worker = FleetWorker(url, http_port=None)
        _drain_after_first(worker)
        worker.run()
        # a bystander worker keeps the fleet from reading as draining
        coordinator.register({})
        coordinator.sweep()            # depth sample 1: steady backlog
        coordinator.add_survey([path2], **_fleet_config())
        coordinator.sweep()            # depth sample 2: backlog GREW
        doc = _get_capacity_doc(server.port)
        advice = doc.get("advice") or {}
        degraded_seen = health.verdict != "OK"
        # drain it for real: a fresh worker finishes both surveys
        finisher = FleetWorker(url, http_port=None)
        finisher.run(max_idle_s=60.0)
        done = coordinator.survey_done
        coordinator.sweep()            # draining -> condition decays
        final_state = coordinator.saturation.state
        final_verdict = health.verdict
    finally:
        server.close()
        coordinator.close()
    fresh = snapshot_outputs(outdir, fingerprint)
    # survey2's candidates are real output, not drift: byte-identity is
    # pinned on the FIRST survey's artifacts (its own ledger + npz)
    fresh["cands"] = {n: v for n, v in fresh["cands"].items()
                     if not n.startswith("survey2")}
    diffs = diff_outputs(baseline, fresh)
    return {"recoverable": True, "fired": 1,
            "state": doc.get("state"),
            "advice": advice, "degraded_seen": degraded_seen,
            "final_state": final_state,
            "final_verdict": final_verdict,
            "survey_done": done,
            "byte_identical": not diffs, "diffs": diffs,
            "wall_s": round(time.time() - t0, 2),
            "ok": (done and not diffs
                   and doc.get("enabled") is True
                   and doc.get("state") == "worker-bound"
                   and advice.get("direction") == "up"
                   and advice.get("desired_workers", 0)
                   > doc.get("workers_alive", 99)
                   and degraded_seen
                   and final_state == "draining"
                   and final_verdict == "OK")}


# ---------------------------------------------------------------------------
# periodicity chaos class (ISSUE 13)
# ---------------------------------------------------------------------------

#: the periodicity drill's own pulsar file: 60 Hz accelerated pulse
#: train at DM 150 over 3 chunks (step 8192, hop 4096)
PSR_F0 = 60.0
PSR_ACCEL = 9.0e4
PSR_NSAMPLES = 16384


def make_pulsar_file(path):
    """Deterministic accelerated-pulsar survey for the periodicity
    class (a single-pulse file would make its byte-identity vacuous —
    empty candidate lists compare equal for free).  The injection
    physics lives in ONE place (``models.simulate``) shared with the
    tests."""
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import simulate_accel_pulsar_data

    arr, hdr = simulate_accel_pulsar_data(
        freq=PSR_F0, dm=DM, accel=PSR_ACCEL, tsamp=TSAMP,
        nsamples=PSR_NSAMPLES, nchan=32, rng=7)
    write_simulated_filterbank(path, arr, hdr, descending=True)
    return path


def _period_job(path, outdir, plan=None, cancel_cb=None):
    from pulsarutils_tpu.periodicity.driver import periodicity_search

    ctx = plan.armed() if plan is not None else contextlib.nullcontext()
    with ctx:
        return periodicity_search(
            path, 130, 170, accel_max=1.8e5, n_accel=5,
            sigma_threshold=8.0, chunk_length=4096 * TSAMP,
            snr_threshold=8.0, output_dir=outdir, progress=False,
            cancel_cb=cancel_cb)


def _period_cands_bytes(res):
    """The candidate artifact, member-by-member (the npz container
    embeds timestamps; content comparison is the stable one)."""
    with np.load(res["candidates_path"], allow_pickle=False) as data:
        return {k: (str(data[k].dtype), data[k].shape,
                    data[k].tobytes()) for k in data.files}


def run_period_class(base_dir, log=print):
    """The ISSUE 13 chaos class: transient fault during accumulation +
    interrupt-and-resume, candidates byte-identical both ways."""
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    t0 = time.time()
    path = os.path.join(base_dir, "pulsar.fil")
    make_pulsar_file(path)
    get_bad_chans(path)

    base = _period_job(path, os.path.join(base_dir, "period_baseline"))
    assert base["complete"] and base["candidates"], \
        "periodicity baseline found no candidates — class is vacuous"
    base_bytes = _period_cands_bytes(base)

    # leg 1: a transient device fault mid-accumulation (retried on the
    # same backend, so the accumulated plane — and every downstream
    # byte — must be identical)
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error",
                                chunks=(4096,), times=1)])
    fault = _period_job(path, os.path.join(base_dir, "period_fault"),
                        plan=plan)
    fault_ok = (bool(plan.fired()) and fault["complete"]
                and _period_cands_bytes(fault) == base_bytes)

    # leg 2: interrupt after the first chunk, then resume — the ledger
    # + accumulator snapshot must hand the resumed session exactly the
    # remaining chunks and identical final bytes
    outdir = os.path.join(base_dir, "period_resume")
    seen = []

    def cancel_after_one():
        return len(seen) >= 1

    from pulsarutils_tpu.periodicity.driver import periodicity_search

    partial = periodicity_search(
        path, 130, 170, accel_max=1.8e5, n_accel=5,
        sigma_threshold=8.0, chunk_length=4096 * TSAMP,
        snr_threshold=8.0, output_dir=outdir, progress=False,
        cancel_cb=cancel_after_one, chunk_cb=seen.append)
    resumed = _period_job(path, outdir)
    resume_ok = (not partial["complete"] and resumed["complete"]
                 and _period_cands_bytes(resumed) == base_bytes)

    rec = {"recoverable": True, "fired": plan.fired(),
           "hits": len(base["candidates"]),
           "wall_s": round(time.time() - t0, 2),
           "byte_identical": fault_ok and resume_ok,
           "fault_leg_ok": fault_ok, "resume_leg_ok": resume_ok,
           "partial_chunks": len(seen),
           "best": {k: base["candidates"][0][k]
                    for k in ("dm", "accel", "freq", "sigma")},
           "ok": fault_ok and resume_ok}
    return rec


# ---------------------------------------------------------------------------
# fleet chaos classes (ISSUE 9): killed and wedged workers
# ---------------------------------------------------------------------------

#: how long the wedge fault hangs a worker at the fleet seam — must sit
#: far past the drill's lease TTL so the steal (not the wedged worker
#: waking up mid-drill) is what finishes the unit
WEDGE_S = 300.0
FLEET_LEASE_TTL_S = 6.0


def _spawn_worker_proc(base_dir, url, worker_id, fault_plan=None):
    """A real worker OS process (``python -m ...cli.fleet_main worker``)
    — the only honest way to SIGKILL one.  ``fault_plan`` rides the
    ``PUTPU_FAULT_PLAN`` env var across the process boundary (the PR 4
    mechanism), so the drill can wedge a worker deterministically."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    if fault_plan is not None:
        env["PUTPU_FAULT_PLAN"] = fault_plan.to_json()
    log_path = os.path.join(base_dir, f"worker_{worker_id}.log")
    logf = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pulsarutils_tpu.cli.fleet_main",
         "worker", "--coordinator", url, "--worker-id", worker_id,
         "--max-idle", "60"],
        env=env, cwd=repo, stdout=logf, stderr=logf)
    proc._drill_logf = logf  # closed by _reap
    return proc


def _reap(proc, kill=True):
    if proc.poll() is None and kill:
        proc.kill()
    try:
        proc.wait(timeout=30)
    finally:
        proc._drill_logf.close()


def _wait_for(predicate, timeout_s, interval_s=0.2):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def _fleet_class(name, base_dir, path, baseline, fingerprint, log,
                 kill_after_lease):
    """One fleet chaos class over the drill survey file.

    ``kill_after_lease=True`` is the **killed_worker** class: the
    victim subprocess is SIGKILLed while it holds a lease (it is wedged
    at the fleet seam pre-search, so nothing is marked); ``False`` is
    **wedged_worker**: the victim stays alive but hung far past the
    lease TTL, so the coordinator must steal from it.  Either way a
    healthy in-process worker finishes the survey and the outputs must
    be byte-identical to the single-process baseline.
    """
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.obs.server import start_obs_server

    outdir = os.path.join(base_dir, name)
    t0 = time.time()
    coordinator = FleetCoordinator(
        outdir, lease_ttl_s=FLEET_LEASE_TTL_S, chunks_per_unit=1,
        probe_interval_s=0.5, auto_sweep=True)
    server = start_obs_server(0, fleet=coordinator)
    url = f"http://127.0.0.1:{server.port}"
    coordinator.add_survey([path], **{k: v for k, v in SEARCH_KW.items()
                                      if k not in ("make_plots",
                                                   "progress")})
    # the victim wedges at the fleet seam before its first unit's
    # search starts — deterministic "mid-lease" state for the kill
    plan = FaultPlan([FaultSpec(site="fleet", kind="hang",
                                seconds=WEDGE_S, times=1)])
    victim = _spawn_worker_proc(base_dir, url, f"victim-{name}",
                                fault_plan=plan)
    rec = {"recoverable": True}
    try:
        leased = _wait_for(
            lambda: coordinator.leases_doc()["leases"], timeout_s=120)
        rec["victim_leased"] = leased
        if kill_after_lease:
            victim.kill()      # SIGKILL: no drain, no release, nothing
            log(f"chaos drill: {name}: victim SIGKILLed holding "
                f"{len(coordinator.leases_doc()['leases'])} lease(s)")
        rescuer = FleetWorker(url, http_port=None)
        rescuer.run(max_idle_s=90)
        done = _wait_for(lambda: coordinator.survey_done, timeout_s=60)
        rec["survey_done"] = done
    finally:
        _reap(victim)
        server.close()
        coordinator.close()
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    stats = coordinator.progress_doc()["stats"]
    rec.update({
        "byte_identical": not diffs, "diffs": diffs,
        "stolen_leases": stats["expired"] + stats["revoked"],
        "stats": stats, "wall_s": round(time.time() - t0, 2),
        "ok": (rec.get("victim_leased", False) and rec["survey_done"]
               and not diffs
               and stats["expired"] + stats["revoked"] >= 1)})
    return rec


def _fleet_oom_class(base_dir, path, baseline, fingerprint, log):
    """**oom_worker** (ISSUE 12): a worker whose first search dispatch
    raises an injected RESOURCE_EXHAUSTED.  The worker's in-process
    degradation ladder must recover (no steal, no requeue storm) and
    finish the survey with outputs byte-identical to the
    single-process baseline."""
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs.server import start_obs_server

    outdir = os.path.join(base_dir, "oom_worker")
    t0 = time.time()
    coordinator = FleetCoordinator(
        outdir, lease_ttl_s=FLEET_LEASE_TTL_S, chunks_per_unit=1,
        probe_interval_s=0.5, auto_sweep=True)
    server = start_obs_server(0, fleet=coordinator)
    url = f"http://127.0.0.1:{server.port}"
    coordinator.add_survey([path], **{k: v for k, v in SEARCH_KW.items()
                                      if k not in ("make_plots",
                                                   "progress")})
    plan = FaultPlan([FaultSpec(site="dispatch", kind="oom",
                                chunks=(NOISE_CHUNK,), times=1)])
    try:
        with plan.armed():
            worker = FleetWorker(url, http_port=None)
            worker.run(max_idle_s=60)
        done = coordinator.survey_done
    finally:
        server.close()
        coordinator.close()
    fresh = snapshot_outputs(outdir, fingerprint)
    diffs = diff_outputs(baseline, fresh)
    return {"recoverable": True, "fired": plan.fired(),
            "survey_done": done, "byte_identical": not diffs,
            "diffs": diffs, "wall_s": round(time.time() - t0, 2),
            "ok": bool(plan.fired()) and done and not diffs}


def run_fleet_drill(log=print, workdir=None, keep=False):
    """The fleet chaos classes: killed_worker (SIGKILL while holding a
    lease, ISSUE 9), wedged_worker (hung far past the lease TTL, ISSUE
    9) and oom_worker (injected RESOURCE_EXHAUSTED recovered by the
    worker's own degradation ladder, ISSUE 12).  All must complete the
    survey byte-identical to the single-process baseline.  Slow
    (spawns real worker processes); runs as a ``slow``+``chaos``
    pytest and via ``--fleet`` here — ``tests/test_fleet.py`` holds the
    fast in-process equivalent.
    """
    t_start = time.time()
    base_dir = workdir or tempfile.mkdtemp(prefix="chaos_fleet_")
    os.makedirs(base_dir, exist_ok=True)
    path = os.path.join(base_dir, "survey.fil")
    make_survey_file(path)
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    get_bad_chans(path)

    log("fleet drill: single-process baseline run")
    hits, store = run_search(path, os.path.join(base_dir, "baseline"))
    assert hits, "baseline run found no candidates — drill is vacuous"
    fingerprint = store.fingerprint
    baseline = snapshot_outputs(os.path.join(base_dir, "baseline"),
                                fingerprint)

    classes = {}
    for name, kill in (("killed_worker", True), ("wedged_worker", False)):
        log(f"fleet drill: class {name}")
        classes[name] = _fleet_class(name, base_dir, path, baseline,
                                     fingerprint, log, kill)
        log(f"fleet drill: class {name}: "
            f"{'PASS' if classes[name]['ok'] else 'FAIL ' + str(classes[name])}")
    log("fleet drill: class oom_worker")
    classes["oom_worker"] = _fleet_oom_class(base_dir, path, baseline,
                                             fingerprint, log)
    log(f"fleet drill: class oom_worker: "
        f"{'PASS' if classes['oom_worker']['ok'] else 'FAIL ' + str(classes['oom_worker'])}")

    result = {
        "n_classes": len(classes),
        "all_ok": all(r["ok"] for r in classes.values()),
        "classes": classes,
        "wall_s": round(time.time() - t_start, 2),
    }
    if not keep and workdir is None:
        shutil.rmtree(base_dir, ignore_errors=True)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default=None, help="write the JSON record here")
    p.add_argument("--workdir", default=None,
                   help="run under this directory (kept) instead of a "
                        "deleted tempdir")
    p.add_argument("--fleet", action="store_true",
                   help="also run the fleet chaos classes "
                        "(killed/wedged worker subprocesses; slow)")
    opts = p.parse_args(argv)
    result = run_drill(log=lambda m: print(m, file=sys.stderr, flush=True),
                       workdir=opts.workdir, keep=bool(opts.workdir))
    if opts.fleet:
        result["fleet"] = run_fleet_drill(
            log=lambda m: print(m, file=sys.stderr, flush=True),
            workdir=(os.path.join(opts.workdir, "fleet")
                     if opts.workdir else None),
            keep=bool(opts.workdir))
        result["all_ok"] = result["all_ok"] and result["fleet"]["all_ok"]
    print(json.dumps(result, indent=1))
    if opts.out:
        with open(opts.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0 if result["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
