"""Fleet orchestrator tests (ISSUE 9).

Tier-1 pins: the wire-protocol/config whitelist, coordinator sharding +
ledger-backed resume, lease expiry -> steal -> duplicate-completion
idempotency, DEGRADED-worker lease starvation (and recovery), the
killed-worker (SIGKILL mid-lease) resume byte-identity, graceful drain,
the ``chunks=``/``cancel_cb=`` driver seams, the sorted/merging ledger,
and the ``/fleet/`` HTTP surface.  The full subprocess chaos classes
(killed + wedged worker over the drill survey) are ``slow``-marked.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pulsarutils_tpu.fleet import protocol
from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
from pulsarutils_tpu.fleet.worker import FleetWorker
from pulsarutils_tpu.io.candidates import CandidateStore
from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
from pulsarutils_tpu.models.simulate import disperse_array
from pulsarutils_tpu.obs import metrics as obs_metrics
from pulsarutils_tpu.obs.health import HealthEngine
from pulsarutils_tpu.obs.server import start_obs_server
from pulsarutils_tpu.pipeline.search_pipeline import (plan_survey,
                                                      search_by_chunks)

TSAMP = 0.0005
NCHAN = 64
#: 24576 samples at chunk_length 8192*TSAMP -> exactly chunks [0, 8192]
NSAMPLES = 24576
CONFIG = dict(dmmin=100, dmmax=200, chunk_length=8192 * TSAMP,
              snr_threshold=6.5)


def write_file(path, seed=0, pulse=False):
    rng = np.random.default_rng(seed)
    arr = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    if pulse:
        arr[:, (3 * NSAMPLES) // 4] += 4.0
        arr = disperse_array(arr, 150.0, 1200., 200., TSAMP)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
              "nsamples": NSAMPLES, "tsamp": TSAMP,
              "foff": 200. / NCHAN}
    write_simulated_filterbank(str(path), arr, header, descending=True)
    return str(path)


def reference_run(fnames, outdir):
    for fname in fnames:
        search_by_chunks(fname, output_dir=str(outdir), make_plots=False,
                         progress=False, **CONFIG)


def snapshot_dir(outdir):
    """{name: bytes-or-npz-members} over ledgers + candidates (the
    chaos-drill comparison rule: npz compared member-wise)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(str(outdir), "*"))):
        name = os.path.basename(path)
        if name.startswith("progress_") and name.endswith(".json"):
            with open(path, "rb") as f:
                out[name] = f.read()
        elif name.endswith(".npz"):
            with np.load(path, allow_pickle=False) as z:
                out[name] = {k: (str(z[k].dtype), z[k].shape,
                                 z[k].tobytes()) for k in z.files}
    return out


def mark_chunks_done(outdir, fingerprint, chunks):
    """Simulate a worker's ledger writes without paying a search."""
    store = CandidateStore(str(outdir), fingerprint)
    for c in chunks:
        store.mark_done(c)


def counter_value(name):
    return obs_metrics.counter(name).value


# ---------------------------------------------------------------------------
# protocol + planning
# ---------------------------------------------------------------------------

def test_search_config_whitelist():
    cfg = protocol.clean_search_config(dict(CONFIG, kernel="hybrid"))
    assert cfg["dmmin"] == 100 and cfg["kernel"] == "hybrid"
    with pytest.raises(ValueError, match="output_dir"):
        protocol.clean_search_config({"output_dir": "/tmp/x"})
    with pytest.raises(ValueError, match="dmax"):
        protocol.clean_search_config({"dmax": 200})  # typo must not pass


def test_plan_survey_matches_driver_fingerprint(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=3)
    sp = plan_survey(fname, **CONFIG)
    assert sp["chunk_starts"] == [0, 8192]
    _, store = search_by_chunks(fname, output_dir=str(tmp_path / "out"),
                                make_plots=False, progress=False,
                                max_chunks=1, **CONFIG)
    # the coordinator's fingerprint IS the driver's — same ledger
    assert store.fingerprint == sp["fingerprint"]
    assert store.done_chunks == sp["chunk_starts"][:1]


def test_coordinator_shards_and_skips_ledger_done(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=4)
    out = tmp_path / "fleet"
    with FleetCoordinator(str(out), auto_sweep=False) as coordinator:
        ids = coordinator.add_survey([fname], **CONFIG)
        assert len(ids) == 2  # chunks_per_unit=1 over [0, 8192]
        fingerprint = plan_survey(fname, **CONFIG)["fingerprint"]
    # chunk 0 already done in the ledger: only 8192 gets sharded
    mark_chunks_done(out, fingerprint, [0])
    with FleetCoordinator(str(out), auto_sweep=False) as c2:
        ids = c2.add_survey([fname], **CONFIG)
        assert len(ids) == 1
        assert c2.progress_doc()["chunks_done"] == 1


def test_lease_complete_lifecycle_resolved_by_ledger(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=5)
    out = tmp_path / "fleet"
    with FleetCoordinator(str(out), auto_sweep=False) as coordinator:
        coordinator.add_survey([fname], **CONFIG)
        fingerprint = coordinator.progress_doc()["files"][0]["fingerprint"]
        w = coordinator.register({"healthz_url": None})["worker"]
        resp = coordinator.lease({"worker": w, "max_units": 2})
        assert len(resp["leases"]) == 2
        lease = resp["leases"][0]
        assert lease["config"]["dmmin"] == 100
        assert lease["output_dir"] == str(out)
        # completing WITHOUT ledger backing requeues, never resolves
        resp2 = coordinator.complete({"worker": w, "lease": lease["lease"],
                                      "unit": lease["unit"],
                                      "error": None})
        assert resp2["unit_done"] is False
        assert resp2["requeued"] == lease["chunks"]
        # now the ledger actually records the chunks: complete resolves
        release = coordinator.lease({"worker": w, "max_units": 1})
        assert len(release["leases"]) == 1
        got = release["leases"][0]
        mark_chunks_done(out, fingerprint, got["chunks"])
        resp3 = coordinator.complete({"worker": w, "lease": got["lease"],
                                      "unit": got["unit"], "error": None})
        assert resp3["unit_done"] is True


def test_lease_expiry_steal_duplicate_completion_idempotent(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=6)
    out = tmp_path / "fleet"
    before = {k: counter_value(f"putpu_fleet_{k}_total")
              for k in ("leases_expired", "duplicate_completions",
                        "units_requeued")}
    with FleetCoordinator(str(out), auto_sweep=False,
                          lease_ttl_s=5.0) as coordinator:
        coordinator.add_survey([fname], **CONFIG)
        fingerprint = coordinator.progress_doc()["files"][0]["fingerprint"]
        w1 = coordinator.register({})["worker"]
        w2 = coordinator.register({})["worker"]
        lease1 = coordinator.lease({"worker": w1,
                                    "max_units": 1})["leases"][0]
        # TTL passes with w1 silent: the sweep requeues via the ledger
        swept = coordinator.sweep(now=time.monotonic() + 10.0)
        assert swept["expired"] == [lease1["lease"]]
        assert counter_value("putpu_fleet_leases_expired_total") \
            == before["leases_expired"] + 1
        # w2 steals the unit and finishes it
        lease2 = coordinator.lease({"worker": w2,
                                    "max_units": 1})["leases"][0]
        assert lease2["unit"] == lease1["unit"]
        assert lease2["chunks"] == lease1["chunks"]
        mark_chunks_done(out, fingerprint, lease2["chunks"])
        done = coordinator.complete({"worker": w2, "lease": lease2["lease"],
                                     "unit": lease2["unit"], "error": None})
        assert done["unit_done"] is True
        ledger = snapshot_dir(out)[f"progress_{fingerprint}.json"]
        # the straggler's late completion: counted, idempotent, no
        # requeue, ledger untouched
        late = coordinator.complete({"worker": w1, "lease": lease1["lease"],
                                     "unit": lease1["unit"], "error": None})
        assert late["unit_done"] is True
        assert late["requeued"] == []
        assert counter_value("putpu_fleet_duplicate_completions_total") \
            == before["duplicate_completions"] + 1
        assert snapshot_dir(out)[f"progress_{fingerprint}.json"] == ledger


def test_degraded_worker_lease_starvation_and_recovery(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=7)
    sick_engine = HealthEngine()
    sick_engine.update(0, quarantined=True)        # -> DEGRADED
    assert sick_engine.verdict == "DEGRADED"
    ok_engine = HealthEngine()
    with start_obs_server(0, health=sick_engine) as sick_srv, \
            start_obs_server(0, health=ok_engine) as ok_srv, \
            FleetCoordinator(str(tmp_path / "fleet"), auto_sweep=False,
                             file_affinity=False) as coordinator:
        coordinator.add_survey([fname], **CONFIG)
        sick = coordinator.register(
            {"healthz_url":
             f"http://127.0.0.1:{sick_srv.port}/healthz"})["worker"]
        ok = coordinator.register(
            {"healthz_url":
             f"http://127.0.0.1:{ok_srv.port}/healthz"})["worker"]
        probed = coordinator.sweep()["probed"]
        assert probed == {sick: "DEGRADED", ok: "OK"}
        denied = coordinator.lease({"worker": sick, "max_units": 1})
        assert denied["leases"] == [] and denied["denied"] == "DEGRADED"
        granted = coordinator.lease({"worker": ok, "max_units": 1})
        assert len(granted["leases"]) == 1
        workers = {w["worker"]: w for w in
                   coordinator.workers_doc()["workers"]}
        assert workers[sick]["verdict"] == "DEGRADED"
        # the condition decays (recover_after clean updates): the next
        # probe re-qualifies the worker for leases
        sick_engine.update(1)
        sick_engine.update(2)
        assert sick_engine.verdict == "OK"
        coordinator.sweep()
        regranted = coordinator.lease({"worker": sick, "max_units": 1})
        assert len(regranted["leases"]) == 1


def test_dead_worker_probe_revokes_and_requeues(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=8)
    with FleetCoordinator(str(tmp_path / "fleet"), auto_sweep=False,
                          dead_after=2) as coordinator:
        coordinator.add_survey([fname], **CONFIG)
        # a healthz URL nothing listens on: every probe fails
        dead = coordinator.register(
            {"healthz_url": "http://127.0.0.1:9/healthz"})["worker"]
        lease = coordinator.lease({"worker": dead,
                                   "max_units": 1})["leases"][0]
        assert coordinator.sweep()["revoked"] == []    # 1 failure: not yet
        revoked = coordinator.sweep()["revoked"]       # 2nd: declared dead
        assert revoked == [lease["lease"]]
        doc = coordinator.workers_doc()["workers"][0]
        assert doc["alive"] is False
        # the unit is back in the queue for a live worker
        alive = coordinator.register({})["worker"]
        again = coordinator.lease({"worker": alive,
                                   "max_units": 1})["leases"]
        assert [le["unit"] for le in again] == [lease["unit"]]


def test_two_worker_fleet_byte_identical_to_single_process(tmp_path):
    """The tentpole contract: a 2-worker fleet run over a 2-file survey
    produces byte-identical candidates and per-file ledgers vs the
    single-process run (real HTTP wire, real searches)."""
    fnames = [write_file(tmp_path / "a.fil", seed=0, pulse=True),
              write_file(tmp_path / "b.fil", seed=1)]
    reference_run(fnames, tmp_path / "single")

    out = tmp_path / "fleet"
    with FleetCoordinator(str(out), lease_ttl_s=120.0,
                          probe_interval_s=0.5) as coordinator:
        with start_obs_server(0, fleet=coordinator) as srv:
            url = f"http://127.0.0.1:{srv.port}"
            coordinator.add_survey(fnames, **CONFIG)
            workers = [FleetWorker(url, http_port=None)
                       for _ in range(2)]
            threads = [threading.Thread(target=w.run,
                                        kwargs={"max_idle_s": 60.0})
                       for w in workers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            assert coordinator.survey_done
            assert sum(w.units_done for w in workers) == 4
    assert snapshot_dir(tmp_path / "single") == snapshot_dir(out)


def test_killed_worker_sigkill_mid_lease_byte_identity(tmp_path):
    """SIGKILL a real worker process while it holds a lease (wedged at
    the fleet fault seam, pre-search): the lease expires, the chunks
    requeue off the ledger, a healthy worker finishes, and the outputs
    are byte-identical to the single-process run."""
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec

    fname = write_file(tmp_path / "a.fil", seed=0, pulse=True)
    reference_run([fname], tmp_path / "single")

    out = tmp_path / "fleet"
    coordinator = FleetCoordinator(str(out), lease_ttl_s=4.0,
                                   probe_interval_s=0.3)
    srv = start_obs_server(0, fleet=coordinator)
    url = f"http://127.0.0.1:{srv.port}"
    coordinator.add_survey([fname], **CONFIG)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               PUTPU_FAULT_PLAN=FaultPlan(
                   [FaultSpec(site="fleet", kind="hang", seconds=300.0,
                              times=1)]).to_json())
    victim = subprocess.Popen(
        [sys.executable, "-m", "pulsarutils_tpu.cli.fleet_main",
         "worker", "--coordinator", url, "--worker-id", "victim",
         "--max-idle", "60"],
        env=env, cwd=repo, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 120.0
        while time.time() < deadline \
                and not coordinator.leases_doc()["leases"]:
            time.sleep(0.2)
        assert coordinator.leases_doc()["leases"], \
            "victim never obtained a lease"
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)
        rescuer = FleetWorker(url, http_port=None)
        rescuer.run(max_idle_s=60.0)
        assert coordinator.survey_done
        stats = coordinator.progress_doc()["stats"]
        assert stats["expired"] + stats["revoked"] >= 1
    finally:
        if victim.poll() is None:
            victim.kill()
        srv.close()
        coordinator.close()
    assert snapshot_dir(tmp_path / "single") == snapshot_dir(out)


def test_worker_graceful_drain_returns_unstarted_leases(tmp_path):
    """Drain before run(): the worker registers, leases nothing more,
    releases unstarted leases mid-batch, and counts the drain."""
    fname = write_file(tmp_path / "a.fil", seed=9)
    out = tmp_path / "fleet"
    before = counter_value("putpu_fleet_drains_total")
    with FleetCoordinator(str(out), auto_sweep=False) as coordinator:
        with start_obs_server(0, fleet=coordinator) as srv:
            url = f"http://127.0.0.1:{srv.port}"
            coordinator.add_survey([fname], **CONFIG)
            worker = FleetWorker(url, http_port=None, max_units=2)
            orig_run_unit = worker._run_unit

            def drain_after_first(lease):
                result = orig_run_unit(lease)
                worker.drain()    # eviction notice mid-batch
                return result

            worker._run_unit = drain_after_first
            worker.run()
            assert worker.drained is True
            assert worker.units_done == 1
            assert counter_value("putpu_fleet_drains_total") == before + 1
            progress = coordinator.progress_doc()
            # first unit completed + ledger-backed; second was released
            # back (requeued) untouched — nothing is leased anymore
            assert progress["chunks_done"] == 1
            assert progress["units"] == {"done": 1, "pending": 1}
            assert coordinator.leases_doc()["leases"] == []
            # cooperative returns never burn the poison-chunk budget:
            # a preemptible fleet draining daily must not fail units
            assert all(u.attempts == 0
                       for u in coordinator._units.values())
            # the drained worker gets nothing further
            denied = coordinator.lease({"worker": worker.worker_id,
                                        "max_units": 1})
            assert denied["denied"] == "draining"
            # a fresh worker finishes the survey exactly
            finisher = FleetWorker(url, http_port=None)
            finisher.run(max_idle_s=30.0)
            assert coordinator.survey_done


def test_chunks_and_cancel_cb_driver_seams(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=10)
    out = str(tmp_path / "out")
    _, store = search_by_chunks(fname, output_dir=out, make_plots=False,
                                progress=False, chunks=[8192], **CONFIG)
    assert store.done_chunks == [8192]     # only the leased chunk
    _, store2 = search_by_chunks(fname, output_dir=out, make_plots=False,
                                 progress=False,
                                 cancel_cb=lambda: True, **CONFIG)
    assert store2.done_chunks == [8192]    # cancelled before chunk 0


def test_mark_done_sorted_and_merging(tmp_path):
    # two sessions over ONE ledger, interleaved out of order (the
    # fleet's steal edge): the final file equals a single ascending
    # session's bytes
    a = CandidateStore(str(tmp_path), "f" * 16)
    b = CandidateStore(str(tmp_path), "f" * 16)
    a.mark_done(16384)
    b.mark_done(0)          # merges a's 16384 from disk
    a.mark_done(8192)       # merges b's 0 from disk
    with open(a._ledger_path, "rb") as f:
        merged = f.read()
    ref = CandidateStore(str(tmp_path / "ref"), "f" * 16)
    for c in (0, 8192, 16384):
        ref.mark_done(c)
    with open(ref._ledger_path, "rb") as f:
        assert f.read() == merged


def test_fleet_http_surface(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=11)
    with FleetCoordinator(str(tmp_path / "fleet"),
                          auto_sweep=False) as coordinator:
        with start_obs_server(0, fleet=coordinator) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            coordinator.add_survey([fname], **CONFIG)
            reg = protocol.post_json(base + "/fleet/register",
                                     {"healthz_url": None})
            assert reg["protocol_version"] == protocol.PROTOCOL_VERSION
            lease = protocol.post_json(
                base + "/fleet/lease",
                {"worker": reg["worker"], "max_units": 1})["leases"][0]
            # completion over the wire, carrying a metrics snapshot the
            # aggregated /fleet/metrics page must re-serve
            mark_chunks_done(tmp_path / "fleet",
                             coordinator.progress_doc()["files"][0]
                             ["fingerprint"], lease["chunks"])
            protocol.post_json(base + "/fleet/complete", {
                "worker": reg["worker"], "lease": lease["lease"],
                "unit": lease["unit"], "error": None,
                "metrics": [{"name": "putpu_chunks_total",
                             "type": "counter", "labels": {},
                             "value": 1}],
                "health": {"status": "OK", "reasons": []}})
            for path in ("/fleet/workers", "/fleet/leases",
                         "/fleet/progress"):
                with urllib.request.urlopen(base + path,
                                            timeout=10.0) as resp:
                    assert resp.status == 200
                    json.loads(resp.read().decode())
            with urllib.request.urlopen(base + "/fleet/metrics",
                                        timeout=10.0) as resp:
                text = resp.read().decode()
            assert ('putpu_chunks_total{worker="%s"} 1'
                    % reg["worker"]) in text
            # protocol violations are 400s with the reason in the body
            status, body = _post_raw(base + "/fleet/lease",
                                     {"worker": "nope"})
            assert status == 400 and "unknown worker" in body
            # bad unit id on complete is a 400 too, not a 500
            status, body = _post_raw(
                base + "/fleet/complete",
                {"worker": reg["worker"], "lease": "L99",
                 "unit": "u99", "error": None})
            assert status == 400 and "unknown unit" in body


def _post_raw(url, doc):
    req = urllib.request.Request(
        url, method="POST", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10.0) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode()


def test_fleet_endpoints_404_unwired():
    with start_obs_server(0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        for path in ("/fleet/progress", "/fleet/workers"):
            try:
                urllib.request.urlopen(base + path, timeout=10.0)
                status = 200
            except urllib.error.HTTPError as exc:
                status = exc.code
            assert status == 404
        assert _post_raw(base + "/fleet/lease", {"worker": "w"})[0] == 404


def test_add_job_service_spec_handoff(tmp_path):
    fname = write_file(tmp_path / "a.fil", seed=12)
    with FleetCoordinator(str(tmp_path / "fleet"),
                          auto_sweep=False) as coordinator:
        ids = coordinator.add_job({"fname": fname, "dmmin": 100,
                                   "dmmax": 200, "snr_threshold": 6.5})
        assert len(ids) >= 1
        with pytest.raises(ValueError, match="missing keys"):
            coordinator.add_job({"fname": fname})
        with pytest.raises(ValueError, match="canary_rate"):
            coordinator.add_job({"fname": fname, "dmmin": 100,
                                 "dmmax": 200, "canary_rate": 0.5})
        # one fleet run, one fingerprint per file
        with pytest.raises(ValueError, match="different search config"):
            coordinator.add_survey([fname], dmmin=100, dmmax=300)


def test_fleet_report_section(tmp_path):
    from pulsarutils_tpu.obs.report import render_markdown, write_report

    fname = write_file(tmp_path / "a.fil", seed=13)
    with FleetCoordinator(str(tmp_path / "fleet"),
                          auto_sweep=False) as coordinator:
        coordinator.add_survey([fname], **CONFIG)
        summary = coordinator.summary()
    write_report(str(tmp_path / "report"), meta={"root": "fleet"},
                 fleet=summary)
    with open(str(tmp_path / "report") + ".json") as f:
        rec = json.load(f)
    md = render_markdown(rec)
    assert "## Fleet" in md
    assert "0/2 chunks completed across the fleet" in md
    # absence stated when no coordinator was involved
    write_report(str(tmp_path / "r2"), meta={"root": "solo"})
    with open(str(tmp_path / "r2") + ".json") as f:
        assert "no fleet coordinator" in render_markdown(json.load(f))


def test_worker_reregisters_after_coordinator_restart(tmp_path):
    """A coordinator restart loses its in-memory worker table; a
    long-lived worker must re-register on the 'unknown worker' 400
    instead of spinning as a zombie."""
    fname = write_file(tmp_path / "a.fil", seed=14)
    first = FleetCoordinator(str(tmp_path / "old"), auto_sweep=False)
    with start_obs_server(0, fleet=first) as srv:
        url = f"http://127.0.0.1:{srv.port}"
        worker = FleetWorker(url, http_port=None, poll_s=0.1)
        thread = threading.Thread(
            target=worker.run, kwargs={"max_idle_s": 60.0})
        thread.start()      # registers with `first`, polls an empty queue
        deadline = time.time() + 30.0
        while time.time() < deadline and worker.worker_id is None:
            time.sleep(0.05)
        assert worker.worker_id is not None
        # "restart": a fresh coordinator (empty worker table) takes
        # over the same surface mid-poll
        second = FleetCoordinator(str(tmp_path / "fleet"),
                                  auto_sweep=False)
        second.add_survey([fname], **CONFIG)
        srv.fleet = second
        thread.join(timeout=120.0)
        assert not thread.is_alive()
        assert worker.units_done == 2 and second.survey_done
        second.close()
    first.close()


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_chaos_drill_killed_and_wedged_workers():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import chaos_drill

    result = chaos_drill.run_fleet_drill(log=lambda *a: None)
    assert result["all_ok"], json.dumps(result, indent=1)


def test_coordinator_role_imports_no_jax(tmp_path):
    """One process per chip (ISSUE 22): the coordinator plans, leases
    and serves HTTP, and must never import JAX — a coordinator that
    touched a backend would hold the chip its workers need.  Run as the
    real CLI role in a child interpreter that interrupts itself once
    the survey is planned and being served."""
    fname = write_file(tmp_path / "a.fil", seed=4)
    code = (
        "import os, signal, sys, threading\n"
        "from pulsarutils_tpu.cli import fleet_main\n"
        "threading.Timer(2.0, os.kill, (os.getpid(), signal.SIGINT))"
        ".start()\n"
        f"rc = fleet_main.main(['coordinator', {fname!r}, '--output-dir', "
        f"{str(tmp_path / 'fleet')!r}, '--http-port', '0', '--dmmin', "
        "'100', '--dmmax', '200', '--snr-threshold', '6.5'])\n"
        "print('JAX', any(m.split('.')[0] in ('jax', 'jaxlib') "
        "for m in sys.modules))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"chunks_total": 80' in proc.stdout  # it did plan the survey
    assert proc.stdout.rstrip().endswith("JAX False"), proc.stdout
