"""Survey-scale periodicity backend (ISSUE 13): accumulator geometry,
acceleration-trial search path identity, the harmonic-aware sift, and
the end-to-end recovery pin — a synthetic accelerated pulsar recovered
at its injected (DM, P, accel) grid cell through BOTH the direct
driver and a service-submitted job, with host/jit/sharded-mesh trial
paths producing identical candidate tables."""

import json
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
from pulsarutils_tpu.models.simulate import simulate_accel_pulsar_data
from pulsarutils_tpu.ops.rebin import stretch_resample
from pulsarutils_tpu.parallel.stream import ChunkPlan, plan_chunks
from pulsarutils_tpu.periodicity.accel import (C_M_S, accel_grid,
                                               accel_search,
                                               fractional_resample,
                                               stretch_index_table)
from pulsarutils_tpu.periodicity.accumulate import (DMTimeAccumulator,
                                                    choose_rebin)
from pulsarutils_tpu.periodicity.candidates import (ZapList,
                                                    harmonic_ratio,
                                                    load_candidates,
                                                    sift_candidates)
from pulsarutils_tpu.periodicity.driver import periodicity_search

TSAMP = 0.0005
NCHAN = 32
NSAMPLES = 16384
#: F0 sits exactly on Fourier bin 492 of the accumulated series — an
#: off-bin fundamental loses power to scalloping and an (on-bin)
#: harmonic can outrank it, which is a spectral-leakage fact of life,
#: not what this recovery pin is about
DM, F0, ACCEL = 150.0, 492 / (NSAMPLES * TSAMP), 9.0e5
ACCEL_MAX, N_ACCEL = 1.8e6, 9   # grid step 4.5e5 -> ACCEL on-grid
#: float DM bounds on purpose: the job-spec validator normalises to
#: float, and the ledger fingerprint hashes the JSON spelling — 130
#: and 130.0 are different fingerprints (every caller pair that must
#: share a ledger must agree on the type, fleet test below pins it)
JOB = dict(dmmin=130.0, dmmax=170.0, accel_max=ACCEL_MAX,
           n_accel=N_ACCEL, sigma_threshold=8.0,
           chunk_length=4096 * TSAMP, snr_threshold=8.0,
           progress=False)


@pytest.fixture(scope="module")
def pulsar_file(tmp_path_factory):
    """Accelerated binary pulsar: phase(t) = f0 (t + a t^2 / 2c) —
    ~12 Fourier bins of drift over the observation, so the
    zero-acceleration trial demonstrably smears it."""
    arr, hdr = simulate_accel_pulsar_data(
        freq=F0, dm=DM, accel=ACCEL, tsamp=TSAMP, nsamples=NSAMPLES,
        nchan=NCHAN, rng=13)
    path = tmp_path_factory.mktemp("psr") / "binary.fil"
    write_simulated_filterbank(str(path), arr, hdr, descending=True)
    return str(path)


@pytest.fixture(scope="module")
def direct_run(pulsar_file, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("direct"))
    res = periodicity_search(pulsar_file, output_dir=out, **JOB)
    assert res["complete"]
    return res


# ---------------------------------------------------------------------------
# accumulator
# ---------------------------------------------------------------------------

def _plan(step=4096, resample=1):
    return ChunkPlan(step=step, hop=step // 2, resample=resample,
                     sample_time=TSAMP * resample)


class TestAccumulator:
    def test_choose_rebin_fits_budget(self):
        # 64 x 65536 floats = 16 MB; a 4 MB budget needs rebin >= 8
        # (0.8 safety fraction -> 3.2 MB usable)
        r = choose_rebin(64, 65536, 2048, budget_bytes=4 << 20)
        assert r >= 8 and 2048 % r == 0
        assert choose_rebin(64, 65536, 2048,
                            budget_bytes=1 << 30) == 1

    def test_choose_rebin_hop_aligned_floor(self):
        # hop 4 admits at most rebin 4: the floor is returned (with a
        # warning) rather than refusing to run
        assert choose_rebin(1024, 1 << 20, 4, budget_bytes=1024) == 4

    def test_consume_tiles_the_observation(self):
        plan = _plan()
        starts = [0, 2048, 4096]
        nsamples = 8192
        acc = DMTimeAccumulator(plan, nsamples, starts, ndm=3, rebin=1)
        truth = np.arange(3 * nsamples, dtype=np.float32).reshape(3, -1)
        for s in starts:
            acc.consume(s, truth[:, s:s + plan.step])
        assert acc.complete and acc.coverage == 1.0
        np.testing.assert_array_equal(acc.plane, truth)

    def test_consume_rebin_and_dedup(self):
        plan = _plan()
        starts = [0, 2048, 4096]
        acc = DMTimeAccumulator(plan, 8192, starts, ndm=2, rebin=4)
        chunk = np.ones((2, plan.step), dtype=np.float32)
        assert acc.consume(0, chunk)
        assert not acc.consume(0, 2 * chunk)   # duplicate ignored
        np.testing.assert_array_equal(acc.plane[:, :512], 4.0)
        np.testing.assert_array_equal(acc.plane[:, 512:], 0.0)

    def test_trial_dm_drift_raises(self):
        plan = _plan()
        acc = DMTimeAccumulator(plan, 8192, [0, 2048], ndm=2, rebin=1)

        class T:
            colnames = ("DM",)

            def __init__(self, dms):
                self._d = np.asarray(dms)

            def __getitem__(self, k):
                return self._d

        acc.consume(0, np.zeros((2, plan.step)), T([1.0, 2.0]))
        with pytest.raises(ValueError, match="drifted"):
            acc.consume(2048, np.zeros((2, plan.step)), T([1.0, 3.0]))

    def test_snapshot_roundtrip_and_torn_file(self, tmp_path):
        plan = _plan()
        starts = [0, 2048, 4096]
        acc = DMTimeAccumulator(plan, 8192, starts, ndm=2, rebin=2)
        acc.consume(0, np.full((2, plan.step), 3.0, dtype=np.float32))
        snap = str(tmp_path / "snap.npz")
        acc.save(snap)
        fresh = DMTimeAccumulator(plan, 8192, starts, ndm=2, rebin=2)
        assert fresh.restore(snap)
        assert fresh.seen == {0}
        np.testing.assert_array_equal(fresh.plane, acc.plane)
        # torn snapshot: backed up .corrupt, accumulation restarts
        with open(snap, "wb") as f:
            f.write(b"PK\x03\x04 torn")
        again = DMTimeAccumulator(plan, 8192, starts, ndm=2, rebin=2)
        assert not again.restore(snap)
        assert os.path.exists(snap + ".corrupt")
        # geometry mismatch is rejected, not mis-applied
        acc.save(snap)
        other = DMTimeAccumulator(plan, 8192, starts, ndm=2, rebin=1)
        assert not other.restore(snap)


# ---------------------------------------------------------------------------
# acceleration trials
# ---------------------------------------------------------------------------

class TestAccel:
    def test_zero_accel_is_identity(self):
        x = np.random.default_rng(0).normal(0, 1, 512).astype(np.float32)
        np.testing.assert_array_equal(
            fractional_resample(x, 0.0, TSAMP), x)
        idx = stretch_index_table([0.0], 512, TSAMP)[0]
        np.testing.assert_array_equal(idx, np.arange(512))

    def test_stretch_resample_generalises_quick_resample(self):
        x = np.arange(10.0)
        out = stretch_resample(x, np.array([0, 3, 6, 9]))
        np.testing.assert_array_equal(out, [0.0, 3.0, 6.0, 9.0])
        out2 = stretch_resample(np.stack([x, 2 * x]), np.array([1, 4]))
        np.testing.assert_array_equal(out2, [[1.0, 4.0], [2.0, 8.0]])

    def test_sign_convention_straightens_accelerated_tone(self):
        # the pinned convention: a series generated with phase
        # f0 (t + a t^2 / 2c) is straightened by trial accel == a
        t_n = 1 << 13
        t = np.arange(t_n) * TSAMP
        f0, a = 200.0, 2.0e6
        x = np.sin(2 * np.pi * f0 * (t + a * t * t / (2 * C_M_S)))
        x = x.astype(np.float32)

        def peak_power(series):
            p = np.abs(np.fft.rfft(series)) ** 2
            return float(p.max() / p.sum())

        smeared = peak_power(x)
        fixed = peak_power(fractional_resample(x, a, TSAMP))
        wrong = peak_power(fractional_resample(x, -a, TSAMP))
        assert fixed > 2 * smeared and fixed > 5 * wrong

    def test_accel_grid_properties(self):
        g = accel_grid(100.0, 0.001, 1 << 16)
        assert g[0] == -100.0 and g[-1] == 100.0
        assert 0.0 in g and g.size % 2 == 1
        np.testing.assert_allclose(g, -g[::-1])
        assert accel_grid(0.0, 0.001, 1024).tolist() == [0.0]
        assert accel_grid(1e9, 0.001, 1 << 16,
                          max_trials=11).size <= 11

    def test_host_jit_mesh_tables_identical(self, direct_run):
        from pulsarutils_tpu.parallel.mesh import make_mesh

        acc = direct_run["accumulator"]
        accels = direct_run["accels"]
        kw = dict(max_harmonics=16, fmin=4.0 / (acc.nout * acc.tsamp),
                  topk=24)
        t_jit = accel_search(acc.plane, acc.tsamp, accels, xp=jnp, **kw)
        t_np = accel_search(acc.plane, acc.tsamp, accels, xp=np, **kw)
        tables = {"np": t_np, "jit": t_jit}
        for shape in [(4, 2), (2, 4)]:
            mesh = make_mesh(shape, ("dm", "chan"))
            tables[f"mesh{shape}"] = accel_search(
                acc.plane, acc.tsamp, accels, xp=jnp, mesh=mesh, **kw)
        for name, tbl in tables.items():
            for k in ("dm_index", "accel_index", "freq_bin", "nharm"):
                np.testing.assert_array_equal(
                    tbl[k], t_jit[k],
                    err_msg=f"{name} diverges from jit on {k}")
            np.testing.assert_allclose(tbl["sigma"], t_jit["sigma"],
                                       rtol=5e-3, atol=5e-3,
                                       err_msg=name)


# ---------------------------------------------------------------------------
# the candidate pipeline
# ---------------------------------------------------------------------------

def _cand(freq, sigma, dm_index=10, accel_index=0):
    return {"dm_index": dm_index, "dm": float(dm_index),
            "accel_index": accel_index, "accel": 0.0, "freq": freq,
            "freq_bin": int(round(freq * 100)), "nharm": 1,
            "power": sigma, "log_sf": -sigma, "sigma": sigma}


class TestSift:
    def test_harmonic_ratio(self):
        assert harmonic_ratio(10.0, 20.0) == 2        # harmonic
        assert harmonic_ratio(10.0, 5.0) == 2         # sub-harmonic
        assert harmonic_ratio(10.0, 30.1, tol=0.01) == 3
        assert harmonic_ratio(10.0, 10.0) == 0        # ratio 1: DM sift
        assert harmonic_ratio(10.0, 23.0) == 0
        assert harmonic_ratio(10.0, 170.0, max_ratio=16) == 0

    def test_sift_order_and_reasons(self):
        zap = ZapList([{"freq": 50.0, "width": 0.1, "harmonics": 2}])
        cands = [
            _cand(60.0, 100.0, dm_index=10),
            _cand(60.001, 50.0, dm_index=12),     # DM duplicate
            _cand(120.0, 30.0, dm_index=10),      # harmonic of 60
            _cand(30.0, 20.0, dm_index=40),       # sub-harmonic of 60
            _cand(50.0, 90.0),                    # zapped fundamental
            _cand(100.0, 15.0),                   # zapped 2nd harmonic
            _cand(37.0, 12.0, dm_index=3),        # genuine survivor
        ]
        kept, stats = sift_candidates(cands, zap=zap, freq_tol=0.01)
        assert [c["freq"] for c in kept] == [60.0, 37.0]
        assert stats["rejected"] == {"zap": 2, "dm_duplicate": 1,
                                     "harmonic": 2}
        assert stats["in"] == 7 and stats["kept"] == 2

    def test_no_freq_tol_means_no_grouping(self):
        # with no frequency window there is no "same frequency":
        # unrelated candidates must all survive (the both-None
        # condition used to be vacuously true and collapsed everything
        # into the strongest candidate)
        cands = [_cand(10.0, 100.0, dm_index=0),
                 _cand(33.3, 50.0, dm_index=50)]
        kept, stats = sift_candidates(cands)
        assert len(kept) == 2
        assert stats["rejected"]["dm_duplicate"] == 0

    def test_dm_radius_bounds_grouping(self):
        cands = [_cand(60.0, 100.0, dm_index=10),
                 _cand(60.0, 50.0, dm_index=40)]
        kept, _ = sift_candidates(cands, freq_tol=0.01, dm_radius=2)
        assert len(kept) == 2
        kept, _ = sift_candidates(cands, freq_tol=0.01)
        assert len(kept) == 1

    def test_zap_list_roundtrip_and_torn(self, tmp_path):
        zap = ZapList()
        zap.add(50.0, width=0.05, harmonics=3, note="mains")
        path = str(tmp_path / "zap.json")
        zap.save(path)
        back = ZapList.load(path)
        assert len(back) == 1
        assert back.matches(150.01) is not None   # 3rd harmonic
        assert back.matches(200.0) is None        # beyond harmonics=3
        with open(path, "w") as f:
            f.write("{torn")
        assert len(ZapList.load(path)) == 0       # degrade, not die
        assert len(ZapList.load(str(tmp_path / "absent.json"))) == 0


# ---------------------------------------------------------------------------
# end-to-end recovery pin (the ISSUE 13 acceptance criterion)
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_direct_driver_recovers_injected_cell(self, direct_run):
        acc = direct_run["accumulator"]
        cands = direct_run["candidates"]
        assert cands, "no candidates above threshold"
        best = cands[0]
        true_bin = 492
        assert abs(best["dm"] - DM) < 5.0
        assert best["accel"] == ACCEL          # exact grid cell
        assert abs(best["freq_bin"] - true_bin) <= 1
        assert best["sigma"] > 20.0
        assert best["h"] > 50.0 and "profile" in best
        # the acceleration axis demonstrably mattered: the best
        # zero-accel cell for this DM is far weaker
        tbl = direct_run["table"]
        zero = [s for s, a in zip(tbl["sigma"], tbl["accel"])
                if a == 0.0]
        assert not zero or max(zero) < best["sigma"] / 2

    def test_host_driver_matches_device_driver(self, pulsar_file,
                                               direct_run, tmp_path):
        """The whole job on the host reference (``backend="numpy"``)
        lists the device run's candidates: discrete fields cell for
        cell, scores to float tolerance."""
        host = periodicity_search(pulsar_file, backend="numpy",
                                  output_dir=str(tmp_path), **JOB)
        dev = direct_run["candidates"]
        assert len(host["candidates"]) == len(dev) > 0
        for ch, cd in zip(host["candidates"], dev):
            for k in ("dm_index", "accel_index", "freq_bin", "nharm"):
                assert ch[k] == cd[k], k
            assert ch["sigma"] == pytest.approx(cd["sigma"], rel=5e-3)

    def test_candidates_persisted_and_loadable(self, direct_run):
        cands, meta = load_candidates(direct_run["candidates_path"])
        assert len(cands) == len(direct_run["candidates"])
        assert meta["fingerprint"] == direct_run["fingerprint"]
        assert cands[0]["sigma"] == pytest.approx(
            direct_run["candidates"][0]["sigma"])
        assert cands[0]["profile"].size > 0

    def test_service_job_matches_direct_run(self, pulsar_file,
                                            direct_run, tmp_path):
        from pulsarutils_tpu.beams.service import SurveyService

        spec = {"fname": pulsar_file, "dmmin": 130, "dmmax": 170,
                "workload": "periodicity", "accel_max": ACCEL_MAX,
                "n_accel": N_ACCEL, "period_sigma_threshold": 8.0,
                "snr_threshold": 8.0,
                "chunk_length": 4096 * TSAMP}
        with SurveyService(str(tmp_path)) as svc:
            job_id = svc.submit(spec)
            deadline = time.time() + 120
            while time.time() < deadline:
                doc = svc.get(job_id)
                if doc["state"] in ("done", "failed", "cancelled"):
                    break
                time.sleep(0.2)
        assert doc["state"] == "done", doc
        assert doc["period"]["complete"] and doc["period"]["kept"] == \
            len(direct_run["candidates"])
        top = doc["period"]["top"][0]
        best = direct_run["candidates"][0]
        assert top["accel"] == best["accel"]
        assert top["freq"] == pytest.approx(best["freq"], rel=1e-6)
        assert top["dm"] == pytest.approx(best["dm"], rel=1e-6)
        assert doc["chunks_done"] == 3

    def test_explicit_single_pulse_normalised_away(self, pulsar_file):
        # an explicit default workload must yield the same spec as
        # omitting the key, or the two never share a co-batch tag
        from pulsarutils_tpu.beams.service import validate_spec

        a = validate_spec({"fname": pulsar_file, "dmmin": 1.0,
                           "dmmax": 2.0, "workload": "single_pulse"})
        b = validate_spec({"fname": pulsar_file, "dmmin": 1.0,
                           "dmmax": 2.0})
        assert a == b and "workload" not in a

    def test_validate_spec_workload_rules(self, pulsar_file):
        from pulsarutils_tpu.beams.service import validate_spec

        ok = validate_spec({"fname": pulsar_file, "dmmin": 1,
                            "dmmax": 2, "workload": "periodicity",
                            "accel_max": 10.0})
        assert ok["workload"] == "periodicity"
        with pytest.raises(ValueError, match="workload"):
            validate_spec({"fname": pulsar_file, "dmmin": 1,
                           "dmmax": 2, "workload": "folding"})
        with pytest.raises(ValueError, match="multibeam-only"):
            validate_spec({"fname": pulsar_file, "dmmin": 1,
                           "dmmax": 2, "workload": "periodicity",
                           "veto_frac": 0.5})
        with pytest.raises(ValueError, match="periodicity"):
            validate_spec({"fname": pulsar_file, "dmmin": 1,
                           "dmmax": 2, "accel_max": 10.0})
        with pytest.raises(ValueError, match="accel_max"):
            validate_spec({"fname": pulsar_file, "dmmin": 1,
                           "dmmax": 2, "workload": "periodicity",
                           "accel_max": -1.0})

    def test_driver_rejects_owned_knobs(self, pulsar_file, tmp_path):
        with pytest.raises(ValueError, match="periodicity driver"):
            periodicity_search(pulsar_file, output_dir=str(tmp_path),
                               period_search=True, **JOB)

    def test_fleet_lease_carries_workload(self, pulsar_file,
                                          direct_run, tmp_path,
                                          direct_dir_fingerprint=None):
        from pulsarutils_tpu.fleet.coordinator import FleetCoordinator

        coord = FleetCoordinator(str(tmp_path), auto_sweep=False)
        with coord:
            spec = {"fname": pulsar_file, "dmmin": 130.0,
                    "dmmax": 170.0, "workload": "periodicity",
                    "accel_max": ACCEL_MAX, "n_accel": N_ACCEL,
                    "snr_threshold": 8.0,
                    "chunk_length": 4096 * TSAMP}
            units = coord.add_job(spec)
            # ONE unit carrying the whole observation
            assert len(units) == 1
            fname = os.path.abspath(pulsar_file)
            rec = coord._files[fname]
            assert rec["workload"] == "periodicity"
            # the coordinator's fingerprint IS the driver's: unit
            # completions read the ledger the worker's
            # periodicity_search run will actually write
            assert rec["fingerprint"] == direct_run["fingerprint"]
            reg = coord.register({"healthz_url": None})
            leases = coord.lease({"worker": reg["worker"]})["leases"]
            assert len(leases) == 1
            cfg = leases[0]["config"]
            assert cfg["workload"] == "periodicity"
            assert cfg["accel_max"] == ACCEL_MAX
            assert len(leases[0]["chunks"]) == 3
            # periodicity-only keys on a single-pulse config are
            # rejected at intake, not exploded inside every worker
            with pytest.raises(ValueError, match="periodicity"):
                coord.add_survey([pulsar_file], dmmin=1.0, dmmax=2.0,
                                 accel_max=10.0)
            # ...and so is a typoed workload (which would otherwise
            # run a silent single-pulse survey)
            with pytest.raises(ValueError, match="workload"):
                coord.add_survey([pulsar_file], dmmin=1.0, dmmax=2.0,
                                 workload="Periodicity")

    def test_fleet_completion_requires_candidate_artifact(
            self, pulsar_file, direct_run, tmp_path):
        """A fully-accumulated ledger with no candidates artifact is
        NOT a finished periodicity job: the trial-search stage still
        owes its npz, so the coordinator must shard (and keep
        requeueing) the unit until the artifact exists."""
        import shutil

        from pulsarutils_tpu.fleet.coordinator import FleetCoordinator

        spec = {"fname": pulsar_file, "dmmin": 130.0, "dmmax": 170.0,
                "workload": "periodicity", "accel_max": ACCEL_MAX,
                "n_accel": N_ACCEL, "snr_threshold": 8.0,
                "chunk_length": 4096 * TSAMP}
        direct_dir = os.path.dirname(direct_run["candidates_path"])
        ledger = f"progress_{direct_run['fingerprint']}.json"
        # arm the coordinator dir with a COMPLETE chunk ledger but no
        # candidates artifact (worker died after accumulation)
        shutil.copy(os.path.join(direct_dir, ledger),
                    str(tmp_path / ledger))
        with FleetCoordinator(str(tmp_path), auto_sweep=False) as coord:
            units = coord.add_job(spec)
            assert len(units) == 1          # still work to do
            unit = coord._units[units[0]]
            assert coord._ledger_remaining(unit, {}) == unit.chunks
            # drop the artifact in place: the unit resolves as done
            shutil.copy(direct_run["candidates_path"],
                        coord._files[os.path.abspath(pulsar_file)]
                        ["artifact"])
            assert coord._ledger_remaining(unit, {}) == ()

    def test_n_accel_one_keeps_zero_trial(self, pulsar_file, tmp_path):
        # n_accel=1 with accel_max>0 used to linspace to the single
        # trial -accel_max and silently drop the zero-acceleration
        # search entirely
        res = periodicity_search(pulsar_file, 130.0, 170.0,
                                 accel_max=1.0e5, n_accel=1,
                                 sigma_threshold=8.0,
                                 chunk_length=4096 * TSAMP,
                                 snr_threshold=8.0, progress=False,
                                 output_dir=str(tmp_path))
        assert res["accels"].tolist() == [0.0]

    def test_canary_recall_and_science_identity(self, pulsar_file,
                                                tmp_path):
        from pulsarutils_tpu.obs import metrics as _metrics
        from pulsarutils_tpu.obs.health import HealthEngine

        engine = HealthEngine(recall_min_injected=1)
        out = str(tmp_path / "canary_on")
        on = periodicity_search(pulsar_file, output_dir=out,
                                canary=True, health=engine, **JOB)
        assert on["canary"]["recovered"]
        assert on["canary"]["best_sigma"] > 8.0
        gauge = [m for m in _metrics.REGISTRY.snapshot()
                 if m["name"] == "putpu_period_canary_recall"]
        assert gauge and gauge[0]["value"] == 1.0
        assert engine.verdict == "OK"
        off = periodicity_search(pulsar_file,
                                 output_dir=str(tmp_path / "off"),
                                 **JOB)
        # the canary never contaminates science output
        assert len(on["candidates"]) == len(off["candidates"])
        for a, b in zip(on["candidates"], off["candidates"]):
            assert a["freq_bin"] == b["freq_bin"]
            assert a["dm_index"] == b["dm_index"]
            assert a["accel_index"] == b["accel_index"]

    def test_report_carries_periodicity_section(self, pulsar_file,
                                                direct_run, tmp_path):
        from pulsarutils_tpu.obs.report import build_report, \
            render_markdown

        summary = {"n_dm": 4, "n_accel": 3, "nout": 128, "rebin": 2,
                   "t_obs_s": 12.8, "raw_candidates": 5, "kept": 1,
                   "rejected": {"zap": 1, "dm_duplicate": 2,
                                "harmonic": 1},
                   "canary": {"dm_index": 1, "freq": 10.0,
                              "recovered": True},
                   "candidates": [{"freq": 60.0, "dm": 150.0,
                                   "accel": 9e5, "sigma": 30.0,
                                   "nharm": 4, "h": 99.0}]}
        md = render_markdown(build_report(meta={"root": "x"},
                                          periodicity=summary))
        assert "## Periodicity search" in md
        assert "4 DM x 3 acceleration trials" in md
        assert "recovered" in md and "60" in md
        md_off = render_markdown(build_report(meta={"root": "x"}))
        assert "No periodicity search ran" in md_off


class TestPlaneConsumerSeam:
    def test_stream_search_plane_consumer(self):
        from pulsarutils_tpu.parallel.stream import stream_search

        rng = np.random.default_rng(3)
        chunks = [(0, rng.normal(0, 1, (16, 2048)).astype(np.float32)),
                  (1024, rng.normal(0, 1, (16, 2048)).astype(np.float32))]
        seen = []
        results, _hits = stream_search(
            chunks, 100, 200, 1200., 200., TSAMP,
            plane_consumer=lambda s, plane, table:
                seen.append((s, np.shape(plane))))
        assert [s for s, _ in seen] == [0, 1024]
        assert all(shape[1] == 2048 for _, shape in seen)
        assert len(results) == 2

    def test_stream_search_mesh_consumer_gets_handle(self):
        # the mesh route must hand the consumer the documented
        # DM-sharded handle, not an eagerly-gathered host plane
        from pulsarutils_tpu.parallel.mesh import make_mesh
        from pulsarutils_tpu.parallel.stream import stream_search

        rng = np.random.default_rng(4)
        chunks = [(0, rng.normal(0, 1, (16, 2048)).astype(np.float32))]
        mesh = make_mesh((2, 2), ("dm", "chan"))
        seen = []
        stream_search(chunks, 100, 200, 1200., 200., TSAMP, mesh=mesh,
                      plane_consumer=lambda s, plane, table:
                          seen.append(type(plane).__name__))
        assert seen == ["ShardedPlane"]
