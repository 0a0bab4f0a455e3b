"""Fault-injection harness + hardened survey loop (ISSUE 4).

Fast deterministic injection tests (``chaos`` marker, tier-1): the
FaultPlan plumbing, the data-integrity gate, deadline-bounded dispatch,
quarantine + dead-letter + audit, torn-ledger recovery, the sticky mesh
fallback — plus the acceptance pin that with no plan armed the hardened
loop's outputs are byte-identical to a run with every robustness knob
off.  The full fault-matrix drill (``tools/chaos_drill.py``) also runs
here, ``slow``-marked.
"""
import json
import logging
import os
import time

import numpy as np
import pytest

from pulsarutils_tpu.faults import (DispatchTimeoutError, FaultPlan,
                                    FaultSpec, IntegrityPolicy,
                                    call_with_deadline, gate_chunk,
                                    resolve_integrity_policy)
from pulsarutils_tpu.faults import inject as fault_inject
from pulsarutils_tpu.faults.audit import audit_run
from pulsarutils_tpu.io.candidates import CandidateStore, config_fingerprint
from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
from pulsarutils_tpu.models.simulate import disperse_array
from pulsarutils_tpu.obs.metrics import REGISTRY
from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

pytestmark = pytest.mark.chaos

TSAMP = 0.0005
NCHAN = 64
NSAMPLES = 32768
CHUNK_LEN_S = 8192 * TSAMP          # -> step 16384, hop 8192
PULSE_T = 20000                     # noise chunk: 0; hit chunks: 8192, 16384
#: 6.5, not the reference 6.0: this geometry's noise ceiling grazes 6.0
#: and the byte-identical assertions need the noise chunk candidate-free
SEARCH_KW = dict(dmmin=100, dmmax=200, backend="jax",
                 chunk_length=CHUNK_LEN_S, make_plots=False,
                 progress=False, snr_threshold=6.5)


def _counter(name):
    for rec in REGISTRY.snapshot():
        if rec["name"] == name and not rec["labels"]:
            return rec["value"]
    return 0


@pytest.fixture(scope="module")
def survey_file(tmp_path_factory):
    """Small survey: noise + one bright dispersed pulse, bad-channel
    cache pre-warmed so armed plans never fire during the stats scan."""
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    tmp = tmp_path_factory.mktemp("faults")
    rng = np.random.default_rng(0)
    array = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    array[:, PULSE_T] += 4.0
    array = disperse_array(array, 150, 1200., 200., TSAMP)
    sim_header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
                  "nsamples": NSAMPLES, "tsamp": TSAMP,
                  "foff": 200. / NCHAN}
    path = str(tmp / "survey.fil")
    write_simulated_filterbank(path, array, sim_header, descending=True)
    get_bad_chans(path)
    return path


def _snapshot(outdir, fingerprint):
    """Ledger bytes + per-member candidate bytes (zip timestamps are
    the only allowed whole-file difference)."""
    with open(os.path.join(outdir, f"progress_{fingerprint}.json"),
              "rb") as f:
        ledger = f.read()
    cands = {}
    for name in sorted(os.listdir(outdir)):
        if name.endswith(".npz"):
            with np.load(os.path.join(outdir, name),
                         allow_pickle=False) as d:
                cands[name] = {k: d[k].tobytes() for k in d.files}
    return ledger, cands


# ---------------------------------------------------------------------------
# FaultPlan plumbing
# ---------------------------------------------------------------------------

def test_fault_plan_budget_counts_and_roundtrip():
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", times=2),
                      FaultSpec(site="persist", kind="error",
                                chunks=(8,), times=None)])
    with plan.armed():
        for _ in range(2):
            with pytest.raises(RuntimeError, match="FAULTPLAN"):
                fault_inject.fire("dispatch", chunk=0)
        fault_inject.fire("dispatch", chunk=0)  # budget exhausted: no-op
        fault_inject.fire("persist", chunk=7)   # chunk mismatch: no-op
        for _ in range(3):                      # times=None: persistent
            with pytest.raises(OSError):
                fault_inject.fire("persist", chunk=8)
    assert plan.fired("dispatch") == 2
    assert plan.fired("persist") == 3
    assert plan.fired() == 5
    # armed() restored: hooks are inert again
    fault_inject.fire("dispatch", chunk=0)
    # JSON roundtrip preserves specs (fired counts reset — it's a plan,
    # not a transcript)
    clone = FaultPlan.from_json(plan.to_json())
    assert [s.to_json() for s in clone.specs] \
        == [s.to_json() for s in plan.specs]
    assert clone.fired() == 0


def test_env_var_arms_a_plan(monkeypatch):
    plan_json = FaultPlan([FaultSpec(site="read", kind="error",
                                     times=1)]).to_json()
    monkeypatch.setattr(fault_inject, "_ACTIVE", None)
    monkeypatch.setattr(fault_inject, "_ENV_CHECKED", False)
    monkeypatch.setenv("PUTPU_FAULT_PLAN", plan_json)
    plan = fault_inject.active()
    assert plan is not None
    with pytest.raises(OSError, match="FAULTPLAN"):
        plan.fire("read", chunk=0)
    # and the monkeypatched state is restored by the fixture teardown


def test_corrupt_kinds_deterministic_and_disarmed_noop():
    rng = np.random.default_rng(3)
    block = np.abs(rng.normal(1.0, 0.3, (16, 256)))
    # disarmed: the hook returns the SAME object
    assert fault_inject.corrupt("corrupt", block, chunk=0) is block
    for kind, check in (
        ("nan", lambda b: np.isnan(b).mean() > 0.005),
        ("inf", lambda b: np.isinf(b).mean() > 0.005),
        ("dead_channels", lambda b: (b.std(1) == 0).sum() >= 1),
        ("zero_run", lambda b: (b == 0).all(0).sum() >= 2),
        ("saturate", lambda b: (b == b.max()).mean() > 0.005),
    ):
        plan = FaultPlan([FaultSpec(site="corrupt", kind=kind,
                                    frac=0.01, times=None)])
        with plan.armed():
            out1 = fault_inject.corrupt("corrupt", block, chunk=5)
            out2 = fault_inject.corrupt("corrupt", block, chunk=5)
        assert out1 is not block and check(out1), kind
        np.testing.assert_array_equal(out1, out2)  # seeded: deterministic
        assert np.isfinite(block).all()            # input untouched
    # a transposed (F-ordered) block — the streaming reader's layout —
    # must corrupt in place of the copy, not into a lost ravel() copy
    plan = FaultPlan([FaultSpec(site="corrupt", kind="nan", frac=0.5)])
    with plan.armed():
        out = fault_inject.corrupt("corrupt", block.T, chunk=0)
    assert np.isnan(out).mean() > 0.2


# ---------------------------------------------------------------------------
# Integrity gate + deadline primitives
# ---------------------------------------------------------------------------

def test_gate_chunk_verdicts():
    rng = np.random.default_rng(4)
    clean = np.abs(rng.normal(1.0, 0.3, (8, 512)))
    pol = IntegrityPolicy()
    out, info = gate_chunk(clean, pol)
    assert out is clean and info["verdict"] == "clean"

    nanny = clean.copy()
    nanny[0, :50] = np.nan
    out, info = gate_chunk(nanny, pol)
    assert info["verdict"] == "sanitized"
    assert np.isfinite(out).all()
    # imputed values are the channel median — signal-free, not zeros
    assert abs(np.median(out[0, :50]) - np.median(clean[0, 50:])) < 0.5

    hard = clean.copy()
    hard[:, :] = np.nan
    out, info = gate_chunk(hard, pol)
    assert info["verdict"] == "quarantine" and "nan_frac" in info["reasons"]

    dead = clean.copy()
    dead[:6] = 0.0
    _, info = gate_chunk(dead, pol)
    assert info["verdict"] == "quarantine" and "dead_frac" in info["reasons"]

    # strict: ANY non-finite value quarantines instead of sanitizing
    _, info = gate_chunk(nanny, resolve_integrity_policy("strict"))
    assert info["verdict"] == "quarantine"
    assert resolve_integrity_policy("off") is None
    with pytest.raises(ValueError, match="quarantine policy"):
        resolve_integrity_policy("bogus")


def test_call_with_deadline():
    assert call_with_deadline(lambda: 42) == 42          # inline when off
    assert call_with_deadline(lambda: 42, 5.0) == 42
    with pytest.raises(ZeroDivisionError):
        call_with_deadline(lambda: 1 / 0, 5.0)           # exc propagates
    t0 = time.perf_counter()
    with pytest.raises(DispatchTimeoutError, match="deadline"):
        call_with_deadline(lambda: time.sleep(10), 0.2)
    assert time.perf_counter() - t0 < 5.0


# ---------------------------------------------------------------------------
# Hardened streaming loop
# ---------------------------------------------------------------------------

def test_default_run_is_inert_and_byte_identical(survey_file, tmp_path):
    """Acceptance pin: with no FaultPlan armed, the hardened loop's
    candidate/ledger outputs are byte-identical to a run with every
    robustness knob off, and BUDGET_JSON grows no new keys/buckets."""
    from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

    acct = BudgetAccountant()
    hits_a, store_a = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "default"), budget=acct,
        **SEARCH_KW)
    hits_b, store_b = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "off"),
        quarantine_policy="off", dispatch_timeout=None,
        **SEARCH_KW)
    assert [h[:2] for h in hits_a] == [h[:2] for h in hits_b]
    led_a, cands_a = _snapshot(str(tmp_path / "default"),
                               store_a.fingerprint)
    led_b, cands_b = _snapshot(str(tmp_path / "off"), store_b.fingerprint)
    assert cands_a == cands_b
    # a non-default policy gets its own resume fingerprint (its ledger
    # is not interchangeable with the default's on flagged data) while
    # the default keeps the pre-hardening fingerprint — so pre-PR
    # ledgers keep resuming; compare ledger CONTENT minus the
    # fingerprint field across the two runs
    assert store_a.fingerprint != store_b.fingerprint
    ja, jb = json.loads(led_a), json.loads(led_b)
    assert ja["done"] == jb["done"]
    assert set(ja) == set(jb) == {"fingerprint", "done"}
    # explicit "sanitize" == default fingerprint (the conditional
    # fingerprint key only appears for non-default policies)
    _, store_c = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "default"),
        quarantine_policy="sanitize", **SEARCH_KW)
    assert store_c.fingerprint == store_a.fingerprint
    # no quarantine manifest, no "quarantined" ledger key on clean runs
    assert not [f for f in os.listdir(str(tmp_path / "default"))
                if f.startswith("quarantine")]
    assert b"quarantined" not in led_a
    # BUDGET_JSON: same record keys as the round-6/7 ledger (plus the
    # ISSUE-5 schema_version stamp, the ISSUE-14 chunk_wall_s
    # percentile block, the ISSUE-25 call_s block and the ISSUE-7
    # autotune decision table — present only when kernel="auto"
    # resolved a geometry key during this stream), and no
    # robustness-named buckets leaked into the default path
    j = acct.to_json()
    assert set(j) <= {"schema_version", "chunks", "wall_s",
                      "chunk_wall_s", "buckets_s",
                      "unattributed_s", "attributed_pct", "counters",
                      "async_s", "call_s", "per_chunk",
                      "per_chunk_truncated",
                      "truncated_chunks", "rtt_s", "trips",
                      "trips_x_rtt_s", "autotune"}
    assert not any(("integrity" in k) or ("sanit" in k) or ("retry" in k)
                   for k in list(j["buckets_s"]) + list(j["call_s"]))


def test_transient_dispatch_error_retries_without_fallback(survey_file,
                                                           tmp_path):
    """One injected device failure -> same-backend retry -> identical
    outputs, no sticky numpy fallback, retry counter + span visible."""
    from pulsarutils_tpu.obs import trace

    base_out = str(tmp_path / "base")
    _, store0 = search_by_chunks(survey_file, output_dir=base_out,
                                 **SEARCH_KW)
    baseline = _snapshot(base_out, store0.fingerprint)

    plan = FaultPlan([FaultSpec(site="dispatch", kind="error",
                                chunks=(8192,), times=1)])
    before = _counter("putpu_dispatch_retries_total")
    tracer = trace.start_tracing()
    try:
        with plan.armed():
            hits, store = search_by_chunks(
                survey_file, output_dir=str(tmp_path / "faulted"),
                **SEARCH_KW)
    finally:
        trace.stop_tracing()
    assert plan.fired() == 1
    assert _counter("putpu_dispatch_retries_total") == before + 1
    names = {e["name"] for e in tracer.to_chrome()["traceEvents"]}
    assert "dispatch_retry" in names
    fresh = _snapshot(str(tmp_path / "faulted"), store.fingerprint)
    assert baseline == fresh


def test_injected_dispatch_hang_is_bounded(survey_file, tmp_path):
    """Acceptance: a wedged dispatch used to stall forever; with a
    sub-second dispatch_timeout the run proceeds past the wedged chunk
    within timeout x retries and still finds the pulse."""
    plan = FaultPlan([FaultSpec(site="dispatch", kind="hang",
                                seconds=30.0, chunks=(0,), times=1)])
    t0 = time.perf_counter()
    with plan.armed():
        hits, _ = search_by_chunks(
            survey_file, output_dir=str(tmp_path),
            dispatch_timeout=0.5, dispatch_retries=2,
            dispatch_backoff=0.01, **SEARCH_KW)
    elapsed = time.perf_counter() - t0
    assert plan.fired() == 1
    assert elapsed < 25.0, "run did not break out of the injected hang"
    assert any(lo <= PULSE_T < hi for lo, hi, _, _ in hits)


def test_hard_corrupt_chunk_quarantined_resume_exact(survey_file,
                                                     tmp_path):
    """An unrecoverably corrupt chunk lands in the manifest + ledger
    (done-with-reason), the pulse is still found, resume skips the
    quarantined chunk, and the audit reports zero inconsistencies."""
    outdir = str(tmp_path)
    plan = FaultPlan([FaultSpec(site="corrupt", kind="nan", chunks=(0,),
                                frac=0.9, times=1)])
    before = _counter("putpu_chunks_quarantined_total")
    with plan.armed():
        hits, store = search_by_chunks(survey_file, output_dir=outdir,
                                       **SEARCH_KW)
    assert _counter("putpu_chunks_quarantined_total") == before + 1
    assert store.quarantined_chunks == {"0": "integrity:nan_frac"}
    assert store.is_done(0)
    assert any(lo <= PULSE_T < hi for lo, hi, _, _ in hits)
    manifest = [f for f in os.listdir(outdir)
                if f.startswith("quarantine_")]
    assert len(manifest) == 1
    recs = [json.loads(line) for line in
            open(os.path.join(outdir, manifest[0]))]
    assert recs[0]["chunk"] == 0 and "nan_frac" in recs[0]["reason"]
    assert recs[0]["stats"]["nan_frac"] > 0.8
    report = audit_run(outdir, store.fingerprint, root="survey")
    assert report["ok"], report["issues"]
    # resume: the quarantined chunk is NOT re-searched (a fresh armed
    # plan would corrupt it again — it must never fire)
    plan2 = FaultPlan([FaultSpec(site="corrupt", kind="nan", chunks=(0,),
                                 frac=0.9, times=1)])
    with plan2.armed():
        hits2, store2 = search_by_chunks(survey_file, output_dir=outdir,
                                         **SEARCH_KW)
    assert plan2.fired() == 0
    assert store2.quarantined_chunks == {"0": "integrity:nan_frac"}
    assert {h[:2] for h in hits2} == {h[:2] for h in hits}


def test_sanitized_chunk_keeps_outputs_byte_identical(survey_file,
                                                      tmp_path):
    base_out = str(tmp_path / "base")
    _, store0 = search_by_chunks(survey_file, output_dir=base_out,
                                 **SEARCH_KW)
    baseline = _snapshot(base_out, store0.fingerprint)
    plan = FaultPlan([FaultSpec(site="corrupt", kind="nan", chunks=(0,),
                                frac=0.02, times=1)])
    before = _counter("putpu_chunks_sanitized_total")
    with plan.armed():
        _, store = search_by_chunks(
            survey_file, output_dir=str(tmp_path / "san"), **SEARCH_KW)
    assert plan.fired() == 1
    assert _counter("putpu_chunks_sanitized_total") == before + 1
    assert store.quarantined_chunks == {}
    assert _snapshot(str(tmp_path / "san"), store.fingerprint) == baseline


def test_persist_transient_retry_then_dead_letter(survey_file, tmp_path):
    # transient: one failed write, retried, candidates intact
    base_out = str(tmp_path / "base")
    _, store0 = search_by_chunks(survey_file, output_dir=base_out,
                                 **SEARCH_KW)
    baseline = _snapshot(base_out, store0.fingerprint)
    plan = FaultPlan([FaultSpec(site="persist", kind="error", times=1)])
    before = _counter("putpu_persist_retries_total")
    with plan.armed():
        _, store = search_by_chunks(
            survey_file, output_dir=str(tmp_path / "retry"),
            persist_backoff=0.01, **SEARCH_KW)
    assert plan.fired() == 1
    assert _counter("putpu_persist_retries_total") == before + 1
    assert _snapshot(str(tmp_path / "retry"), store.fingerprint) == baseline

    # persistent: dead-letter instead of failing the run
    plan = FaultPlan([FaultSpec(site="persist", kind="error", times=None)])
    before_dl = _counter("putpu_persist_dead_letter_total")
    with plan.armed():
        hits, store = search_by_chunks(
            survey_file, output_dir=str(tmp_path / "dl"),
            persist_backoff=0.01, **SEARCH_KW)
    assert len(hits) == 2  # the search itself still reports the pulse
    assert _counter("putpu_persist_dead_letter_total") == before_dl + 2
    assert set(store.quarantined_chunks.values()) == {"persist_dead_letter"}
    assert not [f for f in os.listdir(str(tmp_path / "dl"))
                if f.endswith(".npz")]
    report = audit_run(str(tmp_path / "dl"), store.fingerprint,
                       root="survey")
    assert report["ok"], report["issues"]


def test_torn_ledger_recovers_with_backup(tmp_path, caplog):
    """Satellite: a ledger truncated mid-file used to raise
    json.JSONDecodeError and kill resume entirely."""
    fp = config_fingerprint(x="torn")
    store = CandidateStore(str(tmp_path), fp)
    for c in (0, 8192, 16384):
        store.mark_done(c)
    ledger_path = store._ledger_path
    with open(ledger_path, "rb") as f:
        blob = f.read()
    with open(ledger_path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with caplog.at_level(logging.WARNING, logger="pulsarutils_tpu"):
        fresh = CandidateStore(str(tmp_path), fp)
    assert fresh.done_chunks == []           # fresh ledger, not a crash
    assert not fresh.is_done(0)
    assert os.path.exists(ledger_path + ".corrupt")
    assert any("torn/corrupt resume ledger" in r.getMessage()
               for r in caplog.records)
    # the recovered store keeps working
    fresh.mark_done(0)
    assert CandidateStore(str(tmp_path), fp).done_chunks == [0]


def test_mark_done_reason_roundtrip(tmp_path):
    fp = config_fingerprint(x="q")
    store = CandidateStore(str(tmp_path), fp)
    store.mark_done(0)
    store.mark_done(8192, reason="integrity:nan_frac")
    reloaded = CandidateStore(str(tmp_path), fp)
    assert reloaded.is_done(0) and reloaded.is_done(8192)
    assert reloaded.quarantined_chunks == {"8192": "integrity:nan_frac"}
    # reason-free ledgers carry no "quarantined" key (byte compat)
    fp2 = config_fingerprint(x="plain")
    CandidateStore(str(tmp_path), fp2).mark_done(0)
    with open(os.path.join(str(tmp_path), f"progress_{fp2}.json")) as f:
        assert json.load(f) == {"fingerprint": fp2, "done": [0]}


def test_resume_skips_corrupt_pair_and_counts(survey_file, tmp_path):
    """Satellite: the resume restore path skips a corrupt persisted pair
    via the narrowed load-error list and counts the skip."""
    outdir = str(tmp_path)
    hits, store = search_by_chunks(survey_file, output_dir=outdir,
                                   **SEARCH_KW)
    assert len(hits) == 2
    # corrupt one persisted info file (truncate the zip mid-way)
    name = sorted(f for f in os.listdir(outdir)
                  if f.endswith(".info.npz"))[0]
    path = os.path.join(outdir, name)
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    before = _counter("putpu_resume_pairs_skipped_total")
    hits2, _ = search_by_chunks(survey_file, output_dir=outdir,
                                **SEARCH_KW)
    assert _counter("putpu_resume_pairs_skipped_total") == before + 1
    assert len(hits2) == 1  # the other candidate still restores


def test_audit_detects_and_repairs_torn_pairs(tmp_path):
    fp = config_fingerprint(x="audit")
    store = CandidateStore(str(tmp_path), fp)
    store.mark_done(0)
    # a torn pair: info without table
    stray = os.path.join(str(tmp_path), "survey_0-16384.info.npz")
    np.savez_compressed(stray, __scalars__=json.dumps({"nbin": 4}))
    report = audit_run(str(tmp_path), fp, root="survey")
    assert not report["ok"]
    assert report["issues"][0]["kind"] == "torn_pair"
    report = audit_run(str(tmp_path), fp, root="survey", repair=True)
    assert report["repaired"] == [stray]
    assert not os.path.exists(stray)
    assert audit_run(str(tmp_path), fp, root="survey")["ok"]


@pytest.fixture(scope="module")
def mesh8():
    import jax

    from pulsarutils_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    return make_mesh((4, 2), ("dm", "chan"))


def test_mesh_persistent_failure_sticky_fallback(survey_file, mesh8,
                                                 tmp_path):
    """Satellite: a persistently failing mesh is discovered ONCE (two
    doomed attempts on the first chunk), every later chunk goes straight
    to numpy, and the candidate store sees one consistent trial grid."""
    plan = FaultPlan([FaultSpec(site="mesh", kind="error", times=None)])
    with plan.armed():
        hits, store = search_by_chunks(
            survey_file, output_dir=str(tmp_path), kernel="hybrid",
            mesh=mesh8, resume=False, **SEARCH_KW)
    # exactly the first chunk's two doomed attempts — never re-probed
    assert plan.fired("mesh") == 2
    assert any(lo <= PULSE_T < hi for lo, hi, _, _ in hits)
    # one consistent trial grid across every persisted candidate
    tables = [h[3] for h in hits]
    for t in tables[1:]:
        np.testing.assert_array_equal(np.asarray(t["DM"]),
                                      np.asarray(tables[0]["DM"]))


def test_stream_search_skip_failed_contains_one_bad_chunk():
    from pulsarutils_tpu.models.simulate import simulate_test_data
    from pulsarutils_tpu.parallel.stream import stream_search

    array, header = simulate_test_data(150, nchan=16, nsamples=2048,
                                       rng=13)
    chunks = [(0, array), (2048, array), (4096, array)]
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error",
                                chunks=(2048,), times=None)])
    # default: the failure propagates (pre-hardening contract)
    with plan.armed():
        with pytest.raises(RuntimeError, match="FAULTPLAN"):
            stream_search(chunks, 100, 200., header["fbottom"],
                          header["bandwidth"], header["tsamp"],
                          backend="numpy")
    # skip_failed: the stream survives, the chunk is absent + counted
    before = _counter("putpu_stream_chunks_failed_total")
    plan2 = FaultPlan([FaultSpec(site="dispatch", kind="error",
                                 chunks=(2048,), times=None)])
    with plan2.armed():
        results, hits = stream_search(
            chunks, 100, 200., header["fbottom"], header["bandwidth"],
            header["tsamp"], backend="numpy", skip_failed=True)
    assert [r[0] for r in results] == [0, 4096]
    assert _counter("putpu_stream_chunks_failed_total") == before + 1
    assert plan2.fired() == 1


def test_search_with_fallback_deadline_defaults_inline(monkeypatch):
    """The default DispatchPolicy reproduces the pre-hardening ladder
    (jax, jax, numpy) on the calling thread — pinned against the
    monkeypatch idiom the original fallback test uses."""
    import threading

    from pulsarutils_tpu.models.simulate import simulate_test_data
    from pulsarutils_tpu.pipeline import search_pipeline as sp

    array, header = simulate_test_data(150, nchan=16, nsamples=1024,
                                       rng=33)
    real = sp.dedispersion_search
    calls = []

    def flaky(data, *args, backend="numpy", **kw):
        calls.append((backend, threading.current_thread()
                      is threading.main_thread()))
        if backend == "jax":
            raise RuntimeError("fake device crash")
        return real(data, *args, backend=backend, **kw)

    monkeypatch.setattr(sp, "dedispersion_search", flaky)
    table = sp._search_with_fallback(
        array, 100, 200., header["fbottom"], header["bandwidth"],
        header["tsamp"], backend="jax", kernel="auto",
        capture_plane=False)
    assert [c[0] for c in calls] == ["jax", "jax", "numpy"]
    assert all(on_main for _, on_main in calls)  # no watchdog by default


@pytest.mark.slow
def test_chaos_drill_full_matrix():
    """The committed proof artifact, executed: every fault class in
    tools/chaos_drill.py passes its recoverable/unrecoverable
    contract.

    The counts assert the REAL current matrix (this test drifted again
    when the ISSUE 18/19 classes landed — re-pinned with the ISSUE 20
    capacity classes): recoverable = 7 fault-plan classes (transient
    dispatch/hang/persist/read, sanitizable NaN, dead channels,
    transient OOM) + period_accumulation + torn_ledger +
    killed_coordinator + partitioned_worker + torn_journal +
    dead_subscriber + disconnected_feed + starved_fleet +
    saturated_fleet = 16; contained = oom_floor + hard_corrupt +
    truncated_read + dead_letter + lossy_feed + overrun_feed = 6.
    """
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_drill", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "chaos_drill.py"))
    drill = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drill)
    result = drill.run_drill(log=lambda *_: None)
    assert result["all_ok"], result["classes"]
    assert result["n_classes"] == 22
    assert result["recovered_identical"] == 16
    assert result["contained"] == 6
    for name in ("killed_coordinator", "partitioned_worker",
                 "torn_journal", "starved_fleet", "saturated_fleet"):
        assert result["classes"][name]["ok"], result["classes"][name]


def test_gate_skipped_for_lowbit_unpacked(tmp_path):
    """Quantized low-bit data is ~50% 'at the rail' by construction —
    the gate must not false-quarantine healthy 1-bit chunks on the
    host-decoded (non-packed) route (code-review r8)."""
    rng = np.random.default_rng(5)
    nchan, nsamples = 32, 8192
    array = (rng.normal(0.6, 0.5, (nchan, nsamples)) > 0.5).astype(float)
    sim_header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
                  "nsamples": nsamples, "tsamp": TSAMP,
                  "foff": 200. / nchan}
    path = str(tmp_path / "onebit.fil")
    write_simulated_filterbank(path, array, sim_header, nbits=1)
    before = _counter("putpu_chunks_quarantined_total")
    hits, store = search_by_chunks(
        path, dmmin=100, dmmax=200, backend="numpy",
        chunk_length=2048 * TSAMP, output_dir=str(tmp_path / "out"),
        make_plots=False, progress=False, snr_threshold=1e9)
    assert _counter("putpu_chunks_quarantined_total") == before
    assert store.quarantined_chunks == {}
    assert len(store.done_chunks) >= 2


def test_torn_manifest_line_never_fatal(tmp_path):
    """A crash mid-append leaves a torn manifest line; records() skips
    it and the audit stays clean instead of raising (code-review r8)."""
    from pulsarutils_tpu.faults.policy import QuarantineManifest

    fp = config_fingerprint(x="tornq")
    store = CandidateStore(str(tmp_path), fp)
    m = QuarantineManifest(str(tmp_path), fp)
    m.record(0, 16384, "integrity:nan_frac")
    store.mark_done(0, reason="integrity:nan_frac")
    with open(m.path, "a") as f:
        f.write('{"chunk": 8192, "end": 245')  # torn mid-append
    assert [r["chunk"] for r in m.records()] == [0]
    report = audit_run(str(tmp_path), fp)
    assert report["ok"], report["issues"]


def test_ledger_oserror_propagates(tmp_path, monkeypatch):
    """A transient OSError on an intact ledger must NOT trash it into
    .corrupt — only parse failures mean corruption (code-review r8)."""
    import builtins

    fp = config_fingerprint(x="io")
    store = CandidateStore(str(tmp_path), fp)
    store.mark_done(0)
    real_open = builtins.open

    def flaky_open(path, *a, **k):
        if str(path).endswith(f"progress_{fp}.json"):
            raise OSError("transient EIO")
        return real_open(path, *a, **k)

    monkeypatch.setattr(builtins, "open", flaky_open)
    with pytest.raises(OSError, match="EIO"):
        CandidateStore(str(tmp_path), fp)
    monkeypatch.undo()
    # the intact ledger survived untouched
    assert CandidateStore(str(tmp_path), fp).done_chunks == [0]
    assert not os.path.exists(store._ledger_path + ".corrupt")


def test_gate_dc_offset_float32_not_flagged_dead(tmp_path):
    """One-pass E[x^2]-mean^2 variance cancelled catastrophically on
    float32 blocks with a big DC offset and flagged healthy channels
    dead (code-review r8): two-pass/float64 must not."""
    rng = np.random.default_rng(6)
    block = (rng.normal(2e5, 5.0, (16, 4096))).astype(np.float32)
    from pulsarutils_tpu.faults.policy import chunk_stats

    stats = chunk_stats(block)
    assert stats["dead_frac"] == 0.0
    _, info = gate_chunk(block, IntegrityPolicy())
    assert info["verdict"] == "clean"


def test_gate_tiny_nan_count_still_sanitized():
    """Verdicts must come from the RAW nan fraction: a couple of NaNs
    in a big chunk round to 0.0 at six decimals but poison every DM
    trial they touch (code-review r8)."""
    rng = np.random.default_rng(7)
    block = np.abs(rng.normal(1.0, 0.3, (1024, 4096)))
    block[3, 100] = np.nan
    block[9, 2000] = np.nan
    out, info = gate_chunk(block, IntegrityPolicy())
    assert info["verdict"] == "sanitized"
    assert np.isfinite(out).all()
    assert info["stats"]["nan_frac"] == 0.0  # display rounding only
    # strict mode quarantines the same chunk rather than letting it by
    _, info = gate_chunk(block, resolve_integrity_policy("strict"))
    assert info["verdict"] == "quarantine"


def test_corrupt_preserves_floating_dtype():
    """A float32 survey chunk must stay float32 through corruption — a
    float64 copy would retrace the jitted clean/search for a signature
    production never runs (code-review r8); ints promote to float32 so
    nan is expressible."""
    plan = FaultPlan([FaultSpec(site="corrupt", kind="nan", frac=0.1,
                                times=None)])
    with plan.armed():
        f32 = fault_inject.corrupt(
            "corrupt", np.ones((4, 64), np.float32), chunk=0)
        i8 = fault_inject.corrupt(
            "corrupt", np.ones((4, 64), np.uint8), chunk=0)
    assert f32.dtype == np.float32 and np.isnan(f32).any()
    assert i8.dtype == np.float32 and np.isnan(i8).any()


@pytest.mark.parametrize("victim", ("table_stored", "info_stored",
                                    "info_deflated"))
def test_resume_skips_bitrotted_deflate_member(survey_file, tmp_path,
                                               victim):
    """A .npz with an intact zip directory but a rotted member payload
    fails on load — a stored member (the table always, the record since
    ISSUE 26) with ``BadZipFile`` on its CRC, a deflated one (a record
    written before ISSUE 26) with ``zlib.error`` — and the restore loop
    must skip+count it, not die (code-review r8)."""
    import struct
    import zipfile as _zipfile

    outdir = str(tmp_path)
    hits, store = search_by_chunks(survey_file, output_dir=outdir,
                                   **SEARCH_KW)
    assert len(hits) == 2
    suffix = ".table.npz" if victim == "table_stored" else ".info.npz"
    name = sorted(f for f in os.listdir(outdir) if f.endswith(suffix))[0]
    path = os.path.join(outdir, name)
    if victim == "info_deflated":
        with np.load(path, allow_pickle=False) as data:
            np.savez_compressed(path, **{k: data[k] for k in data.files})
    # bit-rot one member's payload, keeping the zip central directory
    # (and the member sizes/offsets) intact: the start of the first
    # member's stream, or the middle of the stored cutout's samples
    with _zipfile.ZipFile(path) as z:
        member = (z.getinfo("allprofs.npy") if victim == "info_stored"
                  else z.infolist()[0])
    expect = (_zipfile.ZIP_DEFLATED if victim == "info_deflated"
              else _zipfile.ZIP_STORED)
    assert member.compress_type == expect
    with open(path, "r+b") as f:
        f.seek(member.header_offset + 26)
        nlen, elen = struct.unpack("<HH", f.read(4))
        f.seek(member.header_offset + 30 + nlen + elen
               + (member.file_size // 2 if victim == "info_stored" else 2))
        rot = bytes(b ^ 0xFF for b in f.read(4))
        f.seek(-4, os.SEEK_CUR)
        f.write(rot)
    before = _counter("putpu_resume_pairs_skipped_total")
    hits2, _ = search_by_chunks(survey_file, output_dir=outdir,
                                **SEARCH_KW)
    assert _counter("putpu_resume_pairs_skipped_total") == before + 1
    assert len(hits2) == 1


def test_audit_dead_letter_remnant_not_inconsistent(tmp_path):
    """A persist that failed mid-pair (info written, table not) under a
    dead-letter leaves a partial pair — the ledger carries the reason,
    so the audit must report it as an expected remnant, not a torn-pair
    inconsistency (code-review r8)."""
    from pulsarutils_tpu.faults.policy import QuarantineManifest

    fp = config_fingerprint(x="dlrem")
    store = CandidateStore(str(tmp_path), fp)
    stray = os.path.join(str(tmp_path), "survey_0-16384.info.npz")
    np.savez_compressed(stray, __scalars__=json.dumps({"nbin": 4}))
    QuarantineManifest(str(tmp_path), fp).record(
        0, 16384, "persist_dead_letter")
    store.mark_done(0, reason="persist_dead_letter")
    report = audit_run(str(tmp_path), fp, root="survey")
    assert report["ok"], report["issues"]
    assert report["orphans"][0]["kind"] == "dead_letter_remnant"
    # repair removes the stray half either way
    report = audit_run(str(tmp_path), fp, root="survey", repair=True)
    assert report["repaired"] == [stray]
    assert not os.path.exists(stray)


def test_persistent_dispatch_fault_sticky_numpy_fallback(survey_file,
                                                         tmp_path):
    """A PERSISTENT device fault (FaultSpec times=None) must be
    survivable: the injection site skips the numpy last-resort attempt,
    so the run degrades to the reference path instead of crashing
    through its own fallback (code-review r8).  Like the mesh sticky
    test, the dead backend is discovered once — two doomed attempts on
    the first chunk only."""
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error",
                                times=None)])
    with plan.armed():
        hits, store = search_by_chunks(
            survey_file, output_dir=str(tmp_path), resume=False,
            **SEARCH_KW)
    assert plan.fired("dispatch") == 2
    assert any(lo <= PULSE_T < hi for lo, hi, _, _ in hits)


def test_env_armed_read_fault_spares_badchans_prescan(survey_file,
                                                      tmp_path):
    """The bad-channel pre-scan shares the reader seam but runs before
    the hardened chunk loop: injection is suppressed there, so a read
    fault targets the search chunks (and an env/CLI chaos run cannot
    crash at startup) — code-review r8."""
    # force a cold scan: new file path via copy, no .badchans cache
    import shutil

    path = str(tmp_path / "fresh.fil")
    shutil.copy(survey_file, path)
    plan = FaultPlan([FaultSpec(site="read", kind="error", chunks=(0,),
                                times=1)])
    with plan.armed():
        hits, store = search_by_chunks(path, output_dir=str(tmp_path),
                                       **SEARCH_KW)
    # the fault fired on the SEARCH chunk (retried, recovered), not on
    # the pre-scan; the run completed normally
    assert plan.fired("read") == 1
    assert store.quarantined_chunks == {}
    assert len(store.done_chunks) == 3


def test_audit_does_not_recover_torn_ledger(tmp_path):
    """The audit must never move the evidence: a torn ledger is
    reported as an issue, not renamed aside by CandidateStore's
    recovery loader (code-review r8)."""
    fp = config_fingerprint(x="auditledger")
    store = CandidateStore(str(tmp_path), fp)
    store.mark_done(0)
    with open(store._ledger_path, "r+b") as f:
        blob = f.read()
        f.seek(0)
        f.truncate()
        f.write(blob[: len(blob) // 2])
    report = audit_run(str(tmp_path), fp)
    assert not report["ok"]
    assert report["issues"][0]["kind"] == "ledger_unreadable"
    assert not os.path.exists(store._ledger_path + ".corrupt")
    assert os.path.exists(store._ledger_path)  # evidence untouched


def test_corrupt_saturate_composes_after_nan():
    """saturate after nan on the same chunk must still clip (the plain
    quantile/max would be NaN -> silent no-op; code-review r8)."""
    rng = np.random.default_rng(8)
    block = np.abs(rng.normal(1.0, 0.3, (16, 512)))
    plan = FaultPlan([
        FaultSpec(site="corrupt", kind="nan", frac=0.05, times=None),
        FaultSpec(site="corrupt", kind="saturate", frac=0.1, times=None),
    ])
    with plan.armed():
        out = fault_inject.corrupt("corrupt", block, chunk=0)
    assert np.isnan(out).any()
    finite = out[np.isfinite(out)]
    assert (finite == finite.max()).mean() > 0.05  # railed


def test_corrupt_impulse_rfi_storm_kind():
    """kind="impulse" (ISSUE 5): bright broadband un-dispersed columns
    — the candidate-rate-spike signature the health engine's RFI-storm
    detector consumes.  Deterministic, copy-on-write, amp in block
    stds, and the non-default amp survives the JSON round trip."""
    rng = np.random.default_rng(9)
    block = np.abs(rng.normal(0, 0.5, (16, 1024))) + 20.0
    plan = FaultPlan([FaultSpec(site="corrupt", kind="impulse",
                                frac=0.01, amp=50.0, times=None)])
    with plan.armed():
        out = fault_inject.corrupt("corrupt", block, chunk=0)
        again = fault_inject.corrupt("corrupt", block, chunk=0)
    assert out is not block and (block == np.asarray(block)).all()
    np.testing.assert_array_equal(out, again)  # seeded per (seed, chunk)
    delta = out - block
    hit_cols = np.flatnonzero(np.abs(delta).max(axis=0) > 0)
    assert len(hit_cols) == 10  # frac * nsamp
    # broadband: EVERY channel is lifted at the hit columns, by ~amp
    # times the block std (~0.3 for abs-normal*0.5 noise)
    assert (delta[:, hit_cols] > 0).all()
    assert 5.0 < delta[:, hit_cols].mean() < 25.0
    # amp is serialised only when non-default (existing plan JSON pins)
    spec_json = plan.specs[0].to_json()
    assert spec_json["amp"] == 50.0
    assert "amp" not in FaultSpec(site="corrupt",
                                  kind="impulse").to_json()
    clone = FaultPlan.from_json(plan.to_json())
    assert clone.specs[0].amp == 50.0


def test_compile_time_error_propagates_not_numpy_fallback(survey_file,
                                                          tmp_path,
                                                          monkeypatch):
    """An error raised while a program is traced, lowered or compiled is
    deterministic: retrying it, or searching every later chunk with
    NumPy, hides a broken build behind a slow "successful" run.  It
    must propagate like the configuration errors do (ISSUE 22); the
    injected RUNTIME faults above still reach the NumPy floor."""
    import jax

    from pulsarutils_tpu.faults import compile_phase
    from pulsarutils_tpu.pipeline import search_pipeline

    @jax.jit
    def refused(x):
        raise NotImplementedError("lowering refused (stand-in for Mosaic)")

    def broken_search(data, *args, **kwargs):
        if kwargs.get("backend") == "numpy":
            pytest.fail("compile-time failure fell back to NumPy")
        return refused(np.zeros(3, np.float32))

    monkeypatch.setattr(search_pipeline, "dedispersion_search",
                        broken_search)
    retries = _counter("putpu_dispatch_retries_total")
    with pytest.raises(NotImplementedError) as err:
        search_by_chunks(survey_file, output_dir=str(tmp_path),
                         **SEARCH_KW)
    assert compile_phase.failed_phase(err.value) == "trace"
    assert _counter("putpu_dispatch_retries_total") == retries
    # the same type raised at run time carries no mark: still retried
    assert compile_phase.failed_phase(NotImplementedError("x")) is None


def test_cli_exit_status_nonzero_when_run_ends_on_fallback(survey_file,
                                                           tmp_path):
    """``PUsearchfrb`` persists everything as before, but a run that
    ended on the NumPy fall-back backend no longer exits 0."""
    from pulsarutils_tpu.cli import search_main

    argv = [survey_file, "--dmmin", "100", "--dmmax", "200",
            "--chunk-length", str(CHUNK_LEN_S), "--plots", "none",
            "--snr-threshold", "6.5", "--dispatch-retries", "0"]
    assert search_main.main(argv + ["--output-dir",
                                    str(tmp_path / "clean")]) == 0
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error",
                                times=None)])  # persistent device fault
    before = REGISTRY.total("putpu_host_fallbacks_total")
    with plan.armed():
        rc = search_main.main(argv + ["--output-dir",
                                      str(tmp_path / "faulted")])
    assert rc == search_main.EXIT_DEGRADED != 0
    assert REGISTRY.total("putpu_host_fallbacks_total") == before + 1
    # the NumPy floor still found and persisted the pulse
    cands = list(CandidateStore(str(tmp_path / "faulted")).candidates())
    assert any(lo <= PULSE_T < hi for _, lo, hi in cands)
