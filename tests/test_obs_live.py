"""ISSUE 5 — live survey health surface: canary pulse injection, the
rolling health engine, the HTTP scrape endpoints and the end-of-run
survey report.  Tier-1 throughout: tiny surveys, ephemeral ports.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pulsarutils_tpu.obs import metrics
from pulsarutils_tpu.obs.canary import CanaryController
from pulsarutils_tpu.obs.health import CRITICAL, DEGRADED, OK, HealthEngine
from pulsarutils_tpu.obs.server import start_obs_server


def _get(url, timeout=5.0):
    """(status, body) — urllib raises on 5xx, we want the code."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode()


# ---------------------------------------------------------------------------
# health engine
# ---------------------------------------------------------------------------

def test_health_candidate_storm_flags_and_recovers():
    eng = HealthEngine(recover_after=2)
    for i in range(3):
        assert eng.update(i, wall_s=0.1, candidates=1) == OK
    # RFI-storm signature: a candidate-rate spike
    assert eng.update(3, wall_s=0.1, candidates=200) == DEGRADED
    assert eng.reasons() == ["candidate_storm"]
    # hysteresis: one clean chunk is not recovery yet...
    assert eng.update(4, wall_s=0.1, candidates=1) == DEGRADED
    # ...two are
    assert eng.update(5, wall_s=0.1, candidates=1) == OK
    transitions = [(t["from"], t["to"]) for t in eng.transitions]
    assert transitions == [(OK, DEGRADED), (DEGRADED, OK)]
    # incident log carries raise + resolve with the reasoned detail
    kinds = [(i["kind"], i["event"]) for i in eng.snapshot()["incidents"]]
    assert ("candidate_storm", "raised") in kinds
    assert ("candidate_storm", "resolved") in kinds


def test_health_sustained_storm_escalates_to_critical():
    eng = HealthEngine(storm_critical_after=3)
    for i in range(3):
        eng.update(i, candidates=1)
    eng.update(3, candidates=200)
    eng.update(4, candidates=200)
    assert eng.update(5, candidates=200) == CRITICAL


def test_health_wall_time_ewma_spike():
    eng = HealthEngine()
    for i in range(4):
        eng.update(i, wall_s=1.0)
    assert eng.update(4, wall_s=10.0) == DEGRADED
    assert "slow_chunk" in eng.reasons()
    # the spike is EXCLUDED from the baseline: a second normal chunk
    # must not look slow relative to a storm-dragged EWMA
    eng.update(5, wall_s=1.0)
    assert eng.update(6, wall_s=1.0) == OK


def test_health_canary_recall_floor_is_critical():
    eng = HealthEngine(recall_floor=0.7, recall_min_injected=10)
    # below the minimum injected count: recall is not judged yet
    assert eng.update(0, canary={"injected": 5,
                                 "window_recall": 0.0}) == OK
    assert eng.update(1, canary={"injected": 10,
                                 "window_recall": 0.5}) == CRITICAL
    assert "canary_recall" in eng.reasons()
    eng.update(2, canary={"injected": 12, "window_recall": 1.0})
    assert eng.update(3, canary={"injected": 13,
                                 "window_recall": 1.0}) == OK


def test_health_sticky_fallback_never_decays():
    eng = HealthEngine(recover_after=1)
    eng.update(0, fallback=True)
    for i in range(1, 6):
        assert eng.update(i, wall_s=0.1, candidates=0) == DEGRADED
    assert "numpy_fallback" in eng.reasons()


def test_health_quarantine_counts_and_headroom():
    eng = HealthEngine(quarantine_critical=3, recover_after=10)
    assert eng.update(0, quarantined=True) == DEGRADED
    assert eng.update(1, quarantined=True) == DEGRADED
    assert eng.update(2, quarantined=True) == CRITICAL
    eng2 = HealthEngine()
    assert eng2.update(0, headroom_frac=0.5) == OK
    assert eng2.update(1, headroom_frac=0.05) == DEGRADED
    assert eng2.update(2, headroom_frac=0.01) == CRITICAL


# ---------------------------------------------------------------------------
# canary controller
# ---------------------------------------------------------------------------

def test_canary_selection_deterministic_and_rate_bounded():
    c = CanaryController(rate=0.3, seed=7)
    picks = [c.selects(i * 4096) for i in range(200)]
    assert picks == [c.selects(i * 4096) for i in range(200)]  # stable
    assert 20 < sum(picks) < 100  # ~60 expected
    with pytest.raises(ValueError):
        CanaryController(rate=1.5)


def test_canary_inject_is_byte_inert_when_not_selected():
    c = CanaryController(rate=1.0, dm=150.0, seed=0)
    c.bind(nchan=8, start_freq=1200., bandwidth=200., tsamp=0.0005,
           dmmin=100, dmmax=200)
    block = np.ones((8, 512), dtype=np.float32)
    # rate 0 via selects(): fake an unselected chunk by rate=0 clone
    c0 = CanaryController(rate=0.0, dm=150.0)
    assert c0.maybe_inject(block, 0) is block  # the SAME object
    out = c.maybe_inject(block, 0)
    assert out is not block and out.dtype == block.dtype
    assert (out != block).any()


def test_canary_integer_blocks_keep_dtype():
    c = CanaryController(rate=1.0, dm=150.0, snr=50.0)
    c.bind(nchan=8, start_freq=1200., bandwidth=200., tsamp=0.0005,
           dmmin=100, dmmax=200)
    block = np.full((8, 512), 250, dtype=np.uint8)
    out = c.maybe_inject(block, 0)
    assert out.dtype == np.uint8
    assert out.max() == 255  # clipped at the rail, no wraparound


def test_canary_observe_matches_and_excludes(tmp_path):
    # a real single-device search over a synthetic chunk with the
    # canary injected: observe() must recover it with a sane S/N ratio
    from pulsarutils_tpu.ops.search import dedispersion_search

    rng = np.random.default_rng(0)
    nchan, nsamp = 64, 8192
    block = np.abs(rng.normal(0, 0.5, (nchan, nsamp))) + 20.0
    c = CanaryController(rate=1.0, snr=15.0, seed=3)
    c.bind(nchan=nchan, start_freq=1200., bandwidth=200., tsamp=0.0005,
           dmmin=100, dmmax=200)
    injected = c.maybe_inject(block, 0)
    from pulsarutils_tpu.ops.clean_ops import renormalize_data

    table = dedispersion_search(
        np.asarray(renormalize_data(injected)), 100, 200, 1200., 200.,
        0.0005, backend="jax")
    obs = c.observe(0, table, 6.5)
    assert obs["recovered"] and obs["best_is_canary"]
    assert 0.4 < obs["ratio"] < 2.0
    assert abs(obs["dm_error"]) < 5.0
    s = c.summary()
    assert s["injected"] == 1 and s["recovered"] == 1 and s["recall"] == 1.0
    # a chunk that never reached the search is discarded, not a miss
    c.maybe_inject(block, 4096)
    c.discard(4096)
    assert c.summary()["injected"] == 1 and c.discarded == 1


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------

def test_endpoints_metrics_healthz_progress():
    reg = metrics.MetricsRegistry()
    reg.counter("putpu_live_total", help="h").inc(3)
    eng = HealthEngine(storm_critical_after=2)
    progress = {"chunks_done": 1, "chunks_total": 3}
    srv = start_obs_server(0, health=eng,
                           progress_fn=lambda: dict(progress),
                           registry=reg)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status, body = _get(base + "/metrics")
        assert status == 200 and "putpu_live_total 3" in body

        status, body = _get(base + "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["status"] == "OK"

        status, body = _get(base + "/progress")
        doc = json.loads(body)
        assert status == 200
        assert doc["chunks_done"] == 1 and doc["status"] == "OK"

        # storm -> DEGRADED (still HTTP 200: scrapeable, flagged)
        for i in range(3):
            eng.update(i, candidates=0)
        eng.update(3, candidates=500)
        status, body = _get(base + "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["status"] == "DEGRADED"
        assert doc["reasons"][0]["kind"] == "candidate_storm"

        # sustained storm -> CRITICAL -> HTTP 503 (dumb probes act on
        # the status code alone)
        eng.update(4, candidates=500)
        status, body = _get(base + "/healthz")
        assert status == 503
        assert json.loads(body)["status"] == "CRITICAL"

        # recovery -> OK again
        for i in range(5, 9):
            eng.update(i, candidates=0)
        status, body = _get(base + "/healthz")
        assert status == 200 and json.loads(body)["status"] == "OK"

        status, _ = _get(base + "/nope")
        assert status == 404
    finally:
        srv.close()
    # closed: the port no longer accepts connections
    with pytest.raises(Exception):
        urllib.request.urlopen(base + "/healthz", timeout=1.0)


# ---------------------------------------------------------------------------
# end-to-end: tiny survey with canaries, scraped while it runs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def survey_file(tmp_path_factory):
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array

    tmp = tmp_path_factory.mktemp("live")
    rng = np.random.default_rng(5)
    nchan, nsamples = 64, 24576
    array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
    array[:, 13000] += 4.0  # one real DM-150 pulse
    array = disperse_array(array, 150, 1200., 200., 0.0005)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
              "nsamples": nsamples, "tsamp": 0.0005,
              "foff": 200. / nchan}
    path = str(tmp / "survey.fil")
    write_simulated_filterbank(path, array, header, descending=True)
    return path


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_live_survey_scrape_and_canary_recall(survey_file, tmp_path):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    port = _free_port()
    # canary at DM 120, away from the real DM-150 pulse: the science
    # hit must survive, the canaries must be tagged out
    canary = CanaryController(rate=1.0, dm=120.0, snr=15.0, seed=1)
    engine = HealthEngine()
    result = {}

    def run():
        result["hits"], result["store"] = search_by_chunks(
            survey_file, dmmin=100, dmmax=200, backend="jax",
            chunk_length=4096 * 0.0005, snr_threshold=6.5,
            output_dir=str(tmp_path), make_plots=False, resume=True,
            progress=False, canary=canary, health=engine,
            http_port=port,
            report_out=str(tmp_path / "report"))

    t = threading.Thread(target=run)
    t.start()
    base = f"http://127.0.0.1:{port}"
    scraped = {}
    deadline = time.time() + 120
    try:
        while time.time() < deadline and t.is_alive():
            try:
                status, body = _get(base + "/progress", timeout=2.0)
            except Exception:
                time.sleep(0.05)
                continue
            doc = json.loads(body)
            if doc.get("chunks_done", 0) >= 1:
                scraped["progress"] = doc
                _, scraped["metrics"] = _get(base + "/metrics")
                _, healthz = _get(base + "/healthz")
                scraped["healthz"] = json.loads(healthz)
                break
            time.sleep(0.05)
    finally:
        t.join(timeout=300)
    assert not t.is_alive()
    assert scraped, "survey finished before a single scrape landed"

    # scraped DURING the run: progress + verdict + live canary fields
    assert scraped["progress"]["chunks_total"] == 5
    assert scraped["healthz"]["status"] in ("OK", "DEGRADED")
    assert "putpu_canary_injected_total" in scraped["metrics"]
    assert "putpu_chunks_total" in scraped["metrics"]

    # the run's end state: every chunk canaried, recall measured, the
    # real pulse found and persisted, canaries tagged out
    s = canary.summary()
    assert s["injected"] == 5 and s["recall"] is not None
    assert s["recall"] >= 0.8
    hits = result["hits"]
    assert hits, "the real DM-150 pulse was lost"
    # the chunk holding the fixture's real pulse (sample 13000) must be
    # a DM-150 detection; other chunks may legitimately persist their
    # own above-threshold (noise) best rows, promoted past the canary —
    # exactly what the canary-off run persists for them
    pulse = [info for istart, iend, info, _ in hits
             if istart <= 13000 < iend]
    assert pulse and abs(pulse[0].dm - 150.0) < 10.0
    assert metrics.REGISTRY.counter(
        "putpu_canary_tagged_hits_total").value >= 1

    # the report artifact exists and tells the canary story
    md = open(str(tmp_path / "report.md")).read()
    html = open(str(tmp_path / "report.html")).read()
    assert "Canary injection-recovery" in md and "recall" in md
    assert "<svg" in html and "Survey report" in html
    # the server is down after the run
    with pytest.raises(Exception):
        urllib.request.urlopen(base + "/healthz", timeout=1.0)


def test_canary_off_is_byte_identical(survey_file, tmp_path):
    """The ISSUE 5 byte-inertness pin: with canaries off (default), the
    run's durable outputs are byte-identical to a run with the canary
    machinery explicitly disabled (rate=0 normalises to off)."""
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    def run(sub, **kw):
        out = str(tmp_path / sub)
        hits, store = search_by_chunks(
            survey_file, dmmin=100, dmmax=200, backend="jax",
            chunk_length=4096 * 0.0005, snr_threshold=6.5,
            output_dir=out, make_plots=False, resume=True,
            progress=False, **kw)
        return out, store.fingerprint

    out_a, fp = run("plain")
    out_b, fp_b = run("rate0", canary=0.0)
    assert fp == fp_b  # same config fingerprint: no ledger orphaning

    def snapshot(outdir):
        led = open(os.path.join(outdir, f"progress_{fp}.json"),
                   "rb").read()
        cands = {}
        for name in sorted(os.listdir(outdir)):
            if name.endswith(".npz"):
                with np.load(os.path.join(outdir, name),
                             allow_pickle=False) as data:
                    cands[name] = {k: data[k].tobytes()
                                   for k in data.files}
        return led, cands

    led_a, cands_a = snapshot(out_a)
    led_b, cands_b = snapshot(out_b)
    assert led_a == led_b
    assert sorted(cands_a) == sorted(cands_b)
    for name in cands_a:
        assert cands_a[name] == cands_b[name], f"{name} bytes differ"


def test_canary_enabled_keeps_ledger_and_science_candidates(survey_file,
                                                            tmp_path):
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    kw = dict(dmmin=100, dmmax=200, backend="jax",
              chunk_length=4096 * 0.0005, snr_threshold=6.5,
              make_plots=False, resume=True, progress=False)
    hits_a, store_a = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "off"), **kw)
    hits_b, store_b = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "on"),
        canary=CanaryController(rate=1.0, dm=120.0, snr=15.0, seed=1),
        **kw)
    # the ledger's done set is identical (canaries never mark chunks
    # differently) and the science candidate SET survives injection —
    # same chunk spans persisted, no canary-only extras
    assert store_a.done_chunks == store_b.done_chunks
    names_a = sorted(n for n in os.listdir(str(tmp_path / "off"))
                     if n.endswith(".npz"))
    names_b = sorted(n for n in os.listdir(str(tmp_path / "on"))
                     if n.endswith(".npz"))
    assert names_a == names_b
    assert [h[:2] for h in hits_a] == [h[:2] for h in hits_b]


# ---------------------------------------------------------------------------
# stream_search wiring
# ---------------------------------------------------------------------------

def test_stream_search_canary_and_health():
    from pulsarutils_tpu.parallel.stream import stream_search

    rng = np.random.default_rng(2)
    nchan, nsamp = 64, 4096
    chunks = [(i * nsamp,
               np.abs(rng.normal(0, 0.5, (nchan, nsamp))) + 20.0)
              for i in range(3)]
    canary = CanaryController(rate=1.0, snr=15.0, seed=4)
    engine = HealthEngine()
    results, hits = stream_search(
        chunks, 100, 200, 1200., 200., 0.0005, backend="jax",
        snr_threshold=6.5, canary=canary, health=engine)
    assert len(results) == 3
    s = canary.summary()
    assert s["injected"] == 3 and s["recall"] == 1.0
    # every chunk's best row was the canary: the science hit list is
    # empty, the tagged counter moved instead
    assert hits == []
    assert engine.verdict == "OK"
    snap = engine.snapshot()
    assert snap["updates"] == 3


# ---------------------------------------------------------------------------
# survey report
# ---------------------------------------------------------------------------

def test_report_renders_all_sections(tmp_path):
    from pulsarutils_tpu.obs import report

    health = {"status": "DEGRADED",
              "reasons": [{"kind": "candidate_storm",
                           "severity": "DEGRADED", "detail": "spike"}],
              "updates": 5,
              "incidents": [{"chunk": 3, "kind": "candidate_storm",
                             "severity": "DEGRADED", "event": "raised",
                             "detail": "spike <b>", "t": 0.0}],
              "transitions": [{"chunk": 3, "from": "OK",
                               "to": "DEGRADED",
                               "reasons": ["candidate_storm"]}]}
    canary = {"rate": 0.5, "dm": 150.0, "target_snr": 12.0,
              "width_samples": 2, "injected": 12, "recovered": 11,
              "discarded": 0, "recall": 0.9167, "window": 20,
              "window_recall": 0.9, "snr_ratio_mean": 0.95,
              "dm_error_mean": 0.1, "dm_error_rms": 0.4,
              "curve": [[0, 1, 1.0], [4096, 2, 1.0], [8192, 3, 0.667]]}
    budget = {"schema_version": 1, "chunks": 3, "wall_s": 3.0,
              "buckets_s": {"search": 2.0, "read": 0.5},
              "unattributed_s": 0.5, "attributed_pct": 83.3,
              "counters": {"dispatches": 3}, "async_s": {},
              "per_chunk": [], "rtt_s": 0.001, "trips": 6,
              "trips_x_rtt_s": 0.006}
    md_path, html_path = report.write_report(
        str(tmp_path / "rep"),
        meta={"root": "survey", "fingerprint": "abc"},
        budget=budget, health=health, canary=canary,
        quarantine=[{"chunk": 0, "end": 8192, "reason": "read_error"}],
        sift={"in": 4, "kept": 2,
              "rejected": {"duplicate": 1, "width": 1}})
    md = open(md_path).read()
    assert "**DEGRADED**" in md and "candidate_storm" in md
    assert "recall 0.9167" in md
    assert "read_error" in md
    html = open(html_path).read()
    assert html.startswith("<!doctype html>")
    assert 'class="verdict-DEGRADED"' in html
    assert "<svg" in html  # the recall sparkline
    assert "spike &lt;b&gt;" in html  # content is escaped
    # every section states absence explicitly on an empty report
    md2_path, _ = report.write_report(str(tmp_path / "empty"),
                                      meta={"root": "r"})
    md2 = open(md2_path).read()
    assert "No health engine" in md2
    assert "NOT measured" in md2
    assert "No chunks were quarantined" in md2


def test_report_has_no_roofline_section(tmp_path):
    """The host-wall roofline table is gone (the benchmark's
    ``fdmt_roofline`` reads device time): no section, stated or empty,
    and no parameter to feed one."""
    from pulsarutils_tpu.obs import report

    md_path, html_path = report.write_report(
        str(tmp_path / "rep"), meta={"root": "survey"},
        budget={"schema_version": 1, "chunks": 1, "wall_s": 1.0,
                "buckets_s": {"search": 1.0}, "unattributed_s": 0.0,
                "attributed_pct": 100.0, "counters": {}, "async_s": {},
                "per_chunk": []})
    for path in (md_path, html_path):
        assert "roofline" not in open(path).read().lower()
    with pytest.raises(TypeError):
        report.build_report(roofline=[])


def test_canary_time_matching_rejects_coincident_real_pulse():
    """Review fix (r9): matching is DM AND dedispersed-time.  A table
    whose canary-DM row peaks far from the injected t0 (a real pulse
    sharing the canary's DM) must neither score the canary as
    recovered nor be tagged as the canary."""
    from pulsarutils_tpu.utils.table import ResultTable

    c = CanaryController(rate=1.0, dm=150.0, snr=12.0, seed=0)
    c.bind(nchan=8, start_freq=1200., bandwidth=200., tsamp=0.0005,
           dmmin=100, dmmax=200)
    block = np.ones((8, 8192), dtype=np.float32)
    c.maybe_inject(block, 0)
    t0 = c._pending[0]["t0"]
    far = (t0 + 4096) % 8192  # half a chunk away from the injection
    table = ResultTable({"DM": [149.8, 160.0], "snr": [30.0, 5.0],
                         "rebin": [1, 1], "peak": [far, 100]})
    obs = c.observe(0, table, 6.5)
    assert not obs["recovered"]        # right DM, wrong time: a real
    assert not obs["best_is_canary"]   # pulse, not the canary
    # and the converse: a row at the injected time IS the canary
    c2 = CanaryController(rate=1.0, dm=150.0, snr=12.0, seed=0)
    c2.bind(nchan=8, start_freq=1200., bandwidth=200., tsamp=0.0005,
            dmmin=100, dmmax=200)
    c2.maybe_inject(block, 0)
    t0 = c2._pending[0]["t0"]
    table = ResultTable({"DM": [149.8, 160.0], "snr": [10.0, 5.0],
                         "rebin": [1, 1], "peak": [t0, 100]})
    obs = c2.observe(0, table, 6.5)
    assert obs["recovered"] and obs["best_is_canary"]


def test_canary_observe_reports_science_row():
    """Review fix (r9b): observe() exposes the strongest row OUTSIDE
    the canary track so the drivers can promote a genuine weaker pulse
    instead of suppressing the whole chunk's detection."""
    from pulsarutils_tpu.utils.table import ResultTable

    c = CanaryController(rate=1.0, dm=150.0, snr=12.0, seed=0)
    c.bind(nchan=8, start_freq=1200., bandwidth=200., tsamp=0.0005,
           dmmin=100, dmmax=200)
    block = np.ones((8, 8192), dtype=np.float32)
    c.maybe_inject(block, 0)
    t0 = c._pending[0]["t0"]
    table = ResultTable({"DM": [149.9, 180.0, 110.0],
                         "snr": [30.0, 9.0, 3.0],
                         "rebin": [1, 1, 1],
                         "peak": [t0, (t0 + 2000) % 8192,
                                  (t0 + 3000) % 8192]})
    obs = c.observe(0, table, 6.5)
    assert obs["recovered"] and obs["best_is_canary"]
    assert list(obs["canary_rows"]) == [True, False, False]
    assert obs["science_idx"] == 1 and obs["science_snr"] == 9.0
    # every row on the canary track: nothing to promote
    c2 = CanaryController(rate=1.0, dm=150.0, snr=12.0, seed=0)
    c2.bind(nchan=8, start_freq=1200., bandwidth=200., tsamp=0.0005,
            dmmin=100, dmmax=200)
    c2.maybe_inject(block, 0)
    t0 = c2._pending[0]["t0"]
    table = ResultTable({"DM": [150.0], "snr": [30.0], "rebin": [1],
                         "peak": [t0]})
    obs = c2.observe(0, table, 6.5)
    assert obs["best_is_canary"]
    assert obs["science_idx"] is None and obs["science_snr"] is None


def test_stream_search_promotes_real_pulse_under_canary():
    """A canary that outranks a genuine weaker pulse in the same chunk
    must not cost the detection: the science row is promoted as the
    chunk's best_row."""
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.parallel.stream import stream_search

    rng = np.random.default_rng(3)
    nchan, nsamp = 64, 4096
    block = np.abs(rng.normal(0, 0.5, (nchan, nsamp))) + 20.0
    block[:, 2000] += 1.0          # genuine weak pulse at DM 150
    block = disperse_array(block, 150, 1200., 200., 0.0005)
    canary = CanaryController(rate=1.0, dm=120.0, snr=60.0, seed=4)
    before = metrics.REGISTRY.counter(
        "putpu_canary_promoted_hits_total").value
    results, hits = stream_search(
        [(0, block)], 100, 200, 1200., 200., 0.0005, backend="jax",
        snr_threshold=6.5, canary=canary)
    assert canary.summary()["recall"] == 1.0  # the canary was seen...
    assert len(hits) == 1                     # ...and so was the pulse
    _, hit_table, best = hits[0]
    assert abs(float(best["DM"]) - 150.0) < 10.0
    assert metrics.REGISTRY.counter(
        "putpu_canary_promoted_hits_total").value == before + 1
    # the promoted hit's table has the canary-lit rows masked out
    # (same contract as search_by_chunks) — results keeps the raw view
    assert hit_table.nrows < results[0][1].nrows
    assert not np.any(np.abs(np.asarray(hit_table["DM"], dtype=float)
                             - 120.0) < 1.0)


def test_canary_promotion_preserves_science_candidate(survey_file,
                                                      tmp_path):
    """search_by_chunks: with a canary bright enough to outrank the
    fixture's real DM-150 pulse, the candidate SET still matches the
    canary-off run, and the promoted chunk persists the real pulse
    with the canary-track rows masked out of its table."""
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    kw = dict(dmmin=100, dmmax=200, backend="jax",
              chunk_length=4096 * 0.0005, snr_threshold=6.5,
              make_plots=False, resume=True, progress=False)
    hits_off, _ = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "off"), **kw)
    assert hits_off, "fixture's real pulse must be a canary-off hit"
    canary = CanaryController(rate=1.0, dm=120.0, snr=400.0, seed=1)
    before = metrics.REGISTRY.counter(
        "putpu_canary_promoted_hits_total").value
    hits_on, _ = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "on"), canary=canary,
        **kw)
    assert metrics.REGISTRY.counter(
        "putpu_canary_promoted_hits_total").value > before
    assert [h[:2] for h in hits_on] == [h[:2] for h in hits_off]
    # the chunk holding the real pulse (sample 13000): the promoted
    # candidate is the genuine DM-150 row, and the canary-track rows
    # were masked out of its persisted table
    on = {(i, j): (info, t) for i, j, info, t in hits_on}
    off = {(i, j): t for i, j, _, t in hits_off}
    span = next(k for k in on if k[0] <= 13000 < k[1])
    info, table = on[span]
    assert abs(info.dm - 150.0) < 10.0
    assert abs(float(table.best_row()["DM"]) - 150.0) < 10.0
    assert table.nrows < off[span].nrows


def test_period_search_cannot_resurrect_tagged_canary(survey_file,
                                                      tmp_path):
    """Review fix (r9b): on a chunk where the canary is the best row
    and nothing genuine clears the threshold, is_hit is forced False —
    the periodicity stage, folding a plane that CONTAINS the bright
    synthetic track, must not flip it back on and persist the canary
    as a candidate.  Injected chunks skip the period stage."""
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    canary = CanaryController(rate=1.0, dm=120.0, snr=400.0, seed=1)
    before = metrics.REGISTRY.counter(
        "putpu_canary_period_skips_total").value
    hits, _ = search_by_chunks(
        survey_file, dmmin=100, dmmax=200, backend="jax",
        chunk_length=4096 * 0.0005, snr_threshold=6.5,
        period_search=True, period_sigma_threshold=2.0,
        make_plots=False, resume=True, progress=False,
        output_dir=str(tmp_path / "out"), canary=canary)
    assert metrics.REGISTRY.counter(
        "putpu_canary_period_skips_total").value > before
    # no candidate at the canary DM: the only hit is the fixture's
    # real DM-150 pulse (promoted past the brighter canary)
    for _, _, info, _ in hits:
        assert abs(info.dm - 120.0) > 10.0
    assert any(abs(info.dm - 150.0) < 10.0 for _, _, info, _ in hits)


def test_obs_server_host_binding():
    """Review fix (r9b): the bind address is plumbed end to end —
    loopback default, 0.0.0.0 (or an interface) for remote Prometheus
    scrapes / fleet healthz probes."""
    reg = metrics.MetricsRegistry()
    srv = start_obs_server(0, registry=reg, host="0.0.0.0")
    try:
        status, _ = _get(f"http://127.0.0.1:{srv.port}/")
        assert status == 200
    finally:
        srv.close()
    from pulsarutils_tpu.cli.search_main import build_parser

    opts = build_parser().parse_args(
        ["x.fil", "--http-port", "0", "--http-host", "0.0.0.0"])
    assert opts.http_host == "0.0.0.0"
    assert build_parser().parse_args(["x.fil"]).http_host == "127.0.0.1"


def test_report_amend_folds_sift_in(tmp_path):
    from pulsarutils_tpu.obs import report

    base = str(tmp_path / "rep")
    report.write_report(base, meta={"root": "r"})
    assert "No sift telemetry" in open(base + ".md").read()
    assert os.path.exists(base + ".json")
    report.amend_report(base, sift={"in": 7, "kept": 3,
                                    "rejected": {"duplicate": 4}})
    md = open(base + ".md").read()
    assert "7 candidates in, 3 kept" in md
    assert "No sift telemetry" not in md
    # the other sections survive the amend untouched
    assert "No health engine" in md


def test_canary_survey_with_rfi_storm_recall_and_health(tmp_path):
    """A canary in EVERY chunk of a 13-chunk survey (recall from more than
    ten injections) and one chunk hit by an injected broadband RFI storm:
    at most one canary may be lost (a marginal pulse flipping across
    BLAS/CPU rounding), the storm must flip the verdict to DEGRADED as a
    candidate-rate spike, and the clean chunks behind it must bring it
    back to OK."""
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    tsamp, nchan, hop, nhops = 0.0005, 64, 4096, 14
    nsamples = nhops * hop
    rng = np.random.default_rng(10)
    array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
              "nsamples": nsamples, "tsamp": tsamp, "foff": 200. / nchan}
    path = str(tmp_path / "canary.fil")
    write_simulated_filterbank(path, array, header, descending=True)
    # 8 impulses at 100 block-stds: bright enough that the wide boxcar
    # widths light up ~2/3 of the DM trials (a denser storm
    # self-suppresses — the row-std normalisation soaks it up)
    plan = FaultPlan([FaultSpec(site="corrupt", kind="impulse",
                                chunks=(5 * hop,), frac=0.001, times=1,
                                amp=100.0)])
    canary = CanaryController(rate=1.0, snr=15.0, seed=10)
    engine = HealthEngine()
    with plan.armed():
        search_by_chunks(
            path, chunk_length=hop * tsamp, dmmin=100, dmmax=200,
            backend="jax", snr_threshold=6.5,
            output_dir=str(tmp_path / "out"), make_plots=False,
            resume=False, progress=False, canary=canary, health=engine)
    assert plan.fired() == 1
    s = canary.summary()
    assert s["injected"] == 13
    assert s["recall"] >= 12.0 / 13.0
    moves = [(t["chunk"], t["from"], t["to"], t["reasons"])
             for t in engine.transitions]
    assert moves[0] == (5 * hop, OK, DEGRADED, ["candidate_storm"])
    assert moves[-1][2] == OK and engine.verdict == OK
