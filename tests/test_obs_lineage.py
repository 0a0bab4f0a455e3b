"""ISSUE 18 — candidate lifecycle observability: per-candidate lineage
docs, the end-to-end latency SLO, and alert fan-out with delivery
telemetry.  Tier-1 throughout: tiny surveys, in-process webhook sinks,
ephemeral ports.
"""
import glob
import http.server
import json
import os
import threading
import time

import numpy as np
import pytest

from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
from pulsarutils_tpu.models.simulate import disperse_array
from pulsarutils_tpu.obs import metrics as obs_metrics
from pulsarutils_tpu.obs.health import OK, HealthEngine
from pulsarutils_tpu.obs.lineage import (LINEAGE_SCHEMA_VERSION,
                                         LineageRecorder)
from pulsarutils_tpu.obs.push import AlertBroker, Subscriber
from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

TSAMP = 0.0005
NCHAN = 64
#: 16384 samples at chunk_length 8192*TSAMP -> chunks [0, 8192];
#: the pulse sits in chunk 8192
NSAMPLES = 16384
PULSE_T = 12000
CHUNK_LEN_S = 8192 * TSAMP
SEARCH_KW = dict(dmmin=100, dmmax=200, backend="jax",
                 chunk_length=CHUNK_LEN_S, make_plots=False,
                 progress=False, snr_threshold=6.5)


def _counter(name, **labels):
    for rec in obs_metrics.REGISTRY.snapshot():
        if rec["name"] == name and rec["labels"] == labels:
            return rec.get("value", rec.get("count", 0))
    return 0


# ---------------------------------------------------------------------------
# in-process webhook sinks
# ---------------------------------------------------------------------------

class _Sink:
    """Local webhook endpoint collecting every POSTed alert doc."""

    def __init__(self, hang_s=0.0):
        received = self.received = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                if hang_s:
                    # wedged subscriber: accept, then never answer
                    # within any sane client timeout
                    time.sleep(hang_s)
                n = int(self.headers.get("Content-Length") or 0)
                received.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        self._httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), Handler)
        self._httpd.daemon_threads = True
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}/hook"

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()


@pytest.fixture()
def sink():
    s = _Sink()
    yield s
    s.close()


# ---------------------------------------------------------------------------
# survey fixtures + byte-snapshot helper
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def survey_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lineage")
    rng = np.random.default_rng(0)
    arr = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    arr[:, PULSE_T] += 4.0
    arr = disperse_array(arr, 150.0, 1200., 200., TSAMP)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
              "nsamples": NSAMPLES, "tsamp": TSAMP,
              "foff": 200. / NCHAN}
    path = str(tmp / "survey.fil")
    write_simulated_filterbank(path, arr, header, descending=True)
    return path


def _snapshot(outdir, fingerprint):
    """Ledger bytes + npz member bytes — the byte-identity comparison
    set (lineage docs and dead-letter journals are extra files by
    design and excluded)."""
    with open(os.path.join(outdir, f"progress_{fingerprint}.json"),
              "rb") as f:
        ledger = f.read()
    cands = {}
    for path in sorted(glob.glob(os.path.join(outdir, "*.npz"))):
        with np.load(path, allow_pickle=False) as data:
            cands[os.path.basename(path)] = {
                k: data[k].tobytes() for k in data.files}
    return ledger, cands


@pytest.fixture(scope="module")
def baseline(survey_file, tmp_path_factory):
    """One lineage/push-off reference run; (snapshot, fingerprint)."""
    out = str(tmp_path_factory.mktemp("baseline"))
    hits, store = search_by_chunks(survey_file, output_dir=out,
                                   resume=True, **SEARCH_KW)
    assert len(hits) >= 1
    return _snapshot(out, store.fingerprint), store.fingerprint


# ---------------------------------------------------------------------------
# Subscriber / AlertBroker units
# ---------------------------------------------------------------------------

def test_subscriber_validation_and_filters():
    sub = Subscriber.coerce("http://h:1/hook")
    assert sub.name == "h:1/hook"
    with pytest.raises(ValueError):
        Subscriber.coerce("ftp://nope")
    with pytest.raises(ValueError):
        Subscriber.coerce({"min_snr": 9.0})  # no url
    with pytest.raises(ValueError):
        Subscriber.coerce({"url": "http://h/x", "bogus": 1})
    filt = Subscriber("http://h/x", min_snr=8.0, min_dm=100.0,
                      max_dm=200.0)
    assert filt.wants({"snr": 9.0, "dm": 150.0})
    assert not filt.wants({"snr": 7.0, "dm": 150.0})
    assert not filt.wants({"snr": 9.0, "dm": 250.0})
    # a missing field passes the predicate: never silently drop an
    # alert for lacking a value the filter would have tested
    assert filt.wants({"snr": 9.0})


def test_broker_delivers_and_filters(sink):
    deliveries = []
    with AlertBroker([sink.url,
                      {"url": sink.url, "name": "picky",
                       "min_snr": 100.0}]) as broker:
        assert broker.publish({"kind": "candidate", "snr": 9.0},
                              on_delivered=lambda s, lat:
                              deliveries.append(s))
        deadline = time.monotonic() + 10.0
        while not sink.received and time.monotonic() < deadline:
            time.sleep(0.02)
    stats = broker.stats()      # post-close: drained and settled
    assert len(sink.received) == 1
    assert stats["delivered"] == 1 and stats["filtered"] == 1
    assert stats["dead_lettered"] == 0
    # a filtered-out subscriber NEVER receives, and the delivery hook
    # names who did
    assert deliveries == ["127.0.0.1:%d/hook"
                          % int(sink.url.rsplit(":", 1)[1].split("/")[0])]


def test_broker_wedged_subscriber_drop_oldest_bounded(tmp_path):
    """queue_max=1 + a hung webhook: enqueues never block, the oldest
    alert is dropped (counted + dead-lettered), health degrades, and
    close() is bounded and resolves the condition."""
    hung = _Sink(hang_s=30.0)
    dead = str(tmp_path / "dead.jsonl")
    health = HealthEngine()
    try:
        broker = AlertBroker([hung.url], queue_max=1, timeout_s=0.3,
                             retries=0, dead_letter_path=dead,
                             health=health)
        t0 = time.monotonic()
        for i in range(3):
            assert broker.publish({"kind": "candidate", "seq": i})
        assert time.monotonic() - t0 < 1.0  # publish never blocks
        stats = broker.close(timeout_s=2.0)
        assert time.monotonic() - t0 < 15.0  # bounded shutdown
    finally:
        hung.close()
    assert stats["dropped"] >= 1
    assert _counter("putpu_push_dropped_total") >= 1
    with open(dead) as f:
        records = [json.loads(ln) for ln in f]
    assert any(r["reason"] == "dropped_oldest" for r in records)
    # every published alert is accounted for: delivered is 0 here, so
    # dropped + journaled-at-close covers all three
    assert len(records) + stats["delivered"] >= 3
    # the push condition degraded while wedged, and close() resolved it
    events = [(i["kind"], i["event"])
              for i in health.snapshot()["incidents"]]
    assert ("push", "raised") in events
    assert health.verdict == OK
    assert broker.publish({"kind": "late"}) is False  # closed


# ---------------------------------------------------------------------------
# LineageRecorder units
# ---------------------------------------------------------------------------

def test_lineage_recorder_doc_monotone_and_idempotent():
    lr = LineageRecorder(fingerprint="fp0", source="search_by_chunks")
    lr.mark(0, "read")
    first = lr._marks[0]["read"]
    lr.mark(0, "read")  # idempotent: retries keep the first stamp
    assert lr._marks[0]["read"] == first
    lr.mark(0, "dispatch")
    lr.mark(0, "ready")
    cl = lr.candidate(0, 8192, name="x_0-8192", dm=150.0, snr=9.0,
                      width=0.001)
    written = []
    lr.persisted(cl, writer=written.append)
    lr.delivered(cl, subscriber="hook-a")
    lr.delivered(cl, subscriber="hook-b")
    doc = written[-1]
    assert doc["schema_version"] == LINEAGE_SCHEMA_VERSION
    assert doc["fingerprint"] == "fp0" and doc["chunk"] == 0
    assert doc["candidate"] == "x_0-8192" and doc["dm"] == 150.0
    assert len(doc["trace_id"]) == 16
    st = doc["stages"]
    assert st["read"] <= st["dispatch"] <= st["ready"] <= st["sift"] \
        <= st["persist"]
    assert st["alert"] >= st["sift"]
    # the alert stamp is first-delivery-wins; both subscribers recorded
    assert doc["delivered_to"] == ["hook-a", "hook-b"]
    # delivery after persist re-wrote the doc (3 writes total: persist,
    # then one per delivery)
    assert len(written) == 3
    summary = lr.summary()
    assert summary["candidates"] == 1
    assert summary["latency"]["n"] == 1
    assert set(summary["stages"]) >= {"read", "dispatch", "sift",
                                      "persist", "alert"}
    # discarded chunks leave no marks behind
    lr.mark(8192, "read")
    lr.discard(8192)
    assert 8192 not in lr._marks


# ---------------------------------------------------------------------------
# search_by_chunks integration
# ---------------------------------------------------------------------------

def test_lineage_false_and_empty_push_take_the_off_path(
        survey_file, baseline, tmp_path):
    """The CLI spelling of "off" — ``lineage=False`` (store_true flag
    not given) and an empty ``push`` list — must take the pre-PR code
    path, not call ``.mark`` on a bool (regression: test_cli_search)."""
    (ref_ledger, ref_cands), ref_fp = baseline
    out = str(tmp_path / "cli_off")
    hits, store = search_by_chunks(survey_file, output_dir=out,
                                   resume=True, lineage=False, push=[],
                                   **SEARCH_KW)
    assert len(hits) >= 1
    assert store.fingerprint == ref_fp
    assert _snapshot(out, ref_fp) == (ref_ledger, ref_cands)
    assert not glob.glob(os.path.join(out, "*.lineage.json"))


def test_search_armed_byte_identical_and_docs_complete(
        survey_file, baseline, tmp_path, sink):
    """The tentpole pin: lineage+push armed produces byte-identical
    candidates and ledger vs the off run, every persisted hit carries a
    lineage doc with monotone stages, and the sink receives exactly the
    science detections."""
    (ref_ledger, ref_cands), ref_fp = baseline
    docs_before = _counter("putpu_lineage_docs_total")
    out = str(tmp_path / "armed")
    hits, store = search_by_chunks(
        survey_file, output_dir=out, resume=True, lineage=True,
        push=[sink.url], **SEARCH_KW)
    assert store.fingerprint == ref_fp  # host-local knobs: same config
    ledger, cands = _snapshot(out, store.fingerprint)
    assert ledger == ref_ledger
    assert cands == ref_cands
    # every persisted hit has its lineage doc beside the npz pair
    assert len(hits) >= 1
    for istart, iend, info, _tab in hits:
        matches = glob.glob(os.path.join(
            out, f"*_{istart}-{iend}.lineage.json"))
        assert len(matches) == 1, \
            f"no lineage doc for hit {istart}-{iend}"
        with open(matches[0]) as f:
            doc = json.load(f)
        assert doc["schema_version"] == LINEAGE_SCHEMA_VERSION
        assert doc["fingerprint"] == store.fingerprint
        assert doc["chunk"] == istart and doc["iend"] == iend
        assert doc["snr"] == pytest.approx(info.snr)
        st = doc["stages"]
        order = [st[k] for k in ("read", "dispatch", "ready", "sift",
                                 "persist")]
        assert order == sorted(order), f"non-monotone stages: {st}"
    assert _counter("putpu_lineage_docs_total") \
        >= docs_before + len(hits)
    # the latency histogram (the SLO's series) observed every hit
    assert _counter("putpu_candidate_latency_seconds") >= len(hits)
    # the sink got exactly the science hits, chunk-for-chunk
    deadline = time.monotonic() + 10.0
    while len(sink.received) < len(hits) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    assert sorted(a["chunk"] for a in sink.received) \
        == sorted(h[0] for h in hits)
    for alert in sink.received:
        assert alert["kind"] == "candidate"
        assert alert["fingerprint"] == store.fingerprint


def test_search_wedged_subscriber_never_stalls_driver(
        survey_file, baseline, tmp_path):
    """A hung webhook (accepts, never answers): the survey finishes in
    bounded time with byte-identical science outputs; undelivered
    alerts land in the dead-letter journal.  The broker is caller-owned
    here so its close is deterministic in the test; the armed test
    above exercises the driver-owned close path."""
    (ref_ledger, ref_cands), ref_fp = baseline
    hung = _Sink(hang_s=60.0)
    out = str(tmp_path / "wedged")
    dead = str(tmp_path / "dead.jsonl")
    broker = AlertBroker([hung.url], timeout_s=0.3, retries=0,
                         dead_letter_path=dead)
    t0 = time.monotonic()
    try:
        hits, store = search_by_chunks(
            survey_file, output_dir=out, resume=True,
            push=broker, **SEARCH_KW)
        wall = time.monotonic() - t0
        stats = broker.close(timeout_s=2.0)
    finally:
        hung.close()
    assert wall < 60.0, f"driver stalled {wall:.0f}s on a dead webhook"
    ledger, cands = _snapshot(out, store.fingerprint)
    assert ledger == ref_ledger and cands == ref_cands
    assert len(hits) >= 1
    # every alert the wedge swallowed is accounted for
    assert stats["published"] == len(hits)
    assert stats["delivered"] == 0
    assert os.path.exists(dead)
    with open(dead) as f:
        assert sum(1 for _ in f) >= 1


def test_canary_detections_never_pushed(tmp_path, sink):
    """Canary-topped chunks are tagged before the publish site: a
    noise-only survey under rate-1.0 injection recovers canaries but
    pushes NOTHING."""
    from pulsarutils_tpu.obs.canary import CanaryController

    rng = np.random.default_rng(3)
    arr = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
              "nsamples": NSAMPLES, "tsamp": TSAMP,
              "foff": 200. / NCHAN}
    path = str(tmp_path / "noise.fil")
    write_simulated_filterbank(path, arr, header, descending=True)
    canary = CanaryController(rate=1.0, dm=150.0, snr=15.0, seed=7)
    hits, _store = search_by_chunks(
        path, output_dir=str(tmp_path / "out"), resume=True,
        canary=canary, push=[sink.url], lineage=True, **SEARCH_KW)
    assert canary.summary()["recovered"] >= 1
    assert hits == []
    time.sleep(0.5)  # give a (wrong) delivery every chance to land
    assert sink.received == []


def test_delayed_persist_feeds_latency_histogram(survey_file, tmp_path,
                                                 monkeypatch):
    """A slow persist is visible end-to-end: the candidate-latency
    histogram (the SLO's series) observes the injected delay."""
    from pulsarutils_tpu.io.candidates import CandidateStore

    real = CandidateStore.save_candidate

    def slow(self, *a, **kw):
        time.sleep(0.25)
        return real(self, *a, **kw)

    monkeypatch.setattr(CandidateStore, "save_candidate", slow)
    reg_count0 = _counter("putpu_candidate_latency_seconds")
    lr = LineageRecorder(source="search_by_chunks")
    hits, _store = search_by_chunks(
        survey_file, output_dir=str(tmp_path / "slow"), resume=True,
        lineage=lr, **SEARCH_KW)
    assert len(hits) >= 1
    summary = lr.summary()
    assert summary["candidates"] == len(hits)
    assert summary["latency"]["max"] >= 0.25
    assert summary["stages"]["persist"]["max"] >= 0.25
    assert _counter("putpu_candidate_latency_seconds") >= reg_count0


# ---------------------------------------------------------------------------
# the candidate-latency SLO
# ---------------------------------------------------------------------------

def test_candidate_latency_slo_fires_and_resolves():
    from pulsarutils_tpu.obs.slo import SLOEngine, SLOSpec, default_slos

    base = {s.name: s for s in default_slos()}["candidate-latency-p95"]
    assert base.series == "putpu_candidate_latency_seconds"
    assert base.field == "p95" and base.op == "<="

    class _FakeSeries:
        def __init__(self, points):
            self._points = points

        def points(self, last=None):
            return list(self._points)

    spec = SLOSpec(base.name, objective=base.objective, kind=base.kind,
                   series=base.series, field=base.field,
                   bound=base.bound, op=base.op,
                   windows=((2.0, 4.0, 2.0, "page"),),
                   budget_window_s=10.0)
    health = HealthEngine()
    engine = SLOEngine([spec], health=health)
    slow = [{"t": 1000.0 + i,
             "series": {base.series: {"p95": base.bound * 4}}}
            for i in range(6)]
    alerts = engine.evaluate(_FakeSeries(slow), now=1005.0)
    assert [a.slo for a in alerts] == ["candidate-latency-p95"]
    assert "slo:candidate-latency-p95" in health.reasons()
    fast = slow + [{"t": 1006.0 + i,
                    "series": {base.series: {"p95": 0.5}}}
                   for i in range(6)]
    assert engine.evaluate(_FakeSeries(fast), now=1011.0) == []
    assert health.verdict == OK


# ---------------------------------------------------------------------------
# stream_search wiring
# ---------------------------------------------------------------------------

def _stream_chunks(seed=2, n=2):
    rng = np.random.default_rng(seed)
    chunks = []
    for i in range(n):
        arr = np.abs(rng.normal(0, 0.5, (NCHAN, 4096))) + 20.0
        if i == 1:
            arr[:, 2000] += 4.0
            arr = disperse_array(arr, 150.0, 1200., 200., TSAMP)
        chunks.append((i * 4096, arr))
    return chunks


def test_stream_search_lineage_and_push(sink):
    from pulsarutils_tpu.parallel.stream import stream_search

    lr = LineageRecorder(source="stream_search")
    results, hits = stream_search(
        _stream_chunks(), 100, 200, 1200., 200., TSAMP, backend="jax",
        snr_threshold=6.5, lineage=lr, push=[sink.url])
    assert len(hits) >= 1
    summary = lr.summary()
    assert summary["candidates"] == len(hits)
    # stream has no persist store: the emit point is persist-complete,
    # so latency is still measured (dispatch -> emit)
    assert summary["latency"]["n"] == len(hits)
    deadline = time.monotonic() + 10.0
    while len(sink.received) < len(hits) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    assert sorted(a["chunk"] for a in sink.received) \
        == sorted(h[0] for h in hits)


def _stream_hit_key(hit):
    istart, _table, best = hit
    return (istart, float(best["DM"]), float(best["snr"]))


def test_stream_search_wedged_subscriber_bounded():
    from pulsarutils_tpu.parallel.stream import stream_search

    chunks = _stream_chunks()
    ref_results, ref_hits = stream_search(
        chunks, 100, 200, 1200., 200., TSAMP, backend="jax",
        snr_threshold=6.5)
    hung = _Sink(hang_s=60.0)
    t0 = time.monotonic()
    try:
        results, hits = stream_search(
            chunks, 100, 200, 1200., 200., TSAMP, backend="jax",
            snr_threshold=6.5, push=[hung.url])
    finally:
        hung.close()
    assert time.monotonic() - t0 < 60.0
    # science results untouched by the wedge
    assert [_stream_hit_key(h) for h in hits] \
        == [_stream_hit_key(h) for h in ref_hits]
    assert len(hits) >= 1


# ---------------------------------------------------------------------------
# /metrics manifest HELP + warn_unknown (satellite a)
# ---------------------------------------------------------------------------

def test_metrics_scrape_serves_manifest_help_and_warns_unknown(caplog):
    import logging

    from pulsarutils_tpu.obs import names as obs_names
    from pulsarutils_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("putpu_hits_total").inc(2)
    # an undeclared name created straight on a registry bypasses the
    # facade's creation-time warning — the scrape must catch it
    obs_names._warned.discard("putpu_totally_undeclared_total")
    reg.counter("putpu_totally_undeclared_total").inc()
    with caplog.at_level(logging.WARNING, logger="pulsarutils_tpu"):
        text = reg.prometheus_text(manifest_help=True)
        text2 = reg.prometheus_text(manifest_help=True)
    assert ("# HELP putpu_hits_total "
            + obs_names.METRIC_NAMES["putpu_hits_total"]) in text
    assert "putpu_totally_undeclared_total 1" in text
    warnings = [r for r in caplog.records
                if "putpu_totally_undeclared_total" in r.getMessage()]
    assert len(warnings) == 1  # once per name, not per scrape
    assert text == text2


def test_subscribe_endpoint_roundtrip(sink):
    import urllib.error
    import urllib.request

    from pulsarutils_tpu.obs.server import start_obs_server

    with AlertBroker([]) as broker:
        with start_obs_server(0, push=broker) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            req = urllib.request.Request(
                base + "/subscribe",
                data=json.dumps({"url": sink.url,
                                 "min_snr": 7.0}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 201
                doc = json.loads(resp.read())
            assert doc["min_snr"] == 7.0
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(urllib.request.Request(
                    base + "/subscribe", data=b'{"nope": 1}'))
            assert err.value.code == 400
            with urllib.request.urlopen(base + "/subscribers") as resp:
                listed = json.loads(resp.read())
            assert len(listed["subscribers"]) == 1
            # the runtime subscriber actually receives
            broker.publish({"kind": "candidate", "snr": 9.0})
            deadline = time.monotonic() + 10.0
            while not sink.received and time.monotonic() < deadline:
                time.sleep(0.02)
            assert len(sink.received) == 1


def test_subscribe_without_broker_is_404():
    import urllib.error
    import urllib.request

    from pulsarutils_tpu.obs.server import start_obs_server

    with start_obs_server(0) as srv:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/subscribe",
                data=b"{}"))
        assert err.value.code == 404


# ---------------------------------------------------------------------------
# time-series JSONL spill under sustained load (satellite c)
# ---------------------------------------------------------------------------

def test_timeseries_spill_bounded_growth_and_ring_consistency(tmp_path):
    from pulsarutils_tpu.obs.timeseries import TimeSeriesSampler

    reg = obs_metrics.MetricsRegistry()
    spill = str(tmp_path / "history.jsonl")
    sampler = TimeSeriesSampler(registry=reg, interval_s=1.0,
                                capacity=8, spill_path=spill)
    c = reg.counter("putpu_chunks_total")
    for i in range(50):
        c.inc()
        sampler.sample(now=1000.0 + i)
    # bounded growth: exactly one JSONL line per sample, no
    # amplification however long the run
    with open(spill) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == 50
    # ring eviction vs spill consistency: the in-memory ring is exactly
    # the spill's tail
    ring = sampler.points()
    assert len(ring) == 8
    assert [p["t"] for p in ring] == [p["t"] for p in lines[-8:]]
    assert [p["series"]["putpu_chunks_total"]["total"] for p in ring] \
        == [p["series"]["putpu_chunks_total"]["total"]
            for p in lines[-8:]]


def test_history_endpoint_paging_at_ring_boundary(tmp_path):
    import urllib.request

    from pulsarutils_tpu.obs.server import start_obs_server
    from pulsarutils_tpu.obs.timeseries import TimeSeriesSampler

    reg = obs_metrics.MetricsRegistry()
    sampler = TimeSeriesSampler(registry=reg, interval_s=1.0,
                                capacity=4,
                                spill_path=str(tmp_path / "h.jsonl"))
    reg.counter("putpu_chunks_total").inc()
    for i in range(9):
        sampler.sample(now=2000.0 + i)
    with start_obs_server(0, timeseries=sampler) as srv:
        base = f"http://127.0.0.1:{srv.port}/metrics/history"

        def fetch(query=""):
            with urllib.request.urlopen(base + query) as resp:
                return json.loads(resp.read())["samples"]

        # last= at the ring boundary, inside it, and past it: the ring
        # is the source of truth, never the spill
        assert [p["t"] for p in fetch()] == [2005.0, 2006.0, 2007.0,
                                             2008.0]
        assert [p["t"] for p in fetch("?last=4")] \
            == [2005.0, 2006.0, 2007.0, 2008.0]
        assert [p["t"] for p in fetch("?last=2")] == [2007.0, 2008.0]
        assert [p["t"] for p in fetch("?last=99")] \
            == [2005.0, 2006.0, 2007.0, 2008.0]
        assert fetch("?last=0") == []


# ---------------------------------------------------------------------------
# report sections
# ---------------------------------------------------------------------------

def test_report_lineage_and_push_sections():
    from pulsarutils_tpu.obs.report import build_report, render_markdown

    lr = LineageRecorder(source="search_by_chunks")
    lr.mark(0, "read")
    lr.mark(0, "dispatch")
    lr.mark(0, "ready")
    cl = lr.candidate(0, 8192, snr=9.0)
    lr.persisted(cl)
    rec = build_report(meta={"root": "t"}, lineage=lr.summary(),
                       push={"subscribers": 1, "published": 3,
                             "delivered": 2, "filtered": 1,
                             "dropped": 0, "dead_lettered": 0,
                             "queued": 0})
    md = render_markdown(rec)
    assert "## Candidate latency" in md
    assert "Per-stage waterfall" in md and "| persist |" in md
    assert "**2 delivered**" in md
    # absence stated, never silently missing
    md_off = render_markdown(build_report(meta={"root": "t"}))
    assert "Lineage recording was off" in md_off
    assert "Alert push was off" in md_off


# ---------------------------------------------------------------------------
# fleet: worker knobs, coordinator rollup, merged candidate spans
# ---------------------------------------------------------------------------

def test_fleet_worker_lineage_push_rollup_and_candidate_spans(
        tmp_path, sink):
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.obs.collector import TraceCollector
    from pulsarutils_tpu.obs.server import start_obs_server

    rng = np.random.default_rng(0)
    arr = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    arr[:, PULSE_T] += 4.0
    arr = disperse_array(arr, 150.0, 1200., 200., TSAMP)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
              "nsamples": NSAMPLES, "tsamp": TSAMP,
              "foff": 200. / NCHAN}
    fname = str(tmp_path / "a.fil")
    write_simulated_filterbank(fname, arr, header, descending=True)

    out = tmp_path / "fleet"
    collector = TraceCollector()
    with FleetCoordinator(str(out), lease_ttl_s=120.0,
                          probe_interval_s=0.5,
                          collector=collector) as coordinator:
        with start_obs_server(0, fleet=coordinator) as srv:
            url = f"http://127.0.0.1:{srv.port}"
            coordinator.add_survey([fname], **{
                k: v for k, v in SEARCH_KW.items()
                if k in ("dmmin", "dmmax", "chunk_length",
                         "snr_threshold")})
            worker = FleetWorker(url, http_port=None, trace=True,
                                 lineage=True, push=[sink.url])
            worker.run(max_idle_s=60.0)
            assert coordinator.survey_done
            summary = coordinator.summary()
    # the delivery rollup rode the completion's metrics snapshot
    assert summary["push"]["putpu_push_delivered_total"] >= 1
    # the lineage doc landed beside the fleet-written candidate
    docs = glob.glob(os.path.join(str(out), "*.lineage.json"))
    assert len(docs) >= 1
    with open(docs[0]) as f:
        doc = json.load(f)
    # the merged trace has the candidate span INSIDE the unit's
    # distributed trace: same trace_id as the lease stamped
    chrome = collector.to_chrome()
    cand_spans = [ev for ev in chrome["traceEvents"]
                  if ev.get("name") == "candidate"
                  and ev.get("ph") == "b"]
    assert cand_spans, "no candidate span reached the collector"
    assert any((ev.get("args") or {}).get("trace_id")
               == doc["trace_id"] for ev in cand_spans)
    unit_ids = {(ev.get("args") or {}).get("trace_id")
                for ev in chrome["traceEvents"]
                if ev.get("name") == "unit"}
    assert doc["trace_id"] in unit_ids
    # the alert reached the webhook from the fleet path too
    assert any(a.get("chunk") == doc["chunk"] for a in sink.received)


def test_coordinator_summary_push_rollup_absent_when_off(tmp_path):
    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator

    with FleetCoordinator(str(tmp_path / "c")) as coordinator:
        assert "push" not in coordinator.summary()


# ---------------------------------------------------------------------------
# trace_merge filters (satellite b)
# ---------------------------------------------------------------------------

def _fake_trace(path, events):
    doc = {"traceEvents": events,
           "putpu": {"epoch_unix": 1000.0, "clock_offset_s": 0.0}}
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def test_trace_merge_candidate_and_trace_id_filters(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)

    coord = _fake_trace(tmp_path / "coord.json", [
        {"name": "clock_sync", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
         "dur": 1.0},
        {"name": "unit", "ph": "X", "pid": 1, "tid": 1, "ts": 10.0,
         "dur": 50.0, "args": {"trace_id": "aaa111"}},
        {"name": "unit", "ph": "X", "pid": 1, "tid": 1, "ts": 70.0,
         "dur": 50.0, "args": {"trace_id": "bbb222"}}])
    worker = _fake_trace(tmp_path / "worker.json", [
        {"name": "candidate", "ph": "b", "cat": "async", "id": 1,
         "pid": 1, "tid": 2, "ts": 20.0,
         "args": {"chunk": 8192, "trace_id": "aaa111"}},
        {"name": "candidate", "ph": "e", "cat": "async", "id": 1,
         "pid": 1, "tid": 2, "ts": 30.0},
        {"name": "chunk", "ph": "X", "pid": 1, "tid": 2, "ts": 15.0,
         "dur": 40.0, "args": {"trace_id": "bbb222"}}])

    out = str(tmp_path / "merged.json")
    assert tm.main([out, coord, worker, "--candidate", "8192"]) == 0
    with open(out) as f:
        doc = json.load(f)
    names = [ev["name"] for ev in doc["traceEvents"]
             if ev.get("ph") not in ("M",)]
    # kept: the clock anchor, the aaa111 unit, the candidate b/e pair;
    # dropped: the bbb222 unit and chunk spans
    assert names.count("candidate") == 2
    assert names.count("unit") == 1
    assert "chunk" not in names
    assert "clock_sync" in names

    out2 = str(tmp_path / "merged2.json")
    assert tm.main([out2, coord, worker, "--trace-id", "bbb222"]) == 0
    with open(out2) as f:
        doc2 = json.load(f)
    names2 = [ev["name"] for ev in doc2["traceEvents"]
              if ev.get("ph") not in ("M",)]
    assert "chunk" in names2 and "candidate" not in names2

    # an unknown candidate chunk is an error, not an empty file
    assert tm.main([str(tmp_path / "x.json"), coord, worker,
                    "--candidate", "424242"]) == 1
