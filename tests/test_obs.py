"""Round-7 telemetry subsystem (ISSUE 3): span tracing, the metrics
registry — and the byte-compat contract that the span refactor did NOT
change ``BUDGET_JSON``.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from pulsarutils_tpu.obs import memory, metrics, trace
from pulsarutils_tpu.utils.logging_utils import (BUDGET_SCHEMA_VERSION,
                                                 BudgetAccountant,
                                                 budget_bucket,
                                                 budget_count)


@pytest.fixture
def tracer():
    t = trace.start_tracing()
    yield t
    trace.stop_tracing()


def _span_events(t):
    return [e for e in t.to_chrome()["traceEvents"] if e["ph"] == "X"]


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_nesting_intervals(tracer):
    with trace.span("outer"):
        with trace.span("inner"):
            time.sleep(0.01)
        time.sleep(0.01)
    evs = {e["name"]: e for e in _span_events(tracer)}
    outer, inner = evs["outer"], evs["inner"]
    # the child's interval is contained in the parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["dur"] >= 2e4  # two 10ms sleeps, microseconds
    # closed innermost-first: the completed-event list orders inner first
    names = [e["name"] for e in _span_events(tracer)]
    assert names.index("inner") < names.index("outer")


def test_async_span_completion_out_of_stack_order(tracer):
    # async spans model device dispatch -> block-until-ready readback:
    # begin, run OTHER spans, end later (possibly from another thread)
    h = trace.begin_span("dispatch_async", track="device")
    with trace.span("host_work"):
        time.sleep(0.005)
    done = threading.Event()

    def finish():
        h.end(status="ready")
        done.set()

    threading.Thread(target=finish).start()
    assert done.wait(5.0)
    h.end()  # idempotent
    evs = tracer.to_chrome()["traceEvents"]
    b = [e for e in evs if e["ph"] == "b" and e["name"] == "dispatch_async"]
    e = [e for e in evs if e["ph"] == "e" and e["name"] == "dispatch_async"]
    assert len(b) == 1 and len(e) == 1
    assert b[0]["id"] == e[0]["id"] and b[0]["cat"] == e[0]["cat"] == "async"
    # the async pair BRACKETS the sync span that ran in between
    host = [ev for ev in evs if ev.get("name") == "host_work"][0]
    assert b[0]["ts"] <= host["ts"]
    assert e[0]["ts"] >= host["ts"] + host["dur"] - 1e-3
    assert e[0]["args"]["status"] == "ready"


def test_begin_span_is_noop_without_tracer():
    assert not trace.is_tracing()
    h = trace.begin_span("x")
    h.end()  # must not raise, must not record anywhere


def test_chrome_trace_schema_and_tracks(tracer, tmp_path):
    with trace.set_track("chunk 0"):
        with trace.span("read", chunk=0):
            pass
    with trace.span("footer"):
        pass
    path = str(tmp_path / "out.json")
    n = tracer.export(path)
    assert n >= 2
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "pid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "ts" in ev and "tid" in ev
    # one named track per set_track context + the main thread track
    tracks = {ev["args"]["name"] for ev in doc["traceEvents"]
              if ev["name"] == "thread_name"}
    assert {"chunk 0", "main"} <= tracks
    # attrs surface as chrome args
    read = [e for e in doc["traceEvents"] if e["name"] == "read"][0]
    assert read["args"]["chunk"] == 0


def test_budget_bucket_emits_spans_without_accountant(tracer):
    # trace-only runs (no BudgetAccountant) still get kernel spans
    with budget_bucket("search/dispatch"):
        pass
    assert [e["name"] for e in _span_events(tracer)] == ["search/dispatch"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_types_and_labels():
    reg = metrics.MetricsRegistry()
    c = reg.counter("putpu_test_total", help="h")
    c.inc()
    c.inc(3)
    assert reg.counter("putpu_test_total").value == 4  # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("putpu_test_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("putpu_wm")
    g.set(5.0)
    g.set_max(3.0)
    assert g.value == 5.0
    g.set_max(7.0)
    assert g.value == 7.0
    a = reg.counter("putpu_lab_total", reason="width")
    b = reg.counter("putpu_lab_total", reason="duplicate")
    a.inc(2)
    b.inc(5)
    snap = {(m["name"], tuple(sorted(m["labels"].items()))): m
            for m in reg.snapshot()}
    assert snap[("putpu_lab_total", (("reason", "width"),))]["value"] == 2
    assert snap[("putpu_lab_total", (("reason", "duplicate"),))]["value"] == 5


def test_histogram_buckets_and_exporters(tmp_path):
    reg = metrics.MetricsRegistry()
    h = reg.histogram("putpu_snr", edges=(6.0, 10.0, 20.0))
    for v in (5.0, 6.0, 8.0, 15.0, 50.0):
        h.observe(v)
    s = h._sample()
    assert s["counts"] == [2, 1, 1, 1]  # <=6, <=10, <=20, +Inf
    assert s["count"] == 5 and s["sum"] == pytest.approx(84.0)
    # JSONL round-trips
    p = str(tmp_path / "m.jsonl")
    reg.write_jsonl(p)
    lines = [json.loads(line) for line in open(p)]
    assert any(rec["name"] == "putpu_snr" and rec["count"] == 5
               for rec in lines)
    # prometheus text: cumulative buckets + sum/count, parseable shape
    text = reg.prometheus_text()
    assert "# TYPE putpu_snr histogram" in text
    assert 'putpu_snr_bucket{le="+Inf"} 5' in text
    assert "putpu_snr_count 5" in text


def test_metrics_threaded_updates_are_exact():
    reg = metrics.MetricsRegistry()
    c = reg.counter("putpu_threads_total")
    h = reg.histogram("putpu_threads_hist", edges=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h._sample()["count"] == 8000


def test_prometheus_conformance_golden():
    # ISSUE 5 satellite: the whole exposition pinned as golden text —
    # cumulative _bucket samples ending in le="+Inf" == _count,
    # _sum/_count emission, and label-value escaping of backslash,
    # double-quote and newline (backslash escaped FIRST)
    reg = metrics.MetricsRegistry()
    c = reg.counter("putpu_esc_total", help="has \\ and\nnewline",
                    reason='du"p\nli\\c')
    c.inc(2)
    reg.gauge("putpu_g").set(1.5)
    h = reg.histogram("putpu_h", help="hist", edges=(0.5, 1.0), kernel="k")
    h.observe(0.25)
    h.observe(2.0)
    assert reg.prometheus_text() == (
        '# HELP putpu_esc_total has \\\\ and\\nnewline\n'
        '# TYPE putpu_esc_total counter\n'
        'putpu_esc_total{reason="du\\"p\\nli\\\\c"} 2\n'
        '# TYPE putpu_g gauge\n'
        'putpu_g 1.5\n'
        '# HELP putpu_h hist\n'
        '# TYPE putpu_h histogram\n'
        'putpu_h_bucket{kernel="k",le="0.5"} 1\n'
        'putpu_h_bucket{kernel="k",le="1.0"} 1\n'
        'putpu_h_bucket{kernel="k",le="+Inf"} 2\n'
        'putpu_h_sum{kernel="k"} 2.25\n'
        'putpu_h_count{kernel="k"} 2\n')


# ---------------------------------------------------------------------------
# BUDGET_JSON byte-compatibility (the span refactor changed the clockwork
# underneath the accountant; the ledger bytes must not move)
# ---------------------------------------------------------------------------

#: json.dumps(acct.to_json()) captured on the PRE-refactor accountant
#: with the same fake clock and operation sequence as the test below.
#: ISSUE 5 added the leading "schema_version" key, ISSUE 14 the
#: "chunk_wall_s" percentile block (schema_version 1 -> 2), ISSUE 17
#: the snapshot header's backend/precision-policy lane stamps
#: (schema_version 2 -> 3, no BUDGET_JSON byte change beyond the
#: version), ISSUE 25 the "call_s" block of what the call costs outside
#: its chunks (3 -> 4) — all DELIBERATE byte changes, versioned as such;
#: every other byte is still pinned.
_GOLDEN_BUDGET_JSON = (
    '{"schema_version": 4, '
    '"chunks": 2, "wall_s": 1.125, '
    '"chunk_wall_s": {"p50": 0.5625, "p95": 0.5625, "p99": 0.5625}, '
    '"buckets_s": {"search": 0.625, '
    '"read": 0.125, "search/dispatch": 0.125, "search/readback": 0.125}, '
    '"unattributed_s": 0.375, "attributed_pct": 66.7, '
    '"counters": {"dispatches": 2, "readbacks": 4}, '
    '"async_s": {"persist": 0.25}, '
    '"call_s": {"badchans": 0.062}, '
    '"per_chunk": [{"chunk": 0, "wall_s": 0.5625, "buckets": '
    '{"read": 0.0625, "search/dispatch": 0.0625, "search/readback": '
    '0.0625, "search": 0.3125}, "counters": {"dispatches": 1, '
    '"readbacks": 2}, "unattributed_s": 0.1875}, {"chunk": 32768, '
    '"wall_s": 0.5625, "buckets": {"read": 0.0625, "search/dispatch": '
    '0.0625, "search/readback": 0.0625, "search": 0.3125}, "counters": '
    '{"dispatches": 1, "readbacks": 2}, "unattributed_s": 0.1875}], '
    '"rtt_s": 0.015625, "trips": 6, "trips_x_rtt_s": 0.094}'
)


def test_budget_json_byte_identical_to_pre_refactor(monkeypatch):
    ticks = iter(1000.0 + 0.0625 * i for i in range(1, 1000))
    monkeypatch.setattr(time, "perf_counter", lambda: next(ticks))
    acct = BudgetAccountant(rtt_s=0.015625)
    acct.begin_stream()
    with acct.bucket("badchans"):  # outside any chunk: call_s
        pass
    for label in (0, 32768):
        with acct.chunk(label):
            with acct.bucket("read"):
                pass
            with acct.bucket("search"):
                with budget_bucket("search/dispatch"):
                    pass
                budget_count("dispatches")
                with budget_bucket("search/readback"):
                    pass
                budget_count("readbacks")
            budget_count("readbacks")
    acct.add_async("persist", 0.25)
    assert json.dumps(acct.to_json()) == _GOLDEN_BUDGET_JSON


def test_budget_json_byte_identical_while_tracing(monkeypatch):
    # an active tracer must NOT change the ledger bytes either: the
    # tracer reuses the span's endpoints instead of reading the clock
    ticks = iter(1000.0 + 0.0625 * i for i in range(1, 1000))
    tracer = trace.start_tracing()
    try:
        monkeypatch.setattr(time, "perf_counter", lambda: next(ticks))
        acct = BudgetAccountant(rtt_s=0.015625)
        acct.begin_stream()
        with acct.bucket("badchans"):  # outside any chunk: call_s
            pass
        for label in (0, 32768):
            with acct.chunk(label):
                with acct.bucket("read"):
                    pass
                with acct.bucket("search"):
                    with budget_bucket("search/dispatch"):
                        pass
                    budget_count("dispatches")
                    with budget_bucket("search/readback"):
                        pass
                    budget_count("readbacks")
                budget_count("readbacks")
        acct.add_async("persist", 0.25)
        assert json.dumps(acct.to_json()) == _GOLDEN_BUDGET_JSON
    finally:
        trace.stop_tracing()
    # and the same intervals landed in the trace, on per-chunk tracks
    names = {e["name"] for e in _span_events(tracer)}
    assert {"chunk", "read", "search", "search/dispatch"} <= names
    tracks = set(tracer._tracks)
    assert {"chunk 0", "chunk 32768"} <= tracks


def test_truncation_is_counted_and_warned(caplog):
    import logging

    acct = BudgetAccountant()
    for i in range(40):
        with acct.chunk(i):
            pass
    with caplog.at_level(logging.WARNING, logger="pulsarutils_tpu"):
        j = acct.to_json(max_per_chunk=32)
        j2 = acct.to_json(max_per_chunk=32)
    assert j["per_chunk_truncated"] is True
    assert j["truncated_chunks"] == 8
    assert len(j["per_chunk"]) == 32
    assert j2["truncated_chunks"] == 8
    warnings = [r for r in caplog.records
                if "budget JSON truncated" in r.getMessage()]
    assert len(warnings) == 1  # one warning, not one per to_json call
    # explicit "no detail" request: counted, not warned
    acct2 = BudgetAccountant()
    with acct2.chunk(0):
        pass
    with caplog.at_level(logging.WARNING, logger="pulsarutils_tpu"):
        j0 = acct2.to_json(max_per_chunk=0)
    assert j0["truncated_chunks"] == 1 and j0["per_chunk"] == []
    assert not [r for r in caplog.records[len(warnings):]
                if "budget JSON truncated" in r.getMessage()]


def test_small_runs_have_no_truncation_keys():
    acct = BudgetAccountant()
    with acct.chunk(0):
        pass
    j = acct.to_json()
    assert "per_chunk_truncated" not in j
    assert "truncated_chunks" not in j


# ---------------------------------------------------------------------------
# streaming integration: registry vs accountant under persist overlap
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pulse_file(tmp_path_factory):
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array

    tmp = tmp_path_factory.mktemp("obs")
    rng = np.random.default_rng(3)
    nchan, nsamples = 64, 16384
    array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
    array[:, 9000] += 4.0
    array = disperse_array(array, 150, 1200., 200., 0.0005)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
              "nsamples": nsamples, "tsamp": 0.0005, "foff": 200. / nchan}
    path = str(tmp / "pulse.fil")
    write_simulated_filterbank(path, array, header, descending=True)
    return path


def test_streaming_metrics_match_budget_under_overlap(pulse_file, tmp_path):
    # threaded run (reader + persist worker overlap the main loop): the
    # registry's mirrored counters must agree exactly with the budget
    # ledger, and the trace must carry per-chunk tracks + async persist
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    def val(name, **labels):
        return metrics.REGISTRY.counter(name, **labels).value

    before = {k: val(k) for k in ("putpu_dispatches_total",
                                  "putpu_readbacks_total",
                                  "putpu_chunks_total",
                                  "putpu_hits_total",
                                  "putpu_bytes_uploaded_total")}
    acct = BudgetAccountant()
    tracer = trace.start_tracing()
    try:
        hits, _ = search_by_chunks(
            pulse_file, dmmin=100, dmmax=200, backend="jax",
            output_dir=str(tmp_path), make_plots=False, resume=False,
            progress=False, overlap_persist=True, budget=acct)
    finally:
        trace.stop_tracing()
    assert hits
    assert (val("putpu_dispatches_total") - before["putpu_dispatches_total"]
            == acct.counters_total["dispatches"])
    assert (val("putpu_readbacks_total") - before["putpu_readbacks_total"]
            == acct.counters_total["readbacks"])
    assert (val("putpu_chunks_total") - before["putpu_chunks_total"]
            == len(acct.chunks))
    assert (val("putpu_hits_total") - before["putpu_hits_total"]
            == len(hits))
    assert (val("putpu_bytes_uploaded_total")
            > before["putpu_bytes_uploaded_total"])
    evs = tracer.to_chrome()["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] in ("X", "b")}
    # >= 4 distinct spans across stream, search and readback layers
    assert {"chunk", "read", "search", "search/dispatch",
            "search/readback", "persist"} <= names
    assert any(t.startswith("chunk ") for t in tracer._tracks)
    # the async persist spans completed (a "b" without its "e" would
    # mean the worker finished after the drain barrier — impossible)
    n_b = sum(e["ph"] == "b" and e["name"] == "persist" for e in evs)
    n_e = sum(e["ph"] == "e" and e["name"] == "persist" for e in evs)
    assert n_b == n_e > 0


def test_memory_watermark_gauges():
    snap = memory.record_watermark()
    assert snap is not None
    assert snap["source"] in ("memory_stats", "live_arrays")
    assert snap["bytes_in_use"] >= 0
    g = metrics.REGISTRY.gauge("putpu_device_bytes_peak")
    assert g.value >= 0
    # watermark semantics survive a smaller later snapshot
    peak = g.value
    memory.record_watermark()
    assert metrics.REGISTRY.gauge("putpu_device_bytes_peak").value >= peak


# ---------------------------------------------------------------------------
# sift telemetry
# ---------------------------------------------------------------------------

def test_sift_rejection_reasons_and_footer(caplog):
    import logging

    from pulsarutils_tpu.pipeline.sift import sift_candidates, sift_hits

    stats = {}
    cands = [
        {"time": 10.0, "dm": 300.0, "snr": 20.0, "width": 0.001},
        {"time": 10.1, "dm": 300.2, "snr": 15.0, "width": 0.001},  # dup
        {"time": 12.0, "dm": 300.0, "snr": 12.0, "width": 1.0},    # width
        {"time": 10.0, "dm": 303.0, "snr": 11.0, "width": 0.001},  # dm_rad
        {"time": 500.0, "dm": 600.0, "snr": 9.0, "width": 0.001},  # kept
    ]
    kept = sift_candidates(cands, "pair-width", stats=stats)
    assert stats["in"] == 5 and stats["kept"] == len(kept) == 2
    assert stats["rejected"] == {"duplicate": 1, "width": 1, "dm_radius": 1}
    # end-to-end: sift_hits logs the SIFT_JSON footer + fills metrics
    before = metrics.REGISTRY.counter("putpu_sift_candidates_in_total").value

    class _T:  # minimal hit stand-ins for hit_fields
        colnames = ("peak",)

        def __init__(self, dm, snr):
            self._row = {"DM": dm, "snr": snr, "rebin": 1, "peak": 100}

        def best_row(self):
            return self._row

        def __getitem__(self, k):
            return [self._row[k]]

    class _I:
        pulse_freq = 1.0 / (1000 * 0.001)
        nbin = 1000
        t0 = 0.0

    with caplog.at_level(logging.INFO, logger="pulsarutils_tpu"):
        out = sift_hits([(0, 1000, _I(), _T(300.0, 20.0)),
                         (500, 1500, _I(), _T(300.2, 15.0))])
    assert len(out) == 1 and out[0]["n_members"] == 2
    assert (metrics.REGISTRY.counter("putpu_sift_candidates_in_total").value
            == before + 2)
    sift_lines = [r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("SIFT_JSON ")]
    assert len(sift_lines) == 1
    parsed = json.loads(sift_lines[0][len("SIFT_JSON "):])
    assert parsed["in"] == 2 and parsed["kept"] == 1
    assert sum(parsed["rejected"].values()) == 1


# ---------------------------------------------------------------------------
# unified device trace
# ---------------------------------------------------------------------------

def test_trace_session_single_flag_emits_both(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    out = str(tmp_path / "run.json")
    dev = str(tmp_path / "run.json_device")
    with trace.trace_session(path=out, device_trace_dir=dev):
        with trace.span("compute"):
            np.asarray(jnp.ones((8, 8)) * 2)
    doc = json.load(open(out))
    assert any(e.get("name") == "compute" for e in doc["traceEvents"])
    # the jax.profiler device trace landed in the same run directory
    profiled = []
    for root, _dirs, files in os.walk(dev):
        profiled += files
    assert profiled, "device trace directory is empty"


def test_device_trace_still_works(tmp_path):
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from pulsarutils_tpu.utils.logging_utils import device_trace

    with device_trace(str(tmp_path / "dev")):
        np.asarray(jnp.ones((4,)) + 1)
    assert os.path.isdir(str(tmp_path / "dev"))
    with device_trace(None):  # no-op form
        pass


def test_budget_json_carries_schema_version():
    acct = BudgetAccountant()
    with acct.chunk(0):
        pass
    j = acct.to_json()
    assert list(j)[0] == "schema_version"
    assert j["schema_version"] == BUDGET_SCHEMA_VERSION
