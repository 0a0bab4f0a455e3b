"""One timeline per ``PUsearchfrb`` call (ISSUE 25).

A tiny file through ``search_main.main`` on the CPU: the ``call`` span
tree, span identity across the main, reader and persist threads, the
per-chunk compile phases and ``on_disk_lag_s``, the profiler annotations
(and their absence without a tracer), the kernel names of the hybrid
path.
"""
import json
import logging
import time

import numpy as np
import pytest

from pulsarutils_tpu.cli import search_main
from pulsarutils_tpu.io.candidates import CandidateStore
from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
from pulsarutils_tpu.models.simulate import disperse_array
from pulsarutils_tpu.obs import names, trace
from pulsarutils_tpu.utils import logging_utils

TSAMP = 0.0005
NCHAN = 64
NSAMPLES = 32768
PULSE_T = 20000          # three chunks: 0 is noise, 8192 and 16384 hit

#: every span name of a call, as ISSUE 25 lists them; each once per call
CALL_SPANS = ("call", "call/setup", "badchans", "call/plan",
              "call/device_setup", "persist_drain", "call/finish",
              "call/restore", "call/audit", "call/report", "call/sift")
#: child -> the span it must lie inside
PARENT = {"call/setup": "call", "badchans": "call/setup",
          "call/plan": "call/setup", "call/device_setup": "call/setup",
          "persist_drain": "call", "call/finish": "call",
          "call/restore": "call/finish", "call/audit": "call/finish",
          "call/report": "call/finish", "call/sift": "call",
          "chunk": "call"}


@pytest.fixture(scope="module")
def survey_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("call_trace")
    rng = np.random.default_rng(0)
    array = np.abs(rng.normal(0, 0.5, (NCHAN, NSAMPLES))) + 20.0
    array[:, PULSE_T] += 4.0
    array = disperse_array(array, 150, 1200., 200., TSAMP)
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": NCHAN,
              "nsamples": NSAMPLES, "tsamp": TSAMP, "foff": 200. / NCHAN}
    path = str(tmp / "survey.fil")
    write_simulated_filterbank(path, array, header, descending=True)
    return path


class _Budget(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.INFO)
        self.budget = None

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("BUDGET_JSON "):
            self.budget = json.loads(msg[len("BUDGET_JSON "):])


def run_call(path, outdir, traced=True):
    """One ``PUsearchfrb`` call; ``(exit status, BUDGET_JSON, events)``."""
    argv = [path, "--dmmin", "100", "--dmmax", "200", "--chunk-length",
            str(8192 * TSAMP), "--plots", "none", "--snr-threshold", "6.5",
            "--output-dir", str(outdir), "--report-out",
            str(outdir / "report")]
    outdir.mkdir()
    cap = _Budget()
    logger = logging.getLogger("pulsarutils_tpu")
    logger.addHandler(cap)
    tracer = trace.start_tracing() if traced else None
    try:
        rc = search_main.main(argv)
    finally:
        logger.removeHandler(cap)
        if traced:
            trace.stop_tracing()
    events = tracer.events_since(0)[0] if traced else []
    return rc, cap.budget, events


@pytest.fixture(scope="module")
def traced_calls(survey_file, tmp_path_factory):
    """Two traced calls on one file in one process: the second finds
    every kernel compiled and every program built."""
    from pulsarutils_tpu.pipeline import search_pipeline

    # as in a new process: no clean program built yet
    search_pipeline._device_clean_program.cache_clear()
    tmp = tmp_path_factory.mktemp("call_trace_out")
    return [run_call(survey_file, tmp / f"call{i}") for i in range(2)]


def _x(events, name):
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


def test_call_tree_is_complete(traced_calls):
    for rc, _, events in traced_calls:
        assert rc == 0
        for name in CALL_SPANS:
            assert len(_x(events, name)) == 1, name
        assert len(_x(events, "chunk")) == 3
        call = _x(events, "call")[0]
        assert call["args"]["file"] == "survey.fil"
        for child, parent in PARENT.items():
            outer = _x(events, parent)[0]
            for ev in _x(events, child):
                assert outer["ts"] <= ev["ts"], child
                assert (ev["ts"] + ev["dur"]
                        <= outer["ts"] + outer["dur"] + 1e-3), child
        # the call's direct children and its chunks account for it, in
        # the cold call as in the warm one: what lies outside them is
        # 5 % of the call or, where that is less, the 0.2 s a host that
        # six workers share may stall between two spans (0.15 s once);
        # an event's times are microseconds
        covered = sum(e["dur"] for n in ("call/setup", "chunk",
                                         "persist_drain", "call/finish",
                                         "call/sift")
                      for e in _x(events, n))
        assert call["dur"] - covered <= max(0.05 * call["dur"], 0.2e6), \
            (covered, call["dur"])


def test_one_trace_id_and_every_parent_chain_ends_at_call(traced_calls):
    ids = []
    for _, _, events in traced_calls:
        # the collector may pause before the root span opens (imports,
        # argument parsing): such a ``gc`` span belongs to no call
        events = [e for e in events if e["name"] != "gc"
                  or "parent_id" in e["args"]]
        spans = [e for e in events if e["ph"] in ("X", "b")]
        trace_ids = {e["args"].get("trace_id") for e in events}
        assert len(trace_ids) == 1 and None not in trace_ids
        ids.append(trace_ids.pop())
        by_id = {e["args"]["span_id"]: e for e in spans}
        assert len(by_id) == len(spans)
        root = _x(events, "call")[0]
        assert "parent_id" not in root["args"]
        for e in spans:
            hops = 0
            while "parent_id" in e["args"]:
                e = by_id[e["args"]["parent_id"]]
                hops += 1
                assert hops < 20
            assert e is root
        # the worker threads' spans are async pairs begun on the main
        # thread, one per chunk, each closed
        for name, track in (("persist", "persist-worker"),
                            ("read_decode", "reader")):
            begun = [e for e in events
                     if e["ph"] == "b" and e["name"] == name]
            ended = [e for e in events
                     if e["ph"] == "e" and e["name"] == name]
            assert sorted(e["args"]["chunk"] for e in begun) \
                == [0, 8192, 16384]
            assert {e["id"] for e in begun} == {e["id"] for e in ended}
        assert not [e for e in events if e["ph"] == "X"
                    and e["name"] in ("persist", "read_decode")]
    assert ids[0] != ids[1]  # a fresh trace id per call


def test_budget_json_gains_call_s_and_persist_split(traced_calls):
    for _, budget, events in traced_calls:
        assert budget["schema_version"] == 4
        call_s = budget["call_s"]
        assert set(call_s) == {"setup", "badchans", "plan", "device_setup",
                               "persist_drain"}
        assert call_s["setup"] >= max(call_s["badchans"], call_s["plan"],
                                      call_s["device_setup"])
        # the footer's seconds are the spans' own: one measurement
        setup = _x(events, "call/setup")[0]
        assert call_s["setup"] == pytest.approx(setup["dur"] / 1e6,
                                                abs=6e-4)
        async_s = budget["async_s"]
        assert {"persist", "persist/queued", "persist/save",
                "persist/mark", "read_decode"} <= set(async_s)
        assert async_s["persist/save"] + async_s["persist/mark"] \
            <= async_s["persist"] + 2e-3
        # a real footer's counters are all of the manifest's vocabulary
        assert budget["counters"]
        assert names.unknown_budget_counters(budget["counters"]) == []


def test_compile_phases_in_a_process_first_call_only(traced_calls):
    first, second = (b["per_chunk"] for _, b, _ in traced_calls)
    # the first call's first chunk traces and lowers what is not built yet
    assert first[0]["counters"]["trace_s"] > 0
    assert first[0]["counters"]["lower_s"] > 0
    # a second call builds nothing anew: the clean program's jax.jit is
    # kept across calls (ROADMAP S4; it was re-traced and its executable
    # read back on every call's first chunk), so no chunk of it traces,
    # lowers or loads anything
    for rec in second:
        assert not {"trace_s", "lower_s", "cache_load_s"} \
            & set(rec["counters"])


def test_the_clean_program_is_one_function_across_calls():
    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.pipeline import search_pipeline as sp

    opts = (False, True, False, 1)
    unpack = (device_unpack_block, 2, 64, True)
    a = sp._device_clean_program(unpack, (), opts)
    assert a is sp._device_clean_program(unpack, (), opts)
    assert a.__name__ == "unpack_clean"
    # what it closes over keys it: another geometry, option or unpacker
    # (a test's stand-in, say) is another program
    assert a is not sp._device_clean_program((device_unpack_block, 2, 128,
                                              True), (), opts)
    assert a is not sp._device_clean_program(unpack, (),
                                             (False, False, False, 1))
    assert sp._device_clean_program(None, (), opts).__name__ == "clean"


def test_nested_trace_events_are_counted_once():
    # an inner jit's tracing phase begins and ends inside the phase of
    # the one that encloses it: only a phase that no other encloses counts
    logging_utils._install_compile_listener()
    event = "/jax/core/compile/jaxpr_trace_duration"
    before = logging_utils.compile_phase_snapshot()["trace_s"]
    logging_utils._on_build_phase_begin(event, 0.0, fun_name="unpack_clean")
    for name, secs in (("add", 0.010), ("multiply", 0.020)):  # adjacent
        logging_utils._on_build_phase_begin(event, 0.0, fun_name=name)
        logging_utils._on_build_event(event, secs, fun_name=name)
    logging_utils._on_build_event(event, 0.050, fun_name="unpack_clean")
    after = logging_utils.compile_phase_snapshot()["trace_s"]
    assert after - before == pytest.approx(0.050, abs=1e-9)
    assert logging_utils._BUILD.open == []


def test_on_disk_lag_is_taken_after_mark_done_returned(
        survey_file, tmp_path, monkeypatch):
    returned = {}
    real = CandidateStore.mark_done

    def slow_mark_done(self, istart, reason=None):
        out = real(self, istart, reason=reason)
        time.sleep(0.15)    # the mark is on disk; its return is late
        returned[int(istart)] = time.perf_counter()
        return out

    monkeypatch.setattr(CandidateStore, "mark_done", slow_mark_done)
    rc, budget, events = run_call(survey_file, tmp_path / "out")
    assert rc == 0
    recs = budget["per_chunk"]
    assert [r["chunk"] for r in recs] == [0, 8192, 16384]
    for rec in recs:
        assert rec["on_disk_lag_s"] >= 0
    # the last chunk's persist overlaps nothing: its lag holds the whole
    # late return, so the stamp was read after mark_done came back
    assert recs[-1]["on_disk_lag_s"] >= 0.15
    drain = _x(events, "persist_drain")[0]
    assert drain["dur"] / 1e6 >= 0.1
    assert set(returned) == {0, 8192, 16384}


class _CountingAnnotation:
    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_no_tracer_no_annotation_no_span_ids(survey_file, tmp_path,
                                             monkeypatch):
    import jax

    _CountingAnnotation.entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    allocated = []
    real_next_id = trace.Tracer.next_id
    monkeypatch.setattr(
        trace.Tracer, "next_id",
        lambda self: allocated.append(1) or real_next_id(self))

    assert not trace.is_tracing()
    rc, budget, _ = run_call(survey_file, tmp_path / "plain", traced=False)
    assert rc == 0 and budget["chunks"] == 3
    assert _CountingAnnotation.entered == [] and allocated == []
    assert trace.current_trace_context() is None

    rc, _, events = run_call(survey_file, tmp_path / "traced")
    assert rc == 0
    # a ``gc`` span is recorded once its pause is over: no annotation
    sync = [e["name"] for e in events if e["ph"] == "X"
            and e["name"] != "gc"]
    # one annotation per synchronous span, of the same name
    assert sorted(_CountingAnnotation.entered) == sorted(sync)
    assert len(allocated) == len(
        [e for e in events if e["ph"] in ("X", "b")])


def _pallas_call_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            info = eqn.params.get("name_and_src_info")
            out.append(eqn.params.get("name") or info.name)
        for value in eqn.params.values():
            for v in (value if isinstance(value, (list, tuple))
                      else [value]):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    _pallas_call_names(inner, out)
    return out


def test_every_kernel_of_the_hybrid_path_is_named():
    """The coarse sweep as a TPU resolves it (resident head, deep pair,
    one-pass scorer) and the exact rescore, traced in interpret mode:
    every ``pallas_call`` carries a declared name."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt, pallas_dedisperse

    nchan, t = 1024, 4096  # ten levels: head, one merge, the deep pair
    f0, bw, n_lo, n_hi = 1200.0, 200.0, 40, 90
    assert fdmt.head_active(nchan, f0, bw, n_hi, n_lo, t)
    coarse = fdmt._transform_fn(
        nchan, f0, bw, n_hi, t, fdmt._pick_fdmt_tile(t), True, True,
        n_lo=n_lo, with_scores=True, with_plane=False, t_orig=t,
        with_cert=True, use_score=True)
    found = _pallas_call_names(
        jax.make_jaxpr(coarse)(jnp.zeros((nchan, t), jnp.float32)).jaxpr,
        [])
    assert {"fdmt_head", "fdmt_merge", "fdmt_deep_pair", "score_rows"} \
        <= set(found)

    def rescore(data, offs):
        return pallas_dedisperse.dedisperse_plane_pallas_traced(
            data, offs, 512, dm_block=8, interpret=True)

    found += _pallas_call_names(
        jax.make_jaxpr(rescore)(jnp.zeros((nchan, t), jnp.float32),
                                jnp.zeros((8, nchan), jnp.int32)).jaxpr,
        [])
    assert "dedisperse_rows" in found
    for name in found:
        assert name in names.KERNEL_NAMES, name
        assert name not in ("kernel", "run", "fn")


def test_programs_are_named_for_what_they_are():
    from pulsarutils_tpu.ops import search

    assert search._fused_rescore_kernel(512, 8).__name__ == "rescore_rows"
    assert search._jax_search_kernel(False, 64).__name__ == "direct_sweep"
    for name in ("rescore_rows", "rescore_fused", "unpack_clean", "clean",
                 "fdmt_resident", "direct_sweep"):
        assert name in names.KERNEL_NAMES
