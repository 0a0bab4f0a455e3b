"""A cold start as a span tree (ISSUE 38): build-phase spans named for
their program, kernel-body spans inside their program's tracing, the
collector's pauses, and the benchmark's reader and metric files that read
them."""

import gc
import glob
import json
import os
import re
import time

import pytest

from pulsarutils_tpu.faults import compile_phase
from pulsarutils_tpu.obs import trace
from pulsarutils_tpu.obs.metrics import REGISTRY
from pulsarutils_tpu.obs.names import METRIC_NAMES
from pulsarutils_tpu.utils import logging_utils

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GC_COUNTER = "putpu_gc_pause_seconds_total"


@pytest.fixture
def tracer():
    logging_utils._install_compile_listener()
    tr = trace.start_tracing()
    try:
        yield tr
    finally:
        trace.stop_tracing()


def _events(tr):
    return [e for e in tr.events_since(0)[0] if e["ph"] == "X"]


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _gc_total():
    return sum(s["value"] for s in REGISTRY.snapshot()
               if s["name"] == GC_COUNTER)


def test_nested_jit_is_a_child_and_only_the_outer_interval_counts(tracer):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        # long to trace (tens of ms), so that it nearly fills ``outer``'s
        # interval and a scheduler's stall is small beside either
        for _ in range(100):
            x = jnp.sin(x) + 1.0
        return x

    @jax.jit
    def outer(x):
        return inner(x) * 2.0

    x = jnp.ones(7)            # built before the snapshot: its own programs
    before = logging_utils.compile_phase_snapshot()["trace_s"]
    with trace.trace_context("feedc0de00000001"):
        with trace.span("search/coarse") as bucket:
            outer(x)
    grown = logging_utils.compile_phase_snapshot()["trace_s"] - before
    events = _events(tracer)
    (t_outer,) = _named(events, "build/trace:jit_outer")
    (t_inner,) = _named(events, "build/trace:jit_inner")
    (lower,) = _named(events, "build/lower:jit_outer")
    (comp,) = _named(events, "build/compile:jit_outer")
    assert not _named(events, "build/lower:jit_inner")
    # the bucket that was open is the parent; the nested jit hangs under
    # the program that encloses it
    assert t_outer["args"]["parent_id"] == bucket.span_id
    assert t_inner["args"]["parent_id"] == t_outer["args"]["span_id"]
    assert lower["args"]["parent_id"] == comp["args"]["parent_id"] \
        == bucket.span_id
    assert comp["args"]["cache"] in ("hit", "miss")
    (coarse,) = _named(events, "search/coarse")
    built = [e for e in events if e["name"].startswith("build/")
             and e["ts"] >= coarse["ts"]]    # not ``x``'s own programs
    assert len(built) >= 4
    assert all(e["args"]["trace_id"] == "feedc0de00000001" for e in built)
    # one mechanism: the counter grew by the outermost interval alone.
    # The inner ones (jit_inner, jit_sin, ...) lie inside it and nearly
    # fill it: counted too, they would at least double the growth
    assert t_inner["dur"] < t_outer["dur"]
    assert 0.5 * t_outer["dur"] < grown * 1e6 < 1.5 * t_outer["dur"]
    assert logging_utils._BUILD.open == []


def test_a_phase_that_raises_closes_its_span(tracer):
    import jax
    import jax.numpy as jnp

    compile_phase.install()

    @jax.jit
    def boom(x):
        raise ValueError("refused while tracing")

    with pytest.raises(ValueError) as caught:
        with trace.span("search/coarse") as bucket:
            boom(jnp.ones(3))
    assert compile_phase.failed_phase(caught.value) == "trace"
    (ev,) = _named(_events(tracer), "build/trace:jit_boom")
    assert ev["args"]["parent_id"] == bucket.span_id
    assert logging_utils._BUILD.open == []
    # the span stack is whole again: the next span has no stale parent
    with trace.span("after") as after:
        pass
    assert after.parent_id is None


def test_no_tracer_no_span_no_callback_and_a_warm_call_fires_nothing(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    logging_utils._install_compile_listener()
    assert not trace.is_tracing()
    callbacks = list(gc.callbacks)
    allocated, annotated, fired = [], [], []
    monkeypatch.setattr(trace.Tracer, "next_id",
                        lambda self: allocated.append(1) or 0)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: annotated.append(name))

    @jax.jit
    def plain(x):
        return x * 3.0 + 1.0

    x = jnp.ones(5)
    opened = []
    real_open = trace.open_span
    monkeypatch.setattr(trace, "open_span",
                        lambda *a, **k: opened.append(a) or real_open(*a, **k))
    plain(x)                                   # builds, with no tracer
    assert opened == [] and allocated == [] and annotated == []
    assert gc.callbacks == callbacks
    assert logging_utils._BUILD.open == []

    def on_scalar(event, value, **kw):
        fired.append(event)

    def on_duration(event, secs, **kw):
        fired.append(event)

    monitoring.register_scalar_listener(on_scalar)
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        plain(x)                               # the same program, warm
    finally:
        monitoring.unregister_scalar_listener(on_scalar)
        monitoring.unregister_event_duration_listener(on_duration)
    assert fired == []


@pytest.fixture(scope="module")
def sweep_events():
    """The span events of one small coarse sweep built through the tests'
    seam (traced, not run: interpret-mode kernels are slow to execute) and
    of one small program built and run."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt

    logging_utils._install_compile_listener()
    nchan, t = 1024, 4096  # ten levels: head, one merge, the deep pair
    f0, bw, n_lo, n_hi = 1200.0, 200.0, 40, 90
    fdmt._build_transform.cache_clear()
    fdmt._transform_fn.cache_clear()
    tr = trace.start_tracing()
    try:
        run = fdmt._build_transform(
            nchan, f0, bw, n_hi, t, fdmt._pick_fdmt_tile(t), True, True,
            n_lo=n_lo, with_scores=True, with_plane=False, t_orig=t,
            with_cert=True, use_score=True)
        run.trace(jax.ShapeDtypeStruct((nchan, t), jnp.float32))
        jax.jit(lambda x: x - 2.0)(jnp.ones(11))
    finally:
        trace.stop_tracing()
    return _events(tr)


def test_a_sweep_records_its_plan_and_one_span_per_kernel_it_binds(
        sweep_events):
    (plan,) = _named(sweep_events, "build/plan:fdmt")
    assert plan["args"]["nchan"] == 1024 and plan["args"]["rows"] == 51
    (sweep,) = _named(sweep_events, "build/trace:jit_fn")
    kernels = [e for e in sweep_events
               if e["name"].startswith("build/kernel:")]
    assert sorted(e["name"] for e in kernels) == [
        "build/kernel:fdmt_deep_pair", "build/kernel:fdmt_head",
        "build/kernel:fdmt_merge", "build/kernel:score_rows"]
    for e in kernels:
        # each inside the sweep's tracing, by identity and by the clock
        assert e["args"]["parent_id"] == sweep["args"]["span_id"]
        assert sweep["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= sweep["ts"] + sweep["dur"] + 1.0
        assert {"rows", "t", "t_tile"} <= set(e["args"])
        assert e["args"]["t"] == 4096
    # the plan is host work before jax.jit: outside the tracing
    assert plan["ts"] + plan["dur"] <= sweep["ts"] + 1.0
    # what is left of the sweep's tracing is its self time: not negative
    assert sum(e["dur"] for e in kernels) <= sweep["dur"]


def test_a_kernel_span_outside_a_build_phase_is_not_recorded(tracer):
    # an eager call of the same code (no program is being traced)
    with logging_utils.kernel_build_span("dedisperse_rows", rows=8):
        pass
    assert _events(tracer) == []
    logging_utils._on_build_phase_begin(TRACE_EVENT, 0.0, fun_name="fn")
    try:
        with logging_utils.kernel_build_span("dedisperse_rows", rows=8):
            pass
    finally:
        logging_utils._on_build_event(TRACE_EVENT, 1e-4, fun_name="fn")
    kernel, sweep = _events(tracer)
    assert kernel["name"] == "build/kernel:dedisperse_rows"
    assert kernel["args"]["parent_id"] == sweep["args"]["span_id"]
    assert sweep["name"] == "build/trace:jit_fn"


def test_a_compile_span_says_whether_the_cache_served_it(tracer):
    name = "jit(rescore_rows)"
    logging_utils._on_build_phase_begin(COMPILE_EVENT, 0.0, fun_name=name)
    logging_utils._on_build_event(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    logging_utils._on_build_event(COMPILE_EVENT, 0.3, fun_name=name)
    logging_utils._on_build_phase_begin(COMPILE_EVENT, 0.0, fun_name=name)
    logging_utils._on_build_event(COMPILE_EVENT, 0.3, fun_name=name)
    hit, miss = _named(_events(tracer), "build/compile:jit_rescore_rows")
    assert hit["args"]["cache"] == "hit"
    assert hit["args"]["cache_load_s"] == 0.25
    assert miss["args"]["cache"] == "miss"
    assert "cache_load_s" not in miss["args"]


def test_a_phase_that_began_before_the_listener_is_counted_not_popped():
    logging_utils._install_compile_listener()
    assert logging_utils._BUILD.open == []
    before = logging_utils.compile_phase_snapshot()["trace_s"]
    logging_utils._on_build_event(TRACE_EVENT, 0.125, fun_name="fn")
    after = logging_utils.compile_phase_snapshot()["trace_s"]
    assert after - before == pytest.approx(0.125)
    assert logging_utils._BUILD.open == []


def test_the_collector_is_watched_only_between_start_and_stop():
    found = list(gc.callbacks)
    for _ in range(2):
        outside = _gc_total()
        gc.collect()                  # no tracer: not counted
        assert _gc_total() == outside
        tr = trace.start_tracing()
        assert len(gc.callbacks) == len(found) + 1
        junk = [[i] for i in range(50000)]
        junk.append(junk)             # a cycle for the collector
        del junk
        gc.collect()
        assert trace.stop_tracing() is tr
        assert gc.callbacks == found
        inside = _gc_total()
        assert inside > outside
        gc.collect()
        assert _gc_total() == inside
    # a tracer replaced without a stop leaves one callback, not two
    trace.start_tracing()
    trace.start_tracing()
    assert len(gc.callbacks) == len(found) + 1
    trace.stop_tracing()
    assert gc.callbacks == found
    assert GC_COUNTER in METRIC_NAMES


def test_a_long_pause_is_a_gc_span_under_the_span_that_was_open(tracer):
    watch = gc.callbacks[-1]
    assert isinstance(watch, trace._GcWatch)
    with trace.span("search/coarse") as bucket:
        watch("start", {"generation": 2, "collected": 0, "uncollectable": 0})
        time.sleep(0.003)
        watch("stop", {"generation": 2, "collected": 5, "uncollectable": 0})
        watch("start", {"generation": 0, "collected": 0, "uncollectable": 0})
        watch("stop", {"generation": 0, "collected": 0, "uncollectable": 0})
    before = _gc_total()
    trace.stop_tracing()
    pauses = [e for e in _named(_events(tracer), "gc")
              if e["args"].get("collected") == 5]
    (long_pause,) = pauses
    assert long_pause["dur"] >= 3000.0
    assert long_pause["args"]["generation"] == 2
    assert long_pause["args"]["parent_id"] == bucket.span_id
    # the short pause is in the counter and is no span
    assert _gc_total() - before >= 0.003
    assert not [e for e in _named(_events(tracer), "gc")
                if e["args"]["generation"] == 0 and e["dur"] < 1000.0]


# -- the benchmark's side: chipbench/readers/span_union.py and the nine
# -- metric files that read what the program emits

def _read_union(match, spans, per="total"):
    from chipbench.readers import span_union

    src = {"kind": "span_union", "match": match, "pass": "cold", "per": per}
    return span_union.read(src, {"cold": {"spans": spans, "budget": None},
                                 "passes": []})


@pytest.mark.parametrize("spans, seconds", [
    # nested: the inner program's tracing lies inside its parent's
    ([(0.0, 10.0, "build/trace:jit_fn"), (2.0, 3.0, "build/trace:jit_add"),
      (4.0, 4.5, "build/trace:jit__where")], 10.0),
    # overlapping: 2-5 and 4-6 cover four seconds, not five
    ([(2.0, 5.0, "build/trace:jit_a"), (4.0, 6.0, "build/trace:jit_b")],
     4.0),
    # disjoint, among spans of other names
    ([(0.0, 1.0, "build/trace:jit_a"), (1.0, 9.0, "build/lower:jit_a"),
      (9.0, 9.5, "build/trace:jit_b"), (0.0, 20.0, "chunk")], 1.5),
    # nothing matches: nothing to read, as on a program without the spans
    ([(0.0, 20.0, "chunk"), (1.0, 2.0, "search/coarse")], None),
    ([], None),
])
def test_span_union_reader(spans, seconds):
    got = _read_union("^build/trace:", spans)
    assert got == (pytest.approx(seconds) if seconds is not None else None)


#: the cold pass's metrics that read no build phase of a sweep: PR 24's
#: host clock around the whole pass, and ISSUE 45's two of the plan
NOT_OF_A_SWEEP = ("cold_pass_s", "cold_plan_s", "cold_cert_retention_s")


def _cold_metric_files():
    """The nine files ISSUE 38 adds."""
    return sorted(p for p in glob.glob(os.path.join(
        ROOT, "chipbench", "layer_metrics", "cold_*_s.json"))
        if os.path.basename(p)[:-5] not in NOT_OF_A_SWEEP)


@pytest.mark.parametrize("path", _cold_metric_files(),
                         ids=lambda p: os.path.basename(p)[:-5])
def test_each_cold_metric_reads_what_the_program_emits(path, sweep_events):
    with open(path) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == spec["name"]]
    assert entry["moves"] == spec["moves"] == "setup_s"
    assert entry["layer"] == spec["layer"]
    if spec["name"] == "cold_head_trace_s":
        # read in every cell but the one whose sweeps all decline the head
        # (ISSUE 44): no ``fdmt_head`` is bound there, so nothing to read
        assert entry["workloads"] == [
            w["name"] for w in manifest["workloads"]
            if w["config"] != "parkes_uwl_2bit"]
    else:
        assert "workloads" not in entry
    source = spec["source"]
    assert source["pass"] == "cold" and source["per"] == "total"
    if source["kind"] == "registry_counter":
        # the counter the tracer's collector watch adds to
        assert source["key"] == GC_COUNTER and source["key"] in METRIC_NAMES
        assert entry["source"] == "program_counter"
        return
    assert source["kind"] == "span_union"
    assert entry["source"] == "program_span"
    emitted = {e["name"] for e in sweep_events}
    rx = re.compile(source["match"])
    hit = {n for n in emitted if rx.search(n)}
    assert hit, f"{source['match']} matches none of what a sweep emits"
    # a kernel's metric reads that kernel's span and no other
    if "build/kernel:" in source["match"]:
        assert len(hit) == 1
    spans = [(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6, e["name"])
             for e in sweep_events]
    assert _read_union(source["match"], spans) > 0.0


def test_the_nine_cold_metrics_stand_together_in_the_manifest():
    # entries are only ever appended: PR 39's one follows them
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    first = names.index("cold_trace_s")
    assert names[first:first + 9] == [
        "cold_trace_s", "cold_lower_s", "cold_compile_s",
        "cold_sweep_trace_s", "cold_head_trace_s", "cold_merge_trace_s",
        "cold_deep_pair_trace_s", "cold_score_trace_s", "cold_gc_s"]


def test_the_certificate_plan_is_a_span_where_it_is_computed(tracer):
    # host work of a process's first call with a geometry (the retention
    # bound behind ``--snr-threshold certifiable``): computed once, so one
    # span, under whatever span was open
    import numpy as np

    from pulsarutils_tpu.ops import certify

    dms = np.linspace(100.0, 130.0, 7)
    args = (64, dms, 1200.0, 200.0, 5e-4, 4096)
    certify._retention_cached.cache_clear()
    with trace.span("call/plan") as plan:
        first = certify.retention_bound(*args, cert=True)
        again = certify.retention_bound(*args, cert=True)
    assert first == again
    (ev,) = _named(_events(tracer), "build/plan:cert_retention")
    assert ev["args"]["parent_id"] == plan.span_id
    assert ev["args"]["trials"] == 7 and ev["args"]["nchan"] == 64
    # ISSUE 45's two metrics read these spans of the cold pass: the plan
    # through ``span_total``, the bound inside it through ``span_union``
    from chipbench import run as harness

    spans = [(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6, e["name"])
             for e in _events(tracer)]
    ctx = {"cold": {"spans": spans, "budget": None}, "passes": []}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    only = dict(manifest, per_layer=[
        m for m in manifest["per_layer"]
        if m["name"] in ("cold_plan_s", "cold_cert_retention_s")])
    assert len(only["per_layer"]) == 2
    got = harness.read_layer_metrics(only, manifest["workloads"][0]["name"],
                                     ctx)
    assert got["cold_cert_retention_s"]["value"] == pytest.approx(
        ev["dur"] / 1e6)
    assert (0.0 < got["cold_cert_retention_s"]["value"]
            <= got["cold_plan_s"]["value"])
