"""The three readers ISSUE 25 adds, on hand-made spans, budget records and
operation seconds (``python -m pytest chipbench/tests -q``, CPU)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench.readers import (budget_chunk_field,  # noqa: E402
                               span_self_time, trace_kernel_seconds)


def _pass(spans=(), chunks=()):
    return {"spans": list(spans),
            "budget": {"per_chunk": [dict(c) for c in chunks]}}


def test_self_time_takes_out_the_union_of_the_other_spans():
    # a 10 s call; children that overlap (2-5 and 4-6: 4 s, not 5), nest
    # (4.5-5 inside both), stick out of the call (9-12: 1 s counts) and
    # lie wholly outside it (20-21: nothing)
    spans = [(0.0, 10.0, "call"), (2.0, 5.0, "chunk"), (4.0, 6.0, "chunk"),
             (4.5, 5.0, "search"), (9.0, 12.0, "call/finish"),
             (20.0, 21.0, "badchans")]
    src = {"key": "call", "per": "pass", "scale": 1000.0}
    ctx = {"passes": [_pass(spans)]}
    assert span_self_time.read(src, ctx) == pytest.approx(5000.0)
    # per pass: a second pass whose call is fully covered adds no self
    # time and halves the mean
    ctx["passes"].append(_pass([(0.0, 1.0, "call"), (0.0, 1.0, "chunk")]))
    assert span_self_time.read(src, ctx) == pytest.approx(2500.0)


def test_self_time_of_a_span_nobody_recorded_is_nothing():
    ctx = {"passes": [_pass([(0.0, 1.0, "chunk")])]}
    assert span_self_time.read({"key": "call", "per": "pass"}, ctx) is None
    assert span_self_time.read({"key": "call"}, {"passes": []}) is None


def test_chunk_field_is_the_mean_over_the_records_that_carry_it():
    src = {"key": "on_disk_lag_s", "scale": 1000.0}
    chunks = [{"chunk": 0, "on_disk_lag_s": 0.0},
              {"chunk": 1, "on_disk_lag_s": 0.25},
              {"chunk": 2, "on_disk_lag_s": 0.5}]
    ctx = {"passes": [_pass(chunks=chunks[:2]), _pass(chunks=chunks[2:])]}
    assert budget_chunk_field.read(src, ctx) == pytest.approx(250.0)
    # a program that does not stamp the field: nothing to read
    bare = {"passes": [_pass(chunks=[{"chunk": 0}, {"chunk": 1}])]}
    assert budget_chunk_field.read(src, bare) is None
    with open(os.path.join(HERE, "data", "budget_pass.json")) as f:
        recorded = {"passes": [{"spans": [], "budget": json.load(f)}]}
    assert budget_chunk_field.read(src, recorded) is None  # PR 24's footer
    assert budget_chunk_field.read(src, {"passes": [{"budget": None}]}) \
        is None


def test_kernel_seconds_by_program_name():
    ops = {"jit_unpack_clean/reverse.3": 0.3, "jit_clean/fusion.1": 0.1,
           "jit_fn/fdmt_head.1": 0.8, "jit_fn/score_rows.1": 0.1,
           "jit_rescore_rows/dedisperse_rows.1": 0.4,
           "jit_rescore_fused/copy.2": 0.2, "jit_cleanup/x": 9.0}
    chunks = [{"chunk": i} for i in range(3)]
    ctx = {"trace": {"op_seconds": ops},
           "passes": [_pass(chunks=chunks), _pass(chunks=chunks)]}

    def read(match, per):
        return trace_kernel_seconds.read(
            {"match": match, "per": per, "scale": 1000.0}, ctx)

    assert read("^jit_(unpack_)?clean/", "chunk") == pytest.approx(400 / 6)
    assert read("^jit_fn/", "chunk") == pytest.approx(900 / 6)
    assert read("^jit_rescore_(rows|fused)/", "pass") == pytest.approx(300)
    # a match that finds nothing (the parent's programs are named
    # otherwise), and a run without a device trace: nothing, no error
    assert read("^jit_no_such_program/", "pass") is None
    for trace in (None, {"op_seconds": {}}):
        assert trace_kernel_seconds.read(
            {"match": "^jit_fn/", "per": "chunk"},
            dict(ctx, trace=trace)) is None


def test_the_new_metric_files_name_readers_that_exist():
    import importlib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("call_setup_ms_per_pass", "persist_drain_ms_per_pass",
                 "call_finish_ms_per_pass", "call_unattributed_ms_per_pass",
                 "retrace_ms_per_pass", "exec_reload_ms_per_pass",
                 "on_disk_lag_ms_per_chunk", "clean_device_ms_per_chunk",
                 "coarse_device_ms_per_chunk", "rescore_device_ms_per_pass"):
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        entry = declared[name]
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"],
                spec["origin"]) == (name, entry["unit"], entry["layer"],
                                    entry["moves"], entry["source"])
        reader = importlib.import_module(
            "chipbench.readers." + spec["source"]["kind"])
        # on a run that recorded nothing the reader gives nothing
        empty = {"cold": _pass(), "passes": [], "trace": None, "notes": []}
        assert reader.read(spec["source"], empty) is None
