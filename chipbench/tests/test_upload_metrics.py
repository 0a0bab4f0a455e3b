"""The two per-layer metrics ISSUE 46 adds are data files for readers that
were there: ``uploads_ready_per_chunk`` through ``budget_counter`` and
``upload_link_ms_per_chunk`` through ``budget_async``
(``python -m pytest chipbench/tests -q``, CPU).  Both read a recorded
budget footer (``data/budget_pass_upload.json``: ``BUDGET_JSON`` of a
three-chunk window pass of ``htru_bpsr_lowdm.backlog_sparse`` on one TPU
v5e, the program at PR 46: two of its three uploads hid);
``data/budget_pass.json`` is a program's before it, with no such counter
and no such overlapped seconds."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

ENTRIES = {
    "uploads_ready_per_chunk": {"unit": "count", "better": "higher",
                                "source": "program_counter"},
    "upload_link_ms_per_chunk": {"unit": "ms", "better": "lower",
                                 "source": "program_span"},
}


def _ctx(recorded, passes=2):
    with open(os.path.join(HERE, "data", recorded)) as f:
        budget = json.load(f)
    return budget, {"passes": [{"budget": budget}] * passes}


def _read(name, ctx):
    spec = run.load_json(run.HERE, "layer_metrics", name + ".json")
    reader = importlib.import_module(
        "chipbench.readers." + spec["source"]["kind"])
    return reader.read(spec["source"], ctx)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_file_and_the_manifest_entry_agree(name):
    spec = run.load_json(run.HERE, "layer_metrics", name + ".json")
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    # every cell uploads: no list of workloads
    assert entry == dict(ENTRIES[name], name=name, layer="upload",
                         moves="sky_s_per_s")
    assert (spec["name"], spec["unit"], spec["better"], spec["layer"],
            spec["moves"], spec["origin"]) == (
        entry["name"], entry["unit"], entry["better"], entry["layer"],
        entry["moves"], entry["source"])
    assert manifest["per_layer"][-2:] == [
        m for m in manifest["per_layer"] if m["name"] in ENTRIES]


def test_uploads_ready_is_the_counter_per_chunk():
    budget, ctx = _ctx("budget_pass_upload.json")
    ready = [c["counters"].get("uploads_ready", 0)
             for c in budget["per_chunk"]]
    assert ready == [0, 1, 1]  # a call's first upload overlaps nothing
    assert sum(ready) == budget["counters"]["uploads_ready"]
    assert _read("uploads_ready_per_chunk", ctx) == pytest.approx(
        sum(ready) / 3)
    # a program before PR 46 counts no such thing and reads 0, like any
    # budget counter that never moved; no chunk at all reads nothing
    _, before = _ctx("budget_pass.json")
    assert _read("uploads_ready_per_chunk", before) == 0.0
    assert _read("uploads_ready_per_chunk", {"passes": []}) is None


def test_upload_link_is_the_readers_overlapped_seconds_per_chunk():
    budget, ctx = _ctx("budget_pass_upload.json")
    assert _read("upload_link_ms_per_chunk", ctx) == pytest.approx(
        1e3 * budget["async_s"]["upload"] / 3)
    # a program before PR 46 has no ``async_s.upload``: left out of the
    # line, as is a pass that logged no budget
    _, before = _ctx("budget_pass.json")
    assert _read("upload_link_ms_per_chunk", before) is None
    assert _read("upload_link_ms_per_chunk",
                 {"passes": [{"budget": None}]}) is None


def test_a_line_reports_both_beside_upload_wait():
    """Through ``run.read_layer_metrics``, as a traced run's line is
    made: both new names beside the accepted ``upload_wait_ms_per_chunk``
    on this program; the counter alone (at 0) on the program before."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    names = set(ENTRIES) | {"upload_wait_ms_per_chunk"}
    only = {"per_layer": [m for m in manifest["per_layer"]
                          if m["name"] in names]}
    cell = manifest["workloads"][0]["name"]
    _, ctx = _ctx("budget_pass_upload.json")
    assert set(run.read_layer_metrics(only, cell, ctx)) == names
    _, before = _ctx("budget_pass.json")
    line = run.read_layer_metrics(only, cell, before)
    assert set(line) == names - {"upload_link_ms_per_chunk"}
    assert line["uploads_ready_per_chunk"] == {"value": 0.0,
                                               "unit": "count"}
