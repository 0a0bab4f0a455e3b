"""The per-layer metric ISSUE 39 adds is a data file for a reader that was
there: ``cold_frame_reserve_entries`` through ``registry_counter``
(``python -m pytest chipbench/tests -q``, CPU)."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402
from chipbench.readers import registry_counter  # noqa: E402

NAME = "cold_frame_reserve_entries"


def test_the_file_and_the_manifest_entry_agree():
    spec = run.load_json(run.HERE, "layer_metrics", NAME + ".json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "chunk loop",
                     "moves": "setup_s"}
    assert (spec["name"], spec["unit"], spec["better"], spec["layer"],
            spec["moves"], spec["origin"]) == (
        entry["name"], entry["unit"], entry["better"], entry["layer"],
        entry["moves"], entry["source"])
    assert spec["source"]["kind"] == "registry_counter"
    assert spec["source"]["pass"] == "cold" and spec["source"]["per"] == "total"


def test_it_reads_the_cold_pass_alone_and_0_without_the_counter():
    source = run.load_json(run.HERE, "layer_metrics", NAME + ".json")["source"]
    key = source["key"]
    assert key == "putpu_frame_reserve_entries_total"
    ctx = {"cold": {"registry_delta": {key: 1, "other": 3.0}},
           "passes": [{"registry_delta": {key: 1}}] * 4}
    assert registry_counter.read(source, ctx) == 1.0
    # the parent: a program without the counter has no such key
    bare = {"cold": {"registry_delta": {"other": 3.0}}, "passes": []}
    assert registry_counter.read(source, bare) == 0.0


def test_through_the_harness_it_is_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    key = "putpu_frame_reserve_entries_total"
    ctx = {"cold": {"registry_delta": {key: 1}, "budget": None, "spans": []},
           "passes": []}
    only = dict(manifest, per_layer=[m for m in manifest["per_layer"]
                                     if m["name"] == NAME])
    for cell in manifest["workloads"]:
        got = run.read_layer_metrics(only, cell["name"], ctx)
        assert got == {NAME: {"value": 1.0, "unit": "count"}}
