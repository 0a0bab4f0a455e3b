"""The per-layer metric ISSUE 26 adds is a data file for a reader that was
there: ``hit_record_kib_per_pass`` through ``registry_counter``
(``python -m pytest chipbench/tests -q``, CPU)."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench.readers import registry_counter  # noqa: E402


def _spec(name):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_hit_record_kib_is_counter_delta_per_pass_in_kib():
    spec = _spec("hit_record_kib_per_pass")
    assert spec["source"]["kind"] == "registry_counter"
    key = spec["source"]["key"]
    assert key == "putpu_candidate_bytes_written_total"
    # two passes, one hit each: 8,591,000 and 8,593,048 bytes on disk
    ctx = {"passes": [{"registry_delta": {key: 8591000.0, "other": 3.0}},
                      {"registry_delta": {key: 8593048.0}}]}
    assert registry_counter.read(spec["source"], ctx) == pytest.approx(
        (8591000 + 8593048) / 2 / 1024)
    # a program that has no such counter (PR 25 and before) reads 0: no
    # counter, not an empty record; no pass at all reads nothing
    bare = {"passes": [{"registry_delta": {"other": 3.0}}]}
    assert registry_counter.read(spec["source"], bare) == 0.0
    assert registry_counter.read(spec["source"], {"passes": []}) is None


def test_every_per_layer_metric_of_the_manifest_has_its_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert "hit_record_kib_per_pass" in {m["name"]
                                         for m in manifest["per_layer"]}
    for entry in manifest["per_layer"]:
        spec = _spec(entry["name"])
        assert (spec["name"], spec["unit"], spec["better"], spec["layer"],
                spec["moves"], spec["origin"]) == (
            entry["name"], entry["unit"], entry["better"], entry["layer"],
            entry["moves"], entry["source"])
        importlib.import_module("chipbench.readers."
                                + spec["source"]["kind"])
