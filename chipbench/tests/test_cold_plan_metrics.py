"""The two per-layer metrics ISSUE 45 adds are data files for readers that
were there: ``cold_plan_s`` through ``span_total`` and
``cold_cert_retention_s`` through ``span_union``
(``python -m pytest chipbench/tests -q``, CPU).  Both read a recorded cold
pass of ``tiny_cpu_tiers`` (``data/cold_pass_spans.json``: the span list as
``run.run_pass(spans=True)`` keeps it)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

ENTRIES = {
    "cold_plan_s": {"unit": "s", "better": "lower",
                    "source": "program_span", "layer": "CLI per call",
                    "moves": "setup_s"},
    "cold_cert_retention_s": {"unit": "s", "better": "lower",
                              "source": "program_span",
                              "layer": "certificate", "moves": "setup_s"},
}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _recorded():
    with open(os.path.join(HERE, "data", "cold_pass_spans.json")) as f:
        spans = [tuple(s) for s in json.load(f)["spans"]]
    return {"spans": spans, "budget": None, "registry_delta": {}}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_file_and_the_manifest_entry_agree(name):
    spec = run.load_json(run.HERE, "layer_metrics", name + ".json")
    (entry,) = [m for m in _manifest()["per_layer"] if m["name"] == name]
    assert entry == dict(ENTRIES[name], name=name)  # every cell: no list
    assert (spec["name"], spec["unit"], spec["better"], spec["layer"],
            spec["moves"], spec["origin"]) == (
        entry["name"], entry["unit"], entry["better"], entry["layer"],
        entry["moves"], entry["source"])
    assert (spec["source"]["pass"], spec["source"]["per"]) == ("cold",
                                                               "total")


def test_both_read_the_recorded_cold_pass():
    cold = _recorded()
    plan = [(s, e) for s, e, n in cold["spans"] if n == "call/plan"]
    bound = [(s, e) for s, e, n in cold["spans"]
             if n == "build/plan:cert_retention"]
    assert len(plan) == 1 and len(bound) == 2  # one a tier
    # a warm pass of the same process: the plan again, no bound (its
    # lru_cache), and neither counts towards the cold pass's numbers
    warm = {"spans": [(100.0, 100.5, "call/plan")], "budget": None}
    ctx = {"cold": cold, "passes": [warm, warm]}
    manifest = _manifest()
    only = dict(manifest, per_layer=[m for m in manifest["per_layer"]
                                     if m["name"] in ENTRIES])
    for cell in manifest["workloads"]:
        got = run.read_layer_metrics(only, cell["name"], ctx)
        assert set(got) == set(ENTRIES)
        assert got["cold_plan_s"] == {
            "value": pytest.approx(plan[0][1] - plan[0][0]), "unit": "s"}
        assert got["cold_cert_retention_s"] == {
            "value": pytest.approx(sum(e - s for s, e in bound)),
            "unit": "s"}
        # the bound is computed inside the plan
        assert (got["cold_cert_retention_s"]["value"]
                < got["cold_plan_s"]["value"])
    assert all(plan[0][0] <= s and e <= plan[0][1] for s, e in bound)


def test_a_program_without_the_spans_gives_nothing_to_read():
    manifest = _manifest()
    only = dict(manifest, per_layer=[m for m in manifest["per_layer"]
                                     if m["name"] in ENTRIES])
    bare = {"cold": {"spans": [(0.0, 1.0, "call")], "budget": None},
            "passes": []}
    cell = manifest["workloads"][0]["name"]
    assert run.read_layer_metrics(only, cell, bare) == {}
