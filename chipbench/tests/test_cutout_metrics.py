"""The two per-layer metrics ISSUE 50 adds are data files for readers that
were there: ``hit_products_ms_per_hit_chunk`` through ``budget_bucket`` and
``cutout_readback_mib_per_pass`` through ``registry_counter``
(``python -m pytest chipbench/tests -q``, CPU).  Both read recorded passes
(``data/passes_cutout.json``: a tiny CPU rehearsal of the program at PR 50
whose hit's window is over the store's budget and is summed on the device;
its ``recorded`` key says how); ``data/budget_pass_upload.json`` is a pass
of a program before it, which has the bucket and not the counter.  Nothing
here holds an entry to a place in ``per_layer``."""

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
    "hit_products_ms_per_hit_chunk": {"unit": "ms", "better": "lower",
                                      "source": "program_span"},
    "cutout_readback_mib_per_pass": {"unit": "MiB", "better": "lower",
                                     "source": "program_counter"},
}
READ_BACK = "putpu_cutout_readback_bytes_total"


def _recorded():
    with open(os.path.join(HERE, "data", "passes_cutout.json")) as f:
        return json.load(f)


def _before(passes=2):
    """A program before PR 50: its budget has the bucket, its registry no
    such counter."""
    with open(os.path.join(HERE, "data", "budget_pass_upload.json")) as f:
        budget = json.load(f)
    one = {"budget": budget, "registry_delta": {"putpu_hits_total": 1}}
    return budget, {"passes": [one] * passes}


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
    # every cell persists a hit a pass: no list of workloads
    assert entry == dict(ENTRIES[name], name=name, layer="sift and persist",
                         moves="sky_s_per_s")
    assert (spec["name"], spec["unit"], spec["better"], spec["layer"],
            spec["moves"], spec["origin"]) == (
        entry["name"], entry["unit"], entry["better"], entry["layer"],
        entry["moves"], entry["source"])
    assert len(spec["why"]) > 0


def test_hit_products_is_the_bucket_over_the_chunks_that_have_one():
    recorded = _recorded()
    passes = recorded["passes"]
    with_one = [c["buckets"]["hit_products"] for p in passes
                for c in p["budget"]["per_chunk"]
                if "hit_products" in c["buckets"]]
    assert len(with_one) == len(passes)  # one hit chunk of three a pass
    assert _read("hit_products_ms_per_hit_chunk", {"passes": passes}) \
        == pytest.approx(1e3 * sum(with_one) / len(with_one))
    # the cold pass builds the window's program inside the same bucket
    assert _read("hit_products_ms_per_hit_chunk",
                 {"passes": [recorded["cold"]]}) > 1e3 * max(with_one)
    # a program before PR 50 has the bucket too (7.4 ms on HTRU's hit
    # chunk); a pass with no hit, or no budget, gives nothing to read
    budget, before = _before()
    assert _read("hit_products_ms_per_hit_chunk", before) == pytest.approx(
        1e3 * budget["per_chunk"][2]["buckets"]["hit_products"])
    quiet = {"budget": {"per_chunk": passes[0]["budget"]["per_chunk"][:2]}}
    assert _read("hit_products_ms_per_hit_chunk",
                 {"passes": [quiet, {"budget": None}]}) is None


def test_cutout_readback_is_the_counter_per_pass_in_mib():
    passes = _recorded()["passes"]
    crossed = [p["registry_delta"][READ_BACK] for p in passes]
    # 128 channels x 121 sums x 4 B: the record, not its 611-sample window
    assert crossed == [128 * 121 * 4] * len(passes)
    assert [p["registry_delta"]["putpu_cutout_device_decim_total"]
            for p in passes] == [1] * len(passes)
    assert _read("cutout_readback_mib_per_pass", {"passes": passes}) \
        == pytest.approx(sum(crossed) / len(passes) / 2**20)
    # a program before PR 50 has no such counter and reads 0
    # (``registry_counter``'s rule); no pass at all reads nothing
    _, before = _before()
    assert _read("cutout_readback_mib_per_pass", before) == 0.0
    assert _read("cutout_readback_mib_per_pass", {"passes": []}) is None


def test_a_line_reports_both_beside_the_record_on_disk():
    """Through ``run.read_layer_metrics``, as a traced run's line is made:
    both names beside the accepted ``hit_record_kib_per_pass`` on this
    program and on the program before (its counter at 0)."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    names = set(ENTRIES) | {"hit_record_kib_per_pass"}
    only = {"per_layer": [m for m in manifest["per_layer"]
                          if m["name"] in names]}
    for cell in (manifest["workloads"][0]["name"],
                 manifest["workloads"][-1]["name"]):
        line = run.read_layer_metrics(only, cell,
                                      {"passes": _recorded()["passes"]})
        assert set(line) == names
        assert line["cutout_readback_mib_per_pass"]["value"] * 1024 \
            < line["hit_record_kib_per_pass"]["value"]
        _, before = _before()
        line = run.read_layer_metrics(only, cell, before)
        assert set(line) == names
        assert line["cutout_readback_mib_per_pass"] == {"value": 0.0,
                                                        "unit": "MiB"}
