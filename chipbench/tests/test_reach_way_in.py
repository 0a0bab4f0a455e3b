"""What ISSUE 27 adds (``python -m pytest chipbench/tests -q``, CPU): a
configuration names its own reference, a metric file its own counts
function; the control through the harness's own comparison; traffic
``backlog_dense`` and configuration ``rehearsal_1024ch_2bit_defaults``,
which no cell uses yet; the ``sweep_*`` metric files and the ``workloads``
lists of ``per_layer``."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import generate  # noqa: E402
from chipbench import run as harness  # noqa: E402
from chipbench.readers import trace_kernel_roofline  # noqa: E402

TINY = "tiny_cpu_rehearsal"
HYBRID = "rehearsal_1024ch_2bit.backlog_sparse"
HYBRID_CELLS = {HYBRID, "htru_bpsr_lowdm.backlog_sparse"}
HYBRID_ONLY = {"fdmt_roofline", "coarse_ms_per_chunk",
               "coarse_device_ms_per_chunk", "certified_chunk_pct",
               "rescore_ms_per_hit_chunk", "rescore_device_ms_per_pass"}
SWEEP = {"sweep_ms_per_chunk", "sweep_device_ms_per_chunk"}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _differing(a, b):
    return {k for k in set(a) | set(b) if a.get(k) != b.get(k)}


def _tiny_with(monkeypatch, **changes):
    """The tiny geometry with some keys of its configuration replaced, as
    a configuration file of its own would."""
    real = harness.resolve_cell

    def patched(workload, rehearsal):
        manifest, entry, cfg, traffic = real(workload, rehearsal)
        return manifest, entry, dict(cfg, **changes), traffic

    monkeypatch.setattr(harness, "resolve_cell", patched)


def _rehearse(capsys, traffic, seed, *more):
    rc = harness.main(["--workload", f"{TINY}.{traffic}", "--seed",
                       str(seed), "--seconds", "1", "--trace", "0",
                       "--rehearsal", *more])
    cap = capsys.readouterr()
    out = cap.out.strip().splitlines()
    return rc, json.loads(out[-1]), out, cap.err.strip().splitlines()


# -- part A: two look-ups by name ------------------------------------------

@pytest.mark.parametrize("named", [None, "tests.stub_reference"])
def test_a_configuration_names_its_reference(capsys, monkeypatch, named):
    """The stub reports the peak one sample off: asked, the run is not
    correct; the same run without the key asks ``reference.py``."""
    if named:
        _tiny_with(monkeypatch, reference=named)
    rc, line, out, err = _rehearse(capsys, "backlog_sparse", 2**31 + 27)
    said = [ln for ln in out if ln.startswith("reference ")]
    assert len(said) == 1
    assert said[0].startswith(
        f"reference chipbench.{named or 'reference'} (")
    assert line["correct"] is (named is None)
    gap = line["compared"]["peak_sample_gap"]
    assert gap == {"value": 0 if named is None else 1, "limit": 0,
                   "ok": named is None}
    # every number compared, beside its limit: last on standard error and
    # last in the line
    assert list(line)[-1] == "compared"
    assert [ln.split(":")[0] for ln in err[-len(line["compared"]):]] == [
        "compared " + k for k in line["compared"]]
    assert all(c["ok"] for k, c in line["compared"].items()
               if k != "peak_sample_gap")


def test_a_cells_reference_is_a_module_with_best_row():
    """The hybrid cells name none (``reference.py`` is the default); a
    configuration that names one names a module of ``chipbench/``."""
    import importlib

    manifest = _load("BENCHMARK.json")
    by_name = {c["name"]: _load(c["file"]) for c in manifest["configs"]}
    for cell in HYBRID_CELLS:
        assert "reference" not in by_name[cell.partition(".")[0]]
    for cfg in by_name.values():
        module = importlib.import_module(
            "chipbench." + cfg.get("reference", "reference"))
        assert callable(module.best_row)


@pytest.mark.parametrize("counts,factor", [
    ("fdmt_counts", 1.0), ("kernel_counts:fdmt_counts", 1.0),
    ("tests.stub_reference:twice_fdmt_counts", 2.0)])
def test_counts_with_and_without_a_colon(counts, factor):
    shapes = {"nchan": 1024, "nsamples": 1 << 20, "dmmin": 300.0,
              "dmmax": 400.0, "fbottom": 1200.0, "bandwidth": 200.0,
              "tsamp": 5e-4}
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
    # the coarse sweep's program, and one of another name
    ops = {"jit_fn/fdmt_head.1": 1.5, "jit_fn/reshape.3": 0.5,
           "jit_rescore_rows/dedisperse_rows.1": 9.0}
    ctx = {"trace": {"op_seconds": ops},
           "shapes": shapes, "peaks": peaks, "notes": [],
           "passes": [{"budget": {"per_chunk": [{}, {}, {}]}}]}
    src = dict(_load("chipbench", "layer_metrics",
                     "fdmt_roofline.json")["source"], counts=counts)
    least = 1024 * (1 << 20) * 4 / 819e9
    assert trace_kernel_roofline.read(src, ctx) == pytest.approx(
        100.0 * factor * least * 3 / 2.0)
    assert "memory roof" in ctx["notes"][0]
    # nothing of the kernel in the trace: nothing to read, never 0
    assert trace_kernel_roofline.read(dict(src, match="^jit_absent/"),
                                      ctx) is None


def test_the_control_goes_through_the_harness_s_own_comparison(capsys):
    """``--control 1`` puts the bfloat16 control's rows in the program's
    place: ``rms_gap`` and ``compare()`` against the cell's own limit.  It
    reads FAILED; the program's own verdict stands beside it."""
    rc, line, out, err = _rehearse(capsys, "backlog_sparse", 2**31 + 274,
                                   "--control", "1")
    assert line["correct"] is True and line["control_correct"] is False
    ctl = line["compared"]["snr_rel_gap_rms.control"]
    own = line["compared"]["snr_rel_gap_rms"]
    assert ctl["limit"] == own["limit"] and ctl["ok"] is False
    assert own["ok"] is True and 3 * own["value"] < own["limit"]
    assert ctl["value"] == "inf" or ctl["value"] > ctl["limit"]
    said = [ln for ln in out
            if ln.startswith("compare snr_rel_gap_rms.control:")]
    assert len(said) == 1 and said[0].endswith("[FAILED]")
    assert err[-1].startswith("compared snr_rel_gap_rms.control:")
    assert list(line)[-1] == "compared"
    # a plain run compares no control
    rc, line, out, _ = _rehearse(capsys, "backlog_sparse", 2**31 + 274)
    assert "control_correct" not in line
    assert not any(".control" in k for k in line["compared"])


def test_rows_as_table_is_what_rms_gap_reads():
    rows = [{"row": 3, "DM": 301.5, "snr": 10.0, "rebin": 1, "peak": 7},
            {"row": 4, "DM": 302.0, "snr": 20.0, "rebin": 2, "peak": 8}]
    assert harness.rms_gap(harness.rows_as_table(rows), rows) == (0.0, 2)
    off = [dict(rows[0], snr=10.1), dict(rows[1], peak=9)]
    # the row whose peak moved does not count; the other is 1 % off
    rms, n = harness.rms_gap(harness.rows_as_table(off), rows)
    assert n == 1 and rms == pytest.approx(0.01)
    assert harness.rms_gap(harness.rows_as_table([off[1]]), rows) == (
        float("inf"), 0)


# -- part B: traffic backlog_dense -------------------------------------------

def test_backlog_dense_is_backlog_sparse_with_a_pulse_in_every_chunk(
        tmp_path):
    sparse = _load("chipbench", "traffic", "backlog_sparse.json")
    dense = _load("chipbench", "traffic", "backlog_dense.json")
    # (and a file in no cell keeps the run's seed's hit)
    assert _differing(sparse, dense) == {"name", "why", "pulse_hops",
                                         "hit_seed", "hit_why"}
    assert dense["pulse_hops"] == [1, 3]
    cfg = _load("chipbench", "configs", TINY + ".json")
    info = generate.generate(str(tmp_path / "d.fil"), cfg, dense,
                             2**31 + 271)
    hop = info["hop"]
    starts = list(range(0, info["nsamples"] - hop, hop))
    assert len(starts) == 3
    # the harness's own rule: a pulse sits whole inside one hop, and the
    # chunks that start at that hop and at the one before hold it
    held = {s: [p for p in info["pulses"]
                if s <= p["sample"] // hop * hop <= s + hop]
            for s in starts}
    assert [len(v) for v in held.values()] == [1, 1, 1]
    assert held[0] == held[hop] != held[2 * hop]  # one pulse seen twice
    for p in info["pulses"]:
        span = cfg["dmmax"] - cfg["dmmin"]
        assert 0.49 <= (p["dm"] - cfg["dmmin"]) / span <= 0.51
        assert p["width"] == 1 and 28.0 <= p["target_snr"] <= 32.0


def test_rehearsal_of_the_dense_cell(capsys):
    rc, line, out, _ = _rehearse(capsys, "backlog_dense", 2**31 + 272)
    assert rc != 0  # a rehearsal never passes
    assert line["correct"] is True and line["failed"] == 0
    assert set(line) == {"correct", "attempted", "failed", "device",
                         "metrics", "compared"}
    assert set(line["metrics"]) == {"sky_s_per_s", "chunk_wall_p90_ms",
                                    "setup_s"}
    # three reference comparisons a run, none certified away
    assert sum(ln.startswith("reference ") for ln in out) == 3
    assert {"snr_rel_gap_rms", "snr_rel_gap_rms.2",
            "snr_rel_gap_rms.3"} <= set(line["compared"])
    assert "pulse_chunk_candidate_missing" not in line["compared"]


# -- part C: the flags nobody types ------------------------------------------

def test_defaults_configuration_is_its_parent_without_flags():
    parent = _load("chipbench", "configs", "rehearsal_1024ch_2bit.json")
    child = _load("chipbench", "configs",
                  "rehearsal_1024ch_2bit_defaults.json")
    assert _differing(parent, child) == {"name", "source", "deployment",
                                         "why", "cli_flags", "guarantees"}
    assert _differing(parent["guarantees"], child["guarantees"]) == {
        "best_row"}
    assert child["cli_flags"] == [] and child["reduced"] == []
    assert child["limits"] == parent["limits"]
    # data for the issue that argues its cell: no cell uses it yet, so
    # BENCHMARK.json may not list it
    manifest = _load("BENCHMARK.json")
    assert child["name"] not in {c["name"] for c in manifest["configs"]}
    assert {w["config"] for w in manifest["workloads"]} == {
        c["name"] for c in manifest["configs"]}


def test_rehearsal_of_the_defaults_cell(capsys, monkeypatch):
    _tiny_with(monkeypatch, cli_flags=[])
    rc, line, out, _ = _rehearse(capsys, "backlog_dense", 2**31 + 273)
    assert rc != 0 and line["correct"] is True and line["failed"] == 0
    assert sum(ln.startswith("reference ") for ln in out) == 3
    assert set(line["metrics"]) == {"sky_s_per_s", "chunk_wall_p90_ms",
                                    "setup_s"}
    cold = next(ln for ln in out if ln.startswith("cold pass: PUsearchfrb"))
    assert "--kernel" not in cold and "--snr-threshold" not in cold
    # every row exact by construction: the table has no such column, and
    # the comparison admits it
    program = next(ln for ln in out if ln.startswith("program, chunk"))
    assert "'exact': None" in program
    assert line["compared"]["best_row_not_exact"]["ok"] is True


# -- per-layer metrics: which cell prints which ------------------------------

def test_per_layer_workloads_name_cells():
    manifest = _load("BENCHMARK.json")
    cells = {w["name"] for w in manifest["workloads"]}
    assert HYBRID_CELLS <= cells
    names = {m["name"] for m in manifest["per_layer"]}
    assert HYBRID_ONLY <= names and not SWEEP & names
    for m in manifest["per_layer"]:
        if m["name"] in HYBRID_ONLY:
            assert set(m["workloads"]) == HYBRID_CELLS
        # a list names cells, and never a cell twice
        assert set(m.get("workloads", [])) <= cells
        assert len(set(m.get("workloads", []))) == len(m.get("workloads", []))
        spec = _load("chipbench", "layer_metrics", m["name"] + ".json")
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])


def _recorded_ctx():
    budget = _load("chipbench", "tests", "data", "budget_pass.json")
    p = {"budget": budget, "wall_s": budget["wall_s"], "spans": [],
         "registry_delta": {"putpu_certified_chunks_total": 2}}
    return budget, {"cold": p, "passes": [p], "trace": None, "notes": []}


@pytest.mark.parametrize("cell,present,absent", [
    (HYBRID, {"coarse_ms_per_chunk", "rescore_ms_per_hit_chunk",
              "certified_chunk_pct", "trips_per_chunk"}, SWEEP),
    # a pair of files that is no cell is on no metric's list
    (TINY + ".backlog_sparse", {"trips_per_chunk", "pass_overhead_ms"},
     HYBRID_ONLY | SWEEP)])
def test_a_cell_prints_its_own_layer_metrics(cell, present, absent):
    """On a recorded budget of the hybrid path, without a trace: a metric
    that lists its cells is read in those alone."""
    _, ctx = _recorded_ctx()
    got = harness.read_layer_metrics(_load("BENCHMARK.json"), cell, ctx)
    assert present <= set(got) and not absent & set(got)


def test_the_sweep_metric_files_read_a_recorded_budget():
    """The two ``sweep_*`` files wait for a default-flags cell: each names
    a reader that exists, ``sweep_ms_per_chunk`` reads ``search/dispatch``
    + ``search/readback`` per chunk, and without a trace the device's
    share gives nothing, never 0."""
    import importlib

    budget, ctx = _recorded_ctx()
    read = {}
    for name in sorted(SWEEP):
        spec = _load("chipbench", "layer_metrics", name + ".json")
        assert spec["name"] == name and "cells" not in spec
        assert (spec["layer"], spec["moves"], spec["better"]) == (
            "direct sweep", "sky_s_per_s", "lower")
        reader = importlib.import_module(
            "chipbench.readers." + spec["source"]["kind"])
        read[name] = reader.read(spec["source"], ctx)
    assert read["sweep_device_ms_per_chunk"] is None
    chunks = budget["per_chunk"]
    hit = [c["buckets"] for c in chunks if "search/readback" in c["buckets"]]
    assert read["sweep_ms_per_chunk"] == pytest.approx(1e3 * sum(
        b["search/dispatch"] + b["search/readback"] for b in hit)
        / len(chunks))
