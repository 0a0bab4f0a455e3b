"""What ISSUE 28 adds (``python -m pytest chipbench/tests -q``, CPU): the
tiered reference and the tiered counts restate one tier rule, which is the
program's; the tiny two-tier rehearsal of ``htru_bpsr_fulldm.
backlog_sparse_smeared`` ends ``correct``, its bfloat16 control and a
doctored S/N do not; the new cell's entries of ``BENCHMARK.json``."""

import json
import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import kernel_counts, reference_tiered, tier_counts  # noqa: E402
from chipbench import run as harness  # noqa: E402

REHEARSAL = "tiny_cpu_tiers.backlog_sparse_smeared"
CELL = "htru_bpsr_fulldm.backlog_sparse_smeared"
NEW_METRICS = ["tiers_per_chunk", "tiers_certified_per_chunk",
               "tier_downsample_ms_per_chunk",
               "tier_downsample_device_ms_per_chunk",
               "tier_sweep_ms_per_chunk", "tier_sweep_device_ms_per_chunk",
               "tier_rescore_ms_per_hit_chunk", "tiered_fdmt_roofline",
               "tier_downsample_roofline"]
HTRU = dict(nchan=1024, nsamples=1 << 19, dmmin=0.0, dmmax=1000.0,
            fbottom=1182.0, bandwidth=400.0, tsamp=64e-6)


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _last_line(capsys, argv):
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


@pytest.mark.parametrize("dmmin,dmmax,fb,bw,ts,nchan", [
    (0.0, 1000.0, 1182.0, 400.0, 64e-6, 1024),
    (0.0, 52.0, 1182.0, 400.0, 64e-6, 1024),
    (300.0, 400.0, 1200.0, 200.0, 5e-4, 1024),
    (340.0, 700.0, 1200.0, 200.0, 5e-4, 64),
    (60.0, 300.0, 1182.0, 400.0, 64e-6, 1024),
])
def test_the_tier_rule_is_the_programs(dmmin, dmmax, fb, bw, ts, nchan):
    from pulsarutils_tpu.ops.plan import dm_tier_plan

    foff = -bw / nchan
    theirs = dm_tier_plan(nchan, dmmin, dmmax, fb, bw, ts, foff)
    ours = reference_tiered.tier_table(dmmin, dmmax, fb, bw, ts, foff)
    rows = tier_counts.tier_delay_rows(nchan, dmmin, dmmax, fb, bw, ts)
    assert [t["factor"] for t in ours] == [t.downsample for t in theirs] \
        == [r[0] for r in rows]
    for mine, prog, (_, n_first, n_last) in zip(ours, theirs, rows):
        assert np.array_equal(mine["dms"], prog.trial_dms)  # to the bit
        assert (mine["dm_lo"], mine["dm_hi"]) == (prog.dm_lo, prog.dm_hi)
        assert round(n_last - n_first) + 1 == len(prog.trial_dms)


def test_tier_counts_by_hand():
    """HTRU: tier 0 band delays 0-1,068, tiers 1-4 535-1,068, tier 5
    535-641 (3,312 rows); the native chunk read once; the five copies are
    (1/2 + 1/4 + 1/8 + 1/16 + 1/32) of it."""
    assert tier_counts.tier_delay_rows(1024, 0.0, 1000.0, 1182.0, 400.0,
                                       64e-6) == [
        (1, 0.0, 1068.0), (2, 535.0, 1068.0), (4, 535.0, 1068.0),
        (8, 535.0, 1068.0), (16, 535.0, 1068.0), (32, 535.0, 641.0)]
    c = tier_counts.tiered_fdmt_counts(**HTRU)
    native = 1024 * (1 << 19) * 4
    assert c["bytes"] == native and c["rows_out"] == 3312
    # the sum of fdmt_counts over the tiers, each on its own rows
    unit = 4149.0 * (1182.0 ** -2 - 1582.0 ** -2)  # s of band delay per DM
    by_hand = 0
    for k, (first, last) in enumerate([(0, 1068)] + [(535, 1068)] * 4
                                      + [(535, 641)]):
        ts = 64e-6 * 2 ** k
        by_hand += kernel_counts.fdmt_counts(
            1024, (1 << 19) >> k, (first + 0.5) * ts / unit,
            (last - 0.5) * ts / unit, 1182.0, 400.0, ts)["flops"]
    assert c["flops"] == by_hand
    flat = kernel_counts.fdmt_counts(**dict(HTRU, dmmax=52.0))
    assert 1.4 < c["flops"] / flat["flops"] < 1.7
    # one tier: the flat count
    one = tier_counts.tiered_fdmt_counts(**dict(HTRU, dmmax=52.0))
    assert one["flops"] == flat["flops"] and one["bytes"] == flat["bytes"]
    d = tier_counts.tier_downsample_counts(**HTRU)
    assert d["bytes"] == native + native * 31 // 32
    assert d["flops"] == native // 4 * 31 // 32
    least, roof = kernel_counts.roofline_seconds(
        d, {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12})
    assert roof == "memory" and math.isclose(least, d["bytes"] / 819e9)
    assert tier_counts.tier_downsample_counts(
        **dict(HTRU, dmmax=52.0)) == {"bytes": native, "flops": 0}


def test_two_tier_rehearsal_is_correct_and_its_control_is_not(capsys):
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", str(2**31 + 28), "--seconds", "1",
        "--trace", "0", "--rehearsal", "--control", "1"])
    assert rc != 0  # a rehearsal never passes
    assert line["correct"] is True and line["failed"] == 0
    assert line["control_correct"] is False
    said = [ln for ln in out if ln.startswith("reference ")]
    assert len(said) == 1 and said[0].startswith(
        "reference chipbench.reference_tiered (")
    c = line["compared"]
    assert c["trial_dm_rel_gap"]["value"] == 0.0
    assert c["snr_rel_gap_rms"]["ok"] and not c["snr_rel_gap_rms.control"]["ok"]
    # the program ran both tiers in every chunk, through the CLI's flag
    cold = next(ln for ln in out if ln.startswith("cold pass: PUsearchfrb"))
    assert "--dm-tiers smearing" in cold
    assert cold.count("snr_threshold resolved") == 2
    budget = json.loads(next(ln for ln in out if ln.startswith(
        "budget cold: "))[len("budget cold: "):])
    assert [[t["downsample"] for t in ch["tiers"]]
            for ch in budget["per_chunk"]] == [[16, 32]] * 3


def test_a_doctored_snr_is_not_correct(capsys, monkeypatch):
    from pulsarutils_tpu.io.candidates import CandidateStore

    real = CandidateStore.save_candidate

    def altered(self, root, istart, iend, info, table, *a, **kw):
        table._cols["snr"] = table._cols["snr"] * (1 + 1e-3)
        return real(self, root, istart, iend, info, table, *a, **kw)

    monkeypatch.setattr(CandidateStore, "save_candidate", altered)
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", "11", "--seconds", "1",
        "--trace", "0", "--rehearsal"])
    assert line["correct"] is False
    assert any("snr_rel_gap_rms" in ln and "FAILED" in ln for ln in out)


def test_traced_rehearsal_reads_the_tier_metrics(capsys, monkeypatch):
    # the tier metrics list the new cell alone, so the tiny geometry runs
    # under its name
    real = harness.resolve_cell
    monkeypatch.setattr(
        harness, "resolve_cell",
        lambda workload, rehearsal: real(workload, rehearsal)[:2]
        + real(REHEARSAL, True)[2:])
    rc, line, _ = _last_line(capsys, [
        "--workload", CELL, "--seed", "7", "--seconds", "1", "--trace", "1",
        "--rehearsal"])
    assert rc != 0 and line["correct"] is True
    m = line["metrics"]
    assert m["tiers_per_chunk"]["value"] == 2.0
    assert 4 / 3 <= m["tiers_certified_per_chunk"]["value"] <= 2.0
    assert m["tier_downsample_ms_per_chunk"]["value"] > 0
    assert m["tier_sweep_ms_per_chunk"]["value"] > 0
    assert m["tier_rescore_ms_per_hit_chunk"]["value"] > 0
    # no device trace on the CPU: those four read nothing and say nothing
    assert not {"tier_downsample_device_ms_per_chunk",
                "tier_sweep_device_ms_per_chunk", "tiered_fdmt_roofline",
                "tier_downsample_roofline", "fdmt_roofline"} & set(m)


def test_manifest_entries_of_the_new_cell():
    manifest = _load("BENCHMARK.json")
    cfg = _load("chipbench", "configs", "htru_bpsr_fulldm.json")
    low = _load("chipbench", "configs", "htru_bpsr_lowdm.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "htru_bpsr_fulldm")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["beams", "chunk_samples"]
    # cell 2's geometry and clean to the letter
    for key in ("nchans", "nbits", "tsamp_s", "fch1_mhz", "foff_mhz",
                "chunk_samples", "precision", "clean"):
        assert cfg[key] == low[key]
    assert (cfg["dmmin"], cfg["dmmax"]) == (0.0, 1000.0)
    assert cfg["cli_flags"] == low["cli_flags"] + ["--dm-tiers", "smearing"]
    assert cfg["reference"] == "reference_tiered"
    assert set(cfg["guarantees"]) == set(low["guarantees"])
    assert [t["trials"] for t in cfg["tiers"]["table"]] == [
        1069, 534, 534, 534, 534, 107]
    assert cfg["tiers"]["trials"] == 3312
    # the traffic: backlog_sparse key for key, but the pulse's width
    sparse = _load("chipbench", "traffic", "backlog_sparse.json")
    smeared = _load("chipbench", "traffic", "backlog_sparse_smeared.json")
    assert {k for k in set(sparse) | set(smeared)
            if sparse.get(k) != smeared.get(k)} == {
        "name", "why", "pulse_why", "pulse_widths", "hit_seed", "hit_why"}
    assert smeared["pulse_widths"] == [16]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("htru_bpsr_fulldm", "backlog_sparse_smeared", 1)
    # looked up by name: later PRs append metrics, and cells to these lists
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        spec = _load("chipbench", "layer_metrics", name + ".json")
        assert CELL in m["workloads"] and m["moves"] == "sky_s_per_s"
        assert (m["unit"], m["better"], m["layer"], m["source"]) == (
            spec["unit"], spec["better"], spec["layer"], spec["origin"])
    # fdmt_roofline counts one full-size call a chunk: not this cell's
    assert CELL not in per_layer["fdmt_roofline"]["workloads"]
