"""What ISSUE 32 adds (``python -m pytest chipbench/tests -q``, CPU): the
boxcar reference restates the ladder rule, which is the program's; the tiny
two-tier rehearsal of ``htru_bpsr_fulldm_boxcar4096.backlog_pointing_wide``
ends ``correct`` with its pulse matched wider than 8 samples, its bfloat16
control does not; the scorer's counts; the new cell's entries of
``BENCHMARK.json``."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import boxcar_counts, reference_boxcar, tier_counts  # noqa: E402
from chipbench import run as harness  # noqa: E402

REHEARSAL = "tiny_cpu_boxcar.backlog_sparse_smeared"
CONFIG = "htru_bpsr_fulldm_boxcar4096"
CELL = CONFIG + ".backlog_pointing_wide"
NEW_METRICS = ["boxcar_windows_per_chunk", "boxcar_hit_window_samples",
               "boxcar_tiers_certified_per_chunk",
               "boxcar_sweep_device_ms_per_chunk",
               "boxcar_score_device_ms_per_chunk", "boxcar_score_roofline"]
HTRU = dict(nchan=1024, nsamples=1 << 19, dmmin=0.0, dmmax=1000.0,
            fbottom=1182.0, bandwidth=400.0, tsamp=64e-6)


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _last_line(capsys, argv):
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


@pytest.mark.parametrize("boxcar_max", [8, 64, 4096])
def test_the_ladder_rule_is_the_programs(boxcar_max):
    from pulsarutils_tpu.ops.search import boxcar_ladder, score_profiles

    for factor in (1, 2, 16, 32):
        assert tuple(reference_boxcar.ladder(boxcar_max, factor)) \
            == boxcar_ladder(boxcar_max, factor)
    rng = np.random.default_rng(boxcar_max)
    t = 64 * boxcar_max
    plane = rng.standard_normal((3, t))
    plane[0, 5 * boxcar_max + 3:6 * boxcar_max + 3] += 9 / np.sqrt(
        boxcar_max)
    ladder = boxcar_ladder(boxcar_max)
    *_, snr, win, peak = score_profiles(plane, windows=ladder)
    for r in range(3):
        want = reference_boxcar.score_row(plane[r], list(ladder))
        assert (int(win[r]), int(peak[r])) == want[1:]
        assert snr[r] == pytest.approx(want[0], rel=1e-12)


def test_score_counts_by_hand():
    """Each tier's plane read once: 1,069 rows x 2^19, 534 x 2^18 ... 107
    x 2^14 float32 = 3.30 GB, 4.03 ms at 819 GB/s; the ladder adds no
    bytes."""
    rows = [(1069, 19), (534, 18), (534, 17), (534, 16), (534, 15),
            (107, 14)]
    c = boxcar_counts.score_counts(**HTRU)
    assert c["bytes"] == sum(r * 4 << k for r, k in rows) and \
        c["rows_out"] == 3312
    assert [r for r, _ in rows] == [
        int(round(b - a)) + 1 for _, a, b in tier_counts.tier_delay_rows(
            *(HTRU[k] for k in ("nchan", "dmmin", "dmmax", "fbottom",
                                "bandwidth", "tsamp")))]
    short = boxcar_counts.score_counts(**HTRU, boxcar_max=8)
    assert short["bytes"] == c["bytes"] and short["flops"] < c["flops"]
    # under one add a sample of the planes, however long the ladder
    assert c["flops"] < c["bytes"] // 4


def test_boxcar_rehearsal_is_correct_and_its_control_is_not(capsys):
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", str(2**31 + 32), "--seconds", "1",
        "--trace", "0", "--rehearsal", "--control", "1"])
    assert rc != 0  # a rehearsal never passes
    assert line["correct"] is True and line["failed"] == 0
    assert line["control_correct"] is False
    said = [ln for ln in out if ln.startswith("reference ")]
    assert len(said) == 1 and said[0].startswith(
        "reference chipbench.reference_boxcar (")
    c = line["compared"]
    assert c["trial_dm_rel_gap"]["value"] == 0.0 and c["rebin_gap"]["ok"]
    assert c["snr_rel_gap_rms"]["ok"] and not c["snr_rel_gap_rms.control"]["ok"]
    cold = next(ln for ln in out if ln.startswith("cold pass: PUsearchfrb"))
    assert "--dm-tiers smearing --boxcar-max 256" in cold
    budget = json.loads(next(ln for ln in out if ln.startswith(
        "budget cold: "))[len("budget cold: "):])
    # 256 samples of the file are 16 of the 16x tier and 8 of the 32x
    assert [[t["windows"] for t in ch["tiers"]]
            for ch in budget["per_chunk"]] == [[5, 4]] * 3
    # the 16-sample pulse is one sample of the 16x tier: matched there
    hit = budget["per_chunk"][-1]
    assert hit["best_window_samples"] in (16, 32)


def test_traced_rehearsal_reads_the_boxcar_metrics(capsys, monkeypatch):
    # the boxcar metrics list the new cell alone, so the tiny geometry runs
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
    assert m["boxcar_windows_per_chunk"]["value"] == 9.0
    assert m["boxcar_hit_window_samples"]["value"] in (16.0, 32.0)
    assert 4 / 3 <= m["boxcar_tiers_certified_per_chunk"]["value"] <= 2.0
    # no device trace on the CPU: those three read nothing and say nothing;
    # the metrics of the other cells' lists stay out
    assert not {"boxcar_sweep_device_ms_per_chunk",
                "boxcar_score_device_ms_per_chunk", "boxcar_score_roofline",
                "tiers_per_chunk", "fdmt_roofline"} & set(m)
    # the unlisted metrics apply to the new cell as they are
    assert {"trips_per_chunk", "clean_ms_per_chunk", "cold_pass_s",
            "hit_record_kib_per_pass", "prescan_packed_mib"} <= set(m)


def test_manifest_entries_of_the_new_cell():
    manifest = _load("BENCHMARK.json")
    cfg = _load("chipbench", "configs", CONFIG + ".json")
    full = _load("chipbench", "configs", "htru_bpsr_fulldm.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"].endswith(CONFIG + ".json")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["beams", "chunk_samples"]
    # cell 3's geometry, clean, DM range, chunk and tier table to the letter
    for key in ("nchans", "nbits", "tsamp_s", "fch1_mhz", "foff_mhz", "dmmin",
                "dmmax", "chunk_samples", "precision", "clean", "limits"):
        assert cfg[key] == full[key]
    assert cfg["cli_flags"] == full["cli_flags"] + ["--boxcar-max", "4096"]
    assert (cfg["boxcar_max"], cfg["reference"],
            cfg["reference_half_rows"]) == (4096, "reference_boxcar", 48)
    assert set(cfg["guarantees"]) == set(full["guarantees"])
    assert cfg["assumed"][:len(full["assumed"])] == full["assumed"]
    for mine, theirs in zip(cfg["tiers"]["table"], full["tiers"]["table"]):
        assert {k: mine[k] for k in theirs} == theirs
        assert mine["boxcar_widest"] * mine["downsample"] == 4096
    assert [t["windows"] for t in cfg["tiers"]["table"]] == [
        13, 12, 11, 10, 9, 8] and cfg["tiers"]["windows"] == 63
    # the traffic: backlog_sparse_smeared key for key, but the file's
    # length, the pulse's hop and its width
    smeared = _load("chipbench", "traffic", "backlog_sparse_smeared.json")
    wide = _load("chipbench", "traffic", "backlog_pointing_wide.json")
    assert {k for k in set(smeared) | set(wide)
            if smeared.get(k) != wide.get(k)} == {
        "name", "why", "pulse_why", "hops_per_file", "pulse_hops",
        "pulse_widths"}
    assert (wide["hops_per_file"], wide["pulse_hops"],
            wide["pulse_widths"]) == (16, [15], [512])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "backlog_pointing_wide", 1)
    assert len(cell["why"]) <= 200
    # looked up by name: later PRs append metrics, and cells to these lists
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        spec = _load("chipbench", "layer_metrics", name + ".json")
        assert CELL in m["workloads"] and m["moves"] == "sky_s_per_s"
        assert (spec["unit"], spec["better"], spec["layer"]) == (
            m["unit"], m["better"], m["layer"])
        assert spec["origin"] == m["source"]
