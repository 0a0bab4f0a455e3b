"""What ISSUE 49 (ISSUE 48, asked again) adds to the benchmark (``python -m
pytest chipbench/tests -q``, CPU): the configuration ``chime_frb_16k_8bit``
and the cell ``chime_frb_16k_8bit.backlog_sparse_chime`` as entries of
``BENCHMARK.json`` that resolve, files that load, a file the accepted
generator writes, the accepted metrics' lists holding the cell once, and
every accepted ``per_layer`` entry as it was and in its old order.  Nothing
here holds an entry to a place in a list that a later PR extends.  The
program's side (delay bands, the planner, the rehearsal against the
reference) is ``tests/test_chime.py``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import dispersion, generate  # noqa: E402
from chipbench import run as harness  # noqa: E402

CONFIG = "chime_frb_16k_8bit"
TRAFFIC = "backlog_sparse_chime"
CELL = CONFIG + "." + TRAFFIC
#: the accepted metrics that find something to read in the cell: cell 6's
#: seventeen and the per-level merges (six a band's sweep)
SHARED_METRICS = (
    "tiers_per_chunk", "tiers_certified_per_chunk", "tier_sweep_ms_per_chunk",
    "tier_rescore_ms_per_hit_chunk", "fdmt_head_device_ms_per_chunk",
    "raw_upload_chunks_per_chunk", "cold_head_trace_s",
    "time_tiles_per_chunk", "tile_halo_ksamples_per_chunk",
    "chunk_stats_device_ms_per_chunk", "tiled_sweep_device_ms_per_chunk",
    "chunk_stats_roofline", "tiled_sweep_roofline", "tiled_score_roofline",
    "tile_clean_device_ms_per_chunk", "tiled_rescore_device_ms_per_pass",
    "tile_band_mean_device_ms_per_pass", "merge_levels_device_ms_per_chunk")


def _manifest():
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_the_cell_resolves_to_its_files():
    manifest, entry, cfg, traffic = harness.resolve_cell(CELL, False)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    assert config["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert config["reduced"] == cfg["reduced"] == ["dmmax"]
    assert set(cfg["reduced_why"]) == {"dmmax"}
    assert (cfg["name"], traffic["name"]) == (CONFIG, TRAFFIC)
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    # a reference and limits the harness can use
    assert os.path.exists(os.path.join(harness.HERE,
                                       cfg["reference"] + ".py"))
    assert set(cfg["limits"]) == {"snr_rel_gap_rms", "trial_dm_rel_gap"}


def test_the_accepted_generator_writes_the_cells_file():
    """``draw_pulses`` keeps the largest channel delay at ``dmmax`` free on
    both sides of the pulse: the configuration's ceiling is one it takes
    at chunks of 2^16, on the driver's large seeds too, and the pulse it
    draws lies in the native tier's second delay band of four."""
    _, _, cfg, traffic = harness.resolve_cell(CELL, False)
    tables = generate.LevelTables(traffic["noise_sd_levels"], cfg["nbits"])
    hop = cfg["chunk_samples"] // 2
    fbottom, bandwidth = dispersion.band_edges(
        cfg["fch1_mhz"], cfg["foff_mhz"], cfg["nchans"])
    lo, hi = (int(b) for b in cfg["tiers"]["table"][0]["delay_bands"][1]
              .split("-"))
    draws = set()
    for seed in (0, 2**31 + 48, 3400004881):
        ((pos, dm, _, width, _),) = generate.draw_pulses(cfg, traffic, seed,
                                                         tables)
        draws.add((pos, dm))
        shifts = dispersion.channel_shifts(dm, cfg["nchans"], fbottom,
                                           bandwidth, cfg["tsamp_s"])
        assert 3 * hop <= pos + shifts.min()
        assert pos + shifts.max() + width <= 4 * hop
        assert dm < cfg["tiers"]["table"][0]["dm_hi"]
        assert lo <= int(shifts.max() - shifts.min()) <= hi
    assert len(draws) == 1  # hit_seed: every seed brings the same pulse
    assert (traffic["hops_per_file"] * hop * cfg["nchans"]
            * cfg["nbits"] // 8) == 2 << 30


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_the_lists_hold_the_cell_once(name):
    manifest = _manifest()
    (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert metric["workloads"].count(CELL) == 1
    assert metric["moves"] in ("sky_s_per_s", "setup_s")
    spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
    assert os.path.exists(os.path.join(
        harness.HERE, "readers", spec["source"]["kind"] + ".py"))


def test_every_accepted_entry_is_as_it_was():
    """``per_layer`` as PR 47 left it (``data/per_layer_pr47.json``): every
    entry is there letter for letter but for cells appended to its list,
    in the old relative order."""
    old = harness.load_json(HERE, "data", "per_layer_pr47.json")["per_layer"]
    now = _manifest()["per_layer"]
    by_name = {m["name"]: m for m in now}
    assert len(by_name) == len(now)
    for was in old:
        entry = dict(by_name[was["name"]])
        if "workloads" in was:
            kept = entry.pop("workloads")
            assert kept[:len(was["workloads"])] == was["workloads"]
            assert len(set(kept)) == len(kept)
            was = {k: v for k, v in was.items() if k != "workloads"}
        assert entry == was
    names = [m["name"] for m in now if m["name"] in
             {w["name"] for w in old}]
    assert names == [w["name"] for w in old]


def test_traced_rehearsal_reads_the_cells_lists(capsys, monkeypatch):
    """Under the cell's name the tiny geometry runs traced: the readers of
    the lists the cell joined find its counters (two tiers a chunk, no
    tile and no halo where the CPU states no memory), and those of the
    device trace read nothing and say nothing."""
    import json

    real = harness.resolve_cell
    monkeypatch.setattr(
        harness, "resolve_cell",
        lambda workload, rehearsal: real(workload, rehearsal)[:2]
        + real("tiny_cpu_chime." + TRAFFIC, True)[2:])
    rc = harness.main(["--workload", CELL, "--seed", "49", "--seconds", "1",
                       "--trace", "1", "--rehearsal"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0 and line["correct"] is True
    m = line["metrics"]
    assert m["tiers_per_chunk"]["value"] == 2.0
    assert m["time_tiles_per_chunk"]["value"] == 0.0
    assert m["tile_halo_ksamples_per_chunk"]["value"] == 0.0
    assert m["raw_upload_chunks_per_chunk"]["value"] == 1.0
    assert m["tier_sweep_ms_per_chunk"]["value"] > 0
    assert not {"tiled_sweep_device_ms_per_chunk", "tiled_sweep_roofline",
                "merge_levels_device_ms_per_chunk",
                "fdmt_head_device_ms_per_chunk"} & set(m)
