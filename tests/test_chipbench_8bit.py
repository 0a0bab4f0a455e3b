"""The benchmark's 8-bit rehearsal under tier-1 (ISSUE 34 asked, PR 34
could touch no file of ``tests/``; ISSUE 35): ``tiny_cpu_8bit`` under
``backlog_sparse_8bit`` through ``PUsearchfrb`` as ``chipbench/run.py``
drives it — 64 channels of 8-bit samples, two smearing tiers, the bytes
uploaded raw and widened on the device — ends ``correct`` against
``reference_boxcar``, its bfloat16 control does not; and the generator
still writes a 2-bit file byte for byte as PR 33's did."""

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import generate  # noqa: E402
from chipbench import run as harness  # noqa: E402

#: sha256 of the ``tiny_cpu_rehearsal.backlog_sparse`` file of seed 1 as
#: the parent of PR 34 wrote it (``chipbench/tests/test_nbits.py``)
PARENT_2BIT_SEED_1 = \
    "4cd536aa6eecc3aaf57428f280b349685a18081f2bce92cd06045e050b059b64"


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


def _counters(*names):
    from pulsarutils_tpu.obs import metrics

    return [metrics.counter(n).value for n in names]


def test_8bit_rehearsal_is_correct_and_its_control_is_not(capsys):
    moved = ("putpu_lowbit_packed_chunks_total",
             "putpu_prescan_packed_bytes_total",
             "putpu_chunks_quarantined_total")
    before = _counters(*moved)
    rc = harness.main([
        "--workload", "tiny_cpu_8bit.backlog_sparse_8bit", "--seed",
        "3400000355", "--seconds", "1", "--trace", "0", "--rehearsal",
        "--control", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc != 0  # a rehearsal never exits 0
    assert line["correct"] is True and line["control_correct"] is False
    compared = line["compared"]
    assert compared["snr_rel_gap_rms.control"]["ok"] is False
    assert all(c["ok"] for name, c in compared.items()
               if name != "snr_rel_gap_rms.control")
    assert any("reference_boxcar" in ln for ln in out
               if ln.startswith("reference chipbench."))
    # every chunk went up as raw bytes, the pre-scan read the file packed
    # (once, in the cold pass), and the code-domain gate passed them all
    chunks, prescan, quarantined = (
        after - b for after, b in zip(_counters(*moved), before))
    assert chunks >= 3 and quarantined == 0
    cfg = _load("configs", "tiny_cpu_8bit")
    hops = _load("traffic", "backlog_sparse_8bit")["hops_per_file"]
    assert prescan == cfg["nchans"] * cfg["chunk_samples"] // 2 * hops


def test_a_2bit_file_is_still_the_parents(tmp_path):
    path = str(tmp_path / "f.fil")
    traffic = {k: v for k, v in _load("traffic", "backlog_sparse").items()
               if k != "hit_seed"}
    generate.generate(path, _load("configs", "tiny_cpu_rehearsal"),
                      traffic, 1)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == PARENT_2BIT_SEED_1


def test_fulldm_rehearsal_in_time_tiles_is_correct(capsys, tmp_path,
                                                   force_time_tiles):
    """ISSUE 40: ``tiny_cpu_8bit_fulldm`` (five smearing tiers, DM 0-600)
    under ``backlog_sparse_8bit_dm500`` with a planner budget that puts
    the 2x tier, which holds the pulse, in two time tiles and the native
    tier in more: ``correct`` against ``reference_boxcar``'s
    untiled float64 search, the bfloat16 control not."""
    cfg = _load("configs", "tiny_cpu_8bit_fulldm")
    traffic = _load("traffic", "backlog_sparse_8bit_dm500")
    path = str(tmp_path / "plan.fil")
    info = generate.generate(path, cfg, traffic, 3400000401)
    tiles = force_time_tiles(path, dict(
        chunk_length=cfg["chunk_samples"] // 2 * cfg["tsamp_s"],
        dmmin=cfg["dmmin"], dmmax=cfg["dmmax"], backend="jax",
        kernel="hybrid", snr_threshold="certifiable", zero_dm=True,
        dm_tiers="smearing", boxcar_max=cfg["boxcar_max"]), 2, tier=1)
    assert len(tiles) == 5 and tiles[0].tiles > tiles[1].tiles > 1
    assert 42.4 < info["pulses"][0]["dm"] < 84.7  # the 2x tier's
    swept = ("putpu_time_tiles_total", "putpu_host_fallbacks_total")
    before = _counters(*swept)
    rc = harness.main([
        "--workload", "tiny_cpu_8bit_fulldm.backlog_sparse_8bit_dm500",
        "--seed", "3400000401", "--seconds", "1", "--trace", "0",
        "--rehearsal", "--control", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc != 0  # a rehearsal never exits 0
    assert line["correct"] is True and line["control_correct"] is False
    assert all(c["ok"] for name, c in line["compared"].items()
               if name != "snr_rel_gap_rms.control")
    n_tiles, fallbacks = (a - b for a, b in zip(_counters(*swept), before))
    per_chunk = sum(t.tiles for t in tiles if t.tiles > 1)
    assert n_tiles >= 2 * 3 * per_chunk and n_tiles % per_chunk == 0
    assert fallbacks == 0
