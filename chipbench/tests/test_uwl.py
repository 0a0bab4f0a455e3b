"""What ISSUE 44 adds (``python -m pytest chipbench/tests -q``, CPU): the
tiny rehearsal of ``parkes_uwl_2bit.backlog_sparse_uwl`` (Parkes' 5.7:1
band in 52 channels of 2 bits, two smearing tiers) ends ``correct``, its
bfloat16 control and a run with every S/N off by 0.1 % do not, as for the
other configurations; under the cell's name the traced rehearsal reads the
new cell's program counters."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import run as harness  # noqa: E402

REHEARSAL = "tiny_cpu_uwl.backlog_sparse_uwl"
CELL = "parkes_uwl_2bit.backlog_sparse_uwl"


def _last_line(capsys, argv):
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_rehearsal_is_correct_and_its_control_is_not(capsys):
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", str(2**31 + 44), "--seconds", "1",
        "--trace", "0", "--rehearsal", "--control", "1"])
    assert rc != 0  # a rehearsal never passes
    assert line["correct"] is True and line["failed"] == 0
    assert line["control_correct"] is False
    c = line["compared"]
    assert c["trial_dm_rel_gap"]["value"] == 0.0
    assert c["snr_rel_gap_rms"]["ok"] and not c["snr_rel_gap_rms.control"]["ok"]
    (said,) = [ln for ln in out if ln.startswith("reference ")]
    assert said.startswith("reference chipbench.reference_tiered (")
    budget = json.loads(next(ln for ln in out if ln.startswith(
        "budget cold: "))[len("budget cold: "):])
    assert [[t["downsample"] for t in ch["tiers"]]
            for ch in budget["per_chunk"]] == [[1, 2]] * 3


def test_a_doctored_snr_is_not_correct(capsys, monkeypatch):
    from pulsarutils_tpu.io.candidates import CandidateStore

    real = CandidateStore.save_candidate

    def altered(self, root, istart, iend, info, table, *a, **kw):
        table._cols["snr"] = table._cols["snr"] * (1 + 1e-3)
        return real(self, root, istart, iend, info, table, *a, **kw)

    monkeypatch.setattr(CandidateStore, "save_candidate", altered)
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", "44", "--seconds", "1",
        "--trace", "0", "--rehearsal"])
    assert line["correct"] is False
    assert any("snr_rel_gap_rms" in ln and "FAILED" in ln for ln in out)


def test_traced_rehearsal_reads_the_cells_counters(capsys, monkeypatch):
    # the new metrics list the new cell alone, so the tiny geometry runs
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
    # two sweeps a chunk, each padded from 52 to 64 channels
    assert m["fdmt_pad_kchannels_per_chunk"]["value"] == 0.024
    assert m["tiers_per_chunk"]["value"] == 2.0
    assert m["wideband_rescore_rows_per_pass"]["value"] > 0
    # off the TPU no head is asked for, so none declines; nothing is tiled
    assert m["head_declined_sweeps_per_chunk"]["value"] == 0.0
    assert m["time_tiles_per_chunk"]["value"] == 0.0
    # no device trace on the CPU: those read nothing and say nothing
    assert not {"merge_levels_device_ms_per_chunk", "wideband_sweep_roofline",
                "tile2bit_clean_roofline", "wideband_rescore_roofline",
                "tiled_sweep_device_ms_per_chunk"} & set(m)
