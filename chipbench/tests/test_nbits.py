"""What ISSUE 34 adds (``python -m pytest chipbench/tests -q``, CPU): the
generator writes 1-, 4- and 8-bit files beside the 2-bit ones, which stay
byte for byte the parent's; ``reference.read_packed`` reads all four widths
as the program's own reader does; the realised noise is the traffic's; the
tiny 8-bit two-tier rehearsal of ``backlog_sparse_8bit`` ends ``correct``,
its bfloat16 control and a doctored S/N do not."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import generate, reference  # noqa: E402
from chipbench import run as harness  # noqa: E402

REHEARSAL = "tiny_cpu_8bit.backlog_sparse_8bit"
#: sha256 of ``tiny_cpu_rehearsal.backlog_sparse`` files, taken on the
#: parent of PR 34 (commit 1995bb2), whose generator packed 2 bits only
PARENT_2BIT = {
    1: "4cd536aa6eecc3aaf57428f280b349685a18081f2bce92cd06045e050b059b64",
    2147483659:
        "530999a0f8cc54b915789eaa31ee20ae2b9f9de6e1d01e8f3d986c6b3751fc99",
    3400000019:
        "32b06b1443523194dc65af15fb12d156da4bfcf3cddf2b79b679f4f3bc5723f5"}


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


def _traffic(nbits):
    """``backlog_sparse_8bit`` with its levels scaled to the quantiser:
    the same noise in units of the full scale."""
    traffic = _load("traffic", "backlog_sparse_8bit")
    if nbits == 8:
        return traffic
    two = _load("traffic", "backlog_sparse")
    scale = {1: 0.3, 2: 1.0, 4: 4.0}[nbits]
    return dict(
        traffic, noise_mean_levels=two["noise_mean_levels"] * scale,
        noise_sd_levels=two["noise_sd_levels"] * scale,
        hot_channels=[dict(c, excess_levels=c["excess_levels"] * scale)
                      for c in two["hot_channels"]],
        comb=dict(traffic["comb"],
                  amp_levels=two["comb"]["amp_levels"] * scale))


def _file_levels(path):
    """(nsamples, nchan) levels in FILE channel order, by the reference."""
    packed_T, hdr = reference.read_packed(path)
    return np.stack([reference._file_channel(packed_T, hdr["nbits"], fc)
                     for fc in range(hdr["nchans"])], axis=1), hdr


@pytest.mark.parametrize("seed", sorted(PARENT_2BIT))
def test_a_2bit_file_is_the_parents_byte_for_byte(tmp_path, seed):
    path = str(tmp_path / "f.fil")
    # the traffic as the parent had it: its hit was the run's seed's
    traffic = {k: v for k, v in _load("traffic", "backlog_sparse").items()
               if k != "hit_seed"}
    generate.generate(path, _load("configs", "tiny_cpu_rehearsal"),
                      traffic, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == PARENT_2BIT[seed]


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
def test_the_reference_reads_what_the_programs_reader_reads(tmp_path, nbits):
    from pulsarutils_tpu.io.sigproc import FilterbankReader

    cfg = dict(_load("configs", "tiny_cpu_8bit"), nbits=nbits)
    path = str(tmp_path / "f.fil")
    a, b = str(tmp_path / "a.fil"), str(tmp_path / "b.fil")
    info = generate.generate(path, cfg, _traffic(nbits), 3400000301)
    generate.generate(a, cfg, _traffic(nbits), 3400000301, threads=1)
    generate.generate(b, cfg, _traffic(nbits), 3400000302)
    with open(path, "rb") as f, open(a, "rb") as fa, open(b, "rb") as fb:
        bytes_ = f.read()
        assert bytes_ == fa.read() and bytes_ != fb.read()
    levels, hdr = _file_levels(path)
    assert hdr["nbits"] == nbits and levels.shape == (info["nsamples"], 64)
    assert os.path.getsize(path) - len(generate.sigproc_header(cfg)) \
        == info["nsamples"] * 64 * nbits // 8
    reader = FilterbankReader(path)
    block = reader.read_block(0, info["nsamples"])  # (nchan, n), file order
    assert np.array_equal(np.asarray(block), levels.T.astype(block.dtype))
    # every level of the quantiser's middle occurs, none beyond its top
    assert levels.max() <= (1 << nbits) - 1
    if nbits == 8:
        assert set(range(64, 129)) <= set(np.unique(levels).tolist())


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
def test_the_realised_noise_is_the_traffics(tmp_path, nbits):
    """To 1 %: the mean and standard deviation of a quiet channel's levels
    are those of the traffic's normal, rounded and clipped by the
    quantiser (exact, from ``level_probabilities``); at 8 bits, with both
    rails six sigma away, they are the traffic's own numbers."""
    cfg = dict(_load("configs", "tiny_cpu_8bit"), nbits=nbits)
    traffic = dict(_traffic(nbits), comb=None, hot_channels=[],
                   pulse_hops=[])
    path = str(tmp_path / "f.fil")
    generate.generate(path, cfg, traffic, 3400000303)
    levels, _ = _file_levels(path)
    mu, sd = traffic["noise_mean_levels"], traffic["noise_sd_levels"]
    p = generate.level_probabilities(mu, sd, 1 << nbits)
    k = np.arange(1 << nbits)
    want_mean = float((p * k).sum())
    want_sd = float(np.sqrt((p * k * k).sum() - want_mean ** 2))
    assert levels.mean() == pytest.approx(want_mean, rel=0.01)
    assert levels.std() == pytest.approx(want_sd, rel=0.01)
    per_channel = levels.std(axis=0)
    assert per_channel == pytest.approx(np.full(64, want_sd), rel=0.03)
    if nbits == 8:
        assert (want_mean, want_sd) == pytest.approx((mu, sd), rel=0.01)


def _last_line(capsys, argv):
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_8bit_rehearsal_is_correct_and_its_control_is_not(capsys):
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", "3400000305", "--seconds", "1",
        "--trace", "0", "--rehearsal", "--control", "1"])
    assert rc != 0  # a rehearsal never exits 0
    assert line["correct"] is True and line["control_correct"] is False
    compared = line["compared"]
    assert compared["snr_rel_gap_rms.control"]["ok"] is False
    assert all(c["ok"] for name, c in compared.items()
               if name != "snr_rel_gap_rms.control")
    # the pulse is found in the tier the rule gives its DM: the second
    ref = next(ln for ln in out if ln.startswith("reference chipbench."))
    assert "reference_boxcar" in ref
    pulse = json.loads(next(ln for ln in out if ln.startswith("generated ")
                            ).split("pulses ")[1])[0]
    assert 42.4 < pulse["dm"] < 80.0 and pulse["width"] == 4
    budget = json.loads(next(ln for ln in out if ln.startswith(
        "budget cold: "))[len("budget cold: "):])
    hit = budget["per_chunk"][-1]
    assert [t["downsample"] for t in hit["tiers"]] == [1, 2]
    assert "best_window" in hit["tiers"][1]


def test_a_doctored_snr_of_an_8bit_file_is_not_correct(capsys, monkeypatch):
    from pulsarutils_tpu.io.candidates import CandidateStore

    real = CandidateStore.save_candidate

    def altered(self, root, istart, iend, info, table, *a, **kw):
        table._cols["snr"] = table._cols["snr"] * (1 + 1e-3)
        return real(self, root, istart, iend, info, table, *a, **kw)

    monkeypatch.setattr(CandidateStore, "save_candidate", altered)
    rc, line, out = _last_line(capsys, [
        "--workload", REHEARSAL, "--seed", "3400000307", "--seconds", "1",
        "--trace", "0", "--rehearsal"])
    assert line["correct"] is False
    assert any("snr_rel_gap_rms" in ln and "FAILED" in ln for ln in out)


def test_the_meertrap_file_states_the_programs_tiers():
    """In no cell (the v5e compiler refuses the file's tier 0, PERF.md
    section 7): the tier table the file writes down is the reference's
    rule and the program's planner."""
    from pulsarutils_tpu.ops.plan import dm_tier_plan

    from chipbench import dispersion, reference_boxcar, reference_tiered

    name = "meertrap_lband_8bit"
    cfg = _load("configs", name)
    assert cfg["name"] == name and len(cfg["source"]) <= 200
    assert (cfg["nchans"], cfg["nbits"], cfg["chunk_samples"]) == (
        4096, 8, 1 << 17)
    fb, bw = dispersion.band_edges(cfg["fch1_mhz"], cfg["foff_mhz"], 4096)
    assert (fb, bw) == (856.0, 856.0)
    ours = reference_tiered.tier_table(cfg["dmmin"], cfg["dmmax"], fb, bw,
                                       cfg["tsamp_s"], cfg["foff_mhz"])
    theirs = dm_tier_plan(4096, cfg["dmmin"], cfg["dmmax"], fb, bw,
                          cfg["tsamp_s"], abs(cfg["foff_mhz"]),
                          cfg["boxcar_max"])
    table = cfg["tiers"]["table"]
    assert [t["downsample"] for t in table] == [
        t["factor"] for t in ours] == [t.downsample for t in theirs]
    assert table[0]["downsample"] == 1
    assert [t["trials"] for t in table] == [len(t["dms"]) for t in ours] \
        == [len(t.trial_dms) for t in theirs]
    assert cfg["tiers"]["trials"] == sum(t["trials"] for t in table)
    assert [t["windows"] for t in table] == [len(t.windows) for t in theirs] \
        == [len(reference_boxcar.ladder(cfg["boxcar_max"], t["downsample"]))
            for t in table]
    assert cfg["tiers"]["windows"] == sum(t["windows"] for t in table)
    # a hop of the traffic's file holds a whole track at dmmax
    tables = generate.LevelTables(16.0, 8)
    generate.draw_pulses(cfg, _load("traffic", "backlog_sparse_8bit"), 1,
                         tables)
