"""The benchmark's own tests: run by hand (``python -m pytest
chipbench/tests -q``, CPU), not part of the repository's tier-1 suite.

They hold the yardstick still: the generator is deterministic and its
tracks exact, the reference equals the program's NumPy backend on a chunk
small enough to run it, each reader reads a recorded budget, the trace
reduction reads a recorded trace, the whole command prints a well-formed
last line under ``--rehearsal``, the bfloat16 control fails the limit, and a
run whose timed path is broken underneath comes out not correct.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import (dispersion, generate, kernel_counts,  # noqa: E402
                       reference, trace_reduce)
from chipbench import run as harness  # noqa: E402

DATA = os.path.join(HERE, "data")
CELL = "tiny_cpu_rehearsal.backlog_sparse"


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    return _load("configs", "tiny_cpu_rehearsal"), \
        _load("traffic", "backlog_sparse")


def _levels(path, nchan):
    """(nsamples, nchan) levels in ascending band order."""
    packed_T, hdr = reference.read_packed(path)
    lv = np.stack([reference._file_channel(packed_T, hdr["nbits"], fc)
                   for fc in range(nchan)])
    return (lv[::-1] if hdr["foff"] < 0 else lv).T


def test_generator_is_deterministic(tiny, tmp_path):
    cfg, traffic = tiny
    traffic = {k: v for k, v in traffic.items() if k != "hit_seed"}
    a, b, c = (str(tmp_path / n) for n in "abc")
    ia = generate.generate(a, cfg, traffic, 2**31 + 11, threads=1)
    ib = generate.generate(b, cfg, traffic, 2**31 + 11, threads=4)
    generate.generate(c, cfg, traffic, 2**31 + 12)
    assert open(a, "rb").read() == open(b, "rb").read()
    assert open(a, "rb").read() != open(c, "rb").read()
    assert ia["pulses"] == ib["pulses"]
    pulse = ia["pulses"][0]
    hop = cfg["chunk_samples"] // 2
    assert pulse["sample"] // hop == 3  # whole track in the last hop
    assert ia["nsamples"] == 4 * hop


def test_a_hit_seed_gives_every_seed_the_same_pulse_chunk(tiny, tmp_path,
                                                        monkeypatch):
    """Where a traffic file names a ``hit_seed`` the pulse and the hops its
    chunk covers are one seed's for every run, the other hops the run's;
    a run whose seed is the hit seed writes that seed's plain file."""
    cfg, traffic = tiny
    assert traffic["pulse_hops"] == [3]
    monkeypatch.setattr(generate, "BLOCK", 1 << 11)  # four blocks a hop
    hit = traffic["hit_seed"]
    plain = {k: v for k, v in traffic.items() if k != "hit_seed"}
    hop = cfg["chunk_samples"] // 2
    head = len(generate.sigproc_header(cfg))
    files, infos = {}, {}
    for name, tr, seed in (("a", traffic, 2**31 + 11),
                           ("b", traffic, 2**31 + 12),
                           ("h", traffic, hit), ("p", plain, hit)):
        path = str(tmp_path / name)
        infos[name] = generate.generate(path, cfg, tr, seed)
        files[name] = np.frombuffer(open(path, "rb").read()[head:],
                                    np.uint8).reshape(4 * hop, -1)
    assert generate.hit_hops(traffic) == [2, 3]
    assert infos["a"]["pulses"] == infos["b"]["pulses"] == \
        infos["p"]["pulses"]
    assert infos["a"]["hit_seed"] == hit and infos["p"]["hit_seed"] == hit
    assert np.array_equal(files["h"], files["p"])
    for h in range(4):
        same = np.array_equal(files["a"][h * hop:(h + 1) * hop],
                              files["b"][h * hop:(h + 1) * hop])
        assert same == (h in (2, 3))
        assert np.array_equal(files["a"][h * hop:(h + 1) * hop],
                              files["p"][h * hop:(h + 1) * hop]) == same
    assert generate.hit_seed(plain, 9) == 9


def test_generator_tracks_are_exact(tiny, tmp_path):
    cfg, traffic = tiny
    # 1,024 channels: neighbouring channels then share delays, and bytes
    cfg = dict(cfg, nchans=1024, fch1_mhz=1399.90234375,
               foff_mhz=-0.1953125)
    quiet = dict(traffic, noise_sd_levels=0.2, noise_mean_levels=1.0,
                 hot_channels=[], comb=None, pulse_snr=[1e4, 1e4],
                 pulse_widths=[2])
    path = str(tmp_path / "q.fil")
    info = generate.generate(path, cfg, quiet, 5)
    lv = _levels(path, cfg["nchans"])
    fb, bw = dispersion.band_edges(cfg["fch1_mhz"], cfg["foff_mhz"],
                                   cfg["nchans"])
    pulse = info["pulses"][0]
    sh = dispersion.channel_shifts(pulse["dm"], cfg["nchans"], fb, bw,
                                   cfg["tsamp_s"])
    want = np.zeros_like(lv, dtype=bool)
    for k in range(pulse["width"]):
        want[pulse["sample"] + sh + k, np.arange(cfg["nchans"])] = True
    # on the track every sample saturates; off it none can reach level 3
    assert np.array_equal(lv == 3, want)
    # neighbouring channels with equal delays share a packed byte
    assert (np.diff(sh) == 0).any()


def test_dispersion_equals_the_programs_plan():
    from pulsarutils_tpu.ops import plan

    for dm0, dm1, fb, bw, ts in ((300, 400, 1200., 200., 5e-4),
                                 (0, 52, 1182., 400., 64e-6)):
        ours = dispersion.trial_dms(dm0, dm1, fb, bw, ts)
        theirs = plan.dedispersion_plan(1024, dm0, dm1, fb, bw, ts)
        assert np.array_equal(ours, theirs)
        assert np.array_equal(
            dispersion.channel_shifts(ours, 1024, fb, bw, ts),
            plan.dedispersion_shifts_batch(theirs, 1024, fb, bw, ts))


@pytest.mark.parametrize("zero_dm", [False, True])
def test_reference_equals_numpy_backend(tiny, tmp_path, zero_dm):
    from pulsarutils_tpu.cli import search_main

    cfg, traffic = tiny
    cfg = dict(cfg, clean={"zero_dm": zero_dm})
    path = str(tmp_path / "f.fil")
    info = generate.generate(path, cfg, traffic, 3000000007)
    hop = info["hop"]
    out = str(tmp_path / "out")
    rc = search_main.main(
        [path, "--dmmin", "300", "--dmmax", "400", "--chunk-length",
         repr(hop * cfg["tsamp_s"]), "--output-dir", out, "--plots", "none",
         "--backend", "numpy", "--snr-threshold", "8"]
        + (["--zero-dm"] if zero_dm else []))
    assert rc == 0
    rows, _, done, _ = harness.persisted(out)
    got = rows[2 * hop][1]
    ref = reference.best_row(path, cfg, 2 * hop, info["pulses"][0]["dm"])
    assert (got["DM"], got["peak"], got["rebin"]) == (
        ref["DM"], ref["peak"], ref["rebin"])
    assert abs(got["snr"] - ref["snr"]) <= 1e-9 * ref["snr"]
    with open(path + ".badchans") as f:
        bad = [i for i, v in enumerate(f.read().split()) if int(float(v))]
    assert bad == ref["bad_channels_file_order"]


def test_readers_on_a_recorded_budget():
    import importlib

    with open(os.path.join(DATA, "budget_pass.json")) as f:
        budget = json.load(f)
    p = {"budget": budget, "wall_s": budget["wall_s"] + 0.25, "spans":
         [(1.0, 3.5, "badchans")], "registry_delta":
         {"putpu_certified_chunks_total": 2}}
    ctx = {"cold": p, "passes": [p, p], "trace": None}
    chunks = budget["per_chunk"]

    def read(name):
        spec = _load("layer_metrics", name)
        mod = importlib.import_module("chipbench.readers."
                                      + spec["source"]["kind"])
        return mod.read(spec["source"], ctx)

    assert read("trips_per_chunk") == pytest.approx(
        sum(c["counters"]["dispatches"] + c["counters"]["readbacks"]
            for c in chunks) / 3)
    assert read("clean_ms_per_chunk") == pytest.approx(
        1e3 * sum(c["buckets"]["clean"] for c in chunks) / 3)
    assert read("coarse_ms_per_chunk") == pytest.approx(
        1e3 * sum(c["buckets"]["search/coarse"]
                  + c["buckets"]["search/coarse_readback"]
                  for c in chunks) / 3)
    hit = [c for c in chunks if "search/readback" in c["buckets"]]
    assert len(hit) == 1
    assert read("rescore_ms_per_hit_chunk") == pytest.approx(
        1e3 * (hit[0]["buckets"]["search/dispatch"]
               + hit[0]["buckets"]["search/readback"]))
    assert read("persist_ms_per_chunk") == pytest.approx(
        1e3 * budget["async_s"]["persist"] / 3)
    assert read("certified_chunk_pct") == pytest.approx(200 / 3)
    assert read("prescan_s") == pytest.approx(2.5)
    assert read("cold_pass_s") == pytest.approx(p["wall_s"])
    assert read("pass_overhead_ms") == pytest.approx(
        1e3 * (p["wall_s"] - sum(c["wall_s"] for c in chunks)))
    walls = [c["wall_s"] for c in chunks]
    assert read("first_chunk_extra_ms") == pytest.approx(
        1e3 * (walls[0] - np.median(walls[1:])))
    assert read("device_idle_pct") is None  # nothing to read: left out
    assert read("fdmt_roofline") is None


def test_trace_reduction_on_a_recorded_trace():
    with open(os.path.join(DATA, "cpu_small.json")) as f:
        sync = json.load(f)["sync_perf_counter_s"]
    prof = trace_reduce.load(os.path.join(DATA, "cpu_small.xplane.pb"))
    # a CPU trace has no device plane: the default reduction finds nothing
    assert trace_reduce.reduce_trace(prof)["busy_s"] == 0.0
    off = trace_reduce.sync_offset_ns(prof, sync)
    assert off is not None
    spans = [(sync, sync + 1.0, "outer"), (sync, sync + 0.0005, "inner")]
    red = trace_reduce.reduce_trace(
        prof, (sync, sync + 0.02), sync, spans, plane_re=r"^/host:CPU$",
        line_re=r"XLAPjRtCpuClient")
    assert red["clock_tied"] and red["window_s"] == pytest.approx(0.02)
    assert 0 < red["busy_s"] < red["window_s"]
    names = [n for n, _ in red["device_ops"]]
    assert any(n.startswith("dot_general") for n in names)
    gap_s = sum(v for _, v in red["idle_gaps"])
    assert gap_s == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert {n for n, _ in red["idle_gaps"]} <= {"outer", "inner"}
    secs, hit = trace_reduce.kernel_seconds(red["op_seconds"], "^dot_general")
    assert secs > 0 and all(h.startswith("dot_general") for h in hit)
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)], 0.5, 5.5) == \
        [[0.5, 3], [5, 5.5]]
    assert trace_reduce.gaps([[1, 2], [4, 5]], 0, 6) == \
        [(0, 1), (2, 4), (5, 6)]


def test_trace_reduction_on_a_recorded_tpu_trace(tmp_path):
    """Three passes of the HTRU cell on one v5e (PR 24's first traced
    run): one device plane, operations named <program>/<operation>."""
    import gzip
    import shutil

    path = str(tmp_path / "stretch.xplane.pb")
    with gzip.open(os.path.join(DATA, "tpu_htru_stretch.xplane.pb.gz")) as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    prof = trace_reduce.load(path)
    red = trace_reduce.reduce_trace(prof)
    assert red["planes"] == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(2.974307784, rel=1e-9)
    assert red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0] == "jit_fn/fn.2"
    fdmt_s, names = trace_reduce.kernel_seconds(red["op_seconds"], "^jit_fn/")
    assert fdmt_s == pytest.approx(2.263706851, rel=1e-9)
    assert {n.split("/")[0] for n in red["op_seconds"]} >= {
        "jit_fn", "jit_run", "jit__unpack_clean"}
    # the harness's marker ties the clocks: put it at perf_counter 100 s
    off = trace_reduce.sync_offset_ns(prof, 100.0)
    assert off is not None
    first = min(s for evs in trace_reduce.op_events(prof).values()
                for s, _, _ in evs)
    t_first = (first - off) / 1e9
    tied = trace_reduce.reduce_trace(
        prof, (t_first - 1.0, t_first + 7.0), 100.0,
        [(t_first - 1.0, t_first + 7.0, "outer"),
         (t_first - 1.0, t_first - 0.001, "before")])
    assert tied["clock_tied"] and tied["window_s"] == pytest.approx(8.0)
    assert tied["busy_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    gaps = dict(tied["idle_gaps"])
    assert gaps["before"] == pytest.approx(1.0, abs=1e-6)
    assert sum(gaps.values()) == pytest.approx(8.0 - tied["busy_s"], rel=1e-6)
    assert trace_reduce.short_name(
        "%fn.2 = f32[8]{0} custom-call(f32[8]{0} %x)") == "fn.2"
    assert trace_reduce.short_name("jit_fn(8996191183167308178)") == "jit_fn"


def test_fdmt_counts():
    c = kernel_counts.fdmt_counts(1024, 1 << 20, 300.0, 400.0, 1200.0, 200.0,
                                  5e-4)
    assert c["bytes"] == 1024 * (1 << 20) * 4
    assert c["rows_out"] == 155
    least, roof = kernel_counts.roofline_seconds(
        c, {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12})
    assert roof == "memory" and least == pytest.approx(c["bytes"] / 819e9)


def _last_line(capsys, argv):
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_rehearsal_prints_a_well_formed_last_line(capsys):
    rc, line, out = _last_line(capsys, [
        "--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "1",
        "--trace", "0", "--rehearsal", "--control", "1"])
    assert rc != 0  # a rehearsal never passes
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"sky_s_per_s", "chunk_wall_p90_ms",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"  # named, never a TPU's
    gaps = [ln for ln in out if ln.startswith("control ")]
    control_gap = float(gaps[0].split("snr_rel_gap_rms=")[1].split()[0])
    limit = float(gaps[0].split("limit=")[1])
    assert control_gap > limit  # the bfloat16 control is not correct


def test_traced_rehearsal_reports_layer_metrics(capsys, monkeypatch):
    # a metric that lists its cells is read in those alone, so the tiny
    # geometry runs under a real cell's name
    real = harness.resolve_cell
    monkeypatch.setattr(
        harness, "resolve_cell",
        lambda workload, rehearsal: real(workload, rehearsal)[:2]
        + real(CELL, True)[2:])
    rc, line, _ = _last_line(capsys, [
        "--workload", "rehearsal_1024ch_2bit.backlog_sparse", "--seed", "7",
        "--seconds", "1", "--trace", "1", "--rehearsal"])
    assert rc != 0 and line["correct"] is True
    assert line["metrics"]["cold_pass_s"]["value"] > \
        line["metrics"]["prescan_s"]["value"] > 0
    assert {"prescan_s", "trips_per_chunk", "pass_overhead_ms",
            "coarse_ms_per_chunk", "certified_chunk_pct"} <= set(
                line["metrics"])
    assert "device_idle_pct" not in line["metrics"]  # no device trace here
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """An answer altered where it is produced: every persisted S/N is 0.1 %
    off.  Cold pass and window agree with each other, so only the
    comparison with the plain reference can see it."""
    from pulsarutils_tpu.io.candidates import CandidateStore

    real = CandidateStore.save_candidate

    def altered(self, root, istart, iend, info, table, *a, **kw):
        table._cols["snr"] = table._cols["snr"] * (1 + 1e-3)
        return real(self, root, istart, iend, info, table, *a, **kw)

    monkeypatch.setattr(CandidateStore, "save_candidate", altered)
    rc, line, out = _last_line(capsys, [
        "--workload", CELL, "--seed", "11", "--seconds", "1", "--trace", "0",
        "--rehearsal"])
    assert line["correct"] is False
    assert any("snr_rel_gap_rms" in ln and "FAILED" in ln for ln in out)


def test_no_accelerator_no_result(capsys):
    rc = harness.main(["--workload", "rehearsal_1024ch_2bit.backlog_sparse",
                       "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc != 0 and not out[-1].startswith("{")
