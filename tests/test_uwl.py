"""Parkes' ultra-wideband receiver as a deployment (ISSUE 44): 704-4,032
MHz in 3,328 channels of 2 bits, ``chipbench/configs/parkes_uwl_2bit.json``.
Three parts of the program no other cell reaches: a band the FDMT pads to
a power of two, sweeps whose fused head declines, and a 2-bit chunk
cleaned and rescored in time tiles.  Here on the CPU: the tiny rehearsal
(``tiny_cpu_uwl``: the same band in 52 channels) against the plain
reference, tiled and untiled; the transform against the sum along its
tracks at 52 and 3,328 channels; the 2-bit tile clean at a frame of 832
bytes; the planner's and the head's verdicts at the configuration's own
numbers; the counts and the manifest's entries."""

import ast
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import dispersion, generate, wideband_counts  # noqa: E402
from chipbench import run as harness  # noqa: E402

CELL = "parkes_uwl_2bit.backlog_sparse_uwl"
REHEARSAL = "tiny_cpu_uwl.backlog_sparse_uwl"
UWL = (704.0, 3328.0)          # fbottom, bandwidth (MHz)
V5E_BYTES = 16909336064        # bytes_limit a v5e reported (PR 44's runs)


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


def _search_kw(cfg):
    return dict(chunk_length=cfg["chunk_samples"] // 2 * cfg["tsamp_s"],
                dmmin=cfg["dmmin"], dmmax=cfg["dmmax"], backend="jax",
                kernel="hybrid", snr_threshold="certifiable", zero_dm=True,
                dm_tiers="smearing")


def _counter(name, **labels):
    from pulsarutils_tpu.obs import metrics

    return metrics.counter(name, **labels).value


# -- (a) the rehearsal, tiled and untiled ----------------------------------

@pytest.mark.parametrize("tiles", [1, 2], ids=["untiled", "tiled"])
def test_rehearsal_is_the_references_row(capsys, tmp_path, force_time_tiles,
                                         tiles):
    """``PUsearchfrb`` on a ``tiny_cpu_uwl`` file as ``chipbench/run.py``
    drives it: the persisted best row is ``reference_tiered``'s (tier,
    trial DM, peak sample, boxcar, S/N within the file's limit, flagged
    exact), the bfloat16 control is not; with a device so small that the
    native tier, which holds the pulse, is swept and rescored in two time
    tiles from the 2-bit bytes, the same."""
    cfg = _load("configs", "tiny_cpu_uwl")
    traffic = _load("traffic", "backlog_sparse_uwl")
    seed = 3400002044
    moved = ("putpu_time_tiles_total", "putpu_fdmt_pad_channels_total",
             "putpu_host_fallbacks_total")
    if tiles > 1:
        path = str(tmp_path / "plan.fil")
        generate.generate(path, cfg, traffic, seed)
        plan = force_time_tiles(path, _search_kw(cfg), tiles)
        assert [t.tiles for t in plan] == [2, 1]
    before = [_counter(n) for n in moved]
    rc = harness.main(["--workload", REHEARSAL, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0", "--rehearsal",
                       "--control", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc != 0  # a rehearsal never exits 0
    assert line["correct"] is True and line["control_correct"] is False
    assert all(c["ok"] for name, c in line["compared"].items()
               if name != "snr_rel_gap_rms.control")
    ref = next(ln for ln in out if ln.startswith("reference chipbench."))
    assert "reference_tiered" in ref
    # the pulse (DM 8.96-9.46 of 0-24.9) is the native tier's, the first
    # of two: its row counts from 0
    best = json.loads(ref[ref.index("): {") + 3:])
    row = ast.literal_eval(next(
        ln for ln in out if ln.startswith("program, chunk "
                                          )).split(": ", 1)[1])[1]
    assert best["row"] < 203 and row["DM"] == best["DM"]
    assert (row["peak"], row["rebin"], row["exact"]) == (
        best["peak"], best["rebin"], True)
    swept, padded, fallbacks = (_counter(n) - b
                                for n, b in zip(moved, before))
    sweeps = 3 if tiles > 1 else 2     # a chunk: tier 0's tiles + tier 1
    assert padded % (12 * sweeps) == 0 and padded >= 12 * sweeps * 6
    assert (swept > 0) == (tiles > 1) and fallbacks == 0


def test_tiled_and_untiled_tables_agree(tmp_path, force_time_tiles):
    """The two searches of one ``tiny_cpu_uwl`` file agree to float32
    summation order: trial DMs, peaks, boxcars and exact flags equal, S/N
    to 2e-6 (``tests/test_time_tiles.py``'s measure)."""
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    cfg = _load("configs", "tiny_cpu_uwl")
    path = str(tmp_path / "f.fil")
    generate.generate(path, cfg, _load("traffic", "backlog_sparse_uwl"), 44)
    kw = dict(_search_kw(cfg), make_plots=False, resume=False)
    whole, _ = search_by_chunks(path, output_dir=str(tmp_path / "a"), **kw)
    force_time_tiles(path, kw, 2)
    tiled, _ = search_by_chunks(path, output_dir=str(tmp_path / "b"), **kw)
    assert whole and [h[:2] for h in whole] == [h[:2] for h in tiled]
    for (_, _, _, t0), (_, _, _, t1) in zip(whole, tiled):
        for name in ("DM", "rebin", "peak", "exact", "downsample"):
            assert np.array_equal(t0[name], t1[name]), name
        np.testing.assert_allclose(t1["snr"], t0["snr"], rtol=2e-6,
                                   atol=1e-6)


# -- (b) the transform on a padded band, with and without a head -----------

def _track_delays(plan):
    """``(rows, nchan_padded)`` sample delays of every final row's track,
    -1 where a row holds no such channel: the plan's merge tables walked
    on the host (``tests/test_fdmt.py:brute_force_tracks``, in arrays)."""
    nch2 = plan.nchan_padded
    delays = np.full((nch2, nch2), -1, np.int32)
    delays[np.arange(nch2), np.arange(nch2)] = 0
    for it in plan.iterations:
        low, high = delays[it["idx_low"]], delays[it["idx_high"]]
        shift_high = (it["shift_high"] if it["shift_high"] is not None
                      else np.zeros(len(it["shift"]), np.int32))
        delays = np.where(low >= 0, low + it["shift"][:, None],
                          np.where(high >= 0, high + shift_high[:, None],
                                   -1)).astype(np.int32)
    return delays


@pytest.mark.parametrize("nchan,t,hi", [(52, 512, 40), (3328, 256, 12)])
def test_the_transform_sums_its_tracks_and_the_pad_adds_nothing(nchan, t,
                                                                hi):
    """Over Parkes' band in 52 and in 3,328 channels (padded to 64 and
    4,096) every row of the transform is the direct sum of one sample a
    *real* channel along the row's track; the channels the pad adds are
    on every track and add nothing."""
    from pulsarutils_tpu.ops.fdmt import (fdmt_plan, fdmt_transform,
                                          pad_channels)

    data = np.random.default_rng(nchan).normal(
        0, 1, (nchan, t)).astype(np.float32)
    plan = fdmt_plan(nchan, *UWL, hi)
    assert plan.nchan_padded == nchan + pad_channels(nchan)
    assert pad_channels(nchan) == {52: 12, 3328: 768}[nchan]
    delays = _track_delays(plan)[:hi + 1]
    assert (delays >= 0).all()  # a track crosses the pad's channels too
    cols = (np.arange(t)[None, None, :] + delays[:, :nchan, None]) % t
    want = np.take_along_axis(
        np.broadcast_to(data.astype(np.float64), (hi + 1, nchan, t)), cols,
        axis=2).sum(axis=1)
    got = np.asarray(fdmt_transform(data, hi, *UWL))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4)


def test_the_declined_path_is_the_heads_where_both_build():
    """At 3,328 channels and band delays 0-24 the head's plan fits (32
    groups, a 53-sample halo), so the tests' seam builds both sweeps: the
    per-level merges a declined head leaves the sweep to give the head's
    floats bit for bit."""
    from pulsarutils_tpu.ops import fdmt

    nchan, t, hi = 3328, 2048, 24
    assert fdmt.head_active(nchan, *UWL, hi, 0, t)
    data = np.random.default_rng(3).standard_normal(
        (nchan, t)).astype(np.float32)
    head, per_level = (np.asarray(fdmt._build_transform(
        nchan, *UWL, hi, t, fdmt._pick_fdmt_tile(t), False, True, n_lo=0,
        t_orig=t, use_head=use_head)(data)) for use_head in (True, False))
    assert head.shape == (hi + 1, t)
    assert np.array_equal(head, per_level)


def test_a_headless_sweeps_levels_are_one_build_span():
    """``build/levels:fdmt_merge`` wraps the walk of the per-level merges
    of a sweep that runs no head, inside the tracing of its program and
    around the levels' own spans; a sweep with a head records none."""
    import jax

    from pulsarutils_tpu.obs import trace
    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.utils import logging_utils

    logging_utils._install_compile_listener()
    nchan, t, hi = 256, 2048, 180

    def names(use_head):
        tracer = trace.start_tracing()
        try:
            # traced, not lowered: interpret-mode kernels are slow to build
            jax.jit(fdmt._transform_fn(
                nchan, 1200.0, 200.0, hi, t, fdmt._pick_fdmt_tile(t), True,
                True, n_lo=40, t_orig=t, use_head=use_head)).trace(
                jax.ShapeDtypeStruct((nchan, t), np.float32))
        finally:
            trace.stop_tracing()
        return [e for e in tracer.events_since(0)[0] if e.get("ph") == "X"]

    events = names(False)
    (levels,) = [e for e in events if e["name"] == "build/levels:fdmt_merge"]
    assert levels["args"]["levels"] == 6 and levels["args"]["pad"] == 0
    merges = [e for e in events if e["name"] == "build/kernel:fdmt_merge"]
    assert len(merges) == 6 and all(
        levels["ts"] <= e["ts"] and e["ts"] + e["dur"]
        <= levels["ts"] + levels["dur"] for e in merges)
    assert not [e for e in names(True)
                if e["name"] == "build/levels:fdmt_merge"]


# -- (c) the 2-bit tile clean at a frame no power of two -------------------

def test_the_2bit_tile_clean_is_the_whole_chunks():
    """3,328 channels of 2 bits are 832 bytes a frame.  Every tile of a
    chunk cleaned from its packed bytes (``jit_tile_clean`` on
    ``jit_chunk_stats``' moments) holds, sample for sample, what the
    whole-chunk clean (``jit_unpack_clean``) holds there, the halo read
    over the chunk's end included."""
    import jax.numpy as jnp

    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.pipeline.search_pipeline import (
        _device_clean_program,
    )
    from pulsarutils_tpu.pipeline.time_tiles import (TiledTierArray,
                                                     chunk_stats_program,
                                                     wrap_rows_program)

    nchan, t, tiles, halo = 3328, 2048, 4, 256
    rng = np.random.default_rng(832)
    raw = jnp.asarray(rng.integers(0, 256, (t, nchan // 4), dtype=np.uint8))
    assert raw.shape[1] == 832
    mask = jnp.zeros(nchan, bool).at[np.array([975, 2278])].set(True)
    unpack = (device_unpack_block, 2, nchan, True)
    whole = np.asarray(_device_clean_program(
        unpack, (), (False, True, False, 1))(raw, mask))
    assert whole.shape == (nchan, t)
    stats = chunk_stats_program(unpack, t)(raw, mask)
    source = TiledTierArray(wrap_rows_program()(raw), t, stats, mask, unpack,
                            True, (), tiles, halo)
    own = t // tiles
    for i in range(tiles):
        cols = np.arange(i * own, (i + 1) * own + halo) % t
        np.testing.assert_allclose(np.asarray(source.tile(i)),
                                   whole[:, cols], rtol=2e-6, atol=2e-6)


# -- (d) the plan and the verdicts the configuration file states -----------

def _uwl_tiers():
    from pulsarutils_tpu.ops.plan import dm_tier_plan

    cfg = _load("configs", "parkes_uwl_2bit")
    fbottom, bandwidth = dispersion.band_edges(
        cfg["fch1_mhz"], cfg["foff_mhz"], cfg["nchans"])
    assert (fbottom, bandwidth) == UWL
    return cfg, dm_tier_plan(cfg["nchans"], cfg["dmmin"], cfg["dmmax"],
                             fbottom, bandwidth, cfg["tsamp_s"],
                             abs(cfg["foff_mhz"]))


def test_the_tile_plan_is_the_configuration_files():
    """``plan_time_tiles`` at the configuration's numbers on a v5e's
    memory (pure host arithmetic): the tier table and the tile plan the
    file states, to the letter."""
    from pulsarutils_tpu.parallel.stream import plan_time_tiles

    cfg, tiers = _uwl_tiers()
    table = cfg["tiers"]["table"]
    assert [(t.downsample, len(t.trial_dms)) for t in tiers] == [
        (row["downsample"], row["trials"]) for row in table]
    assert sum(len(t.trial_dms) for t in tiers) == cfg["tiers"]["trials"] \
        == 19478
    for tier, row in zip(tiers, table):
        assert round(tier.dm_lo, 4) == row["dm_lo"]
        assert round(tier.dm_hi, 4) == row["dm_hi"]
    # the file's bytes are PR 44's reading; since PR 49 the survey states
    # the 2^15 frames a tiled chunk carries twice as resident as well
    frame = cfg["nchans"] * cfg["nbits"] // 8
    wrap = (1 << 15) * frame
    plan = plan_time_tiles(
        cfg["nchans"], cfg["chunk_samples"], *UWL,
        [(t.downsample, t.sample_time, t.trial_dms, t.windows)
         for t in tiers], V5E_BYTES * 15 // 16,
        2 * cfg["chunk_samples"] * frame + wrap)
    assert [{"tier": k, "tiles": t.tiles, "own": t.own, "halo": t.halo,
             "keep": t.keep, "reckoned_bytes": t.bytes - wrap}
            for k, t in enumerate(plan)] == cfg["tile_plan"]["tiers"]
    assert [t.tiles for t in plan] == [2, 1, 1]
    assert (plan[0].own, plan[0].halo) == (65536, 16384)


def test_every_sweeps_head_declines_for_the_reason_the_file_states(
        monkeypatch):
    """``_head_verdict`` at each tier's plan and time axis: the reason
    (``halo`` judged at the slice that would run; ``shift`` where the
    halo fits that slice) and the SMEM the file states; and the counters
    a chunk's four sweeps move are exactly the file's (declines by
    reason, padded channels)."""
    import jax

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops import fdmt_resident as fr
    from pulsarutils_tpu.pipeline.search_pipeline import _count_head_tiles

    cfg, tiers = _uwl_tiers()
    stated = cfg["head"]["sweeps"]
    axes = [s["time_axis"] for s in stated]
    assert axes == [65536 + 16384, 65536, 32768]
    budget = fr.head_vmem_limit() - fr._VMEM_HEADROOM
    for tier, t, want in zip(tiers, axes, stated):
        _, n_lo, n_hi = fdmt.fdmt_trial_dms(
            cfg["nchans"], tier.dm_lo, tier.dm_hi, *UWL, tier.sample_time)
        assert f"{n_lo}-{n_hi}" == want["band_delays"]
        choice, reason, smem = fdmt._head_verdict(cfg["nchans"], *UWL, n_hi,
                                                  n_lo, t)
        assert choice is None
        assert (reason, smem) == (want["reason"], want["smem_bytes"])
        hp = fr._head_plan_cached(cfg["nchans"], *UWL, n_hi, n_lo,
                                  fr.HEAD_LEVELS)
        assert hp.halo == want["halo"]
        # where no slice holds the two buffers the slice that would run
        # is the floor; and whatever `halo` says, `shift` would decline
        over = fr.head_scratch_bytes(hp, fr.HEAD_T_SLICE) > budget
        assert over == want["scratch_over_vmem"]
        assert (fr.pick_head_t_slice(hp, t) == fr.HEAD_T_SLICE) == over
        assert max(hp.max_shift_per_level) == want["max_level_shift"] >= 256
    names = ("putpu_fdmt_head_declined_total",
             "putpu_fdmt_head_declined_total",
             "putpu_fdmt_pad_channels_total", "putpu_fdmt_head_tiles_total")
    labels = ({"reason": "halo"}, {"reason": "shift"}, {}, {})
    before = [_counter(n, **kw) for n, kw in zip(names, labels)]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for tier, t, tiles in zip(tiers, axes, (2, 1, 1)):
        for _ in range(tiles):
            assert _count_head_tiles(
                {}, ("jax", "hybrid", None), (cfg["nchans"], t), tier.dm_lo,
                tier.dm_hi, *UWL, tier.sample_time) == 0
    by_halo, by_shift, padded, head_tiles = (
        _counter(n, **kw) - b for n, kw, b in zip(names, labels, before))
    assert {"halo": by_halo, "shift": by_shift} \
        == cfg["head"]["declined_by_reason"] == {"halo": 3, "shift": 1}
    assert by_halo + by_shift == cfg["head"]["declined_sweeps_per_chunk"]
    assert padded == cfg["head"]["pad_channels_per_chunk"] == 4 * 768
    assert head_tiles == 0


@pytest.mark.parametrize("halo,reason", [(2000, None), (30000, "halo")])
def test_halo_is_judged_at_the_slice_that_would_run(monkeypatch, halo,
                                                    reason):
    """ROADMAP C5b: a halo of 2,000 samples is past two thirds of the
    2,048-sample floor and well inside the 16,384-32,768 the head runs
    at on HTRU's tier 0; the verdict follows the slice, not the floor."""
    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops import fdmt_resident as fr

    hp = fr.HeadPlan(fdmt.fdmt_plan(1024, 1182.0, 400.0, 1068, 0))
    assert hp.halo <= (2 * fr.HEAD_T_SLICE) // 3
    hp.halo = halo
    monkeypatch.setattr(fr, "_head_plan_cached", lambda *a: hp)
    choice, why, _ = fdmt._head_verdict(1024, 1182.0, 400.0, 1068, 0,
                                        1 << 19)
    assert why == reason
    if reason is None:
        assert choice[1] >= 16384 and not fr.head_supported(
            1024, 10, 1 << 19, halo=halo)


# -- (e) the counts, the reader and the manifest ----------------------------

def test_wideband_counts_by_hand():
    shapes = dict(nchan=3328, nsamples=1 << 17, dmmin=0.0, dmmax=204.8,
                  fbottom=704.0, bandwidth=3328.0, tsamp=6.4e-05)
    cleaned = 3328 * 131072 * 4
    assert cleaned == 1_744_830_464
    assert wideband_counts.sweep_counts(**shapes) == {
        "bytes": cleaned, "flops": 0}
    # a quarter byte a sample read, four written
    assert wideband_counts.tile_clean_counts(**shapes) == {
        "bytes": 109_051_904 + cleaned, "flops": 0}
    assert wideband_counts.rescore_counts(**shapes, rows=314) == {
        "bytes": cleaned + 314 * 131072 * 4, "flops": 0}
    assert wideband_counts.rescore_counts(**shapes)["bytes"] == cleaned


def test_the_rows_roofline_reader():
    """``trace_rows_roofline``: the bytes of the rows each pass asked for
    at the memory roof over the kernel's device time; nothing where the
    program keeps no such counter, asked for no row or ran no such
    operation."""
    from chipbench.readers import trace_rows_roofline as reader

    spec = _load("layer_metrics", "wideband_rescore_roofline")["source"]
    assert spec["kind"] == "trace_rows_roofline"
    shapes = dict(nchan=3328, nsamples=1 << 17, dmmin=0.0, dmmax=204.8,
                  fbottom=704.0, bandwidth=3328.0, tsamp=6.4e-05)
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}

    def ctx(rows, ops):
        return {"trace": {"op_seconds": ops}, "shapes": shapes,
                "peaks": peaks, "notes": [],
                "passes": [{"registry_delta": {spec["rows_key"]: r}}
                           for r in rows]}

    ops = {"jit_rescore_tile/dedisperse_rows.1": 1.5,
           "jit_rescore_tile/copy.7": 0.5, "jit_fn/fdmt_merge.10": 9.0}
    least = 2 * (3328 + 314) * 131072 * 4 / 819e9
    assert reader.read(spec, ctx([314, 314], ops)) == pytest.approx(
        100 * least / 2.0)
    assert reader.read(spec, ctx([314, 0], ops)) == pytest.approx(
        100 * least / 2 / 2.0)
    assert reader.read(spec, ctx([0, 0], ops)) is None
    assert reader.read(spec, ctx([314], {"jit_fn/fdmt_merge.10": 9.0})) \
        is None
    assert reader.read(spec, {"trace": None}) is None
    no_counter = ctx([314], ops)
    no_counter["passes"] = [{"registry_delta": {}}]
    assert reader.read(spec, no_counter) is None


NEW_METRICS = {
    "head_declined_sweeps_per_chunk": "putpu_fdmt_head_declined_total",
    "fdmt_pad_kchannels_per_chunk": "putpu_fdmt_pad_channels_total",
    "merge_levels_device_ms_per_chunk": "^jit_fn/fdmt_merge",
    "wideband_sweep_roofline": "wideband_counts:sweep_counts",
    "tile2bit_clean_roofline": "wideband_counts:tile_clean_counts",
    "wideband_rescore_rows_per_pass": "putpu_rescore_rows_total",
    "wideband_rescore_roofline": "wideband_counts:rescore_counts",
}
SHARED_METRICS = [
    "tiers_per_chunk", "tiers_certified_per_chunk", "tier_sweep_ms_per_chunk",
    "tier_rescore_ms_per_hit_chunk", "time_tiles_per_chunk",
    "tile_halo_ksamples_per_chunk", "chunk_stats_device_ms_per_chunk",
    "tile_clean_device_ms_per_chunk", "tiled_sweep_device_ms_per_chunk",
    "tiled_rescore_device_ms_per_pass"]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_reads_what_the_program_emits(name):
    from pulsarutils_tpu.obs.names import KERNEL_NAMES, is_known

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    spec = _load("layer_metrics", name)
    # the per-level merges run after a head too: CHIME's cell joined that
    # list (PR 49); the others are this cell's alone
    assert entry["workloads"][0] == CELL and entry["moves"] == "sky_s_per_s"
    assert (entry["workloads"] == [CELL]
            or name == "merge_levels_device_ms_per_chunk")
    assert (entry["unit"], entry["better"], entry["layer"],
            entry["source"]) == (spec["unit"], spec["better"],
                                 spec["layer"], spec["origin"])
    source = spec["source"]
    reads = NEW_METRICS[name]
    if source["kind"] == "registry_counter":
        assert source["key"] == reads and is_known(reads)
    elif source["kind"] == "trace_kernel_seconds":
        assert source["match"] == reads and "fdmt_merge" in KERNEL_NAMES
    else:
        assert source["counts"] == reads
        module, _, function = reads.partition(":")
        assert module == "wideband_counts" and callable(
            getattr(wideband_counts, function))
        assert entry["unit"] == "%" and name.endswith("_roofline")


def test_manifest_entries_of_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cfg = _load("configs", "parkes_uwl_2bit")
    traffic = _load("traffic", "backlog_sparse_uwl")
    smeared = _load("traffic", "backlog_sparse_smeared")
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == cfg["name"] == "parkes_uwl_2bit"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["dmmax"]
    assert set(cfg["reduced_why"]) == {"dmmax"}
    assert (cfg["nchans"], cfg["nbits"], cfg["tsamp_s"], cfg["fch1_mhz"],
            cfg["foff_mhz"], cfg["chunk_samples"]) == (
        3328, 2, 6.4e-05, 4031.5, -1.0, 131072)
    assert (cfg["dmmin"], cfg["dmmax"]) == (0.0, 204.8)
    assert cfg["cli_flags"] == _load("configs",
                                     "htru_bpsr_fulldm")["cli_flags"]
    assert cfg["reference"] == "reference_tiered"
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("parkes_uwl_2bit", "backlog_sparse_uwl", 1)
    assert len(cell["why"]) <= 200
    # backlog_sparse_smeared's loop and levels to the letter
    assert {k for k in set(traffic) | set(smeared)
            if traffic.get(k) != smeared.get(k)} == {
        "name", "why", "who", "pulse_why", "pulse_widths",
        "pulse_dm_fraction", "hit_seed", "hit_why"}
    assert traffic["hit_seed"] == 3400001011
    assert traffic["pulse_widths"] == [4]
    assert traffic["pulse_dm_fraction"] == [0.36, 0.38]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in SHARED_METRICS:
        assert per_layer[name]["workloads"].count(CELL) == 1
    # the two readers of kernel fdmt_head find nothing where it declines
    others = [w["name"] for w in manifest["workloads"] if w["name"] != CELL]
    for name in ("fdmt_head_device_ms_per_chunk", "cold_head_trace_s"):
        assert per_layer[name]["workloads"] == others
