"""Ask the TPU v5e's compiler — installed here, no chip attached — for
the programs the file-survey path dispatches on the chip at survey width
(1,024 channels x 2^20 samples, DM 300-400; ``tools/survey_rehearsal.py``).

Interpret-mode Pallas skips Mosaic lowering entirely and the CPU suite
never takes the ``jax.default_backend() == "tpu"`` branches, so these
compiles are the only tier-1 guard of what ``chip_smoke.py`` runs: a
kernel Mosaic refuses, or a program that no longer fits 16 GB of HBM,
fails HERE at no chip time.  Nothing runs, so nothing is said about
results or speed.

Rules this file keeps (the on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be described — never at import, in a ``skipif`` or a
``parametrize``; nothing is ``autouse``; no child process (the worker
that describes the topology holds libtpu's lock); the persistent
compile cache is off around the compiles (an entry written for a
described device cannot be read back without one).  One file: a second
would land on another xdist worker and skip in silence.

Two programs are compiled at a reduced time axis because their compile
time, not their memory, is what scales: the unpack+clean program (its
``median`` lowers to a sort: 62 s at 2^20, 10 s at 2^14) and the
four-device fused mesh hybrid (163 s at 2^20, 14 s at 2^14).  Their
full-size compiles were made by hand and are recorded in CHANGES.md
(PR 22).
"""

import re

import numpy as np
import pytest

NCHAN = 1024
T = 1 << 20
T_SMALL = 1 << 14
F0, BW, TSAMP = 1200.0, 200.0, 5e-4
DMMIN, DMMAX = 300.0, 400.0
HBM_BYTES = 15.75 * 2**30  # what the v5e compiler itself budgets


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch, no_compile_cache):
    """Code that asks ``jax.default_backend()`` takes its TPU branch
    (compiled Pallas, fused programs, donation) — steered here, in the
    test, not through an option of the program."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def plan():
    """The smoke's trial grid and offset table, from the program's own
    planners."""
    from pulsarutils_tpu.ops.fdmt import _pick_fdmt_tile, fdmt_trial_dms
    from pulsarutils_tpu.ops.pallas_dedisperse import rebase_offsets
    from pulsarutils_tpu.ops.plan import dedispersion_plan
    from pulsarutils_tpu.ops.search import _offsets_for

    trial_dms = np.asarray(dedispersion_plan(NCHAN, DMMIN, DMMAX, F0, BW,
                                             TSAMP), np.float64)
    offsets = _offsets_for(trial_dms, NCHAN, F0, BW, TSAMP, T)
    _, roll_k, max_off = rebase_offsets(offsets, T)
    _, n_lo, n_hi = fdmt_trial_dms(NCHAN, DMMIN, DMMAX, F0, BW, TSAMP)
    return {"ndm": len(trial_dms), "roll_k": roll_k, "max_off": max_off,
            "n_lo": n_lo, "n_hi": n_hi, "t_tile": _pick_fdmt_tile(T)}


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    """The compile already proves the program alone fits; the number is
    asserted so a creeping temp shows up as a diff, not an OOM."""
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, m


def test_rows_kernel_and_scorer(as_tpu, one_chip, plan):
    """``--kernel auto`` on a TPU: the exact Pallas rows kernel over the
    whole trial grid (``max_off`` from the plan), then the XLA scorer."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.pallas_dedisperse import (
        dedisperse_plane_pallas_traced,
    )
    from pulsarutils_tpu.ops.search import _jitted_scorer

    def sweep(data, offs):
        return dedisperse_plane_pallas_traced(
            data, offs, plan["max_off"], roll_k=plan["roll_k"])

    compiled = jax.jit(sweep).lower(
        _sds((NCHAN, T), jnp.float32, one_chip),
        _sds((plan["ndm"], NCHAN), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    _fits(_jitted_scorer().lower(
        _sds((plan["ndm"], T), jnp.float32, one_chip)).compile())


def test_fdmt_transform_as_resolved_on_tpu(as_tpu, one_chip, plan):
    """The hybrid's coarse stage exactly as ``_search_jax_fdmt`` builds
    it on a TPU: resident head, deep pair and the one-pass Pallas
    scorer, certificate row included."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt

    run = fdmt._build_transform(
        NCHAN, F0, BW, plan["n_hi"], T, plan["t_tile"], True, False,
        n_lo=plan["n_lo"], with_scores=True, with_plane=False, t_orig=T,
        with_cert=True)
    compiled = run.lower(_sds((NCHAN, T), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


#: HTRU's band in 1,024 channels and MeerKAT's L band in 4,096
HTRU = (1024, 1182.0, 400.0)
MEERTRAP = (4096, 856.0, 856.0)


@pytest.mark.parametrize("band,hi,lo,t,t_slice", [
    (HTRU, 1068, 0, 1 << 19, 32768),  # tier 0: unpruned, 256 rows, 72 MiB
    (HTRU, 641, 535, 1 << 14, 1 << 14),  # tier 5: one slice laps the axis
    # MeerTRAP's tier 0 (ISSUE 35): 32 groups of up to 480 rows, whose 28
    # whole tables were "1.75M of 1.00M smem"; halo 412, 75 MiB of scratch
    (MEERTRAP, 5182, 0, 1 << 17, 16384),
], ids=["htru_tier0", "htru_tier5", "meertrap_tier0"])
def test_fdmt_head_on_the_survey_plans(as_tpu, one_chip, band, hi, lo, t,
                                       t_slice):
    """The head alone at HTRU's tiers (ISSUE 33) and at MeerTRAP's tier 0
    (ISSUE 35): the slice its chooser takes needs more scoped VMEM than
    Mosaic's default 16 MiB, and a step's tables are a block of SMEM, so
    this compile holds the ``vmem_limit_bytes`` it asks for and the SMEM
    it plans to what the v5e's compiler grants."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt_resident as fr

    nchan = band[0]
    hp = fr._head_plan_cached(*band, hi, lo, fr.HEAD_LEVELS)
    assert fr.pick_head_t_slice(hp, t) == t_slice
    assert fr.head_smem_bytes(hp) <= fr.head_smem_limit()
    if lo == 0:
        assert fr.head_scratch_bytes(hp, t_slice) > 16 << 20
    run, _ = fr._build_head_kernel(*band, hi, lo, fr.HEAD_LEVELS, t,
                                   t_slice, False)
    compiled = jax.jit(run).lower(
        _sds((nchan, t), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_meertrap_tier0_sweep_beside_its_chunk(as_tpu, one_chip):
    """Tier 0's whole sweep of ``meertrap_lband_8bit`` as
    ``_search_jax_fdmt`` builds it on a TPU (4,096 x 2^17, band delays
    0-5,182, the 12-window ladder, the certificate row), with room for
    what the chunk loop holds beside it: the three downsampled copies
    (1.75 GiB) and two raw 8-bit chunks in flight (0.5 GiB each).  With
    the widest group's rows for every group the head's plane alone was
    6.5 GiB, twice (this compile read 15.0 GiB; ISSUE 35)."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.search import boxcar_ladder

    nchan, t = MEERTRAP[0], 1 << 17
    assert fdmt.head_active(*MEERTRAP, 5182, 0, t)
    run = fdmt._build_transform(
        *MEERTRAP, 5182, t, fdmt._pick_fdmt_tile(t), True, False, n_lo=0,
        with_scores=True, with_plane=False, t_orig=t, with_cert=True,
        windows=boxcar_ladder(2048))
    compiled = run.lower(_sds((nchan, t), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total + 2.75 * 2**30 < HBM_BYTES, m


def _plane_sized_passes(text, plane_bytes):
    """Instructions of the compiled program's entry computation that are
    no kernel (``custom-call``), ``bitcast`` or ``parameter`` and whose
    result holds at least half of ``plane_bytes``: each is one pass over
    an FDMT state at memory speed that computes nothing."""
    width = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
             "u8": 1, "pred": 1}
    found = []
    for line in text[text.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(3) in ("custom-call", "bitcast", "parameter"):
            continue
        size = sum(width[dtype] * int(np.prod([int(d) for d in
                                               dims.split(",") if d]))
                   for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                 m.group(2))
                   if dtype in width)
        if 2 * size >= plane_bytes:
            found.append((m.group(1), m.group(3), size))
    return found


@pytest.mark.parametrize("band,hi,lo,t,ladder,parent_temp_gib", [
    (HTRU, 1068, 0, 1 << 19, None, 6.38),    # tier 0 (cells 2-4)
    (HTRU, 1068, 535, 1 << 18, None, 1.61),  # a pruned tier (tiers 1-4)
    (MEERTRAP, 5182, 0, 1 << 17, 2048, 7.60),  # three merges, then the pair
], ids=["htru_tier0", "htru_tier1", "meertrap_tier0"])
def test_sweep_keeps_the_kernels_layout(as_tpu, one_chip, band, hi, lo, t,
                                        ladder, parent_temp_gib):
    """The coarse sweep as ``_search_jax_fdmt`` builds it on a TPU
    (ISSUE 37): between the head and the scorer the FDMT state goes from
    kernel to kernel in the layout the kernels read and write.  Three
    plane-sized passes are left that are no kernel: the cleaned chunk to
    the head's lines, the head's plane to the merges' tiles, the last
    stage's tiles to the flat plane the scorer reads.  The parent
    compiled to 11 / 11 / 13 (a relayout either side of every kernel, a
    gather of the head's rows, a slice of each stage's padding, a slice +
    pad + relayout of the whole plane for the scorer's <= 7 remainder
    rows), with the temporaries given here."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.search import boxcar_ladder

    run = fdmt._build_transform(
        *band, hi, t, fdmt._pick_fdmt_tile(t), True, False, n_lo=lo,
        with_scores=True, with_plane=False, t_orig=t, with_cert=True,
        windows=boxcar_ladder(ladder) if ladder else None)
    compiled = run.lower(_sds((band[0], t), jnp.float32, one_chip)).compile()
    text = compiled.as_text()
    # the names every roofline and *_device_ms_* metric matches
    assert text.startswith("HloModule jit_fn")
    assert set(re.findall(r"%(\w+?)\.\d+ = \S+ custom-call\(", text)) == {
        "fdmt_head", "fdmt_merge", "fdmt_deep_pair", "score_rows"}
    passes = _plane_sized_passes(text, (hi - lo + 1) * t * 4)
    assert len(passes) <= 3, passes
    assert {op for _, op, _ in passes} <= {"copy", "reshape"}, passes
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes <= parent_temp_gib * 2**30, m


@pytest.mark.parametrize("with_cert", [False, True])
def test_score_plane_pallas(as_tpu, one_chip, with_cert):
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.score_pallas import score_plane_pallas

    compiled = jax.jit(
        lambda p: score_plane_pallas(p, with_cert=with_cert)).lower(
        _sds((128, T), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_hybrid_seed_program(as_tpu, one_chip, plan):
    """The floorless hybrid's one-dispatch first round on a TPU
    (``ops/search.py:_fused_hybrid_seed_kernel``)."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import search

    ndm = plan["ndm"]
    kernel = search._fused_hybrid_seed_kernel(
        NCHAN, F0, BW, plan["n_hi"], T, plan["t_tile"], plan["n_lo"], None,
        plan["max_off"], ndm, search.HYBRID_SEED_BUCKET,
        bucket2=min(search.HYBRID_NEED_BUCKET, ndm))
    compiled = kernel.lower(
        _sds((NCHAN, T), jnp.float32, one_chip),
        _sds((ndm,), jnp.int32, one_chip),
        _sds((ndm, NCHAN), jnp.int32, one_chip),
        _sds((3,), jnp.float32, one_chip)).compile()
    _fits(compiled)


@pytest.mark.parametrize("bucket", [8, 32])
def test_fused_rescore_program(as_tpu, one_chip, plan, bucket):
    """The certificate-mode hybrid's exact rescore (smallest and largest
    row bucket)."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.search import _fused_rescore_kernel

    _fits(_fused_rescore_kernel(plan["max_off"], bucket).lower(
        _sds((NCHAN, T), jnp.float32, one_chip),
        _sds((bucket, NCHAN), jnp.int32, one_chip)).compile())


@pytest.mark.parametrize("nbits,nchan,zero_dm", [
    (2, NCHAN, False),  # cell 1's clean
    (2, NCHAN, True),   # cells 2-4's (``--zero-dm``)
    (8, 4096, False),
], ids=["2bit", "2bit_zero_dm", "8bit_4096ch"])
def test_packed_unpack_and_clean_with_donation(as_tpu, one_chip, nbits,
                                               nchan, zero_dm):
    """The driver's first device program, as ``search_by_chunks`` builds
    it: packed 2-bit frames (or MeerTRAP's 8-bit bytes) of a descending
    band in, the cleaned ascending-band float chunk out, the raw buffer
    donated (reduced time axis — see the module docstring.  At 4,096 x
    2^17 the 8-bit program holds 0.52 GiB of temporaries, the transposed
    bytes, beside its 2 GiB output; compiled by hand, PR 35).

    The 2-bit program flips the band on the packed bytes (ISSUE 42): the
    TPU compiler fuses a ``reverse`` into nothing, so one on the float
    plane forced that plane to be written, reversed and read back by
    each of the clean's passes (``convert_bitcast_fusion`` and ``rev.3``,
    29.5 ms of cell 1's 75.7 ms clean).  With none there, the widening is
    fused into the clean's readers and the only float32 plane of the
    entry computation is its root.  At 1,024 x 2^19 the temporaries went
    2.00 -> 0.52 GiB, at 2^20 4.00 -> 1.03 (compiled by hand, PR 42)."""
    import jax.numpy as jnp

    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.pipeline.search_pipeline import (
        _device_clean_program,
    )

    # (cut_outliers, zero_dm, fft_zap, resample): the benchmark's cells
    unpack_clean = _device_clean_program(
        (device_unpack_block, nbits, nchan, True), (0,),
        (False, zero_dm, False, 1))
    compiled = unpack_clean.lower(
        _sds((T_SMALL, nchan * nbits // 8), jnp.uint8, one_chip),
        _sds((nchan,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    # the name ``clean_device_ms_per_chunk`` matches
    assert text.startswith("HloModule jit_unpack_clean")
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes == nchan * T_SMALL * 4
    if nbits == 8:  # bytes are transposed, floats are not
        assert m.temp_size_in_bytes < 1.5 * nchan * T_SMALL
        return
    passes = _plane_sized_passes(text, nchan * T_SMALL * 4)
    root = re.search(r"ROOT %?([\w.\-]+) = ", text[text.index("ENTRY"):])
    assert [name for name, _, _ in passes] == [root.group(1)], passes
    # what is reversed is the frames' bytes, a sixteenth of the plane
    packed_bytes = T_SMALL * nchan * nbits // 8
    reverses = [size for _, op, size in
                _plane_sized_passes(text, 2 * packed_bytes)
                if op == "reverse"]
    assert reverses == [packed_bytes]


def test_fused_sharded_hybrid_on_four_devices(as_tpu, topo, plan):
    """``sharded_hybrid_search``'s one ``shard_map`` program on a
    ``(dm=4, chan=1)`` mesh of described devices, set up the way that
    function sets it up: the collectives and the Pallas kernels must
    both survive partitioning (reduced time axis — see the module
    docstring)."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pulsarutils_tpu.ops.fdmt import fdmt_plan
    from pulsarutils_tpu.ops.search import (HYBRID_NEED_BUCKET,
                                            HYBRID_SEED_BUCKET,
                                            auto_chan_block)
    from pulsarutils_tpu.parallel import sharded_fdmt

    t, ndm, dm_size = T_SMALL, plan["ndm"], 4
    mesh = Mesh(np.array(topo.devices[:4]).reshape(dm_size, 1),
                ("dm", "chan"))
    max_off = max(1 << int(np.ceil(np.log2(plan["max_off"] + 1))), 256)
    bucket = -(-HYBRID_SEED_BUCKET // dm_size) * dm_size
    bucket2 = -(-min(HYBRID_NEED_BUCKET, ndm) // dm_size) * dm_size
    plans = [fdmt_plan(NCHAN, F0, BW, hi, lo) for lo, hi in
             sharded_fdmt.slice_delay_range(plan["n_lo"], plan["n_hi"],
                                            dm_size)]
    tables = sharded_fdmt._stacked_tables(plans, plan["t_tile"])
    plan_key = tuple((it["k_tiles"], it["k_tiles_h"], it["rows_max"])
                     for it in tables)
    fn = sharded_fdmt._build_fused_sharded_hybrid(
        mesh, NCHAN, plans[0].nchan_padded, t, plan["t_tile"], True, False,
        plan_key, ndm, bucket, bucket2, "pallas",
        auto_chan_block(NCHAN, t, bucket // dm_size), max_off, NCHAN, None)
    rep = NamedSharding(mesh, P())
    by_dm = NamedSharding(mesh, P("dm"))
    args = [_sds((NCHAN, t), jnp.float32, rep), _sds((ndm,), jnp.int32, rep),
            _sds((ndm, NCHAN), jnp.int32, rep), _sds((3,), jnp.float32, rep),
            _sds((), jnp.int32, rep)]
    args += [_sds(it[k].shape, jnp.int32, by_dm) for it in tables
             for k in ("idx_low", "idx_high", "shift", "shift_high")]
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" in text and "tpu_custom_call" in text


# HTRU's six tiers under --boxcar-max 4096 (ISSUE 32): samples and ladder
# length of each (chipbench/configs/htru_bpsr_fulldm_boxcar4096.json)
HTRU_TIERS = [(1 << 19, 13), (1 << 18, 12), (1 << 17, 11), (1 << 16, 10),
              (1 << 15, 9), (1 << 14, 8)]


@pytest.mark.parametrize("t,length", HTRU_TIERS)
def test_score_plane_pallas_with_a_longer_ladder(as_tpu, one_chip, t, length):
    """The one-pass scorer built for each HTRU tier's ladder, certificate
    captures included: Mosaic takes 8 to 13 levels on the tile the ladder
    asks for."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.score_pallas import (pick_score_tile,
                                                  score_plane_pallas)

    ladder = tuple(1 << j for j in range(length))
    assert pick_score_tile(t, ladder[-1]) == min(t, 16384)
    compiled = jax.jit(
        lambda p: score_plane_pallas(p, with_cert=True,
                                     windows=ladder)).lower(
        _sds((64, t), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_rescore_program_with_a_longer_ladder(as_tpu, one_chip):
    """The exact rescore of the 16x tier's hit (1,024 x 2^15, nine levels):
    a ladder wider than the rebase's alignment undoes the rotation on the
    device before it scores."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.search import (REBASE_ALIGN,
                                            _fused_rescore_kernel)

    ladder = tuple(1 << j for j in range(9))
    assert ladder[-1] > REBASE_ALIGN
    _fits(_fused_rescore_kernel(1152, 32, ladder, -640).lower(
        _sds((NCHAN, 1 << 15), jnp.float32, one_chip),
        _sds((32, NCHAN), jnp.int32, one_chip)).compile())


def test_meertrap_whole_range_tile_sweep_fits_and_the_untiled_does_not(
        as_tpu, one_chip):
    """ISSUE 40: a 2^19-sample chunk of MeerTRAP's beam is searched from
    its resident bytes in time tiles.  Tier 0's sweep of one tile (2^17
    own samples + 8,192 of halo, band delays 0-5,182, the 12-window
    ladder, partial scores) compiles with room for what the chunk loop
    holds beside it: the packed chunk and the next one's prefetch (2 GiB
    each).  So does the 2x tier's (band delays 2,592-5,182, 11 windows)
    beside those and the three deeper tiers' whole arrays, which its tile
    cleans lay (3.5 GiB, donated through the clean).  The chunk's moments
    and a tile's clean from the bytes compile beside them too.  The same
    sweep of the whole 2^19 axis is what the compiler refuses: it is why
    the chunk is tiled."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.search import boxcar_ladder
    from pulsarutils_tpu.pipeline import time_tiles

    nchan, total, own, halo = MEERTRAP[0], 1 << 19, 1 << 17, 8192
    length = own + halo
    held = 4 * 2**30  # two packed chunks
    deep = [(nchan, total // f) for f in (4, 8, 16)]
    laid = sum(4 * rows * t for rows, t in deep)  # 3.5 GiB

    def sweep(t, partial, n_hi=5182, n_lo=0, widest=2048):
        return fdmt._build_transform(
            *MEERTRAP, n_hi, t, fdmt._pick_fdmt_tile(t), True, False,
            n_lo=n_lo, with_scores=True, with_plane=False, t_orig=t,
            with_cert=True, windows=boxcar_ladder(widest), partial=partial)

    def total_of(compiled):
        m = compiled.memory_analysis()
        return (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)

    assert fdmt.head_active(*MEERTRAP, 5182, 0, length)
    tile = _sds((nchan, length), jnp.float32, one_chip)
    compiled = sweep(length, (own, total)).lower(tile).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert total_of(compiled) + held < HBM_BYTES, compiled.memory_analysis()
    compiled = sweep(length, (own, total // 2), 5182, 2592,
                     1024).lower(tile).compile()
    assert total_of(compiled) + held + laid < HBM_BYTES, \
        compiled.memory_analysis()

    unpack = (device_unpack_block, 8, nchan, True)
    clean_args = (
        _sds((total + time_tiles.MAX_BLOCK, nchan), jnp.uint8, one_chip),
        _sds((), jnp.int32, one_chip), _sds((total,), jnp.float32, one_chip),
        _sds((nchan,), jnp.float32, one_chip),
        _sds((nchan,), jnp.bool_, one_chip))
    clean = time_tiles.tile_clean_program(unpack, True, total, length, ())
    # its own argument is one of the two packed chunks held
    assert total_of(clean.lower(*clean_args).compile()) + held // 2 \
        < HBM_BYTES
    laying = time_tiles.tile_clean_program(unpack, True, total, length,
                                           (2,), lay=(4, 8, 16))
    compiled = laying.lower(*clean_args, *(
        _sds(shape, jnp.float32, one_chip) for shape in deep)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= laid  # laid in place, not copied
    assert total_of(compiled) + held // 2 < HBM_BYTES

    with pytest.raises(Exception, match="(?i)memory|RESOURCE_EXHAUSTED"):
        sweep(total, None).lower(
            _sds((nchan, total), jnp.float32, one_chip)).compile()


def test_uwl_2bit_tile_clean_and_moments_at_832_byte_frames(as_tpu,
                                                            one_chip):
    """ISSUE 44: Parkes' ultra-wideband chunk (3,328 channels of 2 bits,
    frames of 832 bytes, 2^17 samples) is cleaned from its resident bytes:
    the chunk's moments and the native tier's tile clean (65,536 + 16,384
    samples, laying the 2x and 4x tiers' whole arrays in place) compile
    beside the two packed chunks held, with no float reverse of the
    band."""
    import jax.numpy as jnp

    from pulsarutils_tpu.io.lowbit import device_unpack_block
    from pulsarutils_tpu.pipeline import time_tiles

    nchan, total, length = 3328, 1 << 17, 65536 + 16384
    held = 2 * total * nchan // 4
    unpack = (device_unpack_block, 2, nchan, True)

    def total_of(compiled):
        m = compiled.memory_analysis()
        return (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)

    stats = time_tiles.chunk_stats_program(unpack, total).lower(
        _sds((total, nchan // 4), jnp.uint8, one_chip),
        _sds((nchan,), jnp.bool_, one_chip)).compile()
    assert total_of(stats) + held < HBM_BYTES
    deep = [(nchan, total // f) for f in (2, 4)]
    laying = time_tiles.tile_clean_program(unpack, True, total, length, (),
                                           lay=(2, 4))
    compiled = laying.lower(
        _sds((total + time_tiles.MAX_BLOCK, nchan // 4), jnp.uint8, one_chip),
        _sds((), jnp.int32, one_chip), _sds((total,), jnp.float32, one_chip),
        _sds((nchan,), jnp.float32, one_chip),
        _sds((nchan,), jnp.bool_, one_chip),
        *(_sds(shape, jnp.float32, one_chip) for shape in deep)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(4 * r * t for r, t in deep)
    assert total_of(compiled) + held < HBM_BYTES
    assert not re.search(r"f32\[3328,\d+\]\S* reverse\(", compiled.as_text())


CHIME = (16384, 400.0, 400.0)


@pytest.mark.parametrize("band", [0, 3])
def test_chime_band_sweep_on_its_tile(as_tpu, one_chip, band):
    """ISSUE 49: CHIME/FRB's native tier (16,384 channels, band delays
    0-20,731) is swept a delay band of 5,183 at a time on a tile of 32,768
    + 24,576 samples.  The sweep of a band (fused head over 128 groups,
    six merge levels, the deep pair, the partial scorer at the 8-window
    ladder) compiles beside the two packed chunks of 1 GiB: in the last
    band the deep pair's composed shifts reach a fourth 8,192-sample tile
    of each parent window, which at sixteen rows a step is 17.1 MiB of the
    core's 16 MiB of scoped VMEM (``ops/fdmt.py:_merge4_pallas`` takes
    such a pass eight rows a step)."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.search import boxcar_ladder

    own, halo, total = 32768, 24576, 1 << 16
    length = own + halo
    lo, hi = band * 5183, band * 5183 + 5182
    assert fdmt.head_active(*CHIME, hi, lo, length)
    run = fdmt._build_transform(
        *CHIME, hi, length, fdmt._pick_fdmt_tile(length), True, False,
        n_lo=lo, with_scores=True, with_plane=False, t_orig=length,
        with_cert=True, windows=boxcar_ladder(128), partial=(own, total))
    compiled = run.lower(
        _sds((CHIME[0], length), jnp.float32, one_chip)).compile()
    text = compiled.as_text()
    assert "fdmt_head" in text and "fdmt_deep_pair" in text
    m = compiled.memory_analysis()
    held = 2 * total * CHIME[0] + (1 << 15) * CHIME[0]  # chunks + the wrap
    assert (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + held) < HBM_BYTES, m


def test_chime_2x_tier_whole(as_tpu, one_chip):
    """The eighth cell's second sweep: CHIME's 2x tier to DM 1,100, band
    delays 10,366-10,882 on the whole axis of 32,768 samples (the head
    over 128 groups, the 7-window ladder), beside the two packed chunks
    and the frames the resident chunk carries twice."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops import fdmt
    from pulsarutils_tpu.ops.search import boxcar_ladder

    length, total, lo, hi = 1 << 15, 1 << 16, 10366, 10882
    assert fdmt.head_active(*CHIME, hi, lo, length)
    run = fdmt._build_transform(
        *CHIME, hi, length, fdmt._pick_fdmt_tile(length), True, False,
        n_lo=lo, with_scores=True, with_plane=False, t_orig=length,
        with_cert=True, windows=boxcar_ladder(64))
    compiled = run.lower(
        _sds((CHIME[0], length), jnp.float32, one_chip)).compile()
    assert "fdmt_head" in compiled.as_text()
    m = compiled.memory_analysis()
    held = 2 * total * CHIME[0] + (1 << 15) * CHIME[0]
    assert (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + held) < HBM_BYTES, m


@pytest.mark.parametrize("nchan,source,length,factor", [
    (16384, 13184, 13184, 52),      # CHIME: a tiled tier's window, DM 333
    (3328, 18845, 18845, 15),       # Parkes UWL: the same, DM 74.3
    (4096, 1 << 15, 6940, 7),       # MeerTRAP's 4x tier, a whole array
])
def test_a_hits_window_is_summed_in_place(no_compile_cache, one_chip, nchan,
                                          source, length, factor):
    """ISSUE 50: the cut-out of a hit in a wide tier is block-summed on
    the device (``jit_window_resample``) so that the 16 MiB record alone
    is read back.  The program holds no window-sized temporary: it reads
    the slice in place (a reduction over ``factor`` lanes made the v5e
    compiler copy the 864 MB window twice first), beside a chunk that
    peaks at 11.3 of 16.9 GB."""
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.rebin import window_resample_program

    compiled = window_resample_program(length, factor).lower(
        _sds((nchan, source), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes <= 4 << 22
    assert m.temp_size_in_bytes <= 1 << 22, m
