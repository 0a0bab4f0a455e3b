"""VMEM-resident fused head for the FDMT: the first ~7 tree levels in
ONE Pallas kernel, intermediate states never touching HBM.

Why: the per-level merge kernel is HBM-bound — every tree level writes
its full state and the next reads it back (plus halo), ~100 GB of
traffic for the 1M-sample benchmark transform, measured at ~40% of the
chip's bandwidth (``docs/performance.md`` round 2: 0.35 s vs the 0.15 s
traffic bound).  The EARLY levels are 75% of that traffic (row counts
shrink slowly: 1023, 767, 639, ... for the benchmark plan) *and* they
are channel-local: level ``l`` only ever combines rows within
``2^(l+1)``-channel bands.  So the first ``HEAD_LEVELS`` levels split
into independent 128-channel groups whose whole sub-tree state
(~260 live rows x a few-thousand-sample slice) fits VMEM:

* grid = (channel groups, time slices);
* each step stitches its input slice (+ the head's cumulative shift
  halo) into a VMEM buffer, runs all head levels ping-pong between two
  VMEM scratch buffers, and writes only the LAST head level's rows to
  HBM — one read of the input + one write of the head output instead of
  ~4 HBM passes per level;
* per-row shifted reads are the aligned-load + lane-rotate + blend of
  the dedispersion kernel
  (:func:`~pulsarutils_tpu.ops.pallas_dedisperse.shifted_row_tile`),
  made once over a row's whole extent in the step; a step's merge
  tables and row counts are its own group's, a block in SMEM
  (:func:`head_tables`);
* the slice is the largest whose two buffers fit the VMEM the core has
  (128 MiB on a v5e; Mosaic's *default scoped limit* is 16 MiB, so the
  ``pallas_call`` asks for its share by ``vmem_limit_bytes``): every
  non-final level computes the slice plus the remaining halo, one chunk
  more than the slice needs, so a step of 2,048 samples computes two
  chunks for one and a step of 16,384 nine for eight
  (:func:`pick_head_t_slice`, :func:`head_tile_counts`).

The deep levels (large shifts, few rows) stay on the existing
per-level kernel: their halos are too wide for VMEM residency and they
carry only ~25% of the traffic.

Numerics: the fused head performs the SAME adds in the SAME order as
the per-level path (each level's partial sums are identical floats,
merely held in VMEM) — outputs are bit-identical, pinned by
``tests/test_fdmt_resident.py``.

Time-axis convention: circular mod T via slice-modulo staggered
``BlockSpec``s (``t_slice`` divides T), the same trick as every other
kernel in this package.
"""

from __future__ import annotations

import functools

import numpy as np

from ..utils.logging_utils import kernel_build_span

#: tree levels fused into the VMEM-resident head; 2^HEAD_LEVELS channels
#: per independent group (128 in -> up to 256 live rows per group from
#: DM 0, 128 when the plan is pruned; 6 to 20 MiB a buffer at the slice
#: :func:`pick_head_t_slice` takes)
HEAD_LEVELS = 7

#: smallest time-slice (samples), the eligibility floor: must divide T
#: and hold the head halo
HEAD_T_SLICE = 2048

#: lane width of the chunked-row layout (one (8, L) chunk = 2048 samples).
#: 256 lanes keep the per-row vector ops wide (the first cut used 128 and
#: measured SLOWER than the per-level kernel: 8x narrower ops than its
#: (8, 1024) tiles drowned the HBM win in instruction overhead); it also
#: lets every head-level shifted read take the static-base fast path —
#: all head-level shifts are < L by eligibility, so a row's load starts
#: at its line 0 and no dynamic sublane rotate is ever issued.
_L = 256
_CHUNK = 8 * _L

#: rows per fori_loop iteration of the head kernel.  The scalar core's
#: per-iteration overhead (loop control + dynamic address formation)
#: dominated the un-unrolled kernel (~110 ns/row vs ~20 ns of vector
#: work -> 0.53 s, SLOWER than the per-level path's 0.37 s); unrolling
#: by 8 amortises it and flips the comparison (0.32 s measured, v5e
#: 1024 x 1M benchmark); 16 regressed hard then (4.2 s — register
#: pressure/spill pathology).  Re-measured with the whole-row body
#: (PR 33, HTRU tier 0 at 16,384): 4 and 2 are slower (35.7 and 41.6
#: against 23.4 ms a call) behind Mosaic compiles of 34 and 23 s, and 4
#: never returned at the tiers-1-4 shape; 16 equals 8 and pads more
#: rows.  8 stays pinned: the plan's row padding is to this number.
_ROW_UNROLL = 8


def _pad_stack(arrs, rows_max):
    """Stack per-group 1-D tables padded (repeat last entry) to rows_max."""
    out = np.empty((len(arrs), rows_max), np.int32)
    for g, a in enumerate(arrs):
        a = np.asarray(a, np.int32)
        if len(a) == 0:
            raise ValueError("empty group table")
        out[g, :len(a)] = a
        out[g, len(a):] = a[-1]
    return out


class HeadPlan:
    """Static per-group merge schedule for the fused head.

    Built from an :class:`~pulsarutils_tpu.ops.fdmt.FdmtPlan`: the first
    ``n_levels`` iterations' flat tables are re-based to each
    ``2^n_levels``-channel group's own input-row window and padded to the
    per-level max row count over groups (padded rows repeat the last
    real row — they compute junk that nothing references and that is
    sliced off host-side).
    """

    def __init__(self, plan, n_levels=HEAD_LEVELS):
        chan_group = 1 << n_levels
        nchp = plan.nchan_padded
        if nchp < chan_group or len(plan.iterations) < n_levels:
            raise ValueError(
                f"head needs nchan_padded >= {chan_group} and >= "
                f"{n_levels} iterations")
        self.n_levels = n_levels
        self.n_groups = nchp // chan_group
        self.rows_in = chan_group

        self.tables = []       # per level: group-local padded tables
        self.rows_out = []     # per level: padded (max) rows per group
        # per-input-band start rows; level 0's input bands are the raw
        # channels themselves (one row each)
        in_offsets = np.arange(nchp + 1)
        for lev in range(n_levels):
            it = plan.iterations[lev]
            nd = np.asarray(it["ndelay"])
            out_offsets = np.concatenate([[0], np.cumsum(nd)])
            n_bands_in = len(in_offsets) - 1
            n_bands_out = len(nd)
            bpg_in = n_bands_in // self.n_groups
            bpg_out = n_bands_out // self.n_groups
            assert bpg_out * self.n_groups == n_bands_out, (lev, n_bands_out)
            ils, ihs, ss, shs, counts = [], [], [], [], []
            for g in range(self.n_groups):
                r0 = out_offsets[g * bpg_out]
                r1 = out_offsets[(g + 1) * bpg_out]
                in_start = int(in_offsets[g * bpg_in])
                in_end = int(in_offsets[(g + 1) * bpg_in])
                il = it["idx_low"][r0:r1] - in_start
                ih = it["idx_high"][r0:r1] - in_start
                # bands merge strictly within the group: group-local
                # indices must land inside the group's input window
                assert il.min() >= 0 and ih.min() >= 0, (lev, g)
                assert max(il.max(), ih.max()) < in_end - in_start, (lev, g)
                ils.append(il)
                ihs.append(ih)
                ss.append(it["shift"][r0:r1])
                shs.append(it["shift_high"][r0:r1]
                           if it["shift_high"] is not None
                           else np.zeros(r1 - r0, np.int32))
                counts.append(int(r1 - r0))
            # padded to the row-loop unroll factor (amortises the
            # scalar loop/address overhead per iteration)
            rows_max = -(-max(counts) // _ROW_UNROLL) * _ROW_UNROLL
            self.rows_out.append(rows_max)
            self.tables.append({
                "idx_low": _pad_stack(ils, rows_max),
                "idx_high": _pad_stack(ihs, rows_max),
                "shift": _pad_stack(ss, rows_max),
                "shift_high": _pad_stack(shs, rows_max),
                "counts": np.asarray(counts),
                "leaf": it["shift_high"] is not None,
            })
            in_offsets = out_offsets[::bpg_out]
        #: per (level, group): iterations of the unrolled row loop that
        #: cover the group's own rows (a narrower group stops early)
        self.row_blocks = np.asarray(
            [-(-tab["counts"] // _ROW_UNROLL) for tab in self.tables],
            np.int32)
        self.rows_valid = self.tables[-1]["counts"]  # real final counts
        self.row_starts = np.concatenate(
            [[0], np.cumsum(self.rows_valid)])[:-1]
        self.rows_total = int(self.rows_valid.sum())
        #: the kernel's output plane: each group's own final rows, in
        #: whole blocks of the row loop, one group after the other (the
        #: real rows plus at most 7 a group; the widest group's count
        #: for every group was 2.53 x the real rows at 32 groups)
        plane_rows = self.row_blocks[-1].astype(np.int64) * _ROW_UNROLL
        self.plane_starts = np.concatenate(
            [[0], np.cumsum(plane_rows)])[:-1]
        self.rows_plane = int(plane_rows.sum())
        #: cumulative worst-case shift a sample travels through the head
        self.max_shift_per_level = [
            int(t["shift"].max(initial=0)) for t in self.tables]
        self.max_shift_per_level[0] = max(
            self.max_shift_per_level[0],
            int(self.tables[0]["shift_high"].max(initial=0)))
        self.halo = int(sum(self.max_shift_per_level))

    def remaining_halo(self, lev):
        """Cumulative max shift applied at levels ``lev..end`` — how far
        past ``t_slice`` level ``lev``'s INPUT must stay valid."""
        return int(sum(self.max_shift_per_level[lev:]))


@functools.lru_cache(maxsize=8)
def _head_plan_cached(nchan, start_freq, bandwidth, max_delay, min_delay,
                      n_levels):
    from .fdmt import fdmt_plan

    return HeadPlan(fdmt_plan(nchan, start_freq, bandwidth, max_delay,
                              min_delay), n_levels)


#: VMEM of one v5e TensorCore (``jax/_src/pallas/mosaic/tpu_info.py``),
#: assumed where no TPU is attached to ask: a program compiled from a
#: CPU host is compiled for a described v5e (``tests/test_chip_compile.py``)
_V5E_VMEM_BYTES = 128 << 20

#: share of the core's VMEM the head asks Mosaic for
#: (``vmem_limit_bytes``; the compiler's default scoped limit is 16 MiB
#: whatever the chip has), and what of it is left to Mosaic's own
#: scratch, the DMA staging and spills — the rest is the budget of the
#: two ping-pong buffers
_VMEM_SHARE = 0.75
_VMEM_HEADROOM = 8 << 20


#: SMEM of one v5e TensorCore (the same table), and the share of it the
#: head's tables may take: the compiler keeps scalars of its own there
_V5E_SMEM_BYTES = 1 << 20
_SMEM_SHARE = 0.5


def _core_bytes(field, assumed):
    """One TensorCore's capacity of a memory, asked of the device."""
    from jax.experimental.pallas import tpu as pltpu

    try:
        return getattr(pltpu.get_tpu_info(), field)
    except ValueError:  # no TPU attached
        return assumed


def head_vmem_limit():
    """Bytes of VMEM the head's ``pallas_call`` may use on this device."""
    return int(_core_bytes("vmem_capacity_bytes", _V5E_VMEM_BYTES)
               * _VMEM_SHARE)


def head_smem_limit():
    """Bytes of SMEM the head's tables may take on this device."""
    return int(_core_bytes("smem_capacity_bytes", _V5E_SMEM_BYTES)
               * _SMEM_SHARE)


def _tables_block(head):
    """``(rows, width)`` of one group's slice of :func:`head_tables`."""
    return (4 * head.n_levels + 1,
            max(head.rows_out + [head.n_levels + 1]))


def head_tables(head):
    """The kernel's merge tables, ``int32 (n_groups, 4 * n_levels + 1,
    width)``: a group's slice is everything one grid step reads.  Row
    ``4 * lev + k`` is level ``lev``'s ``idx_low`` / ``idx_high`` /
    ``shift`` / ``shift_high`` over the rows the level pads to; the last
    row holds the row loops' trip counts, one a level, and then the
    group's first row in the output plane."""
    n_levels = head.n_levels
    out = np.zeros((head.n_groups,) + _tables_block(head), np.int32)
    for lev, tab in enumerate(head.tables):
        for k, key in enumerate(("idx_low", "idx_high", "shift",
                                 "shift_high")):
            out[:, 4 * lev + k, :head.rows_out[lev]] = tab[key]
    out[:, 4 * n_levels, :n_levels] = head.row_blocks.T
    out[:, 4 * n_levels, n_levels] = head.plane_starts
    return out


def head_smem_bytes(head):
    """SMEM the head's tables take as the compiler pads them: one
    group's slice of :func:`head_tables`, in (8, 128)-word tiles, twice
    (the pipeline fetches the next group's while this one computes).
    Whole in SMEM, as 29 scalar-prefetched tables of every group's rows,
    they took ``n_groups`` times a buffer: 1.75 MiB of the 1 MiB a v5e
    has at MeerTRAP's 32 groups from DM 0 (PR 35)."""
    rows, width = _tables_block(head)
    return 2 * (-(-rows // 8) * 8) * (-(-width // 128) * 128) * 4


def _head_geometry(head, t_slice):
    """Derived sizes for one (plan, t_slice): chunks allocated per step,
    the scratch rows and the chunks each level computes — shared by the
    builder, the slice chooser and the tile count."""
    # level-0 input must stay valid over t_slice + halo; +1 chunk so the
    # 16-row shifted loads (8 rows past a chunk's base) never run off
    chunks_alloc = -(-(t_slice + head.halo) // _CHUNK) + 1
    rows_buf = max([head.rows_in] + head.rows_out)
    n_chunks_out = [-(-(t_slice + head.remaining_halo(lev + 1)) // _CHUNK)
                    for lev in range(head.n_levels)]
    n_chunks_out[-1] = t_slice // _CHUNK  # the output is exactly the slice
    return chunks_alloc, rows_buf, n_chunks_out


def head_scratch_bytes(head, t_slice):
    """VMEM of the two ping-pong buffers of one grid step."""
    chunks_alloc, rows_buf, _ = _head_geometry(head, t_slice)
    return 2 * rows_buf * chunks_alloc * _CHUNK * 4


def head_tile_counts(head, t, t_slice, row_extents=True):
    """``(computed, useful)`` tiles of one head call: a tile is one
    (8, L) chunk of one row at one level, the unit of the kernel's inner
    loop.  Useful are the real rows over the slice itself; computed adds
    each non-final level's halo chunks and the rows the row loop pads
    to (a multiple of the unroll of the group's own count, or of the
    widest group's with ``row_extents`` off).  Static: plan, T, slice.
    """
    n_chunks_out = _head_geometry(head, t_slice)[2]
    computed = useful = 0
    for lev, tab in enumerate(head.tables):
        rows = (int(head.row_blocks[lev].sum()) * _ROW_UNROLL
                if row_extents else head.n_groups * head.rows_out[lev])
        computed += rows * n_chunks_out[lev]
        useful += int(tab["counts"].sum()) * (t_slice // _CHUNK)
    return computed * (t // t_slice), useful * (t // t_slice)


def pick_head_t_slice(head, t, vmem_limit=None):
    """Largest power-of-two time slice whose scratch fits VMEM.

    Bigger slices amortise the head's halo recompute (every non-final
    level computes ``ceil((t_slice + halo)/CHUNK)`` chunks for
    ``t_slice/CHUNK`` useful ones: 2-for-1 at 2048, 5-for-4 at 8192,
    9-for-8 at 16384) and cut the per-step grid overhead; the head's
    time follows its tile count (:func:`head_tile_counts`;
    ``docs/performance.md`` has the chip's sweep).  The ceiling is the
    two ping-pong buffers' footprint against ``vmem_limit`` (default:
    :func:`head_vmem_limit`, what the builder asks Mosaic for) less
    :data:`_VMEM_HEADROOM`; the floor is the eligibility t_slice
    (:data:`HEAD_T_SLICE`), which callers have already checked divides T.
    """
    if vmem_limit is None:
        vmem_limit = head_vmem_limit()
    for t_slice in (32768, 16384, 8192, 4096, 2048):
        if t_slice < HEAD_T_SLICE or t % t_slice or t_slice % _CHUNK:
            continue
        if head.halo > (2 * t_slice) // 3:
            continue
        if head_scratch_bytes(head, t_slice) <= vmem_limit - _VMEM_HEADROOM:
            return t_slice
    return HEAD_T_SLICE


@functools.lru_cache(maxsize=8)
def _build_head_kernel(nchan, start_freq, bandwidth, max_delay, min_delay,
                       n_levels, t, t_slice, interpret, row_extents=True):
    """Compile the fused-head pallas program for one (plan, T) config.

    I/O is MANUAL DMA (``ANY``-space operands + ``make_async_copy``)
    rather than pipelined BlockSpecs: the pipelined form double-buffers
    ``k_in`` whole input slices in VMEM, which at t_slice > 2048 blew
    Mosaic's default 16 MiB of scoped VMEM (measured, round 4: every
    (t_slice >= 4096 | levels >= 8) combination failed to compile).
    Manual copies stage exactly the ``chunks_alloc`` chunks a step
    needs, un-double-buffered, and the call asks for its share of the
    VMEM the core has (:func:`head_vmem_limit`), which buys the
    big-slice win (:func:`pick_head_t_slice`).  The copies are NOT
    noise any more: at 16,384 a step of an unpruned plan stages 10 MiB
    in and 12.5 MiB out around its vector work, un-overlapped
    (``docs/performance.md`` round 4 has the chip's reading; ROADMAP
    S3).  The circular wrap is handled by statically-unrolled per-step
    copy segments (DMA shapes must be static; only the last few steps
    wrap and each split is a compile-time constant).

    ``row_extents`` is the tests' seam: off, every group loops to the
    widest group's row count, as the kernel did before PR 33.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    head = _head_plan_cached(nchan, start_freq, bandwidth, max_delay,
                             min_delay, n_levels)
    assert t % t_slice == 0 and t_slice % _CHUNK == 0
    # the static-base fast path requires every level's shift < one lane
    # row (head_supported enforces it; belt and braces here)
    assert max(head.max_shift_per_level) < _L, head.max_shift_per_level
    n_slices = t // t_slice
    cpb = t_slice // _CHUNK          # (8, L) chunks per slice
    chunks_alloc, rows_buf, n_chunks_out = _head_geometry(head, t_slice)
    r_alloc = chunks_alloc * 8
    c8 = n_slices * cpb * 8          # time axis in 8-row units
    grid = (head.n_groups, n_slices)

    def kernel(tab, data_hbm, out_hbm, buf_a, buf_b, sem_in, sem_out):
        # tab: this step's OWN group's tables in SMEM, (4 * n_levels + 1,
        # table width) — see :func:`head_tables`; data_hbm (rows, c8, L)
        # and out_hbm (rows_plane, c8, L) in ANY space

        g = pl.program_id(0)
        i_s = pl.program_id(1)

        # stage this step's input window straight into the level-0
        # buffer, un-overlapped.  DMA shapes must be static, so the
        # circular wrap is handled by per-step static segment lists: only
        # the last few steps wrap, and each such step's (dst, src, size)
        # split is a compile-time constant — no padded copy of the
        # 4 GB input (a device-side pad doubled input HBM and OOMed
        # the 1M benchmark).
        def stage(step, segs):
            @pl.when(i_s == step)
            def _():
                for dst_off, src_off, size in segs:
                    c = pltpu.make_async_copy(
                        data_hbm.at[pl.ds(g * head.rows_in, head.rows_in),
                                    pl.ds(src_off, size)],
                        buf_a.at[pl.ds(0, head.rows_in),
                                 pl.ds(dst_off, size)],
                        sem_in)
                    c.start()
                    c.wait()

        def segments(start):
            segs, p = [], 0
            while p < r_alloc:
                src = (start + p) % c8
                size = min(r_alloc - p, c8 - src)
                segs.append((p, src, size))
                p += size
            return segs

        n_wrap = min(n_slices,
                     -(-(r_alloc - cpb * 8) // (cpb * 8)))
        for w in range(n_wrap):
            step = n_slices - 1 - w
            stage(step, segments(step * cpb * 8))

        if n_slices > n_wrap:
            # generic branch: steps whose window stays in-bounds (dead
            # -- and structurally oversized -- when the window laps the
            # whole axis, so emitted only when some step qualifies)
            @pl.when(i_s < n_slices - n_wrap)
            def _():
                c = pltpu.make_async_copy(
                    data_hbm.at[pl.ds(g * head.rows_in, head.rows_in),
                                pl.ds(i_s * cpb * 8, r_alloc)],
                    buf_a.at[pl.ds(0, head.rows_in), pl.ds(0, r_alloc)],
                    sem_in)
                c.start()
                c.wait()

        def shifted_row(src, row, s, nco):
            """``src[row, s : s + nco*CHUNK]`` as an (8*nco, L) tile: the
            row's whole extent in ONE aligned load of ``nco + 1`` chunks,
            one dynamic lane-rotate and one blend of each L-sample line
            with the line after it.  Every head shift is < L
            (eligibility), so the load starts at line 0 — no dynamic
            sublane rotate (the same q0 specialisation as the
            dedispersion kernel).  Chunk by chunk (a 16-line load each)
            every chunk but the first was loaded and rotated twice and
            the traced body grew with the slice: 32.3 -> 23.6 ms a call
            at 16,384 on the chip (``docs/performance.md`` round 4).
            """
            lines = src[row, pl.ds(0, 8 * nco + 8), :]
            rolled = pltpu.roll(lines, (_L - s) % _L, 1)
            lane = jax.lax.broadcasted_iota(jnp.int32, (8 * nco, _L), 1)
            return jnp.where(lane < _L - s, rolled[0:8 * nco],
                             rolled[1:8 * nco + 1])

        src, dst = buf_a, buf_b
        for lev in range(n_levels):
            il, ih, sl, sh = range(4 * lev, 4 * lev + 4)
            leaf = head.tables[lev]["leaf"]
            nco = n_chunks_out[lev]

            def row_body(rb, _, il=il, ih=ih, sl=sl, sh=sh,
                         leaf=leaf, nco=nco, src=src, dst=dst):
                # row unroll: one loop iteration's scalar overhead
                # (control flow + dynamic address formation) amortised
                # over _ROW_UNROLL rows of vector work
                for dr in range(_ROW_UNROLL):
                    r = rb * _ROW_UNROLL + dr
                    low = shifted_row(src, tab[il, r], tab[sl, r], nco)
                    if leaf:
                        high = shifted_row(src, tab[ih, r], tab[sh, r], nco)
                    else:
                        high = src[tab[ih, r], pl.ds(0, 8 * nco), :]
                    dst[r, pl.ds(0, 8 * nco), :] = low + high
                return 0

            # each group loops over its own rows (a dynamic trip count
            # from its tables' last row): the padding to the widest
            # group's count was 27 % of an unpruned plan's tiles
            n_blocks = (tab[4 * n_levels, lev] if row_extents
                        else head.rows_out[lev] // _ROW_UNROLL)
            jax.lax.fori_loop(0, n_blocks, row_body, 0)
            src, dst = dst, src

        # the final level landed in `src` (post-swap): the group's own
        # rows go to their place in the plane, a block of the row loop a
        # DMA (shapes are static, the group's row count is not), all
        # started before the first is waited for
        out_row0 = tab[4 * n_levels, n_levels]

        def copy_out(b):
            return pltpu.make_async_copy(
                src.at[pl.ds(b * _ROW_UNROLL, _ROW_UNROLL),
                       pl.ds(0, cpb * 8)],
                out_hbm.at[pl.ds(out_row0 + b * _ROW_UNROLL, _ROW_UNROLL),
                           pl.ds(i_s * cpb * 8, cpb * 8)],
                sem_out)

        def start_out(b, _):
            copy_out(b).start()
            return 0

        def wait_out(b, _):
            copy_out(b).wait()
            return 0

        n_out = tab[4 * n_levels, n_levels - 1]
        jax.lax.fori_loop(0, n_out, start_out, 0)
        jax.lax.fori_loop(0, n_out, wait_out, 0)

    tables = head_tables(head)
    call = pl.pallas_call(
        kernel, grid=grid,
        # a step's tables are its own group's, a block of SMEM indexed
        # by the grid's group axis: whole in SMEM (scalar prefetch) they
        # outgrew it at 32 groups (:func:`head_smem_bytes`)
        in_specs=[pl.BlockSpec((None,) + tables.shape[1:],
                               lambda g, i_s: (g, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((rows_buf, r_alloc, _L), jnp.float32),
            pltpu.VMEM((rows_buf, r_alloc, _L), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        out_shape=jax.ShapeDtypeStruct((head.rows_plane, c8, _L),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=head_vmem_limit()),
        interpret=bool(interpret), name="fdmt_head")
    tables = jnp.asarray(tables)

    def run(data):
        # traceable (un-jitted) so the whole-transform jit can inline
        # it; returns the plane as the kernel writes it, (rows_plane,
        # t / L, L): the sweep's next kernel reads it through
        # :func:`head_plane_rows`, :func:`head_flat_rows` relays it flat
        with kernel_build_span("fdmt_head", rows=head.rows_plane, t=t,
                               t_tile=t_slice):
            return call(tables, data.reshape(data.shape[0], c8, _L))

    return run, head


def head_plane_rows(head):
    """The plane row of every row of the head's output state: global
    level-``n_levels`` row (band-major, as the next merge's tables index
    it) -> its row in the kernel's plane, where each group's rows start
    on a block of the row loop."""
    return np.concatenate(
        [start + np.arange(c, dtype=np.int32)
         for start, c in zip(head.plane_starts, head.rows_valid)])


def head_flat_rows(head, plane):
    """The head's plane as the flat ``(rows_total, t)`` state of the
    per-level path: a relayout of the whole plane and, where a group's
    rows are padded, a gather of it — what the sweep's chain of kernels
    does not pay (``ops/fdmt.py:_transform_fn``)."""
    import jax.numpy as jnp

    out = plane.reshape(head.rows_plane, -1)
    if head.rows_plane == head.rows_total:
        return out
    return out[jnp.asarray(head_plane_rows(head))]


def head_transform(data, max_delay, start_freq, bandwidth, min_delay=0,
                   n_levels=HEAD_LEVELS, t_slice=None, interpret=None,
                   row_extents=True):
    """Run the fused head: raw (nchan, T) -> level-``n_levels`` state.

    Returns the same float32 rows the first ``n_levels`` per-level
    merges would produce (bit-identical), band-major.  The caller feeds
    this into the remaining per-level merges.
    """
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    data = jnp.asarray(data, jnp.float32)
    nchan, t = data.shape
    if t_slice is None:
        t_slice = pick_head_t_slice(
            _head_plan_cached(nchan, float(start_freq), float(bandwidth),
                              int(max_delay), int(min_delay),
                              int(n_levels)), int(t))
    run, head = _build_head_kernel(
        nchan, float(start_freq), float(bandwidth), int(max_delay),
        int(min_delay), int(n_levels), int(t), int(t_slice),
        bool(interpret), bool(row_extents))
    if nchan < head.rows_in * head.n_groups:
        data = jnp.concatenate(
            [data, jnp.zeros((head.rows_in * head.n_groups - nchan, t),
                             jnp.float32)])
    def fdmt_resident(data):  # the program's name in a device trace
        return head_flat_rows(head, run(data))

    return jax.jit(fdmt_resident)(data)


def head_supported(nchan_padded, n_iterations, t, t_slice=None,
                   halo=None, max_level_shift=None):
    """Static eligibility check shared with the transform integration."""
    t_slice = t_slice or HEAD_T_SLICE
    if nchan_padded < (1 << HEAD_LEVELS) or n_iterations <= HEAD_LEVELS:
        return False
    if t % t_slice or t_slice % _CHUNK:
        return False
    if halo is not None and halo > (2 * t_slice) // 3:
        return False  # halo-dominated slices waste the residency win
    if max_level_shift is not None and max_level_shift >= _L:
        return False  # static-base shifted reads need shifts < one row
    return True
