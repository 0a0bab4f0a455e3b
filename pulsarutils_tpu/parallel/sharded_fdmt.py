"""DM-sliced sharded FDMT: the fast tree kernel scaled over a device mesh.

:mod:`.sharded` scales the *direct* sweep (the bit-exact kernel) over a
``(dm, chan)`` mesh; this module scales the *FDMT* — the throughput
kernel behind ``kernel="fdmt"`` and the hybrid — over the ``dm`` axis:

* the trial-delay range ``[n_lo, n_hi]`` splits into one contiguous
  slice per device;
* each device runs the **delay-range-pruned** transform
  (:class:`~pulsarutils_tpu.ops.fdmt.FdmtPlan` with its slice as
  ``[min_delay, max_delay]``) — rows outside its slice are never built,
  so per-device work for the deep (delay-dominated) iterations scales
  ~1/D while only the shallow channel-dominated iterations are
  replicated;
* the per-device merge schedules differ (different delay windows), but
  ``shard_map`` compiles ONE program: the tables are padded to common
  shapes and shipped as **sharded runtime operands** riding the merge
  kernel's scalar-prefetch inputs
  (:func:`~pulsarutils_tpu.ops.fdmt.merge_rows_traced`);
* scores come back ``dm``-sharded; each device's leading ``hi - lo + 1``
  rows are its delay slice and the padded remainder is dropped when the
  host stitches the global table.

Input data is replicated across the ``dm`` axis (each device needs the
whole band to dedisperse any trial — same trade the reference's
shared-memory ``prange`` sweep makes, ``pulsarutils/dedispersion.py:174``).
Communication: none at all inside the transform (the slices are
independent), so the layout scales over DCN as well as ICI.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.fdmt import (
    MERGE_ROW_BLOCK,
    _pick_fdmt_tile,
    fdmt_plan,
    fdmt_trial_dms,
)
from ..tuning.geometry import PLAN_CACHE_SIZE, counted_plan_cache
from ..utils.logging_utils import budget_bucket, budget_count, logger
from ..utils.table import ResultTable
from .mesh import fetch_global, pad_to_multiple

__all__ = ["sharded_fdmt_search", "sharded_hybrid_search",
           "slice_delay_range"]


def slice_delay_range(n_lo, n_hi, n_slices):
    """Split ``[n_lo, n_hi]`` (inclusive) into contiguous near-equal
    slices; returns a list of ``(lo, hi)`` pairs.  Requires at least one
    trial per slice."""
    total = n_hi - n_lo + 1
    if total < n_slices:
        raise ValueError(f"{total} trials cannot fill {n_slices} devices; "
                         "use a smaller mesh or a wider DM range")
    edges = [n_lo + (total * i) // n_slices for i in range(n_slices + 1)]
    return [(edges[i], edges[i + 1] - 1) for i in range(n_slices)]


def _pad_rows(a, rows):
    """Pad a 1-D table to ``rows`` by repeating its last entry."""
    return np.concatenate([a, a[-1:].repeat(rows - len(a))])


def _stacked_tables(plans, t_tile):
    """Per-iteration tables stacked over devices + static kernel bounds.

    Returns a list of dicts with ``idx_low/idx_high/shift/shift_high``
    as ``(D, rows_max)`` int32 arrays (device-shardable) and the static
    ``k_tiles``/``k_tiles_h``/``rows_max`` the one compiled program
    needs (maxima over devices).
    """
    n_iter = len(plans[0].iterations)
    assert all(len(p.iterations) == n_iter for p in plans)
    L = t_tile // 8
    out = []
    for i in range(n_iter):
        its = [p.iterations[i] for p in plans]
        rows_max = max(len(it["idx_low"]) for it in its)
        rows_max += (-rows_max) % min(MERGE_ROW_BLOCK, rows_max)
        idx_low = np.stack([_pad_rows(it["idx_low"], rows_max)
                            for it in its])
        idx_high = np.stack([_pad_rows(it["idx_high"], rows_max)
                             for it in its])
        shift = np.stack([_pad_rows(it["shift"], rows_max) for it in its])
        max_shift = int(shift.max(initial=0))
        k_tiles = (max_shift // L + 23) // 8
        if its[0]["shift_high"] is not None:
            shift_high = np.stack([_pad_rows(it["shift_high"], rows_max)
                                   for it in its])
            k_tiles_h = (int(shift_high.max(initial=0)) // L + 23) // 8
        else:
            shift_high = np.zeros_like(shift)
            k_tiles_h = 0
        out.append({
            "idx_low": idx_low.astype(np.int32),
            "idx_high": idx_high.astype(np.int32),
            "shift": shift.astype(np.int32),
            "shift_high": shift_high.astype(np.int32),
            "k_tiles": k_tiles,
            "k_tiles_h": k_tiles_h,
            "rows_max": rows_max,
        })
    return out


@counted_plan_cache("_build_sharded_fdmt", maxsize=PLAN_CACHE_SIZE)
def _build_sharded_fdmt(mesh, axis, nchan, nchan_padded, t, t_tile,
                        use_pallas, interpret, plan_key, t_orig,
                        with_cert=False, with_plane=False,
                        packed_meta=None):
    """Compile the SPMD transform+score program for one mesh/geometry.

    ``plan_key`` carries the static per-iteration bounds (k_tiles,
    rows_max, ...) so the cache key captures the schedule shapes; the
    table *values* are runtime operands.  ``t`` is the (possibly padded)
    run length; scores are computed over the first ``t_orig`` samples.
    ``with_plane`` additionally emits the final transform state — the
    dedispersed plane, DM-sharded ``P(axis, None)`` and device-resident
    (the mesh plane-products path, :mod:`.sharded_plane`).
    ``packed_meta`` (a :meth:`~pulsarutils_tpu.io.lowbit.PackedFrames.
    meta` tuple) makes ``data`` the RAW packed ``(T, bytes_per_frame)``
    uint8 frames, replicated like the float block was: each device's
    shard_map body starts with the bit-unpack, so the host->device
    link carries 1/8-1/16th the bytes (ISSUE 11).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.fdmt import _merge_xla, merge_rows_traced
    from ..ops.search import score_profiles_chunked

    iter_meta = plan_key  # tuple of (k_tiles, k_tiles_h, rows_max)

    def local_fn(data, *tables):
        # data (nchan, T) replicated — or the raw packed frames,
        # unpacked here INSIDE the one shard_map program; tables: 4
        # arrays per iteration, each (1, rows_max) — this device's
        # merge schedule
        if packed_meta is not None:
            from ..io.lowbit import unpack_from_meta

            data = unpack_from_meta(data, packed_meta, jnp)
        state = data
        if nchan < nchan_padded:
            state = jnp.concatenate(
                [state, jnp.zeros((nchan_padded - nchan, t), state.dtype)])
        for i, (k_tiles, k_tiles_h, rows_max) in enumerate(iter_meta):
            il, ih, sh, shh = (tables[4 * i + j][0] for j in range(4))
            if use_pallas:
                state = merge_rows_traced(
                    state, il, ih, sh,
                    shh if k_tiles_h else jnp.zeros_like(sh),
                    k_tiles=k_tiles, k_tiles_h=k_tiles_h, t_tile=t_tile,
                    interpret=interpret)
            else:
                state = _merge_xla(state, il, ih, sh,
                                   shh if k_tiles_h else None)
        if t_orig != t:
            state = state[:, :t_orig]
        # score every (padded) row; junk rows are dropped host-side
        scores = score_profiles_chunked(state, jnp,
                                        with_cert=with_cert)[None]
        return (scores, state) if with_plane else scores

    from .mesh import shard_map_compat

    in_specs = [P()] + [P(axis)] * (4 * len(iter_meta))
    out_specs = (P(axis), P(axis, None)) if with_plane else P(axis)
    fn = jax.jit(shard_map_compat(
        local_fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        # pallas_call outputs carry no varying-mesh-axes metadata, which
        # trips shard_map's vma lint; there are no collectives at all in
        # this program, so the check adds nothing
        check_vma=not use_pallas))
    return fn


def sharded_fdmt_search(data, dmmin, dmmax, start_freq, bandwidth,
                        sample_time, mesh, axis="dm", use_pallas=None,
                        with_cert=False, capture_plane=False):
    """FDMT sweep with the trial-DM axis sharded over ``mesh[axis]``.

    Same scientific contract as ``dedispersion_search(kernel="fdmt")``
    (integer band-delay trial grid, within-one-trial hit agreement with
    the exact kernels), with per-device HBM for the output plane/state
    cut ~1/D and the deep tree iterations parallelised over devices.
    ``use_pallas`` forces the Pallas (True, interpret mode off-TPU — for
    testing the traced-table kernel path) or XLA (False) merge; default
    auto: Pallas on TPU.

    ``data`` may be a :class:`~pulsarutils_tpu.io.lowbit.PackedFrames`
    (ISSUE 11): the raw 1/2/4-bit bytes ship to the devices and each
    shard_map body unpacks them in-program — 1/8-1/16th the link
    traffic, scores byte-identical to the float-block run.

    Returns a :class:`~pulsarutils_tpu.utils.table.ResultTable` with the
    usual ``DM, max, std, snr, rebin, peak`` columns over the full grid.
    With ``capture_plane`` returns ``(table, plane)`` where ``plane`` is
    a :class:`~pulsarutils_tpu.parallel.sharded_plane.ShardedPlane` —
    the dedispersed plane left DM-sharded and device-resident, with
    shard-local per-row products (the mesh diagnostics/period-search
    path; the single-device path's host-gathered plane never exists).
    """
    import jax
    import jax.numpy as jnp

    from ..io.lowbit import PackedFrames
    from ..ops.search import unstack_scores

    packed = data if isinstance(data, PackedFrames) else None
    nchan, t = np.shape(data)  # PackedFrames reports its logical shape
    n_dev = mesh.shape[axis]
    trial_dms, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, start_freq,
                                           bandwidth, sample_time)
    slices = slice_delay_range(n_lo, n_hi, n_dev)

    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    interpret = jax.default_backend() != "tpu"
    packed_meta = packed.meta() if packed is not None else None
    # packed input: the RAW bytes are the program operand — the unpack
    # runs inside the shard_map body (_build_sharded_fdmt)
    data = (jnp.asarray(packed.frames) if packed is not None
            else jnp.asarray(data, jnp.float32))
    t_run = t
    t_tile = _pick_fdmt_tile(t)
    if use_pallas and t_tile == 0:
        # same zero-pad rule as the single-device path
        # (ops/fdmt.py:_transform_setup): the XLA merge's per-row rolls
        # scalarise on TPU, so padding to a tile multiple and slicing
        # the scores back is far cheaper than falling off Pallas
        t_run = -(-t // 1024) * 1024
        if packed is not None:
            # frames are time-major: pad whole zero FRAMES — a zero
            # byte decodes to zero codes, so the unpacked pad equals
            # the float path's zero-sample pad exactly
            data = jnp.pad(data, ((0, t_run - t), (0, 0)))
        else:
            data = jnp.pad(data, ((0, 0), (0, t_run - t)))
        t_tile = _pick_fdmt_tile(t_run)
    elif t_tile == 0:
        t_tile = 1024  # unused by the XLA merge path

    plans = [fdmt_plan(nchan, float(start_freq), float(bandwidth), hi, lo)
             for lo, hi in slices]
    tables = _stacked_tables(plans, t_tile)
    plan_key = tuple((it["k_tiles"], it["k_tiles_h"], it["rows_max"])
                     for it in tables)

    fn = _build_sharded_fdmt(mesh, axis, nchan, plans[0].nchan_padded,
                             t_run, t_tile, use_pallas, interpret,
                             plan_key, t, with_cert, capture_plane,
                             packed_meta)
    flat = []
    for it in tables:
        flat += [jnp.asarray(it[k]) for k in
                 ("idx_low", "idx_high", "shift", "shift_high")]
    plane_handle = None
    if capture_plane:
        from .sharded_plane import ShardedPlane

        with budget_bucket("search/coarse"):
            out, plane = fn(data, *flat)
            budget_count("dispatches")
        with budget_bucket("search/coarse_readback"):
            out = fetch_global(out)
            budget_count("readbacks")
        # device d's padded shard starts at d * rows_max in the global
        # concatenated plane; its first (hi-lo+1) rows are its slice
        rows_max = plane.shape[0] // n_dev
        row_index = np.concatenate(
            [d * rows_max + np.arange(hi - lo + 1)
             for d, (lo, hi) in enumerate(slices)])
        plane_handle = ShardedPlane(plane, mesh, axis, row_index)
    else:
        with budget_bucket("search/coarse"):
            out_dev = fn(data, *flat)
            budget_count("dispatches")
        with budget_bucket("search/coarse_readback"):
            out = fetch_global(out_dev)
            budget_count("readbacks")

    # stitch the dm-sharded scores: device d's first (hi-lo+1) rows are
    # its delay slice; the rest is padding junk
    cols = []
    for d, (lo, hi) in enumerate(slices):
        stacked = out[d]  # (5|6, rows_max_final)
        cols.append(stacked[:, :hi - lo + 1])
    scores = unstack_scores(np.concatenate(cols, axis=1))
    maxvalues, stds, snrs, wins, peaks = scores[:5]
    columns = {
        "DM": trial_dms,
        "max": maxvalues,
        "std": stds,
        "snr": snrs,
        "rebin": wins,
        "peak": peaks,
    }
    if with_cert:
        columns["cert"] = scores[5]
    table = ResultTable(columns)
    return (table, plane_handle) if capture_plane else table


@counted_plan_cache("_plan_offsets", maxsize=PLAN_CACHE_SIZE)
def _plan_offsets(nchan, dmmin, dmmax, start_freq, bandwidth, sample_time,
                  nsamples):
    """Chunk-geometry plan grid + full int32 offset table, cached.

    The sharded hybrid used to re-enter ``dedispersion_plan`` +
    ``_offsets_for`` host-side on EVERY rescore bucket (and on every
    streaming chunk of identical geometry); one cached table is sliced
    per bucket instead.  Returned arrays are shared cache objects —
    callers slice, never mutate.  Size and hit/miss counters come from
    :mod:`..tuning.geometry` — one documented policy for every
    geometry-keyed plan cache (this one sat at 8 while its sibling
    program caches sat at 16, so tuner-induced geometry churn could
    thrash the table while the programs survived).
    """
    from ..ops.plan import dedispersion_plan
    from ..ops.search import _offsets_for

    trial_dms = np.asarray(
        dedispersion_plan(nchan, dmmin, dmmax, start_freq, bandwidth,
                          sample_time), dtype=np.float64)
    offsets = _offsets_for(trial_dms, nchan, start_freq, bandwidth,
                           sample_time, nsamples)
    trial_dms.setflags(write=False)  # shared cache objects: fail loudly
    offsets.setflags(write=False)    # on accidental mutation
    return trial_dms, offsets


@counted_plan_cache("_build_fused_sharded_hybrid", maxsize=PLAN_CACHE_SIZE)
def _build_fused_sharded_hybrid(mesh, nchan, nchan_padded, t, t_tile,
                                use_pallas, interpret, plan_key, ndm_plan,
                                bucket, bucket2, rescore_kernel, chan_block,
                                max_off, nchan_rs, packed_meta=None):
    """ONE ``shard_map`` program for the mesh hybrid's first round:

    DM-sliced coarse FDMT (each dm shard runs its delay-range-pruned
    transform, replicated over ``chan``) -> one small all-gather of the
    per-shard score packs so every device holds the global plan-grid
    coarse table -> the guarantee loop's OWN seed rule evaluated
    device-side (plausible-best + floor rows, grown +/-1 neighbours,
    selected via :func:`~..ops.search.fused_masked_topk`) -> exact
    rescore of the seed bucket sharded over the full ``(dm, chan)`` mesh
    (same per-shard kernel, channel split and psum order as
    :func:`~.sharded.sharded_dedispersion_search`, so the scores are
    bit-identical to the unfused escape hatch) -> the need stage
    (:func:`~..ops.search.fused_need_stage`, shared with the
    single-device fused kernel) rescored the same way -> everything
    packed into one replicated float32 vector
    (:func:`~..ops.search.unpack_fused_hybrid` layout).

    A typical hit chunk's guarantee loop therefore completes in ONE
    dispatch instead of one coarse ``shard_map`` program plus one per
    rescore bucket.  The seed rule deliberately differs from the
    single-device kernel's blind top-k: computing the loop's own mask
    makes the fused path's rescored set — and hence the ``exact``
    column — provably identical to the unfused path whenever the mask
    fits the bucket (the host tops up or falls back otherwise, see
    ``sharded_hybrid_search``), up to one caveat: the device evaluates
    the masks in float32 where the host loop uses float64, so a row
    within one float32 ulp of a criterion threshold can be flagged by
    one and not the other — a measure-zero tie whose members are
    score-equivalent either way (the exact-argbest contract is
    unaffected; the parity tests use decisive data).

    ``check_vma`` is off: the collective structure is three explicit
    collectives (coarse all-gather, rescore psum + all-gather) and the
    outputs are replicated by construction, which the vma lint cannot
    express across the pallas/cond paths.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.dedisperse import dedisperse_block_chunked_jax
    from ..ops.fdmt import _merge_xla, merge_rows_traced
    from ..ops.search import (
        fused_masked_topk,
        fused_need_stage,
        score_profiles_chunked,
        score_profiles_stacked,
    )

    iter_meta = plan_key  # tuple of (k_tiles, k_tiles_h, rows_max)
    dm_size = mesh.shape["dm"]
    chan_size = mesh.shape["chan"]
    c_loc = nchan_rs // chan_size

    def local_fn(data, idx_map, offsets_rs, cert_params, roll_k, *tables):
        # packed low-bit input (ISSUE 11): the operand is the RAW
        # (T, bytes_per_frame) uint8 frames and the bit-unpack is the
        # first op of this ONE shard_map program — coarse transform,
        # seed/need rescore and packing all read the unpacked block
        # from HBM while the link only ever carried the packed bytes
        if packed_meta is not None:
            from ..io.lowbit import unpack_from_meta

            data = unpack_from_meta(data, packed_meta, jnp)
        # ---- coarse: this dm shard's delay-sliced transform (chan
        # replicated) — identical math to _build_sharded_fdmt.local_fn
        state = data
        if nchan < nchan_padded:
            state = jnp.concatenate(
                [state, jnp.zeros((nchan_padded - nchan, t), state.dtype)])
        for i, (k_tiles, k_tiles_h, rows_max) in enumerate(iter_meta):
            il, ih, sh, shh = (tables[4 * i + j][0] for j in range(4))
            if use_pallas:
                state = merge_rows_traced(
                    state, il, ih, sh,
                    shh if k_tiles_h else jnp.zeros_like(sh),
                    k_tiles=k_tiles, k_tiles_h=k_tiles_h, t_tile=t_tile,
                    interpret=interpret)
            else:
                state = _merge_xla(state, il, ih, sh,
                                   shh if k_tiles_h else None)
        stacked = score_profiles_chunked(state, jnp, with_cert=True)
        # ---- ONE small all-gather (6 x D*rows floats): every device
        # sees the global coarse table, mapped onto the plan grid
        gathered = jax.lax.all_gather(stacked, "dm")       # (D, 6, R)
        coarse = gathered.transpose(1, 0, 2).reshape(
            6, -1)[:, idx_map]                             # (6, ndm_plan)
        snr_c = coarse[2]
        floor = cert_params[2]
        # ---- the guarantee loop's seed rule (hybrid_guarantee_loop),
        # device-side: plausible-best + floor rows, grown +/-1 grid
        # neighbours (clipped, not wrapped — matching np.clip there)
        seed = snr_c >= snr_c.max() - 0.5
        seed |= snr_c >= floor - 0.75
        z = jnp.zeros((1,), bool)
        grown = (seed | jnp.concatenate([seed[1:], z])
                 | jnp.concatenate([z, seed[:-1]]))
        sel, n_seed = fused_masked_topk(snr_c, grown, bucket)

        # ---- exact rescore, sharded over the full (dm, chan) mesh with
        # the unfused path's layout: device (i, j) dedisperses its row
        # slice over its channel slice, one psum over chan reduces
        i_dm = jax.lax.axis_index("dm")
        i_ch = jax.lax.axis_index("chan")
        if nchan_rs > nchan:
            data_rs = jnp.concatenate(
                [data, jnp.zeros((nchan_rs - nchan, t), data.dtype)])
        else:
            data_rs = data
        data_loc = jax.lax.dynamic_slice(data_rs, (i_ch * c_loc, 0),
                                         (c_loc, t))

        def rescore_rows(rows):
            nrows = rows.shape[0]
            rps = nrows // dm_size
            offs = offsets_rs[rows]
            offs_loc = jax.lax.dynamic_slice(
                offs, (i_dm * rps, i_ch * c_loc), (rps, c_loc))
            if rescore_kernel == "pallas":
                from ..ops.pallas_dedisperse import (
                    dedisperse_plane_pallas_traced,
                )

                partial = dedisperse_plane_pallas_traced(data_loc, offs_loc,
                                                         max_off)
            else:
                partial = dedisperse_block_chunked_jax(data_loc, offs_loc,
                                                       chan_block)
            dedisp = jax.lax.psum(partial, "chan")
            if rescore_kernel == "pallas":
                dedisp = jnp.roll(dedisp, -roll_k, axis=1)
            scores = score_profiles_stacked(dedisp, xp=jnp)  # (5, rps)
            g = jax.lax.all_gather(scores, "dm")             # (D, 5, rps)
            return g.transpose(1, 0, 2).reshape(5, nrows)

        exact = rescore_rows(sel)
        parts = [coarse.reshape(-1), sel.astype(jnp.float32),
                 exact.reshape(-1), n_seed.astype(jnp.float32)[None]]
        if bucket2:
            best_exact = exact[2].max()
            rescored = jnp.zeros(ndm_plan, bool).at[sel].set(True)
            sel2, n_need = fused_need_stage(coarse, best_exact, rescored,
                                            cert_params, bucket2)
            # skipped (lax.cond) when nothing is flagged, exactly like
            # the single-device kernel — the predicate is replicated, so
            # every device takes the same branch and the branch's
            # collectives stay matched
            exact2 = jax.lax.cond(
                n_need > 0, rescore_rows,
                lambda _: jnp.zeros((5, bucket2), jnp.float32), sel2)
            parts += [sel2.astype(jnp.float32), exact2.reshape(-1),
                      n_need.astype(jnp.float32)[None]]
        return jnp.concatenate(parts)

    from .mesh import shard_map_compat

    in_specs = [P(), P(), P(), P(), P()] + [P("dm")] * (4 * len(iter_meta))
    fn = shard_map_compat(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                          out_specs=P(), check_vma=False)
    return jax.jit(fn)


def sharded_hybrid_search(data, dmmin, dmmax, start_freq, bandwidth,
                          sample_time, mesh, snr_floor=None,
                          noise_certificate=True, capture_plane=False,
                          rho_cert=None, cert_slack=None, fused=None):
    """Hybrid (exact hits at coarse cost) over a ``(dm, chan)`` mesh.

    Multi-device composition of ``dedispersion_search(kernel="hybrid")``:
    the coarse stage is the DM-sliced sharded FDMT (the ``chan`` axis is
    idle/replicated there — use ``chan=1`` meshes when the coarse stage
    dominates), and the exact rescore of candidate rows runs through
    :func:`~pulsarutils_tpu.parallel.sharded.sharded_dedispersion_search`
    over the full mesh.  The guarantee loop, the cert-based skip
    criterion and the noise certificate are shared with the
    single-device hybrid (:mod:`~pulsarutils_tpu.ops.certify`), so the
    contract is identical: the returned argbest row holds the exact
    kernel's scores (unless ``meta["certified"]``, which asserts no
    detection above ``snr_floor`` exists — sound under the stated
    signal model up to the Gaussian noise cross-term, residual risk in
    ``meta["cert_miss_p_at_floor"]``), with an ``exact`` column marking
    exact rows.

    ``capture_plane`` returns ``(table, plane)`` with ``plane`` a
    :class:`~.sharded_plane.ShardedPlane` over the *coarse* (FDMT) plane
    remapped to the plan grid — the same coarse-plane convention as the
    single-device hybrid's capture (``ops/search.py``:
    ``_search_jax_hybrid``), kept DM-sharded and device-resident.

    ``rho_cert`` / ``cert_slack`` mirror ``dedispersion_search``'s
    knobs: a precomputed retention bound (or ``False`` to opt out of
    the cert machinery) and a certificate slack derived from a target
    miss probability (:func:`~pulsarutils_tpu.ops.certify.cert_slack_for_miss_p`).

    ``data`` may be a :class:`~pulsarutils_tpu.io.lowbit.PackedFrames`
    (ISSUE 11): the fused program's operand is then the raw 1/2/4-bit
    bytes, unpacked inside the one ``shard_map`` dispatch — 1/8-1/16th
    the link traffic; the escape-hatch rescore decodes lazily through a
    cached device program, so certified / fused-converged chunks never
    pay the float materialisation.  Results are byte-identical to the
    host-unpacked run (``tests/test_lowbit_e2e.py``).

    ``fused`` (round 6): ``None`` (default) runs the first round —
    coarse FDMT + seed selection + exact seed/need rescore — as ONE
    ``shard_map`` dispatch (:func:`_build_fused_sharded_hybrid`)
    whenever eligible: no plane capture, no certificate-mode floor
    (mirroring the single-device gating — a noise-certified chunk
    should pay one coarse dispatch, not a burned seed rescore), cert
    machinery not opted out, and a trial grid at least one seed bucket
    wide.  The :func:`~..ops.search.hybrid_certificate_gate` loop stays
    as the escape hatch: only rows the fused program did not rescore
    trigger (now rare) follow-up
    :func:`~.sharded.sharded_dedispersion_search` dispatches, and when
    the device's seed or need stage overflows its bucket the host
    discards that stage and completes the round itself, so the rescored
    set — argbest, ``exact`` column and certificate metadata — is
    identical to ``fused=False`` (up to float32-vs-float64 threshold
    ties on the mask criteria — measure-zero, score-equivalent rows;
    see :func:`_build_fused_sharded_hybrid`).  ``fused=False`` forces
    the unfused multi-dispatch composition (the A/B baseline);
    ``fused=True`` raises if the fused program is not eligible.
    """
    import jax
    import jax.numpy as jnp

    from ..ops.certify import cert_meta, fused_cert_params
    from ..ops.search import (
        HYBRID_NEED_BUCKET,
        HYBRID_SEED_BUCKET,
        auto_chan_block,
        fused_scores_to_host,
        hybrid_certificate_gate,
        iter_rescore_buckets,
        nearest_rows,
        unpack_fused_hybrid,
    )
    from .sharded import sharded_dedispersion_search

    from ..io.lowbit import PackedFrames

    pf = data if isinstance(data, PackedFrames) else None
    nchan, nsamples = np.shape(data)  # PackedFrames reports logical shape
    dm_size = mesh.shape["dm"]
    chan_size = mesh.shape["chan"]
    # (the pad-free soundness guard lives in hybrid_certificate_gate,
    # shared verbatim with the single-device hybrid)
    # ONE host->device transfer: the coarse stage and every rescore call
    # reuse the same device-resident array (sharded_dedispersion_search
    # passes aligned device inputs through untouched).  Packed low-bit
    # input (ISSUE 11): the RAW bytes are the transfer; the fused
    # program unpacks them in its own shard_map body, and the float
    # view for the (rare) escape-hatch rescore is decoded lazily by a
    # cached device program — a certified or fused-converged chunk
    # never materialises it.
    if pf is not None:
        raw_dev = jnp.asarray(pf.frames)
        data = None
    else:
        data = jnp.asarray(data, jnp.float32)

    def _float_data():
        nonlocal data
        if data is None:
            data = pf.to_device()
        return data

    # chunk-geometry plan + offsets: ONE cached host computation, sliced
    # per rescore bucket (was re-derived inside every bucket call)
    trial_dms, offsets_full = _plan_offsets(
        nchan, float(dmmin), float(dmmax), float(start_freq),
        float(bandwidth), float(sample_time), int(nsamples))
    ndm = len(trial_dms)

    use_pallas = jax.default_backend() == "tpu"
    # the exact-rescore per-shard kernel: tuner-resolved at the chunk
    # geometry (the same (backend, geometry, mesh) key the sharded
    # direct sweep uses, so both paths agree on the winner); off-TPU
    # meshes have one applicable variant and resolve statically at zero
    # cost.  The escape-hatch rescore below passes this choice
    # explicitly — the fused program and the hatch MUST rescore with
    # the same per-shard kernel for the bit-identity contract
    from ..tuning.autotune import resolve_mesh_kernel

    rescore_kernel = resolve_mesh_kernel(mesh, nchan, nsamples, ndm,
                                         start_freq, bandwidth,
                                         sample_time, trial_dms)
    # rescore offsets aligned to the chan axis once (zero channels are
    # exact no-ops); the escape hatch gets slices of the same raw table
    # and a matching pre-padded device array, so repeat buckets never
    # bounce the chunk through the host again
    offsets_raw, _ = pad_to_multiple(offsets_full, 1, chan_size,
                                     mode="constant")
    nchan_rs = offsets_raw.shape[1]
    _rs_cache = {}

    def _data_rs():
        """Chan-aligned float chunk for the escape-hatch rescore, built
        lazily: the fused program rescoring in-dispatch (the common
        case) and the certified chunk never pay it — on the packed path
        that also skips the whole device decode.  Device-side pad: a
        np.pad here would bounce the (possibly multi-GB,
        device-resident) chunk through the host on every search
        (code-review r7)."""
        if "v" not in _rs_cache:
            d = _float_data() if pf is not None else data
            _rs_cache["v"] = (jnp.pad(d, ((0, nchan_rs - nchan), (0, 0)))
                              if nchan_rs > nchan else d)
        return _rs_cache["v"]

    roll_k = 0
    rescore_max_off = None
    offsets_rs = offsets_raw  # the fused kernel's operand
    if rescore_kernel == "pallas":
        # ONE rebase bound over the full table, power-of-two rounded:
        # every bucket subset shares the compiled programs' static halo
        # (no per-subset cache keys, no silent retrace)
        from ..ops.pallas_dedisperse import rebase_offsets

        offsets_rs, roll_k, rescore_max_off = rebase_offsets(offsets_raw,
                                                             nsamples)
        if rescore_max_off > 0:
            rescore_max_off = 1 << int(
                np.ceil(np.log2(rescore_max_off + 1)))
        rescore_max_off = max(rescore_max_off, 256)

    def _round_up(x, m):
        return -(-x // m) * m

    bucket = _round_up(HYBRID_SEED_BUCKET, dm_size)
    bucket2 = _round_up(min(HYBRID_NEED_BUCKET, ndm), dm_size)
    fused_why = None
    if capture_plane:
        fused_why = "capture_plane needs the two-stage coarse program"
    elif snr_floor is not None and noise_certificate:
        fused_why = ("certificate mode: a certified chunk should pay one "
                     "coarse dispatch, not a burned seed rescore")
    elif rho_cert is False:
        fused_why = ("rho_cert=False drops the loop to legacy margins, "
                     "whose adaptive term the device cannot evaluate")
    elif ndm < max(bucket, bucket2):
        fused_why = f"trial grid ({ndm}) narrower than the seed bucket"
    elif use_pallas and _pick_fdmt_tile(nsamples) == 0:
        fused_why = "padded TPU time axis (rescore wrap convention)"
    if fused is True and fused_why is not None:
        raise ValueError(f"fused=True not eligible: {fused_why}")
    use_fused = fused is not False and fused_why is None
    from ..resilience import ladder as _ladder

    if fused is None and use_fused and _ladder.unfuse_engaged():
        # OOM ladder "unfuse" rung (ISSUE 12): under memory pressure
        # the one-dispatch program splits back into its coarse +
        # rescore composition, whose rescored set is already pinned
        # bit-identical to the fused run (explicit fused=True still
        # forces the fused program — the A/B baseline must not shift
        # under a stale global level)
        use_fused = False

    plane = None
    n_seed = n_need = 0
    seed_done = False
    if use_fused:
        # ---- ONE dispatch: coarse + seed + need-stage rescore ----------
        interpret = jax.default_backend() != "tpu"
        fdmt_dms, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax,
                                              start_freq, bandwidth,
                                              sample_time)
        idx = nearest_rows(fdmt_dms, trial_dms)
        slices = slice_delay_range(n_lo, n_hi, dm_size)
        t_tile = _pick_fdmt_tile(nsamples)
        if not use_pallas and t_tile == 0:
            t_tile = 1024  # unused by the XLA merge path
        plans = [fdmt_plan(nchan, float(start_freq), float(bandwidth), hi,
                           lo) for lo, hi in slices]
        tables = _stacked_tables(plans, t_tile)
        plan_key = tuple((it["k_tiles"], it["k_tiles_h"], it["rows_max"])
                         for it in tables)
        # plan row -> padded position in the all-gathered coarse pack:
        # device d's shard starts at d * rows_max and its row j holds
        # delay lo_d + j (the same stitching rule sharded_fdmt_search
        # applies host-side)
        rows_max = plan_key[-1][2]
        his = np.array([hi for _, hi in slices])
        los = np.array([lo for lo, _ in slices])
        delay = idx + n_lo
        dev = np.searchsorted(his, delay)
        idx_map = (dev * rows_max + (delay - los[dev])).astype(np.int32)

        chan_block = auto_chan_block(nchan_rs // chan_size, nsamples,
                                     bucket // dm_size)
        cert_params = fused_cert_params(
            nchan, trial_dms, start_freq, bandwidth, sample_time, nsamples,
            snr_floor=snr_floor, rho_cert=rho_cert, cert_slack=cert_slack)
        kernel_fn = _build_fused_sharded_hybrid(
            mesh, nchan, plans[0].nchan_padded, nsamples, t_tile,
            use_pallas, interpret, plan_key, ndm, bucket, bucket2,
            rescore_kernel, chan_block,
            0 if rescore_max_off is None else rescore_max_off, nchan_rs,
            pf.meta() if pf is not None else None)
        flat = []
        for it in tables:
            flat += [jnp.asarray(it[k]) for k in
                     ("idx_low", "idx_high", "shift", "shift_high")]
        from ..faults import inject as fault_inject

        try:
            # the "mesh" fault site also fires HERE (not only in the
            # pipeline's run_one): direct callers — stream_search's
            # mesh route, tests — get the same injection seam; a
            # times=1 spec already consumed at the pipeline seam is
            # exhausted and no-ops here
            fault_inject.fire("mesh", chunk=None)
            with budget_bucket("search/fused"):
                # operand conversions stay inside the bucket
                # (attributed); on the packed path the operand IS the
                # raw packed bytes
                fused_args = (raw_dev if pf is not None else data,
                              jnp.asarray(idx_map),
                              jnp.asarray(offsets_rs),
                              jnp.asarray(cert_params),
                              jnp.int32(roll_k), *flat)
                packed = np.asarray(kernel_fn(*fused_args))
                budget_count("dispatches")
                budget_count("readbacks")
        except (ValueError, TypeError):
            raise  # deterministic configuration error, never OOM
        except Exception as exc:  # jax errors share no base class
            if fused is True or not _ladder.is_resource_exhausted(exc):
                raise
            # the fused program's compound footprint OOMed: descend to
            # the two-stage composition (the "unfuse" rung) — its
            # rescored set is bit-identical to the fused one (ISSUE 12)
            _ladder.oom_event("mesh_fused")
            _ladder.descend("unfuse")
            logger.warning("fused mesh hybrid hit RESOURCE_EXHAUSTED "
                           "(%r); un-fusing to the two-stage "
                           "composition", exc)
            use_fused = False
        else:
            (coarse, sel, seed_scores, n_seed, sel2, need_scores,
             n_need) = unpack_fused_hybrid(packed, ndm, bucket, bucket2)
            maxvalues, stds, snrs = coarse[0], coarse[1], coarse[2]
            windows = np.rint(coarse[3]).astype(np.int32)
            peaks = np.rint(coarse[4]).astype(np.int64)
            cert_scores = coarse[5]
    if not use_fused:
        # ---- two-stage composition (plane capture / certificate mode /
        # forced A/B baseline): coarse program, scores mapped host-side
        # (a packed chunk rides through as raw bytes — the coarse
        # shard_map program unpacks in-body)
        coarse_out = sharded_fdmt_search(pf if pf is not None
                                         else data, dmmin, dmmax,
                                         start_freq, bandwidth,
                                         sample_time, mesh,
                                         axis="dm", with_cert=True,
                                         capture_plane=capture_plane)
        t_coarse, plane = (coarse_out if capture_plane
                           else (coarse_out, None))
        # coarse-table columns may still be device-backed; attribute the
        # conversion like every other coarse readback (putpu-lint
        # device-trip)
        with budget_bucket("search/coarse_readback"):
            idx = nearest_rows(np.asarray(t_coarse["DM"]), trial_dms)
            if plane is not None:
                plane = plane.remap(idx)  # coarse rows -> plan grid
            maxvalues = np.asarray(t_coarse["max"], np.float64)[idx]
            stds = np.asarray(t_coarse["std"], np.float64)[idx]
            snrs = np.asarray(t_coarse["snr"], np.float64)[idx]
            windows = np.asarray(t_coarse["rebin"], np.int32)[idx]
            peaks = np.asarray(t_coarse["peak"], np.int64)[idx]
            cert_scores = np.asarray(t_coarse["cert"], np.float64)[idx]
            budget_count("readbacks")

    coarse_snrs = snrs.copy()
    exact = np.zeros(ndm, dtype=bool)

    def _apply(blk, scored):
        m, s, b, w, p = scored
        k = len(blk)
        maxvalues[blk] = m[:k]
        stds[blk] = s[:k]
        snrs[blk] = b[:k]
        windows[blk] = w[:k]
        peaks[blk] = p[:k]
        exact[blk] = True

    def rescore(rows):
        """Escape hatch: exact scores via the sharded direct sweep —
        slices of the one cached offset table, pinned Pallas halo, and
        the pre-aligned device chunk (no per-bucket host work beyond
        the slice)."""
        budget_count("rescore_calls")
        budget_count("rescore_rows", len(rows))
        for blk, padded in iter_rescore_buckets(rows):
            t_ex = sharded_dedispersion_search(
                _data_rs(), dmmin, dmmax, start_freq, bandwidth, sample_time,
                mesh=mesh, trial_dms=trial_dms[padded],
                offsets=offsets_raw[padded],
                # the hatch must rescore with the SAME per-shard kernel
                # the fused program used (bit-identity contract) — an
                # independent kernel="auto" resolution at the bucket's
                # own geometry key could pick the other variant
                kernel=rescore_kernel,
                pallas_max_off=rescore_max_off)
            k = len(blk)
            _apply(blk, (np.asarray(t_ex["max"]), np.asarray(t_ex["std"]),
                         np.asarray(t_ex["snr"]),
                         np.asarray(t_ex["rebin"]),
                         np.asarray(t_ex["peak"])))

    if use_fused and n_seed <= bucket:
        # the device covered the loop's ENTIRE seed round; its scores are
        # the escape hatch's bit for bit (same per-shard kernel, channel
        # split and psum order), so the loop continues from the same
        # state the unfused path would reach.  A need stage that fit its
        # bucket likewise completes round 1; an overflowed stage is
        # discarded — the loop recomputes the full round itself.
        # roll_k=0 HERE: unlike the single-device fused kernel (which
        # scores the rebased plane and leaves the peak correction to
        # this unpack), the mesh kernel un-rotates in-kernel
        # (jnp.roll(dedisp, -roll_k) on the pallas rescore branch) to
        # stay bit-for-bit with the unfused sharded sweep — its peaks
        # arrive already in true coordinates, and subtracting roll_k
        # again would shift every seed/need arrival time on TPU meshes
        # (code-review r7)
        _apply(sel, fused_scores_to_host(seed_scores, 0, nsamples))
        seed_done = True
        if 0 < n_need <= bucket2:
            _apply(sel2, fused_scores_to_host(need_scores, 0, nsamples))

    certified, rho_cert_min = hybrid_certificate_gate(
        cert_scores, coarse_snrs, snrs, exact, rescore, nchan=nchan,
        trial_dms=trial_dms, start_freq=start_freq, bandwidth=bandwidth,
        sample_time=sample_time, nsamples=nsamples, snr_floor=snr_floor,
        noise_certificate=noise_certificate, seed_done=seed_done,
        rho_cert=rho_cert, cert_slack=cert_slack)
    table = ResultTable({
        "DM": trial_dms,
        "max": maxvalues,
        "std": stds,
        "snr": snrs,
        "rebin": windows,
        "peak": peaks,
        "exact": exact,
        "cert": cert_scores,
    }, meta=cert_meta(certified, rho_cert_min, snr_floor, cert_slack))
    return (table, plane) if capture_plane else table
