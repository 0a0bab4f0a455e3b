"""Pallas TPU kernel for the dedispersion sweep hot loop.

Why a hand-written kernel: the XLA lowering of ``take_along_axis`` along
the time (lane) axis scalarises on TPU — the batched-gather formulation of
the sweep (see :mod:`.dedisperse`) runs barely above single-core NumPy
speed.  This kernel restores the op to what it physically is — per-channel
*contiguous shifted reads* accumulated into each trial's series — which the
VPU executes at near HBM bandwidth.

Design (capability-equivalent of the reference's hot trio
``roll_and_sum`` / ``_dedisperse`` / ``_dedispersion_search`` inner loop,
``pulsarutils/dedispersion.py:60-98,174-202``, re-thought for TPU):

* All trial delays are bounded by the band-crossing delay ``max_off``, so
  an output time tile ``[t0, t0 + T_TILE)`` of any trial only ever reads
  input samples from ``[t0, t0 + T_TILE + max_off)`` — i.e. from ``K =
  ceil(max_off / T_TILE) + 1`` *adjacent, tile-aligned* input tiles.  That
  makes the data movement expressible with plain ``BlockSpec``s (the same
  array is passed K times at staggered tile indices); Pallas's pipeline
  machinery then double-buffers the HBM->VMEM streaming automatically.
* Circular wraparound (the reference's ``np.roll`` semantics) is handled
  by extending the array host-side with its own head: ``data_ext[c, t] =
  data[c, t mod T]`` for ``t < Text``.  Gather arithmetic inside the
  kernel is then purely linear.
* The per-(trial, channel) shifts arrive as an SMEM block of int32; the
  inner loop is ``out[d] += window[c, shift[d, c] : shift[d, c] + T_TILE]``
  realised as aligned vector loads plus dynamic rotates (Mosaic forbids
  unaligned vector loads).  Two layouts:

  - ``layout="rows"`` (default, ~3x faster): each time tile is viewed as
    ``(8, L)`` row chunks (row s = samples ``[s*L, (s+1)*L)``), so a
    shifted tile read at offset ``r = q*L + m`` is a 16-row aligned load,
    one lane-rotate by ``m``, one sublane-rotate by ``q mod 8``, and a
    two-row blend at the ``L - m`` lane boundary — every op uses all 8
    sublanes (measured ~150 Gadd/s on v5e vs ~50 for flat).
  - ``layout="flat"``: (1, t_tile + 128)-lane aligned load plus a sub-128
    lane-rotate per (trial, channel) — simpler, but each op occupies one
    sublane of the VPU.
* Grid is ``(dm_blocks, time_tiles, chan_blocks)`` with channels innermost
  so each output block stays resident in VMEM while all channel blocks
  accumulate into it.

The public entry is :func:`dedisperse_plane_pallas`; shape padding (trials
to the DM block, channels to the channel block, time to the tile) happens
host-side and is sliced away on return.
"""

from __future__ import annotations

import functools

import numpy as np

from ..utils.logging_utils import kernel_build_span


def _pallas_modules():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return jax, jnp, pl, pltpu


def _kernel_body(off_ref, *refs, dm_block, chan_block, t_tile, k_tiles,
                 jnp, pl, pltpu):
    """out[d, :] += sum_c window[c, off[d, c] : off[d, c] + t_tile]."""
    import jax

    data_refs = refs[:k_tiles]
    out_ref = refs[k_tiles]
    win_ref = refs[k_tiles + 1]

    i_c = pl.program_id(2)

    @pl.when(i_c == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # stitch the K adjacent tiles into one contiguous VMEM window
    for k in range(k_tiles):
        win_ref[:, k * t_tile:(k + 1) * t_tile] = data_refs[k][:]

    # Mosaic vector loads need lane starts provably 128-aligned, so the
    # unaligned shifted read is an aligned (t_tile + 128)-lane load plus a
    # dynamic sub-128 left-rotate (tpu.DynamicRotateOp via pltpu.roll)
    def body(d, carry):
        acc = out_ref[pl.ds(d, 1), :]
        for c in range(chan_block):
            start = off_ref[0, 0, d, c]
            aligned = pl.multiple_of((start // 128) * 128, 128)
            win = win_ref[pl.ds(c, 1), pl.ds(aligned, t_tile + 128)]
            # left-rotate by r = start - aligned, expressed as a
            # non-negative right-rotate — tpu.DynamicRotateOp mishandles
            # negative dynamic shifts (interpret mode accepts them)
            rolled = pltpu.roll(win, (t_tile + 128 - (start - aligned))
                                % (t_tile + 128), 1)
            acc = acc + rolled[:, :t_tile]
        out_ref[pl.ds(d, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, dm_block, body, 0)


def shifted_row_tile(win_ref, c, r, L, lane, jnp, pl, pltpu, q0=False):
    """Read ``window[r : r + 8L]`` as an (8, L) chunked tile.

    The circular-shift primitive shared by the rows-layout dedispersion
    kernel and the FDMT merge kernel: with ``r = q*L + m``, load 16
    window rows from the 8-aligned base (sublane starts must be provably
    8-aligned), lane-rotate left by ``m``, sublane-rotate up by
    ``q mod 8``, and blend each row with its successor at the ``L - m``
    lane boundary.  ``c`` indexes the leading dim of a 3-D window ref
    (``None`` for a 2-D ref); ``lane`` is a (8, L) lane iota.

    ``q0=True`` is the statically-known ``r < L`` fast path (every offset
    below one lane row, i.e. halo ``k_tiles == 2``): ``q = 0`` always, so
    the load base is static and the dynamic sublane rotate — a full
    16-row VPU op per (trial, channel) — is elided entirely (~1.3-1.5x
    on the benchmark geometry, whose band-crossing delay is < L = 1024).
    """
    if q0:
        rows16 = (win_ref[pl.ds(0, 16), :] if c is None
                  else win_ref[c, pl.ds(0, 16), :])
        rolled = pltpu.roll(rows16, (L - r) % L, 1)
        return jnp.where(lane < L - r, rolled[0:8], rolled[1:9])
    q = r // L
    m = r - q * L
    qa = pl.multiple_of((q // 8) * 8, 8)
    if c is None:
        rows16 = win_ref[pl.ds(qa, 16), :]
    else:
        rows16 = win_ref[c, pl.ds(qa, 16), :]
    rolled = pltpu.roll(rows16, (L - m) % L, 1)
    sr = pltpu.roll(rolled, (16 - (q - qa)) % 16, 0)
    return jnp.where(lane < L - m, sr[0:8], sr[1:9])


def _kernel_body_rows(off_ref, *refs, dm_block, chan_block, t_tile, k_tiles,
                      jnp, pl, pltpu):
    """Chunked-row variant: full-sublane ops.

    Each time tile is viewed as ``(8, L)`` with ``L = t_tile // 8`` (row s
    holds samples ``[s*L, (s+1)*L)``), so a shifted read of the whole tile
    at offset ``r = q*L + m`` is: load window rows ``q..q+8`` (9 rows),
    lane-rotate the block left by ``m``, and blend each row with its
    successor at the ``L - m`` lane boundary.  Every op runs on 8-sublane
    blocks — ~8x the VPU utilisation of the flat (1, t_tile) formulation.
    """
    import jax

    data_refs = refs[:k_tiles]
    out_ref = refs[k_tiles]
    win_ref = refs[k_tiles + 1]
    L = t_tile // 8
    q0 = k_tiles == 2  # halo of 2 tiles <=> every offset < L (see
    # _halo_tiles: (off // L + 23) // 8 == 2 iff off // L == 0)

    i_c = pl.program_id(2)

    @pl.when(i_c == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # stitch the K adjacent (8, L)-chunked tiles into one row window
    for k in range(k_tiles):
        win_ref[:, k * 8:(k + 1) * 8, :] = data_refs[k][:, 0]

    lane = jax.lax.broadcasted_iota(jnp.int32, (8, L), 1)

    def body(d, carry):
        acc = out_ref[d, 0]
        for c in range(chan_block):
            acc = acc + shifted_row_tile(win_ref, c, off_ref[0, 0, d, c],
                                         L, lane, jnp, pl, pltpu, q0=q0)
        out_ref[d, 0] = acc
        return carry

    jax.lax.fori_loop(0, dm_block, body, 0)


@functools.lru_cache(maxsize=64)
def _build_kernel_rows(ndm_p, nchan_p, t_ext, t_out, dm_block, chan_block,
                       t_tile, k_tiles, interpret):
    jax, jnp, pl, pltpu = _pallas_modules()

    n_dm = ndm_p // dm_block
    n_t = t_out // t_tile
    n_chan = nchan_p // chan_block
    n_src = t_ext // t_tile
    L = t_tile // 8

    data_specs = [
        pl.BlockSpec((chan_block, 1, 8, L),
                     functools.partial(lambda i_d, i_t, i_c, _k:
                                       (i_c, (i_t + _k) % n_src, 0, 0), _k=k))
        for k in range(k_tiles)
    ]
    off_spec = pl.BlockSpec((1, 1, dm_block, chan_block),
                            lambda i_d, i_t, i_c: (i_d, i_c, 0, 0),
                            memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((dm_block, 1, 8, L),
                            lambda i_d, i_t, i_c: (i_d, i_t, 0, 0))

    kernel = functools.partial(_kernel_body_rows, dm_block=dm_block,
                               chan_block=chan_block, t_tile=t_tile,
                               k_tiles=k_tiles, jnp=jnp, pl=pl, pltpu=pltpu)

    call = pl.pallas_call(
        kernel,
        grid=(n_dm, n_t, n_chan),
        in_specs=[off_spec] + data_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((ndm_p, n_t, 8, L), jnp.float32),
        scratch_shapes=[pltpu.VMEM((chan_block, k_tiles * 8, L),
                                   jnp.float32)],
        interpret=bool(interpret),
        name="dedisperse_rows",
    )

    @jax.jit
    def dedisperse_rows(offsets, data_ext):
        data_4d = data_ext.reshape(nchan_p, n_src, 8, L)
        out = call(offsets, *([data_4d] * k_tiles))
        return out.reshape(ndm_p, t_out)

    return dedisperse_rows


@functools.lru_cache(maxsize=64)
def _build_kernel(ndm_p, nchan_p, t_ext, t_out, dm_block, chan_block,
                  t_tile, k_tiles, interpret):
    jax, jnp, pl, pltpu = _pallas_modules()

    n_dm = ndm_p // dm_block
    n_t = t_out // t_tile
    n_chan = nchan_p // chan_block
    # number of time tiles in the source array; when it equals n_t (no
    # extension) the staggered reads wrap tile-modulo, which IS the exact
    # circular wrap because t_tile divides the array length
    n_src = t_ext // t_tile

    # the same (extended) array is passed K times at staggered tile
    # indices, giving the kernel a (chan_block, K * t_tile) contiguous
    # window
    data_specs = [
        pl.BlockSpec((chan_block, t_tile),
                     functools.partial(lambda i_d, i_t, i_c, _k:
                                       (i_c, (i_t + _k) % n_src), _k=k))
        for k in range(k_tiles)
    ]
    # Mosaic requires the last two block dims to be (8, 128)-divisible OR
    # equal to the array dims; a raw (dm_block, chan_block) window over the
    # (ndm, nchan) table satisfies neither, so the offsets arrive pre-tiled
    # as (n_dm, n_chan, dm_block, chan_block) and each grid step takes one
    # whole (dm_block, chan_block) tile — trailing dims == array dims.
    off_spec = pl.BlockSpec((1, 1, dm_block, chan_block),
                            lambda i_d, i_t, i_c: (i_d, i_c, 0, 0),
                            memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((dm_block, t_tile),
                            lambda i_d, i_t, i_c: (i_d, i_t))

    kernel = functools.partial(_kernel_body, dm_block=dm_block,
                               chan_block=chan_block, t_tile=t_tile,
                               k_tiles=k_tiles, jnp=jnp, pl=pl, pltpu=pltpu)

    call = pl.pallas_call(
        kernel,
        grid=(n_dm, n_t, n_chan),
        in_specs=[off_spec] + data_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((ndm_p, t_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((chan_block, k_tiles * t_tile),
                                   jnp.float32)],
        interpret=bool(interpret),
        name="dedisperse_flat",
    )

    @jax.jit
    def dedisperse_flat(offsets, data_ext):
        return call(offsets, *([data_ext] * k_tiles))

    return dedisperse_flat


def _pick_t_tile(max_off, nsamples, layout="flat"):
    """Default time tile: 8192 for the rows layout (measured optimum on
    v5e), else the smallest power-of-two >= 2048 covering the halo; capped
    so tiny inputs still work."""
    if layout == "rows":
        t_tile = 8192
    else:
        t_tile = 2048
        while t_tile < min(max_off, 1 << 15):
            t_tile *= 2
    return min(t_tile, max(256, 1 << int(np.floor(np.log2(max(nsamples, 256))))))


#: scoped-VMEM budget (bytes) the auto-blocking tries to stay under; the
#: hardware limit is 16 MB and the pipeline double-buffers in/out blocks
VMEM_BUDGET = 10 << 20


def _halo_tiles(max_off, t_tile, layout):
    """Number of staggered input tiles covering the shifted-read halo.

    One formula shared by the kernel builder and the VMEM fitter — the
    footprint model must match the kernel actually built.
    """
    if layout == "rows":
        l_lane = max(1, t_tile // 8)
        return (max_off // l_lane + 23) // 8
    return (max_off + 128) // t_tile + 2


def _fit_blocks_to_vmem(dm_block, chan_block, t_tile, max_off, layout):
    """Shrink blocking factors until the kernel's VMEM footprint fits.

    Footprint model: double-buffered data blocks (k_tiles * chan_block *
    t_tile), the stitched window scratch (same size), and double-buffered
    output blocks (dm_block * t_tile), all float32.
    """
    while True:
        k_tiles = _halo_tiles(max_off, t_tile, layout)
        win = chan_block * k_tiles * t_tile * 4
        data = 2 * k_tiles * chan_block * t_tile * 4
        out = 2 * dm_block * t_tile * 4
        if win + data + out <= VMEM_BUDGET:
            return dm_block, chan_block, t_tile
        if chan_block > 8:
            chan_block //= 2
        elif dm_block > 8:
            dm_block //= 2
        elif t_tile > 1024:
            t_tile //= 2
        else:
            return dm_block, chan_block, t_tile  # smallest legal; let
            # Mosaic report the real limit if this still does not fit


def rebase_offsets(offsets, nsamples):
    """Host-side offset rebase: wrapped ``[0, T)`` offsets -> small
    non-negative offsets plus a static rotation constant.

    ``normalize_shifts`` wraps negative (above-band-centre) shifts to values
    near ``T``, which would force the kernel's halo to span the whole array.
    Mapping back to signed form and subtracting the (128-aligned) minimum
    yields offsets bounded by the band-crossing span instead.  The kernel
    output is then the reference plane rotated by ``k``; rolling each row by
    ``-k`` restores it exactly (same floats, same summation order).

    Returns ``(offsets_rebased, k, max_off)`` — all host values.
    """
    offsets = np.asarray(offsets, dtype=np.int64)
    half = nsamples // 2
    signed = (offsets + half) % nsamples - half
    k = 128 * int(np.floor(signed.min(initial=0) / 128))
    rebased = (signed - k).astype(np.int32)
    return rebased, k, int(rebased.max(initial=0))


def dedisperse_plane_pallas_traced(data, offsets, max_off, dm_block=None,
                                   chan_block=None, t_tile=None,
                                   interpret=None, roll_k=0, layout="rows"):
    """Trace-friendly core of :func:`dedisperse_plane_pallas`.

    ``data`` and ``offsets`` may be traced jax arrays (e.g. shards inside a
    ``shard_map``); ``max_off`` must be a *static* host int bounding every
    offset (it sets the halo tile count, which is a compile-time property).
    ``roll_k`` is the static rotation constant from :func:`rebase_offsets`
    (the returned plane is rolled by ``-roll_k`` to undo the rebase).
    """
    jax, jnp, pl, pltpu = _pallas_modules()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    data = jnp.asarray(data, dtype=jnp.float32)
    offsets = jnp.asarray(offsets, dtype=jnp.int32)
    nchan, t = data.shape
    ndm = offsets.shape[0]

    max_off = int(max_off)
    if dm_block is None:
        dm_block = 32
    if chan_block is None:
        chan_block = 64
    if t_tile is None:
        t_tile = _pick_t_tile(max_off, t, layout)
    t_tile = int(min(t_tile, t))

    dm_block = int(min(dm_block, max(1, ndm)))
    chan_block = int(min(chan_block, nchan))
    if not interpret:
        # shrink (possibly caller-supplied) blockings that would overrun
        # scoped VMEM — a compile failure helps nobody
        dm_block, chan_block, t_tile = _fit_blocks_to_vmem(
            dm_block, chan_block, t_tile, max_off, layout)
        # Mosaic block rule: trailing block dims must be (8, 128)-divisible
        # or equal to the (padded) array dims.  dm_block/chan_block sit in
        # the sublane slot of their blocks; t_tile in the lane slot.  For
        # the rows layout the lane slot holds L = t_tile // 8, so compiled
        # rows tiles are at least 1024 (an explicit smaller t_tile is
        # honoured in interpret mode, where Mosaic rules don't apply).
        dm_block = max(8, -(-dm_block // 8) * 8)
        chan_block = max(8, -(-chan_block // 8) * 8)
        if layout == "rows":
            t_tile = max(1024, t_tile - t_tile % 1024)
        else:
            t_tile = max(128, t_tile - t_tile % 128)
    elif layout == "rows":
        # interpret mode: honour the requested tile, but the (8, L) row
        # view still needs t_tile divisible by 8
        t_tile = max(8, t_tile - t_tile % 8)

    # halo: rows layout reads window rows qa..qa+15 with qa = 8*(off//(8L));
    # flat layout loads (t_tile + 128) lanes from floor(off/128)*128
    k_tiles = _halo_tiles(max_off, t_tile, layout)

    # pad trials (duplicate last), channels (zeros), time (circular wrap)
    ndm_p = -(-ndm // dm_block) * dm_block
    if ndm_p != ndm:
        offsets = jnp.concatenate(
            [offsets, jnp.repeat(offsets[-1:], ndm_p - ndm, axis=0)])
    nchan_p = -(-nchan // chan_block) * chan_block
    if nchan_p != nchan:
        data = jnp.concatenate(
            [data, jnp.zeros((nchan_p - nchan, t), jnp.float32)])
        # padded channels read window start 0; they contribute zeros anyway
        offsets = jnp.concatenate(
            [offsets, jnp.zeros((ndm_p, nchan_p - nchan), jnp.int32)],
            axis=1)

    # pre-tile the offsets to the (n_dm, n_chan, dm_block, chan_block)
    # layout the kernel's SMEM BlockSpec expects (see _build_kernel)
    offsets = (offsets
               .reshape(ndm_p // dm_block, dm_block,
                        nchan_p // chan_block, chan_block)
               .transpose(0, 2, 1, 3))

    n_t = -(-t // t_tile)
    t_out = n_t * t_tile
    if t % t_tile == 0:
        # no extension: the staggered BlockSpec reads wrap tile-modulo,
        # which is the exact circular wrap when t_tile divides t — zero
        # extra HBM (the extension copy would double the footprint at the
        # 4 GB benchmark size)
        text = t
        data_ext = data
    else:
        # circular extension: data_ext[:, i] = data[:, i % t]
        text = (n_t + k_tiles - 1) * t_tile
        if text - t <= t:
            data_ext = jnp.concatenate([data, data[:, :text - t]], axis=1)
        else:
            reps = max(2, -(-text // t) + 1)
            data_ext = jnp.concatenate([data] * reps, axis=1)[:, :text]

    build = _build_kernel_rows if layout == "rows" else _build_kernel
    with kernel_build_span(f"dedisperse_{layout}", rows=ndm_p, t=t_out,
                           t_tile=t_tile):
        run = build(ndm_p, nchan_p, text, t_out, dm_block, chan_block,
                    t_tile, k_tiles, interpret)
        plane = run(offsets, data_ext)[:ndm, :t]
    if roll_k:
        plane = jnp.roll(plane, -roll_k, axis=1)
    return plane


def dedisperse_plane_pallas(data, offsets, dm_block=None, chan_block=None,
                            t_tile=None, interpret=None, layout="rows"):
    """Dedispersed plane ``out[d, t] = sum_c data[c, (t + off[d,c]) % T]``.

    Parameters
    ----------
    data : (nchan, T) float32 array (device or host)
    offsets : (ndm, nchan) int32 gather offsets — the per-channel DM delays
        in samples, wrapped into ``[0, T)`` (same convention as
        :func:`~pulsarutils_tpu.ops.dedisperse.dedisperse_block_jax`).
        Must be concrete (host) values; inside traced code use
        :func:`dedisperse_plane_pallas_traced` with a static ``max_off``.
    dm_block, chan_block : kernel blocking (trials per output block,
        channels accumulated per grid step).
    t_tile : time-tile length; default picked from the maximum offset.
    interpret : run in the Pallas interpreter.  Default (``None``) auto:
        compiled on TPU, interpreted elsewhere (CPU testing).

    Returns
    -------
    (ndm, T) float32 device array.
    """
    nsamples = int(np.shape(data)[1])
    offsets, roll_k, max_off = rebase_offsets(offsets, nsamples)
    return dedisperse_plane_pallas_traced(data, offsets, max_off,
                                          dm_block=dm_block,
                                          chan_block=chan_block,
                                          t_tile=t_tile, interpret=interpret,
                                          roll_k=roll_k, layout=layout)
