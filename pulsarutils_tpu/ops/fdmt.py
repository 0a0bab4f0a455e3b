"""Fast DM Transform (FDMT): tree dedispersion in O(nchan · T · log nchan).

The direct sweep costs ``O(ndm · nchan · T)`` shifted adds (reference
``pulsarutils/dedispersion.py:174-202``; our Pallas kernel).  The FDMT
(Zackay & Ofek 2017, ApJ 835:11) computes **every integer-delay trial at
once** by recursively merging adjacent frequency sub-bands: partial
dedispersed sums over a sub-band are reused by all trials that cross it,
collapsing the trial axis into ``log2(nchan)`` shift-and-add passes.  For
the benchmark geometry (1024 chan, 512-sample delay span) this is ~100x
fewer adds than the direct sweep.

Semantics and how they relate to the reference:

* The FDMT's natural trial grid IS the reference's plan (one trial per
  integer sample of band-crossing delay, ``dedispersion.py:149-171``):
  row ``N`` of the transform sums one sample per channel along the
  dispersion track whose differential delay across the full band is ``N``
  samples.  DM values are recovered with the same inversion the plan uses.
* Per-channel delays along a track are rounded *recursively* (each merge
  rounds the track's crossing of the sub-band boundary) instead of
  directly per channel, so individual channel delays can differ from the
  reference's ``rint(delay // tsamp)`` by ~1 sample (Zackay & Ofek §2.3
  bound the deviation).  Hit detection therefore agrees with the exact
  kernels to within a trial, but is not bit-identical — use
  ``kernel="pallas"`` when bit-exact parity with the NumPy reference path
  matters, ``kernel="fdmt"`` for throughput.
* Time shifts are circular (the reference's ``np.roll`` convention,
  ``dedispersion.py:60-98``), so no edge-validity bookkeeping is needed.
* Rows are anchored at the top of the band: row ``N`` equals the exact
  trial's series up to a small per-trial circular rotation (scores are
  rotation-invariant; the boxcar scorer sees windows shifted by a few
  samples, a sub-percent S/N effect).

Implementation notes (TPU):

* Each merge pass is ONE fused Pallas kernel launch: for every output row
  ``(band, Δ)`` it reads the two parent rows directly from the state
  array — row indices arrive via scalar-prefetch (the BlockSpec index
  maps read them from SMEM), so the XLA-level gather never materialises —
  applies the re-anchoring circular shift to the low-band row with the
  aligned-load + rotate + blend scheme of
  :mod:`.pallas_dedisperse` (chunked ``(8, L)`` row layout, full-sublane
  ops), adds, and writes the output tile.
* Off TPU (or for time axes no power-of-two tile divides) the same merge
  runs as an XLA ``take_along_axis`` + per-row roll fallback.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from ..obs.trace import build_span_name, span
from ..utils.logging_utils import kernel_build_span
from .plan import DM_DELAY_CONST, delta_delay


# ---------------------------------------------------------------------------
# Plan: per-iteration merge tables (host, numpy, static)
# ---------------------------------------------------------------------------

def _lam(f):
    return f ** -2.0


def pad_channels(nchan):
    """All-zero channels a sweep carries above a band of ``nchan`` to
    reach the tree's power of two (0 on a power-of-two band)."""
    return (1 << max(int(nchan) - 1, 0).bit_length()) - int(nchan)


class FdmtPlan:
    """Static merge schedule for one (nchan, geometry, delay-range) tuple.

    Attributes
    ----------
    iterations : list of dict with keys
        ``idx_low``, ``idx_high`` — (rows_out,) int32 flat parent-row
        indices into the previous state's row axis;
        ``shift`` — (rows_out,) int32 circular shift applied to the
        low-band parent row;
        ``shift_high`` — (rows_out,) int32 shift for the high parent
        (leaf merge only; ``None`` for deeper iterations);
        ``nbands``, ``ndelay`` — output layout (rows_out = sum(ndelay)).
    nchan_padded : channel count rounded up to a power of two (the extra
        channels are zero and contribute nothing).
    max_delay : largest differential band delay (inclusive) produced.
    min_delay : smallest band delay produced (DM-range pruning): the final
        state holds rows ``min_delay..max_delay`` only, and every earlier
        iteration allocates just the (contiguous) parent-delay window
        those rows reach through the recursion — for a search restricted
        to DM 300-635 (the benchmark config) this nearly halves the tree's
        rows, HBM traffic and adds versus the classic 0-anchored transform.
    """

    def __init__(self, nchan, start_freq, bandwidth, max_delay, min_delay=0):
        self.nchan = nchan
        self.max_delay = int(max_delay)
        self.min_delay = int(min_delay)
        if not 0 <= self.min_delay <= self.max_delay:
            raise ValueError(
                f"min_delay {min_delay} outside [0, {max_delay}]")
        nch2 = nchan + pad_channels(nchan)
        self.nchan_padded = nch2
        # zero-padded channels sit ABOVE the real band: they must not
        # stretch the physical frequency span, so give them zero bandwidth
        # by keeping the per-channel width of the real band
        df = bandwidth / nchan
        f_edge = lambda c: start_freq + min(c, nchan) * df  # noqa: E731
        maxn = self.max_delay

        # Flat row layout with PER-BAND delay counts, allocated top-down:
        # only the (band, delay) rows some final trial actually requests
        # exist.  (Padding every band to the bottom band's depth, or even
        # a uniform +1 slack per band, inflates the 1M-sample state past
        # HBM.)  The initial state is the raw data itself — one row per
        # channel, NO delay expansion: the first merge samples each
        # channel directly with per-parent shifts (``shift_high`` = the
        # track's delay at the high channel's lower edge, ``shift`` = at
        # the low channel's lower edge — the reference's frequency
        # convention, ``dedispersion.py:127,135``).  Deeper merges only
        # shift the low parent (the high parent is already anchored).
        # State rows: band-major, delay-minor, nd[b] slots for band b.

        # pass A (top-down): per-iteration band split fractions, then the
        # (contiguous) delay window each band is ever asked for.  Both the
        # min and max of the window propagate: dd increasing by 1 moves
        # dh = round(dd * frac) and dl = dd - dh by 0 or 1 each, so the
        # parent windows of a contiguous child window are contiguous too.
        widths = []
        w = 1
        while w < nch2:
            widths.append(w)
            w *= 2
        fracs = []  # fracs[i][b]: high-band share of band b's delay split
        for w in widths:
            nb = nch2 // (2 * w)
            fr = np.empty(nb)
            for b in range(nb):
                c0, c1, c2 = 2 * b * w, (2 * b + 1) * w, (2 * b + 2) * w
                w02 = _lam(f_edge(c0)) - _lam(f_edge(c2))
                w12 = _lam(f_edge(c1)) - _lam(f_edge(c2))
                fr[b] = w12 / w02 if w02 > 0 else 0.0
            fracs.append(fr)
        used = [None] * (len(widths) + 1)
        used_min = [None] * (len(widths) + 1)
        used[-1] = np.asarray([maxn])  # final band serves Δ = minn..maxn
        used_min[-1] = np.asarray([self.min_delay])
        for i in range(len(widths) - 1, 0, -1):
            u_out, u_out_min = used[i + 1], used_min[i + 1]
            nb = len(u_out)
            u_in = np.zeros(2 * nb, np.int64)
            u_in_min = np.zeros(2 * nb, np.int64)
            for b in range(nb):
                dd = np.arange(u_out_min[b], u_out[b] + 1)
                dh = np.round(dd * fracs[i][b]).astype(np.int64)
                dl = dd - dh
                u_in[2 * b], u_in_min[2 * b] = dl.max(), dl.min()
                u_in[2 * b + 1], u_in_min[2 * b + 1] = dh.max(), dh.min()
            used[i], used_min[i] = u_in, u_in_min

        # pass B (bottom-up): flat index tables over the allocated rows
        # (row layout: band-major, delay-minor, band b holding delays
        # used_min[b]..used[b] inclusive)
        self.iterations = []
        nd_in = [1] * nch2       # the raw channels
        min_in = [0] * nch2
        for i, w in enumerate(widths):
            u_out, u_out_min = used[i + 1], used_min[i + 1]
            nd_out = [int(u_out[b] - u_out_min[b]) + 1
                      for b in range(len(u_out))]
            in_off = np.concatenate([[0], np.cumsum(nd_in)])
            out_rows = int(np.sum(nd_out))
            idx_low = np.empty(out_rows, np.int32)
            idx_high = np.empty(out_rows, np.int32)
            shift = np.empty(out_rows, np.int32)
            shift_high = np.zeros(out_rows, np.int32) if i == 0 else None
            pos = 0
            for b in range(len(nd_out)):
                dd = np.arange(u_out_min[b], u_out[b] + 1)
                dh = np.round(dd * fracs[i][b]).astype(np.int64)
                dl = dd - dh
                if i == 0:
                    # leaf merge: parents are raw channel rows, sampled
                    # at the track's delay at their lower edges (relative
                    # to the pair's top edge): high -> dh, low -> dd
                    idx_low[pos:pos + len(dd)] = in_off[2 * b]
                    idx_high[pos:pos + len(dd)] = in_off[2 * b + 1]
                    shift[pos:pos + len(dd)] = dd
                    shift_high[pos:pos + len(dd)] = dh
                else:
                    assert dh.min() >= min_in[2 * b + 1], (i, b)
                    assert dh.max() - min_in[2 * b + 1] < nd_in[2 * b + 1], \
                        (i, b)
                    assert dl.min() >= min_in[2 * b], (i, b)
                    assert dl.max() - min_in[2 * b] < nd_in[2 * b], (i, b)
                    idx_low[pos:pos + len(dd)] = (in_off[2 * b]
                                                  + dl - min_in[2 * b])
                    idx_high[pos:pos + len(dd)] = (in_off[2 * b + 1]
                                                   + dh - min_in[2 * b + 1])
                    shift[pos:pos + len(dd)] = dh
                pos += len(dd)
            self.iterations.append({
                "idx_low": idx_low,
                "idx_high": idx_high,
                "shift": shift,
                "shift_high": shift_high,
                "nbands": len(nd_out),
                "ndelay": nd_out,
            })
            nd_in = nd_out
            min_in = [int(m) for m in u_out_min]


@functools.lru_cache(maxsize=32)
def fdmt_plan(nchan, start_freq, bandwidth, max_delay, min_delay=0):
    """Cached :class:`FdmtPlan` (all-static inputs)."""
    return FdmtPlan(nchan, start_freq, bandwidth, max_delay, min_delay)


def compose_iterations(it_a, it_b):
    """Fuse two consecutive deep merge iterations into one 4-parent pass.

    With ``state_b[q] = state[ih_a[q]] + roll(state[il_a[q]], s_a[q])``
    and ``out[r] = state_b[ih_b[r]] + roll(state_b[il_b[r]], s_b[r])``,
    substituting gives (roll composition is additive, circular):

    ``out[r] = state[ih_a[ih_b[r]]]
             + roll(state[il_a[ih_b[r]]], s_a[ih_b[r]])
             + roll(state[ih_a[il_b[r]]], s_b[r])
             + roll(state[il_a[il_b[r]]], s_b[r] + s_a[il_b[r]])``

    — the intermediate state never exists, trading one full write + read
    of ``state_b`` (the larger of the deep states) for two extra parent
    reads per output row (round 5, VERDICT r4 #3 deep-level fusion).
    Leaf iterations (``shift_high`` set) cannot be composed this way.

    Returns ``(idx, shift)``: lists of four ``(rows_out,)`` int32 arrays
    (parent row indices / circular shifts; parent 0's shift is 0).
    """
    if it_a["shift_high"] is not None or it_b["shift_high"] is not None:
        raise ValueError("compose_iterations requires deep (post-leaf) "
                         "iterations")
    ih_b, il_b, s_b = it_b["idx_high"], it_b["idx_low"], it_b["shift"]
    ih_a, il_a, s_a = it_a["idx_high"], it_a["idx_low"], it_a["shift"]
    idx = [ih_a[ih_b], il_a[ih_b], ih_a[il_b], il_a[il_b]]
    shift = [np.zeros_like(s_b), s_a[ih_b], s_b, s_b + s_a[il_b]]
    return ([np.ascontiguousarray(i, np.int32) for i in idx],
            [np.ascontiguousarray(s, np.int32) for s in shift])


def fdmt_tracks(plan, dtype=np.int64):
    """The effective dispersion track of every final transform row.

    Walks the plan's merge tables with an offset accumulator instead of
    data: row ``r`` of the transform computes exactly
    ``out[t] = sum_c data[c, (t + tracks[r, c]) mod T]`` (the same gather
    convention as the exact kernels, :mod:`.dedisperse`), so comparing
    ``tracks`` against :func:`~pulsarutils_tpu.ops.plan.dedispersion_shifts`
    gives the tree's per-channel track rounding *exactly* — no data, no
    noise, no device.  Consumers: the hybrid's per-config retention bound
    (:mod:`.certify`) and the track-deviation tests.

    Returns int64 ``(rows_final, nchan_padded)``; rows are the plan's
    ``min_delay..max_delay`` delay slice, columns ``>= plan.nchan`` belong
    to zero-padded channels (no data flows through them — slice them off
    before comparing).  ``dtype=np.int32`` halves the array for a caller
    that gathers from it (a track is a band delay, ``<= max_delay``).
    """
    # a row's track is carried over the band it covers and nowhere else:
    # the state entering a level is (rows, band width), and an output row
    # is its low parent's track beside its high parent's (the low parent
    # covers the lower half of the output band's channels)
    tracks = np.zeros((plan.nchan_padded, 1), dtype)
    for it in plan.iterations:
        width = tracks.shape[1]
        out = np.empty((len(it["idx_low"]), 2 * width), dtype)
        np.add(tracks[it["idx_low"]], it["shift"][:, None],
               out=out[:, :width])
        if it["shift_high"] is None:
            out[:, width:] = tracks[it["idx_high"]]
        else:
            np.add(tracks[it["idx_high"]], it["shift_high"][:, None],
                   out=out[:, width:])
        tracks = out
    assert tracks.shape[1] == plan.nchan_padded, \
        "final band must cover every channel"
    return tracks


def max_band_delay(nchan, dmmax, start_freq, bandwidth, sample_time):
    """Largest integer band-crossing delay for ``dmmax`` (plan row count)."""
    return int(np.ceil(
        delta_delay(float(dmmax), start_freq, start_freq + bandwidth)
        / sample_time))


# ---------------------------------------------------------------------------
# Merge executors
# ---------------------------------------------------------------------------

def _merge_xla(state, idx_low, idx_high, shift, shift_high=None):
    """Portable merge: row gathers + per-row circular roll via gather."""
    import jax.numpy as jnp

    t = state.shape[-1]
    low = state[idx_low]                      # (rows_out, T)
    high = state[idx_high]
    tidx = jnp.arange(t, dtype=jnp.int32)
    gather = (tidx[None, :] + shift[:, None]) % t
    low = jnp.take_along_axis(low, gather, axis=1)
    if shift_high is not None:
        gather_h = (tidx[None, :] + shift_high[:, None]) % t
        high = jnp.take_along_axis(high, gather_h, axis=1)
    return high + low


def _pick_fdmt_tile(t):
    """Largest power-of-two tile in [1024, 8192] dividing ``t`` (0 if none).

    The kernel accepts any power-of-two tile dividing ``t``; VMEM limits
    the (tile x MERGE_ROW_BLOCK) product, and on the v5e the largest
    tile won every sweep (8192 >> 4096 >> 2048).
    """
    for t_tile in (8192, 4096, 2048, 1024):
        if t % t_tile == 0:
            return t_tile
    return 0


def _padded_length(t):
    """The time axis the Pallas merges run on: ``t`` where one of their
    tiles divides it, else the next multiple of 1024."""
    return t if _pick_fdmt_tile(t) else -(-t // 1024) * 1024


def _transform_setup(data, use_pallas):
    """Resolve the Pallas/XLA choice and tile for a time axis of length T.

    When the Pallas path is wanted but no power-of-two tile divides T,
    the data is zero-padded to the next multiple of 1024 (the XLA gather
    fallback scalarises on TPU); circular wraps then cross the short zero
    pad — an edge effect of the same order as the tree's track rounding.
    The caller slices outputs back to ``t_orig``.

    Returns ``(data, t_run, t_tile, use_pallas, interpret, t_orig)``.
    """
    import jax
    import jax.numpy as jnp

    t = data.shape[1]
    t_run = t
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        t_run = _padded_length(t)
        if t_run != t:
            data = jnp.pad(data, ((0, 0), (0, t_run - t)))
    t_tile = _pick_fdmt_tile(t_run)
    return (data, t_run, t_tile, bool(use_pallas),
            jax.default_backend() != "tpu", t)


#: output rows processed per merge-kernel grid step; amortises the
#: per-step Pallas/DMA orchestration overhead (the kernel is otherwise
#: grid-overhead-bound: one row per step = ~1.4M steps per transform).
#: Chosen by the v5e sweep at 1024 x 1M with the DM-pruned plan: 32 @
#: tile 8192 = 0.352 s vs 8 = 0.394 s; 64 @ 8192 exhausts scoped VMEM.
MERGE_ROW_BLOCK = 32


#: bytes of double-buffered parent windows one grid step of the paired
#: deep pass may ask for (:func:`_merge4_pallas`)
DEEP_PAIR_VMEM_BYTES = 12 << 20


def _state_tiles(state, t_tile):
    """A state in the layout the merge kernels read and write,
    ``(rows, t / t_tile, 8, t_tile / 8)``: from the flat ``(rows, t)``,
    or from the head's ``(rows, t / 256, 256)`` plane — a fixed
    permutation of 128-sample blocks inside each ``t_tile``, ONE
    relayout on the chip either way (``tests/test_chip_compile.py``
    holds the compiled sweep to it)."""
    return state.reshape(state.shape[0], -1, 8, t_tile // 8)


@functools.lru_cache(maxsize=64)
def _build_merge_kernel(rows_out, t, t_tile, k_tiles, k_tiles_h,
                        row_block, interpret):
    """Fused FDMT merge: ``out[r] = roll(high[ih[r]], sh[r]) +
    roll(low[il[r]], s[r])``, ``row_block`` rows per grid step.

    ``k_tiles_h = 0`` compiles the common asymmetric form (high parent
    read aligned, no rotation) used by every iteration except the leaf
    merge.  ``rows_out`` must be a multiple of ``row_block`` (callers pad
    the tables; padded rows write copies of the last real row, which no
    later table indexes).  State in and out are :func:`_state_tiles`
    arrays, of any row count in: consecutive stages hand the state on
    with no relayout and no row slice between them.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .pallas_dedisperse import shifted_row_tile

    L = t_tile // 8
    n_t = t // t_tile
    kh = max(1, k_tiles_h)

    def shifted_tile(win_ref, r, lane, jnp, pl, pltpu, q0):
        return shifted_row_tile(win_ref, None, r, L, lane, jnp, pl, pltpu,
                                q0=q0)

    def kernel(idx_low_ref, idx_high_ref, shift_ref, shift_high_ref,
               *refs):
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, L), 1)
        nin = row_block * (k_tiles + kh)
        out_ref = refs[nin]
        win_ref = refs[nin + 1]
        win_h_ref = refs[nin + 2] if k_tiles_h else None
        i_r = pl.program_id(0)

        for j in range(row_block):
            low_refs = refs[j * k_tiles:(j + 1) * k_tiles]
            high_refs = refs[row_block * k_tiles + j * kh:
                             row_block * k_tiles + (j + 1) * kh]
            # stitch the low-band row's staggered (8, L) chunks
            for k in range(k_tiles):
                win_ref[k * 8:(k + 1) * 8, :] = low_refs[k][0, 0]
            low_tile = shifted_tile(win_ref, shift_ref[i_r * row_block + j],
                                    lane, jnp, pl, pltpu, k_tiles == 2)
            if k_tiles_h:
                for k in range(k_tiles_h):
                    win_h_ref[k * 8:(k + 1) * 8, :] = high_refs[k][0, 0]
                high_tile = shifted_tile(
                    win_h_ref, shift_high_ref[i_r * row_block + j], lane,
                    jnp, pl, pltpu, k_tiles_h == 2)
            else:
                high_tile = high_refs[0][0, 0]
            out_ref[j, 0] = high_tile + low_tile

    # scalar-prefetch index maps: parent rows are chosen per grid step by
    # the prefetched tables, so no gathered copy of the state is ever
    # materialised
    def low_spec(j, k):
        return pl.BlockSpec(
            (1, 1, 8, L),
            functools.partial(lambda i_r, i_t, il, ih, sh, shh, _j, _k:
                              (il[i_r * row_block + _j],
                               (i_t + _k) % n_t, 0, 0), _j=j, _k=k))

    def high_spec(j, k):
        return pl.BlockSpec(
            (1, 1, 8, L),
            functools.partial(lambda i_r, i_t, il, ih, sh, shh, _j, _k:
                              (ih[i_r * row_block + _j],
                               (i_t + _k) % n_t, 0, 0), _j=j, _k=k))

    low_specs = [low_spec(j, k) for j in range(row_block)
                 for k in range(k_tiles)]
    high_specs = [high_spec(j, k) for j in range(row_block)
                  for k in range(kh)]
    out_spec = pl.BlockSpec(
        (row_block, 1, 8, L),
        lambda i_r, i_t, il, ih, sh, shh: (i_r, i_t, 0, 0))

    scratch = [pltpu.VMEM((k_tiles * 8, L), jnp.float32)]
    if k_tiles_h:
        scratch.append(pltpu.VMEM((k_tiles_h * 8, L), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(rows_out // row_block, n_t),
        in_specs=low_specs + high_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct(
                              (rows_out, n_t, 8, L), jnp.float32),
                          interpret=bool(interpret), name="fdmt_merge")

    def fdmt_merge(s4, idx_low, idx_high, shift, shift_high):
        n_in = row_block * (k_tiles + kh)
        return call(idx_low, idx_high, shift, shift_high, *([s4] * n_in))

    return fdmt_merge


@functools.lru_cache(maxsize=16)
def _build_merge4_kernel(rows_out, t, t_tile, k_tiles, row_block,
                         interpret):
    """Fused two-level FDMT merge: ``out[r] = sum_p roll(state[idx_p[r]],
    shift_p[r])`` over 4 parents (:func:`compose_iterations`).

    Same scalar-prefetch scheme as :func:`_build_merge_kernel`, with one
    shared ``k_tiles`` bound covering every composed shift (parent 0's
    shift is 0; the rotate machinery handles it without a special
    case).  ``rows_out`` must be a multiple of ``row_block``; state in
    and out are :func:`_state_tiles` arrays.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .pallas_dedisperse import shifted_row_tile

    L = t_tile // 8
    n_t = t // t_tile
    P = 4

    def kernel(*refs):
        idx_refs = refs[:P]          # scalar-prefetch (unused directly)
        shift_refs = refs[P:2 * P]
        data_refs = refs[2 * P:2 * P + row_block * P * k_tiles]
        out_ref = refs[2 * P + row_block * P * k_tiles]
        win_ref = refs[2 * P + row_block * P * k_tiles + 1]
        del idx_refs
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, L), 1)
        i_r = pl.program_id(0)

        for j in range(row_block):
            tiles = []
            for p in range(P):
                base = (j * P + p) * k_tiles
                for k in range(k_tiles):
                    win_ref[k * 8:(k + 1) * 8, :] = \
                        data_refs[base + k][0, 0]
                tiles.append(shifted_row_tile(
                    win_ref, None, shift_refs[p][i_r * row_block + j], L,
                    lane, jnp, pl, pltpu, q0=(k_tiles == 2)))
            # PAIRWISE association — bit-identical to the two per-level
            # merges it replaces: parent pairs (0,1) and (2,3) are the
            # two level-a outputs (the roll distributes exactly over the
            # inner add), and the outer add is level b's
            out_ref[j, 0] = (tiles[0] + tiles[1]) + (tiles[2] + tiles[3])

    def data_spec(j, p, k):
        return pl.BlockSpec(
            (1, 1, 8, L),
            functools.partial(
                lambda i_r, i_t, i0, i1, i2, i3, s0, s1, s2, s3, _j, _p,
                _k: ((i0, i1, i2, i3)[_p][i_r * row_block + _j],
                     (i_t + _k) % n_t, 0, 0), _j=j, _p=p, _k=k))

    data_specs = [data_spec(j, p, k) for j in range(row_block)
                  for p in range(P) for k in range(k_tiles)]
    out_spec = pl.BlockSpec(
        (row_block, 1, 8, L),
        lambda i_r, i_t, i0, i1, i2, i3, s0, s1, s2, s3: (i_r, i_t, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(rows_out // row_block, n_t),
        in_specs=data_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((k_tiles * 8, L), jnp.float32)],
    )
    call = pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct(
                              (rows_out, n_t, 8, L), jnp.float32),
                          interpret=bool(interpret),
                          name="fdmt_deep_pair")

    def fdmt_deep_pair(s4, idx, shift):
        n_in = row_block * P * k_tiles
        return call(*idx, *shift, *([s4] * n_in))

    return fdmt_deep_pair


def _merge4_pallas(s4, idx, shift, t_tile, interpret):
    """Run one composed 4-parent merge pass (host-side table prep) on a
    :func:`_state_tiles` state; the output keeps the rows the row block
    pads to, after the real ones."""
    import jax.numpy as jnp

    t = s4.shape[1] * t_tile
    rows_out = len(idx[0])
    # the 4-parent kernel carries 4x the BlockSpec operands per row, so
    # its row block is kept smaller than MERGE_ROW_BLOCK to bound both
    # operand count and per-step VMEM
    row_block = min(max(1, MERGE_ROW_BLOCK // 2), rows_out)
    L = t_tile // 8
    max_shift = max(
        int(s.max(initial=0))  # putpu-lint: disable=device-trip — host
        for s in shift)
    k_tiles = (max_shift // L + 23) // 8
    # a step's parent windows, double-buffered, must fit the core's
    # scoped VMEM (16 MiB): 16 rows x 4 parents x 3 tiles of 32 KiB is
    # what every band up to MeerTRAP's asks (12 MiB); CHIME's deepest
    # shifts reach a fourth tile (17.1 MiB asked, refused by the v5e's
    # compiler), so such a pass takes its rows eight at a time
    while row_block > 1 and row_block * 4 * k_tiles * t_tile * 4 * 2 \
            > DEEP_PAIR_VMEM_BYTES:
        row_block //= 2
    pad = (-rows_out) % row_block
    with kernel_build_span("fdmt_deep_pair", rows=rows_out + pad, t=t,
                           t_tile=t_tile):
        idx_p = [np.concatenate([i, i[-1:].repeat(pad)]) for i in idx]
        shift_p = [np.concatenate([s, s[-1:].repeat(pad)]) for s in shift]
        run = _build_merge4_kernel(rows_out + pad, t, t_tile, k_tiles,
                                   row_block, interpret)
        return run(s4, tuple(jnp.asarray(i) for i in idx_p),
                   tuple(jnp.asarray(s) for s in shift_p))


def _merge_tiles(s4, idx_low, idx_high, shift, shift_high, k_tiles,
                 k_tiles_h, t_tile, interpret):
    """One merge pass, :func:`_state_tiles` state in and out, tables
    already padded to the row block."""
    rows_out = idx_low.shape[0]
    run = _build_merge_kernel(rows_out, s4.shape[1] * t_tile, t_tile,
                              k_tiles, k_tiles_h,
                              min(MERGE_ROW_BLOCK, rows_out), interpret)
    return run(s4, idx_low, idx_high, shift, shift_high)


def merge_rows_traced(state, idx_low, idx_high, shift, shift_high, *,
                      k_tiles, k_tiles_h, t_tile, interpret):
    """One Pallas merge pass with *traced* (runtime) tables.

    The tables arrive as jax arrays — they ride the scalar-prefetch
    operands, so the same compiled program serves different merge
    schedules of identical shape (the sharded FDMT ships each device its
    own tables through ``shard_map``).  ``k_tiles``/``k_tiles_h`` must be
    static bounds covering every shift value; row count must already be
    a multiple of :data:`MERGE_ROW_BLOCK` (or smaller than it).  Flat
    ``(rows, t)`` states in and out: a relayout either side of the
    kernel, which the single-device sweep's chain of
    :func:`_state_tiles` states does not pay.
    """
    t = state.shape[1]
    rows_out = idx_low.shape[0]
    out = _merge_tiles(_state_tiles(state, t_tile), idx_low, idx_high,
                       shift, shift_high, k_tiles, k_tiles_h, t_tile,
                       interpret)
    return out.reshape(rows_out, t)


def _merge_pallas(s4, it, t_tile, interpret):
    """One per-level merge pass on a :func:`_state_tiles` state; the
    output keeps the rows the row block pads to, after the real ones."""
    import jax.numpy as jnp

    rows_out = len(it["idx_low"])
    row_block = min(MERGE_ROW_BLOCK, rows_out)
    pad = (-rows_out) % row_block
    with kernel_build_span("fdmt_merge", rows=rows_out + pad,
                           t=s4.shape[1] * t_tile, t_tile=t_tile):
        L = t_tile // 8
        max_shift = int(  # putpu-lint: disable=device-trip — host tables
            it["shift"].max(initial=0))
        k_tiles = (max_shift // L + 23) // 8

        idx_low = np.concatenate([it["idx_low"],
                                  it["idx_low"][-1:].repeat(pad)])
        idx_high = np.concatenate([it["idx_high"],
                                   it["idx_high"][-1:].repeat(pad)])
        shift = np.concatenate([it["shift"], it["shift"][-1:].repeat(pad)])

        if it["shift_high"] is not None:
            max_sh = int(  # putpu-lint: disable=device-trip — host tables
                it["shift_high"].max(initial=0))
            k_tiles_h = (max_sh // L + 23) // 8
            shift_high = np.concatenate([it["shift_high"],
                                         it["shift_high"][-1:].repeat(pad)])
        else:
            k_tiles_h = 0
            shift_high = np.zeros(rows_out + pad, np.int32)
        return _merge_tiles(s4, jnp.asarray(idx_low), jnp.asarray(idx_high),
                            jnp.asarray(shift), jnp.asarray(shift_high),
                            k_tiles, k_tiles_h, t_tile, interpret)


def head_active(nchan, start_freq, bandwidth, max_delay, n_lo, t):
    """True iff the fused head WILL run for this transform config: what
    any A/B harness (a head-vs-per-level parity check on hardware) must
    ask — a hand-replicated copy of :func:`_head_choice`'s conditions
    could silently diverge and turn the A/B vacuous.
    """
    return _head_choice(nchan, start_freq, bandwidth, max_delay, n_lo,
                        t) is not None


def _head_choice(nchan, start_freq, bandwidth, max_delay, n_lo, t):
    """``(head plan, time slice)`` of the fused head for this transform
    config, or None where the geometry does not fit it.  THE eligibility
    gate: `_transform_fn` builds the head from it, :func:`head_active`
    and :func:`coarse_head_tiles` report it."""
    return _head_verdict(nchan, start_freq, bandwidth, max_delay, n_lo,
                         t)[0]


def _head_verdict(nchan, start_freq, bandwidth, max_delay, n_lo, t):
    """``(choice, reason, smem bytes)``: :func:`_head_choice`'s answer,
    why it is None where it is (``"shape"``: channels, levels or time
    axis; ``"halo"``; ``"shift"``; ``"smem"``: the tables outgrow the
    core's scalar memory) and the SMEM the head's tables need (0 where
    the shape rules a head out).  ``halo`` is judged at the slice the
    kernel would run at (:func:`~.fdmt_resident.pick_head_t_slice`, which
    divides the time axis by construction), not at the 2,048-sample floor:
    the floor only says whether a head can exist at all (``shape``).
    Declining leaves the sweep to the per-level merges: no geometry ends
    in the compiler's refusal."""
    from .fdmt_resident import (
        HEAD_LEVELS,
        _head_plan_cached,
        head_smem_bytes,
        head_smem_limit,
        head_supported,
        pick_head_t_slice,
    )

    plan = fdmt_plan(nchan, start_freq, bandwidth, max_delay, n_lo)
    shape = (plan.nchan_padded, len(plan.iterations), t)
    if not head_supported(*shape):
        return None, "shape", 0
    hp = _head_plan_cached(nchan, start_freq, bandwidth, max_delay, n_lo,
                           HEAD_LEVELS)
    smem = head_smem_bytes(hp)
    t_slice = pick_head_t_slice(hp, t)
    if not head_supported(*shape, t_slice=t_slice, halo=hp.halo):
        return None, "halo", smem
    if not head_supported(*shape,
                          max_level_shift=max(hp.max_shift_per_level)):
        return None, "shift", smem
    if smem > head_smem_limit():
        return None, "smem", smem
    return (hp, t_slice), None, smem


def coarse_head_tiles(nchan, nsamples, dmmin, dmmax, start_freq, bandwidth,
                      sample_time, delays=None):
    """``(computed, useful, declined, smem bytes)`` of the fused head in
    ONE coarse sweep of the ``fdmt``/``hybrid`` kernels over these
    arguments on this backend — the geometry
    ``ops/search.py:_search_jax_fdmt`` resolves, through the same
    functions: the (8, 256) tiles, why :func:`_head_choice` declined
    (None where the head runs) and the SMEM its tables take;
    ``(0, 0, None, 0)`` where the Pallas merges are off.  ``delays`` is
    the ``(n_lo, n_hi)`` of a sweep over one delay band of that range
    (a tier in delay bands, ``ops/search.py:_search_jax_fdmt_tiled``).
    """
    import jax

    from .fdmt_resident import head_tile_counts

    if jax.default_backend() != "tpu":
        return 0, 0, None, 0
    _, n_lo, n_hi = fdmt_trial_dms(nchan, dmmin, dmmax, start_freq,
                                   bandwidth, sample_time)
    if delays is not None:
        n_lo, n_hi = delays
    t_run = _padded_length(nsamples)
    choice, declined, smem = _head_verdict(
        nchan, float(start_freq), float(bandwidth), n_hi, n_lo, t_run)
    tiles = head_tile_counts(choice[0], t_run, choice[1]) if choice else (0, 0)
    return tiles + (declined, smem)


@functools.lru_cache(maxsize=16)
def _transform_fn(nchan, start_freq, bandwidth, max_delay, t, t_tile,
                  use_pallas, interpret, n_lo=0, with_scores=False,
                  with_plane=True, t_orig=None, with_cert=False, *,
                  use_head=None, use_score=None, deep_pair=None,
                  windows=None, partial=None):
    """The traceable (un-jitted) transform body: DM-pruned merges
    [+ scoring].  :func:`_build_transform` wraps it in ``jax.jit``;
    the hybrid search composes it with its fused seed-rescore program
    (``ops/search.py:_fused_hybrid_seed_kernel``) instead.

    The plan is built with ``min_delay = n_lo`` (see :class:`FdmtPlan`),
    so rows below the searched DM range are never computed — the final
    state IS rows ``n_lo..max_delay``.  Fusing the scorer into the
    program keeps the live set between calls near zero — returning the
    full state keeps gigabytes alive and OOMs back-to-back searches at
    the 1M-sample size.

    THE place the sweep's shape is decided, from what it observes: the
    fused head and the paired deep pass wherever the Pallas merges run
    and the geometry fits, the one-pass scorer where those kernels are
    compiled (interpret-mode Pallas is minutes-slow) and a tile divides
    the time axis.  ``use_head``/``use_score``/``deep_pair`` are the
    tests' seam — ``None`` resolves, a bool forces the variant so a
    parity test can build both sides in one process; nothing outside
    ``tests/`` passes one.  ``windows`` is the scorer's ladder (static;
    ``None`` = the default four): the one-pass scorer runs where one of
    its tiles is a multiple of the ladder's widest window.
    """
    import jax.numpy as jnp

    # host work that no build phase of JAX's holds: the merge tables, the
    # head's choice, plan and kernel, the deep pair's composed tables
    with span(build_span_name("plan", "fdmt"), nchan=nchan, t=t,
              rows=max_delay - n_lo + 1):
        plan = fdmt_plan(nchan, start_freq, bandwidth, max_delay, n_lo)
        if use_head is None:
            use_head = use_pallas
        if deep_pair is None:
            deep_pair = use_pallas
        if use_score is None:
            use_score = use_pallas and not interpret

        # VMEM-resident fused head (ops/fdmt_resident.py): the first
        # HEAD_LEVELS merges — ~75% of the per-level HBM traffic — run in
        # one Pallas program whose intermediate states never leave VMEM,
        # bit-identical to the per-level path (v5e, 1024 x 1M: 0.323 s vs
        # 0.365 s per-level, transform+score).
        head_run = head = None
        n_head = 0
        head_choice = use_head and _head_choice(nchan, start_freq, bandwidth,
                                                max_delay, n_lo, t)
        if head_choice:
            from .fdmt_resident import (HEAD_LEVELS, _build_head_kernel,
                                        head_flat_rows, head_plane_rows)

            head_run, head = _build_head_kernel(
                nchan, start_freq, bandwidth, max_delay, n_lo,
                HEAD_LEVELS, t, head_choice[1], interpret)
            n_head = HEAD_LEVELS

        # deep-level pairing: fuse the LAST TWO per-level merges into one
        # 4-parent pass — the intermediate state (the largest deep state)
        # is never written or re-read (v5e, 1024 x 1M: 0.241 s -> 0.229 s).
        # Pallas path only; leaf merges (shift_high) cannot compose.
        iters = plan.iterations[n_head:]
        paired = None
        if (deep_pair and use_pallas and len(iters) >= 2
                and iters[-1]["shift_high"] is None
                and iters[-2]["shift_high"] is None):
            paired = compose_iterations(iters[-2], iters[-1])
            iters = iters[:-2]

        # the Pallas stage after the head reads the head's plane as the head
        # leaves it, each group's rows padded to the row loop's block: its
        # parent tables are rebased onto plane rows here, on the host, so no
        # gather and no row slice of the state runs on the device
        if head and use_pallas:
            plane_rows = head_plane_rows(head)
            if iters:
                iters = [dict(iters[0],
                              idx_low=plane_rows[iters[0]["idx_low"]],
                              idx_high=plane_rows[iters[0]["idx_high"]])
                         ] + iters[1:]
            else:
                paired = ([plane_rows[i] for i in paired[0]], paired[1])
    rows = max_delay - n_lo + 1

    def levels_span():
        # a sweep that runs no head walks every level from the channels up
        # (ROADMAP C5b): its tracing is the cold start's largest item
        if head_run is not None or not iters:
            return contextlib.nullcontext()
        return kernel_build_span(
            "fdmt_merge", kind="levels", levels=len(iters), t=t,
            rows=len(iters[0]["idx_low"]),
            pad=plan.nchan_padded - nchan)

    def fn(data):
        state = data
        if nchan < plan.nchan_padded:
            state = jnp.concatenate(
                [state,
                 jnp.zeros((plan.nchan_padded - nchan, t), state.dtype)])
        if use_pallas:
            # from the head (or the chunk) to the scorer the state keeps
            # the layout the kernels read and write, the rows each pads
            # to left in place after the real ones: one relayout in, one
            # out, none between two kernels
            if head_run is not None:
                state = head_run(state)
            state = _state_tiles(state, t_tile)
            with levels_span():
                for it in iters:
                    state = _merge_pallas(state, it, t_tile, interpret)
            if paired is not None:
                state = _merge4_pallas(state, paired[0], paired[1], t_tile,
                                       interpret)
            state = state.reshape(state.shape[0], t)
        else:
            if head_run is not None:  # the tests' seam: head, XLA merges
                state = head_flat_rows(head, head_run(state))
            with levels_span():
                for it in iters:
                    sh = (jnp.asarray(it["shift_high"])
                          if it["shift_high"] is not None else None)
                    state = _merge_xla(state, jnp.asarray(it["idx_low"]),
                                       jnp.asarray(it["idx_high"]),
                                       jnp.asarray(it["shift"]), sh)
        # the first `rows` rows are n_lo..max_delay by construction
        plane = state
        if t_orig is not None and t_orig != t:
            plane = plane[:, :t_orig]
        if not with_scores:
            return plane[:rows]
        if partial is not None:
            return _tile_partials(plane, rows, partial, windows, with_cert,
                                  use_score, interpret)
        from .score_pallas import pick_score_tile
        from .search import score_profiles_chunked, scored_windows

        # one-pass Pallas scorer: reads the plane once and accumulates
        # per-row partials in VMEM — the XLA chunked scorer
        # materialises ~9 GB of mean-sub/pyramid/sliding temps at the
        # 513 x 1M coarse plane and measured 0.17 s standalone against
        # this kernel's ~0.02 s.
        widest = scored_windows(windows, plane.shape[1])[-1]
        score_tile = pick_score_tile(plane.shape[1], widest)
        if use_score and not score_tile:
            import warnings

            # trace-time, once per shape
            warnings.warn(
                f"one-pass scorer unavailable: no supported tile "
                f"divides T={plane.shape[1]}; falling back to the XLA "
                "chunked scorer", stacklevel=2)
        if use_score and score_tile:
            from .score_pallas import score_plane_pallas

            stacked = score_plane_pallas(plane, with_cert=with_cert,
                                         interpret=interpret,
                                         windows=windows, rows=rows)
        else:
            # row-chunked scoring bounds the scorer's HBM temps (see
            # score_profiles_chunked) while still emitting ONE (5, ndm)
            # array ((6, ndm) with the hybrid's certificate row) -> one
            # host readback
            stacked = score_profiles_chunked(plane[:rows], jnp,
                                             with_cert=with_cert,
                                             windows=windows)
        return (stacked, plane[:rows]) if with_plane else stacked

    return fn


def _tile_partials(plane, rows, partial, windows, with_cert, use_score,
                   interpret):
    """One time tile's partials of the coarse plane's first ``rows`` rows:
    the one-pass kernel where it is compiled and one of its tiles fits,
    else the XLA scorer over row chunks (its temporaries are several times
    its input's size)."""
    import jax.numpy as jnp

    from .score_pallas import pick_partial_tile, score_partials_pallas
    from .score_partials import score_partials, tile_ladder

    own, total = partial
    widest = tile_ladder(windows, total)[0][-1]
    if use_score and pick_partial_tile(own, plane.shape[1], widest):
        return score_partials_pallas(plane, own, total, with_cert=with_cert,
                                     interpret=interpret, windows=windows,
                                     rows=rows)
    return jnp.concatenate(
        [score_partials(plane[lo:min(lo + 128, rows), :own], jnp, windows,
                        total, with_cert=with_cert)
         for lo in range(0, rows, 128)], axis=0)


@functools.lru_cache(maxsize=16)
def _build_transform(nchan, start_freq, bandwidth, max_delay, t, t_tile,
                     use_pallas, interpret, n_lo=0, with_scores=False,
                     with_plane=True, t_orig=None, with_cert=False, *,
                     use_head=None, use_score=None, deep_pair=None,
                     windows=None, partial=None):
    """Jitted wrapper of :func:`_transform_fn` (same signature)."""
    import jax

    return jax.jit(_transform_fn(nchan, start_freq, bandwidth, max_delay,
                                 t, t_tile, use_pallas, interpret,
                                 n_lo=n_lo, with_scores=with_scores,
                                 with_plane=with_plane, t_orig=t_orig,
                                 with_cert=with_cert, use_head=use_head,
                                 use_score=use_score,
                                 deep_pair=deep_pair, windows=windows,
                                 partial=partial))


# ---------------------------------------------------------------------------
# Public transform + search
# ---------------------------------------------------------------------------

def fdmt_transform(data, max_delay, start_freq, bandwidth, use_pallas=None,
                   min_delay=0):
    """All integer-delay dedispersed series of ``data`` at once.

    Parameters
    ----------
    data : (nchan, T) array (host or device).
    max_delay : largest differential band delay (samples, inclusive).
    start_freq, bandwidth : band geometry in MHz (channel = lower edge,
        reference convention ``dedispersion.py:127,135``).
    use_pallas : force the Pallas (True) or XLA (False) merge; default
        auto (Pallas on TPU when a power-of-two tile divides T).
    min_delay : smallest band delay to compute (DM-range pruning — rows
        below it are never built; see :class:`FdmtPlan`).

    Returns
    -------
    (max_delay - min_delay + 1, T) float32 device array: row ``i`` sums
    one sample per channel along the track with band-crossing delay
    ``min_delay + i``, anchored at the top of the band.
    """
    import jax.numpy as jnp

    data = jnp.asarray(data, dtype=jnp.float32)
    nchan = data.shape[0]
    data, t_run, t_tile, use_pallas, interpret, t_orig = _transform_setup(
        data, use_pallas)

    # The whole transform runs as ONE jitted program: enqueueing the
    # merges eagerly allocates every intermediate state up-front (~4x the
    # live set — an HBM OOM at the 1M-sample size), whereas XLA's buffer
    # assignment inside a single program frees each state as soon as its
    # consumer has read it.
    run = _build_transform(nchan, float(start_freq), float(bandwidth),
                           int(max_delay), t_run, t_tile, use_pallas,
                           interpret, n_lo=int(min_delay), t_orig=t_orig)
    return run(data)


def fdmt_trial_dms(nchan, dmmin, dmmax, start_freq, bandwidth, sample_time):
    """The FDMT's integer band-delay trial grid on ``[dmmin, dmmax]``.

    Same one-sample spacing as the reference plan, but snapped to integer
    band delays — the reference's ``arange(min_n, max_n + 1)`` grid sits
    at the *fractional* offset of ``min_n`` (``dedispersion.py:165-168``),
    so DM values (and occasionally the trial count) differ from the plan
    by up to one trial.

    Returns ``(trial_dms, n_lo, n_hi)`` where rows ``n_lo..n_hi`` of the
    transform correspond to the returned DMs (same inversion as
    ``dedispersion_plan``, reference ``dedispersion.py:168-169``).
    """
    f0 = float(start_freq)
    f1 = f0 + float(bandwidth)
    n_lo = int(np.ceil(delta_delay(float(dmmin), f0, f1) / sample_time))
    n_hi = int(np.floor(delta_delay(float(dmmax), f0, f1) / sample_time))
    if n_hi < n_lo:
        # the range is narrower than one band-delay sample and straddles
        # no integer: return the single nearest trial (never an empty
        # grid — every other backend guarantees >= 1 trial)
        n_hi = n_lo
    trial_n = np.arange(n_lo, n_hi + 1)
    trial_dm = (trial_n * sample_time / DM_DELAY_CONST
                / (f0 ** -2.0 - f1 ** -2.0))
    return trial_dm, n_lo, n_hi
