"""Sharded dedispersion sweep over a (dm, chan) device mesh.

The TPU-native replacement for the reference's numba ``prange`` sweep
(``pulsarutils/dedispersion.py:174-202``), scaled out with
``jax.shard_map``:

* the input filterbank ``(nchan, T)`` is sharded over the ``chan`` mesh
  axis (each device holds a frequency sub-band — HBM per device drops by
  the chan factor);
* the gather-offset table ``(ndm, nchan)`` is sharded over both axes;
* each device dedisperses its (trial-shard x channel-shard) block — a
  purely local batched gather — then a single ``psum`` over ``chan``
  reduces the partial channel sums into full dedispersed series;
* scoring runs on the ``dm``-sharded full series; outputs come back
  ``dm``-sharded (concatenated by the out-spec).

Communication: ONE psum of ``(ndm/dm_size, T)`` per block over ICI — the
collective-per-byte cost is amortised over the whole trial block.  With
``chan=1`` the program contains no collectives at all and is the pure
embarrassingly-parallel layout.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.dedisperse import dedisperse_block_chunked_jax
from ..ops.plan import dedispersion_plan
from ..ops.search import (
    _offsets_for,
    auto_chan_block,
    score_profiles_stacked,
    unstack_scores,
)
from ..tuning.geometry import PLAN_CACHE_SIZE, counted_plan_cache
from ..utils.logging_utils import budget_bucket, budget_count
from ..utils.table import ResultTable
from .mesh import pad_to_multiple


@counted_plan_cache("_sharded_kernel", maxsize=PLAN_CACHE_SIZE)
def _sharded_kernel(mesh, capture_plane, chan_block, kernel="gather",
                    max_off=0, policy=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def local_search(data_local, off_local, roll_k):
        # data_local (C_loc, T); off_local (D_loc, C_loc); roll_k scalar
        if kernel == "pallas":
            from ..ops.pallas_dedisperse import dedisperse_plane_pallas_traced

            partial = dedisperse_plane_pallas_traced(data_local, off_local,
                                                     max_off)
        else:
            partial = dedisperse_block_chunked_jax(data_local, off_local,
                                                   chan_block, policy=policy)
        dedisp = jax.lax.psum(partial, "chan")
        if kernel == "pallas":
            # undo the host-side offset rebase (see rebase_offsets); the
            # rotation is a traced operand so plans whose rebase constant
            # differs still share this compiled program
            dedisp = jnp.roll(dedisp, -roll_k, axis=1)
        # ONE stacked (5, D_loc) score array -> one host readback (each
        # fetched array is a host sync)
        stacked = score_profiles_stacked(dedisp, xp=jnp)
        if capture_plane:
            return stacked, dedisp
        return stacked

    out_scores = P(None, "dm")
    out_specs = ((out_scores, P("dm", None)) if capture_plane
                 else out_scores)

    from .mesh import shard_map_compat

    fn = shard_map_compat(
        local_search,
        mesh=mesh,
        in_specs=(P("chan", None), P("dm", "chan"), P()),
        out_specs=out_specs,
        # pallas_call outputs carry no varying-mesh-axes metadata, which
        # trips shard_map's vma lint; the collective structure here is a
        # single explicit psum, so the check adds nothing
        check_vma=(kernel != "pallas"),
    )
    return jax.jit(fn)


def sharded_dedispersion_search(data, dmmin, dmmax, start_freq, bandwidth,
                                sample_time, mesh, *, trial_dms=None,
                                capture_plane=False, chan_block=None,
                                dtype=None, kernel="auto",
                                plane_handle=False, offsets=None,
                                pallas_max_off=None, precision=None):
    """Run the full DM sweep sharded over ``mesh`` axes ``("dm", "chan")``.

    Same result contract as
    :func:`pulsarutils_tpu.ops.search.dedispersion_search` (same plan, same
    host-side float64 offsets, same scorer) — only the execution layout
    differs.  Works on any mesh built by :mod:`.mesh`, including the
    8-virtual-device CPU mesh used in tests.

    ``kernel``: ``"auto"`` (measured per-(backend, geometry, mesh-shape)
    selection via the plan-level autotuner — see
    :mod:`pulsarutils_tpu.tuning`; the static rule, per-shard Pallas on
    all-TPU float32 meshes and XLA gather elsewhere, remains the
    zero-measurement fallback and the ``PUTPU_AUTOTUNE=off`` escape
    hatch), ``"pallas"``, or ``"gather"``.

    ``plane_handle`` (with ``capture_plane``) keeps the captured plane
    DM-sharded and device-resident, returned as a
    :class:`~.sharded_plane.ShardedPlane` instead of a host gather (the
    mesh streaming diagnostics path).

    ``offsets`` (with an explicit ``trial_dms``) supplies the precomputed
    int32 gather-offset rows for those trials, so a caller cycling many
    small trial subsets over one chunk geometry (the sharded hybrid's
    rescore buckets) slices ONE cached table instead of re-deriving the
    plan shifts host-side per call.  ``pallas_max_off`` pins the Pallas
    kernel's static halo bound to a caller-chosen value covering every
    subset (e.g. the full table's rebased bound, power-of-two rounded):
    without it each subset's own bound keys the compiled-program cache,
    and a subset spanning a different offset range silently retraces —
    the retrace detector (``BudgetAccountant``) flags exactly that.

    ``precision`` names a :mod:`~pulsarutils_tpu.precision` accumulation
    strategy for the per-shard channel partial sums (the cross-shard
    ``psum`` stays plain f32 — it adds at most ``chan_size`` partials).
    ``"auto"`` degrades to the static ``f32`` on the mesh path (the
    policy tuner measures the single-device programs), and the Pallas
    per-shard kernel only supports plain f32.
    """
    import jax
    import jax.numpy as jnp

    from ..io.lowbit import PackedFrames

    if isinstance(data, PackedFrames):
        # packed low-bit chunk (ISSUE 11): upload the RAW bytes and
        # decode through the cached device-unpack program — the chan
        # sharding below cannot split packed frames (byte boundaries
        # straddle channel shards), so the unpack is its own dispatch
        # and the sharded sweep consumes the HBM-resident float block;
        # the link still carries only the packed bytes
        data = data.to_device()
    dtype = dtype or jnp.float32
    nchan, nsamples = np.shape(data)
    if trial_dms is None:
        trial_dms = dedispersion_plan(nchan, dmmin, dmmax, start_freq,
                                      bandwidth, sample_time)
    trial_dms = np.asarray(  # putpu-lint: disable=device-trip — host DM plan list
        trial_dms, dtype=np.float64)
    ndm = len(trial_dms)

    if offsets is None:
        # per-call host plan math — hoist it with offsets= when calling
        # repeatedly at one geometry (the counter makes a hot-loop
        # rebuild visible in the chunk budget)
        budget_count("offset_tables")
        offsets = _offsets_for(trial_dms, nchan, start_freq, bandwidth,
                               sample_time, nsamples)
    else:
        offsets = np.asarray(  # putpu-lint: disable=device-trip — host offset table
            offsets, dtype=np.int32)
        if offsets.shape != (ndm, nchan):
            raise ValueError(f"offsets shape {offsets.shape} does not "
                             f"match ({ndm}, {nchan})")

    dm_size = mesh.shape["dm"]
    chan_size = mesh.shape["chan"]
    # pad trials (duplicates of the last trial) and channels (zeros — exact
    # no-ops for the channel sum)
    offsets, _ = pad_to_multiple(offsets, 0, dm_size, mode="edge")
    offsets, _ = pad_to_multiple(offsets, 1, chan_size, mode="constant")
    if nchan % chan_size:
        # a device-resident input bounces through the host on this
        # misaligned-channel path — attribute the trip (putpu-lint
        # device-trip); the aligned branch below keeps it on-device
        with budget_bucket("search/plan"):
            data_padded, _ = pad_to_multiple(np.asarray(data), 0,
                                             chan_size, mode="constant")
    else:
        # already aligned: keep the caller's array — a device-resident
        # input (e.g. the sharded hybrid's repeated rescore calls) must
        # not bounce through the host on every call
        data_padded = data

    if chan_block is None:
        chan_block = auto_chan_block(data_padded.shape[0] // chan_size,
                                     nsamples, offsets.shape[0] // dm_size)

    if kernel == "auto":
        # measured per-(backend, geometry, mesh-shape) selection with the
        # persistent tune cache; the static rule (per-shard Pallas on
        # all-TPU float32 meshes, gather elsewhere) stays as the
        # zero-measurement fallback and the PUTPU_AUTOTUNE=off hatch.
        # Off-TPU meshes have a single applicable variant and resolve
        # statically at zero cost.
        from ..tuning.autotune import resolve_mesh_kernel

        kernel = resolve_mesh_kernel(mesh, nchan, nsamples, ndm,
                                     start_freq, bandwidth, sample_time,
                                     trial_dms, dtype=dtype)
    # rebase wrapped offsets to the band-crossing span (see rebase_offsets)
    # so the pallas halo stays small; max_off is rounded up to a power of
    # two so small plan changes reuse the compiled kernel (the gather
    # kernel does not depend on either — keep its cache key constant)
    roll_k = 0
    if kernel == "pallas":
        from ..ops.pallas_dedisperse import rebase_offsets

        offsets, roll_k, max_off = rebase_offsets(offsets, nsamples)
        if pallas_max_off is not None:
            # caller-pinned static halo bound: one compiled program per
            # bucket shape across every trial subset (no silent retrace)
            if pallas_max_off < max_off:
                raise ValueError(f"pallas_max_off={pallas_max_off} does "
                                 f"not cover the subset bound {max_off}")
            max_off = int(pallas_max_off)
        else:
            if max_off > 0:
                max_off = 1 << int(np.ceil(np.log2(max_off + 1)))
            max_off = max(max_off, 256)
    else:
        max_off = 0

    from ..precision import engage, resolve_policy

    eff_policy = resolve_policy(precision)
    if eff_policy == "auto":
        # the policy tuner measures the single-device programs; on the
        # mesh path the static f32 default stands
        eff_policy = "f32"
    if eff_policy != "f32" and kernel == "pallas":
        raise ValueError("precision policies other than 'f32' need the "
                         "gather mesh kernel (the per-shard Pallas "
                         "kernel accumulates plain f32)")
    policy_arg = None if eff_policy == "f32" else eff_policy
    if policy_arg is not None:
        engage(policy_arg)

    compiled = _sharded_kernel(mesh, capture_plane, chan_block, kernel,
                               max_off, policy_arg)
    with budget_bucket("search/dispatch"):
        # host->device conversions stay INSIDE the bucket: on CPU the
        # asarray of a full chunk copies synchronously, and those
        # seconds must stay attributed (round-6 contract)
        sweep_args = (jnp.asarray(data_padded, dtype=dtype),
                      jnp.asarray(offsets), jnp.int32(roll_k))
        out = compiled(*sweep_args)
        budget_count("dispatches")

    from .mesh import fetch_global as fetch

    if capture_plane:
        stacked, plane = out
        if plane_handle:
            from .sharded_plane import ShardedPlane

            plane = ShardedPlane(plane, mesh, "dm", np.arange(ndm))
        else:
            with budget_bucket("search/readback"):
                plane = fetch(plane)[:ndm]
                budget_count("readbacks")
    else:
        stacked, plane = out, None
    with budget_bucket("search/readback"):
        stacked_host = fetch(stacked)[:, :ndm]
        budget_count("readbacks")
    maxvalues, stds, best_snrs, best_windows, best_peaks = unstack_scores(
        stacked_host)

    table = ResultTable({
        "DM": trial_dms,
        "max": maxvalues,
        "std": stds,
        "snr": best_snrs,
        "rebin": best_windows,
        "peak": best_peaks,
    })
    if capture_plane:
        return table, plane
    return table
