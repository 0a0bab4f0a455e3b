"""Batched beam dispatch: N same-geometry chunks, ONE device program.

The fused single-dispatch hybrid (PR 2) collapsed a chunk's search to
one device trip, which makes the *per-beam* trip count the next
bottleneck: a 64-beam receiver searched beam-by-beam pays 64 dispatches
per chunk epoch even though every beam shares one geometry, one trial
grid and one offset table.  :class:`BeamBatcher` stacks the beams'
chunks along a leading ``batch`` axis and runs the whole stack as ONE
jitted program — ``lax.map`` over the beam axis of exactly the
single-beam :func:`~pulsarutils_tpu.ops.search.search_kernel_fn` trace,
which is what makes the bit-identity contract hold (the SPMD /
DataParallel stacking discipline of SNIPPETS.md [2][3]):

* per-beam score packs are **bit-identical** to running each beam
  through the single-beam kernel alone (same inner computation graph,
  same shapes, same float association — pinned for both formulations
  in ``tests/test_beams.py``);
* device dispatches per beam-chunk drop ~Nx (one program + one packed
  readback per N-beam batch; ``tests/test_beams.py`` pins the count);
* the dedisperse formulation is resolved by the kernel autotuner under
  a batch-specific geometry key (``…|b<N>`` —
  :func:`~pulsarutils_tpu.tuning.geometry.geometry_key`), so a batched
  winner is measured on the batched program, never assumed from the
  single-beam one.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.search import _offsets_for, block_offsets, search_kernel_fn
from ..tuning.geometry import PLAN_CACHE_SIZE
from ..utils.logging_utils import budget_bucket, budget_count, logger
from ..utils.table import ResultTable

__all__ = ["BeamBatcher", "BeamGeometryError", "batched_search_kernel"]


class BeamGeometryError(ValueError):
    """Beams offered for one batch do not share a chunk geometry."""


def _beam_body(chan_block, formulation, packed, prep, policy=None):
    """The per-beam traceable body shared by the batched and
    single-beam kernels — ONE definition, so the two programs can never
    drift and the bit-identity contract is structural.

    ``packed`` (a :meth:`~pulsarutils_tpu.io.lowbit.PackedFrames.meta`
    tuple) makes the beam operand the RAW ``(T, bytes_per_frame)``
    uint8 frames, unpacked in-jit (ISSUE 11): an N-beam batch uploads
    N stacks of packed bytes — 1/8-1/16th the float32 link traffic.
    ``prep`` = ``(renormalize, resample)`` moves the multibeam driver's
    per-beam conditioning into the same program (device clean), so a
    packed beam-chunk never exists as host floats at all.
    """
    def body(beam, offset_blocks):
        import jax.numpy as jnp

        if packed is not None:
            from ..io.lowbit import unpack_from_meta

            beam = unpack_from_meta(beam, packed, jnp)
        if prep is not None:
            renorm, resample = prep
            if renorm:
                from ..ops.clean_ops import renormalize_data

                beam = renormalize_data(beam, xp=jnp)
            if resample > 1:
                from ..ops.rebin import quick_resample

                beam = quick_resample(beam, resample, xp=jnp)
        return search_kernel_fn(beam, offset_blocks,
                                capture_plane=False,
                                chan_block=chan_block,
                                formulation=formulation,
                                policy=policy)

    return body


@functools.lru_cache(maxsize=PLAN_CACHE_SIZE)
def batched_search_kernel(chan_block, formulation, packed=None, prep=None,
                          policy=None):
    """ONE jitted program: ``lax.map`` over the beam axis of the
    single-beam search kernel.

    Input ``data`` is ``(batch, nchan, T)`` — or ``(batch, T,
    bytes_per_frame)`` raw packed frames with ``packed`` set (the
    per-beam in-jit unpack of ISSUE 11); ``offset_blocks`` the shared
    ``(nblocks, dm_block, nchan)`` int32 table (same geometry = same
    offsets for every beam).  Output is ``(batch, nblocks, 5,
    dm_block)`` stacked score packs.  The per-beam body is literally
    :func:`~pulsarutils_tpu.ops.search.search_kernel_fn` (via
    :func:`_beam_body`) — the same trace the single-beam kernels jit —
    so each beam's float operations (and therefore its scores) are
    bit-identical to a sequential single-beam run.  One compiled
    program serves every batch width per (batch, nchan, T) shape;
    interior survey chunks share one shape by construction, so steady
    state is retrace-free.
    """
    import jax

    body = _beam_body(chan_block, formulation, packed, prep, policy)

    @jax.jit
    def kernel(data, offset_blocks):
        return jax.lax.map(lambda beam: body(beam, offset_blocks), data)

    return kernel


@functools.lru_cache(maxsize=PLAN_CACHE_SIZE)
def single_beam_kernel(chan_block, formulation, packed=None, prep=None,
                       policy=None):
    """The sequential arm for packed/prep batchers: the SAME per-beam
    body as :func:`batched_search_kernel`, without the batch map — the
    bit-identity reference (and the host-unpack A/B partner when fed
    float codes with ``packed=None``)."""
    import jax

    body = _beam_body(chan_block, formulation, packed, prep, policy)

    @jax.jit
    def kernel(beam, offset_blocks):
        return body(beam, offset_blocks)

    return kernel


def batched_probe_runners(candidates, nchan, nsamples, batch, sub_dms,
                          start_freq, bandwidth, sample_time,
                          dm_block=None, chan_block=None):
    """Measurement runners for the autotuner's batched-geometry key.

    Builds one synthetic chunk per beam (distinct seeds, a pulse on the
    middle probe trial's exact track — :func:`~pulsarutils_tpu.tuning.
    autotune.synthetic_chunk`) and returns ``{kernel: run}`` where each
    ``run()`` dispatches the REAL batched program and returns beam 0's
    host ``(max, std, snr, window, peak)`` pack — what the tuner's
    exact-hit-match harness compares and its clock times.

    ``dm_block``/``chan_block`` must be the blocking the PRODUCTION
    batcher will dispatch with (``BeamBatcher`` resolves chan_block via
    ``auto_chan_block`` and passes both here through
    ``resolve_batched_kernel``): a probe timed on an unblocked program
    while production runs a channel-blocked one would cache a winner
    measured on a different program.
    """
    import jax.numpy as jnp

    from ..tuning.autotune import synthetic_chunk

    sub_dms = np.asarray(sub_dms, dtype=np.float64)
    ndm = len(sub_dms)
    offsets = _offsets_for(sub_dms, nchan, start_freq, bandwidth,
                           sample_time, nsamples)
    mid = offsets[ndm // 2]
    synth = np.stack([synthetic_chunk(nchan, nsamples, mid, seed=1601 + b)
                      for b in range(max(int(batch), 1))])
    if dm_block is None:
        dm_block = 32
    blocks = block_offsets(offsets, min(int(dm_block), ndm))

    def make(kern):
        run_kernel = batched_search_kernel(chan_block, kern)

        def run():
            out = np.asarray(run_kernel(jnp.asarray(synth),
                                        jnp.asarray(blocks)))
            pack = out[0].transpose(1, 0, 2).reshape(5, -1)[:, :ndm]
            return tuple(pack[i] for i in range(5))

        return run

    return {k: make(k) for k in candidates}


class BeamBatcher:
    """Align and dispatch same-geometry chunks from N beams.

    Bound to ONE chunk geometry at construction (``nchan`` channels,
    ``nsamples`` post-resample samples, the shared ``trial_dms`` grid);
    :meth:`search` takes the aligned per-beam blocks of one chunk epoch
    and returns one :class:`~pulsarutils_tpu.utils.table.ResultTable`
    per beam.  ``batch_hint`` sizes the autotuner's batched-geometry
    measurement (the key carries it); the compiled program itself
    serves any batch width at this geometry.

    ``kernel`` forces the dedisperse formulation (``"roll"`` /
    ``"gather"``); default resolves through the autotuner's
    batch-keyed ladder (static fallback: roll on CPU, gather
    elsewhere — the measured PR 1 heuristic restricted to the
    formulations that can ride inside the batch map).

    ``packed`` = ``(nbits, band_descending)`` puts the batcher on the
    packed low-bit path (ISSUE 11): :meth:`search` then takes each
    beam's RAW ``(nsamps, bytes_per_frame)`` uint8 frames, stacks the
    packed bytes and unpacks per beam INSIDE the one jitted program —
    N beam-chunks upload 1/8-1/16th the float32 bytes, with scores
    byte-identical to feeding the host-unpacked codes (the decode is
    integer-exact and the downstream graph is the same trace).  With
    no ``prep``, the sweep additionally accumulates in the exact
    integer dtype (:func:`~pulsarutils_tpu.io.lowbit.accum_dtype`).
    ``prep`` = ``(renormalize, resample)`` moves the per-beam
    conditioning into the same program (device clean) — the multibeam
    driver's packed mode sets both.
    """

    def __init__(self, nchan, nsamples, trial_dms, start_freq, bandwidth,
                 sample_time, *, dm_block=None, chan_block=None,
                 kernel=None, batch_hint=1, packed=None, prep=None,
                 precision=None):
        self.nchan = int(nchan)
        self.nsamples = int(nsamples)
        self.trial_dms = np.asarray(trial_dms, dtype=np.float64)
        self.start_freq = float(start_freq)
        self.bandwidth = float(bandwidth)
        self.sample_time = float(sample_time)
        self.ndm = len(self.trial_dms)
        if dm_block is None:
            dm_block = max(1, min(self.ndm, 32))
        self.dm_block = int(dm_block)
        if chan_block is None:
            # the single-beam sweep's auto rule (``_search_jax``):
            # identical blocking = identical float association = the
            # bit-identity contract extends to budget-bound geometries
            from ..ops.search import auto_chan_block

            chan_block = auto_chan_block(self.nchan, self.nsamples,
                                         self.dm_block)
        self.chan_block = chan_block
        if kernel is None:
            from ..tuning.autotune import resolve_batched_kernel

            kernel = resolve_batched_kernel(
                self.nchan, self.nsamples, self.ndm, max(int(batch_hint), 1),
                self.start_freq, self.bandwidth, self.sample_time,
                self.trial_dms, dm_block=self.dm_block,
                chan_block=self.chan_block)
        if kernel not in ("roll", "gather"):
            raise ValueError(
                f"BeamBatcher kernel={kernel!r}: only the traceable "
                "formulations ('roll'/'gather') can ride inside the "
                "batch map")
        self.kernel = kernel
        # precision policy is fixed at construction (it keys the jitted
        # programs and the bit-identity contract only holds within one
        # policy); "auto" degrades to f32 — the policy tuner measures
        # the single-beam dispatch surface, and every beam of a batch
        # must run ONE policy for the stacked packs to stay comparable
        from ..precision import engage, resolve_policy

        eff_policy = resolve_policy(precision)
        if eff_policy == "auto":
            eff_policy = "f32"
        self.policy = None if eff_policy == "f32" else eff_policy
        if self.policy is not None:
            engage(self.policy)
        self.prep = ((bool(prep[0]), int(prep[1]))
                     if prep is not None else None)
        self.packed_meta = None
        if packed is not None:
            from ..io.lowbit import accum_dtype

            nbits, descending = packed
            # integer sweep accumulation only when nothing downstream
            # needs floats (no renormalisation) and the exactness bound
            # holds; conditioning paths unpack straight to float32
            acc = (accum_dtype(nbits, self.nchan)
                   if self.prep is None else None) or "float32"
            self.packed_meta = (int(nbits), self.nchan, bool(descending),
                                acc)
        # per-series-length device offset tables: interior chunks share
        # one (the bound ``nsamples``); a ragged final chunk gets its
        # own (the gather wraps mod T, so offsets are length-specific) —
        # both cached so steady state re-uploads nothing
        self._offs_dev = {}

    def _offsets_dev(self, nsamples):
        import jax.numpy as jnp

        dev = self._offs_dev.get(int(nsamples))
        if dev is None:
            offsets = _offsets_for(self.trial_dms, self.nchan,
                                   self.start_freq, self.bandwidth,
                                   self.sample_time, int(nsamples))
            dev = jnp.asarray(block_offsets(offsets, self.dm_block))
            if len(self._offs_dev) >= PLAN_CACHE_SIZE:
                self._offs_dev.clear()  # bounded; geometries are few
            self._offs_dev[int(nsamples)] = dev
        return dev

    # -- dispatch ------------------------------------------------------------

    def _check(self, blocks):
        shapes = {tuple(np.shape(b)) for b in blocks}
        if len(shapes) != 1:
            raise BeamGeometryError(
                f"beam blocks of one batch must share a shape; got "
                f"{sorted(shapes)} — same-geometry chunks only")
        shape = next(iter(shapes))
        if self.packed_meta is not None:
            nbits = self.packed_meta[0]
            bpf = self.nchan * nbits // 8
            if len(shape) != 2 or shape[1] != bpf:
                raise BeamGeometryError(
                    f"packed beam blocks have shape {shape}; this "
                    f"batcher expects raw (nsamps, {bpf}) frames at "
                    f"{nbits} bits x {self.nchan} channels")
            return shape[0]
        if len(shape) != 2 or shape[0] != self.nchan:
            raise BeamGeometryError(
                f"beam blocks have shape {shape}; this batcher is bound "
                f"to {self.nchan} channels")
        return shape[1]

    def _searched_len(self, raw_len):
        """Post-prep series length (= the offset-table key): the in-jit
        resample truncates exactly like the host ``quick_resample``."""
        if self.prep is not None and self.prep[1] > 1:
            return int(raw_len) // self.prep[1]
        return int(raw_len)

    def _tables(self, stacked):
        tables = []
        for pack in stacked:
            pack = pack.transpose(1, 0, 2).reshape(5, -1)[:, :self.ndm]
            maxvalues, stds, snrs = (pack[i].astype(np.float64)
                                     for i in range(3))
            windows = np.rint(pack[3]).astype(np.int32)
            peaks = np.rint(pack[4]).astype(np.int64)
            tables.append(ResultTable({
                "DM": self.trial_dms, "max": maxvalues, "std": stds,
                "snr": snrs, "rebin": windows, "peak": peaks}))
        return tables

    def _stack(self, blocks):
        """Device stack + the upload accounting: packed batchers ship
        the RAW bytes (uint8) and count the link savings."""
        import jax.numpy as jnp

        from ..obs import metrics as obs_metrics

        if self.packed_meta is not None:
            data = jnp.stack([jnp.asarray(b) for b in blocks])
            obs_metrics.counter("putpu_lowbit_packed_chunks_total").inc(
                len(blocks))
            obs_metrics.counter("putpu_lowbit_bytes_saved_total").inc(
                sum(self.nchan * int(np.shape(b)[0]) * 4
                    - int(getattr(b, "nbytes", 0)) for b in blocks))
        else:
            data = jnp.stack([jnp.asarray(b, dtype=jnp.float32)
                              for b in blocks])
        obs_metrics.counter("putpu_bytes_uploaded_total").inc(
            int(data.nbytes))
        return data

    def max_batch(self, nsamples=None):
        """The beam-batch width the memory budget admits for one
        dispatch (``None`` = budget unknown, no cap) — the admission
        number :class:`~pulsarutils_tpu.beams.service.SurveyService`
        caps co-batches with, and the preflight bound :meth:`search`
        splits against (ISSUE 12)."""
        from ..resilience.memory_budget import max_beam_batch

        return max_beam_batch(
            self.nchan, int(nsamples or self.nsamples), self.ndm,
            dm_block=self.dm_block, chan_block=self.chan_block,
            formulation=self.kernel,
            packed_nbits=self.packed_meta[0] if self.packed_meta else 0)

    def search(self, blocks):
        """Search one chunk epoch across all beams in ONE dispatch.

        ``blocks`` is a sequence of B ``(nchan, nsamples)`` arrays (one
        per beam, any host/device mix) — or B raw ``(nsamps,
        bytes_per_frame)`` packed frames on a ``packed`` batcher.
        Returns B result tables whose columns are bit-identical to B
        sequential :meth:`search_single` calls.  Budget: one
        ``dispatches`` + one ``readbacks`` count for the whole batch —
        that 2 vs ``2B`` trip count is the entire point (config 13
        gates it).

        Resource exhaustion (ISSUE 12): a batch whose preflight
        estimate exceeds measured headroom is split *before* dispatch,
        and a dispatch that still raises ``RESOURCE_EXHAUSTED``
        re-dispatches as two half-batches (the ladder's
        ``halve_batch`` rung) — ``lax.map`` runs the identical
        per-beam trace whatever the batch width, so the per-beam
        tables are byte-identical to the unsplit dispatch (pinned in
        ``tests/test_resilience.py`` for both formulations, packed and
        float).  A single beam that OOMs has no smaller batch left and
        the error propagates to the caller's ladder.
        """
        from ..faults import inject as fault_inject
        from ..resilience import ladder as _ladder

        raw_len = self._check(blocks)
        searched = self._searched_len(raw_len)
        cap = self.max_batch(searched)
        if cap is not None and 1 <= cap < len(blocks):
            # preflight split: the estimate says this co-batch cannot
            # fit — shed batch width BEFORE compiling/dispatching
            _ladder.count_split("preflight")
            return (self.search(blocks[:cap])
                    + self.search(blocks[cap:]))
        kernel = batched_search_kernel(self.chan_block, self.kernel,
                                       self.packed_meta, self.prep,
                                       self.policy)
        try:
            fault_inject.fire("beams", chunk=None, batch=len(blocks))
            with budget_bucket("search/dispatch"):
                offs_dev = self._offsets_dev(searched)
                data = self._stack(blocks)
                out = kernel(data, offs_dev)
                budget_count("dispatches")
            with budget_bucket("search/readback"):
                stacked = np.asarray(out)
                budget_count("readbacks")
        except (ValueError, TypeError):
            raise  # deterministic configuration error, never OOM
        except Exception as exc:  # jax errors share no base class
            if len(blocks) <= 1 or not _ladder.is_resource_exhausted(exc):
                raise
            _ladder.oom_event("beam_batch")
            _ladder.descend("halve_batch")
            _ladder.count_split("ladder")
            half = (len(blocks) + 1) // 2
            logger.warning(
                "batched beam dispatch (%d beams) hit "
                "RESOURCE_EXHAUSTED (%r); re-dispatching as two "
                "half-batches (%d + %d, per-beam tables "
                "byte-identical)", len(blocks), exc, half,
                len(blocks) - half)
            return (self.search(blocks[:half])
                    + self.search(blocks[half:]))
        return self._tables(stacked)

    def search_single(self, block):
        """One beam through the plain single-beam compiled kernel — the
        sequential arm of the A/B, and the bit-identity reference the
        batched path is pinned against.  Packed/prep batchers route
        through :func:`single_beam_kernel` (the SAME per-beam body as
        the batched program); plain batchers keep the original
        ``ops.search`` kernel."""
        import jax.numpy as jnp

        raw_len = self._check([block])
        searched = self._searched_len(raw_len)
        if self.packed_meta is not None or self.prep is not None:
            kernel = single_beam_kernel(self.chan_block, self.kernel,
                                        self.packed_meta, self.prep,
                                        self.policy)

            def operand():
                return self._stack([block])[0]
        else:
            from ..ops.search import _jax_search_kernel

            kernel = _jax_search_kernel(False, self.chan_block, self.kernel,
                                        policy=self.policy)

            def operand():
                return jnp.asarray(block, dtype=jnp.float32)
        with budget_bucket("search/dispatch"):
            offs_dev = self._offsets_dev(searched)
            out = kernel(operand(), offs_dev)
            budget_count("dispatches")
        with budget_bucket("search/readback"):
            stacked = np.asarray(out)
            budget_count("readbacks")
        return self._tables(stacked[None])[0]
