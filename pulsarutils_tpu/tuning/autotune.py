"""Measured kernel autotuner: per-(backend, geometry) variant selection.

The auto-tuning survey (arxiv 1601.01165, PAPERS.md) shows the fastest
dedispersion variant depends strongly on (platform, nchan, nDM, dtype)
— and this repo proved it locally when CPU XLA's batched gather
scalarised and the roll-scan formulation won 14x (PR 1).  Until now
``kernel="auto"`` was a hard-coded static heuristic encoding that one
measurement; this module replaces folklore with measurement:

* on first sight of a :func:`~.geometry.geometry_key` — (backend,
  nchan, nsamples, ndm, dtype, mesh shape) — the applicable variants
  (filtered by each kernel's existing dtype/backend/mesh constraints)
  are micro-benchmarked under measurement discipline: one warm-up
  dispatch excluded (compile), device fences, median of
  :data:`TUNE_REPS` timed runs on **synthetic data of the real
  geometry** (seeded noise + a pulse injected along the middle trial's
  exact integer track, so the equivalence check compares decisive
  tables, not noise ties);
* a candidate's scores must pass the exact-hit-match harness
  (:func:`hits_match`) against the static choice's scores **before its
  winner is ever cached** — same argbest row, exact integer fields,
  score columns equal to float tolerance — so tuning can change speed,
  never hits;
* winners persist in the versioned on-disk :class:`~.cache.TuneCache`;
  a second run at the same geometry (same process or not) performs
  **zero tuning dispatches**;
* the whole subsystem is observable: ``putpu_autotune_*`` counters and
  gauges (declared in :mod:`..obs.names`), a ``search/autotune`` budget
  bucket + trace span around every measurement, and per-key decisions
  in the ``BUDGET_JSON`` footer and the survey report.

Fallback ladder (the static heuristic is never more than one step
away): ``PUTPU_AUTOTUNE=off`` short-circuits to the static choice with
zero side effects (byte-identical to the pre-tuner code path);
``PUTPU_AUTOTUNE=cache`` consults cached winners but never measures;
the default ``on`` measures on a cache miss — unless the geometry sits
below :data:`MIN_TUNE_ELEMENTS` (micro-benchmarking a sub-millisecond
search costs more than it can ever repay; ``PUTPU_AUTOTUNE_MIN``
overrides), only one candidate survives the constraint filter, or
measurement itself fails, all of which resolve to the static choice
and are recorded (and counted) as such.

Measurement cost is bounded three ways: the trial axis is probed at
``min(ndm, TUNE_PROBE_TRIALS)`` trials sliced from the real grid
(every candidate family's per-trial cost is linear in the trial count,
so the ranking transfers while the full ``ndm`` stays in the key), a
candidate measuring slower than :data:`ABANDON_FACTOR` x the best
median after its first timed rep is abandoned early (the PR 1 CPU
gather would otherwise burn ~14x the winner's wall per rep), and the
synthetic chunk is freed as soon as the winner is cached.  Note the
synthetic chunk transiently doubles the chunk-sized device footprint
while a key is being tuned.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..utils.logging_utils import budget_bucket, logger
from .cache import TuneCache, default_cache_path
from .geometry import dtype_name, geometry_key

__all__ = ["KernelTuner", "get_tuner", "set_tuner", "autotune_mode",
           "static_search_kernel", "static_mesh_kernel", "hits_match",
           "accel_tables_match", "measure_kernel_wall",
           "resolve_search_kernel", "resolve_mesh_kernel",
           "resolve_batched_kernel", "resolve_accel_backend",
           "resolve_search_policy", "resolve_harmonic_kernel",
           "decision_seq", "decisions_since", "ACCEL_SIGMA_RTOL",
           "MIN_TUNE_ELEMENTS", "TUNE_REPS", "TUNE_PROBE_TRIALS"]

#: timed repetitions per candidate (median taken); the warm-up
#: dispatch that absorbs the compile is extra
TUNE_REPS = 3

#: trial-axis probe size for measurement runs (the full ndm stays in
#: the cache key; per-trial cost is linear in trials for every family)
TUNE_PROBE_TRIALS = 32

#: a candidate slower than this factor x the best median after one
#: timed rep is abandoned without further reps
ABANDON_FACTOR = 3.0

#: geometries below this ``nchan * nsamples`` floor resolve statically:
#: at 2^25 elements a CPU sweep is already sub-second, the measurement
#: (warm-up + compiles + reps per candidate) costs more than a survey
#: at that geometry could repay, and tier-1-scale test geometries stay
#: on the pre-tuner path.  ``PUTPU_AUTOTUNE_MIN`` overrides.
MIN_TUNE_ELEMENTS = 1 << 25


# ---------------------------------------------------------------------------
# static heuristics (the zero-measurement fallback + escape hatch)
# ---------------------------------------------------------------------------

def static_search_kernel(backend, f32=True, capture_plane=False):
    """The pre-tuner ``kernel="auto"`` heuristic, program-for-program.

    ``"roll"`` on CPU is exactly the program the old ``"gather"``
    spelling resolved to there (PR 1 routed the CPU formulation to the
    roll-scan inside the dedisperse kernel); the spelling is now
    explicit so measured selection and static fallback name the same
    variants.
    """
    if capture_plane == "memmap":
        # the memmap spill needs the superblocked Pallas path (see
        # dedispersion_search); non-f32 falls through to the gather
        # error path exactly as before
        return "pallas" if f32 else "gather"
    if backend == "tpu":
        return "pallas" if f32 else "gather"
    return "roll" if backend == "cpu" else "gather"


def static_mesh_kernel(all_tpu, f32=True):
    """The pre-tuner per-shard kernel heuristic of the sharded paths."""
    return "pallas" if (all_tpu and f32) else "gather"


# ---------------------------------------------------------------------------
# measurement discipline
# ---------------------------------------------------------------------------

def measure_kernel_wall(kernel, run, reps=TUNE_REPS, sync=None):
    """Median wall seconds of ``reps`` timed ``run()`` calls.

    THE sanctioned tuning seam of the ``device-trip`` checker: this is
    deliberately a host-blocking measurement — ``sync`` (when given) is
    fenced with ``block_until_ready`` after every run so asynchronous
    dispatch cannot leak a candidate's device time into the next
    candidate's clock.  The search runners already block on their own
    host readback, making the fence a belt-and-braces no-op there; mesh
    or future device-resident runners rely on it.  Callers time nothing
    themselves: every wall second the tuner attributes comes from here
    (and the whole call sits inside the caller's ``search/autotune``
    budget bucket, so tuning can never land in a chunk's unattributed
    residual).
    """
    walls = []
    for _ in range(max(int(reps), 1)):
        t0 = time.perf_counter()
        out = run()
        if sync is not None:
            fence = sync(out) if callable(sync) else sync
            if hasattr(fence, "block_until_ready"):
                fence.block_until_ready()
        walls.append(time.perf_counter() - t0)
    walls.sort()
    return walls[len(walls) // 2]


def hits_match(ref, cand, rtol=1e-4, atol=1e-6):
    """The exact-hit-match harness gating every cached winner.

    ``ref``/``cand`` are ``(max, std, snr, window, peak)`` score tuples
    over the same probe trial grid.  Equivalent means: the argbest
    trial agrees, its integer fields (boxcar window, peak sample) agree
    exactly, and every score column agrees to float tolerance (distinct
    exact formulations may reassociate f32 sums — the tolerance admits
    that and nothing more).  A variant failing this is rejected from
    tuning regardless of how fast it measured: the tuner may change
    speed, never hits.
    """
    ref_snr = np.asarray(ref[2], dtype=np.float64)
    cand_snr = np.asarray(cand[2], dtype=np.float64)
    if ref_snr.shape != cand_snr.shape:
        return False
    ib_ref = int(np.argmax(ref_snr))
    ib_cand = int(np.argmax(cand_snr))
    if ib_ref != ib_cand:
        return False
    if int(np.asarray(ref[3])[ib_ref]) != int(np.asarray(cand[3])[ib_ref]):
        return False
    if int(np.asarray(ref[4])[ib_ref]) != int(np.asarray(cand[4])[ib_ref]):
        return False
    for r, c in zip(ref[:3], cand[:3]):
        if not np.allclose(np.asarray(r, dtype=np.float64),
                           np.asarray(c, dtype=np.float64),
                           rtol=rtol, atol=atol):
            return False
    return True


def synthetic_chunk(nchan, nsamples, offsets_mid, seed=1601):
    """Seeded noise of the real geometry + one pulse on an exact track.

    ``offsets_mid`` is the middle probe trial's int32 gather-offset row:
    the pulse is injected at ``(t0 + off[c]) mod T`` per channel, so
    dedispersing at that trial reassembles it exactly — the decisive
    argbest the equivalence harness compares.  (arxiv 1601.01165's
    tuners benchmark on representative inputs for the same reason:
    branchless dedispersion cost is data-independent, but the
    *correctness* comparison needs a real detection.)
    """
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((int(nchan), int(nsamples)),
                               dtype=np.float32) * np.float32(0.5)
    t0 = nsamples // 3
    amp = np.float32(10.0 / np.sqrt(nchan))  # matched-filter S/N ~ 20
    cols = (t0 + np.asarray(offsets_mid, dtype=np.int64)) % nsamples
    data[np.arange(nchan), cols] += amp
    return data


# ---------------------------------------------------------------------------
# mode / floor knobs
# ---------------------------------------------------------------------------

_warned_mode = set()


def autotune_mode():
    """``PUTPU_AUTOTUNE`` -> ``"on"`` / ``"cache"`` / ``"off"``.

    Unset means ``on``; an unrecognised value warns once and falls back
    to ``on`` (the tristate-knob lesson: silently ignored garbage makes
    an A/B measure the same thing twice).
    """
    raw = os.environ.get("PUTPU_AUTOTUNE", "").strip().lower()
    if raw in ("off", "0", "false"):
        return "off"
    if raw in ("cache", "cache-only"):
        return "cache"
    if raw in ("", "on", "1", "true"):
        return "on"
    if raw not in _warned_mode:
        _warned_mode.add(raw)
        logger.warning("PUTPU_AUTOTUNE=%r ignored (expected on/cache/off); "
                       "autotuning stays on", raw)
    return "on"


def _min_elements():
    raw = os.environ.get("PUTPU_AUTOTUNE_MIN", "")
    if raw:
        try:
            return int(raw)
        except ValueError:
            logger.warning("PUTPU_AUTOTUNE_MIN=%r ignored (expected an "
                           "integer)", raw)
    return MIN_TUNE_ELEMENTS


# ---------------------------------------------------------------------------
# per-process decision ledger (BUDGET_JSON footer / survey report)
# ---------------------------------------------------------------------------

_DECISIONS = []
_DECISIONS_LOCK = threading.Lock()


def _record_decision(rec):
    with _DECISIONS_LOCK:
        _DECISIONS.append(rec)


def decision_seq():
    """Monotonic count of decisions recorded so far (stream markers)."""
    with _DECISIONS_LOCK:
        return len(_DECISIONS)


def decisions_since(mark=0):
    """Decision records after ``mark`` (a prior :func:`decision_seq`).

    The budget footer and the survey report call this with the mark
    taken at ``begin_stream`` so one run's footer carries exactly that
    run's per-key decisions, not the whole process history.
    """
    with _DECISIONS_LOCK:
        return [dict(r) for r in _DECISIONS[int(mark):]]


def reset_decisions():
    """Test helper: drop the process decision ledger."""
    with _DECISIONS_LOCK:
        del _DECISIONS[:]


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

class KernelTuner:
    """Plan-level kernel selection: cache -> measure -> static ladder.

    ``cache`` is a :class:`~.cache.TuneCache` (in-memory when ``None``);
    ``mode`` pins the resolution mode (default: follow
    :func:`autotune_mode` per call); ``min_elements`` overrides the
    measurement floor (``None``: env/default); ``measurer`` injects the
    timing function for deterministic tests — signature
    ``measurer(kernel, run, reps)`` returning seconds (the default is
    :func:`measure_kernel_wall`); ``reps``/``probe_trials`` bound the
    measurement work.
    """

    def __init__(self, cache=None, mode=None, min_elements=None,
                 reps=TUNE_REPS, probe_trials=TUNE_PROBE_TRIALS,
                 measurer=None):
        self.cache = cache if cache is not None else TuneCache(None)
        self.mode = mode
        self.min_elements = min_elements
        self.reps = int(reps)
        self.probe_trials = int(probe_trials)
        self.measurer = measurer
        self._lock = threading.RLock()
        self._resolved = {}  # key -> kernel (this process's decisions)

    # -- bookkeeping ---------------------------------------------------------

    def _mode(self):
        return self.mode if self.mode is not None else autotune_mode()

    def _floor(self):
        if self.min_elements is not None:
            return int(self.min_elements)
        return _min_elements()

    def _decide(self, key, kernel, source, static, measured_s=None,
                reason=None, abandoned=None):
        from ..obs import metrics as _metrics

        with self._lock:
            self._resolved[key] = kernel
            _metrics.gauge("putpu_autotune_keys").set(len(self._resolved))
        rec = {"key": key, "kernel": kernel, "source": source,
               "static": static}
        if reason:
            rec["reason"] = reason
        if abandoned:
            # these candidates' measured_s figures are ONE early-abandon
            # rep, not a median — flagged wherever the decision surfaces
            rec["abandoned"] = sorted(abandoned)
        if measured_s:
            rec["measured_s"] = {k: round(float(v), 6)
                                 for k, v in measured_s.items()}
            if static in measured_s and kernel in measured_s \
                    and measured_s[kernel] > 0:
                speedup = measured_s[static] / measured_s[kernel]
                rec["speedup_vs_static"] = round(speedup, 3)
                _metrics.gauge("putpu_autotune_speedup").set(
                    round(speedup, 4))
        if source == "static":
            _metrics.counter("putpu_autotune_static_fallbacks_total").inc()
        _record_decision(rec)
        # measured/cached selections are worth one INFO line per key;
        # routine static fallbacks (below-floor geometries) stay DEBUG
        log = logger.info if source != "static" else logger.debug
        log("autotune %s: kernel=%s (%s%s)", key, kernel, source,
            f", {reason}" if reason else "")
        return kernel

    # -- resolution ----------------------------------------------------------

    def resolve(self, *, backend, nchan, nsamples, ndm, dtype, candidates,
                static, runner_factory=None, mesh_shape=None, batch=1,
                equiv=None):
        """One kernel name for this geometry.

        ``candidates`` is the constraint-filtered variant list (static
        choice first); ``runner_factory()`` lazily builds
        ``{kernel: run_callable}`` over synthetic data — only invoked
        when a measurement is actually going to happen.  ``batch`` is
        the beam-batch width of the multi-beam stacked dispatch (1 =
        the classic single-beam search; the key — and therefore the
        measured winner — is batch-specific, see
        :func:`~.geometry.geometry_key`).  ``equiv`` overrides the
        equivalence harness (``equiv(ref_scores, cand_scores) ->
        bool``; default :func:`hits_match`) — contender pairs whose
        score packs are tables rather than hit tuples supply their own
        matcher (``resolve_accel_backend``).
        """
        from ..obs import metrics as _metrics

        mode = self._mode()
        if mode == "off" or static not in candidates:
            # the escape hatch: zero side effects, the pre-tuner path
            # byte for byte (static not in candidates cannot happen from
            # the in-tree call sites; belt-and-braces for callers)
            return static
        key = geometry_key(backend, nchan, nsamples, ndm, dtype, mesh_shape,
                           batch=batch)
        with self._lock:
            hit = self._resolved.get(key)
        if hit is not None:
            _metrics.counter("putpu_autotune_cache_hits_total").inc()
            return hit
        # the floor gates the DISK lookup too, not just measurement:
        # below-floor geometries must resolve statically, full stop
        # (the documented contract) — a per-machine ~/.cache entry
        # steering tiny test/bench searches would make byte-identity
        # comparisons diverge across machines with no indication why
        below_floor = nchan * nsamples < self._floor()
        entry = (self.cache.lookup(key)
                 if len(candidates) >= 2 and not below_floor else None)
        if entry is not None and entry.get("kernel") in candidates:
            # a prior decision — memory or disk — is a hit; only a
            # resolution that found NEITHER counts as a miss (the
            # manifest's stated semantics)
            _metrics.counter("putpu_autotune_cache_hits_total").inc()
            return self._decide(key, entry["kernel"], "cache", static,
                                measured_s=entry.get("measured_s"))
        _metrics.counter("putpu_autotune_cache_misses_total").inc()

        if len(candidates) < 2:
            return self._decide(key, static, "static", static,
                                reason="single applicable variant")
        if below_floor:
            return self._decide(key, static, "static", static,
                                reason=f"geometry below tune floor "
                                       f"({nchan * nsamples} < "
                                       f"{self._floor()} elements)")
        if mode == "cache":
            return self._decide(key, static, "static", static,
                                reason="cache-only mode, no tuned entry")
        if runner_factory is None:
            return self._decide(key, static, "static", static,
                                reason="no measurement runner")
        try:
            return self._measure(key, candidates, static, runner_factory,
                                 equiv=equiv)
        except Exception as exc:  # putpu-lint: disable=broad-except — tuning must degrade to static, never fail a search
            logger.warning("autotune measurement failed for %s (%r); "
                           "using the static heuristic", key, exc)
            return self._decide(key, static, "static", static,
                                reason=f"measurement failed: "
                                       f"{type(exc).__name__}")

    def _measure(self, key, candidates, static, runner_factory,
                 equiv=None):
        """Warm up, fence, median-of-k each candidate; gate equivalence;
        cache and return the winner."""
        from ..obs import metrics as _metrics
        from ..obs.trace import span

        matcher = equiv if equiv is not None else hits_match
        measurer = self.measurer or measure_kernel_wall
        with self._lock:  # one measurement per key, ever
            hit = self._resolved.get(key)
            if hit is not None:
                return hit  # a racing thread measured while we waited
            with budget_bucket("search/autotune"):
                runners = runner_factory()
                medians = {}
                abandoned = set()
                ref_scores = None
                best = None
                # static first: it sets the equivalence reference AND
                # the early-abandon bar
                order = [static] + [c for c in candidates if c != static]
                for cand in order:
                    run = runners.get(cand)
                    if run is None:
                        continue
                    with span("autotune_measure", kernel=cand, key=key):
                        scores = run()  # warm-up: compile excluded
                        if cand == static:
                            ref_scores = scores
                        elif not matcher(ref_scores, scores):
                            _metrics.counter(
                                "putpu_autotune_equiv_rejected_total").inc()
                            logger.warning(
                                "autotune %s: variant %r failed the "
                                "exact-hit-match harness — rejected "
                                "(tuning may change speed, never hits)",
                                key, cand)
                            continue
                        # median of reps single-timed walls; the first
                        # wall doubles as the early-abandon probe, so no
                        # rep is ever discarded (each measurer(.., 1)
                        # call is one fenced timed run)
                        walls = [measurer(cand, run, 1)]
                        if best is not None \
                                and walls[0] > ABANDON_FACTOR * best:
                            # one timed rep is enough to rule it out; a
                            # CPU scalarised gather costs ~14x the
                            # winner per rep (PR 1) — don't pay it k
                            # times just to confirm the loss.  The
                            # single-rep figure is RECORDED as such
                            # (``abandoned``), never passed off as a
                            # median
                            abandoned.add(cand)
                        else:
                            walls += [measurer(cand, run, 1)
                                      for _ in range(self.reps - 1)]
                        walls.sort()
                        medians[cand] = walls[len(walls) // 2]
                    _metrics.counter("putpu_autotune_measurements_total",
                                     kernel=cand).inc()
                    if best is None or medians[cand] < best:
                        best = medians[cand]
            if not medians:
                return self._decide(key, static, "static", static,
                                    reason="no candidate measured")
            winner = min(medians, key=medians.get)
            try:
                self.cache.store(key, winner, measured_s=medians,
                                 reps=self.reps,
                                 abandoned=sorted(abandoned))
            except OSError as exc:
                # a read-only cache path must not throw away a PAID-FOR
                # measurement: keep the winner in-memory for this
                # process (future processes re-measure)
                logger.warning("tune cache persist failed for %s (%r); "
                               "measured winner kept in-memory only",
                               key, exc)
            return self._decide(key, winner, "measured", static,
                                measured_s=medians, abandoned=abandoned)

    def decisions(self):
        """``{key: kernel}`` resolved by this tuner instance."""
        with self._lock:
            return dict(self._resolved)


# ---------------------------------------------------------------------------
# module singleton + the search-facing entry points
# ---------------------------------------------------------------------------

_tuner = None
_tuner_lock = threading.Lock()


def get_tuner():
    """The process tuner (created on first use, persistent disk cache)."""
    global _tuner
    with _tuner_lock:
        if _tuner is None:
            _tuner = KernelTuner(cache=TuneCache(default_cache_path()))
        return _tuner


def set_tuner(tuner):
    """Install ``tuner`` as the process tuner; returns the previous one
    (tests swap in deterministic tuners and restore after)."""
    global _tuner
    with _tuner_lock:
        prev = _tuner
        _tuner = tuner
        return prev


def _probe_grid(trial_dms, probe_trials):
    """``probe_trials`` trials evenly sliced from the real grid."""
    trial_dms = np.asarray(trial_dms, dtype=np.float64)
    ndm = len(trial_dms)
    probe = min(ndm, int(probe_trials))
    idx = np.unique(np.linspace(0, ndm - 1, probe).astype(np.int64))
    return trial_dms[idx]


def _search_candidates(backend, static):
    """Direct-sweep formulations the tuner may MEASURE on ``backend``.

    On a TPU the list is the static choice alone, so ``kernel="auto"``
    resolves there without a measurement.  Measuring means RUNNING
    every candidate at the chunk's own geometry, and neither XLA
    formulation has ever run on a chip at the geometries above the tune
    floor.  Both compile for a v5e at the tuner's blocked 32-trial
    probe of a 1,024 x 2^20 chunk (PR 22's compile probe: ``roll``
    2.9 s, ``gather`` 3.7 s), so the compiler does not rule them out —
    but an unblocked gather of that size is refused (a 128 GiB index
    temporary), older notes say the gather scalarises and takes the
    worker down at these sizes, and a lost worker is not an exception
    :meth:`KernelTuner.resolve` can catch.  A formulation joins the TPU
    list only with a chip run that shows it at survey width.  Off-TPU
    the traceable formulations compete as before (the Pallas kernel is
    TPU-only).
    """
    if backend == "tpu":
        return [static]
    return [static] + [k for k in ("roll", "gather") if k != static]


def resolve_search_kernel(nchan, nsamples, ndm, dtype, capture_plane,
                          start_freq, bandwidth, sample_time, trial_dms,
                          dm_block=None, chan_block=None):
    """``kernel="auto"`` resolution for the single-device jax sweep.

    Candidate families and their constraints: ``"pallas"`` (TPU +
    float32 only), ``"gather"`` (the portable batched XLA gather),
    ``"roll"`` (the roll-scan formulation, PR 1's CPU winner).  Plane
    captures resolve statically — the capture variants differ in spill
    strategy, not sweep kernel, and their wall is dominated by the
    capture itself.  On a TPU nothing is measured at all (see
    :func:`_search_candidates`).
    """
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    f32 = dtype in (None, jnp.float32)
    static = static_search_kernel(backend, f32, capture_plane)
    if capture_plane:
        return static
    candidates = _search_candidates(backend, static)

    def runner_factory():
        from ..ops.search import _offsets_for, _search_jax

        sub_dms = _probe_grid(trial_dms, get_tuner().probe_trials)
        mid = _offsets_for(sub_dms[len(sub_dms) // 2:len(sub_dms) // 2 + 1],
                           nchan, start_freq, bandwidth, sample_time,
                           nsamples)[0]
        # host synthetic chunk: each run pays the same host->device
        # conversion inside the search (identical across candidates, so
        # the ranking is unaffected; the warm-up run absorbs the first
        # touch), and every device wait lands in the search's own
        # budget sub-buckets under the tuner's search/autotune span
        synth = synthetic_chunk(nchan, nsamples, mid)

        def make(kern):
            def run():
                return _search_jax(synth, sub_dms, start_freq,
                                   bandwidth, sample_time,
                                   capture_plane=False, dm_block=dm_block,
                                   chan_block=chan_block, dtype=dtype,
                                   kernel=kern)[:5]
            return run

        return {k: make(k) for k in candidates}

    return get_tuner().resolve(
        backend=backend, nchan=nchan, nsamples=nsamples, ndm=ndm,
        dtype=dtype_name(None if f32 else dtype), candidates=candidates,
        static=static, runner_factory=runner_factory)


def resolve_batched_kernel(nchan, nsamples, ndm, batch, start_freq,
                           bandwidth, sample_time, trial_dms,
                           dm_block=None, chan_block=None):
    """``kernel="auto"`` resolution for the multi-beam batched dispatch.

    The beam batcher (:mod:`pulsarutils_tpu.beams.batcher`) runs the
    dedisperse formulation per beam inside one ``lax.map``-stacked
    program, so the candidate families are the traceable formulations
    only — ``"roll"`` and ``"gather"`` (the Pallas kernel drives its
    own untraced grid and cannot ride inside the batch map).  The
    static fallback mirrors :func:`static_search_kernel` restricted to
    that set: roll on CPU, gather elsewhere.  The geometry key carries
    the batch width (``|b<N>``), so a batched winner never leaks into
    single-beam resolution or vice versa; measurement runs the REAL
    batched program over a synthetic beam stack and gates equivalence
    on beam 0's score pack against the static formulation.
    """
    import jax

    backend = jax.default_backend()
    static = "roll" if backend == "cpu" else "gather"
    candidates = _search_candidates(backend, static)

    def runner_factory():
        from ..beams.batcher import batched_probe_runners

        sub_dms = _probe_grid(trial_dms, get_tuner().probe_trials)
        # the probe batch runs one synthetic chunk per beam, distinct
        # seeds — a batched program must be timed on a batch that
        # cannot be constant-folded into one beam's work; the runner
        # construction (and its host readback) lives with the batcher.
        # dm_block/chan_block are the PRODUCTION blocking: the probe
        # must time the program the batcher will actually dispatch
        return batched_probe_runners(candidates, nchan, nsamples, batch,
                                     sub_dms, start_freq, bandwidth,
                                     sample_time, dm_block=dm_block,
                                     chan_block=chan_block)

    return get_tuner().resolve(
        backend=backend, nchan=nchan, nsamples=nsamples, ndm=ndm,
        dtype=dtype_name(None), candidates=candidates, static=static,
        runner_factory=runner_factory, batch=max(int(batch), 1))


def resolve_mesh_kernel(mesh, nchan, nsamples, ndm, start_freq, bandwidth,
                        sample_time, trial_dms, dtype=None):
    """Per-shard rescore/sweep kernel for the sharded paths.

    One applicable variant per mesh, so the resolution is static and
    costs nothing: ``"pallas"`` on an all-TPU float32 mesh (the XLA
    gather is not measured on a chip — :func:`_search_candidates`),
    ``"gather"`` everywhere else (the roll-scan is the gather's own CPU
    formulation inside the shard kernel).  The decision still goes
    through the tuner so it lands in the run's decision ledger under
    the mesh-shaped key; with nothing to measure, the band and trial
    grid arguments play no part in it.
    """
    import jax.numpy as jnp

    all_tpu = all(d.platform == "tpu" for d in mesh.devices.flat)
    f32 = dtype in (None, jnp.float32)
    static = static_mesh_kernel(all_tpu, f32)
    mesh_shape = tuple(int(mesh.shape[a]) for a in mesh.shape)
    return get_tuner().resolve(
        backend="tpu" if all_tpu else "cpu-mesh", nchan=nchan,
        nsamples=nsamples, ndm=ndm,
        dtype=dtype_name(None if f32 else dtype), candidates=[static],
        static=static, mesh_shape=mesh_shape)


# ---------------------------------------------------------------------------
# the periodicity accel-backend contender pair (time_stretch vs fdas)
# ---------------------------------------------------------------------------

#: cross-backend sigma tolerance for the accel-backend harness.  The
#: two formulations window the signal differently — integer-sample
#: stretch resampling scallops power by ~sinc^2(f0*tsamp) where the
#: truncated z/w-response template clips a few percent of template
#: energy — so bit-exact sigma equality ACROSS backends is not a
#: theorem (within a backend, host/jit/mesh stay cell-for-cell
#: identical).  The discrete cell identity IS a theorem at matched
#: trial grids, and that is what the harness pins exactly.
ACCEL_SIGMA_RTOL = 0.12


def accel_tables_match(ref, cand, rtol=ACCEL_SIGMA_RTOL):
    """The PR 7 rule restated for periodicity trial tables.

    ``ref``/``cand`` are top-k candidate tables over the same probe
    trial grid (rows ranked best-first).  Equivalent means: the top
    candidate's discrete cell — DM row, acceleration/jerk trial index,
    harmonic depth — agrees EXACTLY, its frequency lands on the same
    Fourier bin, and its sigma agrees within ``rtol``
    (:data:`ACCEL_SIGMA_RTOL`).  A backend failing this is rejected
    from tuning regardless of how fast it measured: the tuner may
    change speed, never hits.
    """
    if ref is None or cand is None:
        return False
    try:
        if (len(np.asarray(ref["sigma"])) == 0
                or len(np.asarray(cand["sigma"])) == 0):
            return False
        for col in ("dm_index", "accel_index", "jerk_index", "nharm"):
            if col in ref and col in cand and (
                    int(np.asarray(ref[col])[0])
                    != int(np.asarray(cand[col])[0])):
                return False
        if not np.isclose(float(np.asarray(cand["freq"])[0]),
                          float(np.asarray(ref["freq"])[0]),
                          rtol=1e-5, atol=0.0):
            return False
        return bool(np.isclose(float(np.asarray(cand["sigma"])[0]),
                               float(np.asarray(ref["sigma"])[0]),
                               rtol=float(rtol), atol=1e-2))
    except (KeyError, IndexError, TypeError, ValueError):
        return False


def synthetic_accel_plane(ndm, nsamples, tsamp, accel, jerk=0.0,
                          amp=0.6, seed=1601):
    """Seeded noise plane + one accelerated sinusoid on a probe trial.

    The injection row is ``ndm // 3`` (the canary convention) and the
    phase model is the time-stretch backend's own —
    ``phi = f0*(t + a*t^2/(2c) + j*t^3/(6c))`` — with ``f0`` placed on
    an exact Fourier bin well below Nyquist (scalloping and template
    truncation both stay small there), so both backends must put their
    top cell on the injection: the decisive comparison
    :func:`accel_tables_match` makes.
    """
    from ..periodicity.accel import C_M_S

    rng = np.random.default_rng(seed)
    plane = rng.standard_normal((int(ndm), int(nsamples)))
    k0 = max(int(round(0.175 * int(nsamples))), 4)
    f0 = k0 / (int(nsamples) * float(tsamp))
    t = np.arange(int(nsamples)) * float(tsamp)
    phase = f0 * (t + float(accel) * t * t / (2.0 * C_M_S)
                  + float(jerk) * t ** 3 / (6.0 * C_M_S))
    plane[int(ndm) // 3] += amp * np.sin(2.0 * np.pi * phase)
    return plane


def resolve_accel_backend(ndm, nsamples, tsamp, accels, jerks=None,
                          max_harmonics=16, fmin=None, fmax=None,
                          mesh=None):
    """``accel_backend="auto"`` resolution for the periodicity sweep.

    Candidates: ``"time_stretch"`` (PR 12's stretch-resample + one
    rfft per trial) vs ``"fdas"`` (one rfft per DM + batched
    z/w-response correlation, :mod:`~pulsarutils_tpu.periodicity.
    fdas`).  The static choice is ``time_stretch`` — the proven PR 12
    path — so below-floor geometries (every tier-1 test: the
    documented contract) resolve to it with zero side effects; above
    the floor the winner is platform-dependent (arxiv 1601.01165), so
    it is measured over a synthetic accelerated-pulsar plane,
    equivalence-gated by :func:`accel_tables_match` and cached per
    geometry.  The key maps ``nchan=ndm`` (plane rows stand where
    channels do) and ``ndm=ntrials``, under a ``"-accel"`` backend
    suffix so a periodicity decision can never collide with a
    single-pulse kernel entry of the same shape.

    The probe slices the trial grid exactly as the DM probe does —
    evenly — so probe spacing is coarser than the survey grid and the
    injected cell is non-degenerate at the injection frequency.
    """
    import jax

    backend = jax.default_backend()
    static = "time_stretch"
    candidates = [static, "fdas"]
    ntrials = int(len(accels)) * (int(len(jerks))
                                  if jerks is not None else 1)
    mesh_shape = (tuple(int(mesh.shape[a]) for a in mesh.shape)
                  if mesh is not None else None)

    def runner_factory():
        import jax.numpy as jnp

        from ..periodicity.accel import accel_search
        from ..periodicity.fdas import fdas_search

        tuner = get_tuner()
        sub_acc = _probe_grid(accels, tuner.probe_trials)
        sub_jerks = (_probe_grid(jerks, 5)
                     if jerks is not None and len(jerks) > 1 else None)
        inj_a = float(  # putpu-lint: disable=device-trip — host trial grid
            sub_acc[(3 * len(sub_acc)) // 4])
        inj_j = (float(  # putpu-lint: disable=device-trip — host trial grid
            sub_jerks[(3 * len(sub_jerks)) // 4])
            if sub_jerks is not None else 0.0)
        plane = synthetic_accel_plane(ndm, nsamples, tsamp, inj_a,
                                      jerk=inj_j)
        kw = dict(jerks=sub_jerks, max_harmonics=max_harmonics,
                  fmin=fmin, fmax=fmax, topk=8, xp=jnp, mesh=mesh)

        def make(search):
            def run():
                table = search(plane, tsamp, sub_acc, **kw)
                return {k: np.asarray(v) for k, v in table.items()}
            return run

        return {"time_stretch": make(accel_search),
                "fdas": make(fdas_search)}

    return get_tuner().resolve(
        backend=f"{backend}-accel", nchan=int(ndm),
        nsamples=int(nsamples), ndm=ntrials, dtype=dtype_name(None),
        candidates=candidates, static=static,
        runner_factory=runner_factory, mesh_shape=mesh_shape,
        equiv=accel_tables_match)


# ---------------------------------------------------------------------------
# precision-policy candidates (ISSUE 17)
# ---------------------------------------------------------------------------

def resolve_search_policy(formulation, nchan, nsamples, ndm, start_freq,
                          bandwidth, sample_time, trial_dms,
                          dm_block=None, chan_block=None):
    """``precision="auto"`` resolution: the measured (kernel, policy) pair.

    Candidates are ``"<formulation>+<strategy>"`` pairs over the
    :mod:`~pulsarutils_tpu.precision` registry — the ledger/BUDGET_JSON
    record therefore names the winning (kernel, policy) pair directly.
    The static fallback is the formulation's plain ``f32`` pairing, so
    ``PUTPU_AUTOTUNE=off`` and below-floor geometries stay on the
    byte-identical default.  Equivalence is the exact-hit-match harness
    at each STRATEGY'S OWN stated score tolerance
    (``Strategy.score_rtol``) — discrete fields (rebin window, peak
    sample) must match exactly regardless, so a lower-precision variant
    only ever wins, and is only ever cached, after proving it cannot
    move a hit.  The ``"-precision"`` backend suffix keeps these
    decisions in their own key namespace.
    """
    import jax

    from ..precision import STRATEGIES

    backend = jax.default_backend()
    static = f"{formulation}+f32"
    candidates = [static] + [f"{formulation}+{name}"
                             for name in STRATEGIES if name != "f32"]

    def runner_factory():
        from ..ops.search import _offsets_for, _search_jax

        sub_dms = _probe_grid(trial_dms, get_tuner().probe_trials)
        mid = _offsets_for(sub_dms[len(sub_dms) // 2:len(sub_dms) // 2 + 1],
                           nchan, start_freq, bandwidth, sample_time,
                           nsamples)[0]
        synth = synthetic_chunk(nchan, nsamples, mid)

        def make(pair):
            pol = pair.split("+", 1)[1]

            def run():
                scores = _search_jax(synth, sub_dms, start_freq,
                                     bandwidth, sample_time,
                                     capture_plane=False,
                                     dm_block=dm_block,
                                     chan_block=chan_block, dtype=None,
                                     kernel=formulation,
                                     precision=pol)[:5]
                return (pol, scores)

            return run

        return {c: make(c) for c in candidates}

    def equiv(ref, cand):
        ref_pol, ref_scores = ref
        cand_pol, cand_scores = cand
        del ref_pol
        return hits_match(ref_scores, cand_scores,
                          rtol=STRATEGIES[cand_pol].score_rtol)

    return get_tuner().resolve(
        backend=f"{backend}-precision", nchan=nchan, nsamples=nsamples,
        ndm=ndm, dtype=dtype_name(None), candidates=candidates,
        static=static, runner_factory=runner_factory, equiv=equiv)


#: cross-program score tolerance for the harmonic-kernel harness: the
#: Pallas scorer's normalise may round one f32 ulp away from the XLA
#: chain's (see ops/harmonic_pallas.py), so score columns compare at a
#: tight rtol while the discrete cell fields compare exactly.
HARMONIC_SCORE_RTOL = 1e-5


def harmonic_packs_match(ref, cand, rtol=HARMONIC_SCORE_RTOL,
                         bin_scale=None):
    """The PR 7 rule for the periodicity scoring chain.

    ``ref``/``cand`` are per-row spec dicts (``freq, power, nharm,
    log_sf, sigma``) over the same probe plane.  Equivalent means: the
    harmonic depth agrees EXACTLY row-for-row, the peak's frequency
    names the same BIN (``bin_scale`` = ``nsamples * tsamp`` converts
    Hz back to the integer bin; the float itself may differ by one ulp
    between compiled programs — jit turns ``arange/(t*tsamp)`` into a
    reciprocal multiply, eager divides), and the score columns agree
    within ``rtol``.
    """
    if ref is None or cand is None:
        return False
    try:
        if not np.array_equal(np.asarray(ref["nharm"]),
                              np.asarray(cand["nharm"])):
            return False
        rf = np.asarray(ref["freq"], dtype=np.float64)
        cf = np.asarray(cand["freq"], dtype=np.float64)
        if bin_scale is not None:
            if not np.array_equal(np.rint(rf * float(bin_scale)),
                                  np.rint(cf * float(bin_scale))):
                return False
        elif not np.array_equal(rf, cf):
            return False
        for col in ("power", "log_sf", "sigma"):
            if not np.allclose(np.asarray(cand[col]),
                               np.asarray(ref[col]), rtol=float(rtol),
                               atol=1e-6):
                return False
        return True
    except (KeyError, TypeError, ValueError):
        return False


def resolve_harmonic_kernel(nrows, nsamples, tsamp, max_harmonics=16,
                            fmin=None, fmax=None, policy=None):
    """``kernel="auto"`` resolution for the periodicity scoring chain.

    Candidates: ``"xla"`` (the jitted :func:`~pulsarutils_tpu.ops.
    periodicity.spectral_search` chain — the proven default and static
    fallback) vs ``"pallas"`` (the fused one-pass
    :mod:`~pulsarutils_tpu.ops.harmonic_pallas` kernel).  Measured over
    a seeded noise+tone plane at the production geometry, equivalence-
    gated by :func:`harmonic_packs_match` (discrete fields exact,
    scores within :data:`HARMONIC_SCORE_RTOL`) and cached per geometry
    under a ``"-harmonic"`` backend suffix (``nchan`` maps the plane
    rows, ``ndm`` the harmonic depth).
    """
    import jax

    nrows = int(nrows)
    nsamples = int(nsamples)
    tsamp = float(tsamp)
    backend = jax.default_backend()
    static = "xla"
    candidates = [static, "pallas"]
    # the precision policy changes both programs (and the bf16 variant's
    # tolerance), so it is part of the cache key: a winner measured
    # under one policy never leaks to another
    if policy in (None, "f32"):
        key_dtype = dtype_name(None)
    else:
        from ..precision import policy_name

        key_dtype = f"{dtype_name(None)}/{policy_name(policy)}"

    def runner_factory():
        import jax.numpy as jnp

        from ..ops.harmonic_pallas import spectral_search_pallas
        from ..ops.periodicity import spectral_search

        rng = np.random.default_rng(1601)
        probe_rows = min(nrows, 64)
        plane = rng.standard_normal((probe_rows, nsamples)).astype(
            np.float32)
        tt = np.arange(nsamples) * tsamp
        k0 = max(int(round(0.11 * nsamples)), 4)
        f0 = k0 / (nsamples * tsamp)
        plane[probe_rows // 3] += 0.7 * np.sin(2.0 * np.pi * f0 * tt)
        kw = dict(max_harmonics=max_harmonics, fmin=fmin, fmax=fmax,
                  policy=policy)
        kw_xla = dict(kw, xp=jnp)
        plane_dev = jnp.asarray(plane)

        def run_xla():
            spec = spectral_search(plane_dev, tsamp, **kw_xla)
            return {k: np.asarray(v) for k, v in spec.items()}

        def run_pallas():
            spec = spectral_search_pallas(plane, tsamp, **kw)
            return {k: np.asarray(v) for k, v in spec.items()}

        return {"xla": run_xla, "pallas": run_pallas}

    def equiv(ref, cand):
        return harmonic_packs_match(ref, cand,
                                    bin_scale=nsamples * tsamp)

    return get_tuner().resolve(
        backend=f"{backend}-harmonic", nchan=nrows,
        nsamples=nsamples, ndm=int(max_harmonics),
        dtype=key_dtype, candidates=candidates, static=static,
        runner_factory=runner_factory, equiv=equiv)
