"""Candidate store with deterministic resume.

The reference persisted candidates as ad-hoc pickles named
``{root}_{istart}-{iend}.pkl`` (``pulsarutils/clean.py:349-351``) and had no
way to resume a crashed search except a manual ``tmin`` (``clean.py:276``,
SURVEY §5).  This store makes both first-class:

* candidates are npz records (:class:`..pipeline.pulse_info.PulseInfo`)
  plus the chunk's full result table, named by chunk index — safe to load,
  idempotent to rewrite;
* a ``progress.json`` ledger records every *processed* chunk (hit or not),
  keyed by a config fingerprint, so a restarted search skips exactly the
  work already done and redoes nothing else.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import time

from ..faults import inject as fault_inject
from ..obs import metrics as _metrics
from ..pipeline.pulse_info import PulseInfo
from ..utils.table import ResultTable
from .atomic import atomic_write_json

logger = logging.getLogger("pulsarutils_tpu")


def config_fingerprint(**kwargs):
    """Stable hash of the search configuration; a resume ledger is only
    valid for identical configuration."""
    blob = json.dumps(kwargs, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


class CandidateStore:
    """``fingerprint=None`` disables the resume ledger entirely (every
    chunk reports not-done, nothing is recorded) — a no-resume run must
    never pollute another configuration's ledger.  Each fingerprint gets
    its own ledger file, so interleaved runs over different files/configs
    in one output directory never invalidate each other."""

    def __init__(self, directory, fingerprint=None, fence=None):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.fingerprint = fingerprint
        #: monotonic lease-epoch fencing token (ISSUE 15).  ``None``
        #: (every single-process path) is byte-inert: no fence file is
        #: ever read or written and the store behaves exactly as before.
        #: Set (the fleet worker passes its lease's epoch), every
        #: ``save_candidate`` consults ``fence_<fingerprint>.json`` and
        #: REFUSES to clobber an artifact another session stamped with
        #: a *higher* epoch — the defence the ledger's union merge
        #: cannot give the ``.npz``/report artifacts: a partitioned
        #: zombie whose lease was stolen keeps computing, and its late
        #: writes must never overwrite the new owner's output.
        self.fence = int(fence) if fence is not None else None
        self._fence_path = (
            os.path.join(self.directory, f"fence_{fingerprint}.json")
            if self.fence is not None and fingerprint is not None
            else None)
        #: artifact writes this session refused under the fence
        self.fenced_rejects = 0
        if fingerprint is None:
            self._ledger_path = None
            self._ledger = {"fingerprint": None, "done": []}
        else:
            self._ledger_path = os.path.join(
                self.directory, f"progress_{fingerprint}.json")
            self._ledger = self._load_ledger()
        #: (st_size, st_mtime_ns) of OUR last ledger write — lets
        #: mark_done skip the concurrent-session merge (one stat
        #: instead of a read+parse) when nobody else has written
        self._last_write_stat = None

    def _load_ledger(self):
        """Load the ledger, surviving a torn/corrupt file.

        ``mark_done`` writes atomically (tmp + rename), but the file can
        still arrive torn — a crash mid-``os.replace`` on some
        filesystems, a partial rsync, disk corruption.  A corrupt ledger
        used to raise ``json.JSONDecodeError`` and kill resume entirely;
        now the bad file is backed up to ``<ledger>.corrupt`` and a
        fresh ledger starts (worst case: already-done chunks are
        re-searched, which resume semantics make idempotent).

        Only parse/shape failures (``ValueError``) mean corruption: a
        transient ``OSError`` on an intact file must propagate, not
        trash hours of resume progress (code-review r8).
        """
        if os.path.exists(self._ledger_path):
            try:
                with open(self._ledger_path) as f:
                    ledger = json.load(f)
                if not isinstance(ledger, dict) \
                        or not isinstance(ledger.get("done"), list):
                    raise ValueError("ledger is not a {fingerprint, done} "
                                     "record")
                return ledger
            except ValueError as exc:
                backup = self._ledger_path + ".corrupt"
                try:
                    os.replace(self._ledger_path, backup)
                except OSError:
                    backup = "<unremovable>"
                logger.warning(
                    "torn/corrupt resume ledger %s (%r): backed up to %s, "
                    "starting a fresh ledger (done chunks will be "
                    "re-searched)", self._ledger_path, exc, backup)
        return {"fingerprint": self.fingerprint, "done": []}

    # -- resume ledger -------------------------------------------------------

    def is_done(self, istart):
        if self.fingerprint is None:
            return False
        return istart in self._ledger["done"]

    def mark_done(self, istart, reason=None):
        """Record a chunk as processed.  ``reason`` marks a chunk done
        **with a reason** — quarantined or persist-dead-lettered: it is
        never re-searched on resume (exact resume semantics), and the
        reason survives in the ledger for the integrity audit.  The
        ``quarantined`` key only appears when a reason was recorded, so
        a clean run's ledger stays byte-identical to pre-hardening.

        Fleet sessions (ISSUE 9) made the on-disk bytes *canonical*:

        * the ``done`` list is kept **sorted** — a single-process run
          already completes chunks in ascending order, so its ledger
          bytes are unchanged, while N workers completing interleaved
          subsets of one file converge on the identical file (the
          byte-identity contract ``tests/test_fleet.py`` pins);
        * each write **merges with the on-disk ledger** first.  Two
          sessions share a ledger only in the work-stealing edge — a
          stalled worker's lease expires, its remaining chunks are
          re-leased, and the straggler still finishes its in-flight
          chunk — and a blind rewrite from the straggler's stale
          in-memory copy would erase the thief's entries.  The merge is
          a union (chunks are only ever *added*), so last-writer-wins
          degrades to no-loss; the coordinator additionally re-reads
          the ledger at every grant/complete, so even a torn interleave
          only causes an idempotent re-search, never a lost chunk.
        """
        if self.fingerprint is None:
            return
        quarantined = self._ledger.get("quarantined", {})
        if istart not in self._ledger["done"] \
                or (reason is not None
                    and quarantined.get(str(istart)) != reason):
            if istart not in self._ledger["done"]:
                self._ledger["done"].append(istart)
            if reason is not None:
                self._ledger.setdefault(
                    "quarantined", {})[str(istart)] = str(reason)
            self._merge_from_disk()
            self._ledger["done"].sort()
            if "quarantined" in self._ledger:
                q = self._ledger["quarantined"]
                # tolerant order: a wrong-shaped-but-parseable ledger
                # (non-numeric key) must stay the carried-through
                # oddity it always was, not a crash of every write
                self._ledger["quarantined"] = {
                    k: q[k] for k in sorted(
                        q, key=lambda k: (0, int(k), "") if
                        str(k).lstrip("-").isdigit() else (1, 0, str(k)))}
            atomic_write_json(self._ledger_path, self._ledger)
            try:
                st = os.stat(self._ledger_path)
                self._last_write_stat = (st.st_size, st.st_mtime_ns)
            except OSError:
                self._last_write_stat = None

    def _merge_from_disk(self):
        """Union the in-memory ledger with the current on-disk one.

        Unreadable/torn disk state is simply not merged (the in-memory
        copy wins): this is a best-effort anti-lost-update measure for
        concurrent fleet sessions, NOT the corruption-recovery path —
        that stays in :meth:`_load_ledger`, which backs the bad file up.

        Cost control: when the file's ``(size, mtime_ns)`` still match
        OUR last write, nobody else has written and the read+parse is
        skipped — a plain single-process survey pays one ``stat`` per
        chunk instead of re-parsing an O(n) ledger n times.  A stale
        match can only *skip* a merge, and the fleet coordinator
        re-reads the ledger at every grant/complete anyway, so the
        worst case stays an idempotent re-search, never a lost chunk.
        """
        try:
            if self._last_write_stat is not None:
                st = os.stat(self._ledger_path)
                if (st.st_size, st.st_mtime_ns) == self._last_write_stat:
                    return
            with open(self._ledger_path) as f:
                disk = json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(disk, dict):
            return
        done = disk.get("done")
        if isinstance(done, list):
            have = set(self._ledger["done"])
            self._ledger["done"].extend(
                c for c in done if isinstance(c, int) and c not in have)
        quarantined = disk.get("quarantined")
        if isinstance(quarantined, dict):
            mine = self._ledger.setdefault("quarantined", {})
            for key, val in quarantined.items():
                mine.setdefault(key, val)

    @property
    def done_chunks(self):
        return sorted(self._ledger["done"])

    @property
    def quarantined_chunks(self):
        """``{str(istart): reason}`` for chunks marked done-with-reason."""
        return dict(self._ledger.get("quarantined", {}))

    # -- candidates ----------------------------------------------------------

    def _base(self, root, istart, iend):
        return os.path.join(self.directory, f"{root}_{istart}-{iend}")

    #: persisted-waterfall element budget: above this, ``save_candidate``
    #: stores a window around the pulse instead of the whole chunk (a
    #: 1024 x 1M survey chunk is 4 GiB of float32 per hit).  The record
    #: is a stored npz, so this is what bounds a hit on disk: at most
    #: 16 MiB of cutout beside the chunk-long profiles
    WATERFALL_BUDGET = 1 << 22

    def save_candidate(self, root, istart, iend, info: PulseInfo,
                       table: ResultTable):
        fault_inject.fire("persist", chunk=istart)
        base = self._base(root, istart, iend)

        def write():
            self.trim_waterfall(info, table).save(base + ".info.npz")
            table.to_npz(base + ".table.npz")

        if self.fenced_write(base, write):
            _metrics.counter("putpu_candidate_bytes_written_total").inc(
                self.pair_bytes(base))
        return base

    @staticmethod
    def pair_bytes(base):
        """Size on disk of a candidate's ``.info.npz`` + ``.table.npz``."""
        return sum(os.path.getsize(base + ext)
                   for ext in (".info.npz", ".table.npz"))

    def save_lineage(self, root, istart, iend, doc):
        """Persist a candidate's lineage doc beside its npz pair
        (ISSUE 18): ``{base}.lineage.json``, atomic, under the same
        epoch fence as the candidate artifacts — a zombie's stale
        lineage can no more clobber the new owner's than its npz can.
        Only called when lineage is armed; off-path runs never touch
        this, so their output directories are byte-identical."""
        base = self._base(root, istart, iend)

        def write():
            atomic_write_json(base + ".lineage.json", doc, indent=2,
                              sort_keys=True, trailing_newline=True)

        self.fenced_write(base, write)
        return base + ".lineage.json"

    # -- the artifact fence (ISSUE 15) ---------------------------------------

    def fenced_write(self, path, write_fn):
        """Run ``write_fn()`` (which writes the artifact at ``path``)
        under the epoch fence; returns ``True`` when it ran.

        Unfenced stores (``fence=None`` — every single-process path)
        just run it.  Fenced stores take a cross-process lockfile
        around check → write → stamp, so the steal edge's
        admit-then-write window cannot interleave two writers: without
        it, a zombie could pass the admit check before the new owner
        stamps and land its bytes *after* — and two concurrent stamps
        could lose the higher epoch (read-merge-write races).  The
        re-search is deterministic, so even a lost race rewrites
        identical bytes today; the lock keeps the fence a guarantee
        rather than a bet on that property.
        """
        if self._fence_path is None:
            write_fn()
            return True
        with self._fence_lock():
            if not self._fence_admits(path):
                return False
            write_fn()
            self._fence_stamp(path)
        return True

    @contextlib.contextmanager
    def _fence_lock(self, timeout_s=30.0):
        """Cross-process mutual exclusion for fenced writes: an
        ``O_EXCL`` lockfile beside the fence map (the one primitive
        that works on the fleet's shared filesystems).  A lock held
        past ``timeout_s`` is presumed abandoned (its holder
        SIGKILLed mid-write) and broken with a warning — availability
        over the defence-in-depth, and contention only exists at the
        steal edge at all."""
        lock_path = self._fence_path + ".lock"
        deadline = time.monotonic() + timeout_s
        fd = None
        while fd is None:
            try:
                fd = os.open(lock_path,
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                if time.monotonic() >= deadline:
                    logger.warning(
                        "breaking abandoned fence lock %s (held past "
                        "%.0fs)", lock_path, timeout_s)
                    try:
                        os.unlink(lock_path)
                    except OSError:
                        pass
                    deadline = time.monotonic() + timeout_s
                else:
                    time.sleep(0.05)
        try:
            yield
        finally:
            os.close(fd)
            try:
                os.unlink(lock_path)
            except OSError:
                pass

    def _read_fence(self):
        """``{artifact base name: epoch}`` off disk.  Unreadable/torn
        state resolves to "nothing stamped" — the worst case is an
        *allowed* write of idempotent bytes, never a lost artifact (the
        same degrade-open rule as :meth:`_merge_from_disk`)."""
        try:
            with open(self._fence_path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return {}
        epochs = doc.get("epochs") if isinstance(doc, dict) else None
        if not isinstance(epochs, dict):
            return {}
        return {str(k): int(v) for k, v in epochs.items()
                if isinstance(v, int)}

    def _fence_admits(self, base):
        """False when another session stamped ``base`` with a higher
        epoch — this writer's lease was stolen and the new owner has
        already written; clobbering it would let a zombie's stale
        compute overwrite live output."""
        name = os.path.basename(base)
        stamped = self._read_fence().get(name)
        if stamped is not None and stamped > self.fence:
            self.fenced_rejects += 1
            _metrics.counter("putpu_fleet_fenced_writes_total").inc()
            logger.warning(
                "fenced write rejected: %s is stamped epoch %d, this "
                "session holds epoch %d (lease stolen; the new owner's "
                "artifact stands)", name, stamped, self.fence)
            return False
        return True

    def _fence_stamp(self, base):
        """Record our epoch for ``base`` (read-merge-write keeping the
        max per artifact; callers hold :meth:`_fence_lock`, so the
        merge cannot lose a concurrent higher stamp)."""
        name = os.path.basename(base)
        epochs = self._read_fence()
        epochs[name] = max(epochs.get(name, 0), self.fence)
        atomic_write_json(self._fence_path,
                          {"schema_version": 1,
                           "epochs": dict(sorted(epochs.items()))})

    def trim_waterfall(self, info, table):
        """Bound the persisted record: full chunk in, pulse cutout out.

        The window covers the dispersed track — ``[peak - pad,
        peak + span + pad]`` with ``span`` the band-crossing delay at
        the candidate's DM and ``pad`` at least the hit's boxcar — then
        block-sum decimates if still over budget.  The passed ``info``
        is untouched (a trimmed *copy* is returned, or ``info`` itself
        when already under budget and on the host), with
        ``cutout_start``/``cutout_decim`` recording the window (see
        :class:`..pipeline.pulse_info.PulseInfo`).

        Tracks wrapping the chunk end are followed circularly (round 6,
        ADVICE r5): the search's roll convention wraps a dispersed tail
        past the chunk end to the chunk start, so for a pulse near the
        end the informative columns live at BOTH edges — the window is
        taken mod ``nbin`` (``cutout_start`` may therefore exceed
        ``nbin - width``; consumers recover absolute columns as
        ``(cutout_start + j * cutout_decim) mod nbin``).

        ``info.allprofs`` may live on the device (a ``jax.Array``, or a
        :class:`~pulsarutils_tpu.pipeline.time_tiles.TiledTierArray`
        that cleans a stretch on demand): the record comes back on the
        host and only what it holds crosses the link.  The window is cut
        on the device; a window that is itself over the budget (864 MB at
        16,384 channels where the chunk is 4 GB) is block-summed there
        too, in float32 (``jit_window_resample``), and its
        ``(nchan, (hi - lo) // decim)`` sums are what is read back.  A
        NumPy array is cut and summed by NumPy, as ever.  The bytes read
        back count in ``putpu_cutout_readback_bytes_total`` (and
        ``putpu_bytes_readback_total``), a window summed on the device
        in ``putpu_cutout_device_decim_total``.
        """
        import dataclasses

        import numpy as np

        wf = info.allprofs
        if wf is None:
            return info
        on_device = not isinstance(wf, np.ndarray)
        if wf.size <= self.WATERFALL_BUDGET:
            return (dataclasses.replace(
                info, allprofs=self._read_back(np.asarray(wf)))
                if on_device else info)
        nbin = wf.shape[1]
        tsamp = (1.0 / (info.pulse_freq * info.nbin)
                 if info.pulse_freq and info.nbin else None)
        best = table.best_row()
        peak = int(best["peak"]) if "peak" in table.colnames else nbin // 2
        span = 256
        if tsamp and info.start_freq and info.bandwidth and best["DM"]:
            from ..ops.plan import delta_delay

            span = int(delta_delay(float(best["DM"]), info.start_freq,
                                   info.start_freq + info.bandwidth)
                       / tsamp) + 1
        # a hit matched at a boxcar wider than the pad starts a whole
        # boxcar after ``peak`` at the latest: the window holds it
        width = int(best["rebin"]) if "rebin" in table.colnames else 1
        pad = max(span // 2, 256, width)
        lo = peak - pad
        hi = peak + span + pad
        if hi - lo >= nbin:  # window covers the whole chunk
            lo, hi = 0, nbin
        inside = lo >= 0 and hi <= nbin
        # circular window: the dispersed tail wrapped past an edge
        cols = slice(lo, hi) if inside else np.arange(lo, hi) % nbin
        # what the window holds decides its decimation, before any of it
        # is moved
        decim = -(-(wf.shape[0] * (hi - lo)) // self.WATERFALL_BUDGET)
        if not on_device:
            cut = (wf[:, cols] if inside
                   else np.take(wf, cols, axis=1, mode="wrap"))
            if decim > 1:
                from ..ops.rebin import quick_resample

                cut = np.asarray(quick_resample(cut, decim))
        elif decim == 1:
            cut = self._read_back(np.asarray(wf[:, cols]))
        else:
            import jax

            from ..ops.rebin import window_resample_program

            # a whole array is cut and summed in one program; a tiled
            # tier's window, or one gathered over the chunk's end, is an
            # array of its own already
            src, start = ((wf, lo) if inside and isinstance(wf, jax.Array)
                          else (wf[:, cols], 0))
            cut = self._read_back(np.asarray(
                window_resample_program(hi - lo, decim)(src,
                                                        np.int32(start))))
            _metrics.counter("putpu_cutout_device_decim_total").inc()
        return dataclasses.replace(info, allprofs=cut,
                                   cutout_start=lo % nbin,
                                   cutout_decim=decim)

    @staticmethod
    def _read_back(cut):
        """``cut`` as it came off the device, counted."""
        _metrics.counter("putpu_cutout_readback_bytes_total").inc(cut.nbytes)
        _metrics.counter("putpu_bytes_readback_total").inc(cut.nbytes)
        return cut

    # backward-compatible alias (pre-round-6 name)
    _trim_waterfall = trim_waterfall

    def load_candidate(self, root, istart, iend):
        base = self._base(root, istart, iend)
        return (PulseInfo.load(base + ".info.npz"),
                ResultTable.from_npz(base + ".table.npz"))

    def candidates(self):
        """Yield ``(root, istart, iend)`` for every stored candidate."""
        for name in sorted(os.listdir(self.directory)):
            if name.endswith(".info.npz"):
                stem = name[: -len(".info.npz")]
                root, _, span = stem.rpartition("_")
                lo, _, hi = span.partition("-")
                yield root, int(lo), int(hi)
