"""Candidate / chunk record with periodicity-statistic slots.

Typed re-design of the reference's ``PulseInfo`` (``pulsarutils/clean.py:
27-55``) — the reference decorated a field-less class with ``@dataclass``
(no annotations, so all "fields" were shared class attributes, and ``date``
was attached dynamically at ``clean.py:343``).  Here every field is a real
dataclass field, the Z^2_n / H / M statistic slots are filled by an actual
method (:meth:`PulseInfo.compute_stats`, using the native
:mod:`..ops.robust` statistics), and persistence is npz+json instead of
pickle (:meth:`save` / :meth:`load`) — safe to load, diffable, and
self-describing.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from ..ops.robust import digitize, z_n_and_h

_ARRAY_FIELDS = ("allprofs", "dedisp_profile", "disp_profile",
                 "fold_profile")


@dataclasses.dataclass
class PulseInfo:
    # chunk geometry / metadata
    nbin: int = 0
    nchan: int = 0
    start_freq: float | None = None
    bandwidth: float | None = None
    pulse_freq: float | None = None
    date: float | None = None          # MJD of observation start
    t0: float | None = None            # chunk start time (s into the file)
    istart: int | None = None          # chunk start sample in the file
    # beam provenance (sigproc ``ibeam``/``nbeams``, ISSUE 8): carried
    # on every candidate so the cross-beam coincidence sift and the
    # survey report can label beams without re-opening files
    ibeam: int | None = None
    nbeams: int | None = None

    # candidate parameters
    dm: float | None = None
    snr: float | None = None
    width: float | None = None
    amp: float | None = None
    ph0: float | None = None
    noise_level: float | None = None

    # data products
    allprofs: np.ndarray | None = None        # (nchan, nbin) chunk waterfall
    disp_profile: np.ndarray | None = None    # band-averaged, dispersed
    dedisp_profile: np.ndarray | None = None  # band-averaged, dedispersed
    # persisted-record provenance: when the candidate STORE trims the
    # waterfall to a window around the pulse (a survey chunk's full
    # waterfall is gigabytes — round 5), these record the window so the
    # cutout is self-describing.  ``cutout_start`` is the cutout's
    # first column in the searched chunk's (post-resample) samples;
    # ``cutout_decim`` its time decimation factor.  ``nbin``/``t0``/
    # ``istart`` keep describing the SEARCHED CHUNK, not the cutout.
    cutout_start: int | None = None
    cutout_decim: int | None = None

    # folded-period-search candidate (ops.periodicity stage)
    period_freq: float | None = None   # candidate spin frequency (Hz)
    period_dm: float | None = None     # DM of the plane row it was found in
    period_sigma: float | None = None  # Gaussian-equivalent significance
    period_H: float | None = None      # refined H statistic
    period_M: int | None = None        # best harmonic count of the H-test
    fold_profile: np.ndarray | None = None  # folded pulse profile (nbin,)

    # periodicity statistics (reference clean.py:43-55 slots)
    disp_z2: float | None = None
    disp_z6: float | None = None
    disp_z12: float | None = None
    disp_z20: float | None = None
    disp_H: float | None = None
    disp_M: int | None = None
    dedisp_z2: float | None = None
    dedisp_z6: float | None = None
    dedisp_z12: float | None = None
    dedisp_z20: float | None = None
    dedisp_H: float | None = None
    dedisp_M: int | None = None

    def compute_stats(self):
        """Fill the Z^2_n / H-test slots from the stored profiles.

        Profiles are digitized to counts first (reference intent,
        ``clean.py:183-189,252``).  Harmonic numbers above what the profile
        resolves are left as ``None``.
        """
        for prefix, profile in (("disp", self.disp_profile),
                                ("dedisp", self.dedisp_profile)):
            if profile is None:
                continue
            counts = np.maximum(digitize(np.asarray(profile)), 0)
            nmax = counts.size // 2
            z, h, m = z_n_and_h(
                counts, [n for n in (2, 6, 12, 20) if n <= nmax],
                nmax=min(20, max(nmax, 1)))
            for n, z_n in z.items():
                setattr(self, f"{prefix}_z{n}", float(z_n))
            setattr(self, f"{prefix}_H", float(h))
            setattr(self, f"{prefix}_M", int(m))
        return self

    # -- persistence --------------------------------------------------------

    def save(self, path):
        """Write as ``<path>`` npz (arrays + a json-encoded scalar record).

        The members are stored, not deflated: the arrays are float32
        noise, which zlib shrinks by a fifth to a third at 13-15 MB/s on
        one thread — longer than the chunk the record came from took to
        search.  The store bounds a record instead (``CandidateStore.
        WATERFALL_BUDGET``).  :meth:`load` reads deflated members too, so
        records written before this still load.
        """
        scalars = {}
        arrays = {}
        for f in dataclasses.fields(self):
            value = getattr(self, f.name)
            if f.name in _ARRAY_FIELDS:
                if value is not None:
                    arrays[f.name] = np.asarray(value)
            elif value is not None:
                scalars[f.name] = value
        np.savez(path, __scalars__=json.dumps(scalars), **arrays)
        return path

    @classmethod
    def load(cls, path):
        with np.load(path, allow_pickle=False) as data:
            scalars = json.loads(str(data["__scalars__"]))
            info = cls(**scalars)
            for name in _ARRAY_FIELDS:
                if name in data.files:
                    setattr(info, name, data[name])
        return info
