"""The exact kernels' rebased offset table is a function of the plan,
not of the chunk (ISSUE 29): ``ops/search.py:_hybrid_offsets`` builds it
when a rescore first asks and keeps it by (trial grid, geometry).

The chip branch of ``_search_jax_hybrid`` (``use_fused``) never runs in
the CPU suite, so the last tests take it the way
``tests/test_chip_compile.py`` does, by steering
``jax.default_backend()`` in the test, with the exact kernel in Pallas
interpret mode and the coarse sweep left on the portable path.
"""

import functools

import numpy as np
import pytest

from pulsarutils_tpu.obs.metrics import REGISTRY
from pulsarutils_tpu.ops import search as search_mod
from pulsarutils_tpu.ops.pallas_dedisperse import rebase_offsets
from pulsarutils_tpu.ops.plan import dedispersion_plan, dm_tier_plan
from pulsarutils_tpu.ops.search import (
    _hybrid_offsets,
    _hybrid_offsets_by_key,
    _offsets_for,
    dedispersion_search,
)

NCHAN, T = 64, 4096
GARGS = (1200.0, 200.0, 0.0005)
HTRU = (1182.0, 400.0, 64e-6)  # cell 3's band and native sample time


def make_noise(nchan, nsamples, seed):
    rng = np.random.default_rng(seed)
    return (np.abs(rng.standard_normal((nchan, nsamples))) * 0.5).astype(
        np.float32)


def inject_pulse(array, dm, amp):
    """One-sample pulse along the exact integer dispersion track at
    ``dm`` (``tests/test_certify.py``'s)."""
    from pulsarutils_tpu.ops.plan import dedispersion_shifts

    nchan, t = array.shape
    out = array.copy()
    shifts = np.rint(np.asarray(dedispersion_shifts(
        nchan, dm, *GARGS))).astype(int)
    out[np.arange(nchan), (t // 2 + shifts) % t] += amp
    return out


def _counter(kind):
    name = f"putpu_plan_cache_{kind}_total"
    for rec in REGISTRY.snapshot():
        if rec["name"] == name and rec.get("labels") == {
                "cache": "hybrid_offsets"}:
            return rec["value"]
    return 0


def _grids():
    """(id, trial grid, geometry, nsamples): a ``dedispersion_plan`` grid,
    every tier of a ``dm_tier_plan``, and a grid whose delays wrap past
    ``T/2`` (band delay ~1,900 samples on a 2,048-sample axis)."""
    flat = np.asarray(dedispersion_plan(NCHAN, 100.0, 200.0, *GARGS))
    out = [("plan", flat, GARGS, T)]
    for tier in dm_tier_plan(NCHAN, 0.0, 1000.0, *HTRU, 0.390625):
        out.append((f"tier{tier.downsample}", np.asarray(tier.trial_dms),
                    (HTRU[0], HTRU[1], tier.sample_time),
                    (1 << 15) // tier.downsample))
    wrap = np.asarray(dedispersion_plan(NCHAN, 900.0, 1000.0, *GARGS))
    out.append(("wrap", wrap, GARGS, 2048))
    return out


GRIDS = _grids()


@pytest.mark.parametrize("grid, geom, nsamples",
                         [g[1:] for g in GRIDS], ids=[g[0] for g in GRIDS])
def test_equals_rebase_of_offsets_for(grid, geom, nsamples):
    want, want_k, want_max = rebase_offsets(
        _offsets_for(grid, NCHAN, *geom, nsamples), nsamples)
    got, roll_k, max_off = _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert (roll_k, max_off) == (want_k, want_max)
    assert type(roll_k) is int and type(max_off) is int


def test_wrapping_grid_does_wrap():
    _, grid, geom, nsamples = GRIDS[-1]
    raw = _offsets_for(grid, NCHAN, *geom, nsamples)
    assert raw.max() > nsamples // 2  # the case the rebase exists for
    _, _, max_off = _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    assert max_off < nsamples


def test_second_call_is_the_same_object_and_a_hit():
    _, grid, geom, nsamples = GRIDS[0]
    first = _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    hits, misses = _counter("hits"), _counter("misses")
    # another array object with the same values (a list, even): the key
    # is the grid's bytes
    again = _hybrid_offsets(list(grid), np.int64(NCHAN), *geom, nsamples)
    assert again[0] is first[0] and again[1:] == first[1:]
    assert _counter("hits") == hits + 1
    assert _counter("misses") == misses


@pytest.mark.parametrize("change", ["nsamples", "sample_time", "grid",
                                    "grid_same_ends"])
def test_changed_key_misses(change):
    _, grid, geom, nsamples = GRIDS[0]
    if change == "nsamples":
        args = (grid, NCHAN, *geom, nsamples * 2)
    elif change == "sample_time":
        args = (grid, NCHAN, geom[0], geom[1], geom[2] * 2, nsamples)
    elif change == "grid":
        args = (grid[:-1], NCHAN, *geom, nsamples)
    else:
        # a tier's grid is not dedispersion_plan's: same end points and
        # length, other trials in between
        other = grid.copy()
        other[1:-1] += 0.25 * np.diff(grid)[1:]
        args = (other, NCHAN, *geom, nsamples)
    _hybrid_offsets_by_key.cache_clear()
    misses = _counter("misses")
    base = _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    changed = _hybrid_offsets(*args)
    assert _counter("misses") == misses + 2
    assert changed[0] is not base[0]
    assert np.array_equal(
        changed[0], rebase_offsets(_offsets_for(*args), args[-1])[0])


def test_table_refuses_a_write():
    _, grid, geom, nsamples = GRIDS[0]
    table, _, _ = _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    with pytest.raises(ValueError):
        table[0, 0] = 1
    rows = table[np.array([0, 2, 2])]  # what rescore() uploads: a copy
    rows[0, 0] = 1
    assert not table.flags.writeable and rows.flags.writeable


def test_lru_is_the_geometry_caches_size():
    from pulsarutils_tpu.tuning.geometry import PLAN_CACHE_SIZE

    assert _hybrid_offsets_by_key.cache_info().maxsize == PLAN_CACHE_SIZE


def test_a_miss_has_a_bucket_and_a_hit_has_none():
    from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

    _, grid, geom, nsamples = GRIDS[0]
    _hybrid_offsets_by_key.cache_clear()
    acct = BudgetAccountant()
    with acct.chunk(0) as miss:
        _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    with acct.chunk(1) as hit:
        _hybrid_offsets(grid, NCHAN, *geom, nsamples)
    assert miss["buckets"]["search/offsets"] > 0
    assert "search/offsets" not in hit["buckets"]


# -- the chip branch ------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _interpreted_rescore_kernel(max_off, dm_block, windows=None, roll_k=0):
    """``ops/search.py:_fused_rescore_kernel`` with its Pallas kernel in
    interpret mode (one trace per row bucket for the whole module)."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.pallas_dedisperse import (
        dedisperse_plane_pallas_traced,
    )

    @jax.jit
    def rescore_rows(data, offs):
        plane = dedisperse_plane_pallas_traced(
            data, offs, max_off, dm_block=dm_block, interpret=True,
            roll_k=roll_k)
        return search_mod.score_profiles_stacked(plane, xp=jnp,
                                                 windows=windows)

    return rescore_rows


@pytest.fixture
def chip_branch(monkeypatch):
    """``_search_jax_hybrid`` with ``use_fused`` true on this CPU: the
    backend question answers "tpu", the exact rescore program is the
    chip's own Pallas kernel in interpret mode, and the coarse sweep runs
    under the real backend (its compiled kernels cannot lower here)."""
    import jax

    real_backend = jax.default_backend
    real_coarse = search_mod._search_jax_fdmt

    def coarse(*args, **kwargs):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", real_backend)
            return real_coarse(*args, **kwargs)

    built = []
    real_offsets_for = search_mod._offsets_for

    def offsets_for(trial_dms, *rest):
        built.append(len(trial_dms))
        return real_offsets_for(trial_dms, *rest)

    monkeypatch.setattr(search_mod, "_search_jax_fdmt", coarse)
    monkeypatch.setattr(search_mod, "_fused_rescore_kernel",
                        _interpreted_rescore_kernel)
    monkeypatch.setattr(search_mod, "_offsets_for", offsets_for)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _hybrid_offsets_by_key.cache_clear()
    yield built
    _hybrid_offsets_by_key.cache_clear()


@pytest.fixture(scope="module")
def floor():
    from pulsarutils_tpu.ops.certify import (
        cert_retention,
        certifiable_snr_floor,
    )

    dms = dedispersion_plan(NCHAN, 100.0, 200.0, *GARGS)
    rho = cert_retention(NCHAN, dms, *GARGS, T).min()
    return certifiable_snr_floor(T, len(dms), rho)


def _hybrid(data, floor):
    return dedispersion_search(data, 100.0, 200.0, *GARGS, backend="jax",
                               kernel="hybrid", snr_floor=floor)


def test_certified_call_builds_no_table(chip_branch, floor):
    lookups = (_counter("hits"), _counter("misses"))
    table = _hybrid(make_noise(NCHAN, T, 7001), floor)
    assert table.meta["certified"], "setup: this noise must certify"
    assert chip_branch == []
    # and looks nothing up
    assert (_counter("hits"), _counter("misses")) == lookups
    assert _hybrid_offsets_by_key.cache_info().currsize == 0


def test_rescoring_call_builds_one_table_and_the_next_none(chip_branch,
                                                           floor):
    sig = inject_pulse(make_noise(NCHAN, T, 7100), 150.0, amp=6.0)
    misses = _counter("misses")
    table = _hybrid(sig, floor)
    assert not table.meta["certified"] and table["exact"].any()
    assert chip_branch == [table.nrows]  # one table, the whole grid
    assert _counter("misses") == misses + 1
    hits = _counter("hits")
    again = _hybrid(sig, floor)  # the next chunk of the same plan
    assert chip_branch == [table.nrows]
    assert _counter("misses") == misses + 1 and _counter("hits") > hits
    for name in table.colnames:
        assert np.array_equal(table[name], again[name]), name


def test_chip_branch_equals_the_portable_rescore(chip_branch, floor,
                                                 monkeypatch):
    """The lazily built table feeds the exact kernel the same offsets the
    portable path computes per bucket: the two branches agree on every
    exactly rescored row."""
    import jax

    sig = inject_pulse(make_noise(NCHAN, T, 7100), 150.0, amp=6.0)
    fused = _hybrid(sig, floor)
    monkeypatch.undo()  # back on the CPU branch
    assert jax.default_backend() == "cpu"
    plain = _hybrid(sig, floor)
    assert fused.argbest() == plain.argbest()
    both = np.asarray(fused["exact"]) & np.asarray(plain["exact"])
    assert both.any()
    for name in ("rebin", "peak"):
        assert np.array_equal(fused[name][both], plain[name][both]), name
    assert np.allclose(fused["snr"][both], plain["snr"][both], rtol=1e-5)


@pytest.mark.parametrize("length", [7, 9])
def test_chip_branch_with_a_longer_ladder_equals_the_portable_rescore(
        chip_branch, monkeypatch, length):
    """ISSUE 32: windows up to the rebase's 128-sample alignment (length 7:
    64) are scored on the rotated plane and the peak corrected on the
    host; wider ones (length 9: 256, on 2^15 samples) undo the rotation on
    the device first.  Either way every exactly rescored row is the
    portable path's, and the float64 backend's window and peak."""
    import jax

    from pulsarutils_tpu.ops.plan import dedispersion_shifts

    ladder = tuple(1 << j for j in range(length))
    width, t = ladder[-1], 128 * ladder[-1]
    data = make_noise(NCHAN, t, 7200 + length)
    shifts = np.rint(np.asarray(dedispersion_shifts(
        NCHAN, 110.0, *GARGS))).astype(int)
    # a pulse of the widest window, on a block of it
    for k in range(width):
        data[np.arange(NCHAN), (40 * width + k + shifts) % t] += \
            0.45 / np.sqrt(width)  # S/N about 12 at the widest window

    def hybrid():
        return dedispersion_search(data, 100.0, 120.0, *GARGS,
                                   backend="jax", kernel="hybrid",
                                   snr_floor=9.0, windows=ladder)

    fused = hybrid()
    assert not fused.meta["certified"]
    monkeypatch.undo()  # back on the CPU branch
    assert jax.default_backend() == "cpu"
    plain = hybrid()
    ref = dedispersion_search(data, 100.0, 120.0, *GARGS, backend="numpy",
                              windows=ladder)
    j = ref.argbest()
    assert fused.argbest() == plain.argbest() == j
    assert int(ref["rebin"][j]) == width
    both = np.asarray(fused["exact"]) & np.asarray(plain["exact"])
    assert both.sum() >= 8
    for name in ("rebin", "peak"):
        assert np.array_equal(fused[name][both], plain[name][both]), name
        assert np.array_equal(fused[name][both],
                              np.asarray(ref[name])[both]), name
    assert np.allclose(fused["snr"][both], plain["snr"][both], rtol=1e-5)


def test_fused_seed_branch_asks_where_it_needs_the_table(chip_branch,
                                                         monkeypatch):
    """Without a floor the chip takes the one-dispatch seed program: it
    gets ``max_off`` and the device copy of the whole table from the same
    function, once."""

    class Stop(Exception):
        pass

    seen = {}

    def seed_kernel(*args, **kwargs):
        seen["max_off"] = args[8]

        def run(*_):
            raise Stop

        return run

    def device_copy(offsets_bytes, shape):
        seen["bytes"], seen["shape"] = offsets_bytes, shape

    monkeypatch.setattr(search_mod, "_fused_hybrid_seed_kernel", seed_kernel)
    monkeypatch.setattr(search_mod, "_device_offsets_cache", device_copy)
    dms = np.asarray(dedispersion_plan(NCHAN, 100.0, 200.0, *GARGS))
    with pytest.raises(Stop):
        dedispersion_search(make_noise(NCHAN, T, 7001), 100.0, 200.0,
                            *GARGS, backend="jax", kernel="hybrid")
    want, _, want_max = rebase_offsets(
        _offsets_for(dms, NCHAN, *GARGS, T), T)
    assert chip_branch == [len(dms)]
    assert seen["max_off"] == want_max
    assert seen["bytes"] == want.tobytes() and seen["shape"] == want.shape
