import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _hermetic_tune_cache(tmp_path_factory):
    """Point the kernel-autotune cache at a per-session temp file: a
    developer's ~/.cache tune entries must never steer test kernel
    selection (byte-identity comparisons would diverge per machine),
    and tests must never write the user's cache."""
    import os

    prev = os.environ.get("PUTPU_TUNE_CACHE")
    os.environ["PUTPU_TUNE_CACHE"] = str(
        tmp_path_factory.mktemp("tune") / "tune_cache.json")
    yield
    if prev is None:
        os.environ.pop("PUTPU_TUNE_CACHE", None)
    else:
        os.environ["PUTPU_TUNE_CACHE"] = prev


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def _force_plan(monkeypatch, path, kw, reached, step=16):
    """Give ``search_by_chunks(path, **kw)`` the largest device (going
    down a ``step``-th at a time) whose tile plan ``reached`` accepts;
    returns that plan.  The budget is found through the planner itself."""
    from pulsarutils_tpu.parallel.stream import plan_time_tiles
    from pulsarutils_tpu.pipeline import search_pipeline as sp

    plan_kw = {k: v for k, v in kw.items()
               if k not in ("make_plots", "resume", "output_dir")}
    survey = sp.plan_survey(path, **plan_kw)
    header, plan = survey["reader"].header, survey["plan"]
    args = sp._tile_geometry(header, plan, survey["tiers"],
                             (kw["dmmin"], kw["dmmax"], survey["windows"]),
                             survey["reader"].packed_bits)
    budget = max(t.bytes for t in plan_time_tiles(
        *args[:-1], float("inf"), args[-1]))
    while True:
        budget = budget * (step - 1) // step
        tiles = plan_time_tiles(*args[:-1], budget, args[-1])
        if reached(tiles):
            break
    # the planner leaves a sixteenth of the device to what it does
    # not reckon
    monkeypatch.setattr(sp, "_device_memory_bytes",
                        lambda: budget * 16 // 15 + 1)
    return tiles


@pytest.fixture
def force_time_tiles(monkeypatch):
    """``force(path, kw, want, tier=0)``: make ``search_by_chunks(path,
    **kw)`` plan ``want`` time tiles for that tier, by giving the planner a
    device that small (the CPU states no memory, so nothing is tiled
    here otherwise); returns the tile plan it will choose."""

    def force(path, kw, want, tier=0):
        tiles = _force_plan(monkeypatch, path, kw,
                            lambda plan: plan[tier].tiles >= want)
        assert tiles[tier].tiles == want, tiles
        return tiles

    return force


@pytest.fixture
def force_delay_bands(monkeypatch):
    """``force(path, kw, want, tier=0)``: as :func:`force_time_tiles`, a
    device so small that tier ``tier``'s smallest tile does not fit and
    its delays are swept in ``want`` bands (in finer steps: at a toy's
    channel count a band's state is hardly smaller than the tier's)."""

    def force(path, kw, want, tier=0):
        tiles = _force_plan(monkeypatch, path, kw,
                            lambda plan: len(plan[tier].bands) >= want,
                            step=512)
        assert len(tiles[tier].bands) == want, tiles
        return tiles

    return force
