"""Measured kernel autotuner (ISSUE 7): deterministic tuner tests.

The measurement clock is injected (``measurer=``), so winner selection,
early abandonment, equivalence gating, cache round-trips and the
escape-hatch ladder are all pinned without timing jitter; the handful
of end-to-end tests that run real searches assert *identity* (tuning
may change speed, never hits) and *dispatch counts* (a second run at a
tuned geometry performs zero tuning resolutions), never wall clock.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from pulsarutils_tpu.obs.metrics import REGISTRY
from pulsarutils_tpu.tuning import autotune
from pulsarutils_tpu.tuning.cache import (
    TUNE_SCHEMA_VERSION,
    TuneCache,
    check_artifact,
)
from pulsarutils_tpu.tuning.geometry import (
    PLAN_CACHE_SIZE,
    counted_plan_cache,
    dtype_name,
    geometry_key,
    mesh_tag,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _hermetic_tuner(monkeypatch):
    """Every test runs against its own in-memory tuner (the process
    singleton would otherwise leak decisions/cache across tests) with
    the env knobs cleared."""
    monkeypatch.delenv("PUTPU_AUTOTUNE", raising=False)
    monkeypatch.delenv("PUTPU_AUTOTUNE_MIN", raising=False)
    prev = autotune.set_tuner(autotune.KernelTuner(cache=TuneCache(None)))
    yield
    autotune.set_tuner(prev)


def _counter(name, **labels):
    for rec in REGISTRY.snapshot():
        if rec["name"] == name and rec.get("labels", {}) == labels:
            return rec["value"]
    return 0


def _scores(best=3, n=8, seed=0):
    """A decisive (max, std, snr, window, peak) score tuple."""
    rng = np.random.default_rng(seed)
    snr = rng.uniform(1.0, 5.0, n)
    snr[best] = 10.0
    return (snr + 1.0, np.ones(n), snr,
            np.arange(n, dtype=np.int32),
            np.arange(n, dtype=np.int64) * 2)


def _tuner(cache=None, walls=None, calls=None, **kw):
    """A KernelTuner whose clock is the ``walls`` dict (kernel ->
    seconds); ``calls`` (when given) collects (kernel, reps) pairs."""

    def measurer(kernel, run, reps):
        if calls is not None:
            calls.append((kernel, reps))
        return walls[kernel]

    kw.setdefault("mode", "on")
    kw.setdefault("min_elements", 0)
    return autotune.KernelTuner(cache=cache or TuneCache(None),
                                measurer=measurer if walls else None, **kw)


# ---------------------------------------------------------------------------
# geometry keys + the shared plan-cache policy
# ---------------------------------------------------------------------------

def test_geometry_key_canonical():
    assert geometry_key("cpu", 256, 65536, 512) == \
        "cpu|c256|t65536|d512|float32|m-"
    assert geometry_key("tpu", 1024, 1 << 20, 512, np.float32, (2, 4)) == \
        "tpu|c1024|t1048576|d512|float32|m2x4"
    assert dtype_name(None) == "float32"
    assert dtype_name(np.int16) == "int16"
    assert mesh_tag(None) == "-" and mesh_tag((8, 1)) == "8x1"


def test_counted_plan_cache_counters():
    @counted_plan_cache("test_cache_au", maxsize=2)
    def f(x):
        return x * 2

    h0 = _counter("putpu_plan_cache_hits_total", cache="test_cache_au")
    m0 = _counter("putpu_plan_cache_misses_total", cache="test_cache_au")
    assert f(1) == 2 and f(1) == 2 and f(2) == 4
    assert _counter("putpu_plan_cache_hits_total",
                    cache="test_cache_au") == h0 + 1
    assert _counter("putpu_plan_cache_misses_total",
                    cache="test_cache_au") == m0 + 2
    assert f.cache_info().maxsize == 2
    f.cache_clear()


def test_plan_cache_size_is_uniform():
    # the ISSUE 7 satellite: one documented size for every
    # geometry-keyed plan/program cache (8-vs-16 drift is what it fixes)
    from pulsarutils_tpu.parallel import sharded, sharded_fdmt

    assert PLAN_CACHE_SIZE == 16
    for fn in (sharded_fdmt._plan_offsets,
               sharded_fdmt._build_sharded_fdmt,
               sharded_fdmt._build_fused_sharded_hybrid,
               sharded._sharded_kernel):
        assert fn.cache_info().maxsize == PLAN_CACHE_SIZE


# ---------------------------------------------------------------------------
# the exact-hit-match harness
# ---------------------------------------------------------------------------

def test_hits_match_accepts_float_tolerance():
    ref = _scores()
    cand = tuple(np.array(c, dtype=np.float64) for c in ref)
    cand = (cand[0] * (1 + 1e-7), cand[1], cand[2] * (1 - 1e-7),
            ref[3], ref[4])
    assert autotune.hits_match(ref, cand)


def test_hits_match_rejects_wrong_argbest_and_int_fields():
    ref = _scores(best=3)
    assert not autotune.hits_match(ref, _scores(best=5))
    wrong_window = (ref[0], ref[1], ref[2],
                    np.array(ref[3]) + 1, ref[4])
    assert not autotune.hits_match(ref, wrong_window)
    wrong_scale = (ref[0], ref[1], ref[2] * 1.01, ref[3], ref[4])
    assert not autotune.hits_match(ref, wrong_scale)


# ---------------------------------------------------------------------------
# winner selection (fake clock)
# ---------------------------------------------------------------------------

def test_measured_winner_selected_and_persisted(tmp_path):
    cache = TuneCache(str(tmp_path / "tune.json"))
    calls = []
    tuner = _tuner(cache, walls={"slowk": 0.4, "fastk": 0.1}, calls=calls)
    ref = _scores()
    runners = {"slowk": lambda: ref,
               "fastk": lambda: tuple(np.copy(c) for c in ref)}
    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["slowk", "fastk"],
                        static="slowk", runner_factory=lambda: runners)
    assert got == "fastk"
    entry = cache.lookup(geometry_key("cpu", 64, 4096, 8, "float32"))
    assert entry["kernel"] == "fastk"
    assert entry["source"] == "measured"
    assert entry["measured_s"] == {"slowk": 0.4, "fastk": 0.1}
    # both candidates probed, then measured at full reps
    assert {k for k, _ in calls} == {"slowk", "fastk"}
    # the decision ledger carries the speedup vs the static choice
    dec = autotune.decisions_since(autotune.decision_seq() - 1)[0]
    assert dec["kernel"] == "fastk" and dec["speedup_vs_static"] == 4.0


def test_slow_candidate_abandoned_after_one_rep():
    calls = []
    tuner = _tuner(walls={"fast": 0.1, "awful": 10.0}, calls=calls,
                   reps=5)
    ref = _scores()
    runners = {"fast": lambda: ref,
               "awful": lambda: tuple(np.copy(c) for c in ref)}
    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["fast", "awful"],
                        static="fast", runner_factory=lambda: runners)
    assert got == "fast"
    # the winner's median comes from reps single-timed runs (the first
    # doubles as the abandon probe — no discarded rep); the 100x loser
    # paid exactly ONE timed rep (the PR 1 scalarised gather would
    # otherwise burn ~14x the winner's wall per rep, k times) and is
    # FLAGGED as a single-rep figure, not a median
    assert calls == [("fast", 1)] * 5 + [("awful", 1)]
    (entry,) = tuner.cache.entries().values()
    assert entry["abandoned"] == ["awful"]
    dec = autotune.decisions_since(autotune.decision_seq() - 1)[0]
    assert dec["abandoned"] == ["awful"]


def test_inequivalent_candidate_rejected_even_if_faster():
    rejected0 = _counter("putpu_autotune_equiv_rejected_total")
    tuner = _tuner(walls={"static": 0.4, "cheat": 0.001})
    runners = {"static": lambda: _scores(best=3),
               "cheat": lambda: _scores(best=5)}  # different argbest
    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["static", "cheat"],
                        static="static", runner_factory=lambda: runners)
    assert got == "static"
    assert _counter("putpu_autotune_equiv_rejected_total") == rejected0 + 1
    # the surviving static winner is cached; the rejected variant is
    # neither the winner nor in the measured table (never timed)
    (entry,) = tuner.cache.entries().values()
    assert entry["kernel"] == "static"
    assert "cheat" not in entry.get("measured_s", {})


def test_second_resolve_is_a_memory_hit_and_cache_survives_process(
        tmp_path):
    path = str(tmp_path / "tune.json")
    calls = []
    tuner = _tuner(TuneCache(path), walls={"a": 0.2, "b": 0.1},
                   calls=calls)
    ref = _scores()
    runners = {"a": lambda: ref, "b": lambda: tuple(np.copy(c)
                                                    for c in ref)}

    def resolve(t):
        return t.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                         dtype="float32", candidates=["a", "b"],
                         static="a", runner_factory=lambda: runners)

    assert resolve(tuner) == "b"
    n = len(calls)
    mark = autotune.decision_seq()
    assert resolve(tuner) == "b"          # same-process: memory hit
    assert len(calls) == n                # zero tuning measurements
    assert autotune.decisions_since(mark) == []
    # "new process": same disk cache, measurer that would fail loudly
    def boom(kernel, run, reps):
        raise AssertionError("second process must not measure")

    tuner2 = autotune.KernelTuner(cache=TuneCache(path), mode="on",
                                  min_elements=0, measurer=boom)
    assert resolve(tuner2) == "b"
    dec = autotune.decisions_since(autotune.decision_seq() - 1)[0]
    assert dec["source"] == "cache"


# ---------------------------------------------------------------------------
# the fallback ladder
# ---------------------------------------------------------------------------

def test_mode_off_is_sideeffect_free(monkeypatch):
    monkeypatch.setenv("PUTPU_AUTOTUNE", "off")
    mark = autotune.decision_seq()
    hits0 = _counter("putpu_autotune_cache_hits_total")
    miss0 = _counter("putpu_autotune_cache_misses_total")
    tuner = autotune.KernelTuner(cache=TuneCache(None), min_elements=0)

    def boom():
        raise AssertionError("off mode must not build runners")

    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["roll", "gather"],
                        static="roll", runner_factory=boom)
    assert got == "roll"
    assert autotune.decisions_since(mark) == []
    assert _counter("putpu_autotune_cache_hits_total") == hits0
    assert _counter("putpu_autotune_cache_misses_total") == miss0


def test_cache_only_mode_never_measures():
    tuner = _tuner(walls={}, mode="cache")

    def boom():
        raise AssertionError("cache mode must not build runners")

    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["roll", "gather"],
                        static="roll", runner_factory=boom)
    assert got == "roll"
    dec = autotune.decisions_since(autotune.decision_seq() - 1)[0]
    assert dec["source"] == "static" and "cache-only" in dec["reason"]


def test_below_floor_resolves_statically():
    tuner = autotune.KernelTuner(cache=TuneCache(None), mode="on",
                                 min_elements=1 << 40)

    def boom():
        raise AssertionError("below-floor geometry must not measure")

    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["roll", "gather"],
                        static="roll", runner_factory=boom)
    assert got == "roll"
    dec = autotune.decisions_since(autotune.decision_seq() - 1)[0]
    assert dec["source"] == "static" and "floor" in dec["reason"]


def test_measurement_failure_degrades_to_static():
    def measurer(kernel, run, reps):
        raise RuntimeError("synthetic measurement failure")

    tuner = autotune.KernelTuner(cache=TuneCache(None), mode="on",
                                 min_elements=0, measurer=measurer)
    fb0 = _counter("putpu_autotune_static_fallbacks_total")
    ref = _scores()
    runners = {"roll": lambda: ref, "gather": lambda: ref}
    got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096, ndm=8,
                        dtype="float32", candidates=["roll", "gather"],
                        static="roll", runner_factory=lambda: runners)
    assert got == "roll"
    assert _counter("putpu_autotune_static_fallbacks_total") == fb0 + 1


def test_autotune_mode_parsing(monkeypatch):
    for raw, want in (("off", "off"), ("0", "off"), ("cache", "cache"),
                      ("", "on"), ("on", "on"), ("garbage-value", "on")):
        monkeypatch.setenv("PUTPU_AUTOTUNE", raw)
        assert autotune.autotune_mode() == want


def test_static_heuristic_spellings():
    assert autotune.static_search_kernel("cpu") == "roll"
    assert autotune.static_search_kernel("tpu") == "pallas"
    assert autotune.static_search_kernel("tpu", f32=False) == "gather"
    assert autotune.static_search_kernel("gpu") == "gather"
    assert autotune.static_search_kernel("cpu",
                                         capture_plane="memmap") == "pallas"
    assert autotune.static_mesh_kernel(True) == "pallas"
    assert autotune.static_mesh_kernel(False) == "gather"


def test_nothing_is_measured_on_a_tpu(monkeypatch):
    """ISSUE 22: measuring means RUNNING every candidate at the chunk's
    own geometry, and on a TPU the XLA gather is refused by the
    compiler at survey width (and is said to take the worker down — not
    an exception the tuner could catch).  The TPU candidate list is the
    static choice alone, so ``kernel="auto"`` resolves there with no
    runner ever built — even far above the tune floor, tuner on."""
    import jax

    assert autotune._search_candidates("tpu", "pallas") == ["pallas"]
    assert autotune._search_candidates("tpu", "gather") == ["gather"]
    assert autotune._search_candidates("cpu", "roll") == ["roll", "gather"]

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("PUTPU_AUTOTUNE", "on")
    prev = autotune.set_tuner(autotune.KernelTuner(
        cache=TuneCache(None), min_elements=0,
        measurer=lambda *a: pytest.fail("measured a candidate on a TPU")))
    try:
        assert autotune.resolve_search_kernel(
            1024, 1 << 20, 154, None, False, 1200.0, 200.0, 5e-4,
            np.linspace(300.0, 400.0, 154)) == "pallas"
    finally:
        autotune.set_tuner(prev)


# ---------------------------------------------------------------------------
# the persistent cache: versioning + torn-file recovery
# ---------------------------------------------------------------------------

def test_cache_version_mismatch_rejected_not_corrupted(tmp_path):
    path = tmp_path / "tune.json"
    stale = {"schema_version": TUNE_SCHEMA_VERSION + 1,
             "entries": {"cpu|c1|t1|d1|float32|m-": {"kernel": "roll"}}}
    path.write_text(json.dumps(stale))
    cache = TuneCache(str(path))
    # entries rejected (stale schemas must not drive selection) ...
    assert cache.entries() == {}
    # ... but the FILE is not corruption: kept in place, no .corrupt
    assert json.loads(path.read_text()) == stale
    assert not (tmp_path / "tune.json.corrupt").exists()
    # the next store rewrites at the current version
    cache.store("k", "roll")
    doc = json.loads(path.read_text())
    assert doc["schema_version"] == TUNE_SCHEMA_VERSION
    assert set(doc["entries"]) == {"k"}


def test_corrupt_cache_backed_up_and_rebuilt(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text('{"schema_version": 1, "entr')  # torn write
    cache = TuneCache(str(path))
    assert cache.entries() == {}
    backup = tmp_path / "tune.json.corrupt"
    assert backup.exists()  # the PR 4 torn-ledger rule
    assert backup.read_text().startswith('{"schema_version"')
    cache.store("k", "roll", measured_s={"roll": 0.1}, reps=3)
    fresh = TuneCache(str(path))
    assert fresh.lookup("k")["kernel"] == "roll"


def test_unreadable_cache_degrades_to_empty_not_crash(tmp_path):
    # present-but-unreadable file (permissions, stale mount — here: a
    # directory, whose open() raises IsADirectoryError, an OSError):
    # NOT corruption, NOT fatal — empty cache, file left untouched
    blocked = tmp_path / "cachedir"
    blocked.mkdir()
    cache = TuneCache(str(blocked))
    assert cache.entries() == {}
    assert blocked.is_dir()                      # untouched
    assert not (tmp_path / "cachedir.corrupt").exists()


def test_persist_failure_keeps_measured_winner():
    calls = []
    tuner = _tuner(walls={"slowk": 0.4, "fastk": 0.1}, calls=calls)

    def bad_store(*a, **kw):
        raise OSError("read-only cache path")

    tuner.cache.store = bad_store
    ref = _scores()
    runners = {"slowk": lambda: ref,
               "fastk": lambda: tuple(np.copy(c) for c in ref)}

    def resolve():
        return tuner.resolve(backend="cpu", nchan=64, nsamples=4096,
                             ndm=8, dtype="float32",
                             candidates=["slowk", "fastk"],
                             static="slowk",
                             runner_factory=lambda: runners)

    # the paid-for measurement survives the persist failure ...
    assert resolve() == "fastk"
    dec = autotune.decisions_since(autotune.decision_seq() - 1)[0]
    assert dec["source"] == "measured"
    # ... and is remembered in-process: no re-measurement
    n = len(calls)
    assert resolve() == "fastk"
    assert len(calls) == n


def test_cache_clear_and_match(tmp_path):
    cache = TuneCache(str(tmp_path / "t.json"))
    cache.store("cpu|a", "roll")
    cache.store("tpu|b", "pallas")
    assert cache.clear(match="cpu|") == 1
    assert set(cache.entries()) == {"tpu|b"}
    assert cache.clear() == 1
    assert TuneCache(str(tmp_path / "t.json")).entries() == {}


def test_check_artifact_rules(tmp_path):
    good = tmp_path / "TUNE_good.json"
    TuneCache(str(good)).store("cpu|c1|t1|d1|float32|m-", "roll")
    ok, detail = check_artifact(str(good))
    assert ok and "1 tuned key" in detail
    ok, detail = check_artifact(str(tmp_path / "absent.json"))
    assert not ok and "missing" in detail
    stale = tmp_path / "TUNE_stale.json"
    stale.write_text(json.dumps({"schema_version": 0, "entries": {}}))
    ok, detail = check_artifact(str(stale))
    assert not ok and "schema_version" in detail
    notatune = tmp_path / "TUNE_shape.json"
    notatune.write_text(json.dumps({"anything": 1}))
    ok, detail = check_artifact(str(notatune))
    assert not ok


def test_committed_tune_artifact_is_current():
    # the gate's rule, asserted in tier-1 too: the committed CPU
    # artifact must parse at the current schema version and must carry
    # the PR 1 roll-scan winner for its streaming-geometry key
    path = os.path.join(REPO, "TUNE_cpu.json")
    ok, detail = check_artifact(path)
    assert ok, detail
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert any(e["kernel"] == "roll" and k.startswith("cpu|")
               for k, e in entries.items())


# ---------------------------------------------------------------------------
# budget footer + survey report surfacing
# ---------------------------------------------------------------------------

def test_budget_footer_carries_this_streams_decisions():
    from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

    tuner = autotune.KernelTuner(cache=TuneCache(None), mode="on")
    acct = BudgetAccountant()
    acct.begin_stream()
    with acct.chunk(0):
        got = tuner.resolve(backend="cpu", nchan=64, nsamples=4096,
                            ndm=8, dtype="float32", candidates=["roll"],
                            static="roll")
    assert got == "roll"
    j = acct.to_json()
    assert [d["kernel"] for d in j["autotune"]] == ["roll"]
    assert j["autotune"][0]["source"] == "static"
    # an accountant whose stream saw no resolutions keeps the pre-tuner
    # ledger bytes: no "autotune" key at all
    quiet = BudgetAccountant()
    quiet.begin_stream()
    with quiet.chunk(0):
        pass
    assert "autotune" not in quiet.to_json()


def test_report_renders_autotune_section():
    from pulsarutils_tpu.obs import report as obs_report

    budget = {"chunks": 1, "wall_s": 1.0, "buckets_s": {},
              "unattributed_s": 0.0, "attributed_pct": 100.0,
              "autotune": [{"key": "cpu|c256|t65536|d257|float32|m-",
                            "kernel": "roll", "source": "measured",
                            "static": "roll", "speedup_vs_static": 1.0,
                            "measured_s": {"roll": 1.17, "gather": 7.1}}]}
    rec = obs_report.build_report(meta={"root": "r"}, budget=budget)
    md = obs_report.render_markdown(rec)
    assert "## Kernel autotuning" in md
    # the key renders with "|" replaced (raw pipes would break the
    # markdown table into extra columns)
    assert "cpu·c256·t65536·d257·float32·m-" in md and "measured" in md
    assert "cpu|c256" not in md
    html = obs_report.render_html(rec)
    assert "Kernel autotuning" in html
    # and the stated-absence arm
    md_off = obs_report.render_markdown(obs_report.build_report(
        meta={"root": "r"}, budget={"chunks": 0, "wall_s": 0.0,
                                    "buckets_s": {},
                                    "unattributed_s": 0.0,
                                    "attributed_pct": None}))
    assert "No `kernel=\"auto\"` tuner resolutions" in md_off


# ---------------------------------------------------------------------------
# end-to-end through the real search (small geometries, identity only)
# ---------------------------------------------------------------------------

def _small_problem():
    rng = np.random.default_rng(7)
    nchan, nsamples = 32, 4096
    data = rng.standard_normal((nchan, nsamples)).astype(np.float32)
    dms = np.linspace(300.0, 330.0, 12)
    return data, dms, (1200.0, 200.0, 0.0005)


def test_autotune_off_byte_identical_to_static_heuristic(monkeypatch):
    from pulsarutils_tpu.ops.search import dedispersion_search

    data, dms, geom = _small_problem()
    monkeypatch.setenv("PUTPU_AUTOTUNE", "off")
    t_off = dedispersion_search(data, None, None, *geom, backend="jax",
                                trial_dms=dms, kernel="auto")
    # CPU static heuristic is the PR 1 roll-scan — the "auto" spelling
    # under the escape hatch must be the explicit spelling, byte for byte
    t_static = dedispersion_search(data, None, None, *geom,
                                   backend="jax", trial_dms=dms,
                                   kernel="roll")
    for col in ("DM", "max", "std", "snr", "rebin", "peak"):
        np.testing.assert_array_equal(np.asarray(t_off[col]),
                                      np.asarray(t_static[col]))


def test_measured_auto_matches_static_hits_end_to_end():
    from pulsarutils_tpu.ops.search import dedispersion_search

    data, dms, geom = _small_problem()
    t_ref = dedispersion_search(data, None, None, *geom, backend="jax",
                                trial_dms=dms, kernel="roll")
    calls = []

    def counting_measurer(kernel, run, reps):
        calls.append(kernel)
        return autotune.measure_kernel_wall(kernel, run, reps)

    tuner = autotune.KernelTuner(cache=TuneCache(None), mode="on",
                                 min_elements=0, reps=1, probe_trials=8,
                                 measurer=counting_measurer)
    autotune.set_tuner(tuner)
    t_auto = dedispersion_search(data, None, None, *geom, backend="jax",
                                 trial_dms=dms, kernel="auto")
    assert calls, "forced-floor tuner must actually measure"
    for col in ("DM", "max", "std", "snr", "rebin", "peak"):
        np.testing.assert_array_equal(np.asarray(t_auto[col]),
                                      np.asarray(t_ref[col]))
    # second run, same geometry: ZERO tuning measurements (the PR 2
    # dispatch-count pattern applied to tuning dispatches)
    n = len(calls)
    mark = autotune.decision_seq()
    t_again = dedispersion_search(data, None, None, *geom,
                                  backend="jax", trial_dms=dms,
                                  kernel="auto")
    assert len(calls) == n
    assert autotune.decisions_since(mark) == []
    for col in ("snr", "peak"):
        np.testing.assert_array_equal(np.asarray(t_again[col]),
                                      np.asarray(t_auto[col]))


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------

def _cli():
    spec = importlib.util.spec_from_file_location(
        "autotune_cli", os.path.join(REPO, "tools", "autotune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_show_clear_verify(tmp_path, capsys):
    cli = _cli()
    path = str(tmp_path / "tune.json")
    TuneCache(path).store("cpu|c64|t4096|d8|float32|m-", "roll",
                          measured_s={"roll": 0.1, "gather": 0.9}, reps=3)
    assert cli.main(["show", "--cache", path]) == 0
    out = capsys.readouterr().out
    assert "cpu|c64|t4096|d8|float32|m-" in out and "roll" in out
    assert cli.main(["verify", "--cache", path]) == 0
    # wrong expected version fails, exit 1 (the gate's rule)
    assert cli.main(["verify", "--cache", path,
                     "--expect-version",
                     str(TUNE_SCHEMA_VERSION + 1)]) == 1
    # unknown kernel name in an entry fails verify
    TuneCache(path).store("cpu|bogus", "warp-drive")
    assert cli.main(["verify", "--cache", path]) == 1
    assert cli.main(["clear", "--cache", path]) == 0
    assert TuneCache(path).entries() == {}


def test_cli_tune_small_geometry(tmp_path, capsys):
    cli = _cli()
    path = str(tmp_path / "tune.json")
    rc = cli.main(["tune", "--nchan", "32", "--nsamples", "2048",
                   "--ndm", "8", "--probe-trials", "8", "--reps", "1",
                   "--cache", path])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["kernel"] in ("roll", "gather", "pallas")
    entries = TuneCache(path).entries()
    assert len(entries) == 1
    (key, entry), = entries.items()
    assert entry["source"] == "measured"
    assert key.startswith(("cpu|", "tpu|", "gpu|"))
