"""The reserved frame chunk under every program build (ISSUE 39,
``utils/frame_reserve.py``): CPython keeps a thread's frames in 16 KiB
chunks with no spare, so a loop of calls that straddles a chunk's end maps
and unmaps a chunk at every call.  The drivers enter through a frame so
large that CPython sizes one chunk for the whole stack above it.

Run this file when the interpreter is upgraded: (a) fails if a later
``push_chunk`` no longer sizes the chunk to the frame that asked for it.
"""
import inspect
import statistics
import sys
import threading
import time
import types

import pytest

from pulsarutils_tpu.beams import multibeam, service
from pulsarutils_tpu.faults import policy
from pulsarutils_tpu.obs import metrics
from pulsarutils_tpu.parallel import stream
from pulsarutils_tpu.periodicity import driver as period_driver
from pulsarutils_tpu.pipeline import search_pipeline
from pulsarutils_tpu.resilience import ladder
from pulsarutils_tpu.utils import frame_reserve
from pulsarutils_tpu.utils.frame_reserve import reserve_frames

COUNTER = "putpu_frame_reserve_entries_total"
TRAMPOLINE = frame_reserve._trampoline.__code__

pytestmark = pytest.mark.skipif(
    sys.implementation.name != "cpython",
    reason="the frame chunks are CPython's")


def entries():
    return metrics.REGISTRY.counter(COUNTER).value


def trampolines_below():
    """How many frames of the caller's stack are the trampoline's."""
    n, f = 0, sys._getframe(1)
    while f is not None:
        n += f.f_code is TRAMPOLINE
        f = f.f_back
    return n


class _Stop(Exception):
    """Raised by a stub to leave a driver before it does any work."""


# -- (a) no depth of the stack thrashes above the reserve ------------------

def _leaf(x):
    y = x + 1
    return y


def _loop(n):
    t0 = time.perf_counter()
    for i in range(n):
        _leaf(i)
    return time.perf_counter() - t0


def _nest(d, n):
    return _loop(n) if d == 0 else _nest(d - 1, n)


def _depth_scan(call):
    """Seconds for 20,000 calls of a three-line function made from a
    recursion ``call(d, n)`` ``d`` frames deep, ``d`` over two periods of the 16 KiB chunk
    (136 of ``_nest``'s frames), best of three a depth."""
    return [min(call(d, 20000) for _ in range(3)) for d in range(272)]


def _worst_over_median(times):
    return max(times) / statistics.median(times)


def test_without_the_reserve_some_depth_thrashes():
    # what the reserve is for; an interpreter that keeps a spare chunk
    # shows none, and the reserve can then go
    ratio = _worst_over_median(_depth_scan(_nest))
    if ratio < 5:
        pytest.skip(f"no depth over {ratio:.1f} times the median: this "
                    "interpreter does not thrash at a chunk's end")
    assert ratio > 5


def test_no_depth_thrashes_above_the_reserve():
    ratio = _worst_over_median(_depth_scan(reserve_frames(_nest)))
    assert ratio < 5, f"a depth takes {ratio:.1f} times the median"


def test_no_depth_thrashes_above_the_reserve_in_a_fresh_thread():
    box = {}

    def target():
        box["ratio"] = _worst_over_median(
            _depth_scan(reserve_frames(_nest)))

    t = threading.Thread(target=target)
    t.start()
    t.join(120)
    assert not t.is_alive()
    assert box["ratio"] < 5, f"a depth takes {box['ratio']:.1f} times"


def test_the_room_is_the_chunk_less_the_trampolines_frame():
    # push_chunk doubles 16 KiB until 8 x (frame + 1,000 words) fits: the
    # declared stack is the least that asks for CHUNK_BYTES
    words = frame_reserve.RESERVE_WORDS
    assert TRAMPOLINE.co_stacksize == words
    frame = words + TRAMPOLINE.co_nlocals  # and the frame's header
    assert 8 * (frame + 1000) <= frame_reserve.CHUNK_BYTES
    assert 8 * (frame + 1000) > frame_reserve.CHUNK_BYTES // 2
    assert frame_reserve.ROOM_BYTES == frame_reserve.CHUNK_BYTES - 8 * words
    assert frame_reserve.ROOM_BYTES > frame_reserve.CHUNK_BYTES // 2


# -- (b) every driver and both thread targets stand above it ---------------

def _enter_search_by_chunks(monkeypatch, seen, tmp_path):
    def stub(policy_name):
        seen.append(trampolines_below())
        raise _Stop

    monkeypatch.setattr(search_pipeline, "resolve_integrity_policy", stub)
    with pytest.raises(_Stop):
        search_pipeline.search_by_chunks("no_such.fil")


def _enter_multibeam_search(monkeypatch, seen, tmp_path):
    def stub(fnames):
        seen.append(trampolines_below())
        raise _Stop

    monkeypatch.setattr(multibeam, "open_beams", stub)
    with pytest.raises(_Stop):
        multibeam.multibeam_search(["no_such.fil"])


def _enter_stream_search(monkeypatch, seen, tmp_path):
    def stub():
        seen.append(trampolines_below())
        raise _Stop

    monkeypatch.setattr(ladder, "reset", stub)
    with pytest.raises(_Stop):
        stream.stream_search([], 100.0, 200.0, 1400.0, 200.0, 1e-3)


def _enter_periodicity_search(monkeypatch, seen, tmp_path):
    def stub(fname, **kwargs):
        seen.append(trampolines_below())
        raise _Stop

    monkeypatch.setattr(search_pipeline, "plan_survey", stub)
    with pytest.raises(_Stop):
        period_driver.periodicity_search("no_such.fil")


def _enter_dispatch_watchdog(monkeypatch, seen, tmp_path):
    main = threading.get_ident()

    def fn():
        assert threading.get_ident() != main
        seen.append(trampolines_below())
        return "value"

    assert policy.call_with_deadline(fn, timeout_s=60) == "value"


def _enter_survey_service(monkeypatch, seen, tmp_path):
    done = threading.Event()

    def stub(self):
        seen.append(trampolines_below())
        self._queue.clear()
        done.set()
        return []

    monkeypatch.setattr(service.SurveyService, "_pop_batch", stub)
    svc = service.SurveyService(str(tmp_path), batch_window_s=0)
    try:
        assert threading.current_thread() is not svc._worker
        with svc._lock:
            svc._queue.append("job-stub")
        svc._wake.set()
        assert done.wait(60)
    finally:
        svc.close()


@pytest.mark.parametrize("enter", [
    _enter_search_by_chunks, _enter_multibeam_search, _enter_stream_search,
    _enter_periodicity_search, _enter_dispatch_watchdog,
    _enter_survey_service], ids=lambda f: f.__name__[len("_enter_"):])
def test_each_entry_stands_above_one_reserve(enter, monkeypatch, tmp_path):
    seen = []
    before = entries()
    enter(monkeypatch, seen, tmp_path)
    assert seen == [1]
    assert entries() - before == 1
    assert trampolines_below() == 0


def test_a_nested_driver_call_maps_no_second_chunk(monkeypatch):
    seen = []

    def inner_stub(policy_name):
        seen.append(trampolines_below())
        raise _Stop

    def plan_stub(fname, **kwargs):
        # where periodicity_search calls search_by_chunks
        return search_pipeline.search_by_chunks(fname)

    monkeypatch.setattr(search_pipeline, "resolve_integrity_policy",
                        inner_stub)
    monkeypatch.setattr(search_pipeline, "plan_survey", plan_stub)
    before = entries()
    with pytest.raises(_Stop):
        period_driver.periodicity_search("no_such.fil")
    assert seen == [1]
    assert entries() - before == 1


def test_a_thread_enters_again_after_it_left():
    before = entries()
    f = reserve_frames(trampolines_below)
    assert [f(), f()] == [1, 1]
    assert entries() - before == 2


def test_threads_do_not_share_the_flag():
    # the main thread above its reserve; a new thread has its own stack
    box = {}

    @reserve_frames
    def outer():
        t = threading.Thread(
            target=lambda: box.update(n=reserve_frames(trampolines_below)()))
        t.start()
        t.join(60)
        return trampolines_below()

    before = entries()
    assert outer() == 1
    assert box == {"n": 1}
    assert entries() - before == 2


def test_the_benchmarks_metric_reads_the_counter_the_helper_bumps():
    import json
    import os

    from pulsarutils_tpu.obs.names import METRIC_NAMES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "layer_metrics",
                           "cold_frame_reserve_entries.json")) as f:
        spec = json.load(f)
    assert spec["source"]["kind"] == "registry_counter"
    assert spec["source"]["key"] == COUNTER and COUNTER in METRIC_NAMES
    assert (spec["source"]["pass"], spec["source"]["per"]) == ("cold", "total")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == spec["name"]]
    assert "workloads" not in entry and entry["moves"] == "setup_s"
    before = entries()
    reserve_frames(lambda: None)()
    assert entries() - before == 1


# -- (c) what goes in and what comes out is the function's own -------------

def test_arguments_and_return_value_pass_through():
    @reserve_frames
    def f(a, b=2, *rest, key=None, **more):
        return a, b, rest, key, more

    assert f(1, 3, 4, key="k", z=0) == (1, 3, (4,), "k", {"z": 0})
    assert f(1) == (1, 2, (), None, {})


def test_an_exception_passes_through_and_the_flag_is_dropped():
    @reserve_frames
    def f():
        raise KeyError("mine")

    with pytest.raises(KeyError, match="mine") as err:
        f()
    tb = [fr.name for fr in err.traceback]
    assert tb[-1] == "f" and "_frame_reserve_trampoline" in tb
    assert not frame_reserve._thread.above


def test_system_exit_passes_through():
    @reserve_frames
    def f():
        raise SystemExit(3)

    with pytest.raises(SystemExit) as err:
        f()
    assert err.value.code == 3
    assert not frame_reserve._thread.above


def test_search_by_chunks_keeps_its_signature():
    fn = search_pipeline.search_by_chunks
    assert inspect.signature(fn) == inspect.signature(fn.__wrapped__)
    assert list(inspect.signature(fn).parameters)[:3] == [
        "fname", "chunk_length", "new_sample_time"]
    assert fn.__name__ == "search_by_chunks" and fn.__doc__


# -- (d) only CPython has the chunks ---------------------------------------

def test_another_interpreter_gets_the_function_itself(monkeypatch):
    monkeypatch.setattr(frame_reserve, "sys", types.SimpleNamespace(
        implementation=types.SimpleNamespace(name="pypy")))

    def f():
        return 1

    assert reserve_frames(f) is f
