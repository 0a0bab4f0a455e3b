"""One reserved frame chunk under every program build.

CPython 3.11+ keeps a thread's Python frames in chunks of 16 KiB and keeps
no spare: a call whose frame does not fit the current chunk maps a new
chunk, and the return unmaps it (``Python/pystate.c:push_chunk``,
``_PyThreadState_PopFrame``).  Tracing a Pallas kernel body binds thousands
of primitives through the same few frames, so where that spot of the stack
straddles a chunk's end every bind pays two system calls and the page
faults: 98 us a call on the chip's host, 55-65 s of a tiered cold start
(``PERF.md`` section 6, PRs 38 and 39).  Which programs thrash is an
accident of the stack's depth.

``push_chunk`` doubles the chunk until the frame that asked for it fits
with 1,000 words to spare.  :func:`reserve_frames` therefore calls the
decorated function through a two-line trampoline whose code object
declares an evaluation stack of :data:`RESERVE_WORDS` words: CPython maps
one chunk of :data:`CHUNK_BYTES` for that frame, and the
:data:`ROOM_BYTES` above it hold everything the function calls, with no
chunk end to cross.  The declared stack is never touched (nothing is
zeroed on entry), so the pages under it are never faulted in; entering
costs one ``mmap`` and leaving one ``munmap``, about 10 us.

Every driver that builds programs, and every thread target that runs
device dispatch on a stack of its own, is decorated; a thread already
above its reserve calls straight through.  There is nothing to set: no
input wants the thrash.
"""
from __future__ import annotations

import functools
import sys
import threading
import types

from ..obs import metrics as _metrics

__all__ = ["reserve_frames", "RESERVE_WORDS", "CHUNK_BYTES", "ROOM_BYTES"]

#: the chunk ``push_chunk`` sizes for the trampoline's frame
CHUNK_BYTES = 512 * 1024
#: words of evaluation stack the trampoline declares: the least that makes
#: ``push_chunk`` double to ``CHUNK_BYTES`` (it stops doubling once
#: 8 x (frame + 1,000) fits), so the most room above it.  Sized from the
#: build stack's measured high-water mark (``PERF.md`` section 6, PR 39).
RESERVE_WORDS = CHUNK_BYTES // 16 - 1000
#: what is left of the chunk above the trampoline's frame (less its own
#: few words and the chunk's header)
ROOM_BYTES = CHUNK_BYTES - 8 * RESERVE_WORDS

class _Thread(threading.local):
    above = False   # this thread stands above its reserve


_thread = _Thread()


def _call(fn, args, kwargs):
    return fn(*args, **kwargs)


_trampoline = types.FunctionType(
    _call.__code__.replace(co_stacksize=RESERVE_WORDS,
                           co_name="_frame_reserve_trampoline",
                           co_qualname="_frame_reserve_trampoline"),
    globals(), "_frame_reserve_trampoline")


def reserve_frames(fn):
    """Decorator: run ``fn`` above one reserved frame chunk (see the
    module's docstring).  Re-entrant per thread; ``fn`` unchanged on an
    interpreter that is not CPython."""
    if sys.implementation.name != "cpython":
        return fn

    @functools.wraps(fn)
    def above_reserve(*args, **kwargs):
        if _thread.above:
            return fn(*args, **kwargs)
        _thread.above = True
        try:
            _metrics.counter("putpu_frame_reserve_entries_total").inc()
            return _trampoline(fn, args, kwargs)
        finally:
            _thread.above = False

    return above_reserve
