"""Room on CPython's data stack, so that a deep, hot call chain never
straddles the end of a stack chunk.

CPython 3.11-3.12 keep interpreter frames on a per-thread data stack made
of 16 KiB chunks.  A call whose frame does not fit the current chunk
allocates a new chunk, and the return frees it again.  A loop that makes
its calls from a frame lying right at a chunk's end therefore pays an
allocation and a release on every call: a fixed Python loop read 30 times
slower at the one stack depth where that happens (benchmarks/tests/
test_pystack.py), and JAX's Pallas-to-Mosaic lowering, which walks 1.4
million equations through a call chain some 50 frames deep, read 3.4-3.7
times slower (PERF.md, PR 23).  Where the chunk ends depends on every
frame below the loop, so the same `prove_tpu_batch` lowered in 95 s from a
script's top level, in 256-375 s from inside a function with more locals,
and in 84-88 s from here.

`in_one_chunk(fn, ...)` calls `fn` from a frame that declares a stack of a
million slots: CPython gives that frame a chunk of its own, 16 MiB of
address space of which the upper half stays free, and every frame below it
lands in that free half.  The memory is never touched, so it costs a few
microseconds and no resident pages.
"""

from __future__ import annotations

_SLOTS = (1 << 20) + 64  # just over 8 MiB of pointers: the chunk is rounded up to 16 MiB, half of it free


def in_one_chunk(fn, *args, **kwargs):
    return fn(*args, **kwargs)


in_one_chunk.__code__ = in_one_chunk.__code__.replace(co_stacksize=_SLOTS)
