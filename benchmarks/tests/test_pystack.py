"""The data-stack cliff is real on this interpreter, and `in_one_chunk` removes it."""

import sys
import time

import pytest

from benchmarks.harness.pystack import in_one_chunk


def _leaf(x):
    return x + 1


def _mid(x):
    return _leaf(x) + _leaf(x) + _leaf(x)


def _hot(n=20000):
    t = time.perf_counter()
    for i in range(n):
        _mid(i)
    return time.perf_counter() - t


def _at_depth(depth, roomy):
    a = b = c = d = e = f = g = h = 0  # a frame of some size: a chunk holds about 80 of them
    if depth:
        return _at_depth(depth - 1, roomy)
    return in_one_chunk(_hot) if roomy else _hot()


def _sweep(roomy):
    return [min(_at_depth(d, roomy) for _ in range(2)) for d in range(170)]  # two chunks' worth of depths


@pytest.mark.skipif(sys.version_info[:2] not in ((3, 11), (3, 12)), reason="the chunked data stack of CPython 3.11-3.12")
def test_one_depth_is_many_times_slower_and_a_frame_with_room_cures_it():
    plain, roomy = _sweep(False), _sweep(True)
    typical = sorted(plain)[len(plain) // 2]
    assert max(plain) > 5 * typical, "no chunk-boundary cliff found: the interpreter changed, pystack.py may go"
    assert max(roomy) < 3 * typical


def test_arguments_and_result_pass_through():
    assert in_one_chunk(lambda a, b=0: a - b, 5, b=3) == 2
    assert in_one_chunk(in_one_chunk, int, "7") == 7  # nested: the inner frame fits the outer's chunk
