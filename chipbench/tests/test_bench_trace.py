"""Trace reduction: interval arithmetic by hand, and the reduction of a
small trace recorded on a v5e chip (a 1024x1024 bf16 program run five
times under the benchmark's span annotation)."""
import pathlib

import pytest

import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_touching():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]


def test_gaps_are_the_complement_inside_the_span():
    busy = [(2, 4), (6, 8)]
    assert trace_reduce.gaps(busy, 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert trace_reduce.gaps(busy, 3, 7) == [(4, 6)]
    assert trace_reduce.gaps([], 0, 5) == [(0, 5)]


def test_busy_and_idle_add_up_to_the_span():
    busy = trace_reduce.union([(1, 3), (2, 5), (7, 8)])
    idle = trace_reduce.gaps(busy, 0, 10)
    assert sum(e - s for s, e in busy) + sum(e - s for s, e in idle) == 10


@pytest.fixture(scope="module")
def tiny():
    return trace_reduce.reduce(DATA / "tiny_trace")


def test_recorded_trace_span_and_busy(tiny):
    # five runs of the program inside a span of a few milliseconds
    assert 0 < tiny.busy_s < tiny.window_s < 1.0
    assert len(tiny.devices) == 1


def test_recorded_trace_programs_and_ops(tiny):
    assert tiny.program_time("jit__lambda") > 0
    assert tiny.program_time("no_such_program") == 0
    progs = tiny.programs()
    assert progs and all(v > 0 for v in progs.values())
    assert sum(progs.values()) <= tiny.window_s
    ops = tiny.top_ops(10)
    assert ops and all(s > 0 for _, s in ops)
    assert sum(s for _, s in tiny.top_ops(1000)) >= tiny.busy_s * 0.999


def test_recorded_trace_idle_is_attributed(tiny):
    gaps = tiny.top_gaps(10)
    idle = tiny.window_s - tiny.busy_s
    assert gaps and sum(s for _, s in gaps) <= idle + 1e-9


def test_self_time_leaves_out_enclosed_ops():
    ops = [(0, 10, "loop"), (1, 4, "call"), (2, 3, "kernel"),
           (5, 9, "fusion"), (12, 14, "copy")]
    assert trace_reduce.self_times(ops) == [
        ("loop", 3), ("call", 2), ("kernel", 1), ("fusion", 4), ("copy", 2)]
