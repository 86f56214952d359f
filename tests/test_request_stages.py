"""Request stages and the pump loop's host phases, timed inside the server.

A request's time to first token is the command-queue wait (``submit`` ->
its thunk on the pump thread), the admission-queue wait (engine enqueue ->
the admission attempt that succeeds, ``Request.admit_t``) and the
admission (-> first token). Each stage is a cumulative ``LogHistogram`` in
``stats()``; the loop's host time between consecutive decode chunks is
``host_gap_s`` over ``host_gaps``; the pump thread's phases are
``serve.*`` profiler spans.
"""
import pathlib
import sys
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs.registry import ARCHS
from repro.serving.fleet import FleetServer
from repro.serving.scheduler import LogHistogram
from repro.serving.server import EngineConfig, LLMServer, SamplingParams

B = LogHistogram.BOUNDS_S


def _cfg():
    return ARCHS["qwen2.5-3b"].reduced(dtype="float32", param_dtype="float32",
                                       vocab_size=512)


def _server(**kw):
    return LLMServer(_cfg(), num_slots=3, capacity=256, pump=True,
                     engine_cfg=EngineConfig(cache_mode="paged",
                                             page_size=16), **kw)


# ---- the histogram ----------------------------------------------------------
def test_bounds_span_0_1_ms_to_1000_s_at_ratio_under_1_1():
    assert B[0] == pytest.approx(1e-4) and B[-1] == pytest.approx(1e3)
    ratios = [b / a for a, b in zip(B, B[1:])]
    assert max(ratios) <= 1.1 and min(ratios) > 1.09


@pytest.mark.parametrize("seconds,bucket", [
    (0.0, 0), (1e-6, 0), (B[0], 0), (B[0] * 1.01, 1), (B[10], 10),
    (B[10] * 1.0001, 11), (0.5, None), (B[-1], len(B) - 1),
    (5e3, len(B))])
def test_histogram_bucketing(seconds, bucket):
    h = LogHistogram()
    h.add(seconds)
    c = h.counts()
    assert len(c) == len(B) + 1 and sum(c) == 1
    i = c.index(1)
    if bucket is None:                       # inside its bucket's edges
        assert B[i - 1] < seconds <= B[i]
    else:
        assert i == bucket


def test_histogram_merge_adds_bucket_by_bucket():
    a, b = LogHistogram(), LogHistogram()
    for x in (0.001, 0.002, 3.0):
        a.add(x)
    for x in (0.002, 50.0):
        b.add(x)
    m = LogHistogram.merge([a.counts(), b.counts()])
    assert m == [x + y for x, y in zip(a.counts(), b.counts())]
    assert sum(m) == 5
    c = a.counts()
    c[0] += 1                                # counts() is a copy
    assert a.counts() != c


# ---- stamps and stages on a pumped server -----------------------------------
def test_stages_are_in_order_and_add_up_to_each_ttft():
    """submit call <= engine enqueue <= admission start <= first token,
    and command wait + queue wait + admission is the TTFT measured from
    the caller's submit, within 1 ms. Later submits land while earlier
    requests decode, so commands wait out engine steps."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(0.05)      # no forced GIL switch mid-stamp
    try:
        with _server() as srv:
            waits = []
            add = srv._pump.cmd_wait.add
            srv._pump.cmd_wait.add = lambda s: (waits.append(s), add(s))
            sp = SamplingParams(max_new_tokens=24)
            calls = []
            for i in range(6):
                t = time.perf_counter()
                calls.append((t, srv.submit(f"request {i} " * (2 + i), sp)))
                time.sleep(0.01)
            for _, h in calls:
                h.result()
            srv._pump.cmd_wait.add = add
            st = srv.stats()
    finally:
        sys.setswitchinterval(old)
    assert len(waits) == len(calls)
    for (t, h), cmd in zip(calls, waits):
        r = h.request
        first = r._submit_t + r.first_token_s
        assert t <= r._submit_t <= r.admit_t <= first
        stages = cmd + (r.admit_t - r._submit_t) + (first - r.admit_t)
        assert abs((first - t) - stages) < 1e-3, (first - t, stages)
    # one queue wait and one admission per request; every command waited
    assert sum(st["queue_wait_hist"]) == sum(st["admit_hist"]) == len(calls)
    assert sum(st["cmd_wait_hist"]) >= len(calls)
    assert st["hist_bounds_s"] == list(B)


def test_host_gaps_count_each_consecutive_chunk():
    """Within one burst every decode chunk after the first closes one host
    gap; the idle pump's nap between bursts is not a gap."""
    with _server() as srv:
        sp = SamplingParams(max_new_tokens=30)
        prompts = ["one burst", "of three requests", "that admit together"]
        before = srv.stats()
        for _ in range(2):
            hs = srv._call(lambda: [srv.submit(p, sp) for p in prompts])
            srv.run_until_idle()
            assert all(h.done for h in hs)
            after = srv.stats()
            chunks = after["decode_chunks"] - before["decode_chunks"]
            gaps = after["host_gaps"] - before["host_gaps"]
            gap_s = after["host_gap_s"] - before["host_gap_s"]
            assert chunks >= 2
            assert gaps == chunks - 1
            assert 0 < gap_s < 10.0
            before = after


def test_fleet_sums_stage_histograms_and_keeps_them_per_replica():
    fleet = FleetServer(_cfg(), num_replicas=2, num_slots=2, capacity=256,
                        pump=True, engine_cfg=EngineConfig(
                            cache_mode="paged", page_size=16))
    try:
        sp = SamplingParams(max_new_tokens=8)
        hs = [fleet.submit(f"fleet request {i} with words", sp)
              for i in range(4)]
        for h in hs:
            h.result()
        st = fleet.stats()
    finally:
        fleet.close()
    per = [p for p in st["per_replica"] if p]
    for k in ("queue_wait_hist", "admit_hist", "cmd_wait_hist"):
        assert st[k] == LogHistogram.merge([p[k] for p in per])
    assert sum(st["admit_hist"]) == 4
    assert st["host_gaps"] == sum(p["host_gaps"] for p in per)
    assert st["hist_bounds_s"] == list(B)


# ---- decode time per request ------------------------------------------------
def test_decode_s_is_the_wall_time_of_the_chunks_a_request_waited_for():
    """Every slot of a chunk waits for the whole chunk, so a request's
    decode_s is the time of its chunks, not a share of it by batch width:
    co-batched requests each report about their own decode wall time."""
    with _server() as srv:
        sp = SamplingParams(max_new_tokens=40)
        hs = srv._call(lambda: [srv.submit(f"co-batched {i}", sp)
                                for i in range(3)])
        for h in hs:
            h.result()
    for h in hs:
        r = h.request
        wall = r.latency_s - r.first_token_s
        assert 0.5 * wall < r.decode_s <= wall


# ---- spans on the pump thread -----------------------------------------------
ALLOWED = {("serve.admit", "serve.first_token_wait")}


def test_serve_spans_on_the_pump_thread_nest_only_as_specified(tmp_path):
    with _server() as srv:
        sp = SamplingParams(max_new_tokens=20)
        srv.submit("warm the programs", sp).result()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            hs = [srv.submit(f"traced request {i} " * (1 + i), sp)
                  for i in range(4)]
            for h in hs:
                h.result()
            srv.stats()
        finally:
            jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events if e.name.startswith("serve.")]
            if evs:
                lines.append(sorted(evs))
    assert len(lines) == 1                   # all on the pump thread
    evs = lines[0]
    names = {n for _, _, n in evs}
    assert {"serve.commands", "serve.admit", "serve.first_token_wait",
            "serve.decode_prep", "serve.decode_wait", "serve.emit",
            "serve.deliver"} <= names
    for i, (s0, e0, n0) in enumerate(evs):
        for s1, e1, n1 in evs[i + 1:]:
            if s1 >= e0:
                break
            assert (n0, n1) in ALLOWED and e1 <= e0, (n0, n1)
    for s, e, n in evs:                      # every wait inside an admit
        if n == "serve.first_token_wait":
            assert any(a <= s and e <= b for a, b, m in evs
                       if m == "serve.admit")
