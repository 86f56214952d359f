"""The readers of the request stages and the pump loop's host phases, on
synthetic server stats and a synthetic trace reduction: window deltas of
the stage histograms (summed over a fleet's replicas), the host gap per
consecutive chunk, the device's idle time under the pump's phases, and
nothing read where the window saw nothing or the server keeps no such
counter."""
import numpy as np
import pytest

import manifest
import metrics_run
import stage_hist
import trace_reduce
from repro.serving.scheduler import LogHistogram

BOUNDS = list(LogHistogram.BOUNDS_S)
STAGES = {"cmd_wait_p95_ms": "cmd_wait_hist",
          "queue_wait_p95_ms": "queue_wait_hist",
          "admit_p95_ms": "admit_hist"}


def _hist(values):
    h = LogHistogram()
    for v in values:
        h.add(v)
    return h.counts()


def _server_stats(before, window, gap_s=0.0, gaps=0):
    """Stats at the window's start and end of one server whose three
    stages saw ``before`` earlier and ``before + window`` by the end."""
    start = {"hist_bounds_s": BOUNDS, "host_gap_s": 1.0, "host_gaps": 10}
    end = {"hist_bounds_s": BOUNDS, "host_gap_s": 1.0 + gap_s,
           "host_gaps": 10 + gaps}
    for k in STAGES.values():
        start[k] = _hist(before)
        end[k] = _hist(list(before) + list(window))
    return start, end


def _run(start, end, trace=None):
    run = metrics_run.Run(records=[], t0=0.0, seconds=1.0, setup_s=0.0,
                          spec={}, chips=1, stats={"start": start,
                                                   "end": end},
                          kind="TPU v5 lite")
    run.trace = trace
    return run


def _read(name, run):
    return manifest.reader(name).read(run)


def test_quantile_interpolates_inside_the_bucket():
    counts = [0] * (len(BOUNDS) + 1)
    counts[10] = 4
    lo, hi = BOUNDS[9], BOUNDS[10]
    assert stage_hist.quantile(counts, BOUNDS, 0.5) == pytest.approx(
        lo + (hi - lo) * 0.5)
    counts[0], counts[-1] = 1, 1
    assert 0 <= stage_hist.quantile(counts, BOUNDS, 0.01) <= BOUNDS[0]
    assert stage_hist.quantile(counts, BOUNDS, 1.0) == BOUNDS[-1]


@pytest.mark.parametrize("name", sorted(STAGES))
def test_p95_from_the_window_delta_of_known_data(name):
    rng = np.random.default_rng(7)
    before = rng.uniform(5.0, 9.0, 500)          # outside the window, slow
    window = rng.lognormal(np.log(0.05), 0.6, 2000)
    start, end = _server_stats(before, window)
    got = _read(name, _run(start, end))
    want = 1e3 * np.percentile(window, 95)
    assert got == pytest.approx(want, rel=0.1)    # a bucket is 9.6% wide


@pytest.mark.parametrize("name", sorted(STAGES))
def test_fleet_replicas_sum_bucket_by_bucket(name):
    a0, a1 = _server_stats([0.2] * 5, [0.01] * 90)
    b0, b1 = _server_stats([], [1.0] * 10)
    # the fleet's top level carries no histogram: the reader sums replicas
    run = _run({"per_replica": [a0, None, b0]},
               {"per_replica": [a1, None, b1]})
    # 100 in the window: 90 at 10 ms, 10 at 1 s; the 95th is in the 1 s
    # bucket, half-way up it
    lo, hi = [(BOUNDS[i - 1], b) for i, b in enumerate(BOUNDS) if b >= 1.0][0]
    assert _read(name, run) == pytest.approx(1e3 * (lo + (hi - lo) * 0.5))


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_reads_nothing_without_observations_or_histograms(name):
    start, end = _server_stats([0.1] * 7, [])
    assert _read(name, _run(start, end)) is None
    bare = {"host_gap_s": 0.0}                   # a server without stages
    assert _read(name, _run(bare, dict(bare))) is None
    assert _read(name, _run({"per_replica": [bare]},
                            {"per_replica": [dict(bare)]})) is None


def test_host_gap_is_the_mean_over_the_window_s_consecutive_chunks():
    start, end = _server_stats([], [], gap_s=0.6, gaps=30)
    assert _read("host_gap_ms", _run(start, end)) == pytest.approx(20.0)
    start, end = _server_stats([], [], gap_s=0.0, gaps=0)
    assert _read("host_gap_ms", _run(start, end)) is None
    assert _read("host_gap_ms", _run({}, {})) is None


def _reduction(host, busy, span=(0, 1000)):
    devices = [{"ops": [], "modules": [], "busy": b} for b in busy]
    return trace_reduce.Reduction(span, devices, host)


def test_loop_idle_share_counts_idle_under_host_phases_only():
    host = [
        (0, 100, "chipbench_other"),            # not a pump phase
        (100, 300, "serve.admit"),
        (150, 250, "serve.first_token_wait"),   # a wait: left out
        (300, 400, "serve.decode_prep"),
        (400, 700, "serve.decode_wait"),        # a wait: left out
        (700, 800, "serve.emit"),
        (800, 850, "serve.deliver"),
        (900, 950, "PjitFunction_add_"),
    ]
    # device busy 120-160 and 350-760; idle under the phases: admit less
    # its wait 100-120 and 250-300, prep 300-350, emit 760-800, deliver
    # 800-850
    busy = [[(120, 160), (350, 760)]]
    got = _read("loop_idle_share", _run({}, {}, _reduction(host, busy)))
    assert got == pytest.approx(100.0 * (20 + 50 + 50 + 40 + 50) / 1000)


def test_loop_idle_share_averages_chips_and_reads_nothing_without_spans():
    host = [(0, 500, "serve.commands")]
    busy = [[(0, 1000)], [(250, 1000)]]         # chip 1 idle 250 of it
    got = _read("loop_idle_share", _run({}, {}, _reduction(host, busy)))
    assert got == pytest.approx(100.0 * 250 / 2 / 1000)
    assert _read("loop_idle_share", _run({}, {}, _reduction(
        [(0, 500, "client.submit")], busy))) is None
    assert _read("loop_idle_share", _run({}, {})) is None


def test_the_five_readers_are_in_every_cell():
    bench = manifest.benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    for name in ("cmd_wait_p95_ms", "queue_wait_p95_ms", "admit_p95_ms",
                 "host_gap_ms", "loop_idle_share"):
        for cell in cells:
            assert name in {m["name"] for m in manifest.per_layer(bench,
                                                                  cell)}
        assert callable(manifest.reader(name).read)


def test_counter_readers_on_a_toy_run_of_the_server(tmp_path):
    """A toy open-loop run on the CPU: the server's own stats give every
    stage and the host gap a reading in the window."""
    import _tiny
    import run
    wl = _tiny.workload("open")
    got = run.serve(wl, seed=2 ** 33 + 1, seconds=2.0, trace=False,
                    require_tpu=False, out_dir=tmp_path)
    ctx = _run(got["stats"]["start"], got["stats"]["end"])
    for name in sorted(STAGES) + ["host_gap_ms"]:
        v = _read(name, ctx)
        assert v is not None and 0 < v < 60e3, (name, v)
    assert _read("loop_idle_share", ctx) is None     # no trace taken
