"""The reader of the paged decode kernel's live pages: the live share of the
kernel's grid pages over the window, from the server's cumulative
counters, and nothing read where the window ran no decode step or the
server keeps no such counter."""
import pytest

import manifest
import metrics_run


def _run(start, end):
    return metrics_run.Run(records=[], t0=0.0, seconds=1.0, setup_s=0.0,
                           spec={}, chips=1, stats={"start": start,
                                                    "end": end},
                           kind="TPU v5 lite")


def _read(run):
    return manifest.reader("decode_live_page_share").read(run)


def test_decode_live_page_share_is_live_over_grid_pages_in_the_window():
    start = {"decode_pages_grid": 4096, "decode_pages_live": 1000}
    end = {"decode_pages_grid": 4096 + 16 * 128 * 36,
           "decode_pages_live": 1000 + 16 * 66 * 36}
    assert _read(_run(start, end)) == pytest.approx(100.0 * 66 / 128)
    assert _read(_run(start, dict(start))) is None
    # a server without the counters (a dense cache, or an older server)
    assert _read(_run({}, {})) is None


def test_decode_live_page_share_is_in_every_cell():
    bench = manifest.benchmark()
    for cell in (w["name"] for w in bench["workloads"]):
        assert "decode_live_page_share" in {
            m["name"] for m in manifest.per_layer(bench, cell)}


def test_decode_live_page_share_on_a_toy_run_of_the_server(tmp_path):
    """A toy open-loop run on the CPU: the paged server's own counters give
    the live share a reading in the window."""
    import _tiny
    import run
    wl = _tiny.workload("open")
    got = run.serve(wl, seed=2 ** 33 + 3, seconds=2.0, trace=False,
                    require_tpu=False, out_dir=tmp_path)
    assert 0 < _read(_run(got["stats"]["start"], got["stats"]["end"])) <= 100
