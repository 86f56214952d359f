"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files the harness finds by that name."""
import math
import re

import pytest

import manifest

BENCH = manifest.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTH = re.compile(r"(hidden_size|intermediate_size|latent|state_size|"
                   r"proj|_dim$|_rank$|head_size|expan|factor|"
                   r"experts_per_tok)")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3"
    assert all(LINE.match(w) for w in BENCH["command"])
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_entries_have_exactly_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_units_and_lines():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names + [w["config"] for w in BENCH["workloads"]] + \
            [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert LINE.match(x["why"])
    for m in BENCH["per_layer"]:
        assert LINE.match(m["layer"])


def test_end_to_end_bounds_and_sources():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_configs_resolve_and_reduce_no_width():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("chipbench/") and c["file"] not in files
        files.add(c["file"])
        spec = manifest.load_json(manifest.ROOT / c["file"])
        assert spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
            assert k in spec.get("published", {}), k
        assert (manifest.HERE / "references" /
                f"{spec['reference']}.py").is_file()


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves_every_file(name):
    wl = manifest.workload(BENCH, name)
    assert wl["reference"].is_file()
    assert wl["entry"]["chips"] in (1, 4)
    for m in manifest.per_layer(BENCH, name):
        assert callable(manifest.reader(m["name"]).read)


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_reports_setup_another_metric_and_a_layer(name):
    e2e = {m["name"] for m in manifest.end_to_end(BENCH, name)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.per_layer(BENCH, name)


def test_moved_metric_is_reported_by_each_listed_cell():
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", WORKLOADS):
            assert w in WORKLOADS
            reported = {e["name"] for e in manifest.end_to_end(BENCH, w)}
            assert m["moves"] in reported, (m["name"], w)


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(LINE.match(x) for x in layers)


def test_peaks_table_refuses_unknown_kinds():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks("cpu")


def test_file_size_and_names_of_files():
    raw = (manifest.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert math.isfinite(len(raw))
    for p in manifest.HERE.rglob("*"):
        rel = p.relative_to(manifest.ROOT).as_posix()
        if "__pycache__" in rel or p.is_dir():
            continue
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", rel), rel
