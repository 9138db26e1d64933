"""Every cell of ``BENCHMARK.json`` resolves, by name, to files that exist,
and the file keeps to the shape the benchmark's contract gives it."""

from __future__ import annotations

import importlib
import json
import re

import pytest

from bench.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert (harness.ROOT / bench["command"][1]).is_file()


def test_names_are_names(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in bench["end_to_end"] + bench["per_layer"])


def test_configs_resolve(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (harness.ROOT / cfg["reference"]).is_file()
        importlib.import_module(f"bench.drivers.{cfg['driver']}").DRIVER


@pytest.mark.parametrize("kind", sorted(
    p.stem for p in (harness.BENCH / "drivers").glob("[!_]*.py")))
def test_each_driver_has_a_cell(bench, kind):
    drivers = {harness.load_json(harness.ROOT / c["file"])["driver"]
               for c in bench["configs"]}
    assert kind in drivers


def test_cells_resolve(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        mix = harness.BENCH / "traffic" / f"{w['traffic']}.json"
        assert mix.is_file(), mix
        gen = json.loads(mix.read_text())["generator"]
        assert (harness.BENCH / "generators" / f"{gen}.py").is_file(), gen
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])


def test_metrics_resolve(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        reported = [m for m in bench["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_peaks_table():
    kind = harness.peaks("TPU v5 lite")
    assert kind["bf16_flops_per_s"] == 197e12 and kind["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
