"""The trace reduction: busy union, idle share, time by operation and by
program, and idle gaps by host event; on a hand-made trace, and on a small
trace recorded on a TPU v5e (``record_trace.py``)."""

from __future__ import annotations

import gzip
import importlib.util
import json
import pathlib

import pytest

from bench.lib import counts, harness
from bench.lib.harness import TraceRun
from bench.metrics import _trace

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json.gz"


def _plane(name, **lines):
    return {"name": name, "lines": lines}


def _hand(line: str = "python") -> list[dict]:
    """The main thread's line is named after the process."""
    return [
        _plane("/host:CPU", **{line: [
            (0, 100, "request"), (10, 30, "PjitFunction(a)"),
            (60, 90, "PjitFunction(b)"), (100, 200, "request"),
            (150, 190, "PjitFunction(c)")], "main/300": [(5, 195, "Execute")]}),
        _plane("/device:TPU:0", **{
            "XLA Ops": [(20, 50, "fusion"), (40, 60, "dot"), (110, 140, "dot")],
            "XLA Modules": [(20, 60, "jit_a(123)"), (110, 140, "jit_b(45)")]}),
    ]


HAND = _hand()


def test_busy_union_by_hand():
    assert _trace.busy_seconds([(20, 50), (40, 60), (110, 140)], 0, 200) == \
        pytest.approx(70e-9)
    assert _trace.busy_seconds([(0, 10)], 5, 200) == pytest.approx(5e-9)


def test_idle_gaps_by_hand():
    assert _trace.idle_gaps([(20, 50), (40, 60), (110, 140)], 0, 200) == \
        [(0, 20), (60, 110), (140, 200)]


def test_innermost_host_event():
    host = HAND[0]["lines"]["python"]
    assert _trace.innermost(host, [5, 20, 75, 95, 170, 250]) == [
        "request", "PjitFunction(a)", "PjitFunction(b)", "request",
        "PjitFunction(c)", "no host event"]


@pytest.mark.parametrize("line", ["python", "python3"])
def test_summary_by_hand(line):
    s = _trace.summarize(_hand(line))
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx(70e-9)
    assert s["ops"] == pytest.approx({"jit_a:fusion": 30e-9, "jit_a:dot": 20e-9,
                                      "jit_b:dot": 30e-9})
    assert s["modules"] == pytest.approx({"jit_a": 40e-9, "jit_b": 30e-9})
    # gaps (0,20) mid 10 -> PjitFunction(a) starts at 10; (60,110) mid 85 ->
    # PjitFunction(b); (140,200) mid 170 -> PjitFunction(c)
    assert s["gaps"] == pytest.approx({"PjitFunction(a)": 20e-9,
                                       "PjitFunction(b)": 50e-9,
                                       "PjitFunction(c)": 60e-9})
    b = _trace.breakdown(s)
    assert b["device_ops"][0] == ["jit_a:fusion", pytest.approx(30e-9)]
    assert len(b["idle_gaps"]) == 3


def test_census_counts_spans_and_events():
    assert _trace.census(_hand("python3")) == {
        "spans": 2, "/host:CPU python3": 5, "/device:TPU:0 XLA Ops": 3,
        "/device:TPU:0 XLA Modules": 2}
    assert _trace.census([_plane("/host:CPU", python=[])]) == {"spans": 0}


def _reader(name: str):
    path = harness.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _recorded() -> dict:
    with gzip.open(DATA, "rt") as f:
        return _trace.summarize(json.load(f))


def test_idle_reader_reads_nothing_without_a_device():
    read = _reader("device_idle.serve")
    empty = _trace.summarize([HAND[0]])
    assert read(TraceRun({}, 1.0, empty, {})) is None
    full = _trace.summarize(HAND)
    assert read(TraceRun({}, 1.0, full, {})) == pytest.approx(65.0)


def test_recorded_chip_trace():
    s = _recorded()
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert set(s["modules"]) == {"jit__lambda", "jit_quantize_int8",
                                 "jit_dequantize_int8"}
    # every operation ran inside one of the three programs
    assert {k.split(":")[0] for k in s["ops"]} == set(s["modules"])
    assert sum(s["ops"].values()) == pytest.approx(s["busy_s"], rel=0.05)
    assert sum(s["gaps"].values()) == pytest.approx(s["window_s"] - s["busy_s"])


# the recorded trace holds three requests, each a [512, 2560] x [2560, 2560]
# bf16 matmul and one int8 boundary crossing of its [512, 2560] output
RECORDED = {"flops": 3 * 2.0 * 512 * 2560 * 2560,
            "int8_bytes": 3 * counts.int8_transport_bytes(512, 2560),
            "kernels": ("quantize_int8", "dequantize_int8")}


def test_roofline_readers_on_the_recorded_trace():
    s = _recorded()
    run = TraceRun(RECORDED, s["window_s"], s, harness.peaks("TPU v5 lite"))
    int8 = _reader("int8_roofline")(run)
    kernel_s = s["modules"]["jit_quantize_int8"] + s["modules"]["jit_dequantize_int8"]
    assert int8 == pytest.approx(100 * RECORDED["int8_bytes"] / 819e9 / kernel_s)
    assert int8 > 0
    mfu = _reader("prefill_mfu")(run)
    assert mfu == pytest.approx(100 * RECORDED["flops"] / (s["window_s"] * 197e12))
    assert 0 < mfu < 100


@pytest.mark.parametrize("name", ["int8_roofline", "prefill_mfu"])
def test_roofline_readers_read_nothing_without_their_counts(name):
    s = _recorded()
    assert _reader(name)(TraceRun({}, s["window_s"], s,
                                  harness.peaks("TPU v5 lite"))) is None
