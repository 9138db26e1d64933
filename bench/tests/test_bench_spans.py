"""The reduction of the program's own spans (``bench/metrics/_spans.py``):
self time, idle time charged exactly to the innermost program span, and
the remainder ``outside``; on hand-made traces, on a reduced deployment
traced on the CPU, and on a small trace recorded on a TPU v5e
(``record_spans.py``)."""

from __future__ import annotations

import collections
import gzip
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.lib import harness
from bench.lib.harness import TraceRun
from bench.metrics import _spans, _trace

DATA = pathlib.Path(__file__).parent / "data" / "trace_spans.json.gz"


def _hand(program: bool = True) -> list[dict]:
    """Two requests: the first with two segments, a crossing and a
    monitoring cycle that re-splits, the second with one segment; a JAX
    event inside the first segment."""
    spans = [
        (5, 95, "serve", {"req": 0, "rows": 64}),
        (10, 40, "segment", {"j": 0, "lo": 0, "hi": 2}),
        (40, 50, "transport", {"boundary": 0}),
        (50, 80, "segment", {"j": 1, "lo": 2, "hi": 4}),
        (80, 95, "control", {}),
        (85, 90, "restage", {"version": 2}),
        (100, 190, "serve", {"req": 1, "rows": 64}),
        (110, 150, "segment", {"j": 0, "lo": 0, "hi": 4}),
        (150, 190, "control", {}),
    ]
    host = [(0, 100, "request"), (15, 35, "PjitFunction(scan)"),
            (100, 200, "request")] + (spans if program else [])
    return [{"name": "/host:CPU", "lines": {"python3": host}},
            {"name": "/device:TPU:0", "lines": {
                "XLA Ops": [(20, 30, "fusion"), (45, 48, "quantize"),
                            (60, 75, "dot"), (120, 140, "dot")],
                "XLA Modules": [(20, 30, "jit_scan(1)"), (45, 48, "jit_q(2)"),
                                (60, 75, "jit_scan(3)"),
                                (120, 140, "jit_scan(4)")]}}]


def _reader(name: str):
    path = harness.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_owners_put_each_instant_to_the_innermost_span():
    host = _hand()[0]["lines"]["python3"]
    prog = [ev for ev in host if ev[2] in _spans.PROGRAM_SPANS]
    assert _spans.owners(prog, 0, 200) == [
        (0, 5, "outside"), (5, 10, "serve"), (10, 40, "segment"),
        (40, 50, "transport"), (50, 80, "segment"), (80, 85, "control"),
        (85, 90, "restage"), (90, 95, "control"), (95, 100, "outside"),
        (100, 110, "serve"), (110, 150, "segment"), (150, 190, "control"),
        (190, 200, "outside")]
    # a span that outlasts its parent is cut at the parent's end
    assert _spans.owners([(0, 10, "serve"), (5, 20, "segment")], 0, 30) == [
        (0, 5, "serve"), (5, 10, "segment"), (10, 30, "outside")]


def test_idle_is_charged_exactly_by_hand():
    r = _spans.reduce(_hand())
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["idle_s"] == pytest.approx(152e-9)
    ns = {n: {k: v * 1e9 if k != "count" else v for k, v in d.items()}
          for n, d in r["spans"].items()}
    assert ns == {
        "serve": {"count": 2, "total_s": pytest.approx(180), "self_s": pytest.approx(15),
                  "idle_s": pytest.approx(15)},
        "segment": {"count": 3, "total_s": pytest.approx(100),
                    "self_s": pytest.approx(100), "idle_s": pytest.approx(55)},
        "transport": {"count": 1, "total_s": pytest.approx(10),
                      "self_s": pytest.approx(10), "idle_s": pytest.approx(7)},
        "control": {"count": 2, "total_s": pytest.approx(55),
                    "self_s": pytest.approx(50), "idle_s": pytest.approx(50)},
        "restage": {"count": 1, "total_s": pytest.approx(5),
                    "self_s": pytest.approx(5), "idle_s": pytest.approx(5)},
        "outside": {"count": 0, "total_s": pytest.approx(20),
                    "self_s": pytest.approx(20), "idle_s": pytest.approx(20)}}
    assert sum(d["self_s"] for d in r["spans"].values()) == \
        pytest.approx(r["window_s"])


def test_layer_idle_adds_up_to_device_idle():
    planes = _hand()
    idle = _reader("device_idle.serve")(TraceRun({}, 1.0, _trace.summarize(planes), {}))
    assert idle == pytest.approx(76.0)
    m = _spans.layer_metrics(_spans.reduce(planes),
                             {"requests": 2, "segment_traces": 6})
    assert m == pytest.approx({
        "idle_in_serve": 7.5, "idle_in_segment": 27.5, "idle_in_transport": 3.5,
        "idle_in_control": 27.5, "idle_outside": 10.0, "control_ms": 2.75e-5,
        "segment_traces_per_request": 3.0})
    assert sum(m[k] for k, _ in _spans.IDLE_BY_LAYER) == pytest.approx(idle)
    # the program's spans change only which host event the harness's
    # reduction names a gap after
    with_spans, without = (_trace.summarize(p) for p in (planes, _hand(False)))
    assert with_spans.pop("gaps") != without.pop("gaps")
    assert with_spans == without


def test_a_program_without_spans_or_counters_reads_nothing_of_them():
    r = _spans.reduce(_hand(program=False))
    assert set(r["spans"]) == {"outside"}
    assert _spans.layer_metrics(r, {"flops": 1.0}) == {"idle_outside": pytest.approx(76.0)}
    assert _spans.reduce([_hand()[0]]) == {"window_s": 0.0, "idle_s": 0.0, "spans": {}}


def _children(host, parent) -> collections.Counter:
    return collections.Counter(
        ev[2] for ev in host if ev[2] in _spans.PROGRAM_SPANS and ev is not parent
        and parent[0] <= ev[0] and ev[1] <= parent[1])


def test_served_requests_open_their_spans_under_the_profiler(tmp_path):
    """A reduced deployment traced on the CPU: each ``serve`` span holds
    three ``segment`` spans, two ``transport`` spans and one ``control``
    span, with bare names and their arguments in the event's stats."""
    from repro.launch.serve import deploy

    dep = deploy("stablelm-3b", reduced=True, compress=True, interpret=True,
                 prompt_len=8)
    rng = np.random.default_rng(0)
    toks = [rng.integers(0, dep.bundle.cfg.vocab, (1, 8), dtype=np.int32)
            for _ in range(3)]
    dep.serve(toks[0], now=0.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i, t in enumerate(toks[1:], start=1):
            with jax.profiler.TraceAnnotation("request"):
                dep.serve(t, now=float(i))[0].block_until_ready()
    finally:
        jax.profiler.stop_trace()
    host = _trace.host_line(_spans.read_planes(str(tmp_path)))[1]
    serves = [ev for ev in host if ev[2] == "serve"]
    assert [ev[3] for ev in serves] == [{"req": 1, "rows": 8}, {"req": 2, "rows": 8}]
    bounds = dep.engine.config.boundaries
    for ev in serves:
        assert _children(host, ev) == {"segment": 3, "transport": 2, "control": 1}
        inside = sorted((c for c in host if c[2] in ("segment", "transport")
                         and ev[0] <= c[0] and c[1] <= ev[1]), key=lambda c: c[0])
        assert [(c[2], c[3]) for c in inside] == [
            ("segment", {"j": 0, "lo": bounds[0], "hi": bounds[1]}),
            ("transport", {"boundary": 0}),
            ("segment", {"j": 1, "lo": bounds[1], "hi": bounds[2]}),
            ("transport", {"boundary": 1}),
            ("segment", {"j": 2, "lo": bounds[2], "hi": bounds[3]})]


def _recorded() -> list[dict]:
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_of_program_spans():
    planes = _recorded()
    host = _trace.host_line(planes)[1]
    serves = [ev for ev in host if ev[2] == "serve"]
    assert [ev[3] for ev in serves] == [{"req": i, "rows": 16} for i in (2, 3, 4)]
    for ev in serves:
        assert _children(host, ev) == {"segment": 3, "transport": 2, "control": 1}
    s = _trace.summarize(planes)
    r = _spans.reduce(planes)
    assert s["devices"] == 1 and 0 < s["busy_s"] < s["window_s"]
    assert {"jit_quantize_int8", "jit_dequantize_int8", "jit_scan"} <= set(s["modules"])
    assert r["window_s"] == pytest.approx(s["window_s"])
    assert r["idle_s"] == pytest.approx(s["window_s"] - s["busy_s"])
    assert sum(d["idle_s"] for d in r["spans"].values()) == pytest.approx(r["idle_s"])
    assert {n: d["count"] for n, d in r["spans"].items()} == {
        "serve": 3, "segment": 9, "transport": 6, "control": 3, "outside": 0}
    m = _spans.layer_metrics(r, {})
    idle = _reader("device_idle.serve")(TraceRun({}, s["window_s"], s, {}))
    assert sum(m[k] for k, _ in _spans.IDLE_BY_LAYER) == pytest.approx(idle)
    assert m["idle_in_segment"] > m["idle_in_transport"] > 0
