"""One run of one benchmark cell, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything it needs is found by name: its configuration
file (which names the driver under ``bench/drivers/``), its traffic mix
``bench/traffic/<traffic>.json`` (which names its generator under
``bench/generators/``), and, with ``--trace 1``, one reader
``bench/metrics/<metric>.py`` for each per-layer metric that lists the cell.

The run sets up (weights or fleet from the seed, warm-up of every shape the
mix uses), measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON object as the last line of
standard output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the per-layer ones.  Without a TPU, or with fewer chips than the
cell asks for, it exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime logs to /tmp unless told otherwise; a run writes only
# inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from bench.lib import harness  # noqa: E402
from bench.metrics import _trace  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def _listed(metric: dict, cell: str) -> bool:
    """A metric without a ``workloads`` list belongs to every cell."""
    return cell in metric.get("workloads", (cell,))


def _reader(name: str):
    path = harness.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) of cell ``name``."""
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(ROOT / entry["file"])
    mix = harness.load_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, mix


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    err = sys.stderr

    bench, cell, cfg, mix = load_cell(args.workload)
    try:
        devs = harness.require_chips(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=err)
        return 2
    print(f"compile cache: {harness.place_compile_cache()}", file=err)
    clock = harness.CompileClock()
    driver_mod = importlib.import_module(f"bench.drivers.{cfg['driver']}")
    driver = driver_mod.DRIVER(cfg, mix, args.seed)
    notes = driver.setup(clock)
    setup_s = time.perf_counter() - _T_START
    print(f"setup: {json.dumps(notes)} compiles {clock.compiles} "
          f"compile_s {clock.seconds:.3f} cache_hits {clock.cache_hits}",
          file=err)

    tracing = bool(args.trace)
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    before, loads = clock.compiles, clock.cache_hits
    driver.measure(args.seconds, tracing)
    in_window, loads = clock.compiles - before, clock.cache_hits - loads
    if tracing:
        jax.profiler.stop_trace()
    device = harness.device_info(devs)
    print(f"window: {driver.window_s:.6f} s, attempted {driver.attempted}, "
          f"failed {driver.failed}, compiles in window {in_window}, "
          f"programs loaded from the cache in window {loads}", file=err)

    metrics, breakdown = {}, None
    if tracing:
        planes = _trace.read_planes(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        print(f"trace: {_trace.census(planes)}", file=err)
        summary = _trace.summarize(planes)
        if summary["window_s"] <= 0 or summary["busy_s"] <= 0:
            print("bench: the trace holds no request span or no device "
                  "operation inside one", file=err)
            return 3
        device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        run = harness.TraceRun(driver.counters(), driver.window_s, summary,
                               harness.peaks(devs[0].device_kind))
        for m in bench["per_layer"]:
            if _listed(m, cell["name"]):
                v = _reader(m["name"])(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = _trace.breakdown(summary)
    else:
        values = {"setup_s": setup_s, **driver.end_to_end()}
        for m in bench["end_to_end"]:
            if _listed(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = driver.check()
    correct = driver.failed == 0 and all(ok for *_, ok in checks)
    out = {"correct": correct, "attempted": driver.attempted,
           "failed": driver.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim, _ in checks}
    for n, v, lim, ok in checks:
        print(f"check {n}: {v!r} limit {lim!r} {'ok' if ok else 'FAILED'}",
              file=err)
    err.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
