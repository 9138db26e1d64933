"""Readings that the limits of a cell's check are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 4 \\
        --control-seeds 3

For each seed, one process sets the cell up, drives its mix for
``--seconds`` at the cell's own load, and prints what the check compares,
as a benchmark run computes it.  On the first ``--control-seeds`` seeds it
also prints the same number for the control, the reference one precision
below what the configuration states, in the program's place.  Each driver
says what that is (its ``calibration`` method): for a served cell the
widest gap of the served ids below the reference's best logit over the
check's sample (``gaps``), and the gap of the ids that the reference
computed in int8 or fp8 puts first (``gaps_int8``, ``gaps_fp8``).

One JSON line per seed.  Needs the chip, as a run does.
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    _, cell, cfg, mix = run.load_cell(args.workload)
    harness.require_chips(int(cell["chips"]))
    harness.place_compile_cache()
    clock = harness.CompileClock()
    driver_cls = importlib.import_module(f"bench.drivers.{cfg['driver']}").DRIVER
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        d = driver_cls(cfg, mix, seed)
        d.setup(clock)
        d.measure(args.seconds, False)
        row = {"seed": seed, "failed": d.failed,
               **d.calibration(k < args.control_seeds)}
        row["s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
