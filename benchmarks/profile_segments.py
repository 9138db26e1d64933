"""Measure per-segment step time + boundary wire bytes per catalog model.

Runs each requested architecture's REDUCED config through the serving
:class:`~repro.serving.profiler.SegmentProfiler` (real forward passes via
:class:`~repro.serving.segments.SegmentChain`, exercising the per-family
kernels) and persists the measured/analytic ratios to ``BENCH_profiles.json``
at the repo root — the committed artifact
:class:`~repro.core.profiling.CalibratedCostModel` loads to calibrate the
control plane.  Merge-on-write like ``BENCH_fleet.json``: re-profiling one
arch never drops the others' coverage.

Run:  PYTHONPATH=src python benchmarks/profile_segments.py [--smoke]
          [--arch A ...] [--json out.json] [--compress]

The default arch set spans the calibration-relevant families: attention
(llama3-8b), SSM (mamba2-1.3b), Griffin hybrid (recurrentgemma-9b), and MoE
(qwen3-moe-30b-a3b).  ``--smoke`` profiles only the smallest catalog model
(stablelm-3b) — the scheduled-CI liveness check for the measurement path.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.configs import get_bundle
from repro.core.profiling import SegmentProfile
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import SegmentProfiler

DEFAULT_ARCHS = ("llama3-8b", "mamba2-1.3b", "recurrentgemma-9b",
                 "qwen3-moe-30b-a3b")
SMOKE_ARCH = "stablelm-3b"


def profile_arch(arch: str, *, batch: int, tokens: int, reps: int,
                 compress: bool, seed: int = 0):
    bundle = get_bundle(arch, reduced=True)
    params = bundle.init(jax.random.PRNGKey(seed), jnp.float32)
    prof = SegmentProfiler(bundle, batch=batch, tokens=tokens, reps=reps,
                           compress=compress, seed=seed, params=params)
    return prof.profile()


def main() -> None:  # pragma: no cover
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="arch to profile (repeatable; default: one per "
                         "family: " + ", ".join(DEFAULT_ARCHS) + ")")
    ap.add_argument("--smoke", action="store_true",
                    help=f"profile only {SMOKE_ARCH} (CI liveness check)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--compress", action="store_true",
                    help="route boundaries through int8_transfer — measured "
                         "bytes/token then reflect the compressed wire format")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="profile artifact (default: repo-root "
                         "BENCH_profiles.json; merge-on-write)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump this run's document to PATH")
    args = ap.parse_args()
    enable_compile_cache()

    archs = ([SMOKE_ARCH] if args.smoke
             else tuple(args.arch) if args.arch else DEFAULT_ARCHS)
    profile = SegmentProfile()
    for arch in archs:
        t0 = time.perf_counter()
        mp = profile_arch(arch, batch=args.batch, tokens=args.tokens,
                          reps=args.reps, compress=args.compress)
        wall = time.perf_counter() - t0
        profile.models[arch] = mp
        print(f"{arch:22s} units={mp.graph_units:3d} "
              f"compute_scale={mp.compute_scale:7.3f} "
              f"transfer_scale={mp.transfer_scale:6.3f} "
              f"({wall:.1f}s)")
        for s in mp.segments:
            print(f"  [{s.lo:3d},{s.hi:3d}) {s.step_time_s*1e3:8.2f} ms "
                  f"ratio={s.time_ratio:7.3f} "
                  f"wire={s.boundary_bytes_tok:8.1f} B/tok")

    out = pathlib.Path(args.out) if args.out else (
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_profiles.json"
    )
    # smoke runs must never shrink the committed artifact's coverage — the
    # merge keeps every previously profiled model; `refreshed` records what
    # THIS run actually measured (mirrors BENCH_fleet.json semantics)
    doc = profile.save(out, refreshed=archs)
    print(f"wrote {out} ({len(doc['models'])} models, "
          f"refreshed: {', '.join(doc['refreshed'])})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
