"""Record the small trace of program spans that ``test_bench_spans.py``
reduces.

    python bench/tests/record_spans.py <out.json.gz>

On the chip: the family's reduced stablelm-3b served through
``Deployment.serve`` (three segments, the int8 transport's kernels at both
boundaries, the monitoring cycle after the chain), three requests of 16
tokens, each inside a harness ``request`` span, traced with the benchmark's
profiler options and its compile cache (so each request loads its segment
programs from the cache, as in a cell's window).  The planes are written
as plain JSON: the main thread's host line, with each program span's
arguments as a fourth item (``_spans.read_planes``), and each device's
``XLA Ops`` and ``XLA Modules`` lines.  Prints every plane and line name,
the program spans of each request and their reduction, for a look by
hand.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import harness  # noqa: E402
from bench.metrics import _spans, _trace  # noqa: E402
from repro.launch.serve import deploy  # noqa: E402

DEVICE_LINES = ("XLA Ops", "XLA Modules")


def main(out: str) -> int:
    logdir = ROOT / ".bench_trace"
    shutil.rmtree(logdir, ignore_errors=True)
    harness.place_compile_cache()
    dep = deploy("stablelm-3b", reduced=True, compress=True, prompt_len=16)
    rng = np.random.default_rng(0)
    toks = [rng.integers(0, dep.bundle.cfg.vocab, (1, 16), dtype=np.int32)
            for _ in range(5)]
    for i, t in enumerate(toks[:2]):            # compile every program once
        dep.serve(t, now=float(i))[0].block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    for i, t in enumerate(toks[2:], start=2):
        with jax.profiler.TraceAnnotation("request"):
            dep.serve(t, now=float(i))[0].block_until_ready()
    jax.profiler.stop_trace()
    planes = _spans.read_planes(str(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    host = _trace.host_line(planes)[0]
    for p in planes:
        print(p["name"], {k: len(v) for k, v in p["lines"].items()})
    small = []
    for p in planes:
        keep = {k: v for k, v in p["lines"].items()
                if k in DEVICE_LINES or f"{p['name']} {k}" == host}
        if keep:
            small.append({"name": p["name"], "lines": keep})
    for p in small:
        for k, v in p["lines"].items():
            print("   ", p["name"], k, len(v), sorted({e[2] for e in v})[:12])
            for ev in v:
                if ev[2] in _spans.PROGRAM_SPANS:
                    print("       ", ev[2], ev[1] - ev[0], ev[3])
    with gzip.open(out, "wt") as f:
        json.dump(small, f)
    print(json.dumps(_spans.reduce(small)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
