"""Record the small trace that ``test_bench_trace.py`` reduces.

    python bench/tests/record_trace.py <out.json.gz>

On the chip: three ``request`` spans, each a bf16 matmul program and the
int8 transport's two kernels on a [512, 2560] activation, traced with the
benchmark's profiler options.  The planes are written as plain JSON (the
host's ``python`` line, each device's ``XLA Ops`` and ``XLA Modules``
lines), with every plane and line name printed for a look by hand.
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
import jax.numpy as jnp  # noqa: E402

from bench.metrics import _trace  # noqa: E402
from repro.kernels import ops  # noqa: E402

KEEP = ("python", "XLA Ops", "XLA Modules")


def main(out: str) -> int:
    logdir = ROOT / ".bench_trace"
    shutil.rmtree(logdir, ignore_errors=True)
    x = jax.random.normal(jax.random.key(0), (512, 2560), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (2560, 2560), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)

    def request():
        y = mm(x, w)
        q, s = ops.quantize_int8(y)
        return ops.dequantize_int8(q, s, jnp.bfloat16).block_until_ready()

    request()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("request"):
            request()
    jax.profiler.stop_trace()
    planes = _trace.read_planes(str(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    for p in planes:
        print(p["name"], {k: len(v) for k, v in p["lines"].items()})
        for k, v in p["lines"].items():
            print("   ", k, sorted({e[2] for e in v})[:12])
    small = [{"name": p["name"],
              "lines": {k: v for k, v in p["lines"].items() if k in KEEP}}
             for p in planes]
    with gzip.open(out, "wt") as f:
        json.dump(small, f)
    print(json.dumps(_trace.summarize(small), default=str)[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
