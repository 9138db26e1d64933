"""Share of the memory roofline reached by the int8 boundary transport: the
bytes its quantize and dequantize kernels must move (computed from their
shapes, ``bench/lib/counts.py``) over HBM bandwidth, divided by the kernels'
device time in the trace.  The kernels do a few operations a byte, so
bandwidth bounds them.  Moves ``prompt_tokens_per_s``."""


def read(run):
    names = run.counters.get("kernels", ())
    t = sum(v for k, v in run.summary["modules"].items()
            if any(n in k for n in names))
    moved = run.counters.get("int8_bytes")
    if not moved or t <= 0:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / t
