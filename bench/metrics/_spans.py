"""The program's own spans in a profiler trace: which layer of the served
path the host was in while the device idled.

The served path opens profiler spans on the host's main thread
(``jax.profiler.TraceAnnotation``: a bare name, its arguments in the
event's stats): ``serve`` around each request, ``segment`` around each
segment of the chain, ``transport`` around each boundary crossing,
``control`` around the monitoring cycle that follows the chain, and
``restage`` around a re-split.  They nest on one thread, so a span's parent
is the span around it and the spans of one request lie inside its
``serve`` span.

Inside the window of the harness's ``request`` spans (``_trace.py``), each
instant belongs to the innermost program span that covers it, or to
``outside`` where none does (the harness's own code around the program).
For each span name this gives the count, the total duration, the self time
(the duration less that of its child program spans; JAX's own events are
not children) and the device's idle time under the self time, charged
exactly by intersecting the idle stretches with the self stretches.  The
idle times of every name and of ``outside`` add up to the window's idle
time, ``device_idle.serve``'s numerator.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

from bench.metrics import _trace

PROGRAM_SPANS = ("serve", "segment", "transport", "control", "restage")
OUTSIDE = "outside"


def read_planes(logdir: str) -> list[dict]:
    """``_trace.read_planes``, with the arguments of each program span kept
    as a fourth item of its event: ``(start_ns, end_ns, name, {arg: value})``."""
    import jax

    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {logdir}, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    planes = []
    for p in data.planes:
        lines = {}
        for ln in p.lines:
            lines[ln.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                if e.name in PROGRAM_SPANS else
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in ln.events]
        planes.append({"name": p.name, "lines": lines})
    return planes


def owners(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi] cut into stretches in time order, each put down to the
    innermost of the nested ``spans`` (``(start, end, name, ...)``) that
    covers it, or to ``outside``.  A span that outlasts its parent is cut
    at the parent's end."""
    out: list[tuple[float, float, str]] = []
    stack, t = [(lo, hi, OUTSIDE)], lo

    def close() -> None:
        nonlocal t
        end, name = stack.pop()[1:]
        if end > t:
            out.append((t, end, name))
            t = end

    inside = [(max(s, lo), min(e, hi), n) for s, e, n, *_ in spans
              if e > lo and s < hi]
    for s, e, n in sorted(inside, key=lambda v: (v[0], -v[1])):
        while stack[-1][1] <= s:
            close()
        if s > t:
            out.append((t, s, stack[-1][2]))
            t = s
        stack.append((s, min(e, stack[-1][1]), n))
    while stack:
        close()
    return out


def charge(idle, owned) -> dict[str, float]:
    """Seconds of the ``idle`` stretches ((start, end) ns, disjoint, in time
    order) that fall in each owner's stretches (``owners``)."""
    out: dict[str, float] = defaultdict(float)
    i = k = 0
    while i < len(idle) and k < len(owned):
        s, e = max(idle[i][0], owned[k][0]), min(idle[i][1], owned[k][1])
        if e > s:
            out[owned[k][2]] += (e - s) * 1e-9
        if idle[i][1] < owned[k][1]:
            i += 1
        else:
            k += 1
    return dict(out)


def reduce(planes: list[dict]) -> dict:
    """The window, the device's idle time in it (averaged over the devices,
    as ``_trace.summarize`` does), and per program span name and
    ``outside``: ``count``, ``total_s``, ``self_s``, ``idle_s``."""
    host = _trace.host_line(planes)[1]
    marks = [ev for ev in host if ev[2] in _trace.HARNESS_SPANS]
    devs = [p for p in planes if p["name"].startswith("/device:TPU:")
            and p["lines"].get("XLA Ops")]
    if not marks or not devs:
        return {"window_s": 0.0, "idle_s": 0.0, "spans": {}}
    lo, hi = min(ev[0] for ev in marks), max(ev[1] for ev in marks)
    prog = [ev for ev in host if ev[2] in PROGRAM_SPANS and ev[1] > lo and ev[0] < hi]
    owned = owners(prog, lo, hi)
    spans = {n: {"count": 0, "total_s": 0.0, "self_s": 0.0, "idle_s": 0.0}
             for n in {ev[2] for ev in prog} | {OUTSIDE}}
    for s, e, n, *_ in prog:
        spans[n]["count"] += 1
        spans[n]["total_s"] += (min(e, hi) - max(s, lo)) * 1e-9
    for s, e, n in owned:
        spans[n]["self_s"] += (e - s) * 1e-9
    spans[OUTSIDE]["total_s"] = spans[OUTSIDE]["self_s"]
    idle_s = 0.0
    for p in devs:
        iv = [(ev[0], ev[1]) for ev in p["lines"]["XLA Ops"]]
        gaps = _trace.idle_gaps(iv, lo, hi)
        idle_s += sum(e - s for s, e in gaps) * 1e-9 / len(devs)
        for n, v in charge(gaps, owned).items():
            spans[n]["idle_s"] += v / len(devs)
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle_s, "spans": spans}


# each layer's idle share and the spans whose self time it holds; the five
# add up to ``device_idle.serve``
IDLE_BY_LAYER = (("idle_in_serve", ("serve",)),
                 ("idle_in_segment", ("segment",)),
                 ("idle_in_transport", ("transport",)),
                 ("idle_in_control", ("control", "restage")),
                 ("idle_outside", (OUTSIDE,)))


def layer_metrics(r: dict, counters: dict) -> dict[str, float]:
    """The per-layer numbers that the reduction ``r`` and the program's
    counters over the window give (PERF.md section 3): the share of the
    window, in percent, in which the device idled while the host was in
    each layer's spans, the mean ``control`` span in ms, and segment traces
    per request.  A number whose span or counter is absent is left out."""
    spans, window = r["spans"], r["window_s"]
    out: dict[str, float] = {}
    if window <= 0:
        return out
    for metric, names in IDLE_BY_LAYER:
        if names[0] in spans:
            out[metric] = 100.0 * sum(spans[n]["idle_s"] for n in names
                                      if n in spans) / window
    if "control" in spans:
        c = spans["control"]
        out["control_ms"] = 1e3 * c["total_s"] / c["count"]
    if counters.get("requests") and "segment_traces" in counters:
        out["segment_traces_per_request"] = \
            counters["segment_traces"] / counters["requests"]
    return out
