"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it each device is a plane ``/device:TPU:<i>``; its ``XLA Ops`` line holds
one event per operation run on the device, and its ``XLA Modules`` line one
event per program (``jit_<name>``).  The host's line of the main thread,
named after the process (``python``, ``python3``), holds the harness's own
spans (``request``) and JAX's dispatch events on the same clock.

An operation is named by its program and its HLO instruction
(``jit_scan:%fusion.12``), the program's hash left out.

The window is the stretch from the first harness span's start to the last
one's end.  Busy time is the union of the operation intervals inside it,
averaged over the devices; an idle gap is a stretch of the window in which
no operation ran, and it is put down to the innermost host event that
covers its middle.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

HARNESS_SPANS = ("request",)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_seconds(intervals, lo: float, hi: float) -> float:
    """Length, in seconds, of the union of ns ``intervals`` within [lo, hi]."""
    return sum(e - s for s, e in _clip(_union(intervals), lo, hi)) * 1e-9


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for s, e in _clip(_union(intervals), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(host: list[tuple[float, float, str]],
              instants: list[float]) -> list[str]:
    """For each of the ascending ``instants``, the name of the innermost
    event of ``host`` (nested events of one thread) that covers it."""
    events = sorted(host)
    out, stack, k = [], [], 0
    for t in instants:
        while k < len(events) and events[k][0] <= t:
            while stack and stack[-1][1] < events[k][0]:
                stack.pop()
            stack.append(events[k])
            k += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else "no host event")
    return out


def _top(d: dict, k: int = 10) -> list[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]


def host_line(planes: list[dict]) -> tuple[str, list]:
    """The name and events of the host line that holds the harness's spans:
    the main thread's, whatever the process is called."""
    for p in planes:
        if p["name"].startswith("/host"):
            for ln, evs in p["lines"].items():
                if any(ev[2] in HARNESS_SPANS for ev in evs):
                    return f"{p['name']} {ln}", evs
    return "", []


def summarize(planes: list[dict]) -> dict:
    """``planes``: ``{"name", "lines": {line: [(start_ns, end_ns, name)]}}``.
    Returns the window, busy time, time by operation and by program, and
    the idle gaps by host event."""
    host = host_line(planes)[1]
    spans = [ev for ev in host if ev[2] in HARNESS_SPANS]
    devs = [p for p in planes if p["name"].startswith("/device:TPU:")
            and p["lines"].get("XLA Ops")]
    if not spans or not devs:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": len(devs),
                "ops": {}, "modules": {}, "gaps": {}}
    lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    busy, ops, modules, gaps = 0.0, defaultdict(float), defaultdict(float), \
        defaultdict(float)
    for p in devs:
        evs = _clip_events(p["lines"]["XLA Ops"], lo, hi)
        mods = sorted(_clip_events(p["lines"].get("XLA Modules", []), lo, hi))
        iv = [(s, e) for s, e, _ in evs]
        busy += busy_seconds(iv, lo, hi)
        for (s, e, n), prog in zip(evs, _programs(evs, mods)):
            ops[f"{prog}:{n.split(' = ')[0]}"] += (e - s) * 1e-9
        for s, e, n in mods:
            modules[_program(n)] += (e - s) * 1e-9
        idle = idle_gaps(iv, lo, hi)
        names = innermost(host, [0.5 * (s + e) for s, e in idle])
        for (s, e), name in zip(idle, names):
            gaps[name] += (e - s) * 1e-9
    n = len(devs)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy / n, "devices": n,
            "ops": {k: v / n for k, v in ops.items()},
            "modules": {k: v / n for k, v in modules.items()},
            "gaps": {k: v / n for k, v in gaps.items()}}


def _program(name: str) -> str:
    """``jit_scan(8391...)`` -> ``jit_scan``."""
    return name.split("(")[0]


def _programs(ops, mods) -> list[str]:
    """The program each operation ran in: the module event that covers its
    start (operations in time order)."""
    order = sorted(range(len(ops)), key=lambda i: ops[i][0])
    out, k = [""] * len(ops), 0
    for i in order:
        t = ops[i][0]
        while k < len(mods) and mods[k][1] < t:
            k += 1
        out[i] = (_program(mods[k][2]) if k < len(mods) and mods[k][0] <= t
                  else "no program")
    return out


def _clip_events(evs, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in evs if e > lo and s < hi]


def read_planes(logdir: str) -> list[dict]:
    """The planes of the one trace under ``logdir``, as plain tuples."""
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
            lines[ln.name] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in ln.events]
        planes.append({"name": p.name, "lines": lines})
    return planes


def census(planes: list[dict]) -> dict:
    """The harness's spans and the events of each line the reduction reads,
    for a look by hand at a trace that reduced to nothing."""
    name, host = host_line(planes)
    out = {"spans": sum(ev[2] in HARNESS_SPANS for ev in host)}
    if name:
        out[name] = len(host)
    for p in planes:
        for ln in ("XLA Ops", "XLA Modules"):
            if ln in p["lines"]:
                out[f"{p['name']} {ln}"] = len(p["lines"][ln])
    return out


def breakdown(summary: dict) -> dict:
    return {"device_ops": _top(summary["ops"]),
            "idle_gaps": _top(summary["gaps"])}
