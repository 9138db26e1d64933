"""Benchmark driver — one section per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows per section.
  * table2 / fig3 / overhead : the paper's §IV artifacts (edge simulator)
  * solver_scaling           : re-split decision latency vs fleet size
  * roofline                 : §Roofline summary from the dry-run JSONs
                               (run ``python -m repro.launch.dryrun --all``
                               first; rows are skipped if absent)
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _csv(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    from benchmarks import paper_tables, roofline, solver_scaling
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")

    t0 = time.perf_counter()
    rows = paper_tables.table2_kpis()
    dt = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _csv(
            f"table2/bw{int(r['backhaul_mbps'])}", dt,
            f"static={r['static_latency_ms']}ms adaptive={r['adaptive_latency_ms']}ms "
            f"delta={r['delta_latency_pct']}% paper={r['paper_static_ms']}/"
            f"{r['paper_adaptive_ms']}ms")

    t0 = time.perf_counter()
    rows = paper_tables.fig3_latency_vs_bandwidth()
    dt = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _csv(f"fig3/bw{int(r['backhaul_mbps'])}", dt,
             f"static={r['static_latency_ms']}ms adaptive={r['adaptive_latency_ms']}ms "
             f"urllc_met={r['urllc_150ms_met_adaptive']}")

    t0 = time.perf_counter()
    rows = paper_tables.orchestration_overhead()
    dt = (time.perf_counter() - t0) * 1e6
    for r in rows:
        _csv(f"overhead/{r['metric']}", dt,
             f"value={r['value']} bound={r['paper_bound_ms']}ms")

    t0 = time.perf_counter()
    rows = solver_scaling.solver_scaling()
    for r in rows:
        _csv(f"solver/L{r['graph_units']}xN{r['fleet_nodes']}",
             r["warm_solve_ms"] * 1e3,
             f"segments={r['segments']} dp_nodes={r['dp_nodes']}")

    cells = roofline.load_cells("pod")
    for rec in cells:
        if rec.get("status") != "ok":
            _csv(f"roofline/{rec['arch']}/{rec['shape']}", 0.0, "ERROR")
            continue
        r = rec["roofline"]
        bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        _csv(f"roofline/{rec['arch']}/{rec['shape']}", bound * 1e6,
             f"bottleneck={r['bottleneck']} frac={rec['roofline_fraction']:.4f} "
             f"useful_flops={rec['useful_flops_ratio']:.3f}")


if __name__ == "__main__":
    main()
