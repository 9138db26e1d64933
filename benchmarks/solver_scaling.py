"""Solver-scaling benchmark: re-split decision latency vs problem size.

Backs the paper's claim that runtime graph re-splitting is cheap enough for
real-time orchestration (≤10 ms cycles), and our claim that the jitted DP
scales to 1000+-node fleets (with DP coarsening capping the layer dimension).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import SystemState, Workload
from repro.core.graph import make_transformer_graph
from repro.launch.compile_cache import enable_compile_cache


def _random_state(n: int, seed: int) -> SystemState:
    rng = np.random.default_rng(seed)
    bw = rng.uniform(10e6, 200e6, size=(n, n))
    bw = (bw + bw.T) / 2
    np.fill_diagonal(bw, np.inf)
    return SystemState(
        flops_per_s=rng.uniform(50e12, 600e12, n),
        mem_bytes=rng.uniform(16e9, 320e9, n),
        background_util=rng.uniform(0.05, 0.7, n),
        trusted=(rng.random(n) < 0.5) | (np.arange(n) == 0),
        link_bw=bw,
        link_lat=np.full((n, n), 0.004) * (1 - np.eye(n)),
        mem_bw=rng.uniform(0.5e12, 5e12, n),
    )


def solver_scaling() -> list[dict]:
    from repro.core import SplitRevision

    rows = []
    wl = Workload(tokens_in=56, tokens_out=8, arrival_rate=4.0)
    sr = SplitRevision(strategy="dp", max_units=96, max_nodes=16)
    for layers, nodes in [(34, 4), (66, 8), (66, 16), (98, 32), (130, 128),
                          (130, 1024)]:
        g = make_transformer_graph(
            name=f"L{layers}", num_layers=layers - 2, d_model=4096,
            flops_per_layer_token=4.4e8, weight_bytes_per_layer=4.4e8,
            embed_weight_bytes=1e9, head_weight_bytes=1e9, head_flops_token=1e9,
        )
        st = _random_state(nodes, seed=layers + nodes)
        st.trusted[0] = True
        # compile once, then measure warm decision latency (the runtime path)
        sol = sr.revise(g, st, wl)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            sol = sr.revise(g, st, wl)
            times.append(time.perf_counter() - t0)
        rows.append(
            dict(
                graph_units=layers, fleet_nodes=nodes,
                dp_nodes=min(nodes, 16),
                warm_solve_ms=round(1e3 * float(np.median(times)), 3),
                segments=len(sol.assignment),
                cost_s=round(sol.cost, 4),
            )
        )
    return rows


def main() -> None:  # pragma: no cover
    enable_compile_cache()
    for r in solver_scaling():
        print(r)


if __name__ == "__main__":
    main()
