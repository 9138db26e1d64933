"""End-to-end §IV scenario: static vs adaptive under a backhaul sweep,
with node-failure and straggler drills.

Run:  PYTHONPATH=src python examples/edge_orchestration.py
"""

import itertools

import numpy as np

from repro.core import DecisionKind
from repro.edgesim import MECScenarioParams, build_mec_scenario

print("== Table II reproduction (steady-state, 20-60s window) ==")
for bw in (20, 50, 100, 200):
    row = {}
    for adaptive in (False, True):
        p = MECScenarioParams(backhaul_mbps=bw, duration_s=60.0)
        res = build_mec_scenario(p, adaptive=adaptive).run()
        row["adaptive" if adaptive else "static"] = res.kpis(20.0, 60.0)
    s = row["static"]["mean_latency_s"] * 1e3
    a = row["adaptive"]["mean_latency_s"] * 1e3
    print(f"backhaul {bw:>3} Mb/s: static {s:5.0f} ms | adaptive {a:5.0f} ms "
          f"| Δ {100 * (a / s - 1):+.0f}%")

print("\n== node-failure drill: kill MEC-2 mid-run, watch re-placement ==")
p = MECScenarioParams(backhaul_mbps=50.0, duration_s=80.0)
sim = build_mec_scenario(p, adaptive=True)

# fail node 1 at t=40s by saturating it completely (dead == 100% util)
orig_trace = sim.util_traces[1]
sim.util_traces[1] = type(orig_trace)(
    lambda t: 0.99 if t >= 40.0 else orig_trace(t), 0.0, 0.99)
res = sim.run()
uses_node1_before = any(
    1 in d.config.assignment for d in itertools.islice(sim.orch.decisions, 35)
    if d.config)
final_cfg = sim.orch.current
print(f"node 1 used before failure: {uses_node1_before}")
print(f"final assignment (post-failure): {final_cfg.assignment} "
      f"(node 1 {'EVICTED' if 1 not in final_cfg.assignment else 'still used'})")
kinds = [d.kind for d in sim.orch.decisions if d.kind in
         (DecisionKind.MIGRATE, DecisionKind.RESPLIT)]
print(f"reconfigurations: {len(kinds)} ({[k.value for k in kinds]})")

lat_pre = np.mean([m.latency_s for m in res.window(30, 40)]) * 1e3
lat_post = np.mean([m.latency_s for m in res.window(60, 80)]) * 1e3
print(f"latency before failure {lat_pre:.0f} ms -> after recovery {lat_post:.0f} ms")
