"""Fleet-scaling benchmark: solver amortization, monitoring cost, admission.

Three questions the fleet layer must answer before any further scaling PR:

1. **Solver amortization** — does one ``BatchedJointSplitter.solve_batch``
   call over B sessions beat B sequential ``JaxJointSplitter.solve`` calls?
   (It must: the batched path exists so a monitoring cycle stays flat-cost
   when dozens of sessions blow their QoS budget at once.)  Reported as warm
   per-batch latency vs B× the warm single-session solve.
2. **Monitoring-cycle cost** — what does the PR-3 device-resident
   incremental fleet state save over repacking it from Python session
   objects every cycle (``invalidate_resident_state()`` before each step)?
   Reported as warm per-cycle wall-time percentiles at 32/64/128 saturated
   sessions, with a repack-vs-eval breakdown, on byte-identical fleets.
   NOTE: the cold mode is an in-tree regression A/B, NOT the historical
   PR-2 baseline — it re-pays the full-fleet repack but keeps PR-3's fused
   kernels and pack caches (the real PR-2 code measured ~107 ms p50 at 32
   sessions on the same container vs ~31 ms resident; see ROADMAP).  With
   ``--json`` the sweep is also written to ``BENCH_fleet.json`` at the
   repo root (stable schema — the perf trajectory is tracked PR over PR
   and the scheduled CI job uploads it as an artifact).
3. **Aggregate QoS under churn** — how do mean/p95 latency, QoS violation
   rate, ``max_rho``, and admission outcomes move as the session cap grows
   1→64 on the fixed §IV fleet, with admission control OFF (PR-1 blind
   admit: saturates, ``max_rho`` > 1) vs ON (latency-priced accept/defer/
   reject: bounded)?

Run:  PYTHONPATH=src python benchmarks/fleet_scaling.py [--smoke] [--json out.json]
      (--quick is an alias for --smoke; section flags: --amortization,
       --monitor, --qos, --storm, --shards run a subset)
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.core import (
    BatchedJointSplitter,
    JaxJointSplitter,
    SessionProblem,
    ShardedFleetOrchestrator,
    Workload,
)
from repro.core.placement import repair_capacity, surrogate_cost
from repro.edgesim import (
    ChaosSpec,
    FailureSpec,
    FleetScenarioParams,
    FleetSimConfig,
    MECScenarioParams,
    base_system_state,
    build_fleet_scenario,
    fleet_model_catalog,
    hot_sharded_fleet,
    saturated_fleet,
    spike_onsets,
)
from repro.launch.compile_cache import enable_compile_cache

_BATCHES = (1, 2, 4, 8, 16, 32, 64)


def _problems(n_sessions: int, seed: int = 0) -> list[SessionProblem]:
    """Heterogeneous sessions over the §IV fleet: mixed archs/workloads/ingress."""
    rng = np.random.default_rng(seed)
    catalog = fleet_model_catalog()
    out = []
    for _ in range(n_sessions):
        _, graph = catalog[int(rng.integers(len(catalog)))]
        wl = Workload(
            tokens_in=int(rng.integers(16, 96)),
            tokens_out=int(rng.integers(4, 16)),
            arrival_rate=float(rng.uniform(0.3, 2.0)),
        )
        out.append(SessionProblem(graph, wl, source_node=int(rng.integers(0, 3))))
    return out


def solver_amortization(*, reps: int = 5, max_units: int = 96) -> list[dict]:
    """Warm batched-solve latency vs a MEASURED sequential sweep of the same
    B sessions through the single-session jitted solver."""
    state = base_system_state(MECScenarioParams())
    single = JaxJointSplitter()
    batched = BatchedJointSplitter()
    rows = []
    probs_all = _problems(max(_BATCHES))

    def solve_seq(probs):
        for p in probs:
            single.solve(p.graph, state, p.workload, source_node=p.source_node,
                         max_units=max_units)

    for B in _BATCHES:
        probs = probs_all[:B]
        solve_seq(probs)                                           # compile
        sols = batched.solve_batch(probs, state, max_units=max_units)  # compile
        # cross-check the batch against the single-session solver
        for p, s in zip(probs[: min(B, 4)], sols):
            ref = single.solve(p.graph, state, p.workload,
                               source_node=p.source_node, max_units=max_units)
            sc_b = surrogate_cost(p.graph, s.boundaries, s.assignment, state,
                                  p.workload, source_node=p.source_node)
            sc_r = surrogate_cost(p.graph, ref.boundaries, ref.assignment, state,
                                  p.workload, source_node=p.source_node)
            assert np.isclose(sc_b, sc_r, rtol=1e-5), (B, sc_b, sc_r)
        t_seq, t_bat = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            solve_seq(probs)
            t_seq.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            batched.solve_batch(probs, state, max_units=max_units)
            t_bat.append(time.perf_counter() - t0)
        seq = float(np.median(t_seq))
        bat = float(np.median(t_bat))
        rows.append(dict(
            sessions=B,
            batched_ms=round(1e3 * bat, 3),
            sequential_ms=round(1e3 * seq, 3),
            speedup=round(seq / bat, 2),
            per_session_us=round(1e6 * bat / B, 1),
        ))
    return rows


def _pcts(xs, scale=1e3) -> dict[str, float]:
    return {f"p{q}": round(scale * float(np.percentile(xs, q)), 3)
            for q in (50, 90, 95)}


def monitoring_cost(*, sessions=(32, 64, 128), cycles: int = 15,
                    seed: int = 0) -> list[dict]:
    """Warm monitoring-cycle wall-time percentiles on saturated fleets:
    device-resident incremental state vs forcing a cold full-fleet repack
    every cycle, on byte-identical fleets.  (The cold mode still uses
    PR-3's fused kernels and pack caches — it isolates the repack cost,
    it does not reproduce the PR-2 baseline.)

    ``eval_ms`` is the fused device dispatches (price + migrate + batched
    Eq. 4 repair) and ``pack_ms`` resident-buffer packing inside the cycle
    (row writes on commits; 0 in steady state) — the repack-vs-eval
    breakdown tracked in ``BENCH_fleet.json``.  ``repair_calls_per_cycle``
    counts host `placement.repair_capacity` invocations per measured cycle:
    0 since PR 4 folded Eq. 4 into the batched solver + fused repair pass
    (was ~56/cycle at 32 saturated sessions), regression-gated by
    ``benchmarks/check_regression.py``.
    """
    def _warm(orch, *, cold: bool) -> float:
        """Step until compiles are done AND buffer shapes stop growing —
        a K-axis growth mid-measurement would recompile the fused kernels
        and pollute the percentiles."""
        t = 0.0
        for _ in range(3):
            if cold:
                orch.invalidate_resident_state()
            orch.step(now=t)
            t += 1.0
        for _ in range(8):
            buf = orch._buffers
            shape = (buf.n_rows, buf.max_segs)
            if cold:
                orch.invalidate_resident_state()
            orch.step(now=t)
            t += 1.0
            buf = orch._buffers
            if (buf.n_rows, buf.max_segs) == shape:
                break
        return t

    rows = []
    for n in sessions:
        orch = saturated_fleet(n, seed)
        t = _warm(orch, cold=False)
        t_res, t_eval, t_pack = [], [], []
        repair0 = repair_capacity.calls
        for c in range(cycles):
            t0 = time.perf_counter()
            fd = orch.step(now=t + float(c))
            t_res.append(time.perf_counter() - t0)
            t_eval.append(fd.eval_time_s)
            t_pack.append(fd.pack_time_s)
        repair_per_cycle = (repair_capacity.calls - repair0) / cycles
        ck_per_cycle = sum(
            d.n_conflict_keep for d in orch.decisions[-cycles:]
        ) / cycles

        # A/B: identical fleet, but the resident state is dropped before
        # every cycle so each step pays the full O(fleet) repack + transfer
        orch = saturated_fleet(n, seed)
        t = _warm(orch, cold=True)
        t_cold = []
        for c in range(cycles):
            orch.invalidate_resident_state()
            t0 = time.perf_counter()
            orch.step(now=t + float(c))
            t_cold.append(time.perf_counter() - t0)

        # forecast-on arm: identical fleet with a live CapacityForecaster —
        # measures the fused seasonal update + worst-case re-pricing +
        # forecast-priced migrate overhead on the same cycles (v3 metric)
        orch = saturated_fleet(n, seed, forecast=True)
        t = _warm(orch, cold=False)
        t_fc = []
        for c in range(cycles):
            t0 = time.perf_counter()
            orch.step(now=t + float(c))
            t_fc.append(time.perf_counter() - t0)

        p_res, p_cold = _pcts(t_res), _pcts(t_cold)
        rows.append(dict(
            sessions=n,
            resident_cycle_ms=p_res,
            cold_repack_cycle_ms=p_cold,
            resident_fc_cycle_ms=_pcts(t_fc),
            eval_ms=_pcts(t_eval),
            pack_ms=_pcts(t_pack),
            repair_calls_per_cycle=round(repair_per_cycle, 2),
            conflict_keeps_per_cycle=round(ck_per_cycle, 2),
            repack_overhead_ms_p50=round(p_cold["p50"] - p_res["p50"], 3),
            speedup_p50=round(p_cold["p50"] / max(p_res["p50"], 1e-9), 2),
        ))
    return rows


def write_bench_fleet(sections: dict[str, list[dict]],
                      path: pathlib.Path) -> None:
    """Stable-schema perf artifact, appendable PR over PR.

    v2 added ``repair_calls_per_cycle``; v3 added the ``qos`` section (the
    seed-paired forecast A/B with onset-ρ / SLO-breach / preemption KPIs)
    and ``resident_fc_cycle_ms`` in the monitor rows; v4 added the ``storm``
    section (seed-paired correlated-node-failure A/B: recovery time,
    memory-violation minutes, revocation counts); v5 added the ``drift``
    section (calibrated-vs-analytic pricing on identical placements, from
    the committed ``BENCH_profiles.json``); v6 adds the ``chaos`` section
    (seed-paired control-plane chaos A/B: invariant violations, crash
    recovery, zombie fencing, SLO-breach minutes); v7 adds the ``thrash``
    section (seed-paired high-churn fixed-point A/B: conflict-KEEP rate,
    commit-thrash count, breach-minutes, converged-sweep histogram) and
    ``conflict_keeps_per_cycle`` in the monitor rows; v8 adds the ``shards``
    section (region-sharded cycle-cost sweep at 1,024/4,096/10,240 total
    sessions with a fixed triggered-set size, plus the shards=1
    comparability row gated against the monitor rows).  Sections absent
    from ``sections`` are carried over from the committed file, so a
    ``--monitor``-only refresh never drops the qos baseline (and vice
    versa).
    """
    doc = {"schema": "bench-fleet/v8",
           "source": ("benchmarks/fleet_scaling.py --monitor/--qos/--storm/"
                      "--drift/--chaos/--thrash/--shards")}
    if path.exists():
        try:
            old = json.loads(path.read_text())
            for k in ("monitor", "qos", "storm", "drift", "chaos",
                      "thrash", "shards"):
                if k in old:
                    doc[k] = old[k]
        except (json.JSONDecodeError, OSError):
            pass
    doc.update(sections)
    # which sections THIS run actually produced: check_regression gates the
    # qos absolutes only on a fresh sweep — carried-over rows would let a
    # --monitor-only refresh mask (or spuriously re-flag) a forecast
    # regression the run never exercised
    doc["refreshed"] = sorted(sections)
    path.write_text(json.dumps(doc, indent=2) + "\n")


_AB_HORIZONS = {64: 40}   # cap → forecast horizon (default: ForecastConfig)


def forecast_ab(*, caps=(32, 64), duration_s: float = 180.0,
                warmup_s: float = 96.0, seed: int = 0) -> list[dict]:
    """Seed-paired forecast-on/off A/B on the §IV saturation scenario.

    Both arms run latency-priced admission on the identical arrival stream;
    only the CapacityForecaster differs.  KPIs are measured on the
    post-warmup window [warmup, duration): the predictor needs one observed
    season (40 s) before its forecasts go live, and sessions admitted
    reactively BEFORE that must drain (mean lifetime 30 s) so the window
    measures the regime the forecast controller actually governs.  KPIs
    include the spike-ONSET max node ρ (the PR-2 excursion: sessions
    admitted in the trough transiently pushing the home MEC past ρ = 1
    when the spike lands), SLO-breach-minutes, and the
    preemptive-migration count.  ``benchmarks/check_regression.py`` gates
    the forecast arm's absolutes (onset ρ < 1, zero breach minutes,
    accept-rate within 5 pts of reactive).

    The horizon is an operating-point parameter (``_AB_HORIZONS``): at
    cap 32 the default short horizon (12) maximizes accepts — unsafe
    trough admits still exist but proactive migration has enough slack to
    clear them before the spike; at cap 64 contention leaves no room for
    corrective migration, so admission must see the whole season
    (horizon = 40, "admit only what survives every phase") to keep
    breach-minutes at zero.  Measured on this container: H-sweep
    {12, 16, 24, 40} → cap-32 breach {0, 0.04, 0, 0} / cap-64 breach
    {0.02, 0, 0.03, 0} minutes.
    """
    rows = []
    mec = MECScenarioParams()
    onsets = spike_onsets(mec, duration_s)
    w0 = warmup_s
    for cap in caps:
        for forecast in (False, True):
            p = FleetScenarioParams(sim=FleetSimConfig(
                duration_s=duration_s,
                max_sessions=cap,
                initial_sessions=min(cap, 2),
                session_arrival_per_s=max(0.2, cap / 60.0 * 2.0),
                mean_lifetime_s=30.0,
                seed=seed,
                admission=True,
                forecast=forecast,
                forecast_horizon_steps=_AB_HORIZONS.get(
                    cap, FleetSimConfig.forecast_horizon_steps
                ),
            ))
            sim = build_fleet_scenario(p)
            t0 = time.perf_counter()
            res = sim.run()
            wall = time.perf_counter() - t0
            k = res.kpis(w0, duration_s)
            rows.append(dict(
                arm="forecast" if forecast else "reactive",
                session_cap=cap,
                horizon_steps=p.sim.forecast_horizon_steps,
                onset_max_rho=round(
                    res.onset_max_rho(onsets, t0=w0, t1=duration_s), 3
                ),
                max_rho=round(k.get("max_rho", 0.0), 3),
                slo_breach_minutes=round(
                    k.get("slo_breach_minutes", 0.0), 3
                ),
                preemptive_migrations=int(
                    k.get("preemptive_migrations", 0.0)
                ),
                admit_frac=round(k.get("admit_frac", 1.0), 3),
                mean_sessions=round(k.get("mean_sessions", 0.0), 1),
                p95_latency_ms=round(1e3 * k.get("p95_latency_s", 0.0), 1),
                qos_violation_frac=round(
                    k.get("qos_violation_frac", 0.0), 4
                ),
                sim_wall_s=round(wall, 1),
            ))
    return rows


def failure_storm(*, cap: int = 32, duration_s: float = 60.0,
                  blast_at_s: float = 20.0, blast_mttr_s: float = 25.0,
                  seed: int = 11, fail_seed: int = 5) -> list[dict]:
    """Seed-paired failure-handling on/off A/B: a correlated 2-node blast
    (MEC nodes 1+2, the trusted hosts private segments are pinned to)
    on the saturated cap-``cap`` fleet.

    Both arms share one arrival stream AND one pre-drawn failure timeline;
    only the handling differs.  OFF = the injector still zeroes dead-node
    capacity in ``SystemState`` but no heartbeat registry is wired, so the
    orchestrator only reacts through its ordinary latency/util triggers
    (cooldown + hysteresis gated).  ON = heartbeat-driven ``node-fail``
    trigger class (bypasses cooldown), forced re-placement through the
    fused migrate + batched repair path, and graceful revocation of the
    loosest-SLO sessions when the survivors cannot host everyone.

    KPIs per arm: ``recovery_s`` (blast onset → first tick after which
    Eq. 4 memory violations stay zero; ``null`` = never recovered inside
    the run), ``mem_violation_minutes``, ``slo_breach_minutes``,
    preemption/recovery counts and the per-QoS-class preemption breakdown.
    ``benchmarks/check_regression.py`` gates the ON arm's absolutes
    (bounded recovery, strictly lower violation minutes than OFF, zero
    tier-0 preemptions).
    """
    rows = []
    spec = FailureSpec(seed=fail_seed, blast_at_s=blast_at_s,
                       blast_nodes=(1, 2), blast_mttr_s=blast_mttr_s)
    for handling in (False, True):
        p = FleetScenarioParams(sim=FleetSimConfig(
            duration_s=duration_s,
            tick_s=0.5,
            monitor_interval_s=1.0,
            max_sessions=cap,
            initial_sessions=cap // 2,
            session_arrival_per_s=max(0.2, cap / 60.0 * 2.0),
            mean_lifetime_s=30.0,
            seed=seed,
            admission=True,
            failures=spec,
            failure_handling=handling,
            preempt_patience_s=30.0,
        ))
        sim = build_fleet_scenario(p)
        t0 = time.perf_counter()
        res = sim.run()
        wall = time.perf_counter() - t0
        k = res.kpis(0.0, duration_s)
        rec = res.recovery_time_s(blast_at_s)
        rows.append(dict(
            arm="handling" if handling else "no-handling",
            session_cap=cap,
            blast_nodes=[1, 2],
            blast_at_s=blast_at_s,
            blast_mttr_s=blast_mttr_s,
            recovery_s=None if rec is None else round(rec, 2),
            mem_violation_minutes=round(
                k.get("mem_violation_minutes", 0.0), 4),
            slo_breach_minutes=round(k.get("slo_breach_minutes", 0.0), 4),
            sessions_preempted=int(k.get("sessions_preempted", 0.0)),
            sessions_recovered=int(k.get("sessions_recovered", 0.0)),
            preempted_by_class=dict(sim.admission.preempted_by_class)
            if sim.admission is not None else {},
            p95_latency_ms=round(1e3 * k.get("p95_latency_s", 0.0), 1),
            qos_violation_frac=round(k.get("qos_violation_frac", 0.0), 4),
            sim_wall_s=round(wall, 1),
        ))
    return rows


def chaos_ab(*, cap: int = 32, duration_s: float = 120.0,
             monitor_interval_s: float = 0.5,
             seed: int = 13, chaos_seed: int = 9) -> list[dict]:
    """Seed-paired control-plane chaos A/B: controller crash/restart, RPC
    transport faults (drop/duplicate/delay on prepare/commit), and
    telemetry corruption (NaN utilization + link rows) on the saturated
    cap-``cap`` fleet, ≥200 monitoring cycles per arm.

    Both arms share one arrival stream AND one pre-drawn chaos campaign
    (:class:`~repro.edgesim.ChaosSpec`); only the handling differs.
    OFF = naive control plane: one unfenced RPC attempt per delivery, a
    restarted controller scrapes the data plane (defer queue, EWMAs,
    forecast rings, and the broadcast version counter are lost — reissued
    version numbers break global monotonicity), and poisoned telemetry is
    priced verbatim (NaN latencies = unserved SLO).  ON = the resilient
    control plane: journaled crash recovery + epoch fencing of the
    pre-crash zombie, bounded-retry broadcasts with idempotent agent-side
    dedup, and the telemetry guard (quarantine + last-good substitution).

    The :class:`~repro.edgesim.InvariantChecker` runs after every
    monitoring cycle on BOTH arms; ``benchmarks/check_regression.py``
    gates the ON arm's absolutes (zero invariant violations, zombie never
    commits, bounded restore wall-time, strictly fewer SLO-breach minutes
    than OFF).
    """
    rows = []
    spec = ChaosSpec(
        seed=chaos_seed,
        # two pinned crashes guarantee the recovery machinery is exercised
        # whatever the Poisson draw does; the rate adds seed-dependent extras
        crash_rate_per_s=0.01, min_crash_spacing_s=20.0,
        crash_times=(0.25 * duration_s, 0.625 * duration_s),
        rpc_fault_rate_per_s=0.05, rpc_fault_duration_s=6.0,
        rpc_drop_p=0.2, rpc_dup_p=0.15, rpc_delay_p=0.1,
        telemetry_rate_per_s=0.04, telemetry_duration_s=4.0,
    )
    for handling in (False, True):
        # moderate load (not the storm benchmark's saturation): baseline
        # SLO breaches must stay rare so the A/B margin measures what the
        # CHAOS causes, not what the offered load causes in both arms
        p = FleetScenarioParams(sim=FleetSimConfig(
            duration_s=duration_s,
            tick_s=0.25,
            monitor_interval_s=monitor_interval_s,
            max_sessions=cap,
            initial_sessions=cap // 4,
            session_arrival_per_s=max(0.2, cap / 90.0),
            mean_lifetime_s=40.0,
            seed=seed,
            admission=True,
            chaos=spec,
            chaos_handling=handling,
        ))
        sim = build_fleet_scenario(p)
        t0 = time.perf_counter()
        res = sim.run()
        wall = time.perf_counter() - t0
        k = res.kpis(0.0, duration_s)
        cs = sim.chaos_stats
        guard = sim.orch.telemetry_guard
        rows.append(dict(
            arm="handling" if handling else "no-handling",
            session_cap=cap,
            cycles=int(duration_s / monitor_interval_s),
            crashes=len(sim._chaos.crash_times),
            rpc_fault_windows=len(sim._chaos.rpc_windows),
            telemetry_events=len(sim._chaos.telemetry_events),
            invariant_violations=len(sim.invariants.violations),
            controller_restarts=cs["controller_restarts"],
            zombie_attempts=cs["zombie_attempts"],
            zombie_fenced=cs["zombie_fenced"],
            zombie_committed=cs["zombie_committed"],
            lost_deferred=cs["lost_deferred"],
            max_restore_ms=round(1e3 * cs["max_restore_wall_s"], 2),
            degraded_cycles=sim.orch.degraded_cycles,
            guard_clamped_samples=(guard.clamped_samples
                                   if guard is not None else 0),
            slo_breach_minutes=round(k.get("slo_breach_minutes", 0.0), 4),
            qos_violation_frac=round(k.get("qos_violation_frac", 0.0), 4),
            p95_latency_ms=round(1e3 * k.get("p95_latency_s", 0.0), 1),
            sim_wall_s=round(wall, 1),
        ))
    return rows


def pricing_drift(*, profiles: pathlib.Path | None = None,
                  n_sessions: int = 32, seed: int = 0) -> list[dict]:
    """Calibrated-vs-analytic pricing drift from the committed profiles.

    Per profiled catalog arch: solve ONE joint split analytically, then
    price that identical placement under both providers — the drift is pure
    cost-model delta, no solver feedback.  The ``_fleet`` row is the
    seed-paired fleet-level arm: two orchestrators admit the IDENTICAL
    session stream and differ only in ``cost_model``; their fused
    ``price_fleet`` means quantify how far measured calibration moves the
    control plane's view of the same fleet.  ``check_regression.py`` gates
    the rows' sanity (finite, positive, calibrated within a sane band).
    """
    from repro.core.cost_model import AnalyticCostModel
    from repro.core.profiling import CalibratedCostModel

    if profiles is None:
        profiles = (pathlib.Path(__file__).resolve().parent.parent
                    / "BENCH_profiles.json")
    from repro.configs import get_bundle

    cal = CalibratedCostModel.from_file(profiles)
    ana = AnalyticCostModel()
    state = base_system_state(MECScenarioParams())
    splitter = JaxJointSplitter()
    wl = Workload(tokens_in=64, tokens_out=8, arrival_rate=1.0)
    rows = []
    for arch, mp in sorted(cal.profile.models.items()):
        # the FULL catalog graph — the profile was measured on the reduced
        # config; the ratio projection is exactly what this row quantifies
        graph = get_bundle(arch).model_graph()
        sol = splitter.solve(graph, state, wl, max_units=96)
        lat_a = ana.chain_latency(graph, sol.boundaries, sol.assignment,
                                  state, wl)
        lat_c = cal.chain_latency(graph, sol.boundaries, sol.assignment,
                                  state, wl)
        rows.append(dict(
            arch=arch, family=mp.family, measured_units=mp.graph_units,
            compute_scale=round(mp.compute_scale, 4),
            transfer_scale=round(mp.transfer_scale, 4),
            analytic_ms=round(1e3 * lat_a, 3),
            calibrated_ms=round(1e3 * lat_c, 3),
            drift_frac=round(lat_c / lat_a - 1.0, 4),
        ))
    lat_mean = {}
    for name, cm in (("analytic", None), ("calibrated", cal)):
        orch = saturated_fleet(n_sessions, seed, cost_model=cm)
        _, lat, _ = orch.price_fleet()
        lat_mean[name] = float(np.mean(lat))
    rows.append(dict(
        arch="_fleet", sessions=n_sessions,
        analytic_ms=round(1e3 * lat_mean["analytic"], 3),
        calibrated_ms=round(1e3 * lat_mean["calibrated"], 3),
        drift_frac=round(lat_mean["calibrated"] / lat_mean["analytic"] - 1.0,
                         4),
    ))
    return rows


def fleet_qos(*, duration_s: float = 60.0, seed: int = 0,
              caps=(1, 4, 8, 16, 32, 64)) -> list[dict]:
    """Aggregate QoS + admission outcomes vs session cap, admission OFF
    (PR-1 blind admit) and ON (latency-priced accept/defer/reject)."""
    rows = []
    for admission in (False, True):
        for cap in caps:
            p = FleetScenarioParams(sim=FleetSimConfig(
                duration_s=duration_s,
                max_sessions=cap,
                initial_sessions=min(cap, 2),
                # arrival rate scaled so the cap actually binds within the run
                session_arrival_per_s=max(0.2, cap / duration_s * 2.0),
                mean_lifetime_s=duration_s / 2,
                seed=seed,
                admission=admission,
            ))
            sim = build_fleet_scenario(p)
            t0 = time.perf_counter()
            res = sim.run()
            wall = time.perf_counter() - t0
            k = res.kpis(duration_s * 0.25, duration_s)
            rows.append(dict(
                admission="on" if admission else "off",
                session_cap=cap,
                mean_sessions=round(k.get("mean_sessions", 0.0), 1),
                mean_latency_ms=round(1e3 * k.get("mean_latency_s", 0.0), 1),
                p95_latency_ms=round(1e3 * k.get("p95_latency_s", 0.0), 1),
                qos_violation_frac=round(k.get("qos_violation_frac", 0.0), 3),
                max_rho=round(k.get("max_rho", 0.0), 2),
                admit_frac=round(k.get("admit_frac", 1.0), 3),
                rejected_per_s=round(k.get("rejected_per_s", 0.0), 3),
                deferred_per_s=round(k.get("deferred_per_s", 0.0), 3),
                resplits_per_s=round(k.get("resplits_per_s", 0.0), 3),
                mean_solver_ms=round(k.get("mean_solver_ms", 0.0), 2),
                sim_wall_s=round(wall, 1),
            ))
    return rows


def thrash_ab(*, n_sessions: int = 16, cycles: int = 30,
              churn_every: int = 2, seed: int = 0) -> list[dict]:
    """Seed-paired high-churn A/B: cycle-start-greedy commit gate (fixed
    point OFF) vs the device red/black fixed point (ON).

    Both arms start from byte-identical saturated fleets and replay an
    IDENTICAL pre-drawn churn schedule (every ``churn_every`` cycles the
    oldest session departs and an identically-drawn replacement is
    admitted), so every difference in the rows is the commit gate.

    Per arm: total conflict-KEEPs (dirtied-residual commit-gate rejects —
    the thrash signature this PR eliminates), no-gain KEEPs, commits,
    commit-thrash count (a session assignment returning to its
    2-cycles-ago placement after moving away: A→B→A), SLO breach-minutes
    integrated from each cycle's per-session predicted latency vs its SLO,
    and — ON arm — the converged-sweep histogram and joint-guard aborts.
    ``check_regression.check_thrash`` gates ON-arm conflict-KEEPs == 0 and
    ON breach-minutes ≤ OFF.
    """
    from collections import Counter

    from repro.core import breach_seconds

    catalog = fleet_model_catalog()
    rng = np.random.default_rng(seed + 1)
    schedule = [
        dict(graph_idx=int(rng.integers(len(catalog))),
             tokens_in=int(rng.integers(32, 96)),
             tokens_out=int(rng.integers(8, 16)),
             rate=float(rng.uniform(2.0, 5.0)),
             source=int(rng.integers(0, 3)))
        for _ in range(cycles // churn_every + 1)
    ]
    rows = []
    for fixed_point in (False, True):
        orch = saturated_fleet(n_sessions, seed, fixed_point=fixed_point)
        for t in range(3):                      # warm / compile
            orch.step(now=float(t))
        live = sorted(orch.sessions)
        hist: dict[int, list[tuple]] = {}
        conflict = nogain = commits = thrash = aborts = 0
        sweep_hist: Counter = Counter()
        breach_s = 0.0
        churn_i = 0
        for c in range(cycles):
            now = 3.0 + float(c)
            if c % churn_every == 0 and live:
                orch.depart(live.pop(0))
                sp = schedule[churn_i]
                churn_i += 1
                _, graph = catalog[sp["graph_idx"]]
                live.append(orch.admit(
                    graph,
                    Workload(sp["tokens_in"], sp["tokens_out"], sp["rate"]),
                    source_node=sp["source"], now=now,
                ))
            fd = orch.step(now=now)
            conflict += fd.n_conflict_keep
            nogain += fd.n_nogain_keep
            commits += fd.n_migrate + fd.n_resplit
            aborts += fd.fixed_point_aborts
            if fixed_point and fd.fixed_point_sweeps:
                sweep_hist[fd.fixed_point_sweeps] += 1
            # breach integrated with ONE estimator for both arms: the fused
            # read-path price of every committed config (decision-recorded
            # latencies mix pricing stages and would bias the comparison)
            p_sids, p_lat, _ = orch.price_fleet()
            for sid, lat in zip(p_sids, p_lat):
                sess = orch.sessions[sid]
                slo = (sess.qos.latency_slo_s if sess.qos is not None
                       else orch.thresholds.latency_max_s)
                breach_s += breach_seconds(float(lat), slo)
                h = hist.setdefault(sid, [])
                h.append(sess.config.assignment)
                if (len(h) >= 3 and h[-1] == h[-3] and h[-1] != h[-2]):
                    thrash += 1
        rows.append(dict(
            arm="fixed_point_on" if fixed_point else "fixed_point_off",
            sessions=n_sessions, cycles=cycles, churn_every=churn_every,
            conflict_keeps=conflict, nogain_keeps=nogain, commits=commits,
            commit_thrash=thrash,
            breach_minutes=round(breach_s / 60.0, 3),
            fixed_point_aborts=aborts,
            sweep_hist={str(k): v for k, v in sorted(sweep_hist.items())},
        ))
    return rows


def shard_scaling(*, shard_sessions: int = 128, regions=(8, 32, 80),
                  cycles: int = 12, hot_regions: int = 2,
                  seed: int = 0) -> list[dict]:
    """Region-sharded resident fleet: cycle cost vs TOTAL session count at a
    FIXED triggered-set size (``hot_regions`` shards active per cycle).

    Each region holds ``shard_sessions`` resident sessions; the first
    ``hot_regions`` regions carry a live :class:`CapacityForecaster` (so
    they run a full per-shard step every cycle) and a :func:`diurnal`
    background trace driving their MEC nodes.  Every other shard is
    resolved by the ONE vmapped cross-shard screen dispatch.  The tentpole
    claim this sweep gates: p50 cycle time grows ~O(triggered set) — i.e.
    sub-linearly in total sessions as regions are added — because a quiet
    shard costs only its slice of the screen.

    The ``regions=1`` comparability row wraps the SAME saturated 128-session
    fleet the ``monitor`` section measures in a single-region
    :class:`ShardedFleetOrchestrator` (which delegates verbatim), so
    ``check_regression.check_shards`` can gate the wrapper's overhead
    against the monitor row of the same artifact.
    """
    rows = []
    for n_regions in regions:
        w, drive_hot = hot_sharded_fleet(n_regions, shard_sessions, seed,
                                         hot_regions=hot_regions)
        t = 1.0
        for _ in range(3):                     # warm: compile + settle
            drive_hot(t)
            w.step(t)
            t += 1.0
        disp0 = sum(o.kernel.dispatches for o in w.inners)
        stepped0 = w.shards_stepped
        cross0 = w.cross_migrations
        t_cycle = []
        for _ in range(cycles):
            drive_hot(t)
            t0 = time.perf_counter()
            w.step(t)
            t_cycle.append(time.perf_counter() - t0)
            t += 1.0
        disp = sum(o.kernel.dispatches for o in w.inners) - disp0
        rows.append(dict(
            sessions=n_regions * shard_sessions,
            regions=n_regions,
            shard_sessions=shard_sessions,
            hot_regions=min(hot_regions, n_regions),
            cycle_ms=_pcts(t_cycle),
            shards_stepped_per_cycle=round(
                (w.shards_stepped - stepped0) / cycles, 2),
            dispatches_per_cycle=round(disp / cycles, 2),
            cross_migrations=w.cross_migrations - cross0,
        ))

    # regions=1 comparability row: the monitor section's saturated fleet,
    # stepped through the (verbatim-delegating) wrapper
    orch = saturated_fleet(shard_sessions, seed)
    w1 = ShardedFleetOrchestrator(
        [orch], region_of=np.zeros(
            orch.profiler.base_state.num_nodes, dtype=np.int64))
    t = 0.0
    for _ in range(5):                         # warm like monitoring_cost
        w1.step(t)
        t += 1.0
    t_cycle = []
    for _ in range(cycles):
        t0 = time.perf_counter()
        w1.step(t)
        t_cycle.append(time.perf_counter() - t0)
        t += 1.0
    rows.append(dict(
        sessions=shard_sessions, regions=1,
        shard_sessions=shard_sessions, hot_regions=0,
        cycle_ms=_pcts(t_cycle),
        comparability="monitor",
    ))
    return rows


def main() -> None:  # pragma: no cover
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--quick", dest="smoke", action="store_true",
                    help="short horizons / small sweeps for CI smoke")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write all sections as a JSON artifact")
    ap.add_argument("--amortization", action="store_true")
    ap.add_argument("--monitor", action="store_true")
    ap.add_argument("--qos", action="store_true")
    ap.add_argument("--storm", action="store_true")
    ap.add_argument("--drift", action="store_true",
                    help="calibrated-vs-analytic pricing drift from the "
                         "committed BENCH_profiles.json")
    ap.add_argument("--chaos", action="store_true",
                    help="control-plane chaos A/B (crash recovery, RPC "
                         "faults, telemetry corruption, invariant checks)")
    ap.add_argument("--thrash", action="store_true",
                    help="seed-paired high-churn fixed-point A/B "
                         "(conflict-KEEP rate, commit thrash, breach-"
                         "minutes, converged-sweep histogram)")
    ap.add_argument("--shards", action="store_true",
                    help="region-sharded cycle-cost sweep to 10,240 total "
                         "sessions at a fixed triggered-set size, plus the "
                         "shards=1 comparability row")
    args = ap.parse_args()
    enable_compile_cache()
    run_all = not (args.amortization or args.monitor or args.qos
                   or args.storm or args.drift or args.chaos or args.thrash
                   or args.shards)

    out: dict[str, list[dict]] = {}
    if run_all or args.amortization:
        print("== solver amortization (warm, batched vs B x single) ==")
        out["solver_amortization"] = solver_amortization(
            reps=3 if args.smoke else 5
        )
        for r in out["solver_amortization"]:
            print(r)
    bench_sections: dict[str, list[dict]] = {}
    if run_all or args.monitor:
        print("\n== monitoring cycle cost (saturated fleet, warm, resident "
              "vs cold repack vs forecast-on) ==")
        out["monitoring_cost"] = monitoring_cost(
            sessions=(8, 16) if args.smoke else (32, 64, 128),
            cycles=5 if args.smoke else 15,
        )
        for r in out["monitoring_cost"]:
            print(r)
        if not args.smoke:
            bench_sections["monitor"] = out["monitoring_cost"]
    if run_all or args.qos:
        print("\n== fleet QoS vs session cap (3 MEC + cloud, churn, "
              "admission off/on) ==")
        out["fleet_qos"] = fleet_qos(
            duration_s=20.0 if args.smoke else 60.0,
            caps=(4, 16) if args.smoke else (1, 4, 8, 16, 32, 64),
        )
        for r in out["fleet_qos"]:
            print(r)
        print("\n== forecast A/B (seed-paired, admission on, saturation "
              "scenario) ==")
        out["forecast_ab"] = forecast_ab(
            caps=(8,) if args.smoke else (32, 64),
            duration_s=60.0 if args.smoke else 180.0,
            warmup_s=20.0 if args.smoke else 96.0,
        )
        for r in out["forecast_ab"]:
            print(r)
        if not args.smoke:
            bench_sections["qos"] = out["forecast_ab"]
    if run_all or args.storm:
        print("\n== failure storm A/B (correlated 2-node blast, seed-paired "
              "handling off/on) ==")
        out["failure_storm"] = failure_storm(
            cap=8 if args.smoke else 32,
            duration_s=40.0 if args.smoke else 60.0,
            blast_at_s=12.0 if args.smoke else 20.0,
        )
        for r in out["failure_storm"]:
            print(r)
        if not args.smoke:
            bench_sections["storm"] = out["failure_storm"]
    if run_all or args.chaos:
        print("\n== control-plane chaos A/B (crash/restart + RPC faults + "
              "telemetry corruption, seed-paired handling off/on) ==")
        out["chaos_ab"] = chaos_ab(
            cap=8 if args.smoke else 32,
            duration_s=30.0 if args.smoke else 120.0,
        )
        for r in out["chaos_ab"]:
            print(r)
        if not args.smoke:
            bench_sections["chaos"] = out["chaos_ab"]
    if run_all or args.thrash:
        print("\n== fixed-point thrash A/B (seed-paired high churn, "
              "commit gate off/on) ==")
        out["thrash_ab"] = thrash_ab(
            n_sessions=8 if args.smoke else 16,
            cycles=10 if args.smoke else 30,
        )
        for r in out["thrash_ab"]:
            print(r)
        if not args.smoke:
            bench_sections["thrash"] = out["thrash_ab"]
    if run_all or args.shards:
        print("\n== region-sharded cycle cost (fixed triggered set, "
              "128-session shards, sweep to 10,240 sessions) ==")
        out["shard_scaling"] = shard_scaling(
            shard_sessions=32 if args.smoke else 128,
            regions=(2, 4) if args.smoke else (8, 32, 80),
            cycles=5 if args.smoke else 12,
        )
        for r in out["shard_scaling"]:
            print(r)
        if not args.smoke:
            bench_sections["shards"] = out["shard_scaling"]
    if run_all or args.drift:
        print("\n== calibrated-vs-analytic pricing drift (committed "
              "BENCH_profiles.json) ==")
        out["pricing_drift"] = pricing_drift(
            n_sessions=8 if args.smoke else 32,
        )
        for r in out["pricing_drift"]:
            print(r)
        if not args.smoke:
            bench_sections["drift"] = out["pricing_drift"]
    # the tracked artifact carries the FULL sweeps only — a smoke run must
    # never overwrite the committed perf trajectory; sections not re-run
    # are carried over from the committed file (merge-on-write)
    if args.json and bench_sections:
        bench = pathlib.Path(__file__).resolve().parent.parent / "BENCH_fleet.json"
        write_bench_fleet(bench_sections, bench)
        print(f"wrote {bench}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
