"""Differential harness for the region-sharded fleet control plane (PR 10).

Three contracts pin the sharded system to the monolithic one:

1. **n_regions=1 bit-identity** — a single-region
   :class:`ShardedFleetOrchestrator` must be indistinguishable from a bare
   :class:`FleetOrchestrator` across a churny seed-paired run: identical
   prices, identical decisions, identical resident rows.  The wrapper
   delegates verbatim at one region; this suite makes that a contract, not
   an implementation accident.
2. **Session conservation** — across admits, departs, and cross-region
   migrations, every session lives in exactly one shard, its resident row
   lives in exactly that shard's buffers, and nothing is ever orphaned or
   double-placed (property-tested per ``_hypothesis_compat``).
3. **Steady-state dispatch shape** — with forecasting AND the calibrated
   cost-model provider on, a quiet sharded cycle costs exactly one pricing
   dispatch per shard (plus the one vmapped cross-shard screen) and stays
   pack-free.
"""

import numpy as np
import pytest

from repro.core import (
    CapacityForecaster,
    CapacityProfiler,
    CostWeights,
    ForecastConfig,
    InProcessAgent,
    ReconfigurationBroadcast,
    Thresholds,
    Workload,
)
from repro.core.fleet import FleetOrchestrator, ShardedFleetOrchestrator
from repro.core.graph import make_transformer_graph
from repro.core.profiling import CalibratedCostModel
from repro.core.triggers import QOS_BATCH, QOS_INTERACTIVE, QOS_STANDARD
from repro.edgesim import MECScenarioParams, base_system_state
from repro.edgesim.scenario import build_regional_orchestrator

from _hypothesis_compat import given, settings, st

_ROW_FIELDS = ("seg_flops", "seg_wbytes", "seg_priv", "seg_node",
               "valid", "xfer_bytes_tok", "n_segs", "t_in", "t_out",
               "lam", "source", "input_bytes_tok", "active")
_QOS = (QOS_INTERACTIVE, QOS_STANDARD, QOS_BATCH)


def _tiny_graph(layers: int = 8, name: str = "tiny") -> "object":
    return make_transformer_graph(
        name=name, num_layers=layers, d_model=256,
        flops_per_layer_token=4e9, weight_bytes_per_layer=3e8,
        embed_weight_bytes=1e8, head_weight_bytes=1e8,
        head_flops_token=2e8,
    )


_CATALOG = [("tiny-a", _tiny_graph(8, "tiny-a")),
            ("tiny-b", _tiny_graph(12, "tiny-b"))]


def _mono_orch(m: MECScenarioParams) -> FleetOrchestrator:
    state = base_system_state(m)
    return FleetOrchestrator(
        profiler=CapacityProfiler(base_state=state),
        broadcast=ReconfigurationBroadcast(
            [InProcessAgent(i) for i in range(state.num_nodes)]),
        thresholds=Thresholds(cooldown_s=10.0),
        weights=CostWeights(alpha=1.0, beta=0.02, gamma=1000.0),
    )


def _drive_churn(orch, *, cycles: int = 30, seed: int = 7):
    """One churny seed-paired schedule: admits, departs, background swings.

    Everything is drawn from ONE rng so two orchestrators driven with the
    same seed see the identical op sequence; returns the per-cycle
    (sids, lat, rho) price triples and FleetDecisions for comparison.
    """
    rng = np.random.default_rng(seed)
    prices, decisions = [], []
    base = orch.profiler.base_state
    for t in range(1, cycles + 1):
        # background swings across the whole util range → real trigger mix
        base.background_util[:] = rng.uniform(0.15, 0.9, base.num_nodes)
        base.background_util[3] = 0.10
        if rng.random() < 0.6 and len(orch.sessions) < 12:
            arch, g = _CATALOG[int(rng.integers(len(_CATALOG)))]
            wl = Workload(tokens_in=int(rng.integers(16, 64)),
                          tokens_out=int(rng.integers(4, 12)),
                          arrival_rate=float(rng.uniform(0.3, 1.5)))
            orch.admit(g, wl, source_node=int(rng.integers(0, 3)),
                       arch=arch, now=float(t),
                       qos=_QOS[int(rng.integers(len(_QOS)))])
        if rng.random() < 0.25 and orch.sessions:
            sids = sorted(orch.sessions)
            orch.depart(sids[int(rng.integers(len(sids)))])
        prices.append(orch.price_fleet(None, now=float(t)))
        decisions.append(orch.step(float(t)))
    return prices, decisions


def _buffer_rows(orch):
    """{sid: (field -> row array)} for every live resident row."""
    buf = orch._buffers if not isinstance(orch, ShardedFleetOrchestrator) \
        else orch.inners[0]._buffers
    out = {}
    for sid, row in buf.row_of.items():
        out[sid] = {f: np.asarray(getattr(buf, f))[row] for f in _ROW_FIELDS}
    return out


# --------------------------------------------------------------------------- #
# 1. n_regions=1 bit-identity
# --------------------------------------------------------------------------- #
def test_single_region_sharded_is_bit_identical_to_monolithic():
    m = MECScenarioParams()
    mono = _mono_orch(m)
    shard = build_regional_orchestrator(m, 1)
    assert shard.n_regions == 1

    p_mono, d_mono = _drive_churn(mono, cycles=30, seed=7)
    p_shard, d_shard = _drive_churn(shard, cycles=30, seed=7)

    for (s1, l1, r1), (s2, l2, r2) in zip(p_mono, p_shard):
        assert s1 == s2
        assert np.array_equal(np.asarray(l1), np.asarray(l2))
        assert np.array_equal(np.asarray(r1), np.asarray(r2))

    for a, b in zip(d_mono, d_shard):
        for f in ("n_keep", "n_migrate", "n_resplit", "n_cooldown",
                  "n_conflict_keep", "n_nogain_keep", "fixed_point_sweeps",
                  "fixed_point_aborts", "n_preempt"):
            assert getattr(a, f) == getattr(b, f), f
        assert sorted(a.per_session) == sorted(b.per_session)
        for sid in a.per_session:
            da, db = a.per_session[sid], b.per_session[sid]
            assert da.kind == db.kind
            if da.config is not None and db.config is not None:
                assert da.config.boundaries == db.config.boundaries
                assert da.config.assignment == db.config.assignment

    # resident rows bit-identical at the end of the run
    ra, rb = _buffer_rows(mono), _buffer_rows(shard)
    assert sorted(ra) == sorted(rb)
    for sid in ra:
        for f in _ROW_FIELDS:
            assert np.array_equal(ra[sid][f], rb[sid][f]), (sid, f)

    # the single-region wrapper never ran the screen machinery
    assert shard.screen_cycles == 0
    assert shard._shstate is None


def test_single_region_wrapper_shares_sid_sequence():
    m = MECScenarioParams()
    shard = build_regional_orchestrator(m, 1)
    g = _CATALOG[0][1]
    sid0 = shard.admit(g, Workload(32, 8, 0.5), source_node=0)
    sid1 = shard.admit(g, Workload(32, 8, 0.5), source_node=1)
    assert (sid0, sid1) == (0, 1)       # no region stride at S == 1


# --------------------------------------------------------------------------- #
# 2. session conservation under churn + cross-region migration
# --------------------------------------------------------------------------- #
def _assert_conserved(w, expected_alive: set):
    """Every live session in exactly one shard; rows mirror sessions."""
    seen = {}
    for r, o in enumerate(w.inners):
        for sid in o.sessions:
            assert sid not in seen, f"sid {sid} in regions {seen[sid]},{r}"
            seen[sid] = r
        if o._buffers is not None:
            assert set(o._buffers.row_of) == set(o.sessions)
            act = np.asarray(o._buffers.active)
            assert int(act.sum()) == len(o.sessions)
    assert set(seen) == expected_alive


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_sharded_churn_conserves_sessions(seed):
    rng = np.random.default_rng(seed)
    m = MECScenarioParams()
    w = build_regional_orchestrator(m, 3)
    alive: set = set()
    g = _CATALOG[0][1]
    for t in range(1, 15):
        op = rng.random()
        if op < 0.55 or not alive:
            src = int(rng.integers(0, 12))
            if src % 4 == 3:            # cloud nodes don't take ingress
                src -= 1
            sid = w.admit(g, Workload(tokens_in=24, tokens_out=6,
                                      arrival_rate=0.4),
                          source_node=src, now=float(t),
                          qos=_QOS[int(rng.integers(len(_QOS)))])
            alive.add(sid)
        elif op < 0.8:
            sid = sorted(alive)[int(rng.integers(len(alive)))]
            w.depart(sid)
            alive.discard(sid)
        else:
            w.step(float(t))
        _assert_conserved(w, alive)


def test_cross_region_migration_conserves_sessions_and_sids():
    m = MECScenarioParams()
    w = build_regional_orchestrator(m, 3)
    g = _CATALOG[0][1]
    alive = set()
    for r in (0, 1, 2):
        for i in range(3):
            alive.add(w.admit(
                g, Workload(tokens_in=48, tokens_out=8, arrival_rate=0.8),
                source_node=4 * r + i, now=0.0, qos=QOS_INTERACTIVE))
    w.step(1.0)
    _assert_conserved(w, alive)
    before = {sid: w.region_of_sid(sid) for sid in alive}
    # saturate region 1's MEC nodes: its sessions breach and the aggregator
    # must move some of them into the idle regions — sids preserved
    w.inners[1].profiler.base_state.background_util[:3] = 0.97
    for t in range(2, 30):
        w.step(float(t))
        _assert_conserved(w, alive)
        if w.cross_migrations:
            break
    assert w.cross_migrations > 0
    moved = [sid for sid in alive if w.region_of_sid(sid) != before[sid]]
    assert moved, "expected at least one session to change region"
    for sid in moved:
        assert sid in w.sessions          # same sid, new region
        assert w.region_of_sid(sid) != 1  # fled the saturated region


# --------------------------------------------------------------------------- #
# 3. steady-state dispatch shape with forecast + calibration ON
# --------------------------------------------------------------------------- #
def test_steady_state_one_dispatch_per_shard_pack_free():
    m = MECScenarioParams()
    w = build_regional_orchestrator(m, 3, cost_model=CalibratedCostModel())
    w.forecaster = CapacityForecaster(ForecastConfig(
        horizon_steps=4, season_steps=8, sample_interval_s=1.0))
    assert all(o.forecaster is not None for o in w.inners)
    g = _CATALOG[0][1]
    for r in (0, 1, 2):
        for i in range(2):
            w.admit(g, Workload(tokens_in=24, tokens_out=6,
                                arrival_rate=0.3),
                    source_node=4 * r + i, now=0.0, qos=QOS_BATCH)
    for t in range(1, 4):                 # warm up: compile + settle shapes
        w.step(float(t))
    disp0 = [o.kernel.dispatches for o in w.inners]
    packs0 = [dict(o._buffers.stats) for o in w.inners]
    screens0 = w._shstate.screen_dispatches
    rebuilds0 = [o.full_rebuilds for o in w.inners]
    cycles = 5
    for t in range(4, 4 + cycles):
        d = w.step(float(t))
        assert d.n_migrate == 0 and d.n_resplit == 0
    for r, o in enumerate(w.inners):
        # forecast ON → every shard prices every cycle: EXACTLY one fused
        # dispatch per shard per cycle, nothing else
        assert o.kernel.dispatches - disp0[r] == cycles
        st_ = o._buffers.stats
        assert st_["pack_time_s"] == packs0[r]["pack_time_s"]
        assert st_["row_writes"] == packs0[r]["row_writes"]
        assert st_["rebuilds"] == packs0[r]["rebuilds"]
        assert o.full_rebuilds == rebuilds0[r]
    assert w._shstate.screen_dispatches - screens0 == cycles
