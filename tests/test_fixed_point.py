"""Device red/black fixed point vs the scalar sequential-commit oracle.

Property coverage for PR 9 (ROADMAP open item 5):

* the jitted device program is BIT-IDENTICAL to the pinned numpy
  reference on randomized triggered sets (integer assignments exact,
  latencies to float tolerance),
* the loop converges within the sweep budget and is idempotent (running
  it again from its own fixed point moves nothing),
* the final joint Eq. 4 guard never commits an assignment with more
  total memory overflow than the cycle-start one,
* the orchestrator's steady state stays one-dispatch and pack-free with
  forecasting + calibration ON, and a churning fleet on the fixed-point
  path commits with zero conflict-KEEPs.
"""

import numpy as np
import pytest

from repro.core import (
    CalibratedCostModel,
    CapacityProfiler,
    FleetOrchestrator,
    GraphNode,
    InProcessAgent,
    ModelGraph,
    ModelProfile,
    ReconfigurationBroadcast,
    SegmentProfile,
    SegmentProfileEntry,
    SystemState,
    Thresholds,
    Workload,
    fixed_point_reference,
)
from repro.core.fleet_eval import _BIG, _make_fixed_point

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import enable_x64  # noqa: E402


# --------------------------------------------------------------------- #
# randomized raw instances (B rows packed to K segments over n nodes)
# --------------------------------------------------------------------- #
def _instance(seed, B=8, K=4, n=4, tight=False):
    rng = np.random.default_rng(seed)
    n_segs = rng.integers(1, K + 1, size=B)
    valid = np.arange(K)[None, :] < n_segs[:, None]
    seg_flops = rng.uniform(1e9, 8e10, (B, K)) * valid
    seg_w = rng.uniform(2e8, 2e9, (B, K)) * valid
    seg_priv = (rng.random((B, K)) < 0.15) & valid
    seg_node0 = rng.integers(0, n, (B, K)) * valid
    xbytes = rng.uniform(1e4, 5e5, (B, K)) * valid
    active = rng.random(B) < 0.9
    active[0] = True                      # at least one live row
    trig = (rng.random(B) < 0.7) & active
    force = (rng.random(B) < 0.15) & trig
    slo = rng.uniform(0.05, 0.4, B)
    bg = rng.uniform(0.05, 0.45, n)
    bw = rng.uniform(5e7, 5e8, (n, n))
    bw = (bw + bw.T) / 2
    np.fill_diagonal(bw, _BIG)            # same-node hop is free
    link_lat = np.full((n, n), 2e-3) * (1 - np.eye(n))
    trusted = rng.random(n) < 0.8
    trusted[0] = True                     # privacy always satisfiable
    per_node = seg_w[valid].sum() / n
    mem = rng.uniform(1.2 if tight else 2.5, 1.8 if tight else 4.0, n)
    mem_bytes = mem * per_node
    return dict(
        seg_flops=seg_flops, seg_w=seg_w, seg_priv=seg_priv,
        seg_node0=seg_node0.astype(np.int64), valid=valid, xbytes=xbytes,
        n_segs=n_segs.astype(np.int64),
        t_in=rng.uniform(16, 64, B), t_out=rng.uniform(4, 16, B),
        lam=rng.uniform(0.5, 4.0, B),
        source=rng.integers(0, n, B).astype(np.int64),
        input_bytes_tok=np.full(B, 4.0),
        active=active, trig=trig, force=force, slo=slo,
        base_bg=bg, base_lbw=bw, link_bw=bw, link_lat=link_lat,
        flops_per_s=rng.uniform(5e12, 3e13, n),
        mem_bw=np.full(n, 1e12), trusted=trusted, mem_bytes=mem_bytes,
    )


_ORDER = [
    "seg_flops", "seg_w", "seg_priv", "seg_node0", "valid", "xbytes",
    "n_segs", "t_in", "t_out", "lam", "source", "input_bytes_tok",
    "active", "trig", "force", "slo", "base_bg", "base_lbw", "link_bw",
    "link_lat", "flops_per_s", "mem_bw", "trusted", "mem_bytes",
]


def _run_device(inst, K=4, n=4, max_sweeps=8):
    with enable_x64(True):
        fn = jax.jit(_make_fixed_point(
            K, n, 1.0, 0.05, 1000.0, 1e3, 0.05, 0.10, max_sweeps,
        ))
        out = fn(*[jnp.asarray(inst[k]) for k in _ORDER])
        return [np.asarray(o) for o in out]


def _run_reference(inst, max_sweeps=8):
    return fixed_point_reference(
        *[inst[k] for k in _ORDER], alpha=1.0, beta=0.05, gamma=1000.0,
        mem_penalty=1e3, bw_floor=0.05, imp_frac=0.10,
        max_sweeps=max_sweeps,
    )


# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("tight", [False, True])
def test_device_bit_identical_to_scalar_oracle(seed, tight):
    """Integer joint assignments match the sequential oracle EXACTLY."""
    inst = _instance(seed, tight=tight)
    a_d, lat_d, sw_d, moved_d, mpre_d, ab_d = _run_device(inst)[:6]
    a_r, lat_r, sw_r, moved_r, mpre_r, ab_r = _run_reference(inst)
    np.testing.assert_array_equal(a_d, a_r)
    np.testing.assert_array_equal(moved_d, moved_r)
    np.testing.assert_array_equal(mpre_d, mpre_r)
    assert int(sw_d) == int(sw_r)
    assert bool(ab_d) == bool(ab_r)
    live = inst["active"]
    np.testing.assert_allclose(lat_d[live], lat_r[live], rtol=1e-9)


def test_converges_within_budget_and_is_idempotent():
    inst = _instance(42)
    a, _, sweeps, moved, _, _ = _run_device(inst)[:6]
    assert int(sweeps) <= 8
    # a second pass FROM the fixed point finds nothing left to move
    inst2 = dict(inst, seg_node0=(a * inst["valid"]).astype(np.int64))
    _, _, _, moved2, mpre2, _ = _run_device(inst2)[:6]
    assert not moved2.any()
    assert not mpre2.any()


@pytest.mark.parametrize("seed", range(8))
def test_never_commits_worse_joint_overflow(seed):
    """The final guard: total Eq. 4 overflow never exceeds cycle-start."""
    inst = _instance(seed, tight=True)
    a, *_ = _run_device(inst)

    def overflow(assign):
        used = np.zeros(len(inst["mem_bytes"]))
        av = inst["valid"] & inst["active"][:, None]
        np.add.at(used, assign[av], inst["seg_w"][av])
        return np.maximum(0.0, used - inst["mem_bytes"]).sum()

    assert overflow(a.astype(int)) <= overflow(inst["seg_node0"]) + 1e-6


def test_unmoved_rows_keep_incumbent_assignment():
    inst = _instance(5)
    a, _, _, moved, _, _ = _run_device(inst)[:6]
    same = (a == inst["seg_node0"]) | ~inst["valid"]
    for b in range(len(moved)):
        if not moved[b]:
            assert same[b].all()


# --------------------------------------------------------------------- #
# orchestrator-level invariants
# --------------------------------------------------------------------- #
def _m_graph():
    return ModelGraph("m", [
        GraphNode(f"u{i}", 5e9, 5e8, 8e3, privacy_critical=(i == 0))
        for i in range(8)
    ])


def _calibration_for_m():
    """Real (non-identity) calibration: measured times 1.5x analytic."""
    g = _m_graph()
    segs = []
    for i in range(len(g)):
        ab = g.boundary_act_bytes(i + 1) if i + 1 < len(g) else 0.0
        segs.append(SegmentProfileEntry(
            lo=i, hi=i + 1, step_time_s=1.5e-3, analytic_time_s=1e-3,
            boundary_bytes_tok=ab, analytic_boundary_bytes_tok=ab,
        ))
    return CalibratedCostModel(SegmentProfile({"m": ModelProfile(
        arch="m", family="test", graph_units=len(g), batch=2, tokens=32,
        compressed_transfer=False, segments=tuple(segs),
    )}))


def _fleet(n_nodes=4, forecast=True, calibrated=True):
    rng = np.random.default_rng(0)
    bw = np.full((n_nodes, n_nodes), 1e8)
    np.fill_diagonal(bw, np.inf)
    state = SystemState(
        flops_per_s=np.full(n_nodes, 2e13),
        mem_bytes=np.full(n_nodes, 40e9),
        background_util=rng.uniform(0.1, 0.4, n_nodes),
        trusted=np.array([True] * (n_nodes - 1) + [False]),
        link_bw=bw,
        link_lat=np.full((n_nodes, n_nodes), 2e-3) * (1 - np.eye(n_nodes)),
        mem_bw=np.full(n_nodes, 1.0e12),
    )
    kw = {}
    if forecast:
        from repro.core import CapacityForecaster, ForecastConfig

        kw["forecaster"] = CapacityForecaster(
            ForecastConfig(horizon_steps=4, season_steps=8)
        )
    if calibrated:
        kw["cost_model"] = _calibration_for_m()
    orch = FleetOrchestrator(
        profiler=CapacityProfiler(base_state=state),
        broadcast=ReconfigurationBroadcast(
            [InProcessAgent(i) for i in range(n_nodes)]
        ),
        thresholds=Thresholds(cooldown_s=0.5),
        **kw,
    )
    assert orch.use_fixed_point
    return orch, state


def test_steady_state_stays_one_dispatch_and_pack_free():
    """Forecast + calibration ON: warm steady cycles never re-pack rows,
    never dispatch the repair pass, and report zero conflict-KEEPs."""
    orch, _ = _fleet()
    g = _m_graph()
    rng = np.random.default_rng(1)
    for _ in range(4):
        orch.admit(g, Workload(32, 8, float(rng.uniform(0.5, 1.5))),
                   source_node=0, now=0.0)
    for t in range(3):                      # warm-up / settle
        orch.step(now=float(t))
    rep0 = orch.repairer.dispatches
    for t in range(3, 8):                   # steady state
        fd = orch.step(now=float(t))
        assert fd.pack_time_s == 0.0
        assert fd.n_migrate == 0 and fd.n_resplit == 0
        assert fd.n_conflict_keep == 0
    assert orch.repairer.dispatches == rep0


def test_churn_on_fixed_point_path_has_zero_conflict_keeps():
    """High-churn admit/depart cycle: the fixed point retires the
    conflict-KEEP re-check entirely (the --thrash ON-arm gate)."""
    orch, state = _fleet(forecast=False, calibrated=False)
    g = ModelGraph("m", [
        GraphNode(f"u{i}", 2e10, 2e9, 8e3) for i in range(8)
    ])
    rng = np.random.default_rng(9)
    sids = [
        orch.admit(g, Workload(48, 12, float(rng.uniform(1.0, 3.0))),
                   source_node=int(rng.integers(0, 3)), now=0.0)
        for _ in range(6)
    ]
    for t in range(10):
        fd = orch.step(now=float(t))
        assert fd.n_conflict_keep == 0
        assert fd.fixed_point_aborts == 0
        # churn: rotate one session out, one in
        if t % 2 == 0 and sids:
            orch.depart(sids.pop(0))
            sids.append(orch.admit(
                g, Workload(48, 12, float(rng.uniform(1.0, 3.0))),
                source_node=int(rng.integers(0, 3)), now=float(t),
            ))
        # every live config stays Eq. 4-feasible after each cycle
        used = np.zeros(state.num_nodes)
        for s in orch.sessions.values():
            for seg_w, node in zip(
                [sum(u.weight_bytes for u in s.graph.nodes[lo:hi])
                 for lo, hi in zip(s.config.boundaries[:-1],
                                   s.config.boundaries[1:])],
                s.config.assignment,
            ):
                used[node] += seg_w
        assert (used <= state.mem_bytes + 1e-6).all()
