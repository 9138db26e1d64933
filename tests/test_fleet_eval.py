"""Batched fleet evaluation: the jitted (B, K) evaluator ≡ per-session
numpy `chain_latency`/`evaluate`; the vmapped migration DP ≡ the per-session
placement chain DP; the batched monitoring hot path runs zero Python local
search."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    BatchedMigrationSolver,
    FleetCostEvaluator,
    FleetOrchestrator,
    InProcessAgent,
    ReconfigurationBroadcast,
    SystemState,
    Thresholds,
    Workload,
    chain_latency,
    evaluate,
    pack_sessions,
    packed_induced_loads,
    solve_placement_chain_dp,
    surrogate_cost,
)
from repro.core.broadcast import PartitionConfig
from repro.core.fleet import FleetSession, session_induced_loads
from repro.core.graph import GraphNode, ModelGraph
from repro.core.profiling import CapacityProfiler

N_NODES = 4


def _random_state(seed, n=N_NODES):
    rng = np.random.default_rng(seed)
    bw = rng.uniform(1e6, 1e8, (n, n))
    bw = (bw + bw.T) / 2
    np.fill_diagonal(bw, np.inf)
    trusted = rng.random(n) < 0.6
    trusted[0] = True
    return SystemState(
        flops_per_s=rng.uniform(1e12, 1e14, n),
        mem_bytes=rng.uniform(5e8, 5e9, n),
        background_util=rng.uniform(0.0, 0.8, n),
        trusted=trusted,
        link_bw=bw,
        link_lat=np.full((n, n), 4e-3) * (1 - np.eye(n)),
        mem_bw=rng.uniform(1e11, 2e12, n),
    )


def _random_items(rng, n_sessions, n=N_NODES):
    """(graph, boundaries, assignment, workload, source, ibt) per session."""
    items = []
    for _ in range(n_sessions):
        L = int(rng.integers(3, 9))
        g = ModelGraph("g", [
            GraphNode(f"u{i}", float(rng.uniform(1e8, 2e9)),
                      float(rng.uniform(1e7, 5e8)),
                      float(rng.uniform(1e3, 2e4)),
                      privacy_critical=bool(rng.random() < 0.3))
            for i in range(L)
        ])
        wl = Workload(tokens_in=int(rng.integers(8, 128)),
                      tokens_out=int(rng.integers(1, 32)),
                      arrival_rate=float(rng.uniform(0.1, 8.0)))
        k = int(rng.integers(1, min(4, L) + 1))
        cuts = sorted(rng.choice(np.arange(1, L), size=k - 1,
                                 replace=False).tolist())
        b = tuple([0] + cuts + [L])
        a = tuple(int(x) for x in rng.integers(0, n, len(b) - 1))
        items.append((g, b, a, wl, int(rng.integers(0, n)), 4.0))
    return items


def _per_session_states(rng, state, B, n=N_NODES):
    """Per-session effective (bg, link_bw, mem) perturbations."""
    bg = np.clip(np.stack([
        state.background_util + rng.uniform(0, 0.15, n) for _ in range(B)
    ]), 0, 0.99)
    lbw = np.stack([state.link_bw * rng.uniform(0.4, 1.0) for _ in range(B)])
    for i in range(B):
        np.fill_diagonal(lbw[i], np.inf)
    mem = np.stack([state.mem_bytes * rng.uniform(0.5, 1.0) for _ in range(B)])
    return bg, lbw, mem


def _ref_state(state, bg, lbw, mem):
    st = state.copy()
    st.background_util = bg.copy()
    st.link_bw = lbw.copy()
    st.mem_bytes = mem.copy()
    return st


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_batched_evaluator_matches_scalar_cost_model(seed):
    """One jitted call ≡ per-session chain_latency AND evaluate (float64)."""
    rng = np.random.default_rng(seed)
    state = _random_state(seed)
    items = _random_items(rng, 6)
    packed = pack_sessions(items)
    bg, lbw, mem = _per_session_states(rng, state, packed.batch)
    lat, tot, rho = FleetCostEvaluator().evaluate_batch(
        packed, bg=bg, link_bw=lbw, mem_bytes=mem, state=state,
    )
    for i, (g, b, a, wl, _, _) in enumerate(items):
        st = _ref_state(state, bg[i], lbw[i], mem[i])
        assert lat[i] == pytest.approx(chain_latency(g, b, a, st, wl),
                                       rel=1e-9)
        assert tot[i] == pytest.approx(evaluate(g, b, a, st, wl), rel=1e-9)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_batched_migration_dp_matches_per_session(seed):
    """Vmapped masked placement DP ≡ numpy solve_placement_chain_dp on the
    additive surrogate, with per-session effective states."""
    rng = np.random.default_rng(seed)
    state = _random_state(seed + 1)
    items = _random_items(rng, 5)
    packed = pack_sessions(items)
    bg, lbw, _ = _per_session_states(rng, state, packed.batch)
    sols = BatchedMigrationSolver().solve_batch(
        packed, bg=bg, link_bw=lbw, state=state,
    )
    for i, (g, b, _, wl, src, _) in enumerate(items):
        st = _ref_state(state, bg[i], lbw[i], state.mem_bytes)
        ref = solve_placement_chain_dp(g, b, st, wl, source_node=src)
        sc = surrogate_cost(g, sols[i].boundaries, sols[i].assignment, st, wl,
                            source_node=src)
        sc_ref = surrogate_cost(g, ref.boundaries, ref.assignment, st, wl,
                                source_node=src)
        assert sols[i].boundaries == b
        assert sc == pytest.approx(sc_ref, rel=1e-9)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_device_surrogate_expansion_matches_host_reference(seed):
    """The on-device Eq. 7 surrogate expansion (_surrogate_batch — what the
    batched solvers/repairer/fused migrate now run, expanding the
    (B, K, n, n) transfer tensor from xfer_bytes_tok inside the dispatch)
    reproduces the pinned host reference _surrogate_inputs, with and
    without the Eq. 4 memory mask."""
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.core.fleet_eval import _BIG, _surrogate_batch, _surrogate_inputs

    rng = np.random.default_rng(seed)
    state = _random_state(seed + 3)
    B = int(rng.integers(1, 6))
    packed = pack_sessions(_random_items(rng, B))
    bg, lbw, mem = _per_session_states(rng, state, B)
    n = state.num_nodes

    for mem_arg in (None, mem):
        host = _surrogate_inputs(
            packed, bg=bg, link_bw=lbw, state=state, mem=mem_arg
        )
        with enable_x64(True):
            dev = _surrogate_batch(
                jnp.asarray(packed.seg_flops), jnp.asarray(packed.seg_wbytes),
                jnp.asarray(packed.seg_priv),
                jnp.asarray(packed.xfer_bytes_tok),
                jnp.asarray(packed.t_in), jnp.asarray(packed.t_out),
                jnp.asarray(packed.lam), jnp.asarray(packed.source),
                jnp.asarray(packed.input_bytes_tok),
                jnp.asarray(bg),
                jnp.asarray(np.nan_to_num(lbw, posinf=_BIG)),
                jnp.asarray(np.nan_to_num(state.link_lat, posinf=_BIG)),
                jnp.asarray(state.flops_per_s), jnp.asarray(state.mem_bw),
                jnp.asarray(state.trusted.astype(bool)),
                None if mem_arg is None else jnp.asarray(mem_arg),
                n,
            )
        for name, h, d in zip(("exec_cost", "xfer", "src_xfer"), host, dev):
            np.testing.assert_allclose(
                np.asarray(d), h, rtol=1e-12, atol=0.0, err_msg=name
            )


def test_packed_induced_loads_match_per_session():
    rng = np.random.default_rng(2)
    state = _random_state(2)
    items = _random_items(rng, 6)
    packed = pack_sessions(items)
    node_r, link_r, wb = packed_induced_loads(packed, state)
    for i, (g, b, a, wl, src, _) in enumerate(items):
        sess = FleetSession(sid=i, graph=g, workload=wl, source_node=src,
                            config=PartitionConfig(1, b, a))
        r_n, r_l, r_w = session_induced_loads(sess, state)
        np.testing.assert_allclose(node_r[i], r_n, rtol=1e-12)
        np.testing.assert_allclose(link_r[i], r_l, rtol=1e-12)
        np.testing.assert_allclose(wb[i], r_w, rtol=1e-12)


def test_evaluator_pow2_padding_bounds_compiles():
    """5, 6, 7, 8 sessions share one compiled (8, K, n) program."""
    rng = np.random.default_rng(3)
    state = _random_state(3)
    ev = FleetCostEvaluator()
    for B in (5, 6, 7, 8):
        items = _random_items(rng, B)
        # fix K by reusing 4-unit graphs only
        items = [(g, (0, len(g)), (0,), wl, s, ibt)
                 for (g, _, _, wl, s, ibt) in items]
        packed = pack_sessions(items, min_k=4)
        bg, lbw, mem = _per_session_states(rng, state, packed.batch)
        ev.evaluate_batch(packed, bg=bg, link_bw=lbw, mem_bytes=mem,
                          state=state)
    assert len(ev._compiled) == 1


def _hot_fleet(n_sessions=6, seed=0):
    rng = np.random.default_rng(seed)
    n = N_NODES
    bw = np.full((n, n), 2e7)
    np.fill_diagonal(bw, np.inf)
    state = SystemState(
        flops_per_s=np.full(n, 5e12),
        mem_bytes=np.full(n, 40e9),
        background_util=np.full(n, 0.6),
        trusted=np.array([True] * (n - 1) + [False]),
        link_bw=bw,
        link_lat=np.full((n, n), 2e-3) * (1 - np.eye(n)),
        mem_bw=np.full(n, 2e11),
    )
    orch = FleetOrchestrator(
        profiler=CapacityProfiler(base_state=state),
        broadcast=ReconfigurationBroadcast(
            [InProcessAgent(i) for i in range(n)]
        ),
        thresholds=Thresholds(cooldown_s=0.5),
        solve_backoff_s=0.0,
    )
    g = ModelGraph("m", [
        GraphNode(f"u{i}", 5e10, 5e8, 8e4, privacy_critical=(i == 0))
        for i in range(8)
    ])
    for _ in range(n_sessions):
        orch.admit(g, Workload(64, 16, float(rng.uniform(2.0, 4.0))),
                   source_node=int(rng.integers(0, 3)), now=0.0)
    return orch


def test_batched_step_runs_no_python_local_search(monkeypatch):
    """The batched monitoring cycle must never enter the Python Φ local
    search — migrations and re-splits are priced entirely by batched JAX."""
    import repro.core.fleet as fleet_mod

    orch = _hot_fleet()

    def _banned(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("local_search invoked on the batched hot path")

    monkeypatch.setattr(fleet_mod, "local_search", _banned)
    for t in range(4):
        fd = orch.step(now=float(t))
        total = fd.n_keep + fd.n_migrate + fd.n_resplit + fd.n_cooldown
        assert total == len(orch.sessions)
    # the hot fleet must actually have exercised the decision path
    assert any(
        fd.n_migrate + fd.n_resplit + fd.n_cooldown > 0
        for fd in orch.decisions
    )


def test_resident_step_preserves_invariants_vs_cold_repack():
    """Incremental resident buffers and a repack-every-cycle fleet keep
    identical config invariants (privacy, boundary validity) on the same
    fleet (full decision equivalence lives in test_resident_state.py)."""
    for cold_repack in (False, True):
        orch = _hot_fleet(seed=1)
        for t in range(4):
            if cold_repack:
                orch.invalidate_resident_state()
            orch.step(now=float(t))
        for sess in orch.sessions.values():
            b, a = sess.config.boundaries, sess.config.assignment
            assert b[0] == 0 and b[-1] == len(sess.graph)
            assert len(a) == len(b) - 1
            st = orch.profiler.base_state
            for j, (lo, hi) in enumerate(zip(b[:-1], b[1:])):
                if sess.graph.segment_has_private(lo, hi):
                    assert st.trusted[a[j]]


def test_batched_step_migrations_respect_memory():
    """The migration DP prices a memory-blind surrogate; the commit-time
    guard must keep every node within capacity anyway (24 GB sessions on
    40 GB nodes: two residents never fit one node)."""
    n = N_NODES
    rng = np.random.default_rng(4)
    bw = np.full((n, n), 1e8)
    np.fill_diagonal(bw, np.inf)
    state = SystemState(
        flops_per_s=np.full(n, 5e12),
        mem_bytes=np.full(n, 40e9),
        background_util=np.full(n, 0.55),
        trusted=np.full(n, True),
        link_bw=bw,
        link_lat=np.full((n, n), 2e-3) * (1 - np.eye(n)),
        mem_bw=np.full(n, 2e11),
    )
    orch = FleetOrchestrator(
        profiler=CapacityProfiler(base_state=state),
        broadcast=ReconfigurationBroadcast(
            [InProcessAgent(i) for i in range(n)]
        ),
        thresholds=Thresholds(cooldown_s=0.0),
        solve_backoff_s=0.0,
    )
    g = ModelGraph("heavy", [
        GraphNode(f"u{i}", 2e10, 3e9, 8e4) for i in range(8)  # 24 GB weights
    ])
    for k in range(4):
        orch.admit(g, Workload(64, 16, float(rng.uniform(2.0, 4.0))),
                   source_node=k % 3, now=0.0)
    for t in range(5):
        orch.step(now=float(t))
        used = np.zeros(n)
        for s in orch.sessions.values():
            b, a = s.config.boundaries, s.config.assignment
            for j, (lo, hi) in enumerate(zip(b[:-1], b[1:])):
                used[a[j]] += s.graph.segment_weight_bytes(lo, hi)
        assert (used <= state.mem_bytes + 1e6).all(), (t, used / 1e9)
