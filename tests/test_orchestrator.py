"""Alg. 1 semantics: triggers, cool-down, hysteresis, 2-phase broadcast."""

from repro.core import (
    AdaptiveOrchestrator,
    CapacityProfiler,
    DecisionKind,
    EWMA,
    InProcessAgent,
    ReconfigurationBroadcast,
    SplitRevision,
    Thresholds,
    TriggerState,
    Workload,
    should_reconfigure,
)
from repro.edgesim import MECScenarioParams, base_system_state, llama3_8b_graph


def test_triggers_fire_on_any_condition():
    th = Thresholds()
    ok = TriggerState(0.05, 0.5, 100e6 / 8)
    assert not should_reconfigure(ok, th)
    for bad in [TriggerState(0.2, 0.5, 100e6 / 8),
                TriggerState(0.05, 0.9, 100e6 / 8),
                TriggerState(0.05, 0.5, 10e6 / 8)]:
        assert should_reconfigure(bad, th)
        assert bad.reasons


def test_ewma():
    e = EWMA(0.5)
    assert e.update(1.0) == 1.0
    assert e.update(0.0) == 0.5
    assert e.get() == 0.5


def _orchestrator(backhaul=20.0):
    graph = llama3_8b_graph()
    state = base_system_state(MECScenarioParams(backhaul_mbps=backhaul))
    wl = Workload(56, 8, 4.0)
    profiler = CapacityProfiler(base_state=state)
    agents = [InProcessAgent(i) for i in range(state.num_nodes)]
    orch = AdaptiveOrchestrator(
        graph=graph, profiler=profiler,
        broadcast=ReconfigurationBroadcast(agents), workload=wl,
        thresholds=Thresholds(), splitter=SplitRevision())
    orch.deploy_initial((0, 5, 29, 34), (0, 3, 0))
    return orch, profiler, agents


def test_keep_when_no_trigger():
    orch, profiler, _ = _orchestrator(backhaul=200.0)
    profiler.observe_latency(0.05)
    d = orch.step(now=100.0)
    assert d.kind == DecisionKind.KEEP


def test_reconfigures_on_latency_and_respects_cooldown():
    orch, profiler, _ = _orchestrator(backhaul=20.0)
    profiler.observe_latency(0.5)
    d1 = orch.step(now=100.0)
    assert d1.kind in (DecisionKind.MIGRATE, DecisionKind.RESPLIT)
    v1 = orch.current.version
    # still bad, but inside the cool-down window -> no new rollout
    profiler.observe_latency(0.5)
    d2 = orch.step(now=110.0)
    assert d2.kind in (DecisionKind.COOLDOWN, DecisionKind.KEEP)
    assert orch.current.version == v1


def test_privacy_respected_after_reconfig():
    orch, profiler, _ = _orchestrator(backhaul=20.0)
    profiler.observe_latency(0.5)
    orch.step(now=100.0)
    cfg = orch.current
    g = orch.graph
    state = profiler.system_state()
    for j, (lo, hi) in enumerate(zip(cfg.boundaries[:-1], cfg.boundaries[1:])):
        if g.segment_has_private(lo, hi):
            assert state.trusted[cfg.assignment[j]]


def test_broadcast_two_phase_abort_on_prepare_failure():
    agents = [InProcessAgent(0), InProcessAgent(1, fail_prepare=True)]
    rb = ReconfigurationBroadcast(agents)
    ok = rb.rollout((0, 2, 4), (0, 0))          # node 1 unused -> commits
    assert ok is not None
    bad = rb.rollout((0, 2, 4), (0, 1))         # node 1 must prepare -> abort
    assert bad is None
    assert agents[0].staged is None             # rolled back
    assert rb.active_version == ok.version      # old config still active


def test_broadcast_commit_failure_rolls_back():
    agents = [InProcessAgent(0), InProcessAgent(1, fail_commit=True)]
    rb = ReconfigurationBroadcast(agents)
    out = rb.rollout((0, 2, 4), (0, 1))
    assert out is None
    assert rb.active_version == 0


def test_segments_for_node():
    agents = [InProcessAgent(i) for i in range(3)]
    rb = ReconfigurationBroadcast(agents)
    cfg = rb.rollout((0, 2, 5, 9), (0, 2, 0))
    assert cfg.segments_for(0) == [(0, 2), (5, 9)]
    assert cfg.segments_for(2) == [(2, 5)]
    assert cfg.segments_for(1) == []


def test_warmup_is_dp_only(monkeypatch):
    """Deploy-time warmup compiles the jitted DP WITHOUT running the Python
    Φ local search (whose result a warmup would throw away anyway)."""
    import repro.core.splitter as splitter_mod

    calls = {"local_search": 0}
    real = splitter_mod.local_search

    def counting(*a, **k):
        calls["local_search"] += 1
        return real(*a, **k)

    monkeypatch.setattr(splitter_mod, "local_search", counting)
    state = base_system_state(MECScenarioParams())
    graph = llama3_8b_graph()
    wl = Workload(tokens_in=32, tokens_out=8, arrival_rate=2.0)
    sr = SplitRevision()
    sr.warmup(graph, state, wl, source_node=0)
    assert calls["local_search"] == 0
    # the warm compile covers the shape the first real revision hits: the
    # revise() below reuses the cached program (and DOES refine with Φ)
    assert len(sr._jax_dp._compiled) == 1
    sr.revise(graph, state, wl, source_node=0)
    assert calls["local_search"] == 1
    assert len(sr._jax_dp._compiled) == 1


def test_decision_log_is_capped_and_counts_every_kind(monkeypatch):
    """The log keeps the most recent ``DECISION_LOG`` decisions; the counts
    by kind cover every cycle since deployment."""
    from repro.core import orchestrator as orch_mod

    monkeypatch.setattr(orch_mod, "DECISION_LOG", 4)
    orch, profiler, _ = _orchestrator(backhaul=20.0)
    kinds = []
    for t in range(10):
        profiler.observe_latency(0.5 if t < 3 else 0.05)
        kinds.append(orch.step(now=100.0 + t).kind)
    assert [d.kind for d in orch.decisions] == kinds[-4:]
    assert orch.decision_counts == {k.value: kinds.count(k) for k in DecisionKind}
    assert sum(orch.decision_counts.values()) == 10
    assert orch.decision_counts["cooldown"] >= 1     # re-split, then cool-down
