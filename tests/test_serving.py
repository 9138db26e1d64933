"""Serving semantics: split == monolith; prefill+decode == full forward;
transport compression accounting; wave batching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import ALL_ARCHS, get_bundle
from repro.serving import (
    ActivationTransport,
    Request,
    SegmentChain,
    SegmentRunner,
    SplitInferenceEngine,
    WaveBatcher,
    split_params,
)
from repro.core.broadcast import PartitionConfig

_KEY = jax.random.PRNGKey(7)


def _bundle_params(arch):
    b = get_bundle(arch, reduced=True)
    params = b.init(_KEY, jnp.float32)
    return b, params


from conftest import tier1_subset


# tier-1 keeps a split==monolith canary of each family's segment walk (the
# dense transformer, the one with a dense lead block, mamba2 and griffin);
# the rest of the sweep rides the slow marker
@pytest.mark.parametrize("arch", tier1_subset(
    ["llama3-8b", "gemma2-9b", "mamba2-1.3b", "recurrentgemma-9b",
     "qwen3-moe-30b-a3b", "deepseek-v2-lite-16b", "musicgen-medium"],
    keep=("llama3-8b", "mamba2-1.3b", "recurrentgemma-9b",
          "deepseek-v2-lite-16b")))
def test_split_chain_equals_monolith(arch):
    b, params = _bundle_params(arch)
    L = len(b.model_graph())
    toks = jax.random.randint(_KEY, (2, 24), 0, b.cfg.vocab)
    mono = SegmentChain(b, params, (0, L))(toks)
    candidates = [(0, 1, L), (0, L // 2, L), (0, 1, L - 1, L),
                  (0, 2, 3, L - 1, L)]
    for bounds in candidates:
        bounds = tuple(sorted(set(min(max(x, 0), L) for x in bounds)))
        if len(bounds) < 2 or bounds[0] != 0 or bounds[-1] != L:
            continue
        split = SegmentChain(b, params, bounds)(toks)
        err = float(jnp.max(jnp.abs(mono - split)))
        assert err < 1e-4, (bounds, err)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(cuts=st.sets(st.integers(1, 3), max_size=2))
def test_split_equivalence_random_cuts(cuts):
    b, params = _bundle_params("llama3-8b")
    L = len(b.model_graph())
    bounds = tuple([0] + sorted(cuts) + [L])
    toks = jax.random.randint(_KEY, (1, 12), 0, b.cfg.vocab)
    mono = SegmentChain(b, params, (0, L))(toks)
    split = SegmentChain(b, params, bounds)(toks)
    assert float(jnp.max(jnp.abs(mono - split))) < 1e-4


@pytest.mark.parametrize("arch", tier1_subset(ALL_ARCHS, keep=("stablelm-3b",)))
def test_prefill_decode_matches_full_forward(arch):
    b, params = _bundle_params(arch)
    cfg = b.cfg
    B, S = 2, 33
    prefix = getattr(cfg, "prefix_tokens", 0)
    toks = jax.random.randint(_KEY, (B, S - prefix), 0, cfg.vocab)
    full_b = {"tokens": toks}
    pre_b = {"tokens": toks[:, :-1]}
    if prefix:
        pe = jax.random.normal(_KEY, (B, prefix, cfg.prefix_dim), jnp.bfloat16)
        full_b["prefix_embeds"] = pe
        pre_b["prefix_embeds"] = pe
    logits_full, _ = b.prefill(params, full_b)
    _, cache = b.prefill(params, pre_b, max_len=S)
    logits_dec, _ = b.decode(params, cache, toks[:, -1],
                             jnp.asarray(S - 1, jnp.int32))
    a = np.asarray(logits_full, np.float32)
    d = np.asarray(logits_dec, np.float32)
    rel = np.max(np.abs(a - d)) / (np.max(np.abs(a)) + 1e-9)
    # both paths use flash-kernel numerics (bf16 QK/PV operands, f32
    # accumulate; §Perf E2a) — prefill's online softmax and decode's plain
    # softmax round differently at bf16, so equality is bf16-level.
    # MLA's absorbed decode reassociates matmuls; attention soft-capping
    # (gemma2) compresses logit magnitudes, inflating the relative metric.
    tol = 5e-2 if (getattr(cfg, "mla", None) is not None
                   or getattr(cfg, "attn_softcap", 0.0)
                   or b.family in ("mamba2", "griffin")) else 2e-2
    assert rel < tol, rel


def test_engine_reconfigure_preserves_outputs():
    b, params = _bundle_params("llama3-8b")
    eng = SplitInferenceEngine(b, params)
    L = len(b.model_graph())
    toks = jax.random.randint(_KEY, (1, 16), 0, b.cfg.vocab)
    eng.apply_config(PartitionConfig(1, (0, 2, L), (0, 3)))
    out1 = eng.infer_logits(toks)
    eng.apply_config(PartitionConfig(2, (0, 1, 3, L), (1, 2, 0)))
    out2 = eng.infer_logits(toks)
    assert float(jnp.max(jnp.abs(out1 - out2))) < 1e-4
    assert eng.reconfigurations == 1
    staged = eng.staged_bytes_per_node()
    assert sum(staged.values()) == pytest.approx(
        b.model_graph().total_weight_bytes)


def test_serve_deploy_answers_through_the_compressed_chain():
    """`launch.serve` builds the engine behind the adaptive orchestrator and
    answers every request through the int8 chain (interpreted on CPU)."""
    from repro.launch.serve import deploy

    dep = deploy("llama3-8b", reduced=True, compress=True, interpret=True,
                 prompt_len=8)
    vocab = dep.bundle.cfg.vocab
    for i in range(3):
        toks = jax.random.randint(jax.random.PRNGKey(i), (1, 8), 0, vocab)
        logits, priced = dep.serve(toks, now=float(i))
        assert logits.shape == (1, 8, vocab)
        assert np.isfinite(np.asarray(logits)).all() and priced > 0.0
    assert dep.engine.config == dep.orch.current
    stats = dep.engine.transfer_stats()
    assert stats.transfers >= 3       # every request crossed a boundary
    assert stats.compression_ratio > 1.7


def test_serve_counters_count_the_served_path_and_survive_a_resplit():
    """``Deployment.serve`` counts requests, segment calls and the traces of
    the segments' scan bodies into the engine's stats, the transport its
    crossings, and the orchestrator each decision by kind; a re-split keeps
    every count.  A segment is traced once per shape: a second request of
    the same shape traces nothing, a re-split only its new segments."""
    from repro.launch.serve import deploy
    from repro.serving import segments

    # programs another test of this process compiled would hide the traces
    segments._segment_program.cache_clear()
    dep = deploy("stablelm-3b", reduced=True, compress=True, interpret=True,
                 prompt_len=8)
    eng, vocab = dep.engine, dep.bundle.cfg.vocab
    L = len(dep.bundle.model_graph())

    def counts():
        s, t = eng.stats, eng.transfer_stats()
        return (s.requests, s.segment_calls, s.segment_traces, t.transfers,
                sum(dep.orch.decision_counts.values()))

    seen = [counts()]
    assert seen[0] == (0, 0, 0, 0, 0)
    for i in range(4):
        if i == 2:      # re-split between requests: the chain is rebuilt
            old = set(zip(eng.config.boundaries, eng.config.boundaries[1:]))
            new = {(0, 2), (2, 3), (3, L)} - old
            eng.apply_config(PartitionConfig(eng.config.version + 1,
                                             (0, 2, 3, L), (0, 3, 0)))
        toks = jax.random.randint(jax.random.PRNGKey(i), (1, 8), 0, vocab)
        dep.serve(toks, now=float(i))
        seen.append(counts())
    for before, after in zip(seen, seen[1:]):
        assert all(b <= a for b, a in zip(before, after))
    requests, calls, traces, transfers, decisions = seen[-1]
    assert requests == 4 and calls == 3 * requests
    assert transfers == 2 * requests and decisions == requests
    assert 1 <= traces <= calls
    traced = [after[2] - before[2] for before, after in zip(seen, seen[1:])]
    assert traced[1] == 0             # the same shape again: nothing traced
    assert traced[2] <= len(new)      # the re-split traced its new segments
    # the next request's cycle restaged the orchestrator's own config
    assert eng.reconfigurations == 2 and eng.config == dep.orch.current


def test_compiled_segments_outlive_the_chain_across_resplits():
    """Each segment's compiled program is kept per segment, not per chain:
    going back to a split served before traces nothing, and answers with
    the same logits."""
    from repro.launch.serve import deploy

    dep = deploy("stablelm-3b", reduced=True, compress=True, interpret=True,
                 prompt_len=8)
    eng, vocab = dep.engine, dep.bundle.cfg.vocab
    L = len(dep.bundle.model_graph())
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, vocab)
    split_a, split_b = (0, 2, L), (0, 1, 3, L)
    eng.apply_config(PartitionConfig(eng.config.version + 1, split_a, (0, 3)))
    first = np.asarray(eng.infer_logits(toks))
    eng.apply_config(PartitionConfig(eng.config.version + 1, split_b,
                                     (0, 3, 0)))
    eng.infer_logits(toks)
    eng.apply_config(PartitionConfig(eng.config.version + 1, split_a, (0, 3)))
    traces = eng.stats.segment_traces
    again = np.asarray(eng.infer_logits(toks))
    assert eng.stats.segment_traces == traces
    np.testing.assert_array_equal(again, first)


def test_transport_compression_accounting():
    b, params = _bundle_params("llama3-8b")
    L = len(b.model_graph())
    toks = jax.random.randint(_KEY, (2, 16), 0, b.cfg.vocab)
    raw = ActivationTransport(compress=False, interpret=True)
    SegmentChain(b, params, (0, 2, L), transfer_hook=raw)(toks)
    comp = ActivationTransport(compress=True, interpret=True)
    out_c = SegmentChain(b, params, (0, 2, L), transfer_hook=comp)(toks)
    out_r = SegmentChain(b, params, (0, 2, L))(toks)
    assert comp.stats.compression_ratio > 1.7       # ~2x minus scale overhead
    assert raw.stats.compression_ratio == 1.0
    # int8 transfer costs bounded accuracy loss at the logits
    rel = float(jnp.max(jnp.abs(out_c - out_r)) / jnp.max(jnp.abs(out_r)))
    assert rel < 0.35


def test_split_params_cover_and_partition():
    b, params = _bundle_params("deepseek-v2-lite-16b")
    L = len(b.model_graph())
    segs = split_params(b, params, (0, 1, 2, L))
    assert "embed" in segs[0]
    assert "final_norm" in segs[-1]
    assert "lead_blocks" in segs[1] or "blocks" in segs[1]


def test_part_range_runner_refuses_the_full_tree():
    """A runner runs every block of the view it is handed, so a part-range
    runner given more blocks than its segment holds raises while its
    program traces, where it would otherwise run the wrong layers."""
    b, params = _bundle_params("stablelm-3b")
    L = len(b.model_graph())
    toks = jax.random.randint(_KEY, (1, 8), 0, b.cfg.vocab)
    runner = SegmentRunner(b, 0, L - 2)
    with pytest.raises(ValueError, match="blocks"):
        runner(params, toks)
    view = split_params(b, params, (0, L - 2, L))[0]
    assert runner(view, toks).shape == (1, 8, b.cfg.d_model)


def test_wave_batcher_completes_all():
    b, params = _bundle_params("llama3-8b")
    wb = WaveBatcher(b, params, max_batch=3, max_len=64)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, b.cfg.vocab, 9 + i,
                                               dtype=np.int32),
                    max_new_tokens=5) for i in range(7)]
    for r in reqs:
        wb.submit(r)
    stats = wb.run()
    assert stats.completed == 7
    assert all(r.done for r in reqs)
    assert all(1 <= len(r.output) <= 5 for r in reqs)
    assert stats.waves == 3
