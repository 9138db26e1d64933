"""DeepSeek-V2-Lite's cell at a small size on the CPU: the plain reference
against the program, the counts by hand, the driver's counters, the check's
faults and the roofline reader."""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.drivers import served, served_mla_moe
from bench.lib import counts_mla_moe, harness
from bench.reference import deepseek_v2

CELL = "deepseek-v2-lite.long"
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_hidden_layers=3, vocab_size=512, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=24, n_routed_experts=8, num_experts_per_tok=3)
# ``served_gap``'s limit at this size, set by the rule the cell's own limit
# follows: sound runs of the program read 0.016-0.035 and the fp8 control
# 0.30-0.46 (seeds 2**35 + 7, 2**36 + 11, 5 and 17, two requests each)
TINY_GAP_LIMIT = 0.1


@pytest.fixture(scope="module")
def tiny() -> dict:
    cfg = copy.deepcopy(harness.load_json(harness.BENCH / "configs" /
                                          "deepseek-v2-lite.json"))
    cfg.update(TINY)
    return cfg


@pytest.fixture(scope="module")
def m(tiny) -> dict:
    return served_mla_moe.model_dims(tiny)


def test_config_is_the_published_one_cut_in_depth():
    """The file holds the catalog row's keys as published, but the depth."""
    cfg = harness.load_json(harness.BENCH / "configs" / "deepseek-v2-lite.json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6 and cfg["first_k_dense_replace"] == 1
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) == (2048, 1408, 64, 6, 2)
    assert cfg["norm_topk_prob"] is False and cfg["q_lora_rank"] is None
    assert cfg["rope_scaling"]["type"] == "yarn"


def test_weights_have_the_programs_layout(m):
    bundle = served_mla_moe.program_bundle("deepseek-v2-lite", m)
    theirs = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0), jnp.bfloat16))
    ours = jax.eval_shape(lambda: deepseek_v2.make_params(m, 1))
    assert jax.tree_util.tree_structure(theirs) == jax.tree_util.tree_structure(ours)
    assert [a.shape for a in jax.tree_util.tree_leaves(theirs)] == \
        [a.shape for a in jax.tree_util.tree_leaves(ours)]


def test_reference_yarn_is_the_programs(m):
    """The reference's own YaRN (written from the published code) and the
    program's give the same frequencies and softmax scale."""
    bundle = served_mla_moe.program_bundle("deepseek-v2-lite", m)
    mla = bundle.cfg.mla
    rs = m["rope_scaling"]
    for dim in (8, 64):
        np.testing.assert_allclose(
            np.asarray(mla.rope_scaling.frequencies(dim, m["rope_theta"])),
            deepseek_v2.yarn_inv_freq(dim, m["rope_theta"], rs), rtol=1e-6)
    assert mla.softmax_scale == pytest.approx(
        deepseek_v2.yarn_softmax_scale(m["nope_dim"] + m["rope_dim"], rs), rel=1e-12)


def _float32_chain(m, params, bounds, toks):
    """The program's split chain run in float32 from the first block on
    (its embedding lookup casts to bf16, so the chain starts after it)."""
    from repro.serving.segments import SegmentChain

    bundle = served_mla_moe.program_bundle("deepseek-v2-lite", m)
    x = params["embed"][jnp.asarray(toks)][None].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return np.asarray(SegmentChain(bundle, params, bounds)(x)[0], np.float64)


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("bounds", [(1, 5), (1, 2, 4, 5)])
def test_reference_matches_the_chain_in_float32(m, norm_topk, bounds):
    """No int8 cut and float32 throughout: the program's chain (at one and
    at three segments) and the reference compute the same function, with
    the gates kept as they are (published) or renormalised.  Tolerance:
    float32 rounding of differently ordered sums, 1e-4 of the largest
    logit."""
    m = {**m, "norm_topk": norm_topk}
    params = deepseek_v2.make_params(m, 3, jnp.float32)
    toks = np.random.default_rng(0).integers(0, m["vocab"], 40, dtype=np.int32)
    theirs = _float32_chain(m, params, bounds, toks)
    ours = np.asarray(deepseek_v2.logits(params, toks, m, ()), np.float64)
    assert np.max(np.abs(theirs - ours)) < 1e-4 * np.max(np.abs(ours))


def test_unnormalised_gates_differ_from_renormalised(m):
    """The routing flag reaches the logits: the published un-normalised
    gates and renormalised ones give different models."""
    params = deepseek_v2.make_params(m, 3, jnp.float32)
    toks = np.random.default_rng(1).integers(0, m["vocab"], 16, dtype=np.int32)
    a = np.asarray(deepseek_v2.logits(params, toks, m, ()))
    b = np.asarray(deepseek_v2.logits(params, toks, {**m, "norm_topk": True}, ()))
    assert np.max(np.abs(a - b)) > 1e-2 * np.max(np.abs(a))


def test_dropless_when_every_token_picks_the_same_experts(m):
    """Zero routers: equal gates, so every token, the padding too, goes to
    the same experts (the lowest indices).  A layer that caps each expert at
    its share of the tokens drops most of them; the served chain has to give
    the reference's logits all the same."""
    params = deepseek_v2.make_params(m, 4, jnp.float32)
    params["blocks"]["moe"]["router"] = jnp.zeros_like(params["blocks"]["moe"]["router"])
    toks = np.random.default_rng(2).integers(0, m["vocab"], 48, dtype=np.int32)
    theirs = _float32_chain(m, params, (1, 3, 5), toks)
    ours = np.asarray(deepseek_v2.logits(params, toks, m, ()), np.float64)
    assert np.max(np.abs(theirs - ours)) < 1e-4 * np.max(np.abs(ours))


def test_int8_cuts_match_the_deployed_chain(m):
    """``deploy(..., compress=True)``: the int8 split chain that serves,
    against the reference with the same int8 cuts.  The program computes
    in bf16, so agreement is to bf16 rounding: 5 % of the largest logit,
    and the same greedy id at 90 % of the rows."""
    from repro.launch import serve as serve_mod

    params = deepseek_v2.make_params(m, 5, jnp.float32)
    bundle = dataclasses.replace(
        served_mla_moe.program_bundle("deepseek-v2-lite", m),
        init=lambda key, dtype: params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve_mod, "get_bundle", lambda arch, reduced=False: bundle)
        dep = serve_mod.deploy("deepseek-v2-lite", compress=True, interpret=True)
    bounds = dep.engine.config.boundaries
    assert len(bounds) == 4            # two int8 boundaries
    toks = np.random.default_rng(3).integers(0, m["vocab"], 32, dtype=np.int32)
    theirs, _ = dep.serve(jnp.asarray(toks)[None], now=0.0)
    theirs = np.asarray(theirs[0], np.float64)
    ours = np.asarray(deepseek_v2.logits(
        params, toks, m, tuple(b - 2 for b in bounds[1:-1])), np.float64)
    assert np.max(np.abs(theirs - ours)) < 0.05 * np.max(np.abs(ours))
    assert np.mean(theirs.argmax(-1) == ours.argmax(-1)) >= 0.9


def test_weights_from_large_seeds_differ(m):
    a = deepseek_v2.make_params(m, 2**40 + 1)["head"]
    b = deepseek_v2.make_params(m, 2**41 + 1)["head"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a),
                          np.asarray(deepseek_v2.make_params(m, 2**40 + 1)["head"]))


# --------------------------------------------------------------------------- #
# counts
# --------------------------------------------------------------------------- #
M = {"d_model": 8, "n_heads": 2, "kv_lora": 4, "nope_dim": 3, "rope_dim": 2,
     "v_dim": 3, "d_ff": 16, "d_expert": 5, "n_experts": 4, "top_k": 2,
     "n_shared": 1, "n_layers": 2, "n_dense": 1, "vocab": 10}


def test_prefill_flops_by_hand():
    # MLA multiply-adds a token: wq 8x2x5 = 80, latent 8x4 = 32, rotary key
    # 8x2 = 16, decompression 4x2x(3+3) = 48, output 2x3x8 = 48: 224
    # dense layer: 3 x 8 x 16 = 384; expert layer: router 8x4 = 32, two
    # routed and one shared expert of 3 x 8 x 5 = 120 each: 392
    # attention of 3 rows: 6 pairs x 2 heads x (5 + 3) x 2 FLOPs = 192
    # head: 3 x 8 x 10 x 2 = 480
    dense = 3 * 2 * (224 + 384) + 192
    moe = 3 * 2 * (224 + 392) + 192
    assert counts_mla_moe.prefill_flops(M, 3) == dense + moe + 480


def test_roofline_by_hand():
    # bytes: the embedding's 3 rows of 8 bf16 = 48; the dense layer's
    # 224 + 384 = 608 parameters, 1,216 B; the expert layer's 224 + 32 +
    # 4 x 120 + 120 = 856, 1,712 B; the head's 80, 160 B.  With a peak of
    # 1 FLOP/s and 1,000 B/s the FLOPs bound every stage but the embedding.
    peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1000.0}
    want = 0.048 + (3 * 2 * 608 + 192) + (3 * 2 * 616 + 192) + 480
    assert counts_mla_moe.roofline_s(M, 3, peaks) == pytest.approx(want)
    # with FLOPs free every stage is bound by its bytes
    peaks = {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 1.0}
    assert counts_mla_moe.roofline_s(M, 3, peaks) == pytest.approx(
        48 + 1216 + 1712 + 160)


# --------------------------------------------------------------------------- #
# the driver and the check
# --------------------------------------------------------------------------- #
_LOAD_CELL = run.load_cell


def _tiny_cell(name: str):
    bench, cell, cfg, mix = _LOAD_CELL(name)
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    cfg.update(TINY)
    cfg["checks"]["served_gap"] = TINY_GAP_LIMIT
    mix.update(pool=32, check_requests=2, buckets=[64, 128],
               lengths={"dist": "uniform", "min": 32, "max": 128})
    return bench, cell, cfg, mix



@dataclasses.dataclass
class _Served(served_mla_moe.ServedMlaMoe):
    interpret: bool = True


@dataclasses.dataclass
class _TokenAltered(_Served):
    """The served logits favour token 0 at every row."""

    def setup(self, clock):
        notes = super().setup(clock)
        real = self.dep.engine.infer_logits
        self.dep.engine.infer_logits = lambda toks: real(toks).at[..., 0].add(100.0)
        return notes


@dataclasses.dataclass
class _Fp8Control(_Served):
    """Every request answered by the reference computed in float8 e4m3,
    one precision below bf16, in the program's place."""

    def setup(self, clock):
        notes = super().setup(clock)
        cuts = tuple(b - 2 for b in self.dep.engine.config.boundaries[1:-1])
        self.dep.engine.infer_logits = lambda toks: deepseek_v2.logits(
            self.params, np.asarray(toks)[0], self.m, cuts, low="fp8")[None]
        return notes


def _run(monkeypatch, capsys, driver_cls) -> dict:
    monkeypatch.setattr(run, "load_cell", _tiny_cell)
    monkeypatch.setattr(harness, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "off")
    monkeypatch.setattr(served_mla_moe, "DRIVER", driver_cls)
    assert run.main(["--workload", CELL, "--seed", str(2**35 + 7),
                     "--seconds", "0.5", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("driver_cls, correct", [
    (_Served, True), (_TokenAltered, False), (_Fp8Control, False)],
    ids=["sound", "token_altered", "fp8_control"])
def test_check(monkeypatch, capsys, driver_cls, correct):
    out = _run(monkeypatch, capsys, driver_cls)
    assert out["correct"] is correct
    gap = out["checks"]["served_gap"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert set(out["metrics"]) == {"ttft_p95_ms", "prompt_tokens_per_s", "setup_s"}


def test_served_control_reads_far_above_a_sound_run(tiny, monkeypatch):
    """The fp8 control against the program's own served ids on the same
    sampled requests; the driver's counters over the same window."""
    _, _, cfg, mix = _tiny_cell(CELL)
    d = _Served(cfg, mix, 2**36 + 11)
    d.setup(harness.CompileClock())
    d.measure(1.0, False)
    assert d.failed == 0 and d.done
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks)
    c = d.counters()
    lengths = [d.requests[i % len(d.requests)][1] for i, _, _ in d.done]
    assert c["flops"] == pytest.approx(
        sum(counts_mla_moe.prefill_flops(d.m, n) for n in lengths))
    assert c["roofline_s"] == pytest.approx(
        sum(counts_mla_moe.roofline_s(d.m, n, peaks) for n in lengths))
    assert c["int8_bytes"] > 0 and c["kernels"] == ("quantize_int8", "dequantize_int8")
    sample = d.sample()
    d.free()
    program = max(d.gaps(sample))
    control = min(d.gaps(sample, control="fp8"))
    assert control > 3 * program, (program, control)
    # the stablelm driver's names are its own again once a call is done
    assert served.counts.__name__ == "bench.lib.counts"
    assert served.decoder.__name__ == "bench.reference.decoder"


def _reader(name: str):
    path = harness.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"test_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_prefill_roofline_reader():
    read = _reader("prefill_roofline")
    run_ = harness.TraceRun({"roofline_s": 3.0}, 10.0, {"window_s": 12.0}, {})
    assert read(run_) == pytest.approx(25.0)
    # a driver that counts no roofline (the stablelm cells) reads nothing
    assert read(harness.TraceRun({"flops": 1.0}, 10.0, {"window_s": 12.0}, {})) is None
