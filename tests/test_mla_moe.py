"""DeepSeek-V2's layers in the transformer family: YaRN on the latent
attention's rotary key, dropless routing, and the named scopes that the
device trace carries for them."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, get_bundle
from repro.configs.deepseek_v2_lite_16b import ROPE_SCALING
from repro.models import transformer
from repro.models.common import YaRN, apply_rope, rope_frequencies
from repro.models.transformer import moe_ffn


def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V2-Lite's published rope_scaling at its 64-wide rotary key:
    the ramp runs from pair 64 ln(4096 / (32 2 pi)) / (2 ln 1e4) = 10.47
    (floor 10) to 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 (ceil 23); the
    softmax scale is 192^-1/2 (0.1 * 0.707 ln 40 + 1)^2 = 0.114721, and the
    cos/sin factor mscale / mscale_all_dim is 1."""
    y = ROPE_SCALING
    assert y.ramp(64, 10_000.0) == (10, 23)
    f = np.asarray(y.frequencies(64, 10_000.0), np.float64)
    base = np.asarray(rope_frequencies(64, 10_000.0), np.float64)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)      # ramp 0
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)  # ramp 1
    ramp = (np.arange(32) - 10) / 13
    np.testing.assert_allclose(f[11:23], base[11:23] * (1 - ramp[11:23] * 39 / 40),
                               rtol=1e-6)
    assert y.softmax_mscale == pytest.approx(1.58963, abs=1e-5)
    mla = get("deepseek-v2-lite-16b").mla
    assert mla.softmax_scale == pytest.approx(0.114721, abs=1e-6)
    assert y.rope_mscale == 1.0


def test_yarn_magnitude_and_no_scaling():
    """Without ``mscale_all_dim`` the softmax keeps 1/sqrt(d) and the rotated
    features carry mscale; at factor 1 YaRN is plain RoPE."""
    y = YaRN(factor=4.0, original_max_position=64, mscale=1.0)
    assert y.softmax_mscale == 1.0
    assert y.rope_mscale == pytest.approx(0.1 * math.log(4.0) + 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 16))
    pos = jnp.arange(9)
    flat = YaRN(factor=1.0, original_max_position=64)
    np.testing.assert_array_equal(np.asarray(apply_rope(x, pos, yarn=flat)),
                                  np.asarray(apply_rope(x, pos)))
    norms = jnp.linalg.norm(apply_rope(x, pos, yarn=y), axis=-1)
    np.testing.assert_allclose(np.asarray(norms),
                               np.asarray(y.rope_mscale * jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)


def test_published_routing():
    """DeepSeek-V2-Lite keeps its top-6 gates as they are
    (``norm_topk_prob`` false); Qwen3-MoE renormalises its top-8."""
    ds = get("deepseek-v2-lite-16b")
    assert ds.moe.router_scale is False
    assert (ds.moe.num_experts, ds.moe.top_k, ds.moe.num_shared) == (64, 6, 2)
    assert ds.mla.rope_scaling == ROPE_SCALING
    assert get("qwen3-moe-30b-a3b").moe.router_scale is True


def _plain_moe(x, p, cfg):
    """Every expert on every token, weighted by the token's gate for it
    (zero unless picked): dropless by construction."""
    moe = cfg.moe
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xf @ np.asarray(p["router"], np.float64)
    g = np.exp(logits - logits.max(-1, keepdims=True))
    g /= g.sum(-1, keepdims=True)
    top = np.argsort(-g, -1, kind="stable")[:, :moe.top_k]
    w = np.zeros_like(g)
    np.put_along_axis(w, top, np.take_along_axis(g, top, -1), -1)
    if moe.router_scale:
        w /= w.sum(-1, keepdims=True)

    def ffn(q, i=None):
        wi, wg, wo = (np.asarray(q[k] if i is None else q[k][i], np.float64)
                      for k in ("wi", "wg", "wo"))
        a = xf @ wi
        return (a / (1 + np.exp(-a)) * (xf @ wg)) @ wo

    out = sum(w[:, e:e + 1] * ffn(p["experts"], e) for e in range(moe.num_experts))
    if moe.num_shared:
        out = out + ffn(p["shared"])
    return out.reshape(x.shape)


def _force_grouped_kernel(monkeypatch):
    """Run the routed experts through megablox's kernel, interpreted on the
    CPU, whatever the shapes."""
    monkeypatch.setattr(transformer, "uses_grouped_kernel", lambda *a: True)
    monkeypatch.setattr(transformer, "grouped_matmul", functools.partial(
        transformer.grouped_matmul, interpret=True))


@pytest.mark.parametrize("router,kernel", [
    pytest.param("random", False, id="random"),
    pytest.param("zero", False, id="zero"),
    pytest.param("random", True, id="random-gmm"),
    pytest.param("zero", True, id="zero-gmm"),
])
@pytest.mark.parametrize("router_scale", [False, True])
def test_moe_is_dropless(monkeypatch, router, kernel, router_scale):
    """A zero router sends every token to the same top-k experts (equal
    gates, the lowest indices win): each of them gets every token, far past
    any capacity a dropping layer would give it, and the layer still equals
    the plain dropless sum.  On the grouped-matmul kernel (``-gmm``) it also
    equals the ``lax.ragged_dot`` path, over 4 row tiles: with the zero
    router 2 experts hold 2 tiles each and the other 6 none, with a random
    one each tile holds rows of several experts."""
    b = get_bundle("deepseek-v2-lite-16b", reduced=True)
    cfg = dataclasses.replace(
        b.cfg, moe=dataclasses.replace(b.cfg.moe, router_scale=router_scale))
    params = b.init(jax.random.PRNGKey(1), jnp.float32)
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])["moe"]
    if router == "zero":
        p = {**p, "router": jnp.zeros_like(p["router"])}
    tokens = 2 * transformer._GMM_TM // cfg.moe.top_k if kernel else 24
    x = jax.random.normal(jax.random.PRNGKey(2), (2, tokens, cfg.d_model),
                          jnp.float32)

    def run():      # a program of its own, traced with the path as it is now
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda x, p: moe_ffn(x, p, cfg))(x, p),
                              np.float64)

    got = run()
    want = _plain_moe(x, p, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * np.abs(want).max())
    if kernel:
        _force_grouped_kernel(monkeypatch)
        on_kernel = run()
        np.testing.assert_allclose(on_kernel, got, rtol=1e-5,
                                   atol=1e-6 * np.abs(want).max())
        np.testing.assert_allclose(on_kernel, want, rtol=1e-4,
                                   atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("backend,rows,k,n,holds", [
    ("cpu", 6144, 2048, 1408, False),
    ("tpu", 6144, 2048, 1408, True),      # DeepSeek-V2-Lite, 1,024 tokens
    ("tpu", 12288, 1408, 2048, True),
    ("tpu", 3072, 2048, 768, True),       # Qwen3-MoE, 512 tokens
    ("tpu", 768, 2048, 1408, True),       # 128 tokens: 6 whole row tiles
    ("tpu", 6, 2048, 1408, False),        # decode
    ("tpu", 6000, 2048, 1408, False),     # no whole row tiles
    ("tpu", 6144, 64, 32, False),         # widths off the 128 lanes
])
def test_grouped_kernel_predicate(monkeypatch, backend, rows, k, n, holds):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert transformer.uses_grouped_kernel(rows, k, n) is holds


def test_no_grouped_kernel_under_an_activation_mesh(monkeypatch):
    from repro.distributed.context import activation_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with activation_mesh(object()):
        assert not transformer.uses_grouped_kernel(6144, 2048, 1408)


@pytest.mark.parametrize("sizes", [
    pytest.param((256, 0, 0, 0), id="one-group"),
    pytest.param((0, 512, 0, 256), id="empty-groups"),
    pytest.param((100, 300, 60, 308), id="across-tiles"),
    pytest.param((256, 256, 256, 0), id="whole-tiles"),
    pytest.param((1, 0, 766, 1), id="tiny-groups"),
])
def test_grouped_kernel_matches_ragged_dot(sizes):
    """The kernel, interpreted, equals ``lax.ragged_dot`` on rows whose
    groups are empty, span several row tiles, or share a tile."""
    m = sum(sizes)
    a = jax.random.normal(jax.random.PRNGKey(0), (m, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), 256, 128),
                          jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = transformer.grouped_matmul(a, w, sizes, interpret=True)
        want = jax.lax.ragged_dot(a, w, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * float(np.abs(want).max()))


def test_segment_program_carries_the_named_scopes():
    """The compiled segment program of the reduced model names its latent
    attention and each part of its mixture in the ops' metadata, which the
    device trace carries."""
    from repro.serving.segments import SegmentRunner

    b = get_bundle("deepseek-v2-lite-16b", reduced=True)
    n = len(b.model_graph())
    params = jax.eval_shape(lambda: b.init(jax.random.PRNGKey(0), jnp.bfloat16))
    runner = SegmentRunner(b, 0, n)
    toks = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    text = runner._program.lower(params, toks).compile().as_text()
    for scope in ("mla", "moe_router", "moe_dispatch", "moe_experts",
                  "moe_shared", "moe_combine"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("bounds_of,experts", [
    (lambda n: (0, 2, n), [0, 2]),           # the lead block, then the MoE blocks
    (lambda n: (0, 3, n), [1, 1]),
    (lambda n: (0, 1, n - 1, n), [0, 2, 0]),
])
def test_expert_kernel_layer_count_per_segment(monkeypatch, bounds_of, experts):
    """At 128-lane widths the chain counts a segment's MoE layers on a
    request whose routed rows fill whole row tiles (1,024 tokens top-2, or
    two sequences of 96) and none on one that does not (one sequence of
    96), the dense lead block never."""
    from repro.models.api import bundle_for
    from repro.serving import SegmentChain

    b = get_bundle("deepseek-v2-lite-16b", reduced=True)
    cfg = dataclasses.replace(b.cfg, d_model=128, moe=dataclasses.replace(
        b.cfg.moe, d_expert=128))
    b = bundle_for(b.arch, cfg)
    params = b.init(jax.random.PRNGKey(0), jnp.bfloat16)
    chain = SegmentChain(b, params, bounds_of(len(b.model_graph())))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [s.runner.kernel_layers(1, 1024).experts for s in chain.segments] == experts
    # programs stubbed: the count is the host's, nothing runs
    for s in chain.segments:
        s.runner._program = lambda params, x: x
    chain(jnp.zeros((1, 96), jnp.int32))
    assert chain.stats.expert_kernel_layers == 0
    chain(jnp.zeros((1, 1024), jnp.int32))
    assert chain.stats.expert_kernel_layers == sum(experts) == 2
    assert chain.stats.attention_kernel_layers == 3      # 1,024 rows: splash
    # the routed rows of a batch are those of all its sequences
    chain(jnp.zeros((2, 96), jnp.int32))
    assert chain.stats.expert_kernel_layers == 2 * sum(experts)
