"""DeepSeek-V2's layers in the transformer family: YaRN on the latent
attention's rotary key, dropless routing, and the named scopes that the
device trace carries for them."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, get_bundle
from repro.configs.deepseek_v2_lite_16b import ROPE_SCALING
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


@pytest.mark.parametrize("router", ["random", "zero"])
@pytest.mark.parametrize("router_scale", [False, True])
def test_moe_is_dropless(router, router_scale):
    """A zero router sends every token to the same top-k experts (equal
    gates, the lowest indices win): each of them gets every token, far past
    any capacity a dropping layer would give it, and the layer still equals
    the plain dropless sum."""
    b = get_bundle("deepseek-v2-lite-16b", reduced=True)
    cfg = dataclasses.replace(
        b.cfg, moe=dataclasses.replace(b.cfg.moe, router_scale=router_scale))
    params = b.init(jax.random.PRNGKey(1), jnp.float32)
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])["moe"]
    if router == "zero":
        p = {**p, "router": jnp.zeros_like(p["router"])}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(moe_ffn, static_argnums=2)(x, p, cfg), np.float64)
    want = _plain_moe(x, p, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * np.abs(want).max())


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
