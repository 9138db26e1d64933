"""Plain reference of DeepSeek-V2-Lite's forward pass, and the weights both
sides run.

The decoder as deepseek-ai/DeepSeek-V2-Lite publishes it (``config.json``
and ``modeling_deepseek.py``): RMSNorm (eps from the config), latent
attention (MLA) with no query compression, keys and values decompressed from
an RMS-normed latent of ``kv_lora_rank`` and a decoupled rotary key of
``qk_rope_head_dim`` shared by every head, under YaRN rotary scaling; the
first ``first_k_dense_replace`` layers with a SiLU-gated MLP, every later
one a mixture of experts: a softmax router over ``n_routed_experts``, the
top ``num_experts_per_tok`` gates kept as they are (``norm_topk_prob``
false), no token dropped, plus ``n_shared_experts`` shared experts that see
every token; an untied head.  Written in float32 ``jax.numpy`` at
``Precision.HIGHEST``, one layer per call, with nothing of the program
imported.  The mixture is computed in its plainest dropless form: each
expert's FFN on every row, weighted by that row's gate for it, which is
zero unless the row picked the expert.

Departures from the published checkpoint, taken to match the system under
test:

- the rotary embedding rotates interleaved pairs (0,1), (2,3), ... of the
  rotary slice.  DeepSeek's own code permutes the slice into interleaved
  order and then rotates halves, so this is the same rotation;
- random weights from the seed (``make_params``), with the projections that
  write into the residual stream (attention's output, the dense, expert and
  shared ``wo``) scaled by 1/sqrt(2 L), and the router at 1/sqrt(d).

The served split rounds the activations that cross a segment boundary to
symmetric per-row int8, so the reference rounds its own at the same
boundaries, with ``decoder``'s rounding.  ``low="int8"`` or ``low="fp8"`` is
the control, as in ``decoder``: every tensor the program holds in bf16 held
one precision step below it, float32 arithmetic in between.  The router's
logits, which the program computes in float32, are not rounded.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.decoder import (  # noqa: F401  (served_gap: the check's)
    HI,
    _embed,
    _held,
    _linear,
    _transport,
    key_from_seed,
    served_gap,
)


# --------------------------------------------------------------------------- #
# weights: made on the device in one jitted call from the seed
# --------------------------------------------------------------------------- #
def _attn_shapes(m: dict, *lead: int) -> dict:
    d, h, lat = m["d_model"], m["n_heads"], m["kv_lora"]
    nope, rope, vd = m["nope_dim"], m["rope_dim"], m["v_dim"]
    return {"wq": (*lead, d, h, nope + rope), "wdkv": (*lead, d, lat),
            "wkr": (*lead, d, rope), "kv_ln": {"scale": (*lead, lat)},
            "wuk": (*lead, lat, h, nope), "wuv": (*lead, lat, h, vd),
            "wo": (*lead, h, vd, d)}


def _ffn_shapes(d: int, ff: int, *lead: int) -> dict:
    return {"wi": (*lead, d, ff), "wg": (*lead, d, ff), "wo": (*lead, ff, d)}


def param_shapes(m: dict) -> dict:
    """The parameter tree's shapes, in the layout the program's transformer
    reads: leading dense blocks as a list, the expert blocks stacked, an
    embedding table and an untied head."""
    d, V, E = m["d_model"], m["vocab"], m["n_experts"]
    n = m["n_layers"] - m["n_dense"]
    lead = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
            "attn": _attn_shapes(m), "mlp": _ffn_shapes(d, m["d_ff"])}
    return {
        "embed": (V, d),
        "final_norm": {"scale": (d,)},
        "head": (d, V),
        "lead_blocks": [lead] * m["n_dense"],
        "blocks": {
            "ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
            "attn": _attn_shapes(m, n),
            "moe": {"router": (n, d, E),
                    "experts": _ffn_shapes(d, m["d_expert"], n, E),
                    "shared": _ffn_shapes(d, m["d_expert"] * m["n_shared"], n)},
        },
    }


def _std(path: tuple, shape: tuple[int, ...], m: dict) -> float:
    """Standard deviation of each leaf: 1/sqrt(fan_in) for a projection,
    1/sqrt(2 L) less for the four that write into the residual stream, as
    GPT-2 initialises them, so the random model is not chaotic.  Norm
    scales sit near 1, away from exactly."""
    name = path[-1]
    if name == "scale":
        return 0.05
    if path == ("embed",):
        return 1.0
    if name in ("wq", "wdkv", "wkr", "wi", "wg", "head", "router"):
        return float(shape[-3 if name == "wq" else -2]) ** -0.5
    if name in ("wuk", "wuv"):
        return float(shape[-3]) ** -0.5
    fan_in = int(np.prod(shape[-3:-1])) if path[-2] == "attn" else shape[-2]
    return (float(fan_in) ** -0.5) / math.sqrt(2 * m["n_layers"])


def make_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, in ``dtype``, made by one jitted call."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(i, int) for i in x))
    specs = [(tuple(getattr(k, "key", getattr(k, "idx", None)) for k in p), s)
             for p, s in leaves]

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(specs))
        out = []
        for k, (path, shape) in zip(keys, specs):
            x = jax.random.normal(k, shape, jnp.float32) * _std(path, shape, m)
            if path[-1] == "scale":
                x = x + 1.0
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return init(key_from_seed(seed))


# --------------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes a layer reads, hashable so jit can key on them."""

    n_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    top_k: int
    norm_topk: bool
    rms_eps: float
    rope_theta: float
    yarn: tuple          # rope_scaling's items, sorted

    @classmethod
    def of(cls, m: dict) -> "Dims":
        return cls(m["n_heads"], m["nope_dim"], m["rope_dim"], m["v_dim"],
                   m["top_k"], m["norm_topk"], m["rms_eps"], m["rope_theta"],
                   tuple(sorted(m["rope_scaling"].items())))


def _mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s frequencies of a ``dim``-wide
    rotary slice, in float64."""
    def corr(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def yarn_softmax_scale(qk_dim: int, rs: dict) -> float:
    """The attention's softmax scale: qk_dim^-1/2, times mscale^2 of
    ``mscale_all_dim`` when the config gives one."""
    scale = qk_dim ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x: jax.Array, m: Dims) -> jax.Array:
    """x [S, H, r]: rotate interleaved pairs by position at YaRN's
    frequencies, times its cos/sin magnitude."""
    rs = dict(m.yarn)
    inv = jnp.asarray(yarn_inv_freq(x.shape[-1], m.rope_theta, rs), jnp.float32)
    mag = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"],
                                                         rs["mscale_all_dim"])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = (mag * jnp.cos(ang))[:, None, :], (mag * jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _mla(y, a, m: Dims, low):
    """Latent attention on normed rows y [S, d], causal over all S rows."""
    s, d = y.shape
    h, nope, r, vd = m.n_heads, m.nope_dim, m.rope_dim, m.v_dim
    lat = a["wdkv"].shape[-1]
    q = _linear(y, a["wq"].reshape(d, h * (nope + r)), low).reshape(s, h, nope + r)
    q_nope, q_pe = q[..., :nope], _held(_rope(q[..., nope:], m), low)
    ckv = _held(_rms(_linear(y, a["wdkv"], low), a["kv_ln"]["scale"], m.rms_eps), low)
    k_pe = _held(_rope(_linear(y, a["wkr"], low)[:, None, :], m), low)
    k_nope = _linear(ckv, a["wuk"].reshape(lat, h * nope), low).reshape(s, h, nope)
    v = _linear(ckv, a["wuv"].reshape(lat, h * vd), low).reshape(s, h, vd)
    sc = (jnp.einsum("qhc,khc->hqk", q_nope, k_nope, precision=HI)
          + jnp.einsum("qhc,kc->hqk", q_pe, k_pe[:, 0], precision=HI))
    sc = sc * yarn_softmax_scale(nope + r, dict(m.yarn))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = _held(jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1), low)
    o = _held(jnp.einsum("hqk,khc->qhc", probs, v, precision=HI), low)
    return _linear(o.reshape(s, h * vd), a["wo"].reshape(h * vd, d), low)


def _ffn(y, f, low):
    g = _held(jax.nn.silu(_linear(y, f["wi"], low)) * _linear(y, f["wg"], low), low)
    return _linear(g, f["wo"], low)


def _moe(y, p, m: Dims, low):
    """Dropless mixture: every row reaches all ``top_k`` of its experts."""
    gates = jax.nn.softmax(jnp.dot(y, p["router"].astype(jnp.float32),
                                   precision=HI), -1)               # [S, E]
    picked = jnp.argsort(-gates, -1, stable=True)[:, :m.top_k]
    rows = jnp.arange(y.shape[0])[:, None]
    top = gates[rows, picked]
    if m.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros_like(gates).at[rows, picked].set(top)        # [S, E]

    def expert(acc, inputs):
        w, f = inputs
        return acc + w[:, None] * _ffn(y, f, low), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          (weight.T, p["experts"]))
    return _held(out + _ffn(y, p["shared"], low), low)


@partial(jax.jit, static_argnames=("m", "low"))
def _block(x, p, *, m: Dims, low):
    """One decoder layer on x [S, d] float32; ``p`` holds ``mlp`` (dense) or
    ``moe``."""
    y = _held(_rms(x, p["ln1"]["scale"], m.rms_eps), low)
    x = _held(x + _mla(y, p["attn"], m, low), low)
    y = _held(_rms(x, p["ln2"]["scale"], m.rms_eps), low)
    f = _ffn(y, p["mlp"], low) if "mlp" in p else _moe(y, p["moe"], m, low)
    return _held(x + f, low)


@partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, final_norm, head, *, eps, low):
    return _linear(_held(_rms(x, final_norm["scale"], eps), low), head, low)


@jax.jit
def _layer(blocks, i):
    return jax.tree_util.tree_map(lambda a: a[i], blocks)


def logits(params, tokens: np.ndarray, m: dict, cuts: tuple[int, ...],
           *, low: str | None = None) -> jax.Array:
    """Float32 logits [S, V] of a token row ``tokens`` [S].

    ``cuts`` are the layer indices after which the activations cross a
    segment boundary as int8.  Rows past a prompt's end only pad it: the
    attention is causal and the mixture routes each row alone, so they
    change no row before them.
    """
    dims = Dims.of(m)
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for layer in range(m["n_layers"]):
        if layer < m["n_dense"]:
            p = params["lead_blocks"][layer]
        else:
            p = _layer(params["blocks"], jnp.int32(layer - m["n_dense"]))
        x = _block(x, p, m=dims, low=low)
        if layer in cuts:
            x = _transport(x)
    return _head(x, params["final_norm"], params["head"], eps=m["rms_eps"],
                 low=low)
