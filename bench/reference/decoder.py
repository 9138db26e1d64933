"""Plain reference of the served decoder, and the weights both sides run.

A pre-norm decoder as stablelm-3b-4e1t publishes it: LayerNorm (eps 1e-5,
with bias), multi-head attention with rotary embedding on the first quarter
of each head, a SiLU-gated MLP and an untied head.  Written in float32
``jax.numpy`` at ``Precision.HIGHEST``, one layer per call, with nothing of
the program imported.

One departure from the published checkpoint, taken to match the system
under test: the rotary embedding rotates interleaved pairs (0,1), (2,3), ...
where the published code rotates the two halves of the rotary slice.  With
random weights this is a fixed permutation of the query and key columns.

The served split sends the activations that cross a segment boundary as
symmetric per-row int8 (absmax / 127 scales, round half to even), so the
reference quantizes and dequantizes its own float32 activations at the same
boundaries.

``low="int8"`` or ``low="fp8"`` is the control, the reference computed one
precision step below the bf16 the configuration states.  Every tensor the
program holds in bf16 (weights, scaled per output channel; activations,
matmul outputs, the residual stream, attention probabilities and logits,
scaled per row) is held as symmetric int8, or as float8 e4m3 scaled to its
largest finite value, with float32 arithmetic in between.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


# --------------------------------------------------------------------------- #
# weights: made on the device in one jitted call from the seed
# --------------------------------------------------------------------------- #
def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer: all its bits count (JAX's
    own ``key(seed)`` keeps only the low 32 bits without 64-bit mode)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def param_shapes(m: dict) -> dict:
    """The parameter tree's shapes, in the layout the program's transformer
    reads: stacked blocks, an embedding table and an untied head."""
    d, h, hd, ff, L, V = (m["d_model"], m["n_heads"], m["head_dim"],
                          m["d_ff"], m["n_layers"], m["vocab"])
    kv = m["n_kv"]
    return {
        "embed": (V, d),
        "final_norm": {"scale": (d,), "bias": (d,)},
        "head": (d, V),
        "blocks": {
            "ln1": {"scale": (L, d), "bias": (L, d)},
            "ln2": {"scale": (L, d), "bias": (L, d)},
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd),
                     "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
            "mlp": {"wi": (L, d, ff), "wg": (L, d, ff), "wo": (L, ff, d)},
        },
    }


def _std(path: tuple[str, ...], shape: tuple[int, ...], m: dict) -> float:
    """Standard deviation of each leaf.  Projections read 1/sqrt(fan_in);
    the two projections that write into the residual stream are scaled down
    by 1/sqrt(2 L) more, as GPT-2 initialises them, so the random model is
    not chaotic: a rounding at one layer stays a rounding at the logits.
    Norm scales sit near 1 and biases near 0, both away from exactly."""
    name = path[-1]
    if name == "scale" or name == "bias":
        return 0.05
    if path == ("embed",):
        return 1.0
    if name in ("wq", "wk", "wv"):
        return float(shape[-3]) ** -0.5
    if name in ("wi", "wg", "head"):
        return float(shape[-2]) ** -0.5
    fan_in = int(np.prod(shape[-3:-1])) if path[-2] == "attn" else shape[-2]
    return (float(fan_in) ** -0.5) / float(np.sqrt(2 * m["n_layers"]))


def make_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, in ``dtype``, made by one jitted call."""
    shapes = param_shapes(m)
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(i, int) for i in x))
    specs = [(tuple(k.key for k in p), s) for p, s in leaves]

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
def _round_int8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric absmax int8 along ``axis``, returned dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _round_fp8(x: jax.Array, axis: int) -> jax.Array:
    """float8 e4m3 scaled so the absmax along ``axis`` is its largest
    finite value (448), returned dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_ROUND = {"int8": _round_int8, "fp8": _round_fp8}


def _held(x: jax.Array, low: str | None, axis: int = -1) -> jax.Array:
    """A tensor as the control holds it (per row, or per ``axis``)."""
    return _ROUND[low](x, axis) if low else x


def _linear(x: jax.Array, w: jax.Array, low: str | None) -> jax.Array:
    """x [S, K] @ w [K, N] in float32."""
    w = _held(w.astype(jnp.float32), low, 0)
    return _held(jnp.dot(x, w, precision=HI), low)


def _layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _rope(x: jax.Array, rot: int, theta: float) -> jax.Array:
    """x [S, H, hd]: rotate interleaved pairs of the first ``rot`` features
    by position."""
    s = x.shape[0]
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs      # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(*x.shape[:-1], rot), x[..., rot:]], -1)


@partial(jax.jit, static_argnames=("m", "low"))
def _block(x, blocks, layer, *, m, low):
    """One decoder layer on x [S, d] float32 (causal, over all S rows)."""
    p = jax.tree_util.tree_map(lambda a: a[layer], blocks)
    s, d = x.shape
    h, kv, hd = m.n_heads, m.n_kv, m.head_dim
    a = p["attn"]
    y = _held(_layer_norm(x, p["ln1"]), low)
    q = _linear(y, a["wq"].reshape(d, h * hd), low).reshape(s, h, hd)
    k = _linear(y, a["wk"].reshape(d, kv * hd), low).reshape(s, kv, hd)
    v = _linear(y, a["wv"].reshape(d, kv * hd), low).reshape(s, kv, hd)
    rot = int(hd * m.rope_frac)
    q = _held(_rope(q, rot, m.rope_theta), low)
    k = _held(_rope(k, rot, m.rope_theta), low)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    sc = jnp.einsum("qhc,khc->hqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None], sc, -jnp.inf)
    probs = _held(jax.nn.softmax(sc, -1), low)
    o = _held(jnp.einsum("hqk,khc->qhc", probs, v, precision=HI), low)
    x = _held(x + _linear(o.reshape(s, h * hd), a["wo"].reshape(h * hd, d), low),
              low)
    y = _held(_layer_norm(x, p["ln2"]), low)
    f = p["mlp"]
    g = _held(jax.nn.silu(_linear(y, f["wi"], low)) * _linear(y, f["wg"], low),
              low)
    return _held(x + _linear(g, f["wo"], low), low)


@partial(jax.jit, static_argnames=("low",))
def _head(x, final_norm, head, *, low):
    return _linear(_held(_layer_norm(x, final_norm), low), head, low)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@jax.jit
def _transport(x):
    return _round_int8(x, -1)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the layer reads, hashable so jit can key on them."""

    n_heads: int
    n_kv: int
    head_dim: int
    rope_frac: float
    rope_theta: float

    @classmethod
    def of(cls, m: dict) -> "Dims":
        return cls(m["n_heads"], m["n_kv"], m["head_dim"], m["rope_frac"],
                   m["rope_theta"])


def logits(params, tokens: np.ndarray, m: dict, cuts: tuple[int, ...],
           *, low: str | None = None) -> jax.Array:
    """Float32 logits [S, V] of a token row ``tokens`` [S].

    ``cuts`` are the layer indices after which the activations cross a
    segment boundary as int8.  Rows past a prompt's end only pad it: the
    attention is causal, so they change no row before them.
    """
    dims = Dims.of(m)
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for layer in range(m["n_layers"]):
        x = _block(x, params["blocks"], jnp.int32(layer), m=dims, low=low)
        if layer in cuts:
            x = _transport(x)
    return _head(x, params["final_norm"], params["head"], low=low)


@jax.jit
def served_gap(ref: jax.Array, ids: jax.Array, n: jax.Array) -> jax.Array:
    """Widest gap, over the first ``n`` rows, by which the reference's logit
    of the served id lies below the reference's best logit."""
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, ids[:, None].astype(jnp.int32), -1)[:, 0]
    rows = jnp.arange(ref.shape[0]) < n
    return jnp.max(jnp.where(rows, best - got, 0.0))
