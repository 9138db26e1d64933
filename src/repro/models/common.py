"""Shared building blocks for all model families (pure-functional JAX)."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # nested dict of jnp arrays

DEFAULT_COMPUTE = jnp.bfloat16


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 style logit soft-capping: cap·tanh(x/cap)."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    s = (1.0 + scale.astype(jnp.float32)) if plus_one else scale.astype(jnp.float32)
    return (x * s).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(x: jax.Array, p: Params, kind: str, **kw) -> jax.Array:
    if kind == "rms":
        return rms_norm(x, p["scale"], **kw)
    if kind == "rms1":  # gemma-style (1 + scale)
        return rms_norm(x, p["scale"], plus_one=True, **kw)
    if kind == "ln":
        return layer_norm(x, p["scale"], p["bias"], **kw)
    raise ValueError(kind)


def norm_params(d: int, kind: str, dtype=jnp.float32) -> Params:
    if kind in ("rms", "rms1"):
        init = jnp.zeros if kind == "rms1" else jnp.ones
        return {"scale": init((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(kind)


# --------------------------------------------------------------------------- #
# rotary embeddings
# --------------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float = 10_000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling, as a published config's ``rope_scaling`` of type
    ``yarn`` gives it (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``).

    Frequencies of the lowest indices (fast rotations, above ``beta_fast``
    turns over the original context) stay as they are, those of the highest
    (below ``beta_slow`` turns) are divided by ``factor``, and a linear ramp
    blends the two in between.  Rotated features are multiplied by
    ``rope_mscale``; the attention that uses them multiplies its softmax
    scale by ``softmax_mscale``.
    """

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _correction_dim(self, turns: float, dim: int, theta: float) -> float:
        return (dim * math.log(self.original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    def ramp(self, dim: int, theta: float) -> tuple[int, int]:
        """First and last pair index of the blend."""
        lo = math.floor(self._correction_dim(self.beta_fast, dim, theta))
        hi = math.ceil(self._correction_dim(self.beta_slow, dim, theta))
        return max(lo, 0), min(hi, dim - 1)

    def frequencies(self, dim: int, theta: float) -> jax.Array:
        extra = rope_frequencies(dim, theta)
        lo, hi = self.ramp(dim, theta)
        span = (hi - lo) if hi > lo else 0.001
        interp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / span,
                          0.0, 1.0)
        return extra / self.factor * interp + extra * (1.0 - interp)

    @property
    def rope_mscale(self) -> float:
        return (yarn_mscale(self.factor, self.mscale)
                / yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        if not self.mscale_all_dim:
            return 1.0
        return yarn_mscale(self.factor, self.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
               rope_dim: int | None = None, yarn: YaRN | None = None
               ) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S].

    ``rope_dim``: rotate only the first ``rope_dim`` features (partial RoPE).
    ``yarn``: YaRN's blended frequencies and magnitude (see :class:`YaRN`).
    Uses the interleaved-pairs convention throughout the repo.
    """
    hd = x.shape[-1]
    rd = hd if rope_dim is None else rope_dim
    xr, xp = x[..., :rd], x[..., rd:]
    freqs = (rope_frequencies(rd, theta) if yarn is None
             else yarn.frequencies(rd, theta))                # [rd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs    # [..., S, rd/2]
    cos = jnp.cos(ang)[..., None, :]                          # [..., S, 1, rd/2]
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None and yarn.rope_mscale != 1.0:
        cos, sin = cos * yarn.rope_mscale, sin * yarn.rope_mscale
    x1 = xr[..., 0::2].astype(jnp.float32)
    x2 = xr[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rd < hd else out


# --------------------------------------------------------------------------- #
# initializers (shape-only friendly: usable under jax.eval_shape)
# --------------------------------------------------------------------------- #
def dense_init(key, shape, dtype=jnp.float32, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


class KeyGen:
    """Deterministic PRNG key dispenser for building param trees."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


def count_params(params: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def cast_tree(params: Params, dtype) -> Params:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


def unit_blocks(cfg: Any, lo: int, hi: int) -> range:
    """The blocks among graph units [lo, hi) of a model whose units are
    [embed, block_0 .. block_{n_layers-1}, lm_head]."""
    return range(max(lo, 1) - 1, min(hi - 1, cfg.n_layers))


def segment_ends(cfg: Any, params: Params, lo: int, hi: int) -> dict:
    """The embedding, final norm and head that graph units [lo, hi) need;
    a tied head reads the embedding."""
    last = hi == cfg.n_layers + 2
    seg = {"embed": params["embed"]} if lo == 0 or (last and cfg.tie_embeddings) else {}
    if last:
        seg["final_norm"] = params["final_norm"]
        if not cfg.tie_embeddings:
            seg["head"] = params["head"]
    return seg


def segment_blocks(blocks: Any, n: int) -> Any:
    """A segment view's blocks (a list, or one tree stacked on axis 0),
    checked while tracing to be the segment's ``n``: it runs every block
    it is handed, so another range's view would run the wrong layers."""
    held = len(blocks) if isinstance(blocks, list) else jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if held != n:
        raise ValueError(f"a segment of {n} blocks was handed {held}")
    return blocks
