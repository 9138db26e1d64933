"""Griffin / RecurrentGemma (arXiv:2402.19427) — RG-LRU + local-attention hybrid.

Pattern: (recurrent, recurrent, local-attention) repeated 1:2, each layer being
a temporal-mixing residual followed by a GeGLU MLP residual.  Decode state is
O(1) per recurrent layer (LRU state + conv tail) and O(window) per attention
layer (ring-buffer KV cache, window=2048) — which is why this arch runs the
``long_500k`` shape with a bounded cache.

The associative-scan linear recurrence here is the oracle for the Pallas
kernel in ``repro.kernels.rglru``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.context import constrain
from .common import (
    KeyGen,
    Params,
    activation,
    apply_norm,
    apply_rope,
    dense_init,
    embed_init,
    norm_params,
    segment_ends,
    softcap,
    unit_blocks,
)

__all__ = ["GriffinConfig", "init_params", "forward_hidden", "decode_step",
           "cache_spec", "init_cache", "rglru", "rglru_reference", "logits_fn",
           "embed_tokens", "segment_params", "segment_forward"]

NEG_INF = -2.0e38
_C = 8.0  # RG-LRU decay sharpness constant


@dataclass(frozen=True)
class GriffinConfig:
    name: str
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    head_dim: int
    d_ff: int
    lru_width: int = 0            # 0 -> d_model
    n_lru_heads: int = 16         # block-diagonal gate heads
    window: int = 2048
    pattern: tuple[str, ...] = ("rec", "rec", "attn")
    d_conv: int = 4
    act: str = "gelu"
    norm: str = "rms1"            # gemma-style (1+scale) RMSNorm
    rope_theta: float = 10_000.0
    final_softcap: float = 30.0
    tie_embeddings: bool = True
    embed_scale: bool = True

    @property
    def w(self) -> int:
        return self.lru_width or self.d_model

    def layer_kinds(self) -> list[str]:
        return [self.pattern[i % len(self.pattern)] for i in range(self.n_layers)]

    def tail_kinds(self) -> list[str]:
        glen = len(self.pattern)
        return self.layer_kinds()[(self.n_layers // glen) * glen:]

    @property
    def n_rec(self) -> int:
        return sum(k == "rec" for k in self.layer_kinds())

    @property
    def n_attn(self) -> int:
        return self.n_layers - self.n_rec

    def params_per_layer(self, kind: str) -> int:
        d, w = self.d_model, self.w
        mlp = 3 * d * self.d_ff
        if kind == "rec":
            gates = 2 * self.n_lru_heads * (w // self.n_lru_heads) ** 2
            return 2 * d * w + self.d_conv * w + gates + 2 * w + w * d + mlp
        attn = d * self.n_heads * self.head_dim + 2 * d * self.head_dim + \
            self.n_heads * self.head_dim * d
        return attn + mlp

    def num_params(self) -> int:
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return emb + sum(self.params_per_layer(k) for k in self.layer_kinds())


# --------------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------------- #
def _rec_params(cfg: GriffinConfig, kg: KeyGen, dtype) -> Params:
    d, w, nb = cfg.d_model, cfg.w, cfg.n_lru_heads
    bd = w // nb
    return {
        "ln": norm_params(d, cfg.norm, dtype),
        "wx": dense_init(kg(), (d, w), dtype),
        "wy": dense_init(kg(), (d, w), dtype),
        "conv_w": dense_init(kg(), (cfg.d_conv, w), dtype, scale=0.5),
        "conv_b": jnp.zeros((w,), dtype),
        "gate_a": dense_init(kg(), (nb, bd, bd), dtype),
        "gate_x": dense_init(kg(), (nb, bd, bd), dtype),
        "lam": jnp.full((w,), 0.7, jnp.float32),   # softplus^-1 gives a≈0.9-ish
        "wo": dense_init(kg(), (w, d), dtype),
    }


def _attn_params(cfg: GriffinConfig, kg: KeyGen, dtype) -> Params:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "ln": norm_params(d, cfg.norm, dtype),
        "wq": dense_init(kg(), (d, h, hd), dtype),
        "wk": dense_init(kg(), (d, 1, hd), dtype),
        "wv": dense_init(kg(), (d, 1, hd), dtype),
        "wo": dense_init(kg(), (h, hd, d), dtype),
    }


def _mlp_params(cfg: GriffinConfig, kg: KeyGen, dtype) -> Params:
    d = cfg.d_model
    return {
        "ln": norm_params(d, cfg.norm, dtype),
        "wi": dense_init(kg(), (d, cfg.d_ff), dtype),
        "wg": dense_init(kg(), (d, cfg.d_ff), dtype),
        "wo": dense_init(kg(), (cfg.d_ff, d), dtype),
    }


def init_params(cfg: GriffinConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    kg = KeyGen(key)
    kinds = cfg.layer_kinds()
    glen = len(cfg.pattern)
    n_groups = cfg.n_layers // glen
    rem = kinds[n_groups * glen:]

    def stack(items):
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)

    groups = []
    for _ in range(n_groups):
        grp = {}
        for i, kind in enumerate(cfg.pattern):
            tm = _rec_params(cfg, kg, dtype) if kind == "rec" else \
                _attn_params(cfg, kg, dtype)
            grp[f"t{i}"] = tm
            grp[f"m{i}"] = _mlp_params(cfg, kg, dtype)
        groups.append(grp)
    params = {
        "embed": embed_init(kg(), (cfg.vocab, cfg.d_model), dtype),
        "final_norm": norm_params(cfg.d_model, cfg.norm, dtype),
        "groups": stack(groups) if groups else {},
        # layer kinds for the tail live in the config (cfg.tail_kinds()), not
        # in the params pytree — jit arguments must be arrays only
        "tail": [
            {"t": (_rec_params(cfg, kg, dtype) if k == "rec"
                   else _attn_params(cfg, kg, dtype)),
             "m": _mlp_params(cfg, kg, dtype)}
            for k in rem
        ],
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(kg(), (cfg.d_model, cfg.vocab), dtype)
    return params


# --------------------------------------------------------------------------- #
# RG-LRU
# --------------------------------------------------------------------------- #
def _lru_gates(u: jax.Array, p: Params, cfg: GriffinConfig):
    """u: [B,S,w] -> (a, gated_input) both [B,S,w] fp32."""
    b, s, w = u.shape
    nb = cfg.n_lru_heads
    uh = u.reshape(b, s, nb, w // nb)
    r = jax.nn.sigmoid(jnp.einsum(
        "bsnd,nde->bsne", uh, p["gate_a"].astype(u.dtype)).astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum(
        "bsnd,nde->bsne", uh, p["gate_x"].astype(u.dtype)).astype(jnp.float32))
    r = r.reshape(b, s, w)
    i = i.reshape(b, s, w)
    log_a = -_C * jax.nn.softplus(p["lam"])[None, None, :] * r
    a = jnp.exp(log_a)
    x_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * \
        (i * u.astype(jnp.float32))
    return a, x_in


def rglru_reference(a: jax.Array, x: jax.Array, h0: jax.Array | None = None):
    """Sequential oracle: h_t = a_t h_{t-1} + x_t. a,x: [B,S,w] fp32."""
    b, s, w = x.shape
    h = jnp.zeros((b, w), jnp.float32) if h0 is None else h0

    def step(h, inp):
        at, xt = inp
        h = at * h + xt
        return h, h

    _, hs = jax.lax.scan(step, h, (jnp.moveaxis(a, 1, 0), jnp.moveaxis(x, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)


def rglru(a: jax.Array, x: jax.Array, h0: jax.Array | None = None):
    """Parallel linear recurrence via associative_scan (log-depth)."""
    if h0 is not None:
        # fold the carried state into the first step: h_0 = a_0 h_init + x_0
        # (a_0 itself never multiplies later terms in the scan, so no reset)
        x = x.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, x), axis=1)
    return h


# --------------------------------------------------------------------------- #
# temporal blocks
# --------------------------------------------------------------------------- #
def _conv1d(u, w, bias, prev=None):
    k = w.shape[0]
    if prev is None:
        up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        up = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    out = sum(up[:, i:i + u.shape[1], :] * w[i][None, None, :] for i in range(k))
    return out + bias[None, None, :]


def rec_forward(x, p, cfg: GriffinConfig, *, state=None, conv_prev=None,
                return_state: bool = False):
    """Recurrent temporal block. x: [B,S,d]."""
    h = apply_norm(x, p["ln"], cfg.norm)
    branch_y = activation(constrain(h @ p["wy"].astype(h.dtype), "ff"), cfg.act)
    u = constrain(h @ p["wx"].astype(h.dtype), "ff")
    u_conv = _conv1d(u, p["conv_w"].astype(h.dtype), p["conv_b"].astype(h.dtype),
                     conv_prev)
    a, xin = _lru_gates(u_conv, p, cfg)
    hs = rglru(a, xin, h0=state)                              # [B,S,w] fp32
    y = constrain((hs.astype(h.dtype) * branch_y) @ p["wo"].astype(h.dtype),
                  "hidden_full")
    if return_state:
        return x + y, (hs[:, -1], u[:, -(cfg.d_conv - 1):, :])
    return x + y


def attn_forward(x, p, cfg: GriffinConfig, *, q_offset=0,
                 return_kv: bool = False):
    from .attention import chunked_attention

    h = apply_norm(x, p["ln"], cfg.norm)
    q = constrain(jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype)),
                  "heads")
    k = jnp.einsum("bsd,dgk->bsgk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dgk->bsgk", h, p["wv"].astype(h.dtype))
    pos = q_offset + jnp.arange(x.shape[1])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=True, window=cfg.window,
                          kv_block=min(1024, max(x.shape[1], 16)))
    y = constrain(jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype)),
                  "hidden_full")
    if return_kv:
        return x + y, (k, v)
    return x + y


def mlp_forward(x, p, cfg: GriffinConfig):
    h = apply_norm(x, p["ln"], cfg.norm)
    y = activation(constrain(h @ p["wi"].astype(h.dtype), "ff"), cfg.act) * \
        constrain(h @ p["wg"].astype(h.dtype), "ff")
    return x + constrain(y @ p["wo"].astype(y.dtype), "hidden_full")


# --------------------------------------------------------------------------- #
# full forward (train / prefill compute)
# --------------------------------------------------------------------------- #
def embed_tokens(params, cfg: GriffinConfig, tokens, compute_dtype=jnp.bfloat16):
    x = params["embed"].astype(compute_dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), compute_dtype)
    return x


def forward_hidden(params, cfg: GriffinConfig, x, *, remat: bool = True):
    glen = len(cfg.pattern)

    def group_body(h, gp):
        for i, kind in enumerate(cfg.pattern):
            if kind == "rec":
                h = rec_forward(h, gp[f"t{i}"], cfg)
            else:
                h = attn_forward(h, gp[f"t{i}"], cfg)
            h = mlp_forward(h, gp[f"m{i}"], cfg)
        return h, None

    if remat:
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    if params["groups"]:
        x, _ = jax.lax.scan(group_body, x, params["groups"])
    for layer, kind in zip(params["tail"], cfg.tail_kinds()):
        if kind == "rec":
            x = rec_forward(x, layer["t"], cfg)
        else:
            x = attn_forward(x, layer["t"], cfg)
        x = mlp_forward(x, layer["m"], cfg)
    return apply_norm(x, params["final_norm"], cfg.norm)


def logits_fn(params, cfg: GriffinConfig, h):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return softcap((h @ w.astype(h.dtype)).astype(jnp.float32), cfg.final_softcap)


def segment_params(cfg: GriffinConfig, params: Params, lo: int, hi: int) -> Params:
    """What graph units [lo, hi) run over: the tree their node holds, with
    every layer (``groups`` and ``tail`` whole, indexed by layer)."""
    seg = segment_ends(cfg, params, lo, hi)
    if unit_blocks(cfg, lo, hi):
        seg["groups"], seg["tail"] = params["groups"], params["tail"]
    return seg


def segment_forward(cfg: GriffinConfig, params: Params, lo: int, hi: int,
                    x: jax.Array) -> jax.Array:
    """Graph units [lo, hi) over their :func:`segment_params` view."""
    if lo == 0:
        x = embed_tokens(params, cfg, x)
    kinds = cfg.layer_kinds()
    glen = len(cfg.pattern)
    n_groups = cfg.n_layers // glen
    for li in unit_blocks(cfg, lo, hi):
        if li < n_groups * glen:
            g, i = divmod(li, glen)
            gp = jax.tree_util.tree_map(lambda a, g=g: a[g], params["groups"])
            tm, mp = gp[f"t{i}"], gp[f"m{i}"]
        else:
            tl = params["tail"][li - n_groups * glen]
            tm, mp = tl["t"], tl["m"]
        if kinds[li] == "rec":
            x = rec_forward(x, tm, cfg)
        else:
            x = attn_forward(x, tm, cfg)
        # round at every layer boundary as a cut would, so that where the
        # split falls never changes what the layers compute
        x = jax.lax.optimization_barrier(mlp_forward(x, mp, cfg))
    if hi == cfg.n_layers + 2:
        x = apply_norm(x, params["final_norm"], cfg.norm)
        return logits_fn(params, cfg, x)
    return x


# --------------------------------------------------------------------------- #
# decode with ring-buffer attention cache + O(1) recurrent state
# --------------------------------------------------------------------------- #
def cache_spec(cfg: GriffinConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    w = min(cfg.window, max_len)
    return {
        "lru": jax.ShapeDtypeStruct((cfg.n_rec, batch, cfg.w), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (cfg.n_rec, batch, cfg.d_conv - 1, cfg.w), dtype),
        "k": jax.ShapeDtypeStruct((cfg.n_attn, batch, w, 1, cfg.head_dim), dtype),
        "v": jax.ShapeDtypeStruct((cfg.n_attn, batch, w, 1, cfg.head_dim), dtype),
        "slot_pos": jax.ShapeDtypeStruct((cfg.n_attn, w), jnp.int32),
    }


def init_cache(cfg: GriffinConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    spec = cache_spec(cfg, batch, max_len, dtype)
    cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    cache["slot_pos"] = jnp.full(spec["slot_pos"].shape, -1, jnp.int32)
    return cache


def _ring_attn_decode(x, p, cfg: GriffinConfig, kc, vc, slot_pos, pos):
    """x: [B,1,d]; ring cache kc/vc: [B,W,1,hd]; slot_pos: [W]."""
    b = x.shape[0]
    w = kc.shape[1]
    h = apply_norm(x, p["ln"], cfg.norm)
    posv = pos + jnp.zeros((1,), jnp.int32)
    q = apply_rope(jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype)),
                   posv, cfg.rope_theta)[:, 0]               # [B,H,hd]
    kn = apply_rope(jnp.einsum("bsd,dgk->bsgk", h, p["wk"].astype(h.dtype)),
                    posv, cfg.rope_theta)[:, 0]              # [B,1,hd]
    vn = jnp.einsum("bsd,dgk->bsgk", h, p["wv"].astype(h.dtype))[:, 0]
    slot = jnp.mod(pos, w)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, kn[:, None].astype(kc.dtype),
                                             slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, vn[:, None].astype(vc.dtype),
                                             slot, axis=1)
    slot_pos = slot_pos.at[slot].set(jnp.asarray(pos, jnp.int32))
    scores = jnp.einsum("bhk,bwgk->bhw", q.astype(jnp.float32) * cfg.head_dim ** -0.5,
                        kc.astype(jnp.float32))
    valid = (slot_pos >= 0) & (slot_pos <= pos) & (slot_pos > pos - cfg.window)
    scores = jnp.where(valid[None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhw,bwgk->bhk", probs, vc.astype(jnp.float32))
    y = jnp.einsum("bhk,hkd->bd", o.astype(h.dtype), p["wo"].astype(h.dtype))
    return x + y[:, None], kc, vc, slot_pos


def _rec_decode(x, p, cfg: GriffinConfig, lru, conv):
    h = apply_norm(x, p["ln"], cfg.norm)
    branch_y = activation(h @ p["wy"].astype(h.dtype), cfg.act)
    u = h @ p["wx"].astype(h.dtype)                           # [B,1,w]
    full = jnp.concatenate([conv.astype(h.dtype), u], axis=1)  # [B,K,w]
    u_conv = (full * p["conv_w"].astype(h.dtype)[None]).sum(axis=1, keepdims=True) \
        + p["conv_b"].astype(h.dtype)[None, None]
    a, xin = _lru_gates(u_conv, p, cfg)                       # [B,1,w]
    hnew = a[:, 0] * lru + xin[:, 0]
    y = (hnew[:, None].astype(h.dtype) * branch_y) @ p["wo"].astype(h.dtype)
    return x + y, hnew, full[:, 1:].astype(conv.dtype)


def decode_step(params, cfg: GriffinConfig, cache, tokens, pos):
    x = embed_tokens(params, cfg, tokens[:, None])
    kinds = cfg.layer_kinds()
    glen = len(cfg.pattern)
    n_groups = cfg.n_layers // glen
    ri = ai = 0
    lru, conv = list(cache["lru"]), list(cache["conv"])
    kc, vc, sp = list(cache["k"]), list(cache["v"]), list(cache["slot_pos"])

    def run_layer(x, tm, mp, kind):
        nonlocal ri, ai
        if kind == "rec":
            x, lru[ri], conv[ri] = _rec_decode(x, tm, cfg, lru[ri], conv[ri])
            ri += 1
        else:
            x, kc[ai], vc[ai], sp[ai] = _ring_attn_decode(
                x, tm, cfg, kc[ai], vc[ai], sp[ai], pos)
            ai += 1
        return mlp_forward(x, mp, cfg)

    for gidx in range(n_groups):
        gp = jax.tree_util.tree_map(lambda a, g=gidx: a[g], params["groups"])
        for i, kind in enumerate(cfg.pattern):
            x = run_layer(x, gp[f"t{i}"], gp[f"m{i}"], kind)
    for layer, kind in zip(params["tail"], cfg.tail_kinds()):
        x = run_layer(x, layer["t"], layer["m"], kind)

    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(params, cfg, x)[:, 0]
    new_cache = {
        "lru": jnp.stack(lru), "conv": jnp.stack(conv),
        "k": jnp.stack(kc), "v": jnp.stack(vc), "slot_pos": jnp.stack(sp),
    }
    return logits, new_cache
