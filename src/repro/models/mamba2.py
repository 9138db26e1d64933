"""Mamba-2 (SSD, arXiv:2405.21060) — attention-free SSM family.

Train/prefill use the chunked state-space-duality algorithm: quadratic
attention-like compute *within* chunks (matmul-friendly on the MXU) plus a
linear inter-chunk state recurrence (``lax.scan`` carry) — the TPU adaptation
of the paper's SM-centric kernel.  Decode is an O(1) recurrent state update:
no KV cache at all, which is why this arch runs the ``long_500k`` shape.

The pure-jnp intra-chunk math here is the oracle for the Pallas kernel in
``repro.kernels.ssd_chunk``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .common import (KeyGen, Params, activation, apply_norm, dense_init, embed_init,
                     norm_params, segment_blocks, segment_ends, unit_blocks)

__all__ = ["Mamba2Config", "init_params", "forward_hidden", "decode_step",
           "cache_spec", "init_cache", "ssd_chunked", "ssd_reference",
           "logits_fn", "embed_tokens", "segment_params", "segment_forward"]


@dataclass(frozen=True)
class Mamba2Config:
    name: str
    vocab: int
    d_model: int
    n_layers: int
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64            # P
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    act: str = "silu"
    norm: str = "rms"
    tie_embeddings: bool = True
    dt_min: float = 0.001
    dt_max: float = 0.1

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def params_per_block(self) -> int:
        d, di = self.d_model, self.d_inner
        in_proj = d * (2 * di + 2 * self.n_groups * self.d_state + self.n_heads)
        return in_proj + self.d_conv * self.conv_dim + di * d + 2 * di + \
            2 * self.n_heads + d

    def num_params(self) -> int:
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return emb + self.n_layers * self.params_per_block


# --------------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------------- #
def _block_params(cfg: Mamba2Config, kg: KeyGen, dtype) -> Params:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.n_heads
    proj_out = 2 * di + 2 * cfg.n_groups * cfg.d_state + h
    a = jnp.linspace(1.0, float(h), h)
    return {
        "ln": norm_params(d, cfg.norm, dtype),
        "in_proj": dense_init(kg(), (d, proj_out), dtype),
        "conv_w": dense_init(kg(), (cfg.d_conv, cfg.conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
        "A_log": jnp.log(a).astype(jnp.float32),         # A = -exp(A_log) < 0
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "out_norm": norm_params(di, cfg.norm, dtype),
        "out_proj": dense_init(kg(), (di, d), dtype),
    }


def init_params(cfg: Mamba2Config, key: jax.Array, dtype=jnp.float32) -> Params:
    kg = KeyGen(key)
    blocks = [_block_params(cfg, kg, dtype) for _ in range(cfg.n_layers)]
    params = {
        "embed": embed_init(kg(), (cfg.vocab, cfg.d_model), dtype),
        "final_norm": norm_params(cfg.d_model, cfg.norm, dtype),
        "blocks": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(kg(), (cfg.d_model, cfg.vocab), dtype)
    return params


# --------------------------------------------------------------------------- #
# SSD core
# --------------------------------------------------------------------------- #
def _segsum(x: jax.Array) -> jax.Array:
    """L[i,j] = sum_{j<k<=i} x[k] for i>=j else -inf.  x: [..., Q]."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]           # [..., i, j]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_reference(x, dt, A, Bm, Cm):
    """O(S²) oracle: y[i] = Σ_{j<=i} C_i·B_j · exp(Σ_{j<k<=i} dtA[k]) · dt_j x[j].

    x: [B,S,H,P], dt: [B,S,H], A: [H], Bm/Cm: [B,S,G,N] (G divides H).
    """
    b, s, h, p = x.shape
    g = Bm.shape[2]
    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)  # [B,S,H,N]
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    dtA = dt * A[None, None, :]                           # [B,S,H]
    L = jnp.exp(_segsum(jnp.moveaxis(dtA, 1, 2)))         # [B,H,S,S]
    scores = jnp.einsum("bihn,bjhn->bhij", Ch, Bh) * L
    xbar = (x * dt[..., None]).astype(jnp.float32)
    return jnp.einsum("bhij,bjhp->bihp", scores, xbar).astype(x.dtype)


def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int, state_in=None,
                return_state: bool = False):
    """Chunked SSD: intra-chunk quadratic + inter-chunk scan.

    Same signature/semantics as :func:`ssd_reference` plus optional initial
    state [B,H,N,P] (prefill continuation) and final-state return.
    """
    b, s, h, p = x.shape
    g = Bm.shape[2]
    n = Bm.shape[3]
    rep = h // g
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = x.shape[1] // q

    def rs(t, extra):  # [B, S, ...] -> [nc, B, q, ...]
        return jnp.moveaxis(t.reshape(b, nc, q, *extra), 1, 0)

    xc = rs(x, (h, p)).astype(jnp.float32)
    dtc = rs(dt, (h,)).astype(jnp.float32)
    Bc = jnp.repeat(rs(Bm, (g, n)), rep, axis=3).astype(jnp.float32)
    Cc = jnp.repeat(rs(Cm, (g, n)), rep, axis=3).astype(jnp.float32)

    state0 = (jnp.zeros((b, h, n, p), jnp.float32) if state_in is None
              else state_in.astype(jnp.float32))

    def step(state, inp):
        xq, dtq, Bq, Cq = inp                       # [B,q,H,*]
        dtA = dtq * A[None, None, :]                # [B,q,H]
        cums = jnp.cumsum(dtA, axis=1)              # Σ_{k<=i}
        L = jnp.exp(_segsum(jnp.moveaxis(dtA, 1, 2)))        # [B,H,q,q]
        scores = jnp.einsum("bihn,bjhn->bhij", Cq, Bq) * L
        xbar = xq * dtq[..., None]
        y_intra = jnp.einsum("bhij,bjhp->bihp", scores, xbar)
        # contribution of the carried state: decay from chunk start to i
        decay_i = jnp.exp(cums)                     # [B,q,H]
        y_inter = jnp.einsum("bihn,bhnp->bihp", Cq * decay_i[..., None], state)
        # new chunk state: Σ_j exp(cum_last - cum_j) B_j ⊗ xbar_j
        decay_out = jnp.exp(cums[:, -1:, :] - cums)  # [B,q,H]
        state_c = jnp.einsum("bjhn,bjhp->bhnp", Bq * decay_out[..., None], xbar)
        state = state * jnp.exp(cums[:, -1, :])[:, :, None, None] + state_c
        return state, y_intra + y_inter

    state, yc = jax.lax.scan(step, state0, (xc, dtc, Bc, Cc))
    y = jnp.moveaxis(yc, 0, 1).reshape(b, nc * q, h, p)[:, :s]
    y = y.astype(x.dtype)
    return (y, state) if return_state else y


# --------------------------------------------------------------------------- #
# block forward
# --------------------------------------------------------------------------- #
def _split_proj(z: jax.Array, cfg: Mamba2Config):
    di, gn, h = cfg.d_inner, cfg.n_groups * cfg.d_state, cfg.n_heads
    zg = z[..., :di]
    xh = z[..., di:2 * di]
    Bm = z[..., 2 * di:2 * di + gn]
    Cm = z[..., 2 * di + gn:2 * di + 2 * gn]
    dt = z[..., 2 * di + 2 * gn:]
    return zg, xh, Bm, Cm, dt


def _conv1d(u: jax.Array, w: jax.Array, bias: jax.Array,
            prev: jax.Array | None = None):
    """Causal depthwise conv: u [B,S,C], w [K,C]. prev: [B,K-1,C] history."""
    k = w.shape[0]
    if prev is None:
        up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        up = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    out = sum(up[:, i:i + u.shape[1], :] * w[i][None, None, :] for i in range(k))
    return out + bias[None, None, :]


def block_forward(x, p, cfg: Mamba2Config, *, state_in=None, conv_in=None,
                  return_state: bool = False):
    """x: [B,S,d]. Optional carried SSM/conv state for chunked prefill."""
    h = apply_norm(x, p["ln"], cfg.norm)
    z = h @ p["in_proj"].astype(h.dtype)
    zg, xh, Bm, Cm, dt = _split_proj(z, cfg)
    conv_inp = jnp.concatenate([xh, Bm, Cm], axis=-1)
    conv_out = activation(
        _conv1d(conv_inp, p["conv_w"].astype(h.dtype), p["conv_b"].astype(h.dtype),
                conv_in),
        cfg.act)
    di, gn = cfg.d_inner, cfg.n_groups * cfg.d_state
    xh = conv_out[..., :di]
    Bm = conv_out[..., di:di + gn]
    Cm = conv_out[..., di + gn:]
    b, s, _ = x.shape
    xheads = xh.reshape(b, s, cfg.n_heads, cfg.head_dim)
    Bg = Bm.reshape(b, s, cfg.n_groups, cfg.d_state)
    Cg = Cm.reshape(b, s, cfg.n_groups, cfg.d_state)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    out = ssd_chunked(xheads, dtv, A, Bg, Cg, chunk=cfg.chunk,
                      state_in=state_in, return_state=return_state)
    y, state = out if return_state else (out, None)
    y = y + xheads * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(b, s, di)
    y = apply_norm(y * activation(zg, cfg.act), p["out_norm"], cfg.norm)
    y = y @ p["out_proj"].astype(y.dtype)
    if return_state:
        new_conv = conv_inp[:, -(cfg.d_conv - 1):, :]
        return x + y, (state, new_conv)
    return x + y


def embed_tokens(params, cfg: Mamba2Config, tokens, compute_dtype=jnp.bfloat16):
    return params["embed"].astype(compute_dtype)[tokens]


def forward_hidden(params, cfg: Mamba2Config, x, *, remat: bool = True):
    def body(h, lp):
        return block_forward(h, lp, cfg), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return apply_norm(x, params["final_norm"], cfg.norm)


def logits_fn(params, cfg: Mamba2Config, h):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (h @ w.astype(h.dtype)).astype(jnp.float32)


def segment_params(cfg: Mamba2Config, params: Params, lo: int, hi: int) -> Params:
    """What graph units [lo, hi) run over: the tree their node holds."""
    seg = segment_ends(cfg, params, lo, hi)
    blocks = unit_blocks(cfg, lo, hi)
    if blocks:
        seg["blocks"] = jax.tree_util.tree_map(
            lambda a: a[blocks.start:blocks.stop], params["blocks"])
    return seg


def segment_forward(cfg: Mamba2Config, params: Params, lo: int, hi: int,
                    x: jax.Array) -> jax.Array:
    """Graph units [lo, hi) over their :func:`segment_params` view."""
    if lo == 0:
        x = embed_tokens(params, cfg, x)
    blocks = unit_blocks(cfg, lo, hi)
    if blocks:
        def body(h, lp):
            return block_forward(h, lp, cfg), None

        x, _ = jax.lax.scan(body, x, segment_blocks(params["blocks"], len(blocks)))
    if hi == cfg.n_layers + 2:
        x = apply_norm(x, params["final_norm"], cfg.norm)
        return logits_fn(params, cfg, x)
    return x


# --------------------------------------------------------------------------- #
# decode: O(1) state recurrence
# --------------------------------------------------------------------------- #
def cache_spec(cfg: Mamba2Config, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Any:
    del max_len  # state size is independent of sequence length
    return {
        "ssm": jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, cfg.n_heads, cfg.d_state, cfg.head_dim),
            jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, cfg.d_conv - 1, cfg.conv_dim), dtype),
    }


def init_cache(cfg: Mamba2Config, batch: int, max_len: int, dtype=jnp.bfloat16):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_spec(cfg, batch, max_len, dtype))


def decode_step(params, cfg: Mamba2Config, cache, tokens, pos):
    """tokens: [B] int32; pos unused (stateful). Returns (logits, cache)."""
    del pos
    x = embed_tokens(params, cfg, tokens[:, None])

    def body(h, inputs):
        lp, ssm, conv = inputs
        hin = apply_norm(h, lp["ln"], cfg.norm)
        z = hin @ lp["in_proj"].astype(hin.dtype)
        zg, xh, Bm, Cm, dt = _split_proj(z, cfg)
        conv_inp = jnp.concatenate([xh, Bm, Cm], axis=-1)     # [B,1,C]
        full = jnp.concatenate([conv.astype(h.dtype), conv_inp], axis=1)
        conv_out = activation(
            (full * lp["conv_w"].astype(h.dtype)[None]).sum(axis=1)
            + lp["conv_b"].astype(h.dtype)[None], cfg.act)    # [B,C]
        di, gn = cfg.d_inner, cfg.n_groups * cfg.d_state
        b = h.shape[0]
        xh1 = conv_out[:, :di].reshape(b, cfg.n_heads, cfg.head_dim)
        Bg = conv_out[:, di:di + gn].reshape(b, cfg.n_groups, cfg.d_state)
        Cg = conv_out[:, di + gn:].reshape(b, cfg.n_groups, cfg.d_state)
        rep = cfg.n_heads // cfg.n_groups
        Bh = jnp.repeat(Bg, rep, axis=1).astype(jnp.float32)  # [B,H,N]
        Ch = jnp.repeat(Cg, rep, axis=1).astype(jnp.float32)
        dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + lp["dt_bias"][None])
        A = -jnp.exp(lp["A_log"])                             # [H]
        decay = jnp.exp(dtv * A[None])[..., None, None]       # [B,H,1,1]
        xbar = (xh1 * dtv[..., None]).astype(jnp.float32)     # [B,H,P]
        ssm = ssm * decay + Bh[..., :, None] * xbar[..., None, :]  # [B,H,N,P]
        y = jnp.einsum("bhn,bhnp->bhp", Ch, ssm)
        y = y.astype(h.dtype) + xh1 * lp["D"][None, :, None].astype(h.dtype)
        y = y.reshape(b, 1, di)
        y = apply_norm(y * activation(zg, cfg.act), lp["out_norm"], cfg.norm)
        y = y @ lp["out_proj"].astype(y.dtype)
        new_conv = full[:, 1:, :].astype(conv.dtype)
        return h + y, (ssm, new_conv)

    x, (ssm, conv) = jax.lax.scan(
        body, x, (params["blocks"], cache["ssm"], cache["conv"]))
    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(params, cfg, x)[:, 0]
    return logits, {"ssm": ssm, "conv": conv}
