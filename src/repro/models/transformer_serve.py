"""Serving paths for the composable transformer: prefill + single-token decode.

Decode uses per-layer KV caches stacked along a leading layer axis so the
layer loop stays a ``lax.scan`` (cache enters as scanned xs and leaves as
stacked ys — O(1) HLO for 64-layer models).

MLA decode is the *absorbed* formulation: only the 512-dim latent ``c_kv`` and
the 64-dim shared RoPE key are cached (the paper-exact memory saving), and
W_uk/W_uv are folded into the query/output sides so no per-step decompression
of K/V ever materializes.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .attention import decode_attention, update_kv_cache
from .common import Params, apply_norm, apply_rope, softcap
from .transformer import (
    TransformerConfig,
    _static_window,
    block_forward,
    dense_ffn,
    embed_tokens,
    logits_fn,
    moe_ffn,
)

NEG_INF = -2.0e38


# --------------------------------------------------------------------------- #
# cache specs
# --------------------------------------------------------------------------- #
def cache_spec(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct pytree for the KV cache (leading axis = layer)."""
    moe = cfg.moe
    n_lead = moe.first_dense_layers if moe else 0
    n_scan = cfg.n_layers - n_lead

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    if cfg.mla is not None:
        m = cfg.mla

        def mk(n):
            return {"ckv": sds(n, batch, max_len, m.kv_lora),
                    "kr": sds(n, batch, max_len, m.rope_head_dim)}
    else:
        def mk(n):
            return {"k": sds(n, batch, max_len, cfg.n_kv, cfg.hd),
                    "v": sds(n, batch, max_len, cfg.n_kv, cfg.hd)}
    out = {"blocks": mk(n_scan)}
    if n_lead:
        out["lead"] = mk(n_lead)
    return out


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Any:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_spec(cfg, batch, max_len, dtype)
    )


# --------------------------------------------------------------------------- #
# prefill: full forward that also fills the cache
# --------------------------------------------------------------------------- #
def _project_kv(x, p, cfg: TransformerConfig, pos):
    if cfg.mla is not None:
        m = cfg.mla
        ckv = apply_norm(
            jnp.einsum("bsd,dl->bsl", x, p["wdkv"].astype(x.dtype)),
            p["kv_ln"], "rms")
        kr = jnp.einsum("bsd,dr->bsr", x, p["wkr"].astype(x.dtype))
        kr = m.rope(kr[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
        return {"ckv": ckv, "kr": kr}
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        k = apply_norm(k, p["k_norm"], "rms")
    rd = int(cfg.hd * cfg.rope_frac) if cfg.rope_frac < 1.0 else None
    k = apply_rope(k, pos, cfg.rope_theta, rope_dim=rd)
    return {"k": k, "v": v}


def prefill(params: Params, cfg: TransformerConfig, tokens_or_embeds: jax.Array,
            *, prefix_embeds: jax.Array | None = None, remat: bool = True,
            kv_block: int = 1024, cache_dtype=jnp.bfloat16,
            max_len: int | None = None):
    """Returns (last-position logits [B, V], cache sized for ``max_len``).

    ``max_len`` defaults to the prompt length; serving must pass prompt +
    decode-budget so decode steps have free cache slots (dynamic_update_slice
    CLAMPS out-of-range indices — an exactly-sized cache would silently
    overwrite its last entry).
    """
    if cfg.embed_inputs:
        x = tokens_or_embeds
    else:
        x = embed_tokens(params, cfg, tokens_or_embeds)
    if prefix_embeds is not None:
        pe = prefix_embeds.astype(x.dtype) @ params["prefix_proj"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    b, s, _ = x.shape
    pos = jnp.arange(s)
    win_np = cfg.windows()
    moe = cfg.moe
    n_lead = moe.first_dense_layers if moe else 0
    lead_cache = []
    if n_lead:
        dense_cfg = dataclasses.replace(cfg, moe=None,
                                        d_ff=moe.dense_d_ff or cfg.d_ff)
        for lp in params["lead_blocks"]:
            lead_cache.append(
                jax.tree_util.tree_map(
                    lambda a: a.astype(cache_dtype),
                    _project_kv(apply_norm(x, lp["ln1"], cfg.norm), lp["attn"],
                                dense_cfg, pos)))
            x = block_forward(x, lp, dense_cfg, window=0, kv_block=kv_block)

    window = _static_window(cfg)

    def body(h, inputs):
        lp, w = inputs if window is None else (inputs, window)
        kv = _project_kv(apply_norm(h, lp["ln1"], cfg.norm), lp["attn"], cfg, pos)
        kv = jax.tree_util.tree_map(lambda a: a.astype(cache_dtype), kv)
        h = block_forward(h, lp, cfg, window=w, kv_block=kv_block)
        return h, kv

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = params["blocks"] if window is not None else (
        params["blocks"], jnp.asarray(win_np)[n_lead:])
    x, scan_cache = jax.lax.scan(body, x, xs)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(params, cfg, x[:, -1:, :])[:, 0]
    cache = {"blocks": scan_cache}
    if n_lead:
        cache["lead"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lead_cache)
    if max_len is not None and max_len > s:
        pad = max_len - s
        cache = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, pad)] +
                              [(0, 0)] * (a.ndim - 3)), cache)
    return logits, cache


# --------------------------------------------------------------------------- #
# decode: one token for the whole batch
# --------------------------------------------------------------------------- #
def _decode_attn_dense(x, p, cfg: TransformerConfig, layer_cache, pos, window):
    """x: [B,1,d]; cache: {k,v}: [B,S,KV,hd]. Returns (out [B,1,d], new cache)."""
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    kv = {"k": jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(x.dtype)),
          "v": jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(x.dtype))}
    if cfg.qk_norm:
        q = apply_norm(q, p["q_norm"], "rms")
        kv["k"] = apply_norm(kv["k"], p["k_norm"], "rms")
    posv = pos + jnp.zeros((1,), jnp.int32)
    rd = int(cfg.hd * cfg.rope_frac) if cfg.rope_frac < 1.0 else None
    q = apply_rope(q, posv, cfg.rope_theta, rope_dim=rd)
    kv["k"] = apply_rope(kv["k"], posv, cfg.rope_theta, rope_dim=rd)
    k_cache, v_cache = update_kv_cache(
        layer_cache["k"], layer_cache["v"], kv["k"], kv["v"], pos)
    o = decode_attention(q[:, 0], k_cache, v_cache, pos + 1, window=window,
                         logit_cap=cfg.attn_softcap, scale=cfg.attn_scale)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(o.dtype))[:, None]
    return out, {"k": k_cache, "v": v_cache}


def _decode_attn_mla(x, p, cfg: TransformerConfig, layer_cache, pos, window):
    """Absorbed MLA decode: scores/values live in the 512-d latent space."""
    m = cfg.mla
    b = x.shape[0]
    q = jnp.einsum("bsd,dhq->bshq", x, p["wq"].astype(x.dtype))[:, 0]  # [B,h,qk]
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    posv = pos + jnp.zeros((1,), jnp.int32)
    q_rope = m.rope(q_rope[:, None], posv, cfg.rope_theta)[:, 0]

    ckv_new = apply_norm(
        jnp.einsum("bsd,dl->bsl", x, p["wdkv"].astype(x.dtype)), p["kv_ln"], "rms")
    kr_new = jnp.einsum("bsd,dr->bsr", x, p["wkr"].astype(x.dtype))
    kr_new = m.rope(kr_new[:, :, None, :], posv, cfg.rope_theta)[:, :, 0, :]
    ckv = jax.lax.dynamic_update_slice_in_dim(
        layer_cache["ckv"], ckv_new.astype(layer_cache["ckv"].dtype), pos, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(
        layer_cache["kr"], kr_new.astype(layer_cache["kr"].dtype), pos, axis=1)

    # absorb W_uk into q:  q_lat[b,h,l] = q_nope[b,h,n] · wuk[l,h,n]
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope, p["wuk"].astype(q_nope.dtype))
    scale = m.softmax_scale
    # bf16 operands + f32 accumulation; no f32 shadow of the latent cache
    s_nope = jnp.einsum("bhl,bsl->bhs", q_lat.astype(ckv.dtype), ckv,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhr,bsr->bhs", q_rope.astype(kr.dtype), kr,
                        preferred_element_type=jnp.float32)
    scores = (s_nope + s_rope) * scale
    if cfg.attn_softcap:
        scores = softcap(scores, cfg.attn_softcap)
    valid = jnp.arange(ckv.shape[1])[None, None, :] < pos + 1
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhs,bsl->bhl", probs.astype(ckv.dtype), ckv,
                         preferred_element_type=jnp.float32)
    # absorb W_uv on the way out: v[b,h,v] = ctx_lat[b,h,l] · wuv[l,h,v]
    vout = jnp.einsum("bhl,lhv->bhv", ctx_lat.astype(x.dtype),
                      p["wuv"].astype(x.dtype))
    out = jnp.einsum("bhv,hvd->bd", vout, p["wo"].astype(vout.dtype))[:, None]
    return out, {"ckv": ckv, "kr": kr}


def _decode_block(x, lp, cfg: TransformerConfig, layer_cache, pos, window):
    h = apply_norm(x, lp["ln1"], cfg.norm)
    fn = _decode_attn_mla if cfg.mla is not None else _decode_attn_dense
    attn_out, new_cache = fn(h, lp["attn"], cfg, layer_cache, pos, window)
    if cfg.post_norm:
        attn_out = apply_norm(attn_out, lp["ln1_post"], cfg.norm)
    if cfg.parallel_block:
        ffn_out = (moe_ffn(h, lp["moe"], cfg) if cfg.moe is not None
                   else dense_ffn(h, lp["mlp"], cfg))
        return x + attn_out + ffn_out, new_cache
    x = x + attn_out
    h = apply_norm(x, lp["ln2"], cfg.norm)
    ffn_out = (moe_ffn(h, lp["moe"], cfg) if cfg.moe is not None
               else dense_ffn(h, lp["mlp"], cfg))
    if cfg.post_norm:
        ffn_out = apply_norm(ffn_out, lp["ln2_post"], cfg.norm)
    return x + ffn_out, new_cache


def decode_step(params: Params, cfg: TransformerConfig, cache: Any,
                tokens: jax.Array, pos: jax.Array):
    """One decode step. tokens: [B] int32 (or [B,d] embeds); pos: scalar int32.

    Returns (logits [B,V] fp32, new cache).
    """
    if cfg.embed_inputs:
        x = tokens[:, None, :]  # [B,1,d]
    else:
        x = embed_tokens(params, cfg, tokens[:, None])
    windows = jnp.asarray(cfg.windows())
    moe = cfg.moe
    n_lead = moe.first_dense_layers if moe else 0
    new_cache: dict[str, Any] = {}
    if n_lead:
        dense_cfg = dataclasses.replace(cfg, moe=None,
                                        d_ff=moe.dense_d_ff or cfg.d_ff)
        outs = []
        for i, lp in enumerate(params["lead_blocks"]):
            lc = jax.tree_util.tree_map(lambda a, i=i: a[i], cache["lead"])
            x, nc = _decode_block(x, lp, dense_cfg, lc, pos, 0)
            outs.append(nc)
        new_cache["lead"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    def body(h, inputs):
        lp, w, lc = inputs
        h, nc = _decode_block(h, lp, cfg, lc, pos, w)
        return h, nc

    x, scan_cache = jax.lax.scan(
        body, x, (params["blocks"], windows[n_lead:], cache["blocks"]))
    new_cache["blocks"] = scan_cache
    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(params, cfg, x)[:, 0]
    return logits, new_cache
