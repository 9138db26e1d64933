"""Composable decoder-only transformer covering 8 of the 10 assigned archs.

One config dataclass + pure functions.  Feature axes (all combinable):
  * GQA / MQA / MHA via ``n_kv``
  * MLA (DeepSeek-V2) latent KV compression + decoupled RoPE
  * MoE (token-choice top-k, dropless: a grouped matmul over the experts)
  * alternating local/global attention (per-layer window schedule)
  * attention & final logit soft-capping (Gemma-2)
  * parallel attention+FFN blocks (Command-R), QK-norm (Qwen3),
    pre+post sandwich norms (Gemma-2), partial RoPE (StableLM-2)
  * embedding inputs (VLM patch embeds / audio frames prepended or direct)

Layers are weight-stacked and executed with ``jax.lax.scan`` so 60+-layer
models produce O(1)-size HLO and compile quickly; per-layer schedule values
(window size) ride along as scanned arrays.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm

from ..distributed.context import constrain, current_mesh
from .attention import causal_attention, uses_flash_kernel
from .common import (
    KernelLayers,
    KeyGen,
    Params,
    YaRN,
    activation,
    apply_norm,
    apply_rope,
    dense_init,
    embed_init,
    norm_params,
    segment_blocks,
    segment_ends,
    softcap,
    unit_blocks,
)

# --------------------------------------------------------------------------- #
# configs
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                   # per-expert FFN hidden size
    num_shared: int = 0             # always-on shared experts (DeepSeek)
    first_dense_layers: int = 0     # leading dense layers (DeepSeek-V2)
    dense_d_ff: int = 0             # FFN width of those dense layers
    router_scale: bool = True       # normalize top-k gate weights to sum 1


@dataclass(frozen=True)
class MLAConfig:
    kv_lora: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128
    rope_scaling: YaRN | None = None   # on the decoupled rotary key/query

    def rope(self, x: jax.Array, positions: jax.Array,
             theta: float) -> jax.Array:
        return apply_rope(x, positions, theta, yarn=self.rope_scaling)

    @property
    def softmax_scale(self) -> float:
        scale = (self.nope_head_dim + self.rope_head_dim) ** -0.5
        if self.rope_scaling is not None:
            scale *= self.rope_scaling.softmax_mscale
        return scale


@dataclass(frozen=True)
class TransformerConfig:
    name: str
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    d_ff: int
    head_dim: int = 0                  # 0 -> d_model // n_heads
    act: str = "silu"
    norm: str = "rms"                  # rms | rms1 | ln
    glu: bool = True                   # gated FFN (SwiGLU/GeGLU) vs plain MLP
    parallel_block: bool = False
    qk_norm: bool = False
    post_norm: bool = False            # gemma2 sandwich norms
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    rope_theta: float = 10_000.0
    rope_frac: float = 1.0             # partial rotary (stablelm-2: 0.25)
    attn_scale: float | None = None    # override 1/sqrt(head_dim)
    # per-layer window schedule, cycled: 0 = global, w>0 = sliding window
    window_pattern: tuple[int, ...] = (0,)
    tie_embeddings: bool = False
    embed_inputs: bool = False         # inputs are embeddings, not token ids
    embed_scale: bool = False          # multiply embeddings by sqrt(d) (gemma)
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    # vlm: number of prepended modality tokens in input_specs (0 = none)
    prefix_tokens: int = 0
    prefix_dim: int = 0                # raw dim of modality embeddings

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def windows(self) -> np.ndarray:
        pat = self.window_pattern or (0,)
        return np.array([pat[i % len(pat)] for i in range(self.n_layers)],
                        dtype=np.int32)

    @property
    def params_per_block(self) -> int:
        d, hd = self.d_model, self.hd
        if self.mla is not None:
            m = self.mla
            qk = m.nope_head_dim + m.rope_head_dim
            attn = (d * self.n_heads * qk                 # W_q
                    + d * (m.kv_lora + m.rope_head_dim)   # W_dkv + W_kr
                    + m.kv_lora * self.n_heads * (m.nope_head_dim + m.v_head_dim)
                    + self.n_heads * m.v_head_dim * d)    # W_o
        else:
            attn = d * self.n_heads * hd + 2 * d * self.n_kv * hd \
                + self.n_heads * hd * d
        if self.moe is not None:
            f = (3 if self.glu else 2) * d * self.moe.d_expert
            ffn = self.moe.num_experts * f + self.moe.num_shared * f \
                + d * self.moe.num_experts  # router
        else:
            ffn = (3 if self.glu else 2) * d * self.d_ff
        return attn + ffn

    @property
    def active_params_per_block(self) -> int:
        if self.moe is None:
            return self.params_per_block
        d = self.d_model
        f = (3 if self.glu else 2) * d * self.moe.d_expert
        total = self.params_per_block
        return total - self.moe.num_experts * f + self.moe.top_k * f

    def num_params(self) -> int:
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return emb + self.n_layers * self.params_per_block

    def num_active_params(self) -> int:
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return emb + self.n_layers * self.active_params_per_block


# --------------------------------------------------------------------------- #
# parameter construction (works under jax.eval_shape for the dry-run)
# --------------------------------------------------------------------------- #
def _block_params(cfg: TransformerConfig, kg: KeyGen, dtype) -> Params:
    d, hd, h, kv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv
    p: dict[str, Any] = {"ln1": norm_params(d, cfg.norm, dtype)}
    if not cfg.parallel_block:
        p["ln2"] = norm_params(d, cfg.norm, dtype)
    if cfg.post_norm:
        p["ln1_post"] = norm_params(d, cfg.norm, dtype)
        p["ln2_post"] = norm_params(d, cfg.norm, dtype)
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.nope_head_dim + m.rope_head_dim
        p["attn"] = {
            "wq": dense_init(kg(), (d, h, qk), dtype),
            "wdkv": dense_init(kg(), (d, m.kv_lora), dtype),
            "wkr": dense_init(kg(), (d, m.rope_head_dim), dtype),
            "kv_ln": norm_params(m.kv_lora, "rms", dtype),
            "wuk": dense_init(kg(), (m.kv_lora, h, m.nope_head_dim), dtype),
            "wuv": dense_init(kg(), (m.kv_lora, h, m.v_head_dim), dtype),
            "wo": dense_init(kg(), (h, m.v_head_dim, d), dtype),
        }
    else:
        p["attn"] = {
            "wq": dense_init(kg(), (d, h, hd), dtype),
            "wk": dense_init(kg(), (d, kv, hd), dtype),
            "wv": dense_init(kg(), (d, kv, hd), dtype),
            "wo": dense_init(kg(), (h, hd, d), dtype),
        }
    if cfg.qk_norm:
        p["attn"]["q_norm"] = norm_params(hd, "rms", dtype)
        p["attn"]["k_norm"] = norm_params(hd, "rms", dtype)

    def ffn(width: int, prefix_shape=()) -> Params:
        q = {"wi": dense_init(kg(), (*prefix_shape, d, width), dtype),
             "wo": dense_init(kg(), (*prefix_shape, width, d), dtype)}
        if cfg.glu:
            q["wg"] = dense_init(kg(), (*prefix_shape, d, width), dtype)
        return q

    if cfg.moe is not None:
        p["moe"] = {
            "router": dense_init(kg(), (d, cfg.moe.num_experts), jnp.float32),
            "experts": ffn(cfg.moe.d_expert, (cfg.moe.num_experts,)),
        }
        if cfg.moe.num_shared:
            p["moe"]["shared"] = ffn(cfg.moe.d_expert * cfg.moe.num_shared)
    else:
        p["mlp"] = ffn(cfg.d_ff)
    return p


def init_params(cfg: TransformerConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    kg = KeyGen(key)
    moe = cfg.moe
    n_dense_lead = moe.first_dense_layers if moe else 0

    # stacked homogeneous blocks (scanned); leading dense MoE layers unrolled
    def stack(n: int, make):
        ps = [make() for _ in range(n)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)

    params: dict[str, Any] = {
        "embed": embed_init(kg(), (cfg.vocab, cfg.d_model), dtype),
        "final_norm": norm_params(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(kg(), (cfg.d_model, cfg.vocab), dtype)
    if n_dense_lead:
        dense_cfg = dataclasses.replace(
            cfg, moe=None, d_ff=moe.dense_d_ff or cfg.d_ff)
        params["lead_blocks"] = [
            _block_params(dense_cfg, kg, dtype) for _ in range(n_dense_lead)
        ]
    n_scanned = cfg.n_layers - n_dense_lead
    params["blocks"] = stack(n_scanned, partial(_block_params, cfg, kg, dtype))
    if cfg.prefix_tokens:
        params["prefix_proj"] = dense_init(
            kg(), (cfg.prefix_dim or cfg.d_model, cfg.d_model), dtype)
    return params


# --------------------------------------------------------------------------- #
# MoE: token-choice top-k, dropless (every token reaches all k of its experts)
# --------------------------------------------------------------------------- #
# The routed experts' grouped matmul on the TPU is megablox's (Pallas, shipped
# with JAX): tiles of _GMM_TM expert-sorted rows that run on across group
# boundaries, where XLA's grouped matmul gives every group tiles of its own.
# Each tile takes the whole contraction and width in one step, so the tiles of
# one expert keep its weight block resident: of the tilings a v5e sweep timed
# at DeepSeek-V2-Lite's routed row counts, the fastest (PERF.md).
_GMM_TM = 128


def uses_grouped_kernel(routed_rows: int, k: int, n: int) -> bool:
    """Whether :func:`moe_ffn` runs its grouped products of ``routed_rows``
    expert-sorted rows, ``k`` wide into ``n``, through megablox's kernel.

    It does on the TPU, in a program of one device (under an activation mesh
    the compiler would have to partition the kernel, which it cannot), for
    whole row tiles and widths that are multiples of 128 lanes; everywhere
    else (the CPU, meshes, decode's few rows) ``lax.ragged_dot`` runs.
    """
    return (jax.default_backend() == "tpu" and current_mesh() is None
            and routed_rows % _GMM_TM == 0 and k % 128 == 0 and n % 128 == 0)


def grouped_matmul(a: jax.Array, w: jax.Array, sizes: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """Rows ``a`` [M, k], sorted by group, times their group's ``w`` [G, k,
    n]: rows [sum(sizes[:g]), sum(sizes[:g+1])) through ``w[g]``, in the
    rows' dtype with float32 accumulation, as ``lax.ragged_dot`` computes
    it.  megablox's forward kernel, which has no backward pass: training
    traces under an activation mesh, where ``lax.ragged_dot`` runs
    (``interpret=True`` runs the kernel on the CPU, for tests)."""
    k, n = w.shape[1:]
    return megablox_gmm(a, w.astype(a.dtype), sizes, a.dtype, (_GMM_TM, k, n),
                        interpret=interpret)


def _ffn(x: jax.Array, p: Params, cfg: TransformerConfig, mm) -> jax.Array:
    """An FFN on rows ``x`` [N, d] whose products ``mm(a, w)`` compute."""
    h = activation(mm(x, p["wi"]), cfg.act)
    if cfg.glu:
        h = h * mm(x, p["wg"])
    return mm(h, p["wo"])


def moe_ffn(x: jax.Array, p: Params, cfg: TransformerConfig) -> jax.Array:
    """x: [B, S, d] -> [B, S, d].

    Each token's top-k (token, expert) pairs are sorted by expert, the routed
    experts run as one grouped matmul with ragged group sizes a projection
    (:func:`grouped_matmul` where :func:`uses_grouped_kernel` holds, else
    ``lax.ragged_dot``), and the outputs are summed back per token by gate
    weight in float32; no capacity, so no token ever loses an expert.  The
    shared experts see every token.
    """
    moe = cfg.moe
    b, s, d = x.shape
    t, k = b * s, moe.top_k
    xf = x.reshape(t, d)
    with jax.named_scope("moe_router"):
        logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)                   # [T, E]
        topv, tope = jax.lax.top_k(gates, k)                      # [T, k]
        if moe.router_scale:
            topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    with jax.named_scope("moe_dispatch"):
        e_flat = tope.reshape(-1)                                 # [T*k]
        order = jnp.argsort(e_flat, stable=True)
        sizes = jnp.bincount(e_flat, length=moe.num_experts).astype(jnp.int32)
        xs = xf[order // k]                                       # [T*k, d]
    with jax.named_scope("moe_experts"):
        # rows [sum(sizes[:e]), sum(sizes[:e+1])) go through expert e: one
        # grouped matmul a projection, so the work is that of the routed
        # rows only
        mm = (partial(grouped_matmul, sizes=sizes)
              if uses_grouped_kernel(t * k, d, moe.d_expert) else
              lambda a, w: jax.lax.ragged_dot(a, w.astype(a.dtype), sizes))
        ys = _ffn(xs, p["experts"], cfg, mm)                      # [T*k, d]
    with jax.named_scope("moe_combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        pairs = ys[back].reshape(t, k, d).astype(jnp.float32)
        out = jnp.sum(pairs * topv[..., None], axis=1)
    if moe.num_shared:
        with jax.named_scope("moe_shared"):
            out = out + _ffn(xf, p["shared"], cfg,
                             lambda a, w: a @ w.astype(a.dtype)).astype(jnp.float32)
    return out.reshape(b, s, d).astype(x.dtype)


def dense_ffn(x: jax.Array, p: Params, cfg: TransformerConfig) -> jax.Array:
    hg = constrain(x @ p["wi"].astype(x.dtype), "ff")
    if cfg.glu:
        h = activation(hg, cfg.act) * constrain(
            x @ p["wg"].astype(x.dtype), "ff")
    else:
        h = activation(hg, cfg.act)
    return constrain(h @ p["wo"].astype(h.dtype), "hidden")


# --------------------------------------------------------------------------- #
# attention projections (dense-GQA and MLA)
# --------------------------------------------------------------------------- #
def _qk_normed(q, k, p, cfg):
    if cfg.qk_norm:
        q = apply_norm(q, p["q_norm"], "rms")
        k = apply_norm(k, p["k_norm"], "rms")
    return q, k


@jax.named_scope("mla")
def _mla_forward(x, p, cfg: TransformerConfig, *, window, q_offset, kv_block):
    """Latent attention (DeepSeek-V2): keys and values decompressed from a
    normed latent, a decoupled rotary key shared by every head."""
    b, s, d = x.shape
    m = cfg.mla
    q = jnp.einsum("bsd,dhq->bshq", x, p["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    ckv = apply_norm(
        jnp.einsum("bsd,dl->bsl", x, p["wdkv"].astype(x.dtype)),
        p["kv_ln"], "rms")
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["wkr"].astype(x.dtype))
    pos = q_offset + jnp.arange(s)
    q_rope = m.rope(q_rope, pos, cfg.rope_theta)
    k_rope = m.rope(k_rope[:, :, None, :], pos, cfg.rope_theta)
    k_nope = jnp.einsum("bsl,lhq->bshq", ckv, p["wuk"].astype(x.dtype))
    v = jnp.einsum("bsl,lhv->bshv", ckv, p["wuv"].astype(x.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, cfg.n_heads, m.rope_head_dim))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = causal_attention(q_full, k, v, window=window,
                         logit_cap=cfg.attn_softcap, q_offset=q_offset,
                         kv_block=kv_block, scale=m.softmax_scale)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(o.dtype))


def attn_forward(
    x: jax.Array, p: Params, cfg: TransformerConfig, *,
    window: jax.Array | int, q_offset=0, kv_block: int = 1024,
) -> jax.Array:
    """Full-sequence attention (train / prefill compute). x: [B,S,d]."""
    b, s, d = x.shape
    if cfg.mla is not None:
        return _mla_forward(x, p, cfg, window=window, q_offset=q_offset,
                            kv_block=kv_block)

    q = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype)),
                  "heads")
    k = constrain(jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(x.dtype)),
                  "heads")
    v = constrain(jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(x.dtype)),
                  "heads")
    q, k = _qk_normed(q, k, p, cfg)
    pos = q_offset + jnp.arange(s)
    rd = int(cfg.hd * cfg.rope_frac) if cfg.rope_frac < 1.0 else None
    q = apply_rope(q, pos, cfg.rope_theta, rope_dim=rd)
    k = apply_rope(k, pos, cfg.rope_theta, rope_dim=rd)
    o = causal_attention(q, k, v, window=window,
                         logit_cap=cfg.attn_softcap, q_offset=q_offset,
                         kv_block=kv_block, scale=cfg.attn_scale)
    return constrain(jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype)),
                     "hidden")


# --------------------------------------------------------------------------- #
# block + full model forward (train / prefill)
# --------------------------------------------------------------------------- #
def block_forward(x, p, cfg: TransformerConfig, *, window, q_offset=0,
                  kv_block: int = 1024):
    h = apply_norm(x, p["ln1"], cfg.norm)
    attn_out = attn_forward(h, p["attn"], cfg, window=window,
                            q_offset=q_offset, kv_block=kv_block)
    if cfg.post_norm:
        attn_out = apply_norm(attn_out, p["ln1_post"], cfg.norm)
    if cfg.parallel_block:
        ffn_out = (moe_ffn(h, p["moe"], cfg) if cfg.moe is not None
                   else dense_ffn(h, p["mlp"], cfg))
        return x + attn_out + ffn_out
    x = x + attn_out
    h = apply_norm(x, p["ln2"], cfg.norm)
    ffn_out = (moe_ffn(h, p["moe"], cfg) if cfg.moe is not None
               else dense_ffn(h, p["mlp"], cfg))
    if cfg.post_norm:
        ffn_out = apply_norm(ffn_out, p["ln2_post"], cfg.norm)
    return x + ffn_out


def embed_tokens(params, cfg: TransformerConfig, tokens: jax.Array,
                 compute_dtype=jnp.bfloat16) -> jax.Array:
    x = params["embed"].astype(compute_dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), compute_dtype)
    return x


def forward_hidden(
    params: Params, cfg: TransformerConfig, x: jax.Array, *,
    q_offset=0, remat: bool = True, kv_block: int = 1024,
) -> jax.Array:
    """Run all blocks on embedded inputs x: [B,S,d] -> [B,S,d] (pre-head)."""
    x = constrain(x, "hidden")
    win_np = cfg.windows()
    moe = cfg.moe
    n_lead = moe.first_dense_layers if moe else 0
    if n_lead:
        dense_cfg = dataclasses.replace(cfg, moe=None,
                                        d_ff=moe.dense_d_ff or cfg.d_ff)
        for lp in params["lead_blocks"]:
            x = block_forward(x, lp, dense_cfg, window=0, q_offset=q_offset,
                              kv_block=kv_block)

    window = _static_window(cfg)   # static window -> cheaper masks

    def body(h, inputs):
        lp, w = inputs if window is None else (inputs, window)
        return block_forward(h, lp, cfg, window=w, q_offset=q_offset,
                             kv_block=kv_block), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = params["blocks"] if window is not None else (
        params["blocks"], jnp.asarray(win_np)[n_lead:])
    x, _ = jax.lax.scan(body, x, xs)
    return apply_norm(x, params["final_norm"], cfg.norm)


def logits_fn(params: Params, cfg: TransformerConfig, h: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = h @ w.astype(h.dtype)
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


# --------------------------------------------------------------------------- #
# segments: graph units [lo, hi) as one node of a split holds and runs them
# --------------------------------------------------------------------------- #
def _static_window(cfg: TransformerConfig) -> int | None:
    """The attention window every scanned block gets, as a Python int, where
    the schedule is uniform (so the program knows it while tracing); None
    where blocks differ and the scan carries each one's window."""
    w = cfg.windows()
    return int(w[0]) if len(set(w.tolist())) == 1 else None


def _block_range(cfg: TransformerConfig, lo: int, hi: int):
    """Graph units [lo, hi) as blocks: the count of dense lead blocks, and
    the indices the segment holds of ``params["lead_blocks"]`` and of the
    scanned ``params["blocks"]``."""
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    blocks = unit_blocks(cfg, lo, hi)
    return (n_lead, range(blocks.start, min(blocks.stop, n_lead)),
            range(max(blocks.start - n_lead, 0), blocks.stop - n_lead))


def segment_params(cfg: TransformerConfig, params: Params, lo: int, hi: int) -> Params:
    """What graph units [lo, hi) run over: the tree their node holds."""
    seg = segment_ends(cfg, params, lo, hi)
    if lo == 0 and "prefix_proj" in params:
        seg["prefix_proj"] = params["prefix_proj"]
    _, lead, scanned = _block_range(cfg, lo, hi)
    if lead:
        seg["lead_blocks"] = params["lead_blocks"][lead.start:lead.stop]
    if scanned:
        seg["blocks"] = jax.tree_util.tree_map(
            lambda a: a[scanned.start:scanned.stop], params["blocks"])
    return seg


def segment_forward(cfg: TransformerConfig, params: Params, lo: int, hi: int,
                    x: jax.Array) -> jax.Array:
    """Graph units [lo, hi) over their :func:`segment_params` view."""
    if lo == 0:
        x = embed_tokens(params, cfg, x)
    n_lead, lead, scanned = _block_range(cfg, lo, hi)
    if lead:
        dense_cfg = dataclasses.replace(
            cfg, moe=None, d_ff=cfg.moe.dense_d_ff or cfg.d_ff)
        for lp in segment_blocks(params["lead_blocks"], len(lead)):
            x = block_forward(x, lp, dense_cfg, window=0)
    if scanned:
        sub = segment_blocks(params["blocks"], len(scanned))
        window = _static_window(cfg)
        if window is None:       # per-layer windows ride the scan
            xs = (sub, jnp.asarray(cfg.windows())[
                n_lead + scanned.start:n_lead + scanned.stop])
        else:                    # one static window: no traced mask
            xs = sub

        def body(h, inputs):
            lp, w = inputs if window is None else (inputs, window)
            return block_forward(h, lp, cfg, window=w), None

        x, _ = jax.lax.scan(body, x, xs)
    if hi == cfg.n_layers + 2:
        x = apply_norm(x, params["final_norm"], cfg.norm)
        return logits_fn(params, cfg, x)
    return x


def segment_kernel_layers(cfg: TransformerConfig, lo: int, hi: int,
                          batch: int, rows: int) -> KernelLayers:
    """The layers of units [lo, hi) that run a Pallas kernel on an input of
    ``batch`` sequences of ``rows`` rows, each as :func:`segment_forward`
    calls it: attention, per sequence, through the splash kernel (lead
    blocks at window 0, scanned ones at :func:`_static_window`, offset 0),
    and the routed experts of the scanned blocks, over the whole batch,
    through the grouped-matmul kernel."""
    _, lead, scanned = _block_range(cfg, lo, hi)
    moe = cfg.moe
    return KernelLayers(
        attention=(len(lead) * uses_flash_kernel(rows, 0, 0)
                   + len(scanned) * uses_flash_kernel(rows, _static_window(cfg), 0)),
        experts=len(scanned) * (moe is not None and uses_grouped_kernel(
            batch * rows * moe.top_k, cfg.d_model, moe.d_expert)))
