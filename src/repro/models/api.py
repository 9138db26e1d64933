"""Unified model API: one ModelBundle per architecture family.

Everything downstream (training step, serving engine, dry-run, orchestrator
graph extraction) goes through this interface, so adding an architecture means
writing a config file, not touching the runtime.  The served split chain
(``repro.serving.segments``) uses ``model_graph`` and the three ``segment_*``
functions, which each family defines in its own module; ``prefill`` and
``decode`` serve the batcher, ``loss`` the training step.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import GraphNode, ModelGraph
from . import griffin, mamba2, transformer, transformer_serve
from .common import KernelLayers

__all__ = ["ModelBundle", "bundle_for", "softmax_xent", "chunked_softmax_xent",
           "SHAPES", "ShapeSpec"]


# --------------------------------------------------------------------------- #
# assigned input shapes (LM-family: seq_len × global_batch)
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


# --------------------------------------------------------------------------- #
# losses
# --------------------------------------------------------------------------- #
def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean masked token xent; labels < 0 are ignored. logits fp32 [B,S,V]."""
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    per_tok = (lse - ll) * mask
    return per_tok.sum() / jnp.maximum(mask.sum(), 1)


def chunked_softmax_xent(h: jax.Array, w_head: jax.Array, labels: jax.Array,
                         *, chunk: int = 512, final_softcap: float = 0.0
                         ) -> jax.Array:
    """Sequence-chunked xent: logits never materialize beyond [B,chunk,V].

    For a 256k vocab at 4k×(per-device 16) this is the difference between a
    67 GB fp32 logits buffer and ~0.5 GB peak.  The chunk body is rematerialized
    in the backward pass.
    """
    b, s, d = h.shape
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = h.shape[1] // c
    hc = jnp.moveaxis(h.reshape(b, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)

    @jax.checkpoint
    def chunk_fn(carry, inp):
        hx, lx = inp
        logits = (hx @ w_head.astype(hx.dtype)).astype(jnp.float32)
        if final_softcap:
            logits = final_softcap * jnp.tanh(logits / final_softcap)
        mask = lx >= 0
        safe = jnp.maximum(lx, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        tot, cnt = carry
        return (tot + ((lse - ll) * mask).sum(), cnt + mask.sum()), None

    (tot, cnt), _ = jax.lax.scan(chunk_fn, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((), jnp.int32)), (hc, lc))
    return tot / jnp.maximum(cnt, 1)


# --------------------------------------------------------------------------- #
# bundle
# --------------------------------------------------------------------------- #
@dataclass
class ModelBundle:
    arch: str
    cfg: Any
    family: str
    init: Callable[..., Any]                  # (key, dtype) -> params
    loss: Callable[..., jax.Array]            # (params, batch) -> scalar
    prefill: Callable[..., tuple]             # (params, batch) -> (logits, cache)
    decode: Callable[..., tuple]              # (params, cache, tokens, pos)
    cache_spec: Callable[..., Any]            # (batch, max_len) -> SDS pytree
    model_graph: Callable[[], ModelGraph]
    # graph units [lo, hi) as one node holds and runs them (x: token ids if
    # lo == 0, else activations; fp32 logits out if hi ends with the head);
    # module functions taking cfg first, so a program cache can key on them
    segment_params: Callable[..., Any]         # (cfg, params, lo, hi) -> view
    segment_forward: Callable[..., jax.Array]  # (cfg, view, lo, hi, x) -> x
    segment_kernel_layers: Callable[..., KernelLayers]  # (cfg, lo, hi, batch, rows)
    supports_long_context: bool = False

    def param_specs(self, dtype=jnp.float32) -> Any:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0), dtype))

    def num_params(self) -> int:
        return self.cfg.num_params()

    def num_active_params(self) -> int:
        fn = getattr(self.cfg, "num_active_params", None)
        return fn() if fn else self.cfg.num_params()

    # ---------------- input specs for the dry run ---------------- #
    def input_specs(self, shape: ShapeSpec) -> dict[str, Any]:
        s, b = shape.seq_len, shape.global_batch
        i32 = jnp.int32
        prefix = getattr(self.cfg, "prefix_tokens", 0)
        if shape.kind == "train":
            spec = {
                "tokens": jax.ShapeDtypeStruct((b, s - prefix), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
            if prefix:
                spec["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (b, prefix, self.cfg.prefix_dim), jnp.bfloat16)
            return spec
        if shape.kind == "prefill":
            spec = {"tokens": jax.ShapeDtypeStruct((b, s - prefix), i32)}
            if prefix:
                spec["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (b, prefix, self.cfg.prefix_dim), jnp.bfloat16)
            return spec
        # decode: one new token against a cache of seq_len
        return {
            "cache": self.cache_spec(b, s),
            "tokens": jax.ShapeDtypeStruct((b,), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
        }


# --------------------------------------------------------------------------- #
# family adapters
# --------------------------------------------------------------------------- #
def _graph_from_blocks(name: str, n_layers: int, d_model: int,
                       flops_per_block: float, bytes_per_block: float,
                       embed_bytes: float, head_bytes: float,
                       head_flops: float) -> ModelGraph:
    units = [GraphNode("embed", 2.0 * d_model, embed_bytes, 2.0 * d_model,
                       privacy_critical=True)]
    units += [GraphNode(f"block_{i}", flops_per_block, bytes_per_block,
                        2.0 * d_model) for i in range(n_layers)]
    units += [GraphNode("lm_head", head_flops, head_bytes, 0.0,
                        privacy_critical=True)]
    return ModelGraph(name, units)


def _transformer_bundle(arch: str, cfg: transformer.TransformerConfig,
                        xent_chunk: int = 512) -> ModelBundle:
    def loss(params, batch):
        prefix = batch.get("prefix_embeds")
        x = transformer.embed_tokens(params, cfg, batch["tokens"])
        if prefix is not None:
            pe = prefix.astype(x.dtype) @ params["prefix_proj"].astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
        h = transformer.forward_hidden(params, cfg, x)
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        return chunked_softmax_xent(h, w, batch["labels"], chunk=xent_chunk,
                                    final_softcap=cfg.final_softcap)

    def prefill(params, batch, max_len=None):
        return transformer_serve.prefill(
            params, cfg, batch["tokens"],
            prefix_embeds=batch.get("prefix_embeds"), max_len=max_len)

    def decode(params, cache, tokens, pos):
        return transformer_serve.decode_step(params, cfg, cache, tokens, pos)

    emb_b = 2.0 * cfg.vocab * cfg.d_model
    return ModelBundle(
        arch=arch, cfg=cfg, family="transformer",
        init=partial(transformer.init_params, cfg),
        loss=loss, prefill=prefill, decode=decode,
        cache_spec=partial(transformer_serve.cache_spec, cfg),
        model_graph=lambda: _graph_from_blocks(
            arch, cfg.n_layers, cfg.d_model,
            2.0 * cfg.active_params_per_block, 2.0 * cfg.params_per_block,
            emb_b, 0.0 if cfg.tie_embeddings else emb_b,
            2.0 * cfg.vocab * cfg.d_model),
        segment_params=transformer.segment_params,
        segment_forward=transformer.segment_forward,
        segment_kernel_layers=transformer.segment_kernel_layers,
        supports_long_context=False,
    )


def _mamba2_bundle(arch: str, cfg: mamba2.Mamba2Config) -> ModelBundle:
    def loss(params, batch):
        x = mamba2.embed_tokens(params, cfg, batch["tokens"])
        h = mamba2.forward_hidden(params, cfg, x)
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        return chunked_softmax_xent(h, w, batch["labels"])

    def prefill(params, batch, max_len=None):
        del max_len  # SSM state is sequence-length independent
        x = mamba2.embed_tokens(params, cfg, batch["tokens"])
        b = x.shape[0]

        def body(h, inputs):
            lp = inputs
            h, (ssm, conv) = mamba2.block_forward(h, lp, cfg, return_state=True)
            return h, (ssm, conv)

        h, (ssm, conv) = jax.lax.scan(body, x, params["blocks"])
        h = mamba2.apply_norm(h, params["final_norm"], cfg.norm)
        logits = mamba2.logits_fn(params, cfg, h[:, -1:])[:, 0]
        return logits, {"ssm": ssm, "conv": conv.astype(jnp.bfloat16)}

    emb_b = 2.0 * cfg.vocab * cfg.d_model
    return ModelBundle(
        arch=arch, cfg=cfg, family="mamba2",
        init=partial(mamba2.init_params, cfg),
        loss=loss, prefill=prefill,
        decode=(lambda params, cache, tokens, pos:
                mamba2.decode_step(params, cfg, cache, tokens, pos)),
        cache_spec=partial(mamba2.cache_spec, cfg),
        model_graph=lambda: _graph_from_blocks(
            arch, cfg.n_layers, cfg.d_model,
            2.0 * cfg.params_per_block, 2.0 * cfg.params_per_block,
            emb_b, 0.0 if cfg.tie_embeddings else emb_b,
            2.0 * cfg.vocab * cfg.d_model),
        segment_params=mamba2.segment_params,
        segment_forward=mamba2.segment_forward,
        segment_kernel_layers=lambda cfg, lo, hi, batch, rows: KernelLayers(),
        supports_long_context=True,
    )


def _griffin_bundle(arch: str, cfg: griffin.GriffinConfig) -> ModelBundle:
    def loss(params, batch):
        x = griffin.embed_tokens(params, cfg, batch["tokens"])
        h = griffin.forward_hidden(params, cfg, x)
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        return chunked_softmax_xent(h, w, batch["labels"],
                                    final_softcap=cfg.final_softcap)

    def prefill(params, batch, max_len=None):
        x = griffin.embed_tokens(params, cfg, batch["tokens"])
        b, s, _ = x.shape
        w = min(cfg.window, max_len or s)                # ring size
        m = min(s, w)                                    # tail tokens kept
        ring_slots = (jnp.arange(s - m, s)) % w          # where the tail lands
        ring_pos = jnp.full((w,), -1, jnp.int32).at[ring_slots].set(
            jnp.arange(s - m, s, dtype=jnp.int32))

        def extract_kv(k, v):
            kr = jnp.zeros((b, w, 1, cfg.head_dim), jnp.bfloat16)
            vr = jnp.zeros_like(kr)
            kr = kr.at[:, ring_slots].set(k[:, s - m:].astype(jnp.bfloat16))
            vr = vr.at[:, ring_slots].set(v[:, s - m:].astype(jnp.bfloat16))
            return kr, vr

        def group_body(h, gp):
            states = {}
            for i, kind in enumerate(cfg.pattern):
                if kind == "rec":
                    h, (lru, conv) = griffin.rec_forward(
                        h, gp[f"t{i}"], cfg, return_state=True)
                    states[f"lru{i}"] = lru
                    states[f"conv{i}"] = conv.astype(jnp.bfloat16)
                else:
                    h, (k, v) = griffin.attn_forward(
                        h, gp[f"t{i}"], cfg, return_kv=True)
                    states[f"k{i}"], states[f"v{i}"] = extract_kv(k, v)
                h = griffin.mlp_forward(h, gp[f"m{i}"], cfg)
            return h, states

        def interleave(per_position):  # list over pattern positions of [G, ...]
            st = jnp.stack(per_position, axis=1)       # [G, P, ...]
            return st.reshape(st.shape[0] * st.shape[1], *st.shape[2:])

        lru_l, conv_l, k_l, v_l = [], [], [], []
        if params["groups"]:
            x, st = jax.lax.scan(group_body, x, params["groups"])
            rec_pos = [i for i, k in enumerate(cfg.pattern) if k == "rec"]
            att_pos = [i for i, k in enumerate(cfg.pattern) if k == "attn"]
            # group-major interleave matches decode_step's layer traversal
            if rec_pos:
                lru_l.append(interleave([st[f"lru{i}"] for i in rec_pos]))
                conv_l.append(interleave([st[f"conv{i}"] for i in rec_pos]))
            if att_pos:
                k_l.append(interleave([st[f"k{i}"] for i in att_pos]))
                v_l.append(interleave([st[f"v{i}"] for i in att_pos]))
        for layer, kind in zip(params["tail"], cfg.tail_kinds()):
            if kind == "rec":
                x, (lru, conv) = griffin.rec_forward(
                    x, layer["t"], cfg, return_state=True)
                lru_l.append(lru[None])
                conv_l.append(conv.astype(jnp.bfloat16)[None])
            else:
                x, (k, v) = griffin.attn_forward(x, layer["t"], cfg,
                                                 return_kv=True)
                kr, vr = extract_kv(k, v)
                k_l.append(kr[None])
                v_l.append(vr[None])
            x = griffin.mlp_forward(x, layer["m"], cfg)
        x = griffin.apply_norm(x, params["final_norm"], cfg.norm)
        logits = griffin.logits_fn(params, cfg, x[:, -1:])[:, 0]
        cache = {
            "lru": jnp.concatenate(lru_l, axis=0)
            if lru_l else jnp.zeros((0, b, cfg.w), jnp.float32),
            "conv": jnp.concatenate(conv_l, axis=0)
            if conv_l else jnp.zeros((0, b, cfg.d_conv - 1, cfg.w), jnp.bfloat16),
            "k": jnp.concatenate(k_l, axis=0) if k_l else
            jnp.zeros((0, b, w, 1, cfg.head_dim), jnp.bfloat16),
            "v": jnp.concatenate(v_l, axis=0) if v_l else
            jnp.zeros((0, b, w, 1, cfg.head_dim), jnp.bfloat16),
            "slot_pos": jnp.broadcast_to(ring_pos, (max(cfg.n_attn, 1), w))[
                : cfg.n_attn],
        }
        return logits, cache

    emb_b = 2.0 * cfg.vocab * cfg.d_model
    kinds = cfg.layer_kinds()
    mean_block = float(np.mean([cfg.params_per_layer(k) for k in kinds]))
    return ModelBundle(
        arch=arch, cfg=cfg, family="griffin",
        init=partial(griffin.init_params, cfg),
        loss=loss, prefill=prefill,
        decode=(lambda params, cache, tokens, pos:
                griffin.decode_step(params, cfg, cache, tokens, pos)),
        cache_spec=partial(griffin.cache_spec, cfg),
        model_graph=lambda: _graph_from_blocks(
            arch, cfg.n_layers, cfg.d_model, 2.0 * mean_block, 2.0 * mean_block,
            emb_b, 0.0 if cfg.tie_embeddings else emb_b,
            2.0 * cfg.vocab * cfg.d_model),
        segment_params=griffin.segment_params,
        segment_forward=griffin.segment_forward,
        segment_kernel_layers=lambda cfg, lo, hi, batch, rows: KernelLayers(),
        supports_long_context=True,
    )


def bundle_for(arch: str, cfg: Any) -> ModelBundle:
    if isinstance(cfg, transformer.TransformerConfig):
        return _transformer_bundle(arch, cfg)
    if isinstance(cfg, mamba2.Mamba2Config):
        return _mamba2_bundle(arch, cfg)
    if isinstance(cfg, griffin.GriffinConfig):
        return _griffin_bundle(arch, cfg)
    raise TypeError(f"unknown config type {type(cfg)}")
