"""Attention cores.

Entry points:

* :func:`causal_attention` — the full-sequence causal attention of training
  and prefill (``transformer.attn_forward``, dense and latent).  It runs the
  block-sparse splash flash-attention kernel (Pallas, shipped with JAX) where
  :func:`uses_flash_kernel` holds — on the TPU, global attention from
  position 0, a query length that is a multiple of 128 and at least 1,024 —
  and :func:`chunked_attention` everywhere else (the CPU, short prompts,
  sliding windows, prefill chunks at an offset).
* :func:`chunked_attention` — flash-style online-softmax attention scanning
  over KV blocks in XLA.  Memory is O(S · kv_block) instead of O(S²), so
  32k-token prefill lowers/compiles without materializing the score matrix;
  it masks causal pairs after the products instead of skipping them.
* :func:`splash_attention` — the kernel path on its own (``interpret=True``
  runs it on the CPU, for tests).
* :func:`decode_attention` — one-token GQA attention against a KV cache,
  fp32 accumulation, position masking.

The full-sequence paths take causal masks, logit soft-capping, grouped-query
heads (any H/KV ratio, including MQA kv=1) and a value head size of their own
(MLA); the XLA paths also take sliding windows (Gemma-2 local layers).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from ..distributed.context import current_mesh
from .common import softcap as _softcap

NEG_INF = -2.0e38

# the kernel's block: 512 rows of q and of kv was the fastest of twelve
# (block_q, block_kv, block_kv_compute) choices at 512, 1,024 and 2,048 rows,
# for dense and latent heads alike (TPU v5e, PERF.md); a length it does not
# divide takes the largest 128-row multiple that does
_SPLASH_BLOCK = 512
# below 1,024 rows a layer's attention ran faster through the XLA scan,
# then a single fused block (TPU v5e, PERF.md)
_SPLASH_MIN_ROWS = 1024


def _gqa_reshape(q: jax.Array, n_kv: int):
    """[B,S,H,hd] -> [B,S,KV,G,hd] grouping query heads per KV head."""
    b, s, h, hd = q.shape
    assert h % n_kv == 0, (h, n_kv)
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def chunked_attention(
    q: jax.Array,                # [B, Sq, H, hd]
    k: jax.Array,                # [B, Sk, KV, hd]
    v: jax.Array,                # [B, Sk, KV, hd]
    *,
    causal: bool = True,
    window: int = 0,             # 0 = global; >0 = sliding window
    logit_cap: float = 0.0,
    q_offset: int | jax.Array = 0,  # absolute position of q[0] (prefill chunks)
    kv_block: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax attention, scanned over KV blocks. Returns [B,Sq,H,hd]."""
    b, sq, h, hd = q.shape
    _, sk, n_kv, _ = k.shape
    hd_v = v.shape[-1]                                       # may differ (MLA)
    g = h // n_kv
    blk = min(kv_block, sk)
    nblk = (sk + blk - 1) // blk
    pad = nblk * blk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sc = (hd ** -0.5) if scale is None else scale

    # keep operands in their storage dtype; accumulate in f32 via the dot —
    # explicit .astype(f32) on S-sized tensors materializes full-precision
    # shadows of the KV stream (§Perf E2a)
    qg = _gqa_reshape(q, n_kv) * jnp.asarray(sc, q.dtype)    # [B,Sq,KV,G,hd]
    q_pos = q_offset + jnp.arange(sq)                        # [Sq]

    kb = k.reshape(b, nblk, blk, n_kv, hd)
    vb = v.reshape(b, nblk, blk, n_kv, hd_v)

    def step(carry, inputs):
        m, l, acc = carry                                    # running max/sum/out
        kblk, vblk, start = inputs                           # [B,blk,KV,hd], start pos
        s = jnp.einsum("bqkgh,bckh->bqkgc", qg, kblk,
                       preferred_element_type=jnp.float32)
        if logit_cap:
            s = _softcap(s, logit_cap)
        k_pos = start + jnp.arange(blk)                      # [blk]
        if pad:
            mask = (k_pos < sk)[None, :]                     # mask the padding
        else:
            mask = jnp.ones((1, blk), bool)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        # window: static 0 (global) skips the mask term entirely; a traced
        # per-layer scalar (mixed local/global schedules) stays dynamic
        if not (isinstance(window, int) and window <= 0):
            w = jnp.asarray(window)
            mask = mask & ((w <= 0) | (k_pos[None, :] > q_pos[:, None] - w))
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))               # [B,Sq,KV,G]
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        # PV in the value dtype with f32 accumulation (flash-kernel numerics)
        pv = jnp.einsum("bqkgc,bckh->bqkgh", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, n_kv, g), jnp.float32)
    a0 = jnp.zeros((b, sq, n_kv, g, hd_v), jnp.float32)
    starts = jnp.arange(nblk) * blk
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), starts),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, hd_v).astype(q.dtype)


def uses_flash_kernel(rows: int, window, q_offset) -> bool:
    """Whether :func:`causal_attention` runs the splash kernel for a query
    of ``rows`` rows with this ``window`` and ``q_offset``.

    It does on the TPU for global attention from position 0, both known
    while tracing (a Python ``0``; a traced window or offset is not), over
    a multiple of 128 rows (the kernel's blocks are) and at least 1,024,
    in a program of one device: under an activation mesh the compiler
    would have to partition the kernel, which it cannot.
    """
    return (jax.default_backend() == "tpu" and current_mesh() is None
            and isinstance(window, int) and window == 0
            and isinstance(q_offset, int) and q_offset == 0
            and rows % 128 == 0 and rows >= _SPLASH_MIN_ROWS)


@functools.cache
def _splash_kernel(heads: int, rows: int, logit_cap: float, interpret: bool):
    """The causal splash kernel over ``heads`` heads of ``rows`` rows."""
    blk = math.gcd(rows, _SPLASH_BLOCK)
    blocks = splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        # the backward pass (a differentiated caller) at the same blocks
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    mask = splash.MultiHeadMask([splash.CausalMask((rows, rows))] * heads)
    # the kernel holds its block tables as arrays: make them concrete even
    # when the first caller is tracing, so every later program can use them
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=blocks, head_shards=1, q_seq_shards=1,
            attn_logits_soft_cap=logit_cap or None, interpret=interpret)


def splash_attention(
    q: jax.Array,                # [B, S, H, hd]
    k: jax.Array,                # [B, S, KV, hd]
    v: jax.Array,                # [B, S, KV, hd_v]
    *,
    logit_cap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention from position 0 through the splash kernel, which
    skips the blocks wholly above the diagonal and keeps scores in VMEM.
    Products in the operands' dtype with f32 softmax and accumulation, as
    :func:`chunked_attention`.  Returns [B, S, H, hd_v]."""
    b, s, h, hd = q.shape
    sc = (hd ** -0.5) if scale is None else scale
    kernel = _splash_kernel(h, s, float(logit_cap), interpret)
    # the kernel applies no scale: scale q in its storage dtype, as
    # chunked_attention does, and lay each head's rows out contiguously
    qh = jnp.swapaxes(q * jnp.asarray(sc, q.dtype), 1, 2)    # [B,H,S,hd]
    out = jax.vmap(kernel)(qh, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(out, 1, 2)


def causal_attention(
    q: jax.Array,                # [B, Sq, H, hd]
    k: jax.Array,                # [B, Sk, KV, hd]
    v: jax.Array,                # [B, Sk, KV, hd_v]
    *,
    window: int | jax.Array = 0,
    logit_cap: float = 0.0,
    q_offset: int | jax.Array = 0,
    kv_block: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Causal attention of a full sequence: the splash kernel where
    :func:`uses_flash_kernel` holds, :func:`chunked_attention` otherwise."""
    if uses_flash_kernel(q.shape[1], window, q_offset):
        return splash_attention(q, k, v, logit_cap=logit_cap, scale=scale)
    return chunked_attention(q, k, v, causal=True, window=window,
                             logit_cap=logit_cap, q_offset=q_offset,
                             kv_block=kv_block, scale=scale)


def decode_attention(
    q: jax.Array,                # [B, H, hd] — one new token per sequence
    k_cache: jax.Array,          # [B, S, KV, hd]
    v_cache: jax.Array,          # [B, S, KV, hd]
    cur_len: jax.Array,          # [] or [B] — tokens valid in the cache
    *,
    window: int = 0,
    logit_cap: float = 0.0,
    scale: float | None = None,
) -> jax.Array:
    """Single-step GQA attention over the cache. Returns [B, H, hd]."""
    b, s, n_kv, hd = k_cache.shape
    h = q.shape[1]
    g = h // n_kv
    sc = (hd ** -0.5) if scale is None else scale
    qg = q.reshape(b, n_kv, g, hd) * jnp.asarray(sc, q.dtype)
    s_ = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                    preferred_element_type=jnp.float32)
    if logit_cap:
        s_ = _softcap(s_, logit_cap)
    pos = jnp.arange(s)
    cur = jnp.asarray(cur_len)
    cur_b = cur[:, None] if cur.ndim == 1 else cur[None, None]
    mask = pos[None, :] < cur_b                               # [B or 1, S]
    w = jnp.asarray(window)
    mask = mask & ((w <= 0) | (pos[None, :] > cur_b - 1 - w))
    if mask.shape[0] == 1:
        mask = jnp.broadcast_to(mask, (b, s))
    s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, hd).astype(q.dtype)


def update_kv_cache(
    k_cache: jax.Array, v_cache: jax.Array,
    k_new: jax.Array, v_new: jax.Array, pos: jax.Array,
):
    """Write [B, KV, hd] (or [B,1,KV,hd]) entries at ``pos`` (scalar)."""
    if k_new.ndim == 3:
        k_new = k_new[:, None]
        v_new = v_new[:, None]
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    return k_cache, v_cache
