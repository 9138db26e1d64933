"""Operations and bytes of a DeepSeek-V2-style prefill (latent attention,
a leading dense layer, routed and shared experts), from its shapes.

``m`` holds the sizes under the names ``bench/drivers/served_mla_moe.py``
gives them.  FLOPs are those of the ``n`` real tokens: padding rows are not
counted, since no answer needs them.  Norms, rotary, softmax and the
combine are left out (a few FLOPs a byte).
"""

from __future__ import annotations

from bench.lib.counts import int8_transport_bytes  # noqa: F401  (the transport's)

BF16 = 2


def _attn_macs(m: dict) -> int:
    """Multiply-adds of MLA's projections, per token: the query, the latent
    and the rotary key, the latent's decompression to keys and values, and
    the output."""
    d, h, lat = m["d_model"], m["n_heads"], m["kv_lora"]
    nope, rope, vd = m["nope_dim"], m["rope_dim"], m["v_dim"]
    return (d * h * (nope + rope) + d * lat + d * rope
            + lat * h * (nope + vd) + h * vd * d)


def _attn_pairs_flops(m: dict, n: int) -> float:
    """Causal attention's two products: row i reads i + 1 keys, QK at the
    query-key width, PV at the value width."""
    qk = m["nope_dim"] + m["rope_dim"]
    return 2.0 * m["n_heads"] * (qk + m["v_dim"]) * n * (n + 1) / 2


def _ffn_params(d: int, ff: int) -> int:
    return 3 * d * ff


def layer_terms(m: dict, n: int) -> list[tuple[float, float]]:
    """(FLOPs, HBM bytes) of each stage of an ``n``-token prefill: the
    embedding, each layer, the head.  Bytes are the bf16 weights a stage
    must read once (every one of the experts: at 512 tokens or more each of
    them is picked with probability above 1 - 1e-20), and the embedding's
    rows."""
    d = m["d_model"]
    attn = _attn_macs(m)
    pairs = _attn_pairs_flops(m, n)
    dense = _ffn_params(d, m["d_ff"])
    expert = _ffn_params(d, m["d_expert"])
    shared = m["n_shared"] * expert
    router = d * m["n_experts"]
    out = [(0.0, float(n * d * BF16))]
    for layer in range(m["n_layers"]):
        if layer < m["n_dense"]:
            macs, params = attn + dense, attn + dense
        else:
            macs = attn + router + m["top_k"] * expert + shared
            params = attn + router + m["n_experts"] * expert + shared
        out.append((2.0 * n * macs + pairs, float(params * BF16)))
    out.append((2.0 * n * d * m["vocab"], float(d * m["vocab"] * BF16)))
    return out


def prefill_flops(m: dict, n: int) -> float:
    """FLOPs that the logits of an ``n``-token prompt need: the active
    parameters (MLA, the dense layer, per expert layer the router, the
    ``top_k`` routed experts and the shared ones) for each real token,
    attention at its causal count, and the head at every real row."""
    return sum(f for f, _ in layer_terms(m, n))


def roofline_s(m: dict, n: int, peaks: dict) -> float:
    """The least time the chip could answer an ``n``-token prompt in: per
    stage the larger of its FLOPs over the bf16 peak and its bytes over HBM
    bandwidth, summed."""
    return sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
               for f, b in layer_terms(m, n))
