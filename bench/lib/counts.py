"""Operations and bytes a call needs, computed from its shapes."""

from __future__ import annotations


def prefill_flops(m: dict, n: int) -> float:
    """FLOPs that the logits of an ``n``-token prompt need.

    Every projection of every layer for each of the ``n`` real tokens,
    attention's two products at their causal count (row i reads i + 1 keys),
    and the head at every real position.  Norms, rotary and softmax are left
    out (a few FLOPs a byte, under 1 % here); padding rows are not counted,
    since no answer needs them.
    """
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"]
    proj = 2.0 * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * m["d_ff"])
    attn = 4.0 * h * hd * n * (n + 1) / 2
    return m["n_layers"] * (n * proj + attn) + 2.0 * n * d * m["vocab"]


def int8_transport_bytes(rows: int, d: int, act_bytes: int = 2) -> float:
    """HBM bytes of one boundary crossing: quantize reads the activations and
    writes int8 values plus one float32 scale a row; dequantize reads those
    and writes the activations back."""
    quant = rows * d * act_bytes + rows * d + 4 * rows
    dequant = rows * d + 4 * rows + rows * d * act_bytes
    return float(quant + dequant)
