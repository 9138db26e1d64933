"""The FLOP and byte counts against counts made by hand at a small shape."""

from __future__ import annotations

from bench.lib import counts

M = {"d_model": 8, "n_heads": 2, "n_kv": 2, "head_dim": 4, "d_ff": 16,
     "n_layers": 3, "vocab": 10}


def test_prefill_flops_by_hand():
    # per token and layer: q, k, v, o are 8x8 each (4 * 64), the gated MLP
    # three 8x16 (3 * 128): 640 multiply-adds, 1,280 FLOPs
    # attention of 4 rows: 1 + 2 + 3 + 4 = 10 query-key pairs, each 2 heads
    # x 4 wide x (QK + PV) x 2 FLOPs = 32: 320 FLOPs a layer
    # the head: 4 rows x 8 x 10 x 2 = 640
    assert counts.prefill_flops(M, 4) == 3 * (4 * 1280 + 320) + 640


def test_prefill_flops_grows_quadratically_in_attention_only():
    one = counts.prefill_flops(M, 1)
    two = counts.prefill_flops(M, 2)
    # a second row adds one more token's projections and head, and two
    # more query-key pairs (rows 1 and 2 read 1 and 2 keys)
    assert two - one == 3 * 1280 + 3 * 2 * 32 + 160


def test_int8_transport_bytes_by_hand():
    # 4 rows of 8 bf16: quantize reads 64 B, writes 32 B of int8 and 16 B of
    # scales; dequantize reads 48 B and writes 64 B
    assert counts.int8_transport_bytes(4, 8) == 64 + 32 + 16 + 48 + 64
