"""Main-path programs compile for a TPU v5e at their real shapes.

Nothing runs: the TPU compiler installed with JAX compiles for a described
v5e:2x2 topology with no chip attached, and refuses what the chip's compiler
would refuse (tiling, fast-memory limits, unsupported dtypes).  The topology
is described inside a fixture, so collecting this file never loads the TPU
library; where it cannot be described, the tests skip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.core.cost_model import CostWeights
from repro.core.fleet_eval import _make_fused_price
from repro.kernels import ops

# the serve phase of chip_smoke.py: stablelm-3b, one 128-token prompt
_SERVE_ROWS = 128
_D_MODEL = get("stablelm-3b").d_model
# the saturated 128-session fleet on the 4-node §IV topology: its resident
# buffers hold 128 rows of 4 segments after a few monitoring cycles
_SESSIONS, _SEGS, _NODES = 128, 4, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel", ["quantize", "dequantize"])
def test_int8_transport_compiles_for_v5e(one_chip, kernel):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = (_SERVE_ROWS, _D_MODEL)
    if kernel == "quantize":
        lowered = ops.quantize_int8.lower(sds(rows, jnp.bfloat16))
    else:
        lowered = ops.dequantize_int8.lower(
            sds(rows, jnp.int8), sds((_SERVE_ROWS, 1), jnp.float32),
            jnp.bfloat16)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    assert compiled.memory_analysis() is not None


def test_resident_price_compiles_for_v5e(one_chip):
    """The fused price program every monitoring cycle dispatches, in the
    float64 scope it runs in."""
    w = CostWeights()
    B, K, n = _SESSIONS, _SEGS, _NODES
    with jax.enable_x64(True):
        f64, i64 = jnp.float64, jnp.int64

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        rows = (sds((B, K), f64), sds((B, K), f64), sds((B, K), bool),
                sds((B, K), i64), sds((B, K), bool), sds((B, K), f64),
                sds((B,), f64), sds((B,), f64), sds((B,), f64),
                sds((B,), i64), sds((B,), bool))
        state = (sds((n,), f64), sds((n, n), f64), sds((n, n), f64),
                 sds((n,), f64), sds((n,), f64), sds((n,), bool),
                 sds((n,), f64))
        price = jax.jit(_make_fused_price(n, w.alpha, w.beta, w.gamma,
                                          1e3, 0.05))
        compiled = price.lower(*rows, *state).compile()
    assert compiled.memory_analysis() is not None


def test_moe_layer_compiles_to_a_grouped_matmul_for_v5e(one_chip):
    """DeepSeek-V2-Lite's expert layer at its published widths on a
    2,048-row prompt: the routed experts compile to the grouped-matmul
    kernel, and the compiled FLOPs are those of the routed rows (6 of 64
    experts a token), not of a dense product over all 64."""
    from repro.models.transformer import moe_ffn

    cfg = get("deepseek-v2-lite-16b")
    moe, d, t = cfg.moe, cfg.d_model, 2_048

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def ffn(width, *lead):
        return {"wi": sds((*lead, d, width)), "wg": sds((*lead, d, width)),
                "wo": sds((*lead, width, d))}

    p = {"router": sds((d, moe.num_experts)),
         "experts": ffn(moe.d_expert, moe.num_experts),
         "shared": ffn(moe.d_expert * moe.num_shared)}
    compiled = jax.jit(moe_ffn, static_argnums=2).lower(
        sds((1, t, d)), p, cfg).compile()
    assert "ragged-dot" in compiled.as_text()
    routed = 2 * t * d * (moe.num_experts + 3 * moe.d_expert * (
        moe.top_k + moe.num_shared))
    flops = compiled.cost_analysis()["flops"]
    assert 0.95 * routed < flops < 1.1 * routed, (flops, routed)


@pytest.mark.parametrize("arch", ["stablelm-3b", "deepseek-v2-lite-16b"])
def test_prefill_attention_compiles_to_the_flash_kernel_for_v5e(
        one_chip, monkeypatch, arch):
    """One layer's attention at published widths on a 2,048-row prompt,
    as the chip runs it (stablelm-3b: 32 heads of 80; DeepSeek-V2-Lite's
    latent attention: 16 heads of 192 / 128): the splash kernel's custom
    call, and no KV-block loop of the XLA scan."""
    import re

    from repro.models.common import KeyGen
    from repro.models.transformer import _block_params, attn_forward

    cfg = get(arch)
    rows = 2_048
    # the program asks JAX's backend, which here is the CPU's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    p = jax.eval_shape(lambda: _block_params(
        cfg, KeyGen(jax.random.PRNGKey(0)), jnp.bfloat16)["attn"])
    x = jax.ShapeDtypeStruct((1, rows, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(lambda x, p: attn_forward(x, p, cfg, window=0)).lower(
        x, jax.tree_util.tree_map(sds, p)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "splash" in hlo
    assert not re.search(r"\swhile\(", hlo)


@pytest.mark.parametrize("arch,layers", [
    ("stablelm-3b", 2), ("deepseek-v2-lite-16b", 3), ("gemma2-9b", 0)])
def test_segment_program_runs_the_kernel_where_the_chain_counts_it(
        one_chip, monkeypatch, arch, layers):
    """A whole-model segment at the reduced widths on a 1,024-row prompt:
    the compiled program holds the splash kernel exactly where
    ``attention_kernel_layers`` counts its layers (gemma2's mixed local and
    global windows keep the XLA scan)."""
    from repro.configs import get_bundle
    from repro.serving import SegmentRunner

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b = get_bundle(arch, reduced=True)
    # a whole-model segment's view is the full tree
    params = jax.eval_shape(lambda: b.init(jax.random.PRNGKey(0),
                                           jnp.bfloat16))
    runner = SegmentRunner(b, 0, len(b.model_graph()))
    assert runner.attention_kernel_layers(1_024) == layers

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    hlo = runner._program.lower(
        jax.tree_util.tree_map(sds, params),
        jax.ShapeDtypeStruct((1, 1_024), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert ("splash_mha_fwd" in hlo) == (layers > 0)
