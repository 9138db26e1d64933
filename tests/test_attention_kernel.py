"""The served prefill's attention: the splash kernel against the XLA scan,
the predicate that chooses between them, and the counter of the layers that
ran the kernel.

On the CPU the kernel runs in the Pallas interpreter (``interpret=True``);
the program itself never picks it there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_bundle
from repro.models.attention import (
    causal_attention,
    chunked_attention,
    splash_attention,
    uses_flash_kernel,
)
from repro.models.transformer import _static_window
from repro.serving import SegmentChain

# (heads, kv heads, qk head size, v head size, softmax scale)
_DENSE = (4, 4, 80, 80, None)           # stablelm-3b's head size
_MLA = (2, 2, 192, 128, 0.114721)       # DeepSeek-V2-Lite's latent heads
_GQA = (4, 2, 64, 64, None)


def _qkv(rows, heads, kv, hd, hd_v, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, rows, heads, hd), jnp.bfloat16),
            jax.random.normal(ks[1], (1, rows, kv, hd), jnp.bfloat16),
            jax.random.normal(ks[2], (1, rows, kv, hd_v), jnp.bfloat16))


@pytest.mark.parametrize("shape,rows,cap", [
    (_DENSE, 128, 0.0), (_DENSE, 512, 0.0), (_MLA, 128, 0.0),
    (_MLA, 512, 0.0), (_GQA, 640, 0.0), (_DENSE, 128, 30.0)],
    ids=["dense-128", "dense-512", "mla-128", "mla-512", "gqa-640",
         "dense-128-softcap"])
def test_kernel_matches_chunked_attention(shape, rows, cap):
    heads, kv, hd, hd_v, scale = shape
    q, k, v = _qkv(rows, heads, kv, hd, hd_v)
    got = splash_attention(q, k, v, logit_cap=cap, scale=scale,
                           interpret=True)
    want = chunked_attention(q, k, v, causal=True, logit_cap=cap, scale=scale)
    assert got.shape == want.shape == (1, rows, heads, hd_v)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("shape", [_DENSE, _MLA], ids=["dense", "mla"])
def test_padding_rows_do_not_reach_the_real_rows(shape):
    """A prompt right-padded to its bucket: whatever fills the padding, the
    real rows' outputs are the same, as causality requires."""
    heads, kv, hd, hd_v, scale = shape
    rows, real = 256, 173
    q, k, v = _qkv(rows, heads, kv, hd, hd_v, seed=1)
    q2, k2, v2 = _qkv(rows, heads, kv, hd, hd_v, seed=2)
    keep = (jnp.arange(rows) < real)[None, :, None, None]
    out = splash_attention(q, k, v, scale=scale, interpret=True)
    other = splash_attention(jnp.where(keep, q, q2), jnp.where(keep, k, k2),
                             jnp.where(keep, v, v2), scale=scale,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :real], np.float32),
                                  np.asarray(other[:, :real], np.float32))
    assert not np.array_equal(np.asarray(out[:, real:], np.float32),
                              np.asarray(other[:, real:], np.float32))


@pytest.mark.parametrize("backend,rows,window,q_offset,kernel", [
    ("cpu", 2048, 0, 0, False),
    ("tpu", 64, 0, 0, False),
    ("tpu", 128, 0, 0, False),
    ("tpu", 1000, 0, 0, False),
    ("tpu", 2048, jnp.int32(0), 0, False),       # a traced window
    ("tpu", 2048, None, 0, False),               # per-layer windows
    ("tpu", 2048, 4096, 0, False),               # a sliding window
    ("tpu", 2048, 0, 512, False),                # a chunk at an offset
    ("tpu", 2048, 0, jnp.int32(0), False),       # a traced offset
    ("tpu", 512, 0, 0, False),
    ("tpu", 896, 0, 0, False),
    ("tpu", 1024, 0, 0, True),
    ("tpu", 1152, 0, 0, True),
    ("tpu", 2048, 0, 0, True),
])
def test_flash_kernel_predicate(monkeypatch, backend, rows, window, q_offset,
                                kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert uses_flash_kernel(rows, window, q_offset) is kernel


def test_no_flash_kernel_under_an_activation_mesh(monkeypatch):
    """A sharded program keeps the XLA scan: the compiler cannot partition
    the kernel."""
    from jax.sharding import Mesh

    from repro.distributed.context import activation_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with activation_mesh(mesh):
        assert not uses_flash_kernel(2048, 0, 0)
    assert uses_flash_kernel(2048, 0, 0)


def test_static_window_only_where_the_schedule_is_uniform():
    assert _static_window(get_bundle("stablelm-3b", reduced=True).cfg) == 0
    assert _static_window(get_bundle("gemma2-9b", reduced=True).cfg) is None


def test_causal_attention_keeps_the_xla_scan_off_the_tpu():
    """On the CPU every shape goes through chunked_attention, bit for bit."""
    q, k, v = _qkv(512, *_DENSE[:4])
    np.testing.assert_array_equal(
        np.asarray(causal_attention(q, k, v), np.float32),
        np.asarray(chunked_attention(q, k, v), np.float32))


def _chain(arch, bounds_of):
    """The chain over zeros of the params' shapes: host arrays, which its
    segment views slice without running anything."""
    b = get_bundle(arch, reduced=True)
    params = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: b.init(jax.random.PRNGKey(0), jnp.float32)))
    return SegmentChain(b, params, bounds_of(len(b.model_graph())))


@pytest.mark.parametrize("arch,bounds_of,layers", [
    ("stablelm-3b", lambda n: (0, 2, n), [1, 1]),
    ("stablelm-3b", lambda n: (0, 1, n - 1, n), [0, 2, 0]),
    ("deepseek-v2-lite-16b", lambda n: (0, 2, n), [1, 2]),   # a lead block
    ("gemma2-9b", lambda n: (0, 3, n), [0, 0]),              # mixed windows
    ("mamba2-1.3b", lambda n: (0, 2, n), [0, 0]),
])
def test_kernel_layer_count_per_segment(monkeypatch, arch, bounds_of, layers):
    """A qualifying prompt counts every attention layer of a segment that
    the kernel runs, a 64-row one none."""
    chain = _chain(arch, bounds_of)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [s.runner.attention_kernel_layers(2048)
            for s in chain.segments] == layers
    assert [s.runner.attention_kernel_layers(64)
            for s in chain.segments] == [0] * len(layers)
    # the chain counts each request through its runners (programs stubbed:
    # nothing may run the kernel on the CPU)
    for s in chain.segments:
        s.runner._program = lambda params, x: x
    chain(jnp.zeros((1, 64), jnp.int32))
    assert chain.stats.attention_kernel_layers == 0
    chain(jnp.zeros((1, 2048), jnp.int32))
    assert chain.stats.attention_kernel_layers == sum(layers)
    assert chain.stats.segment_calls == 2 * len(layers)


@pytest.mark.parametrize("rows", [64, 512])
def test_served_requests_count_no_kernel_layers_on_the_cpu(rows):
    b = get_bundle("stablelm-3b", reduced=True)
    params = b.init(jax.random.PRNGKey(0), jnp.float32)
    n = len(b.model_graph())
    chain = SegmentChain(b, params, (0, 2, n))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, rows), 0, b.cfg.vocab)
    chain(toks)
    assert chain.stats.segment_calls == 2
    assert chain.stats.attention_kernel_layers == 0
