"""The plain references against the program at a small size on the CPU."""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import served
from bench.lib import harness
from bench.reference import decoder

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, num_hidden_layers=4, vocab_size=512)


@pytest.fixture(scope="module")
def tiny():
    cfg = copy.deepcopy(harness.load_json(harness.BENCH / "configs" / "stablelm-3b.json"))
    cfg["config"].update(TINY)
    return cfg


def test_weights_have_the_programs_layout(tiny):
    m = served.model_dims(tiny)
    bundle = served.program_bundle("stablelm-3b", m)
    theirs = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0), jnp.bfloat16))
    ours = jax.eval_shape(lambda: decoder.make_params(m, 1))
    assert jax.tree_util.tree_structure(theirs) == jax.tree_util.tree_structure(ours)
    assert [a.shape for a in jax.tree_util.tree_leaves(theirs)] == \
        [a.shape for a in jax.tree_util.tree_leaves(ours)]


def test_weights_from_large_seeds_differ():
    m = served.model_dims({"config": {**TINY, "partial_rotary_factor": 0.25,
                                      "rope_theta": 1e4}})
    a = decoder.make_params(m, 2**40 + 1)["head"]
    b = decoder.make_params(m, 2**41 + 1)["head"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(decoder.make_params(m, 2**40 + 1)["head"]))


def test_decoder_matches_the_program_in_float32(tiny):
    """No int8 cut and float32 weights: the program's monolithic forward and
    the reference compute the same function."""
    from repro.serving.segments import SegmentRunner

    m = served.model_dims(tiny)
    params = decoder.make_params(m, 3, jnp.float32)
    bundle = served.program_bundle("stablelm-3b", m)
    toks = np.random.default_rng(0).integers(0, m["vocab"], (1, 24), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = SegmentRunner(bundle, 0, m["n_layers"] + 2)(
            jax.tree_util.tree_map(lambda a: a, params), jnp.asarray(toks))
    ours = decoder.logits(params, toks[0], m, ())
    theirs = np.asarray(theirs[0], np.float64)
    # the program computes its activations in bf16 whatever the weights'
    # dtype (its embedding lookup casts), so agreement is to bf16 rounding
    assert np.max(np.abs(theirs - np.asarray(ours))) < 0.05 * np.max(np.abs(theirs))
    assert np.mean(np.argmax(theirs, -1) == np.argmax(np.asarray(ours), -1)) > 0.8


def test_int8_transport_rounds_as_the_kernel_oracle():
    from repro.kernels import ref

    x = jax.random.normal(jax.random.key(0), (16, 64), jnp.float32)
    q, s = ref.quantize_int8_ref(x)
    theirs = ref.dequantize_int8_ref(q, s, jnp.float32)
    np.testing.assert_allclose(np.asarray(decoder._transport(x)),
                               np.asarray(theirs), rtol=1e-6, atol=1e-7)


def test_served_control_reads_far_above_a_sound_run(tiny):
    """The control (the reference one precision below bf16, fp8 e4m3, in
    the program's place) against the program's own served ids, on the same
    sampled requests of a short window at a test size: the program's
    widest gap stays a small fraction of the control's."""
    mix = harness.load_json(harness.BENCH / "traffic" / "short.json")
    mix.update(pool=32, check_requests=3)
    d = served.Served(tiny, mix, 2**36 + 11, interpret=True)
    d.setup(harness.CompileClock())
    d.measure(1.0, False)
    sample = d.sample()
    d.free()
    program = max(d.gaps(sample))
    control = min(d.gaps(sample, control="fp8"))
    assert control > 3 * program, (program, control)
