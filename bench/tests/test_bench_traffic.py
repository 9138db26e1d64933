"""The traffic generators: the same seed gives the same traffic, and every
seed the same work in another order."""

from __future__ import annotations

import numpy as np
import pytest

from bench.generators import prompts
from bench.lib import harness


def _mix(name: str) -> dict:
    return harness.load_json(harness.BENCH / "traffic" / f"{name}.json")


def _gen(mix: dict, seed: int, vocab: int):
    return harness.generate(mix, seed, vocab=vocab)


@pytest.mark.parametrize("name", ["long", "short"])
def test_prompts_deterministic_per_seed(name):
    a = _gen(_mix(name), 2**40 + 1, 50_304)
    b = _gen(_mix(name), 2**40 + 1, 50_304)
    c = _gen(_mix(name), 2**40 + 2, 50_304)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["long", "short"])
def test_prompts_same_sizes_every_seed(name):
    mix = _mix(name)
    a = [n for _, n in _gen(mix, 5, 100)]
    b = [n for _, n in _gen(mix, 2**33 + 9, 100)]
    assert sorted(a) == sorted(b) and a != b
    lo, hi = mix["lengths"]["min"], mix["lengths"]["max"]
    assert min(a) >= lo and max(a) <= hi
    # any stratum-long stretch holds one size of each band
    k = mix["stratum"]
    means = [np.mean(a[i:i + k]) for i in range(0, len(a), k)]
    assert np.std(means) < 0.05 * np.mean(a)


def test_prompts_padded_to_buckets():
    mix = _mix("long")
    for toks, n in _gen(mix, 3, 50_304)[:64]:
        b = toks.shape[1]
        assert b in mix["buckets"] and n <= b
        assert not toks[0, n:].any()
        assert all(b2 < n for b2 in mix["buckets"] if b2 < b)


def test_generator_found_by_the_mix_name():
    mix = _mix("short")
    via_name = harness.generate(mix, 9, vocab=100)
    direct = prompts.generate(mix, 9, vocab=100)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(via_name, direct))
    with pytest.raises(ModuleNotFoundError):
        harness.generate(dict(mix, generator="no_such_generator"), 9, vocab=100)


def test_stratified_order_holds_one_of_each_band():
    sizes = np.arange(48)
    order = prompts.stratified_order(sizes, 4, np.random.default_rng(1))
    assert sorted(order) == list(sizes)
    for block in order.reshape(-1, 4):
        assert sorted(b // 12 for b in block) == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        prompts.stratified_order(np.arange(10), 4, np.random.default_rng(1))
