"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false.

Small sizes on the CPU; the Pallas kernels run in the interpreter.  The
faults a served cell can have: a token altered where it is produced.  (One
chip: no exchange between chips to leave out.  A served request holds no
state between steps, and the batch is one request.)
"""

from __future__ import annotations

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.drivers import served
from bench.lib import harness
from bench.reference import decoder

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=4, num_hidden_layers=4, vocab_size=512)
# ``served_gap``'s limit at this size, set by the rule the cell's own limit
# follows: sound runs of the program read 0.010-0.051 and the fp8 control
# 0.13-0.52 (seeds 11-13 and 2**35 + 7, both mixes, one request each)
TINY_GAP_LIMIT = 0.15


_LOAD_CELL = run.load_cell


def _tiny_cell(name: str):
    bench, cell, cfg, mix = _LOAD_CELL(name)
    cfg = copy.deepcopy(cfg)
    mix = copy.deepcopy(mix)
    cfg["config"].update(TINY_MODEL)
    cfg["checks"]["served_gap"] = TINY_GAP_LIMIT
    mix.update(pool=32, check_requests=2)
    return bench, cell, cfg, mix


def _run(monkeypatch, capsys, name: str, driver_cls) -> dict:
    monkeypatch.setattr(run, "load_cell", lambda n: _tiny_cell(n))
    monkeypatch.setattr(harness, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "off")
    monkeypatch.setattr(served, "DRIVER", driver_cls)
    assert run.main(["--workload", name, "--seed", str(2**35 + 7),
                     "--seconds", "0.5", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@dataclasses.dataclass
class _Served(served.Served):
    interpret: bool = True


@dataclasses.dataclass
class _TokenAltered(_Served):
    """The served logits favour token 0 at every row."""

    def setup(self, clock):
        notes = super().setup(clock)
        real = self.dep.engine.infer_logits
        self.dep.engine.infer_logits = lambda toks: real(toks).at[..., 0].add(100.0)
        return notes


@dataclasses.dataclass
class _Fp8Control(_Served):
    """The control in the program's place: every request answered by the
    reference computed in float8 e4m3, one precision below bf16."""

    def setup(self, clock):
        notes = super().setup(clock)
        cuts = tuple(b - 2 for b in self.dep.engine.config.boundaries[1:-1])
        self.dep.engine.infer_logits = lambda toks: decoder.logits(
            self.params, np.asarray(toks)[0], self.m, cuts, low="fp8")[None]
        return notes


def test_served_sound_run_is_correct(monkeypatch, capsys):
    out = _run(monkeypatch, capsys, "stablelm-3b.long", _Served)
    assert out["correct"] and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_served_token_altered(monkeypatch, capsys):
    out = _run(monkeypatch, capsys, "stablelm-3b.long", _TokenAltered)
    assert out["correct"] is False
    assert out["checks"]["served_gap"]["value"] > out["checks"]["served_gap"]["limit"]


def test_served_fp8_control_is_not_correct(monkeypatch, capsys):
    out = _run(monkeypatch, capsys, "stablelm-3b.long", _Fp8Control)
    assert out["correct"] is False
    assert out["checks"]["served_gap"]["value"] > out["checks"]["served_gap"]["limit"]


def test_reference_gap_of_an_exact_answer_is_zero():
    ref = np.random.default_rng(0).normal(size=(6, 50)).astype(np.float32)
    ids = jnp.asarray(ref.argmax(-1))
    assert float(decoder.served_gap(jnp.asarray(ref), ids, jnp.int32(6))) == 0.0
    worst = jnp.asarray(ref.argmin(-1))
    assert float(decoder.served_gap(jnp.asarray(ref), worst, jnp.int32(6))) == \
        pytest.approx(float((ref.max(-1) - ref.min(-1)).max()))
