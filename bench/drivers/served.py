"""Driver for a model served through the split chain.

Set-up makes the weights from the seed, deploys them through
``repro.launch.serve.deploy`` (the paper's three-segment split with int8
transport at both boundaries, under the adaptive orchestrator) and warms
every bucket of the mix.  In the window each request goes through
``Deployment.serve``, its greedy id at every real row is taken on the
device, and the id of the last real row, the first token, is fetched.  The
loop is closed, one client: the next request goes out when the last one is
answered.  The check runs the reference over a sample of the served
requests once the window has closed.
"""

from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import counts
from bench.lib.harness import generate, p95, span
from bench.reference import decoder


def model_dims(cfg: dict) -> dict:
    """The sizes of a configuration file, under the names this driver and
    the reference use."""
    c = cfg["config"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d_model": d, "n_heads": h, "n_kv": c["num_key_value_heads"],
            "head_dim": d // h, "d_ff": c["intermediate_size"],
            "n_layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
            "rope_frac": c["partial_rotary_factor"],
            "rope_theta": c["rope_theta"]}


def program_bundle(arch: str, m: dict):
    """The program's model for these sizes (the family's own layers)."""
    from repro.models.api import bundle_for
    from repro.models.transformer import TransformerConfig

    return bundle_for(arch, TransformerConfig(
        name=arch, vocab=m["vocab"], d_model=m["d_model"],
        n_layers=m["n_layers"], n_heads=m["n_heads"], n_kv=m["n_kv"],
        d_ff=m["d_ff"], act="silu", glu=True, norm="ln",
        rope_frac=m["rope_frac"], rope_theta=m["rope_theta"]))


@jax.jit
def _greedy(logits, last):
    """Greedy id of every row, the first token (row ``last``), and whether
    every row up to it is finite."""
    rows = logits[0]
    ids = jnp.argmax(rows, -1).astype(jnp.int32)
    ok = jnp.all(jnp.where(jnp.arange(rows.shape[0]) <= last,
                           jnp.isfinite(jnp.max(rows, -1)), True))
    return ids, ids[last], ok


@dataclasses.dataclass
class Served:
    cfg: dict
    mix: dict
    seed: int
    interpret: bool = False        # Pallas interpreter, for CPU tests only
    log: object = sys.stderr

    def __post_init__(self):
        self.m = model_dims(self.cfg)
        self.attempted = self.failed = 0
        self.done: list[tuple[int, jax.Array, tuple[int, ...]]] = []
        self.ttft: list[float] = []
        self.tokens = 0
        self.window_s = 0.0
        self.flops = 0.0
        self.int8_bytes = 0.0

    # ------------------------------------------------------------------ #
    def setup(self, clock) -> dict:
        from repro.launch import serve as serve_mod

        m = self.m
        self.params = decoder.make_params(m, self.seed)
        bundle = dataclasses.replace(program_bundle(self.cfg["name"], m),
                                     init=lambda key, dtype: self.params)
        with mock.patch.object(serve_mod, "get_bundle",
                               lambda arch, reduced=False: bundle):
            self.dep = serve_mod.deploy(self.cfg["name"], compress=True,
                                        interpret=self.interpret)
        self.t0 = time.perf_counter()
        self.requests = generate(self.mix, self.seed, vocab=m["vocab"])
        # one request of each bucket until a pass compiles nothing
        by_bucket = {}
        for toks, n in self.requests:
            by_bucket.setdefault(toks.shape[1], (toks, n))
        passes = 0
        for passes in range(1, 5):
            before = clock.compiles
            for toks, n in by_bucket.values():
                self._serve(toks, n, time.perf_counter() - self.t0)
            if clock.compiles == before:
                break
        return {"warm_passes": passes, "buckets": sorted(by_bucket),
                "split": list(self.dep.engine.config.boundaries)}

    def _serve(self, toks: np.ndarray, n: int, now: float):
        logits, _ = self.dep.serve(jnp.asarray(toks), now=now)
        ids, first, ok = _greedy(logits, jnp.int32(n - 1))
        first, ok = jax.device_get((first, ok))
        return ids, int(first), bool(ok)

    # ------------------------------------------------------------------ #
    def measure(self, seconds: float, tracing: bool) -> None:
        reqs, n_req = self.requests, len(self.requests)
        t_start = t_end = time.perf_counter()
        i = 0
        while t_end - t_start < seconds:
            ts = time.perf_counter()
            toks, n = reqs[i % n_req]
            i += 1
            self.attempted += 1
            try:
                with span("request", tracing):
                    ids, _, ok = self._serve(toks, n, ts - self.t0)
            except Exception:   # a fault in the served path: count, go on
                self.failed += 1
                traceback.print_exc(file=self.log)
                t_end = time.perf_counter()
                continue
            t_end = time.perf_counter()
            self.ttft.append(t_end - ts)
            if not ok:
                self.failed += 1
                continue
            self.done.append((i - 1, ids, self.dep.engine.config.boundaries))
            self.tokens += n
            self.flops += counts.prefill_flops(self.m, n)
            cuts = len(self.dep.engine.config.boundaries) - 2
            self.int8_bytes += cuts * counts.int8_transport_bytes(
                toks.shape[1], self.m["d_model"])
        self.window_s = t_end - t_start

    def end_to_end(self) -> dict:
        if not self.ttft:
            return {}
        print(f"served {len(self.ttft)} requests, {self.tokens} prompt tokens",
              file=self.log)
        return {"ttft_p95_ms": 1e3 * p95(self.ttft),
                "prompt_tokens_per_s": self.tokens / self.window_s}

    def counters(self) -> dict:
        return {"flops": self.flops, "int8_bytes": self.int8_bytes,
                "kernels": ("quantize_int8", "dequantize_int8")}

    # ------------------------------------------------------------------ #
    def free(self) -> None:
        """Drop the program's staged chain; the weights stay for the check."""
        self.dep.engine.chain = None
        self.dep.engine.node_params = {}
        self.dep = None

    def sample(self) -> list:
        """The requests the check reads: the longest finished one, and the
        rest drawn from the seed."""
        k = int(self.mix["check_requests"])
        if not self.done:
            return []
        lengths = [self.requests[i % len(self.requests)][1] for i, _, _ in self.done]
        longest = int(np.argmax(lengths))
        rest = [j for j in range(len(self.done)) if j != longest]
        rng = np.random.default_rng([int(self.seed), 0x636865636B])
        pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
        return [self.done[longest]] + [self.done[rest[j]] for j in sorted(pick)]

    def gaps(self, sample, *, control: str | None = None) -> list[float]:
        """Per sampled request, the widest gap of the served ids (or, with
        ``control`` "int8" or "fp8", of the ids that the reference computed
        in that precision puts first) below the reference's best logit."""
        out = []
        for i, ids, bounds in sample:
            toks, n = self.requests[i % len(self.requests)]
            # unit boundaries (embed, blocks..., head) -> layer indices
            cuts = tuple(b - 2 for b in bounds[1:-1])
            ref = decoder.logits(self.params, toks[0], self.m, cuts)
            if control:
                low = decoder.logits(self.params, toks[0], self.m, cuts, low=control)
                ids = jnp.argmax(low, -1)
                del low
            out.append(float(decoder.served_gap(ref, ids, jnp.int32(n))))
            del ref
        return out

    def calibration(self, control: bool) -> dict:
        """What the check's limit is set from (``bench/calibrate.py``): the
        program's gaps over the check's sample and, with ``control``, those
        of the ids that the reference in int8 or fp8 puts first."""
        sample = self.sample()
        self.free()
        row = {"served": len(self.done), "gaps": self.gaps(sample)}
        if control:
            for low in ("int8", "fp8"):
                row[f"gaps_{low}"] = self.gaps(sample, control=low)
        return row

    def check(self) -> list[tuple[str, float, float, bool]]:
        """(name, number, limit, within it) for each number compared."""
        limit = float(self.cfg["checks"]["served_gap"])
        want = min(int(self.mix["check_requests"]), len(self.done))
        sample = self.sample()
        self.free()
        gaps = self.gaps(sample)
        widest = max(gaps) if gaps else float("inf")
        return [("served_gap", widest, limit, widest <= limit),
                ("checked_requests", float(len(gaps)), float(want),
                 len(gaps) >= max(want, 1))]


DRIVER = Served
