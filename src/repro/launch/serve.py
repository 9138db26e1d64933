"""Serving driver: adaptive split inference over the edge simulator.

Combines the pieces end-to-end: a SplitInferenceEngine executes a REAL model
(the published config in bf16 by default; ``--reduced`` for the family's
float32 smoke-test config on CPU) under the partition configs that the
Adaptive Orchestrator commits while the 5G-MEC environment fluctuates.
Per-request latencies are priced by the edgesim cost model; the numerics of
every request flow through the actual split segment chain (int8 transport
optional).

Usage:
  python -m repro.launch.serve --arch stablelm-3b --requests 16 --compress
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \\
      --reduced --interpret --compress
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_bundle
from repro.core import (
    AdaptiveOrchestrator,
    CapacityProfiler,
    InProcessAgent,
    ReconfigurationBroadcast,
    SplitRevision,
    Thresholds,
    Workload,
)
from repro.core.cost_model import SystemState, chain_latency
from repro.edgesim import MECScenarioParams, base_system_state
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import ModelBundle
from repro.serving import ActivationTransport, SplitInferenceEngine


@dataclass
class Deployment:
    """A model staged on the §IV fleet under an adaptive orchestrator."""

    bundle: ModelBundle
    engine: SplitInferenceEngine
    orch: AdaptiveOrchestrator
    profiler: CapacityProfiler
    state: SystemState
    workload: Workload

    def serve(self, tokens: jax.Array, now: float) -> tuple[jax.Array, float]:
        """Answer one request through the split chain, then run one
        monitoring cycle and re-split the engine if the orchestrator
        committed a new config.  Returns (fp32 logits, priced latency).

        The call is the profiler span ``serve`` (arguments ``req``, this
        deployment's sequence number of the request, and ``rows``, its
        padded length); the chain's ``segment`` and ``transport`` spans and
        the monitoring cycle's ``control`` span, with ``restage`` around a
        re-split, nest inside it.
        """
        stats = self.engine.stats
        with TraceAnnotation("serve", req=stats.requests, rows=tokens.shape[1]):
            stats.requests += 1
            logits = self.engine.infer_logits(tokens)
            with TraceAnnotation("control"):
                c = self.orch.current
                lat = chain_latency(self.orch.graph, c.boundaries, c.assignment,
                                    self.profiler.system_state(), self.workload)
                self.profiler.observe_latency(lat)
                self.profiler.observe_links(self.state.link_bw)
                d = self.orch.step(now=now)
                if (d.config is not None
                        and d.config.version != self.engine.config.version):
                    with TraceAnnotation("restage", version=d.config.version):
                        self.engine.apply_config(d.config)
        return logits, lat


def deploy(arch: str, *, reduced: bool = False, compress: bool = False,
           interpret: bool = False, prompt_len: int = 32,
           backhaul_mbps: float = 50.0, seed: int = 0) -> Deployment:
    """Build ``arch`` with random weights from ``seed`` and deploy the
    paper's three-segment baseline split through the orchestrator."""
    bundle = get_bundle(arch, reduced=reduced)
    dtype = jnp.float32 if reduced else jnp.bfloat16
    # eager: a jitted init of a 32-layer model takes minutes to compile
    params = bundle.init(jax.random.PRNGKey(seed), dtype)
    engine = SplitInferenceEngine(
        bundle, params,
        transport=ActivationTransport(compress=compress, interpret=interpret))

    # orchestration substrate over the model's REAL graph
    graph = bundle.model_graph()
    state = base_system_state(MECScenarioParams(backhaul_mbps=backhaul_mbps))
    wl = Workload(tokens_in=prompt_len, tokens_out=8, arrival_rate=2.0)
    profiler = CapacityProfiler(base_state=state)
    agents = [InProcessAgent(i) for i in range(state.num_nodes)]
    orch = AdaptiveOrchestrator(
        graph=graph, profiler=profiler,
        broadcast=ReconfigurationBroadcast(agents), workload=wl,
        thresholds=Thresholds(), splitter=SplitRevision())
    L = len(graph)
    cfg0 = orch.deploy_initial((0, max(1, L // 3), max(2, 2 * L // 3), L),
                               (0, 3, 0))
    engine.apply_config(cfg0)
    return Deployment(bundle, engine, orch, profiler, state, wl)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    # llama3-8b's 15 GiB of bf16 weights leave no room on one 16 GB chip
    # for a staged split chain; stablelm-3b's 5.2 GiB do
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="the family's smoke-test config in float32 (CPU)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="run the int8 transport's Pallas kernels in the "
                         "interpreter (needed on CPU)")
    ap.add_argument("--backhaul-mbps", type=float, default=50.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    dep = deploy(args.arch, reduced=args.reduced, compress=args.compress,
                 interpret=args.interpret, prompt_len=args.prompt_len,
                 backhaul_mbps=args.backhaul_mbps)
    rng = np.random.default_rng(0)
    lat = []
    for i in range(args.requests):
        toks = jnp.asarray(rng.integers(0, dep.bundle.cfg.vocab,
                                        (1, args.prompt_len), dtype=np.int32))
        logits, priced = dep.serve(toks, now=float(i))
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        lat.append(priced)
    engine = dep.engine
    stats = engine.transfer_stats()
    out = {
        "requests": engine.stats.requests,
        "mean_latency_ms": round(float(np.mean(lat)) * 1e3, 1),
        "reconfigurations": engine.reconfigurations,
        "wire_MB": round(stats.wire_bytes / 1e6, 2),
        "compression_ratio": round(stats.compression_ratio, 2),
        "final_split": str(engine.config.boundaries),
        # the served path's counters: a segment is traced once per prompt
        # shape, not once per request
        "segment_calls": engine.stats.segment_calls,
        "segment_traces": engine.stats.segment_traces,
        "attention_kernel_layers": engine.stats.attention_kernel_layers,
        "transfers": stats.transfers,
        "decisions": dict(dep.orch.decision_counts),
    }
    print(out)
    return out


if __name__ == "__main__":
    main()
