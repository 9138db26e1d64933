"""Split-inference engine: the runtime half of the paper's framework.

Ties together:
  * the ACTIVE partition config (versioned, from the Reconfiguration
    Broadcast) — which segments exist and which node owns each,
  * per-segment parameter views (what RB stages on each node),
  * chained segment execution with activation transport (optionally int8),
  * live reconfiguration: ``apply_config`` swaps the split between requests
    with zero math change (equivalence tested against the monolith).

Node "execution" is in-process (the container has no cluster), but every
hand-off passes through the transport layer, so per-boundary wire bytes match
what a real deployment would ship.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp

from ..core.broadcast import PartitionConfig
from ..models.api import ModelBundle
from .segments import SegmentChain, SegmentRunner, ServingStats
from .transfer import ActivationTransport, TransferStats

__all__ = ["SplitInferenceEngine"]


@dataclass
class SplitInferenceEngine:
    bundle: ModelBundle
    params: Any
    transport: ActivationTransport = field(default_factory=ActivationTransport)
    config: PartitionConfig | None = None
    reconfigurations: int = 0
    chain: SegmentChain | None = None
    # counts of the served path; every chain the engine stages counts here,
    # so they outlive a re-split
    stats: ServingStats = field(default_factory=ServingStats)

    # -------------------------------------------------------------- config --
    def apply_config(self, cfg: PartitionConfig) -> None:
        """Stage each segment's params (its chain segment holds them) and
        activate the new split.

        The old chain's staged slices are dropped first: at full width a
        second staged copy alongside the full tree does not fit one device.
        """
        self.chain = None
        self.chain = SegmentChain(self.bundle, self.params, cfg.boundaries,
                                  transfer_hook=self.transport,
                                  stats=self.stats)
        if self.config is not None and cfg.version != self.config.version:
            self.reconfigurations += 1
        self.config = cfg

    def staged_bytes_per_node(self) -> dict[int, float]:
        """Weight bytes resident per node under the active split (Eq. 4)."""
        g = self.bundle.model_graph()
        out: dict[int, float] = {}
        assert self.config is not None
        for j, node in enumerate(self.config.assignment):
            lo, hi = self.config.boundaries[j], self.config.boundaries[j + 1]
            out[node] = out.get(node, 0.0) + g.segment_weight_bytes(lo, hi)
        return out

    # ------------------------------------------------------------ execution --
    def infer_logits(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Full forward through the active split chain; fp32 logits.

        Runs the staged :class:`SegmentChain` — every segment executes on
        its own :func:`split_params` view, exactly the tree its node holds.
        """
        assert self.chain is not None, "apply_config first"
        return self.chain(tokens)

    def infer_monolithic(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Reference single-node forward (equivalence oracle)."""
        n = len(self.bundle.model_graph())
        return SegmentRunner(self.bundle, 0, n)(self.params, tokens)

    def transfer_stats(self) -> TransferStats:
        return self.transport.stats
