"""Driver for a DeepSeek-V2-style model served through the split chain.

The served loop, the warm-up, the check and its sample are those of
``served.Served``; this driver gives it the model's own sizes (latent
attention under YaRN, a leading dense layer, routed and shared experts),
weights, plain reference (``bench/reference/deepseek_v2.py``) and counts
(``bench/lib/counts_mla_moe.py``).  ``served`` reads the weights, the
reference and the counts through its module's names ``decoder``, ``counts``
and ``program_bundle``; each call below that reaches them runs with those
names bound to this model's.
"""

from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock

import jax

from bench.drivers import served
from bench.lib import counts_mla_moe, harness
from bench.reference import deepseek_v2


def model_dims(cfg: dict) -> dict:
    """The sizes of a configuration file (the published ``config.json``'s
    keys at its top level), under the names the reference and the counts
    use."""
    return {"d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
            "n_layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "d_ff": cfg["intermediate_size"],
            "n_dense": cfg["first_k_dense_replace"],
            "d_expert": cfg["moe_intermediate_size"],
            "n_experts": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "n_shared": cfg["n_shared_experts"],
            "norm_topk": cfg["norm_topk_prob"],
            "kv_lora": cfg["kv_lora_rank"], "nope_dim": cfg["qk_nope_head_dim"],
            "rope_dim": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
            "rope_theta": cfg["rope_theta"], "rms_eps": cfg["rms_norm_eps"],
            "rope_scaling": dict(cfg["rope_scaling"])}


def program_bundle(arch: str, m: dict):
    """The program's model for these sizes (the transformer family's MLA
    and MoE layers)."""
    from repro.models.api import bundle_for
    from repro.models.common import YaRN
    from repro.models.transformer import MLAConfig, MoEConfig, TransformerConfig

    rs = m["rope_scaling"]
    yarn = YaRN(factor=rs["factor"],
                original_max_position=rs["original_max_position_embeddings"],
                beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])
    return bundle_for(arch, TransformerConfig(
        name=arch, vocab=m["vocab"], d_model=m["d_model"],
        n_layers=m["n_layers"], n_heads=m["n_heads"], n_kv=m["n_heads"],
        d_ff=m["d_ff"], act="silu", glu=True, norm="rms",
        rope_theta=m["rope_theta"],
        mla=MLAConfig(kv_lora=m["kv_lora"], rope_head_dim=m["rope_dim"],
                      nope_head_dim=m["nope_dim"], v_head_dim=m["v_dim"],
                      rope_scaling=yarn),
        moe=MoEConfig(num_experts=m["n_experts"], top_k=m["top_k"],
                      d_expert=m["d_expert"], num_shared=m["n_shared"],
                      first_dense_layers=m["n_dense"], dense_d_ff=m["d_ff"],
                      router_scale=m["norm_topk"])))


@dataclasses.dataclass
class ServedMlaMoe(served.Served):

    def __post_init__(self):
        with self._this_model():
            super().__post_init__()

    @contextlib.contextmanager
    def _this_model(self):
        with mock.patch.multiple(served, model_dims=model_dims,
                                 program_bundle=program_bundle,
                                 decoder=deepseek_v2, counts=counts_mla_moe):
            yield

    def setup(self, clock) -> dict:
        # fail at once where the program lacks this model's layers
        program_bundle(self.cfg["name"], self.m)
        with self._this_model():
            return super().setup(clock)

    def measure(self, seconds: float, tracing: bool) -> None:
        with self._this_model():
            super().measure(seconds, tracing)

    def gaps(self, sample, *, control: str | None = None) -> list[float]:
        with self._this_model():
            return super().gaps(sample, control=control)

    def counters(self) -> dict:
        """``served``'s, and ``roofline_s``: the least time the chip could
        have answered the requests of the window in."""
        peaks = harness.peaks(jax.devices()[0].device_kind)
        reqs = self.requests
        roof = sum(counts_mla_moe.roofline_s(self.m, reqs[i % len(reqs)][1], peaks)
                   for i, _, _ in self.done)
        return {**super().counters(), "roofline_s": roof}


DRIVER = ServedMlaMoe
