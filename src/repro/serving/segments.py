"""Segment execution: run a contiguous unit range of a model on one node.

This is the paper's S_j made executable.  The orchestrator's ModelGraph units
are [embed, block_0..block_{L-1}, lm_head]; a :class:`SegmentRunner` takes a
(lo, hi) unit range and runs exactly those units, consuming/producing boundary
activations.  Chaining runners over a split scheme reproduces the monolithic
forward bit-for-bit (tested in tests/test_serving.py) — re-splitting changes
WHERE layers run, never WHAT they compute.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..models import griffin, mamba2, transformer
from ..models.api import ModelBundle
from ..models.attention import uses_flash_kernel

__all__ = ["BoundSegment", "SegmentChain", "SegmentRunner", "ServingStats",
           "split_params", "run_chain"]


@dataclass
class ServingStats:
    """Plain counters of the served path, kept by whoever serves.

    ``requests`` counts the requests answered (``Deployment.serve``),
    ``segment_calls`` the segments run, and ``segment_traces`` the times
    JAX traced a segment's scan body: that Python runs only while JAX
    traces, so the count is exact and costs nothing once a body is traced.
    Each segment's program is traced once per (segment, input shape) in the
    process and reused after that, across re-splits too, so a warm server
    counts none.  The trace counts into the stats of the runner whose call
    caused it.  Griffin segments have no scan body and count none.
    ``attention_kernel_layers`` counts the attention layers that ran
    through the splash kernel, decided per segment call from its row count
    by the predicate the program itself uses
    (:func:`~repro.models.attention.uses_flash_kernel`).
    """

    requests: int = 0
    segment_calls: int = 0
    segment_traces: int = 0
    attention_kernel_layers: int = 0


# the stats that a trace of a segment's scan body counts into: those of the
# runner whose call is tracing it
_TRACING_FOR: contextvars.ContextVar[ServingStats | None] = \
    contextvars.ContextVar("segment_tracing_for", default=None)


def _count_trace() -> None:
    stats = _TRACING_FOR.get()
    if stats is not None:
        stats.segment_traces += 1


def _tf_slice_blocks(params: Any, lo: int, hi: int) -> Any:
    return jax.tree_util.tree_map(lambda a: a[lo:hi], params["blocks"])


def _static_window(cfg: Any) -> int | None:
    """The attention window every scanned block gets, as a Python int, where
    the schedule is uniform (so the program knows it while tracing); None
    where blocks differ and the scan carries each one's window."""
    w = cfg.windows()
    return int(w[0]) if len(set(w.tolist())) == 1 else None


def _tf_block_range(cfg: Any, lo: int, hi: int, n_units: int):
    """Graph units [lo, hi) of a transformer as block indices: the lead
    (unscanned) blocks [blo, min(bhi, n_lead)) and the scanned ones
    [slo, shi) of ``params["blocks"]``."""
    blo, bhi = max(lo, 1) - 1, min(hi - 1, n_units - 2)
    n_lead = cfg.moe.first_dense_layers if cfg.moe else 0
    return blo, bhi, n_lead, max(blo - n_lead, 0), bhi - n_lead


def _segment_forward(family: str, cfg: Any, lo: int, hi: int, local: bool,
                     n_units: int, params: Any, x: jax.Array) -> jax.Array:
    """Graph units [lo, hi) of one architecture (see :class:`SegmentRunner`)."""
    L = n_units - 2                      # number of blocks

    if family == "transformer":
        if lo == 0:
            x = transformer.embed_tokens(params, cfg, x)
        blo, bhi, n_lead, slo, shi = _tf_block_range(cfg, lo, hi, n_units)
        for i in range(blo, min(bhi, n_lead)):
            dense_cfg = dataclasses.replace(
                cfg, moe=None, d_ff=cfg.moe.dense_d_ff or cfg.d_ff)
            li = i - blo if local else i
            x = transformer.block_forward(
                x, params["lead_blocks"][li], dense_cfg, window=0)
        if shi > slo:
            sub = (params["blocks"] if local
                   else _tf_slice_blocks(params, slo, shi))
            window = _static_window(cfg)
            if window is None:       # per-layer windows ride the scan
                xs = (sub, jnp.asarray(cfg.windows())[n_lead + slo:n_lead + shi])
            else:                    # one static window: no traced mask
                xs = sub

            def body(h, inputs):
                _count_trace()
                lp, w = inputs if window is None else (inputs, window)
                return transformer.block_forward(h, lp, cfg, window=w), None

            x, _ = jax.lax.scan(body, x, xs)
        if hi == L + 2:
            x = transformer.apply_norm(x, params["final_norm"], cfg.norm)
            return transformer.logits_fn(params, cfg, x)
        return x

    if family == "mamba2":
        if lo == 0:
            x = mamba2.embed_tokens(params, cfg, x)
            lo = 1
        blo, bhi = lo - 1, min(hi - 1, L)
        if bhi > blo:
            sub = (params["blocks"] if local
                   else _tf_slice_blocks(params, blo, bhi))

            def body(h, lp):
                _count_trace()
                return mamba2.block_forward(h, lp, cfg), None

            x, _ = jax.lax.scan(body, x, sub)
        if hi == L + 2:
            x = mamba2.apply_norm(x, params["final_norm"], cfg.norm)
            return mamba2.logits_fn(params, cfg, x)
        return x

    if family == "griffin":
        if lo == 0:
            x = griffin.embed_tokens(params, cfg, x)
            lo = 1
        blo, bhi = lo - 1, min(hi - 1, L)
        kinds = cfg.layer_kinds()
        glen = len(cfg.pattern)
        n_groups = cfg.n_layers // glen
        for li in range(blo, bhi):
            if li < n_groups * glen:
                g, i = divmod(li, glen)
                gp = jax.tree_util.tree_map(
                    lambda a, g=g: a[g], params["groups"])
                tm, mp = gp[f"t{i}"], gp[f"m{i}"]
            else:
                tl = params["tail"][li - n_groups * glen]
                tm, mp = tl["t"], tl["m"]
            if kinds[li] == "rec":
                x = griffin.rec_forward(x, tm, cfg)
            else:
                x = griffin.attn_forward(x, tm, cfg)
            # round at every layer boundary as a cut would, so that where
            # the split falls never changes what the layers compute
            x = jax.lax.optimization_barrier(griffin.mlp_forward(x, mp, cfg))
        if hi == L + 2:
            x = griffin.apply_norm(x, params["final_norm"], cfg.norm)
            return griffin.logits_fn(params, cfg, x)
        return x

    raise ValueError(family)


@functools.cache
def _segment_program(family: str, cfg: Any, lo: int, hi: int, local: bool,
                     n_units: int):
    """The compiled program of one segment, shared by every runner of the
    same segment in the process: keyed on what its trace depends on, so a
    rebuilt chain finds the programs of a split it has run before.  The
    params are an argument, never a constant of the program."""

    def segment(params, x):
        return _segment_forward(family, cfg, lo, hi, local, n_units, params, x)

    # the device trace names the program jit_segment_<lo>_<hi>
    segment.__name__ = f"segment_{lo}_{hi}"
    return jax.jit(segment)


@dataclass
class SegmentRunner:
    """Executes graph units [lo, hi) for one architecture.

    ``local=False`` (default) indexes block stacks GLOBALLY — ``params`` is
    the full parameter tree and the runner picks its own layers out of it.
    ``local=True`` expects the segment-local view produced by
    :func:`split_params` (what actually ships to a node): block stacks are
    pre-sliced to this segment, so they are consumed whole.  Layer-position
    effects (attention windows, griffin's layer-kind pattern) always use
    global positions in both modes.

    The segment runs as one jitted program (embed, blocks, final norm and
    head, as the range holds them), traced once per input shape and shared
    with every other runner of the same segment.
    """

    bundle: ModelBundle
    lo: int
    hi: int
    local: bool = False
    stats: ServingStats = dataclasses.field(default_factory=ServingStats)
    _kernel_layers: dict[int, int] = dataclasses.field(
        default_factory=dict, init=False, repr=False)

    def __post_init__(self) -> None:
        n = self.n_units
        assert 0 <= self.lo < self.hi <= n
        self._program = _segment_program(self.bundle.family, self.bundle.cfg,
                                         self.lo, self.hi, self.local, n)

    @property
    def n_units(self) -> int:
        return len(self.bundle.model_graph())

    def attention_kernel_layers(self, rows: int) -> int:
        """The attention layers of this segment that run the splash kernel
        on a ``rows``-row input: each transformer block decides as
        ``_segment_forward`` calls it (lead blocks at window 0, scanned ones
        at :func:`_static_window`, both from position 0).  Kept per row
        count, since the chain asks on every request."""
        if rows not in self._kernel_layers:
            count = 0
            if self.bundle.family == "transformer":
                cfg = self.bundle.cfg
                blo, bhi, n_lead, slo, shi = _tf_block_range(
                    cfg, self.lo, self.hi, self.n_units)
                count = (max(min(bhi, n_lead) - blo, 0)
                         * uses_flash_kernel(rows, 0, 0)
                         + max(shi - slo, 0)
                         * uses_flash_kernel(rows, _static_window(cfg), 0))
            self._kernel_layers[rows] = count
        return self._kernel_layers[rows]

    def __call__(self, params: Any, x: jax.Array) -> jax.Array:
        """x: token ids [B,S] if lo==0, else boundary activations [B,S,d].

        Returns boundary activations, or fp32 logits if hi == n_units.
        """
        token = _TRACING_FOR.set(self.stats)
        try:
            return self._program(params, x)
        finally:
            _TRACING_FOR.reset(token)


def split_params(bundle: ModelBundle, params: Any,
                 boundaries: tuple[int, ...]) -> list[Any]:
    """Per-segment param subsets (what RB ships to each node).

    Returns one params-view per segment containing only what that segment's
    units need.  Shared trees (embed for tied heads) are included where used.
    """
    out = []
    L = len(bundle.model_graph()) - 2
    tied = getattr(bundle.cfg, "tie_embeddings", False)
    for lo, hi in zip(boundaries[:-1], boundaries[1:]):
        seg: dict[str, Any] = {}
        if lo == 0 or (hi == L + 2 and tied):
            seg["embed"] = params["embed"]
        if hi == L + 2:
            seg["final_norm"] = params["final_norm"]
            if not tied and "head" in params:
                seg["head"] = params["head"]
        if "prefix_proj" in params and lo == 0:
            seg["prefix_proj"] = params["prefix_proj"]
        blo, bhi = max(lo - 1, 0), min(hi - 1, L)
        if bhi > blo:
            if "blocks" in params:
                moe = getattr(bundle.cfg, "moe", None)
                n_lead = moe.first_dense_layers if moe else 0
                if n_lead and blo < n_lead:
                    seg["lead_blocks"] = params["lead_blocks"][blo:min(bhi, n_lead)]
                slo, shi = max(blo - n_lead, 0), bhi - n_lead
                if shi > slo:
                    seg["blocks"] = _tf_slice_blocks(params, slo, shi)
            else:  # griffin
                seg["groups"] = params["groups"]
                seg["tail"] = params["tail"]
        out.append(seg)
    return out


@dataclass
class BoundSegment:
    """A :class:`SegmentRunner` bound to the params it runs with."""

    runner: SegmentRunner
    params: Any

    @property
    def lo(self) -> int:
        return self.runner.lo

    @property
    def hi(self) -> int:
        return self.runner.hi

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.runner(self.params, x)


@dataclass
class SegmentChain:
    """THE segment-execution entrypoint: a split scheme bound to params.

    Everything that drives segments — the inference engine, the segment
    profiler, and the equivalence tests — builds one of these instead of
    hand-rolling `SegmentRunner` loops, so they all execute the exact same
    path.  With ``slice_params=True`` (default) each segment is bound to the
    :func:`split_params` view of its own units — the tree a node actually
    holds in deployment; ``slice_params=False`` binds every segment to the
    full tree with global indexing (the historical :func:`run_chain`
    behaviour).  Both produce bit-identical outputs (test-enforced).

    ``transfer_hook(j, x)`` — e.g. an
    :class:`~repro.serving.transfer.ActivationTransport` — sees the
    activations crossing boundary ``j`` and returns what arrives on the
    other side.

    Each segment runs inside a profiler span ``segment`` (arguments ``j``,
    ``lo``, ``hi``) and counts into ``stats`` (its calls, and its attention
    layers that run the splash kernel at the request's row count), which an
    owner that rebuilds the chain passes on so its counts outlive the chain.
    """

    bundle: ModelBundle
    params: Any
    boundaries: tuple[int, ...]
    transfer_hook: Any = None
    slice_params: bool = True
    stats: ServingStats = dataclasses.field(default_factory=ServingStats)
    segments: list[BoundSegment] = dataclasses.field(default_factory=list)

    def __post_init__(self) -> None:
        pairs = list(zip(self.boundaries[:-1], self.boundaries[1:]))
        if self.slice_params:
            views = split_params(self.bundle, self.params, self.boundaries)
        else:
            views = [self.params] * len(pairs)
        self.segments = [
            BoundSegment(SegmentRunner(self.bundle, lo, hi,
                                       local=self.slice_params,
                                       stats=self.stats), view)
            for (lo, hi), view in zip(pairs, views)
        ]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        x = tokens
        n = len(self.bundle.model_graph())
        rows = tokens.shape[1]
        for j, seg in enumerate(self.segments):
            with TraceAnnotation("segment", j=j, lo=seg.lo, hi=seg.hi):
                self.stats.segment_calls += 1
                self.stats.attention_kernel_layers += \
                    seg.runner.attention_kernel_layers(rows)
                x = seg(x)
            if self.transfer_hook is not None and seg.hi < n:
                x = self.transfer_hook(j, x)
        return x


def run_chain(bundle: ModelBundle, params: Any, boundaries: tuple[int, ...],
              tokens: jax.Array, *, transfer_hook=None) -> jax.Array:
    """Execute the full split chain over the FULL param tree.

    Thin wrapper over :class:`SegmentChain` with ``slice_params=False``;
    kept for callers that hold one un-split tree.
    """
    chain = SegmentChain(bundle, params, boundaries,
                         transfer_hook=transfer_hook, slice_params=False)
    return chain(tokens)
