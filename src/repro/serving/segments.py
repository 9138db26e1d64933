"""Segment execution: run a contiguous unit range of a model on one node.

This is the paper's S_j made executable.  The orchestrator's ModelGraph units
are [embed, block_0..block_{L-1}, lm_head]; a :class:`SegmentRunner` takes a
(lo, hi) unit range and runs exactly those units, consuming/producing boundary
activations.  Chaining runners over a split scheme reproduces the monolithic
forward bit-for-bit (tested in tests/test_serving.py) — re-splitting changes
WHERE layers run, never WHAT they compute.

Each model family owns what a node holds of its tree and the walk over it
(the ``segment_*`` functions of its :class:`~repro.models.api.ModelBundle`);
this module compiles, binds, chains and counts segments of any family.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
from jax.profiler import TraceAnnotation

from ..models.api import ModelBundle

__all__ = ["BoundSegment", "SegmentChain", "SegmentRunner", "ServingStats",
           "split_params"]


@dataclass
class ServingStats:
    """Plain counters of the served path, kept by whoever serves.

    ``requests`` counts the requests answered (``Deployment.serve``),
    ``segment_calls`` the segments run, and ``segment_traces`` the times
    JAX traced a segment's program, of any family and whether or not it
    holds blocks: that Python runs only while JAX traces, so the count is
    exact and costs nothing once a program is traced.  Each segment's
    program is traced once per (segment, input shape) in the process and
    reused after that, across re-splits too, so a warm server counts none.
    The trace counts into the stats of the runner whose call caused it.
    ``attention_kernel_layers`` counts the attention layers that ran
    through the splash kernel, decided per segment call from its row count
    by the predicate the program itself uses
    (:func:`~repro.models.attention.uses_flash_kernel`).
    """

    requests: int = 0
    segment_calls: int = 0
    segment_traces: int = 0
    attention_kernel_layers: int = 0


# the stats that a trace of a segment's program counts into: those of the
# runner whose call is tracing it
_TRACING_FOR: contextvars.ContextVar[ServingStats | None] = \
    contextvars.ContextVar("segment_tracing_for", default=None)


@functools.cache
def _segment_program(forward: Callable[..., jax.Array], cfg: Any, lo: int,
                     hi: int):
    """The compiled program of one segment, shared by every runner of the
    same segment in the process: keyed on what its trace depends on (the
    family's ``segment_forward``, its config and the range), so a rebuilt
    chain or bundle finds the programs of a split it has run before.  The
    params are an argument, never a constant of the program."""

    def segment(params, x):
        stats = _TRACING_FOR.get()
        if stats is not None:
            stats.segment_traces += 1
        return forward(cfg, params, lo, hi, x)

    # the device trace names the program jit_segment_<lo>_<hi>
    segment.__name__ = f"segment_{lo}_{hi}"
    return jax.jit(segment)


@dataclass
class SegmentRunner:
    """Executes graph units [lo, hi) of one model over their segment view:
    the tree :func:`split_params` stages for them, which for the whole
    range [0, n) is the full tree.  Layer-position effects (attention
    windows, layer-kind patterns) use global positions.  A part-range
    runner handed a block stack of another size (the full tree's, say)
    raises while its program traces.

    The segment runs as one jitted program (embed, blocks, final norm and
    head, as the range holds them), traced once per input shape and shared
    with every other runner of the same segment.
    """

    bundle: ModelBundle
    lo: int
    hi: int
    stats: ServingStats = dataclasses.field(default_factory=ServingStats)
    n_units: int = dataclasses.field(init=False)
    _kernel_layers: dict[int, int] = dataclasses.field(
        default_factory=dict, init=False, repr=False)

    def __post_init__(self) -> None:
        self.n_units = len(self.bundle.model_graph())
        assert 0 <= self.lo < self.hi <= self.n_units
        self._program = _segment_program(self.bundle.segment_forward,
                                         self.bundle.cfg, self.lo, self.hi)

    def attention_kernel_layers(self, rows: int) -> int:
        """The attention layers of this segment that run the splash kernel
        on a ``rows``-row input, as the family counts them.  Kept per row
        count, since the chain asks on every request."""
        if rows not in self._kernel_layers:
            self._kernel_layers[rows] = self.bundle.segment_kernel_layers(
                self.bundle.cfg, self.lo, self.hi, rows)
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
    """Per-segment param views (what RB ships to each node): one per
    segment, holding only what that segment's units need."""
    return [bundle.segment_params(bundle.cfg, params, lo, hi)
            for lo, hi in zip(boundaries[:-1], boundaries[1:])]


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
    path.  Each segment is bound to the :func:`split_params` view of its
    own units — the tree a node actually holds in deployment.

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
    stats: ServingStats = dataclasses.field(default_factory=ServingStats)
    segments: list[BoundSegment] = dataclasses.field(init=False)

    def __post_init__(self) -> None:
        views = split_params(self.bundle, self.params, self.boundaries)
        self.segments = [
            BoundSegment(SegmentRunner(self.bundle, lo, hi, stats=self.stats),
                         view)
            for lo, hi, view in zip(self.boundaries[:-1], self.boundaries[1:],
                                    views)
        ]

    def __call__(self, tokens: jax.Array) -> jax.Array:
        x = tokens
        rows = tokens.shape[1]
        for j, seg in enumerate(self.segments):
            with TraceAnnotation("segment", j=j, lo=seg.lo, hi=seg.hi):
                self.stats.segment_calls += 1
                self.stats.attention_kernel_layers += \
                    seg.runner.attention_kernel_layers(rows)
                x = seg(x)
            if self.transfer_hook is not None and seg.hi < seg.runner.n_units:
                x = self.transfer_hook(j, x)
        return x
