"""Split-inference serving: segments, transport, engine, batching."""

from .batching import BatchStats, Request, WaveBatcher
from .engine import SplitInferenceEngine
from .profiler import SegmentProfiler
from .segments import (BoundSegment, SegmentChain, SegmentRunner,
                       ServingStats, split_params)
from .transfer import ActivationTransport, TransferStats

__all__ = ["ActivationTransport", "BatchStats", "BoundSegment", "Request",
           "SegmentChain", "SegmentProfiler", "SegmentRunner",
           "ServingStats", "SplitInferenceEngine", "TransferStats",
           "WaveBatcher",
           "split_params"]
