"""Fleet-wide batched cost evaluation + batched migration DP.

The PR-1 fleet monitoring cycle spent ~80 ms/cycle at 32 saturated sessions
because the *decision* hot path was per-session Python: ``chain_latency`` /
``evaluate`` loops priced every session's current config each cycle, and each
triggered session ran its own numpy placement DP plus a Φ local search.  This
module batches both halves across the session set, the same way
:class:`~repro.core.splitter.BatchedJointSplitter` already batches re-splits:

* :func:`pack_sessions` — pad the per-session (segment, placement, workload)
  tensors to a shared ``(B, K)`` layout (power-of-two padded on both axes so
  the number of compiled variants stays ``O(log B · log K)`` per fleet size).
* :func:`packed_induced_loads` — vectorized numpy replacement for the
  per-session :func:`repro.core.fleet.session_induced_loads` loop: one shot
  of scatter-adds yields every session's induced node ρ / link ρ / resident
  weights, from which each session's *effective* C(t) (everyone else folded
  in as load) falls out as array arithmetic.
* :class:`FleetCostEvaluator` — a jitted batched mirror of
  :func:`repro.core.cost_model.chain_latency` and
  :func:`repro.core.cost_model.evaluate`: one XLA dispatch prices the whole
  fleet, each session against its own effective background-utilization vector
  and link matrix (float64 so it is bit-comparable to the numpy reference).
* :class:`BatchedMigrationSolver` — ``jax.vmap`` of the placement chain DP
  (Eq. 7: fixed boundaries, choose nodes) with per-step validity masking, so
  all triggered sessions' migration searches resolve in ONE jitted call
  instead of one numpy DP + Python local search per session.

Exactness: the evaluator reproduces the numpy cost model to float64 rounding;
the migration DP is exact on the same additive surrogate as
:func:`repro.core.placement.solve_placement_chain_dp` (both property-tested in
``tests/test_fleet_eval.py``).

Resident fleet state (PR 3)
---------------------------

PR 2 still rebuilt the whole fleet's (B, K) tensors from Python session
objects every monitoring cycle (``FleetOrchestrator._pack_fleet`` →
:func:`pack_sessions`), folded induced loads with host-side ``np.add.at``
scatters, and re-transferred everything to device — O(fleet) host work per
tick even when nothing changed.  :class:`FleetStateBuffers` inverts the
ownership: sessions live as ROWS of long-lived device tensors,

* admit / depart / commit apply row-level ``.at[b].set(...)`` updates
  (amortized-doubling growth of the row axis, power-of-two growth of the
  segment axis, so compiled variants stay O(log B · log K)),
* the induced-load fold moves onto jitted scatter-adds inside
  :class:`ResidentFleetKernel`'s fused pricing program (loads → effective
  C(t) → batched Φ → per-session trigger env in ONE dispatch), and
* the migration DP + candidate pricing run as a second fused program with a
  device-side backtrack, so only O(B) trigger scalars and the triggered
  set's assignments ever return to host.

**Lifecycle / ownership**: a :class:`~repro.core.fleet.FleetOrchestrator`
owns exactly one :class:`FleetStateBuffers`; the orchestrator's ``admit`` /
``depart`` / ``_commit`` are the only writers.  Anything else (simulator
ticks, admission pricing, benchmarks) reads through the orchestrator's
``price_fleet`` / ``resident_table`` accessors.  Mutating a
``FleetSession``'s config without going through the orchestrator desyncs
the buffers; ``FleetOrchestrator.invalidate_resident_state()`` forces a
cold rebuild (bit-identical to a fresh :func:`pack_sessions` repack — the
equivalence is test-enforced in ``tests/test_resident_state.py``).
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import Sequence

import numpy as np

from .cost_model import (_EPS, _RHO_CAP, AnalyticCostModel, CostModel,
                         CostWeights, SystemState, Workload)
from .forecast import seasonal_update, worst_case_capacity
from .graph import ModelGraph
from .placement import Solution

__all__ = [
    "PackedSessions",
    "pack_sessions",
    "packed_induced_loads",
    "FleetCostEvaluator",
    "BatchedMigrationSolver",
    "BatchedRepairPass",
    "FleetStateBuffers",
    "FixedPointResult",
    "ResidentFleetKernel",
    "ResidentPrice",
    "ShardScreen",
    "ShardedFleetState",
    "gather_rows",
]

_BIG = 1e30

# process-wide mutation stamps for FleetStateBuffers (see .version)
_BUF_VERSIONS = itertools.count(1)


def _pow2(x: int) -> int:
    return 1 << max(0, x - 1).bit_length()


@dataclass(frozen=True)
class PackedSessions:
    """B sessions' chains padded to a shared (B, K) segment layout.

    Row ``b`` describes session ``b``'s current (boundaries, assignment):
    segment k covers ``seg_flops[b, k]`` FLOPs/token and ``seg_wbytes[b, k]``
    parameter bytes on node ``seg_node[b, k]``; ``xfer_bytes_tok[b, k]`` is
    the activation bytes/token entering segment k (0 for k = 0 — the cost
    model does not charge the ingress hop).  ``valid`` masks padding rows and
    ``n_segs[b]`` is the true segment count.
    """

    seg_flops: np.ndarray       # (B, K) float64
    seg_wbytes: np.ndarray      # (B, K) float64
    seg_priv: np.ndarray        # (B, K) bool
    seg_node: np.ndarray        # (B, K) int64 (0-padded)
    valid: np.ndarray           # (B, K) bool
    xfer_bytes_tok: np.ndarray  # (B, K) float64; entry k is the k-1→k boundary
    n_segs: np.ndarray          # (B,) int64
    t_in: np.ndarray            # (B,) float64
    t_out: np.ndarray           # (B,) float64
    lam: np.ndarray             # (B,) float64
    source: np.ndarray          # (B,) int64
    input_bytes_tok: np.ndarray  # (B,) float64 (ingress bytes, migration DP)
    boundaries: tuple[tuple[int, ...], ...]  # per-session, unpadded

    @property
    def batch(self) -> int:
        return int(self.seg_flops.shape[0])

    @property
    def max_segs(self) -> int:
        return int(self.seg_flops.shape[1])

    def with_assignment(self, assignments: Sequence[Sequence[int]]) -> "PackedSessions":
        """Same chains, different placements (candidate evaluation)."""
        seg_node = np.zeros_like(self.seg_node)
        for b, a in enumerate(assignments):
            seg_node[b, : len(a)] = a
        return PackedSessions(
            self.seg_flops, self.seg_wbytes, self.seg_priv, seg_node,
            self.valid, self.xfer_bytes_tok, self.n_segs, self.t_in,
            self.t_out, self.lam, self.source, self.input_bytes_tok,
            self.boundaries,
        )

    def rows(self, idx: Sequence[int]) -> "PackedSessions":
        """Row subset (e.g. the triggered sessions only)."""
        ix = np.asarray(idx, dtype=np.int64)
        return PackedSessions(
            self.seg_flops[ix], self.seg_wbytes[ix], self.seg_priv[ix],
            self.seg_node[ix], self.valid[ix], self.xfer_bytes_tok[ix],
            self.n_segs[ix], self.t_in[ix], self.t_out[ix], self.lam[ix],
            self.source[ix], self.input_bytes_tok[ix],
            tuple(self.boundaries[int(i)] for i in idx),
        )


def pack_sessions(
    items: Sequence[tuple[ModelGraph, Sequence[int], Sequence[int], Workload, int, float]],
    *,
    pad_pow2: bool = True,
    min_k: int = 0,
) -> PackedSessions:
    """Pack (graph, boundaries, assignment, workload, source, input_bytes).

    Segment quantities come from the graphs' prefix sums, so packing is
    O(B·K) array slicing with no cost-model calls.  ``min_k`` floors the
    padded segment axis — callers evaluating a *subset* of a fleet pass the
    fleet's K so every pack in a monitoring cycle shares one compiled shape.
    """
    B = len(items)
    kmax = max(max(len(b) - 1 for _, b, _, _, _, _ in items), min_k)
    K = _pow2(kmax) if pad_pow2 else kmax
    seg_flops = np.zeros((B, K))
    seg_w = np.zeros((B, K))
    seg_priv = np.zeros((B, K), dtype=bool)
    seg_node = np.zeros((B, K), dtype=np.int64)
    valid = np.zeros((B, K), dtype=bool)
    xbytes = np.zeros((B, K))
    n_segs = np.zeros(B, dtype=np.int64)
    t_in = np.zeros(B)
    t_out = np.zeros(B)
    lam = np.zeros(B)
    source = np.zeros(B, dtype=np.int64)
    in_bytes = np.zeros(B)
    bounds: list[tuple[int, ...]] = []
    for i, (g, b, a, wl, src, ibt) in enumerate(items):
        bb = np.asarray(b, dtype=np.int64)
        k = len(bb) - 1
        seg_flops[i, :k] = g._flops_ps[bb[1:]] - g._flops_ps[bb[:-1]]
        seg_w[i, :k] = g._wbytes_ps[bb[1:]] - g._wbytes_ps[bb[:-1]]
        seg_priv[i, :k] = (g._priv_ps[bb[1:]] - g._priv_ps[bb[:-1]]) > 0
        seg_node[i, :k] = a
        valid[i, :k] = True
        # bytes/token crossing each *interior* boundary (entering segment k≥1)
        xbytes[i, 1:k] = [g.boundary_act_bytes(int(x)) for x in bb[1:-1]]
        n_segs[i] = k
        t_in[i], t_out[i] = float(wl.tokens_in), float(wl.tokens_out)
        lam[i] = float(wl.arrival_rate)
        source[i] = int(src)
        in_bytes[i] = float(ibt)
        bounds.append(tuple(int(x) for x in bb))
    return PackedSessions(
        seg_flops, seg_w, seg_priv, seg_node, valid, xbytes, n_segs,
        t_in, t_out, lam, source, in_bytes, tuple(bounds),
    )


def packed_induced_loads(
    packed: PackedSessions, state: SystemState
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Every session's induced (node ρ, link ρ, resident bytes) at once.

    Vectorized equivalent of looping :func:`repro.core.fleet.
    session_induced_loads` over the fleet: raw (un-derated) λ·service-time
    scattered onto nodes, boundary traffic scattered onto links, weights onto
    nodes.  Returns ``(node_rho (B, n), link_rho (B, n, n), wbytes (B, n))``.
    """
    B, K = packed.seg_flops.shape
    n = state.num_nodes
    f = state.flops_per_s[packed.seg_node]            # (B, K)
    m = state.mem_bw[packed.seg_node]
    ft = packed.seg_flops / np.maximum(f, _EPS)
    svc = (packed.t_in[:, None] * ft
           + packed.t_out[:, None]
           * np.maximum(ft, packed.seg_wbytes / np.maximum(m, _EPS)))
    svc = np.where(packed.valid, svc, 0.0)
    contrib = packed.lam[:, None] * svc
    rows = np.repeat(np.arange(B), K)
    node_rho = np.zeros((B, n))
    np.add.at(node_rho, (rows, packed.seg_node.ravel()), contrib.ravel())
    wbytes = np.zeros((B, n))
    np.add.at(wbytes, (rows, packed.seg_node.ravel()),
              np.where(packed.valid, packed.seg_wbytes, 0.0).ravel())

    # link loads: boundary k ≥ 1 moves xbytes·total_tokens from node k-1 to k
    prev = np.concatenate(
        [packed.source[:, None], packed.seg_node[:, :-1]], axis=1
    )
    total_tok = packed.t_in + packed.t_out
    bw = state.link_bw[prev, packed.seg_node]         # (B, K)
    cross = (prev != packed.seg_node) & packed.valid & (packed.xfer_bytes_tok > 0)
    lrho = np.where(
        cross,
        packed.lam[:, None] * packed.xfer_bytes_tok * total_tok[:, None]
        / np.maximum(bw, _EPS),
        0.0,
    )
    link_rho = np.zeros((B, n, n))
    np.add.at(
        link_rho,
        (rows, prev.ravel(), packed.seg_node.ravel()),
        lrho.ravel(),
    )
    return node_rho, link_rho, wbytes


# --------------------------------------------------------------------------- #
# jitted batched Φ evaluator
# --------------------------------------------------------------------------- #
def _make_eval(n: int, alpha: float, beta: float, gamma: float, mem_penalty: float):
    """Batched (B, K)-shaped mirror of chain_latency + evaluate."""
    import jax.numpy as jnp

    def ev(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
           t_in, t_out, lam, bg, link_bw, link_lat, flops_per_s, mem_bw,
           trusted, mem_bytes):
        B, K = seg_flops.shape
        bidx = jnp.arange(B)[:, None]
        derate = jnp.maximum(_EPS, 1.0 - bg)                     # (B, n)
        f_eff = jnp.maximum(flops_per_s[None, :] * derate, _EPS)
        m_eff = jnp.maximum(mem_bw[None, :] * derate, _EPS)
        f_seg = jnp.take_along_axis(f_eff, seg_node, axis=1)     # (B, K)
        m_seg = jnp.take_along_axis(m_eff, seg_node, axis=1)
        ft = seg_flops / f_seg
        svc = t_in[:, None] * ft + t_out[:, None] * jnp.maximum(ft, seg_w / m_seg)
        svc = jnp.where(valid, svc, 0.0)

        # raw (un-derated) service for the utilization KPI rho
        f_raw = jnp.maximum(flops_per_s[seg_node], _EPS)
        m_raw = jnp.maximum(mem_bw[seg_node], _EPS)
        ft_r = seg_flops / f_raw
        svc_raw = t_in[:, None] * ft_r + t_out[:, None] * jnp.maximum(
            ft_r, seg_w / m_raw
        )
        svc_raw = jnp.where(valid, svc_raw, 0.0)

        rho_q = jnp.zeros((B, n)).at[bidx, seg_node].add(lam[:, None] * svc)
        rho = bg + jnp.zeros((B, n)).at[bidx, seg_node].add(
            lam[:, None] * svc_raw
        )

        t_proc = svc.sum(axis=1)
        r = jnp.minimum(jnp.take_along_axis(rho_q, seg_node, axis=1), _RHO_CAP)
        t_queue = (svc * r / (1.0 - r)).sum(axis=1)

        prev = jnp.concatenate([seg_node[:, :1], seg_node[:, :-1]], axis=1)
        has_prev = jnp.arange(K)[None, :] > 0
        cross = (prev != seg_node) & valid & has_prev
        bw = link_bw[bidx, prev, seg_node]
        lat = link_lat[prev, seg_node]
        bytes_ = xbytes * (t_in + t_out)[:, None]
        t_tx = jnp.where(cross, bytes_ / jnp.maximum(bw, _EPS) + lat, 0.0).sum(axis=1)

        latency = t_proc + t_queue + t_tx
        util = rho.max(axis=1) + rho.std(axis=1)
        tr_seg = trusted[seg_node]
        priv = (valid & seg_priv & ~tr_seg).sum(axis=1).astype(latency.dtype)
        used = jnp.zeros((B, n)).at[bidx, seg_node].add(
            jnp.where(valid, seg_w, 0.0)
        )
        over = jnp.maximum(0.0, used - mem_bytes).sum(axis=1)
        total = (alpha * latency + beta * util + gamma * priv
                 + mem_penalty * over / 1e9)
        return latency, total, rho

    return ev


class FleetCostEvaluator:
    """One XLA dispatch prices every session against its own effective C(t).

    ``evaluate_batch`` mirrors :func:`repro.core.cost_model.chain_latency`
    (Eq. 10: T_proc + T_queue + T_tx) and the scalar
    :func:`~repro.core.cost_model.evaluate` (Φ + soft memory penalty) exactly,
    computed in float64 inside an ``enable_x64`` scope so results match the
    numpy reference to rounding error.  Compiled once per (B, K, n, weights)
    shape; B and K arrive power-of-two padded from :func:`pack_sessions`.

    ``cost_model`` selects the pricing provider; measured calibration enters
    through :meth:`pack` (a calibrated-graph view of each packed item), so
    the compiled programs are identical for analytic and calibrated runs.
    """

    def __init__(self, cost_model: CostModel | None = None) -> None:
        self._compiled: dict[tuple, object] = {}
        self.cost_model = cost_model if cost_model is not None \
            else AnalyticCostModel()

    def pack(
        self,
        items: Sequence[tuple[ModelGraph, Sequence[int], Sequence[int],
                              Workload, int, float]],
        *,
        pad_pow2: bool = True,
        min_k: int = 0,
    ) -> PackedSessions:
        """:func:`pack_sessions` through this evaluator's cost model."""
        cal = self.cost_model.calibrated
        return pack_sessions(
            [(cal(g), b, a, wl, src, ib) for g, b, a, wl, src, ib in items],
            pad_pow2=pad_pow2, min_k=min_k,
        )

    def _build(self, key, n, weights: CostWeights, mem_penalty: float):
        import jax

        if key not in self._compiled:
            self._compiled[key] = jax.jit(
                _make_eval(n, weights.alpha, weights.beta, weights.gamma,
                           mem_penalty)
            )
        return self._compiled[key]

    def evaluate_batch(
        self,
        packed: PackedSessions,
        *,
        bg: np.ndarray,                 # (B, n) per-session background util
        link_bw: np.ndarray,            # (B, n, n) per-session link bandwidth
        mem_bytes: np.ndarray,          # (B, n) per-session residual memory
        state: SystemState,             # shared capacities / latencies / trust
        weights: CostWeights = CostWeights(),
        mem_penalty: float = 1e3,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Returns (latency (B,), total Φ (B,), node ρ (B, n))."""
        import jax.numpy as jnp
        from jax import enable_x64

        B, K = packed.seg_flops.shape
        n = state.num_nodes
        # pad the batch axis to the next power of two: the triggered-subset
        # size varies cycle to cycle, and each distinct B would otherwise
        # compile a fresh XLA program (recompiles on the hot path)
        Bp = _pow2(B)

        def pad(a):
            if Bp == B:
                return a
            return np.concatenate(
                [a, np.repeat(a[-1:], Bp - B, axis=0)], axis=0
            )

        key = (Bp, K, n, weights, float(mem_penalty))
        fn = self._build(key, n, weights, mem_penalty)
        # the cost model treats an infinite (local) link as free; keep the
        # arrays finite for XLA and let the same-node mask zero those hops
        finite_bw = np.nan_to_num(link_bw, posinf=_BIG)
        with enable_x64(True):
            lat, total, rho = fn(
                jnp.asarray(pad(packed.seg_flops)),
                jnp.asarray(pad(packed.seg_wbytes)),
                jnp.asarray(pad(packed.seg_priv)),
                jnp.asarray(pad(packed.seg_node)),
                jnp.asarray(pad(packed.valid)),
                jnp.asarray(pad(packed.xfer_bytes_tok)),
                jnp.asarray(pad(packed.t_in)), jnp.asarray(pad(packed.t_out)),
                jnp.asarray(pad(packed.lam)), jnp.asarray(pad(bg)),
                jnp.asarray(pad(finite_bw)),
                jnp.asarray(np.nan_to_num(state.link_lat, posinf=_BIG)),
                jnp.asarray(state.flops_per_s), jnp.asarray(state.mem_bw),
                jnp.asarray(state.trusted.astype(bool)),
                jnp.asarray(pad(mem_bytes)),
            )
        return (np.asarray(lat)[:B], np.asarray(total)[:B],
                np.asarray(rho)[:B])


# --------------------------------------------------------------------------- #
# batched migration DP (Eq. 7 vmapped over the triggered set)
# --------------------------------------------------------------------------- #
def _surrogate_inputs(
    packed: PackedSessions,
    *,
    bg: np.ndarray,
    link_bw: np.ndarray,
    state: SystemState,
    mem: np.ndarray | None = None,
):
    """Additive Eq. 7 surrogate tensors for B sessions (host-side numpy).

    Returns ``(exec_cost (B, K, n), xfer (B, K, n, n), src_xfer (B, n))``:
    per-segment M/M/1-inflated derated service with privacy +``_BIG`` masks,
    per-boundary transfer matrices, and the ingress transfer row.  ``mem``
    (B, n) adds the Eq. 4 single-segment mask — a node whose residual memory
    cannot hold a segment's weights alone is +``_BIG`` for that segment,
    masked exactly like a privacy breach (multi-segment accumulation on one
    node is outside the DP state; the repair pass handles it).

    This is the PINNED HOST REFERENCE: the hot paths
    (:class:`BatchedMigrationSolver`, :class:`BatchedRepairPass`, the fused
    migrate kernel) expand the same tensors ON DEVICE from the (B, K)
    ``xfer_bytes_tok`` vector via :func:`_surrogate_batch` — the per-dispatch
    O(B·K·n²) numpy build + upload this function represents is off the
    control plane (ROADMAP open item), and the device expansion is
    equivalence-tested against this function in ``tests/test_fleet_eval.py``.
    """
    B, K = packed.seg_flops.shape
    n = state.num_nodes
    derate = np.maximum(_EPS, 1.0 - bg)                      # (B, n)
    f_eff = np.maximum(state.flops_per_s[None, :] * derate, _EPS)
    m_eff = np.maximum(state.mem_bw[None, :] * derate, _EPS)
    ft = packed.seg_flops[:, :, None] / f_eff[:, None, :]    # (B, K, n)
    svc = (packed.t_in[:, None, None] * ft
           + packed.t_out[:, None, None]
           * np.maximum(ft, packed.seg_wbytes[:, :, None] / m_eff[:, None, :]))
    load = np.minimum(packed.lam[:, None, None] * svc, 0.9)
    exec_cost = svc / (1.0 - load)
    untrusted = ~state.trusted.astype(bool)
    exec_cost = np.where(
        packed.seg_priv[:, :, None] & untrusted[None, None, :],
        _BIG, exec_cost,
    )
    if mem is not None:
        exec_cost = np.where(
            packed.seg_wbytes[:, :, None] > mem[:, None, :], _BIG, exec_cost
        )

    total_tok = (packed.t_in + packed.t_out)[:, None, None, None]
    bw = np.nan_to_num(link_bw, posinf=_BIG)                 # (B, n, n)
    lat = np.nan_to_num(state.link_lat, posinf=_BIG)
    xfer = (packed.xfer_bytes_tok[:, :, None, None] * total_tok
            / np.maximum(bw[:, None], _EPS)) + lat[None, None]
    diag = np.eye(n, dtype=bool)
    xfer[:, :, diag] = 0.0

    src_bytes = packed.input_bytes_tok * (packed.t_in + packed.t_out)
    src_xfer = (src_bytes[:, None]
                / np.maximum(bw[np.arange(B), packed.source], _EPS)
                + lat[packed.source])
    same = packed.source[:, None] == np.arange(n)[None, :]
    src_xfer = np.where(same, 0.0, src_xfer)
    return exec_cost, xfer, src_xfer


def _surrogate_batch(seg_flops, seg_w, seg_priv, xbytes, t_in, t_out, lam,
                     source, input_bytes_tok, bg, lbw, link_lat, flops_per_s,
                     mem_bw, trusted, mem, n: int):
    """Device expansion of the Eq. 7 surrogate tensors from the row layout.

    jnp mirror of :func:`_surrogate_inputs` (the pinned host reference):
    the (B, K, n, n) transfer tensor and (B, K, n) exec-cost tensor are
    expanded INSIDE the jitted programs from the (B, K) boundary-bytes
    vector and the per-row effective link matrix — nothing O(n²·K) is built
    or uploaded host-side per dispatch.  ``mem=None`` statically omits the
    Eq. 4 single-segment mask (the memory-blind PR-2 surrogate).  Callers
    pass ``lbw`` / ``link_lat`` already ``nan_to_num``-finited, exactly like
    the host path.
    """
    import jax.numpy as jnp

    B = seg_flops.shape[0]
    derate = jnp.maximum(_EPS, 1.0 - bg)                      # (B, n)
    f_eff = jnp.maximum(flops_per_s[None, :] * derate, _EPS)
    m_eff = jnp.maximum(mem_bw[None, :] * derate, _EPS)
    ft = seg_flops[:, :, None] / f_eff[:, None, :]            # (B, K, n)
    svc = (t_in[:, None, None] * ft
           + t_out[:, None, None]
           * jnp.maximum(ft, seg_w[:, :, None] / m_eff[:, None, :]))
    load = jnp.minimum(lam[:, None, None] * svc, 0.9)
    exec_cost = svc / (1.0 - load)
    exec_cost = jnp.where(
        seg_priv[:, :, None] & (~trusted)[None, None, :], _BIG, exec_cost
    )
    if mem is not None:
        # Eq. 4 per-step mask: a segment that alone overflows a node's
        # residual memory loses that node inside the DP, not at commit time
        exec_cost = jnp.where(
            seg_w[:, :, None] > mem[:, None, :], _BIG, exec_cost
        )
    total_tok = (t_in + t_out)[:, None, None, None]
    xfer = (xbytes[:, :, None, None] * total_tok
            / jnp.maximum(lbw[:, None], _EPS)) + link_lat[None, None]
    xfer = jnp.where(jnp.eye(n, dtype=bool)[None, None], 0.0, xfer)
    src_bytes = input_bytes_tok * (t_in + t_out)
    src_xfer = (src_bytes[:, None]
                / jnp.maximum(lbw[jnp.arange(B), source], _EPS)
                + link_lat[source])
    src_xfer = jnp.where(
        source[:, None] == jnp.arange(n)[None, :], 0.0, src_xfer
    )
    return exec_cost, xfer, src_xfer


def _make_migration_dp(K: int, n: int):
    """Single-session masked placement DP; lifted over the batch by vmap."""
    import jax
    import jax.numpy as jnp

    def dp(exec_cost, xfer, k_valid, src_xfer):
        # exec_cost (K, n): per-segment cost on each node (+_BIG on privacy
        # breach); xfer (K, n, n): boundary-k transfer matrix; src_xfer (n,)
        # is the ingress transfer row for segment 0.
        C0 = exec_cost[0] + src_xfer

        def step(C, j):
            active = j < k_valid
            cand = C[:, None] + xfer[j] + exec_cost[j][None, :]
            best_prev = jnp.argmin(cand, axis=0)
            newC = jnp.min(cand, axis=0)
            C = jnp.where(active, newC, C)
            parent = jnp.where(active, best_prev, jnp.arange(n))
            return C, parent

        C, parents = jax.lax.scan(step, C0, jnp.arange(1, K))
        return C, parents

    return dp


class BatchedMigrationSolver:
    """All triggered sessions' placement migrations in ONE jitted call.

    Same additive surrogate as :func:`repro.core.placement.
    solve_placement_chain_dp` (per-segment M/M/1-inflated service + boundary
    transfers, privacy as +``_BIG`` masks), with per-session effective states:
    each row carries its own background-utilization vector and link matrix.
    Chains shorter than the padded K are masked with identity DP steps, so
    mixed segment counts share one compiled program.
    """

    def __init__(self) -> None:
        self._compiled: dict[tuple, object] = {}

    def _build(self, B: int, K: int, n: int, use_mem: bool):
        import jax

        key = (B, K, n, use_mem)
        if key not in self._compiled:
            dp = jax.vmap(_make_migration_dp(K, n), in_axes=(0, 0, 0, 0))

            # surrogate expansion fused with the DP: the (B, K, n, n)
            # transfer tensor exists only on device (see _surrogate_batch)
            def run(seg_flops, seg_w, seg_priv, xbytes, n_segs, t_in, t_out,
                    lam, source, input_bytes_tok, bg, lbw, link_lat,
                    flops_per_s, mem_bw, trusted, mem):
                exec_cost, xfer, src_xfer = _surrogate_batch(
                    seg_flops, seg_w, seg_priv, xbytes, t_in, t_out, lam,
                    source, input_bytes_tok, bg, lbw, link_lat, flops_per_s,
                    mem_bw, trusted, mem if use_mem else None, n,
                )
                return dp(exec_cost, xfer, n_segs, src_xfer)

            self._compiled[key] = jax.jit(run)
        return self._compiled[key]

    def solve_batch(
        self,
        packed: PackedSessions,
        *,
        bg: np.ndarray,
        link_bw: np.ndarray,
        state: SystemState,
        mem: np.ndarray | None = None,
    ) -> list[Solution]:
        """``mem`` (B, n) residual memory enables the Eq. 4 per-step mask
        (see :func:`_surrogate_inputs`); ``None`` keeps the memory-blind
        PR-2 surrogate, bit-compatible with the scalar reference DP."""
        import jax.numpy as jnp
        from jax import enable_x64

        B, K = packed.seg_flops.shape
        n = state.num_nodes
        use_mem = mem is not None

        # pow2 batch padding: the triggered-session count varies per cycle;
        # without it every distinct B would recompile (see FleetCostEvaluator)
        Bp = _pow2(B)

        def rep(a):
            if Bp == B:
                return a
            return np.concatenate(
                [a, np.repeat(a[-1:], Bp - B, axis=0)], axis=0
            )

        fn = self._build(Bp, K, n, use_mem)
        with enable_x64(True):
            C, parents = fn(
                jnp.asarray(rep(packed.seg_flops)),
                jnp.asarray(rep(packed.seg_wbytes)),
                jnp.asarray(rep(packed.seg_priv)),
                jnp.asarray(rep(packed.xfer_bytes_tok)),
                jnp.asarray(rep(packed.n_segs)),
                jnp.asarray(rep(packed.t_in)),
                jnp.asarray(rep(packed.t_out)),
                jnp.asarray(rep(packed.lam)),
                jnp.asarray(rep(packed.source)),
                jnp.asarray(rep(packed.input_bytes_tok)),
                jnp.asarray(rep(np.asarray(bg, dtype=np.float64))),
                jnp.asarray(rep(np.nan_to_num(link_bw, posinf=_BIG))),
                jnp.asarray(np.nan_to_num(state.link_lat, posinf=_BIG)),
                jnp.asarray(state.flops_per_s), jnp.asarray(state.mem_bw),
                jnp.asarray(state.trusted.astype(bool)),
                jnp.asarray(rep(np.asarray(
                    mem if use_mem else np.zeros((B, n)), dtype=np.float64
                ))),
            )
        C = np.asarray(C)
        parents = np.asarray(parents)                            # (B, K-1, n)

        out: list[Solution] = []
        for b in range(B):
            k = int(packed.n_segs[b])
            j = int(np.argmin(C[b]))
            assign = [j]
            for step in range(k - 2, -1, -1):
                j = int(parents[b, step, j])
                assign.append(j)
            assign.reverse()
            out.append(
                Solution(packed.boundaries[b], tuple(assign), float(C[b].min()))
            )
        return out


# --------------------------------------------------------------------------- #
# batched Eq. 4 repair (greedy heaviest-segment moves, vmapped)
# --------------------------------------------------------------------------- #
def _make_repair_core(K: int, n: int):
    """Single-session greedy memory repair; lifted over the batch by vmap.

    Device mirror of :func:`repro.core.placement.repair_capacity`'s
    feasibility loop: each iteration moves the heaviest *movable* segment
    off the most overfull node to the cheapest destination that fits
    (movable = some destination has room for it).  A move never creates a
    new violation — the fit check admits only in-capacity destinations — so
    every segment relocates at most once and K iterations suffice; a row
    with no violation is an exact no-op, and a stuck row (nothing movable
    off the worst node) stays put, same as the scalar ``break``.

    Destination choice prices the additive surrogate (exec + the two
    adjacent boundary transfers) instead of the scalar path's full Φ, so
    the chosen node may differ; feasibility restoration is what must match
    (property-tested in ``tests/test_repair_batch.py``).  Privacy enters
    through the +``_BIG`` exec mask: a breaching destination is taken only
    when nothing else fits, exactly like the scalar path's γ-dominated Φ.
    """
    import jax
    import jax.numpy as jnp

    def repair(seg_w, valid, n_segs, assign, mem, exec_cost, xfer, src_xfer):
        # seg_w/valid (K,), assign (K,) int64, mem (n,), exec_cost (K, n),
        # xfer (K, n, n) — boundary k's transfer matrix, src_xfer (n,)
        idx = jnp.arange(n)

        def body(_, a):
            used = jnp.zeros(n).at[a].add(jnp.where(valid, seg_w, 0.0))
            over = jnp.maximum(0.0, used - mem)
            bad = jnp.argmax(over)
            has_over = over[bad] > 0.0
            fits = ((used[None, :] + seg_w[:, None] <= mem[None, :])
                    & (idx[None, :] != bad))                  # (K, n)
            movable = valid & (a == bad) & fits.any(axis=1)
            k_star = jnp.argmax(jnp.where(movable, seg_w, -1.0))
            can_move = has_over & movable.any()
            prev = a[jnp.maximum(k_star - 1, 0)]
            in_c = jnp.where(k_star == 0, src_xfer, xfer[k_star, prev])
            nxt_k = jnp.minimum(k_star + 1, K - 1)
            out_c = jnp.where(k_star + 1 < n_segs, xfer[nxt_k, :, a[nxt_k]], 0.0)
            cost = exec_cost[k_star] + in_c + out_c
            dest = jnp.argmin(jnp.where(fits[k_star], cost, jnp.inf))
            return jnp.where(can_move, a.at[k_star].set(dest), a)

        return jax.lax.fori_loop(0, K, body, assign)

    return repair


def _make_repair(K: int, n: int):
    """Batched surrogate expansion + greedy Eq. 4 repair, one program.

    The destination-cost surrogate is memory-UNmasked (matching the host
    reference path: the fit check, not the price, enforces capacity), and
    its (B, K, n, n) transfer tensor is expanded on device
    (:func:`_surrogate_batch`) — nothing O(n²) crosses the host boundary.
    """
    import jax

    rep = _make_repair_core(K, n)

    def run(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes, n_segs,
            t_in, t_out, lam, source, input_bytes_tok, bg, lbw, mem,
            link_lat, flops_per_s, mem_bw, trusted):
        exec_cost, xfer, src_xfer = _surrogate_batch(
            seg_flops, seg_w, seg_priv, xbytes, t_in, t_out, lam, source,
            input_bytes_tok, bg, lbw, link_lat, flops_per_s, mem_bw,
            trusted, None, n,
        )
        return jax.vmap(rep)(seg_w, valid, n_segs, seg_node, mem,
                             exec_cost, xfer, src_xfer)

    return run


def _make_repair_price(K: int, n: int, alpha: float, beta: float,
                       gamma: float, mem_penalty: float):
    """Batched repair + Φ pricing of the repaired assignments, one program."""

    rep = _make_repair(K, n)
    ev = _make_eval(n, alpha, beta, gamma, mem_penalty)

    def run(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes, n_segs,
            t_in, t_out, lam, source, input_bytes_tok, bg, lbw, mem,
            link_lat, flops_per_s, mem_bw, trusted):
        assign = rep(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
                     n_segs, t_in, t_out, lam, source, input_bytes_tok,
                     bg, lbw, mem, link_lat, flops_per_s, mem_bw, trusted)
        lat, _, _ = ev(seg_flops, seg_w, seg_priv, assign, valid, xbytes,
                       t_in, t_out, lam, bg, lbw, link_lat, flops_per_s,
                       mem_bw, trusted, mem)
        return assign, lat

    return run


class BatchedRepairPass:
    """All violating sessions' Eq. 4 repairs in ONE jitted call.

    Replaces the per-session ``repair_capacity`` Python Φ loops on the fleet
    control plane (ROADMAP measured ~56 invocations per saturated 32-session
    cycle): the greedy heaviest-segment moves for B sessions run as one
    vmapped device program, pow2-padded on B like the other batched solvers
    so compiled variants stay O(log B) per (K, n).  Rows already feasible
    come back bit-unchanged.  :meth:`repair_and_price_batch` additionally
    prices the repaired assignments (the batched Φ mirror) inside the same
    dispatch, so a violating re-split set costs ONE device round-trip for
    repair *and* latency.  The scalar
    :func:`repro.core.placement.repair_capacity` remains the pinned
    reference path.
    """

    def __init__(self) -> None:
        self._compiled: dict[tuple, object] = {}
        self.dispatches = 0

    def _build(self, B: int, K: int, n: int):
        import jax

        key = (B, K, n)
        if key not in self._compiled:
            self._compiled[key] = jax.jit(_make_repair(K, n))
        return self._compiled[key]

    def _build_priced(self, B: int, K: int, n: int, weights: CostWeights,
                      mem_penalty: float):
        import jax

        key = (B, K, n, weights, float(mem_penalty))
        if key not in self._compiled:
            self._compiled[key] = jax.jit(_make_repair_price(
                K, n, weights.alpha, weights.beta, weights.gamma, mem_penalty
            ))
        return self._compiled[key]

    # program argument order shared by _make_repair and _make_repair_price
    _ARGS = ("seg_flops", "seg_w", "seg_priv", "seg_node", "valid", "xbytes",
             "n_segs", "t_in", "t_out", "lam", "source", "input_bytes_tok",
             "bg", "lbw", "mem")

    @staticmethod
    def _padded(packed: PackedSessions, bg, link_bw, mem):
        """pow2-pad the RAW row tensors only — the Eq. 7 surrogate is
        expanded on device inside the jitted programs (_surrogate_batch)."""
        args = {
            "seg_flops": packed.seg_flops,
            "seg_w": packed.seg_wbytes,
            "seg_priv": packed.seg_priv,
            "seg_node": packed.seg_node,
            "valid": packed.valid,
            "xbytes": packed.xfer_bytes_tok,
            "n_segs": packed.n_segs,
            "t_in": packed.t_in, "t_out": packed.t_out, "lam": packed.lam,
            "source": packed.source,
            "input_bytes_tok": packed.input_bytes_tok,
            "bg": np.asarray(bg, dtype=np.float64),
            "lbw": np.nan_to_num(link_bw, posinf=_BIG),
            "mem": np.asarray(mem, dtype=np.float64),
        }
        B = packed.batch
        Bp = _pow2(B)
        if Bp > B:
            args = {
                k: np.concatenate([a, np.repeat(a[-1:], Bp - B, axis=0)])
                for k, a in args.items()
            }
        return args, Bp

    def _state_tail(self, state: SystemState):
        import jax.numpy as jnp

        return (
            jnp.asarray(np.nan_to_num(state.link_lat, posinf=_BIG)),
            jnp.asarray(state.flops_per_s), jnp.asarray(state.mem_bw),
            jnp.asarray(state.trusted.astype(bool)),
        )

    def repair_batch(
        self,
        packed: PackedSessions,
        *,
        bg: np.ndarray,
        link_bw: np.ndarray,
        mem: np.ndarray,
        state: SystemState,
    ) -> np.ndarray:
        """Repaired assignments (B, K) for the packed rows' current
        ``seg_node`` against per-row residual memory ``mem`` (B, n)."""
        import jax.numpy as jnp
        from jax import enable_x64

        B, K = packed.seg_flops.shape
        a, Bp = self._padded(packed, bg, link_bw, mem)
        fn = self._build(Bp, K, state.num_nodes)
        self.dispatches += 1
        with enable_x64(True):
            out = fn(*(jnp.asarray(a[k]) for k in self._ARGS),
                     *self._state_tail(state))
        return np.asarray(out)[:B]

    def repair_and_price_batch(
        self,
        packed: PackedSessions,
        *,
        bg: np.ndarray,
        link_bw: np.ndarray,
        mem: np.ndarray,
        state: SystemState,
        weights: CostWeights = CostWeights(),
        mem_penalty: float = 1e3,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(repaired assignments (B, K), latency (B,) of the repaired
        assignment) in one fused dispatch — the batched Φ mirror prices
        exactly what :class:`FleetCostEvaluator` would."""
        import jax.numpy as jnp
        from jax import enable_x64

        B, K = packed.seg_flops.shape
        n = state.num_nodes
        a, Bp = self._padded(packed, bg, link_bw, mem)
        fn = self._build_priced(Bp, K, n, weights, mem_penalty)
        self.dispatches += 1
        with enable_x64(True):
            assign, lat = fn(*(jnp.asarray(a[k]) for k in self._ARGS),
                             *self._state_tail(state))
        return np.asarray(assign)[:B], np.asarray(lat)[:B]


# --------------------------------------------------------------------------- #
# device-resident incremental fleet state (PR 3)
# --------------------------------------------------------------------------- #
# buffer attrs deliberately share PackedSessions' field names, so rows copy
# between the two layouts by getattr on the same name
_ROW_FIELDS = ("seg_flops", "seg_wbytes", "seg_priv", "seg_node", "valid",
               "xfer_bytes_tok")
_VEC_FIELDS = ("n_segs", "t_in", "t_out", "lam", "source", "input_bytes_tok")


def gather_rows(rows: Sequence[int], *arrays) -> tuple[np.ndarray, ...]:
    """Fetch a row subset of device arrays to host.

    The per-cycle host round-trip is supposed to be O(triggered set), not
    O(fleet) — every device→host row gather goes through here so that stays
    auditable in one place.  ``np.asarray`` on a committed array is a
    zero-copy view on CPU (and a single contiguous D2H copy elsewhere), and
    the numpy take that follows costs O(rows) — both far cheaper per cycle
    than dispatching a jitted gather per tensor.
    """
    ix = np.asarray(rows, dtype=np.int64)
    return tuple(np.asarray(a)[ix] for a in arrays)


class FleetStateBuffers:
    """Persistent device-resident (B, K) fleet tensors, updated row-wise.

    Row ``b`` holds one live session in the :class:`PackedSessions` layout
    (``active[b]`` masks free rows).  The row axis grows by amortized
    doubling and the segment axis by powers of two, so the fused kernels
    compile O(log B · log K) variants over a fleet's lifetime.  Rows are
    written with ``.at[b].set(...)`` — a departure-then-admit reuses the
    freed slot, so steady-state churn never reallocates.

    Invariant (test-enforced): an inactive row is all-zeros, and every
    active row is bit-identical to what a cold :func:`pack_sessions` repack
    of the same session would produce — :meth:`upsert` builds the row
    through :func:`pack_sessions` itself.
    """

    def __init__(self, *, rows: int = 8, segs: int = 4) -> None:
        import jax.numpy as jnp
        from jax import enable_x64

        rows = _pow2(max(1, rows))
        segs = _pow2(max(1, segs))
        with enable_x64(True):
            self.seg_flops = jnp.zeros((rows, segs))
            self.seg_wbytes = jnp.zeros((rows, segs))
            self.seg_priv = jnp.zeros((rows, segs), dtype=bool)
            self.seg_node = jnp.zeros((rows, segs), dtype=jnp.int64)
            self.valid = jnp.zeros((rows, segs), dtype=bool)
            self.xfer_bytes_tok = jnp.zeros((rows, segs))
            self.n_segs = jnp.zeros(rows, dtype=jnp.int64)
            self.t_in = jnp.zeros(rows)
            self.t_out = jnp.zeros(rows)
            self.lam = jnp.zeros(rows)
            self.source = jnp.zeros(rows, dtype=jnp.int64)
            self.input_bytes_tok = jnp.zeros(rows)
            self.active = jnp.zeros(rows, dtype=bool)
        self.row_of: dict[int, int] = {}
        self._free: list[int] = list(range(rows - 1, -1, -1))
        self._boundaries: list[tuple[int, ...] | None] = [None] * rows
        self.stats = {"row_writes": 0, "rebuilds": 0, "grow_rows": 0,
                      "grow_segs": 0, "pack_time_s": 0.0}
        # globally-unique mutation stamp: every write assigns a fresh value
        # from one process-wide counter, so (even across buffer objects that
        # reuse a freed id) equal stamps imply bit-identical row tensors —
        # the sharded screen keys its stacked-block cache on it
        self.version = next(_BUF_VERSIONS)

    # -- capacity ------------------------------------------------------- #
    @property
    def n_rows(self) -> int:
        return int(self.seg_flops.shape[0])

    @property
    def max_segs(self) -> int:
        return int(self.seg_flops.shape[1])

    def __len__(self) -> int:
        return len(self.row_of)

    def _grow_rows(self, need: int) -> None:
        import jax.numpy as jnp
        from jax import enable_x64

        old = self.n_rows
        new = _pow2(max(need, 2 * old))
        with enable_x64(True):
            for name in _ROW_FIELDS:
                a = getattr(self, name)
                pad = jnp.zeros((new - old, a.shape[1]), dtype=a.dtype)
                setattr(self, name, jnp.concatenate([a, pad], axis=0))
            for name in (*_VEC_FIELDS, "active"):
                a = getattr(self, name)
                pad = jnp.zeros(new - old, dtype=a.dtype)
                setattr(self, name, jnp.concatenate([a, pad]))
        self._free.extend(range(new - 1, old - 1, -1))
        self._boundaries.extend([None] * (new - old))
        self.stats["grow_rows"] += 1
        self.version = next(_BUF_VERSIONS)

    def _grow_segs(self, need: int) -> None:
        import jax.numpy as jnp
        from jax import enable_x64

        old = self.max_segs
        new = _pow2(need)
        if new <= old:
            return
        with enable_x64(True):
            for name in _ROW_FIELDS:
                a = getattr(self, name)
                pad = jnp.zeros((a.shape[0], new - old), dtype=a.dtype)
                setattr(self, name, jnp.concatenate([a, pad], axis=1))
        self.stats["grow_segs"] += 1
        self.version = next(_BUF_VERSIONS)

    # -- row updates ---------------------------------------------------- #
    def upsert(
        self,
        sid: int,
        graph: ModelGraph,
        boundaries: Sequence[int],
        assignment: Sequence[int],
        workload: Workload,
        source_node: int,
        input_bytes_per_token: float,
    ) -> None:
        """Write one session's current config into its row (allocating one)."""
        import jax.numpy as jnp
        from jax import enable_x64

        t0 = time.perf_counter()
        self._grow_segs(len(boundaries) - 1)
        row = self.row_of.get(sid)
        if row is None:
            if not self._free:
                self._grow_rows(self.n_rows + 1)
            row = self._free.pop()
            self.row_of[sid] = row
        one = pack_sessions(
            [(graph, tuple(boundaries), tuple(assignment), workload,
              source_node, input_bytes_per_token)],
            pad_pow2=False, min_k=self.max_segs,
        )
        with enable_x64(True):
            for name in (*_ROW_FIELDS, *_VEC_FIELDS):
                a = getattr(self, name)
                setattr(self, name,
                        a.at[row].set(jnp.asarray(getattr(one, name)[0])))
            self.active = self.active.at[row].set(True)
        self._boundaries[row] = one.boundaries[0]
        self.stats["row_writes"] += 1
        self.stats["pack_time_s"] += time.perf_counter() - t0
        self.version = next(_BUF_VERSIONS)

    def remove(self, sid: int) -> None:
        """Free a departed session's row (zeroed: inactive rows stay zeros)."""
        import jax.numpy as jnp
        from jax import enable_x64

        row = self.row_of.pop(sid)
        with enable_x64(True):
            for name in (*_ROW_FIELDS, *_VEC_FIELDS, "active"):
                a = getattr(self, name)
                setattr(self, name, a.at[row].set(jnp.zeros((), a.dtype)))
        self._boundaries[row] = None
        self._free.append(row)
        self.version = next(_BUF_VERSIONS)

    @classmethod
    def from_sessions(
        cls,
        items: Sequence[tuple[int, tuple]],
        *,
        min_rows: int = 8,
        min_segs: int = 4,
    ) -> "FleetStateBuffers":
        """Cold full repack: ``items`` is [(sid, pack_sessions item), ...].

        Rows land densely in ``items`` order and are bit-identical to a
        :func:`pack_sessions` call over the same items — this IS the
        reference the incremental path is equivalence-tested against.
        """
        import jax.numpy as jnp
        from jax import enable_x64

        t0 = time.perf_counter()
        n = len(items)
        if n == 0:
            return cls(rows=min_rows, segs=min_segs)
        packed = pack_sessions([it for _, it in items], pad_pow2=True,
                               min_k=min_segs)
        buf = cls(rows=max(min_rows, n), segs=packed.max_segs)
        with enable_x64(True):
            for name in (*_ROW_FIELDS, *_VEC_FIELDS):
                a = getattr(buf, name)
                setattr(buf, name,
                        a.at[:n].set(jnp.asarray(getattr(packed, name))))
            buf.active = buf.active.at[:n].set(True)
        buf.row_of = {sid: i for i, (sid, _) in enumerate(items)}
        buf._free = list(range(buf.n_rows - 1, n - 1, -1))
        for i, b in enumerate(packed.boundaries):
            buf._boundaries[i] = b
        buf.stats["rebuilds"] += 1
        buf.stats["pack_time_s"] += time.perf_counter() - t0
        return buf

    # -- host views ----------------------------------------------------- #
    def rows_packed(self, sids: Sequence[int]) -> PackedSessions:
        """Host :class:`PackedSessions` view of the given sessions' rows."""
        rows = [self.row_of[s] for s in sids]
        fields = gather_rows(
            rows, *(getattr(self, name) for name in (*_ROW_FIELDS, *_VEC_FIELDS))
        )
        return PackedSessions(
            *fields,
            boundaries=tuple(self._boundaries[r] for r in rows),
        )


# --------------------------------------------------------------------------- #
# fused monitoring-step kernels over the resident buffers
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ResidentPrice:
    """Device-side outputs of one fused pricing dispatch (row-indexed).

    Only ``lat`` / ``max_util`` / ``min_bw`` — O(B) scalars — are meant to
    be pulled to host every cycle; the effective-state tensors stay on
    device and are row-gathered only for the triggered set.

    The ``*_fc`` fields are populated only when a
    :class:`~repro.core.forecast.CapacityForecaster` rode the dispatch:
    the same quantities priced against the worst-case forecast capacity
    over the horizon (current values until one season has been observed,
    and bit-identically the current values at ``horizon_steps = 0``).
    """

    lat: object        # (B,)   current-config latency per row
    max_util: object   # (B,)   max node util over the nodes the row touches
    min_bw: object     # (B,)   min effective bw over the row's cross hops
    bg: object         # (B, n) effective background util (others folded in)
    link_bw: object    # (B, n, n) effective link bandwidth
    mem: object        # (B, n) residual memory
    tot_node: object   # (n,)   fleet-total induced node rho
    tot_link: object   # (n, n) fleet-total link rho
    tot_w: object      # (n,)   fleet-total resident weight bytes
    lat_fc: object = None       # (B,) latency under worst-case forecast C
    max_util_fc: object = None  # (B,) forecast trigger-env max node util
    min_bw_fc: object = None    # (B,) forecast trigger-env min link bw
    bg_fc: object = None        # (B, n) forecast effective background util
    lbw_fc: object = None       # (B, n, n) forecast effective link bw

    @property
    def has_forecast(self) -> bool:
        return self.lat_fc is not None


def _price_core(n: int, ev, bw_floor: float):
    """The shared fused-pricing body: induced loads → effective C(t) →
    batched Φ → trigger env.

    Mirrors the PR-2 cycle-start sequence exactly: jitted scatter-adds
    replace :func:`packed_induced_loads`'s ``np.add.at``, the fold replicates
    ``FleetOrchestrator._fold_loads``, pricing reuses :func:`_make_eval`, and
    the per-row (max util, min bw) reductions replicate
    ``FleetOrchestrator._session_env``.  Returns a dict so the plain and
    forecast-fused wrappers pick the outputs (and intermediates) they need
    from ONE body that cannot drift between them.
    """
    import jax.numpy as jnp

    def core(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
             t_in, t_out, lam, source, active,
             bg0, link_bw, link_lat, flops_per_s, mem_bw, trusted,
             mem_bytes):
        B, K = seg_flops.shape
        bidx = jnp.arange(B)[:, None]
        av = valid & active[:, None]
        # induced loads: raw (un-derated) λ·service scattered onto nodes
        f_raw = jnp.maximum(flops_per_s[seg_node], _EPS)
        m_raw = jnp.maximum(mem_bw[seg_node], _EPS)
        ft = seg_flops / f_raw
        svc = t_in[:, None] * ft + t_out[:, None] * jnp.maximum(
            ft, seg_w / m_raw
        )
        svc = jnp.where(av, svc, 0.0)
        node_r = jnp.zeros((B, n)).at[bidx, seg_node].add(lam[:, None] * svc)
        wb = jnp.zeros((B, n)).at[bidx, seg_node].add(
            jnp.where(av, seg_w, 0.0)
        )
        prev = jnp.concatenate([source[:, None], seg_node[:, :-1]], axis=1)
        total_tok = t_in + t_out
        cross = (prev != seg_node) & av & (xbytes > 0)
        lrho = jnp.where(
            cross,
            lam[:, None] * xbytes * total_tok[:, None]
            / jnp.maximum(link_bw[prev, seg_node], _EPS),
            0.0,
        )
        link_r = jnp.zeros((B, n, n)).at[bidx, prev, seg_node].add(lrho)
        tot_node = node_r.sum(axis=0)
        tot_link = link_r.sum(axis=0)
        tot_w = wb.sum(axis=0)
        # per-row effective C(t): everyone else folded in (_fold_loads)
        bg = jnp.clip(bg0[None, :] + (tot_node[None, :] - node_r), 0.0, 0.99)
        lbw = link_bw[None] * jnp.clip(
            1.0 - (tot_link[None] - link_r), bw_floor, 1.0
        )
        mem = jnp.maximum(0.0, mem_bytes[None, :] - (tot_w[None, :] - wb))
        lat, _, _ = ev(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
                       t_in, t_out, lam, bg, lbw, link_lat, flops_per_s,
                       mem_bw, trusted, mem)
        # trigger env per row (_session_env): fleet-level vectors, reduced
        # over the nodes/links THIS row touches
        util_vec = jnp.clip(bg0 + tot_node, 0.0, 2.0)
        u_seg = jnp.where(valid, util_vec[seg_node], -jnp.inf)
        max_util = jnp.maximum(u_seg.max(axis=1), util_vec[source])
        ebw = link_bw * jnp.clip(1.0 - tot_link, bw_floor, 1.0)
        hop_ok = valid & (prev != seg_node)
        min_bw = jnp.where(hop_ok, ebw[prev, seg_node], jnp.inf).min(axis=1)
        return dict(
            lat=lat, max_util=max_util, min_bw=min_bw, bg=bg, lbw=lbw,
            mem=mem, tot_node=tot_node, tot_link=tot_link, tot_w=tot_w,
            node_r=node_r, link_r=link_r, prev=prev, hop_ok=hop_ok,
        )

    return core


_PRICE_OUT = ("lat", "max_util", "min_bw", "bg", "lbw", "mem",
              "tot_node", "tot_link", "tot_w")


def _make_fused_price(n: int, alpha: float, beta: float, gamma: float,
                      mem_penalty: float, bw_floor: float):
    """The forecast-free fused pricing program (see :func:`_price_core`)."""
    ev = _make_eval(n, alpha, beta, gamma, mem_penalty)
    core = _price_core(n, ev, bw_floor)

    def price(*args):
        c = core(*args)
        return tuple(c[k] for k in _PRICE_OUT)

    return price


def _make_fused_price_fc(n: int, alpha: float, beta: float, gamma: float,
                         mem_penalty: float, bw_floor: float,
                         horizon: int, resid_alpha: float):
    """Fused pricing + seasonal-naive forecast update + forecast pricing.

    One dispatch per cycle does everything the plain program does AND (a)
    appends the cycle's C(t) sample to the device-resident forecast rings
    (:func:`repro.core.forecast.seasonal_update`; a no-op on read-only
    dispatches via the traced ``advance`` gate), (b) reduces the horizon to
    a worst-case capacity (max util / min bandwidth over {now} ∪ forecast),
    and (c) re-prices every row and its trigger env against that worst case
    — so the proactive control plane costs zero extra dispatches in steady
    state.  With ``horizon == 0`` the forecast outputs ARE the current
    outputs (same traced values), making the reactive A/B bit-identical.
    """
    import jax.numpy as jnp

    ev = _make_eval(n, alpha, beta, gamma, mem_penalty)
    core = _price_core(n, ev, bw_floor)

    def price(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
              t_in, t_out, lam, source, active,
              bg0, link_bw, link_lat, flops_per_s, mem_bw, trusted,
              mem_bytes,
              util_ring, bw_ring, resid_u, resid_b, idx, count, advance):
        c = core(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
                 t_in, t_out, lam, source, active, bg0, link_bw, link_lat,
                 flops_per_s, mem_bw, trusted, mem_bytes)
        # ring/residual update (cadence-gated by the traced `advance`)
        util_ring2, resid_u2 = seasonal_update(
            util_ring, resid_u, idx, count, bg0, advance, resid_alpha)
        bw_ring2, resid_b2 = seasonal_update(
            bw_ring, resid_b, idx, count, link_bw, advance, resid_alpha)
        count2 = count + jnp.where(advance, 1, 0)
        bg_wc, bw_wc = worst_case_capacity(
            util_ring2, resid_u2, bw_ring2, resid_b2, idx, count2,
            bg0, link_bw, horizon)
        if horizon == 0:
            lat_fc, util_fc, bw_fc = c["lat"], c["max_util"], c["min_bw"]
            bg_fc, lbw_fc = c["bg"], c["lbw"]
        else:
            # per-row fold of the worst-case base capacity (_fold_loads
            # with bg_wc/bw_wc in place of the instantaneous C(t))
            bg_fc = jnp.clip(
                bg_wc[None, :] + (c["tot_node"][None, :] - c["node_r"]),
                0.0, 0.99,
            )
            lbw_fc = bw_wc[None] * jnp.clip(
                1.0 - (c["tot_link"][None] - c["link_r"]), bw_floor, 1.0
            )
            lat_fc, _, _ = ev(seg_flops, seg_w, seg_priv, seg_node, valid,
                              xbytes, t_in, t_out, lam, bg_fc, lbw_fc,
                              link_lat, flops_per_s, mem_bw, trusted,
                              c["mem"])
            util_vec_fc = jnp.clip(bg_wc + c["tot_node"], 0.0, 2.0)
            u_seg_fc = jnp.where(valid, util_vec_fc[seg_node], -jnp.inf)
            util_fc = jnp.maximum(u_seg_fc.max(axis=1), util_vec_fc[source])
            ebw_fc = bw_wc * jnp.clip(1.0 - c["tot_link"], bw_floor, 1.0)
            bw_fc = jnp.where(
                c["hop_ok"], ebw_fc[c["prev"], seg_node], jnp.inf
            ).min(axis=1)
        return (*(c[k] for k in _PRICE_OUT),
                lat_fc, util_fc, bw_fc, bg_fc, lbw_fc,
                bg_wc, bw_wc, util_ring2, bw_ring2, resid_u2, resid_b2)

    return price


def _make_fused_migrate(K: int, n: int, alpha: float, beta: float,
                        gamma: float, mem_penalty: float):
    """Placement DP + device backtrack + Eq. 4 repair + candidate pricing.

    Same surrogate prep as :class:`BatchedMigrationSolver` (moved from numpy
    onto device) and the same DP; running every row — triggered or not —
    keeps the compiled shape fixed at (B, K, n), so the varying triggered-set
    size never recompiles and never round-trips the fleet through host.

    Memory feasibility is first-class (PR 4): the DP's per-step exec cost
    carries the Eq. 4 single-segment mask against each row's residual memory
    (masked like the privacy/validity masks), and the backtracked optimum
    then runs the vmapped greedy repair (:func:`_make_repair_core`) for the
    accumulation violations the additive DP cannot see.  The candidate
    latency returned to host is priced on the REPAIRED assignment, so a
    violating candidate can never look cheap: it either repairs on device
    or surfaces its true (post-repair) price.
    """
    import jax
    import jax.numpy as jnp

    dp = _make_migration_dp(K, n)
    ev = _make_eval(n, alpha, beta, gamma, mem_penalty)
    rep = _make_repair_core(K, n)

    def migrate(seg_flops, seg_w, seg_priv, valid, xbytes, n_segs,
                t_in, t_out, lam, source, input_bytes_tok,
                bg, lbw, mem, link_lat, flops_per_s, mem_bw, trusted):
        B = seg_flops.shape[0]
        # shared device surrogate expansion (with the Eq. 4 per-step mask:
        # a segment that alone overflows a node's residual memory loses
        # that node inside the DP, not at commit time)
        exec_cost, xfer, src_xfer = _surrogate_batch(
            seg_flops, seg_w, seg_priv, xbytes, t_in, t_out, lam, source,
            input_bytes_tok, bg, lbw, link_lat, flops_per_s, mem_bw,
            trusted, mem, n,
        )
        C, parents = jax.vmap(dp)(exec_cost, xfer, n_segs, src_xfer)
        # backtrack on device: rows shorter than K hold the carry until the
        # scan enters their chain, so position k-1 lands the argmin row-end
        j0 = jnp.argmin(C, axis=1)                                # (B,)
        rows = jnp.arange(B)

        def bt(j, step):
            j = jnp.where(step <= n_segs - 2, parents[rows, step, j], j)
            return j, j

        _, ys = jax.lax.scan(bt, j0, jnp.arange(K - 2, -1, -1))   # (K-1, B)
        assign = jnp.concatenate(
            [jnp.flip(ys, axis=0).T, j0[:, None]], axis=1
        )                                                         # (B, K)
        # batched Eq. 4 repair of the accumulation violations the DP's
        # per-step mask cannot express (several segments sharing one node)
        assign = jax.vmap(rep)(seg_w, valid, n_segs, assign, mem,
                               exec_cost, xfer, src_xfer)
        mig_lat, _, _ = ev(seg_flops, seg_w, seg_priv, assign, valid, xbytes,
                           t_in, t_out, lam, bg, lbw, link_lat, flops_per_s,
                           mem_bw, trusted, mem)
        return assign, mig_lat, C.min(axis=1)

    return migrate


def _make_fixed_point(K: int, n: int, alpha: float, beta: float, gamma: float,
                      mem_penalty: float, bw_floor: float, imp_frac: float,
                      max_sweeps: int):
    """Red/black fixed-point joint reconfiguration over the triggered set.

    The fused migrate kernel prices every candidate against CYCLE-START
    residuals, so two simultaneous movers cannot see each other's landing —
    the host commit gate re-checked each row against dirtied residuals and
    KEEPed on conflict, degrading to thrash at high churn (ROADMAP open
    item 5).  This program replaces that with a device-side sequential-
    consistency loop: rows are coloured by parity, and each half-sweep

    1. recomputes every row's EFFECTIVE state (bg / link bw / residual
       memory) from the fleet's *current* joint assignment — i.e. including
       all moves committed by earlier half-sweeps (the :func:`_price_core`
       fold with ``base_bg`` / ``base_lbw`` as the fold base, so the
       forecast worst-case base slots in unchanged),
    2. runs the migration DP + greedy Eq. 4 repair for ALL rows against
       those residuals (one colour's accepts per half-sweep keeps the
       compiled shape fixed),
    3. accepts a candidate only for triggered, active rows of the sweep's
       colour whose move is fleet-globally justified: the objective is each
       row's predicted SLO *breach-seconds* (``max(0, lat - slo)``), with
       the legacy hysteresis latency test as the tie-break at equal breach
       — so the loop is coordinate descent on total predicted
       breach-seconds, not per-session greedy latency,

    iterating until no row moves or the sweep budget is exhausted.  A final
    JOINT Eq. 4 guard compares total fleet overflow at the fixed point
    against the starting assignment and reverts everything if the loop made
    it worse (counted by the caller as conflict-KEEPs; the thrash gate
    asserts it never fires).  Rows never accept an Eq. 4-violating
    candidate (``cand_over`` mask), but an overfull INCUMBENT may escape
    through a feasible candidate even without a latency gain (``escape``).

    The scalar reference is :func:`repro.core.placement.
    fixed_point_reference` — the same schedule, op for op, in numpy; device
    bit-identity on the integer assignments is test-enforced in
    ``tests/test_fixed_point.py``.
    """
    import jax
    import jax.numpy as jnp

    dp = _make_migration_dp(K, n)
    ev = _make_eval(n, alpha, beta, gamma, mem_penalty)
    rep = _make_repair_core(K, n)

    def fixed_point(seg_flops, seg_w, seg_priv, seg_node0, valid, xbytes,
                    n_segs, t_in, t_out, lam, source, input_bytes_tok,
                    active, trig, force, slo,
                    base_bg, base_lbw, link_bw, link_lat, flops_per_s,
                    mem_bw, trusted, mem_bytes):
        B = seg_flops.shape[0]
        bidx = jnp.arange(B)[:, None]
        rows = jnp.arange(B)
        av = valid & active[:, None]
        w_av = jnp.where(av, seg_w, 0.0)
        total_tok = t_in + t_out
        colour = (jnp.arange(B) % 2) == 0

        def eff(a):
            # induced loads at joint assignment `a`, folded onto the base
            # capacities — the _price_core sequence with seg_node := a
            f_raw = jnp.maximum(flops_per_s[a], _EPS)
            m_raw = jnp.maximum(mem_bw[a], _EPS)
            ft = seg_flops / f_raw
            svc = t_in[:, None] * ft + t_out[:, None] * jnp.maximum(
                ft, seg_w / m_raw
            )
            svc = jnp.where(av, svc, 0.0)
            node_r = jnp.zeros((B, n)).at[bidx, a].add(lam[:, None] * svc)
            wb = jnp.zeros((B, n)).at[bidx, a].add(w_av)
            prev = jnp.concatenate([source[:, None], a[:, :-1]], axis=1)
            cross = (prev != a) & av & (xbytes > 0)
            lrho = jnp.where(
                cross,
                lam[:, None] * xbytes * total_tok[:, None]
                / jnp.maximum(link_bw[prev, a], _EPS),
                0.0,
            )
            link_r = jnp.zeros((B, n, n)).at[bidx, prev, a].add(lrho)
            tot_node = node_r.sum(axis=0)
            tot_link = link_r.sum(axis=0)
            tot_w = wb.sum(axis=0)
            bg = jnp.clip(
                base_bg[None, :] + (tot_node[None, :] - node_r), 0.0, 0.99
            )
            lbw = base_lbw[None] * jnp.clip(
                1.0 - (tot_link[None] - link_r), bw_floor, 1.0
            )
            mem = jnp.maximum(
                0.0, mem_bytes[None, :] - (tot_w[None, :] - wb)
            )
            return bg, lbw, mem, wb, tot_node, tot_link, tot_w

        def half(a, colour_mask):
            bg, lbw, mem, wb, *_ = eff(a)
            exec_cost, xfer, src_xfer = _surrogate_batch(
                seg_flops, seg_w, seg_priv, xbytes, t_in, t_out, lam,
                source, input_bytes_tok, bg, lbw, link_lat, flops_per_s,
                mem_bw, trusted, mem, n,
            )
            C, parents = jax.vmap(dp)(exec_cost, xfer, n_segs, src_xfer)
            j0 = jnp.argmin(C, axis=1)

            def bt(j, step):
                j = jnp.where(step <= n_segs - 2, parents[rows, step, j], j)
                return j, j

            _, ys = jax.lax.scan(bt, j0, jnp.arange(K - 2, -1, -1))
            cand = jnp.concatenate(
                [jnp.flip(ys, axis=0).T, j0[:, None]], axis=1
            )
            cand = jax.vmap(rep)(seg_w, valid, n_segs, cand, mem,
                                 exec_cost, xfer, src_xfer)
            # invalid positions carry the incumbent so `changed` is clean
            cand = jnp.where(valid, cand, a)
            cur_lat, _, _ = ev(seg_flops, seg_w, seg_priv, a, valid,
                               xbytes, t_in, t_out, lam, bg, lbw, link_lat,
                               flops_per_s, mem_bw, trusted, mem)
            cand_lat, _, _ = ev(seg_flops, seg_w, seg_priv, cand, valid,
                                xbytes, t_in, t_out, lam, bg, lbw, link_lat,
                                flops_per_s, mem_bw, trusted, mem)
            used_cand = jnp.zeros((B, n)).at[bidx, cand].add(w_av)
            cand_over = jnp.any(used_cand > mem, axis=1)
            cur_over = jnp.any(wb > mem, axis=1)
            changed = jnp.any(cand != a, axis=1)
            cur_breach = jnp.maximum(0.0, cur_lat - slo)
            cand_breach = jnp.maximum(0.0, cand_lat - slo)
            better = cand_lat < cur_lat * (1.0 - imp_frac)
            gain = (cand_breach < cur_breach) | (
                (cand_breach == cur_breach) & better
            )
            escape = cur_over & ~cand_over
            accept = (trig & active & colour_mask & changed & ~cand_over
                      & (gain | escape | force))
            a_new = jnp.where(accept[:, None], cand, a)
            # fleet-global monotonicity: the colour's accepted moves only
            # stand if the TOTAL predicted breach-seconds — re-priced under
            # the residuals those moves induce — does not increase (or the
            # moves shrink total Eq. 4 overflow: storm escapes must land
            # even at a latency cost).  Per-row accepts are greedy in the
            # row's own breach; this gate makes each half-sweep a descent
            # step on the JOINT objective, so an exhausted sweep budget can
            # never commit a mid-oscillation state worse than cycle start.
            bg2, lbw2, mem2, *_ = eff(a_new)
            new_lat, _, _ = ev(seg_flops, seg_w, seg_priv, a_new, valid,
                               xbytes, t_in, t_out, lam, bg2, lbw2,
                               link_lat, flops_per_s, mem_bw, trusted, mem2)
            breach_cur = jnp.where(
                active, jnp.maximum(0.0, cur_lat - slo), 0.0
            ).sum()
            breach_new = jnp.where(
                active, jnp.maximum(0.0, new_lat - slo), 0.0
            ).sum()

            def tot_over(ax):
                used = jnp.zeros((B, n)).at[bidx, ax].add(w_av)
                return jnp.maximum(0.0, used.sum(axis=0) - mem_bytes).sum()

            over_cur, over_new = tot_over(a), tot_over(a_new)
            # lexicographic descent on (total overflow, total breach): the
            # half-sweep may never increase joint Eq. 4 overflow, and at
            # equal overflow may not increase total breach — so the final
            # joint guard below is a belt-and-braces check that cannot
            # actually fire, and a commit is never a conflict by design
            ok = (over_new <= over_cur) & (
                (breach_new <= breach_cur + 1e-9) | (over_new < over_cur)
            )
            return jnp.where(ok, a_new, a), ok & accept.any()

        def body(carry):
            a, i, _, moved_rows = carry
            a1, m1 = half(a, colour)
            a2, m2 = half(a1, ~colour)
            moved_rows = moved_rows | jnp.any(a2 != a, axis=1)
            return a2, i + 1, m1 | m2, moved_rows

        def cond(carry):
            _, i, moved, _ = carry
            return (i < max_sweeps) & moved

        init = (seg_node0, jnp.zeros((), jnp.int64), jnp.ones((), bool),
                jnp.zeros(B, dtype=bool))
        a_fp, sweeps, _, moved_pre = jax.lax.while_loop(cond, body, init)

        # final joint Eq. 4 guard: the fixed point must not be worse than
        # the starting joint assignment in total fleet overflow
        def total_over(ax):
            used = jnp.zeros((B, n)).at[bidx, ax].add(w_av)
            return jnp.maximum(0.0, used.sum(axis=0) - mem_bytes).sum()

        abort = total_over(a_fp) > total_over(seg_node0)
        a_out = jnp.where(abort, seg_node0, a_fp)
        moved = moved_pre & jnp.any(a_out != seg_node0, axis=1)
        bg, lbw, mem, _, tot_node, tot_link, tot_w = eff(a_out)
        lat, _, _ = ev(seg_flops, seg_w, seg_priv, a_out, valid, xbytes,
                       t_in, t_out, lam, bg, lbw, link_lat, flops_per_s,
                       mem_bw, trusted, mem)
        return (a_out, lat, sweeps, moved, moved_pre, abort,
                bg, lbw, mem, tot_node, tot_link, tot_w)

    return fixed_point


@dataclass(frozen=True)
class FixedPointResult:
    """Device outputs of one fixed-point dispatch (row-indexed).

    ``assign`` / ``lat`` are the JOINT fixed-point assignment and the
    latency each row sees under it; ``moved`` marks rows whose final
    assignment differs from cycle start (already accept-gated on device —
    the host commits them without re-checking hysteresis).  ``tot_*`` are
    the fleet totals AT the final assignment, so the caller can seed a
    residual table that is consistent with the committed moves without any
    per-commit refresh; ``bg`` / ``link_bw`` / ``mem`` are the matching
    per-row effective states for the re-split refinement stage.
    """

    assign: object     # (B, K) joint fixed-point assignment
    lat: object        # (B,)   latency at the joint assignment
    sweeps: object     # ()     red/black sweeps run (incl. the converged one)
    moved: object      # (B,)   rows whose assignment changed (post-guard)
    moved_pre: object  # (B,)   rows that moved before the joint Eq. 4 guard
    aborted: object    # ()     joint guard fired — all rows reverted
    bg: object         # (B, n) effective background util at `assign`
    link_bw: object    # (B, n, n) effective link bandwidth at `assign`
    mem: object        # (B, n) residual memory at `assign`
    tot_node: object   # (n,)   fleet-total induced node rho at `assign`
    tot_link: object   # (n, n) fleet-total link rho at `assign`
    tot_w: object      # (n,)   fleet-total resident bytes at `assign`


class ResidentFleetKernel:
    """Compiled fused-step programs, keyed by (rows, segs, n, weights).

    Two programs per shape: ``price`` (every cycle) and ``migrate`` (only
    on cycles with a non-empty triggered set).  The buffer axes grow
    pow2/doubling, so a fleet compiles O(log B · log K) variants total.

    ``cost_model`` is the pricing provider the owning orchestrator threads
    through (calibration is an input transform on the packed rows — see
    :meth:`FleetCostEvaluator.pack` — so both programs compile identically
    for analytic and calibrated fleets).
    """

    def __init__(self, cost_model: CostModel | None = None) -> None:
        self._price_c: dict[tuple, object] = {}
        self._mig_c: dict[tuple, object] = {}
        self._fp_c: dict[tuple, object] = {}
        # fused-program launches (price + migrate + fixed point), mirroring
        # BatchedRepairPass.dispatches: the sharded equivalence tests assert
        # steady-state cycles cost exactly one dispatch per shard
        self.dispatches = 0
        self.cost_model = cost_model if cost_model is not None \
            else AnalyticCostModel()

    @staticmethod
    def state_args(state: SystemState):
        """C(t) vectors uploaded once per cycle; ``price`` and ``migrate``
        share the same upload when the caller passes it through."""
        import jax.numpy as jnp
        from jax import enable_x64

        with enable_x64(True):
            return (
                jnp.asarray(state.background_util),
                jnp.asarray(np.nan_to_num(state.link_bw, posinf=_BIG)),
                jnp.asarray(np.nan_to_num(state.link_lat, posinf=_BIG)),
                jnp.asarray(state.flops_per_s),
                jnp.asarray(state.mem_bw),
                jnp.asarray(state.trusted.astype(bool)),
                jnp.asarray(state.mem_bytes),
            )

    def price(
        self,
        buf: FleetStateBuffers,
        state: SystemState,
        *,
        weights: CostWeights = CostWeights(),
        mem_penalty: float = 1e3,
        bw_floor: float = 0.05,
        state_args: tuple | None = None,
        forecaster=None,
        now: float | None = None,
    ) -> ResidentPrice:
        """``forecaster`` (a :class:`~repro.core.forecast.CapacityForecaster`)
        fuses the seasonal forecast update + worst-case re-pricing into the
        same dispatch; ``now`` gates ring advancement (``None`` → read-only
        dispatch that observes but does not append)."""
        import jax
        from jax import enable_x64

        n = state.num_nodes
        if state_args is None:
            state_args = self.state_args(state)
        row_args = (
            buf.seg_flops, buf.seg_wbytes, buf.seg_priv, buf.seg_node,
            buf.valid, buf.xfer_bytes_tok, buf.t_in, buf.t_out, buf.lam,
            buf.source, buf.active,
        )
        if forecaster is None:
            key = (buf.n_rows, buf.max_segs, n, weights, float(mem_penalty),
                   float(bw_floor))
            if key not in self._price_c:
                self._price_c[key] = jax.jit(_make_fused_price(
                    n, weights.alpha, weights.beta, weights.gamma,
                    mem_penalty, bw_floor,
                ))
            self.dispatches += 1
            with enable_x64(True):
                out = self._price_c[key](*row_args, *state_args)
            return ResidentPrice(*out)

        cfg = forecaster.cfg
        key = (buf.n_rows, buf.max_segs, n, weights, float(mem_penalty),
               float(bw_floor), cfg)
        if key not in self._price_c:
            self._price_c[key] = jax.jit(_make_fused_price_fc(
                n, weights.alpha, weights.beta, weights.gamma,
                mem_penalty, bw_floor, cfg.horizon_steps, cfg.residual_alpha,
            ))
        fc_args, advance = forecaster.kernel_args(n, now)
        self.dispatches += 1
        with enable_x64(True):
            out = self._price_c[key](*row_args, *state_args, *fc_args)
        price = ResidentPrice(*out[:14])
        forecaster.commit(*out[16:], *out[14:16], advance=advance, now=now)
        return price

    def migrate(
        self,
        buf: FleetStateBuffers,
        price: ResidentPrice,
        state: SystemState,
        *,
        weights: CostWeights = CostWeights(),
        mem_penalty: float = 1e3,
        state_args: tuple | None = None,
        use_forecast: bool = False,
    ):
        """(repaired assignments (B, K), candidate latency (B,) priced on
        the repaired assignment, DP surrogate cost (B,)).

        ``use_forecast`` prices the DP surrogate and the candidates against
        the dispatch's forecast effective state (``price.bg_fc`` /
        ``price.lbw_fc``) instead of the instantaneous one — the SAME
        compiled program, different input rows — so a proactive migration
        never targets a node that is about to spike."""
        import jax
        from jax import enable_x64

        n = state.num_nodes
        key = (buf.n_rows, buf.max_segs, n, weights, float(mem_penalty))
        if key not in self._mig_c:
            self._mig_c[key] = jax.jit(_make_fused_migrate(
                buf.max_segs, n, weights.alpha, weights.beta, weights.gamma,
                mem_penalty,
            ))
        if state_args is None:
            state_args = self.state_args(state)
        (_, _, link_lat, flops_per_s, mem_bw, trusted, _) = state_args
        bg, lbw = price.bg, price.link_bw
        if use_forecast and price.has_forecast:
            bg, lbw = price.bg_fc, price.lbw_fc
        self.dispatches += 1
        with enable_x64(True):
            assign, mig_lat, cost = self._mig_c[key](
                buf.seg_flops, buf.seg_wbytes, buf.seg_priv, buf.valid,
                buf.xfer_bytes_tok, buf.n_segs, buf.t_in, buf.t_out,
                buf.lam, buf.source, buf.input_bytes_tok,
                bg, lbw, price.mem,
                link_lat, flops_per_s, mem_bw, trusted,
            )
        return assign, mig_lat, cost

    def migrate_fixed_point(
        self,
        buf: FleetStateBuffers,
        state: SystemState,
        *,
        trig: np.ndarray,
        force: np.ndarray,
        slo: np.ndarray,
        weights: CostWeights = CostWeights(),
        mem_penalty: float = 1e3,
        bw_floor: float = 0.05,
        min_improvement_frac: float = 0.10,
        max_sweeps: int = 8,
        state_args: tuple | None = None,
        base_bg: np.ndarray | None = None,
        base_lbw: np.ndarray | None = None,
    ) -> FixedPointResult:
        """One dispatch: red/black fixed point over the triggered set.

        ``trig`` / ``force`` / ``slo`` are (n_rows,) row-indexed masks/SLOs;
        a forced row (failure storm) accepts any feasible change regardless
        of gain.  ``base_bg`` / ``base_lbw`` override the fold base with the
        forecast worst-case capacities (``None`` keeps the instantaneous
        C(t), matching the reactive path); induced-load denominators always
        use the instantaneous link matrix, exactly like the fused forecast
        pricing.  Needs no :class:`ResidentPrice` — the program recomputes
        effective state per half-sweep from the evolving joint assignment.
        """
        import jax
        import jax.numpy as jnp
        from jax import enable_x64

        n = state.num_nodes
        key = (buf.n_rows, buf.max_segs, n, weights, float(mem_penalty),
               float(bw_floor), float(min_improvement_frac), int(max_sweeps))
        if key not in self._fp_c:
            self._fp_c[key] = jax.jit(_make_fixed_point(
                buf.max_segs, n, weights.alpha, weights.beta, weights.gamma,
                mem_penalty, bw_floor, min_improvement_frac, max_sweeps,
            ))
        if state_args is None:
            state_args = self.state_args(state)
        (bg0, link_bw, link_lat, flops_per_s, mem_bw, trusted,
         mem_bytes) = state_args
        self.dispatches += 1
        with enable_x64(True):
            bb = bg0 if base_bg is None else jnp.asarray(
                np.asarray(base_bg, dtype=np.float64))
            bl = link_bw if base_lbw is None else jnp.asarray(np.nan_to_num(
                np.asarray(base_lbw, dtype=np.float64), posinf=_BIG))
            out = self._fp_c[key](
                buf.seg_flops, buf.seg_wbytes, buf.seg_priv, buf.seg_node,
                buf.valid, buf.xfer_bytes_tok, buf.n_segs, buf.t_in,
                buf.t_out, buf.lam, buf.source, buf.input_bytes_tok,
                buf.active,
                jnp.asarray(np.asarray(trig, dtype=bool)),
                jnp.asarray(np.asarray(force, dtype=bool)),
                jnp.asarray(np.asarray(slo, dtype=np.float64)),
                bb, bl, link_bw, link_lat, flops_per_s, mem_bw, trusted,
                mem_bytes,
            )
        return FixedPointResult(*out)


# --------------------------------------------------------------------------- #
# region-sharded resident fleet state (PR 10)
# --------------------------------------------------------------------------- #
_SCREEN_ROW_ARGS = ("seg_flops", "seg_wbytes", "seg_priv", "seg_node",
                    "valid", "xfer_bytes_tok", "t_in", "t_out", "lam",
                    "source", "active")


def _make_sharded_screen(n: int, alpha: float, beta: float, gamma: float,
                         mem_penalty: float, bw_floor: float):
    """The cross-shard screen: :func:`_price_core` vmapped over the shard
    axis.  Each shard's rows are priced against its OWN regional C(t) —
    exactly what one per-shard :func:`_make_fused_price` dispatch would
    compute — but the whole fleet resolves in a single XLA launch, so the
    monitoring cycle's dispatch count stays O(1) in the shard count.  Only
    the trigger-env scalars and the per-shard totals come out; the (S, B,
    n, n) effective-state tensors never materialize as outputs."""
    import jax

    ev = _make_eval(n, alpha, beta, gamma, mem_penalty)
    core = _price_core(n, ev, bw_floor)

    def one(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
            t_in, t_out, lam, source, active,
            bg0, link_bw, link_lat, flops_per_s, mem_bw, trusted,
            mem_bytes):
        c = core(seg_flops, seg_w, seg_priv, seg_node, valid, xbytes,
                 t_in, t_out, lam, source, active, bg0, link_bw, link_lat,
                 flops_per_s, mem_bw, trusted, mem_bytes)
        return c["lat"], c["max_util"], c["min_bw"], c["tot_node"], c["tot_w"]

    return jax.vmap(one)


@dataclass(frozen=True)
class ShardScreen:
    """Host-side outputs of one cross-shard screen dispatch.

    Row ``[s, b]`` is shard ``s``'s buffer row ``b`` (inactive rows carry
    zero loads and garbage trigger scalars — mask with each shard's
    ``active``).  The per-shard totals are what the cross-region aggregator
    ranks residual headroom with.
    """

    lat: np.ndarray       # (S, B) current-config latency per row
    max_util: np.ndarray  # (S, B) trigger env: max node util per row
    min_bw: np.ndarray    # (S, B) trigger env: min cross-hop bandwidth
    tot_node: np.ndarray  # (S, n) per-shard induced node rho totals
    tot_w: np.ndarray     # (S, n) per-shard resident weight-byte totals


class ShardedFleetState:
    """One (:class:`FleetStateBuffers`, :class:`ResidentFleetKernel`) pair
    per MEC region, plus the stacked screen program across them.

    Shards are fully load-disjoint by construction: every session is placed
    on its own region's nodes only, so per-shard pricing against the
    region-local C(t) is *exact*, not an approximation — the block-diagonal
    fleet decomposes.  The screen stacks all shards' row tensors (shapes
    synchronized to the max shard first, so one compiled variant covers the
    fleet) and prices them in one vmapped dispatch; the per-region fixed
    point / migrate / re-split machinery then runs only on shards whose
    screen shows trigger activity.
    """

    def __init__(self, shards: Sequence[FleetStateBuffers],
                 kernels: Sequence["ResidentFleetKernel"]) -> None:
        if len(shards) != len(kernels):
            raise ValueError("one kernel per shard required")
        self.shards = list(shards)
        self.kernels = list(kernels)
        self._screen_c: dict[tuple, object] = {}
        self.screen_dispatches = 0
        # stacked (S, B, K) row block, cached across cycles and refreshed
        # per shard by buffer mutation stamp: a quiet cycle re-uploads
        # NOTHING, so the screen's host cost is O(dirty shards), not O(S)
        self._stack: tuple | None = None
        self._stack_key: tuple | None = None
        self._stack_vers: list[int] = []

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def sync_shapes(self) -> tuple[int, int]:
        """Grow every shard to the fleet-max (rows, segs) so the stacked
        screen sees one uniform (S, B, K) block.  Both axes only ever grow
        (pow2), so this settles immediately in steady state."""
        rows = max(b.n_rows for b in self.shards)
        segs = max(b.max_segs for b in self.shards)
        for b in self.shards:
            if b.max_segs < segs:
                b._grow_segs(segs)
            if b.n_rows < rows:
                b._grow_rows(rows)
        return rows, segs

    def screen(self, states: Sequence[SystemState], *,
               weights: CostWeights = CostWeights(),
               mem_penalty: float = 1e3,
               bw_floor: float = 0.05) -> ShardScreen:
        """Price every shard against its regional C(t) in ONE dispatch."""
        import jax
        import jax.numpy as jnp
        from jax import enable_x64

        S = self.n_shards
        if len(states) != S:
            raise ValueError(f"{len(states)} states for {S} shards")
        n = states[0].num_nodes
        if any(st.num_nodes != n for st in states):
            raise ValueError("regional states must share a node count")
        rows, segs = self.sync_shapes()
        key = (S, rows, segs, n, weights, float(mem_penalty),
               float(bw_floor))
        if key not in self._screen_c:
            self._screen_c[key] = jax.jit(_make_sharded_screen(
                n, weights.alpha, weights.beta, weights.gamma,
                mem_penalty, bw_floor,
            ))
        with enable_x64(True):
            row_args = self._stacked_rows(S, rows, segs)
            # one host stack + one upload per C(t) field (NOT one per
            # shard): the screen's state cost stays flat in S
            state_args = (
                jnp.asarray(np.stack([st.background_util for st in states])),
                jnp.asarray(np.stack(
                    [np.nan_to_num(st.link_bw, posinf=_BIG)
                     for st in states])),
                jnp.asarray(np.stack(
                    [np.nan_to_num(st.link_lat, posinf=_BIG)
                     for st in states])),
                jnp.asarray(np.stack([st.flops_per_s for st in states])),
                jnp.asarray(np.stack([st.mem_bw for st in states])),
                jnp.asarray(np.stack(
                    [st.trusted.astype(bool) for st in states])),
                jnp.asarray(np.stack([st.mem_bytes for st in states])),
            )
            out = self._screen_c[key](*row_args, *state_args)
        self.screen_dispatches += 1
        return ShardScreen(*(np.asarray(o) for o in out))

    def _stacked_rows(self, S: int, rows: int, segs: int) -> tuple:
        """The (S, B, K) stacked row block, rebuilt only where buffers
        actually changed since the last screen.  Shards report mutations
        through ``FleetStateBuffers.version`` (globally-unique stamps), so
        a steady-state cycle reuses the device block verbatim; a cycle
        that admitted/migrated in d shards rewrites d slices.  When most
        of the fleet is dirty (cold start, growth resync) a full restack
        is cheaper than per-slice copies."""
        import jax.numpy as jnp

        vers = [b.version for b in self.shards]
        skey = (S, rows, segs)
        dirty = ([r for r, v in enumerate(vers)
                  if v != self._stack_vers[r]]
                 if self._stack is not None and self._stack_key == skey
                 else None)
        if dirty is None or len(dirty) > max(1, S // 4):
            self._stack = tuple(
                jnp.stack([getattr(b, f) for b in self.shards])
                for f in _SCREEN_ROW_ARGS
            )
        elif dirty:
            stack = list(self._stack)
            for r in dirty:
                b = self.shards[r]
                stack = [a.at[r].set(getattr(b, f))
                         for f, a in zip(_SCREEN_ROW_ARGS, stack)]
            self._stack = tuple(stack)
        self._stack_key = skey
        self._stack_vers = vers
        return self._stack
