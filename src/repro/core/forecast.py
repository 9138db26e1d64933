"""Short-horizon capacity forecasting for the fleet control plane.

The paper frames orchestration as optimization "subject to evolving latency,
utilization, and privacy gradients", and companion work calls for *model-aware
capacity profiling* feeding placement (arXiv:2504.03668) and for control loops
that anticipate load instead of reacting to it (Splitwise, arXiv:2512.23310).
Until now every consumer of C(t) — admission pricing, trigger evaluation,
migration targets — saw only the instantaneous snapshot, so sessions admitted
in a background-load trough transiently pushed the home MEC past ρ = 1 when
the next saturation spike landed (ROADMAP open item, retired by this module).

The predictor is deliberately a strong *baseline*, not a learned model:

* **Seasonal-naive** — the edge background-load signal of interest (tenant
  saturation events on a base station) is periodic; a ring buffer holding the
  last ``season_steps`` samples predicts step ``t + h`` as the sample from one
  season earlier, ``y(t + h - S)``.  After one full observed period this
  reproduces a periodic signal exactly.
* **EWMA residual** — a slowly-adapted bias term ``r ← a·(y - ŷ) + (1-a)·r``
  absorbs level shifts the seasonal lookup cannot (e.g. an OU-wandering
  backhaul with no true period).  Under bounded noise the residual stays
  bounded by construction (it is a convex combination of past one-step
  errors — property-tested in ``tests/test_forecast.py``).

State is **device-resident** (JAX arrays) and the per-cycle update is pure
``jnp`` — :func:`seasonal_update` / :func:`seasonal_forecast` /
:func:`worst_case_capacity` are the single source of truth, called both by
the fused :class:`~repro.core.fleet_eval.ResidentFleetKernel` pricing program
(so a steady-state monitoring cycle stays ONE dispatch) and by the standalone
:meth:`CapacityForecaster.observe` driver used by tests and non-fleet callers.

Consumers (wired in PR 5):

1. :class:`~repro.core.admission.FleetAdmissionController` prices an arrival
   against the *minimum residual capacity over the horizon* (worst-case
   background utilization / link bandwidth within H steps) instead of the
   instantaneous snapshot — a trough-time admit that would violate at the
   next spike DEFERs.
2. :meth:`~repro.core.fleet.FleetOrchestrator.step` raises *proactive*
   triggers when a session's forecast latency/util/bandwidth would cross its
   Θ within the horizon, and prices migration candidates against the
   forecast C(t+h) so nothing migrates ONTO an about-to-spike node.
3. ``repro.edgesim.FleetSimulator`` / ``benchmarks/fleet_scaling.py --qos``
   run seed-paired forecast-on/off arms with onset-ρ / SLO-breach KPIs.

``horizon_steps = 0`` is the contractual off-switch: every forecast quantity
degenerates to the current value and the control plane is bit-identical to
the reactive path (A/B-equivalence-tested).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = [
    "ForecastConfig",
    "CapacityForecaster",
    "seasonal_update",
    "seasonal_forecast",
    "worst_case_capacity",
]

_UTIL_CAP = 0.99  # background-utilization clip shared with the cost model


@dataclass(frozen=True)
class ForecastConfig:
    """Knobs for the seasonal-naive + EWMA-residual predictor.

    ``season_steps`` is the period of the signal in *samples* (the §IV
    home-MEC saturation square wave has a 40 s period and the monitoring
    cadence is 1 s → 40).  ``horizon_steps`` is H: how many future samples
    the worst-case capacity reduction covers; 0 disables forecasting
    entirely (bit-identical reactive behavior).  ``sample_interval_s`` gates
    ring advancement so multiple pricing dispatches within one monitoring
    interval observe, but do not re-append, the same sample.
    """

    horizon_steps: int = 12
    season_steps: int = 40
    sample_interval_s: float = 1.0
    residual_alpha: float = 0.2

    def __post_init__(self) -> None:
        if self.season_steps < 1:
            raise ValueError("season_steps must be >= 1")
        if not 0 <= self.horizon_steps <= self.season_steps:
            raise ValueError(
                f"horizon_steps must be in [0, season_steps={self.season_steps}]"
            )


# --------------------------------------------------------------------------- #
# pure jnp update/predict — shared by the fused kernel and the host driver
# --------------------------------------------------------------------------- #
def seasonal_update(ring, resid, idx, count, y, advance, alpha: float):
    """One observation step: residual EWMA against the season-old prediction,
    then write ``y`` into slot ``idx``.

    ``ring`` is (S, *shape) with slot ``p`` holding the most recent sample
    taken at a step ≡ p (mod S); ``resid`` matches ``y``'s shape.  ``idx`` /
    ``count`` / ``advance`` are traced scalars so neither the write position
    nor the advance gate recompiles the program.  When ``advance`` is false
    the inputs pass through unchanged (a read-only pricing dispatch).
    Returns ``(ring', resid')``.

    Non-finite elements of ``y`` are skipped element-wise: one NaN capacity
    sample used to enter the ring AND the residual EWMA, and because both
    recursions feed the sample forward, every future forecast for that node
    went NaN *permanently* (which admission then read as worst-case
    capacity ∞/NaN).  A poisoned element keeps its season-old ring value
    and its previous residual instead — skip-and-hold, bit-identical for
    finite inputs.
    """
    import jax.numpy as jnp

    S = ring.shape[0]
    yhat = ring[idx]                      # prediction made one season ago
    ok = jnp.isfinite(y)
    y_safe = jnp.where(ok, y, yhat)       # poisoned element: hold the prior
    seen = count >= S                     # slot idx only valid after 1 season
    upd = advance & seen
    resid2 = jnp.where(
        upd & ok, alpha * (y_safe - yhat) + (1.0 - alpha) * resid, resid)
    ring2 = ring.at[idx].set(jnp.where(advance, y_safe, yhat))
    return ring2, resid2


def seasonal_forecast(ring, resid, idx, horizon: int):
    """(H, *shape) predictions for steps t+1 … t+H, taken AFTER the step-t
    write: ŷ(t+h) = ring[(idx + h) mod S] + resid — the sample from time
    t + h − S plus the residual bias.  Requires 1 ≤ H ≤ S (slot t+h−S is
    still un-overwritten exactly when h ≤ S)."""
    import jax.numpy as jnp

    S = ring.shape[0]
    slots = (idx + 1 + jnp.arange(horizon)) % S
    return ring[slots] + resid[None]


def worst_case_capacity(util_ring, resid_u, bw_ring, resid_b, idx, count,
                        y_util, y_bw, horizon: int):
    """(bg_wc (n,), bw_wc (n, n)): the capacity floor over the next H steps.

    Element-wise MAX background utilization and MIN link bandwidth over
    {now} ∪ {forecast t+1 … t+H} — "min over the horizon of forecast
    residual capacity".  Until one full season has been observed
    (``count < S``, counted AFTER the current write) or with H = 0, both
    collapse to the current values: the consumer silently degrades to
    reactive behavior instead of trusting an unseeded ring.
    """
    import jax.numpy as jnp

    if horizon == 0:
        return y_util, y_bw
    S = util_ring.shape[0]
    ready = count >= S
    fc_u = jnp.clip(seasonal_forecast(util_ring, resid_u, idx, horizon),
                    0.0, _UTIL_CAP)
    fc_b = jnp.maximum(seasonal_forecast(bw_ring, resid_b, idx, horizon), 0.0)
    bg_wc = jnp.where(ready, jnp.maximum(y_util, fc_u.max(axis=0)), y_util)
    bw_wc = jnp.where(ready, jnp.minimum(y_bw, fc_b.min(axis=0)), y_bw)
    return bg_wc, bw_wc


# --------------------------------------------------------------------------- #
# host-side controller owning the device rings
# --------------------------------------------------------------------------- #
class CapacityForecaster:
    """Owns the device-resident forecast state and its advancement cadence.

    The ring/residual arrays live as JAX device arrays between cycles, like
    :class:`~repro.core.fleet_eval.FleetStateBuffers`; the fused pricing
    program threads them through one dispatch per cycle
    (:meth:`kernel_args` → dispatch → :meth:`commit`).  ``idx`` / ``count`` /
    ``_last_t`` stay host-side — they change once per sample interval, and
    passing them as traced scalars keeps the compiled program count at one
    per (S, H) configuration.

    :meth:`observe` is the standalone driver (tests, single-session callers
    without a resident kernel): the SAME jnp update/predict helpers run
    eagerly on host-shaped arrays, so the two paths cannot drift.
    """

    def __init__(self, config: ForecastConfig = ForecastConfig()) -> None:
        self.cfg = config
        self.idx = 0
        self.count = 0
        self._last_t = float("-inf")
        self._pending_steps = 0    # ring slots the in-flight dispatch spans
        self._pending_credit = 0   # warm-up credit for those slots
        self.util_ring = None          # (S, n) device
        self.bw_ring = None            # (S, n, n) device
        self.resid_util = None         # (n,) device
        self.resid_bw = None           # (n, n) device
        # host copies of the latest worst-case capacity (admission pricing)
        self.bg_wc: np.ndarray | None = None
        self.bw_wc: np.ndarray | None = None
        # non-finite sample elements skipped by the update guard (counted
        # where the sample is host-visible; the fused path skips silently)
        self.bad_samples = 0

    # -- state ---------------------------------------------------------- #
    @property
    def enabled(self) -> bool:
        """False only for the degenerate H = 0 configuration."""
        return self.cfg.horizon_steps > 0

    @property
    def ready(self) -> bool:
        """One full season observed — forecasts are live (H > 0 only)."""
        return self.enabled and self.count >= self.cfg.season_steps

    def ensure(self, n: int) -> None:
        import jax.numpy as jnp
        from jax import enable_x64

        if self.util_ring is not None:
            return
        S = self.cfg.season_steps
        with enable_x64(True):
            self.util_ring = jnp.zeros((S, n))
            self.bw_ring = jnp.zeros((S, n, n))
            self.resid_util = jnp.zeros(n)
            self.resid_bw = jnp.zeros((n, n))

    def _advance_steps(self, now: float | None) -> int:
        """Whole sample intervals elapsed since the last committed sample
        (0 = cadence-gated read-only dispatch; clamped at one season)."""
        if now is None:
            return 0
        if self._last_t == float("-inf"):
            return 1
        steps = int((now - self._last_t + 1e-9)
                    // self.cfg.sample_interval_s)
        return max(0, min(steps, self.cfg.season_steps))

    def should_advance(self, now: float | None) -> bool:
        """True iff a dispatch at ``now`` appends a fresh sample (does not
        mutate state — :meth:`commit` records the advancement)."""
        return self._advance_steps(now) > 0

    def kernel_args(self, n: int, now: float | None):
        """(traced forecast inputs, advance) for one fused pricing dispatch.

        Phase alignment is wall-clock anchored: a stalled or jittered
        monitoring loop that skips sample intervals advances the ring by
        the MISSED step count, so slot ``p`` keeps meaning "time ≡ p
        (mod S)" — the write lands in the slot for ``now``, and (once warm)
        the skipped slots simply retain their season-old values, i.e. the
        seasonal prior.  A gap during WARM-UP instead restarts the count:
        ``ready`` must never trust slots that were skipped before they
        were ever written.
        """
        import jax.numpy as jnp

        self.ensure(n)
        steps = self._advance_steps(now)
        if steps > 1 and not self.ready:
            self.count = 0
        # the slot for `now` (idx is the next contiguous write position)
        write_idx = ((self.idx + steps - 1) % self.cfg.season_steps
                     if steps else self.idx)
        self._pending_steps = steps
        self._pending_credit = 1 if (steps > 1 and not self.ready) else steps
        return (
            self.util_ring, self.bw_ring, self.resid_util, self.resid_bw,
            jnp.asarray(write_idx, dtype=jnp.int32),
            jnp.asarray(self.count, dtype=jnp.int32),
            jnp.asarray(steps > 0),
        ), steps > 0

    def commit(self, util_ring, bw_ring, resid_util, resid_bw,
               bg_wc, bw_wc, *, advance: bool, now: float | None) -> None:
        """Adopt one dispatch's outputs (rings stay on device; the worst-case
        vectors are pulled to host for the admission control plane)."""
        self.util_ring = util_ring
        self.bw_ring = bw_ring
        self.resid_util = resid_util
        self.resid_bw = resid_bw
        self.bg_wc = np.asarray(bg_wc, dtype=np.float64)
        self.bw_wc = np.asarray(bw_wc, dtype=np.float64)
        steps = self._pending_steps
        if advance and steps:
            dt = self.cfg.sample_interval_s
            self.idx = (self.idx + steps) % self.cfg.season_steps
            self.count += getattr(self, "_pending_credit", steps)
            # stay wall-aligned: advance by whole intervals so sub-interval
            # jitter (e.g. steady 1.05 s cycles) cannot accumulate into
            # phase drift; re-anchor only on the first sample or when the
            # clamp left us more than an interval behind
            anchored = self._last_t + steps * dt
            if self._last_t == float("-inf") or now - anchored >= dt:
                self._last_t = float(now)
            else:
                self._last_t = anchored
            self._pending_steps = 0
            self._pending_credit = 0

    # -- persistence across restarts (PR 6) ----------------------------- #
    def state_dict(self) -> dict[str, np.ndarray]:
        """Host-side snapshot of the seasonal state (empty pre-``ensure``).

        A restart mid-storm used to reset ``count`` to zero, disabling
        proactive triggers for a full season exactly when capacity is most
        volatile; persisting the ring closes that blind window.
        """
        if self.util_ring is None:
            return {}
        return {
            "util_ring": np.asarray(self.util_ring, dtype=np.float64),
            "bw_ring": np.asarray(self.bw_ring, dtype=np.float64),
            "resid_util": np.asarray(self.resid_util, dtype=np.float64),
            "resid_bw": np.asarray(self.resid_bw, dtype=np.float64),
            "idx": np.asarray(self.idx, dtype=np.int64),
            "count": np.asarray(self.count, dtype=np.int64),
            "last_t": np.asarray(self._last_t, dtype=np.float64),
            "season_steps": np.asarray(self.cfg.season_steps, dtype=np.int64),
        }

    def load_state_dict(self, d: dict) -> None:
        """Seed the rings from a snapshot; ``ready`` carries over.

        The season length is structural (slot p means "time ≡ p mod S"), so
        a mismatched snapshot is an error, not a silent re-warm-up.
        """
        import jax.numpy as jnp
        from jax import enable_x64

        if not d:
            return
        S = int(np.asarray(d["season_steps"]))
        if S != self.cfg.season_steps:
            raise ValueError(
                f"snapshot season_steps={S} != configured "
                f"{self.cfg.season_steps}")
        with enable_x64(True):
            self.util_ring = jnp.asarray(d["util_ring"])
            self.bw_ring = jnp.asarray(d["bw_ring"])
            self.resid_util = jnp.asarray(d["resid_util"])
            self.resid_bw = jnp.asarray(d["resid_bw"])
        self.idx = int(np.asarray(d["idx"]))
        self.count = int(np.asarray(d["count"]))
        self._last_t = float(np.asarray(d["last_t"]))

    def save(self, path) -> None:
        """Persist the seasonal state to an ``.npz`` file (no-op pre-warm)."""
        sd = self.state_dict()
        if sd:
            np.savez(path, **sd)

    def load(self, path) -> bool:
        """Seed from :meth:`save` output; returns whether state was loaded."""
        with np.load(path) as z:
            d = {k: z[k] for k in z.files}
        self.load_state_dict(d)
        return bool(d)

    # -- standalone driver (no resident kernel) ------------------------- #
    def observe(self, now: float, bg_util: np.ndarray,
                link_bw: np.ndarray | None = None) -> bool:
        """Feed one (background-util, link-bw) sample directly.

        Runs the shared jnp update/worst-case helpers eagerly — identical
        math to the fused kernel path.  Returns whether the sample advanced
        the ring (False → cadence-gated no-op)."""
        import jax.numpy as jnp
        from jax import enable_x64

        bg = np.asarray(bg_util, dtype=np.float64)
        n = bg.shape[0]
        bw = (np.full((n, n), np.inf) if link_bw is None
              else np.asarray(link_bw, dtype=np.float64))
        self.bad_samples += int((~np.isfinite(bg)).sum()
                                + np.isnan(bw).sum())
        # +inf is the legitimate "local link" encoding → clamp to BIG; NaN
        # is poison → keep it NaN so the update guard skips-and-holds
        bw = np.nan_to_num(bw, nan=np.nan, posinf=1e30)
        (args, adv) = self.kernel_args(n, now)
        util_ring, bw_ring, resid_u, resid_b, idx, count, advance = args
        a = self.cfg.residual_alpha
        with enable_x64(True):
            y_u, y_b = jnp.asarray(bg), jnp.asarray(bw)
            util_ring2, resid_u2 = seasonal_update(
                util_ring, resid_u, idx, count, y_u, advance, a)
            bw_ring2, resid_b2 = seasonal_update(
                bw_ring, resid_b, idx, count, y_b, advance, a)
            # count advances only by the committed credit — a cadence-gated
            # call at count == S-1 must NOT flip `ready` a sample early,
            # and a warm-up gap restart must not double-count its slots
            bg_wc, bw_wc = worst_case_capacity(
                util_ring2, resid_u2, bw_ring2, resid_b2, idx,
                count + self._pending_credit,
                y_u, y_b, self.cfg.horizon_steps)
        self.commit(util_ring2, bw_ring2, resid_u2, resid_b2, bg_wc, bw_wc,
                    advance=adv, now=now)
        return adv

    def predict_util(self) -> np.ndarray:
        """(H, n) background-utilization forecast for t+1 … t+H (host copy,
        residual-corrected, unclipped readiness: caller checks ``ready``)."""
        from jax import enable_x64

        if self.util_ring is None or not self.enabled:
            raise RuntimeError("forecaster has no samples / horizon is 0")
        import jax.numpy as jnp

        # anchor at the slot LAST WRITTEN (self.idx is the next write
        # position): predictions cover last-observed+1 … last-observed+H,
        # matching the in-dispatch semantics where the forecast is taken
        # right after the cycle's sample lands
        idx_last = (self.idx - 1) % self.cfg.season_steps
        with enable_x64(True):
            fc = seasonal_forecast(
                self.util_ring, self.resid_util,
                jnp.asarray(idx_last, dtype=jnp.int32),
                self.cfg.horizon_steps,
            )
        return np.asarray(fc, dtype=np.float64)
