"""Chip smoke test: the control plane and the split-serving path on one TPU.

Run from the root of a checkout, on a machine with one TPU:

    python chip_smoke.py

Phase ``control`` steps the saturated 128-session fleet (forecaster on) and
the region-sharded fleet of 8 regions x 128 sessions through their monitoring
cycles, checks the control-plane invariants after every cycle, and checks the
device pricing against the host float64 reference.  Phase ``serve`` deploys
stablelm-3b at its published widths in bf16 through the adaptive
orchestrator, answers requests through the split chain with the int8
boundary transport compiled for the chip, re-splits the chain once, and
checks the chained logits against the monolithic forward.

Everything runs in this one process and nothing is caught to carry on.  The
last line of standard output is ``{"ok": true, "device": {...}}``, printed
only when every phase passed on a TPU; otherwise the exit code is non-zero.
Timings printed here are bring-up records (compile and first-cycle seconds),
not benchmark results.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# The served model has random weights, and at these widths it is chaotic: on
# CPU, one bf16 ulp added to one element of a boundary activation moved the
# logits by 9 % of their largest entry and flipped 5 % of the arg-max tokens
# after 4 layers.  So only checks that start from the same input are sharp.
#
# int8 kernels vs their jnp oracle (kernels/ref.py) on the real boundary
# activations: x / scale may round a .5 tie the other way, one step, on a
# few elements (7 of 327,680 on CPU); scales agree to f32 rounding, and
# dequantization is one f32 multiply and one bf16 rounding.
INT8_FLIP_SHARE = 1e-3
REL_TOL_SCALE = 1e-6
REL_TOL_DEQUANT = 2.0 ** -8
# uncompressed chain vs monolith: the same per-layer program cut into
# segments, bit-identical on CPU.  Any other rounding would be amplified
# into a different trajectory, so a difference is reported as measured and
# more than one bf16 rounding of the largest logit fails.
REL_TOL_CHAIN = 2.0 ** -8
# int8 requests vs the monolith, end to end: the quantization error is
# amplified the same way, so the arg-max agreement falls with depth (on CPU
# at stablelm-3b widths: 29 % of positions at 4 layers, 14 % at 8; 10-16 %
# at 32 layers with d=640), while two different prompts agree on 0 of 128.
# The floor asks for agreement well above that, not for accuracy.
ARGMAX_SHARE_INT8 = 0.02
# device float64 pricing vs the host numpy float64 reference.  The TPU has
# no native f64 and XLA emulates it; decisions are taken at a 10 %
# hysteresis margin, far above this.
REL_TOL_PRICE = 1e-6
# the sharded fleet's resident sessions are light; a saturation event on
# region 0's home MEC (the §IV scenario's, above Thresholds.util_max = 0.85)
# makes its sessions trigger, so the fixed point runs
SATURATED_UTIL = 0.95


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit records its short retrieval instead)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def _since(clock: CompileClock, snap) -> dict:
    s, c, h = clock.snapshot()
    return {"compile_s": s - snap[0], "compiles": c - snap[1],
            "cache_hits": h - snap[2]}


def _rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-300))


# --------------------------------------------------------------------------- #
# control plane
# --------------------------------------------------------------------------- #
def reference_price(sessions, state, bw_floor: float):
    """Per-session latency and node rho totals from the host session objects,
    in numpy float64: the scalar path the fused device pricing reproduces."""
    from repro.core import chain_latency, pack_sessions, packed_induced_loads

    items = [(s.graph, s.config.boundaries, s.config.assignment, s.workload,
              s.source_node, s.input_bytes_per_token) for s in sessions]
    node_r, link_r, wb = packed_induced_loads(pack_sessions(items), state)
    tot_n, tot_l, tot_w = node_r.sum(0), link_r.sum(0), wb.sum(0)
    bg = np.clip(state.background_util + (tot_n[None] - node_r), 0.0, 0.99)
    lbw = state.link_bw * np.clip(1.0 - (tot_l[None] - link_r), bw_floor, 1.0)
    mem = np.maximum(0.0, state.mem_bytes - (tot_w[None] - wb))
    lat = []
    for i, s in enumerate(sessions):
        st = state.copy()
        st.background_util, st.link_bw, st.mem_bytes = bg[i], lbw[i], mem[i]
        lat.append(chain_latency(s.graph, s.config.boundaries,
                                 s.config.assignment, st, s.workload))
    return np.asarray(lat), tot_n


def _price_errors(orch, lat_rows, tot_node, state) -> tuple[float, float]:
    """(latency, node-total) relative errors of one region's device pricing."""
    sessions = list(orch.sessions.values())
    ref_lat, ref_tot = reference_price(sessions, state, orch.bw_floor_frac)
    rows = [orch._buffers.row_of[s.sid] for s in sessions]
    return (_rel_err(np.asarray(lat_rows)[rows], ref_lat),
            _rel_err(tot_node, ref_tot))


def _step(name: str, step, inners, clock: CompileClock, *, cycles: int,
          t: float = 1.0, max_warm: int = 12) -> dict:
    """Step from time ``t`` until a cycle compiles nothing, then ``cycles``
    more; check the control-plane invariants of every region after every
    cycle."""
    from repro.edgesim import InvariantChecker

    checker = InvariantChecker()
    times, trig, compiles = [], 0, []
    warm_left, measured = max_warm, 0
    while measured < cycles:
        c0 = clock.compiles
        t0 = time.perf_counter()
        d = step(t)
        times.append(time.perf_counter() - t0)
        compiles.append(clock.compiles - c0)
        trig += d.fixed_point_sweeps > 0
        for o in inners:
            errs = checker.check(t=t, orch=o, agents=o.broadcast.agents)
            if errs:
                raise RuntimeError(f"{name}: invariant broken at t={t}: {errs[:3]}")
        t += 1.0
        if warm_left and compiles[-1]:
            warm_left -= 1
        else:
            warm_left = 0
            measured += 1
    if not trig:
        raise RuntimeError(f"{name}: no cycle ran the fixed-point dispatch")
    return {
        "sessions": sum(len(o.sessions) for o in inners),
        "cycles": len(times),
        "warm_cycles": len(times) - cycles,
        "fixed_point_cycles": trig,
        "first_cycle_s": times[0],
        "rest_s": float(sum(times[1:])),
        "compiles_in_last_cycles": int(sum(compiles[-cycles:])),
    }


def control_phase(clock: CompileClock, *, sessions: int = 128,
                  regions: int = 8, shard_sessions: int = 128,
                  cycles: int = 5, seed: int = 0) -> list[dict]:
    from repro.edgesim import hot_sharded_fleet, saturated_fleet

    out = []
    snap = clock.snapshot()
    t0 = time.perf_counter()
    orch = saturated_fleet(sessions, seed, forecast=True)
    build_s = time.perf_counter() - t0
    r = _step("fleet", orch.step, [orch], clock, cycles=cycles)
    state = orch.profiler.system_state()
    price = orch.kernel.price(orch._buffers, state,
                              weights=orch.weights, bw_floor=orch.bw_floor_frac)
    lat_err, tot_err = _price_errors(orch, price.lat, price.tot_node, state)
    out.append({"part": "fleet", "build_s": build_s, **r,
                "price_lat_rel_err": lat_err, "price_rho_rel_err": tot_err,
                **_since(clock, snap)})

    snap = clock.snapshot()
    t0 = time.perf_counter()
    w, drive = hot_sharded_fleet(regions, shard_sessions, seed)
    build_s = time.perf_counter() - t0
    home = w.inners[0].profiler.base_state

    def step(t: float):
        drive(t)
        home.background_util[0] = SATURATED_UTIL
        return w.step(t)

    # the sessions were admitted at t=0 and sit out their cool-down first
    r = _step("sharded", step, w.inners, clock, cycles=cycles,
              t=w.inners[0].thresholds.cooldown_s)
    states = [o.profiler.system_state() for o in w.inners]
    scr = w._sharded().screen(states, weights=w.inners[0].weights,
                              bw_floor=w.inners[0].bw_floor_frac)
    errs = [_price_errors(o, scr.lat[k], scr.tot_node[k], states[k])
            for k, o in enumerate(w.inners)]
    out.append({"part": "sharded", "regions": regions, "build_s": build_s,
                **r, "price_lat_rel_err": max(e[0] for e in errs),
                "price_rho_rel_err": max(e[1] for e in errs),
                **_since(clock, snap)})
    for row in out:
        if max(row["price_lat_rel_err"], row["price_rho_rel_err"]) > REL_TOL_PRICE:
            raise RuntimeError(f"device pricing off the reference: {row}")
    return out


# --------------------------------------------------------------------------- #
# served path
# --------------------------------------------------------------------------- #
def _argmax_share(a, b) -> float:
    return float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))


def serve_phase(clock: CompileClock, *, arch: str = "stablelm-3b",
                reduced: bool = False, interpret: bool = False,
                requests: int = 4, prompt_len: int = 128,
                seed: int = 0) -> dict:
    from repro.core.broadcast import PartitionConfig
    from repro.launch.serve import deploy

    snap = clock.snapshot()
    t0 = time.perf_counter()
    dep = deploy(arch, reduced=reduced, compress=True, interpret=interpret,
                 prompt_len=prompt_len, seed=seed)
    eng, cfg = dep.engine, dep.bundle.cfg
    deploy_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, (1, prompt_len), dtype=np.int32)
               for _ in range(requests)]
    shape = (1, prompt_len, cfg.vocab)

    def checked(logits) -> np.ndarray:
        logits = np.asarray(logits)
        if logits.shape != shape or not np.isfinite(logits).all():
            raise RuntimeError(f"bad logits: shape {logits.shape}")
        return logits

    req_s, served = [], []
    for i, toks in enumerate(prompts):
        t0 = time.perf_counter()
        logits, _ = dep.serve(jnp.asarray(toks), now=float(i))
        served.append(checked(logits))
        req_s.append(time.perf_counter() - t0)

    # a second split with other boundaries, staged as a broadcast commit
    # would stage it, then one more request through it
    L = len(dep.orch.graph)
    old = eng.config
    bounds = next(b for b in ((0, L // 2, L), (0, L // 4, 3 * L // 4, L))
                  if b != old.boundaries)
    eng.apply_config(PartitionConfig(old.version + 1, bounds,
                                     tuple(range(len(bounds) - 1))))
    if eng.reconfigurations < 1 or eng.config.boundaries != bounds:
        raise RuntimeError("re-split was not applied")
    served.append(checked(eng.infer_logits(jnp.asarray(prompts[0]))))

    refs = [np.asarray(eng.infer_monolithic(jnp.asarray(p)))
            for p in prompts]
    refs.append(refs[0])
    share_int8 = float(np.mean([_argmax_share(s, r)
                                for s, r in zip(served, refs)]))
    share_other = _argmax_share(served[0], refs[1])

    # one uncompressed request through the staged chain, keeping the
    # activations that cross each boundary
    x, acts = jnp.asarray(prompts[0]), []
    for seg in eng.chain.segments:
        x = seg(x)
        if seg.hi < L:
            acts.append(x)
    plain = checked(x)
    chain_err = _rel_err(plain, refs[0])

    stats = eng.transfer_stats()
    out = {
        "arch": arch, "layers": cfg.n_layers, "d_model": cfg.d_model,
        "vocab": cfg.vocab, "dtype": str(jax.tree_util.tree_leaves(
            eng.params)[0].dtype),
        "requests": len(served), "prompt_len": prompt_len,
        "compression_ratio": stats.compression_ratio,
        "reconfigurations": eng.reconfigurations,
        "splits": [list(old.boundaries), list(eng.config.boundaries)],
        "chain_vs_monolith_rel_err": chain_err,
        "argmax_share_chain": _argmax_share(plain, refs[0]),
        "argmax_share_int8": share_int8,
        "argmax_share_other_prompt": share_other,
        **_int8_vs_oracle(acts, eng.transport.interpret),
        "deploy_s": deploy_s, "first_request_s": req_s[0],
        "rest_requests_s": float(sum(req_s[1:])),
        **_since(clock, snap),
    }
    if (chain_err > REL_TOL_CHAIN or share_int8 < ARGMAX_SHARE_INT8
            or out["int8_flip_share"] > INT8_FLIP_SHARE
            or out["int8_max_step"] > 1
            or out["int8_scale_rel_err"] > REL_TOL_SCALE
            or out["int8_dequant_rel_err"] > REL_TOL_DEQUANT
            or not (interpret or out["int8_transport"] == "compiled")):
        raise RuntimeError(f"served path off its references: {out}")
    return out


def _int8_vs_oracle(acts, interpret: bool) -> dict:
    """The transport's kernels against their jnp oracle on the same input."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    flips = steps = n = 0
    scale_err = deq_err = 0.0
    for x in acts:
        x2 = x.reshape(-1, x.shape[-1])
        qk, sk = kops.quantize_int8(x2, interpret=interpret)
        qo, so = kref.quantize_int8_ref(x2)
        dq = np.abs(np.asarray(qk, np.int32) - np.asarray(qo, np.int32))
        flips, n = flips + int((dq > 0).sum()), n + dq.size
        steps = max(steps, int(dq.max()))
        scale_err = max(scale_err, _rel_err(sk, so))
        deq_err = max(deq_err, _rel_err(
            kops.dequantize_int8(qk, sk, x.dtype, interpret=interpret),
            kref.dequantize_int8_ref(qk, sk, x.dtype)))
    hlo = kops.quantize_int8.lower(x2, interpret=interpret).compile().as_text()
    return {
        # a Mosaic custom call is the kernel compiled for the TPU; the
        # interpreter lowers the kernel body to plain XLA instead
        "int8_transport": ("compiled" if "tpu_custom_call" in hlo
                           else "interpreted"),
        "int8_flip_share": flips / n, "int8_max_step": steps,
        "int8_scale_rel_err": scale_err, "int8_dequant_rel_err": deq_err,
    }


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    for row in control_phase(clock):
        print("control", json.dumps(row))
    print("serve", json.dumps(serve_phase(clock)))
    stats = dev.memory_stats() or {}
    print("memory", json.dumps({"peak_bytes_in_use":
                                stats.get("peak_bytes_in_use")}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
