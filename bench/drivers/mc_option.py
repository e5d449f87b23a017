"""Closed-loop Monte Carlo option pricing on leased counter windows.

Each call leases the next window of ``draws_per_call`` counter steps from
a ``BlockService`` channel and prices a European call over every lane and
draw of it with the fused kernel (``mc.option_partials_from_plans``): the
random numbers are made in the kernel and never written out.  The counter
is a traced argument, so every call runs one executable.

Correctness: the leases must be consecutive from counter 0, and a
reservoir sample of calls, drawn from the seed, must give per-lane payoff
sums within the limit of the plain reference at each call's own window.
"""
from __future__ import annotations

import dataclasses
import random
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import harness
from bench.reference import ctr, option

# Reported in place of a gap that is not a finite number (NaN or inf in
# the program's output): far above any limit, and valid JSON.
NOT_A_NUMBER = 1e30


@dataclasses.dataclass
class State:
    cell: Any
    seed: int
    service: Any
    call: Any            # lo -> (S,) float32 per-lane payoff sums
    leases: List[int]
    kept: List[Tuple[int, Any]]
    rng: random.Random
    seen: int = 0


def _params(cfg) -> Tuple[float, ...]:
    o = cfg["option"]
    return (o["s0"], o["strike"], o["r"], o["sigma"], o["t"])


def setup(cell, seed: int, devs) -> State:
    import jax
    import jax.numpy as jnp
    from repro.core import engine, u64
    from repro.kernels import mc
    from repro.runtime import blocks

    cfg, tr = cell.config, cell.traffic
    S, T = cfg["num_lanes"], tr["draws_per_call"]
    s0, strike, r, sigma, t = _params(cfg)
    svc = blocks.BlockService(seed=seed)
    svc.open(cfg["channel"], num_streams=S)
    px = engine.make_plan(seed=seed, num_streams=S, num_steps=T,
                          purpose=cfg["purpose_x"])
    py = engine.make_plan(seed=seed, num_streams=S, num_steps=T,
                          purpose=cfg["purpose_y"])
    interpret = engine.use_interpret()

    # the seed's root states and leaf tables are arguments, not
    # constants, so one executable serves every seed
    @jax.jit
    def lanes(c_hi, c_lo, xx, hx, xy, hy):
        ctr_ = (c_hi, c_lo)
        part = mc.option_partials_from_plans(
            dataclasses.replace(px, x0=xx, h=hx, ctr=ctr_, offset=None),
            dataclasses.replace(py, x0=xy, h=hy, ctr=ctr_, offset=None),
            s0=s0, strike=strike, r=r, sigma=sigma, t=t,
            interpret=interpret)
        return jnp.sum(part, axis=0)

    def call(lo: int):
        c_hi, c_lo = u64.const64(lo)
        return lanes(c_hi, c_lo, px.x0, px.h, py.x0, py.h)

    st = State(cell=cell, seed=seed, service=svc, call=call, leases=[],
               kept=[], rng=random.Random(seed * 7919 + 29))
    for _ in range(tr["warmup_calls"]):
        _one(st)
    return st


def _one(st: State, span=harness._nospan):
    name = st.cell.config["channel"]
    with span("bench.lease"):
        lease = st.service.lease(name, st.cell.traffic["draws_per_call"])
    with span("bench.call"):
        out = st.call(lease.lo)
    with span("bench.ready"):
        out.block_until_ready()
    st.service.commit(lease)
    st.leases.append(lease.lo)
    return lease.lo, out


def _keep(st: State, lo: int, out) -> None:
    k = st.cell.traffic["check_calls"]
    st.seen += 1
    if len(st.kept) < k:
        st.kept.append((lo, out))
        return
    j = st.rng.randrange(st.seen)
    if j < k:
        st.kept[j] = (lo, out)


def measure(st: State, seconds: float, span) -> Dict[str, Any]:
    cfg, tr = st.cell.config, st.cell.traffic
    n = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t = t0
    while t < deadline:
        lo, out = _one(st, span)
        t = time.perf_counter()
        n += 1
        _keep(st, lo, out)
    elapsed = t - t0
    paths = n * cfg["num_lanes"] * tr["draws_per_call"]
    return {"attempted": n,
            "end_to_end": {"paths_per_s": paths / elapsed / 1e9},
            "work": {"calls": n, "elapsed_s": elapsed}}


def check(st: State) -> Dict[str, Any]:
    cfg, tr = st.cell.config, st.cell.traffic
    T, S = tr["draws_per_call"], cfg["num_lanes"]
    st.call = None
    lease_faults = sum(1 for i, lo in enumerate(st.leases) if lo != i * T)
    cols = np.arange(S)
    x = ctr.Stream(st.seed, cfg["purpose_x"], cols)
    y = ctr.Stream(st.seed, cfg["purpose_y"], cols)
    limit = cfg["limits"]["lane_gap"]
    gaps = []
    kept, st.kept = st.kept, []
    for lo, out in kept:
        got = np.asarray(out, np.float64)
        want = np.asarray(option.lane_sums(lo, T, x, y, _params(cfg)),
                          np.float64)
        gap = float(np.max(np.abs(got - want)) / np.mean(np.abs(want)))
        gaps.append(gap if np.isfinite(gap) else NOT_A_NUMBER)
    worst = max(gaps, default=0.0)
    bad = sum(g > limit for g in gaps)
    return {"failed": bad, "checks": [
        harness.Check("lane_gap", worst, limit),
        harness.Check("lease_faults", lease_faults, 0),
        harness.Check("calls_checked", len(kept),
                      min(tr["check_calls"], 1), kind="min")]}
