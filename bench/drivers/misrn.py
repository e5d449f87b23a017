"""Closed-loop consumer of MISRN windows from ``BlockService.producer``.

The consumer asks the producer for the next window, waits until it is
ready, and asks again: one window in its hands, the producer's own
prefetch behind it.  On a mesh the service delivers each window through
``engine.generate_sharded``.

Correctness: the leases must be the consecutive windows of the channel
from counter 0 (none repeated, none skipped), and a reservoir sample of
whole windows, drawn from the seed, must equal the plain reference bit
for bit, every shard on its own device.
"""
from __future__ import annotations

import dataclasses
import random
import statistics
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import harness
from bench.reference import ctr, faithful


@dataclasses.dataclass
class State:
    cell: Any
    seed: int
    service: Any
    producer: Any
    leases: List[int]
    kept: List[Tuple[int, Any]]
    rng: random.Random
    seen: int = 0


def _mesh(cfg: Dict[str, Any], devs):
    if not cfg.get("mesh"):
        return None
    from jax.sharding import Mesh
    shape = tuple(cfg["mesh"]["shape"])
    return Mesh(np.array(devs).reshape(shape), tuple(cfg["mesh"]["axes"]))


def setup(cell, seed: int, devs) -> State:
    from repro.runtime import blocks

    cfg, tr = cell.config, cell.traffic
    svc = blocks.BlockService(seed=seed, mesh=_mesh(cfg, devs))
    svc.open(cfg["channel"], num_streams=cfg["num_streams"],
             mode=cfg["mode"], deco=cfg["deco"], sampler=cfg["sampler"],
             out_dtype=cfg["out_dtype"])
    prod = svc.producer(cfg["channel"], tr["window_steps"],
                        **cfg.get("producer", {}))
    st = State(cell=cell, seed=seed, service=svc, producer=prod,
               leases=[], kept=[], rng=random.Random(seed * 7919 + 17))
    for _ in range(tr["warmup_windows"]):
        lease, blk = next(prod)
        blk.block_until_ready()
        st.leases.append(lease.lo)
    return st


def _keep(st: State, lo: int, blk) -> None:
    """Reservoir sample (Algorithm R) of the timed windows."""
    k = st.cell.traffic["check_windows"]
    st.seen += 1
    if len(st.kept) < k:
        st.kept.append((lo, blk))
        return
    j = st.rng.randrange(st.seen)
    if j < k:
        st.kept[j] = (lo, blk)


def measure(st: State, seconds: float, span) -> Dict[str, Any]:
    cfg, tr = st.cell.config, st.cell.traffic
    prod = st.producer
    waits: List[float] = []
    nexts: List[float] = []
    ends: List[float] = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t = t0
    while t < deadline:
        with span("bench.next"):
            a = time.perf_counter()
            lease, blk = next(prod)
            nexts.append(time.perf_counter() - a)
        with span("bench.ready"):
            blk.block_until_ready()
            t = time.perf_counter()
        waits.append(t - a)
        ends.append(t - t0)
        st.leases.append(lease.lo)
        _keep(st, lease.lo, blk)
        del blk
    elapsed = t - t0
    n = len(waits)
    samples = n * tr["window_steps"] * cfg["num_streams"]
    return {"attempted": n,
            "end_to_end": {
                "samples_per_s": samples / elapsed / 1e9,
                "window_p95_ms":
                    statistics.quantiles(waits, n=20)[18] * 1e3
                    if n >= 2 else waits[0] * 1e3},
            "work": {"windows": n, "elapsed_s": elapsed},
            "waits": _waits(waits, nexts, ends)}


def _waits(waits: List[float], nexts: List[float],
           ends: List[float]) -> Dict[str, Any]:
    """How the waits are spread, for the log: quantiles in ms of the whole
    wait and of its ``next()`` part, and the 95th percentile of each
    second of the window."""
    if len(waits) < 20:
        return {}
    def q(xs):
        c = statistics.quantiles(xs, n=100)
        return [round(c[i] * 1e3, 4) for i in (9, 49, 89, 94, 98)]
    by_s: Dict[int, List[float]] = {}
    for w, e in zip(waits, ends):
        by_s.setdefault(int(e), []).append(w)
    return {"wait_q10_50_90_95_99_ms": q(waits),
            "next_q10_50_90_95_99_ms": q(nexts),
            "max_ms": round(max(waits) * 1e3, 3),
            "p95_ms_by_second": [
                round(statistics.quantiles(v, n=20)[18] * 1e3, 4)
                for _, v in sorted(by_s.items()) if len(v) >= 20]}


def _shards(blk):
    """(device, global column start, local block) of every shard."""
    out = []
    for sh in blk.addressable_shards:
        cols = sh.index[1]
        out.append((sh.device, cols.start or 0, sh.data))
    return sorted(out, key=lambda x: x[1])


def check(st: State) -> Dict[str, Any]:
    cfg, tr = st.cell.config, st.cell.traffic
    st.producer.close()
    st.producer = None
    T = tr["window_steps"]
    lease_faults = sum(1 for i, lo in enumerate(st.leases) if lo != i * T)
    purpose = ctr.channel_purpose(cfg["channel"])
    mismatched = 0
    bad_windows = 0
    kept, st.kept = st.kept, []
    for lo, blk in kept:
        n = 0
        for dev, c0, local in _shards(blk):
            cols = np.arange(c0, c0 + local.shape[1])
            stream = ctr.Stream(st.seed, purpose, cols)
            if cfg["mode"] == "ctr":
                n += ctr.mismatches(local, lo, stream, device=dev)
            else:
                n += faithful.mismatches(local, lo, stream, cols, device=dev)
        mismatched += n
        bad_windows += n > 0
        del blk, local
    return {"failed": bad_windows, "checks": [
        harness.Check("mismatched_samples", mismatched, 0),
        harness.Check("lease_faults", lease_faults, 0),
        harness.Check("windows_checked", len(kept),
                      min(tr["check_windows"], 1), kind="min")]}
