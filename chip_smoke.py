#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, at real sizes.

    python chip_smoke.py               # every one-chip phase
    python chip_smoke.py --four-chips  # only the sharded phase, 4 chips

Each phase drives a public entry point and compares what it returns, on
the chip, with an independent reference (the ``xla`` backend, the numpy
golden, the ``use_kernel=False`` path, journal replay or a closed form).
Every phase prints one line: its shapes, its first-call and steady wall
times (informational, not metrics), whether its compiled program holds a
Mosaic kernel (``tpu_custom_call``), and PASS or FAIL.  The last line of
a run in which every phase passed is one JSON object naming the device;
any failure exits non-zero without it.  Off the TPU the script refuses
to run: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import engine, golden, stream as stream_mod, u64  # noqa: E402
from repro.inference import (GumbelMaxSampler, ScheduleConfig,  # noqa: E402
                             run_offline)
from repro.kernels import ops  # noqa: E402
from repro.launch.analysis import collective_bytes  # noqa: E402
from repro.runtime import blocks  # noqa: E402
from repro.service import audit, burst  # noqa: E402
from repro.service.server import RandServer, ServerConfig  # noqa: E402

OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"
SEED = 20_210_518


@dataclasses.dataclass
class Result:
    """One phase's outcome.

    ``kernel``: whether the compiled program holds a Mosaic kernel
    (``None`` for a phase that has no kernel of its own to check).
    ``backend``: the engine backend the phase's generation ran on
    (``None`` where the entry point does not go through the engine).
    ``detail``: readings printed with the verdict (e.g. a gap to a ref).
    """
    name: str
    shapes: str
    first_s: float
    steady_s: float
    checks: Dict[str, bool]
    kernel: Optional[bool] = None
    backend: Optional[str] = None
    detail: str = ""

    @property
    def ok(self) -> bool:
        return bool(self.checks) and all(self.checks.values())

    def line(self) -> str:
        failed = [k for k, v in self.checks.items() if not v]
        kern = {None: "n/a", True: "yes", False: "NO"}[self.kernel]
        verdict = "PASS" if passed(self) else "FAIL"
        return (f"{verdict} {self.name} [{self.shapes}] "
                f"backend={self.backend or 'n/a'} tpu_custom_call={kern} "
                f"first={self.first_s:.3f}s steady={self.steady_s:.6f}s"
                + (f" {self.detail}" if self.detail else "")
                + (f" failed={failed}" if failed else ""))


def passed(r: Result) -> bool:
    """Every check holds, and the phase ran its Mosaic kernel and the
    pallas backend wherever it has them."""
    return r.ok and r.kernel is not False and r.backend in (None, "pallas")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _compile(fn: Callable, *args):
    """(compiled, first-call seconds incl. lower+compile, output)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(compiled(*args))
    return compiled, time.perf_counter() - t0, out


def _compile_static(jitted, kwargs):
    """Compile a jitted entry point whose arguments are all static."""
    t0 = time.perf_counter()
    compiled = jitted.lower(**kwargs, use_kernel=True).compile()
    out = jax.block_until_ready(compiled())
    return compiled, time.perf_counter() - t0, out


def _steady(compiled, *args) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    return time.perf_counter() - t0


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@jax.jit
def _equal(a, b) -> jnp.ndarray:
    return jnp.array_equal(a, b)


@jax.jit
def _max_ulp(a, b) -> jnp.ndarray:
    """Max ULP distance between two float32 arrays."""
    def ordered(x):   # sign-magnitude bits -> monotone int32
        i = jax.lax.bitcast_convert_type(x, jnp.int32)
        return jnp.where(i < 0, jnp.int32(-2 ** 31) - i, i)
    return jnp.max(jnp.abs(ordered(a) - ordered(b)))


def _plan_fn(plan: engine.GenPlan, backend: Optional[str]) -> Callable:
    """Jittable fn(h_hi, h_lo) -> engine.generate of ``plan``."""
    def fn(h_hi, h_lo):
        return engine.generate(dataclasses.replace(plan, h=(h_hi, h_lo)),
                               backend=backend)
    return fn


def _normal_cdf(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


# kernel vs use_kernel=False, as tests/test_kernels.py holds them
PI_ABS_TOL = 1e-12
OPTION_REL_TOL = 1e-6


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_bulk(*, streams: int = 65_536, steps: int = 4_096,
               short_steps: int = 1_024, golden_cols: int = 64
               ) -> List[Result]:
    """Bulk MISRN blocks through ``engine.generate`` against ``xla``."""
    cases = [("ctr", "bits", "float32", steps),
             ("ctr", "uniform", "bfloat16", short_steps),
             ("ctr", "normal", "float32", short_steps),
             ("faithful", "bits", "float32", short_steps)]
    results = []
    for mode, spec, dtype, T in cases:
        plan = engine.make_plan(seed=SEED, num_streams=streams, num_steps=T,
                                mode=mode, sampler=spec, out_dtype=dtype)
        be = engine.select_backend(plan)
        compiled, first, out = _compile(_plan_fn(plan, be), *plan.h)
        steady = _steady(compiled, *plan.h)
        ref = jax.jit(_plan_fn(plan, "xla"))(*plan.h)
        checks = {}
        if spec == "normal":
            checks["xla<=2ulp"] = int(_max_ulp(out, ref)) <= 2
        else:
            checks["xla-exact"] = bool(_equal(out, ref))
        if mode == "ctr" and spec == "bits":
            checks["golden"] = _golden_columns(plan, out, golden_cols)
        results.append(Result(
            name=f"bulk/{mode}/{spec}/{dtype}", shapes=f"T={T} S={streams}",
            first_s=first, steady_s=steady, checks=checks,
            kernel=_has_kernel(compiled), backend=be))
        del out, ref
    return results


def _golden_columns(plan: engine.GenPlan, out, n_cols: int) -> bool:
    """``n_cols`` evenly spread columns of a ctr bits block vs numpy."""
    S = plan.num_streams
    cols = np.unique(np.linspace(0, S - 1, min(n_cols, S)).astype(np.int64))
    x0 = u64.join64(np.asarray(plan.x0[0]), np.asarray(plan.x0[1]))
    hh, hl = np.asarray(plan.h[0]), np.asarray(plan.h[1])
    h = np.array([u64.join64(hh[c], hl[c]) for c in cols], dtype=object)
    want = golden.thundering_block(x0, h, plan.num_steps, mode="ctr",
                                   offset=plan.offset).T
    return bool(np.array_equal(np.asarray(out[:, cols]), want))


def phase_delivery(*, streams: int = 65_536, window: int = 1_024,
                   windows: int = 8, fuse: int = 4) -> Result:
    """``BlockService.take`` and a donated, fused producer ring."""
    svc = blocks.BlockService(seed=SEED)
    name = "smoke/delivery"
    svc.open(name, num_streams=streams)
    base = engine.make_plan(seed=SEED, num_streams=streams,
                            num_steps=window,
                            purpose=svc.channel(name).purpose)
    be = engine.select_backend(base)

    def ref_fn(c_hi, c_lo, h_hi, h_lo):
        plan = dataclasses.replace(base, h=(h_hi, h_lo), ctr=(c_hi, c_lo),
                                   offset=None)
        return engine.generate(plan, backend="xla")
    ref_jit = jax.jit(ref_fn)

    def ref(lo):
        c_hi, c_lo = (u64.to_u32(v) for v in u64.const64(lo))
        return ref_jit(c_hi, c_lo, *base.h)

    checks = {"donation": blocks.donation_supported()}
    t0 = time.perf_counter()
    first = jax.block_until_ready(svc.take(name, window))
    first_s = time.perf_counter() - t0
    checks["take"] = bool(_equal(first, ref(0)))
    del first
    ok, times = True, []
    t0 = time.perf_counter()
    with svc.producer(name, window, depth=2, fuse=fuse, count=windows,
                      donate=True) as prod:
        for lease, blk in prod:
            jax.block_until_ready(blk)
            times.append(time.perf_counter() - t0)
            ok &= bool(_equal(blk, ref(lease.lo)))
            t0 = time.perf_counter()
    checks["producer"] = ok and len(times) == windows
    steady = float(np.median(times[fuse:])) if len(times) > fuse else 0.0
    # the producer's fused dispatch is engine.generate_windows
    fused = jax.jit(lambda hh, hl: engine.generate_windows(
        dataclasses.replace(base, h=(hh, hl)), fuse, backend=be))
    return Result(
        name="delivery/take+producer",
        shapes=f"L={window} S={streams} windows={windows} fuse={fuse}",
        first_s=first_s, steady_s=steady, checks=checks,
        kernel=_has_kernel(fused.lower(*base.h).compile()), backend=be)


def phase_service(*, tenants: int = 1_024, burst_size: int = 512,
                  journal_dir: Path = OUT_DIR) -> Result:
    """An in-process ``RandServer`` burst, then journal replay."""
    journal_dir.mkdir(parents=True, exist_ok=True)
    path = journal_dir / "service.jsonl"
    if path.exists():
        path.unlink()   # an existing journal would be restored, not written
    journal = audit.Journal(str(path))
    cfg = ServerConfig(max_batch=256, max_delay_s=0.25,
                       queue_depth=max(4096, 2 * burst_size),
                       hot_classes=(("uniform", "float32"),))
    server = RandServer(SEED, config=cfg, journal=journal, start=False)
    responses = {}
    times = []
    try:
        for prefix in ("first", "steady"):
            reqs = burst.make_requests(burst=burst_size, tenants=tenants,
                                       seed=SEED, pattern="mixed",
                                       rid_prefix=prefix)
            t0 = time.perf_counter()
            futs = [server.submit(r) for r in reqs]
            server.start()
            responses.update({r.rid: f.result(timeout=900)
                              for r, f in zip(reqs, futs)})
            times.append(time.perf_counter() - t0)
        served = server.stats()["requests_served"]
    finally:
        server.shutdown()
    replayed = audit.replay(str(path), seed=SEED)
    checks = {
        "all-served": (len(responses) == 2 * burst_size
                       and served == 2 * burst_size),
        "replay-digest": (set(replayed) == set(responses)
                          and audit.response_digest(replayed)
                          == audit.response_digest(responses)),
    }
    return Result(name="service/burst+replay",
                  shapes=f"burst={burst_size}x2 tenants={tenants}",
                  first_s=times[0], steady_s=times[1], checks=checks)


def phase_tokens(*, batch: int = 128, vocab: int = 151_552,
                 max_steps: int = 16) -> Result:
    """Fused gumbel-max decode steps, checked against the two-pass path."""
    cfg = ScheduleConfig(capacity=batch, vocab=vocab, seed=SEED,
                         path="fused", max_steps=max_steps)
    report = run_offline(cfg, parity=True)
    steps = report.result.step_seconds
    checks = {
        "parity-digest": report.parity_digest == report.result.digest,
        "decoded": report.result.total_tokens > 0,
        "one-call-per-step":
            report.result.sampler_stats["calls_per_step"] == 1.0,
    }
    sampler = GumbelMaxSampler.standalone(seed=SEED, vocab=vocab,
                                          capacity=batch)
    args = (jnp.zeros((batch, vocab), jnp.float32),
            jnp.zeros((batch,), jnp.uint32), jnp.arange(batch, dtype=jnp.uint32),
            jnp.uint32(0), jnp.uint32(0))
    kernel = _has_kernel(sampler.jitted("fused").lower(*args).compile())
    return Result(name="tokens/fused-gumbel-argmax",
                  shapes=f"B={batch} V={vocab} steps={max_steps}",
                  first_s=steps[0], steady_s=float(np.median(steps[1:])),
                  checks=checks, kernel=kernel)


def phase_apps(*, lanes: int = 65_536, draws: int = 4_096) -> List[Result]:
    """The paper's pi and option-pricing kernels vs their ref paths.

    The kernel must match ``use_kernel=False`` within ``PI_ABS_TOL`` and
    ``OPTION_REL_TOL`` (the gap is printed), and the estimates must fall
    within six standard errors of the closed-form values.
    """
    n = lanes * draws
    common = dict(seed=SEED, num_lanes=lanes, draws_per_lane=draws)
    results = []

    pi_c, first, pi_k = _compile_static(ops.estimate_pi, common)
    steady = _steady(pi_c)
    pi_r = float(ops.estimate_pi(**common, use_kernel=False))
    sigma = 4.0 * math.sqrt((math.pi / 4) * (1 - math.pi / 4) / n)
    results.append(Result(
        name="apps/estimate_pi", shapes=f"lanes={lanes} draws={draws}",
        first_s=first, steady_s=steady,
        checks={"ref-close": abs(float(pi_k) - pi_r) <= PI_ABS_TOL,
                "6sigma": abs(float(pi_k) - math.pi) < 6 * sigma},
        kernel=_has_kernel(pi_c),
        detail=f"ref_gap_abs={abs(float(pi_k) - pi_r):.3e}"))

    s0, k, r, vol, t = 100.0, 100.0, 0.05, 0.2, 1.0
    opt = dict(common, s0=s0, strike=k, r=r, sigma=vol, t=t)
    op_c, first, op_k = _compile_static(ops.price_option, opt)
    steady = _steady(op_c)
    op_r = float(ops.price_option(**opt, use_kernel=False))
    d2 = (math.log(s0 / k) + (r - 0.5 * vol * vol) * t) / (vol * math.sqrt(t))
    d1 = d2 + vol * math.sqrt(t)
    price = s0 * _normal_cdf(d1) - k * math.exp(-r * t) * _normal_cdf(d2)
    # second moment of the discounted payoff, closed form
    m2 = math.exp(-2 * r * t) * (
        s0 * s0 * math.exp((2 * r + vol * vol) * t)
        * _normal_cdf(d2 + 2 * vol * math.sqrt(t))
        - 2 * k * s0 * math.exp(r * t) * _normal_cdf(d1)
        + k * k * _normal_cdf(d2))
    sigma = math.sqrt((m2 - price * price) / n)
    gap = abs(float(op_k) - op_r) / abs(op_r)
    results.append(Result(
        name="apps/price_option", shapes=f"lanes={lanes} draws={draws}",
        first_s=first, steady_s=steady,
        checks={"ref-close": gap <= OPTION_REL_TOL,
                "6sigma": abs(float(op_k) - price) < 6 * sigma},
        kernel=_has_kernel(op_c), detail=f"ref_gap_rel={gap:.3e}"))
    return results


def phase_dropout(*, rows: int = 8_192, cols: int = 4_096,
                  rate: float = 0.1) -> Result:
    """``ops.fused_dropout`` on a bf16 activation vs the unfused path."""
    x = jax.random.normal(jax.random.key(SEED), (rows, cols), jnp.bfloat16)
    s = stream_mod.new_stream(SEED, 0)
    compiled, first, out = _compile(
        lambda a: ops.fused_dropout(a, s, rate, use_kernel=True), x)
    steady = _steady(compiled, x)
    ref = jax.jit(lambda a: ops.fused_dropout(a, s, rate,
                                              use_kernel=False))(x)
    bits = functools.partial(jax.lax.bitcast_convert_type,
                             new_dtype=jnp.uint16)
    return Result(name="dropout/fused", shapes=f"({rows}, {cols}) bf16",
                  first_s=first, steady_s=steady,
                  checks={"ref-exact": bool(_equal(bits(out), bits(ref)))},
                  kernel=_has_kernel(compiled))


def phase_sharded(*, streams: int = 262_144, steps: int = 4_096
                  ) -> List[Result]:
    """``engine.generate_sharded`` on a 1-D and a 2x2 mesh vs one device."""
    from jax.sharding import Mesh

    devs = jax.devices()[:4]
    if len(devs) != 4:
        raise ValueError(f"the sharded phase needs 4 devices, have {len(devs)}")
    meshes = {"1d": (Mesh(np.array(devs), ("streams",)), ("streams",)),
              "2x2": (Mesh(np.array(devs).reshape(2, 2), ("hosts", "streams")),
                      ("hosts", "streams"))}
    results = []
    for mode in ("ctr", "faithful"):
        def make():
            return engine.make_plan(seed=SEED, num_streams=streams,
                                    num_steps=steps, mode=mode)
        be = engine.select_backend(make())
        with jax.default_device(devs[0]):
            ref = jax.jit(lambda: engine.generate(make()))()
        for label, (mesh, axes) in meshes.items():
            compiled, first, out = _compile(
                lambda: engine.generate_sharded(make(), mesh=mesh,
                                                axis_names=axes))
            steady = _steady(compiled)
            ok = True
            for shard in out.addressable_shards:
                got = jax.device_put(shard.data, devs[0])
                ok &= bool(_equal(got, ref[shard.index]))
            results.append(Result(
                name=f"sharded/{mode}/{label}",
                shapes=f"T={steps} S={streams} mesh={dict(mesh.shape)}",
                first_s=first, steady_s=steady,
                checks={"single-device-exact": ok,
                        "no-collectives":
                            collective_bytes(compiled.as_text())["total"] == 0},
                kernel=_has_kernel(compiled), backend=be))
            del out
        del ref
    return results


ONE_CHIP_PHASES = (phase_bulk, phase_delivery, phase_service, phase_tokens,
                   phase_apps, phase_dropout)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on four chips")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, not "
              f"'tpu'; this script runs only on a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    phases = (phase_sharded,) if args.four_chips else ONE_CHIP_PHASES
    all_ok = True
    for phase in phases:
        try:
            out = phase()
        except Exception:  # noqa: BLE001 — reported, and the run fails
            traceback.print_exc()
            print(f"FAIL {phase.__name__}: raised (traceback on stderr)",
                  flush=True)
            all_ok = False
            continue
        for r in out if isinstance(out, list) else [out]:
            print(r.line(), flush=True)
            all_ok &= passed(r)
    if not all_ok:
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
