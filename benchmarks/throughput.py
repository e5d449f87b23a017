"""Paper Figs. 5-6 analogue: bulk MISRN throughput vs number of stream
instances, plus the fused sampler pipeline.

The paper scales SOU instances on a U250 (up to 655 Gnum/s).  Here the
jnp reference path (the same arithmetic the Pallas kernel runs per tile)
executes on the host CPU; the figure of merit is throughput scaling with
S (the state-sharing claim: cost per stream is one add + output stage —
adding streams must scale ~linearly until bandwidth saturates) plus the
projected TPU bound (bulk generation writes 4 B/sample; one v5e chip at
819 GB/s is HBM-bound at ~205 Gsample/s; bf16 fused sampling halves the
written bytes -> ~410 GSample/s ceiling; the fused-consumer kernels in
benchmarks/apps.py beat both by never writing the samples).

``run``/``smoke``/``sampler_smoke``/``dist_smoke``/``pipelined_smoke``/
``service_smoke`` also append machine-readable row dicts (GSample/s per
backend/sampler/dtype/variant; jitted rows carry ``compile_us`` so
``us_per_call`` is always steady state) that ``run.py`` and ``__main__``
dump to ``BENCH_throughput.json`` — the perf trajectory file.  The
sampler section times the fused one-pass path
(transform applied where the bits are generated) against the historical
two-pass path (uint32 block materialized by one jitted call, transformed
by a second), which is the HBM round-trip the sampler stage deletes.
``pipelined_smoke`` times the block-delivery layer: double-buffered
producer vs synchronous lease+generate, and the 1-D vs 2-D mesh rows.
``service_smoke`` times the randomness-as-a-service layer: a mixed
multi-tenant burst through the coalescing frontend + standing pool
(requests/s, p50/p99 latency, coalescing factor).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (BENCH_JSON, bytes_per_sample, row, time_fn,
                               time_fn_stats, write_bench_json)
from repro.core import engine, sampler as sampler_mod
from repro.kernels import ops
from repro.runtime import BlockService

T_STEPS = 4096
HBM_BW = 819e9

SAMPLER_CASES = (
    ("uniform", "float32"),
    ("uniform", "bfloat16"),
    ("normal", "float32"),
    ("normal", "bfloat16"),
    ("bernoulli(0.5)", "float32"),
)

@functools.partial(jax.jit, static_argnames=("s", "t", "mode", "deco",
                                             "backend"))
def _bulk(s: int, t: int, mode: str, deco: str = "splitmix64",
          backend: str = "ref"):
    return ops.thundering_bulk(seed=7, num_streams=s, num_steps=t,
                               mode=mode, deco=deco, backend=backend)


@functools.partial(jax.jit, static_argnames=("s", "t", "sampler", "dtype",
                                             "backend"))
def _fused(s: int, t: int, sampler: str, dtype: str, backend: str):
    plan = engine.make_plan(seed=7, num_streams=s, num_steps=t,
                            sampler=sampler, out_dtype=dtype)
    return engine.generate(plan, backend=backend)


@functools.partial(jax.jit, static_argnames=("sampler", "dtype"))
def _transform(bits, sampler: str, dtype: str):
    return sampler_mod.apply(bits, sampler_mod.parse(sampler), dtype)


def _two_pass(s: int, t: int, sampler: str, dtype: str, backend: str):
    """bits-then-transform: two jitted calls, the uint32 block crosses the
    jit boundary (i.e. HBM on a real chip) in between."""
    bits = _fused(s, t, "bits", "float32", backend)
    return _transform(bits, sampler, dtype)


def _record(records, **kw):
    """Append one perf-trajectory row, deriving the bandwidth fields.

    Every row with a parseable sampler gains ``bytes_per_sample`` (the
    output element width — the roofline's traffic model) and
    ``gbytes_per_s`` (= GSample/s x bytes/sample), so bandwidth-bound
    comparisons never re-derive dtype widths from row names.  Rows may
    pre-set both (the service row's effective mixed-burst value).
    """
    if records is None:
        return
    g = kw.get("gsamples_per_s")
    if g is not None and "bytes_per_sample" not in kw:
        bps = bytes_per_sample(kw.get("sampler", ""),
                               kw.get("dtype") or "float32")
        if bps is not None:
            kw["bytes_per_sample"] = bps
            kw["gbytes_per_s"] = g * bps
    records.append(kw)


def _sampler_section(out, records, s: int, t: int, iters: int) -> None:
    backend = engine.select_backend(
        engine.make_plan(seed=7, num_streams=s, num_steps=t))
    n = s * t
    for sampler, dtype in SAMPLER_CASES:
        st_f = time_fn_stats(_fused, s, t, sampler, dtype, backend,
                             iters=iters)
        st_2 = time_fn_stats(_two_pass, s, t, sampler, dtype, backend,
                             iters=iters)
        sec_f, sec_2 = st_f["median_s"], st_2["median_s"]
        gs_f, gs_2 = n / sec_f / 1e9, n / sec_2 / 1e9
        speed = sec_2 / sec_f
        tag = f"{sampler}/{dtype}"
        out(row(f"throughput/sampler/{tag}/S={s}", sec_f * 1e6,
                f"{gs_f:.3f} GSample/s {backend} fused "
                f"x{speed:.2f} vs two-pass"))
        _record(records, name=f"sampler/{tag}/S={s}", backend=backend,
                sampler=sampler, dtype=dtype, variant="fused",
                num_streams=s, num_steps=t, us_per_call=st_f["us_per_call"],
                compile_us=st_f["compile_us"],
                gsamples_per_s=gs_f, speedup_vs_two_pass=speed)
        _record(records, name=f"sampler/{tag}/S={s}", backend=backend,
                sampler=sampler, dtype=dtype, variant="two_pass",
                num_streams=s, num_steps=t, us_per_call=st_2["us_per_call"],
                compile_us=st_2["compile_us"], gsamples_per_s=gs_2)


def run(out, records=None):
    prev = None
    for s in (128, 512, 2048, 8192):
        st = time_fn_stats(_bulk, s, T_STEPS, "ctr", iters=3)
        sec = st["median_s"]
        samples = s * T_STEPS
        gs = samples / sec / 1e9
        scale = f" x{gs / prev:.2f}" if prev else ""
        prev = gs
        out(row(f"throughput/ctr/S={s}", sec * 1e6,
                f"{gs:.3f} GSample/s host{scale}"))
        _record(records, name=f"bulk/ctr/S={s}", backend="ref",
                sampler="bits", dtype="uint32", variant="fused",
                num_streams=s, num_steps=T_STEPS,
                us_per_call=st["us_per_call"],
                compile_us=st["compile_us"], gsamples_per_s=gs)
    # faithful mode (serial xorshift decorrelator) at one size
    st = time_fn_stats(_bulk, 512, T_STEPS, "faithful", iters=3)
    sec = st["median_s"]
    gs = 512 * T_STEPS / sec / 1e9
    out(row("throughput/faithful/S=512", sec * 1e6,
            f"{gs:.3f} GSample/s host"))
    _record(records, name="bulk/faithful/S=512", backend="ref",
            sampler="bits", dtype="uint32", variant="fused",
            num_streams=512, num_steps=T_STEPS,
            us_per_call=st["us_per_call"], compile_us=st["compile_us"],
            gsamples_per_s=gs)
    # fmix32 decorrelator (beyond-paper; 96 -> 30 uint ops/sample)
    sec64 = time_fn(_bulk, 2048, T_STEPS, "ctr", iters=3)
    sec32 = time_fn(_bulk, 2048, T_STEPS, "ctr", "fmix32", iters=3)
    gs = 2048 * T_STEPS / sec32 / 1e9
    out(row("throughput/ctr_fmix32/S=2048", sec32 * 1e6,
            f"{gs:.3f} GSample/s host x{sec64 / sec32:.2f} vs splitmix64"))
    # engine dispatch overhead: same plan through ref vs xla backends
    sec_ref = time_fn(_bulk, 2048, T_STEPS, "ctr", "splitmix64", "ref",
                      iters=3)
    sec_xla = time_fn(_bulk, 2048, T_STEPS, "ctr", "splitmix64", "xla",
                      iters=3)
    out(row("throughput/engine_xla/S=2048", sec_xla * 1e6,
            f"{2048 * T_STEPS / sec_xla / 1e9:.3f} GSample/s host "
            f"x{sec_ref / sec_xla:.2f} vs ref backend"))
    # fused sampler pipeline vs the bits-then-transform two-pass path
    _sampler_section(out, records, s=2048, t=T_STEPS, iters=3)
    out(row("throughput/tpu_projection", 0.0,
            f"bulk HBM-bound {HBM_BW / 4 / 1e9:.0f} GSample/s/chip "
            f"(f32/u32), {HBM_BW / 2 / 1e9:.0f} bf16 fused;"
            f" paper FPGA 655 Gnum/s"))


def smoke(out=print, records=None) -> None:
    """CI-sized sanity run: one small block per backend, bit-equal check.

    Each path is timed as a JITTED function with the warm-up factored
    out (``time_fn_stats``): ``us_per_call`` is steady-state dispatch +
    execution, and trace+compile cost lands in its own ``compile_us``
    field — an eager first call used to dominate these rows and made
    them incomparable with the jitted sampler rows.
    """
    plan = engine.make_plan(seed=7, num_streams=256, num_steps=64)
    base = np.asarray(engine.generate(plan, backend="ref"))
    for backend in ("xla", "pallas"):
        fn = jax.jit(functools.partial(engine.generate, plan,
                                       backend=backend))
        st = time_fn_stats(fn, iters=3)
        assert np.array_equal(base, np.asarray(fn())), \
            f"{backend} disagrees with ref"
        out(row(f"smoke/{backend}", st["us_per_call"],
                f"bit-equal to ref, compile {st['compile_us'] / 1e3:.0f}ms"))
        _record(records, name=f"smoke/{backend}", backend=backend,
                sampler="bits", dtype="uint32", variant="fused",
                num_streams=256, num_steps=64,
                us_per_call=st["us_per_call"], compile_us=st["compile_us"],
                gsamples_per_s=256 * 64 / st["median_s"] / 1e9)
    fn = jax.jit(functools.partial(engine.generate_sharded, plan))
    st = time_fn_stats(fn, iters=3)
    assert np.array_equal(base, np.asarray(fn()))
    out(row("smoke/sharded", st["us_per_call"],
            f"bit-equal over {len(jax.devices())} device(s), "
            f"compile {st['compile_us'] / 1e3:.0f}ms"))
    _record(records, name="smoke/sharded", backend="sharded",
            sampler="bits", dtype="uint32", variant="fused",
            num_streams=256, num_steps=64, us_per_call=st["us_per_call"],
            compile_us=st["compile_us"],
            gsamples_per_s=256 * 64 / st["median_s"] / 1e9)


def sampler_smoke(out=print, records=None) -> None:
    """CI-sized fused-sampler run: parity per backend + fused/two-pass
    timing at one small size."""
    for sampler, dtype in SAMPLER_CASES:
        plan = engine.make_plan(seed=11, num_streams=256, num_steps=64,
                                sampler=sampler, out_dtype=dtype)
        base = np.asarray(engine.generate(plan, backend="ref"))

        def raw(a):
            return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a

        for backend in ("xla", "pallas"):
            got = np.asarray(engine.generate(plan, backend=backend))
            if sampler == "normal":  # libm ULP slack, see test_sampler
                assert np.allclose(got.astype(np.float32),
                                   base.astype(np.float32), rtol=1e-5), \
                    (sampler, backend)
            else:
                assert np.array_equal(raw(got), raw(base)), \
                    (sampler, backend)
        out(row(f"smoke/sampler/{sampler}/{dtype}", 0.0,
                "matches ref on xla+pallas"))
    _sampler_section(out, records, s=2048, t=2048, iters=2)


DIST_CASES = (
    ("exponential(1.5)", "float32"),
    ("exponential(1.5)", "bfloat16"),
    ("poisson(3.5)", "float32"),
    ("gamma(2.5)", "float32"),
    ("categorical[0.5,0.25,0.125,0.125]", "float32"),
)


def dist_smoke(out=print, records=None, *, s: int = 2048,
               t: int = 2048) -> None:
    """Distribution-stage rows: backend parity at small size, then
    fused-vs-two-pass GSample/s per (distribution, dtype) at S=2048.

    The fused path applies the distribution transform where the bits are
    generated (one executable, no uint32 intermediate); the two-pass
    path materializes the bit block first — the HBM round-trip the
    in-kernel stages delete.  Gamma is the expensive row (6 unrolled
    Marsaglia-Tsang retry rows, each with a Box-Muller candidate);
    poisson costs one compare per threshold-ladder rung; categorical one
    compare per outcome."""
    for spec, dtype in DIST_CASES:
        plan = engine.make_plan(seed=11, num_streams=256, num_steps=64,
                                sampler=spec, out_dtype=dtype)
        base = np.asarray(engine.generate(plan, backend="ref"))

        def raw(a):
            return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a

        for backend in ("xla", "pallas"):
            got = np.asarray(engine.generate(plan, backend=backend))
            if backend == "pallas" and spec.startswith(("exponential",
                                                        "gamma")):
                # log-based stages: few-ULP libm lane slack on padded
                # tiles (see tests/test_distributions.py)
                assert np.allclose(got.astype(np.float32),
                                   base.astype(np.float32), rtol=1e-5), \
                    (spec, backend)
            else:
                assert np.array_equal(raw(got), raw(base)), (spec, backend)
        out(row(f"smoke/dist/{spec}/{dtype}", 0.0,
                "matches ref on xla+pallas"))
    n = s * t
    backend = engine.select_backend(
        engine.make_plan(seed=7, num_streams=s, num_steps=t))
    for spec, dtype in DIST_CASES:
        st_f = time_fn_stats(_fused, s, t, spec, dtype, backend, iters=2)
        st_2 = time_fn_stats(_two_pass, s, t, spec, dtype, backend, iters=2)
        sec_f, sec_2 = st_f["median_s"], st_2["median_s"]
        gs_f, gs_2 = n / sec_f / 1e9, n / sec_2 / 1e9
        speed = sec_2 / sec_f
        tag = f"{spec}/{dtype}"
        out(row(f"throughput/dist/{tag}/S={s}", sec_f * 1e6,
                f"{gs_f:.3f} GSample/s {backend} fused "
                f"x{speed:.2f} vs two-pass"))
        _record(records, name=f"dist/{tag}/S={s}", backend=backend,
                sampler=spec, dtype=dtype, variant="fused",
                num_streams=s, num_steps=t, us_per_call=st_f["us_per_call"],
                compile_us=st_f["compile_us"],
                gsamples_per_s=gs_f, speedup_vs_two_pass=speed)
        _record(records, name=f"dist/{tag}/S={s}", backend=backend,
                sampler=spec, dtype=dtype, variant="two_pass",
                num_streams=s, num_steps=t, us_per_call=st_2["us_per_call"],
                compile_us=st_2["compile_us"], gsamples_per_s=gs_2)


def _consume(block):
    """Stand-in application kernel: one jitted reduction per block (so the
    double-buffered producer has real consumer work to overlap with)."""
    return jnp.sum(jnp.asarray(block, jnp.float32) if block.dtype ==
                   jnp.uint32 else block)


def pipelined_smoke(out=print, records=None, *, s: int = 512, t: int = 2048,
                    n_blocks: int = 8) -> None:
    """Block-delivery smoke: double-buffered producer vs synchronous
    lease+generate, and the 1-D vs 2-D mesh fan-out, all bit-checked.

    On this 1-CPU container the producer thread shares the XLA device
    with the consumer, so the double-buffer win is host-dispatch overlap
    only; the HBM-level story is the TPU projection (see EXPERIMENTS.md).
    """
    n = s * t * n_blocks

    # one standing service per variant: successive timed calls consume
    # FRESH windows (the ledger forbids reuse) through one cached window
    # executable — the steady-state delivery cost, not trace time.
    svc_s = BlockService(seed=23)
    svc_s.open("bench", num_streams=s)
    svc_p = BlockService(seed=23)
    svc_p.open("bench", num_streams=s)

    def run_sync():
        acc = []
        for _ in range(n_blocks):
            acc.append(_consume(svc_s.take("bench", t)))
        return jax.block_until_ready(jnp.stack(acc))

    def run_pipelined():
        with svc_p.producer("bench", t, count=n_blocks) as prod:
            acc = [_consume(block) for _, block in prod]
        return jax.block_until_ready(jnp.stack(acc))

    # same seed + same windows => bit-identical first pass
    base = np.asarray(run_sync())
    assert np.array_equal(base, np.asarray(run_pipelined())), \
        "double-buffered blocks disagree with synchronous"
    st_s = time_fn_stats(run_sync, iters=3, warmup=1)
    st_p = time_fn_stats(run_pipelined, iters=3, warmup=1)
    sec_s, sec_p = st_s["median_s"], st_p["median_s"]
    gs_s, gs_p = n / sec_s / 1e9, n / sec_p / 1e9
    out(row(f"pipelined/sync/S={s}", sec_s * 1e6,
            f"{gs_s:.3f} GSample/s lease+generate per block"))
    out(row(f"pipelined/double_buffered/S={s}", sec_p * 1e6,
            f"{gs_p:.3f} GSample/s x{sec_s / sec_p:.2f} vs sync"))
    _record(records, name=f"pipelined/S={s}", backend="service",
            sampler="bits", dtype="uint32", variant="sync",
            num_streams=s, num_steps=t * n_blocks,
            us_per_call=st_s["us_per_call"], compile_us=st_s["compile_us"],
            gsamples_per_s=gs_s)
    _record(records, name=f"pipelined/S={s}", backend="service",
            sampler="bits", dtype="uint32", variant="double_buffered",
            num_streams=s, num_steps=t * n_blocks,
            us_per_call=st_p["us_per_call"], compile_us=st_p["compile_us"],
            gsamples_per_s=gs_p, speedup_vs_two_pass=sec_s / sec_p)

    # 1-D vs 2-D mesh fan-out (degenerate single-device grids here; the
    # row exists so the TPU run records the real (hosts, streams) split)
    plan = engine.make_plan(seed=23, num_streams=s, num_steps=t)
    base = np.asarray(engine.generate(plan, backend="xla"))
    devs = np.array(jax.devices())
    meshes = {
        "mesh1d": (jax.sharding.Mesh(devs, ("streams",)), ("streams",)),
        "mesh2d": (jax.sharding.Mesh(devs.reshape(1, -1),
                                     ("hosts", "streams")),
                   ("hosts", "streams")),
    }
    for name, (mesh, axes) in meshes.items():
        fn = jax.jit(functools.partial(engine.generate_sharded, plan,
                                       mesh=mesh, axis_names=axes))
        assert np.array_equal(base, np.asarray(fn())), name
        st = time_fn_stats(fn, iters=2)
        gs = s * t / st["median_s"] / 1e9
        out(row(f"pipelined/{name}/S={s}", st["us_per_call"],
                f"{gs:.3f} GSample/s over {mesh.devices.size} device(s) "
                f"axes={'x'.join(axes)}"))
        _record(records, name=f"pipelined/{name}/S={s}", backend="sharded",
                sampler="bits", dtype="uint32", variant=name,
                num_streams=s, num_steps=t, us_per_call=st["us_per_call"],
                compile_us=st["compile_us"], gsamples_per_s=gs)


def service_smoke(out=print, records=None, *, burst: int = 192,
                  tenants: int = 64) -> None:
    """RandService serving rows: requests/s, p50/p99 latency, coalescing.

    A first (untimed) burst traces/compiles the fused window functions
    and fills the standing pool; the timed burst re-runs the same shape
    mix against fresh counter windows, so the row is steady-state
    serving cost (the warm-up wall time is reported as ``compile_us``).
    """
    import time as _time

    from repro.service import RandServer, ServerConfig
    from repro.service.audit import verify_ledger_disjoint
    from repro.service.burst import make_requests, run_burst

    srv = RandServer(seed=29, config=ServerConfig(
        max_batch=64, max_delay_s=0.05,
        hot_classes=(("uniform", "float32"),)))
    reqs = make_requests(burst=burst, tenants=tenants, seed=1)
    t0 = _time.perf_counter()
    run_burst(srv, reqs)                       # warm-up: trace + compile
    warm_s = _time.perf_counter() - t0
    srv.reset_metrics()
    t0 = _time.perf_counter()
    got = run_burst(srv, reqs)                 # fresh windows, cached fns
    wall = _time.perf_counter() - t0
    assert len(got) == burst
    stats = srv.stats()
    verify_ledger_disjoint(srv.block_service)
    srv.shutdown()
    rps = burst / wall
    # mixed burst: effective bytes/sample from the actual responses
    total_samples = sum(int(np.asarray(a).size) for a in got)
    total_bytes = sum(int(np.asarray(a).nbytes) for a in got)
    eff_bps = total_bytes / max(1, total_samples)
    gs = total_samples / wall / 1e9
    out(row(f"service/burst={burst}", wall / burst * 1e6,
            f"{rps:.0f} req/s p50={stats['latency_p50_ms']:.1f}ms "
            f"p99={stats['latency_p99_ms']:.1f}ms "
            f"{stats['calls_per_request']:.3f} calls/req "
            f"(x{stats['coalescing_factor']:.0f} coalescing)"))
    _record(records, name=f"service/burst={burst}", backend="service",
            sampler="mixed", dtype="mixed", variant="coalesced+pool",
            num_streams=tenants, num_steps=burst,
            us_per_call=wall / burst * 1e6, compile_us=warm_s * 1e6,
            gsamples_per_s=gs, bytes_per_sample=eff_bps,
            gbytes_per_s=gs * eff_bps,
            requests_per_s=rps,
            latency_p50_ms=stats["latency_p50_ms"],
            latency_p99_ms=stats["latency_p99_ms"],
            calls_per_request=stats["calls_per_request"],
            coalescing_factor=stats["coalescing_factor"],
            fill_ratio=stats["fill_ratio"])


def fleet_smoke(out=print, records=None, *, burst: int = 96,
                tenants: int = 32, shards: int = 2) -> None:
    """Wire-level fleet rows: the adversarial traffic suite over
    subprocess shards + socket transport, with pipelined clients,
    microbatch coalescing and standing pools in the shards.

    Accounting: warm variants run an untimed warm-up burst first
    (rids prefixed ``warm/`` so they never collide with the timed
    burst in the journal), then reset both client- and shard-side
    metrics — so the row is steady-state serving cost with the
    first-connect/handshake/jit split out (reported as
    ``compile_us``).  The ``kill`` pair runs COLD: warm-up rids parse
    through ``rid_index`` and would fire the scripted injector early.

    Variants: ``binary`` vs ``json`` (same array-heavy traffic, wire
    v2 vs v1 — the transport speedup pair CI gates on), ``hammer``
    (every request from ONE tenant — no routing spread), ``unique``
    (every request a distinct shape — zero class coalescing), and
    ``kill`` (mixed traffic, scripted kill at the burst midpoint:
    ``recovery_ms`` is the failover cost and the response digest is
    asserted equal to the cold no-fault run — the failover correctness
    check as a benchmark side effect, now with pools + coalescing +
    pipelining all on).
    """
    import tempfile
    import time as _time

    from repro.runtime.fault import FaultPlan
    from repro.service import transport
    from repro.service.audit import response_digest
    from repro.service.burst import make_requests
    from repro.service.fleet import Fleet, FleetConfig, run_fleet_burst

    def reset_fleet(client) -> None:
        for logical, proc in sorted(client._owner.items()):
            transport.rpc(client.addresses[proc],
                          {"op": "reset", "shard": logical}, timeout=10.0)
        client.reset_metrics()

    def shard_counters(client) -> dict:
        engine = leases = served = pooled = 0
        for logical, proc in sorted(client._owner.items()):
            try:
                reply = transport.rpc(client.addresses[proc],
                                      {"op": "stats", "shard": logical},
                                      timeout=10.0)
            except (OSError, transport.TransportError):
                continue            # fenced/dead owner
            if reply.get("ok"):
                s = reply["stats"]
                engine += s.get("engine_calls", 0)
                leases += s.get("lease_calls", 0)
                served += s.get("requests_served", 0)
                pooled += s.get("pool_requests", 0)
        return {"coalesce_calls_per_req": ((engine + leases) / served
                                           if served else 0.0),
                "pool_hit_rate": pooled / served if served else 0.0}

    def one(variant: str, pattern: str, plan: FaultPlan, *,
            binary: bool = True, warm: bool = True, max_side: int = 64):
        with tempfile.TemporaryDirectory() as jdir:
            cfg = FleetConfig(num_shards=shards, seed=31,
                              journal_dir=jdir)
            reqs = make_requests(burst=burst, tenants=tenants, seed=2,
                                 pattern=pattern, max_side=max_side)
            with Fleet(cfg, plan) as fleet:
                client = fleet.client(binary=binary)
                warm_s = 0.0
                if warm:
                    t0 = _time.perf_counter()
                    run_fleet_burst(client, make_requests(
                        burst=burst, tenants=tenants, seed=2,
                        pattern=pattern, max_side=max_side,
                        rid_prefix="warm"))
                    warm_s = _time.perf_counter() - t0
                    reset_fleet(client)
                t0 = _time.perf_counter()
                got = run_fleet_burst(client, reqs)
                wall = _time.perf_counter() - t0
                stats = client.stats()
                stats.update(shard_counters(client))
                client.close()
        assert len(got) == burst
        digest = response_digest(got)
        rps = burst / wall
        rec_ms = stats["recovery_ms"]
        out(row(f"fleet/{variant}/burst={burst}", wall / burst * 1e6,
                f"{rps:.0f} req/s p50={stats['latency_p50_ms']:.1f}ms "
                f"p99={stats['latency_p99_ms']:.1f}ms "
                f"{stats['bytes_on_wire_per_req']:.0f} B/req "
                f"{stats['coalesce_calls_per_req']:.2f} calls/req "
                f"pool={stats['pool_hit_rate']:.2f}"
                + (f" recovery={rec_ms:.0f}ms" if rec_ms is not None
                   else "")))
        _record(records, name=f"fleet/{variant}/burst={burst}",
                backend="fleet", sampler="mixed", dtype="mixed",
                variant=variant, num_streams=tenants, num_steps=burst,
                us_per_call=wall / burst * 1e6,
                compile_us=warm_s * 1e6,
                requests_per_s=rps,
                latency_p50_ms=stats["latency_p50_ms"],
                latency_p99_ms=stats["latency_p99_ms"],
                retries=stats["retries"], failovers=stats["failovers"],
                recovery_ms=rec_ms,
                bytes_on_wire_per_req=stats["bytes_on_wire_per_req"],
                coalesce_calls_per_req=stats["coalesce_calls_per_req"],
                pool_hit_rate=stats["pool_hit_rate"])
        return digest, rps

    def wire_pair():
        """Transport-isolated array-heavy pair: framed round-trips of
        1 MiB-array replies over a socketpair, v2 vs v1.  This is the
        layer the binary format accelerates (no serving cost mixed
        in) — the CI ``fleet-perf`` gate asserts v2 >= 2x v1 here."""
        import socket as _socket
        import threading as _threading

        arr = (np.arange(512 * 512, dtype=np.uint32)
               .astype(np.float32).reshape(512, 512))
        frames = 32
        for variant, ver in (("wire-binary", transport.WIRE_V2),
                             ("wire-json", transport.WIRE_V1)):
            a, b = _socket.socketpair()
            a.settimeout(60.0); b.settimeout(60.0)
            got = []

            def pump():
                for _ in range(frames):
                    msg, _v = transport.recv_wire(b)
                    got.append(transport.reply_array(msg))

            t = _threading.Thread(target=pump, daemon=True)
            t.start()
            t0 = _time.perf_counter()
            sent = 0
            for i in range(frames):
                sent += transport.send_wire(
                    a, {"ok": True, "rid": f"w/{i}", "array": arr},
                    version=ver)
            t.join(timeout=120)
            wall = _time.perf_counter() - t0
            a.close(); b.close()
            assert len(got) == frames
            assert got[0].tobytes() == arr.tobytes()
            rps = frames / wall
            out(row(f"fleet/{variant}/frames={frames}",
                    wall / frames * 1e6,
                    f"{rps:.0f} frames/s "
                    f"{sent / frames / 1e6:.2f} MB/frame "
                    f"{sent / wall / 1e9:.2f} GB/s"))
            _record(records, name=f"fleet/{variant}/frames={frames}",
                    backend="fleet", sampler="bits", dtype="float32",
                    variant=variant, num_streams=1, num_steps=frames,
                    us_per_call=wall / frames * 1e6,
                    requests_per_s=rps,
                    bytes_on_wire_per_req=sent / frames,
                    gbytes_per_s=sent / wall / 1e9)

    wire_pair()
    # end-to-end pair: identical array-heavy traffic, wire v2 vs v1 —
    # asserts payload transparency (serving cost dominates this scale,
    # so the e2e ratio is informational; the gate reads the wire pair)
    bin_digest, bin_rps = one("binary", "mixed", FaultPlan(),
                              binary=True, max_side=128)
    json_digest, json_rps = one("json", "mixed", FaultPlan(),
                                binary=False, max_side=128)
    assert bin_digest == json_digest, (
        "binary v2 responses diverged from JSON v1 — wire framing is "
        "NOT payload-transparent")
    out(f"# fleet: binary/json steady-state speedup "
        f"{bin_rps / json_rps:.2f}x e2e (digests equal)")
    one("hammer", "hammer", FaultPlan())
    one("unique", "unique", FaultPlan())
    # kill pair runs cold (no warm-up: warm rids would fire the injector)
    baseline, _ = one("nofault", "mixed", FaultPlan(), warm=False)
    killed, _ = one("kill", "mixed",
                    FaultPlan.parse(f"kill@{burst // 2}"), warm=False)
    assert killed == baseline, (
        "kill-mid-burst digest diverged from the no-fault run — "
        "failover is NOT bit-identical")
    out("# fleet: kill-mid-burst digest == no-fault digest "
        "(bit-identical, pools+coalescing+pipelining on)")


def inference_smoke(out=print, records=None, *, batch: int = 64,
                    vocab: int = 512, sequences: int = 96,
                    rate: float = 8.0, max_steps: int = 400) -> None:
    """Continuous-batching serving rows: tokens/s, slot occupancy,
    p50/p99 per-token latency, calls/step — plus a fused-vs-two-pass
    step-kernel microbenchmark (the HBM-noise-block round trip the
    fused gumbel-max kernel deletes).

    The offline run executes with ``--parity`` semantics (the fused
    run's transcript digest is asserted against an xla two-pass re-run)
    so every benchmark invocation is also a correctness check.
    """
    import time as _time

    from repro.core import u64
    from repro.inference import (GumbelMaxSampler, SamplingSpec,
                                 ScheduleConfig, run_offline)

    cfg = ScheduleConfig(capacity=batch, vocab=vocab, sequences=sequences,
                         rate=rate, seed=29, max_steps=max_steps)
    report = run_offline(cfg, parity=True)      # raises on digest mismatch
    j = report.to_json()
    out(row(f"inference/offline/b={batch}", j["p50_ms"] * 1e3,
            f"{j['tokens_per_s']:.0f} tok/s occ={j['occupancy']:.2f} "
            f"p99={j['p99_ms']:.1f}ms "
            f"{j['calls_per_step']:.2f} calls/step (parity ok)"))
    _record(records, name=f"inference/offline/b={batch}",
            backend="inference", sampler="gumbel", dtype="float32",
            variant="continuous-batching", num_streams=batch,
            num_steps=j["decode_steps"], us_per_call=j["p50_ms"] * 1e3,
            gsamples_per_s=j["tokens_per_s"] / 1e9,
            tokens_per_s=j["tokens_per_s"], occupancy=j["occupancy"],
            latency_p50_ms=j["p50_ms"], latency_p99_ms=j["p99_ms"],
            calls_per_step=j["calls_per_step"],
            parity_checked=j["parity_digest"] is not None)

    # step-kernel micro, three variants of the same step (tokens equal):
    #   twopass — noise block materialized by one jitted call, reduced by
    #             a second (crosses the jit boundary = HBM round trip);
    #   onepass — the xla path, noise + reduce in ONE executable;
    #   fused   — the Pallas kernel, bits -> token ids in-kernel (runs
    #             interpreted off-TPU, so its CPU timing is informational).
    s = GumbelMaxSampler.standalone(seed=29, vocab=vocab, capacity=batch,
                                    spec=SamplingSpec(temperature=0.8))
    from repro.inference.kernels import twopass_argmax
    purpose = s.service.channel(s.channel).purpose
    x0, h_fam = engine.family_from_seed(s.service.seed, purpose)
    inv_temp = s.spec.inv_temp

    @jax.jit
    def _noise(tag_hi, tag_lo, c_hi, c_lo):
        h = engine.derive_leaf(
            (jnp.broadcast_to(jnp.asarray(h_fam[0]), tag_hi.shape),
             jnp.broadcast_to(jnp.asarray(h_fam[1]), tag_lo.shape)),
            (tag_hi, tag_lo))
        plan = engine.GenPlan(x0=x0, h=h, num_steps=vocab,
                              ctr=(c_hi, c_lo), offset=None, mode="ctr",
                              deco=s.deco, sampler="gumbel",
                              out_dtype="float32")
        return engine.generate(plan, backend="xla",
                               block_t=s.service.block_t,
                               block_s=s.service.block_s)

    @jax.jit
    def _reduce(lg, noise):
        lt = lg.astype(jnp.float32).T
        thresh = jnp.full((batch,), -jnp.inf, jnp.float32)
        return twopass_argmax(lt, noise, thresh, inv_temp=inv_temp)

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(batch, vocab)).astype(np.float32))
    tags = jnp.arange(batch, dtype=jnp.uint32)
    c = tuple(map(jnp.asarray, u64.const64(0)))
    args = (logits, jnp.zeros_like(tags), tags, c[0], c[1])

    def two_pass():
        return _reduce(logits, _noise(*args[1:]))

    got = {"fused": np.asarray(s.jitted("fused")(*args)),
           "onepass": np.asarray(s.jitted("xla")(*args)),
           "twopass": np.asarray(two_pass())}
    assert np.array_equal(got["fused"], got["onepass"]) and \
        np.array_equal(got["onepass"], got["twopass"]), \
        "step-micro token mismatch across fused/onepass/twopass"
    t_fused = time_fn_stats(lambda: s.jitted("fused")(*args), iters=30)
    t_one = time_fn_stats(lambda: s.jitted("xla")(*args), iters=30)
    t_two = time_fn_stats(two_pass, iters=30)
    sp = {"fused": t_two["us_per_call"] / t_fused["us_per_call"],
          "onepass": t_two["us_per_call"] / t_one["us_per_call"],
          "twopass": 1.0}
    best = max(sp["fused"], sp["onepass"])
    tok = batch / (t_one["us_per_call"] * 1e-6)
    out(row(f"inference/step/b={batch}", t_one["us_per_call"],
            f"onepass {tok / 1e6:.2f} Mtok/s, {sp['onepass']:.2f}x vs "
            f"two-pass (pallas {sp['fused']:.2f}x"
            f"{', interpreted' if engine.use_interpret() else ''}; "
            f"parity-asserted)"))
    for variant, t in (("fused", t_fused), ("onepass", t_one),
                       ("twopass", t_two)):
        _record(records, name=f"inference/step/b={batch}",
                backend="inference", sampler="gumbel", dtype="float32",
                variant=variant, num_streams=batch, num_steps=vocab,
                us_per_call=t["us_per_call"], compile_us=t["compile_us"],
                gsamples_per_s=batch / (t["us_per_call"] * 1e-6) / 1e9,
                fused_speedup=sp[variant], best_fused_speedup=best,
                interpreted=bool(engine.use_interpret())
                            and variant == "fused")


SMOKES = {
    "smoke": smoke,
    "sampler": sampler_smoke,
    "dist": dist_smoke,
    "pipelined": pipelined_smoke,
    "service": service_smoke,
    "fleet": fleet_smoke,
    "inference": inference_smoke,
}


if __name__ == "__main__":
    import sys

    from repro import compile_cache

    compile_cache.enable()
    only = sys.argv[1:]
    unknown = set(only) - set(SMOKES)
    if unknown:
        raise SystemExit(f"unknown smoke(s) {sorted(unknown)}; "
                         f"have {sorted(SMOKES)}")
    records = []
    for name, fn in SMOKES.items():
        if only and name not in only:
            continue
        fn(records=records)
    write_bench_json(records, merge=bool(only))
    print(f"# wrote {BENCH_JSON} ({len(records)} rows"
          f"{' merged' if only else ''})")
