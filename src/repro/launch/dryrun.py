import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-touching import: jax locks the device count at
# first init.  512 host devices back both the 16x16 single-pod mesh and
# the 2x16x16 multi-pod mesh.  Only this entry point does this — tests,
# benchmarks and examples see the real single CPU device.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, get_config, input_specs,
                           shape_skipped)  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh, rng_axes  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.models.common import flatten  # noqa: E402
from repro.optim import adamw_init  # noqa: E402

from repro.launch.analysis import (  # noqa: E402
    HBM_BW, ICI_BW, PEAK_FLOPS, _DTYPE_BYTES, _shape_bytes, collective_bytes)


def _mem_report(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    rep = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            rep[attr] = int(v)
    rep["total_bytes_per_device"] = (
        rep.get("argument_size_in_bytes", 0)
        + rep.get("output_size_in_bytes", 0)
        + rep.get("temp_size_in_bytes", 0)
        - rep.get("alias_size_in_bytes", 0))
    return rep


def _cost_report(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "transcendentals") or "bytes" in k)}


def count_params(shapes_tree) -> int:
    total = 0
    for x in jax.tree.leaves(shapes_tree):
        n = 1
        for d in x.shape:
            n *= int(d)
        total += n
    return total


def active_params(cfg, params_shapes) -> int:
    """MoE-aware active parameter count for MODEL_FLOPS = 6*N_active*D."""
    total = 0
    for path, leaf in flatten(params_shapes).items():
        n = 1
        for d in leaf.shape:
            n *= d
        if "moe_" in path and cfg.n_experts:
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total


def np_prod(t) -> int:
    out = 1
    for v in t:
        out *= int(v)
    return out


def model_flops_from_counts(cfg, n_active: int, shape_name: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N MoE-active."""
    spec = SHAPES[shape_name]
    if spec.kind == "train":
        return 6.0 * n_active * spec.global_batch * spec.seq_len
    if spec.kind == "prefill":
        return 2.0 * n_active * spec.global_batch * spec.seq_len
    return 2.0 * n_active * spec.global_batch  # decode: 1 token/sequence


def _compile_cell(cfg, shape_name: str, mesh,
                  param_dtype: Optional[str] = None) -> Dict[str, Any]:
    """Lower + compile one step for one concrete cfg; return compiled +
    timing + params info."""
    model = registry.build(cfg)
    spec = SHAPES[shape_name]
    holder = {}

    def initf():
        p, s = model.init(0)
        holder["specs"] = s
        return p

    t0 = time.time()
    params_shapes = jax.eval_shape(initf)
    specs = holder["specs"]

    mode = "train" if spec.kind == "train" else "serve"
    if mode == "serve":
        # serving weights are bf16 (training keeps fp32 masters)
        params_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if s.dtype == jnp.float32 else s, params_shapes)
    pshard, _ = steps_mod.param_sharding_tree(model, params_shapes, specs,
                                              mesh, mode)
    batch_specs = input_specs(cfg, shape_name, model)
    bshard = steps_mod.batch_sharding(cfg, batch_specs, mesh)

    with jax.set_mesh(mesh):
        if spec.kind == "train":
            opt_shapes = jax.eval_shape(adamw_init, params_shapes)
            oshard = steps_mod.opt_sharding_like(pshard, mesh)
            # gradient accumulation for the big archs: per-microbatch
            # activations must fit 16 GB/chip alongside FSDP param shards
            n_params = count_params(params_shapes)
            micro = 8 if n_params > 5e10 else (2 if n_params > 2e10 else 1)
            if cfg.scan_unroll:
                micro = 1  # cost-fit compiles measure the whole batch once
            micro = int(os.environ.get("REPRO_MICROBATCHES", micro))
            train_step = steps_mod.make_train_step(model, microbatches=micro,
                                                   param_dtype=param_dtype)
            step_spec = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                train_step,
                in_shardings=(pshard, oshard, bshard,
                              NamedSharding(mesh, P())),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1))  # params/opt update in place
            lowered = jitted.lower(params_shapes, opt_shapes, batch_specs,
                                   step_spec)
        elif spec.kind == "prefill":
            prefill_step, _ = steps_mod.make_serve_fns(model)
            # prefill OUTPUT cache must come out sharded (kv/ctx over
            # model, batch over data) — explicit, not inferred
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(spec.global_batch, spec.seq_len))
            from repro.models import sharding as shd_mod
            cache_pspec = shd_mod.cache_pspecs(cfg, cache_shapes, mesh)
            cache_shard = jax.tree.map(
                lambda s: NamedSharding(mesh, s), cache_pspec,
                is_leaf=lambda x: isinstance(x, P))
            jitted = jax.jit(prefill_step, in_shardings=(pshard, bshard),
                             out_shardings=(None, cache_shard))
            lowered = jitted.lower(params_shapes, batch_specs)
        else:  # decode
            _, decode_step = steps_mod.make_serve_fns(model)
            jitted = jax.jit(
                decode_step,
                in_shardings=(pshard, bshard["cache"], bshard["token"],
                              bshard["pos"]),
                out_shardings=(None, bshard["cache"]),
                donate_argnums=(1,))  # KV cache updates in place
            lowered = jitted.lower(params_shapes, batch_specs["cache"],
                                   batch_specs["token"], batch_specs["pos"])
        lower_s = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = round(time.time() - t1, 2)
    return {"compiled": compiled, "lower_s": lower_s,
            "compile_s": compile_s, "params_shapes": params_shapes}


def _fit_layers(cfg):
    """(L1, L2) reduced depths for the cost-fit compiles."""
    if cfg.family == "hybrid":
        return cfg.attn_every, 2 * cfg.attn_every
    return 1, 2


def _fit_cfg(cfg, L, shape_name: str):
    over = dict(n_layers=L, scan_unroll=True, loss_chunks=1,
                q_chunk=SHAPES[shape_name].seq_len)
    if cfg.family == "encdec":
        over["enc_layers"] = L
    return cfg.scaled(**over)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               fit_costs: bool = True,
               overrides: Optional[Dict[str, Any]] = None,
               param_dtype: Optional[str] = None) -> Dict[str, Any]:
    """Lower + compile one (arch x shape x mesh) cell; return the report.

    Protocol (XLA's HloCostAnalysis counts while-loop bodies ONCE, so the
    scanned full-depth program under-reports flops/bytes/collectives):
      1. FULL-depth scanned compile  -> memory_analysis (peak is real)
         + proof that the production program compiles on this mesh.
      2. Two reduced-depth compiles with layer scans UNROLLED (L1, L2)
         -> per-layer linear fit of flops / bytes / collective bytes,
         extrapolated to the full depth.  Known residual: loops whose
         trip count is layer-independent (the 16-chunk xent scan and the
         SSD inter-chunk scan) stay counted once in the fit compiles too;
         they are made loop-free there (loss_chunks=1, q_chunk=seq), which
         preserves total flops and, to first order, total bytes.
    """
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    skip = shape_skipped(cfg, shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name, "skipped": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np_prod(mesh.devices.shape))
    spec = SHAPES[shape_name]
    report: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "kind": spec.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": n_chips,
    }

    # --- 1. full-depth compile: memory + shardability proof --------------
    full = _compile_cell(cfg, shape_name, mesh, param_dtype=param_dtype)
    report["lower_s"] = full["lower_s"]
    report["compile_s"] = full["compile_s"]
    report["n_params"] = count_params(full["params_shapes"])
    report["n_params_active"] = active_params(cfg, full["params_shapes"])
    report["memory"] = _mem_report(full["compiled"])
    report["cost_raw"] = _cost_report(full["compiled"])
    try:
        hlo = full["compiled"].as_text()
        report["collectives_raw"] = collective_bytes(hlo)
        report["hlo_lines"] = hlo.count("\n")
    except Exception as e:  # pragma: no cover
        report["collectives_raw"] = {"error": str(e)}
    del full

    # --- 2. reduced-depth unrolled compiles: linear layer fit ------------
    if fit_costs:
        L1, L2 = _fit_layers(cfg)
        fit = {}
        for L in (L1, L2):
            c = _compile_cell(_fit_cfg(cfg, L, shape_name), shape_name, mesh,
                              param_dtype=param_dtype)
            cost = _cost_report(c["compiled"])
            coll = collective_bytes(c["compiled"].as_text())
            fit[L] = {"flops": cost.get("flops", 0.0),
                      "bytes": cost.get("bytes accessed", 0.0),
                      "coll": float(coll.get("total", 0)),
                      "coll_by_op": coll,
                      "compile_s": c["compile_s"]}
            del c
        Lf = cfg.n_layers

        def extrap(key):
            y1, y2 = fit[L1][key], fit[L2][key]
            return y1 + (y2 - y1) * (Lf - L1) / (L2 - L1)

        flops = extrap("flops")
        bytes_acc = extrap("bytes")
        coll = extrap("coll")
        report["cost_fit"] = {
            "flops": flops, "bytes_accessed": bytes_acc,
            "collective_bytes": coll,
            "fit_points": {str(L): fit[L] for L in (L1, L2)},
        }
    else:
        flops = report["cost_raw"].get("flops", 0.0)
        bytes_acc = report["cost_raw"].get("bytes accessed", 0.0)
        coll = report["collectives_raw"].get("total", 0)

    # --- roofline terms (per-device program values) -----------------------
    mf = model_flops_from_counts(cfg, report["n_params_active"], shape_name)
    # NOTE: cost_analysis/HLO values are PER-DEVICE (the SPMD program), so
    # each term divides by per-chip peak only.  The spec's
    # "collective_bytes / (chips x link_bw)" assumes GLOBAL collective
    # bytes; ours are per-device, so the chips factor cancels.
    report["roofline"] = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll / ICI_BW,
        "model_flops_total": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else 0.0,
    }
    terms = {k: report["roofline"][k]
             for k in ("compute_s", "memory_s", "collective_s")}
    report["roofline"]["bottleneck"] = max(terms, key=terms.get)
    return report


def rng_fanout_cell(*, multi_pod: bool = False, num_streams: int = 2 ** 14,
                    num_steps: int = 256) -> Dict[str, Any]:
    """Lower + compile the RNG block fan-out on the production mesh.

    The 2-D/3-D ``(host, stream)`` layout of ``engine.generate_sharded``
    over ALL mesh axes: proves the (T, S) block shards over the full
    production device grid with ZERO collectives (counter addressing —
    the paper's "no extra root hardware per instance" claim, verified on
    the compiled HLO) and reports the per-device memory footprint.
    """
    from repro.core import engine

    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = rng_axes(mesh)
    n_chips = int(np_prod(mesh.devices.shape))
    report: Dict[str, Any] = {
        "kind": "rng_fanout",
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "axes": list(axes), "chips": n_chips,
        "num_streams": num_streams, "num_steps": num_steps,
    }
    for sampler, out_dtype in (("bits", "float32"), ("uniform", "bfloat16")):
        plan = engine.make_plan(seed=7, num_streams=num_streams,
                                num_steps=num_steps, sampler=sampler,
                                out_dtype=out_dtype)
        t0 = time.time()
        lowered = jax.jit(lambda: engine.generate_sharded(
            plan, mesh=mesh, axis_names=axes)).lower()
        compiled = lowered.compile()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        report[sampler] = {
            "compile_s": round(time.time() - t0, 2),
            "collective_bytes": coll,
            "memory": _mem_report(compiled),
            "hlo_lines": hlo.count("\n"),
        }
    return report


def service_cell(*, burst: int = 192, tenants: int = 96,
                 seed: int = 11) -> Dict[str, Any]:
    """In-process RandService burst on the forced host platform.

    The serving analogue of ``rng_fanout_cell``: fires a deterministic
    mixed (shape, sampler, dtype) burst through the coalescing frontend
    + standing pool, then asserts the acceptance properties — zero
    counter-window overlap (ledger-verified on both the live service
    and the journal) and bit-identical journal replay — and reports
    requests/s, p50/p99 latency and the coalescing factor.
    """
    from repro.service import (Journal, RandServer, ServerConfig, replay,
                               verify_ledger_disjoint)
    from repro.service.audit import response_digest
    from repro.service.burst import make_requests, run_burst

    journal = Journal()
    server = RandServer(seed, config=ServerConfig(
        max_batch=64, max_delay_s=0.25,
        hot_classes=(("uniform", "float32"),)), journal=journal)
    t0 = time.time()
    responses = run_burst(server, make_requests(
        burst=burst, tenants=tenants, seed=seed))
    wall_s = time.time() - t0
    stats = server.stats()
    windows = verify_ledger_disjoint(server.block_service)
    verify_ledger_disjoint(journal)
    digest = response_digest(responses)
    replay_ok = response_digest(replay(journal, seed=seed)) == digest
    server.shutdown()
    return {
        "kind": "service", "burst": burst, "tenants": tenants,
        "seed": seed, "wall_s": round(wall_s, 3), "digest": digest,
        "replay_ok": replay_ok, "ledger_windows": windows,
        "stats": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in stats.items()},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (hillclimb variants)")
    ap.add_argument("--param-dtype", default=None, choices=[None, "bf16"])
    ap.add_argument("--tag", default="",
                    help="suffix for output json names")
    ap.add_argument("--rng-fanout", action="store_true",
                    help="compile the RNG (host, stream) block fan-out on "
                         "the production mesh(es) and report collective "
                         "bytes (expected 0) + memory")
    ap.add_argument("--service", action="store_true",
                    help="run an in-process RandService mixed burst and "
                         "report requests/s, latency, coalescing factor, "
                         "ledger disjointness and replay bit-identity")
    args = ap.parse_args()

    if args.service:
        os.makedirs(args.out, exist_ok=True)
        rep = service_cell()
        with open(os.path.join(args.out, "service.json"), "w") as f:
            json.dump(rep, f, indent=2)
        s = rep["stats"]
        status = "OK" if rep["replay_ok"] else "FAIL"
        print(f"[{status}] service burst={rep['burst']} "
              f"tenants={s['tenants']} req/s={s['requests_per_s']:.0f} "
              f"p50={s['latency_p50_ms']:.1f}ms "
              f"p99={s['latency_p99_ms']:.1f}ms "
              f"calls/req={s['calls_per_request']:.3f} "
              f"replay={'bit-identical' if rep['replay_ok'] else 'MISMATCH'}",
              flush=True)
        if not rep["replay_ok"]:
            raise SystemExit("service replay mismatch")
        return

    if args.rng_fanout:
        os.makedirs(args.out, exist_ok=True)
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            rep = rng_fanout_cell(multi_pod=mp)
            tag = f"rng_fanout__{'multipod' if mp else 'pod'}"
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rep, f, indent=2)
            coll = {s: rep[s]["collective_bytes"]["total"]
                    for s in ("bits", "uniform")}
            print(f"[OK] {tag} mesh={rep['mesh']} chips={rep['chips']} "
                  f"collective_bytes={coll}", flush=True)
        return

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}"
        if args.tag:
            tag += "__" + args.tag
        try:
            # roofline fit only on the single-pod mesh; the multi-pod pass
            # proves the "pod" axis shards (memory + compile success)
            rep = lower_cell(arch, shape, multi_pod=mp, fit_costs=not mp,
                             overrides=overrides or None,
                             param_dtype=args.param_dtype)
        except Exception as e:
            rep = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "error": f"{type(e).__name__}: {e}"}
            failures += 1
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rep, f, indent=2)
        status = ("SKIP" if rep.get("skipped") else
                  "FAIL" if rep.get("error") else "OK")
        extra = ""
        if status == "OK":
            r = rep["roofline"]
            extra = (f" mem/dev={rep['memory'].get('total_bytes_per_device', 0)/2**30:.2f}GiB"
                     f" compute={r['compute_s']*1e3:.2f}ms"
                     f" memory={r['memory_s']*1e3:.2f}ms"
                     f" coll={r['collective_s']*1e3:.2f}ms"
                     f" bottleneck={r['bottleneck']}"
                     f" compile={rep['compile_s']}s")
        elif status == "FAIL":
            extra = " " + rep["error"][:200]
        print(f"[{status}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
