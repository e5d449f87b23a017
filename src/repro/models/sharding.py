"""Logical-axis -> mesh PartitionSpec resolution (DP/FSDP/TP/EP/SP).

Per-param assignment (not a single global map) so indivisible dims fall
back gracefully per-tensor:

  TP ("model" axis): first divisible axis in priority order
      experts > kv_heads > q_rep > f > ssm_inner > ssm_heads > vocab
      > embed (>=2-D params only — the row-parallel fallback for archs
      like qwen1.5-32b whose 40 heads don't divide a 16-way model axis).
  FSDP (train only; "data" [+ "pod"] axes): first remaining divisible
      axis in order embed > vocab > f > ssm_inner > head — ZeRO-3-style
      parameter + optimizer-state sharding.

Serve mode skips FSDP (weights TP-only, batch over data) and shards KV
caches: kv_heads over model when divisible, else the *context* axis over
model (flash-decoding); batch over data when divisible, else context over
data too (the long_500k single-sequence case).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ArchConfig

TP_PRIORITY = ("experts", "kv_heads", "q_rep", "f", "ssm_inner",
               "ssm_heads", "vocab")
TP_FALLBACK = ("embed",)
FSDP_PRIORITY = ("embed", "vocab", "f", "ssm_inner", "head")


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """Axis name -> size, for a concrete ``Mesh`` or an ``AbstractMesh``."""
    return dict(mesh.shape)


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return fsdp_axes(mesh)


def _axsize(mesh: Mesh, axes) -> int:
    sizes = mesh_axis_sizes(mesh)
    if isinstance(axes, str):
        return sizes[axes]
    return int(np.prod([sizes[a] for a in axes])) if axes else 1


def param_pspec(axes: Tuple[str, ...], shape: Tuple[int, ...], mesh: Mesh,
                mode: str = "train") -> P:
    """PartitionSpec for one param given its logical axes + shape."""
    model_sz = _axsize(mesh, "model")
    assign: list = [None] * len(axes)

    def try_assign(names, mesh_axis, mesh_sz, skip_1d=False):
        for name in names:
            if name in axes:
                i = axes.index(name)
                if assign[i] is None and shape[i] % mesh_sz == 0 \
                        and shape[i] > 0:
                    if skip_1d and sum(s > 1 for s in shape) < 2:
                        continue
                    assign[i] = mesh_axis
                    return True
        return False

    ok = try_assign(TP_PRIORITY, "model", model_sz)
    if not ok:
        # (Measured, kept: removing the row-parallel fallback from
        # unshardable-head attention params cut collectives only 2% while
        # adding 7 GiB of full-head k/v transients — refuted hypothesis,
        # see EXPERIMENTS.md §Perf/H2.)
        try_assign(TP_FALLBACK, "model", model_sz, skip_1d=True)
    # Embedding/unembedding tables stay TP-only: FSDP-sharding their
    # d_model axis makes the gather/scatter backward reshard the (B,S,D)
    # cotangent to a batch-replicated fp32 layout (multi-GiB per buffer).
    if mode == "train" and "vocab" not in axes:
        fa = fsdp_axes(mesh)
        if fa:
            fsz = _axsize(mesh, fa)
            remaining = [n for n in FSDP_PRIORITY
                         if n in axes and assign[axes.index(n)] is None]
            try_assign(remaining, fa if len(fa) > 1 else fa[0], fsz)
    return P(*assign)


def param_pspecs(specs: Dict[str, Tuple[str, ...]], params_flat,
                 mesh: Mesh, mode: str = "train") -> Dict[str, P]:
    out = {}
    for path, axes in specs.items():
        out[path] = param_pspec(axes, tuple(params_flat[path].shape), mesh,
                                mode)
    return out


def batch_pspec(mesh: Mesh, batch_size: int) -> P:
    da = data_axes(mesh)
    if da and batch_size % _axsize(mesh, da) == 0:
        return P(da if len(da) > 1 else da[0])
    return P(None)


def _cache_kv_pspec(mesh: Mesh, shape, kv_idx: int, ctx_idx: int,
                    batch_idx: int = 1) -> P:
    """(L/napps, B, T, K, hd) attention-cache spec."""
    sizes = mesh_axis_sizes(mesh)
    assign: list = [None] * len(shape)
    da = data_axes(mesh)
    dsz = _axsize(mesh, da) if da else 1
    if shape[kv_idx] % sizes["model"] == 0:
        assign[kv_idx] = "model"
    elif shape[ctx_idx] % sizes["model"] == 0:
        assign[ctx_idx] = "model"
    if da:
        if shape[batch_idx] % dsz == 0:
            assign[batch_idx] = da if len(da) > 1 else da[0]
        elif assign[ctx_idx] is None and shape[ctx_idx] % dsz == 0:
            assign[ctx_idx] = da if len(da) > 1 else da[0]
        elif assign[ctx_idx] == "model" and \
                shape[ctx_idx] % (dsz * sizes["model"]) == 0:
            assign[ctx_idx] = (*da, "model")
    return P(*assign)


def cache_pspecs(cfg: ArchConfig, cache, mesh: Mesh):
    """PartitionSpecs matching Model.init_cache's pytree structure."""
    sizes = mesh_axis_sizes(mesh)
    da = data_axes(mesh)
    dsz = _axsize(mesh, da) if da else 1

    def b_axis(b):
        if da and b % dsz == 0:
            return da if len(da) > 1 else da[0]
        return None

    def feat_axis(n):
        return "model" if n % sizes["model"] == 0 else None

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        k, v = cache
        spec = _cache_kv_pspec(mesh, k.shape, kv_idx=3, ctx_idx=2)
        return (spec, spec)
    if fam == "encdec":
        sk, sv, ck, cv = cache
        s_spec = _cache_kv_pspec(mesh, sk.shape, kv_idx=3, ctx_idx=2)
        c_spec = _cache_kv_pspec(mesh, ck.shape, kv_idx=3, ctx_idx=2)
        return (s_spec, s_spec, c_spec, c_spec)
    if fam == "ssm":
        st, tx, tb, tc = cache
        return (P(None, b_axis(st.shape[1]), feat_axis(st.shape[2]), None, None),
                P(None, b_axis(tx.shape[1]), None, feat_axis(tx.shape[3])),
                P(None, b_axis(tb.shape[1]), None, None),
                P(None, b_axis(tc.shape[1]), None, None))
    if fam == "hybrid":
        kc, vc, st, tx, tb, tc = cache
        kv_spec = _cache_kv_pspec(mesh, kc.shape, kv_idx=3, ctx_idx=2)
        return (kv_spec, kv_spec,
                P(None, b_axis(st.shape[1]), feat_axis(st.shape[2]), None, None),
                P(None, b_axis(tx.shape[1]), None, feat_axis(tx.shape[3])),
                P(None, b_axis(tb.shape[1]), None, None),
                P(None, b_axis(tc.shape[1]), None, None))
    raise ValueError(fam)


def tree_shardings(mesh: Mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def context_parallel_attention(mesh_or_none, n_kv: int, n_rep: int) -> bool:
    """True when neither kv heads nor query repeats divide the model axis
    (e.g. qwen1.5-32b's 40 MHA heads on a 16-way axis): attention then runs
    context-parallel — q stays sequence-sharded, k/v are gathered."""
    m = mesh_or_none or ambient_mesh()
    if m is None or "model" not in m.axis_names:
        return False
    ms = mesh_axis_sizes(m)["model"]
    return (n_kv % ms != 0) and (n_rep % ms != 0)


def ambient_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The mesh installed by ``with jax.set_mesh(mesh):``, or None outside
    any mesh context (e.g. single-device tests).  Abstract, so it can be
    read while tracing."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def prefer_seq_gather(cfg, batch: int, seq: int) -> bool:
    """Resolve the SP-carry-vs-TP-weight einsum conflict by napkin math:
    inside a layer, EITHER the (B,S,D) activation's sequence axis or the
    (D,F)/head weight's model axis must be gathered.  Gather whichever is
    smaller: activations win for big-F archs once microbatching shrinks
    B_local (qwen2-vl-72b, granite-34b); weights win for glm4-class."""
    m = ambient_mesh()
    if m is None:
        return False
    sizes = mesh_axis_sizes(m)
    if "model" not in sizes or seq <= 1 or seq % sizes["model"]:
        return False
    da = data_axes(m)
    dsz = _axsize(m, da) if da else 1
    b_loc = batch // dsz if (da and batch % dsz == 0) else batch
    act_bytes = b_loc * seq * cfg.d_model * 2 * 2   # bf16, gather+scatter
    n_mats = 3 if cfg.act in ("silu", "geglu") else 2
    w_bytes = cfg.d_model * cfg.d_ff * 4 * n_mats
    # 2x margin: XLA's default (weight-gather) also keeps remat cheaper,
    # so only force activation-gather on a clear win (measured: granite-34b
    # regresses at ~1.3x, qwen2-vl-72b wins at ~10x)
    return act_bytes * 2 < w_bytes


def gather_seq_hint(x):
    """Constraint (batch over data, seq REPLICATED): applied at the input
    of head-/f-sharded einsums so XLA gathers the SP'd sequence instead of
    'involuntarily' replicating the much larger head/f dimension."""
    m = ambient_mesh()
    if m is None:
        return x
    da = data_axes(m)
    spec: list = [None] * x.ndim
    if da and x.shape[0] % _axsize(m, da) == 0:
        spec[0] = da if len(da) > 1 else da[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, P(*spec)))


def activation_hint(x, *, seq_axis: Optional[int] = 1):
    """Sequence-parallel sharding constraint for a (B, S, ...) activation.

    Applied to the scan-over-layers carry: the *saved* per-layer tensor is
    (batch over data axes) x (seq over model axis); the full-sequence /
    full-head tensors inside a layer are transient and rematerialized.
    This is what lets 72B-class train_4k activations fit 16 GB/chip.
    No-op outside a mesh context or when dims don't divide.
    """
    m = ambient_mesh()
    if m is None:
        return x
    sizes = mesh_axis_sizes(m)
    da = data_axes(m)
    spec: list = [None] * x.ndim
    if da and x.shape[0] % _axsize(m, da) == 0:
        spec[0] = da if len(da) > 1 else da[0]
    if (seq_axis is not None and "model" in sizes and x.ndim > seq_axis
            and x.shape[seq_axis] % sizes["model"] == 0
            and x.shape[seq_axis] > 1):
        spec[seq_axis] = "model"
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, P(*spec)))
