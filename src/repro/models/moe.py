"""Mixture-of-Experts MLP: GShard/Switch-style dense dispatch with capacity.

Tokens are grouped (group dim shards over the data axes), routed top-k with
optional ThundeRiNG jitter, and dispatched to (E, C) expert slots via
one-hot einsums — collective-light and fully SPMD-partitionable; experts
shard over the "model" mesh axis (EP) when E divides it, otherwise the
expert FFN dim shards (TP inside each expert).

Aux losses: load-balance (Switch) + router z-loss, returned per layer.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sampler as sampler_mod, stream as tstream
from repro.models import layers as L
from repro.models.common import ArchConfig


def _group_size(n: int, want: int = 2048, min_groups: int = 32) -> int:
    """Largest divisor of n that is <= want and (if possible) keeps
    n/gs >= min_groups so the group dim stays shardable over data axes."""
    best = 1
    for gs in range(1, min(want, n) + 1):
        if n % gs == 0:
            if n // gs >= min_groups:
                best = gs
            elif best == 1:
                best = gs
    return best


def router_probs(x, router_w, rng: Optional[tstream.ThunderStream],
                 jitter: float = 1e-2):
    """x: (G, gs, D) -> router probabilities (G, gs, E) fp32."""
    if rng is not None and jitter > 0:
        bits = L.dropout_bits((rng.h_hi, rng.h_lo), (rng.ctr_hi, rng.ctr_lo),
                              x.shape)
        u = sampler_mod.uniform_from_bits(bits)
        x = x * (1.0 + jitter * (2.0 * u - 1.0)).astype(x.dtype)
    logits = jnp.einsum("gsd,de->gse", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return jax.nn.softmax(logits, axis=-1), logits


def moe_mlp(cfg: ArchConfig, h: jnp.ndarray, router_w, wg, wi, wo,
            rng: Optional[tstream.ThunderStream]
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h: (B, S, D) -> (B, S, D), aux scalar loss.

    wg/wi: (E, D, F); wo: (E, F, D).
    """
    from repro.models import sharding as shd
    B, S, D = h.shape
    E, k = cfg.n_experts, cfg.top_k
    # gather the SP'd sequence before routing: the (B,S,D) activation is
    # far smaller than the (E,D,F) expert weights XLA would otherwise
    # gather to resolve the S-vs-E model-axis conflict (§Perf/H1)
    h = shd.gather_seq_hint(h)
    N = B * S
    gs = _group_size(N, want=cfg.moe_group)
    G = N // gs
    x = h.reshape(G, gs, D)

    probs, logits = router_probs(x, router_w, rng)
    top_w, top_idx = jax.lax.top_k(probs, k)                  # (G, gs, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

    C = max(1, int(np.ceil(cfg.capacity_factor * k * gs / E)))

    # slot assignment: for each of the k choices in priority order, position
    # within the chosen expert = running count of prior tokens routed there.
    dispatch = jnp.zeros((G, gs, E, C), jnp.bfloat16)
    combine = jnp.zeros((G, gs, E, C), jnp.float32)
    counts = jnp.zeros((G, E), jnp.int32)
    for j in range(k):
        idx_j = top_idx[..., j]                               # (G, gs)
        onehot = jax.nn.one_hot(idx_j, E, dtype=jnp.int32)    # (G, gs, E)
        pos_in_e = jnp.cumsum(onehot, axis=1) - onehot + counts[:, None, :]
        pos_j = jnp.sum(pos_in_e * onehot, axis=-1)           # (G, gs)
        keep = pos_j < C
        slot = jax.nn.one_hot(jnp.where(keep, pos_j, C), C + 1,
                              dtype=jnp.float32)[..., :C]     # (G, gs, C)
        d_j = onehot.astype(jnp.float32)[..., None] * slot[..., None, :]
        dispatch = dispatch + d_j.astype(jnp.bfloat16)
        combine = combine + d_j * top_w[..., j][..., None, None]
        counts = counts + jnp.sum(onehot, axis=1)

    # dispatch tokens -> (G, E, C, D)
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, x)
    # expert FFN (E sharded over model when divisible)
    gate = jnp.einsum("gecd,edf->gecf", xe, wg.astype(xe.dtype))
    up = jnp.einsum("gecd,edf->gecf", xe, wi.astype(xe.dtype))
    act = jax.nn.silu(gate) * up
    ye = jnp.einsum("gecf,efd->gecd", act, wo.astype(xe.dtype))
    # combine back
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(ye.dtype), ye)

    # Switch load-balance loss + router z-loss
    density = jnp.mean(probs, axis=1)                         # (G, E)
    top1 = jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32)
    frac = jnp.mean(top1, axis=1)                             # (G, E)
    lb = E * jnp.mean(jnp.sum(density * frac, axis=-1))
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    aux = lb + 1e-3 * z
    return y.reshape(B, S, D), aux
