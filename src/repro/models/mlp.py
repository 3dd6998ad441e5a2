"""Dense MLP and GShard-style Mixture-of-Experts with capacity routing.

MoE uses scatter dispatch / gather combine (token-dropping, capacity factor)
so compiled FLOPs scale with ACTIVE parameters (top-k), which the roofline
check compares against 6·N_active·D.  Experts are sharded on the "model"
mesh axis = expert parallelism (the survey's model-parallelism specialized
to MoE).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.sharding import shard
from repro.models.common import ParamDesc, dense
from repro.models.config import ModelConfig


def mlp_descs(cfg: ModelConfig, d_ff: Optional[int] = None,
              dtype: Optional[str] = None) -> Dict[str, ParamDesc]:
    dt = dtype or cfg.param_dtype
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    descs = {
        "w1": ParamDesc((d, ff), (None, "model"), dt, fan_in=d),
        "w2": ParamDesc((ff, d), ("model", None), dt, fan_in=ff),
    }
    if cfg.activation == "swiglu":
        descs["w3"] = ParamDesc((d, ff), (None, "model"), dt, fan_in=d)
    return descs


@jax.named_scope("mlp")
def mlp(p, x, cfg: ModelConfig):
    if cfg.activation == "swiglu":
        h = jax.nn.silu(dense(x, p["w1"])) * dense(x, p["w3"])
    elif cfg.activation == "squared_relu":
        h = jnp.square(jax.nn.relu(dense(x, p["w1"])))
    else:
        h = jax.nn.gelu(dense(x, p["w1"]))
    h = shard(h, "batch", None, "model")
    return dense(h, p["w2"])


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------
def moe_descs(cfg: ModelConfig, dtype: Optional[str] = None) -> Dict[str, ParamDesc]:
    dt = dtype or cfg.param_dtype
    d, E, ffe = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    descs = {
        "router": ParamDesc((d, E), (None, None), "float32", fan_in=d),
        "w1": ParamDesc((E, d, ffe), ("model", None, None), dt, fan_in=d),
        "w2": ParamDesc((E, ffe, d), ("model", None, None), dt, fan_in=ffe),
    }
    if cfg.activation == "swiglu":
        descs["w3"] = ParamDesc((E, d, ffe), ("model", None, None), dt, fan_in=d)
    if cfg.moe_dense_residual:
        descs["dense"] = mlp_descs(cfg, cfg.dense_residual_d_ff, dt)
    return descs


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    cap = int(cfg.capacity_factor * num_tokens * cfg.top_k / cfg.num_experts)
    return max(cap, cfg.top_k)


@jax.named_scope("mlp")
def moe(p, x, cfg: ModelConfig, groups: Optional[int] = None):
    """x: (B,S,d) -> (y, aux_loss).  GShard-style GROUP-WISE routing.

    Tokens are routed within independent groups (default: one group per
    sequence).  The group dim shards over the data axis, so the dispatch
    bookkeeping (one-hot, prefix-sum position-in-expert, scatter/gather)
    is data-parallel — with a single global group the prefix sum is an
    unsharded (B·S·k, E) op that every chip replicates (measured 60x
    compute bloat on qwen3-moe train_4k; EXPERIMENTS.md §Perf iteration 1).
    Capacity is per-group: C_g = cf·n·k/E, same total slots as global
    routing.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    N = B * S
    if groups is None:
        groups = cfg.moe_groups or None
    G = groups if groups is not None else (B if S > 1 else 1)
    n = N // G
    assert N % G == 0, (N, G)
    C = moe_capacity(cfg, n)
    xg = x.reshape(G, n, d)

    logits = jnp.einsum("gnd,de->gne", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # (G,n,k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss (global statistics).
    density = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                       axis=(0, 1))
    aux = E * jnp.sum(density * jnp.mean(probs, axis=(0, 1)))

    # position of each (token, choice) within its expert, PER GROUP —
    # sort-based: a stable argsort groups the choices by expert while
    # preserving token order, so position = rank − segment start.  The
    # one-hot+prefix-sum formulation builds (G, n·k, E) intermediates
    # whose scatter/gather lowering dominated the collective term
    # (EXPERIMENTS.md §Perf, MoE iteration 4); everything here is (G, n·k).
    eidx = idx.reshape(G, n * k)
    order = jnp.argsort(eidx, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(eidx, order, axis=1)
    iota = jnp.broadcast_to(jnp.arange(n * k)[None], (G, n * k))
    is_start = jnp.concatenate(
        [jnp.ones((G, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]],
        axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_start, iota, 0), axis=1)
    pos_sorted = iota - seg_start
    gids = jnp.arange(G)[:, None]
    pos = jnp.zeros_like(eidx).at[gids, order].set(pos_sorted)
    keep = pos < C
    # dropped tokens go to a trash row E*C
    rows = jnp.where(keep, eidx * C + pos, E * C)

    # invert the routing into a slot->source table (int32, E-C-sized) so
    # BOTH dispatch and combine are take_along_axis (= gather with a
    # batching dim) instead of two-index scatter/gather: GSPMD cannot
    # partition the batch dim of a general scatter and was all-gathering
    # the full (G, n·k, d) operands every layer (EXPERIMENTS.md §Perf,
    # MoE iteration 4).  The int32 inversion scatter is 512x smaller
    # than the activations it replaces.
    slot_rows = jnp.where(keep, eidx * C + pos, E * C)
    slot_to_src = jnp.full((G, E * C + 1), n * k, jnp.int32)
    slot_to_src = slot_to_src.at[gids, slot_rows].set(
        jnp.broadcast_to(jnp.arange(n * k)[None], (G, n * k)))

    xrep = jnp.repeat(xg, k, axis=1)  # (G, n*k, d)
    xrep = jnp.concatenate(
        [xrep, jnp.zeros((G, 1, d), x.dtype)], axis=1)  # trash source row
    xrep = shard(xrep, "batch", None, "model")
    eb = jnp.take_along_axis(
        xrep, slot_to_src[:, : E * C, None], axis=1)   # batched gather
    eb = eb.reshape(G, E, C, d)
    eb = shard(eb, "batch", "model", None, None)  # <- all-to-all (d -> E)

    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", eb, p["w1"])) * \
            jnp.einsum("gecd,edf->gecf", eb, p["w3"])
    else:
        h = jnp.square(jax.nn.relu(
            jnp.einsum("gecd,edf->gecf", eb, p["w1"])))
    h = shard(h, "batch", "model", None, None)
    out = jnp.einsum("gecf,efd->gecd", h, p["w2"])
    out = shard(out, "batch", "model", None, None)

    # all-to-all back (E -> d) before the combine gather, same reasoning
    flat = jnp.concatenate(
        [out.reshape(G, E * C, d), jnp.zeros((G, 1, d), out.dtype)], axis=1)
    flat = shard(flat, "batch", None, "model")
    gathered = jnp.take_along_axis(flat, rows[:, :, None], axis=1)
    gathered = gathered.reshape(G, n, k, d)
    gathered = shard(gathered, "batch", None, None, "model")
    y = jnp.sum(gathered * gate[..., None].astype(out.dtype), axis=2)
    y = y.reshape(B, S, d)
    if cfg.moe_dense_residual:
        y = y + mlp(p["dense"], x, cfg)
    return shard(y, "batch", "seq", None), aux
