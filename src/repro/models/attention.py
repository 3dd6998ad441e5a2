"""Grouped-query attention: batched (train/prefill) and one-token decode.

Projections are stored flattened (d_model, heads*head_dim) so the tensor-
parallel dim (heads*head_dim) is always divisible by the model axis (head_dim
is a multiple of the 128-lane register width on every assigned arch).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sharding import shard
from repro.models.common import ParamDesc, apply_rope, dense, head_rms_norm
from repro.models.config import ModelConfig

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attn_descs(cfg: ModelConfig, dtype: Optional[str] = None) -> Dict[str, ParamDesc]:
    dt = dtype or cfg.param_dtype
    d = cfg.d_model
    descs = {
        "wq": ParamDesc((d, cfg.q_dim), (None, "model"), dt, fan_in=d),
        "wk": ParamDesc((d, cfg.kv_dim), (None, "model"), dt, fan_in=d),
        "wv": ParamDesc((d, cfg.kv_dim), (None, "model"), dt, fan_in=d),
        "wo": ParamDesc((cfg.q_dim, d), ("model", None), dt, fan_in=cfg.q_dim),
    }
    if cfg.qk_norm:
        descs["q_scale"] = ParamDesc((cfg.head_dim,), (None,), dt, init="ones")
        descs["k_scale"] = ParamDesc((cfg.head_dim,), (None,), dt, init="ones")
    return descs


def _project_qkv(p, x, positions, cfg: ModelConfig):
    B, S, _ = x.shape
    q = dense(x, p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = dense(x, p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = dense(x, p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_scale"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_scale"], cfg.norm_eps)
    if cfg.rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k, cfg: ModelConfig):
    """q: (B,S,Hq,dh), k: (B,T,Hk,dh) -> scores (B,Hk,G,S,T) in fp32."""
    B, S, Hq, dh = q.shape
    Hk = k.shape[2]
    G = Hq // Hk
    qg = q.reshape(B, S, Hk, G, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    return scores * (dh ** -0.5)


def _gqa_out(probs, v):
    """probs: (B,Hk,G,S,T) fp32; v: (B,T,Hk,dh) -> (B,S,Hq,dh)."""
    B, Hk, G, S, T = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, Hk * G, -1)


def causal_mask(S: int, T: int, offset: int = 0,
                window: Optional[int] = None) -> jax.Array:
    """(S,T) bool mask; query i (global pos offset+i) attends key j<=pos.

    With `window`, only the last `window` positions are visible
    (sliding-window attention)."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


@jax.named_scope("attention")
def attention(p, x, positions, cfg: ModelConfig, *,
              encoder_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True) -> jax.Array:
    """Batched attention. x: (B,S,d). encoder_kv -> cross-attention."""
    B, S, _ = x.shape
    if encoder_kv is not None:
        q = dense(x, p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = head_rms_norm(q, p["q_scale"], cfg.norm_eps)
        k, v = encoder_kv
        is_causal = False
    else:
        q, k, v = _project_qkv(p, x, positions, cfg)
        is_causal = causal
    window = (cfg.sliding_window
              if cfg.attention_kind == "sliding_window" else None)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    out = gqa_attend(q, k, v, cfg, causal=is_causal, window=window)
    out = shard(out, "batch", None, "model", None)
    y = dense(out.reshape(B, S, -1), p["wo"])
    return shard(y, "batch", "seq", None)


def gqa_attend(q, k, v, cfg: ModelConfig, *, causal: bool = True,
               window: Optional[int] = None) -> jax.Array:
    """Backend dispatch for batched GQA attention: the Pallas flash
    kernel (cfg.use_flash_kernel), the q-chunked lax.map path
    (cfg.attn_q_chunk), or the flat softmax."""
    S = q.shape[1]
    if cfg.use_flash_kernel and S > 1:
        from repro.kernels import ops as K
        return K.flash_attention(q, k, v, causal=causal, window=window)
    if cfg.attn_q_chunk and S > cfg.attn_q_chunk:
        return _gqa_chunked(q, k, v, cfg, causal=causal, window=window)
    scores = _gqa_scores(q, k, cfg)
    if causal:
        T = k.shape[1]
        m = causal_mask(S, T, T - S, window)
        scores = jnp.where(m[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v)


def _gqa_chunked(q, k, v, cfg: ModelConfig, *, causal: bool,
                 window: Optional[int]) -> jax.Array:
    """Flash-style q-chunked attention: scores materialize only per
    (chunk x T) block inside a lax.map — bounds the activation working set
    for long-context prefill (EXPERIMENTS.md §Perf iteration 3)."""
    B, S, Hq, dh = q.shape
    Qc = min(cfg.attn_q_chunk, S)
    nq = -(-S // Qc)
    Sp = nq * Qc
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    qs = jnp.moveaxis(q.reshape(B, nq, Qc, Hq, dh), 1, 0)

    def blk(args):
        i, qb = args  # qb: (B, Qc, Hq, dh)
        scores = _gqa_scores(qb, k, cfg)  # (B,Hk,G,Qc,T)
        if causal:
            qpos = i * Qc + jnp.arange(Qc)[:, None]
            kpos = jnp.arange(k.shape[1])[None, :]
            m = kpos <= qpos
            if window is not None:
                m &= kpos > qpos - window
            scores = jnp.where(m[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return _gqa_out(probs, v)

    outs = jax.lax.map(blk, (jnp.arange(nq), qs))  # (nq,B,Qc,Hq,dh)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sp, Hq, dh)
    return out[:, :S]


def encoder_kv(p, enc_x, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (cached at prefill)."""
    B, T, _ = enc_x.shape
    k = dense(enc_x, p["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = dense(enc_x, p["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = head_rms_norm(k, p["k_scale"], cfg.norm_eps)
    return k, v


# --------------------------------------------------------------------------
# Decode (one new token against a KV cache)
# --------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, layers: int,
                  dtype=jnp.bfloat16):
    shape = (layers, batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(cfg: ModelConfig, batch: int, cache_len: int, layers: int,
                   dtype=jnp.bfloat16):
    shape = (layers, batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def init_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                        layers: int, dtype=jnp.bfloat16):
    shape = (layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_kv_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int,
                         layers: int, dtype=jnp.bfloat16):
    shape = (layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def _paged_at(layer, *idx):
    """Index into a paged pool: `idx` into one layer's pool (Np,P,Hk,dh)
    when layer is None, else `(layer, *idx)` into the stacked pools
    (L,Np,P,Hk,dh) of every layer."""
    return idx if layer is None else (layer,) + idx


def _paged_gather(pool, bt, C, layer=None):
    """pool: (Np,P,Hk,dh), or the stacked (L,Np,P,Hk,dh) read at `layer`;
    bt: (B,n_max) page ids -> (B,C,Hk,dh) view.

    The gathered view is bit-identical to a dense (B,C) cache on every
    position < the row's logical length: page j of row b holds positions
    [j*P, (j+1)*P).  Positions beyond the logical length read whatever the
    page holds (zeros or a previous tenant's KV) — callers mask them with
    NEG_INF, which underflows softmax to an exact 0.0, so stale pages can
    never perturb the output (the bit-identity argument the paged engine
    rests on)."""
    B = bt.shape[0]
    Hk, dh = pool.shape[-2:]
    return pool[_paged_at(layer, bt)].reshape(B, -1, Hk, dh)[:, :C]


@jax.named_scope("attention")
def attention_decode(p, x, cache_k, cache_v, pos, cfg: ModelConfig,
                     *, layer=None, encoder_kv_cache=None, active=None,
                     block_tables=None, logical_len=None):
    """x: (B,1,d); cache_k/v: (B,C,Hk,dh); pos: () int32 current length,
    or (B,) int32 — one position per batch row, so slots of a continuous-
    batching pool can each decode at their own offset.

    active: optional (B,) bool (vector-pos only): rows where it is False are
    retired pool slots — their cache write is DROPPED (scatter to an out-of-
    bounds row with mode="drop"), so a no-op costs nothing extra.

    block_tables: optional (B, n_max) int32 — PAGED mode: cache_k/v are a
    shared page pool (Np, P, Hk, dh) and row b's position q lives in
    pool[block_tables[b, q // P], q % P].  logical_len bounds the gathered
    view (static; = the dense cache_len it replaces).  Requires vector pos;
    ring buffers (sliding window) do not compose with paging.

    layer: optional int32 scalar (paged mode) — cache_k/v are then the
    STACKED pools (L, Np, P, Hk, dh) of every layer, this layer's token is
    written at pool[layer, page, q % P] and the gather reads pool[layer,
    block_tables]; the updated stacked pools are returned.  A caller that
    carries the stacked pools through its layer scan this way updates them
    in place, with no per-layer slice or restack of the pool.

    Returns (y, new_cache_k, new_cache_v).  With a sliding window the cache
    is a ring buffer of size C=window; otherwise C >= pos+1.
    """
    B, _, _ = x.shape
    paged = block_tables is not None
    C = logical_len if paged else cache_k.shape[1]
    ring = cfg.attention_kind == "sliding_window"
    if paged and ring:
        raise ValueError("paged KV does not support sliding-window caches")
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    if paged and not per_row:
        raise ValueError("paged KV requires a per-row pos vector")
    pos_b = pos if per_row else jnp.broadcast_to(pos, (B,))  # (B,)
    positions = pos_b[:, None]
    if encoder_kv_cache is not None:
        q = dense(x, p["wq"]).reshape(B, 1, cfg.num_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = head_rms_norm(q, p["q_scale"], cfg.norm_eps)
        k, v = encoder_kv_cache
        valid = jnp.ones((B, k.shape[1]), bool)
        cache_k, cache_v = cache_k, cache_v  # untouched
        new_k, new_v = cache_k, cache_v
    elif paged:
        q, k1, v1 = _project_qkv(p, x, positions, cfg)
        Np, P = cache_k.shape[-4], cache_k.shape[-3]
        page = jnp.take_along_axis(block_tables, (pos_b // P)[:, None],
                                   axis=1)[:, 0]  # (B,) physical page ids
        if active is not None:
            page = jnp.where(active, page, Np)  # OOB -> write dropped
        at = _paged_at(layer, page, pos_b % P)
        with jax.named_scope("paged_cache_write"):
            new_k = cache_k.at[at].set(k1[:, 0], mode="drop")
            new_v = cache_v.at[at].set(v1[:, 0], mode="drop")
        if cfg.use_paged_kernel:
            from repro.kernels import ops as K
            lk, lv = ((new_k, new_v) if layer is None
                      else (new_k[layer], new_v[layer]))
            out = K.paged_attention(q[:, 0], lk, lv, block_tables,
                                    pos_b, logical_len=C)[:, None]
            y = dense(out.reshape(B, 1, -1), p["wo"])
            return shard(y, "batch", None, None), new_k, new_v
        k = _paged_gather(new_k, block_tables, C, layer)
        v = _paged_gather(new_v, block_tables, C, layer)
        valid = jnp.arange(C)[None, :] <= pos_b[:, None]  # (B,C)
    else:
        q, k1, v1 = _project_qkv(p, x, positions, cfg)
        slot = jnp.mod(pos, C) if ring else pos
        if per_row:
            rows = jnp.arange(B)
            if active is not None:
                slot = jnp.where(active, slot, C)  # OOB -> write dropped
            new_k = cache_k.at[rows, slot].set(k1[:, 0], mode="drop")
            new_v = cache_v.at[rows, slot].set(v1[:, 0], mode="drop")
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k1, slot,
                                                        axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v1, slot,
                                                        axis=1)
        k, v = new_k, new_v
        idx = jnp.arange(C)[None, :]
        if ring:
            valid = (idx <= jnp.mod(pos_b, C)[:, None]) | (pos_b[:, None] >= C)
        else:
            valid = idx <= pos_b[:, None]  # (B,C)
    q = shard(q, "batch", None, "model", None)
    scores = _gqa_scores(q, k, cfg)  # (B,Hk,G,1,C)
    scores = jnp.where(valid[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v)
    y = dense(out.reshape(B, 1, -1), p["wo"])
    return shard(y, "batch", None, None), new_k, new_v


@jax.named_scope("attention")
def attention_verify(p, x, cache_k, cache_v, pos, cfg: ModelConfig,
                     *, layer=None, active=None, block_tables=None,
                     logical_len=None):
    """Draft-verify attention: S candidate tokens per row in ONE pass.

    x: (B,S,d) — row b's tokens sit at positions pos[b] .. pos[b]+S-1.
    Writes all S keys/values (query i attends the cache plus candidates
    0..i, exactly what S sequential `attention_decode` calls would see),
    so the verifier's logits match sequential decode and acceptance is
    deterministic.  Rejected candidates leave stale KV beyond the accepted
    prefix; the next round overwrites positions pos'..pos'+S-1 before any
    query can see them (pos' <= pos + S), so no rollback write is needed —
    rolling back IS just not advancing `pos`.

    Dense cache (B,C,Hk,dh) or paged pool + block_tables (one layer's, or
    the stacked pools read and written at `layer`), as in
    `attention_decode`.  Returns (y (B,S,d), new_k, new_v)."""
    B, S, _ = x.shape
    paged = block_tables is not None
    C = logical_len if paged else cache_k.shape[1]
    if cfg.attention_kind == "sliding_window":
        raise ValueError("attention_verify: sliding-window caches "
                         "unsupported")
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim != 1:
        raise ValueError("attention_verify requires a per-row pos vector")
    qpos = pos[:, None] + jnp.arange(S)[None, :]  # (B,S) global positions
    q, k1, v1 = _project_qkv(p, x, qpos, cfg)
    if paged:
        Np, P = cache_k.shape[-4], cache_k.shape[-3]
        page = jnp.take_along_axis(block_tables, qpos // P, axis=1)  # (B,S)
        if active is not None:
            page = jnp.where(active[:, None], page, Np)
        at = _paged_at(layer, page, qpos % P)
        with jax.named_scope("paged_cache_write"):
            new_k = cache_k.at[at].set(k1, mode="drop")
            new_v = cache_v.at[at].set(v1, mode="drop")
        k = _paged_gather(new_k, block_tables, C, layer)
        v = _paged_gather(new_v, block_tables, C, layer)
    else:
        rows = jnp.arange(B)[:, None]
        slot = qpos
        if active is not None:
            slot = jnp.where(active[:, None], slot, C)  # OOB -> dropped
        new_k = cache_k.at[rows, slot].set(k1, mode="drop")
        new_v = cache_v.at[rows, slot].set(v1, mode="drop")
        k, v = new_k, new_v
    valid = jnp.arange(C)[None, None, :] <= qpos[:, :, None]  # (B,S,C)
    q = shard(q, "batch", None, "model", None)
    scores = _gqa_scores(q, k, cfg)  # (B,Hk,G,S,C)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v)
    y = dense(out.reshape(B, S, -1), p["wo"])
    return shard(y, "batch", None, None), new_k, new_v
