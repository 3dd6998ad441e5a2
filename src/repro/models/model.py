"""Top-level models: decoder LM (dense/moe/vlm), encoder-decoder (audio),
hybrid SSM+shared-attention (Zamba2-style), RWKV6.

All depth iteration is `lax.scan` over stacked per-layer params so HLO size
is O(1) in depth (96-layer 340B configs compile on one CPU core).

Public entry points:
  model_descs / init_model / model_abstract / model_pspecs
  forward(params, cfg, tokens, ...)          -> (logits, aux, cache|None)
  decode_step(params, cfg, tokens, pos, cache) -> (logits, cache)
  lm_loss(params, cfg, batch)                -> scalar
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.sharding import shard
from repro.models import attention as A
from repro.models import mlp as M
from repro.models import rwkv as RW
from repro.models import ssm as SSM
from repro.models.common import (ParamDesc, abstract_params, apply_rope,
                                 dense, init_params, param_pspecs, rms_norm)
from repro.models.config import ModelConfig


def _scan(cfg, fn, carry, xs):
    unroll = cfg.num_layers if cfg.unroll_layers else 1
    return jax.lax.scan(fn, carry, xs, unroll=max(unroll, 1))


def _scan_kv(cfg, fn, carry, xs, kv, *, in_carry: bool):
    """Scan `fn(carry, xs, kv, layer) -> (carry, kv)` over the layers.

    in_carry (paged pools): the stacked KV `kv` travels in the scan's carry
    beside the int32 layer index, so each layer writes its tokens into the
    stacked buffers in place; no layer slices, restacks or copies a pool.
    Otherwise (per-slot caches) each layer gets its own slice of `kv` as
    xs, with layer None, and returns it as ys.  Returns (carry, new kv)."""
    if in_carry:
        def body(c, x):
            c, layer, kv = c
            c, kv = fn(c, x, kv, layer)
            return (c, layer + 1, kv), None
        (carry, _, kv), _ = _scan(
            cfg, body, (carry, jnp.zeros((), jnp.int32), kv), xs)
        return carry, kv
    return _scan(cfg, lambda c, x: fn(c, x[0], x[1], None), carry, (xs, kv))


# ---------------------------------------------------------------------------
# Parameter descriptor trees
# ---------------------------------------------------------------------------
VISION_EMBED_DIM = 1024  # stub ViT output dim (CLIP ViT-L) for VLM backbones


def _stack(tree, L: int):
    return jax.tree_util.tree_map(
        lambda d: ParamDesc((L,) + d.shape, ("layers",) + tuple(d.spec),
                            d.dtype, d.init, d.fan_in),
        tree, is_leaf=lambda x: isinstance(x, ParamDesc))


def _norm_desc(cfg):
    return ParamDesc((cfg.d_model,), (None,), cfg.param_dtype, init="ones")


def _attn_mlp_block_descs(cfg: ModelConfig, cross: bool = False):
    d = {"ln1": _norm_desc(cfg), "attn": A.attn_descs(cfg),
         "ln2": _norm_desc(cfg), "mlp": M.mlp_descs(cfg)}
    if cross:
        d["lnc"] = _norm_desc(cfg)
        d["cross"] = A.attn_descs(cfg)
    return d


def block_descs(cfg: ModelConfig) -> Dict[str, Any]:
    at = cfg.arch_type
    if at in ("dense", "vlm"):
        return _attn_mlp_block_descs(cfg)
    if at == "audio":
        return _attn_mlp_block_descs(cfg, cross=True)
    if at == "moe":
        return {"ln1": _norm_desc(cfg), "attn": A.attn_descs(cfg),
                "ln2": _norm_desc(cfg), "moe": M.moe_descs(cfg)}
    if at == "hybrid":
        return {"ln": _norm_desc(cfg), "ssm": SSM.ssm_descs(cfg)}
    if at == "ssm":
        return RW.rwkv_descs(cfg)
    raise ValueError(at)


def model_descs(cfg: ModelConfig) -> Dict[str, Any]:
    dt = cfg.param_dtype
    descs: Dict[str, Any] = {
        "embed": ParamDesc((cfg.vocab_size, cfg.d_model), ("model", None), dt,
                           init="small_normal"),
        "blocks": _stack(block_descs(cfg), cfg.num_layers),
        "final_norm": _norm_desc(cfg),
        "lm_head": ParamDesc((cfg.d_model, cfg.vocab_size), (None, "model"),
                             dt, fan_in=cfg.d_model),
    }
    if cfg.arch_type == "hybrid":
        descs["shared"] = _attn_mlp_block_descs(cfg)
    if cfg.arch_type == "audio":
        descs["enc_blocks"] = _stack(_attn_mlp_block_descs(cfg),
                                     cfg.num_encoder_layers)
        descs["enc_final_norm"] = _norm_desc(cfg)
    if cfg.arch_type == "vlm":
        descs["vproj"] = ParamDesc((VISION_EMBED_DIM, cfg.d_model),
                                   (None, None), dt, fan_in=VISION_EMBED_DIM)
    return descs


def init_model(cfg: ModelConfig, key):
    return init_params(model_descs(cfg), key)


def model_abstract(cfg: ModelConfig):
    return abstract_params(model_descs(cfg))


def model_pspecs(cfg: ModelConfig):
    return param_pspecs(model_descs(cfg))


# ---------------------------------------------------------------------------
# Block application (batched: train / prefill)
# ---------------------------------------------------------------------------
def _attn_sublayer(p, x, positions, cfg, collect_kv=False):
    """Pre-norm attention sublayer; optionally return rope'd (k, v) for the
    decode cache (same layout `attention_decode` writes)."""
    pre = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = A._project_qkv(p["attn"], pre, positions, cfg)
    B, S = pre.shape[:2]
    window = cfg.sliding_window if cfg.attention_kind == "sliding_window" else None
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    out = A.gqa_attend(q, k, v, cfg, causal=True, window=window)
    out = shard(out, "batch", None, "model", None)
    y = dense(out.reshape(B, S, -1), p["attn"]["wo"])
    x = x + shard(y, "batch", "seq", None)
    return (x, (k, v)) if collect_kv else (x, None)


def _apply_attn_mlp(p, x, positions, cfg, *, enc=None, collect_kv=False):
    x, kv = _attn_sublayer(p, x, positions, cfg, collect_kv)
    if enc is not None:
        h = rms_norm(x, p["lnc"], cfg.norm_eps)
        ekv = A.encoder_kv(p["cross"], enc, cfg)
        x = x + A.attention(p["cross"], h, positions, cfg, encoder_kv=ekv)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + M.mlp(p["mlp"], h, cfg)
    return shard(x, "batch", "seq", None), kv


def _encode_audio(params, cfg, frames):
    B, Te, _ = frames.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Te, dtype=jnp.int32)[None], (B, Te))

    def enc_body(h, lp):
        h1 = rms_norm(h, lp["ln1"], cfg.norm_eps)
        h = h + A.attention(lp["attn"], h1, enc_pos, cfg, causal=False)
        h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = h + M.mlp(lp["mlp"], h2, cfg)
        return h, None

    fn = jax.checkpoint(enc_body) if cfg.remat == "block" else enc_body
    enc, _ = _scan(cfg, fn, frames, params["enc_blocks"])
    return rms_norm(enc, params["enc_final_norm"], cfg.norm_eps)


def _pad_cache(k, v, C, dt):
    S = k.shape[1]
    if C < S:
        raise ValueError(f"cache_len {C} < seq {S}")
    if C > S:
        pad = [(0, 0), (0, C - S), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return k.astype(dt), v.astype(dt)


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None,
            return_cache: bool = False, cache_len: Optional[int] = None):
    """tokens: (B, S) int32.  extra_embeds: modality-frontend stub outputs —
    audio: (B, T_enc, d_model) frame embeddings; vlm: (B, P, 1024) patches.

    Returns (logits (B, S_tok, V), aux_loss scalar, cache|None)."""
    at = cfg.arch_type
    B, _ = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    x = shard(x, "batch", None, None)

    n_prefix = 0
    if at == "vlm":
        patches = dense(extra_embeds.astype(x.dtype), params["vproj"])
        x = jnp.concatenate([patches, x], axis=1)
        n_prefix = patches.shape[1]

    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc_out = _encode_audio(params, cfg, extra_embeds) if at == "audio" else None
    C = cache_len or S
    aux0 = jnp.zeros((), jnp.float32)

    if at in ("dense", "vlm", "moe", "audio"):
        def body(carry, lp):
            h, aux = carry
            if at == "moe":
                h, kv = _attn_sublayer(lp, h, positions, cfg, return_cache)
                pre = rms_norm(h, lp["ln2"], cfg.norm_eps)
                y, a = M.moe(lp["moe"], pre, cfg)
                h, aux = h + y, aux + a
            else:
                h, kv = _apply_attn_mlp(lp, h, positions, cfg, enc=enc_out,
                                        collect_kv=return_cache)
            ys = (_pad_cache(*kv, C, jnp.dtype(cfg.compute_dtype))
                  if return_cache else None)
            return (h, aux), ys

        fn = jax.checkpoint(body) if cfg.remat == "block" else body
        (x, aux), ys = _scan(cfg, fn, (x, aux0), params["blocks"])
        cache = None
        if return_cache:
            cache = {"k": ys[0], "v": ys[1]}
            if at == "audio":
                def ckv(_, lp):
                    return None, A.encoder_kv(lp["cross"], enc_out, cfg)
                _, (ck, cv) = _scan(cfg, ckv, None, params["blocks"])
                cache["ck"], cache["cv"] = ck, cv

    elif at == "hybrid":
        x, aux, cache = _run_hybrid(params, cfg, x, positions, return_cache, C)
    elif at == "ssm":
        x, aux, cache = _run_rwkv(params, cfg, x, return_cache)
    else:
        raise ValueError(at)

    logits = _logits(params, cfg, x)
    logits = shard(logits, "batch", None, "model")
    if n_prefix:
        logits = logits[:, n_prefix:]
    return logits, aux, cache


def _run_hybrid(params, cfg, x, positions, return_cache, C):
    """Zamba2-style: scan of Mamba2 blocks; a SHARED attn+MLP block (same
    weights each time) applied after every cfg.hybrid_attn_every layers."""
    B, S, _ = x.shape
    k_every = cfg.hybrid_attn_every
    shared = params["shared"]
    aux0 = jnp.zeros((), jnp.float32)
    cdt = jnp.dtype(cfg.compute_dtype)
    kvshape = (B, C, cfg.num_kv_heads, cfg.head_dim)

    def body(carry, lp):
        h, aux, idx = carry
        pre = rms_norm(h, lp["ln"], cfg.norm_eps)
        y, (st, conv) = SSM.ssm_block(lp["ssm"], pre, cfg)
        h = h + y
        apply_shared = (idx + 1) % k_every == 0

        def with_shared(h):
            h2, kv = _apply_attn_mlp(shared, h, positions, cfg,
                                     collect_kv=return_cache)
            if return_cache:
                return h2, _pad_cache(*kv, C, cdt)
            return h2, (jnp.zeros(kvshape, cdt),) * 2

        def without(h):
            return h, (jnp.zeros(kvshape, cdt),) * 2

        h, skv = jax.lax.cond(apply_shared, with_shared, without, h)
        ys = ((st, conv) + skv) if return_cache else None
        return (h, aux, idx + 1), ys

    fn = jax.checkpoint(body) if cfg.remat == "block" else body
    (x, aux, _), ys = _scan(
        cfg, fn, (x, aux0, jnp.zeros((), jnp.int32)), params["blocks"])
    cache = None
    if return_cache:
        st, conv, sk, sv = ys
        idxs = [i for i in range(cfg.num_layers) if (i + 1) % k_every == 0]
        cache = {"ssm": st, "conv": conv,
                 "sk": sk[jnp.array(idxs)], "sv": sv[jnp.array(idxs)]}
    return x, aux, cache


def _run_rwkv(params, cfg, x, return_cache):
    aux0 = jnp.zeros((), jnp.float32)

    def body(carry, lp):
        h, aux = carry
        h, st = RW.rwkv_block(lp, h, cfg)
        return (h, aux), (st if return_cache else None)

    fn = jax.checkpoint(body) if cfg.remat == "block" else body
    (x, aux), ys = _scan(cfg, fn, (x, aux0), params["blocks"])
    return x, aux, ys


@jax.named_scope("logits")
def _logits(params, cfg: ModelConfig, x):
    """Final norm and LM head: (B, S, d) -> (B, S, V)."""
    return dense(rms_norm(x, params["final_norm"], cfg.norm_eps),
                 params["lm_head"])


# ---------------------------------------------------------------------------
# Decode (one token against a cache)
# ---------------------------------------------------------------------------
def _gate_rows(active, new, old):
    """Keep `old` batch rows where the slot is inactive. new/old: (B, ...)."""
    a = active.reshape(active.shape + (1,) * (new.ndim - 1))
    return jnp.where(a, new, old)


def decode_step(params, cfg: ModelConfig, tokens, pos, cache, *,
                active=None, block_tables=None, logical_len=None):
    """tokens: (B,1) int32; pos: () int32 current sequence length, or (B,)
    int32 — one position per batch row (continuous batching: every slot of
    the pool decodes at its own offset).

    active: optional (B,) bool (requires vector pos) — rows where it is
    False are retired slots: their cache/state updates are no-ops (KV
    writes are dropped in-place, recurrent-state rows keep their old
    value), so a pool can keep ticking while a slot waits for backfill.

    block_tables: optional (B, n_max) int32 — PAGED mode: the cache's KV
    leaves (`paged_leaf_names`) are shared page pools (stack, Np, P, Hk,
    dh) and row b reads/writes through its block table; every other leaf
    (audio cross-KV, hybrid recurrent state) stays per-slot.  logical_len
    is the static dense cache_len the pool replaces.  The layer scan
    carries the stacked pools with the layer index: each layer's
    `attention_decode` takes the stacked pools and its index and returns
    the updated stacked pools, so the pools are written in place (no
    per-layer slice, restack or copy); per-slot leaves are the scan's
    xs/ys.

    Returns (logits (B,1,V), new cache)."""
    at = cfg.arch_type
    B = tokens.shape[0]
    if active is not None and jnp.asarray(pos).ndim != 1:
        raise ValueError("active mask requires a per-row pos vector")
    if block_tables is not None and at == "ssm":
        raise ValueError("arch_type ssm has no KV cache to page")
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    x = shard(x, "batch", None, None)

    if at in ("dense", "vlm", "moe", "audio"):
        def body(carry, xs, kv, layer):
            h, aux = carry
            lp, xkv = xs  # xkv: audio's per-slot cross-KV (ck, cv), else None
            ck, cv = kv
            pre = rms_norm(h, lp["ln1"], cfg.norm_eps)
            y, nk, nv = A.attention_decode(lp["attn"], pre, ck, cv, pos, cfg,
                                           layer=layer, active=active,
                                           block_tables=block_tables,
                                           logical_len=logical_len)
            h = h + y
            if at == "audio":
                hc = rms_norm(h, lp["lnc"], cfg.norm_eps)
                yc, _, _ = A.attention_decode(
                    lp["cross"], hc, ck * 0, cv * 0, pos, cfg,
                    encoder_kv_cache=xkv)
                h = h + yc
            pre2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            if at == "moe":
                y2, a = M.moe(lp["moe"], pre2, cfg)
                h, aux = h + y2, aux + a
            else:
                h = h + M.mlp(lp["mlp"], pre2, cfg)
            return (h, aux), (nk, nv)

        xkv = (cache["ck"], cache["cv"]) if at == "audio" else None
        (x, _), (nk, nv) = _scan_kv(
            cfg, body, (x, jnp.zeros((), jnp.float32)),
            (params["blocks"], xkv), (cache["k"], cache["v"]),
            in_carry=block_tables is not None)
        new_cache = dict(cache, k=nk, v=nv)

    elif at == "hybrid":
        x, new_cache = _decode_hybrid(params, cfg, x, pos, cache,
                                      active=active,
                                      block_tables=block_tables,
                                      logical_len=logical_len)
    elif at == "ssm":
        def body(h, xs):
            lp, st = xs
            h, nst = RW.rwkv_block(lp, h, cfg, state=st)
            if active is not None:
                nst = jax.tree_util.tree_map(
                    lambda n, o: _gate_rows(active, n, o), nst, st)
            return h, nst
        x, nst = _scan(cfg, body, x, (params["blocks"], cache))
        new_cache = nst
    else:
        raise ValueError(at)

    logits = _logits(params, cfg, x)
    return shard(logits, "batch", None, "model"), new_cache


def _decode_hybrid(params, cfg, x, pos, cache, *, active=None,
                   block_tables=None, logical_len=None):
    k_every = cfg.hybrid_attn_every
    shared = params["shared"]

    def body(carry, xs):
        h, idx, sidx = carry
        lp, st, conv, sk, sv = xs
        pre = rms_norm(h, lp["ln"], cfg.norm_eps)
        y, (nst, nconv) = SSM.ssm_block(lp["ssm"], pre, cfg, state=st,
                                        conv_cache=conv)
        if active is not None:
            nst = _gate_rows(active, nst, st)
            nconv = jax.tree_util.tree_map(
                lambda n, o: _gate_rows(active, n, o), nconv, conv)
        h = h + y
        apply_shared = (idx + 1) % k_every == 0

        def with_shared(args):
            h, sk, sv = args
            pre = rms_norm(h, shared["ln1"], cfg.norm_eps)
            y, nk, nv = A.attention_decode(shared["attn"], pre, sk, sv, pos,
                                           cfg, active=active,
                                           block_tables=block_tables,
                                           logical_len=logical_len)
            h = h + y
            pre2 = rms_norm(h, shared["ln2"], cfg.norm_eps)
            h = h + M.mlp(shared["mlp"], pre2, cfg)
            return h, nk, nv

        h, nsk, nsv = jax.lax.cond(
            apply_shared, with_shared, lambda a: a, (h, sk, sv))
        sidx = sidx + jnp.where(apply_shared, 1, 0)
        return (h, idx + 1, sidx), (nst, nconv, nsk, nsv)

    # scatter shared-cache slots across layers: layer i uses shared slot i//k
    L = cfg.num_layers
    slot = jnp.arange(L) // k_every
    sk_l = cache["sk"][slot]  # (L, B, C, Hk, dh) gathered view
    sv_l = cache["sv"][slot]
    (x, _, _), (nst, nconv, nsk, nsv) = _scan(
        cfg, body, (x, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)),
        (params["blocks"], cache["ssm"], cache["conv"], sk_l, sv_l))
    idxs = jnp.array([i for i in range(L) if (i + 1) % k_every == 0])
    new_cache = {"ssm": nst, "conv": nconv,
                 "sk": nsk[idxs], "sv": nsv[idxs]}
    return x, new_cache


def verify_step(params, cfg: ModelConfig, tokens, pos, cache, *,
                active=None, block_tables=None, logical_len=None):
    """Speculative-decoding verify: score S candidate tokens per row in one
    fused pass.  tokens: (B,S) int32 — row b's candidates occupy positions
    pos[b] .. pos[b]+S-1; logits[:, i] is the model's next-token
    distribution after candidate i, bit-matching what S sequential
    `decode_step` calls would produce (same reductions over the same
    arrays), which is what makes greedy accept/reject exact.

    Supports the attention-only decoder families (dense/vlm/moe): the
    recurrent families (hybrid/ssm) would need state snapshots to roll
    back, not just a position register.

    Returns (logits (B,S,V), new cache)."""
    at = cfg.arch_type
    if at not in ("dense", "vlm", "moe"):
        raise ValueError(f"verify_step: unsupported arch_type {at}")
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    x = shard(x, "batch", None, None)

    def body(carry, lp, kv, layer):
        h, aux = carry
        ck, cv = kv
        pre = rms_norm(h, lp["ln1"], cfg.norm_eps)
        y, nk, nv = A.attention_verify(lp["attn"], pre, ck, cv, pos, cfg,
                                       layer=layer, active=active,
                                       block_tables=block_tables,
                                       logical_len=logical_len)
        h = h + y
        pre2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
        if at == "moe":
            y2, a = M.moe(lp["moe"], pre2, cfg)
            h, aux = h + y2, aux + a
        else:
            h = h + M.mlp(lp["mlp"], pre2, cfg)
        return (h, aux), (nk, nv)

    (x, _), (nk, nv) = _scan_kv(cfg, body, (x, jnp.zeros((), jnp.float32)),
                                params["blocks"], (cache["k"], cache["v"]),
                                in_carry=block_tables is not None)
    new_cache = dict(cache, k=nk, v=nv)
    logits = _logits(params, cfg, x)
    return shard(logits, "batch", None, "model"), new_cache


# ---------------------------------------------------------------------------
# Cache construction / specs (for serving and the dry-run)
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, cache_len: int):
    at = cfg.arch_type
    L = cfg.num_layers
    if cfg.attention_kind == "sliding_window":
        cache_len = min(cache_len, cfg.sliding_window)
    cdt = jnp.dtype(cfg.compute_dtype)
    if at in ("dense", "vlm", "moe", "audio"):
        sp = A.kv_cache_specs(cfg, batch, cache_len, L, cdt)
        if at == "audio":
            shape = (L, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim)
            sp["ck"] = jax.ShapeDtypeStruct(shape, cdt)
            sp["cv"] = jax.ShapeDtypeStruct(shape, cdt)
        return sp
    if at == "hybrid":
        base = SSM.ssm_state_specs(cfg, batch, L)
        n_shared = cfg.num_layers // cfg.hybrid_attn_every
        shape = (n_shared, batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
        return {"ssm": base["state"], "conv": base["conv"],
                "sk": jax.ShapeDtypeStruct(shape, cdt),
                "sv": jax.ShapeDtypeStruct(shape, cdt)}
    if at == "ssm":
        return RW.rwkv_state_specs(cfg, batch, L)
    raise ValueError(at)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        cache_specs(cfg, batch, cache_len))


def paged_leaf_names(cfg: ModelConfig) -> tuple:
    """Cache leaves that page (position-indexed KV); everything else —
    audio cross-KV (fixed encoder length), hybrid SSM/conv state, RWKV
    state — stays a per-slot batch row."""
    at = cfg.arch_type
    if at in ("dense", "vlm", "moe", "audio"):
        return ("k", "v")
    if at == "hybrid":
        return ("sk", "sv")
    return ()


def paged_cache_specs(cfg: ModelConfig, num_slots: int, num_pages: int,
                      page_size: int):
    """Like `cache_specs`, but KV leaves become shared page pools
    (stack, num_pages, page_size, Hk, dh): capacity is governed by tokens
    actually resident, not slots x worst-case length."""
    if cfg.attention_kind == "sliding_window":
        raise ValueError("paged KV does not support sliding-window caches")
    names = paged_leaf_names(cfg)
    if not names:
        raise ValueError(f"arch_type {cfg.arch_type} has no KV to page")
    sp = dict(cache_specs(cfg, num_slots, page_size))
    cdt = jnp.dtype(cfg.compute_dtype)
    for n in names:
        stack = sp[n].shape[0]
        sp[n] = jax.ShapeDtypeStruct(
            (stack, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim),
            cdt)
    return sp


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        paged_cache_specs(cfg, num_slots, num_pages, page_size))


@jax.named_scope("paged_cache_write")
def write_paged_cache(pool_cache, request_cache, slot, page_ids, cfg):
    """Install one request's B=1 prefill cache into a paged pool: KV
    leaves (prefilled to a page multiple) scatter whole pages onto the
    `page_ids` rows of the shared pool; per-slot leaves scatter batch row
    `slot` as in `write_cache_slot`."""
    names = set(paged_leaf_names(cfg))
    npg = page_ids.shape[0]
    new = {}
    for name, pool in pool_cache.items():
        one = request_cache[name]
        if name in names:
            stack, _, P = pool.shape[:3]
            pages = one[:, 0].reshape((stack, npg, P) + pool.shape[3:])
            new[name] = pool.at[:, page_ids].set(pages.astype(pool.dtype))
        else:
            # per-slot leaves may themselves be trees (hybrid conv ring)
            new[name] = jax.tree_util.tree_map(
                lambda p, o: p.at[:, slot].set(o[:, 0].astype(p.dtype)),
                pool, one)
    return new


def write_cache_slot(pool_cache, request_cache, slot):
    """Scatter one request's cache (batch==1, from a B=1 prefill) into batch
    row `slot` of a slot-pool cache.

    Works for every arch family because every cache leaf — KV (L,B,C,Hk,dh),
    cross-KV, SSM state (L,B,H,N,P), conv ring (L,B,W-1,·), RWKV wkv/shift —
    is laid out (stack, batch, ...): the write is a single batch-row scatter
    per leaf."""
    return jax.tree_util.tree_map(
        lambda pool, one: pool.at[:, slot].set(one[:, 0].astype(pool.dtype)),
        pool_cache, request_cache)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(params, cfg: ModelConfig, batch, aux_weight: float = 0.01):
    """batch: {"tokens": (B,S), "labels": (B,S), optional "extra_embeds"}."""
    logits, aux, _ = forward(params, cfg, batch["tokens"],
                             extra_embeds=batch.get("extra_embeds"))
    logits = logits.astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + aux_weight * aux
