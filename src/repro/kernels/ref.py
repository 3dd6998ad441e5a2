"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics the kernels must reproduce bit-for-bit (up to
accumulation-order fp error).  Tests sweep shapes/dtypes and
`assert_allclose` kernel-vs-oracle with the kernel in interpret mode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Flash attention oracle: GQA attention, causal / sliding-window / full
# ---------------------------------------------------------------------------
def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """q: (B,S,Hq,dh); k,v: (B,T,Hk,dh), Hq % Hk == 0.  fp32 softmax.

    Returns (B,S,Hq,dh) in q.dtype."""
    B, S, Hq, dh = q.shape
    T, Hk = k.shape[1], k.shape[2]
    G = Hq // Hk
    sc = scale if scale is not None else dh ** -0.5
    qg = q.reshape(B, S, Hk, G, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * sc
    if causal:
        qpos = jnp.arange(S)[:, None] + (T - S)  # queries end at position T-1
        kpos = jnp.arange(T)[None, :]
        m = kpos <= qpos
        if window is not None:
            m &= kpos > qpos - window
        scores = jnp.where(m[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, Hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# SSD (Mamba2) oracle: exact sequential recurrence
# ---------------------------------------------------------------------------
def ssd_ref(xe, loga, b, c) -> tuple[jax.Array, jax.Array]:
    """Sequential state-space recurrence (the definition SSD factorizes).

    xe:   (B,S,H,P)  dt-scaled inputs (x * dt)
    loga: (B,S,H)    per-step log decay (negative)
    b,c:  (B,S,N)    input/output projections (shared across heads)

    state_t = state_{t-1} * exp(loga_t) + b_t ⊗ xe_t
    y_t     = c_t · state_t
    Returns y (B,S,H,P) fp32 and final state (B,H,N,P) fp32."""
    B, S, H, P = xe.shape
    N = b.shape[-1]
    xe = xe.astype(jnp.float32)
    loga = loga.astype(jnp.float32)
    b = b.astype(jnp.float32)
    c = c.astype(jnp.float32)

    def step(state, t):
        a_t = jnp.exp(loga[:, t])  # (B,H)
        upd = jnp.einsum("bn,bhp->bhnp", b[:, t], xe[:, t])
        state = state * a_t[..., None, None] + upd
        y_t = jnp.einsum("bn,bhnp->bhp", c[:, t], state)
        return state, y_t

    state0 = jnp.zeros((B, H, N, P), jnp.float32)
    final, ys = jax.lax.scan(step, state0, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1), final


# ---------------------------------------------------------------------------
# Natural compression oracle (given pre-drawn uniforms — deterministic)
# ---------------------------------------------------------------------------
_BIAS = 70


def nc_pack_ref(x, u) -> jax.Array:
    """Stochastic power-of-two rounding to the int8 wire format.

    x: any float array; u: uniforms in [0,1) of the same shape.
    value = sign * 2^(code - 70), code 0 => zero."""
    a = jnp.abs(x).astype(jnp.float32)
    zero = a == 0
    m, e = jnp.frexp(a)  # a = m * 2^e, m in [0.5, 1)
    p = 2.0 * m - 1.0
    up = (u < p).astype(jnp.int32)
    code = jnp.clip(e - 1 + up + _BIAS, 1, 127)
    code = jnp.where(zero, 0, code)
    sign = (x < 0).astype(jnp.int32) << 7
    return (code | sign).astype(jnp.uint8)


def nc_unpack_ref(bcode, dtype=jnp.float32) -> jax.Array:
    bi = bcode.astype(jnp.int32)
    sign = jnp.where((bi & 0x80) != 0, -1.0, 1.0)
    code = bi & 0x7F
    mag = jnp.where(code == 0, 0.0, jnp.ldexp(1.0, code - _BIAS))
    return (sign * mag).astype(dtype)


# ---------------------------------------------------------------------------
# Paged-attention decode oracle: block-table gather + masked softmax
# ---------------------------------------------------------------------------
def paged_attention_ref(q, k_pool, v_pool, block_tables, pos, *,
                        scale=None) -> jax.Array:
    """q: (B,Hq,dh) one decode token per row; k/v_pool: (Np,P,Hk,dh);
    block_tables: (B,n_max) page ids; pos: (B,) — attend idx <= pos[b].

    The gather+mask is the same math `models.attention.attention_decode`
    runs in paged mode (minus projections), so this doubles as the
    engine-side semantics the kernel must reproduce."""
    B, Hq, dh = q.shape
    Np, P, Hk, _ = k_pool.shape
    G = Hq // Hk
    C = block_tables.shape[1] * P
    sc = scale if scale is not None else dh ** -0.5
    k = k_pool[block_tables].reshape(B, C, Hk, dh)
    v = v_pool[block_tables].reshape(B, C, Hk, dh)
    qg = q.reshape(B, Hk, G, dh)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * sc
    valid = jnp.arange(C)[None, :] <= pos[:, None]          # (B,C)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Hq, dh).astype(q.dtype)
