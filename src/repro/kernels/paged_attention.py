"""Paged-attention decode as a Pallas TPU kernel.

One query token per batch row reads its KV history THROUGH a block table:
position q of row b lives in page `block_tables[b, q // P]` of a shared
(Np, P, Hk, dh) pool, so the kernel never materializes the gathered
(B, C, Hk, dh) view the pure-jnp reference builds — each grid step DMAs
exactly one physical page into VMEM, which is what makes decode reads
O(tokens resident) instead of O(slots x max length).

The page id is data: `PrefetchScalarGridSpec` prefetches the block table
(and the per-row positions) into SMEM so the k/v BlockSpec index_maps can
address HBM by `bt[b, j]` before the body runs.

The pool is viewed as (Np, P, Hk*dh) (a free reshape), so one kv head of
one page is a (P, dh) tile whose last two dims are lane/sublane aligned;
a (P, 1, dh) block of the 4-D pool would put a 1 against Hk on the
second-minor dim, which Mosaic does not tile.

Grid: (B, Hk, n_pages_per_row), pages innermost (sequential); the online
softmax accumulator lives in VMEM scratch across the page dimension,
exactly like flash_attention.py's k-block loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, page_size: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    G = q_ref.shape[2]
    P = page_size

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]

    # pages wholly beyond the row's length would be fully masked anyway;
    # skipping them saves the dot without changing the accumulator
    @pl.when(j * P <= pos)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)        # (G, dh)
        k = k_ref[0].astype(jnp.float32)           # (P, dh)
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * P + jax.lax.broadcasted_iota(jnp.int32, (G, P), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)     # decode: attend idx <= pos

        m_prev = m_ref[...]                        # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # (G, P)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked row -> 0
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, pos, *,
                    scale=None, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, dh) one decode token per row; k/v_pool: (Np, P, Hk, dh);
    block_tables: (B, n_max) int32 physical page ids; pos: (B,) int32 —
    row b attends positions 0..pos[b] of its logical sequence.

    Returns (B, Hq, dh) in q.dtype (the attention context; projections
    stay in the model layer)."""
    B, Hq, dh = q.shape
    Np, P, Hk, _ = k_pool.shape
    assert Hq % Hk == 0, (Hq, Hk)
    G = Hq // Hk
    n_max = block_tables.shape[1]
    sc = scale if scale is not None else dh ** -0.5

    qg = q.reshape(B, Hk, G, dh)
    kf = k_pool.reshape(Np, P, Hk * dh)
    vf = v_pool.reshape(Np, P, Hk * dh)
    grid = (B, Hk, n_max)

    out = pl.pallas_call(
        functools.partial(_kernel, page_size=P, scale=sc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, dh),
                             lambda b, h, j, bt, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, P, dh),
                             lambda b, h, j, bt, pos: (bt[b, j], 0, h)),
                pl.BlockSpec((1, P, dh),
                             lambda b, h, j, bt, pos: (bt[b, j], 0, h)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, dh),
                                   lambda b, h, j, bt, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, dh), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(pos, jnp.int32),
      qg, kf, vf)
    return out.reshape(B, Hq, dh)
