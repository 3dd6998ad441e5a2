"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels run in interpret mode; on every other
backend they compile to Mosaic.  Model code calls these wrappers, never
pallas_call directly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import nat_compress as _nc
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """GQA flash attention.  q: (B,S,Hq,dh); k,v: (B,T,Hk,dh)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xe, loga, b, c, *, chunk: int = 128):
    """Mamba2 SSD chunk scan.  Returns (y, final_state)."""
    return _ssd.ssd_scan(xe, loga, b, c, chunk=chunk,
                         interpret=_interpret())


@jax.jit
def nc_pack(x, key):
    """Natural-compress to int8 wire format."""
    return _nc.nc_pack(x, key, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("dtype",))
def nc_unpack(b, dtype=jnp.float32):
    return _nc.nc_unpack(b, dtype=dtype, interpret=_interpret())


def nc_roundtrip(x, key):
    """pack+unpack: the on-device view of a compressed gradient (unbiased)."""
    return nc_unpack(nc_pack(x, key), dtype=x.dtype)


# re-export oracles for tests / fallbacks
attention_ref = _ref.attention_ref
paged_attention_ref = _ref.paged_attention_ref
ssd_ref = _ref.ssd_ref
nc_pack_ref = _ref.nc_pack_ref
nc_unpack_ref = _ref.nc_unpack_ref


@functools.partial(jax.jit, static_argnames=("logical_len",))
def paged_attention(q, k_pool, v_pool, block_tables, pos, *,
                    logical_len: Optional[int] = None) -> jax.Array:
    """Paged decode attention through a block table.

    q: (B,Hq,dh); k/v_pool: (Np,P,Hk,dh); block_tables: (B,n_max) int32;
    pos: (B,) int32.  logical_len (static) crops the block table to
    ceil(logical_len / P) pages — callers that size their tables past the
    engine's cache_len don't pay for the dead pages."""
    if logical_len is not None:
        P = k_pool.shape[1]
        block_tables = block_tables[:, :-(-logical_len // P)]
    return _pa.paged_attention(q, k_pool, v_pool, block_tables, pos,
                               interpret=_interpret())
