"""Natural compression (stochastic power-of-two rounding) as a Pallas kernel.

The paper's (survey ref 75) trick is that C_nat needs no mantissa
arithmetic: take the exponent, round up with probability equal to the
normalized mantissa remainder, emit sign+exponent (9 bits; we pack into
int8 wire format with a biased 7-bit exponent).  On TPU this is a pure
VPU elementwise kernel; the win is fusing pack into the gradient
producer so the fp32 gradient never round-trips to HBM before the wire.

Randomness: uniforms are an explicit input (drawn by the caller with
jax.random), keeping the kernel deterministic and oracle-checkable.

Grid: 1-D over row blocks of the (rows, 128) reshaped array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BIAS = 70
_LANE = 128
_BLOCK_ROWS = 256


def _pack_kernel(x_ref, u_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    a = jnp.abs(x)
    zero = a == 0
    # a = m * 2^e with m in [0.5, 1): a lies in [2^(e-1), 2^e)
    m, e = jnp.frexp(a)
    p = 2.0 * m - 1.0  # normalized mantissa remainder in [0,1), exact
    up = (u < p).astype(jnp.int32)
    code = jnp.clip(e - 1 + up + _BIAS, 1, 127)
    code = jnp.where(zero, 0, code)
    sign = jnp.where(x < 0, 128, 0)
    o_ref[...] = (code | sign).astype(jnp.int32)


def _unpack_kernel(b_ref, o_ref):
    bi = b_ref[...]
    sign = jnp.where((bi & 0x80) != 0, -1.0, 1.0)
    code = bi & 0x7F
    # a vector mantissa: Mosaic bitcasts vectors only, not a scalar 1.0
    one = jnp.ones(code.shape, jnp.float32)
    mag = jnp.where(code == 0, 0.0, jnp.ldexp(one, code - _BIAS))
    o_ref[...] = (sign * mag).astype(o_ref.dtype)


def _tile(n: int):
    rows = -(-n // _LANE)
    rows_pad = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    return rows, rows_pad


def nc_pack(x: jax.Array, key: jax.Array, *,
            interpret: bool = False) -> jax.Array:
    """Pack to the int8 wire format (returned as uint8, same shape as x).

    int32 is used inside the kernel (TPU-native lane width); the uint8
    cast is the wire serialization boundary."""
    shape = x.shape
    n = x.size
    u = jax.random.uniform(key, (n,), jnp.float32)
    rows, rows_pad = _tile(n)
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32),
                 (0, rows_pad * _LANE - n)).reshape(rows_pad, _LANE)
    uf = jnp.pad(u, (0, rows_pad * _LANE - n)).reshape(rows_pad, _LANE)
    out = pl.pallas_call(
        _pack_kernel,
        grid=(rows_pad // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANE), jnp.int32),
        interpret=interpret,
    )(xf, uf)
    return out.reshape(-1)[:n].astype(jnp.uint8).reshape(shape)


def nc_unpack(b: jax.Array, dtype=jnp.float32, *,
              interpret: bool = False) -> jax.Array:
    shape = b.shape
    n = b.size
    rows, rows_pad = _tile(n)
    bf = jnp.pad(b.reshape(-1).astype(jnp.int32),
                 (0, rows_pad * _LANE - n)).reshape(rows_pad, _LANE)
    out = pl.pallas_call(
        _unpack_kernel,
        grid=(rows_pad // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANE), dtype),
        interpret=interpret,
    )(bf)
    return out.reshape(-1)[:n].reshape(shape)
