"""Natural compression (Horvath et al., surveyed as ref 75): unbiased
stochastic rounding of gradients to powers of two.

C_nat(x) rounds |x| to one of the two nearest powers of two, with
probability proportional to the distance — E[C_nat(x)] = x (unbiased), and
the result needs only sign + 8-bit exponent = 9 bits (we pack to int8
exponent + sign bit, a 4x reduction vs fp32 wire format; the paper's
"natural" trick is that no mantissa arithmetic is needed).

Used as a gradient-aggregation hook in the data-parallel trainer
(`repro.core.data_parallel`), compressing worker->aggregator traffic
(and optionally the broadcast back = bidirectional compression).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# int8 wire format: value = sign * 2^(code - _BIAS); code 0 => zero.
_BIAS = 70


def natural_compress(x: jax.Array, key: jax.Array) -> jax.Array:
    """Unbiased stochastic rounding to the nearest powers of two."""
    a = jnp.abs(x).astype(jnp.float32)
    zero = a == 0
    m, e = jnp.frexp(a)  # a = m * 2^e, m in [0.5, 1): a in [lo, 2 lo)
    lo = jnp.ldexp(1.0, e - 1)  # exact, where exp2 of a float is not
    p = 2.0 * m - 1.0  # in [0, 1): prob of rounding UP to 2 lo
    up = jax.random.uniform(key, x.shape) < p
    mag = jnp.where(up, lo * 2.0, lo)
    out = jnp.sign(x).astype(jnp.float32) * jnp.where(zero, 0.0, mag)
    return out.astype(x.dtype)


def nc_pack(x: jax.Array, key: jax.Array) -> jax.Array:
    """Compress to the int8 wire format (sign in bit 7, exponent code)."""
    a = jnp.abs(x).astype(jnp.float32)
    zero = a == 0
    m, e = jnp.frexp(a)
    p = 2.0 * m - 1.0
    up = (jax.random.uniform(key, x.shape) < p).astype(jnp.int32)
    code = jnp.clip(e - 1 + up + _BIAS, 1, 127)
    code = jnp.where(zero, 0, code)
    sign = (x < 0).astype(jnp.int32) << 7
    return (code | sign).astype(jnp.uint8)


def nc_unpack(b: jax.Array, dtype=jnp.float32) -> jax.Array:
    bi = b.astype(jnp.int32)
    sign = jnp.where((bi & 0x80) != 0, -1.0, 1.0)
    code = bi & 0x7F
    mag = jnp.where(code == 0, 0.0, jnp.ldexp(1.0, code - _BIAS))
    return (sign * mag).astype(dtype)


def compress_tree(grads, key) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    keys = jax.random.split(key, len(leaves))
    out = [natural_compress(l, k) for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def wire_bytes(tree, compressed: bool) -> int:
    """Bytes on the wire for one gradient exchange."""
    n = sum(l.size for l in jax.tree_util.tree_leaves(tree))
    return n * (1 if compressed else 4)
