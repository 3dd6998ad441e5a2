"""The main-path Pallas kernels compile for a TPU v5e chip (Mosaic, not
interpret mode), at qwen3-0.6b widths.

Nothing runs: the chip is described (`topologies.get_topology_desc`), not
attached, and each test lowers and compiles one kernel for it.  This finds
what interpret mode cannot — unaligned blocks, over-budget VMEM — before
any chip time is spent.  The topology is described inside a fixture so
that importing this file never loads the TPU library; keep every such
compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as FA
from repro.kernels import nat_compress as NC
from repro.kernels import paged_attention as PA

CFG = get_config("qwen3-0.6b")
HQ, HK, DH = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    # libtpu would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_kernel(fn, *args):
    """Compile for the described chip; the HLO must hold the Mosaic
    kernel (a fallback to interpret mode would inline plain HLO)."""
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_compiles(sds, causal):
    S = 2048
    q = sds((1, S, HQ, DH), BF16)
    kv = sds((1, S, HK, DH), BF16)
    _compiled_kernel(
        lambda q, k, v: FA.flash_attention(q, k, v, causal=causal),
        q, kv, kv)


def test_paged_attention_compiles(sds):
    B, P, n_max = 4, 16, 2048 // 16
    Np = B * n_max
    pool = sds((Np, P, HK, DH), BF16)
    _compiled_kernel(PA.paged_attention, sds((B, HQ, DH), BF16), pool, pool,
                     sds((B, n_max), jnp.int32), sds((B,), jnp.int32))


def test_nc_pack_compiles(sds):
    key = sds((2,), jnp.uint32)
    _compiled_kernel(NC.nc_pack, sds((CFG.d_model, CFG.d_ff), jnp.float32),
                     key)


def test_nc_unpack_compiles(sds):
    _compiled_kernel(NC.nc_unpack, sds((CFG.d_model, CFG.d_ff), jnp.uint8))
