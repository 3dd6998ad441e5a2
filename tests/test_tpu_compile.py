"""The main-path Pallas kernels compile for a TPU v5e chip (Mosaic, not
interpret mode), at qwen3-0.6b widths; so does the paged decode chunk,
whose KV pools the chip's compiler must update in place.

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
from repro.models import model as MD
from repro.serving.engine import ServeProgram
from tests._hlo import pool_traffic

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


def test_paged_chunk_updates_pool_in_place(sds):
    """The serving engine's paged decode chunk at qwen3-0.6b's full depth
    and widths (a small pool): no pool- or layer-shaped copy, slice,
    restack or broadcast; one scatter per KV leaf writes the pool."""
    B, P, n_max, Np = 8, 16, 16, 160
    as_sds = lambda t: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), t)
    cache = as_sds(MD.paged_cache_specs(CFG, B, Np, P))
    row = sds((B,), jnp.int32)
    args = (as_sds(MD.model_abstract(CFG)), cache, sds((B, 1), jnp.int32),
            row, sds((B,), jnp.bool_), row, row, row,
            sds((B, n_max), jnp.int32))
    prog = ServeProgram(CFG, cache_len=n_max * P, page_size=P)
    text = prog.chunk(8).lower(*args).compile().as_text()
    moves, writes = pool_traffic(text, cache["k"].shape)
    assert moves == []
    assert writes == 2
