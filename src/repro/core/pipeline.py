"""Pipeline parallelism (survey §Pipelining parallelism, GPipe-style).

TPU-native adaptation: stages are a mesh axis; activations move between
stages with `jax.lax.ppermute` inside `shard_map` (point-to-point on the ICI
torus / DCN across pods).  The schedule is synchronous microbatching
(GPipe / torchgpipe): M microbatches flow through S stages in M+S-1 ticks,
bubble fraction (S-1)/(M+S-1).  PipeDream's asynchronous weight stashing is
deliberately NOT reproduced (staleness-free training is the TPU-world norm;
see DESIGN.md §7) — its *schedule* benefit (overlap) is what ppermute gives.

Differentiable end-to-end: grad of ppermute is the reverse ppermute, so
`jax.grad` through `pipeline_apply` yields pipeline-parallel backprop with
the same bubble structure.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_apply(block_fn: Callable, stacked_params: Any, x: jax.Array,
                   mesh: Mesh, *, axis: str = "stage",
                   num_microbatches: int = 8) -> jax.Array:
    """Run `block_fn` stacks over `x` with GPipe pipelining.

    block_fn(layer_params, h) -> h, applied over a stack of L layers.
    stacked_params: pytree with leading layer dim L (L % num_stages == 0);
    layers are assigned contiguously to stages.
    x: (B, ...) with B % num_microbatches == 0.

    Returns block-stack output, numerically identical to the sequential
    application (tests/test_parallelism.py asserts this).
    """
    S = mesh.shape[axis]
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M

    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert L % S == 0, f"layers {L} not divisible by stages {S}"
    per_stage = L // S
    # reshape (L, ...) -> (S, per_stage, ...); shard_map slices dim 0
    staged = jax.tree_util.tree_map(
        lambda p: p.reshape((S, per_stage) + p.shape[1:]), stacked_params)

    pspec_params = jax.tree_util.tree_map(
        lambda _: P(axis), staged)

    def stage_fn(params_s, x_all):
        # params_s: (1, per_stage, ...) local slice; x_all: full batch
        # (replicated input; only stage 0 consumes it).
        params_s = jax.tree_util.tree_map(lambda p: p[0], params_s)
        idx = jax.lax.axis_index(axis)
        xs = x_all.reshape((M, mb) + x_all.shape[1:])

        def local_stack(h):
            def body(h, lp):
                return block_fn(lp, h), None
            h, _ = jax.lax.scan(body, h, params_s)
            return h

        state = jnp.zeros((mb,) + x_all.shape[1:], x_all.dtype)
        outputs = jnp.zeros_like(xs)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            state, outputs = carry
            # feed microbatch t at stage 0 (zeros elsewhere / after drain)
            feed = jnp.where(t < M, 1, 0).astype(x_all.dtype)
            x_t = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, M - 1), 0, keepdims=False) * feed
            inp = jnp.where(idx == 0, x_t, state)
            out = local_stack(inp)
            # last stage writes its finished microbatch t-(S-1)
            done = t - (S - 1)
            write = jnp.logical_and(idx == S - 1, done >= 0)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(done, 0), 0),
                lambda o: o, outputs)
            # rotate activations to the next stage
            state = jax.lax.ppermute(out, axis, perm)
            return state, outputs

        state, outputs = jax.lax.fori_loop(
            0, M + S - 1, tick, (state, outputs))
        # bring final outputs (resident on the last stage) to all stages
        outputs = jax.lax.psum(
            outputs * jnp.where(idx == S - 1, 1, 0).astype(outputs.dtype),
            axis)
        return outputs.reshape((B,) + x_all.shape[1:])

    fn = jax.shard_map(stage_fn, mesh=mesh,
                       in_specs=(pspec_params, P()),
                       out_specs=P(),
                       check_vma=False)
    return fn(staged, x)


def sequential_apply(block_fn: Callable, stacked_params: Any,
                     x: jax.Array) -> jax.Array:
    """Reference: plain scan over the full stack (no pipeline)."""
    def body(h, lp):
        return block_fn(lp, h), None
    h, _ = jax.lax.scan(body, x, stacked_params)
    return h
