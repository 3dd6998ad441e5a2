"""Pipeline parallelism (paper §Pipelining): bubble fraction vs the
(S-1)/(M+S-1) formula, and the equivalence + wall time of the shard_map
GPipe schedule on an in-process multi-device CPU mesh.

Must run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks.run does this); standalone it degrades to the formula table.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import bubble_fraction, pipeline_apply, sequential_apply
from repro.launch.mesh import make_mesh


def main(argv=None) -> list:
    rows = []
    for S in (2, 4, 8):
        for M in (4, 8, 32, 128):
            rows.append((f"bubble_S{S}_M{M}", bubble_fraction(S, M)))
    print("name,value")
    for r in rows:
        print(f"{r[0]},{r[1]:.4f}")

    if jax.device_count() >= 8:
        L, D, B = 8, 64, 32
        kp = jax.random.PRNGKey(0)
        stack = {"w": jax.random.normal(kp, (L, D, D)) * 0.3,
                 "b": jnp.zeros((L, D))}
        x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        mesh = make_mesh((8,), ("stage",))

        def block_fn(lp, h):
            return jnp.tanh(h @ lp["w"] + lp["b"])

        y_seq = sequential_apply(block_fn, stack, x)
        for M in (4, 8, 16):
            f = jax.jit(lambda s, x: pipeline_apply(
                block_fn, s, x, mesh, num_microbatches=M))
            y = f(stack, x)
            ok = np.allclose(np.asarray(y), np.asarray(y_seq),
                             rtol=1e-5, atol=1e-5)
            y.block_until_ready()
            t0 = time.time()
            for _ in range(10):
                y = f(stack, x)
            y.block_until_ready()
            dt = (time.time() - t0) / 10
            print(f"pipeline_exec_M{M},{1.0 if ok else 0.0} "
                  f"# {dt*1e3:.2f} ms/call, equals sequential: {ok}")
            rows.append((f"pipeline_equals_seq_M{M}", 1.0 if ok else 0.0))
    else:
        print("# single-device process: schedule table only "
              "(benchmarks.run re-executes under an 8-device mesh)")
    return rows


if __name__ == "__main__":
    main()
