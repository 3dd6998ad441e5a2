"""Subprocess worker for tests/test_parallelism.py.

Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
parent test BEFORE python starts) and verifies on a real 8-device mesh:

  dp:    train step under data parallelism == single-device step
  tp:    forward/loss under tensor parallelism == single-device
  fsdp:  ZeRO param+opt sharding == single-device step
  pp:    GPipe pipeline_apply == sequential scan (fwd + grad)
  smdp:  shard_map psum data-parallel == vmap mean semantics

Prints "OK <name>" per check; the parent asserts on them.
"""
import os
import sys

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", ""), "worker must run with 8 host devices"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import sharding as SH
from repro.core.pipeline import pipeline_apply, sequential_apply
from repro.launch.mesh import make_mesh
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.optim.optimizers import get_optimizer

assert jax.device_count() == 8, jax.device_count()

CFG = ModelConfig(name="tiny", arch_type="dense", num_layers=2,
                  d_model=128, num_heads=8, num_kv_heads=4, d_ff=256,
                  vocab_size=512, param_dtype="float32",
                  compute_dtype="float32", remat="none")
B, S = 8, 32
KEY = jax.random.PRNGKey(0)


def batch():
    kt, kl = jax.random.split(jax.random.PRNGKey(1))
    return {"tokens": jax.random.randint(kt, (B, S), 0, CFG.vocab_size),
            "labels": jax.random.randint(kl, (B, S), 0, CFG.vocab_size)}


def single_device_step():
    params = MD.init_model(CFG, KEY)
    opt = get_optimizer("adamw", lambda s: 1e-2)
    st = opt.init(params)

    def step(params, st, b):
        loss, g = jax.value_and_grad(MD.lm_loss)(params, CFG, b)
        p2, st2 = opt.update(g, st, params)
        return p2, st2, loss, g

    p2, st2, loss, g = jax.jit(step)(params, st, batch())
    return params, p2, float(loss), g


P0, P1, LOSS0, G0 = single_device_step()


def check(name, env, mesh_shape, axis_names):
    mesh = make_mesh(mesh_shape, axis_names)
    opt = get_optimizer("adamw", lambda s: 1e-2)
    with SH.use_mesh(mesh), SH.axis_env(env):
        pspecs = MD.model_pspecs(CFG)
        shardings = jax.tree_util.tree_map(
            lambda p: NamedSharding(mesh, p), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        params = jax.jit(lambda k: MD.init_model(CFG, k),
                         out_shardings=shardings)(KEY)
        st = jax.jit(opt.init)(params)

        def step(params, st, b):
            loss, g = jax.value_and_grad(MD.lm_loss)(params, CFG, b)
            p2, st2 = opt.update(g, st, params)
            return p2, st2, loss, g

        bspec = NamedSharding(mesh, SH.logical("batch", None))
        b = {k: jax.device_put(v, bspec) for k, v in batch().items()}
        p2, st2, loss, g = jax.jit(step)(params, st, b)
        # initial params must be identical to single-device init
        for a, c in zip(jax.tree_util.tree_leaves(P0),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(float(loss), LOSS0, rtol=1e-5)
        # gradients match tightly (collective reassociation only); the
        # post-AdamW params are NOT compared element-wise — 1/sqrt(nu)
        # amplifies ~1e-8 grad noise unboundedly where nu ~ 0
        for a, c in zip(jax.tree_util.tree_leaves(G0),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=1e-4, atol=1e-6)
        # params move in lockstep in aggregate
        num = sum(float(jnp.sum((a.astype(jnp.float32) -
                                 np.asarray(c, np.float32)) ** 2))
                  for a, c in zip(jax.tree_util.tree_leaves(P1),
                                  jax.tree_util.tree_leaves(p2)))
        den = sum(float(jnp.sum(jnp.square(a.astype(jnp.float32))))
                  for a in jax.tree_util.tree_leaves(P1))
        assert num / den < 1e-9, (name, num / den)
    print(f"OK {name}", flush=True)


check("dp", SH.DP_ENV, (8, 1), ("data", "model"))
check("tp", SH.DP_TP_ENV, (1, 8), ("data", "model"))
check("dp_tp", SH.DP_TP_ENV, (4, 2), ("data", "model"))
check("fsdp", SH.TRAIN_ENV, (4, 2), ("data", "model"))


# ---------------------------------------------------------------------------
# pipeline parallelism == sequential (fwd + grad)
# ---------------------------------------------------------------------------
def block_fn(lp, h):
    return jnp.tanh(h @ lp["w"] + lp["b"])


L, D = 8, 16
kp = jax.random.PRNGKey(3)
stack = {"w": jax.random.normal(kp, (L, D, D)) * 0.3,
         "b": jnp.zeros((L, D))}
x = jax.random.normal(jax.random.PRNGKey(4), (16, D))
pmesh = make_mesh((8,), ("stage",))

y_seq = sequential_apply(block_fn, stack, x)
y_pp = pipeline_apply(block_fn, stack, x, pmesh, num_microbatches=4)
np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_seq),
                           rtol=1e-5, atol=1e-5)

g_seq = jax.grad(lambda s: jnp.sum(sequential_apply(block_fn, s, x) ** 2))(stack)
g_pp = jax.grad(lambda s: jnp.sum(
    pipeline_apply(block_fn, s, x, pmesh, num_microbatches=4) ** 2))(stack)
for a, c in zip(jax.tree_util.tree_leaves(g_seq),
                jax.tree_util.tree_leaves(g_pp)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-4, atol=1e-5)
print("OK pp", flush=True)


# ---------------------------------------------------------------------------
# shard_map data-parallel: explicit psum == vmap-mean semantics
# ---------------------------------------------------------------------------
mesh8 = make_mesh((8,), ("data",))
W = 8
xw = jax.random.normal(jax.random.PRNGKey(5), (W, 4, D))
w0 = jax.random.normal(jax.random.PRNGKey(6), (D,)) * 0.1
yw = jnp.einsum("wnd,d->wn", xw, jnp.ones((D,)))


def loss_fn(w, xb, yb):
    return jnp.mean((xb @ w - yb) ** 2)


def smap_step(w, xw, yw):
    # w enters sharded, one broadcast row per worker, so its gradient is
    # local; the survey's Fig. 2 all-reduce is the explicit psum below
    # (/W -> worker mean).
    wb = jnp.broadcast_to(w[None], (W,) + w.shape)

    def worker(wb, xb, yb):
        g = jax.grad(loss_fn)(wb[0], xb[0], yb[0])
        return jax.lax.psum(g, "data") / W

    return jax.shard_map(worker, mesh=mesh8,
                         in_specs=(P("data"), P("data"), P("data")),
                         out_specs=P())(wb, xw, yw)


g_sm = smap_step(w0, xw, yw)
g_vm = jax.tree_util.tree_map(
    lambda g: jnp.mean(g, 0),
    jax.vmap(lambda xb, yb: jax.grad(loss_fn)(w0, xb, yb))(xw, yw))
np.testing.assert_allclose(np.asarray(g_sm), np.asarray(g_vm),
                           rtol=1e-5, atol=1e-6)
print("OK smdp", flush=True)

print("ALL_CHECKS_PASSED", flush=True)
