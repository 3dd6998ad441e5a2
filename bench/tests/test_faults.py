"""A run with the timed path broken underneath must come out not correct.

Each test skips only the harness's look for a chip (it passes the CPU
device and toy-width files) and drives the rest of a run through
`run.execute`, with one fault planted in the program:
- serving: a token altered where it is produced;
- training: a step that returns its state unchanged; half of the batch
  left out, the mean taken over the rest; and, on four devices, the
  gradient exchange between the data shards left out.
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from bench import run
from bench.common import ROOT, SRC
from bench.tests import tiny

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[:1]


def _train(cpu, **kw):
    return run.execute(tiny.train_cell(**kw), tiny.CONFIG, tiny.LM_MIX, SEED,
                       1.0, 0, platform="cpu", devices=cpu, bench=tiny.BENCH,
                       cell="tiny-train")


def test_sound_runs_are_correct(cpu):
    assert _train(cpu)["correct"]
    out = run.execute(tiny.SERVE, tiny.CONFIG, tiny.SERVE_MIX, SEED, 2.0, 0,
                      platform="cpu", devices=cpu, bench=tiny.BENCH,
                      cell="tiny-serve")
    assert out["correct"], out


def test_altered_token_fails(cpu, monkeypatch):
    from repro.serving.engine import ServeEngine
    orig = ServeEngine._consume

    def consume(self, slot, tok):
        if len(self.pool.generated[slot]) == 2:
            tok = (tok + 1) % tiny.CONFIG["vocab_size"]
        return orig(self, slot, tok)

    monkeypatch.setattr(ServeEngine, "_consume", consume)
    out = run.execute(tiny.SERVE, tiny.CONFIG, tiny.SERVE_MIX, SEED, 2.0, 0,
                      platform="cpu", devices=cpu, bench=tiny.BENCH,
                      cell="tiny-serve")
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_unchanged_state_fails(cpu, monkeypatch):
    from repro.launch import steps
    real = steps.make_train_step

    def make(cfg, opt, compress_grads=False):
        inner = real(cfg, opt, compress_grads)

        def step(params, opt_state, batch, *a):
            _, _, metrics = inner(params, opt_state, batch, *a)
            return params, opt_state, metrics
        return step

    monkeypatch.setattr(steps, "make_train_step", make)
    out = _train(cpu)
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] > 0.9


def test_half_batch_fails(cpu, monkeypatch):
    from repro.launch import steps
    real = steps.make_train_step

    def make(cfg, opt, compress_grads=False):
        inner = real(cfg, opt, compress_grads)

        def step(params, opt_state, batch, *a):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return inner(params, opt_state, half, *a)
        return step

    monkeypatch.setattr(steps, "make_train_step", make)
    out = _train(cpu)
    assert not out["correct"]


EXCHANGE = textwrap.dedent("""
    import sys
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    sys.path.insert(0, {root!r})
    from bench import run
    from bench.tests import tiny
    from repro.core import sharding as SH
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as MD
    from repro.optim.optimizers import clip_by_global_norm

    def make(cfg, opt, compress_grads=False):
        def step(params, opt_state, batch):
            mesh = make_host_mesh(2, 2)

            def local(p, b):
                with SH.axis_env(SH.AxisEnv()):  # nothing sharded inside
                    return jax.value_and_grad(MD.lm_loss)(p, cfg, b)

            # each data shard's own gradient, never exchanged
            loss, grads = jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P("data")),
                out_specs=(P(), P()), check_vma=False)(params, batch)
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            p, o = opt.update(grads, opt_state, params)
            return p, o, {{"loss": loss, "gnorm": gnorm}}
        return step

    steps.make_train_step = make if {fault} else steps.make_train_step
    out = run.execute(tiny.train_cell(chips=4, mesh=(2, 2), env="fsdp"),
                      tiny.CONFIG, tiny.LM_MIX, {seed}, 1.0, 0,
                      platform="cpu", bench=tiny.BENCH, cell="tiny-train")
    print("CORRECT", out["correct"])
""")


@pytest.mark.parametrize("fault", [False, True])
def test_exchange_left_out_fails_on_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC))
    code = EXCHANGE.format(root=str(ROOT), fault=fault, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert f"CORRECT {not fault}" in p.stdout, p.stdout[-2000:]
