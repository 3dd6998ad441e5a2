"""Training launcher.

Drives any registered architecture (``--arch``, ``--smoke`` for the
reduced variant) on the active device set: 1 CPU device for local runs,
a host mesh for multi-device CPU integration (set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE launching),
or the production TPU mesh.

The survey's parallelism taxonomy is selected by ``--env``:
  dp       data parallelism only
  dp_tp    hybrid data x tensor (production default)
  tp       model/tensor parallelism only
  fsdp     dp_tp + ZeRO param/optimizer sharding

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 100 --batch 8 --seq 256 --data 1 --model 1
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.configs import get_config
from repro.core import sharding as SH
from repro.data import make_pipeline
from repro.launch import cli
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (batch_abstract, batch_pspecs,
                                make_train_step, named_tree)
from repro.models import model as MD
from repro.obs.spans import span
from repro.optim.optimizers import get_optimizer, warmup_cosine

ENVS = {
    "dp": SH.DP_ENV,
    "dp_tp": SH.DP_TP_ENV,
    "tp": SH.TP_ENV,
    "fsdp": SH.TRAIN_ENV,
}


def train(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--env", default="dp_tp", choices=list(ENVS))
    ap.add_argument("--data", type=int, default=1, help="data mesh dim")
    ap.add_argument("--model", type=int, default=1, help="model mesh dim")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true",
                    help="natural compression on gradients (survey ref 75)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic training: survive worker death/join/"
                         "slowdown from a failure trace (repro.elastic)")
    cli.add_cluster_args(ap, context="--elastic", workers=4,
                         workers_help="logical data-parallel workers "
                                      "for --elastic")
    cli.add_trace_args(ap)
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "local_sgd", "easgd", "async_ps",
                             "ssp"],
                    help="--elastic training mode (repro.elastic.modes): "
                         "sync all-reduce with checkpoint/rewind recovery "
                         "(default); local_sgd/easgd per-worker replicas "
                         "with survivor continuation; async_ps/ssp "
                         "parameter-server push/pull on the cluster "
                         "transport")
    ap.add_argument("--staleness", type=int, default=2,
                    help="--mode=ssp staleness bound s: a worker may run "
                         "at most s clocks ahead of the slowest")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint retention for --elastic")
    ap.add_argument("--async-ckpt", dest="async_ckpt", action="store_true",
                    default=None,
                    help="non-blocking checkpoint saves on a background "
                         "writer (repro.checkpoint.AsyncCheckpointer); "
                         "default: on for --elastic, off otherwise")
    ap.add_argument("--no-async-ckpt", dest="async_ckpt",
                    action="store_false")
    args = ap.parse_args(argv)
    if args.elastic and args.mode == "sync" and not args.ckpt_dir:
        ap.error("--elastic --mode=sync requires --ckpt-dir (sync "
                 "recovery restores from the last checkpoint); other "
                 "modes checkpoint only when --ckpt-dir is given")
    if args.async_ckpt is None:
        # elastic checkpoints every ~10-20 steps: a blocking save there
        # steals a full step from every worker, so async is the default
        args.async_ckpt = args.elastic

    return cli.run_traced(args, lambda: _train(args))


def _train(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    # keep params fp32 on CPU for small-scale training stability
    if jax.default_backend() == "cpu":
        cfg = cfg.with_(param_dtype="float32", compute_dtype="float32")

    mesh = make_host_mesh(args.data, args.model)
    opt = get_optimizer(args.optimizer,
                        warmup_cosine(args.lr, 20, args.steps))

    with SH.use_mesh(mesh), SH.axis_env(ENVS[args.env]):
        pspecs = MD.model_pspecs(cfg)
        params = jax.jit(
            lambda k: MD.init_model(cfg, k),
            out_shardings=named_tree(mesh, pspecs),
        )(jax.random.PRNGKey(args.seed))
        # the moments depend on no value of params, so without
        # out_shardings they would land whole on the default device
        opt_state = jax.jit(
            opt.init, out_shardings=named_tree(mesh, opt.state_specs(pspecs)),
        )(params)

        step0 = 0
        if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            abs_tree = {"params": jax.eval_shape(lambda: params),
                        "opt": jax.eval_shape(lambda: opt_state)}
            tree, meta = restore_checkpoint(args.ckpt_dir, abs_tree)
            params, opt_state = tree["params"], tree["opt"]
            step0 = meta.get("step", 0)
            print(f"resumed from step {step0}")

        batch_abs = batch_abstract(cfg, args.batch, args.seq)
        bspecs = batch_pspecs(cfg, batch_abs)
        bshard = named_tree(mesh, bspecs)
        step_fn = jax.jit(
            make_train_step(cfg, opt, compress_grads=args.compress_grads),
            donate_argnums=(0, 1))

        pipe = make_pipeline(cfg.vocab_size, args.batch, args.seq,
                             seed=args.seed)
        entropy_floor = pipe.source.entropy_nats

        if args.elastic:
            from repro.elastic import elastic_lm_loop
            out = elastic_lm_loop(
                args=args, cfg=cfg, step_fn=step_fn, params=params,
                opt_state=opt_state, bshard=bshard, batch_abs=batch_abs,
                pipe_factory=lambda shard, num: make_pipeline(
                    cfg.vocab_size, args.batch, args.seq,
                    shard_id=shard, num_shards=num, seed=args.seed),
                step0=step0, opt=opt,
                loss_fn=lambda p, b: MD.lm_loss(p, cfg, b))
            return {"losses": out["losses"],
                    "entropy_floor": entropy_floor,
                    "params": out["params"],
                    "recoveries": out["recoveries"],
                    "final_alive": out["final_alive"],
                    "transitions": out["transitions"]}

        saver = (AsyncCheckpointer(args.ckpt_dir)
                 if args.async_ckpt and args.ckpt_dir else None)

        def _save(at_step):
            tree = {"params": params, "opt": opt_state}
            meta = {"step": at_step, "arch": args.arch}
            if saver is not None:
                saver.save(at_step, tree, meta)
            else:
                save_checkpoint(args.ckpt_dir, at_step, tree, meta)

        losses = []
        step_s = []  # train.step seconds since the last log line
        batches = iter(pipe)
        try:
            for i in range(args.steps):
                step = step0 + i
                with span("train.data", cat="train", step=step):
                    batch = next(batches)
                    dev_batch = {k: jax.device_put(v, bshard[k])
                                 for k, v in batch.items()}
                if cfg.arch_type in ("vlm", "audio"):
                    ee = batch_abs["extra_embeds"]
                    dev_batch["extra_embeds"] = jnp.zeros(ee.shape, ee.dtype)
                extra = ((jax.random.PRNGKey(args.seed + 1 + step),)
                         if args.compress_grads else ())
                with span("train.step", cat="train", step=step) as s:
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         dev_batch, *extra)
                    loss = float(metrics["loss"])
                step_s.append(s.seconds)
                losses.append(loss)
                if step % args.log_every == 0:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"(floor~{entropy_floor:.3f}) "
                          f"gnorm {float(metrics['gnorm']):.3f} "
                          f"{sum(step_s) / len(step_s):.2f}s/step",
                          flush=True)
                    step_s.clear()
                if (args.ckpt_dir and args.ckpt_every
                        and (step + 1) % args.ckpt_every == 0):
                    _save(step + 1)

            if args.ckpt_dir:
                _save(step0 + args.steps)
            if saver is not None:
                saver.wait()  # barrier: the final save is durable on return
        finally:
            if saver is not None:
                saver.close(wait=False)  # never leak the writer thread

    return {"losses": losses, "entropy_floor": entropy_floor,
            "params": params}


if __name__ == "__main__":
    from repro.obs import log as _log
    _log.configure()  # CLI runs show [info] progress; library use stays quiet
    cli.use_compile_cache()
    train()
