"""Training cells: the program's jitted train step, set up as the train
launcher sets it up (mesh, axis env, `out_shardings` for weights and
optimizer state, donation), fed by the program's synthetic pipeline inline,
with the loss read back every step, as the launcher's loop does.

Set-up drives the first three steps through that same step and feed and
keeps what the check needs: the three losses, each leaf's norm of the first
gradient (from AdamW's first moment after one step) and each leaf's norm
of the weights' change after three steps.  The window then runs on from
step four.  After the window the plain reference runs the same three steps
on the same rows and weights.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict

import numpy as np

from bench import correct, flops, program
from bench.common import load_reference, peak_bytes


def _slice_norm_fn():
    """Per-leaf norms, with each layer of a stacked (L, ...) leaf its own
    leaf."""
    import jax
    import jax.numpy as jnp

    def f(tree, stacked: bool):
        def one(a):
            a = a.astype(jnp.float32)
            ax = tuple(range(1, a.ndim)) if stacked else None
            return jnp.sqrt(jnp.sum(a * a, axis=ax))
        return jax.tree_util.tree_map(one, tree)
    return jax.jit(f, static_argnums=(1,))


def _named(blocks_norms, top_norms) -> Dict[str, float]:
    import jax
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(
            jax.device_get(blocks_norms))[0]:
        name = ".".join(k.key for k in path)
        for i, x in enumerate(np.asarray(v)):
            out[f"blocks.{i}.{name}"] = float(x)
    for path, v in jax.tree_util.tree_flatten_with_path(
            jax.device_get(top_norms))[0]:
        out[".".join(k.key for k in path)] = float(v)
    return out


def tree_norms(tree, norm_fn) -> Dict[str, float]:
    top = {k: v for k, v in tree.items() if k != "blocks"}
    return _named(norm_fn(tree["blocks"], True), norm_fn(top, False))


def change_norms(params, ref, c, seed, dtype, norm_fn) -> Dict[str, float]:
    """Per-leaf norm of (weights now - weights at the seed), each seed leaf
    made again alone, beside the live state."""
    import jax
    import jax.numpy as jnp
    diff = jax.jit(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32))
    out = {}
    for idx, (path, _, _) in enumerate(ref.leaf_table(c)):
        live = ref._get(params, path)
        w0 = ref.make_leaf(c, seed, idx, dtype, live.sharding)
        d = diff(live, w0)
        del w0
        stacked = path[0] == "blocks"
        n = jax.device_get(norm_fn(d, stacked))
        del d
        name = ".".join(path[1:] if stacked else path)
        if stacked:
            for i, x in enumerate(np.asarray(n)):
                out[f"blocks.{i}.{name}"] = float(x)
        else:
            out[name] = float(n)
    return out


def run(env: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from repro.core import sharding as SH
    from repro.data import make_pipeline
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (batch_abstract, batch_pspecs,
                                    make_train_step, named_tree)
    from repro.launch.train import ENVS
    from repro.models import model as MD
    from repro.optim.optimizers import get_optimizer, warmup_cosine

    c, work, mix = env["config"], env["work"], env["mix"]
    seed, seconds = env["seed"], env["seconds"]
    spans, clock, tracer = env["spans"], env["clock"], env["tracer"]
    ref = load_reference(c["family"])
    hyp = work["optimizer"]
    pdt = jnp.dtype(work["param_dtype"])

    # ---------------- set-up, as launch/train.py:_train does it
    cfg = program.model_config(c, param_dtype=work["param_dtype"],
                               compute_dtype=work["compute_dtype"],
                               remat=work["remat"])
    mesh = make_host_mesh(work["mesh"]["data"], work["mesh"]["model"])
    opt = get_optimizer("adamw", warmup_cosine(hyp["lr"], hyp["warmup"],
                                               hyp["total_steps"]))
    B, S = work["batch"], mix["seq"]
    norm_fn = _slice_norm_fn()
    with SH.use_mesh(mesh), SH.axis_env(ENVS[work["env"]]):
        pspecs = MD.model_pspecs(cfg)
        params = ref.make_weights(c, seed, pdt, named_tree(mesh, pspecs))
        program.check_tree(params, cfg)
        opt_state = jax.jit(
            opt.init,
            out_shardings=named_tree(mesh, opt.state_specs(pspecs)))(params)
        batch_abs = batch_abstract(cfg, B, S)
        bshard = named_tree(mesh, batch_pspecs(cfg, batch_abs))
        step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
        it = iter(make_pipeline(cfg.vocab_size, B, S, seed=seed))
        state = {"p": params, "o": opt_state}
        del params, opt_state

        def one_step():
            d0 = time.perf_counter()
            batch = next(it)
            dev = {k: jax.device_put(v, bshard[k]) for k, v in batch.items()}
            d1 = time.perf_counter()
            state["p"], state["o"], m = step_fn(state["p"], state["o"], dev)
            loss = float(m["loss"])
            s1 = time.perf_counter()
            spans.append(("data", d0, d1))
            spans.append(("step", d1, s1))
            return batch, loss, s1

        losses, rows = [], []
        for k in range(3):
            batch, loss, _ = one_step()
            losses.append(loss)
            rows.append((batch["tokens"], batch["labels"]))
            if k == 0:
                mu = tree_norms(state["o"]["mu"], norm_fn)
                gnorms = {n: v / (1 - hyp["b1"]) for n, v in mu.items()}
        changes = change_norms(state["p"], ref, c, seed, pdt, norm_fn)
        jax.block_until_ready(state["p"])
        spans.clear()

        # ---------------- window
        c0 = clock.compiles
        tracer.start()
        t0 = time.perf_counter()
        setup_s = t0 - env["t_proc0"]
        tend = t0 + seconds
        started, failed = 0, 0
        # whole steps: the window ends with the first step that ends at or
        # after `seconds`, and every step in it counts
        while time.perf_counter() < tend:
            _, loss, t1 = one_step()
            started += 1
            if not math.isfinite(loss):
                failed += 1
        t_end = time.perf_counter()
        summary = tracer.stop(spans, (t0, t_end))
        compiles = clock.compiles - c0

    data_s = sum(e - s for n, s, e in spans if n == "data")

    # ---------------- check the first three steps against the reference
    peak = peak_bytes(env["devices"])
    state.clear()
    del one_step, it, state
    gc.collect()
    t_ref = time.perf_counter()
    rmesh = None
    if len(env["devices"]) > 1:
        rmesh = jax.sharding.Mesh(np.array(env["devices"]), ("x",))
    ref_out = ref.train_reference(c, seed, rows, hyp, "f32", rmesh)
    prog_out = {"losses": losses, "grad_norms": gnorms,
                "change_norms": changes}
    nums = correct.train_numbers(prog_out, ref_out)
    checks = correct.train_checks(nums, work["limits"])
    ok = all(v <= lim for v, lim in checks.values())
    ref_s = time.perf_counter() - t_ref
    controls = {}
    for mode in env.get("controls", ()):
        if mode == "half":  # half of the batch left out, mean over the rest
            out = ref.train_reference(c, seed, rows, hyp, "f32", rmesh,
                                      rows_used=0.5)
        else:               # the reference at a lower precision
            out = ref.train_reference(c, seed, rows, hyp, mode, rmesh)
        controls[mode] = correct.train_numbers(out, ref_out)

    tok = started * B * S
    return {
        "setup_s": setup_s, "window_s": t_end - t0,
        "attempted": started, "failed": failed,
        "correct": ok, "checks": checks, "check_detail": nums,
        "memory_peak_bytes": peak,
        "train_tokens": tok,
        "window_flops": tok * flops.train_flops_per_token(c, S),
        "data_s": data_s,
        "compiles_in_window": compiles,
        "reference_s": ref_s,
        "trace": summary,
        "controls": controls,
    }
