"""Step builders: train / prefill / serve steps with full sharding specs,
plus abstract input specs (ShapeDtypeStruct) for AOT lowering (the dry-run
never allocates real arrays for the production configs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import InputShape
from repro.core import sharding as SH
from repro.core.compression import natural_compress
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.optim.optimizers import clip_by_global_norm, get_optimizer, warmup_cosine


# ---------------------------------------------------------------------------
# Cache sharding specs
# ---------------------------------------------------------------------------
def _kv_cache_names(cfg: ModelConfig) -> tuple:
    """KV cache (L,B,C,Hk,dh) sharding: heads on the model axis when they
    divide it; otherwise CONTEXT-SHARD the cache length C.  A non-divisible
    head dim used to fall back to a replicated cache, which GSPMD then
    re-all-gathered every decode step (the whole 32k cache per token —
    EXPERIMENTS.md §Perf, decode iteration)."""
    shards = SH.axis_size(SH.get_axis_env().resolve("model"))
    if shards <= 1 or cfg.num_kv_heads % shards == 0:
        return ("layers", "batch", None, "model", None)
    return ("layers", "batch", "model", None, None)


def _cache_spec_names(cfg: ModelConfig) -> Dict[str, Any]:
    at = cfg.arch_type
    kv = _kv_cache_names(cfg)
    if at in ("dense", "vlm", "moe", "audio"):
        names = {"k": kv, "v": kv}
        if at == "audio":
            names["ck"] = kv
            names["cv"] = kv
        return names
    if at == "hybrid":
        return {"ssm": ("layers", "batch", "model", None, None),
                "conv": {"x": ("layers", "batch", None, "model"),
                         "B": ("layers", "batch", None, None),
                         "C": ("layers", "batch", None, None)},
                "sk": kv, "sv": kv}
    if at == "ssm":
        return {"wkv": ("layers", "batch", "model", None, None),
                "tm": ("layers", "batch", None),
                "cm": ("layers", "batch", None)}
    raise ValueError(at)


def cache_pspecs(cfg: ModelConfig, cache_abstract) -> Any:
    names = _cache_spec_names(cfg)

    def f(path, leaf):
        node = names
        for k in path:
            node = node[k.key]
        return SH.resolve_spec(leaf.shape, node)

    return jax.tree_util.tree_map_with_path(f, cache_abstract)


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------
def batch_abstract(cfg: ModelConfig, B: int, S: int, train: bool = True):
    i32 = jnp.int32
    out = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if train:
        out["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    if cfg.arch_type == "vlm":
        out["extra_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, MD.VISION_EMBED_DIM), jnp.bfloat16)
    if cfg.arch_type == "audio":
        out["extra_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return out


def batch_pspecs(cfg: ModelConfig, batch_abs):
    def spec(s):
        names = ("batch",) + (None,) * (len(s.shape) - 1)
        return SH.resolve_spec(s.shape, names)
    return jax.tree_util.tree_map(spec, batch_abs)


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, opt,
                    compress_grads: bool = False) -> Callable:
    """compress_grads: natural-compress gradients before the optimizer —
    the on-device view of putting survey ref 75's compressor on the wire
    (unbiased, so convergence holds; examples/train_lm.py --compress)."""
    def train_step(params, opt_state, batch, *args):
        loss, grads = jax.value_and_grad(MD.lm_loss)(params, cfg, batch)
        if compress_grads:
            if args:
                key = args[0]
            else:
                # no key supplied: fold the optimizer's step counter into a
                # fixed seed so each step draws FRESH compression randomness
                # (a constant key re-uses the same rounding pattern every
                # step, which breaks the unbiasedness argument across steps)
                key = jax.random.fold_in(jax.random.PRNGKey(0),
                                         opt_state["step"])
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            keys = jax.random.split(key, len(leaves))
            grads = jax.tree_util.tree_unflatten(
                treedef, [natural_compress(l, k)
                          for l, k in zip(leaves, keys)])
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            new_params, new_state = opt.update(grads, opt_state, params)
        return new_params, new_state, {"loss": loss, "gnorm": gnorm}
    return train_step


def make_prefill_step(cfg: ModelConfig, cache_len: int) -> Callable:
    def prefill_step(params, batch):
        logits, _, cache = MD.forward(
            params, cfg, batch["tokens"],
            extra_embeds=batch.get("extra_embeds"),
            return_cache=True, cache_len=cache_len)
        return logits[:, -1:], cache
    return prefill_step


def sharded_argmax(logits: jax.Array) -> jax.Array:
    """argmax over the (model-sharded) vocab dim without gathering it.

    jnp.argmax over a sharded axis makes GSPMD all-gather the full logits
    (78 GB/step for a 128-batch 152k-vocab decode — the collective term
    dominated every decode pair, EXPERIMENTS.md §Perf).  Two elementwise
    passes + two scalar-per-row reduces keep the vocab dim sharded:
    cross-shard traffic drops from O(B·V) to O(B)."""
    m = jnp.max(logits, axis=-1, keepdims=True)          # (B,1) reduce
    V = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    cand = jnp.where(logits >= m, iota, V)
    return jnp.min(cand, axis=-1).astype(jnp.int32)      # first max index


def make_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, tokens, pos):
        logits, new_cache = MD.decode_step(params, cfg, tokens, pos, cache)
        nxt = sharded_argmax(logits[:, -1])[:, None]
        return nxt, new_cache
    return serve_step


def make_serve_cb_step(cfg: ModelConfig) -> Callable:
    """Continuous-batching decode tick: one token for EVERY pool slot.

    pos: (B,) per-slot sequence lengths; active: (B,) bool slot liveness.
    Retired slots are no-ops — their cache rows are kept and their token is
    passed through unchanged, so the engine can keep ticking at full batch
    while a slot waits for backfill."""
    def serve_cb_step(params, cache, tokens, pos, active):
        logits, new_cache = MD.decode_step(params, cfg, tokens, pos, cache,
                                           active=active)
        nxt = sharded_argmax(logits[:, -1])[:, None]
        nxt = jnp.where(active[:, None], nxt, tokens)
        return nxt, new_cache
    return serve_cb_step


def make_paged_serve_cb_step(cfg: ModelConfig, logical_len: int) -> Callable:
    """Paged-pool variant of the continuous-batching tick: the cache's KV
    leaves are a shared page pool and each slot reads/writes through its
    block-table row.  logical_len is the dense cache_len the pool replaces
    (static: it bounds the gathered view)."""
    def serve_cb_paged_step(params, cache, tokens, pos, active,
                            block_tables):
        logits, new_cache = MD.decode_step(params, cfg, tokens, pos, cache,
                                           active=active,
                                           block_tables=block_tables,
                                           logical_len=logical_len)
        nxt = sharded_argmax(logits[:, -1])[:, None]
        nxt = jnp.where(active[:, None], nxt, tokens)
        return nxt, new_cache
    return serve_cb_paged_step


# ---------------------------------------------------------------------------
# Lowering plans (used by dryrun.py, train.py, serve.py)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StepPlan:
    name: str
    fn: Callable
    args: Tuple[Any, ...]  # abstract ShapeDtypeStructs
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()


def named_tree(mesh, tree):
    """A pytree of PartitionSpecs -> the same tree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), tree,
        is_leaf=lambda x: isinstance(x, P))


def build_plan(cfg: ModelConfig, shape: InputShape, mesh,
               optimizer: str = "adamw") -> StepPlan:
    """Build the (fn, abstract args, shardings) plan for one arch x shape.

    Must be called under `SH.use_mesh(mesh)` and the desired `SH.axis_env`.
    """
    params_abs = MD.model_abstract(cfg)
    pspecs = MD.model_pspecs(cfg)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        opt = get_optimizer(optimizer, warmup_cosine(3e-4, 100, 10_000))
        opt_state_abs = jax.eval_shape(opt.init, params_abs)
        opt_specs = opt.state_specs(pspecs)
        batch_abs = batch_abstract(cfg, B, S, train=True)
        bspecs = batch_pspecs(cfg, batch_abs)
        scalar = P()
        out_shardings = (named_tree(mesh, pspecs), named_tree(mesh, opt_specs),
                         {"loss": NamedSharding(mesh, scalar),
                          "gnorm": NamedSharding(mesh, scalar)})
        return StepPlan(
            name=f"train[{cfg.name}x{shape.name}]",
            fn=make_train_step(cfg, opt),
            args=(params_abs, opt_state_abs, batch_abs),
            in_shardings=(named_tree(mesh, pspecs),
                          named_tree(mesh, opt_specs),
                          named_tree(mesh, bspecs)),
            out_shardings=out_shardings,
            donate_argnums=(0, 1),
        )

    if shape.kind == "prefill":
        batch_abs = batch_abstract(cfg, B, S, train=False)
        bspecs = batch_pspecs(cfg, batch_abs)
        # the VLM prepends patch embeddings: the cache must hold them too
        S_cache = S + (cfg.num_patches if cfg.arch_type == "vlm" else 0)
        cache_abs = MD.cache_specs(cfg, B, S_cache)
        cspecs = cache_pspecs(cfg, cache_abs)
        logit_spec = SH.resolve_spec((B, 1, cfg.vocab_size),
                                     ("batch", None, "model"))
        return StepPlan(
            name=f"prefill[{cfg.name}x{shape.name}]",
            fn=make_prefill_step(cfg, S_cache),
            args=(params_abs, batch_abs),
            in_shardings=(named_tree(mesh, pspecs), named_tree(mesh, bspecs)),
            out_shardings=(NamedSharding(mesh, logit_spec),
                           named_tree(mesh, cspecs)),
        )

    if shape.kind == "decode":
        cache_abs = MD.cache_specs(cfg, B, S)
        cspecs = cache_pspecs(cfg, cache_abs)
        tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
        tok_spec = SH.resolve_spec((B, 1), ("batch", None))
        return StepPlan(
            name=f"decode[{cfg.name}x{shape.name}]",
            fn=make_serve_step(cfg),
            args=(params_abs, cache_abs, tok_abs, pos_abs),
            in_shardings=(named_tree(mesh, pspecs), named_tree(mesh, cspecs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, tok_spec),
                           named_tree(mesh, cspecs)),
            donate_argnums=(1,),
        )

    if shape.kind == "decode_cb":
        # continuous-batching decode: per-slot position vector + active mask,
        # both sharded like the batch dim (a slot lives on one data shard)
        cache_abs = MD.cache_specs(cfg, B, S)
        cspecs = cache_pspecs(cfg, cache_abs)
        tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
        act_abs = jax.ShapeDtypeStruct((B,), jnp.bool_)
        tok_spec = SH.resolve_spec((B, 1), ("batch", None))
        row_spec = SH.resolve_spec((B,), ("batch",))
        return StepPlan(
            name=f"decode_cb[{cfg.name}x{shape.name}]",
            fn=make_serve_cb_step(cfg),
            args=(params_abs, cache_abs, tok_abs, pos_abs, act_abs),
            in_shardings=(named_tree(mesh, pspecs), named_tree(mesh, cspecs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, row_spec),
                          NamedSharding(mesh, row_spec)),
            out_shardings=(NamedSharding(mesh, tok_spec),
                           named_tree(mesh, cspecs)),
            donate_argnums=(1,),
        )

    raise ValueError(shape.kind)


def lower_plan(plan: StepPlan):
    jitted = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                     out_shardings=plan.out_shardings,
                     donate_argnums=plan.donate_argnums)
    return jitted.lower(*plan.args)
