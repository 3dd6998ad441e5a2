"""Serving launcher: static batched serving or continuous batching.

Static (default): a batch of requests is prefilled once (builds the cache),
then decoded token-by-token in lockstep — the whole batch advances behind
one scalar position and retires when its longest request finishes.

Continuous (--continuous): the `repro.serving.ServeEngine` slot pool —
per-request position vectors, active-mask gated cache updates, and FIFO
admission that backfills a slot the moment its request retires, so a
mixed-length request stream sustains near-full batch occupancy.

Elastic fleet (--replicas N): N continuous-batching replicas behind the
straggler-aware router, driven by the same trace-driven membership
machine as elastic training — replica death drains + re-admits in-flight
requests across survivors (`--failure-trace` replays crash / hang /
join / slow events; without one the fleet runs failure-free).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --continuous --requests 16 --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --replicas 3 --requests 24 --batch 2 --failure-trace trace.json
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import sharding as SH
from repro.launch import cli
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import named_tree, sharded_argmax
from repro.models import model as MD
from repro.obs import recorder as obs


def _make_extra(cfg, B):
    if cfg.arch_type == "vlm":
        return jnp.zeros((B, cfg.num_patches, MD.VISION_EMBED_DIM),
                         jnp.dtype(cfg.compute_dtype))
    if cfg.arch_type == "audio":
        return jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                         jnp.dtype(cfg.compute_dtype))
    return None


def make_static_fns(cfg, cache_len, extra=None):
    """Jitted (prefill, decode) pair for the static serve path — also the
    baseline benchmarks/bench_serving.py measures against."""

    @jax.jit
    def prefill(params, tokens):
        logits, _, cache = MD.forward(params, cfg, tokens,
                                      extra_embeds=extra,
                                      return_cache=True,
                                      cache_len=cache_len)
        # sharded_argmax keeps the model-sharded vocab dim sharded: a plain
        # jnp.argmax re-all-gathers full logits every token (steps.py)
        nxt = sharded_argmax(logits[:, -1])[:, None]
        return nxt, cache

    @jax.jit
    def decode(params, tok, pos, cache):
        logits, cache = MD.decode_step(params, cfg, tok, pos, cache)
        nxt = sharded_argmax(logits[:, -1])[:, None]
        return nxt, cache

    return prefill, decode


def _serve_static(params, cfg, args):
    B, S, G = args.batch, args.prompt_len, args.gen
    # the VLM prepends patch embeddings: the cache must hold them too
    cache_len = S + G + (cfg.num_patches if cfg.arch_type == "vlm" else 0)
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (B, S), 0, cfg.vocab_size)
    prefill, decode = make_static_fns(cfg, cache_len, _make_extra(cfg, B))

    t0 = time.time()
    tok, cache = prefill(params, prompts)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    out = [tok]
    t0 = time.time()
    for i in range(G - 1):
        # VLM caches include the patch prefix before the prompt tokens
        pos = S + i + (cfg.num_patches if cfg.arch_type == "vlm" else 0)
        tok, cache = decode(params, tok, jnp.int32(pos), cache)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_decode = time.time() - t0

    gen = np.asarray(jnp.concatenate(out, axis=1))
    tput = B * (G - 1) / max(t_decode, 1e-9)
    print(f"arch={cfg.name} B={B} prompt={S} gen={G}")
    print(f"prefill: {t_prefill:.3f}s   decode: {t_decode:.3f}s "
          f"({tput:.1f} tok/s incl. compile)")
    print("sample generation (first request):", gen[0, :16].tolist())
    return {"generated": gen, "t_prefill": t_prefill, "t_decode": t_decode}


def _serve_continuous(params, cfg, args):
    from repro.serving import ServeEngine
    from repro.serving.speculative import (LookupDraft, ModelDraft,
                                           SpecDecodeEngine)

    # drawn lengths never exceed the CLI bounds: cache_len = S + G must
    # hold the longest prompt plus the largest generation budget
    S, G = args.prompt_len, args.gen
    reqs = make_stream(cfg, args)
    n_prefix = cfg.num_patches if cfg.arch_type == "vlm" else 0
    cache_len = S + G + n_prefix
    paged = dict(page_size=args.page_size,
                 num_pages=args.num_pages) if args.paged else {}
    if args.speculative:
        if args.draft_arch:
            dcfg = get_config(args.draft_arch, smoke=args.smoke)
            if jax.default_backend() == "cpu":
                dcfg = dcfg.with_(param_dtype="float32",
                                  compute_dtype="float32")
            dparams = jax.jit(lambda k: MD.init_model(dcfg, k))(
                jax.random.PRNGKey(args.seed + 7))
            draft = ModelDraft(dparams, dcfg)
        else:
            draft = LookupDraft()
        engine = SpecDecodeEngine(params, cfg, num_slots=args.batch,
                                  cache_len=cache_len + args.spec_k,
                                  draft=draft, spec_k=args.spec_k, **paged)
    else:
        engine = ServeEngine(params, cfg, num_slots=args.batch,
                             cache_len=cache_len, **paged)

    t0 = time.time()
    finished = engine.run(reqs)
    dt = time.time() - t0
    st = engine.stats()
    tput = st["generated_tokens"] / max(dt, 1e-9)
    print(f"arch={cfg.name} slots={args.batch} requests={args.requests} "
          f"prompt<=~{S} gen<={G}")
    print(f"continuous: {dt:.3f}s  {st['generated_tokens']} tokens "
          f"({tput:.1f} tok/s incl. compile)  "
          f"occupancy={st['occupancy']:.2f}  "
          f"ticks={st['ticks']} (prefill {st['prefill_ticks']}, "
          f"decode {st['decode_ticks']})")
    # the host's side of the engine: chunk dispatches, every harvested
    # token, and the host seconds of each `serve.<part>` span
    print(f"host: chunks={st['decode_chunks']} "
          f"emitted={st['emitted_tokens']} " + " ".join(
              f"{p}={st[f'host_{p}_s']:.3f}s" for p in
              ("coverage", "dispatch", "wait", "harvest", "prefill")))
    if args.paged:
        print(f"paged: page_size={engine.page_size} "
              f"pages={engine.num_pages} "
              f"pool_occupancy={st['pool_occupancy']:.2f} "
              f"preemptions={st['preemptions']}")
    if args.speculative:
        print(f"speculative: k={args.spec_k} "
              f"draft={'model:' + args.draft_arch if args.draft_arch else 'lookup'} "
              f"rounds={st['spec_rounds']} "
              f"accept_rate={st['accept_rate']:.2f} "
              f"tokens/round={st['tokens_per_round']:.2f}")
    print("sample generation (first request):",
          finished[0].tokens[:16])
    return {"finished": finished, "stats": st, "t_total": dt}


def make_stream(cfg, args):
    """Deterministic mixed-length request stream shared by the continuous
    and fleet paths."""
    from repro.serving import Request

    rng = np.random.RandomState(args.seed + 1)
    S, G = args.prompt_len, args.gen
    plens = sorted({min(S, max(1, S // 2)), min(S, max(1, 3 * S // 4)), S})
    gens = sorted({max(1, G // 4), max(1, G // 2), G})
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=int(rng.choice(plens))),
                    max_new_tokens=int(rng.choice(gens)))
            for i in range(args.requests)]
    if cfg.arch_type in ("vlm", "audio"):
        for r in reqs:
            r.extra_embeds = _make_extra(cfg, 1)
    return reqs


def _serve_fleet(params, cfg, args):
    from repro.serving import ServeFleet

    trace = cli.load_failure_trace(args)
    transport = (cli.make_transport(args, trace)
                 if args.transport == "proc" else None)
    n_prefix = cfg.num_patches if cfg.arch_type == "vlm" else 0
    fleet = ServeFleet(params, cfg, replicas=args.replicas,
                       num_slots=args.batch,
                       cache_len=args.prompt_len + args.gen + n_prefix,
                       trace=None if transport else trace,
                       transport=transport,
                       page_size=args.page_size if args.paged else None,
                       num_pages=args.num_pages if args.paged else None,
                       hedged_decode=args.hedged)
    reqs = make_stream(cfg, args)
    t0 = time.time()
    try:
        finished = fleet.run(reqs)
    finally:
        fleet.close()
    dt = time.time() - t0
    st = fleet.stats()
    print(f"arch={cfg.name} replicas={args.replicas} slots={args.batch} "
          f"requests={args.requests} trace="
          f"{args.failure_trace or '<failure-free>'}")
    print(f"fleet: {dt:.3f}s wall={st['wall']} ticks  "
          f"{st['delivered_tokens']} tokens  "
          f"goodput={st['goodput']:.2f} tok/wall-tick  "
          f"drains={st['drains']} readmitted={st['readmitted']}  "
          f"survivors={st['replicas']}")
    print(f"routing: {st['routed']}")
    print("sample generation (first request):", finished[0].tokens[:16])
    return {"finished": finished, "stats": st, "t_total": dt}


def serve(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="static: batch size; continuous: pool slots")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a slot pool "
                         "(repro.serving.ServeEngine)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="elastic fleet of N continuous-batching replicas "
                         "(repro.serving.ServeFleet); --batch = slots per "
                         "replica")
    ap.add_argument("--requests", type=int, default=16,
                    help="--continuous/--replicas: requests in the stream")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache pool: slots share fixed-size "
                         "pages instead of reserving max-length rows")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--paged: tokens per KV page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="--paged: pool pages (default: worst-case "
                         "slots x ceil(cache_len/page_size))")
    ap.add_argument("--speculative", action="store_true",
                    help="--continuous: draft-verify decoding "
                         "(repro.serving.speculative); bit-identical "
                         "output, fewer target dispatches")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="--speculative: draft tokens per round")
    ap.add_argument("--draft-arch", default=None,
                    help="--speculative: config-zoo arch drafting for "
                         "--arch (e.g. qwen3-0.6b for qwen3-1.7b); "
                         "default: model-free n-gram lookup draft")
    ap.add_argument("--hedged", action="store_true",
                    help="--replicas: hedged decode — SUSPECT replicas "
                         "keep serving while a speculative continuation "
                         "races them on a healthy replica "
                         "(first-token-wins)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    cli.add_cluster_args(ap, context="--replicas")
    cli.add_trace_args(ap)
    args = ap.parse_args(argv)

    return cli.run_traced(args, lambda: _serve(args))


def _serve(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    if jax.default_backend() == "cpu":
        cfg = cfg.with_(param_dtype="float32", compute_dtype="float32")

    mesh = make_host_mesh(args.data, args.model)
    with SH.use_mesh(mesh), SH.axis_env(SH.DP_TP_ENV):
        # weights land sharded over the mesh as they are made, never
        # whole on one device
        params = jax.jit(
            lambda k: MD.init_model(cfg, k),
            out_shardings=named_tree(mesh, MD.model_pspecs(cfg)),
        )(jax.random.PRNGKey(args.seed))
        if args.replicas:
            out = _serve_fleet(params, cfg, args)
        elif args.continuous:
            out = _serve_continuous(params, cfg, args)
        else:
            out = _serve_static(params, cfg, args)
    return {**out, "params": params}


if __name__ == "__main__":
    from repro.obs import log as _log
    _log.configure()  # CLI runs show [info] progress; library use stays quiet
    cli.use_compile_cache()
    serve()
