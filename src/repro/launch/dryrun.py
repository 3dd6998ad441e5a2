import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, record memory/cost/collective analysis for the roofline.

The two lines above MUST run before any jax import: jax locks the device
count at first init.  Do not set this flag globally — smoke tests and
benches must see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single --out benchmarks/results
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, shape_plan
from repro.core import sharding as SH
from repro.core.roofline import analyze, model_flops
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_plan, lower_plan


def env_for(kind: str, sp: bool = False) -> SH.AxisEnv:
    # training uses ZeRO/FSDP param+optimizer sharding; serving shards params
    # on the model axis only (weights must be resident per decode step).
    # sp=True adds Megatron-SP sequence sharding of the residual stream
    # (the beyond-paper optimized variant; EXPERIMENTS.md §Perf).
    if kind == "train":
        return SH.TRAIN_SP_ENV if sp else SH.TRAIN_ENV
    return SH.DP_TP_SP_ENV if sp else SH.DP_TP_ENV


def _compile(cfg, shape, mesh, optimizer):
    plan = build_plan(cfg, shape, mesh, optimizer=optimizer)
    return lower_plan(plan).compile()


def _cost_point(cfg, shape, mesh, mesh_name, optimizer, layers):
    """Compile a reduced-depth fully-unrolled variant and return its roofline
    measurements (XLA's HloCostAnalysis counts a while-loop body once, so the
    full-depth scan compile cannot be used for FLOPs/collectives)."""
    c = _compile(cfg.with_(num_layers=layers, unroll_layers=True),
                 shape, mesh, optimizer)
    return analyze(c, cfg.name, shape.name, mesh_name, chips=mesh.size,
                   mflops=0.0)


def run_one(arch: str, shape_name: str, mesh, mesh_name: str,
            optimizer: str = "adamw", sp: bool = False, q_chunk: int = 0,
            moe_groups: int = 0):
    shape = SHAPES[shape_name]
    cfg = shape_plan(arch, shape_name)
    if cfg is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped",
                "reason": "full-attention long-context (see DESIGN.md)"}
    cfg = cfg.with_(param_dtype="bfloat16", compute_dtype="bfloat16")
    if q_chunk:
        cfg = cfg.with_(attn_q_chunk=q_chunk)
    if moe_groups:
        cfg = cfg.with_(moe_groups=moe_groups)
    t0 = time.time()
    with SH.use_mesh(mesh), SH.axis_env(env_for(shape.kind, sp)):
        # 1) full-depth compile (scan over layers): proves the production
        #    config lowers, partitions, and fits (memory_analysis).
        compiled = _compile(cfg, shape, mesh, optimizer)
        mem = compiled.memory_analysis()

        # 2) cost model: two reduced-depth unrolled compiles -> per-layer
        #    delta -> extrapolate to full depth (exact for homogeneous
        #    stacks; ~5% high for zamba2's shared-block cadence 38 vs 36).
        la = cfg.hybrid_attn_every if cfg.arch_type == "hybrid" else 2
        lb = 2 * la
        ra = _cost_point(cfg, shape, mesh, mesh_name, optimizer, la)
        rb = _cost_point(cfg, shape, mesh, mesh_name, optimizer, lb)
        L = cfg.num_layers

        def extrap(a, b):
            return a + (b - a) / (lb - la) * (L - la)

        flops = extrap(ra.flops_per_chip, rb.flops_per_chip)
        byts = extrap(ra.bytes_per_chip, rb.bytes_per_chip)
        coll = extrap(ra.coll_bytes_per_chip, rb.coll_bytes_per_chip)
        by_op = {k: int(extrap(ra.coll_by_op[k], rb.coll_by_op[k]))
                 for k in ra.coll_by_op}

        from repro.core.roofline import Roofline
        rf = Roofline(arch=arch, shape=shape_name, mesh=mesh_name,
                      chips=mesh.size, flops_per_chip=flops,
                      bytes_per_chip=byts, coll_bytes_per_chip=coll,
                      coll_by_op=by_op,
                      model_flops_total=model_flops(
                          cfg, shape.seq_len, shape.global_batch, shape.kind))
    dt = time.time() - t0
    rec = rf.to_dict()
    rec.update(status="ok", compile_s=round(dt, 1),
               argument_bytes=int(mem.argument_size_in_bytes),
               output_bytes=int(mem.output_size_in_bytes),
               temp_bytes=int(mem.temp_size_in_bytes),
               cost_method=f"extrapolated L={la},{lb}->{L}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--out", default="benchmarks/results")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="Megatron-SP sequence sharding (optimized variant)")
    ap.add_argument("--q-chunk", type=int, default=0,
                    help="flash-style q-chunked attention block size")
    ap.add_argument("--moe-groups", type=int, default=0,
                    help="MoE routing groups (1 = survey-era global baseline)")
    ap.add_argument("--tag", default="",
                    help="suffix for the results file (e.g. '_opt')")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    for multi in meshes:
        mesh_name = "2x16x16" if multi else "16x16"
        mesh = make_production_mesh(multi_pod=multi)
        path = outdir / f"dryrun_{mesh_name}{args.tag}.json"
        results = json.loads(path.read_text()) if path.exists() else {}
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch}|{shape_name}"
                if args.skip_existing and key in results and \
                        results[key].get("status") in ("ok", "skipped"):
                    continue
                try:
                    rec = run_one(arch, shape_name, mesh, mesh_name,
                                  args.optimizer, sp=args.sp,
                                  q_chunk=args.q_chunk,
                                  moe_groups=args.moe_groups)
                except Exception as e:  # a failure here is a bug in the system
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "FAIL",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                results[key] = rec
                path.write_text(json.dumps(results, indent=1))
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    extra = (f"bottleneck={rec['bottleneck']} "
                             f"tc={rec['t_compute']:.2e} tm={rec['t_memory']:.2e} "
                             f"tx={rec['t_collective']:.2e} "
                             f"useful={rec['useful_ratio']:.2f} "
                             f"compile={rec['compile_s']}s")
                elif status == "FAIL":
                    extra = rec["error"][:160]
                print(f"[{mesh_name}] {arch} x {shape_name}: {status} {extra}",
                      flush=True)
    print("dry-run complete")


if __name__ == "__main__":
    from repro.launch import cli
    cli.use_compile_cache()
    main()
