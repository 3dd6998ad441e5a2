"""Readings that set the limits of `correct`: the program's, and the
controls', over many seeds in one process.  The benchmark's own runs never
run this.

    python bench/control.py --workload <cell> --seeds 1,2,3 \
        [--controls bf16,half] [--control-seeds 1,2,3] [--seconds 30]

Training cells need no window (`--seconds 0`): set-up drives the first
three steps, and the reference compares them.  Serving cells run the
cell's own window and load, then compare the program's sample and the
control's.  Controls: `bf16`, `fp8` (the reference at that precision in
the program's place), `half` (training: half of the batch left out),
`exchange` (training on a mesh: the reference with no gradient exchange
between the cell's data shards), `altered` (serving: one served token
changed where produced).  One JSON line per seed.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def readings(work, config, mix, seed, seconds, controls, *, platform="tpu",
             devices=None):
    from bench import peaks
    devices = devices or common.require_devices(work["chips"], platform)
    env = {"config": config, "work": work, "mix": mix, "seed": seed,
           "seconds": seconds, "devices": devices,
           "clock": common.CompileClock(), "spans": [],
           "tracer": common.Tracer(False), "t_proc0": time.perf_counter(),
           "peaks": peaks.PEAKS["TPU v5 lite"], "controls": controls}
    ctx = common.load_driver(work["driver"]).run(env)
    out = {"seed": seed, "correct": ctx["correct"],
           "program": {k: v for k, (v, _) in ctx["checks"].items()},
           "controls": {m: ({k: v for k, (v, _) in c.items()}
                            if "loss_gap" not in c else c)
                        for m, c in ctx["controls"].items()},
           "memory_peak_bytes": ctx["memory_peak_bytes"]}
    if "check_detail" in ctx:
        out["detail"] = ctx["check_detail"]
    del ctx
    gc.collect()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default=None,
                    help="seeds that also read the controls (default all)")
    ap.add_argument("--seconds", type=float, default=0.0)
    a = ap.parse_args(argv)
    work = common.load_json("workloads", a.workload)
    work["name"] = a.workload
    config = common.load_json("configs", work["config"])
    mix = common.load_json("traffic", work["traffic"])
    common.require_devices(work["chips"])
    common.use_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",")]
    cseeds = ([int(s) for s in a.control_seeds.split(",")]
              if a.control_seeds else seeds)
    controls = [c for c in a.controls.split(",") if c]
    for s in seeds:
        t = time.perf_counter()
        out = readings(work, config, mix, s, a.seconds,
                       controls if s in cseeds else [])
        out["wall_s"] = time.perf_counter() - t
        print(json.dumps(out, default=str), flush=True)


if __name__ == "__main__":
    main()
