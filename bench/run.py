"""One run of one benchmark cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads `bench/workloads/<cell>.json` and what it names, checks that JAX
sees the chips the cell asks for (and exits non-zero, printing no result,
when it does not), sets up, measures for `--seconds`, checks the output
against the plain reference, and prints one JSON line last on stdout:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics
untraced, its per-layer metrics with `--trace 1`), `device`, with `--trace
1` `breakdown`, and last `checks`: each compared number with its limit,
which are also the last lines on stderr.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def execute(work, config, mix, seed, seconds, trace, *, platform="tpu",
            devices=None, bench=None, cell=None, t_proc0=None):
    """Set up, measure and check one run; returns the result line's dict.
    `platform` is the device kind the run must find; tests pass 'cpu' and
    small files of their own."""
    devices = devices or common.require_devices(work["chips"], platform)
    from bench import correct, peaks
    clock = common.CompileClock()
    env = {"config": config, "work": work, "mix": mix, "seed": seed,
           "seconds": seconds, "devices": devices, "clock": clock,
           "spans": [], "tracer": common.Tracer(bool(trace)),
           "t_proc0": T_PROC0 if t_proc0 is None else t_proc0,
           "peaks": peaks.peaks(devices[0].device_kind)
           if platform == "tpu" else peaks.PEAKS["TPU v5 lite"]}
    ctx = common.load_driver(work["driver"]).run(env)
    ctx.update(chips=len(devices), peaks=env["peaks"], config=config,
               work=work, mix=mix)
    bench = bench or common.load_benchmark()
    metrics = {}
    for m in common.cell_metrics(bench, cell or work["name"], bool(trace)):
        v = common.load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(common.device_info(devices),
                  memory_peak_bytes=ctx["memory_peak_bytes"])
    out = {"correct": bool(ctx["correct"]), "attempted": ctx["attempted"],
           "failed": ctx["failed"], "metrics": metrics, "device": device}
    if trace and ctx.get("trace"):
        s = ctx["trace"]
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        out["breakdown"] = s["breakdown"]
    info = {k: ctx[k] for k in ("checked_requests", "checked_tokens",
                                "reference_s", "preemptions", "admitted",
                                "finished", "compiles_in_window",
                                "queue_wait_ms_by_third", "output_tokens",
                                "data_s", "window_s")
            if k in ctx}
    if "check_detail" in ctx:
        info.update({k: v for k, v in ctx["check_detail"].items()
                     if k.startswith("_")})
    if info:
        print("check info " + json.dumps(info), file=sys.stderr)
    correct.print_checks(ctx["checks"])
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in ctx["checks"].items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    a = ap.parse_args(argv)
    work = common.load_json("workloads", a.workload)
    work["name"] = a.workload
    config = common.load_json("configs", work["config"])
    mix = common.load_json("traffic", work["traffic"])
    common.require_devices(work["chips"])
    common.use_compile_cache()
    out = execute(work, config, mix, a.seed, a.seconds, a.trace)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
