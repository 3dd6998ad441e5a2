"""The knee of an open-loop serving cell: its own engine, mix and window
at each of several arrival rates, in one process.  The benchmark's own
runs never run this; its readings fix the rate written into the cell.

    python bench/knee.py --workload serve-steady --rates 2,2.5,3 \
        --seed 7 --seconds 51

One JSON line per rate: the rate, requests due and admitted in the
window, the median queue wait (due time to admission) of each third of
the requests by due time, and `ttft_p95_ms`.  A rate is sustained when the
queue holds no more than a second's arrivals at the window's end and the
thirds' waits do not grow.
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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    work = common.load_json("workloads", a.workload)
    config = common.load_json("configs", work["config"])
    mix = common.load_json("traffic", work["traffic"])
    devices = common.require_devices(work["chips"])
    common.use_compile_cache()
    from bench import peaks, readers
    for rate in (float(r) for r in a.rates.split(",")):
        w = dict(work, traffic_params=dict(work.get("traffic_params", {}),
                                           rate_per_s=rate))
        env = {"config": config, "work": w, "mix": mix, "seed": a.seed,
               "seconds": a.seconds, "devices": devices,
               "clock": common.CompileClock(), "spans": [],
               "tracer": common.Tracer(False), "t_proc0": time.perf_counter(),
               "peaks": peaks.peaks(devices[0].device_kind)}
        ctx = common.load_driver(w["driver"]).run(env)
        print(json.dumps({
            "rate_per_s": rate, "due": ctx["attempted"],
            "admitted": ctx["admitted"], "finished": ctx["finished"],
            "waiting_at_end": ctx["attempted"] - ctx["admitted"],
            "queue_wait_ms_by_third": ctx["queue_wait_ms_by_third"],
            "ttft_p95_ms": readers.p95(ctx, "ttft_ms"),
            "tpot_p95_ms": readers.p95(ctx, "tpot_ms"),
            "compiles_in_window": ctx["compiles_in_window"],
            "preemptions": ctx["preemptions"], "correct": ctx["correct"]}),
            flush=True)
        del ctx
        gc.collect()


if __name__ == "__main__":
    main()
