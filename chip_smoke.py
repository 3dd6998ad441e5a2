"""Run the system's main path once on TPU chips, at qwen3-0.6b's full width.

    python chip_smoke.py            # one chip: serve (dense, paged) + train
    python chip_smoke.py --chips 4  # four chips: the (data=2, model=2) mesh
                                    # path and its one-device comparison

Everything runs in this one process, through the launchers a user calls
(`repro.launch.serve.serve`, `repro.launch.train.train`), in bf16 with
random weights made from a seed.  Earlier lines report wall time with
compile time separated out, peak device memory, losses and tokens served;
none of them is a benchmark number.  The last line is one JSON object
naming the device.  Any failed phase or check exits non-zero, and without a
TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

MODEL = ["--arch", "qwen3-0.6b"]
# 8 requests over 4 slots, prompts of up to 128 tokens, up to 32 new
STREAM = argparse.Namespace(requests=8, prompt_len=128, gen=32, seed=0)
SERVE = ["--continuous", "--batch", "4", "--requests", str(STREAM.requests),
         "--prompt-len", str(STREAM.prompt_len), "--gen", str(STREAM.gen),
         "--seed", str(STREAM.seed)]
TRAIN = ["--batch", "4", "--seq", "512", "--seed", "0", "--log-every", "1"]
# step-0 losses of one model on two meshes agree to bf16 reassociation
LOSS_RTOL = 1e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of backend compiles, read from jax.monitoring."""

    def __init__(self):
        self.secs = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.secs += secs
            self.compiles += name.endswith("backend_compile_duration")

    def timed(self, name, fn):
        """Run fn(); print its wall time split into compile and the rest
        (host work and device time)."""
        c0, n0, t0 = self.secs, self.compiles, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        comp = self.secs - c0
        say(f"{name}: wall {wall:.2f}s = compile {comp:.2f}s "
            f"({self.compiles - n0} compiles) + run {wall - comp:.2f}s")
        return out


def peak_bytes():
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def param_bytes_per_device(params):
    per = collections.Counter()
    for leaf in jax.tree_util.tree_leaves(params):
        for s in leaf.addressable_shards:
            per[s.device.id] += math.prod(s.data.shape) * s.data.dtype.itemsize
    return [per[d.id] for d in jax.local_devices()]


def check(cond, msg):
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")


# ---------------------------------------------------------------------------
def serve_phase(clock, model=MODEL):
    """Continuous batching, dense cache then paged pool: every request
    runs to its budget, every token is in the vocabulary, and paged
    reproduces dense token for token."""
    from repro.configs import get_config
    from repro.launch import serve as S

    cfg = get_config(model[1], smoke="--smoke" in model)
    budget = {r.rid: r.max_new_tokens for r in S.make_stream(cfg, STREAM)}

    toks = {}
    for mode, extra in (("dense", []), ("paged", ["--paged"])):
        out = clock.timed(f"serve[{mode}]",
                          lambda: S.serve(model + SERVE + extra))
        fin = {f.rid: f for f in out["finished"]}
        check(sorted(fin) == sorted(budget),
              f"{mode}: finished {sorted(fin)} != submitted {sorted(budget)}")
        for rid, f in fin.items():
            check(0 < len(f.tokens) <= budget[rid],
                  f"{mode}: request {rid} gave {len(f.tokens)} tokens, "
                  f"budget {budget[rid]}")
            check(all(0 <= t < cfg.vocab_size for t in f.tokens),
                  f"{mode}: request {rid} has a token outside the vocab")
        toks[mode] = {rid: list(f.tokens) for rid, f in fin.items()}
        served = [t for ts in toks[mode].values() for t in ts]
        say(f"serve[{mode}]: {len(fin)} requests, {len(served)} tokens "
            f"served ({len(set(served))} distinct ids), "
            f"peak device bytes {peak_bytes()}")
        del out
    diff = [rid for rid in budget if toks["dense"][rid] != toks["paged"][rid]]
    check(not diff, f"paged tokens differ from dense for requests {diff}")
    say("serve: paged == dense for every request")


def train_phase(clock, model=MODEL, steps=6, mesh=(), name="train"):
    """`steps` AdamW steps through the training launcher; every loss
    finite.  Returns (losses, per-device parameter bytes)."""
    from repro.launch.train import train

    out = clock.timed(name, lambda: train(
        model + TRAIN + ["--steps", str(steps)] + list(mesh)))
    losses = out["losses"]
    check(losses and all(math.isfinite(l) for l in losses),
          f"{name}: non-finite loss in {losses}")
    per_dev = param_bytes_per_device(out["params"])
    say(f"{name}: losses {losses}")
    say(f"{name}: param bytes per device {per_dev}, "
        f"peak device bytes {peak_bytes()}")
    return losses, per_dev


def four_chip_phase(clock, model=MODEL):
    """The train step on a (data=2, model=2) mesh under --env fsdp and
    --env dp_tp against the same model, seed and batch on one device; then
    the serve launcher with its weights split over --model 4."""
    check(jax.device_count() >= 4,
          f"--chips 4 needs 4 devices, found {jax.device_count()}")
    ref, one_dev = train_phase(clock, model, 2,
                               ["--data", "1", "--model", "1"],
                               "train[1 device]")
    total = sum(one_dev)
    for env in ("fsdp", "dp_tp"):
        losses, per_dev = train_phase(
            clock, model, 2, ["--data", "2", "--model", "2", "--env", env],
            f"train[2x2 {env}]")
        check(abs(losses[0] - ref[0]) <= LOSS_RTOL * abs(ref[0]),
              f"{env}: step-0 loss {losses[0]} vs one device {ref[0]}")
        check(max(per_dev) < total and sum(per_dev) >= total,
              f"{env}: param bytes per device {per_dev}, model {total}")
        say(f"train[2x2 {env}]: step-0 loss {losses[0]} vs one device "
            f"{ref[0]} (|diff| {abs(losses[0] - ref[0]):.3g})")

    from repro.launch.serve import serve
    out = clock.timed("serve[--model 4]",
                      lambda: serve(model + SERVE + ["--model", "4"]))
    per_dev = param_bytes_per_device(out["params"])
    check(len(out["finished"]) == STREAM.requests,
          f"serve --model 4 finished {len(out['finished'])} requests")
    check(max(per_dev) < total,
          f"serve --model 4: a device holds the whole model {per_dev}")
    say(f"serve[--model 4]: {len(out['finished'])} requests, "
        f"{sum(len(f.tokens) for f in out['finished'])} tokens served, "
        f"param bytes per device {per_dev} (whole model {total})")


# ---------------------------------------------------------------------------
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip mesh path")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"[chip_smoke] needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")

    from repro.launch import cli
    cli.use_compile_cache()
    say(f"devices: {jax.device_count()} x {dev.device_kind}")
    clock = CompileClock()
    if args.chips == 4:
        four_chip_phase(clock)
    else:
        serve_phase(clock)
        train_phase(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
