"""Serving cells: `ServeEngine` (paged) under open-loop or backlog traffic.

Set-up makes the weights from the seed, builds the engine, and warms up
every prompt length the mix uses and every decode chunk length, then
clears the engine.  The window submits each request when it is due and
calls `tick()` until the window's seconds are up.  Afterwards a sample of
finished requests, drawn from the seed and holding the longest, is
checked against the plain reference: the widest gap by which a served
token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import correct, flops, program, traffic
from bench.common import load_reference, peak_bytes


def _warm(eng, lengths: List[int], Request) -> None:
    """Every prefill shape, then decode chunks of 8 and 1 (budget 10),
    2 and 1 (budget 4), 4 and 1 (budget 6)."""
    rid = -1
    batch = []
    for L in lengths:
        batch.append(Request(rid=rid, prompt=np.arange(L, dtype=np.int32) % 7,
                             max_new_tokens=10))
        rid -= 1
    eng.run(batch)
    for budget in (4, 6):
        eng.run([Request(rid=rid, prompt=np.zeros(lengths[0], np.int32),
                         max_new_tokens=budget)])
        rid -= 1
    eng.cache = None  # free the pool before reset allocates a new one
    eng.reset()


def _with_tokens(f, tokens):
    import dataclasses
    return dataclasses.replace(f, tokens=tokens)


def _tokens_out(eng) -> int:
    act = eng.pool.active
    return (sum(len(f.tokens) for f in eng.finished)
            + sum(len(eng.pool.generated[s]) for s in np.flatnonzero(act)))


def run(env: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from repro.obs import recorder as obs
    from repro.serving import Request, ServeEngine

    c, work, mix = env["config"], env["work"], env["mix"]
    seed, seconds = env["seed"], env["seconds"]
    spans, clock, tracer = env["spans"], env["clock"], env["tracer"]
    ref = load_reference(c["family"])

    # ---------------- set-up
    cfg = program.model_config(c)
    wdt = jnp.dtype(cfg.param_dtype)
    params = ref.make_weights(c, seed, wdt)
    program.check_tree(params, cfg)
    eng = ServeEngine(params, cfg, num_slots=work["slots"],
                      cache_len=work["cache_len"],
                      page_size=work["page_size"],
                      num_pages=work.get("num_pages"))
    mix = dict(mix, **work.get("traffic_params", {}))
    arrivals = traffic.requests(mix, seed, seconds, c["vocab_size"])
    lengths = sorted({len(a.prompt) for a in arrivals})
    _warm(eng, lengths, Request)
    jax.block_until_ready(eng.cache)
    rec = obs.Recorder(clock=time.perf_counter)
    prev = obs.install(rec)
    st0 = eng.stats()
    c0 = clock.compiles

    # ---------------- window
    chunks = []      # (t0, t1, ticks, active slots, resident tokens)
    out_tok = 0      # output tokens harvested so far
    n, i = len(arrivals), 0
    failed = 0
    tracer.start()
    t0 = time.perf_counter()
    setup_s = t0 - env["t_proc0"]
    tend = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= tend:
            break
        if i < n and t0 + arrivals[i].t <= now:
            g0 = now
            while i < n and t0 + arrivals[i].t <= now:
                a = arrivals[i]
                try:
                    eng.submit(Request(rid=a.rid, prompt=a.prompt,
                                       max_new_tokens=a.max_new))
                except ValueError:
                    failed += 1
                i += 1
            spans.append(("generator", g0, time.perf_counter()))
        act = np.flatnonzero(eng.pool.active)
        resident = sum(int(eng.pool.pos[s]) + len(eng.pool.generated[s])
                       for s in act)
        d0 = eng.decode_ticks
        ts = time.perf_counter()
        kind = eng.tick()
        te = time.perf_counter()
        spans.append(("tick:" + kind, ts, te))
        if kind == "decode" and eng.decode_ticks > d0:
            chunks.append((ts, te, eng.decode_ticks - d0, len(act), resident))
        if kind != "idle":
            out_tok = _tokens_out(eng)
        else:
            nxt = min(t0 + arrivals[i].t if i < n else tend, tend)
            wait = nxt - time.perf_counter()
            if wait > 0:
                spans.append(("wait", te, nxt))
                time.sleep(wait)
    # rates run to the end of the last tick the window started, so every
    # tick's work and time count whole
    t_end = time.perf_counter()
    summary = tracer.stop(spans, (t0, t_end))
    st1 = eng.stats()
    compiles = clock.compiles - c0
    obs.install(prev)

    # ---------------- what the window did
    admit, first, finish = {}, {}, {}
    for ev in rec.events:
        rid = (ev.args or {}).get("rid")
        if ev.name == "serve.admit":
            admit[rid] = ev.ts
        elif ev.name == "serve.first_token":
            first[rid] = ev.ts
        elif ev.name == "request":
            finish[rid] = ev.ts + ev.dur
    due = {a.rid: t0 + a.t for a in arrivals[:i]}
    ttft, qwait, tpot = [], [], []
    for rid, t_arr in due.items():
        ft = first.get(rid)
        ttft.append(((ft if ft is not None and ft <= tend else tend)
                     - t_arr) * 1e3)
        at = admit.get(rid)
        qwait.append(((at if at is not None and at <= tend else tend)
                      - t_arr) * 1e3)
    budget = {a.rid: a.max_new for a in arrivals}
    for f in eng.finished:
        ft, lt = first.get(f.rid), finish.get(f.rid)
        if (ft is not None and lt is not None and lt <= tend
                and len(f.tokens) > 1):
            tpot.append((lt - ft) * 1e3 / (len(f.tokens) - 1))
    plen = {a.rid: len(a.prompt) for a in arrivals}
    win_flops = sum(flops.prefill_flops(c, plen[r]) for r in admit)
    least_s = 0.0
    pk = env["peaks"]
    for ts, te, k, a, res in chunks:
        for j in range(k):
            f = flops.decode_tick_flops(c, a, res + j * a)
            b = flops.decode_tick_bytes(c, a, res + j * a)
            least_s += max(f / pk["bf16_flops_per_s"],
                           b / pk["hbm_bytes_per_s"])
            win_flops += f

    # ---------------- check what the timed path produced
    finished = list(eng.finished)
    wrong_len = sum(len(f.tokens) != budget[f.rid] for f in finished)
    peak = peak_bytes(env["devices"])
    eng.cache = None
    eng.params = None
    del eng, params
    gc.collect()
    t_ref = time.perf_counter()
    sample = correct.serve_sample(finished, seed, work["check_tokens"],
                                  work.get("check_max_requests", 16))
    prompts = {a.rid: a.prompt for a in arrivals}
    gaps = correct.serve_gaps(ref, c, seed, sample, prompts,
                              work["cache_len"], wdt)
    checks = correct.serve_checks(gaps, wrong_len, work["limits"])
    ok = bool(sample) and all(v <= lim for v, lim in checks.values())
    ref_s = time.perf_counter() - t_ref
    controls = {}
    for mode in env.get("controls", ()):
        if mode == "altered":  # one served token changed where produced
            f = sample[0]
            toks = list(f.tokens)
            toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % c["vocab_size"]
            g = correct.serve_gaps(ref, c, seed, [_with_tokens(f, toks)],
                                   prompts, work["cache_len"], wdt)
        else:
            g = correct.serve_gaps(ref, c, seed, sample, prompts,
                                   work["cache_len"], wdt, control=mode)
        controls[mode] = correct.serve_checks(g, 0, work["limits"])

    return {
        "setup_s": setup_s, "window_s": t_end - t0,
        "attempted": i, "failed": failed,
        "correct": ok, "checks": checks,
        "checked_requests": len(sample),
        "checked_tokens": sum(len(f.tokens) for f in sample),
        "memory_peak_bytes": peak,
        "ttft_ms": ttft, "tpot_ms": tpot, "queue_wait_ms": qwait,
        "output_tokens": out_tok,
        "stats0": st0, "stats1": st1, "slots": work["slots"],
        "compiles_in_window": compiles,
        "preemptions": st1.get("preemptions", 0) - st0.get("preemptions", 0),
        "admitted": sum(t <= tend for t in admit.values()),
        "queue_wait_ms_by_third": [
            sorted(qwait[j * len(qwait) // 3:(j + 1) * len(qwait) // 3])[
                len(qwait) // 6] if len(qwait) >= 3 else None
            for j in range(3)],
        "finished": sum(t <= tend for t in finish.values()),
        "reference_s": ref_s,
        "window_flops": win_flops,
        "decode_least_s": least_s,
        "decode_chunks": len(chunks),
        "trace": summary,
        "controls": controls,
    }
