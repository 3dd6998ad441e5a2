"""ServeEngine: continuous batching over a fixed pool of cache slots.

Each engine step does one of two things:

  1. **Admit**: if the FIFO queue is non-empty and a slot is free, prefill
     that one request (batch 1, its true prompt length) and scatter its
     cache into the free slot's batch row (`model.write_cache_slot` — one
     batch-row scatter per cache leaf, uniform across all five arch
     families).  Nothing is read back: the first sampled token stays on
     device and is harvested with the next chunk.
  2. **Decode a chunk**: run k batched decode ticks over the whole pool
     without touching the host.  The jitted tick updates the full slot
     lifecycle on device — per-slot position vector, active-mask gated
     cache writes, token count, EOS/budget retirement — so a slot that
     finishes mid-chunk self-retires and its later writes are dropped.
     One transfer at the chunk boundary harvests the (k, B) token block;
     the host then evicts finished slots and backfills from the queue.

  k is chosen as the smallest remaining budget among active slots (capped),
  so budget retirements land exactly on chunk boundaries and a freed slot
  is never left idle; only an early EOS can idle a slot, for at most
  CHUNK_CAP ticks (bounded staleness of the host's view of the pool).

Syncing the host every tick (the obvious implementation) halves throughput:
the blocking read serializes dispatch, while the static baseline streams
its whole batch without ever reading back.  Chunked harvesting keeps the
device queue full and makes the scheduler's host work free.

**Paged mode** (`page_size=`): KV leaves stop being per-slot max-length
reservations and become a shared pool of fixed-size pages; each slot owns
a block table and grows page-by-page as it decodes, so admission capacity
is governed by tokens actually RESIDENT, not worst-case length.  The
gathered block-table view is bit-identical to the dense cache on every
live position and stale page contents are masked to an exact softmax
zero, so paged outputs match the dense engine bit-for-bit.  When the pool
runs dry mid-decode the engine preempts the most-recently-admitted slot
(deterministic victim), requeueing it at the queue head as a prefix
continuation — the oldest work always runs to completion, so the pool can
be sized for the AVERAGE resident footprint instead of the worst case.

Paged mode also unlocks **KV migration on drain**: `drain()` harvests each
live slot's pages host-side into a `MigratedKV`, and a paged engine that
receives a continuation carrying one installs the pages (`device_put` +
page scatter) instead of re-prefilling the prefix — bit-identical resumes
with zero re-prefill FLOPs (`elastic.recovery.ServingDrainReadmit` wires
this across a fleet).

Greedy decoding is deterministic and slot-local, so per-request outputs are
identical to serving the same request alone — continuous batching changes
WHEN work runs, never WHAT each request computes.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.steps import (make_paged_serve_cb_step, make_serve_cb_step,
                                sharded_argmax)
from repro.obs import recorder as obs
from repro.obs.spans import span
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.serving.request import (FinishedRequest, Request,
                                   validate_budget)
from repro.serving.scheduler import FifoScheduler, PagePool, SlotPool

CHUNK_CAP = 8  # max decode ticks between host syncs (EOS eviction latency)


@dataclasses.dataclass
class MigratedKV:
    """Host-side harvest of one slot's live KV, taken at a chunk boundary.

    `pos` positions are resident (0 .. pos-1); the last emitted token
    (`last_token`, position pos) has NO cache entry yet — exactly the
    sequential-decode invariant, so installing this state and ticking once
    computes bit-for-bit what the source replica's next tick would have.
    `pages` maps each paged cache leaf to (stack, n_pages, P, Hk, dh);
    `rows` carries the per-slot leaves (audio cross-KV, hybrid recurrent
    state) as (stack, ...) batch rows."""
    pos: int
    last_token: int
    page_size: int
    pages: Dict[str, np.ndarray]
    rows: Dict[str, np.ndarray]


@dataclasses.dataclass
class DrainedRequest:
    """Resumable state of one in-flight request pulled off a dying replica.

    `emitted` is what the HOST had harvested (and hence streamed to the
    client) before the drain; tokens still device-side — the un-synced tail
    of a chunk, a pending prefill token — die with the replica and must be
    recomputed by the continuation (`elastic.recovery.ServingDrainReadmit`).
    `kv` (paged engines only) is the harvested cache: a continuation that
    carries it re-admits with zero prefill instead of recomputing the
    prefix."""
    request: Request
    emitted: List[int]
    kv: Optional[MigratedKV] = None


class ServeProgram:
    """The compiled half of the engine: admit + chunk-decode dispatches for
    one (cfg, cache_len).  Engines hold host-side slot state; the program
    holds jitted callables, so a fleet shares ONE program across all its
    replicas and a scale-up `join` replica starts serving without paying
    compilation (jax.jit re-traces per shape under the hood, so one program
    also serves engines with different slot counts)."""

    def __init__(self, cfg: ModelConfig, *, cache_len: int,
                 page_size: Optional[int] = None):
        self.cfg = cfg
        self.cache_len = cache_len
        self.page_size = page_size
        C = cache_len
        P = page_size

        def _admit_fn(params, prompt, extra, cache, tokens, pos, active,
                      gen, maxgen, eos, slot, start_pos, max_new, eos_id):
            """Prefill one request AND install it into its slot — cache
            scatter + every lifecycle register — in a single dispatch.
            Compiled once per prompt length (scalars are traced)."""
            logits, _, req_cache = MD.forward(params, cfg, prompt,
                                              extra_embeds=extra,
                                              return_cache=True, cache_len=C)
            first = sharded_argmax(logits[:, -1])  # (1,)
            cache = MD.write_cache_slot(cache, req_cache, slot)
            tokens = tokens.at[slot].set(first)
            pos = pos.at[slot].set(start_pos)
            # max_new_tokens == 1 is satisfied by the prefill token alone
            active = active.at[slot].set(max_new > 1)
            gen = gen.at[slot].set(1)
            maxgen = maxgen.at[slot].set(max_new)
            eos = eos.at[slot].set(eos_id)
            return first[None], cache, tokens, pos, active, gen, maxgen, eos

        def _admit_paged_fn(params, prompt, extra, cache, tokens, pos,
                            active, gen, maxgen, eos, slot, page_ids,
                            start_pos, max_new, eos_id):
            """Paged admit: prefill to a page multiple and scatter whole
            pages onto this request's block-table rows.  Compiled once per
            (prompt length, page count)."""
            npg = page_ids.shape[0]
            logits, _, req_cache = MD.forward(params, cfg, prompt,
                                              extra_embeds=extra,
                                              return_cache=True,
                                              cache_len=npg * P)
            first = sharded_argmax(logits[:, -1])
            cache = MD.write_paged_cache(cache, req_cache, slot, page_ids,
                                         cfg)
            tokens = tokens.at[slot].set(first)
            pos = pos.at[slot].set(start_pos)
            active = active.at[slot].set(max_new > 1)
            gen = gen.at[slot].set(1)
            maxgen = maxgen.at[slot].set(max_new)
            eos = eos.at[slot].set(eos_id)
            return first[None], cache, tokens, pos, active, gen, maxgen, eos

        def _install_fn(cache, tokens, pos, active, gen, maxgen, eos,
                        slot, page_ids, kv_pages, kv_rows, pos_val,
                        last_tok, remaining, eos_id):
            """Migrated admit: install harvested KV pages + per-slot rows
            and the lifecycle registers — NO prefill.  gen starts at 0
            (nothing emitted by THIS incarnation yet) and maxgen is the
            remaining budget, so the device retirement rule sees exactly
            a fresh continuation."""
            for name, pages in kv_pages.items():
                n = pages.shape[1]
                cache = dict(cache)
                cache[name] = cache[name].at[:, page_ids[:n]].set(
                    pages.astype(cache[name].dtype))
            for name, row in kv_rows.items():
                cache = dict(cache)
                # per-slot leaves may themselves be trees (hybrid conv)
                cache[name] = jax.tree_util.tree_map(
                    lambda c, r: c.at[:, slot].set(r.astype(c.dtype)),
                    cache[name], row)
            tokens = tokens.at[slot].set(last_tok)
            pos = pos.at[slot].set(pos_val)
            active = active.at[slot].set(True)
            gen = gen.at[slot].set(0)
            maxgen = maxgen.at[slot].set(remaining)
            eos = eos.at[slot].set(eos_id)
            return cache, tokens, pos, active, gen, maxgen, eos

        serve_cb = (make_paged_serve_cb_step(cfg, C) if page_size
                    else make_serve_cb_step(cfg))

        def _chunk_fn(k):
            """k pool-decode ticks as ONE dispatch (lax.scan): the slot
            lifecycle — position, token count, EOS/budget retirement —
            advances entirely on device; the host reads back only the
            (k, B) token/active blocks at the chunk boundary.  The tick
            itself is the same serve_cb step the lowering plans compile
            (steps.make_serve_cb_step); only the lifecycle is engine-side."""
            def chunk(params, cache, tokens, pos, active, gen, maxgen, eos,
                      block_tables=None):
                def body(carry, _):
                    tokens, cache, pos, active, gen = carry
                    if page_size:
                        nxt, cache = serve_cb(params, cache, tokens, pos,
                                              active, block_tables)
                    else:
                        nxt, cache = serve_cb(params, cache, tokens, pos,
                                              active)
                    out = (nxt[:, 0], active)
                    pos = pos + active
                    gen = gen + active
                    fin = active & ((nxt[:, 0] == eos) | (gen >= maxgen))
                    return (nxt, cache, pos, active & ~fin, gen), out

                (tokens, cache, pos, active, gen), (T, A) = jax.lax.scan(
                    body, (tokens, cache, pos, active, gen), None, length=k)
                return tokens, cache, pos, active, gen, T, A

            return jax.jit(chunk, donate_argnums=(1,))

        # jax.jit caches compilations per prompt length (shape-keyed); a
        # production deployment would bucket prompt lengths — the smoke
        # streams here draw from a handful of lengths
        self.admit = jax.jit(_admit_paged_fn if page_size else _admit_fn,
                             donate_argnums=(3,))
        self.install = jax.jit(_install_fn, donate_argnums=(0,))
        self._chunk_fns: Dict[int, Any] = {}
        self._make_chunk = _chunk_fn

    def chunk(self, k: int):
        fn = self._chunk_fns.get(k)
        if fn is None:
            fn = self._chunk_fns[k] = self._make_chunk(k)
        return fn


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, *, num_slots: int,
                 cache_len: int, chunk_cap: int = CHUNK_CAP,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 program: Optional[ServeProgram] = None,
                 host: Any = "serve"):
        self.host = host  # obs lane (fleet replicas pass their id)
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.chunk_cap = chunk_cap
        self.page_size = page_size
        self.paged = page_size is not None
        if self.paged:
            if not MD.paged_leaf_names(cfg):
                raise ValueError(f"arch_type {cfg.arch_type} has no KV "
                                 f"cache to page")
            self.n_max = -(-cache_len // page_size)
            self.num_pages = num_pages or self.n_max * num_slots
            if self.num_pages < self.n_max:
                # one slot at max length must always fit, or a lone
                # request could deadlock the pool with nothing to preempt
                raise ValueError(
                    f"num_pages {self.num_pages} < {self.n_max} pages "
                    f"needed by a single max-length request")
        else:
            self.num_pages = 0
        self.n_prefix = cfg.num_patches if cfg.arch_type == "vlm" else 0
        if program is not None and (program.cache_len != cache_len
                                    or program.page_size != page_size):
            raise ValueError(f"program (cache_len={program.cache_len}, "
                             f"page_size={program.page_size}) != engine "
                             f"(cache_len={cache_len}, page_size="
                             f"{page_size})")
        self.program = program or ServeProgram(cfg, cache_len=cache_len,
                                               page_size=page_size)
        self.reset()

    def reset(self) -> None:
        """Clear queue/pool/stats but keep the compiled step functions —
        lets benchmarks re-run a warmed engine without paying compile."""
        B = self.num_slots
        self.pool = SlotPool(B)
        self.scheduler = FifoScheduler(self.pool)
        self.finished: List[FinishedRequest] = []
        if self.paged:
            self.cache = MD.init_paged_cache(self.cfg, B, self.num_pages,
                                             self.page_size)
            self.pages = PagePool(self.num_pages, self.page_size)
            # host block tables; unassigned entries stay 0 (never read:
            # reads are bounded by the slot's position coverage)
            self.block_tables = np.zeros((B, self.n_max), np.int32)
        else:
            self.cache = MD.init_cache(self.cfg, B, self.cache_len)
        # device-resident slot lifecycle (host mirrors only what scheduling
        # needs: request binding + harvested tokens)
        self.tokens = jnp.zeros((B, 1), jnp.int32)
        self.pos_d = jnp.zeros((B,), jnp.int32)
        self.active_d = jnp.zeros((B,), bool)
        self.gen_d = jnp.zeros((B,), jnp.int32)
        self.maxgen_d = jnp.zeros((B,), jnp.int32)
        self.eos_d = jnp.full((B,), -1, jnp.int32)
        # first token of each admitted request: device ref, harvested later
        self._pending_first: Dict[int, jax.Array] = {}
        self._req_t0: Dict[int, float] = {}  # obs: rid -> admit clock
        # engine-local preemption ledger: rid -> (original request, tokens
        # already emitted across incarnations) — stitched back in _finish
        self._preempted: Dict[int, tuple] = {}
        self.ticks = 0
        self.decode_ticks = 0
        self.decode_chunks = 0
        self.emitted_tokens = 0  # every token harvested, finished or not
        # host seconds by part of the work between device programs
        # (`_host_part`): what a decode tick costs the host beyond the chip
        self._host_s = dict.fromkeys(
            ("coverage", "dispatch", "wait", "harvest", "prefill"), 0.0)
        self.prefill_ticks = 0
        self.prefill_tokens = 0
        self.migrated_admits = 0
        self.migrated_tokens_saved = 0
        self.preemptions = 0
        self._occupied_slot_steps = 0  # active slots summed over decode ticks
        self._page_steps = 0           # pages in use summed over decode ticks

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        validate_budget(req, self.n_prefix, self.cache_len)
        self.scheduler.submit(req)

    # ------------------------------------------------------------------
    def _slot_pos(self, slot: int) -> int:
        """Device `pos` register of an active slot, derived from host state
        (exact at chunk boundaries): admit sets pos=start and emits one
        token, every tick emits one more and advances pos."""
        g = len(self.pool.generated[slot]) + (
            1 if slot in self._pending_first else 0)
        return int(self.pool.pos[slot]) + max(0, g - 1)

    def _bt_dev(self):
        return jnp.asarray(self.block_tables)

    @contextlib.contextmanager
    def _host_part(self, part: str):
        """Span `serve.<part>` (obs recorder and profiler trace), whose
        host seconds add to `host_<part>_s` in `stats()`."""
        with span("serve." + part, host=self.host, cat="serving") as s:
            yield
        self._host_s[part] += s.seconds

    def _admit(self, req: Request, slot: int) -> None:
        if self.paged and req.kv_seed is not None:
            self._admit_migrated(req, slot)
            return
        with self._host_part("prefill"):
            prompt = jnp.asarray(np.asarray(req.prompt, np.int32))[None, :]
            start_pos = prompt.shape[1] + self.n_prefix
            pages = ()
            if self.paged:
                npg = self.pages.pages_for(start_pos + 1)
                page_ids = self.pages.alloc(slot, npg)
                assert page_ids is not None, "admission gate checked pages"
                self.block_tables[slot, :npg] = page_ids
                pages = (jnp.asarray(page_ids, jnp.int32),)
            (first, self.cache, self.tokens, self.pos_d, self.active_d,
             self.gen_d, self.maxgen_d, self.eos_d) = self.program.admit(
                self.params, prompt, req.extra_embeds, self.cache,
                self.tokens, self.pos_d, self.active_d, self.gen_d,
                self.maxgen_d, self.eos_d, jnp.int32(slot), *pages,
                jnp.int32(start_pos), jnp.int32(req.max_new_tokens),
                jnp.int32(-1 if req.eos_id is None else req.eos_id))
        self.pool.occupy(slot, req, start_pos, self.ticks)
        self._pending_first[slot] = first  # harvested with the next chunk
        self.prefill_ticks += 1
        self.prefill_tokens += int(prompt.shape[1])
        rec = obs.get()
        if rec.enabled:
            self._req_t0[req.rid] = rec.clock()
            rec.event("serve.admit", host=self.host, cat="serving",
                      rid=req.rid, slot=slot)

    def _admit_migrated(self, req: Request, slot: int) -> None:
        """Install a continuation's harvested KV pages instead of
        re-prefilling its prefix: `device_put` the pages onto freshly
        allocated block-table rows, set the lifecycle registers to the
        sequential-decode invariant (last emitted token pending at `pos`),
        and the next chunk continues bit-identically — zero prefill."""
        kv = req.kv_seed
        if kv.page_size != self.page_size:
            raise ValueError(f"migrated page size {kv.page_size} != "
                             f"engine page size {self.page_size}")
        start_pos = len(np.asarray(req.prompt)) + self.n_prefix
        assert kv.pos == start_pos - 1, (kv.pos, start_pos)
        npg = self.pages.pages_for(kv.pos + 1)  # coverage incl. next write
        page_ids = self.pages.alloc(slot, npg)
        assert page_ids is not None, "admission gate checked pages"
        self.block_tables[slot, :npg] = page_ids
        remaining = req.max_new_tokens
        kv_pages = {n: jax.device_put(p) for n, p in kv.pages.items()}
        kv_rows = {n: jax.device_put(r) for n, r in kv.rows.items()}
        (self.cache, self.tokens, self.pos_d, self.active_d, self.gen_d,
         self.maxgen_d, self.eos_d) = self.program.install(
            self.cache, self.tokens, self.pos_d, self.active_d, self.gen_d,
            self.maxgen_d, self.eos_d, jnp.int32(slot),
            jnp.asarray(page_ids, jnp.int32), kv_pages, kv_rows,
            jnp.int32(kv.pos), jnp.int32(kv.last_token),
            jnp.int32(remaining),
            jnp.int32(-1 if req.eos_id is None else req.eos_id))
        self.pool.occupy(slot, req, start_pos, self.ticks)
        self.migrated_admits += 1
        self.migrated_tokens_saved += int(kv.pos)
        rec = obs.get()
        if rec.enabled:
            self._req_t0[req.rid] = rec.clock()
            rec.event("serve.admit_migrated", host=self.host, cat="serving",
                      rid=req.rid, slot=slot, pages=npg,
                      tokens_resident=int(kv.pos))

    # ------------------------------------------------------------------
    def _release_slot(self, slot: int) -> None:
        self.pool.release(slot)
        if self.paged:
            self.pages.release(slot)

    def _finish(self, slot: int, reason: str) -> None:
        req = self.pool.request[slot]
        orig, prefix = self._preempted.pop(req.rid, (req, []))
        self.finished.append(FinishedRequest(
            rid=req.rid,
            prompt_len=len(np.asarray(orig.prompt)),
            tokens=prefix + list(self.pool.generated[slot]),
            finish_reason=reason,
            admitted_tick=int(self.pool.admitted_tick[slot]),
            finished_tick=self.ticks))
        self._release_slot(slot)
        rec = obs.get()
        if rec.enabled:
            # the request lifecycle as one span: admit -> finish
            t0 = self._req_t0.pop(req.rid, None)
            if t0 is not None:
                rec.complete("request", t0, rec.clock() - t0,
                             host=self.host, cat="serving", rid=req.rid,
                             reason=reason,
                             tokens=len(self.finished[-1].tokens))

    def _consume(self, slot: int, tok: int) -> None:
        """Host mirror of the device retirement rule for one token."""
        req = self.pool.request[slot]
        self.pool.generated[slot].append(tok)
        self.emitted_tokens += 1
        if len(self.pool.generated[slot]) == 1:
            obs.get().event("serve.first_token", host=self.host,
                            cat="serving", rid=req.rid)
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(slot, "eos")
        elif len(self.pool.generated[slot]) >= req.max_new_tokens:
            self._finish(slot, "length")

    def _harvest_pending(self) -> None:
        if not self._pending_first:
            return
        pend = sorted(self._pending_first.items())
        self._pending_first = {}
        for slot, ref in pend:
            tok = int(np.asarray(ref)[0, 0])
            self._consume(slot, tok)
            if not self.pool.active[slot]:
                # finished on the prefill token (EOS, or budget 1): the
                # device never saw that token in a tick, so reconcile its
                # active flag before the next chunk
                self.active_d = self.active_d.at[slot].set(False)

    def _device_active(self) -> List[int]:
        """Remaining token budget of every slot the DEVICE still decodes —
        derivable from host state alone (the host mirror replicates the
        device retirement rule exactly at every chunk boundary)."""
        out = []
        for s in np.flatnonzero(self.pool.active):
            s = int(s)
            rem = (self.pool.request[s].max_new_tokens
                   - len(self.pool.generated[s])
                   - (1 if s in self._pending_first else 0))
            if rem > 0:
                out.append(rem)
        return out

    # -- paged growth / preemption -------------------------------------
    def _preempt(self, slot: int) -> None:
        """Evict an active slot to reclaim its pages: its harvested tokens
        become an engine-local prefix continuation requeued at the HEAD of
        the queue (it lost its place in the pool, not in line).  The
        victim is always the most recently admitted (see _ensure_coverage)
        so the oldest work runs to completion — the invariant that makes
        pool exhaustion a stall, never a livelock."""
        req = self.pool.request[slot]
        orig, prefix = self._preempted.pop(req.rid, (req, []))
        prefix = prefix + list(self.pool.generated[slot])
        remaining = orig.max_new_tokens - len(prefix)
        if prefix:
            prompt = np.concatenate([np.asarray(orig.prompt, np.int32),
                                     np.asarray(prefix, np.int32)])
            cont = Request(rid=req.rid, prompt=prompt,
                           max_new_tokens=remaining, eos_id=orig.eos_id,
                           extra_embeds=orig.extra_embeds)
            self._preempted[req.rid] = (orig, prefix)
        else:
            cont = orig  # nothing emitted: re-admit verbatim
        self._release_slot(slot)
        self._pending_first.pop(slot, None)
        self.active_d = self.active_d.at[slot].set(False)
        self.scheduler.queue.appendleft(cont)
        self.preemptions += 1
        obs.get().event("serve.preempt", host=self.host, cat="serving",
                        rid=req.rid, slot=slot, emitted=len(prefix))

    def _ensure_coverage(self, k: int) -> None:
        """Grow every active slot's block table to cover the next k ticks
        (writes land at pos..pos+k-1), preempting newest-first when the
        pool runs dry.  Oldest slots are served first so the allocation
        order — and therefore the whole run — is deterministic."""
        order = sorted(
            (int(self.pool.admitted_tick[s]), s)
            for s in np.flatnonzero(self.pool.active))
        for _, slot in order:
            if not self.pool.active[slot]:
                continue  # preempted below an earlier slot in this pass
            # clamp to the table width: near its budget end a slot's
            # pos + k overshoots cache_len, but no write can land there
            # (submit bounds prompt + budget by cache_len)
            need = min(self.pages.pages_for(self._slot_pos(slot) + k),
                       self.n_max)
            have = len(self.pages.owned.get(slot, ()))
            while need > have:
                got = self.pages.alloc(slot, need - have)
                if got is not None:
                    self.block_tables[slot, have:need] = got
                    have = need
                    break
                victims = [
                    (int(self.pool.admitted_tick[s]), s)
                    for s in np.flatnonzero(self.pool.active)
                    if s != slot]
                assert victims, ("pool sized below one max-length request "
                                 "slipped past the constructor check")
                self._preempt(max(victims)[1])

    def _decode_chunk(self, remaining: List[int]) -> None:
        """One fused k-tick dispatch, one host sync.  k = the largest power
        of two <= the smallest remaining budget (so budget retirements land
        on chunk boundaries and only a handful of chunk lengths ever
        compile), capped at chunk_cap.

        Spans: `serve.decode` holds `serve.coverage` (page growth),
        `serve.dispatch` (block-table upload and the chunk call),
        `serve.wait` (the host blocked on the device for the token block)
        and `serve.harvest` (the host's walk over the block)."""
        m = min(min(remaining), self.chunk_cap)
        k = 1 << (m.bit_length() - 1)
        fn = self.program.chunk(k)
        with span("serve.decode", host=self.host, cat="serving", k=k):
            if self.paged:
                with self._host_part("coverage"):
                    self._ensure_coverage(k)
                if not self.pool.num_active and not self._pending_first:
                    return  # coverage preempted the whole pool
                self._page_steps += self.pages.pages_in_use * k
            with self._host_part("dispatch"):
                tables = (self._bt_dev(),) if self.paged else ()
                (self.tokens, self.cache, self.pos_d, self.active_d,
                 self.gen_d, T, A) = fn(self.params, self.cache, self.tokens,
                                        self.pos_d, self.active_d, self.gen_d,
                                        self.maxgen_d, self.eos_d, *tables)
            self.decode_chunks += 1
            self.decode_ticks += k
            # single harvest: (k,B) token block + the per-tick active masks
            with self._host_part("wait"):
                T = np.asarray(T)
                A = np.asarray(A)
            with self._host_part("harvest"):
                self._occupied_slot_steps += int(A.sum())
                self._harvest_pending()
                for t in range(k):
                    for slot in np.flatnonzero(A[t]):
                        slot = int(slot)
                        if self.pool.active[slot]:
                            self._consume(slot, int(T[t, slot]))

    # ------------------------------------------------------------------
    def _next_admission(self):
        """FIFO admission, gated in paged mode on the pool actually having
        pages for the prompt (or the migrated KV): a request that does not
        fit yet stays at the head of the queue — decode progress frees
        pages (retirement or preemption), never admission."""
        admission = self.scheduler.next_admission()
        if admission is None or not self.paged:
            return admission
        req, slot = admission
        if req.kv_seed is not None:
            need = self.pages.pages_for(req.kv_seed.pos + 1)
        else:
            plen = len(np.asarray(req.prompt)) + self.n_prefix
            need = self.pages.pages_for(plen + 1)
        if need > self.pages.num_free:
            self.scheduler.queue.appendleft(req)  # keep head-of-line
            return None
        return req, slot

    def tick(self) -> str:
        """One scheduling step: admit a request, or decode a chunk of the
        pool.  Returns "prefill" | "decode" | "idle"."""
        admission = self._next_admission()
        if admission is not None:
            self.ticks += 1
            self._admit(*admission)
            return "prefill"
        if self.pool.num_active or self._pending_first:
            self.ticks += 1
            remaining = self._device_active()
            if remaining:
                self._decode_chunk(remaining)
            else:
                self._harvest_pending()
            return "decode"
        return "idle"

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> List[FinishedRequest]:
        """Drain `requests` (plus anything already queued) to completion;
        returns finished requests sorted by request id."""
        for req in requests or ():
            self.submit(req)
        while not self.scheduler.done:
            self.tick()
        return sorted(self.finished, key=lambda f: f.rid)

    # ------------------------------------------------------------------
    @property
    def free_capacity(self) -> int:
        """Requests this engine can still accept without queueing beyond
        its pool: free slots minus admissions already waiting in the
        engine's own FIFO.  The fleet router admits against this, keeping
        the per-replica queue bounded by the slot count so a replica death
        never strands a deep private backlog."""
        return max(0, self.num_slots - self.pool.num_active
                   - self.scheduler.pending)

    def cancel(self, rid: int) -> bool:
        """Abort one request wherever it is — active slot (pages freed,
        device row deactivated) or queue — without recording a finish.
        Used by hedged decode to kill the losing copy."""
        for slot in np.flatnonzero(self.pool.active):
            slot = int(slot)
            if self.pool.request[slot].rid == rid:
                self._release_slot(slot)
                self._pending_first.pop(slot, None)
                self.active_d = self.active_d.at[slot].set(False)
                self._req_t0.pop(rid, None)
                self._preempted.pop(rid, None)
                return True
        for i, req in enumerate(self.scheduler.queue):
            if req.rid == rid:
                del self.scheduler.queue[i]
                self._preempted.pop(rid, None)
                return True
        return False

    def harvest_kv(self, slot: int) -> Optional[MigratedKV]:
        """Pull one active slot's live KV to the host (paged mode, chunk
        boundary): ceil(pos/P) pages per paged leaf + this slot's batch
        row of every per-slot leaf.  None when nothing was emitted yet
        (the continuation re-prefills its prompt anyway)."""
        if not self.paged or not self.pool.generated[slot]:
            return None
        pos = self._slot_pos(slot)
        npg = self.pages.pages_for(pos)
        page_ids = np.asarray(self.pages.owned[slot][:npg], np.int32)
        paged_names = set(MD.paged_leaf_names(self.cfg))
        pages = {n: np.asarray(self.cache[n][:, page_ids])
                 for n in self.cache if n in paged_names}
        rows = {n: jax.tree_util.tree_map(lambda l: np.asarray(l[:, slot]),
                                          self.cache[n])
                for n in self.cache if n not in paged_names}
        return MigratedKV(pos=pos,
                          last_token=int(self.pool.generated[slot][-1]),
                          page_size=self.page_size, pages=pages, rows=rows)

    def drain(self, migrate_kv: bool = True) -> List[DrainedRequest]:
        """Tear down the replica: pull every in-flight and queued request
        off the engine in a resumable form.

        Active slots keep their host-harvested tokens (`pool.generated` —
        already streamed to clients); device-side tokens (the pending
        prefill token, the un-synced tail of a chunk) are lost with the
        replica's device state and will be recomputed by the continuation.
        In paged mode (migrate_kv=True) each active slot's live KV pages
        ride along (`DrainedRequest.kv`) so the continuation can re-admit
        with zero prefill.  Queued-but-unadmitted requests come back
        untouched.  Ordered by request id so re-admission stays FIFO-fair
        in submission order.
        """
        rec = obs.get()
        out = []
        for slot in np.flatnonzero(self.pool.active):
            slot = int(slot)
            req = self.pool.request[slot]
            kv = self.harvest_kv(slot) if migrate_kv else None
            orig, prefix = self._preempted.pop(req.rid, (req, []))
            out.append(DrainedRequest(
                orig, prefix + list(self.pool.generated[slot]), kv))
            self._release_slot(slot)
            if rec.enabled:
                rec.event("serve.drain", host=self.host, cat="serving",
                          rid=orig.rid, emitted=len(out[-1].emitted),
                          migrated=kv is not None)
                self._req_t0.pop(orig.rid, None)
        while self.scheduler.queue:
            req = self.scheduler.queue.popleft()
            orig, prefix = self._preempted.pop(req.rid, (req, []))
            out.append(DrainedRequest(orig, list(prefix),
                                      getattr(req, "kv_seed", None)))
            if rec.enabled:
                rec.event("serve.drain", host=self.host, cat="serving",
                          rid=orig.rid, emitted=len(out[-1].emitted))
        self._pending_first = {}
        self.active_d = jnp.zeros((self.num_slots,), bool)
        return sorted(out, key=lambda d: d.request.rid)

    # ------------------------------------------------------------------
    @property
    def occupancy(self) -> float:
        """Mean fraction of slots doing useful work per decode tick."""
        if not self.decode_ticks:
            return 0.0
        return self._occupied_slot_steps / (self.decode_ticks *
                                            self.num_slots)

    @property
    def pool_occupancy(self) -> float:
        """Token-resident occupancy: mean fraction of POOL PAGES in use
        per decode tick.  The honest utilization number for paged mode —
        slot occupancy says a slot is busy, this says its reservation is
        actually holding tokens (dense engines reserve cache_len per slot,
        so their page-equivalent occupancy is pinned to resident/worst-
        case, the gap this engine reclaims)."""
        if not self.paged or not self.decode_ticks:
            return 0.0
        return self._page_steps / (self.decode_ticks * self.num_pages)

    def stats(self) -> Dict[str, float]:
        """Cumulative counters since `reset()`.  `decode_chunks` counts
        chunk dispatches (`decode_ticks / decode_chunks` ticks each);
        `host_<part>_s` are the host seconds of each `serve.<part>` span;
        `emitted_tokens` counts every token harvested, finished or not."""
        gen_tokens = sum(len(f.tokens) for f in self.finished)
        out = {"ticks": self.ticks, "decode_ticks": self.decode_ticks,
               "decode_chunks": self.decode_chunks,
               "prefill_ticks": self.prefill_ticks,
               "prefill_tokens": self.prefill_tokens,
               "occupancy": self.occupancy,
               "generated_tokens": gen_tokens,
               "emitted_tokens": self.emitted_tokens}
        out.update({f"host_{k}_s": v for k, v in self._host_s.items()})
        if self.paged:
            out.update({"pool_occupancy": self.pool_occupancy,
                        "num_pages": self.num_pages,
                        "preemptions": self.preemptions,
                        "migrated_admits": self.migrated_admits,
                        "migrated_tokens_saved": self.migrated_tokens_saved})
        return out
