"""Continuous-batching serving engine (slot-level admission scheduling).

The static serve path (`launch/serve.py` without ``--continuous``) prefills
one fixed batch and decodes it in lockstep behind a single scalar position:
every request advances together and the batch retires only when its LONGEST
request finishes.  That is precisely the straggler/synchronization cost the
survey charges to bulk-synchronous distributed execution — the whole batch
barrier-waits on its slowest member, and measured throughput degrades to the
speed of the longest request.

This package applies the survey's asynchrony playbook at the granularity of
a *batch slot* instead of a worker:

* **Slot pool** (`scheduler.SlotPool`): a fixed pool of B cache rows.  Each
  slot runs its own request with its own position counter — the per-request
  ``pos: (B,)`` vector threaded through ``model.decode_step`` — so slots
  never synchronize on each other's progress.
* **Admission = bounded-staleness work injection**: like async parameter-
  server updates that apply whenever a worker shows up (rather than at a
  barrier), a new request is admitted the moment a slot frees, mid-stream,
  without draining the batch.  The decode tick keeps running over whatever
  mix of positions the pool currently holds.
* **Retired slots are no-ops**: an ``active: (B,)`` mask gates every cache
  and recurrent-state update (KV writes are scattered to an out-of-bounds
  row with mode="drop"; recurrent-state rows keep their old value), so an
  empty slot costs only its share of the batched matmul until backfill —
  the serving analogue of decoupled/delayed-gradient training hiding
  latency by overlapping independent work.
* **Bounded-staleness host view**: the engine decodes in fused multi-tick
  chunks (`engine.ServeEngine._decode_chunk`); slot retirement (EOS /
  budget) happens ON DEVICE inside the chunk, and the host's scheduler
  view is refreshed only at chunk boundaries.  This is the survey's stale-
  synchronous-parallel trade: the host tolerates a bounded lag (<= chunk
  cap ticks) in exchange for never blocking the device on a readback —
  syncing every tick measurably halved CPU throughput.

The result: a stream of mixed-length requests sustains near-full slot
occupancy, and total tokens/s approaches B x single-request decode speed
instead of being gated by the slowest request in each static batch
(`benchmarks/bench_serving.py` measures both).

**Paged KV pool** (`ServeEngine(page_size=...)`): slot occupancy says a
slot is busy; it does not say its cache reservation is earning its
memory.  The dense engine reserves ``cache_len`` positions per slot, so a
mixed-length stream leaves the average slot's reservation mostly empty —
the same worst-case-provisioning waste the survey charges to static
resource partitioning.  Paged mode replaces the per-slot reservation with
a shared pool of fixed-size pages (`scheduler.PagePool`, vLLM-style):
each slot owns a block table of page ids and grows page-by-page as it
decodes, `models.attention` gathers KV through the table (bit-identical
to the dense cache — stale page contents mask to an exact softmax zero;
`kernels/paged_attention.py` is the Pallas decode kernel for the same
read), and admission is gated on TOKENS RESIDENT rather than worst-case
length.  When the pool runs dry the engine preempts the most recently
admitted slot into a prefix continuation (deterministic, oldest-work-
first), so the pool can be sized for the average footprint.  The honest
utilization number is `pool_occupancy` (pages in use / pool pages,
reported by `stats()` next to the slot `occupancy`).

Paging also makes the KV cache a first-class migratable object: `drain()`
harvests each live slot's pages host-side (`engine.MigratedKV`), and a
continuation carrying them (`Request.kv_seed`, attached by
`elastic.recovery.ServingDrainReadmit`) re-admits on another replica by
installing pages instead of re-prefilling — bit-identical resume, zero
prefill FLOPs.  The fleet layer adds **hedged decode** on top
(`ServeFleet(hedged_decode=True)`): a SUSPECT replica keeps serving
while a speculative continuation races it on a healthy replica through
the cluster's `backup` role ledger, first token past the hedge point
wins, and the loser's slot and pages are freed (`ServeEngine.cancel`).

**Speculative decoding** (`speculative.SpecDecodeEngine`): continuous
batching parallelizes ACROSS requests; the draft–verify engine attacks
the per-request sequential bottleneck.  A drafter (model-free n-gram
lookup, or a smaller config-zoo model sharing the vocab) proposes k
tokens and `model.verify_step` scores all k+1 positions in one dispatch;
greedy acceptance emits the agreeing prefix plus the target's correction
token, so outputs are bit-identical to sequential decode — speculation
changes the dispatch count, never the stream.

The fleet layer (`fleet.py` / `router.py`) lifts the same playbook one
level up — from slots within a replica to replicas within a fleet: the
fleet subscribes to the shared `repro.cluster.Coordinator` control plane
(the same failure detector elastic training uses, over a simulated clock
or real heartbeat processes), which drives replica drain/re-admit
(crash, hang-to-timeout, preemptive drain on SUSPECT), scale-up joins,
and a throughput-EMA router that weights admission away from stragglers
(`benchmarks/bench_elastic_serving.py` pins the recovery cost).

Public API:
  Request / FinishedRequest      (request.py)
  FifoScheduler / SlotPool / PagePool          (scheduler.py)
  ServeEngine / ServeProgram / DrainedRequest / MigratedKV  (engine.py)
  SpecDecodeEngine / LookupDraft / ModelDraft  (speculative.py)
  ServeFleet / Replica           (fleet.py)
  ThroughputRouter               (router.py)
"""
from repro.serving.engine import (DrainedRequest, MigratedKV, ServeEngine,
                                  ServeProgram)
from repro.serving.fleet import Replica, ServeFleet
from repro.serving.request import FinishedRequest, Request
from repro.serving.router import ThroughputRouter
from repro.serving.scheduler import FifoScheduler, PagePool, SlotPool
from repro.serving.speculative import (LookupDraft, ModelDraft,
                                       SpecDecodeEngine)

__all__ = ["Request", "FinishedRequest", "FifoScheduler", "SlotPool",
           "PagePool", "ServeEngine", "ServeProgram", "DrainedRequest",
           "MigratedKV", "SpecDecodeEngine", "LookupDraft", "ModelDraft",
           "ServeFleet", "Replica", "ThroughputRouter"]
