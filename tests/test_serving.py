"""Continuous-batching serving engine: per-slot position-vector decode
matches the scalar-pos decode on every arch family; the slot-pool engine
reproduces one-at-a-time greedy generations exactly on mixed-length
streams; eviction + backfill keeps occupancy full; the serve_cb plan
lowers and compiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import InputShape, get_config
from repro.models import model as MD
from repro.launch.steps import sharded_argmax
from repro.serving import Request, ServeEngine, ServeProgram
from tests._hlo import pool_traffic

KEY = jax.random.PRNGKey(0)

# one representative smoke config per arch family
FAMILY_ARCHS = ["qwen3-0.6b", "qwen3-moe-30b-a3b", "phi-3-vision-4.2b",
                "whisper-tiny", "rwkv6-1.6b", "zamba2-1.2b"]


def _cfg(arch):
    return get_config(arch, smoke=True).with_(param_dtype="float32",
                                              compute_dtype="float32")


def _extra(cfg, B):
    if cfg.arch_type == "vlm":
        return jax.random.normal(KEY, (B, cfg.num_patches,
                                       MD.VISION_EMBED_DIM), jnp.float32)
    if cfg.arch_type == "audio":
        return jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model),
                                 jnp.float32)
    return None


# ---------------------------------------------------------------------------
# per-slot pos vector == scalar pos
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_pos_vector_matches_scalar(arch):
    """decode_step with pos (B,) all equal == decode_step with scalar pos,
    bit-for-bit, logits and every cache leaf."""
    cfg = _cfg(arch)
    params = MD.init_model(cfg, KEY)
    B, S = 3, 8
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    ex = _extra(cfg, B)
    n_prefix = cfg.num_patches if cfg.arch_type == "vlm" else 0
    C = S + 8 + n_prefix
    _, _, cache = MD.forward(params, cfg, toks[:, :S], extra_embeds=ex,
                             return_cache=True, cache_len=C)
    p = S + n_prefix
    l_s, c_s = MD.decode_step(params, cfg, toks[:, S:S + 1],
                              jnp.int32(p), cache)
    l_v, c_v = MD.decode_step(params, cfg, toks[:, S:S + 1],
                              jnp.full((B,), p, jnp.int32), cache)
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
    for a, b in zip(jax.tree_util.tree_leaves(c_s),
                    jax.tree_util.tree_leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_inactive_slots_are_noops(arch):
    """active=False rows keep their cache row bit-identical; active rows
    update exactly as without the mask."""
    cfg = _cfg(arch)
    params = MD.init_model(cfg, KEY)
    B, S = 3, 8
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    ex = _extra(cfg, B)
    n_prefix = cfg.num_patches if cfg.arch_type == "vlm" else 0
    C = S + 8 + n_prefix
    _, _, cache = MD.forward(params, cfg, toks[:, :S], extra_embeds=ex,
                             return_cache=True, cache_len=C)
    pos = jnp.full((B,), S + n_prefix, jnp.int32)
    active = jnp.array([True, False, True])
    _, c_all = MD.decode_step(params, cfg, toks[:, S:S + 1], pos, cache)
    _, c_msk = MD.decode_step(params, cfg, toks[:, S:S + 1], pos, cache,
                              active=active)
    for full, msk, old in zip(jax.tree_util.tree_leaves(c_all),
                              jax.tree_util.tree_leaves(c_msk),
                              jax.tree_util.tree_leaves(cache)):
        np.testing.assert_array_equal(np.asarray(msk[:, 1]),
                                      np.asarray(old[:, 1]))  # frozen row
        np.testing.assert_array_equal(np.asarray(msk[:, 0]),
                                      np.asarray(full[:, 0]))
        np.testing.assert_array_equal(np.asarray(msk[:, 2]),
                                      np.asarray(full[:, 2]))


# ---------------------------------------------------------------------------
# engine == one-at-a-time static serving
# ---------------------------------------------------------------------------
def _single_reference(params, cfg, prompt, gen, cache_len, extra=None):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, _, cache = MD.forward(params, cfg, toks, extra_embeds=extra,
                                  return_cache=True, cache_len=cache_len)
    nxt = sharded_argmax(logits[:, -1])[:, None]
    out = [int(nxt[0, 0])]
    pos = toks.shape[1] + (cfg.num_patches if cfg.arch_type == "vlm" else 0)
    for _ in range(gen - 1):
        logits, cache = MD.decode_step(params, cfg, nxt, jnp.int32(pos),
                                       cache)
        nxt = sharded_argmax(logits[:, -1])[:, None]
        out.append(int(nxt[0, 0]))
        pos += 1
    return out


def _engine_archs():
    # moe: raise capacity so routing never drops tokens — with drops, slots
    # in a shared decode batch compete for expert capacity and batched !=
    # single is expected (group routing is per-batch at S==1)
    return ["qwen3-0.6b", "rwkv6-1.6b", "zamba2-1.2b", "qwen3-moe-30b-a3b"]


@pytest.mark.parametrize("arch", _engine_archs())
def test_engine_matches_single_request_serving(arch):
    cfg = _cfg(arch)
    if cfg.arch_type == "moe":
        cfg = cfg.with_(capacity_factor=float(cfg.num_experts))
    params = MD.init_model(cfg, KEY)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=int(rng.choice([6, 10]))),
                    max_new_tokens=int(rng.choice([3, 6])))
            for i in range(5)]
    eng = ServeEngine(params, cfg, num_slots=2, cache_len=20)
    finished = eng.run(reqs)
    assert len(finished) == len(reqs)
    for fin, req in zip(finished, reqs):
        assert fin.rid == req.rid
        ref = _single_reference(params, cfg, req.prompt, req.max_new_tokens,
                                20)
        assert fin.tokens == ref, (
            f"{arch} rid={req.rid}: engine {fin.tokens} != single {ref}")


def test_engine_vlm_extra_embeds():
    """VLM requests carry patch embeddings; slot positions include the
    patch prefix."""
    cfg = _cfg("phi-3-vision-4.2b")
    params = MD.init_model(cfg, KEY)
    rng = np.random.RandomState(1)
    ex = _extra(cfg, 1)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, size=6),
                    max_new_tokens=3, extra_embeds=ex) for i in range(3)]
    eng = ServeEngine(params, cfg, num_slots=2,
                      cache_len=16 + cfg.num_patches)
    finished = eng.run(reqs)
    assert len(finished) == 3
    for fin, req in zip(finished, reqs):
        ref = _single_reference(params, cfg, req.prompt, 3,
                                16 + cfg.num_patches, extra=ex)
        assert fin.tokens == ref


# ---------------------------------------------------------------------------
# scheduling: eviction, backfill, occupancy
# ---------------------------------------------------------------------------
def test_eviction_backfill_keeps_occupancy_full():
    """With uniform work and a full queue, every decode tick runs with every
    slot busy (perfect backfill); all requests complete."""
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)
    rng = np.random.RandomState(2)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, size=8),
                    max_new_tokens=5) for i in range(6)]
    eng = ServeEngine(params, cfg, num_slots=2, cache_len=16)
    finished = eng.run(reqs)
    assert len(finished) == 6
    assert all(len(f.tokens) == 5 for f in finished)
    assert eng.occupancy == 1.0
    # 6 admissions, and decode ticks strictly fewer than 6 requests x 4
    # lockstep rounds would need if the pool drained between batches
    assert eng.stats()["prefill_ticks"] == 6


def test_eos_evicts_early_and_backfills():
    """A request hitting EOS frees its slot early; the queue backfills and
    all requests still finish with correct outputs."""
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, size=8) for _ in range(4)]
    # learn request 0's greedy continuation, then make its 2nd token EOS
    ref0 = _single_reference(params, cfg, prompts[0], 8, 24)
    eos = ref0[1]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8,
                    eos_id=eos if i == 0 else None)
            for i, p in enumerate(prompts)]
    eng = ServeEngine(params, cfg, num_slots=2, cache_len=24)
    finished = eng.run(reqs)
    assert len(finished) == 4
    f0 = finished[0]
    assert f0.finish_reason == "eos"
    assert f0.tokens == ref0[:2]
    for fin, req in zip(finished[1:], reqs[1:]):
        assert len(fin.tokens) == 8
        assert fin.finish_reason == "length"
        assert fin.tokens == _single_reference(params, cfg, req.prompt, 8,
                                               24)


def test_engine_rejects_oversized_request():
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)
    eng = ServeEngine(params, cfg, num_slots=1, cache_len=8)
    with pytest.raises(ValueError, match="exceeds cache_len"):
        eng.submit(Request(rid=0, prompt=np.zeros(6, np.int32),
                           max_new_tokens=4))


# ---------------------------------------------------------------------------
# serve_cb lowering plan
# ---------------------------------------------------------------------------
def test_serve_cb_plan_lowers_and_runs():
    from repro.core import sharding as SH
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_plan, lower_plan

    cfg = _cfg("qwen3-0.6b")
    mesh = make_host_mesh(1, 1)
    shape = InputShape("decode_cb_smoke", 32, 4, "decode_cb")
    with SH.use_mesh(mesh), SH.axis_env(SH.DP_TP_ENV):
        plan = build_plan(cfg, shape, mesh)
        compiled = lower_plan(plan).compile()
        params = MD.init_model(cfg, KEY)
        cache = MD.init_cache(cfg, 4, 32)
        tok = jnp.zeros((4, 1), jnp.int32)
        pos = jnp.full((4,), 7, jnp.int32)
        active = jnp.array([True, True, False, True])
        nxt, _ = compiled(params, cache, tok, pos, active)
        assert nxt.shape == (4, 1)
        assert int(nxt[2, 0]) == 0  # inactive slot passes its token through


# ---------------------------------------------------------------------------
# paged KV pool: attention_decode parity, engine bit-identity, preemption
# ---------------------------------------------------------------------------
PAGED_ARCHS = ["qwen3-0.6b", "qwen3-moe-30b-a3b", "phi-3-vision-4.2b",
               "whisper-tiny", "zamba2-1.2b"]    # every family with a KV cache


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_decode_step_matches_dense(arch):
    """decode_step through a block table over a shared page pool ==
    decode_step over the dense per-slot cache, bit-for-bit, logits and
    the KV written back — on every arch family that has KV to page."""
    cfg = _cfg(arch)
    params = MD.init_model(cfg, KEY)
    B, S, P = 2, 6, 4
    n_prefix = cfg.num_patches if cfg.arch_type == "vlm" else 0
    p = S + n_prefix
    npg = -(-(p + 2) // P)           # pages covering prefill + 2 decode steps
    n_max = npg + 1
    C = n_max * P
    toks = jax.random.randint(KEY, (B, S + 2), 0, cfg.vocab_size)
    ex = _extra(cfg, 1)

    dense = MD.init_cache(cfg, B, C)
    paged = MD.init_paged_cache(cfg, B, 2 * n_max, P)
    # scrambled, DISJOINT page ids per slot (the pool allocator's
    # invariant) — the fragmented-pool layout
    ids = np.random.RandomState(7).permutation(
        2 * n_max).reshape(B, n_max).astype(np.int32)
    for b in range(B):
        _, _, c1 = MD.forward(params, cfg, toks[b:b + 1, :S],
                              extra_embeds=ex, return_cache=True,
                              cache_len=C)
        dense = MD.write_cache_slot(dense, c1, b)
        _, _, c2 = MD.forward(params, cfg, toks[b:b + 1, :S],
                              extra_embeds=ex, return_cache=True,
                              cache_len=npg * P)
        paged = MD.write_paged_cache(paged, c2, b,
                                     jnp.asarray(ids[b, :npg]), cfg)
    bt = jnp.asarray(ids)
    pos = jnp.full((B,), p, jnp.int32)
    for step in range(2):
        tok = toks[:, S + step:S + step + 1]
        l_d, dense = MD.decode_step(params, cfg, tok, pos, dense)
        l_p, paged = MD.decode_step(params, cfg, tok, pos, paged,
                                    block_tables=bt, logical_len=C)
        np.testing.assert_array_equal(np.asarray(l_d), np.asarray(l_p))
        pos = pos + 1


def _chunk_inputs(cfg, params, B, P, n_max, npages, lens, active):
    """Prefilled dense cache, paged pool (sentinel 7.0 on every page no
    prefill wrote; scrambled disjoint block tables) and the chunk's lifecycle
    registers for slots with prompt lengths `lens`."""
    C = n_max * P
    toks = jax.random.randint(KEY, (B, max(lens) + 1), 0, cfg.vocab_size)
    dense = MD.init_cache(cfg, B, C)
    paged = jax.tree_util.tree_map(lambda a: jnp.full_like(a, 7.0),
                                   MD.init_paged_cache(cfg, B, npages, P))
    ids = np.random.RandomState(3).permutation(npages)[:B * n_max]
    ids = ids.reshape(B, n_max).astype(np.int32)
    for b, n in enumerate(lens):
        npg = -(-n // P)
        _, _, c1 = MD.forward(params, cfg, toks[b:b + 1, :n],
                              return_cache=True, cache_len=C)
        dense = MD.write_cache_slot(dense, c1, b)
        _, _, c2 = MD.forward(params, cfg, toks[b:b + 1, :n],
                              return_cache=True, cache_len=npg * P)
        paged = MD.write_paged_cache(paged, c2, b,
                                     jnp.asarray(ids[b, :npg]), cfg)
    regs = (toks[jnp.arange(B), jnp.asarray(lens)][:, None],     # tokens
            jnp.asarray(lens, jnp.int32),                       # pos
            jnp.asarray(active),                                # active
            jnp.zeros((B,), jnp.int32),                         # gen
            jnp.full((B,), 100, jnp.int32),                     # maxgen
            jnp.full((B,), -1, jnp.int32))                      # eos
    return dense, paged, jnp.asarray(ids), regs


def test_paged_chunk_writes_pool_in_place():
    """The paged decode chunk carries the stacked KV pools through the
    layer scan: no copy, dynamic-slice, dynamic-update-slice or broadcast
    makes a value shaped like a pool or one layer of it, and one scatter
    per KV leaf writes the pool."""
    cfg = _cfg("qwen3-0.6b")
    B, P, n_max, npages = 3, 4, 5, 17      # gathered view 3x20: no clash
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    cache = jax.tree_util.tree_map(
        sds, MD.paged_cache_specs(cfg, B, npages, P))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    args = (MD.model_abstract(cfg), cache, i32(B, 1), i32(B),
            jax.ShapeDtypeStruct((B,), jnp.bool_), i32(B), i32(B), i32(B),
            i32(B, n_max))
    prog = ServeProgram(cfg, cache_len=n_max * P, page_size=P)
    text = prog.chunk(4).lower(*args).compile().as_text()
    moves, writes = pool_traffic(text, cache["k"].shape)
    assert moves == []
    assert writes == len(MD.paged_leaf_names(cfg))


def test_paged_chunk_pool_matches_dense_cache():
    """After one chunk of ticks the pool holds, at every (slot, position)
    below the slot's length, exactly the K/V the dense cache holds — across
    a page boundary crossed inside the chunk — and nothing else in the pool
    changed: a retired slot's writes are dropped."""
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)
    B, P, n_max, npages, k = 3, 4, 4, 15, 4
    lens = [6, 3, 5]                       # slot 0 crosses 8, slot 1 crosses 4
    active = [True, True, False]           # slot 2 is retired
    dense, paged, bt, regs = _chunk_inputs(cfg, params, B, P, n_max, npages,
                                           lens, active)
    before = {n: np.asarray(paged[n]) for n in MD.paged_leaf_names(cfg)}
    C = n_max * P
    out_d = ServeProgram(cfg, cache_len=C).chunk(k)(params, dense, *regs)
    out_p = ServeProgram(cfg, cache_len=C, page_size=P).chunk(k)(
        params, paged, *regs, bt)
    np.testing.assert_array_equal(np.asarray(out_d[5]), np.asarray(out_p[5]))
    pos = np.asarray(out_p[2])
    assert list(pos) == [lens[0] + k, lens[1] + k, lens[2]]
    ids = np.asarray(bt)
    for name in MD.paged_leaf_names(cfg):
        want = before[name].copy()
        got, dref = np.asarray(out_p[1][name]), np.asarray(out_d[1][name])
        for b in range(B):
            for q in range(pos[b]):
                want[:, ids[b, q // P], q % P] = dref[:, b, q]
        np.testing.assert_array_equal(got, want)


def test_paged_kernel_decode_reads_its_layer():
    """With `use_paged_kernel`, the carried stacked pools reach the Pallas
    kernel one layer at a time: logits and pools agree with the gather
    path up to float32 reduction order, and layer 0's write, which no
    attention precedes, is bit-identical."""
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)
    B, P, n_max, npages = 3, 4, 4, 15
    lens = [6, 3, 5]
    _, paged, bt, regs = _chunk_inputs(cfg, params, B, P, n_max, npages,
                                       lens, [True, True, False])
    tok, pos, active = regs[:3]
    outs = [MD.decode_step(params, c, tok, pos, paged, active=active,
                           block_tables=bt, logical_len=n_max * P)
            for c in (cfg, cfg.with_(use_paged_kernel=True))]
    (l_g, c_g), (l_k, c_k) = outs
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_g),
                               rtol=1e-5, atol=1e-5)
    for name in MD.paged_leaf_names(cfg):
        k, g = np.asarray(c_k[name]), np.asarray(c_g[name])
        np.testing.assert_array_equal(k[0], g[0])
        np.testing.assert_allclose(k, g, rtol=1e-5, atol=1e-5)


def test_paged_decode_rejects_recurrent_cache():
    cfg = _cfg("rwkv6-1.6b")
    with pytest.raises(ValueError, match="no KV"):
        MD.init_paged_cache(cfg, 2, 8, 4)
    params = MD.init_model(cfg, KEY)
    with pytest.raises(ValueError, match="no KV cache to page"):
        ServeEngine(params, cfg, num_slots=2, cache_len=16, page_size=4)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "zamba2-1.2b"])
def test_paged_engine_matches_dense_engine(arch):
    """A full mixed-length stream through the paged engine produces the
    same tokens as the dense engine (page granularity is invisible)."""
    cfg = _cfg(arch)
    params = MD.init_model(cfg, KEY)

    def stream():
        rng = np.random.RandomState(4)
        return [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size,
                                           size=int(rng.choice([5, 9]))),
                        max_new_tokens=int(rng.choice([3, 7])))
                for i in range(7)]

    dense = ServeEngine(params, cfg, num_slots=3, cache_len=20)
    ref = {f.rid: f.tokens for f in dense.run(stream())}
    eng = ServeEngine(params, cfg, num_slots=3, cache_len=20, page_size=4)
    fins = eng.run(stream())
    assert len(fins) == 7
    for f in fins:
        assert f.tokens == ref[f.rid], f"rid {f.rid}"
    st = eng.stats()
    assert st["preemptions"] == 0      # ample pool: no pressure
    assert 0.0 < st["pool_occupancy"] <= 1.0


def test_paged_tight_pool_preempts_and_stays_identical():
    """Undersized pool: the engine must preempt (newest slot first) into
    prefix continuations when pages run dry, and the outputs must STILL
    match the dense engine bit-for-bit — preemption changes scheduling,
    never content."""
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)

    def stream():
        rng = np.random.RandomState(5)
        return [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size, size=8),
                        max_new_tokens=10) for i in range(6)]

    dense = ServeEngine(params, cfg, num_slots=3, cache_len=20)
    ref = {f.rid: f.tokens for f in dense.run(stream())}
    # n_max = 5 pages; 9 pages cannot hold 3 slots at full length
    eng = ServeEngine(params, cfg, num_slots=3, cache_len=20, page_size=4,
                      num_pages=9)
    fins = eng.run(stream())
    st = eng.stats()
    assert st["preemptions"] >= 1
    assert len(fins) == 6
    for f in fins:
        assert f.tokens == ref[f.rid]
    # a tight pool is a BUSY pool — that is the point of paging
    assert st["pool_occupancy"] >= 0.5


def test_paged_pool_too_small_raises():
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)
    with pytest.raises(ValueError, match="num_pages"):
        ServeEngine(params, cfg, num_slots=2, cache_len=20, page_size=4,
                    num_pages=4)    # one max-length request needs 5


def test_cancel_frees_slot_and_pages():
    """cancel() on an active request frees its slot AND its pages; on a
    queued request it just drops it.  Survivors finish identically."""
    cfg = _cfg("qwen3-0.6b")
    params = MD.init_model(cfg, KEY)

    def stream():
        rng = np.random.RandomState(6)
        return [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size, size=6),
                        max_new_tokens=8) for i in range(4)]

    dense = ServeEngine(params, cfg, num_slots=2, cache_len=16)
    ref = {f.rid: f.tokens for f in dense.run(stream())}

    eng = ServeEngine(params, cfg, num_slots=2, cache_len=16, page_size=4)
    for q in stream():
        eng.submit(q)
    for _ in range(3):          # rid 0,1 active; 2,3 queued
        eng.tick()
    assert eng.cancel(1)        # active
    assert eng.cancel(3)        # queued
    assert not eng.cancel(99)   # unknown rid
    while not eng.scheduler.done:
        eng.tick()
    fins = {f.rid: f.tokens for f in eng.finished}
    assert set(fins) == {0, 2}
    for rid, toks in fins.items():
        assert toks == ref[rid]
    assert eng.pages.num_free == eng.num_pages   # every page returned


def test_page_pool_unit():
    from repro.serving import PagePool
    pool = PagePool(6, page_size=4)
    assert pool.pages_for(0) == 0 and pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1 and pool.pages_for(5) == 2
    a = pool.alloc(0, 3)
    assert a == [0, 1, 2] and pool.num_free == 3 and pool.pages_in_use == 3
    b = pool.alloc(1, 2)
    assert b == [3, 4]
    assert pool.alloc(2, 2) is None       # only one page left: refuse whole
    assert pool.num_free == 1             # ... and nothing leaked
    assert pool.release(0) == [0, 1, 2]
    assert pool.num_free == 4
    c = pool.alloc(2, 4)
    assert c == [0, 1, 2, 5]              # lowest-id-first, deterministic
