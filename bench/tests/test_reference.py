"""The plain reference against the program on the CPU at the SMOKE size of
qwen3-0.6b: the full forward's logits, the loss and its gradients, and
prefill plus decode through the cache, compared by logits; and the tokens
`ServeEngine` serves, judged by the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program
from bench.common import load_json
from bench.reference import qwen3 as R

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def smoke():
    from repro.configs.qwen3_0p6b import SMOKE
    c = dict(load_json("configs", "qwen3-0.6b"),
             hidden_size=SMOKE.d_model, intermediate_size=SMOKE.d_ff,
             num_hidden_layers=SMOKE.num_layers,
             num_attention_heads=SMOKE.num_heads,
             num_key_value_heads=SMOKE.num_kv_heads,
             head_dim=SMOKE.head_dim, vocab_size=SMOKE.vocab_size,
             torch_dtype="float32")
    cfg = program.model_config(c, remat="none")
    w = R.make_weights(c, SEED, jnp.float32)
    program.check_tree(w, cfg)
    return c, cfg, w


def _tokens(c, B, S, salt=0):
    rng = np.random.default_rng(salt)
    return rng.integers(0, c["vocab_size"], (B, S)).astype(np.int32)


def test_weights_are_seeded_and_leafwise(smoke):
    c, _, w = smoke
    again = R.make_weights(c, SEED, jnp.float32)
    other = R.make_weights(c, SEED + 1, jnp.float32)
    leaf = jax.tree_util.tree_leaves
    assert all(np.array_equal(a, b) for a, b in zip(leaf(w), leaf(again)))
    assert not np.array_equal(w["lm_head"], other["lm_head"])
    for i, (path, _, _) in enumerate(R.leaf_table(c)):
        np.testing.assert_array_equal(R.make_leaf(c, SEED, i, jnp.float32),
                                      R._get(w, path))


def test_forward_logits_match(smoke):
    from repro.models import model as MD
    c, cfg, w = smoke
    toks = _tokens(c, 1, 24)
    prog, _, _ = MD.forward(w, cfg, jnp.asarray(toks))
    fwd = R.Forward(c, w, "f32")
    ref = fwd.logits(toks[0], np.arange(24), pad_to=32)
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_loss_and_gradients_match(smoke):
    from repro.models import model as MD
    c, cfg, w = smoke
    toks = _tokens(c, 2, 17, salt=1)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss, grads = jax.value_and_grad(MD.lm_loss)(
        w, cfg, {k: jnp.asarray(v) for k, v in batch.items()})
    hyp = dict(load_json("workloads", "train-1chip")["optimizer"])
    tr = R.Trainer(c, SEED, hyp, "f32")
    rloss, rnorms = tr.step(batch["tokens"], batch["labels"])
    assert abs(float(loss) - rloss) < 1e-5
    gn = float(jnp.sqrt(sum(jnp.sum(g * g)
                            for g in jax.tree_util.tree_leaves(grads))))
    scale = min(1.0, 1.0 / (gn + 1e-9))
    for i, (path, _, _) in enumerate(R.leaf_table(c)):
        g = np.asarray(R._get(grads, path)) * scale
        if path[0] == "blocks":
            for layer in range(g.shape[0]):
                name = R.leaf_name(layer + 1, path[1:])
                assert abs(np.linalg.norm(g[layer]) - rnorms[name]) <= \
                    1e-4 * max(rnorms[name], 1e-3), name
        else:
            name = R.leaf_name(0, path)
            assert abs(np.linalg.norm(g) - rnorms[name]) <= \
                1e-4 * max(rnorms[name], 1e-3), name


def test_prefill_then_decode_match(smoke):
    """Prefill into the cache, then decode token by token through it, as
    the serving engine's programs do; each step's logits against the
    reference's full forward of the same sequence."""
    from repro.models import model as MD
    c, cfg, w = smoke
    seq = _tokens(c, 1, 20, salt=2)[0]
    P, C = 12, 32
    logits, _, cache = MD.forward(w, cfg, jnp.asarray(seq[None, :P]),
                                  return_cache=True, cache_len=C)
    got = [np.asarray(logits[0, -1])]
    for t in range(P, len(seq) - 1):
        lg, cache = MD.decode_step(w, cfg, jnp.asarray(seq[None, t:t + 1]),
                                   jnp.int32(t), cache)
        got.append(np.asarray(lg[0, -1]))
    fwd = R.Forward(c, w, "f32")
    ref = np.asarray(fwd.logits(seq, np.arange(P - 1, len(seq) - 1), C))
    np.testing.assert_allclose(np.stack(got), ref, atol=2e-4, rtol=2e-4)


def test_served_tokens_are_the_references_best(smoke):
    from repro.serving import Request, ServeEngine
    c, cfg, w = smoke
    eng = ServeEngine(w, cfg, num_slots=3, cache_len=48, page_size=8)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, c["vocab_size"], n)
                    .astype(np.int32), max_new_tokens=m)
            for i, (n, m) in enumerate([(9, 12), (17, 7), (4, 20), (30, 5)])]
    done = eng.run(reqs)
    fwd = R.Forward(c, w, "f32")
    for f in done:
        gaps = R.served_gaps(fwd, reqs[f.rid].prompt, f.tokens, 48)
        assert len(f.tokens) == reqs[f.rid].max_new_tokens
        assert float(np.max(gaps)) < 1e-4
