"""Plain Qwen3 reference: weights from a seed, logits, loss and AdamW steps.

Qwen3 dense decoder (https://huggingface.co/Qwen/Qwen3-0.6B): pre-norm
RMSNorm blocks, grouped-query attention with RMSNorm on each query and key
head (qk-norm), rotary embeddings on the two halves of each head
(rotate-half, theta from the config), a SwiGLU MLP, a final RMSNorm and an
LM head.  Departure from the published model, shared with the program: the
LM head is its own matrix (`tie_word_embeddings` false).

Written from that description in straightforward `jax.numpy`: no cache, no
kernels, no batching of requests, one layer at a time.  It imports nothing
of the program under test.  Its default precision is float32 with every
matrix product at `Precision.HIGHEST`.  Two lower precisions exist for the
controls only: "bf16" (weights, activations and the stored parameters
rounded to bfloat16, products accumulated in float32) and "fp8" (each
weight matrix rounded to float8_e4m3fn with one scale per output column).

The weights are the benchmark's, made from the seed by `make_weights`; the
program is handed the same tree in the dtype it serves or trains in.
"""
from __future__ import annotations

import gc
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import jax_key

HI = jax.lax.Precision.HIGHEST
WEIGHT_SALT = 0x5EED

# (path, shape-from-config, init) of every leaf, in a fixed order; the index
# in this list picks the leaf's random stream, so adding a leaf at the end
# never changes the others
_LAYER = [
    (("ln1",), "d", "norm"),
    (("attn", "wq"), "d,q", "in_d"),
    (("attn", "wk"), "d,kv", "in_d"),
    (("attn", "wv"), "d,kv", "in_d"),
    (("attn", "wo"), "q,d", "in_q"),
    (("attn", "q_scale"), "dh", "norm"),
    (("attn", "k_scale"), "dh", "norm"),
    (("ln2",), "d", "norm"),
    (("mlp", "w1"), "d,ff", "in_d"),
    (("mlp", "w2"), "ff,d", "in_ff"),
    (("mlp", "w3"), "d,ff", "in_d"),
]
_TOP = [
    (("embed",), "V,d", "embed"),
    (("final_norm",), "d", "norm"),
    (("lm_head",), "d,V", "in_d"),
]


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    d = c["hidden_size"]
    dh = c["head_dim"]
    return {"d": d, "dh": dh, "q": c["num_attention_heads"] * dh,
            "kv": c["num_key_value_heads"] * dh,
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "L": c["num_hidden_layers"], "Hq": c["num_attention_heads"],
            "Hk": c["num_key_value_heads"]}


def leaf_table(c) -> List[Tuple[tuple, tuple, str]]:
    """(path, shape, init) of every leaf; layer leaves stacked (L, ...)."""
    D = dims(c)
    out = []
    for path, shp, init in _LAYER:
        out.append((("blocks",) + path,
                    (D["L"],) + tuple(D[s] for s in shp.split(",")), init))
    for path, shp, init in _TOP:
        out.append((path, tuple(D[s] for s in shp.split(",")), init))
    return out


def _init_value(key, shape, init, D):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "norm":
        return 1.0 + 0.1 * z
    if init == "embed":
        return 0.02 * z
    fan = {"in_d": D["d"], "in_q": D["q"], "in_ff": D["ff"]}[init]
    return z / math.sqrt(fan)


def _set(tree, path, val):
    node = tree
    for k in path[:-1]:
        node = node.setdefault(k, {})
    node[path[-1]] = val


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def make_weights(c, seed, dtype, shardings=None):
    """The whole weight tree, made on the device in one jitted call from
    the seed, in `dtype`; `shardings` (a tree of the same shape) places
    each leaf as the program wants it."""
    table = leaf_table(c)
    D = dims(c)
    base = jax_key(seed, WEIGHT_SALT)

    def build(base):
        tree: Dict[str, Any] = {}
        for i, (path, shape, init) in enumerate(table):
            v = _init_value(jax.random.fold_in(base, i), shape, init, D)
            _set(tree, path, v.astype(dtype))
        return tree

    return jax.jit(build, out_shardings=shardings)(base)


def make_leaf(c, seed, index: int, dtype, sharding=None):
    """One leaf of `make_weights`, alone, with the same values."""
    table = leaf_table(c)
    path, shape, init = table[index]
    D = dims(c)

    def build(base):
        key = jax.random.fold_in(base, index)
        return _init_value(key, shape, init, D).astype(dtype)

    return jax.jit(build, out_shardings=sharding)(jax_key(seed, WEIGHT_SALT))


# --------------------------------------------------------------------------
# the model, one layer at a time
# --------------------------------------------------------------------------
def _fp8(w):
    """float8_e4m3fn with one scale per output column, back in float32."""
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    if mode == "bf16":
        y = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        return y.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        w = _fp8(w.astype(jnp.float32))
    return jnp.dot(x, w.astype(jnp.float32), precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (B,S,H,dh); rotate-half over the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def layer(lp, h, c, mode="f32"):
    """One decoder layer on h (B,S,d), positions 0..S-1, causal."""
    D = dims(c)
    eps = c["rms_norm_eps"]
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = _rms(h, lp["ln1"], eps)
    q = _mm(x, lp["attn"]["wq"], mode).reshape(B, S, D["Hq"], D["dh"])
    k = _mm(x, lp["attn"]["wk"], mode).reshape(B, S, D["Hk"], D["dh"])
    v = _mm(x, lp["attn"]["wv"], mode).reshape(B, S, D["Hk"], D["dh"])
    q = _rope(_rms(q, lp["attn"]["q_scale"], eps), pos, c["rope_theta"])
    k = _rope(_rms(k, lp["attn"]["k_scale"], eps), pos, c["rope_theta"])
    g = D["Hq"] // D["Hk"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    if mode == "bf16":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI,
                   preferred_element_type=jnp.float32) / math.sqrt(D["dh"])
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if mode == "bf16":
        p = p.astype(jnp.bfloat16)
    o = jnp.einsum("bhst,bthd->bshd", p, v, precision=HI,
                   preferred_element_type=jnp.float32)
    h = h + _mm(o.reshape(B, S, D["q"]), lp["attn"]["wo"], mode)
    x = _rms(h, lp["ln2"], eps)
    a = jax.nn.silu(_mm(x, lp["mlp"]["w1"], mode)) * _mm(x, lp["mlp"]["w3"],
                                                          mode)
    h = h + _mm(a, lp["mlp"]["w2"], mode)
    if mode == "bf16":
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
    return h


def split_layers(c, weights, sharding_of=None) -> Tuple[dict, List[dict]]:
    """Stacked tree -> (top-level leaves, per-layer list), float32."""
    L = dims(c)["L"]
    layers = []
    put = sharding_of or (lambda a: None)
    for i in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32),
                                    weights["blocks"])
        lp = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, put(a)) if put(a) else a, lp)
        layers.append(lp)
    top = {k: weights[k].astype(jnp.float32)
           for k in ("embed", "final_norm", "lm_head")}
    top = {k: jax.device_put(a, put(a)) if put(a) else a
           for k, a in top.items()}
    return top, layers


# --------------------------------------------------------------------------
# serving: logits of given positions of one sequence
# --------------------------------------------------------------------------
class Forward:
    """Full forward of one sequence (padded to a fixed length, so one
    program serves every request) and the logits at chosen positions."""

    def __init__(self, c, weights, mode="f32"):
        self.c, self.mode = c, mode
        self.top, self.layers = split_layers(c, weights)
        self._layer = jax.jit(lambda lp, h: layer(lp, h, c, mode))

        def head(top, h, rows):
            x = _rms(h[0, rows], top["final_norm"], c["rms_norm_eps"])
            return _mm(x, top["lm_head"], mode)

        self._head = jax.jit(head)

    def logits(self, tokens: np.ndarray, rows: np.ndarray, pad_to: int):
        """tokens (S,), rows: positions whose next-token logits to return."""
        t = np.zeros((pad_to,), np.int32)
        t[: len(tokens)] = tokens
        h = jnp.take(self.top["embed"], jnp.asarray(t)[None], axis=0)
        for lp in self.layers:
            h = self._layer(lp, h)
        return self._head(self.top, h, jnp.asarray(rows, jnp.int32))


def served_gaps(fwd: Forward, prompt, served, pad_to: int,
                judge: Optional[Forward] = None) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position.  With `judge`, `fwd` is a control:
    the token it would put first is judged by `judge`'s logits instead."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = judge or fwd
    lg = ref.logits(seq, rows, pad_to)
    if judge is not None:
        served = np.asarray(jnp.argmax(fwd.logits(seq, rows, pad_to), -1))
    gap = jnp.max(lg, -1) - jnp.take_along_axis(
        lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return np.asarray(gap)


# --------------------------------------------------------------------------
# training: loss, gradients and AdamW, layer by layer
# --------------------------------------------------------------------------
def warmup_cosine(h, step: int) -> float:
    """Learning rate at update `step` (0-based): linear warm-up over
    `warmup` updates to `lr`, then a cosine to `lr * floor` at `total`."""
    lr, warm, total, floor = h["lr"], h["warmup"], h["total_steps"], h["floor"]
    if step < warm:
        return lr * min(1.0, (step + 1) / max(warm, 1))
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))


class Trainer:
    """AdamW on the plain model, layer by layer, so that it fits beside
    nothing else on the device(s).  One step is two passes over the
    layers: the first finds the global gradient norm (for clipping), the
    second applies the update with it.  `mesh` spreads weights over its one
    axis `x` and rows of the batch over the same axis."""

    def __init__(self, c, seed, hyper, mode="f32", mesh=None, rows_used=1.0):
        self.c, self.h, self.mode = c, hyper, mode
        self.mesh = mesh
        self.rows_used = rows_used
        pdt = jnp.bfloat16 if mode == "bf16" else jnp.float32
        self.pdt = pdt
        w = make_weights(c, seed, pdt)
        shard = self._weight_sharding if mesh is not None else None
        self.top, self.layers = split_layers(c, w, shard)
        del w
        if pdt == jnp.bfloat16:
            cast = lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), t)
            self.top, self.layers = cast(self.top), [cast(l) for l in
                                                       self.layers]
        zeros = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32, device=a.sharding), t)
        self.m = [zeros(self.top)] + [zeros(l) for l in self.layers]
        self.v = [zeros(self.top)] + [zeros(l) for l in self.layers]
        self.t = 0
        eps = c["rms_norm_eps"]

        def fwd_layer(lp, h):
            return layer(lp, h, c, mode)

        def vjp_layer(lp, h, ct):
            _, f = jax.vjp(fwd_layer, lp, h)
            return f(ct)

        def head_loss(top, h, labels, n_tok):
            def chunk(hc, lc):
                x = _rms(hc, top["final_norm"], eps)
                lg = _mm(x, top["lm_head"], mode)
                lse = jax.nn.logsumexp(lg, -1)
                gold = jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
                return jnp.sum(lse - gold)
            B, S, d = h.shape
            T = min(S, 512)
            hs = h.reshape(B * S // T, T, d)
            ls = labels.reshape(B * S // T, T)
            sums = jax.lax.map(lambda a: jax.checkpoint(chunk)(*a), (hs, ls))
            return jnp.sum(sums) / n_tok

        def head_grad(top, h, labels, n_tok):
            return jax.value_and_grad(head_loss, argnums=(0, 1))(
                top, h, labels, n_tok)

        def sq(t):
            return sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                       for a in jax.tree_util.tree_leaves(t))

        def adam(p, g, m, v, scale, lr, t):
            b1, b2, e, wd = (self.h["b1"], self.h["b2"], self.h["eps"],
                             self.h["weight_decay"])

            def one(p, g, m, v):
                g = g.astype(jnp.float32) * scale
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                mh = m / (1 - b1 ** t)
                vh = v / (1 - b2 ** t)
                pf = p.astype(jnp.float32)
                pf = pf - lr * (mh / (jnp.sqrt(vh) + e) + wd * pf)
                return pf.astype(p.dtype), m, v
            out = jax.tree_util.tree_map(one, p, g, m, v)
            tr = lambda i: jax.tree_util.tree_map(
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
            return tr(0), tr(1), tr(2)

        def embed_grad(ct, tokens, V):
            return jnp.zeros((V, ct.shape[-1]), jnp.float32).at[
                tokens.reshape(-1)].add(ct.reshape(-1, ct.shape[-1]))

        self._fwd = jax.jit(fwd_layer)
        self._vjp = jax.jit(vjp_layer)
        self._head = jax.jit(head_grad)
        self._sq = jax.jit(sq)
        self._adam = jax.jit(adam, donate_argnums=(0, 2, 3))
        self._embed_grad = jax.jit(embed_grad, static_argnums=(2,))
        self._gather = jax.jit(lambda e, t: jnp.take(e, t, axis=0)
                               .astype(jnp.float32))

    def _weight_sharding(self, a):
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = self.mesh.shape["x"]
        if a.ndim >= 2 and a.shape[0] % n == 0:
            return NamedSharding(self.mesh, P("x"))
        return NamedSharding(self.mesh, P())

    def _rows(self, x):
        if self.mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.mesh, P("x")))

    def _grads(self, tokens, labels, hs):
        """Yields (index, gradient tree) top first, then layers last-first;
        index 0 is the top-level tree, i+1 layer i."""
        n_tok = float(labels.size)
        loss, (g_top, ct) = self._head(
            {k: self.top[k] for k in ("final_norm", "lm_head")}, hs[-1],
            labels, n_tok)
        g_layers = {}
        for i in range(len(self.layers) - 1, -1, -1):
            g_lp, ct = self._vjp(self.layers[i], hs[i], ct)
            yield i + 1, g_lp
        g_top = dict(g_top, embed=self._embed_grad(
            ct, tokens, self.top["embed"].shape[0]))
        self._loss = loss
        yield 0, g_top

    def step(self, tokens: np.ndarray, labels: np.ndarray):
        """One AdamW update on the batch; returns (loss, per-leaf norms of
        the gradient the optimizer got, as {name: norm})."""
        if self.rows_used < 1.0:
            keep = max(1, int(round(tokens.shape[0] * self.rows_used)))
            tokens, labels = tokens[:keep], labels[:keep]
        tokens, labels = self._rows(tokens), self._rows(labels)
        h = self._gather(self.top["embed"], tokens)
        hs = [h]
        for lp in self.layers:
            hs.append(self._fwd(lp, hs[-1]))
        total = 0.0
        norms = {}
        for idx, g in self._grads(tokens, labels, hs):
            for name, val in flat_norms(g, idx).items():
                norms[name] = val
            total = total + self._sq(g)
        gnorm = float(jnp.sqrt(total))
        loss = float(self._loss)
        scale = min(1.0, 1.0 / (gnorm + 1e-9))
        self.t += 1
        lr = warmup_cosine(self.h, self.t - 1)
        for idx, g in self._grads(tokens, labels, hs):
            if idx == 0:
                top = self.top
                p, m, v = self._adam(top, g, self.m[0], self.v[0],
                                     scale, lr, float(self.t))
                self.top, self.m[0], self.v[0] = p, m, v
            else:
                p, m, v = self._adam(self.layers[idx - 1], g, self.m[idx],
                                     self.v[idx], scale, lr, float(self.t))
                self.layers[idx - 1], self.m[idx], self.v[idx] = p, m, v
        del hs
        return loss, {k: v * scale for k, v in norms.items()}

    def change_norms(self, seed) -> Dict[str, float]:
        """Per-leaf norm of (weights now - weights at the seed)."""
        w0 = make_weights(self.c, seed, self.pdt)
        top0, layers0 = split_layers(self.c, w0)
        del w0
        out = {}
        diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
        out.update(flat_norms(diff(self.top, jax.device_put(top0)), 0))
        for i, (lp, lp0) in enumerate(zip(self.layers, layers0)):
            out.update(flat_norms(diff(lp, lp0), i + 1))
        return out


def leaf_name(idx: int, path: Sequence[str]) -> str:
    """Names shared with the program's per-layer slices: 'embed',
    'blocks.3.attn.wq', ..."""
    p = ".".join(path)
    return p if idx == 0 else f"blocks.{idx - 1}.{p}"


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def flat_norms(tree, idx: int) -> Dict[str, float]:
    norms = jax.device_get(_leaf_norms(tree))
    out = {}
    for path, val in jax.tree_util.tree_flatten_with_path(norms)[0]:
        keys = tuple(k.key for k in path)
        out[leaf_name(idx, keys)] = float(val)
    return out


def train_reference(c, seed, batches, hyper, mode="f32", mesh=None,
                    rows_used=1.0, steps=3):
    """Losses of the first `steps` updates, per-leaf norms of the first
    gradient as the optimizer got it, and per-leaf norms of the change of
    the weights after those steps."""
    tr = Trainer(c, seed, hyper, mode, mesh, rows_used)
    losses, g1 = [], None
    for i, (tok, lab) in enumerate(batches[:steps]):
        loss, norms = tr.step(np.asarray(tok), np.asarray(lab))
        losses.append(loss)
        if i == 0:
            g1 = norms
    out = {"losses": losses, "grad_norms": g1,
           "change_norms": tr.change_norms(seed)}
    del tr
    gc.collect()  # the trainer's jitted closures hold it in a cycle
    return out
