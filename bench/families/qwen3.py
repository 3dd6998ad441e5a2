"""The qwen3 family (https://huggingface.co/Qwen/Qwen3-0.6B): dense decoder
with GQA, qk-norm, SwiGLU and an untied LM head.  The program's
`ModelConfig` from a configuration file, and its model operations and
bytes from shapes.  `c` is a configuration file's dict (published key
names).

- Training, per token of a row of S: 6 x (layer matmul params + head) +
  6 x L x Hq x dh x S, i.e. causal attention at an average context S/2,
  forward and backward.  Recomputation and the embedding gather are not
  counted.
- Serving, one token at context t (t positions attended, itself
  included): 2 x (layer matmul params + head) + 4 x L x Hq x dh x t.
- A decode tick's least bytes: every weight once except the embedding
  (a gather of one row per token), plus the resident KV it reads, plus
  the KV it writes, in the served dtype.
"""
from __future__ import annotations


def model_config(c, **override):
    """Configuration file (published key names) -> the program's
    ModelConfig.  `override` sets run settings such as dtypes and remat."""
    from repro.models.config import ModelConfig
    kw = dict(
        name=c["name"], arch_type="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=True, rope=True, rope_theta=float(c["rope_theta"]),
        activation="swiglu", norm_eps=c["rms_norm_eps"],
        param_dtype=c.get("torch_dtype", "bfloat16"),
        compute_dtype=c.get("torch_dtype", "bfloat16"))
    kw.update(override)
    return ModelConfig(**kw)


def _d(c):
    d = c["hidden_size"]
    dh = c["head_dim"]
    return (d, c["num_attention_heads"], c["num_key_value_heads"], dh,
            c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"])


def layer_matmul_params(c) -> int:
    d, hq, hk, dh, ff, _, _ = _d(c)
    return d * hq * dh + 2 * d * hk * dh + hq * dh * d + 3 * d * ff


def head_params(c) -> int:
    d, *_ , V, _ = _d(c)
    return d * V


def matmul_params(c) -> int:
    return c["num_hidden_layers"] * layer_matmul_params(c) + head_params(c)


def norm_params(c) -> int:
    d, hq, hk, dh, ff, V, L = _d(c)
    return L * (2 * d + 2 * dh) + d


def total_params(c) -> int:
    d, *_ , V, _ = _d(c)
    return matmul_params(c) + V * d + norm_params(c)


def train_flops_per_token(c, seq: int) -> int:
    d, hq, hk, dh, ff, V, L = _d(c)
    return 6 * matmul_params(c) + 6 * L * hq * dh * seq


def token_flops(c, t: int) -> int:
    d, hq, hk, dh, ff, V, L = _d(c)
    return 2 * matmul_params(c) + 4 * L * hq * dh * t


def prefill_flops(c, n: int) -> int:
    """A prompt of n tokens: token i (1-based) attends i positions."""
    d, hq, hk, dh, ff, V, L = _d(c)
    return 2 * matmul_params(c) * n + 4 * L * hq * dh * n * (n + 1) // 2


def kv_bytes_per_token(c, itemsize: int = 2) -> int:
    d, hq, hk, dh, ff, V, L = _d(c)
    return L * 2 * hk * dh * itemsize


def decode_tick_bytes(c, active: int, resident: int,
                      itemsize: int = 2) -> int:
    """One decode tick of `active` slots holding `resident` tokens of KV
    between them before the tick."""
    weights = (matmul_params(c) + norm_params(c)) * itemsize
    kv = kv_bytes_per_token(c, itemsize)
    return weights + kv * resident + kv * active


def decode_tick_flops(c, active: int, resident: int) -> int:
    """One decode tick: each active slot's new token attends its resident
    tokens plus itself."""
    d, hq, hk, dh, ff, V, L = _d(c)
    return (2 * matmul_params(c) * active
            + 4 * L * hq * dh * (resident + active))
