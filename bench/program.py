"""The system under test, as the harness builds it from a configuration
file: the program's own `ModelConfig`, filled from published key names."""
from __future__ import annotations

import sys

from bench.common import SRC

if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def model_config(c, **override):
    """Configuration file (published key names) -> the program's
    ModelConfig.  `override` sets run settings such as dtypes and remat."""
    from repro.models.config import ModelConfig
    if c.get("family") != "qwen3":
        raise ValueError(f"no mapping for family {c.get('family')!r}")
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


def check_tree(weights, cfg) -> None:
    """The benchmark's weight tree has exactly the program's leaves."""
    import jax
    from repro.models import model as MD
    want = MD.model_abstract(cfg)
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), weights)
    exp = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise ValueError(f"weight tree differs from the program's: "
                         f"{got} vs {exp}")
