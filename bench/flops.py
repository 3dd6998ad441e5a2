"""Model operations and bytes from shapes, by family: each function calls
the one of the same name in `bench/families/<c["family"]>.py`, where the
formulas and what they count are written (the contract is in
bench/families/__init__.py).  `c` is a configuration file's dict."""
from __future__ import annotations

from bench.common import load_family


def _by_family(name: str):
    def count(c, *args, **kw):
        return getattr(load_family(c["family"]), name)(c, *args, **kw)
    count.__name__ = count.__qualname__ = name
    return count


# what the drivers read
train_flops_per_token = _by_family("train_flops_per_token")
prefill_flops = _by_family("prefill_flops")
decode_tick_flops = _by_family("decode_tick_flops")
decode_tick_bytes = _by_family("decode_tick_bytes")
# the sizes behind them, for tests and reports
layer_matmul_params = _by_family("layer_matmul_params")
head_params = _by_family("head_params")
matmul_params = _by_family("matmul_params")
norm_params = _by_family("norm_params")
total_params = _by_family("total_params")
token_flops = _by_family("token_flops")
kv_bytes_per_token = _by_family("kv_bytes_per_token")
