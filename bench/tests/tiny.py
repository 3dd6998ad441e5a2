"""Small files for CPU tests: a qwen3-shaped configuration at toy widths
and cells that drive the same drivers at sizes a test run can hold."""
import copy

from bench.common import load_json

CONFIG = dict(load_json("configs", "qwen3-0.6b"), name="qwen3-tiny",
              hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              vocab_size=512, torch_dtype="float32")

SERVE_MIX = {"kind": "open_loop", "rate_per_s": 40.0,
             "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64,
                        "grid": [8, 16, 32, 64]},
             "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
LM_MIX = {"kind": "lm", "seq": 32, "source": "bigram"}

SERVE = {"name": "tiny-serve", "config": "qwen3-tiny", "chips": 1,
         "driver": "serve", "slots": 4, "page_size": 8, "cache_len": 80,
         "check_tokens": 40, "check_max_requests": 4,
         "limits": {"logit_gap": 1e-3}}


def train_cell(chips=1, mesh=(1, 1), env="dp_tp", batch=4):
    w = copy.deepcopy(load_json("workloads", "train-1chip"))
    w.update(name="tiny-train", config="qwen3-tiny", chips=chips,
             mesh={"data": mesh[0], "model": mesh[1]}, env=env, batch=batch,
             compute_dtype="float32",
             limits={"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                     "update_norm_gap": 1e-3})
    return w


BENCH = {"end_to_end": [
    {"name": n, "unit": "x"} for n in
    ("ttft_p95_ms", "tpot_p95_ms", "output_tokens_per_s",
     "train_tokens_per_s", "setup_s")],
    "per_layer": []}
