"""bench/flops.py against counts made by hand, and against the program's
own parameter shapes."""
import math

import jax
import pytest

from bench import flops, program
from bench.common import load_json


def test_hand_counts_qwen3_0p6b():
    c = load_json("configs", "qwen3-0.6b")
    # wq 1024x2048, wk/wv 1024x1024, wo 2048x1024, w1/w3 1024x3072, w2
    layer = 2_097_152 + 2 * 1_048_576 + 2_097_152 + 3 * 3_145_728
    assert flops.layer_matmul_params(c) == layer == 15_728_640
    assert flops.head_params(c) == 155_582_464
    assert flops.matmul_params(c) == 28 * layer + 155_582_464
    assert flops.total_params(c) == 751_632_384
    # 6 x 595,984,384 + 6 x 28 x 16 x 128 x 2048
    assert flops.train_flops_per_token(c, 2048) == 4_280_549_376
    assert flops.token_flops(c, 1) == 2 * 595_984_384 + 4 * 28 * 2048
    assert flops.kv_bytes_per_token(c) == 28 * 2 * 8 * 128 * 2


def test_hand_counts_qwen3_1p7b():
    c = load_json("configs", "qwen3-1.7b")
    layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144
    assert flops.layer_matmul_params(c) == layer == 50_331_648
    assert flops.matmul_params(c) == 1_720_451_072
    assert flops.train_flops_per_token(c, 2048) == 11_027_349_504


def test_prefill_is_the_sum_of_its_tokens():
    c = load_json("configs", "qwen3-0.6b")
    assert flops.prefill_flops(c, 5) == sum(flops.token_flops(c, t)
                                            for t in range(1, 6))


def test_decode_tick_counts():
    c = load_json("configs", "qwen3-0.6b")
    w = (flops.matmul_params(c) + flops.norm_params(c)) * 2
    kv = flops.kv_bytes_per_token(c)
    assert flops.decode_tick_bytes(c, 4, 100) == w + 100 * kv + 4 * kv
    assert flops.decode_tick_flops(c, 4, 100) == (
        4 * 2 * flops.matmul_params(c) + 4 * 28 * 16 * 128 * 104)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "qwen3-1.7b"])
def test_total_params_match_the_program(name):
    from repro.models import model as MD
    c = load_json("configs", name)
    leaves = jax.tree_util.tree_leaves(MD.model_abstract(
        program.model_config(c)))
    assert sum(math.prod(a.shape) for a in leaves) == flops.total_params(c)
