"""The controls that set the upper readings of `correct`, kept at a size a
test run can hold: on the CPU, at toy widths and in float32, the program
reads under the tiny cells' limits and each control reads over one of
them.  (On the chip the same readings come from `bench/control.py` at the
cells' own sizes.)"""
import jax
import pytest

from bench import control
from bench.tests import tiny

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[:1]


def test_serving_controls_fail(cpu):
    r = control.readings(dict(tiny.SERVE, check_tokens=200,
                              check_max_requests=12),
                         tiny.CONFIG, tiny.SERVE_MIX, SEED, 3.0,
                         ["fp8", "bf16", "altered"], platform="cpu",
                         devices=cpu)
    lim = tiny.SERVE["limits"]["logit_gap"]
    assert r["correct"] and r["program"]["logit_gap"] <= lim
    for mode in ("fp8", "bf16", "altered"):
        assert r["controls"][mode]["logit_gap"] > lim, (mode, r)


def test_training_controls_fail(cpu):
    w = tiny.train_cell()
    r = control.readings(w, tiny.CONFIG, tiny.LM_MIX, SEED, 0.0,
                         ["bf16", "half"], platform="cpu", devices=cpu)
    assert r["correct"], r
    for mode in ("bf16", "half"):
        c = r["controls"][mode]
        assert any(c[k] > w["limits"][k] for k in w["limits"]), (mode, c)


def test_exchange_left_out_fails():
    """The reference with no gradient exchange between two data shards,
    against the reference, reads over the tiny cell's limits."""
    import numpy as np
    from bench import correct
    from bench.reference import qwen3 as R
    w = tiny.train_cell()
    rng = np.random.default_rng(1)
    rows = [(t[:, :-1], t[:, 1:]) for t in
            (rng.integers(0, tiny.CONFIG["vocab_size"], (4, 33))
             for _ in range(3))]
    ref = R.train_reference(tiny.CONFIG, SEED, rows, w["optimizer"])
    local = R.train_reference(tiny.CONFIG, SEED, rows, w["optimizer"],
                              local_groups=2)
    nums = correct.train_numbers(local, ref)
    assert any(nums[k] > w["limits"][k] for k in w["limits"]), nums
    assert correct.train_numbers(ref, ref)["update_norm_gap"] == 0
