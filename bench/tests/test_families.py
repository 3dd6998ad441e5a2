"""A model family is found by name: a configuration of a new family needs
only `bench/families/<family>.py` and `bench/reference/<family>.py`, and
no edit to `program.py`, `flops.py` or a driver.  A family with no module
is an error that names the missing file."""
import sys
import textwrap

import jax
import pytest

import bench.families
import bench.reference
from bench import flops, program, run
from bench.common import load_reference
from bench.tests import tiny

SEED = 2**31 + 23

FAMILY = textwrap.dedent("""
    from bench.families import qwen3 as _q
    from bench.families.qwen3 import *  # noqa: F401,F403

    calls = []


    def _logged(name):
        def f(c, *a, **kw):
            calls.append(name)
            return getattr(_q, name)(c, *a, **kw)
        return f


    model_config = _logged("model_config")
    train_flops_per_token = _logged("train_flops_per_token")
    prefill_flops = _logged("prefill_flops")
    decode_tick_flops = _logged("decode_tick_flops")
    decode_tick_bytes = _logged("decode_tick_bytes")
""")
REFERENCE = "from bench.reference.qwen3 import *  # noqa: F401,F403\n"


@pytest.fixture
def alias(tmp_path, monkeypatch):
    """A family `alias3`, qwen3's formulas and reference under another
    name, in new files on the two packages' lookup paths."""
    for pkg, text in ((bench.families, FAMILY), (bench.reference, REFERENCE)):
        d = tmp_path / pkg.__name__.split(".")[-1]
        d.mkdir()
        (d / "alias3.py").write_text(text)
        monkeypatch.setattr(pkg, "__path__", [str(d), *pkg.__path__])
    yield dict(tiny.CONFIG, family="alias3", name="alias3-tiny")
    for pkg in ("bench.families", "bench.reference"):
        sys.modules.pop(f"{pkg}.alias3", None)


def test_new_family_runs_through_the_drivers(alias):
    cpu = jax.devices("cpu")[:1]
    serve = run.execute(dict(tiny.SERVE, config="alias3-tiny"), alias,
                        tiny.SERVE_MIX, SEED, 2.0, 0, platform="cpu",
                        devices=cpu, bench=tiny.BENCH, cell="tiny-serve")
    train = run.execute(dict(tiny.train_cell(), config="alias3-tiny"), alias,
                        tiny.LM_MIX, SEED, 1.0, 0, platform="cpu",
                        devices=cpu, bench=tiny.BENCH, cell="tiny-train")
    assert serve["correct"] and train["correct"], (serve, train)
    assert serve["metrics"]["output_tokens_per_s"]["value"] > 0
    assert train["metrics"]["train_tokens_per_s"]["value"] > 0
    calls = set(sys.modules["bench.families.alias3"].calls)
    assert calls == {"model_config", "train_flops_per_token",
                     "prefill_flops", "decode_tick_flops",
                     "decode_tick_bytes"}


@pytest.mark.parametrize("lookup, missing", [
    (lambda c: program.model_config(c), "bench/families/nosuch.py"),
    (lambda c: flops.prefill_flops(c, 8), "bench/families/nosuch.py"),
    (lambda c: load_reference(c["family"]), "bench/reference/nosuch.py"),
])
def test_unknown_family_names_the_missing_file(lookup, missing):
    c = dict(tiny.CONFIG, family="nosuch")
    with pytest.raises(ModuleNotFoundError, match=missing):
        lookup(c)
