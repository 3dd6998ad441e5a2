"""Parallelism equivalence on a real 8-device CPU mesh.

XLA locks the device count at first jax init, so the mesh checks run in a
subprocess with XLA_FLAGS set (tests/_par_worker.py); this file asserts on
its output and adds single-process property tests (bubble fraction,
sharding-rule resolution).

The worker checks that sharded init equals single-device init, and the
train-step results at tolerances that admit only collective
reassociation."""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core.pipeline import bubble_fraction

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def worker_output():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_par_worker.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.mark.parametrize("name", ["dp", "tp", "dp_tp", "fsdp", "pp", "smdp"])
def test_mesh_equivalence(worker_output, name):
    assert f"OK {name}" in worker_output


def test_all_checks_marker(worker_output):
    assert "ALL_CHECKS_PASSED" in worker_output


# ---------------------------------------------------------------------------
# schedule math (survey's pipeline bubble claim)
# ---------------------------------------------------------------------------
def test_bubble_fraction_formula():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 4) - 3 / 7) < 1e-12
    # GPipe's claim: bubble -> 0 as microbatches grow
    assert bubble_fraction(4, 64) < 0.05


# ---------------------------------------------------------------------------
# sharding-rule resolution (no mesh needed)
# ---------------------------------------------------------------------------
def test_resolve_spec_drops_indivisible_dims():
    from jax.sharding import PartitionSpec as P
    from repro.core import sharding as SH
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    with SH.use_mesh(mesh), SH.axis_env(SH.DP_TP_ENV):
        # 51865 (whisper vocab) is not divisible by any model axis > 1:
        # with a size-1 axis it shards trivially; the API must not raise
        spec = SH.resolve_spec((51865,), ("model",))
        assert isinstance(spec, P)


def test_axis_env_filters_absent_mesh_axes():
    from repro.core import sharding as SH
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    with SH.use_mesh(mesh), SH.axis_env(SH.DP_TP_ENV):
        # 'pod' is not in this mesh; logical batch = ("pod","data") -> data
        spec = SH.logical("batch")
        assert "pod" not in str(spec)
