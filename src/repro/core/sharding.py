"""Logical-axis sharding: map logical tensor axes to mesh axes.

The framework names logical axes ("batch", "model", "expert", "seq") and maps
them onto whatever physical mesh is active.  The mapping lives in a module
level context (set by the trainer / dry-run / tests), so model code never
hard-codes mesh axis names — the survey's data/model/hybrid parallelism
choices become different AxisEnv mappings over the same model code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class AxisEnv:
    """Logical-axis -> mesh-axis mapping.

    batch:  axes the global batch is split over (data parallelism)
    model:  axes tensor-parallel dims (heads / ffn / experts / vocab) split over
    seq:    axes the sequence dim is split over (context parallelism; beyond-
            paper optimization, default None)
    """
    batch: Axes = None
    model: Axes = None
    seq: Axes = None
    # ZeRO/FSDP: additionally shard each param's largest replicated dim over
    # these axes (storage sharding; GSPMD all-gathers at use)
    fsdp: Axes = None

    def resolve(self, name: Optional[str]) -> Axes:
        if name is None:
            return None
        # unknown logical names (e.g. "layers", the stacked scan dim) are
        # never mesh-sharded
        return getattr(self, name, None)


# data parallel only (survey: "data parallelism")
DP_ENV = AxisEnv(batch=("pod", "data", "model"))
# hybrid data x tensor (survey: "hybrid parallelization"), the production default
DP_TP_ENV = AxisEnv(batch=("pod", "data"), model="model")
# pure tensor/model parallel (survey: "model parallelism")
TP_ENV = AxisEnv(batch=None, model=("data", "model"))
# hybrid + ZeRO param/optimizer sharding (training default for big models)
TRAIN_ENV = AxisEnv(batch=("pod", "data"), model="model", fsdp="data")
# hybrid + sequence sharding for long prefill (beyond-paper)
DP_TP_SP_ENV = AxisEnv(batch=("pod", "data"), model="model", seq="model")
# TRAIN_ENV + Megatron-SP: the residual stream (and all elementwise/norm
# work between the TP blocks) is sharded over the model axis along the
# sequence dim; GSPMD turns the TP all-reduces into reduce-scatter +
# all-gather pairs (beyond-paper; EXPERIMENTS.md §Perf)
TRAIN_SP_ENV = AxisEnv(batch=("pod", "data"), model="model", seq="model",
                       fsdp="data")

_state = threading.local()


def set_axis_env(env: AxisEnv):
    _state.env = env


def get_axis_env() -> AxisEnv:
    return getattr(_state, "env", DP_TP_ENV)


@contextlib.contextmanager
def axis_env(env: AxisEnv):
    prev = get_axis_env()
    set_axis_env(env)
    try:
        yield env
    finally:
        set_axis_env(prev)


def _mesh_shape() -> dict:
    shape = getattr(_state, "mesh_shape", None)
    if shape:
        return shape
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and am.axis_names:
            return dict(am.shape)
    except Exception:
        pass
    return {}


def _mesh_axis_names():
    return tuple(_mesh_shape().keys())


def set_mesh(mesh: Optional[Mesh]):
    _state.mesh_shape = dict(mesh.shape) if mesh is not None else {}


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = getattr(_state, "mesh_shape", {})
    set_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh_shape = prev


def axis_size(axes: Axes) -> int:
    shape = _mesh_shape()
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= shape.get(a, 1)
    return n


def _filter(axes: Axes, present: Tuple[str, ...]) -> Axes:
    """Drop mesh axes not present in the active mesh (e.g. 'pod' on 1 pod)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    kept = tuple(a for a in axes if a in present)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def logical(*names: Optional[str]) -> P:
    """Build a PartitionSpec from logical axis names for the active env+mesh."""
    env = get_axis_env()
    present = _mesh_axis_names()
    return P(*[_filter(env.resolve(n), present) for n in names])


def resolve_spec(shape: Tuple[int, ...], names: Tuple[Optional[str], ...]) -> P:
    """Like `logical`, but drop shardings a dim is not divisible by.

    GSPMD can pad uneven dims, but replicating a small non-divisible dim
    (e.g. whisper's 51865 vocab on 16 shards) is cheaper and predictable.
    """
    env = get_axis_env()
    present = _mesh_axis_names()
    parts = []
    for dim, name in zip(shape, names):
        axes = _filter(env.resolve(name), present)
        if axes is not None and dim % axis_size(axes) != 0:
            axes = None
        parts.append(axes)
    return P(*parts)


def resolve_param_spec(shape: Tuple[int, ...],
                       names: Tuple[Optional[str], ...]) -> P:
    """`resolve_spec` + FSDP: put env.fsdp axes on the last still-replicated
    dim that divides evenly (dim 0 of stacked layer params is excluded —
    scan unstacks it)."""
    env = get_axis_env()
    base = resolve_spec(shape, names)
    if env.fsdp is None:
        return base
    present = _mesh_axis_names()
    fs = _filter(env.fsdp, present)
    if fs is None:
        return base
    nshards = axis_size(fs)
    used = set()
    for part in base:
        if part is None:
            continue
        for a in (part if isinstance(part, tuple) else (part,)):
            used.add(a)
    fs_axes = fs if isinstance(fs, tuple) else (fs,)
    if any(a in used for a in fs_axes):
        return base
    parts = list(base)
    for i in range(len(shape) - 1, -1, -1):
        if names[i] == "layers":  # scan unstacks this dim; never shard it
            continue
        if parts[i] is None and shape[i] % nshards == 0 and shape[i] >= nshards:
            parts[i] = fs
            break
    return P(*parts)


def shard(x, *names: Optional[str]):
    """with_sharding_constraint by logical axis names (no-op outside jit/mesh).

    Non-divisible dims fall back to replicated (see `resolve_spec`).
    """
    present = _mesh_axis_names()
    if not present:
        return x
    return jax.lax.with_sharding_constraint(x, resolve_spec(x.shape, names))


def mesh_shards(name: str, mesh: Mesh) -> int:
    """Number of shards a logical axis maps to on `mesh`."""
    env = get_axis_env()
    axes = _filter(env.resolve(name), tuple(mesh.axis_names))
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    present = tuple(mesh.axis_names)

    def fix(part):
        return _filter(part, present) if part is not None else None

    return NamedSharding(mesh, P(*[fix(p) for p in spec]))
