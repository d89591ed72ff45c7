"""Mesh construction: the one place that builds ``Mesh``/``AbstractMesh``.

Everything else asks for a mesh by ``(axis_sizes, axis_names)``. Every
mesh built here has ``AxisType.Auto`` axes: the planner and
``repro.parallel.actshard`` place arrays with ``NamedSharding`` pins and
leave the rest to the SPMD partitioner, which is what Auto axes mean.
(``jax.make_mesh`` alone would give Explicit axes, under which ops such
as the embedding gather must name their output sharding.)

Production topologies live here too: single-pod 16x16 = 256 chips
(``('data', 'model')``) and multi-pod 2x16x16 = 512 chips
(``('pod', 'data', 'model')``); the ``'pod'`` axis composes with
``'data'`` for DP/FSDP (see ``repro.parallel.planner``).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

SINGLE_POD = ((16, 16), ("data", "model"))
MULTI_POD = ((2, 16, 16), ("pod", "data", "model"))


def axis_names(mesh) -> Tuple[str, ...]:
    """Axis names of a concrete or abstract mesh."""
    return tuple(mesh.axis_names)


def axis_sizes(mesh) -> Tuple[int, ...]:
    """Axis sizes of a concrete or abstract mesh, in axis order."""
    return tuple(int(s) for s in mesh.axis_sizes)


def shape_dict(mesh) -> dict:
    """``{axis_name: size}`` for a concrete or abstract mesh."""
    return dict(zip(axis_names(mesh), axis_sizes(mesh)))


def _axes(sizes: Sequence[int], names: Sequence[str]):
    sizes = tuple(int(s) for s in sizes)
    names = tuple(str(n) for n in names)
    if len(sizes) != len(names):
        raise ValueError(f"axis count mismatch: sizes={sizes} names={names}")
    return sizes, names, (AxisType.Auto,) * len(names)


def make_abstract_mesh(sizes: Sequence[int], names: Sequence[str]) -> AbstractMesh:
    """An ``AbstractMesh`` (no devices needed) with Auto axes."""
    sizes, names, types = _axes(sizes, names)
    return AbstractMesh(sizes, names, axis_types=types)


def make_mesh(
    sizes: Sequence[int],
    names: Sequence[str],
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """A concrete device mesh with Auto axes over the first
    ``prod(sizes)`` of ``devices`` (default: ``jax.devices()``), in the
    bandwidth-aware order ``jax.make_mesh`` picks."""
    sizes, names, types = _axes(sizes, names)
    n = math.prod(sizes)
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices for mesh {sizes}, have {len(devs)}")
    return jax.make_mesh(sizes, names, axis_types=types, devices=devs[:n])


def make_production_mesh(*, multi_pod: bool = False, abstract: bool = False):
    """The production topology: (16,16) single-pod or (2,16,16) multi-pod.

    ``abstract=True`` returns an ``AbstractMesh`` (no devices needed —
    what the planner and the sharding tests use); otherwise a concrete
    mesh over real devices.
    """
    sizes, names = MULTI_POD if multi_pod else SINGLE_POD
    if abstract:
        return make_abstract_mesh(sizes, names)
    return make_mesh(sizes, names)


def make_host_mesh() -> Mesh:
    """``(data=1, model=n)`` mesh over this process's ``n`` devices; on
    one device every rule degrades to replicate."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_serving_mesh(*, data: Optional[int] = None, model: int = 1) -> Mesh:
    """``(data, model)`` mesh over this process's visible devices for the
    sharded serving pool (docs/DESIGN_scaling.md): slots/pages shard over
    'data', weights over 'model'.  ``data`` defaults to every device not
    claimed by ``model`` — on a 1-device CPU it degrades to ``(1, 1)``
    (all rules -> replicate), while under the multi-process smoke path
    (``repro.parallel.smoke``, run with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) or on an
    N-chip host the same call yields a real N-way data axis."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(f"model={model} must divide the {n} visible devices")
    if data is None:
        data = n // model
    if data * model > n:
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices, have {n}"
        )
    return make_mesh((data, model), ("data", "model"))
