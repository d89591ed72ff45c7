"""Activation sharding constraints at layer boundaries.

The SPMD partitioner only has fixed points at jit in/out shardings and
explicit ``with_sharding_constraint``s; for deep scanned models it can
(and, observed in the dry-run HLO, does) drop the DP batch sharding when
propagating through the microbatch reshape — silently replicating the
whole layer stack.  Production frameworks pin activations at every block
boundary; we do the same.

The active :class:`~repro.parallel.planner.ShardingPlan` is threaded via
a module-level context (set by the launcher / dry-run around tracing) so
model code stays mesh-agnostic:

    with actshard.use_plan(plan):
        lowered = jax.jit(step).lower(...)

Inside model code, ``shard_tokens`` pins (B, S, ...) activations to the
plan's activation rule (batch -> FSDP axes, seq -> 'model'); no-op when
no plan is active (CPU tests) or when a dim doesn't divide.
``use_mesh(mesh)`` is kept as a shim for callers that have a mesh but no
model config; it activates a params-less plan over that mesh.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

from repro.parallel.planner import ShardingPlan

_ACTIVE: Optional[ShardingPlan] = None


def active_plan() -> Optional[ShardingPlan]:
    return _ACTIVE


@contextlib.contextmanager
def use_plan(plan: Optional[ShardingPlan]):
    """Activate ``plan`` for in-model activation pinning (None deactivates).

    The plan's mesh is also made the ambient abstract mesh, under which
    each Pallas kernel runs whole on every device (``kernels/ops.py``);
    eager arrays stay where they are."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = plan
    try:
        if plan is None:
            yield
        else:
            with jax.sharding.use_abstract_mesh(plan.mesh.abstract_mesh):
                yield
    finally:
        _ACTIVE = prev


@contextlib.contextmanager
def use_mesh(mesh):
    """Back-compat shim: activate a mesh with the default activation rules
    (a params-less plan).  Prefer ``use_plan(planner.plan_for(cfg, mesh))``."""
    plan = None
    if mesh is not None:
        plan = ShardingPlan(
            mesh=mesh, params=None, data=None, cache=None, moe={}, report=()
        )
    with use_plan(plan):
        yield


def shard_tokens(x: jax.Array, *, seq_dim: int = 1) -> jax.Array:
    """Constrain a (B, S, ...) activation: B->fsdp, S->'model'."""
    plan = _ACTIVE
    if plan is None or x.ndim < 2:
        return x
    sd = seq_dim if seq_dim < x.ndim else None
    spec = plan.activation_pspec(
        x.ndim,
        batch_size=x.shape[0],
        seq_len=x.shape[sd] if sd is not None else None,
        seq_dim=sd,
    )
    if all(e is None for e in spec):  # batch_pspec emits exactly ndim entries
        return x
    return jax.lax.with_sharding_constraint(x, plan.named(spec))
