"""Logical-axis -> mesh-axis sharding rules (DESIGN.md §4).

Baseline production scheme (applies uniformly to every arch):

* weights 2D-sharded: 'embed' over the FSDP axes (('pod','data') multi-pod,
  ('data',) single-pod), 'ffn'/'heads'/'kv'/'vocab' over 'model' (TP);
* MoE experts: expert axis over 'model' (EP) when num_experts divides the
  model-axis size, otherwise TP inside each expert;
* activations: batch over FSDP axes, sequence over 'model' (context/
  sequence parallelism — head counts never constrain the mesh);
* anything that doesn't divide evenly falls back to replication (checked
  per-dim, so whisper's 1500-frame encoder axis just replicates).

Rules are *functions of the mesh*, so the same model code runs on the
single-pod (16,16) and multi-pod (2,16,16) meshes, and on 1-device CPU
test meshes (where every rule degrades to replication).  Meshes may be
concrete or abstract — introspection goes through
``repro.parallel.meshes``.

These are the low-level rules; consumers should go through the validated
:class:`repro.parallel.planner.ShardingPlan` instead of calling the
per-tensor functions here directly.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import spec as pspec
from repro.parallel import meshes


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in meshes.axis_names(mesh))


def model_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in meshes.axis_names(mesh) else None


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    shape = meshes.shape_dict(mesh)
    n = 1
    for a in axes:
        n *= shape[a]
    return n


def logical_rules(mesh: Mesh):
    fa = fsdp_axes(mesh)
    ma = model_axis(mesh)
    return {
        "embed": fa if fa else None,
        "ffn": ma,
        "heads": ma,
        "kv": ma,
        "vocab": ma,
        "expert": None,  # resolved per-tensor (EP vs TP) below
        "layer": None,
        "state": None,
        None: None,
    }


def spec_to_pspec(s: "pspec.ParamSpec", mesh: Mesh) -> P:
    """Map one ParamSpec to a PartitionSpec under the baseline rules."""
    rules = logical_rules(mesh)
    ma = rules["ffn"]
    fa = rules["embed"]
    axes = list(s.axes)
    out = [None] * len(axes)
    used = set()

    if "expert" in axes and ma is not None:
        e_dim = s.shape[axes.index("expert")]
        if e_dim % _axis_size(mesh, ma) == 0:
            # EP: experts over 'model'; 'ffn' inside each expert replicated.
            out[axes.index("expert")] = ma
            used.add(ma)
        # else: TP inside each expert via the normal 'ffn' rule below.

    for i, name in enumerate(axes):
        if out[i] is not None or name == "expert":
            continue
        tgt = rules.get(name)
        if tgt is None:
            continue
        tgt_t = (tgt,) if isinstance(tgt, str) else tuple(tgt)
        if any(a in used for a in tgt_t):
            continue
        if s.shape[i] % _axis_size(mesh, tgt_t) != 0:
            continue  # ragged: replicate this dim
        out[i] = tgt_t[0] if len(tgt_t) == 1 else tgt_t
        used.update(tgt_t)
    return P(*out)


def param_pspecs(specs_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: spec_to_pspec(s, mesh), specs_tree, is_leaf=pspec.is_spec
    )


def param_shardings(specs_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, mesh)),
        specs_tree,
        is_leaf=pspec.is_spec,
    )


# ---------------------------------------------------------------------------
# Activation / batch shardings
# ---------------------------------------------------------------------------

def _maybe(dim: int, mesh: Mesh, axes) -> Optional[Tuple[str, ...]]:
    if not axes:
        return None
    if dim % _axis_size(mesh, axes) != 0:
        return None
    return tuple(axes) if not isinstance(axes, str) else (axes,)


def batch_pspec(mesh: Mesh, batch_dim: int, seq_dim: Optional[int], ndim: int,
                *, batch_size: int, seq_len: Optional[int]) -> P:
    """P for a (B, S, ...) activation/batch tensor under the baseline."""
    fa = fsdp_axes(mesh)
    ma = model_axis(mesh)
    out = [None] * ndim
    b_axes = _maybe(batch_size, mesh, fa)
    if b_axes:
        out[batch_dim] = b_axes if len(b_axes) > 1 else b_axes[0]
    if seq_dim is not None and seq_len is not None and ma:
        s_axes = _maybe(seq_len, mesh, ma)
        if s_axes:
            out[seq_dim] = s_axes[0]
    return P(*out)


def data_pspecs(mesh: Mesh, batch_shapes):
    """PartitionSpecs for a batch dict of ShapeDtypeStructs.

    tokens/labels/mask: (B, S) -> (fsdp, model).
    frames: (B, enc_seq, F) -> (fsdp, None, None)  (1500 is ragged).
    patch_embeds: (B, P, F) -> (fsdp, None, None).
    """
    out = {}
    for name, sds in batch_shapes.items():
        shape = sds.shape
        if name in ("tokens", "labels", "mask"):
            out[name] = batch_pspec(
                mesh, 0, 1, len(shape), batch_size=shape[0], seq_len=shape[1]
            )
        elif name in ("frames", "patch_embeds"):
            out[name] = batch_pspec(
                mesh, 0, None, len(shape), batch_size=shape[0], seq_len=None
            )
        else:
            out[name] = P()
    return out


def cache_pspecs(mesh: Mesh, cache_tree, *, pool: bool = False):
    """Shardings for a decode cache pytree (of arrays or SDS).

    Rules are keyed on the cache-leaf name (registry.init_cache layouts):
      k/v/ck/cv  (L,B,S,KV,hd) or (B,S,KV,hd): B->fsdp, S->model
      conv       (L,B,W,C) or (B,W,C):         B->fsdp, C->model
      ssm        (L,B,H,N,P):                  B->fsdp, H->model
      lru        (B,C):                        B->fsdp, C->model
      pos/len:   replicated
    Ragged dims (whisper's 1500-frame cross cache, batch=1 long-context)
    fall back to replication per-dim.

    ``pool=True`` (the planner sets it for ``pool_slots`` plans) adds the
    sharded-pool rules over the slot-pooled layouts of serve/slots.py
    (docs/DESIGN_scaling.md):

      k/v        (L, num_pages+1, page, KV, hd): physical pages -> fsdp
                 (the 5-D rule above already lands there), in-page
                 position -> model;
      k_beta/    (L, num_pages+1, page) quantized per-token scales:
      v_beta     physical pages -> fsdp, so a page's scales shard with
                 the code page they describe;
      len        (slots,) and ``table`` (slots, pages_per_slot): slot
                 axis -> fsdp — slots ARE the data-parallel batch;
      pos        (num_pages+1, page): replicated — it is the gather/mask
                 index metadata every shard consults, a few KiB of int32.

    Each rule still falls back to replication per-dim when the size does
    not divide (e.g. 8 slots on a 16-wide data axis), so the same plan
    call degrades cleanly on the 1-device host mesh.
    """
    fa = fsdp_axes(mesh)
    ma = model_axis(mesh)

    def assign(shape, dim_axes):
        out = [None] * len(shape)
        for dim, axes in dim_axes:
            a = _maybe(shape[dim], mesh, axes)
            if a:
                out[dim] = a if len(a) > 1 else a[0]
        while out and out[-1] is None:  # canonical: trailing None == P()
            out.pop()
        return P(*out)

    def one(path, x):
        name = None
        for e in reversed(path):
            if hasattr(e, "key"):
                name = e.key
                break
        shape = x.shape
        nd = len(shape)
        mt = (ma,) if ma else ()
        if pool:
            if name == "len" and nd == 1:
                return assign(shape, [(0, fa)])
            if name == "table" and nd == 2:
                return assign(shape, [(0, fa)])
            if name in ("k_beta", "v_beta") and nd == 3:
                return assign(shape, [(1, fa)])
        if name in ("k", "v", "ck", "cv"):
            if nd == 5:
                return assign(shape, [(1, fa), (2, mt)])
            if nd == 4:
                return assign(shape, [(0, fa), (1, mt)])
        if name == "conv":
            if nd == 4:
                return assign(shape, [(1, fa), (3, mt)])
            if nd == 3:
                return assign(shape, [(0, fa), (2, mt)])
        if name == "ssm" and nd == 5:
            return assign(shape, [(1, fa), (2, mt)])
        if name == "lru" and nd == 2:
            return assign(shape, [(0, fa), (1, mt)])
        return P()

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def constrain(x, mesh: Mesh, pspec_: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec_))
