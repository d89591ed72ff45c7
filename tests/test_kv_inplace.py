"""The pooled steps update the paged K/V stores in place.

The plan-less decode, chunk and verify steps donate the cache they are
given, and their layer scan carries the stacked (L, pages, page, KV, hd)
stores whole (``transformer._scan_layers``).  So the compiled program
aliases each store's input buffer to its output, and holds no
dynamic-slice that cuts one layer's store out of the stack, no
dynamic-update-slice that stacks it back, and no copy of a whole store:
a step moves the positions it writes and the pages it gathers, not the
cache.  Compiled on the CPU at smoke width, bf16 pages and the pinned
PoT-quantized pages."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.core.policy import KV_PINNED, PAPER_FAITHFUL
from repro.models import registry, spec as pspec
from repro.serve import engine

SLOTS, MAX_LEN, PAGE, PAGES, CHUNK = 2, 16, 4, 8, 4

BUILDERS = {"decode": engine.make_decode_step,
            "chunk": engine.make_chunk_step,
            "verify": engine.make_verify_step}


@pytest.fixture(scope="module")
def olmo():
    cfg = C.smoke_config("olmo-1b")
    assert cfg.n_layers >= 2 and cfg.window is None
    params = pspec.materialize(registry.param_specs(cfg),
                               jax.random.PRNGKey(0))
    return cfg, params


def _entry_params(hlo):
    """{cache leaf name: entry parameter number}."""
    found = re.findall(
        r"parameter\((\d+)\), metadata=\{op_name=\"cache\[\\'(\w+)\\'\]",
        hlo)
    return {name: int(n) for n, name in found}


def _aliased(hlo):
    """Entry parameter numbers whose buffers the outputs reuse."""
    (alias,) = re.findall(r"input_output_alias=\{(.*?)\s\}", hlo) or [""]
    return {int(n) for n in re.findall(r"\((\d+), \{\}, \w+-alias\)", alias)}


def _moves(hlo):
    """(op, result dims) of every dynamic-slice, dynamic-update-slice and
    copy, leading unit dims dropped."""
    out = []
    for dims, op in re.findall(
            r"= \w+\[([\d,]*)\]\S* (dynamic-slice|dynamic-update-slice|copy)\(",
            hlo):
        shape = tuple(int(d) for d in dims.split(",") if d)
        while shape and shape[0] == 1:
            shape = shape[1:]
        out.append((op, shape))
    return out


@pytest.mark.parametrize("kvq", [False, True], ids=["bf16", "kv_pinned"])
@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_pooled_step_updates_kv_stores_in_place(olmo, kind, kvq):
    cfg, params = olmo
    policy = (dataclasses.replace(PAPER_FAITHFUL, kv_quant=KV_PINNED)
              if kvq else PAPER_FAITHFUL)
    cache = registry.init_pool_cache(
        cfg, SLOTS, MAX_LEN, jnp.bfloat16, page_size=PAGE, num_pages=PAGES,
        kv_quant=policy.kv_quant)
    stores = {n: cache[n].shape for n in ("k", "v", "k_beta", "v_beta")
              if n in cache}
    assert set(stores) == ({"k", "v", "k_beta", "v_beta"} if kvq
                           else {"k", "v"})
    BUILDERS[kind](cfg, policy)
    step = engine._STEP_CACHE[(kind, cfg, policy)]
    if kind == "decode":
        args = (jnp.zeros((SLOTS,), jnp.int32),)
    else:
        args = (jnp.zeros((SLOTS, CHUNK), jnp.int32),
                jnp.array([CHUNK, 1], jnp.int32))
    hlo = step.lower(params, *args, cache).compile().as_text()

    number = _entry_params(hlo)
    assert set(stores) <= set(number), number
    aliased = _aliased(hlo)
    for name in stores:
        assert number[name] in aliased, (name, number, aliased)
    # a store is moved whole as (L, P, page, ...) or as the (L * P, page,
    # ...) pages the step indexes, and one layer's store is (P, page, ...)
    whole = {s for shape in stores.values()
             for s in (shape, (shape[0] * shape[1],) + shape[2:])}
    layer = {s[1:] for s in stores.values()}
    moved = [(op, s) for op, s in _moves(hlo)
             if (op == "dynamic-slice" and s in layer)
             or (op == "dynamic-update-slice" and s in whole)]
    assert not moved, moved
    # verify writes C positions a layer, and nothing orders position i's
    # gather before position i + 1's scatter but the store itself, so
    # XLA:CPU copies the PoT code pages between them (the TPU compiler
    # does not); every other step has one write a layer
    if not (kind == "verify" and kvq):
        copied = [s for op, s in _moves(hlo) if op == "copy" and s in whole]
        assert not copied, copied
