"""Compile the main path for a described TPU v5e: no chip is needed.

Interpret mode runs the Pallas kernels as plain XLA, so it cannot see what
the TPU's compiler refuses: tiles not aligned to the hardware tiling, more
VMEM than a kernel may use, a program larger than HBM, a Mosaic kernel the
SPMD partitioner would have to split.  These tests compile the kernels at
``olmo-1b`` widths, a full-width train step, and a kernel under a 2x2
mesh, against the ``v5e:2x2`` topology described in the ``topo`` fixture.

The topology is described only inside that fixture: one process at a time
may load the TPU library, so a description made at import would make the
other test workers fail to collect.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import configs as C
from repro.configs.base import ShapeConfig
from repro.core import potq
from repro.core.policy import PAPER_FAITHFUL
from repro.data import pipeline
from repro.kernels import ops
from repro.kernels import potq_encode as _ke
from repro.kernels import potq_grad as _kg
from repro.kernels import potq_matmul as _k
from repro.optim import adamw, warmup_cosine_schedule
from repro.parallel import meshes, planner
from repro.train import TrainConfig, make_train_step

# olmo-1b at batch 4 x seq 1024: tokens, d_model, d_ff
M, K, N = 4096, 2048, 8192
HBM_BYTES = 16e9  # one v5e chip
EMAX = potq.pot_emax(5)


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache

        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back without the chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, tile):
    """(kernel with statics bound, operand shapes) for one padded kernel."""
    t = dict(bm=tile, bn=tile, bk=tile, interpret=False)
    one = (1, 1)
    if name == "potq_matmul":
        return (functools.partial(_k.potq_matmul_padded, emax_a=EMAX,
                                  emax_w=EMAX, quantize=True, **t),
                [(M, K), (K, N)] + [one] * 5)
    if name == "grad_da_prc":
        return (functools.partial(_kg.grad_da_padded, emax_g=EMAX, prc=True,
                                  **t),
                [(M, N), (K, N), (M, K)] + [one] * 3)
    if name == "grad_da_raw":
        return (functools.partial(_kg.grad_da_padded, emax_g=EMAX,
                                  prc=False, **t),
                [(M, N), (K, N), (M, N)] + [one] * 3)
    if name == "grad_dw":
        return (functools.partial(_kg.grad_dw_padded, emax_g=EMAX, **t),
                [(M, K), (M, N)] + [one] * 2)
    assert name == "potq_encode"
    return (functools.partial(_ke.potq_encode_padded, emax=EMAX, bm=tile,
                              bn=tile, interpret=False),
            [(M, N), one])


@pytest.mark.parametrize("tile", [256, 512])
@pytest.mark.parametrize(
    "name",
    ["potq_matmul", "grad_da_prc", "grad_da_raw", "grad_dw", "potq_encode"],
)
def test_kernel_compiles_for_v5e_at_olmo_widths(one_chip, name, tile):
    kernel, shapes = _kernel_case(name, tile)
    args = [_sds(s, one_chip) for s in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_olmo_train_step_compiles_for_one_v5e(topo, one_chip, monkeypatch):
    """Two full-width olmo-1b layers with use_pallas and AdamW at batch 4 x
    seq 1024, jitted as launch/train.py jits it: the Mosaic kernels are in
    the program and it fits one chip's HBM."""
    # ops picks interpret mode from the (CPU) default backend
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(C.get_config("olmo-1b"), n_layers=2)
    policy = dataclasses.replace(PAPER_FAITHFUL, use_pallas=True)
    shape = ShapeConfig("cli", 1024, 4, "train")
    mesh = meshes.make_mesh((1, 1), ("data", "model"), devices=topo.devices)
    plan = planner.plan_for(cfg, mesh, shape=shape)
    opt = adamw(warmup_cosine_schedule(3e-3, 20, 10))
    param_sh = plan.param_shardings()
    opt_sh = {"m": param_sh, "v": param_sh}
    data_sh = plan.data_shardings()

    def place(tree, sh):
        return jax.tree_util.tree_map(
            lambda a, s: _sds(a.shape, s, a.dtype), tree, sh)

    params = place(plan.abstract_params(), param_sh)
    step = jax.jit(
        make_train_step(cfg, policy, opt, TrainConfig()),
        in_shardings=(param_sh, opt_sh, data_sh, plan.replicated()),
        out_shardings=(param_sh, opt_sh, plan.replicated()),
        donate_argnums=(0, 1),
    )
    with jax.set_mesh(mesh):
        compiled = step.lower(
            params, {"m": params, "v": params},
            place(pipeline.batch_specs(cfg, shape), data_sh),
            _sds((), plan.replicated(), jnp.int32),
        ).compile()
    assert compiled.as_text().count("tpu_custom_call") > 0
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_kernel_runs_whole_per_device_under_2x2_mesh(topo):
    """The SPMD partitioner refuses to split a Mosaic kernel; under a
    multi-device mesh ops.py runs it whole on each device (shard_map with
    replicated specs), so a sharded-operand matmul compiles for 4 chips."""
    mesh = meshes.make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    x = _sds((512, K), NamedSharding(mesh, P("data", "model")))
    y = _sds((K, N), NamedSharding(mesh, P(None, "model")))
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            functools.partial(ops.pot_value_matmul, interpret=False)
        ).lower(x, y).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
