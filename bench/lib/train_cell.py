"""A training cell: the MF-MAC train step that ``launch/train.py`` builds
(plan, AdamW, ``make_train_step``, jitted with the plan's shardings and
donated state), driven step after step until the window is spent.

Set-up builds that one step and its state from the seed and drives it
through its first three steps with the window's own call and feed; those
steps are the ones the reference checks.  Their readings are taken from
the state as it stands: the losses and the global gradient norms the step
reports, the first gradient as AdamW got it (``m / (1 - b1)`` after one
step) and the change of every parameter after three steps.  The window then goes on with the same object.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from bench.lib import check, model as model_lib, refmath
from bench.lib import traffic as traffic_lib
from bench.lib import weights as W
from bench.lib.seeds import seed_arg

B1 = 0.9
CHECKED_STEPS = 3


def model_config(cfg_json: dict):
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in cfg_json.items() if k in fields})


def flat(tree, prefix=""):
    """Program parameter tree -> {"layers/wq/w": leaf}."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, name + "/"))
        else:
            out[name] = v
    return out


def build(run):
    """The program's jitted, donated train step with its state, made from
    the seed.  Returns a ``Trainer``."""
    import jax

    from repro.core import policy as policy_lib
    from repro.configs.base import ShapeConfig
    from repro.models import registry
    from repro.models import spec as pspec
    from repro.optim import adamw, warmup_cosine_schedule
    from repro.parallel import meshes, planner
    from repro.train import TrainConfig, make_train_step

    cell, cfg_json = run.cell, run.cfg
    model = model_lib.load(cfg_json)
    opt_cfg = cell["optimizer"]
    cfg = model_config(cfg_json)
    policy = dataclasses.replace(policy_lib.PAPER_FAITHFUL,
                                 use_pallas=cell["pallas"])
    shape = ShapeConfig("bench", run.traffic["seq"], run.traffic["batch"],
                        "train")
    mesh = meshes.make_host_mesh()
    plan = planner.plan_for(cfg, mesh, shape=shape)
    want = {k: v.shape for k, v in flat(pspec.abstract(
        registry.param_specs(cfg))).items()}
    have = {k: tuple(shape) if depth is None else (depth, *shape)
            for k, (shape, _, depth) in model.layout(cfg_json).items()}
    if want != have:
        raise RuntimeError(f"the program's parameters {want} are not the "
                           f"benchmark's {have}")
    opt = adamw(warmup_cosine_schedule(opt_cfg["lr"], opt_cfg["warmup"],
                                       opt_cfg["total_steps"]))
    step = run.hooks.get("make_train_step", make_train_step)(
        cfg, policy, opt, TrainConfig(clip_norm=opt_cfg["clip_norm"]))
    param_sh = plan.param_shardings()
    opt_sh = {"m": param_sh, "v": param_sh}
    data_sh = plan.data_shardings()
    seed = seed_arg(run.seed)

    def make_params(s):
        return model.tree(cfg_json, {n: W.leaf(s, cfg_json, n)
                                     for n in model.layout(cfg_json)})

    with jax.set_mesh(mesh):
        params = jax.jit(make_params, out_shardings=param_sh)(seed)
        opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)
    jit_step = jax.jit(step, in_shardings=(param_sh, opt_sh, data_sh,
                                           plan.replicated()),
                       out_shardings=(param_sh, opt_sh, plan.replicated()),
                       donate_argnums=(0, 1))
    t = run.traffic
    batch_fn = jax.jit(lambda s, i: traffic_lib.make_batch(
        cfg_json["vocab"], t["batch"], t["seq"], s, i),
        out_shardings=data_sh)
    return Trainer(mesh, jit_step, functools.partial(batch_fn, seed), params,
                   opt_state)


class Trainer:
    def __init__(self, mesh, jit_step, batch_fn, params, opt_state):
        self.mesh, self.jit_step, self.batch_fn = mesh, jit_step, batch_fn
        self.params, self.opt_state = params, opt_state
        self.step = 0
        self.losses, self.global_norms = [], []

    def one_step(self):
        """The window's call and feed: make the batch, dispatch the step,
        wait for it and read the loss on the host."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        i = jnp.int32(self.step)
        with TraceAnnotation("bench.batch"):
            batch = self.batch_fn(i)
        with TraceAnnotation("bench.train_step"):
            self.params, self.opt_state, m = self.jit_step(
                self.params, self.opt_state, batch, i)
        with TraceAnnotation("bench.sync"):
            m = jax.block_until_ready(m)
            loss = float(m["loss"])
        self.losses.append(loss)
        self.global_norms.append(m["grad_norm"])
        self.step += 1
        return loss


def _leaf_norms(tree, scale=1.0):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda t: {k: jnp.linalg.norm(v) * scale
                           for k, v in flat(t).items()})
    return {k: float(v) for k, v in f(tree).items()}


def _change_norms(params, seed, cfg_json):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda t, s: {k: jnp.linalg.norm(v - W.leaf(s, cfg_json, k))
                              for k, v in flat(t).items()})
    return {k: float(v) for k, v in f(params, seed_arg(seed)).items()}


def checked_steps(run):
    """Build the step and drive it through the checked steps; returns the
    trainer and the program's readings."""
    import jax

    tr = build(run)
    with jax.set_mesh(tr.mesh):
        readings = {}
        for i in range(CHECKED_STEPS):
            tr.one_step()
            if i == 0:
                readings["grad_norms"] = _leaf_norms(tr.opt_state["m"],
                                                     1.0 / (1.0 - B1))
        readings["change_norms"] = _change_norms(tr.params, run.seed,
                                                 run.cfg)
        readings["losses"] = list(tr.losses)
        readings["global_norms"] = [float(g) for g in tr.global_norms]
    return tr, readings


def run_cell(run):
    """Set-up, window, readings and the check.  Returns the run's record."""
    import jax
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.setup"):
        tr, readings = checked_steps(run)
        with jax.set_mesh(tr.mesh):
            tr.one_step()  # one more step: the state after three is donated
    t = run.traffic
    tokens_per_step = t["batch"] * t["seq"]
    with jax.set_mesh(tr.mesh):
        run.start_window()
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            first = tr.step
            while time.perf_counter() - t0 < run.seconds:
                tr.one_step()
            window_s = time.perf_counter() - t0
        run.end_window()
    steps = tr.step - first
    losses = tr.losses[first:]
    record = {
        "window_s": window_s,
        "steps": steps,
        "tokens": steps * tokens_per_step,
        "attempted": steps,
        "failed": sum(1 for x in losses if not np.isfinite(x)),
        "train_tokens_per_s": steps * tokens_per_step / window_s,
        "device": run.describe_device(),
    }
    del tr
    jax.clear_caches()
    record["check"] = compare(run, readings)
    return record


def reference_batches(run, rows=None):
    """The checked steps' batches, made again from the seed (the first
    ``rows`` rows of each where given)."""
    import jax

    t = run.traffic
    batch_fn = jax.jit(lambda s, i: traffic_lib.make_batch(
        run.cfg["vocab"], t["batch"], t["seq"], s, i))
    out = [batch_fn(seed_arg(run.seed), i) for i in range(CHECKED_STEPS)]
    if rows is not None:
        out = [{k: v[:rows] for k, v in b.items()} for b in out]
    return out


def reference_readings(run, num=refmath.Num(False), rows=None):
    """The reference's readings of the checked steps, through the loss of
    the configuration's model module."""
    return refmath.train_readings(model_lib.load(run.cfg),
                                  seed_arg(run.seed), run.cfg,
                                  run.cell["optimizer"],
                                  reference_batches(run, rows), num,
                                  CHECKED_STEPS)


def compare(run, readings):
    """The reference's first three steps against the program's."""
    return check.train_numbers(readings, reference_readings(run),
                               run.cell["limits"])
