"""End-to-end training driver with checkpoint/restart fault tolerance.

Runs on whatever devices exist: the production meshes via
``--mesh single_pod|multi_pod`` (requires the device count), or the
1-device CPU test mesh (``--mesh host``, default) for smoke-scale runs.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt

``--layers N`` cuts depth to N whole layers (widths untouched), so a
published configuration fits one chip: e.g. ``--arch olmo-1b --layers 6
--pallas`` on one TPU v5e (``chip_smoke.py``).

Fault tolerance: the job restores the latest checkpoint at startup (if
any), the data pipeline is stateless in the step index, and checkpoints
are atomic — kill the process at any point and rerun the same command to
continue.

``main`` returns a summary of the run: per-step losses and grad norms,
compile seconds, mean seconds per step after the first (each step timed
to ``block_until_ready``), the ``tpu_custom_call`` count of the compiled
step, and the device's ``peak_bytes_in_use`` where the backend reports it.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import configs as C
from repro.configs.base import ShapeConfig
from repro.core import policy as policy_lib
from repro.ckpt import CheckpointManager
from repro.data import pipeline
from repro.launch import compile_cache
from repro.models import spec as pspec
from repro.optim import adamw, sgd_momentum, step_decay_schedule, warmup_cosine_schedule
from repro.parallel import actshard, meshes, planner
from repro.train import TrainConfig, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut: keep the first N whole layers, "
                         "widths untouched")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--policy", default="paper",
                    choices=["paper", "fp32", "no_wbc", "no_prc"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--pallas", action="store_true",
                    help="route MF-MAC matmuls through the fused Pallas "
                         "kernel (interpret mode off-TPU); required for "
                         "--autotune to have any effect")
    ap.add_argument("--autotune", default="cache",
                    choices=["off", "cache", "measure"],
                    help="kernel block-shape source (with --pallas): tuned "
                         "cache (default), measure+persist now, or off "
                         "(heuristic only)")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single_pod", "multi_pod"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = C.smoke_config(args.arch) if args.smoke else C.get_config(args.arch)
    if args.layers is not None:
        period = len(cfg.pattern) if cfg.pattern else 1
        if not 1 <= args.layers <= cfg.n_layers or args.layers % period:
            raise ValueError(
                f"--layers {args.layers}: need a multiple of {period} in "
                f"[1, {cfg.n_layers}]"
            )
        print(f"depth cut: n_layers {cfg.n_layers} -> {args.layers}")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    policy = {
        "paper": policy_lib.PAPER_FAITHFUL,
        "fp32": policy_lib.FP32_BASELINE,
        "no_wbc": policy_lib.ABLATION_NO_WBC,
        "no_prc": policy_lib.ABLATION_NO_PRC,
    }[args.policy]
    if args.pallas:
        policy = dataclasses.replace(policy, use_pallas=True)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    if args.mesh == "host":
        mesh = meshes.make_host_mesh()
    else:
        mesh = meshes.make_production_mesh(multi_pod=args.mesh == "multi_pod")

    # One validated plan drives every sharding decision below (params,
    # optimizer mirrors, batch, in-model activation pins).
    plan = planner.plan_for(cfg, mesh, shape=shape)
    specs = plan.specs
    print(f"arch={cfg.name} params={pspec.count_params(specs)/1e6:.2f}M "
          f"policy={args.policy} mesh={meshes.shape_dict(mesh)}")

    if args.optimizer == "sgd":
        opt = sgd_momentum(step_decay_schedule(args.lr, [10**9]))
    else:
        opt = adamw(warmup_cosine_schedule(args.lr, 20, args.steps))
    # Consult the kernel autotuner for this run's matmul shapes (tuned
    # cache -> heuristic; `measure` benchmarks and persists).  Training
    # also primes the fused backward MACs (grad_da / grad_dw keys —
    # ops.potq_grad_matmuls resolves them in every backward).  Tiling is
    # numerics-free (fixed-order reduction), so this only affects speed.
    if args.autotune != "off" and policy.use_pallas:
        from repro.kernels import autotune as _autotune

        primed = _autotune.prime_for_model(
            cfg, batch=args.batch // max(args.microbatches, 1), seq=args.seq,
            bits_a=policy.bits_a, bits_w=policy.bits_w,
            bits_g=policy.bits_g, bits_g_last=policy.bits_g_last,
            include_grads=True, prc=policy.prc_enabled,
            measure=args.autotune == "measure",
        )
        for (mkn, choice) in primed:
            print(f"autotune {mkn} -> ({choice.bm},{choice.bn},{choice.bk}) "
                  f"[{choice.source}]")

    # the step reads the active plan (actshard.use_plan below) for its
    # microbatch-reshape constraint — no raw mesh argument
    tstep = make_train_step(
        cfg, policy, opt, TrainConfig(microbatches=args.microbatches)
    )

    param_sh = plan.param_shardings()
    opt_sh = ({"mu": param_sh} if args.optimizer == "sgd"
              else {"m": param_sh, "v": param_sh})
    with jax.set_mesh(mesh):
        params = jax.jit(
            lambda k: pspec.materialize(specs, k), out_shardings=param_sh
        )(jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)

    start_step = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        latest = mgr.latest_step()
        if latest is not None:
            print(f"restoring checkpoint step {latest}")
            _, state = mgr.restore_latest(
                {"params": params, "opt_state": opt_state},
                shardings={"params": param_sh, "opt_state": opt_sh},
            )
            params, opt_state = state["params"], state["opt_state"]
            start_step = latest

    data_sh = plan.data_shardings()
    jit_step = jax.jit(
        tstep,
        in_shardings=(param_sh, opt_sh, data_sh, plan.replicated()),
        out_shardings=(param_sh, opt_sh, plan.replicated()),
        donate_argnums=(0, 1),
    )
    summary = {"losses": [], "grad_norms": [], "compile_s": None,
               "step_s": None, "tpu_custom_calls": None,
               "peak_bytes_in_use": None}
    t0 = time.time()
    with jax.set_mesh(mesh), actshard.use_plan(
            plan if args.mesh != "host" else None):
        if start_step < args.steps:
            t_c = time.perf_counter()
            step_fn = jit_step.lower(
                params, opt_state,
                jax.device_put(pipeline.make_batch(cfg, shape, start_step),
                               data_sh),
                jnp.int32(start_step),
            ).compile()
            summary["compile_s"] = time.perf_counter() - t_c
            summary["tpu_custom_calls"] = step_fn.as_text().count(
                "tpu_custom_call")
            print(f"compiled step in {summary['compile_s']:.1f}s "
                  f"({summary['tpu_custom_calls']} tpu_custom_call)")
        done_at = []
        for step in range(start_step, args.steps):
            batch = jax.device_put(pipeline.make_batch(cfg, shape, step),
                                   data_sh)
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, jnp.int32(step)
            )
            jax.block_until_ready(metrics)
            done_at.append(time.perf_counter())
            loss = float(metrics["loss"])
            gn = float(metrics["grad_norm"])
            summary["losses"].append(loss)
            summary["grad_norms"].append(gn)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss {loss:.4f} |g| {gn:.3f} "
                      f"({dt:.1f}s)", flush=True)
            if mgr and step and step % args.ckpt_every == 0:
                mgr.save(step, {"params": params, "opt_state": opt_state})
    if len(done_at) > 1:
        # the first step is warm-up; the rest are steady state
        summary["step_s"] = (done_at[-1] - done_at[0]) / (len(done_at) - 1)
        print(f"seconds/step after warm-up: {summary['step_s']:.4f}")
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        summary["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
        print(f"peak_bytes_in_use: {summary['peak_bytes_in_use']}")
    if mgr:
        mgr.save(args.steps, {"params": params, "opt_state": opt_state},
                 blocking=True)
    print("done")
    return summary


if __name__ == "__main__":
    main()
