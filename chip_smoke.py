#!/usr/bin/env python3
"""On-chip smoke check: olmo-1b's MF-MAC train step and PoolEngine on a TPU.

  python chip_smoke.py             # one chip: kernels, serving, training
  python chip_smoke.py --chips 4   # one host's four chips: the sharded pool
                                   # and the train CLI's host mesh, each
                                   # against the same program on one chip

Every phase drives the repository's own entry points at the published
widths of ``olmo-1b`` (``configs/olmo_1b.py``: d_model 2048, 16 heads x
128, d_ff 8192, vocab 50304) with random weights made from ``--seed``:

* kernels — ``pot_value_matmul``, ``potq_matmul`` and the fused backward
  ``potq_grad_matmuls`` at one real-width shape, each equal bit for bit to
  its ``kernels/ref.py`` oracle (the ``ACC_SCHEME`` contract);
* serve — ``PoolEngine`` over all 16 layers (PoT weights in bf16) with the
  Pallas kernels, chunked admission and the paged cache; every request is
  served and two of them, served alone, give the same tokens
  (``POOL_SOLO_BIT_IDENTITY``);
* train — ``repro.launch.train.main`` with ``--pallas`` and AdamW for a
  few steps, cut to ``TRAIN_LAYERS`` whole layers so weights, Adam state
  and activations fit one chip; losses and grad norms must be finite.

Each phase prints its compile seconds, step seconds (host clock around
``block_until_ready``), its compiled programs' ``tpu_custom_call`` counts
(zero is a failure) and the device's memory counters.  These are smoke
numbers, not a benchmark.  The last line of standard output is the JSON
object ``{"ok": true, "device": {...}}``, printed only when every phase
passed; any failure, a backend other than the TPU, or a device count other
than ``--chips`` exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "olmo-1b"
#: Whole layers kept for training.  Compiled for a v5e at batch 4 x seq
#: 1024 with AdamW, memory_analysis() gives 10.5 GB at 4 layers, 13.6 GB
#: at 6, 15.0 GB at 7 and 16.2 GB at 8 (arguments + temporaries).
TRAIN_LAYERS = 6
TRAIN_ARGS = ("--batch", "4", "--seq", "1024", "--steps", "10",
              "--pallas", "--autotune", "off", "--log-every", "1")
#: 4-chip train CLI vs 1 chip, per step, for the loss and the grad norm:
#: the host mesh splits the jnp reductions (norms, softmax, loss, grad
#: norm) over 4 chips in another order, so the two runs agree to f32
#: rounding, not bit for bit.  Rehearsed on 4 CPU devices (olmo-1b smoke
#: widths, 10 steps) the largest gaps were 1.76e-07 of the loss and
#: 1.58e-07 of the grad norm; a planted fault that drops half the batch's
#: rows from the loss moved them by 1.56e-03 and 0.348 at step 0 and by at
#: least 4.0e-04 and 0.105 at every step (PERF.md).
REL_TOL = 1e-5
SERVE = dict(slots=4, requests=8, prompt_len=512, new_tokens=64, chunk=128,
             page=64)
KERNEL_MKN = (512, 2048, 8192)  # 4 slots x 128-token chunk, MLP up-proj


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _count_custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _memory_line(label: str) -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
    return f"{label} memory: " + " ".join(
        f"{k}={stats.get(k, 'n/a')}" for k in keys)


def kernel_phase(seed: int) -> None:
    """Pallas kernels vs the ref.py oracle at one real-width shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import potq
    from repro.kernels import ops, ref
    from repro.kernels.potq_matmul import ACC_SCHEME

    m, k, n = KERNEL_MKN
    ka, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(ka, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.02
    g = jax.random.normal(kg, (m, n), jnp.float32) * 1e-3
    aq = potq.pot_quantize(a, 5)  # PoT-valued operands, as mfmac passes
    wq = potq.pot_quantize(w, 5)
    w_mean = jnp.mean(w)
    clip_t = jnp.max(jnp.abs(a)) * 0.95  # PRC at gamma 0.95
    amax = jnp.max(jnp.abs(a))
    cases = {
        "pot_value_matmul": (
            ops.pot_value_matmul, ref.pot_value_matmul_ref, (aq, wq)),
        "potq_matmul": (
            lambda a, w, mu, t: ops.potq_matmul(a, w, w_mean=mu, clip_t=t),
            lambda a, w, mu, t: ref.potq_matmul_ref(a, w, w_mean=mu,
                                                    clip_t=t),
            (a, w, w_mean, clip_t)),
        "potq_grad_matmuls": (
            lambda g, aq, wq, a, t, am: ops.potq_grad_matmuls(
                g, aq, wq, a=a, clip_t=t, amax=am),
            lambda g, aq, wq, a, t, am: ref.potq_grad_ref(
                g, aq, wq, a=a, clip_t=t, amax=am),
            (g, aq, wq, a, clip_t, amax)),
    }
    for name, (kern, oracle, args) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(kern).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        calls = _count_custom_calls(compiled)
        _check(calls > 0, f"kernel {name}: no tpu_custom_call")
        got = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        want = jax.jit(oracle)(*args)
        for i, (x, y) in enumerate(zip(jax.tree_util.tree_leaves(got),
                                       jax.tree_util.tree_leaves(want))):
            x, y = np.asarray(x), np.asarray(y)
            _check(np.isfinite(x).all(), f"kernel {name}[{i}]: non-finite")
            diff = int(np.sum(x != y))
            _check(diff == 0, f"kernel {name}[{i}]: {diff} of {x.size} "
                   f"elements differ from the ref.py oracle "
                   f"(max |d| {float(np.max(np.abs(x - y)))})")
        print(f"kernel {name} (M,K,N)={KERNEL_MKN}: equal to ref.py oracle "
              f"under {ACC_SCHEME}; tpu_custom_call {calls}; compile "
              f"{compile_s:.2f}s; run {run_s:.6f}s")
    print(_memory_line("kernels"))


def _serve_requests(cfg, seed: int):
    from repro.serve import poisson_trace

    return poisson_trace(
        cfg, n_requests=SERVE["requests"], prompt_len=SERVE["prompt_len"],
        lam=4.0, new_lo=SERVE["new_tokens"], new_hi=SERVE["new_tokens"],
        seed=seed,
    )


def _engine(cfg, params, *, plan=None, num_pages=None):
    from repro.core.policy import PAPER_FAITHFUL
    from repro.serve import PoolEngine

    policy = dataclasses.replace(PAPER_FAITHFUL, use_pallas=True)
    return PoolEngine(
        cfg, policy, params, max_slots=SERVE["slots"],
        max_len=SERVE["prompt_len"] + SERVE["new_tokens"],
        prefill_chunk=SERVE["chunk"], page_size=SERVE["page"],
        num_pages=num_pages, plan=plan,
    )


def _chunk_step_report(eng, args, label: str) -> None:
    """Compile the engine's pooled chunk step for ``args`` (shapes) under
    its policy and plan context, and check it holds Mosaic kernels.  A
    plan-carrying step comes from the public ``make_chunk_step(plan=)``,
    which jits it against the plan's shardings; a plan-less one is the
    registry body."""
    import functools

    import jax

    from repro.models import registry
    from repro.parallel import actshard
    from repro.serve import engine

    with actshard.use_plan(eng.plan):
        fn = (engine.make_chunk_step(eng.cfg, eng.policy, plan=eng.plan)
              if eng.plan else jax.jit(functools.partial(
                  registry.chunk_step, eng.cfg, eng.policy)))
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
    calls = _count_custom_calls(compiled)
    _check(calls > 0, f"{label} chunk step: no tpu_custom_call")
    print(f"{label} chunk step: compile {time.perf_counter() - t0:.1f}s; "
          f"tpu_custom_call {calls}")


def _serve(eng, reqs, label: str, *, first: bool = False):
    """Serve ``reqs``; with ``first`` the engine's chunk step program is
    also compiled alone and its custom-call count checked."""
    import jax

    step, seen = eng._chunk_step, []

    def capture(*args):  # records the first call's argument shapes
        if not seen:
            seen.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        return step(*args)

    if first:
        eng._chunk_step = capture
    t0 = time.perf_counter()
    out = eng.run(reqs)
    dt = time.perf_counter() - t0
    eng._chunk_step = step
    st = eng.last_stats
    total = sum(len(v) for v in out.values())
    print(f"{label}: {len(reqs)} requests, {total} tokens in {dt:.3f}s "
          f"(host clock{', compilation included' if first else ''}): "
          f"{st.decode_steps} pooled steps, {dt / st.decode_steps:.5f}s/"
          f"step, {st.weight_passes} weight passes, mean TTFT "
          f"{st.mean_ttft_passes:.1f} passes")
    if first:
        _check(bool(seen), f"{label}: the chunk step never ran")
        _chunk_step_report(eng, seen[0], label)
    return out, st


def serve_phase(seed: int) -> None:
    """PoolEngine at all 16 layers; pool vs solo byte identity."""
    import jax
    import numpy as np

    from repro import configs as C
    from repro.models import registry, spec as pspec

    cfg = C.get_config(ARCH)
    eng = _engine(cfg, pspec.materialize(registry.param_specs(cfg),
                                         jax.random.PRNGKey(seed)))
    reqs = _serve_requests(cfg, seed)
    out, _ = _serve(eng, reqs, "serve pool", first=True)
    again, _ = _serve(eng, reqs, "serve pool after warm-up")
    for r in reqs:
        toks = np.asarray(out[r.uid])
        _check(toks.shape == (r.max_new_tokens,),
               f"serve: request {r.uid} got {toks.shape[0]} tokens of "
               f"{r.max_new_tokens}")
        _check(bool(((toks >= 0) & (toks < cfg.vocab_padded)).all()),
               f"serve: request {r.uid} has out-of-vocab tokens")
        _check(np.array_equal(np.asarray(again[r.uid]), toks),
               f"serve: request {r.uid} changed on the second run")
    for r in (reqs[0], reqs[-1]):
        solo, _ = _serve(eng, [dataclasses.replace(r, arrival=0)],
                         f"serve solo uid={r.uid}")
        _check(np.array_equal(np.asarray(solo[r.uid]), np.asarray(out[r.uid])),
               f"serve: POOL_SOLO_BIT_IDENTITY broken for uid {r.uid}")
    print(f"serve: all {len(reqs)} requests served; uids {reqs[0].uid} and "
          f"{reqs[-1].uid} byte-identical pool vs solo")
    print(_memory_line("serve"))


def _train(label: str):
    from repro.launch import train

    summary = train.main(["--arch", ARCH, "--layers", str(TRAIN_LAYERS),
                          *TRAIN_ARGS])
    steps = int(TRAIN_ARGS[TRAIN_ARGS.index("--steps") + 1])
    _check(len(summary["losses"]) == steps,
           f"{label}: {len(summary['losses'])} of {steps} steps ran")
    _check(all(math.isfinite(x) for x in summary["losses"]),
           f"{label}: non-finite loss {summary['losses']}")
    _check(all(math.isfinite(x) for x in summary["grad_norms"]),
           f"{label}: non-finite grad norm {summary['grad_norms']}")
    _check(summary["tpu_custom_calls"] > 0,
           f"{label}: compiled train step has no tpu_custom_call")
    print(f"{label}: depth cut {TRAIN_LAYERS} layers; losses "
          f"{summary['losses']}; grad norms {summary['grad_norms']}; "
          f"compile {summary['compile_s']:.1f}s; {summary['step_s']:.4f}s/step "
          f"after warm-up; tpu_custom_call {summary['tpu_custom_calls']}; "
          f"peak_bytes_in_use {summary['peak_bytes_in_use']}")
    return summary


def train_phase(seed: int) -> None:
    del seed  # train.main seeds its weights and batches itself
    _train("train")


def sharded_serve_phase(seed: int) -> None:
    """Sharded pool on make_serving_mesh(data=4) vs the plan-less pool on
    one chip: byte-identical per request (SHARDED_POOL_BIT_IDENTITY)."""
    import jax
    import numpy as np

    from repro import configs as C
    from repro.models import registry, spec as pspec
    from repro.parallel import meshes, planner

    cfg = C.get_config(ARCH)
    mesh = meshes.make_serving_mesh(data=len(jax.devices()))
    max_len = SERVE["prompt_len"] + SERVE["new_tokens"]
    plan = planner.plan_for(
        cfg, mesh, shape=C.ShapeConfig("serve", max_len, SERVE["slots"],
                                       "decode"),
        pool_slots=SERVE["slots"], page_size=SERVE["page"])
    params = pspec.materialize(registry.param_specs(cfg),
                               jax.random.PRNGKey(seed))
    reqs = _serve_requests(cfg, seed)
    results = {}
    for label, p in (("one-chip pool", None), ("sharded pool", plan)):
        placed = params if p is None else jax.device_put(
            params, p.param_shardings())  # as a deployment places them
        eng = _engine(cfg, placed, plan=p, num_pages=plan.num_pages)
        del placed
        _serve(eng, reqs, label, first=True)
        results[label] = _serve(eng, reqs, f"{label} after warm-up")
        del eng
    (one, st1), (four, st4) = results.values()
    for r in reqs:
        _check(np.array_equal(np.asarray(one[r.uid]),
                              np.asarray(four[r.uid])),
               f"SHARDED_POOL_BIT_IDENTITY broken for uid {r.uid}")
    _check(st1.weight_passes == st4.weight_passes,
           f"weight passes {st1.weight_passes} vs {st4.weight_passes}")
    print(f"sharded serve: mesh {meshes.shape_dict(mesh)}; all {len(reqs)} "
          f"requests byte-identical to the one-chip pool; "
          f"{st4.weight_passes} weight passes on both")
    print(_memory_line("sharded serve"))


def host_mesh_train_phase(seed: int) -> None:
    """Train CLI on the (1, 4) host mesh vs the same run on one chip."""
    from repro.parallel import meshes

    del seed
    one_chip = lambda: meshes.make_mesh((1, 1), ("data", "model"))  # noqa
    with mock.patch.object(meshes, "make_host_mesh", one_chip):
        one = _train("train 1 chip")
    four = _train("train host mesh")
    worst = {}
    for key in ("losses", "grad_norms"):
        for i, (x, y) in enumerate(zip(one[key], four[key])):
            rel = abs(x - y) / abs(x)
            worst[key] = max(worst.get(key, 0.0), rel)
            _check(rel <= REL_TOL, f"step {i}: {key} {y} on the host mesh "
                   f"vs {x} on one chip (rel {rel:.3g} > {REL_TOL})")
    print(f"train host mesh vs 1 chip: losses "
          f"{'bit-identical' if one['losses'] == four['losses'] else 'differ'}"
          f"; max rel diff {worst['losses']!r} in losses, "
          f"{worst['grad_norms']!r} in grad norms (bound {REL_TOL}); 1-chip "
          f"step {one['step_s']:.4f}s, host-mesh step {four['step_s']:.4f}s")


PHASES = {
    1: (kernel_phase, serve_phase, train_phase),
    4: (sharded_serve_phase, host_mesh_train_phase),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=sorted(PHASES))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, not "
              "'tpu'; nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.enable()}")
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; jax {jax.__version__}")
    failed = []
    for phase in PHASES[args.chips]:
        name = phase.__name__
        t0 = time.perf_counter()
        print(f"== {name}", flush=True)
        try:
            phase(args.seed)
        except Exception:  # reported below; the run then exits non-zero
            traceback.print_exc()
            failed.append(name)
        print(f"== {name}: {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        jax.clear_caches()
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
