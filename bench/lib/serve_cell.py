"""A serving cell: ``PoolEngine`` with chunked admission, the paged cache
and the Pallas kernels, serving offline batches.

Set-up makes the serving weights on the device from the seed, one leaf at
a time and one layer at a time: each linear weight is drawn in float32
and PoT-quantized to bf16 by the program's own ``quantize_for_serving``,
so the float32 masters of a whole model never coexist.  It then serves
one call of the cell's traffic, which compiles or loads every program
the window's calls use.  The window serves further calls until
``--seconds`` is spent and ends with the call that crosses it; it is the
wall time of those ``PoolEngine.run`` calls.

Afterwards a sample of the finished requests, drawn from the seed and
holding the longest, is run through the plain reference over its prompt
and served tokens.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from bench.lib import check, model as model_lib, refmath
from bench.lib import traffic as traffic_lib
from bench.lib import weights as W
from bench.lib.seeds import rng, seed_arg
from bench.lib.train_cell import flat, model_config


def serve_policy(cell):
    from repro.core import policy as policy_lib

    return dataclasses.replace(policy_lib.PAPER_FAITHFUL,
                               use_pallas=cell["pallas"])


def make_weights(seed: int, cfg_json: dict, cfg, policy):
    """The program's serving parameter tree: linear weights quantized to
    bf16 PoT values, everything else float32."""
    import jax

    from repro.serve.quantized_weights import quantize_for_serving

    model = model_lib.load(cfg_json)
    out = {}
    for name, (_, _, depth) in model.layout(cfg_json).items():
        if name.endswith("/w"):
            def quant(s, l, name=name):
                x = W.leaf(s, cfg_json, name, l)
                return quantize_for_serving(cfg, policy, {"w": x})["w"]

            if depth is not None:
                fn = jax.jit(lambda s, quant=quant, depth=depth: jax.lax.map(
                    functools.partial(quant, s), jax.numpy.arange(depth)))
            else:
                fn = jax.jit(lambda s, quant=quant: quant(s, 0))
        else:
            fn = jax.jit(lambda s, name=name: W.leaf(s, cfg_json, name))
        out[name] = fn(seed_arg(seed))
    return model.tree(cfg_json, out)


def to_requests(call):
    from repro.serve.scheduler import Request

    return [Request(uid=r["uid"], tokens=r["tokens"],
                    max_new_tokens=r["max_new_tokens"], arrival=r["arrival"])
            for r in call]


def run_cell(run):
    import jax
    from jax.profiler import TraceAnnotation

    from repro.models import registry
    from repro.models import spec as pspec
    from repro.serve import PoolEngine

    cell, cfg_json, t = run.cell, run.cfg, run.traffic
    eng_cfg = cell["engine"]
    chunk = eng_cfg["prefill_chunk"]
    with TraceAnnotation("bench.setup"):
        model_lib.load(cfg_json, need=model_lib.SERVE)
        cfg = model_config(cfg_json)
        policy = serve_policy(cell)
        want = {k: v.shape for k, v in flat(pspec.abstract(
            registry.param_specs(cfg))).items()}
        params = make_weights(run.seed, cfg_json, cfg, policy)
        have = {k: v.shape for k, v in flat(params).items()}
        if want != have:
            raise RuntimeError(f"the program's parameters {want} are not "
                               f"the benchmark's {have}")
        eng = PoolEngine(
            cfg, dataclasses.replace(policy, weights_prequantized=True),
            params, max_slots=eng_cfg["slots"], max_len=eng_cfg["max_len"],
            prefill_chunk=chunk, page_size=eng_cfg["page_size"])
        eng = run.hooks.get("engine", lambda e: e)(eng)
        with TraceAnnotation("bench.warmup"):
            eng.run(to_requests(traffic_lib.serve_call(
                t, cfg_json["vocab"], chunk, run.seed, 0)))
    served, stats = [], []
    window_s = 0.0
    call = 1
    run.start_window()
    with TraceAnnotation("bench.window"):
        # a traced window holds one call: a call's trace is already large
        while window_s < run.seconds and not (run.trace and call > 1):
            reqs = traffic_lib.serve_call(t, cfg_json["vocab"], chunk,
                                          run.seed, call)
            batch = to_requests(reqs)
            with TraceAnnotation("bench.serve_run"):
                t0 = time.perf_counter()
                out = eng.run(batch)
                window_s += time.perf_counter() - t0
            st = eng.last_stats
            stats.append(st)
            for r in reqs:
                served.append((r, np.asarray(out[r["uid"]])))
            call += 1
    run.end_window()
    emitted = sum(s.emitted_tokens for s in stats)
    steps = sum(s.decode_steps for s in stats)
    record = {
        "window_s": window_s,
        "calls": call - 1,
        "attempted": len(served),
        "failed": sum(1 for r, toks in served
                      if toks.shape != (r["max_new_tokens"],)),
        "emitted": emitted,
        "steps": steps,
        "occupancy_sum": sum(s.occupancy_sum for s in stats),
        "requests": [(r["tokens"].shape[-1], len(toks)) for r, toks in served],
        "serve_tokens_per_s": emitted / window_s,
        "serve_step_ms": 1000.0 * window_s / steps,
        "device": run.describe_device(),
    }
    del eng, params
    jax.clear_caches()
    record["check"] = compare(run, served)
    return record


def sample(seed: int, served, min_tokens: int):
    """The longest finished request, then others drawn from the seed until
    ``min_tokens`` served tokens are in the sample."""
    order = sorted(range(len(served)),
                   key=lambda i: (-len(served[i][1]),
                                  -served[i][0]["tokens"].shape[-1]))
    rest = list(rng(seed, 3).permutation(order[1:]))
    picked, n = [order[0]], len(served[order[0]][1])
    while rest and n < min_tokens:
        i = int(rest.pop(0))
        picked.append(i)
        n += len(served[i][1])
    return [served[i] for i in picked]


def compare(run, served, nums=(refmath.Num(False),)):
    """The served tokens' readings under the reference.  With a second
    ``num`` (the control) also the readings of that control's own first
    choices (``control``) and of every served token altered by one
    (``altered_token``), in the detail."""
    cell = run.cell
    picked = sample(run.seed, served, cell["check_tokens"])
    seqs = [(np.concatenate([r["tokens"].reshape(-1), toks]),
             r["tokens"].shape[-1]) for r, toks in picked]
    ref = model_lib.load(run.cfg, need=model_lib.SERVE).StepReference(
        seed_arg(run.seed), run.cfg, cell["engine"]["prefill_chunk"],
        cell["engine"]["max_len"])
    logits = ref.logits(seqs, list(nums))
    base = logits[nums[0]]
    n_tokens = sum(len(toks) for _, toks in picked)

    def numbers(choices):
        return check.serve_numbers(
            [check.logit_gaps(lg, c) for lg, c in zip(base, choices)],
            cell["limits"], n_tokens, len(picked))

    result = numbers([toks for _, toks in picked])
    if len(nums) > 1:
        vocab = run.cfg["vocab"]
        result["detail"]["control"] = numbers(
            [np.argmax(clg, axis=-1) for clg in logits[nums[1]]]
        )["detail"]["readings"]
        result["detail"]["altered_token"] = numbers(
            [(toks + 1) % vocab for _, toks in picked])["detail"]["readings"]
    return result
