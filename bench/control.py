#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, the lower-precision
control and the planted faults, each against the plain reference, on
several seeds in one process.  The benchmark's own runs never run this.

  python3 bench/control.py --workload <name> --seeds 11,12,13

Training: for each seed the program's first three steps (the cell's own
step, state and feed), the reference computed in bfloat16 put in the
program's place (the control), the reference fed half of each batch, its
mean taken over the rest, and the reference with the clipping ratios'
gradient dropped (planted faults); each read by the cell's numbers
against the float32 reference.  A state left unchanged reads 1
by the measure of ``change_gap`` and needs no run.

Serving: for each seed one call of the cell's own traffic served by the
program, then the readings of the served tokens (the program), of the
bfloat16 reference's own first choices (the control) and of each served
token altered by one (a planted fault).

Each seed's readings are one JSON line on standard output.
"""
from __future__ import annotations

import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402

TRAIN_VARIANTS = ("program", "control", "half_batch", "no_dgamma")


def train_readings(run, variants=TRAIN_VARIANTS) -> dict:
    import jax

    from bench.lib import check, refmath, train_cell

    tr, prog = train_cell.checked_steps(run)
    del tr
    jax.clear_caches()
    ref = train_cell.reference_readings(run)
    make = {
        "control": lambda: train_cell.reference_readings(
            run, refmath.Num(True)),
        "half_batch": lambda: train_cell.reference_readings(
            run, rows=run.traffic["batch"] // 2),
        "no_dgamma": lambda: train_cell.reference_readings(
            run, refmath.Num(no_dgamma=True)),
    }
    runs = [("program", prog)] + [(v, make[v]()) for v in variants
                                  if v != "program"]
    limits = run.cell["limits"]
    out = {name: check.train_numbers(r, ref, limits)["detail"]["readings"]
           for name, r in runs}
    # each leaf's norms, the reference's first, for a look at the numbers
    out["leaves"] = {k: {"grad": [ref["grad_norms"][k]]
                         + [r["grad_norms"][k] for _, r in runs],
                         "change": [ref["change_norms"][k]]
                         + [r["change_norms"][k] for _, r in runs]}
                     for k in ref["grad_norms"]}
    return out


def serve_readings(run) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from bench.lib import refmath, serve_cell, traffic as traffic_lib

    cell, cfg_json = run.cell, run.cfg
    eng_cfg = cell["engine"]
    cfg = serve_cell.model_config(cfg_json)
    policy = serve_cell.serve_policy(cell)
    params = serve_cell.make_weights(run.seed, cfg_json, cfg, policy)
    from repro.serve import PoolEngine

    eng = PoolEngine(
        cfg, dataclasses.replace(policy, weights_prequantized=True), params,
        max_slots=eng_cfg["slots"], max_len=eng_cfg["max_len"],
        prefill_chunk=eng_cfg["prefill_chunk"],
        page_size=eng_cfg["page_size"])
    reqs = traffic_lib.serve_call(run.traffic, cfg_json["vocab"],
                                  eng_cfg["prefill_chunk"], run.seed, 1)
    out = eng.run(serve_cell.to_requests(reqs))
    served = [(r, np.asarray(out[r["uid"]])) for r in reqs]
    del eng, params
    jax.clear_caches()
    res = serve_cell.compare(run, served, nums=(refmath.Num(False),
                                                refmath.Num(True)))
    return {"program": res["detail"]["readings"],
            "control": res["detail"]["control"],
            "altered_token": res["detail"]["altered_token"],
            "tokens_compared": res["detail"]["tokens_compared"],
            "requests_compared": res["detail"]["requests_compared"]}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(TRAIN_VARIANTS),
                    help="training: which of " + ", ".join(TRAIN_VARIANTS))
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(bench_run.ROOT, "src")]
    spec = bench_run.load_spec(args.workload)

    from bench.lib import device

    try:
        devices = device.require_tpu(spec["workload"]["chips"])
    except device.NoAccelerator as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 3
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    variants = tuple(args.variants.split(","))
    read = {"train": lambda r: train_readings(r, variants),
            "serve": serve_readings}[spec["cell"]["kind"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.Run(spec, seed, 0.0, False, devices)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **read(run)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
