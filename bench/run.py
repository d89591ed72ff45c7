#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
workload names its configuration (``bench/configs/<config>.json``) and
its traffic (``bench/traffic/<traffic>.json``); ``bench/cells/<workload>.json``
holds its kind (train or serve), the program's settings and the limits of
its check; each per-layer metric is read by ``bench/metrics/<metric>.py``.
The configuration's ``reference`` key names its model module
(``bench/lib/model.py``): parameter layout, plain reference, model FLOPs.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
its per-layer metrics, the device's busy and window seconds and a
``breakdown``.  Every run checks what its timed path produced against the
plain reference and prints each number compared beside its limit, last
on standard error and last in the result line.  The last line of standard
output is that one JSON object.  A backend other than the TPU, or fewer
chips than the cell asks for, exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, "bench_out")
COMPILE_EVENTS = ("/jax/core/compile/",
                  "/jax/compilation_cache/cache_retrieval")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec(workload: str) -> dict:
    """The workload's entries and files, by name."""
    spec = load_json(ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if workload not in wl:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(wl)}")
    w = wl[workload]
    config = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return {
        "workload": w,
        "cfg": load_json(ROOT, config["file"]),
        "cell": load_json(BENCH, "cells", f"{workload}.json"),
        "traffic": load_json(BENCH, "traffic", f"{w['traffic']}.json"),
        "end_to_end": [m for m in spec["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in spec["per_layer"] if _applies(spec, m,
                                                               workload)],
    }


def _applies(spec, metric, workload) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = {m["name"]: m for m in spec["end_to_end"]}[metric["moves"]]
    return workload in moves.get("workloads", [workload])


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Run:
    """One run of one cell: its inputs, its clock and its trace."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 devices, hooks=None):
        self.spec = spec
        self.workload = spec["workload"]["name"]
        self.cell, self.cfg, self.traffic = (spec["cell"], spec["cfg"],
                                             spec["traffic"])
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.hooks = hooks or {}
        self.setup_s = None
        self.trace_dir = os.path.join(OUT, "trace", self.workload)
        self.in_window = False
        self.window_compiles = 0

    def count_compile(self, event: str, *args, **kwargs):
        """A JAX monitoring listener: counts the programs traced, compiled
        or loaded from the compile cache while the window is open."""
        if self.in_window and event.startswith(COMPILE_EVENTS):
            self.window_compiles += 1

    def start_window(self):
        import jax

        self.setup_s = time.perf_counter() - T_START
        jax.monitoring.register_event_duration_secs_listener(
            self.count_compile)
        self.in_window = True
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def end_window(self):
        import jax

        self.in_window = False
        jax.monitoring.unregister_event_duration_listener(self.count_compile)
        if self.trace:
            jax.profiler.stop_trace()

    def describe_device(self) -> dict:
        from bench.lib import device

        return device.describe(self.devices)


def execute(run: Run) -> dict:
    """Drive the cell and assemble its result object."""
    from bench.lib import check, serve_cell, train_cell

    driver = {"train": train_cell, "serve": serve_cell}[run.cell["kind"]]
    rec = driver.run_cell(run)
    chk = rec["check"]
    correct = check.passed(chk) and rec["failed"] == 0
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": {}, "device": rec["device"]}
    if not run.trace:
        for m in run.spec["end_to_end"]:
            value = run.setup_s if m["name"] == "setup_s" else rec[m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from bench.lib import device, flops, model, weights

        red = reduce_trace(run)
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        ctx = {"record": rec, "trace": red, "cell": run.cell, "cfg": run.cfg,
               "traffic": run.traffic, "flops": flops,
               "model": model.load(run.cfg),
               "vocab_padded": weights.vocab_padded(run.cfg),
               "peak": device.peaks(result["device"]["kind"])}
        for m in run.spec["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["check"] = {k: {"value": v, "limit": chk["limits"][k],
                           "sound_if": ">=" if k in check.AT_LEAST else "<="}
                       for k, v in chk["numbers"].items()}
    result["_detail"] = dict(chk["detail"],
                             window_compiles=run.window_compiles)
    return result


def reduce_trace(run: Run) -> dict:
    import glob

    from bench.lib import tracereduce

    paths = glob.glob(os.path.join(run.trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no profiler trace under {run.trace_dir}")
    trace = tracereduce.load_xplane(max(paths, key=os.path.getmtime))
    families = run.cell["kernel_families"]
    return tracereduce.reduce(trace, tracereduce.window_of(
        trace, "bench.window"), families)


def emit(result: dict) -> None:
    detail = result.pop("_detail")
    print(f"check detail: {json.dumps(detail)}", file=sys.stderr)
    for k, v in result["check"].items():
        print(f"check {k} {v['value']!r} sound if {v['sound_if']} "
              f"{v['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    spec = load_spec(args.workload)

    from bench.lib import device

    try:
        devices = device.require_tpu(spec["workload"]["chips"])
    except device.NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    # every program, however quick to compile, is kept for the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    emit(execute(Run(spec, args.seed, args.seconds, bool(args.trace),
                     devices)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
