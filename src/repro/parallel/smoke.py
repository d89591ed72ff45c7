"""Multi-process / multi-device smoke path for the sharded serving pool.

CI has no accelerator fleet, so the sharded pool's collective paths would
go untested between here and a real pod.  XLA's host platform can fake a
fleet: launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
this driver sees N CPU devices, builds the ``(N, 1)`` serving mesh
(``meshes.make_serving_mesh``), plans a sharded pool on it
(``planner.plan_for(..., pool_slots=...)`` — slots, page tables, page
stores over the real N-way data axis) and drives a deterministic request
trace through the same :class:`repro.serve.PoolEngine` production code,
printing the served tokens as JSON.

The conformance harness (tests/conformance/test_serve_sharded.py) runs
this module in a subprocess — the env var must be set before jax imports,
hence a fresh process — and asserts the JSON tokens are byte-identical to
a single-device pool run of the same trace: the headline scaling
invariant (docs/DESIGN_scaling.md) exercised over an actual data-axis
split.  Run it by hand the same way:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        PYTHONPATH=src python -m repro.parallel.smoke --expect-devices 2
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

MAX_LEN = 24


def smoke_requests(cfg, n: int, *, seed: int = 0):
    """The deterministic smoke trace: ``n`` requests with heterogeneous
    prompt lengths / budgets / arrivals.  Shared between the subprocess
    driver and the in-process reference so both serve literally the same
    requests."""
    import jax

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 9))
        toks = rng.integers(0, cfg.vocab, (1, plen)).astype(np.int32)
        extras = {}
        if cfg.family == "encdec":
            extras["frames"] = np.asarray(
                jax.random.normal(
                    jax.random.PRNGKey(1000 + i),
                    (1, cfg.enc_seq, cfg.frame_dim),
                ),
                np.float32,
            )
        reqs.append(
            Request(
                uid=i, tokens=toks, max_new_tokens=int(rng.integers(2, 6)),
                arrival=i, extras=extras,
            )
        )
    return reqs


def run_smoke(arch: str = "llama3-8b", *, slots: int = 2, chunk: int = 4,
              n_requests: int = 4, sharded: bool = True,
              num_pages=None) -> dict:
    """Serve the smoke trace; returns a JSON-ready result dict.

    ``sharded=True`` plans the pool on ``make_serving_mesh()`` (all
    visible devices on the data axis) and runs the plan-carrying engine;
    ``sharded=False`` is the plan-less single-device reference.  The
    harness passes the subprocess's reported ``num_pages`` back in here
    (the planner rounds the default page count up per data axis, so a
    1-device reference would otherwise resolve fewer pages) — explicit
    geometry is honoured verbatim, making the comparison pure
    sharding-on vs sharding-off over shape-identical caches."""
    import jax

    from repro import configs as C
    from repro.core.policy import PAPER_FAITHFUL
    from repro.models import registry, spec as pspec
    from repro.parallel import meshes, planner
    from repro.serve import PoolEngine

    cfg = C.smoke_config(arch)
    params = pspec.materialize(registry.param_specs(cfg), jax.random.PRNGKey(0))
    mesh = meshes.make_serving_mesh()
    shape = C.ShapeConfig("serve", MAX_LEN, slots, "decode")
    plan = planner.plan_for(cfg, mesh, shape=shape, pool_slots=slots,
                            num_pages=num_pages)
    eng = PoolEngine(
        cfg, PAPER_FAITHFUL, params, max_slots=slots, max_len=MAX_LEN,
        prefill_chunk=chunk, page_size=plan.page_size,
        num_pages=plan.num_pages, plan=plan if sharded else None,
    )
    out = eng.run(smoke_requests(cfg, n_requests))
    stats = eng.last_stats
    return {
        "arch": arch,
        "devices": len(jax.devices()),
        "mesh": plan.mesh_shape(),
        "data_shards": stats.data_shards,
        "model_shards": stats.model_shards,
        "num_pages": plan.num_pages,
        "weight_passes": stats.weight_passes,
        "tokens": {str(uid): [int(t) for t in toks]
                   for uid, toks in out.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument(
        "--expect-devices", type=int, default=None,
        help="fail fast unless jax sees exactly this many devices (the "
        "XLA_FLAGS device-count forcing must land before jax imports)",
    )
    args = ap.parse_args(argv)
    import jax

    if (args.expect_devices is not None
            and len(jax.devices()) != args.expect_devices):
        print(
            f"expected {args.expect_devices} devices, found "
            f"{len(jax.devices())}; set XLA_FLAGS="
            "--xla_force_host_platform_device_count before launching",
            file=sys.stderr,
        )
        return 2
    result = run_smoke(
        args.arch, slots=args.slots, chunk=args.chunk,
        n_requests=args.requests,
    )
    json.dump(result, sys.stdout)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
