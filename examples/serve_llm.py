"""Continuous-batching serving with PoT-quantized weights.

  PYTHONPATH=src python examples/serve_llm.py --arch olmo-1b --pallas \\
      --prefill-chunk 128 --prompt-len 512 --new-tokens 64   # one TPU chip
  PYTHONPATH=src python examples/serve_llm.py --arch llama3-8b --smoke  # CPU

Spins up a :class:`repro.serve.PoolEngine` — slot-pooled KV cache + FIFO
continuous batching — and replays a small Poisson arrival trace through
it.  Weights are PoT-prequantized at engine construction (the default:
bit-identical outputs, half the decode weight-read bytes), and batching
never changes a request's tokens (tests/conformance/test_serve_batching).

Without ``--smoke`` the published config is served at full width;
``--smoke`` swaps in the reduced same-family config for CPU runs.
``--pallas`` routes the MF-MAC matmuls through the Pallas kernels
(compiled on a TPU, interpret mode elsewhere).  On a TPU pod the same
code runs under the production mesh — build the plan with
``planner.plan_for(cfg, mesh, shape=decode_shape, pool_slots=slots)`` and
pass ``plan=`` to the engine.
"""
import argparse
import dataclasses
import time

import jax

from repro import configs as C
from repro.core.policy import PAPER_FAITHFUL
from repro.launch import compile_cache
from repro.models import registry, spec as pspec
from repro.serve import PoolEngine, poisson_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU scale)")
    ap.add_argument("--pallas", action="store_true",
                    help="MF-MAC matmuls through the Pallas kernels")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--arrival-lam", type=float, default=2.0)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="enable chunked piggybacked prefill: admission "
                         "prompts stream C tokens per pooled step instead "
                         "of a solo batch-1 prefill pass per request")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = C.smoke_config(args.arch) if args.smoke else C.get_config(args.arch)
    policy = dataclasses.replace(PAPER_FAITHFUL, use_pallas=args.pallas)

    reqs = poisson_trace(
        cfg, n_requests=args.requests, prompt_len=args.prompt_len,
        lam=args.arrival_lam, new_lo=min(4, args.new_tokens),
        new_hi=args.new_tokens, seed=args.seed,
    )

    # vlm prompts occupy patch positions ahead of the text tokens
    prefix = cfg.num_patches if cfg.family == "vlm" and cfg.num_patches else 0
    engine = PoolEngine(
        cfg, policy,
        pspec.materialize(registry.param_specs(cfg), jax.random.PRNGKey(0)),
        max_slots=args.slots,
        max_len=prefix + args.prompt_len + args.new_tokens,
        prefill_chunk=args.prefill_chunk,
    )
    t0 = time.time()
    out = engine.run(reqs)
    dt = time.time() - t0
    st = engine.last_stats
    total = sum(len(v) for v in out.values())
    dev = jax.devices()[0]
    print(
        f"arch={cfg.name} served {len(reqs)} requests / {total} tokens "
        f"in {dt:.1f}s, compilation included ({total / dt:.1f} tok/s, "
        f"{st.decode_steps} pooled steps, {st.weight_passes} weight passes, "
        f"mean TTFT {st.mean_ttft_passes:.1f} passes, occupancy "
        f"{st.mean_occupancy:.0%}; {dev.platform} {dev.device_kind})"
    )
    print("sample:", out[0][:12].tolist())


if __name__ == "__main__":
    main()
