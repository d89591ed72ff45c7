"""Traffic generators.  One general generator per kind of cell reads the
parameters of a traffic file (``bench/traffic/<name>.json``):

* ``token_batches`` (training): a copy of ``data/pipeline.make_batch``'s
  generator — a Zipf-ish token marginal whose second half repeats the
  first — keyed by the seed and the step, so every step's rows differ.
* ``offline_batch`` (serving): calls of ``requests_per_call`` requests,
  all queued at the start.  Prompt and output lengths are a lognormal
  (median, sigma) clipped to [lo, hi], drawn as the stratified quantiles
  ``(i + 0.5) / n``, so every call and every seed serves the same set of
  sizes; outputs are paired with prompts by a fixed shuffle.  The
  requests are queued longest first (prefill chunks plus outputs), as an
  offline batch job orders its work.  The seed draws the prompt tokens.
  Arrivals follow a copy of ``serve/trace.py``'s Poisson clock on engine
  steps; ``arrival_lambda`` 0 queues every request at step 0.
"""
from __future__ import annotations

import math
import statistics
from typing import List, Tuple

import numpy as np

from bench.lib.seeds import rng


def clipped_lognormal(spec: dict, n: int) -> List[int]:
    """n stratified lengths of a lognormal with the given median and
    sigma, rounded and clipped to [lo, hi], ascending."""
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length bounds [{lo}, {hi}]")
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"]
                                      * nd.inv_cdf((i + 0.5) / n))
        out.append(min(hi, max(lo, int(round(x)))))
    return out


def poisson_arrivals(n: int, lam: float, r: np.random.Generator):
    """Copy of ``serve/trace.py``'s clock: Poisson(lam) gaps in engine
    steps, the first request at step 0."""
    arrivals = np.cumsum(r.poisson(lam, n)) if lam > 0 else np.zeros(n, int)
    if n:
        arrivals[0] = 0
    return [int(a) for a in arrivals]


def call_sizes(traffic: dict, chunk: int) -> List[Tuple[int, int]]:
    """The (prompt, output) lengths of one call, in submission order."""
    n = int(traffic["requests_per_call"])
    prompts = clipped_lognormal(traffic["prompt"], n)
    outs = clipped_lognormal(traffic["output"], n)
    shuffle = np.random.default_rng(int(traffic["pairing_seed"])).permutation(n)
    pairs = [(prompts[i], outs[j]) for i, j in enumerate(shuffle)]
    return sorted(pairs, key=lambda po: (-(-(-po[0] // chunk) + po[1]),
                                         -po[0]))


def serve_call(traffic: dict, vocab: int, chunk: int, seed: int,
               call: int) -> List[dict]:
    """Requests of call number ``call``: uid, prompt tokens (1, P) int32,
    max_new_tokens, arrival step."""
    sizes = call_sizes(traffic, chunk)
    r = rng(seed, 1, call)
    arrivals = poisson_arrivals(len(sizes), float(traffic["arrival_lambda"]),
                                r)
    out = []
    for i, ((p, o), arr) in enumerate(zip(sizes, arrivals)):
        out.append({
            "uid": call * len(sizes) + i,
            "tokens": r.integers(0, vocab, (1, p)).astype(np.int32),
            "max_new_tokens": o,
            "arrival": arr,
        })
    return out


def make_batch(vocab: int, batch: int, seq: int, seed, step):
    """Copy of ``data/pipeline.make_batch`` for a decoder: (tokens,
    labels, mask), pure in (seed, step).  Trace under ``jax.jit`` with the
    32-bit ``seeds.seed_arg`` and ``step`` arguments."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), 2),
                             step)
    k1, _, _, _ = jax.random.split(key, 4)
    u = jax.random.uniform(k1, (batch, seq))
    base = jnp.minimum((vocab * u**3).astype(jnp.int32), vocab - 1)
    period = max(seq // 2, 1)
    idx = jax.lax.iota(jnp.int32, seq) % period
    tokens = jnp.take_along_axis(
        base, jnp.broadcast_to(idx[None], (batch, seq)), axis=1)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((batch, seq), jnp.float32).at[:, -1].set(0.0)
    return {"tokens": tokens, "labels": labels, "mask": mask}
