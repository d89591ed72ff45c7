"""Serving engines over the registry API: continuous batching + lockstep.

The production surface is :class:`PoolEngine` — a slot-pooled KV cache
(one fixed ``max_slots x max_len`` cache built once via
``registry.init_pool_cache``) driven by a FIFO continuous-batching
scheduler (serve/scheduler.py): queued requests are admitted into free
slots mid-flight — via a solo prefill-into-slot step, or, with
``prefill_chunk=C``, by streaming the prompt C tokens at a time through
the same fused pooled step the decoding slots ride (chunked piggybacked
prefill) — a single jitted fixed-shape step advances the whole pool with
per-slot position indices, and slots retire on EOS / ``max_new_tokens``
and are refilled immediately.  Decode is weight-bound, so dead slots
streaming weights for nothing is the dominant waste of the old lockstep
loop, and every solo admission prefill is an extra full weight pass —
``benchmarks/servebench.py`` measures the recovered tokens/sec, weight
passes, and per-request TTFT.

The headline guarantee (docs/DESIGN_serving.md, enforced by
tests/conformance/test_serve_batching.py): **batching policy never
changes a request's tokens**.  For any arrival order and slot count, each
request's output is bit-identical to running it alone, because every
per-row computation in the decode step is batch-invariant — matmul rows
reduce independently (the PR-2 tiling-invariant kernels), softmax/norms
are row-local, and activation quantization scales are per-sample under
``policy.per_sample_act_scales`` (forced on by the engine).

``generate`` is a thin wrapper over a pool with one slot per request;
``lockstep_generate`` keeps the pre-pool semantics (batched prefill, one
shared position, fixed horizon) as the servebench baseline.

Sharded serving consumes a validated
:class:`repro.parallel.planner.ShardingPlan` built with ``pool_slots``
(so its cache specs cover the lifted per-slot ``pos``/``len`` leaves):
pass ``plan=`` to the step factories or engines; with ``plan=None`` (CPU
tests, single device) everything runs unsharded.  Pool plans shard the
pool itself — slots, page tables, page stores and beta leaves over the
data axes, weights over 'model' (docs/DESIGN_scaling.md) — and the
engine's admission pipeline is double-buffered so host-side scheduling
and prefill-chunk staging overlap the in-flight jitted step (see
:meth:`PoolEngine.run`); because the staged rows are byte-identical to
what the synchronous loop would build, sharding and overlap never change
a request's tokens (tests/conformance/test_serve_sharded.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import compress
from repro.core.policy import QuantPolicy, draft_policy
from repro.models import registry
from repro.parallel import actshard
from repro.parallel.planner import ShardingPlan
from repro.serve import slots as slots_lib
from repro.serve import spec as spec_lib
from repro.serve.scheduler import FIFOScheduler, Request


def _plan_batch(plan: ShardingPlan) -> int:
    assert plan.shape is not None, (
        "serving plans must be built with a ShapeConfig "
        "(planner.plan_for(cfg, mesh, shape=decode_shape))"
    )
    return plan.shape.global_batch


def prime_kernel_autotune(cfg: ModelConfig, policy: QuantPolicy, *,
                          batch: int, seq: int = 1,
                          chunk: Optional[int] = None,
                          draft_bits: Optional[int] = None,
                          measure: bool = False):
    """Warm (or, with ``measure=True``, benchmark and persist) the tuned
    block choices for EVERY matmul shape this engine's serve steps can
    dispatch.

    With ``policy.use_pallas`` the serve-step matmuls resolve their block
    shapes through ``kernels/autotune.py`` at trace time (tuned cache ->
    heuristic).  Historically this primed the forward *decode* shapes
    only, so a chunked engine's first ``(B, C)`` ``chunk_step`` trace —
    and a speculative engine's draft/verify traces — hit a cold cache.
    Now:

    * ``chunk=C`` also primes the ``M = batch * C`` chunk-step shapes
      (the fused decode+prefill dispatch; the spec verify step's inner
      per-position matmuls are decode-shaped and need nothing extra);
    * ``draft_bits=b`` also primes the low-bit self-draft decode shapes
      under ``core.policy.draft_policy`` bit-widths (on the raw
      value-matmul path these normalize onto the same cache keys as the
      serving bits — ``cache_key`` drops emax for ``quantize=False`` —
      so this is a cheap no-op hit that *asserts* coverage rather than a
      new sweep);
    * shapes still missing after the consult are seeded with their
      heuristic choice as **transient** cache entries (never flushed to
      disk), so a primed engine performs zero tuning-cache misses at
      serve time.  Tiling is numerics-free — the kernel's fixed-order
      reduction is bit-identical across block shapes — so neither
      seeding nor later retuning ever changes served outputs.

    Returns [(shape, BlockChoice), ...], or [] when the jnp path is in
    use.  Serving primes forward keys only (``include_grads=False``): a
    serve step never executes the fused backward MACs; training runs
    prime those via ``launch/train.py --autotune``.
    """
    if not policy.use_pallas:
        return []
    from repro.kernels import autotune

    seqs = [seq]
    if chunk is not None and chunk > 1 and chunk != seq:
        seqs.append(chunk)
    out = []
    for s in seqs:
        out += autotune.prime_for_model(
            cfg, batch=batch, seq=s, bits_a=policy.bits_a,
            bits_w=policy.bits_w, measure=measure,
        )
    if draft_bits is not None:
        out += autotune.prime_for_model(
            cfg, batch=batch, seq=seq, bits_a=draft_bits,
            bits_w=draft_bits, measure=measure,
        )
    if not measure:
        # seed the still-cold shapes so serve-time lookups all hit
        cache = autotune.active_cache()
        for (m, k, n), choice in out:
            if choice.source != "heuristic":
                continue
            key = autotune.cache_key(m, k, n, quantize=False)
            if cache.get(key) is None:
                cache.put(
                    key,
                    {"bm": choice.bm, "bn": choice.bn, "bk": choice.bk,
                     "source": "primed"},
                    persist=False,
                )
    return out


# One jitted step per (cfg, policy): generate, PoolEngine, lockstep waves
# and the tests all reuse literally the same compiled closure instead of
# re-jitting a fresh lambda per call.  Plan-carrying steps are built once
# per engine by their callers and skip the cache (plans hold pytrees and
# are not hashable) — and so must any step *traced* under an ambient
# actshard plan: the model's shard_tokens constraints bake the plan
# active at trace time into the compiled step, so a shared cache entry
# would leak one caller's mesh constraints into another's.  jax traces
# lazily (at first call, not at build), so the shared entries are wrapped
# in a call-time check that the ambient plan still matches the one at
# build time — build your step inside the sharding context you will call
# it in.
_STEP_CACHE: Dict = {}


def _prefill_fn(cfg: ModelConfig, policy: QuantPolicy):
    def prefill_step(params, batch, cache):
        return registry.prefill(cfg, policy, params, batch, cache)

    return prefill_step


def _decode_fn(cfg: ModelConfig, policy: QuantPolicy):
    def decode_step(params, token, cache):
        logits, cache = registry.decode_step(cfg, policy, params, token, cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, cache

    return decode_step


def _chunk_fn(cfg: ModelConfig, policy: QuantPolicy):
    def chunk_step(params, tokens, n_new, cache):
        logits, cache = registry.chunk_step(
            cfg, policy, params, tokens, n_new, cache
        )
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, cache

    return chunk_step


def _encxkv_fn(cfg: ModelConfig, policy: QuantPolicy):
    def encxkv_step(params, frames):
        return registry.encode_cross_kv(cfg, policy, params, frames)

    return encxkv_step


def _verify_fn(cfg: ModelConfig, policy: QuantPolicy):
    def verify_step(params, tokens, n_new, cache):
        logits, cache = registry.verify_step(
            cfg, policy, params, tokens, n_new, cache
        )
        # the same argmax the decode/chunk steps apply, per position —
        # position i's token is exactly what plain decode would emit
        # after tokens[:, i]
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, C)
        return next_tok, logits, cache

    return verify_step


def _draft_fn(cfg: ModelConfig, draft_pol: QuantPolicy, k: int):
    """k greedy decode steps under the low-bit draft policy, on the live
    pool cache.  Returns (draft tokens (B, k), cache with ``len`` rewound
    to the pre-draft positions — the verify pass starts from there; the
    draft's K/V + pos pollution is erased by the engine's snapshot
    restore before verification)."""

    def draft_steps(params, token, cache):
        toks = []
        for _ in range(k):
            logits, cache = registry.decode_step(
                cfg, draft_pol, params, token, cache
            )
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            toks.append(token)
        cache = dict(cache)
        cache["len"] = cache["len"] - k
        return jnp.stack(toks, axis=1), cache

    return draft_steps


def _spec_snap_fn(c: int):
    def snap_step(cache):
        return slots_lib.spec_snapshot(cache, c)

    return snap_step


def _spec_restore_fn():
    def restore_step(cache, snap, keep):
        return slots_lib.spec_restore(cache, snap, keep)

    return restore_step


def _shared_step(kind: str, cfg, policy, body, donate=()):
    """Cache-or-build a plan-less jitted step, enforcing at call time that
    the ambient actshard plan matches the one active at build time (it
    would otherwise silently bake into — or be missing from — the shared
    trace).  ``donate`` names the cache argument of a step that returns
    the updated cache: donated, the step updates the K/V stores in place
    (``transformer._scan_layers``) instead of writing a second cache, so
    a caller must use the returned cache and never the one it passed."""
    ambient = actshard.active_plan()
    if ambient is not None:
        # private closure: the ambient plan's constraints bake in at trace
        # time, so this trace must never be shared (plans are unhashable,
        # and id()-keying would risk stale reuse after gc)
        jitted = jax.jit(body, donate_argnums=donate)
    else:
        key = (kind, cfg, policy)
        if key not in _STEP_CACHE:
            _STEP_CACHE[key] = jax.jit(body, donate_argnums=donate)
        jitted = _STEP_CACHE[key]

    def checked(*args, _jitted=jitted, _ambient=ambient):
        if actshard.active_plan() is not _ambient:
            raise RuntimeError(
                f"{kind} step was built under a different actshard plan "
                "than is active now; rebuild it (make_prefill_step/"
                "make_decode_step) inside the context you call it in"
            )
        return _jitted(*args)

    return checked


def make_prefill_step(cfg: ModelConfig, policy: QuantPolicy,
                      plan: Optional[ShardingPlan] = None):
    """The batched prefill step (``registry.prefill``): consumes a batch
    dict, returns (last-position logits, filled cache); the cache passed
    in is donated either way.  Plan-less calls share one jitted closure
    per (cfg, policy) under the ambient-plan contract of
    ``_shared_step``; with a ``plan`` the step is jitted against the
    plan's param/data/cache shardings, so the compiled step IS the
    sharded program — no per-call re-derivation."""
    prefill_step = _prefill_fn(cfg, policy)
    if plan is None:
        return _shared_step("prefill", cfg, policy, prefill_step, (2,))
    b = _plan_batch(plan)
    cache_sh = plan.cache_shardings()
    return jax.jit(
        prefill_step,
        in_shardings=(
            plan.param_shardings(),
            plan.data_shardings(),
            cache_sh,
        ),
        out_shardings=(
            plan.named(plan.logits_pspec(b)),
            cache_sh,
        ),
        donate_argnums=(2,),
    )


def make_decode_step(cfg: ModelConfig, policy: QuantPolicy, *,
                     plan: Optional[ShardingPlan] = None):
    """The ONE greedy decode-step builder: ``generate``, :class:`PoolEngine`
    and the tests all jit through here (a single closure per engine, not a
    fresh lambda per ``generate`` call), so every caller decodes through
    literally the same compiled step."""
    decode_step = _decode_fn(cfg, policy)
    if plan is None:
        return _shared_step("decode", cfg, policy, decode_step, (2,))
    b = _plan_batch(plan)
    cache_sh = plan.cache_shardings()
    tok_sh = plan.named(plan.token_pspec(b))
    return jax.jit(
        decode_step,
        in_shardings=(
            plan.param_shardings(),
            tok_sh,
            cache_sh,
        ),
        out_shardings=(
            tok_sh,
            plan.named(plan.logits_pspec(b)),
            cache_sh,
        ),
        donate_argnums=(2,),
    )


def make_chunk_step(cfg: ModelConfig, policy: QuantPolicy, *,
                    plan: Optional[ShardingPlan] = None):
    """The fused decode/prefill-chunk step (``registry.chunk_step``) for
    chunked piggybacked prefill: one fixed-shape dispatch advances decode
    slots by one token and prefilling slots by up to C prompt tokens.
    The chunk width is carried by the call shapes (jit re-traces per
    width), so the closure is shared exactly like the decode step's."""
    chunk_step = _chunk_fn(cfg, policy)
    if plan is None:
        return _shared_step("chunk", cfg, policy, chunk_step, (3,))
    b = _plan_batch(plan)
    cache_sh = plan.cache_shardings()
    tok_sh = plan.named(plan.token_pspec(b))
    chunk_sh = plan.named(plan.chunk_pspec(b))
    return jax.jit(
        chunk_step,
        in_shardings=(
            plan.param_shardings(),
            chunk_sh,
            tok_sh,
            cache_sh,
        ),
        out_shardings=(
            tok_sh,
            plan.named(plan.logits_pspec(b)),
            cache_sh,
        ),
        donate_argnums=(3,),
    )


def make_verify_step(cfg: ModelConfig, policy: QuantPolicy, *,
                     plan: Optional[ShardingPlan] = None):
    """The speculative-decoding verifier (``registry.verify_step``): one
    full-policy weight pass scoring each slot's verify row, bit-identical
    to sequential decode steps.  Returns per-position argmax tokens
    (B, C), logits (B, C, V) and the advanced cache; the verify width is
    carried by the call shapes (jit re-traces per width), so the closure
    is shared exactly like the chunk step's."""
    verify_step = _verify_fn(cfg, policy)
    if plan is None:
        return _shared_step("verify", cfg, policy, verify_step, (3,))
    b = _plan_batch(plan)
    cache_sh = plan.cache_shardings()
    chunk_sh = plan.named(plan.chunk_pspec(b))
    tok_sh = plan.named(plan.token_pspec(b))
    return jax.jit(
        verify_step,
        in_shardings=(
            plan.param_shardings(),
            chunk_sh,
            tok_sh,
            cache_sh,
        ),
        out_shardings=(chunk_sh, None, cache_sh),
        donate_argnums=(3,),
    )


# ---------------------------------------------------------------------------
# Continuous-batching pool engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeStats:
    """Host-side counters from one :meth:`PoolEngine.run`.

    ``weight_passes`` is the deterministic cost/latency clock: decode is
    weight-bound, so every full weight-streaming dispatch — a pooled
    decode/chunk step, a solo admission prefill, an encdec encoder-side
    admission — counts one pass regardless of batch composition.
    ``ttft_passes[uid]`` measures a request's time-to-first-token on that
    clock, from the first engine step at which it was admissible (queue
    wait included).  Both are exactly reproducible for a fixed trace,
    which is what lets CI gate them (benchmarks/compare.py).
    """

    decode_steps: int = 0  # pooled step dispatches (plain decode or fused chunk)
    prefills: int = 0  # completed admissions
    emitted_tokens: int = 0
    occupancy_sum: float = 0.0  # sum over steps of occupied/max_slots
    weight_passes: int = 0
    ttft_passes: Dict = dataclasses.field(default_factory=dict)
    # speculative decoding (serve/spec.py) — deterministic, CI-gated
    accepted_tokens: int = 0  # draft tokens accepted by verify rounds
    draft_weight_passes: int = 0  # low-bit self-draft passes, counted
    # separately from weight_passes: a 2-3-bit PoT draft stream is the
    # nearly-free pass the paper's cost model promises, not a full one
    # paged-pool counters (zero for unpaged families) — all deterministic
    # for a fixed trace, so benchmarks/compare.py gates on them directly
    prompt_tokens: int = 0  # total prompt tokens across admitted requests
    prefix_hit_tokens: int = 0  # prompt tokens served from the prefix cache
    cow_copies: int = 0
    evictions: int = 0
    admission_deferrals: int = 0  # head-blocked admissions (page pressure)
    pages_in_use_sum: int = 0  # sum over decode steps of live pages
    page_size: int = 0
    kv_page_bytes: int = 0  # HBM bytes of one K+V page across all layers
    # sharded serving (docs/DESIGN_scaling.md): the mesh-shape keys of the
    # engine's plan — data_shards slots-per-device divisor, model_shards
    # weight-shard divisor; both 1 for plan-less / host-mesh engines
    data_shards: int = 1
    model_shards: int = 1

    @property
    def mean_occupancy(self) -> float:
        """Mean fraction of slots doing useful work per pooled step."""
        return self.occupancy_sum / self.decode_steps if self.decode_steps else 0.0

    @property
    def per_device_weight_passes(self) -> float:
        """Full-weight-equivalent streams per device: with weights sharded
        ``model_shards``-way, each SPMD dispatch streams 1/model_shards of
        the weight bytes per device, so the per-device cost clock is
        ``weight_passes / model_shards`` — the tensor-parallel payoff the
        sharded pool exists for (data_shards divides the KV traffic, not
        the weight traffic).  Deterministic like ``weight_passes``, so
        benchmarks/compare.py gates on it directly."""
        return self.weight_passes / max(1, self.model_shards)

    @property
    def mean_ttft_passes(self) -> float:
        """Mean per-request time-to-first-token on the weight-pass clock
        (queue wait included) — the deterministic admission-latency gate."""
        if not self.ttft_passes:
            return 0.0
        return sum(self.ttft_passes.values()) / len(self.ttft_passes)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens served from shared prefix pages."""
        if not self.prompt_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prompt_tokens

    @property
    def accepted_tokens_per_weight_pass(self) -> float:
        """Tokens served per full-policy weight pass — THE speculative-
        decoding lever (decode is weight-bound).  Plain decode emits at
        most one token per pass, so anything > 1.0 is speculation's win;
        the low-bit draft passes are tracked in ``draft_weight_passes``
        and priced separately."""
        if not self.weight_passes:
            return 0.0
        return self.emitted_tokens / self.weight_passes

    @property
    def kv_hbm_bytes_per_token(self) -> float:
        """Mean live KV HBM footprint per emitted token: the capacity side
        of the paged refactor (pages, not whole rows, pin memory)."""
        if not self.emitted_tokens:
            return 0.0
        return self.pages_in_use_sum * self.kv_page_bytes / self.emitted_tokens


class _InflightTokens:
    """Handle to the token vector of a dispatched pooled step.

    JAX dispatch is asynchronous — the jitted step returns device buffers
    immediately while the computation runs; ``copy_to_host_async`` then
    starts the device->host transfer of the (max_slots,) token vector in
    the background too.  The engine's double-buffered admission work
    (arrival stamping, next-step prefill-chunk staging — see
    :meth:`PoolEngine.run`) happens between dispatch and :meth:`wait`, so
    host-side scheduling overlaps both the step and the copy.  ``wait()``
    is the ONE synchronization point per engine step; retirement, EOS
    cuts and the admissions they unblock all run as the continuation of
    the arrived copy (host-callback retirement)."""

    def __init__(self, tok):
        self._tok = tok
        try:
            tok.copy_to_host_async()
        except AttributeError:  # non-jax stand-ins in unit tests
            pass

    def wait(self) -> np.ndarray:
        """Block until the copy lands; returns the host token vector."""
        return np.asarray(self._tok)


class PoolEngine:
    """Continuous-batching serving engine over a slot-pooled KV cache.

    Weights are PoT-prequantized at construction by default
    (serve/quantized_weights.py): re-quantization at use is idempotent on
    PoT values, so served outputs are bit-identical to quantize-at-use
    while the decode weight-read term halves.  Pass ``prequantize=False``
    to serve raw weights (or a disabled policy, which never quantizes).

    The bit-identity guarantee holds for every family in
    ``registry.POOLED_FAMILIES``, MoE included: expert-capacity dispatch
    runs per slot (``transformer._moe_apply(per_slot=True)``), so a
    request's expert routing never depends on its pool neighbours — live
    or retired (docs/DESIGN_serving.md §3).

    ``prefill_chunk=C`` switches admission to **chunked piggybacked
    prefill**: instead of a solo batch-1 prefill pass per admission (an
    extra full weight-streaming pass that also recompiles per prompt
    length), prompts are consumed C tokens per engine step by the same
    fused fixed-shape ``registry.chunk_step`` that advances the decoding
    slots — admission rides along with the pool.  Chunking is part of the
    request's computation recipe (activation-scale groups cover a chunk,
    not the whole prompt), so chunked output is *not* bit-identical to
    solo-prefill output; what IS guaranteed — and pinned by the
    conformance suite — is that batching still never changes a request's
    tokens: pool output is bit-identical to the same request driven alone
    through the same chunked steps.  Families outside
    ``registry.CHUNKED_FAMILIES`` (ssm/hybrid: single-position
    recurrences) and VLM requests with patch prefixes fall back to solo
    prefill admission per request.
    """

    def __init__(self, cfg: ModelConfig, policy: QuantPolicy, params, *,
                 max_slots: int, max_len: int, cache_dtype=jnp.bfloat16,
                 prequantize: bool = True,
                 prefill_chunk: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 spec=None,
                 kv_quant=None,
                 plan: Optional[ShardingPlan] = None):
        if cfg.family not in registry.POOLED_FAMILIES:
            raise NotImplementedError(
                f"PoolEngine: family {cfg.family!r} lacks per-slot decode"
            )
        if spec is not None:
            if cfg.family not in registry.SPEC_FAMILIES:
                raise NotImplementedError(
                    f"spec: family {cfg.family!r} has no verify step "
                    f"(supported: {registry.SPEC_FAMILIES})"
                )
            if not isinstance(spec, (spec_lib.NgramDrafter,
                                     spec_lib.LowBitSelfDraft)):
                raise TypeError(
                    "spec must be a serve.spec.NgramDrafter or "
                    f"serve.spec.LowBitSelfDraft (got {type(spec).__name__})"
                )
            span = registry.pool_span(cfg, max_len)
            if spec.max_draft + 1 > span:
                raise ValueError(
                    f"spec.max_draft={spec.max_draft}: a verify row of "
                    f"{spec.max_draft + 1} positions exceeds the cache "
                    f"span {span}"
                )
        if prefill_chunk is not None:
            if cfg.family not in registry.CHUNKED_FAMILIES:
                raise NotImplementedError(
                    f"prefill_chunk: family {cfg.family!r} has no fused "
                    f"chunk step (supported: {registry.CHUNKED_FAMILIES})"
                )
            span = registry.pool_span(cfg, max_len)
            if not 1 <= prefill_chunk <= span:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be in [1, "
                    f"{span}] (the cache span) so a chunk's ring writes "
                    "cannot collide"
                )
        self.paged = cfg.family in registry.PAGED_FAMILIES
        if not self.paged and (page_size is not None or num_pages is not None
                               or prefix_cache):
            raise ValueError(
                f"family {cfg.family!r} has no paged cache (paged: "
                f"{registry.PAGED_FAMILIES}); drop page_size/num_pages/"
                "prefix_cache"
            )
        # PoT-quantized KV pages (core.policy.KVQuantSpec): the kwarg wins,
        # else a recipe already on the policy applies; either way the spec
        # is pushed onto the policy so every step body (and the step cache
        # key) sees it as a static jit argument.
        kv_quant = kv_quant if kv_quant is not None else policy.kv_quant
        if kv_quant is not None:
            if not self.paged:
                raise ValueError(
                    f"kv_quant: family {cfg.family!r} has no paged KV cache "
                    f"to quantize (paged: {registry.PAGED_FAMILIES})"
                )
            compress.kv_code_width(kv_quant, cfg.head_dim)  # even-hd check
        self.kv_quant = kv_quant
        policy = dataclasses.replace(policy, kv_quant=kv_quant)
        if self.paged:
            span = registry.pool_span(cfg, max_len)
            self.page_size = page_size or span
            if span % self.page_size != 0:
                raise ValueError(
                    f"page_size={self.page_size} must divide the cache "
                    f"span {span}"
                )
            self.pages_per_slot = span // self.page_size
            self.num_pages = (max_slots * self.pages_per_slot
                              if num_pages is None else num_pages)
            if self.num_pages < self.pages_per_slot:
                raise ValueError(
                    f"num_pages={self.num_pages} < pages_per_slot="
                    f"{self.pages_per_slot}: nothing could ever be admitted"
                )
        if prefix_cache:
            if prefill_chunk is None:
                raise ValueError(
                    "prefix_cache needs prefill_chunk: solo prefill's "
                    "activation-scale groups cover the whole prompt, so "
                    "its pages are never content-shareable"
                )
        self.prefix_cache = prefix_cache
        if prequantize and policy.enabled and not policy.weights_prequantized:
            from repro.serve import quantized_weights as qw

            params = qw.quantize_for_serving(cfg, policy, params)
            policy = dataclasses.replace(policy, weights_prequantized=True)
        # Batch-invariant decode: per-slot activation scale groups, so a
        # row's quantization never depends on its pool neighbours.  At
        # batch 1 (solo prefill, solo decode) this is bit-identical to the
        # per-tensor reduction, so it changes nothing for lone requests.
        policy = dataclasses.replace(policy, per_sample_act_scales=True)
        if plan is not None and getattr(plan, "pool_slots", None) != max_slots:
            raise ValueError(
                "PoolEngine plans must be built with "
                "planner.plan_for(..., pool_slots=max_slots) so the cache "
                f"specs cover the lifted per-slot pos/len leaves; got "
                f"pool_slots={getattr(plan, 'pool_slots', None)!r}, "
                f"max_slots={max_slots}"
            )
        if plan is not None and self.paged:
            plan_page = getattr(plan, "page_size", None)
            plan_np = getattr(plan, "num_pages", None)
            if plan_page is not None and (
                plan_page != self.page_size or plan_np != self.num_pages
            ):
                raise ValueError(
                    "PoolEngine plan was built for page geometry "
                    f"(page_size={plan_page}, num_pages={plan_np}) but the "
                    f"engine uses (page_size={self.page_size}, "
                    f"num_pages={self.num_pages}); rebuild the plan with "
                    "planner.plan_for(..., page_size=..., num_pages=...)"
                )
            plan_bits = getattr(plan, "kv_bits", None)
            eng_bits = kv_quant.bits if kv_quant is not None else None
            if plan_bits != eng_bits:
                raise ValueError(
                    f"PoolEngine plan was built for kv_bits={plan_bits} but "
                    f"the engine quantizes at kv_bits={eng_bits}; rebuild "
                    "the plan with planner.plan_for(..., kv_quant=...) — "
                    "quantized caches have different leaf shapes/dtypes"
                )
        self.cfg = cfg
        self.policy = policy
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.prefill_chunk = prefill_chunk
        self.spec = spec
        self.span = registry.pool_span(cfg, max_len)
        self.plan = plan
        # Decode fast path: with nobody PREFILLING, a chunked engine may
        # dispatch the cheaper plain decode step instead of the chunk step.
        # That never changes served tokens only where the two step bodies
        # are bit-equal on decode rows, which is pinned on the CPU
        # (tests/conformance).  On a TPU v5e they are not: there the
        # layer norm of one row reduces in another order than the same
        # row inside a chunk, and pool output stopped matching solo
        # output with the fast path in place (PERF.md).
        # Windowed archs keep the chunk step everywhere (its in-chunk
        # ring-wrap concat layout differs from decode's scatter).
        self._fast_decode = (cfg.window is None
                             and jax.default_backend() == "cpu")
        self._decode = make_decode_step(cfg, policy, plan=plan)
        self._chunk_step = (
            make_chunk_step(cfg, policy, plan=plan)
            if prefill_chunk is not None else None
        )
        self._spec_snap = self._spec_restore = self._draft = None
        if spec is not None:
            self._verify = make_verify_step(cfg, policy, plan=plan)
            if plan is None:
                self._build_spec_steps()
            # else: deferred to run()'s plan context (the builders'
            # build-time/call-time ambient-plan contract)
        self._encxkv = None  # built lazily inside run()'s plan context
        # batch-1 prefill-into-slot: plan-less jit (in-model activations
        # are pinned through the actshard context when a plan is active).
        # With a plan the step must be BUILT inside that context too (the
        # builders' build-time/call-time plan contract), so defer to the
        # first run(); the private closure is then reused across runs.
        self._prefill = make_prefill_step(cfg, policy) if plan is None else None
        self.last_stats: Optional[ServeStats] = None

    def _build_spec_steps(self):
        """Jitted spec-round helpers (snapshot / restore / low-bit draft).
        Built in __init__ for plan-less engines; plan-carrying engines
        defer to run()'s actshard context (the _shared_step build-time /
        call-time plan contract)."""
        spec = self.spec
        c = spec.max_draft + 1
        self._spec_snap = _shared_step(
            f"spec_snap{c}", self.cfg, self.policy, _spec_snap_fn(c)
        )
        self._spec_restore = _shared_step(
            "spec_restore", self.cfg, self.policy, _spec_restore_fn(), (0,)
        )
        if spec.needs_draft_pass:
            # same weights, 2-3 PoT bits, re-quantized at use; the
            # engine's prequantize/per-sample mutations already landed in
            # self.policy, and draft_policy clears weights_prequantized
            dpol = draft_policy(self.policy, spec.bits)
            self._draft = _shared_step(
                f"spec_draft{spec.max_draft}", self.cfg, dpol,
                _draft_fn(self.cfg, dpol, spec.max_draft), (2,),
            )

    # -- request admission -------------------------------------------------
    def _validate(self, requests: Sequence[Request]) -> None:
        seen = set()
        for r in requests:
            if r.uid in seen:
                raise ValueError(f"duplicate request uid {r.uid!r}")
            seen.add(r.uid)
            plen = int(jnp.asarray(r.tokens).shape[-1])
            if "patch_embeds" in r.extras:  # vlm prefix occupies positions
                plen += int(jnp.asarray(r.extras["patch_embeds"]).shape[1])
            need = plen + r.max_new_tokens
            # Windowed archs decode from a ring whose wrap is the model
            # semantics, and ssm/hybrid recurrent state is O(1) in
            # sequence length; everything else must fit its page budget
            # (unpaged: the contiguous row) or the ring wrap would
            # silently change the request's tokens.
            if self.cfg.family == "ssm" or self.cfg.window is not None:
                continue
            if self.paged:
                need_pages = -(-need // self.page_size)
                if need_pages > self.pages_per_slot:
                    raise ValueError(
                        f"request {r.uid!r}: prompt ({plen}) + "
                        f"max_new_tokens ({r.max_new_tokens}) = {need} "
                        f"tokens need {need_pages} pages of "
                        f"{self.page_size}, exceeding the per-slot budget "
                        f"of {self.pages_per_slot} pages "
                        f"(max_len={self.max_len})"
                    )
            elif need > self.max_len:
                raise ValueError(
                    f"request {r.uid!r}: prompt ({plen}) + max_new_tokens "
                    f"({r.max_new_tokens}) = {need} exceeds the pool's "
                    f"max_len={self.max_len}"
                )

    def _prefill_into(self, cache, slot: int, req: Request, pages=None):
        """Solo-prefill ``req`` (batch 1) and copy the result into ``slot``.
        Returns (new pool cache, first generated token).  ``pages`` routes
        the write through the slot's allocated pages on a paged pool."""
        mini = registry.init_cache(self.cfg, 1, self.max_len, self.cache_dtype)
        batch = {"tokens": jnp.asarray(req.tokens, jnp.int32)}
        batch.update({k: jnp.asarray(v) for k, v in req.extras.items()})
        logits, mini = self._prefill(self.params, batch, mini)
        tok = int(jnp.argmax(logits, axis=-1).astype(jnp.int32)[0])
        cache = slots_lib.write_slot(
            cache, mini, slot, pages=pages, kv_quant=self.kv_quant
        )
        return cache, tok

    def _chunkable(self, req: Request) -> bool:
        """Chunked admission for this request?  VLM patch prefixes are
        activations, not tokens — those requests solo-prefill even in a
        chunked engine (family-level support was checked at init)."""
        return (self.prefill_chunk is not None
                and "patch_embeds" not in req.extras)

    def _admit_chunked(self, cache, slot: int, req: Request, *,
                       reset: bool = True):
        """Chunked admission: rewind the slot's position bookkeeping (on
        engine-managed paged pools the allocator sync already did — and a
        blanket reset would clobber shared prefix pages, so ``reset=False``
        there); the prompt then streams into the live pool cache via the
        fused chunk steps.  encdec additionally runs the encoder side here
        (one fixed-shape pass) and writes the slot's cross-attention K/V."""
        if reset:
            cache = slots_lib.reset_slot(cache, slot)
        if self.cfg.family == "encdec":
            if self._encxkv is None:
                self._encxkv = _shared_step(
                    "encxkv", self.cfg, self.policy,
                    _encxkv_fn(self.cfg, self.policy),
                )
            cks, cvs = self._encxkv(
                self.params, jnp.asarray(req.extras["frames"])
            )
            cache = dict(cache)
            cache["ck"] = jax.lax.dynamic_update_slice(
                cache["ck"], cks.astype(cache["ck"].dtype), (0, slot, 0, 0, 0)
            )
            cache["cv"] = jax.lax.dynamic_update_slice(
                cache["cv"], cvs.astype(cache["cv"].dtype), (0, slot, 0, 0, 0)
            )
        return cache

    # -- paged admission ----------------------------------------------------
    def _request_tokens(self, req: Request) -> int:
        plen = int(jnp.asarray(req.tokens).shape[-1])
        if "patch_embeds" in req.extras:
            plen += int(jnp.asarray(req.extras["patch_embeds"]).shape[1])
        return plen

    def _admission_plan(self, alloc, req: Request):
        """Page plan for one request: worst-case token need (capped at the
        span — ring wraps revisit pages) + prefix-cache lookup for
        chunk-streamed prompts when enabled."""
        need = self._request_tokens(req) + req.max_new_tokens
        span = self.page_size * self.pages_per_slot
        prompt = None
        chunk = None
        if (self.prefix_cache and self._chunkable(req)
                and self.cfg.window is None):
            prompt = np.asarray(req.tokens, np.int32).reshape(-1)
            chunk = self.prefill_chunk
        return alloc.plan_admission(prompt, min(need, span), chunk)

    def _sync_admission(self, cache, slot: int, hold, aplan):
        """Mirror one allocator admission into the device cache: the
        slot's table row (drop-padded), fresh-page ``pos`` resets, COW
        content copies (with future positions clamped back to the -1
        sentinel, matching what a solo replay would hold at ``resume``),
        and ``len`` = the prompt position streaming resumes from."""
        drop = slots_lib.drop_id(self.num_pages)
        row = list(hold["table"])
        row += [drop] * (self.pages_per_slot - len(row))
        cache = dict(cache)
        cache["table"] = cache["table"].at[slot].set(
            jnp.asarray(row, jnp.int32)
        )
        if hold["new"]:
            idx = jnp.asarray(hold["new"], jnp.int32)
            cache["pos"] = cache["pos"].at[idx].set(-1)
        for src, dst in hold["copies"]:
            leaves = ("k", "v") + (("k_beta", "v_beta")
                                   if self.kv_quant is not None else ())
            for key in leaves:
                cache[key] = cache[key].at[:, dst].set(cache[key][:, src])
            sp = cache["pos"][src]
            cache["pos"] = cache["pos"].at[dst].set(
                jnp.where(sp < aplan.resume, sp, -1)
            )
        cache["len"] = cache["len"].at[slot].set(aplan.resume)
        return cache

    # -- main loop ---------------------------------------------------------
    def run(self, requests: Sequence[Request]) -> Dict:
        """Drive all ``requests`` to completion; returns {uid: np.ndarray of
        generated token ids}.  Host-side loop; the pooled step (plain
        decode, or the fused decode+prefill-chunk step under
        ``prefill_chunk``) is a single fixed-shape jitted dispatch per
        step.

        Admission is **double-buffered** against the in-flight step
        (docs/DESIGN_scaling.md): dispatch is async, the token vector's
        device->host copy is started immediately (:class:`_InflightTokens`)
        and, while both run, the host stamps the next step's arrivals and
        stages the next prefill-chunk row of every slot that stays
        PREFILLING — work that provably cannot depend on the in-flight
        tokens.  ``wait()`` is the one sync point per step; retirements,
        EOS cuts, and the admissions they unblock execute as the copy's
        continuation and patch the staged buffer (decode rows, fresh
        admissions) before the next dispatch.  The staged rows are
        byte-identical to the rows the synchronous loop would build, so
        overlap changes wall-clock only — never tokens or counters.

        Under a ``jax.profiler`` session the call leaves ``serve.*`` host
        spans in the trace (docs/DESIGN_serving.md, "Tracing a call"); with
        no session they cost nothing measurable."""
        with TraceAnnotation("serve.run", requests=len(requests)):
            return self._run(requests)

    def _run(self, requests: Sequence[Request]) -> Dict:
        self._validate(requests)
        sched = FIFOScheduler(self.max_slots)
        for r in requests:
            sched.submit(r)
        stats = ServeStats()
        if self.plan is not None:
            stats.data_shards = self.plan.fsdp_size()
            stats.model_shards = self.plan.model_size()
        alloc = None
        if self.paged:
            alloc = slots_lib.PageAllocator(
                self.num_pages, self.page_size, self.pages_per_slot,
                self.max_slots,
            )
            stats.page_size = self.page_size
            if self.kv_quant is not None:
                # wire format: nibble/byte codes + one int32 beta per token,
                # per layer per K/V leaf (core.compress.kv_page_wire_bytes)
                stats.kv_page_bytes = (
                    2 * self.cfg.n_layers * compress.kv_page_wire_bytes(
                        self.kv_quant, self.page_size, self.cfg.kv_heads,
                        self.cfg.head_dim,
                    )
                )
            else:
                dt = jnp.dtype(self.cache_dtype).itemsize
                stats.kv_page_bytes = (
                    2 * self.cfg.n_layers * self.page_size
                    * self.cfg.kv_heads * self.cfg.head_dim * dt
                )
        out: Dict = {r.uid: [] for r in requests}
        remaining: Dict[int, int] = {}  # slot -> tokens still to emit
        pending: Dict[int, np.ndarray] = {}  # slot -> unconsumed prompt
        prompts: Dict[int, np.ndarray] = {}  # slot -> full prompt (paged)
        histories: Dict[int, List[int]] = {}  # slot -> prompt+emitted (ngram)
        spec_dropped: Dict[int, set] = {}  # slot -> table cols at drop_id
        track_hist = isinstance(self.spec, spec_lib.NgramDrafter)
        arrival_pass: Dict = {}  # uid -> weight_passes when first admissible
        last_tok = np.zeros((self.max_slots,), np.int32)
        chunk = self.prefill_chunk
        # double-buffered admission: the (row, take, finishes) chunk rows
        # pre-staged for the NEXT step while the current one is in flight
        staged: Dict[int, tuple] = {}
        step = 0

        def stamp_arrivals(now=None):
            now = step if now is None else now
            for arr, uid in sched.pending_arrivals():
                if arr <= now and uid not in arrival_pass:
                    arrival_pass[uid] = stats.weight_passes

        holds: List = []  # reserve() results, FIFO with sched.admit's pairs

        def can_admit_cb(req):
            aplan = self._admission_plan(alloc, req)
            protect = set(aplan.shared) | {p for p, _ in aplan.cow}
            if not alloc.can_admit(alloc.fresh_needed(aplan), protect):
                stats.admission_deferrals += 1
                return False
            # commit now: the next head's check must see these pages gone
            holds.append((aplan, alloc.reserve(aplan)))
            return True

        def retire(slot):
            sched.retire(slot)
            if alloc is not None:
                alloc.release_slot(slot)
                dead_rows.append(slot)
            prompts.pop(slot, None)
            histories.pop(slot, None)
            spec_dropped.pop(slot, None)

        def first_token(slot, req, tok):
            out[req.uid].append(tok)
            if track_hist:
                histories[slot].append(tok)
            last_tok[slot] = tok
            stats.emitted_tokens += 1
            stats.ttft_passes[req.uid] = (
                stats.weight_passes - arrival_pass.get(req.uid,
                                                       stats.weight_passes)
            )
            remaining[slot] = req.max_new_tokens - 1
            if remaining[slot] <= 0 or tok == req.eos_id:
                retire(slot)

        ctx = (actshard.use_plan(self.plan) if self.plan is not None
               else contextlib.nullcontext())
        with ctx:
            if self._prefill is None:  # plan mode: build inside the context
                self._prefill = make_prefill_step(self.cfg, self.policy)
            if self.spec is not None and self._spec_snap is None:
                self._build_spec_steps()  # plan mode: build inside the ctx
            with TraceAnnotation("serve.init_cache"):
                cache = registry.init_pool_cache(
                    self.cfg, self.max_slots, self.max_len, self.cache_dtype,
                    **({"page_size": self.page_size,
                        "num_pages": self.num_pages,
                        "kv_quant": self.kv_quant}
                       if self.paged else {}),
                )
                if alloc is not None:
                    # engine-managed pool: the allocator owns every mapping,
                    # so void the identity table init — dead slots must
                    # scatter into nothing, not into pages the allocator
                    # will hand out
                    cache = dict(cache)
                    cache["table"] = jnp.full(
                        (self.max_slots, self.pages_per_slot),
                        slots_lib.drop_id(self.num_pages), jnp.int32,
                    )
            while not sched.all_done():
                with TraceAnnotation("serve.step", step=step):
                    stamp_arrivals()
                    dead_rows: List[int] = []
                    if alloc is not None:
                        alloc.tick(step)
                    for slot, req in sched.admit(
                        step, can_admit_cb if alloc is not None else None
                    ):
                        with TraceAnnotation("serve.admit", uid=req.uid,
                                             step=step):
                            stats.prompt_tokens += int(
                                jnp.asarray(req.tokens).shape[-1]
                            )
                            if track_hist:
                                histories[slot] = np.asarray(
                                    req.tokens, np.int64
                                ).reshape(-1).tolist()
                            aplan = None
                            if alloc is not None:
                                aplan, hold = holds.pop(0)
                                alloc.bind(slot, hold)
                                cache = self._sync_admission(
                                    cache, slot, hold, aplan
                                )
                                stats.prefix_hit_tokens += aplan.hit_tokens
                            if self._chunkable(req):
                                cache = self._admit_chunked(
                                    cache, slot, req, reset=alloc is None
                                )
                                if self.cfg.family == "encdec":
                                    # encoder-side pass
                                    stats.weight_passes += 1
                                sched.mark_prefilling(slot)
                                prompt = np.asarray(
                                    req.tokens, np.int32
                                ).reshape(-1)
                                prompts[slot] = prompt
                                resume = (aplan.resume if aplan is not None
                                          else 0)
                                pending[slot] = prompt[resume:]
                            else:
                                pages = (hold["table"] if alloc is not None
                                         else None)
                                if pages is not None:
                                    pages = pages + [
                                        slots_lib.drop_id(self.num_pages)
                                    ] * (self.pages_per_slot - len(pages))
                                cache, tok = self._prefill_into(
                                    cache, slot, req, pages=pages
                                )
                                stats.prefills += 1
                                stats.weight_passes += 1
                                first_token(slot, req, tok)
                    active = sched.active_slots()
                    prefilling = sched.prefilling_slots()
                    if not active and not prefilling:
                        # Fast-forward the clock to the next arrival instead of
                        # spinning empty decode steps.
                        if dead_rows:
                            cache = self._void_table_rows(cache, dead_rows)
                        nxt = sched.next_arrival()
                        if nxt is None:
                            break
                        step = max(step + 1, nxt)
                        continue
                    if spec_dropped:
                        # re-bind table entries lazily dropped by spec
                        # rollback (wholly-rejected pages) before anything
                        # writes through them again; numerically a no-op —
                        # their restored pos is the -1 sentinel, masked
                        # either way
                        with TraceAnnotation("serve.dispatch", step=step):
                            cache = dict(cache)
                            tbl = cache["table"]
                            for slot, cols in spec_dropped.items():
                                row = alloc.tables[slot]
                                for lp in sorted(cols):
                                    if lp < len(row):
                                        tbl = tbl.at[slot, lp].set(
                                            int(row[lp])
                                        )
                            cache["table"] = tbl
                            spec_dropped.clear()
                    if self.spec is not None and active and not prefilling:
                        # Speculative round: draft -> one verify pass ->
                        # accept.  Greedy argmax acceptance emits exactly the
                        # tokens sequential pooled decode would (verify_step
                        # is bit-identical to decode_step per position), so
                        # speculation only changes the weight-pass count.
                        spec = self.spec
                        c = spec.max_draft + 1
                        with TraceAnnotation("serve.dispatch", step=step):
                            lens = np.asarray(cache["len"])
                            snap = self._spec_snap(cache)
                            drafts: Dict[int, np.ndarray] = {}
                            if spec.needs_draft_pass:
                                dtoks, cache = self._draft(
                                    self.params, jnp.asarray(last_tok), cache
                                )
                                stats.draft_weight_passes += spec.max_draft
                                # erase the draft's K/V + pos pollution: the
                                # verify pass must see the pristine pre-round
                                # cache (ring wraps near the span end and
                                # windowed evictions would otherwise hide keys
                                # decode would attend to)
                                cache = self._spec_restore(
                                    cache, snap,
                                    jnp.zeros((self.max_slots,), jnp.int32),
                                )
                                dhost = np.asarray(dtoks)
                                for slot in active:
                                    drafts[slot] = dhost[slot]
                            else:
                                for slot in active:
                                    drafts[slot] = spec.propose(
                                        histories[slot], spec.max_draft
                                    )
                            tokens = np.zeros((self.max_slots, c), np.int32)
                            n_new = np.zeros((self.max_slots,), np.int32)
                            for slot in active:
                                cap = remaining[slot]
                                if self.cfg.window is None:
                                    # a verify row's valid positions may not
                                    # wrap the span ring (windowed archs wrap
                                    # by design)
                                    cap = min(cap, self.span - int(lens[slot]))
                                nd = max(0, min(len(drafts[slot]), cap - 1,
                                                c - 1))
                                tokens[slot, 0] = last_tok[slot]
                                if nd:
                                    tokens[slot, 1:1 + nd] = drafts[slot][:nd]
                                n_new[slot] = 1 + nd
                            verify = int(n_new.max()) > 1
                            if verify:
                                vtok, _, cache = self._verify(
                                    self.params, jnp.asarray(tokens),
                                    jnp.asarray(n_new), cache,
                                )
                                stats.decode_steps += 1
                                stats.weight_passes += 1
                                stats.occupancy_sum += (
                                    len(active) / self.max_slots
                                )
                                if alloc is not None:
                                    stats.pages_in_use_sum += (
                                        alloc.pages_in_use()
                                    )
                        if verify:
                            with TraceAnnotation("serve.wait"):
                                vhost = np.asarray(vtok)
                            with TraceAnnotation("serve.retire"):
                                keep = np.zeros((self.max_slots,), np.int32)
                                for slot in active:
                                    req = sched.active_request(slot)
                                    nd = int(n_new[slot]) - 1
                                    a = spec_lib.greedy_accept(
                                        tokens[slot, 1:1 + nd],
                                        vhost[slot, :nd],
                                    )
                                    # accepted drafts + the verifier's bonus
                                    # token, then exactly sequential decode's
                                    # stop rules: cut at the first EOS, cap at
                                    # the budget
                                    emit = [int(t)
                                            for t in tokens[slot, 1:1 + a]]
                                    emit.append(int(vhost[slot, a]))
                                    for j, t in enumerate(emit):
                                        if t == req.eos_id:
                                            emit = emit[:j + 1]
                                            break
                                    emit = emit[:remaining[slot]]
                                    keep[slot] = len(emit)
                                    stats.accepted_tokens += len(emit) - 1
                                    out[req.uid].extend(emit)
                                    if track_hist:
                                        histories[slot].extend(emit)
                                    stats.emitted_tokens += len(emit)
                                    last_tok[slot] = emit[-1]
                                    remaining[slot] -= len(emit)
                                    if (remaining[slot] <= 0
                                            or emit[-1] == req.eos_id):
                                        retire(slot)
                                # roll back the rejected tail: keep[slot]
                                # kept positions cache exactly the consumed
                                # context (the last emitted token is never
                                # cached, as in decode)
                                cache = self._spec_restore(
                                    cache, snap, jnp.asarray(keep)
                                )
                                if (alloc is not None
                                        and self.cfg.window is None):
                                    # wholly-rejected pages: table entries ->
                                    # drop_id (pos already back at the -1
                                    # sentinel); re-bound from alloc.tables
                                    # before next write
                                    drop = slots_lib.drop_id(self.num_pages)
                                    didx = []
                                    for slot in active:
                                        if slot in dead_rows:
                                            continue
                                        p0 = int(lens[slot])
                                        lo = -(-(p0 + int(keep[slot]))
                                               // self.page_size)
                                        hi = (p0 + int(n_new[slot]) - 1) \
                                            // self.page_size
                                        nmap = len(alloc.tables[slot])
                                        cols = [lp for lp in range(lo, hi + 1)
                                                if lp < nmap]
                                        if cols:
                                            spec_dropped.setdefault(
                                                slot, set()
                                            ).update(cols)
                                            didx += [(slot, lp) for lp in cols]
                                    if didx:
                                        cache = dict(cache)
                                        rr = jnp.asarray([s for s, _ in didx],
                                                         jnp.int32)
                                        cc = jnp.asarray([l for _, l in didx],
                                                         jnp.int32)
                                        cache["table"] = (
                                            cache["table"].at[rr, cc].set(drop)
                                        )
                                if dead_rows:
                                    cache = self._void_table_rows(
                                        cache, dead_rows
                                    )
                            with TraceAnnotation("serve.check"):
                                sched.check_conservation()
                                if alloc is not None:
                                    alloc.check_conservation()
                            step += 1
                            continue
                        # no slot had a draft: the cache is pristine (any
                        # self-draft pollution was restored above), so fall
                        # through to the plain fixed-shape dispatch
                    finishing = []
                    with TraceAnnotation("serve.dispatch", step=step):
                        if chunk is None or (self._fast_decode
                                             and not prefilling):
                            ntok, _, cache = self._decode(
                                self.params, jnp.asarray(last_tok), cache
                            )
                        else:
                            tokens = np.zeros((self.max_slots, chunk),
                                              np.int32)
                            n_new = np.zeros((self.max_slots,), np.int32)
                            for slot in active:
                                tokens[slot, 0] = last_tok[slot]
                                n_new[slot] = 1
                            for slot in prefilling:
                                if slot in staged:
                                    # double-buffered: this row was staged
                                    # while the previous step was in flight
                                    row, take, fin = staged.pop(slot)
                                    tokens[slot] = row
                                    n_new[slot] = take
                                    if fin:
                                        finishing.append(slot)
                                    continue
                                buf = pending[slot]
                                take = min(chunk, len(buf))
                                tokens[slot, :take] = buf[:take]
                                n_new[slot] = take
                                pending[slot] = buf[take:]
                                if take == len(buf):
                                    finishing.append(slot)
                            ntok, _, cache = self._chunk_step(
                                self.params, jnp.asarray(tokens),
                                jnp.asarray(n_new), cache,
                            )
                        flight = _InflightTokens(ntok)
                        stats.decode_steps += 1
                        stats.weight_passes += 1
                        stats.occupancy_sum += (
                            (len(active) + len(prefilling)) / self.max_slots
                        )
                        if alloc is not None:
                            stats.pages_in_use_sum += alloc.pages_in_use()
                    # -- overlap window: the jitted step (and the async host
                    # copy of its token vector) is in flight.  Host-side
                    # scheduling overlaps the step: next-step arrivals stamp
                    # against the already-bumped pass clock (identical to
                    # stamping at the top of the next iteration — no weight
                    # pass can land in between), and every slot that STAYS
                    # prefilling gets its next chunk row staged now.  Neither
                    # depends on this step's tokens: finishing slots are known
                    # at dispatch (their whole prompt is consumed) and
                    # prefilling slots are never retired, so the staging
                    # buffer can't be invalidated by the retirements the
                    # arriving tokens trigger.
                    with TraceAnnotation("serve.overlap"):
                        stamp_arrivals(step + 1)
                        if chunk is not None:
                            for slot in prefilling:
                                if slot in finishing:
                                    # the next row needs this step's token
                                    continue
                                buf = pending[slot]
                                take = min(chunk, len(buf))
                                row = np.zeros((chunk,), np.int32)
                                row[:take] = buf[:take]
                                staged[slot] = (row, take, take == len(buf))
                                pending[slot] = buf[take:]
                    # -- synchronize: tokens arrive; retirement and the
                    # admissions it unblocks run as the copy's continuation --
                    with TraceAnnotation("serve.wait"):
                        ntok_host = flight.wait()
                    with TraceAnnotation("serve.retire"):
                        for slot in finishing:
                            sched.finish_prefill(slot)
                            stats.prefills += 1
                            if (alloc is not None and self.prefix_cache
                                    and self.cfg.window is None):
                                # publish the finished prompt's full pages for
                                # reuse BEFORE first_token may retire the slot
                                alloc.register_prefix(slot, prompts[slot],
                                                      chunk)
                            first_token(slot, sched.active_request(slot),
                                        int(ntok_host[slot]))
                        for slot in active:
                            req = sched.active_request(slot)
                            tok = int(ntok_host[slot])
                            out[req.uid].append(tok)
                            if track_hist:
                                histories[slot].append(tok)
                            last_tok[slot] = tok
                            stats.emitted_tokens += 1
                            remaining[slot] -= 1
                            if remaining[slot] <= 0 or tok == req.eos_id:
                                retire(slot)
                        if dead_rows:
                            # retired slots keep riding the fixed-shape
                            # dispatch; void their table rows so their
                            # scatters drop instead of landing in pages the
                            # allocator may reassign
                            cache = self._void_table_rows(cache, dead_rows)
                    with TraceAnnotation("serve.check"):
                        sched.check_conservation()
                        if alloc is not None:
                            alloc.check_conservation()
                    step += 1
        if alloc is not None:
            stats.cow_copies = alloc.cow_copies
            stats.evictions = alloc.evictions
            alloc.check_conservation()
        self.last_stats = stats
        return {uid: np.asarray(toks, np.int32) for uid, toks in out.items()}

    def _void_table_rows(self, cache, dead_slots):
        drop = slots_lib.drop_id(self.num_pages)
        cache = dict(cache)
        rows = jnp.asarray(sorted(dead_slots), jnp.int32)
        cache["table"] = cache["table"].at[rows].set(drop)
        return cache


# ---------------------------------------------------------------------------
# generate: thin wrappers
# ---------------------------------------------------------------------------


def generate(
    cfg: ModelConfig,
    policy: QuantPolicy,
    params,
    batch,
    *,
    max_new_tokens: int,
    max_len: int,
    cache_dtype=jnp.bfloat16,
    plan: Optional[ShardingPlan] = None,
    prequantize: bool = False,
):
    """Greedy generation driver — a thin wrapper over a :class:`PoolEngine`
    with one slot per request (all arrivals at step 0).

    Because pool decode is per-request bit-identical to solo decode, each
    row of the result no longer depends on which other rows share the
    batch (unlike :func:`lockstep_generate`, the pre-pool behaviour).
    Returns (B, max_new_tokens) int32.

    Families without per-slot decode, and legacy plans built without
    ``pool_slots``, fall back to :func:`lockstep_generate` — the exact
    pre-pool behaviour those callers always had.  (Since PR 5 every
    decode family pools — hybrid included — so the family fallback only
    guards hypothetical future families.)

    Each call with a pool plan builds (and re-jits) a fresh engine; a
    sharded caller generating repeatedly should construct one
    :class:`PoolEngine` and ``run`` traces through it instead.
    """
    toks = batch["tokens"]
    b = toks.shape[0]
    legacy_plan = plan is not None and getattr(plan, "pool_slots", None) != b
    if cfg.family not in registry.POOLED_FAMILIES or legacy_plan:
        return lockstep_generate(
            cfg, policy, params, batch, max_new_tokens=max_new_tokens,
            max_len=max_len, cache_dtype=cache_dtype, plan=plan,
        )
    reqs: List[Request] = []
    for i in range(b):
        extras = {
            k: batch[k][i : i + 1]
            for k in ("frames", "patch_embeds")
            if k in batch
        }
        reqs.append(
            Request(
                uid=i, tokens=toks[i : i + 1],
                max_new_tokens=max_new_tokens, extras=extras,
            )
        )
    eng = PoolEngine(
        cfg, policy, params, max_slots=b, max_len=max_len,
        cache_dtype=cache_dtype, prequantize=prequantize, plan=plan,
    )
    out = eng.run(reqs)
    return jnp.stack([jnp.asarray(out[i], jnp.int32) for i in range(b)], axis=0)


def lockstep_generate(
    cfg: ModelConfig,
    policy: QuantPolicy,
    params,
    batch,
    *,
    max_new_tokens: int,
    max_len: int,
    cache_dtype=jnp.bfloat16,
    plan: Optional[ShardingPlan] = None,
):
    """Pre-pool serving loop, kept as the servebench baseline: every request
    enters at prefill time (one batched prefill, per-tensor activation
    scales) and the whole batch decodes in lockstep to ``max_new_tokens``
    — dead slots stream every weight for nothing.
    """
    b = batch["tokens"].shape[0]
    ctx = actshard.use_plan(plan) if plan is not None else contextlib.nullcontext()
    with ctx:
        # plan-less jit on purpose: in-model activations are pinned through
        # the actshard context, matching the historical decode loop.  Built
        # inside the context, so a plan-carrying call gets a private
        # plan-baked closure while plan-less calls share the step cache.
        step = make_decode_step(cfg, policy)
        prefill = make_prefill_step(cfg, policy)
        cache = registry.init_cache(cfg, b, max_len, cache_dtype)
        logits, cache = prefill(params, batch, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [tok]
        for _ in range(max_new_tokens - 1):
            tok, _, cache = step(params, tok, cache)
            out.append(tok)
    return jnp.stack(out, axis=1)
