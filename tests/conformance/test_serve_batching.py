"""Serve conformance: continuous batching never changes a request's tokens.

The headline invariant of serve/engine.py (docs/DESIGN_serving.md): for
any arrival order and slot count, each request decoded through the
slot-pooled continuous-batching engine yields a token sequence
bit-identical to running it alone.

The reference deliberately avoids the pool code: it drives the *lockstep*
cache layout (scalar ``len``, shared ``pos`` — the other branch of
``decode_step``) through raw ``registry.prefill``/``registry.decode_step``
at batch 1 with quantize-at-use weights and per-tensor activation scales.
The pool engine instead uses per-slot offsets, per-sample scales and
PoT-prequantized weights (its default) — so a match certifies, in one
assert: per-slot == scalar positions, per-sample == per-tensor scales at
batch 1, and ``quantize_for_serving`` idempotence under the pool path.

Matrix: >=3 arrival schedules x >=2 slot counts x {transformer, encdec,
hybrid} x {jnp, pallas} kernel paths, plus MoE (per-slot expert
dispatch), ssm, EOS-prefix, and chunked piggybacked prefill.

Chunked prefill (``PoolEngine(prefill_chunk=C)``) changes the
computation *recipe* — activation-scale groups cover a chunk, not the
whole prompt — so its reference is the same recipe driven solo: raw
``registry.chunk_step`` calls at batch 1 (per-tensor scales,
quantize-at-use weights), mirroring the engine's chunking of the prompt
— then, for window-free archs, plain ``registry.decode_step`` calls,
mirroring the engine's decode fast-path (the two step bodies are
bit-equal on decode rows; pinned below per backend).  The invariant
under test is unchanged: batching never changes a request's tokens.

Since PR 6 the pool cache is block-table **paged** (serve/slots.py), so
the matrix gains a page-size axis: page = span (the legacy-equivalent
geometry) and small pages must serve bit-identical tokens — attention
gathers K/V through the page table in logical order, so the physical
layout can never reach the numbers.  Prefix-cache reuse
(``prefix_cache=True``) maps shared prompt pages instead of recomputing
them; because the mapped bytes are exactly what replay would have
written, that too is pinned bit-identical.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core.policy import PAPER_FAITHFUL
from repro.models import registry, spec as pspec
from repro.serve import PoolEngine, Request, generate

MAX_LEN = 24
PALLAS = dataclasses.replace(PAPER_FAITHFUL, use_pallas=True)

#: arrival schedules (engine steps), keyed for test ids.  >=3 per ISSUE 4.
SCHEDULES = {
    "all_at_once": lambda n: [0] * n,
    "staggered": lambda n: [2 * i for i in range(n)],
    "burst_then_tail": lambda n: [0] * (n // 2)
    + [5 + 3 * i for i in range(n - n // 2)],
}
SLOT_COUNTS = (2, 3)


def _params_for(arch):
    """``arch`` may carry a ``@w<N>`` suffix for a sliding-window variant
    of the smoke config — no stock chunked-family arch ships a window
    (mistral-nemo only gains one in its long_500k shape cell), and the
    ring/window code paths need real wraps to bite."""
    base, _, win = arch.partition("@w")
    cfg = C.smoke_config(base)
    if win:
        cfg = dataclasses.replace(cfg, window=int(win))
    params = pspec.materialize(registry.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n, *, seed=0):
    """n requests with heterogeneous prompt lengths and output budgets."""
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
                uid=i, tokens=toks,
                max_new_tokens=int(rng.integers(2, 6)), extras=extras,
            )
        )
    return reqs


def _solo_reference(cfg, policy, params, req):
    """Batch-1 lockstep loop: raw registry calls, scalar-len cache,
    quantize-at-use weights, per-tensor scales."""
    cache = registry.init_cache(cfg, 1, MAX_LEN)
    batch = {"tokens": jnp.asarray(req.tokens, jnp.int32)}
    batch.update({k: jnp.asarray(v) for k, v in req.extras.items()})
    logits, cache = registry.prefill(cfg, policy, params, batch, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [int(tok[0])]
    for _ in range(req.max_new_tokens - 1):
        logits, cache = registry.decode_step(cfg, policy, params, tok, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(int(tok[0]))
    return np.asarray(out, np.int32)


# memoized per (arch, pallas): model + solo refs + one engine per slot
# count, so the jitted decode steps are reused across the schedule matrix.
_CACHE = {}


def _case(arch, *, use_pallas=False, n=5):
    key = (arch, use_pallas, n)
    if key not in _CACHE:
        cfg, params = _params_for(arch)
        policy = PALLAS if use_pallas else PAPER_FAITHFUL
        reqs = _requests(cfg, n, seed=len(arch))
        solo = {r.uid: _solo_reference(cfg, policy, params, r) for r in reqs}
        engines = {}
        _CACHE[key] = (cfg, policy, params, reqs, solo, engines)
    return _CACHE[key]


def _run_pool(case, slots, schedule, page=None):
    cfg, policy, params, reqs, solo, engines = case
    key = (slots, page)
    if key not in engines:
        engines[key] = PoolEngine(
            cfg, policy, params, max_slots=slots, max_len=MAX_LEN,
            **({"page_size": page} if page is not None else {}),
        )
    arrivals = SCHEDULES[schedule](len(reqs))
    scheduled = [dataclasses.replace(r, arrival=a) for r, a in zip(reqs, arrivals)]
    return engines[key].run(scheduled), solo


#: page-size axis (ISSUE 6): None lets the engine default to page = span
#: (the legacy-equivalent geometry); 6 packs each 24-token row into 4
#: pages.  Non-paged families (ssm/hybrid recurrent state) skip the
#: small-page point — they have no KV pages to split.
PAGES = (None, 6)


def _skip_unpaged(cfg, page):
    if page is not None and cfg.family not in registry.PAGED_FAMILIES:
        pytest.skip(f"family {cfg.family!r} has no paged KV cache")


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("slots", SLOT_COUNTS)
@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize(
    "arch", ["llama3-8b", "whisper-large-v3", "recurrentgemma-2b"]
)
def test_pool_bit_identical_to_solo(arch, schedule, slots, page):
    """recurrentgemma (hybrid) joined the matrix in PR 5: its attention
    layers now carry per-slot positions like transformer/encdec, and the
    RG-LRU conv/lru states are per-row by construction.  PR 6 adds the
    page axis: the same solo reference must fall out of every page
    geometry."""
    case = _case(arch)
    _skip_unpaged(case[0], page)
    out, solo = _run_pool(case, slots, schedule, page=page)
    for uid, ref in solo.items():
        np.testing.assert_array_equal(
            out[uid], ref,
            err_msg=f"{arch} uid={uid} schedule={schedule} slots={slots} "
                    f"page={page}",
        )


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("schedule", ["all_at_once", "staggered"])
@pytest.mark.parametrize("arch", ["llama3-8b", "recurrentgemma-2b"])
def test_pool_bit_identical_pallas(arch, schedule, page):
    """Same invariant through the fused Pallas kernels (interpret mode on
    CPU) — the tiling-invariant, row-independent reduction is exactly what
    makes the guarantee hold on the kernel path too, for every page
    geometry."""
    case = _case(arch, use_pallas=True, n=3)
    _skip_unpaged(case[0], page)
    out, solo = _run_pool(case, 2, schedule, page=page)
    for uid, ref in solo.items():
        np.testing.assert_array_equal(
            out[uid], ref, err_msg=f"uid={uid} page={page}"
        )


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("schedule", ["all_at_once", "staggered"])
def test_pool_bit_identical_moe(schedule, use_pallas):
    """MoE joined the bit-exact matrix in PR 5: expert-capacity dispatch
    and expert activation-scale groups both run per slot
    (``transformer._moe_apply(per_slot=True)``), so neither live nor
    retired neighbours can perturb a request's routing or quantization."""
    n = 2 if use_pallas else 3
    out, solo = _run_pool(
        _case("llama4-scout-17b-a16e", use_pallas=use_pallas, n=n),
        2, schedule,
    )
    for uid, ref in solo.items():
        np.testing.assert_array_equal(out[uid], ref, err_msg=f"uid={uid}")


def test_pool_bit_identical_ssm():
    """Recurrent-state families pool for free (no positions to offset):
    mamba2 rides the same engine, same guarantee."""
    out, solo = _run_pool(_case("mamba2-2.7b", n=4), 2, "staggered")
    for uid, ref in solo.items():
        np.testing.assert_array_equal(out[uid], ref, err_msg=f"uid={uid}")


def test_generate_rows_are_batch_independent():
    """generate() (one slot per request) emits, per row, exactly the solo
    sequence — batch composition can no longer change anyone's tokens."""
    cfg, policy, params, reqs, solo, _ = _case("llama3-8b")
    # pad all prompts to one length so they form a rectangular batch
    plen = 6
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab, (3, plen)).astype(np.int32)
    got = generate(
        cfg, policy, params, {"tokens": jnp.asarray(toks)},
        max_new_tokens=4, max_len=MAX_LEN,
    )
    for i in range(3):
        req = Request(uid=i, tokens=toks[i : i + 1], max_new_tokens=4)
        np.testing.assert_array_equal(
            np.asarray(got[i]), _solo_reference(cfg, policy, params, req)
        )


def test_moe_dead_and_live_slots_bit_identical():
    """Upgraded from PR 4's 'retired slots are inert': per-slot expert
    dispatch makes MoE fully batch-invariant, so a live request's tokens
    equal its raw solo reference whether it runs alone, next to a live
    neighbour, or next to a retired slot rotting garbage into its row."""
    cfg, params = _params_for("llama4-scout-17b-a16e")
    assert cfg.moe is not None
    rng = np.random.default_rng(11)
    live = Request(
        uid="live", tokens=rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
        max_new_tokens=5,
    )
    brief = Request(
        uid="brief", tokens=rng.integers(0, cfg.vocab, (1, 4)).astype(np.int32),
        max_new_tokens=1,
    )
    solo_live = _solo_reference(cfg, PAPER_FAITHFUL, params, live)
    eng = PoolEngine(cfg, PAPER_FAITHFUL, params, max_slots=2, max_len=MAX_LEN)
    alone = eng.run([live])["live"]
    with_dead_neighbour = eng.run([brief, live])["live"]
    np.testing.assert_array_equal(alone, solo_live)
    np.testing.assert_array_equal(with_dead_neighbour, solo_live)


# ---------------------------------------------------------------------------
# Chunked piggybacked prefill
# ---------------------------------------------------------------------------

CHUNK = 4

#: jitted raw chunk-step per (cfg, policy) for the solo references
_CHUNK_FNS = {}


def _chunk_fn(cfg, policy):
    key = (cfg, policy)
    if key not in _CHUNK_FNS:
        _CHUNK_FNS[key] = jax.jit(
            lambda p, t, n, c: registry.chunk_step(cfg, policy, p, t, n, c)
        )
    return _CHUNK_FNS[key]


_DEC_FNS = {}


def _dec_fn(cfg, policy):
    key = (cfg, policy)
    if key not in _DEC_FNS:
        _DEC_FNS[key] = jax.jit(
            lambda p, t, c: registry.decode_step(cfg, policy, p, t, c)
        )
    return _DEC_FNS[key]


def _solo_chunked_reference(cfg, policy, params, req, chunk=CHUNK):
    """Batch-1 chunked loop: raw ``registry.chunk_step`` calls on a
    one-slot pool cache with quantize-at-use weights and per-tensor
    activation scales — the chunk-recipe analogue of ``_solo_reference``
    (the engine instead runs prequantized weights + per-sample scales
    inside a shared pool, so a match certifies the same three properties).

    After the prompt, window-free archs switch to plain
    ``registry.decode_step`` — mirroring the engine's decode fast-path
    (with nobody PREFILLING it dispatches plain decode; the two step
    bodies are bit-equal on decode rows, pinned by
    ``test_decode_fast_path_matches_chunk_step``).  Windowed archs stay
    on chunk-shaped decode, exactly like the engine.
    """
    step = _chunk_fn(cfg, policy)
    cache = registry.init_pool_cache(cfg, 1, MAX_LEN)
    if cfg.family == "encdec":
        cks, cvs = registry.encode_cross_kv(
            cfg, policy, params, jnp.asarray(req.extras["frames"])
        )
        cache = dict(cache)
        cache["ck"] = cks.astype(cache["ck"].dtype)
        cache["cv"] = cvs.astype(cache["cv"].dtype)
    buf = np.asarray(req.tokens, np.int32).reshape(-1)
    logits = None
    while len(buf):
        take = int(min(chunk, len(buf)))
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :take] = buf[:take]
        buf = buf[take:]
        logits, cache = step(
            params, jnp.asarray(tokens), jnp.asarray([take], jnp.int32), cache
        )
    tok = int(jnp.argmax(logits, -1)[0])
    out = [tok]
    one = jnp.asarray([1], jnp.int32)
    dec_step = _dec_fn(cfg, policy) if cfg.window is None else None
    for _ in range(req.max_new_tokens - 1):
        if dec_step is not None:  # engine decode fast-path
            logits, cache = dec_step(
                params, jnp.asarray([tok], jnp.int32), cache
            )
        else:
            dec = np.zeros((1, chunk), np.int32)
            dec[0, 0] = tok
            logits, cache = step(params, jnp.asarray(dec), one, cache)
        tok = int(jnp.argmax(logits, -1)[0])
        out.append(tok)
    return np.asarray(out, np.int32)


# memoized solo-chunked refs + engines, like _CACHE above
_CHUNK_CACHE = {}


def _run_chunked(arch, schedule, *, use_pallas=False, n=4, slots=2,
                 chunk=CHUNK, page=None, backend=None):
    key = (arch, use_pallas, n, chunk)
    if key not in _CHUNK_CACHE:
        cfg, params = _params_for(arch)
        policy = PALLAS if use_pallas else PAPER_FAITHFUL
        reqs = _requests(cfg, n, seed=31 + len(arch))
        solo = {
            r.uid: _solo_chunked_reference(cfg, policy, params, r, chunk)
            for r in reqs
        }
        _CHUNK_CACHE[key] = (cfg, policy, params, reqs, solo, {})
    cfg, policy, params, reqs, solo, engines = _CHUNK_CACHE[key]
    ekey = (slots, page, backend)
    if ekey not in engines:
        with pytest.MonkeyPatch.context() as mp:
            if backend is not None:  # the engine picks its decode path by it
                mp.setattr(jax, "default_backend", lambda: backend)
            engines[ekey] = PoolEngine(
                cfg, policy, params, max_slots=slots, max_len=MAX_LEN,
                prefill_chunk=chunk,
                **({"page_size": page} if page is not None else {}),
            )
    arrivals = SCHEDULES[schedule](len(reqs))
    scheduled = [
        dataclasses.replace(r, arrival=a) for r, a in zip(reqs, arrivals)
    ]
    out = engines[ekey].run(scheduled)
    for r in reqs:
        np.testing.assert_array_equal(
            out[r.uid], solo[r.uid],
            err_msg=f"{arch} uid={r.uid} schedule={schedule} chunk={chunk} "
                    f"page={page}",
        )
    return engines[ekey]


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("schedule", ["staggered", "burst_then_tail"])
def test_chunked_prefill_bit_identical(schedule, page):
    """Mid-flight chunked-prefill admission: requests arriving while
    neighbours decode stream their prompts through the fused chunk step
    C tokens per pooled dispatch; every request's tokens bit-equal the
    same chunked recipe run alone — at every page geometry."""
    _run_chunked("llama3-8b", schedule, page=page)


@pytest.mark.parametrize("schedule", ["staggered", "burst_then_tail"])
def test_chunked_prefill_bit_identical_off_cpu(schedule):
    """Off the CPU a chunked engine takes no decode fast path: every
    pooled step is the chunk step, also when nobody is PREFILLING.  The
    tokens still equal the solo chunked recipe (on the CPU the two step
    bodies are bit-equal, so one reference covers both paths)."""
    eng = _run_chunked("llama3-8b", schedule, page=4, backend="tpu")
    assert not eng._fast_decode


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("schedule", ["staggered", "burst_then_tail"])
def test_chunked_prefill_bit_identical_pallas(schedule, page):
    """Chunked admission through the fused Pallas kernels (interpret
    mode): padded chunk rows are separate matmul rows of the
    tiling-invariant reduction, so the guarantee carries over."""
    _run_chunked("llama3-8b", schedule, use_pallas=True, n=3, page=page)


@pytest.mark.parametrize("page", [None, 4])
def test_chunked_prefill_encdec(page):
    """encdec chunked admission = one encoder-side pass (cross K/V into
    the slot, which stays slot-rowed — only decoder-side K/V pages) +
    piggybacked decoder-prompt chunks."""
    _run_chunked("whisper-large-v3", "staggered", n=3, page=page)


@pytest.mark.parametrize("page", [None, 4])
def test_chunked_prefill_ring_window(page):
    """Windowed arch (@w8 smoke variant): a chunk's ring writes can wrap;
    attending over [old cache ∪ fresh chunk] keeps earlier in-chunk
    queries' windows intact as positions run past the window bound.  The
    ring span (= window 8) splits into two 4-token pages — ring offsets,
    not global positions, pick the page."""
    _run_chunked("mistral-nemo-12b@w8", "staggered", n=3, page=page)


@pytest.mark.parametrize("page", [None, 4])
def test_pool_bit_identical_ring_window_paged(page):
    """Windowed decoder WITHOUT chunking: the engine always dispatches
    plain decode, so this pins the paged ring in ``decode_step`` itself
    (slot = pos %% span, then page = slot // page_size)."""
    out, solo = _run_pool(
        _case("mistral-nemo-12b@w8"), 2, "staggered", page=page
    )
    for uid, ref in solo.items():
        np.testing.assert_array_equal(
            out[uid], ref, err_msg=f"uid={uid} page={page}"
        )


def test_chunk_step_pad_rows_ignore_stale_cache():
    """Slot reuse: a pad query's mask is all-False, so its softmax
    degenerates to a uniform average over EVERY key — including whatever
    junk the slot's previous occupant left in K/V (``reset_slot`` only
    rewinds ``pos``/``len``).  chunk_step zeroes pad attention rows, so
    logits at the valid positions must be bitwise identical between a
    fresh-zero cache and one whose K/V rows hold huge stale values."""
    from repro.serve import slots as slots_lib

    cfg, params = _params_for("llama3-8b")
    tokens = np.zeros((1, CHUNK), np.int32)
    tokens[0, :3] = [5, 7, 9]
    n_new = jnp.asarray([3], jnp.int32)
    fresh = registry.init_pool_cache(cfg, 1, MAX_LEN)
    junk = jax.tree_util.tree_map(
        lambda x: (jnp.full_like(x, 1e4)
                   if jnp.issubdtype(x.dtype, jnp.floating) else x),
        fresh,
    )
    junk = slots_lib.reset_slot(junk, 0)
    lg_fresh, c_fresh = registry.chunk_step(
        cfg, PAPER_FAITHFUL, params, jnp.asarray(tokens), n_new, fresh
    )
    lg_junk, c_junk = registry.chunk_step(
        cfg, PAPER_FAITHFUL, params, jnp.asarray(tokens), n_new, junk
    )
    np.testing.assert_array_equal(np.asarray(lg_fresh), np.asarray(lg_junk))
    # and one decode-shaped step (1 valid token + C-1 pads) on top
    dec = np.zeros((1, CHUNK), np.int32)
    dec[0, 0] = int(jnp.argmax(lg_fresh, -1)[0])
    one = jnp.asarray([1], jnp.int32)
    lg2_fresh, _ = registry.chunk_step(
        cfg, PAPER_FAITHFUL, params, jnp.asarray(dec), one, c_fresh
    )
    lg2_junk, _ = registry.chunk_step(
        cfg, PAPER_FAITHFUL, params, jnp.asarray(dec), one, c_junk
    )
    np.testing.assert_array_equal(np.asarray(lg2_fresh), np.asarray(lg2_junk))


def test_chunked_prefill_single_chunk_covers_prompt():
    """chunk >= prompt length: admission costs zero extra weight passes
    (the whole prompt rides one fused step) and TTFT on the weight-pass
    clock is 1 for an uncontended slot."""
    eng = _run_chunked("llama3-8b", "staggered", n=3, chunk=9)
    st = eng.last_stats
    assert st.weight_passes == st.decode_steps  # no solo admission passes
    assert min(st.ttft_passes.values()) == 1


# ---------------------------------------------------------------------------
# Decode fast-path (ISSUE 6 satellite): plain decode_step vs chunk step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_decode_fast_path_matches_chunk_step(use_pallas):
    """The engine's decode fast-path dispatches plain ``decode_step``
    whenever no slot is PREFILLING.  That is only sound because the fused
    chunk step at ``n_new=1`` and the plain decode step are **bit-equal
    on decode rows** — same scatter-then-attend reduction, pad rows
    zeroed before every activation-scale group — which this test pins
    per backend: identical logits AND an identical cache afterwards."""
    arch = "llama3-8b"
    cfg, params = _params_for(arch)
    policy = PALLAS if use_pallas else PAPER_FAITHFUL
    cache = registry.init_pool_cache(cfg, 2, MAX_LEN, page_size=4)
    step = _chunk_fn(cfg, policy)
    # stream two unequal prompts in, pool-style, via chunk steps
    prompts = [[5, 7, 9, 11, 2, 13], [3, 1, 4]]
    bufs = [list(p) for p in prompts]
    logits = None
    while any(bufs):
        tokens = np.zeros((2, CHUNK), np.int32)
        n_new = np.zeros((2,), np.int32)
        for s, buf in enumerate(bufs):
            take = min(CHUNK, len(buf))
            tokens[s, :take] = buf[:take]
            n_new[s] = take
            bufs[s] = buf[take:]
        logits, cache = step(
            params, jnp.asarray(tokens), jnp.asarray(n_new), cache
        )
    last = np.asarray(jnp.argmax(logits, -1), np.int32)
    # one decode step, both ways, from the same cache
    dec = np.zeros((2, CHUNK), np.int32)
    dec[:, 0] = last
    lg_chunk, c_chunk = step(
        params, jnp.asarray(dec), jnp.asarray([1, 1], jnp.int32), cache
    )
    lg_plain, c_plain = registry.decode_step(
        cfg, policy, params, jnp.asarray(last), cache
    )
    np.testing.assert_array_equal(np.asarray(lg_chunk), np.asarray(lg_plain))
    for key in ("k", "v", "pos", "len", "table"):
        np.testing.assert_array_equal(
            np.asarray(c_chunk[key]), np.asarray(c_plain[key]),
            err_msg=f"cache leaf {key!r} diverged",
        )


# ---------------------------------------------------------------------------
# Page-budget admission validation (ISSUE 6 satellite)
# ---------------------------------------------------------------------------


def test_admission_at_exactly_full_page_capacity():
    """A request whose prompt + budget lands exactly on the per-slot page
    budget admits and completes; one more token is rejected up front with
    the page arithmetic in the message."""
    cfg, params = _params_for("llama3-8b")
    eng = PoolEngine(
        cfg, PAPER_FAITHFUL, params, max_slots=2, max_len=MAX_LEN,
        page_size=4,
    )
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, (1, 20)).astype(np.int32)
    full = Request(uid="full", tokens=toks, max_new_tokens=4)  # 24 == span
    out = eng.run([full])
    assert len(out["full"]) == 4
    over = Request(uid="over", tokens=toks, max_new_tokens=5)  # 25 > span
    with pytest.raises(ValueError, match="pages"):
        eng.run([over])


@pytest.mark.parametrize(
    "arch", ["mistral-nemo-12b@w8", "recurrentgemma-2b", "mamba2-2.7b"]
)
def test_page_budget_exempts_ring_and_recurrent(arch):
    """Windowed archs (paged decoder ring and hybrid alike) decode from a
    ring whose wrap IS the model semantics, and ssm state is O(1) in
    sequence length — neither is capacity-bounded by pages, so over-span
    requests must pass validation (same exemptions the unpaged engine
    had)."""
    cfg, params = _params_for(arch)
    eng = PoolEngine(
        cfg, PAPER_FAITHFUL, params, max_slots=1, max_len=MAX_LEN
    )
    toks = np.zeros((1, 20), np.int32)
    over = Request(uid=0, tokens=toks, max_new_tokens=10)  # 30 > max_len
    eng._validate([over])  # must not raise


# ---------------------------------------------------------------------------
# Shared-prefix cache (ISSUE 6): reuse never changes anyone's tokens
# ---------------------------------------------------------------------------


def test_prefix_cache_bit_identical():
    """Shared-system-prompt workload: with ``prefix_cache=True`` later
    admissions map the first request's prompt pages instead of
    recomputing them.  The mapped bytes are exactly what chunked replay
    would have written (chunk-complete pages only; COW'd positions
    clamped to the resume point), so every request's tokens stay
    bit-identical to the solo chunked reference — while the engine
    provably skips prompt work (hit tokens > 0, strictly fewer weight
    passes than the unshared run)."""
    from repro.serve import shared_prefix_trace

    cfg, params = _params_for("llama3-8b")
    reqs = shared_prefix_trace(
        cfg, n_requests=5, prefix_len=8, suffix_len=3, lam=2.0,
        new_lo=2, new_hi=5, seed=5,
    )
    solo = {
        r.uid: _solo_chunked_reference(cfg, PAPER_FAITHFUL, params, r)
        for r in reqs
    }
    kw = dict(max_slots=2, max_len=MAX_LEN, prefill_chunk=CHUNK, page_size=4)
    base = PoolEngine(cfg, PAPER_FAITHFUL, params, **kw)
    out_base = base.run(reqs)
    shared = PoolEngine(
        cfg, PAPER_FAITHFUL, params, prefix_cache=True, **kw
    )
    out_shared = shared.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            out_shared[r.uid], solo[r.uid], err_msg=f"uid={r.uid} vs solo"
        )
        np.testing.assert_array_equal(
            out_base[r.uid], solo[r.uid], err_msg=f"uid={r.uid} unshared"
        )
    st, sb = shared.last_stats, base.last_stats
    assert st.prefix_hit_tokens > 0
    assert st.weight_passes < sb.weight_passes
    assert st.mean_ttft_passes < sb.mean_ttft_passes


def test_eos_early_retire_is_solo_prefix():
    """EOS retires a slot early; the emitted tokens are a bit-identical
    prefix of the fixed-horizon solo decode, and the freed slot is reused."""
    cfg, policy, params, reqs, solo, _ = _case("llama3-8b")
    eng = PoolEngine(cfg, policy, params, max_slots=2, max_len=MAX_LEN)
    # use each request's own 2nd solo token as its EOS -> retire after 2
    scheduled = [
        dataclasses.replace(
            r, arrival=i, eos_id=int(solo[r.uid][1]) if len(solo[r.uid]) > 1 else None
        )
        for i, r in enumerate(reqs)
    ]
    out = eng.run(scheduled)
    for r in scheduled:
        ref = solo[r.uid]
        got = out[r.uid]
        assert len(got) <= len(ref)
        np.testing.assert_array_equal(got, ref[: len(got)])
        if r.eos_id is not None and r.eos_id in ref.tolist():
            assert got[-1] == r.eos_id
