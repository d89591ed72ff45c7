"""Property tests for the PoT-quantized KV page wire format.

Three contracts of core/compress.py's ``kv_page_encode``/``kv_page_decode``
(+ the paged-pool plumbing in serve/slots.py) that the conformance matrix
(tests/conformance/test_kv_quant.py) relies on but cannot sweep:

* **roundtrip idempotence** — decode∘encode is a projection: quantizing
  an already-quantized page reproduces it bit-exactly (PoT values are
  exact in bf16, so the encode-side canonicalization is lossless on
  them), across subnormals, ±amax, exact zeros and huge magnitudes;
* **per-page scale independence** — a token's dequant depends only on
  its own codes and its own beta: scribbling arbitrary junk (codes AND
  betas) into one physical page never changes any other page's
  dequantized values, and junk betas still decode finite (the defensive
  clamp keeps exponents inside exp2i's window);
* **COW-after-quantize isolation** — copying a page's (codes, betas) to
  a fresh physical page, as the engine's ``_sync_admission`` does, fully
  detaches it: mutating the source afterwards leaves the copy's dequant
  bit-identical.

hypothesis is an optional dev dep; without it the same drivers run on a
fixed sweep.  The nightly workflow raises the example budget via
``REPRO_HYPOTHESIS_SCALE``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compress, potq
from repro.core.policy import KV_PINNED, KVQuantSpec
from repro.serve import slots as slots_lib

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # degrade to the deterministic sweep only
    hypothesis = None

_SCALE = max(1, int(__import__("os").environ.get("REPRO_HYPOTHESIS_SCALE", "1")))

SPECS = (KV_PINNED, KVQuantSpec(bits=3, pack=False), KVQuantSpec(bits=5, pack=False))


def _tokens(seed, t, kv, hd, mag_lo, mag_hi):
    """(t, kv, hd) float32 with per-token magnitudes spanning
    [2^mag_lo, 2^mag_hi], plus the special values the grid must handle:
    an all-zero token, subnormals, and exact ±amax duplicates."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((t, kv, hd)).astype(np.float32)
    mags = np.logspace(
        mag_lo, mag_hi, t, base=2.0, dtype=np.float64
    ).astype(np.float32)
    x *= mags.reshape(t, 1, 1)
    x[0] = 0.0
    if t > 1:
        x[1].reshape(-1)[: hd // 2] = np.float32(1e-40)  # subnormal
    if t > 2:
        flat = x[2].reshape(-1)
        flat[0] = np.abs(flat).max()  # +amax
        flat[1] = -flat[0]  # -amax, exactly
    return x


def _roundtrip(spec, x):
    codes, beta = compress.kv_page_encode(jnp.asarray(x), spec)
    q = np.asarray(compress.kv_page_decode(codes, beta, spec))
    codes2, beta2 = compress.kv_page_encode(jnp.asarray(q), spec)
    q2 = np.asarray(compress.kv_page_decode(codes2, beta2, spec))
    assert np.all(np.isfinite(q))
    np.testing.assert_array_equal(q[(x == 0.0).all(axis=(1, 2))], 0.0)
    np.testing.assert_array_equal(q2, q)  # decode∘encode is a projection


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"b{s.bits}p{int(s.pack)}")
@pytest.mark.parametrize("seed,maglo,maghi", [
    (0, -3, 3), (1, -140, -120), (2, 60, 120), (3, -20, 40), (4, 0, 0),
])
def test_roundtrip_idempotent_fixed(spec, seed, maglo, maghi):
    _roundtrip(spec, _tokens(seed, 6, 2, 4, maglo, maghi))


def test_nibble_pack_roundtrip_exact():
    """pack/unpack is lossless on every signed-nibble code value."""
    codes = np.arange(-8, 8, dtype=np.int8).reshape(2, 8)
    out = np.asarray(compress.unpack_nibbles(compress.pack_nibbles(
        jnp.asarray(codes)
    )))
    np.testing.assert_array_equal(out, codes)


def _quantized_pool(seed, *, slots=2, span=8, page=4, L=2, kv=2, hd=4):
    """A small quantized paged pool with every slot's pages written from
    a random fp mini cache (the identity table of the default geometry)."""
    base = {
        "k": jnp.zeros((L, slots, span, kv, hd), jnp.float32),
        "v": jnp.zeros((L, slots, span, kv, hd), jnp.float32),
        "pos": jnp.zeros((span,), jnp.int32),
        "len": jnp.zeros((), jnp.int32),
    }
    pool = slots_lib.page_pool_cache(base, slots, page, kv_quant=KV_PINNED)
    rng = np.random.default_rng(seed)
    for s in range(slots):
        mini = {
            "k": jnp.asarray(
                rng.standard_normal((L, 1, span, kv, hd)), jnp.float32
            ),
            "v": jnp.asarray(
                rng.standard_normal((L, 1, span, kv, hd)), jnp.float32
            ),
            "pos": jnp.arange(span, dtype=jnp.int32),
            "len": jnp.asarray(span, jnp.int32),
        }
        pool = slots_lib.write_slot(pool, mini, s, kv_quant=KV_PINNED)
    return pool


def _dequant_slot(pool, slot):
    """Dequantized logical K/V of one slot, gathered through its table."""
    pids = pool["table"][slot]
    out = []
    for key in ("k", "v"):
        codes = pool[key][:, pids]  # (L, n, page, kv, hdw)
        beta = pool[f"{key}_beta"][:, pids]  # (L, n, page)
        out.append(np.asarray(compress.kv_page_decode(codes, beta, KV_PINNED)))
    return out


def _scribble(pool, pids, seed):
    """Arbitrary junk — code bytes AND betas (unclamped int32) — into the
    given physical pages of every wire leaf."""
    rng = np.random.default_rng(seed)
    pool = dict(pool)
    for key in ("k", "v"):
        junk = rng.integers(0, 256, pool[key][:, pids].shape)
        pool[key] = pool[key].at[:, pids].set(
            jnp.asarray(junk, pool[key].dtype)
        )
        bjunk = rng.integers(-(2 ** 30), 2 ** 30, pool[f"{key}_beta"][:, pids].shape)
        pool[f"{key}_beta"] = pool[f"{key}_beta"].at[:, pids].set(
            jnp.asarray(bjunk, jnp.int32)
        )
    return pool


def _page_independence(seed, scribble_seed):
    pool = _quantized_pool(seed)
    before = _dequant_slot(pool, 0)
    scribbled = _scribble(pool, pool["table"][1], scribble_seed)
    after = _dequant_slot(scribbled, 0)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a, b)
    # junk betas must still decode finite (defensive clamp in decode):
    # masked-out positions contribute softmax weight 0, and 0 * inf
    # would poison the V reduction
    for leaf in _dequant_slot(scribbled, 1):
        assert np.all(np.isfinite(leaf))


def _cow_isolation(seed, scribble_seed):
    pool = _quantized_pool(seed, slots=2)
    src = int(pool["table"][0][0])
    dst = int(pool["table"][1][1])  # overwrite an unrelated page
    # the engine's _sync_admission COW leaf copy, verbatim
    pool = dict(pool)
    for key in ("k", "v", "k_beta", "v_beta"):
        pool[key] = pool[key].at[:, dst].set(pool[key][:, src])
    pool["table"] = pool["table"].at[1, 1].set(src).at[1, 1].set(dst)
    copy_before = _dequant_slot(pool, 1)
    scribbled = _scribble(pool, jnp.asarray([src]), scribble_seed)
    copy_after = _dequant_slot(scribbled, 1)
    for b, a in zip(copy_before, copy_after):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", range(3))
def test_page_scale_independence_fixed(seed):
    _page_independence(seed, seed + 100)


@pytest.mark.parametrize("seed", range(3))
def test_cow_after_quantize_isolation_fixed(seed):
    _cow_isolation(seed, seed + 200)


if hypothesis is not None:

    @hypothesis.given(
        spec=st.sampled_from(SPECS),
        seed=st.integers(0, 2 ** 16),
        t=st.integers(1, 8),
        kv=st.integers(1, 3),
        hd=st.sampled_from([2, 4, 8]),
        maglo=st.integers(-140, 120),
        span=st.integers(0, 20),
    )
    @hypothesis.settings(deadline=None, max_examples=60 * _SCALE)
    def test_roundtrip_idempotent(spec, seed, t, kv, hd, maglo, span):
        _roundtrip(spec, _tokens(seed, t, kv, hd, maglo, maglo + span))

    @hypothesis.given(
        seed=st.integers(0, 2 ** 16), scribble=st.integers(0, 2 ** 16)
    )
    @hypothesis.settings(deadline=None, max_examples=30 * _SCALE)
    def test_page_scale_independence(seed, scribble):
        _page_independence(seed, scribble)

    @hypothesis.given(
        seed=st.integers(0, 2 ** 16), scribble=st.integers(0, 2 ** 16)
    )
    @hypothesis.settings(deadline=None, max_examples=30 * _SCALE)
    def test_cow_after_quantize_isolation(seed, scribble):
        _cow_isolation(seed, scribble)
