"""The traffic generators: deterministic in the seed, different across
seeds, and within their clipping bounds."""
import json
import os

import numpy as np
import pytest

from bench.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 7, 2**31 + 5, 3 * 2**32 + 1)


#: a long-prompt, short-output mix beside the cells' own traffic files
EXTRA = {"code-completion-offline": {
    "kind": "offline_batch", "requests_per_call": 16,
    "prompt": {"median": 1536, "sigma": 0.5, "lo": 256, "hi": 3584},
    "output": {"median": 32, "sigma": 0.7, "lo": 8, "hi": 128},
    "pairing_seed": 0, "arrival_lambda": 0}}


def _traffic(name):
    if name in EXTRA:
        return EXTRA[name]
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,chunk", [("code-completion-offline", 128),
                                        ("chat-offline", 16)])
def test_offline_batch_deterministic_and_seeded(name, chunk):
    t = _traffic(name)
    a = traffic.serve_call(t, 1000, chunk, SEEDS[2], 1)
    b = traffic.serve_call(t, 1000, chunk, SEEDS[2], 1)
    c = traffic.serve_call(t, 1000, chunk, SEEDS[3], 1)
    assert len(a) == t["requests_per_call"]
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert x["max_new_tokens"] == y["max_new_tokens"]
    assert any(not np.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(a, c))
    # every seed serves the same sizes
    assert [(x["tokens"].shape, x["max_new_tokens"]) for x in a] == \
        [(x["tokens"].shape, x["max_new_tokens"]) for x in c]


@pytest.mark.parametrize("name,chunk", [("code-completion-offline", 128),
                                        ("chat-offline", 16)])
def test_offline_batch_within_bounds(name, chunk):
    t = _traffic(name)
    for seed in SEEDS:
        for call in range(3):
            for r in traffic.serve_call(t, 50, chunk, seed, call):
                p = r["tokens"].shape[-1]
                assert t["prompt"]["lo"] <= p <= t["prompt"]["hi"]
                assert t["output"]["lo"] <= r["max_new_tokens"] <= \
                    t["output"]["hi"]
                assert r["tokens"].dtype == np.int32
                assert 0 <= r["tokens"].min() and r["tokens"].max() < 50
                assert r["arrival"] == 0


def test_lognormal_median_and_clip():
    spec = {"median": 100, "sigma": 2.0, "lo": 10, "hi": 300}
    xs = traffic.clipped_lognormal(spec, 101)
    assert xs == sorted(xs)
    assert xs[50] == 100
    assert xs[0] == 10 and xs[-1] == 300
    with pytest.raises(ValueError):
        traffic.clipped_lognormal({**spec, "lo": 400}, 5)


def test_longest_first_order():
    t = _traffic("code-completion-offline")
    sizes = traffic.call_sizes(t, 128)
    work = [-(-p // 128) + o for p, o in sizes]
    assert work == sorted(work, reverse=True)


def test_poisson_arrivals_copy():
    r = np.random.default_rng(0)
    arr = traffic.poisson_arrivals(50, 3.0, r)
    assert arr[0] == 0 and arr == sorted(arr) and arr[-1] > 0
    assert traffic.poisson_arrivals(4, 0.0, r) == [0, 0, 0, 0]


def test_token_batches_deterministic_and_seeded():
    import jax

    from bench.lib.seeds import seed_arg

    f = jax.jit(lambda s, i: traffic.make_batch(257, 2, 16, s, i))
    a, b = f(seed_arg(SEEDS[2]), 3), f(seed_arg(SEEDS[2]), 3)
    c, d = f(seed_arg(SEEDS[3]), 3), f(seed_arg(SEEDS[2]), 4)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])
    tok = np.asarray(a["tokens"])
    assert tok.min() >= 0 and tok.max() < 257
    assert np.array_equal(tok[:, :8], tok[:, 8:])  # second half repeats
    assert not np.array_equal(tok[0], tok[1])  # rows differ
    assert np.array_equal(np.asarray(a["labels"])[:, :-1], tok[:, 1:])
    assert float(np.asarray(a["mask"])[:, -1].sum()) == 0.0
