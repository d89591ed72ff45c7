"""The program's trace marks: ``PoolEngine.run``'s ``serve.*`` host spans
under a profiler session, and the ``als_potq`` / ``paged_kv`` name scopes
in the lowered train and chunk steps."""
import functools
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core.policy import PAPER_FAITHFUL
from repro.models import registry, transformer
from repro.models import spec as pspec
from repro.optim.optimizers import adamw, warmup_cosine_schedule
from repro.serve import PoolEngine
from repro.serve.scheduler import Request
from repro.train import TrainConfig, make_train_step

SPANS = {"serve.run", "serve.init_cache", "serve.step", "serve.admit",
         "serve.dispatch", "serve.overlap", "serve.wait", "serve.retire",
         "serve.check"}
IN_STEP = SPANS - {"serve.run", "serve.init_cache", "serve.step"}


@pytest.fixture(scope="module")
def olmo():
    cfg = C.smoke_config("olmo-1b")
    params = pspec.materialize(registry.param_specs(cfg),
                               jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg):
    rng = np.random.default_rng(13)
    return [Request(uid=100 + i,
                    tokens=rng.integers(0, cfg.vocab, (1, plen)).astype(
                        np.int32),
                    max_new_tokens=new)
            for i, (plen, new) in enumerate([(9, 3), (5, 4), (11, 2),
                                             (6, 3)])]


def _engine(cfg, params):
    # two slots for four requests: two are admitted mid-run, after the
    # first retirements
    return PoolEngine(cfg, PAPER_FAITHFUL, params, max_slots=2, max_len=24,
                      prefill_chunk=4, page_size=4)


def _spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in line.events if e.name.startswith("serve.")]
    return out


def _scopes(hlo_text):
    """The name scopes in a lowered program's ``op_name`` locations;
    transformations wrap a scope's name (``transpose(jvp(als_potq))``)."""
    names = re.findall(r'loc\("([^"]*)"', hlo_text)
    return {m for n in names for m in re.findall(r"[a-z_]+", n)}


def _inside(child, parents):
    return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]


def test_run_leaves_nested_serve_spans(olmo, tmp_path):
    cfg, params = olmo
    reqs = _requests(cfg)
    plain = _engine(cfg, params).run(reqs)
    eng = _engine(cfg, params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    for r in reqs:
        np.testing.assert_array_equal(traced[r.uid], plain[r.uid])

    spans = _spans(tmp_path)
    by = {n: [s for s in spans if s[0] == n] for n in SPANS}
    assert {s[0] for s in spans} == SPANS
    (run,) = by["serve.run"]
    assert run[3]["requests"] == len(reqs)
    assert all(_inside(s, [run]) for s in spans)
    assert len(by["serve.step"]) == eng.last_stats.decode_steps
    assert [s[3]["step"] for s in by["serve.step"]] == \
        list(range(len(by["serve.step"])))
    (init,) = by["serve.init_cache"]
    assert not _inside(init, by["serve.step"])
    admits = sorted(s[3]["uid"] for s in by["serve.admit"])
    assert admits == sorted(r.uid for r in reqs)
    for name in IN_STEP:
        for s in by[name]:
            (step,) = _inside(s, by["serve.step"])
            if "step" in s[3]:
                assert s[3]["step"] == step[3]["step"], name
    for step in by["serve.step"]:
        assert len([w for w in by["serve.wait"] if _inside(w, [step])]) == 1
    # the mid-run admissions happen in later steps than the first two
    assert sorted(s[3]["step"] for s in by["serve.admit"])[2] > 0


def test_lowered_steps_carry_scopes(olmo):
    cfg, params = olmo
    cache = registry.init_pool_cache(cfg, 2, 16, jnp.bfloat16, page_size=4,
                                     num_pages=8)
    chunk = jax.jit(functools.partial(transformer.chunk_step, cfg,
                                      PAPER_FAITHFUL)).lower(
        params, jnp.zeros((2, 4), jnp.int32), jnp.array([4, 1], jnp.int32),
        cache).as_text(debug_info=True)
    assert {"paged_kv", "als_potq"} <= _scopes(chunk)

    opt = adamw(warmup_cosine_schedule(1e-3, 2, 100))
    step = make_train_step(cfg, PAPER_FAITHFUL, opt, TrainConfig())
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32),
             "mask": jnp.ones((2, 8), jnp.float32)}
    train = jax.jit(step).lower(params, opt.init(params), batch,
                                jnp.int32(1)).as_text(debug_info=True)
    assert "als_potq" in _scopes(train) and "paged_kv" not in _scopes(train)
    # the backward's gradient quantizer is scoped too
    assert "transpose(jvp(als_potq))" in train
