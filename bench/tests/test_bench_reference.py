"""The plain reference against the program's own pieces, at small sizes
on the CPU: the PoT quantizer bit for bit, and the dense decoder's MF-MAC
loss and its gradients, through the model module's interface, against the
program's training path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import model
from bench.lib import refmath as R
from bench.lib import weights as W
from bench.tests import tiny


@pytest.mark.parametrize("bits", [5, 6])
def test_pot_matches_program_quantizer(bits):
    from repro.core import potq

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4096,)) * 3.0
    # values right at and around the sqrt(2) rounding boundaries
    edge = jnp.ldexp(jnp.float32(np.sqrt(2.0)),
                     jnp.arange(-8, 8)).astype(jnp.float32)
    x = jnp.concatenate([x, edge, jnp.nextafter(edge, 10.0),
                         jnp.nextafter(edge, -10.0), -edge, jnp.zeros(3)])
    amax = jnp.max(jnp.abs(x))
    want = potq.pot_quantize(x, bits, potq.compute_beta(x, bits))
    np.testing.assert_array_equal(np.asarray(R.pot(x, bits, amax)),
                                  np.asarray(want))


def test_weight_quantizer_matches_program():
    from repro.core import mfmac, policy

    w = jax.random.normal(jax.random.PRNGKey(1), (64, 96)) * 0.02 + 1e-3
    want = mfmac._quantize_w(w, policy.PAPER_FAITHFUL).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(R.quant_w(w)),
                                  np.asarray(want))


def _program_and_reference_grads(num):
    from repro.models import registry
    from repro.core import policy
    from repro.train import step as step_lib

    cfg_json = tiny.cfg()
    from bench.lib.train_cell import flat, model_config

    cfg = model_config(cfg_json)
    from bench.lib.seeds import seed_arg

    dense = model.load(cfg_json)
    params = {n: W.leaf(seed_arg(tiny.SEED), cfg_json, n)
              for n in dense.layout(cfg_json)}
    tree = dense.tree(cfg_json, params)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 257)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1),
             "mask": jnp.ones((2, 16)).at[:, -1].set(0.0)}
    pol = policy.PAPER_FAITHFUL
    # the program's straight-through step: gradients taken at the shadow
    shadow = step_lib._quantize_shadow(tree, pol)
    pl, pg = jax.value_and_grad(lambda s: registry.loss_fn(
        cfg, dataclasses.replace(pol, weights_prequantized=True), s,
        batch))(shadow)
    rl, rg = jax.value_and_grad(dense.loss_fn, argnums=1)(
        cfg_json, params, batch["tokens"], batch["labels"], batch["mask"],
        num)
    return pl, flat(pg), rl, rg


def test_loss_and_gradients_match_program_path():
    pl, pg, rl, rg = _program_and_reference_grads(R.Num(False))
    assert float(pl) == pytest.approx(float(rl), rel=1e-6)
    for k, g in rg.items():
        a, b = np.linalg.norm(np.asarray(pg[k])), np.linalg.norm(np.asarray(g))
        assert a == pytest.approx(b, rel=1e-4, abs=1e-9), k


def test_dropped_dgamma_is_seen():
    """The clipping ratios' gradient is not nought: a backward that drops
    it parts from the program here, leaf by leaf, where the chip's
    norms cannot tell it (PERF.md)."""
    _, pg, _, rg = _program_and_reference_grads(R.Num(no_dgamma=True))
    gammas = [k for k in rg if k.endswith("/gamma")]
    assert gammas
    for k in gammas:
        assert np.linalg.norm(np.asarray(pg[k])) > 0, k
        assert np.linalg.norm(np.asarray(rg[k])) == 0, k


def test_control_is_lower_precision():
    x = jnp.float32(1.0) + jnp.float32(2.0 ** -12)
    assert float(R.Num(True).r(x)) == 1.0
    assert float(R.Num(False).r(x)) == float(x)
