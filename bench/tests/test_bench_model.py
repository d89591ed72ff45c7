"""The model module a configuration names under ``reference``: every
configuration's module has the interface and lays out the program's own
parameters; a module in a new file drives the tiny cells with no harness
file edited; leaves follow the key derivation; a module that lacks part
of the interface fails at set-up, naming the configuration and the part."""
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import model, weights as W
from bench.lib.seeds import seed_arg
from bench.lib.train_cell import flat, model_config
from bench.tests import tiny

SPEC = run.load_json(run.ROOT, "BENCHMARK.json")
DATA = "bench/tests/data"


def _config(entry):
    return run.load_json(run.ROOT, entry["file"])


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_module_lays_out_the_program_parameters(entry):
    from repro.models import registry
    from repro.models import spec as pspec

    cfg = _config(entry)
    mod = model.load(cfg, need=model.SERVE)
    for part in model.INTERFACE + model.SERVE:
        assert callable(getattr(mod, part)), part
    want = pspec.abstract(registry.param_specs(model_config(cfg)))
    layout = mod.layout(cfg)
    assert {k: tuple(shape) if depth is None else (depth, *shape)
            for k, (shape, _, depth) in layout.items()} == \
        {k: v.shape for k, v in flat(want).items()}
    # the tree the cells hand the program, at full size, as shapes only
    have = jax.eval_shape(lambda s: mod.tree(cfg, {
        n: W.leaf(s, cfg, n) for n in layout}), seed_arg(tiny.SEED))
    assert jax.tree.structure(have) == jax.tree.structure(want)
    for h, w in zip(jax.tree.leaves(have), jax.tree.leaves(want)):
        assert (h.shape, h.dtype) == (w.shape, w.dtype)


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_module_in_a_new_file_drives_the_tiny_cell(kind):
    spec = tiny.train_spec() if kind == "train" else tiny.serve_spec()
    spec["cfg"]["reference"] = f"{DATA}/counted_dense.py"
    mod = model.load(spec["cfg"])
    assert os.path.samefile(mod.__file__, os.path.join(run.ROOT, DATA,
                                                       "counted_dense.py"))
    mod.CALLS.clear()
    result = tiny.execute(spec)
    assert result["correct"], result["check"]
    used = {"train": ("layout", "tree", "loss_fn"),
            "serve": ("layout", "tree", "StepReference")}[kind]
    for part in used:
        assert mod.CALLS[part] > 0, (part, dict(mod.CALLS))


@pytest.mark.parametrize("name,layer,shape,kind", [
    ("embed", None, (320, 64), "normal"),
    ("layers/wq/w", 0, (64, 64), "normal"),
    ("layers/mlp/wo/w", 1, (128, 64), "normal"),
    ("layers/mlp/wi_up/gamma", 1, (), "gamma"),
])
def test_tiny_leaves_follow_the_key_derivation(name, layer, shape, kind):
    cfg = tiny.cfg()
    seed = seed_arg(tiny.SEED)
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, 0 if layer is None else layer)
    want = (jax.random.normal(key, shape, jnp.float32) * 0.02
            if kind == "normal" else jnp.full(shape, 0.95, jnp.float32))
    whole = W.leaf(seed, cfg, name)
    if layer is None:
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(want))
        return
    assert whole.shape == (cfg["n_layers"], *shape)
    np.testing.assert_array_equal(np.asarray(whole[layer]), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(W.leaf(seed, cfg, name, layer)),
                                  np.asarray(want))


@pytest.mark.parametrize("reference,kind,said", [
    (None, "train", "names no model module under 'reference'"),
    (f"{DATA}/no_loss_fn.py", "train", "/no_loss_fn.py lacks loss_fn$"),
    (f"{DATA}/train_only.py", "serve", "/train_only.py lacks StepReference$"),
])
def test_incomplete_module_fails_at_setup(reference, kind, said):
    spec = tiny.train_spec() if kind == "train" else tiny.serve_spec()
    spec["cfg"]["name"] = "tiny-incomplete"
    spec["cfg"].pop("reference")
    if reference is not None:
        spec["cfg"]["reference"] = reference
    with pytest.raises(model.ModelModuleError,
                       match=f"'tiny-incomplete'.*{said}"):
        tiny.execute(spec)
