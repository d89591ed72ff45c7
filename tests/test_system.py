"""End-to-end behaviour tests: CLI train driver (with restart), serving."""
import importlib.util
import math

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.configs.base import ShapeConfig
from repro.core.policy import PAPER_FAITHFUL
from repro.data import pipeline
from repro.launch import compile_cache
from repro.launch import train as train_cli
from repro.models import registry, spec as pspec
from repro.serve import generate


@pytest.mark.slow
def test_train_cli_runs_and_restarts(tmp_path, capsys):
    args = [
        "--arch", "olmo-1b", "--smoke", "--steps", "6", "--batch", "4",
        "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
        "--log-every", "2",
    ]
    summary = train_cli.main(args)
    out1 = capsys.readouterr().out
    assert "step     5" in out1
    assert len(summary["losses"]) == 6
    assert all(map(math.isfinite, summary["losses"] + summary["grad_norms"]))
    assert summary["compile_s"] > 0 and summary["step_s"] > 0
    # restart: must restore step 6 checkpoint and exit immediately
    train_cli.main(args)
    out2 = capsys.readouterr().out
    assert "restoring checkpoint step 6" in out2


@pytest.mark.parametrize("layers", [1, 3])
def test_train_cli_depth_cut(layers, capsys):
    """--layers keeps N whole layers and leaves widths alone; more layers
    than the config has is refused."""
    args = ["--arch", "olmo-1b", "--smoke", "--steps", "0",
            "--layers", str(layers)]
    if layers > C.smoke_config("olmo-1b").n_layers:
        with pytest.raises(ValueError, match="--layers"):
            train_cli.main(args)
        return
    train_cli.main(args)
    assert f"depth cut: n_layers 2 -> {layers}" in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(env_dir, monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins; otherwise the fixed, git-ignored
    <checkout>/.jax_cache."""
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = compile_cache.DEFAULT_DIR
        root = want.parent
        assert (root / "chip_smoke.py").is_file()  # the checkout's root
        ignored = (root / ".gitignore").read_text().split()
        assert f"{want.name}/" in ignored
    else:
        want = tmp_path / env_dir
        monkeypatch.setenv(compile_cache.ENV, str(want))
    assert compile_cache.enable() == str(want)
    assert jax.config.jax_compilation_cache_dir == str(want)


def test_chip_smoke_refuses_a_cpu_backend(capsys):
    """chip_smoke.py runs no phase and prints no result line off the TPU."""
    path = compile_cache.DEFAULT_DIR.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "not 'tpu'" in err


@pytest.mark.slow
def test_generate_batched():
    cfg = C.smoke_config("llama3-8b")
    params = pspec.materialize(registry.param_specs(cfg), jax.random.PRNGKey(0))
    shape = ShapeConfig("t", 16, 3, "decode")
    batch = pipeline.make_batch(cfg, shape, 0)
    toks = generate(
        cfg, PAPER_FAITHFUL, params, {"tokens": batch["tokens"]},
        max_new_tokens=5, max_len=32,
    )
    assert toks.shape == (3, 5)
    assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab_padded)))


def test_data_pipeline_deterministic():
    cfg = C.smoke_config("llama3-8b")
    shape = ShapeConfig("t", 16, 4, "train")
    b1 = pipeline.make_batch(cfg, shape, 7)
    b2 = pipeline.make_batch(cfg, shape, 7)
    b3 = pipeline.make_batch(cfg, shape, 8)
    assert bool(jnp.all(b1["tokens"] == b2["tokens"]))
    assert not bool(jnp.all(b1["tokens"] == b3["tokens"]))
    assert bool(jnp.all(b1["labels"][:, :-1] == b1["tokens"][:, 1:]))
