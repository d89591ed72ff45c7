"""Tiny cells for the CPU tests: the harness's own drivers, the program at
a smoke size, and the plain reference beside it."""
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 12345


def cfg(gqa: bool = False) -> dict:
    """A smoke-size decoder.  Attention's operands are stated float32: the
    program's float32 dots run at full precision on the CPU."""
    return {"name": "tiny", "reference": "bench/lib/reference.py",
            "family": "decoder", "n_layers": 2,
            "d_model": 64, "n_heads": 4, "kv_heads": 2 if gqa else 4,
            "head_dim": 16, "d_ff": 128, "vocab": 257,
            "vocab_pad_multiple": 64, "act": "gelu" if gqa else "swiglu",
            "norm": "ln" if gqa else "nonparam_ln", "rope_theta": 10000.0,
            "window": None, "tie_embeddings": not gqa,
            "act_dtype": "float32", "attention_operands": "float32"}


def _cell(name):
    with open(os.path.join(HERE, "cells", f"{name}.json")) as f:
        return json.load(f)


def train_spec() -> dict:
    return {"workload": {"name": "tiny-train", "chips": 1}, "cfg": cfg(),
            "cell": _cell("olmo1b-train"),
            "traffic": {"kind": "token_batches", "batch": 2, "seq": 32},
            "end_to_end": [{"name": "train_tokens_per_s",
                            "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def serve_spec(gqa: bool = False) -> dict:
    cell = _cell("olmo1b-serve-decode")
    cell.update(engine={"slots": 4, "prefill_chunk": 8, "max_len": 64,
                        "page_size": 8}, check_tokens=20)
    return {"workload": {"name": "tiny-serve", "chips": 1}, "cfg": cfg(gqa),
            "cell": cell,
            "traffic": {"kind": "offline_batch", "requests_per_call": 6,
                        "prompt": {"median": 12, "sigma": 0.5, "lo": 4,
                                   "hi": 30},
                        "output": {"median": 6, "sigma": 0.5, "lo": 2,
                                   "hi": 16},
                        "pairing_seed": 0, "arrival_lambda": 0},
            "end_to_end": [{"name": "serve_tokens_per_s",
                            "unit": "tokens/s"},
                           {"name": "serve_step_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def execute(spec, hooks=None, seed=SEED):
    """A whole run on the CPU, past the harness's look for a chip."""
    import jax

    from bench import run

    return run.execute(run.Run(spec, seed, 0.5, False, jax.devices(),
                               hooks=hooks))
