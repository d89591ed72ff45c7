"""Whole training runs on the CPU past the harness's look for a chip: a
sound run is correct, and each fault a one-chip training cell can have,
planted under the timed path, makes ``correct`` false."""
import pytest

from bench.tests import tiny


def _broken(kind):
    from repro.train import make_train_step

    def make(*args, **kwargs):
        real = make_train_step(*args, **kwargs)

        def step(params, opt_state, batch, i):
            if kind == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return real(params, opt_state, half, i)
            _, _, metrics = real(params, opt_state, batch, i)
            return params, opt_state, metrics  # state left unchanged

        return step

    return {"make_train_step": make}


def test_sound_run_is_correct():
    result = tiny.execute(tiny.train_spec())
    assert result["correct"], result["check"]
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(result["check"]) == set(tiny.train_spec()["cell"]["limits"])


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_fault_is_caught(kind):
    result = tiny.execute(tiny.train_spec(), hooks=_broken(kind))
    assert not result["correct"], result["check"]
