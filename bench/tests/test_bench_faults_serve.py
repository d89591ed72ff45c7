"""Whole serving runs on the CPU past the harness's look for a chip: a
sound run is correct, and a token altered where the pooled step produces
it makes ``correct`` false."""
from bench.tests import tiny


def _alter_tokens(eng):
    """Every token the pooled steps produce, chunked or decoding (the CPU
    decodes through its fast path), altered by one."""
    def altered(step):
        def run(*args):
            tok, logits, cache = step(*args)
            return (tok + 1) % eng.cfg.vocab, logits, cache

        return run

    eng._chunk_step = altered(eng._chunk_step)
    eng._decode = altered(eng._decode)
    return eng


def test_sound_run_is_correct():
    result = tiny.execute(tiny.serve_spec(gqa=True))
    assert result["correct"], result["check"]
    assert result["metrics"]["serve_step_ms"]["value"] > 0


def test_altered_token_is_caught():
    result = tiny.execute(tiny.serve_spec(), hooks={"engine": _alter_tokens})
    assert not result["correct"], result["check"]
