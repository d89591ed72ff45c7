"""The controls at a size a test run holds: the reference computed in
bfloat16 in the program's place fails the training cell's limits, as does
the reference fed half of each batch; the program itself passes them.  In the
serving cell every served token altered by one fails its limit, the
bfloat16 reference's own first choices read below the program's, and the
program passes it."""
import jax

from bench import control, run
from bench.lib import check
from bench.tests import tiny


def test_train_control_and_half_batch_fail_the_limits():
    spec = tiny.train_spec()
    limits = spec["cell"]["limits"]
    got = control.train_readings(run.Run(spec, tiny.SEED, 0.0, False,
                                         jax.devices()))

    def passes(name):
        return check.passed({"numbers": {k: got[name][k] for k in limits},
                             "limits": limits})

    assert passes("program"), got["program"]
    for name in ("control", "half_batch"):
        assert not passes(name), (name, got[name])


def test_serve_control_and_altered_token_fail_the_limits():
    # four layers at width 256: enough for the control to flip argmaxes
    spec = tiny.serve_spec()
    spec["cfg"].update(n_layers=4, d_model=256, head_dim=64, d_ff=512,
                       vocab=2048)
    limits = spec["cell"]["limits"]
    got = control.serve_readings(run.Run(spec, tiny.SEED, 0.0, False,
                                         jax.devices()))

    def passes(name):
        return check.passed({"numbers": {k: got[name][k] for k in limits},
                             "limits": limits})

    assert got["tokens_compared"] >= spec["cell"]["check_tokens"]
    assert passes("program"), got["program"]
    assert got["program"]["argmax_share"] == 1.0
    assert not passes("altered_token"), got["altered_token"]
    # at this depth the control's flips stay few; at the cell's size on
    # the chip they spread over 16 layers and fail the limit (PERF.md)
    assert got["control"]["argmax_share"] < 1.0, got["control"]
