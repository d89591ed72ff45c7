"""Operations, bytes, the dense decoder's model FLOPs and the table of
peaks."""
import json
import os

import pytest

from bench.lib import device, flops, reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_matmul_cost_counts_code_width():
    ops, nbytes = flops.matmul_cost(512, 2048, 8192, 5, 5)
    assert ops == 2 * 512 * 2048 * 8192
    assert nbytes == 512 * 2048 * 5 / 8 + 2048 * 8192 * 5 / 8 \
        + 512 * 8192 * 2


def test_model_flops_olmo_train_per_token():
    cfg = _cfg("olmo-1b-8l")
    per_layer = 2 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    attn = 4 * 16 * 128 * (2048 + 1) / 2
    head = 2 * 2048 * 50304
    want = 3 * (8 * (per_layer + attn) + head)
    assert reference.model_flops_train_per_token(cfg, 2048) == \
        pytest.approx(want)


def test_model_flops_serve_request_counts_fed_rows():
    # StarCoder2-7B's widths at 16 layers: grouped-query, GELU, untied
    cfg = {"n_layers": 16, "d_model": 4608, "n_heads": 36, "kv_heads": 4,
           "head_dim": 128, "d_ff": 18432, "vocab": 49152, "act": "gelu"}
    rows = 100 + 10 - 1
    want = (16 * (rows * reference.layer_flops_per_row(cfg)
                  + 4 * 36 * 128 * rows * (rows + 1) / 2)
            + 10 * 2 * 4608 * 49152)
    assert reference.model_flops_serve_request(cfg, 100, 10) == \
        pytest.approx(want)
    assert reference.layer_flops_per_row(cfg) == 2 * (
        4608 * 4608 * 2 + 2 * 4608 * 512 + 2 * 4608 * 18432)


def test_kernel_call_cost_per_kind():
    m, k, n = 4096, 2048, 8192
    assert flops.kernel_call_cost("fwd", m, k, n, 50688) == \
        flops.matmul_cost(m, k, n, 5, 5)
    ops, nbytes = flops.kernel_call_cost("da", m, k, n, 50688)
    assert ops == 2 * m * k * n
    assert nbytes == m * n * 5 / 8 + k * n * 5 / 8 + m * k * 2 + m * k / 8
    ops, nbytes = flops.kernel_call_cost("dw", m, k, n, 50688)
    assert ops == 2 * m * k * n
    assert nbytes == m * k * 5 / 8 + m * n * 5 / 8 + k * n * 2
    # the LM head's gradient has 6 bits
    _, head = flops.kernel_call_cost("dw", m, k, 50688, 50688)
    assert head == m * k * 5 / 8 + m * 50688 * 6 / 8 + k * 50688 * 2


def test_kernel_roofline_sums_calls_and_names_its_bound():
    peak = device.peaks("TPU v5 lite")
    m = k = n = 4096
    t_c = 2 * m * k * n / 197e12
    share, bound = flops.kernel_roofline([("fwd", m, k, n, 2 * t_c)], 0,
                                         peak)
    assert share == pytest.approx(50.0) and bound == "compute"
    # one row through a big matrix is bound by the weight stream
    share, bound = flops.kernel_roofline(
        [("fwd", 1, k, n, 1.0), ("fwd", 1, k, n, 1.0)], 0, peak)
    assert bound == "memory"
    assert share == pytest.approx(100.0 * (k * n * 5 / 8 + k * 5 / 8 + n * 2)
                                  / 819e9 / 1.0)
    assert flops.kernel_roofline([], 0, peak) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        device.peaks("TPU v4")
    assert device.peaks("TPU v5 lite")["source"]


def test_cpu_backend_is_refused():
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(device.NoAccelerator):
        device.require_tpu(1)
