"""The trace reduction, on a small synthetic trace and on one train step
recorded on a TPU v5e: busy as a union of leaf operations, idle gaps
named by the host span around them, kernel calls by family with their
shapes."""
import json
import os
from collections import Counter

import pytest

from bench.lib import device, flops
from bench.lib import tracereduce as T

FAM = {"potq_fwd": {"potq_matmul_padded": "fwd"},
       "potq_grad": {"grad_da_padded": "da", "grad_dw_padded": "dw"}}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tpu_train_step.json")
MS = 1e6


def _op(name, out, opcode, args):
    return f"%{name} = {out}{{1,0}} {opcode}({args})"


def _trace():
    ops = [
        (_op("fusion.1", "f32[8,8]", "fusion", "f32[8,8]{1,0} %p"), 0, 10 * MS),
        (_op("potq_matmul_padded.3", "f32[256,512]", "custom-call",
             "f32[256,128]{1,0} %a, bf16[128,512]{1,0} %w"), 5 * MS, 10 * MS),
        (_op("while.2", "(s32[], f32[8])", "while", "%t"), 20 * MS, 80 * MS),
        (_op("grad_da_padded.7", "(f32[256,128], f32[256,128])",
             "custom-call", "f32[256,512]{1,0} %g, f32[128,512]{1,0} %w"),
         30 * MS, 20 * MS),
        (_op("copy.7", "f32[8]", "copy", "f32[8]{0} %x"), 60 * MS, 5 * MS),
        (_op("grad_dw_padded.2", "f32[3,128,512]", "fusion",
             "f32[3,128,512]{2,1,0} %acc, f32[256,128]{1,0} %a, "
             "f32[256,512]{1,0} %g), kind=kCustom, calls=%f"),
         90 * MS, 20 * MS),
        (_op("grad_dw_padded.9", "f32[128,512]", "fusion",
             "f32[128,512]{1,0} %x"), 92 * MS, 1 * MS),  # not a kernel
    ]
    spans = [("bench.window", 0, 100 * MS), ("bench.train_step", 0, 50 * MS),
             ("bench.sync", 50 * MS, 30 * MS),
             ("bench.batch", 80 * MS, 10 * MS), ("other", 0, 100 * MS)]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_union_merges_and_clips():
    assert T.union([(0, 5), (3, 8), (10, 12), (11, 20)], 1, 15) == \
        [[1, 8], [10, 15]]


def test_parse_op_reads_name_opcode_and_shapes():
    name, op, outs, args = T.parse_op(
        "%grad_da_padded.79 = (f32[4096,2048]{1,0:T(8,128)}, f32[4096,128]"
        "{1,0:T(8,128)S(1)}) custom-call(f32[4096,8192]{1,0:T(8,128)} %g, "
        "bf16[2048,8192]{1,0} %w), custom_call_target=\"tpu_custom_call\"")
    assert (name, op) == ("grad_da_padded.79", "custom-call")
    assert outs == [(4096, 2048), (4096, 128)]
    assert args[:2] == [(4096, 8192), (2048, 8192)]
    assert T.parse_op("bench.step")[1] is None


def test_reduce_busy_gaps_and_families():
    tr = _trace()
    red = T.reduce(tr, T.window_of(tr, "bench.window"), FAM)
    assert red["window_s"] == pytest.approx(0.1)
    # leaves (the while is left out): [0,15) [30,50) [60,65) [90,110)->100
    assert red["busy_s"] == pytest.approx(0.05)
    assert red["kernel_s"]["potq_fwd"] == pytest.approx(0.010)
    assert red["kernel_s"]["potq_grad"] == pytest.approx(0.030)
    assert red["kernels"]["potq_fwd"] == [("fwd", 256, 128, 512, 0.01)]
    assert [c[:4] for c in red["kernels"]["potq_grad"]] == [
        ("da", 256, 128, 512), ("dw", 256, 128, 512)]
    gaps = red["idle_gaps"]
    assert gaps[0][0] == "bench.sync" and gaps[0][1] == pytest.approx(0.025)
    assert [g[0] for g in gaps] == ["bench.sync", "bench.train_step",
                                    "bench.sync"]
    assert red["device_ops"][0] == ["grad_da_padded", pytest.approx(0.02)]
    assert "while" not in dict(red["device_ops"])


def test_window_needs_its_span():
    with pytest.raises(ValueError):
        T.window_of(_trace(), "bench.nothing")


def test_recorded_tpu_train_step():
    with open(DATA) as f:
        tr = json.load(f)
    red = T.reduce(tr, T.window_of(tr, "bench.window"), FAM)
    assert 0 < red["busy_s"] < red["window_s"]
    calls = Counter(c[:4] for c in red["kernels"]["potq_fwd"])
    # 2 layers: q, k, v, o and gate, up run twice (the backward recomputes
    # them), the MLP's down projection once, the tied LM head once
    assert calls == {("fwd", 1024, 2048, 2048): 16,
                     ("fwd", 1024, 2048, 8192): 8,
                     ("fwd", 1024, 8192, 2048): 2,
                     ("fwd", 1024, 2048, 50688): 1}
    grads = Counter(c[0] for c in red["kernels"]["potq_grad"])
    assert grads == {"da": 15, "dw": 15}
    share, bound = flops.kernel_roofline(red["kernels"]["potq_fwd"], 50688,
                                         device.peaks("TPU v5 lite"))
    assert 0 < share < 100 and bound == "compute"
