"""FLOP and byte counts against shapes worked out by hand."""
import json
import os

import pytest

import counts
import model_ref
import tiny

with open(os.path.join(tiny.CHIP, "configs", "code-brute-256k.json")) as f:
    QWEN = json.load(f)
QWEN3 = model_ref.family("qwen3", os.path.join(tiny.CHIP, "models"))


def test_lm_forward_flops_qwen3_0_6b_at_128_tokens():
    # per token: q,o 2*1024*2048 each, k,v 2*1024*1024 each, FFN 3 matmuls
    per_tok = 2 * 1024 * (2 * 2048 + 2 * 1024) + 2 * 3 * 1024 * 3072
    assert per_tok == 31_457_280
    attn = 2 * 2 * (128 * 129 // 2) * 2048     # causal q.k and p.v
    assert QWEN3.forward_flops(QWEN, 128) == 28 * (128 * per_tok + attn)
    assert QWEN3.forward_flops(QWEN, 128) == 114_636_619_776


def test_weight_bytes_qwen3_0_6b():
    # per layer: bf16 q,o 1024x2048, k,v 1024x1024, FFN 3 x 1024x3072,
    # and two float32 norm scales of 1024; no embedding, no final norm
    mats = 2 * 1024 * 2048 + 2 * 1024 * 1024 + 3 * 1024 * 3072
    per_layer = 2 * mats + 4 * 2 * 1024
    assert per_layer == 31_465_472
    assert QWEN3.weight_bytes(QWEN) == 28 * per_layer == 881_033_216


def test_brute_scan_counts():
    flops, nbytes = counts.ann_topk(64, 1 << 18, 1024)
    assert flops == 2 * 64 * (1 << 18) * 1024 == 34_359_738_368
    # rows once, active mask, the query block
    assert nbytes == (1 << 30) + (1 << 18) * 4 + 64 * 1024 * 4


def test_ivf_scan_counts():
    flops, nbytes = counts.ann_topk_ivf(64, 8, 1024, 1024)
    rows = 64 * 8 * 1024
    assert flops == 2 * rows * 1024
    assert nbytes == rows * (4096 + 4) + 64 * 1024 * 4 == 2_149_842_944


def test_int8_counts():
    ops, nbytes = counts.ann_topk_quant(8, 1 << 20, 1024)
    assert ops == 2 * 8 * (1 << 20) * 1024
    assert nbytes == (1 << 20) * (1024 + 8) + 8 * (1024 + 4)
    ops, nbytes = counts.ann_topk_ivf_quant(8, 8, 1024, 1024)
    assert ops == 2 * 8 * 8 * 1024 * 1024
    assert nbytes == 8 * 8 * 1024 * (1024 + 8) + 8 * (1024 + 4)


def test_least_time_picks_the_binding_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = counts.least_time(*counts.ann_topk(64, 1 << 18, 1024), peak)
    assert bound == "memory"
    assert t == pytest.approx(1_075_052_544 / 819e9)
    t, bound = counts.least_time(1e15, 1e6, peak)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
    t, _ = counts.least_time(393e12, 1.0, peak, int8=True)
    assert t == pytest.approx(1.0)


def test_scan_work_reads_a_logged_pass():
    shape = dict(kind="ann_topk_ivf", B=8, C=1280, cap=1024, D=1024,
                 nprobe=8, itemsize=4)
    assert counts.scan_work(shape)[:2] == counts.ann_topk_ivf(8, 8, 1024,
                                                              1024)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_judge_roofline_reads_each_prefill_call():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "judge_roofline", os.path.join(tiny.CHIP, "layer_metrics",
                                       "judge_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    peak = counts.peaks("TPU v5 lite")
    trace = {"op_s": {"jit_score:fusion.1": 0.3, "jit_score:fusion.2": 0.2,
                      "jit_encode:fusion.1": 9.0}, "kernels": []}
    run = {"trace": trace, "judge_calls": [1, 40], "peaks": peak,
           "cfg": QWEN, "judge": model_ref.Model(QWEN, QWEN3)}
    flops, nbytes = 114_636_619_776, 881_033_216
    # 40 pairs go in two calls, of 32 and 8; one pair reads the weights
    # for longer than it computes
    assert nbytes / peak["hbm_bytes_per_s"] > flops / peak["bf16_flops_per_s"]
    need = nbytes / peak["hbm_bytes_per_s"] \
        + (32 + 8) * flops / peak["bf16_flops_per_s"]
    assert reader.read(run) == pytest.approx(100 * need / 0.5)
    assert reader.read(dict(run, trace=None)) is None
    assert reader.read(dict(run, judge_calls=[])) is None
    assert reader.read(dict(run, trace=dict(trace, op_s={}))) is None
