"""Operation and byte counts of the kernels and of the model, each checked
against a shape worked by hand."""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import spec  # noqa: E402

DENSE = spec.load_metric("kernel.paged_decode_roofline")
MLA = spec.load_metric("kernel.paged_mla_decode_roofline")
MFU = spec.load_metric("device.mfu_pct")

OLMO = {"n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
        "d_head": 128, "d_ff": 8192, "vocab": 50304, "attention": "gqa"}
MINICPM = {"n_layers": 24, "d_model": 2560, "n_heads": 40, "n_kv_heads": 40,
           "d_ff": 6400, "vocab": 73448, "attention": "mla",
           "q_lora_rank": 768, "kv_lora_rank": 256, "qk_nope_dim": 64,
           "qk_rope_dim": 32, "v_head_dim": 64}
V5E = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}


def test_dense_kernel_lane_by_hand():
    # one lane over 1,000 positions, 16 heads of 128: QK^T and PV are
    # 2 * 16 * 1000 * 128 each; K and V are 1000 * 16 * 128 bf16 each, the
    # query and output 16 * 128 bf16 each
    ops, byt = DENSE.lane_work(OLMO, 1000)
    assert ops == 2 * (2 * 16 * 1000 * 128)
    assert byt == 2 * (1000 * 16 * 128 * 2) + 2 * (16 * 128 * 2)


def test_dense_kernel_int8_pool_reads_codes_and_scales():
    ops, byt = DENSE.lane_work(dict(OLMO, kv_quant=True), 1000)
    assert byt == 2 * (1000 * 16 * 128) + 2 * (1000 * 16 * 4) \
        + 2 * (16 * 128 * 2)


def test_mla_kernel_lane_by_hand():
    # scores against latent (256) and rotary key (32), 40 heads; the
    # latent-weighted sum over rank 256
    ops, byt = MLA.lane_work(MINICPM, 1000)
    assert ops == 2 * 40 * 1000 * 288 + 2 * 40 * 1000 * 256
    assert byt == 2 * (1000 * 288 + 40 * 288 + 40 * 256)


def test_decode_is_bandwidth_bound_and_summed_per_step_and_layer():
    steps = {0: [1000, 1000], 1: [500]}
    per = [DENSE.lane_work(OLMO, c) for c in (1000, 1000, 500)]
    want = 16 * ((per[0][1] + per[1][1]) / 819e9 + per[2][1] / 819e9)
    assert DENSE.bound_s(OLMO, steps, V5E) == pytest.approx(want)


def test_model_weights_by_hand():
    # olmo-1b: per layer 4 * 2048^2 attention + 3 * 2048 * 8192 MLP, and the
    # tied read-out 2048 * 50304
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert MFU.matmul_weights(OLMO) == 16 * per_layer + 2048 * 50304
    mla = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 256 * 40 * 128
           + 40 * 64 * 2560)
    assert MFU.matmul_weights(MINICPM) == \
        24 * (mla + 3 * 2560 * 6400) + 2560 * 73448


def test_model_ops_of_prefill_and_decode():
    # one request: 4 prompt positions prefilled, then decodes at contexts
    # 5 and 6 (three served tokens)
    rep = types.SimpleNamespace(results=[types.SimpleNamespace(
        prompt_len=4, tokens=np.zeros(7), admitted_at=0.0, shared_prefix=0)])
    per_tok = 2 * MFU.matmul_weights(OLMO)
    per_ctx = 16 * 2 * 16 * 256
    want = 6 * per_tok + per_ctx * (1 + 2 + 3 + 4 + 5 + 6)
    assert MFU.model_ops(OLMO, rep) == pytest.approx(want)


def test_roofline_reader_on_a_hand_built_window():
    rep = types.SimpleNamespace(results=[types.SimpleNamespace(
        prompt_len=999, tokens=np.zeros(1001), admitted_at=0.0,
        shared_prefix=0)])
    bound = DENSE.bound_s(OLMO, {0: [1000]}, V5E)
    run = types.SimpleNamespace(
        config=OLMO, report=rep, peaks=V5E,
        trace={"ops": {"jit_serve_step/paged_decode_dense.3": 4 * bound,
                       "jit_prefill/fusion.1": 1.0}})
    assert DENSE.read(run) == pytest.approx(25.0)
