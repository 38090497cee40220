"""Work functions against hand-worked numbers."""

import pytest

from benchmark import run as brun
from benchmark.reference import gpt_ref, llama_ref
from benchmark.work import transformer as w

MISTRAL = brun.load_json("configs", "mistral-7b.json")
GPT = brun.load_json("configs", "gpt3-6.7b.json")


def test_matmul_parameters():
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert w.matmul_params(MISTRAL, 1) - 4096 * 32000 == 218_103_808
    assert w.matmul_params(MISTRAL, 2) == 567_279_616
    # qkv 4096x12288, out 4096x4096, two 4096x16384; tied head 4096x50304
    assert w.matmul_params(GPT, 2) == 608_698_368
    # the references count the same
    assert llama_ref.matmul_params(MISTRAL, 2) == 567_279_616
    assert gpt_ref.matmul_params(GPT, 2) == 608_698_368


def test_train_flops():
    per_token = w.train_flops_per_token(MISTRAL, 2, 4096)
    assert per_token == 6 * 567_279_616 + 6 * 2 * 4096 * 4096
    assert per_token * 8192 == pytest.approx(29.53e12, rel=1e-3)
    assert w.train_flops_per_token(GPT, 2, 2048) * 8192 == \
        pytest.approx(30.74e12, rel=1e-3)


def test_attention_and_adamw():
    # forward only: 2 products x 2 flops x B S^2 (H D) / 2 (causal)
    assert w.causal_attention_flops(MISTRAL, 1, 1, 4096, False) == \
        2 * 2 * 4096 * 4096 * 4096 / 2
    assert w.adamw_bytes(10) == 280
    assert w.all_params(MISTRAL, 2) == 567_279_616 + 4096 * 32000
    assert w.kv_bytes_per_token(MISTRAL, 16) == 65536
    t, bound = w.roofline_seconds(197e12, 819e9 * 2, {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert (t, bound) == (2.0, "bytes")


def test_peaks_unknown_kind_raises():
    assert brun.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        brun.peaks_of("cpu")
