"""Operation and byte counts against numbers worked by hand for both
configurations (24 layers, d 2048, 16 heads of 128, feed-forward 8192)."""

import os

import pytest

from harness import costs, env, peaks

CFG = os.path.join(env.BENCH_DIR, "configs")
SC = env.load_json(os.path.join(CFG, "starcoderbase-1b.json"))
CG = env.load_json(os.path.join(CFG, "cerebras-gpt-1.3b.json"))


def test_block_weights():
    # MHA: qkv 2048 x 6144, out 2048 x 2048, MLP 2 x 2048 x 8192
    assert costs.block_params(CG) == 12_582_912 + 4_194_304 + 33_554_432
    # MQA: one KV head of 128 -> qkv 2048 x (2048 + 256)
    assert costs.block_params(SC) == 4_718_592 + 4_194_304 + 33_554_432


def test_totals_as_built():
    # Cerebras-GPT-1.3B, untied: 1.42 B; 1.31 B of it multiplies a token
    assert costs.matmul_params(CG) == 24 * 50_331_648 + 2048 * 50257
    assert costs.matmul_params(CG) == pytest.approx(1.311e9, rel=1e-3)
    assert costs.total_params(CG) == pytest.approx(1.419e9, rel=2e-3)
    # StarCoderBase-1B, untied: 1.24 B (published, tied: 1.137 B)
    assert costs.total_params(SC) == pytest.approx(1.238e9, rel=2e-3)
    assert costs.total_params(SC) - 2048 * 49152 == pytest.approx(1.137e9, rel=3e-3)


def test_train_flops_per_token():
    # 6 x 1.311 B + causal attention 6 x 24 x 2048 x 2048 = 7.866 + 0.604 G
    f = costs.train_flops_per_token(CG, 2048)
    assert f == 6 * costs.matmul_params(CG) + 603_979_776
    assert f == pytest.approx(8.47e9, rel=2e-3)


def test_kv_bytes_per_token():
    assert costs.kv_bytes_per_token(SC) == 2 * 24 * 1 * 128 * 4 == 24_576
    assert costs.kv_bytes_per_token(CG) == 2 * 24 * 16 * 128 * 4 == 393_216
    # sixteen slots of 8192 tokens: the 3.2 GB the decode step gathers
    assert 16 * 8192 * costs.kv_bytes_per_token(SC) == pytest.approx(3.22e9, rel=1e-2)


def test_decode_flops():
    f = costs.decode_flops_per_token(SC, 4096)
    assert f == 2 * costs.matmul_params(SC) + 4 * 24 * 4096 * 2048


def test_flash_forward_call_and_its_roofline():
    # one data-parallel replica of the training cell: (2, 2048, 8 heads, 128)
    c = costs.flash_fwd_call(2, 8, 2048, 128)
    assert c["flops"] == 4 * 2 * 8 * 2048 * 2048 * 128 / 2
    assert c["bytes"] == 4 * 2 * 8 * 2048 * 128 * 2 + 4 * 2 * 8 * 2048
    r = costs.roofline_s(c["flops"], c["bytes"], peaks.peak("TPU v5 lite"))
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(c["flops"] / 197e12)
    tiny = costs.roofline_s(1e6, 1e9, peaks.peak("TPU v5 lite"))
    assert tiny["bound"] == "memory" and tiny["seconds"] == pytest.approx(1e9 / 819e9)


def test_peaks_have_no_default():
    assert peaks.peak("TPU v5 lite").bf16_flops == 197e12
    assert peaks.peak("TPU v5e").hbm_bytes_s == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9")
