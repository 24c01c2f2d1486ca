"""FLOPs/token against a hand count, for both train configurations."""
import json
import os

import pytest

from lib import flops, manifest

D, F, H, KV, HD, V = 4096, 14336, 32, 8, 128, 32000
ATTN = D * (H + 2 * KV) * HD + H * HD * D           # q, k, v, o
MLP = 3 * D * F                                     # gate, up, down


def config(name):
    with open(os.path.join(manifest.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_depth2_by_hand():
    sizes = config("mistral-7b-v0.1-depth2")
    weights = 2 * (ATTN + MLP) + D * V
    assert ATTN == 41_943_040 and MLP == 176_160_768
    assert flops.matmul_params_active(sizes) == weights == 567_279_616
    attention = 2 * 4 * H * HD * (2048 + 1) / 2      # 2 layers, QK^T and PV
    assert flops.train_flops_per_token(sizes, 2048) == pytest.approx(
        3 * (2 * weights + attention))
    assert flops.train_flops_per_token(sizes, 2048) == pytest.approx(
        3.504e9, rel=1e-3)
    # embedding and head, two norms a layer, the final norm
    assert flops.param_count(sizes) == 698_372_096


def test_mixtral_depth1_by_hand_counts_active_experts_only():
    sizes = config("mixtral-8x7b-v0.1-depth1")
    weights = ATTN + 2 * MLP + D * 8 + D * V         # top-2 of 8 experts
    assert flops.matmul_params_active(sizes) == weights == 525_369_344
    attention = 4 * H * HD * (2048 + 1) / 2
    assert flops.train_flops_per_token(sizes, 2048) == pytest.approx(
        3 * (2 * weights + attention))
    assert flops.param_count(sizes) == ATTN + 8 * MLP + D * 8 + 2 * D + D \
        + 2 * V * D


def test_flash_and_decode_bytes():
    sizes = dict(config("mistral-7b-v0.1-depth16"), num_hidden_layers=1)
    # one layer, batch 4 x 2048: 4 * heads * hd * S * (S + 1) / 2 per row
    assert flops.flash_fwd_flops(sizes, 4, 2048) == pytest.approx(
        4 * 4 * H * HD * 2048 * 2049 / 2)
    assert flops.flash_bwd_flops(sizes, 4, 2048) == pytest.approx(
        2.5 * flops.flash_fwd_flops(sizes, 4, 2048))
    # a cached token holds K and V of 8 heads x 128 in bf16: 4096 bytes
    assert flops.kv_row_bytes(sizes) == 4096
    assert flops.decode_attention_bytes(sizes, 64 * 800) == 64 * 800 * 4096
