"""The JoyAI-LLM-Flash training cell without the chip: the FLOP/byte
functions against a hand count at the published widths, the configuration's
cut, and the new generator rehearsed on the CPU (toy widths, the same control
flow: build, the reference before and after the first step, the window, the
program's own counters at close)."""
import json
import os
import subprocess
import sys

import pytest

from lib import flops_mla_train as flops
from lib import joyai_system, manifest

MAN = manifest.manifest()
CELL = "joyaiflash-train-mtp-1chip"
CONFIG = manifest.config_of(MAN, manifest.cell(MAN, CELL)["config"])
Z = joyai_system.sizes_of(CONFIG, rehearsal=False)


def test_parameters_by_hand():
    """ISSUE 64's table: MLA 26.35 M a layer; dense layer 70.4 M; an expert
    layer with 16 held 107.1 M; MTP 115.5 M; embedding + head 66.2 M; in all
    680.5 M (norms: 0.03 M)."""
    mla = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert flops.mla_params(Z) == mla == 26_345_472
    assert flops.expert_params(Z) == 3 * 2048 * 768 == 4_718_592
    norms = 2 * 2048 + 1536 + 512
    dense = mla + 3 * 2048 * 7168 + norms
    expert_layer = mla + 2048 * 256 + 17 * 4_718_592 + norms
    mtp = expert_layer + 2 * 2048 * 2048 + 3 * 2048
    total = dense + 4 * expert_layer + mtp + 2048 + 2 * 16160 * 2048
    assert flops.param_count(Z) == total
    assert round(total / 1e6, 1) == 680.4
    assert round(dense / 1e6, 1) == 70.4
    assert round(expert_layer / 1e6, 1) == 107.1
    assert round(mtp / 1e6, 1) == 115.5


def test_the_program_holds_what_the_count_says():
    import jax

    model = joyai_system.model_of(Z, 4096)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == flops.param_count(Z)


def test_flops_a_token_by_hand():
    """Forward matmul weights a token meets with 8 x 1/16 = 0.5 pairs a
    layer computed here; attention at (192 + 128) x 32 heads over 2048.5
    positions in 6 layers; x 3 for the backward."""
    mla = flops.mla_params(Z)
    per_expert_layer = mla + 2048 * 256 + 4_718_592 * (1 + 0.5)
    weights = (mla + 3 * 2048 * 7168) + 5 * per_expert_layer \
        + 2 * 2048 * 2048 + 2 * 2048 * 16160
    assert flops.matmul_params_active(Z, 0.5) == weights
    attention = 6 * 2 * 32 * 320 * 4097 / 2
    assert flops.attention_flops_per_token(Z, 4096) == attention
    per_token = flops.train_flops_per_token(Z, 4096, 0.5)
    assert per_token == 3 * (2 * weights + attention)
    # ISSUE 64: ~2.65 GFLOPs a token (0.63 matmul + 0.25 attention, x 3)
    assert 2.5e9 < per_token < 2.8e9
    assert round(2 * weights / 1e9, 2) == 0.63
    assert round(attention / 1e9, 2) == 0.25


def test_flash_flops_and_bytes_by_hand():
    rows, seq = 2, 4096
    fwd = 2 * 32 * (192 + 128) * 4097 / 2 * rows * seq
    assert flops.flash_fwd_flops(Z, rows, seq) == fwd
    bwd = 2 * 32 * (3 * 192 + 2 * 128) * 4097 / 2 * rows * seq
    assert flops.flash_bwd_flops(Z, rows, seq) == bwd
    assert bwd / fwd == 2.6                      # 832 / 320
    assert flops.flash_bytes(Z, rows, seq) \
        == rows * seq * 32 * (192 + 192 + 128 + 128) * 2
    assert flops.flash_bytes(Z, rows, seq, backward=True) \
        == rows * seq * 32 * (4 * 192 + 3 * 128) * 2


def test_the_configuration_states_its_cut():
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert CONFIG["n_routed_experts"] * 16 == CONFIG["router_outputs"] == 256
    assert CONFIG["vocab_size"] * 8 == 129280
    assert CONFIG["num_hidden_layers"] == 1 + 4      # dense + four expert
    assert CONFIG["num_nextn_predict_layers"] == 1   # the MTP module is held
    for key in ("mtp_loss_weight", "bias_update_rate"):
        assert key in CONFIG["assumed"]
    traffic = manifest.traffic_of("train-2x4096")
    like = manifest.traffic_of("train-4x2048")
    assert traffic["ds_config"] == like["ds_config"]
    assert traffic["seq_len"] * traffic["micro_batch_per_chip"] == 8192
    assert traffic["model_options"] == {"remat": True}


def test_the_system_module_refuses_a_program_without_the_model(monkeypatch,
                                                               tmp_path):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit, match="joyai_flash"):
        joyai_system.require()


def test_the_generator_rehearsed_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH, "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 77), "--seconds", "2",
         "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    checks = line["checks"]
    # toy widths: the verdict's limits are the real cell's, so only what
    # does not depend on bf16 noise at 64-wide layers is held here
    assert checks["loss_abs_diff"] < 0.01
    assert checks["bias_sign_unexplained"] == 0
    assert checks["pairs_computed"] == checks["pairs_routed_to_held"] > 0
    assert checks["main_logits_rel_l2_median"] < 0.05
    assert checks["mtp_logits_rel_l2_median"] < 0.05
    facts = line["facts"]
    assert facts["compiles_in_window"] == 0
    assert facts["tokens_per_step"] == 2 * 128
    also = line["also"]
    assert 0.1 < also["moe_pairs_held_share.train"]["value"] < 0.5
    assert 0 < also["moe_load_max_share.train"]["value"] <= 1
