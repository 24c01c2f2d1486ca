"""Minimal end-to-end training example: a llama-family model through
``deepspeed_tpu.initialize`` with ZeRO-3, bf16, warmup LR, and checkpointing.

Runs on one TPU chip or on the CPU-sim mesh:

    # 8 simulated devices (no TPU needed)
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_llama.py

DeepSpeed users: the config dict below is DeepSpeed-JSON compatible — a
``ds_config.json`` loads unchanged via ``config="ds_config.json"``.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--save", type=str, default="")
    args = ap.parse_args()

    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=args.hidden,
        intermediate_size=args.hidden * 11 // 4, num_layers=args.layers,
        num_heads=max(args.hidden // 64, 1),
        num_kv_heads=max(args.hidden // 128, 1),
        max_seq_len=args.seq, remat=True,
        use_flash=jax.default_backend() == "tpu")
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    print(f"model: {model.num_params()/1e6:.1f}M params, "
          f"{len(jax.devices())} device(s)")

    engine, _, _, scheduler = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": args.batch,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 3e-4, "weight_decay": 0.1}},
            "scheduler": {"type": "WarmupLR",
                          "params": {"warmup_max_lr": 3e-4,
                                     "warmup_num_steps": 10}},
            "zero_optimization": {"stage": 3},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
        })

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size,
                         size=(engine.train_batch_size(), args.seq)),
            jnp.int32)}
        loss = engine.train_batch(batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss):.4f}")

    if args.save:
        engine.save_checkpoint(args.save, tag="final")
        print(f"checkpoint saved to {args.save}")


if __name__ == "__main__":
    main()
