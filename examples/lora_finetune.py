"""LoRA finetune → merge → generate (ref: deepspeed/linear
LoRAOptimizedLinear + the DeepSpeed-Chat LoRA finetuning recipe).

Only the low-rank adapters train: the engine's optimizer state, ZeRO
sharding, and checkpoints are adapter-sized, while the frozen base
weights ride inside the jitted step as device constants.

Run (any backend; sized for the 8-device CPU mesh or one TPU chip):
    python examples/lora_finetune.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

import deepspeed_tpu as dstpu
from deepspeed_tpu.inference.generation import generator
from deepspeed_tpu.lora import (LoRAConfig, count_trainable, init_lora,
                                lora_loss_fn, merge_lora)
from deepspeed_tpu.models import llama


def main():
    cfg = llama.LlamaConfig.tiny()
    base = llama.init_params(jax.random.PRNGKey(0), cfg)
    lcfg = LoRAConfig(lora_r=8, lora_alpha=16,
                      target_modules=("wq", "wk", "wv", "wo",
                                      "w1", "w2", "w3"))
    adapters = init_lora(jax.random.PRNGKey(1), base, lcfg)
    n_ad, _ = count_trainable(adapters)
    print(f"trainable adapters: {n_ad:,} params "
          f"({n_ad / llama.param_count(cfg):.1%} of the base model)")

    engine, _, _, _ = dstpu.initialize(
        loss_fn=lora_loss_fn(llama.loss_fn(cfg), base, lcfg),
        params=adapters,
        config={"train_micro_batch_size_per_gpu": 1,
                "zero_optimization": {"stage": 2},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}}})

    # "finetune data": one fixed batch (sized to the engine's resolved
    # global batch) that the adapters memorize
    B = engine.train_batch_size
    seq = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, 25)), jnp.int32)
    for step in range(120):
        loss = engine.train_batch({"tokens": seq})
        if step % 30 == 0 or step == 119:
            print(f"step {step:3d}: loss {float(loss):.4f}")

    merged = merge_lora(base, engine.module_params(), lcfg)
    gen = generator(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), merged), cfg)
    out = gen.generate(seq[:, :8], max_new_tokens=17, temperature=0.0)
    agree = float((np.asarray(out)[:, 8:] == np.asarray(seq)[:, 8:]).mean())
    print(f"merged model reproduces the finetune data: {agree:.0%}")


if __name__ == "__main__":
    main()
