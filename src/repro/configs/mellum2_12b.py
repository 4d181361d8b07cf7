"""Mellum2-12B-A2.5B — fine-grained MoE (64 experts of 896, top 8, no
shared expert), GQA 32Q/4KV of 128, three sliding-window layers (1,024)
to each full layer, YaRN on the full layers only.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

``mellum2-12b-ep4`` is what one chip serves of an expert-parallel
deployment over 4 chips: experts 0-15 of every layer (the router keeps
its 64 outputs and top 8) and two whole periods of the layer pattern,
8 of 28 layers."""
import dataclasses

from repro.configs.base import ArchConfig, MoECfg, YaRN, register

CFG = register(ArchConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv=4, d_head=128,
    d_ff=7168, vocab=98304, window=1024, local_global_period=4,
    rope_theta=500_000.0,
    yarn=YaRN(factor=16.0, original_max_position=8192, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.2772588722239782),
    moe=MoECfg(n_experts=64, top_k=8, d_expert=896),
    tie_embeddings=False,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct"))

EP4 = register(dataclasses.replace(
    CFG, name="mellum2-12b-ep4", n_layers=8,
    moe=dataclasses.replace(CFG.moe, held=tuple(range(16)))))
