"""deepseek-v3-ep32 — one chip's share of DeepSeek-V3 in an expert-parallel
unit of 32 chips. [arXiv:2412.19437; hf; DeepSeek-V3/R1 inference system
overview, github.com/deepseek-ai/open-infra-index]

In DeepSeek's EP32 prefill unit, 32 chips share each MoE layer's 256 routed
experts, 8 to a chip, and MLA and the shared expert are data-parallel. This
chip holds experts 0-7 and the shared expert of each MoE layer, and routes
every token over all 256 with the published scoring. Every width is as
published. Cut: 1 dense layer and 8 MoE layers (the layers left out would
lie on further chips, as pipeline stages), 1/8 of the vocabulary (16160 of
129280 rows), no multi-token-prediction layer. About 5.5 B parameters,
11.0 GB in bf16.
"""
import dataclasses

from repro.configs import deepseek_v3_671b as full

CONFIG = full.CONFIG.scaled(
    name="deepseek-v3-ep32",
    num_layers=9,
    num_dense_layers=1,
    vocab_size=full.CONFIG.vocab_size // 8,
    mtp_depth=0,
    moe=dataclasses.replace(full.CONFIG.moe, first_moe_layer=1,
                            held_experts=8),
)

# the published routing shape (8 groups, 4 kept, top-8) and share of the
# router's outputs held (1/32: 2 of 64), at smoke widths
SMOKE = full.SMOKE.scaled(
    name="deepseek-v3-ep32",
    mtp_depth=0,
    moe=dataclasses.replace(CONFIG.moe, num_experts=64, held_experts=2,
                            d_ff_expert=full.SMOKE.moe.d_ff_expert),
)
