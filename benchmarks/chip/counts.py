"""Operations and bytes of the work the served path does, from shapes.

Counted is what the algorithm needs, not what the program happens to
execute: causal attention over the valid positions only, the output head on
the last prompt position only (the served path computes logits for that
position alone), weights streamed once per decode step, K/V or recurrent
state read for the valid context only. One function per mixer kind and one
per MLP kind; ``Sizes`` holds one ladder level of a configuration file.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

WEIGHT_BYTES = 2        # bf16 weights and K/V cache
STATE_BYTES = 4         # the RWKV6 WKV state is float32


@dataclasses.dataclass(frozen=True)
class Sizes:
    mixer: str           # "gqa" | "rwkv6"
    mlp: str             # "swiglu" | "gelu" | "rwkv6_channel_mix"
    layers: int
    d_model: int
    d_ff: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    tied: bool
    lora: int = 64       # RWKV6 decay LoRA rank


def sizes(doc: Dict, level: int) -> Sizes:
    """One ladder level of a configuration file."""
    lv = doc["ladder"][level]
    arch = doc["reference"]
    if arch == "dense_gqa":
        return Sizes(mixer="gqa", mlp={"silu": "swiglu"}.get(
                         doc["hidden_act"], "gelu"),
                     layers=lv["num_hidden_layers"],
                     d_model=doc["hidden_size"],
                     d_ff=lv["intermediate_size"], vocab=doc["vocab_size"],
                     heads=doc["num_attention_heads"],
                     kv_heads=doc["num_key_value_heads"],
                     head_dim=doc["head_dim"],
                     tied=doc["tie_word_embeddings"])
    if arch == "rwkv6":
        hs = doc["head_size"]
        return Sizes(mixer="rwkv6", mlp="rwkv6_channel_mix",
                     layers=lv["num_hidden_layers"],
                     d_model=doc["hidden_size"],
                     d_ff=lv["intermediate_size"], vocab=doc["vocab_size"],
                     heads=doc["attention_hidden_size"] // hs,
                     kv_heads=doc["attention_hidden_size"] // hs,
                     head_dim=hs, tied=doc["tie_word_embeddings"],
                     lora=doc["time_decay_extra_dim"])
    raise ValueError(f"no counts for reference {arch!r}")


# ----------------------------------------------------------------------
# parameters (weights streamed per decode step)
def mixer_params(z: Sizes) -> int:
    d = z.d_model
    if z.mixer == "gqa":
        return d * z.heads * z.head_dim * 2 + d * z.kv_heads * z.head_dim * 2
    # r, k, v, g, o projections and the decay LoRA
    return 5 * d * d + 2 * d * z.lora


def mlp_params(z: Sizes) -> int:
    d, f = z.d_model, z.d_ff
    if z.mlp == "swiglu":
        return 3 * d * f
    if z.mlp == "gelu":
        return 2 * d * f
    return 2 * d * f + d * d          # channel mix: k, v, r


def head_params(z: Sizes) -> int:
    return z.d_model * z.vocab


# ----------------------------------------------------------------------
# prefill: B prompts of S tokens
def attention_flops(z: Sizes, batch: int, seq: int) -> float:
    """Causal QK^T and PV over the valid (lower-triangle) positions."""
    pairs = seq * (seq + 1) / 2
    return 4.0 * batch * z.heads * z.head_dim * pairs


def wkv_flops(z: Sizes, batch: int, seq: int) -> float:
    """RWKV6 recurrence per token and head: k v^T (1), s + u k v^T (2),
    r (s + u k v^T) (2), w s + k v^T (2) operations per state element."""
    return 7.0 * batch * seq * z.heads * z.head_dim * z.head_dim


def prefill_flops(z: Sizes, batch: int, seq: int) -> float:
    tokens = batch * seq
    per_layer = 2.0 * tokens * (mixer_params(z) + mlp_params(z))
    if z.mixer == "gqa":
        per_layer += attention_flops(z, batch, seq)
    else:
        per_layer += wkv_flops(z, batch, seq)
    head = 2.0 * batch * head_params(z)          # last position only
    return z.layers * per_layer + head


# ----------------------------------------------------------------------
# decode: one step of B rows, each with ``context`` positions valid after
# the new token is written
def decode_flops(z: Sizes, batch: int, context: float) -> float:
    per_layer = 2.0 * batch * (mixer_params(z) + mlp_params(z))
    if z.mixer == "gqa":
        per_layer += 4.0 * batch * z.heads * z.head_dim * context
    else:
        per_layer += wkv_flops(z, batch, 1)
    return z.layers * per_layer + 2.0 * batch * head_params(z)


def decode_bytes(z: Sizes, batch: int, context: float) -> float:
    weights = z.layers * (mixer_params(z) + mlp_params(z)) + head_params(z)
    if z.mixer == "gqa":
        # read the valid K and V, write the new token's
        state = 2.0 * batch * z.kv_heads * z.head_dim * (context + 1) \
            * WEIGHT_BYTES
    else:
        # read and write the WKV state
        state = 2.0 * batch * z.heads * z.head_dim * z.head_dim * STATE_BYTES
    return WEIGHT_BYTES * weights + z.layers * state


def decode_bound_s(z: Sizes, batch: int, context: float,
                   peak_flops: float, peak_bw: float) -> Dict[str, float]:
    """Least time of one decode step, and which bound sets it."""
    t_c = decode_flops(z, batch, context) / peak_flops
    t_m = decode_bytes(z, batch, context) / peak_bw
    return {"s": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "hbm"}
