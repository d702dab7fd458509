"""Operations and bytes of the work the served path does, from shapes.

Counted is what the algorithm needs, not what the program happens to
execute: causal attention over the valid positions only, the output head on
the last prompt position only (the served path computes logits for that
position alone), weights streamed once per decode step, K/V or recurrent
state read for the valid context only.

What a configuration's level counts is its reference's business
(``reference/<doc["reference"]>.py`` defines ``counts(doc, level)``), and
``of`` looks it up. Here are the shared pieces the references build from:
``Sizes``, with one function per mixer kind and one per MLP kind, and
``decode_bound_s``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
from typing import Dict

WEIGHT_BYTES = 2        # bf16 weights and K/V cache
STATE_BYTES = 4         # the RWKV6 WKV state is float32


def of(doc: Dict, level: int):
    """The counts of one ladder level of a configuration file: an object
    with ``prefill_flops(batch, seq)``, ``decode_flops(batch, context)`` and
    ``decode_bytes(batch, context)``, made by the file's reference."""
    name = f"reference.{doc['reference']}"
    ref = importlib.import_module(name) \
        if importlib.util.find_spec(name) is not None else None
    if not hasattr(ref, "counts"):
        raise ValueError(f"no counts for reference {doc['reference']!r}")
    return ref.counts(doc, level)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One level of a stack of like layers, a mixer and an MLP each, under
    an output head."""
    mixer: str           # "gqa" | "wkv" (RWKV6 time mix)
    mlp: str             # "swiglu" | "gelu" | "channel_mix" (RWKV6)
    layers: int
    d_model: int
    d_ff: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    tied: bool
    lora: int = 64       # RWKV6 decay LoRA rank

    # prefill: B prompts of S tokens
    def prefill_flops(self, batch: int, seq: int) -> float:
        tokens = batch * seq
        per_layer = 2.0 * tokens * (mixer_params(self) + mlp_params(self))
        if self.mixer == "gqa":
            per_layer += attention_flops(self, batch, seq)
        else:
            per_layer += wkv_flops(self, batch, seq)
        head = 2.0 * batch * head_params(self)          # last position only
        return self.layers * per_layer + head

    # decode: one step of B rows, each with ``context`` positions valid
    # after the new token is written
    def decode_flops(self, batch: int, context: float) -> float:
        per_layer = 2.0 * batch * (mixer_params(self) + mlp_params(self))
        if self.mixer == "gqa":
            per_layer += 4.0 * batch * self.heads * self.head_dim * context
        else:
            per_layer += wkv_flops(self, batch, 1)
        return self.layers * per_layer + 2.0 * batch * head_params(self)

    def decode_bytes(self, batch: int, context: float) -> float:
        weights = self.layers * (mixer_params(self) + mlp_params(self)) \
            + head_params(self)
        if self.mixer == "gqa":
            # read the valid K and V, write the new token's
            state = 2.0 * batch * self.kv_heads * self.head_dim \
                * (context + 1) * WEIGHT_BYTES
        else:
            # read and write the WKV state
            state = 2.0 * batch * self.heads * self.head_dim \
                * self.head_dim * STATE_BYTES
        return WEIGHT_BYTES * weights + self.layers * state


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
def attention_flops(z: Sizes, batch: int, seq: int) -> float:
    """Causal QK^T and PV over the valid (lower-triangle) positions."""
    pairs = seq * (seq + 1) / 2
    return 4.0 * batch * z.heads * z.head_dim * pairs


def wkv_flops(z: Sizes, batch: int, seq: int) -> float:
    """RWKV6 recurrence per token and head: k v^T (1), s + u k v^T (2),
    r (s + u k v^T) (2), w s + k v^T (2) operations per state element."""
    return 7.0 * batch * seq * z.heads * z.head_dim * z.head_dim


def decode_bound_s(c, batch: int, context: float,
                   peak_flops: float, peak_bw: float) -> Dict[str, float]:
    """Least time of one decode step of the counts ``c`` (what ``of``
    returns), and which bound sets it."""
    t_c = c.decode_flops(batch, context) / peak_flops
    t_m = c.decode_bytes(batch, context) / peak_bw
    return {"s": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "hbm"}
