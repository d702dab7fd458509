"""The MLA/MoE reference's counts against counts made by hand at the smoke
sizes, and one level-0 share of the served file."""
import json
import os

import counts
import pytest
from conftest import BENCH, smoke_doc

ARCH = "deepseek-v3-ep32"


def test_prefill_and_decode_by_hand():
    doc = smoke_doc(ARCH)   # d 64, 4 heads, q 32, kv 16, nope 16, rope 8, v 16
    assert (doc["hidden_size"], doc["router_experts"],
            doc["n_routed_experts"], doc["num_hidden_layers"]) == (64, 64, 2, 4)
    z = counts.of(doc, 0)
    b, s = 2, 10
    t = b * s
    mla = (64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * (16 + 16)
           + 4 * 16 * 64)
    attn = 2 * b * 4 * (24 + 16) * (s * (s + 1) // 2)
    dense = 3 * 64 * 128
    expert = 3 * 64 * 256
    routed = t * 8 * 2 / 64                   # rows expected on this chip
    moe = 2 * t * (expert + 64 * 64) + 2 * routed * expert
    head = 2 * b * 64 * 256
    assert z.prefill_flops(b, s) == pytest.approx(
        4 * (2 * t * mla + attn) + 2 * t * dense + 3 * moe + head)
    ctx = 12
    attn_d = 2 * b * 4 * (2 * 16 + 8) * ctx     # latent scores, rope, values
    moe_d = 2 * b * (expert + 64 * 64) + 2 * (b * 8 * 2 / 64) * expert
    assert z.decode_flops(b, ctx) == pytest.approx(
        4 * (2 * b * mla + attn_d) + 2 * b * dense + 3 * moe_d + head)
    touched = 2 * (1 - (1 - 8 / 64) ** b)
    weights = 4 * mla + dense + 3 * (expert + 64 * 64 + touched * expert) \
        + 64 * 256
    cache = 4 * b * (16 + 8) * (ctx + 1)
    assert z.decode_bytes(b, ctx) == pytest.approx(2 * (weights + cache))


def test_level0_share_of_the_served_file():
    with open(os.path.join(BENCH, "configs", f"{ARCH}.json")) as f:
        doc = json.load(f)
    z = counts.of(doc, 0)
    # 8 prompts of 512 tokens: 21.6 TFLOP, of which the routed experts
    # (128 rows per held expert a layer) are 0.7
    assert 21.4e12 < z.prefill_flops(8, 512) < 21.8e12
    # a decode step of 8 rows streams 6.4 GB: every weight but the held
    # experts, of which the 1.8 that 8 rows are expected to touch
    assert 6.3e9 < z.decode_bytes(8, 517) < 6.5e9
    # the deepest level: 7 of 9 layers, narrower MLPs, MLA as it was
    deep = counts.of(doc, 5)
    assert 0.6 < deep.prefill_flops(8, 512) / z.prefill_flops(8, 512) < 0.7
