"""The references' counts (``counts.of``) against counts made by hand at
the smoke configs."""
import json
import os

import counts
import pytest
from conftest import BENCH, smoke_doc


def test_dense_prefill_and_decode_by_hand():
    doc = smoke_doc("phi4-mini-3.8b")   # d 64, 4/2 heads of 16, d_ff 128, V 256, 2 layers
    z = counts.of(doc, 0)
    b, s = 3, 10
    qkvo = 64 * 64 + 2 * 64 * 32 + 64 * 64            # wq, wk, wv, wo
    mlp = 3 * 64 * 128
    attn = 4 * b * 4 * 16 * (s * (s + 1) // 2)          # causal QK^T and PV
    per_layer = 2 * b * s * (qkvo + mlp) + attn
    head = 2 * b * 64 * 256                             # last position only
    assert z.prefill_flops(b, s) == 2 * per_layer + head
    ctx = 12
    dec = 2 * (2 * b * (qkvo + mlp) + 4 * b * 4 * 16 * ctx) + 2 * b * 64 * 256
    assert z.decode_flops(b, ctx) == dec
    weights = 2 * (qkvo + mlp) + 64 * 256               # tied head read once
    kv = 2 * (2 * b * 2 * 16 * (ctx + 1) * 2)           # per layer, 2 layers
    assert z.decode_bytes(b, ctx) == 2 * weights + kv


def test_rwkv_prefill_and_decode_by_hand():
    doc = smoke_doc("rwkv6-1.6b")       # d 64, heads of 16 (4), d_ff 128, V 256
    z = counts.of(doc, 0)
    b, s = 2, 7
    tm = 5 * 64 * 64 + 2 * 64 * 64                      # r k v g o + decay LoRA
    cm = 2 * 64 * 128 + 64 * 64
    wkv = 7 * b * s * 4 * 16 * 16
    per_layer = 2 * b * s * (tm + cm) + wkv
    assert z.prefill_flops(b, s) == 2 * per_layer + 2 * b * 64 * 256
    state = 2 * b * 4 * 16 * 16 * 4                     # f32 read + write
    weights = 2 * (tm + cm) + 64 * 256                  # untied head
    assert z.decode_bytes(b, 99) == 2 * weights + 2 * state


def test_ladder_level_changes_width_and_depth():
    with open(os.path.join(BENCH, "configs", "phi4-mini-3.8b.json")) as f:
        doc = json.load(f)
    deep, full = counts.of(doc, 5), counts.of(doc, 0)
    assert (deep.d_ff, deep.layers) == (2816, 24)
    assert (full.d_ff, full.layers) == (8192, 32)
    # level 0 prefill of one share, B=8, S=512: 26.8 TFLOP of matmuls
    assert 26.5e12 < full.prefill_flops(8, 512) < 27e12


def test_decode_bound_names_the_binding_side():
    doc = smoke_doc("phi4-mini-3.8b")
    z = counts.of(doc, 0)
    assert counts.decode_bound_s(z, 8, 100, 1e30, 1.0)["bound"] == "hbm"
    assert counts.decode_bound_s(z, 8, 100, 1.0, 1e30)["bound"] == "compute"


def test_unknown_reference_is_refused():
    with pytest.raises(ValueError):
        counts.of({"reference": "nope", "ladder": [{}]}, 0)
    with pytest.raises(ValueError):     # a module of the package, no counts
        counts.of({"reference": "numerics", "ladder": [{}]}, 0)
