"""Shared helpers of the yardstick's tests: the benchmark's directory on
``sys.path`` and configuration documents for the program's smoke configs
(the CPU stands in for the chip; no number here is a chip number)."""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import entry  # noqa: E402


def smoke_doc(arch: str) -> dict:
    """The configuration file of ``arch`` with the program's smoke sizes in
    place of the published ones, and the smoke ladder."""
    from repro.configs import get_smoke_config
    smoke = get_smoke_config(arch)
    with open(os.path.join(BENCH, "configs", f"{arch}.json")) as f:
        doc = copy.deepcopy(json.load(f))
    if doc["reference"] == "dense_gqa":
        doc.update(hidden_size=smoke.d_model, num_hidden_layers=smoke.num_layers,
                   num_attention_heads=smoke.num_heads,
                   num_key_value_heads=smoke.num_kv_heads,
                   head_dim=smoke.head_dim, intermediate_size=smoke.d_ff,
                   vocab_size=smoke.vocab_size)
    else:
        doc.update(hidden_size=smoke.d_model, num_hidden_layers=smoke.num_layers,
                   attention_hidden_size=smoke.d_model,
                   head_size=smoke.ssm.wkv_head_dim,
                   intermediate_size=smoke.d_ff, vocab_size=smoke.vocab_size)
    pool = entry.VariantPool(smoke)
    doc["ladder"] = [{"level": v.level, "intermediate_size": v.config.d_ff,
                      "num_hidden_layers": v.config.num_layers,
                      "accuracy": v.accuracy} for v in pool.variants]
    return doc


@pytest.fixture(params=["phi4-mini-3.8b", "rwkv6-1.6b"])
def arch(request):
    return request.param
