"""The count-based readers and ``counts.decode_bound_s`` on fixed hand-made
contexts of the configuration files as served, against the numbers they
read before the counts moved into the references: equal to the last bit."""
import importlib
import json
import os
import types

import counts
import peaks
import pytest

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
READERS = ("prefill_mfu", "decode_roofline", "window_mfu")
CASES = {"phi4-mini-3.8b": (0, 5, 5, 0), "rwkv6-1.6b": (0, 4, 2, 0)}
BATCH, CONTEXT = 8, 517

GOLDEN = {      # as read before the counts moved into the references
    "phi4-mini-3.8b": {
        "prefill_mfu": 33.432505319454684,
        "decode_roofline": 39.73161384766776,
        "window_mfu": 35.8792480989285,
        "bound": {0: (0.0100302888009768, "hbm"),
                  5: (0.004994179282051282, "hbm")},
    },
    "rwkv6-1.6b": {
        "prefill_mfu": 16.721772406082117,
        "decode_roofline": 17.291121734809067,
        "window_mfu": 15.206694301312835,
        "bound": {0: (0.0037846039755799754, "hbm"),
                  2: (0.0032622364444444443, "hbm"),
                  4: (0.002205978568986569, "hbm")},
    },
}


def context(name):
    """Two requests of two shares each, at the case's levels, with fixed
    served rows and seconds; the first request is the traced one."""
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        doc = json.load(f)
    shares = [types.SimpleNamespace(level=lv, served=(8, 3, 8, 5)[i],
                                    prefill_s=0.2 + 0.013 * i,
                                    decode_step_s=0.016 + 0.0017 * i)
              for i, lv in enumerate(CASES[name])]
    records = [types.SimpleNamespace(shares=shares[:2]),
               types.SimpleNamespace(shares=shares[2:])]
    return types.SimpleNamespace(
        records=records, traced=records[:1],
        trace=types.SimpleNamespace(window_s=0.4375), doc=doc,
        peaks=peaks.peaks_for("TPU v5 lite"), chips=1, prompt_len=512,
        decode_steps=4)


@pytest.mark.parametrize("name", sorted(CASES))
def test_readings_are_the_parents(name):
    ctx, want = context(name), GOLDEN[name]
    for reader in READERS:
        got = importlib.import_module(f"metrics.{reader}").read(ctx)
        assert got == want[reader], reader
    for level, (s, bound) in want["bound"].items():
        b = counts.decode_bound_s(counts.of(ctx.doc, level), BATCH, CONTEXT,
                                  ctx.peaks.bf16_flops,
                                  ctx.peaks.hbm_bytes_per_s)
        assert (b["s"], b["bound"]) == (s, bound), level
