"""A new architecture enters the yardstick by new files alone.

A throwaway reference of the program's MLA/MoE smoke model
(``deepseek-v3-671b``: a dense prelude, sparse expert layers and a
multi-token-prediction block) is written to a scratch ``reference/``
directory beside the benchmark's own, with a configuration document for
it. No file of the benchmark changes, and the harness checks the served
config and ladder against it, draws its weights, walks every group of
them, and the count-based readers read it."""
import copy
import importlib
import sys
import textwrap
import types

import counts
import entry
import harness
import jax
import jax.numpy as jnp
import numpy as np
import peaks
import pytest
import reference
from conftest import smoke_doc

from repro.configs import get_smoke_config
from repro.models import transformer as tfm

ARCH = "deepseek-v3-671b"
TOY = "toy_moe"

TOY_REFERENCE = '''
"""A throwaway reference of an MLA/MoE decoder: its names, sizes, weights
and counts; no forward pass."""
from .weights import Group, LevelWeights


def served_config(doc):
    return {"d_model": doc["hidden_size"],
            "num_layers": doc["num_hidden_layers"],
            "num_heads": doc["num_attention_heads"],
            "vocab_size": doc["vocab_size"], "attention_kind": "mla",
            "mla.q_lora_rank": doc["q_lora_rank"],
            "mla.kv_lora_rank": doc["kv_lora_rank"],
            "mla.qk_nope_head_dim": doc["qk_nope_head_dim"],
            "mla.qk_rope_head_dim": doc["qk_rope_head_dim"],
            "mla.v_head_dim": doc["v_head_dim"],
            "moe.num_experts": doc["n_routed_experts"],
            "moe.num_shared_experts": doc["n_shared_experts"],
            "num_dense_layers": doc["first_k_dense_replace"],
            "mtp_depth": doc["num_nextn_predict_layers"]}


def served_ladder(doc, level):
    lv = doc["ladder"][level]
    return {"num_layers": lv["num_hidden_layers"],
            "d_ff_dense": lv["intermediate_size"],
            "moe.top_k": lv["num_experts_per_tok"],
            "moe.d_ff_expert": lv["moe_intermediate_size"]}


def smoke_sizes(doc, cfg):
    m, e = cfg.mla, cfg.moe
    return dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads, vocab_size=cfg.vocab_size,
                q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
                qk_nope_head_dim=m.qk_nope_head_dim,
                qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
                n_routed_experts=e.num_experts,
                n_shared_experts=e.num_shared_experts,
                num_experts_per_tok=e.top_k,
                moe_intermediate_size=e.d_ff_expert,
                intermediate_size=cfg.d_ff_dense,
                first_k_dense_replace=cfg.num_dense_layers,
                num_nextn_predict_layers=cfg.mtp_depth)


def _block(doc, mlp):
    d, h = doc["hidden_size"], doc["num_attention_heads"]
    q, kv = doc["q_lora_rank"], doc["kv_lora_rank"]
    nope, rope, v = (doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
                     doc["v_head_dim"])
    return [(("attn", "w_dq"), (d, q), "normal"),
            (("attn", "q_norm"), (q,), "ones"),
            (("attn", "w_uq"), (q, h, nope + rope), "normal"),
            (("attn", "w_dkv"), (d, kv + rope), "normal"),
            (("attn", "kv_norm"), (kv,), "ones"),
            (("attn", "w_uk"), (kv, h, nope), "normal"),
            (("attn", "w_uv"), (kv, h, v), "normal"),
            (("attn", "wo"), (h, v, d), "normal"),
            (("norm_mixer",), (d,), "ones"),
            (("norm_mlp",), (d,), "ones")] + mlp


def weights(doc, level):
    lv = doc["ladder"][level]
    d, e = doc["hidden_size"], doc["n_routed_experts"]
    f, fe = lv["intermediate_size"], lv["moe_intermediate_size"]
    fs = fe * doc["n_shared_experts"]
    dense = [(("mlp", "w_gate"), (d, f), "normal"),
             (("mlp", "w_up"), (d, f), "normal"),
             (("mlp", "w_down"), (f, d), "normal")]
    moe = [(("moe", "w_router"), (d, e), "normal", 0.1),
           (("moe", "we_gate"), (e, d, fe), "normal"),
           (("moe", "we_up"), (e, d, fe), "normal"),
           (("moe", "we_down"), (e, fe, d), "normal"),
           (("moe", "ws_gate"), (d, fs), "normal"),
           (("moe", "ws_up"), (d, fs), "normal"),
           (("moe", "ws_down"), (fs, d), "normal")]
    k = doc["first_k_dense_replace"]

    def sub(leaves, prefix):
        return tuple((prefix + p, *rest) for p, *rest in leaves)
    return LevelWeights(doc, level, [
        Group("embed", ((("embedding",), (doc["vocab_size"], d), "normal"),
                        (("lm_head",), (d, doc["vocab_size"]), "normal"))),
        Group("final_norm", (((), (d,), "ones"),)),
        Group("dense_layers", sub(_block(doc, dense), ("sub0",)), k),
        Group("layers", sub(_block(doc, moe), ("sub0",)),
              lv["num_hidden_layers"] - k),
        Group("mtp", sub(_block(doc, dense), ("block",)) + (
            (("proj",), (2 * d, d), "normal"), (("norm_h",), (d,), "ones"),
            (("norm_e",), (d,), "ones"), (("final_norm",), (d,), "ones")))])


def logits(doc, level, blocks, last, fp8=False):
    raise NotImplementedError("a throwaway reference has no forward pass")


class Counts:
    """Weights a token passes through: the routed experts it is sent to
    and the shared ones, not all of them."""

    def __init__(self, doc, level):
        lv = doc["ladder"][level]
        d = doc["hidden_size"]
        swiglu = 3 * d * lv["moe_intermediate_size"]
        active = (lv["num_experts_per_tok"] + doc["n_shared_experts"]) \\
            * swiglu + d * doc["n_routed_experts"]
        k = doc["first_k_dense_replace"]
        self.params = k * 3 * d * lv["intermediate_size"] \\
            + (lv["num_hidden_layers"] - k) * active
        self.head = d * doc["vocab_size"]

    def prefill_flops(self, batch, seq):
        return 2.0 * batch * seq * self.params + 2.0 * batch * self.head

    def decode_flops(self, batch, context):
        return 2.0 * batch * (self.params + self.head)

    def decode_bytes(self, batch, context):
        return 2.0 * (self.params + self.head)


def counts(doc, level):
    return Counts(doc, level)
'''


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """The toy reference in a scratch ``reference/`` directory that the
    benchmark's ``reference`` package searches too, and its document at
    the program's smoke sizes."""
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / f"{TOY}.py").write_text(
        textwrap.dedent(TOY_REFERENCE))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setattr(reference, "__path__",
                        [*reference.__path__, str(tmp_path / "reference")])
    doc = {"arch": ARCH, "reference": TOY,
           "weights": {"key": 0, "fold_in": "level", "dtype": "bfloat16"},
           "ladder": [{"level": 0, "intermediate_size": 0,
                       "moe_intermediate_size": 0, "num_experts_per_tok": 0,
                       "num_hidden_layers": 0, "accuracy": 0.0}]}
    yield smoke_doc(ARCH, doc)
    sys.modules.pop(f"reference.{TOY}", None)


def test_served_config_and_ladder_are_checked(toy):
    cfg = get_smoke_config(ARCH)
    harness.check_served_config(toy, cfg)
    off = copy.deepcopy(toy)
    off["ladder"][2]["num_experts_per_tok"] += 1
    with pytest.raises(SystemExit, match=r"level 2 moe\.top_k"):
        harness.check_served_config(off, cfg)


@pytest.mark.parametrize("level", [0, 5])
def test_weights_of_every_group_are_the_served_ones(toy, level):
    vcfg = entry.VariantPool(get_smoke_config(ARCH))[level].config
    rng = jax.random.fold_in(jax.random.PRNGKey(0), level)
    params = jax.jit(lambda r: tfm.init_params(vcfg, r,
                                               dtype=jnp.bfloat16))(rng)
    lw = importlib.import_module(f"reference.{TOY}").weights(toy, level)
    assert set(lw.groups) == set(params) == {
        "embed", "final_norm", "dense_layers", "layers", "mtp"}
    for where, drawn, served in lw.against(params):
        np.testing.assert_array_equal(drawn, served, err_msg=where)
    # the control path counts differing weights over every group
    eng = types.SimpleNamespace(params=params)
    runner = types.SimpleNamespace(resident={"n0": (level, eng)})
    assert harness.weights_differing(toy, runner) == {level: 0}
    eng.params = dict(params, mtp=dict(params["mtp"],
                                       proj=params["mtp"]["proj"] * 2))
    assert harness.weights_differing(toy, runner)[level] > 0


def test_count_readers_read_it(toy):
    shares = [types.SimpleNamespace(level=lv, served=8, prefill_s=0.02,
                                    decode_step_s=0.004) for lv in (0, 5)]
    records = [types.SimpleNamespace(shares=shares)]
    ctx = types.SimpleNamespace(
        records=records, traced=records,
        trace=types.SimpleNamespace(window_s=0.1), doc=toy,
        peaks=peaks.peaks_for("TPU v5 lite"), chips=1, prompt_len=512,
        decode_steps=4)
    assert type(counts.of(toy, 0)).__name__ == "Counts"
    for name in ("prefill_mfu", "decode_roofline", "window_mfu"):
        got = importlib.import_module(f"metrics.{name}").read(ctx)
        assert isinstance(got, float) and got > 0, name
