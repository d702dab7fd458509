"""The plain references against the program at the smoke sizes: the
weights they draw again are the served ones, bit for bit; in float32 they
compute what the program computes; the float8 control reads far from the
bf16 program."""
import dataclasses
import itertools

import compare
import entry
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import traffic
from conftest import smoke_doc
from reference import dense_gqa, rwkv6

from repro.configs import get_smoke_config
from repro.models import model as model_lib
from repro.models import transformer as tfm

REFS = {"phi4-mini-3.8b": dense_gqa, "rwkv6-1.6b": rwkv6}


def _program(arch, dtype, level):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype=dtype)
    vcfg = dataclasses.replace(entry.VariantPool(cfg)[level].config,
                               dtype=dtype)
    rng = jax.random.fold_in(jax.random.PRNGKey(0), level)
    params = jax.jit(lambda r: tfm.init_params(vcfg, r,
                                               dtype=jnp.dtype(dtype)))(rng)
    return vcfg, params


@pytest.mark.parametrize("level", [0, 5])
def test_weights_are_the_served_ones(arch, level):
    doc = smoke_doc(arch)
    _, params = _program(arch, "bfloat16", level)
    lw = REFS[arch].weights(doc, level)
    for k, v in lw.embed().items():
        np.testing.assert_array_equal(
            v, np.asarray(params["embed"][k], np.float32))
    flat = jax.tree_util.tree_flatten_with_path(params["layers"])[0]
    for i in range(lw.n_layers):
        w = lw.layer(i)
        assert len(w) == len(flat)
        for path, leaf in flat:
            name = "/".join(p.key for p in path)
            np.testing.assert_array_equal(w[name],
                                          np.asarray(leaf[i], np.float32))


def test_float32_reference_is_the_program(arch):
    doc = smoke_doc(arch)
    doc["weights"] = dict(doc["weights"], dtype="float32")
    vcfg, params = _program(arch, "float32", 0)
    toks = np.random.default_rng(0).integers(0, doc["vocab_size"], (3, 24),
                                             dtype=np.int32)
    got, _ = jax.jit(lambda p, t: model_lib.forward(vcfg, p, t))(params, toks)
    want = REFS[arch].logits(doc, 0, [toks], last=4)[0]
    err = np.abs(np.asarray(got[:, -4:]) - want).max() / np.abs(want).max()
    assert err < 1e-5


def _served_shares(arch, seed):
    """One request of fig7-mix served by the program at the smoke size."""
    doc = smoke_doc(arch)
    cfg = get_smoke_config(arch)
    gn = entry.build_gateway(cfg)
    nodes = [n.name for n in gn.table.nodes]
    runner = entry.ShareRunner(cfg, entry.place_nodes(nodes, jax.devices()))
    spec = next(traffic.passes(traffic.load("fig7-mix"), seed,
                               gn.table.perf[0].sum(),
                               gn.table.perf[-1].sum()))[0]
    gn.handle(entry.Event(kind="workload", request=entry.InferenceRequest(
        rid=spec.rid, num_items=spec.num_items, perf_req=spec.perf_req,
        acc_req=spec.acc_req)))
    shares = runner.run(gn.dispatches[-1])
    runner.close()
    return doc, [{"level": s.level, "tokens": s.tokens, "logits": s.logits,
                  "prompts": compare.prompts(spec.rid, nodes.index(s.node),
                                             s.served, doc["vocab_size"],
                                             entry.PROMPT_LEN)}
                 for s in shares]


def test_control_reads_far_from_the_program(arch):
    """The control (the reference in float8) against the bf16 program, on
    three seeds: it reads at least three times the program's logit error."""
    for seed in (11, 12, 13):
        doc, shares = _served_shares(arch, seed)
        prog = compare.model_checks(shares, doc, entry.PROMPT_LEN)
        ctl = compare.model_checks(shares, doc, entry.PROMPT_LEN,
                                   control=True)
        assert ctl["logit_err"] > 3 * prog["logit_err"], (prog, ctl)
        assert prog["logit_err"] < doc["limits"]["logit_err"] \
            < ctl["logit_err"]


def test_sample_is_drawn_from_the_seed():
    recs = list(range(30))
    assert compare.sample(recs, 5) == compare.sample(recs, 5)
    assert len(compare.sample(recs, 5)) == compare.SAMPLE_REQUESTS
    assert any(compare.sample(recs, s) != compare.sample(recs, 5)
               for s in itertools.islice(itertools.count(6), 5))
