"""Per-arch smoke tests: reduced config, one forward/loss + one train step
on CPU, asserting output shapes and no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.models import forward, init_params
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts


def _batch(cfg, rng, b=2, s=16):
    batch = {"tokens": jax.random.randint(rng, (b, s), 0, cfg.vocab_size)}
    if cfg.frontend_stub:
        batch["embeds"] = jax.random.normal(
            rng, (b, cfg.stub_embed_len, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_shapes_finite(arch, rng):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, rng)
    batch = _batch(cfg, rng)
    logits, aux = forward(cfg, params, batch["tokens"], batch.get("embeds"))
    b, s = batch["tokens"].shape
    assert logits.shape == (b, s, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_train_step(arch, rng):
    cfg = get_smoke_config(arch)
    tcfg = ts.TrainConfig(opt=opt_lib.OptimizerConfig(peak_lr=1e-3),
                          remat=False)
    state = ts.init_train_state(cfg, tcfg, rng)
    batch = _batch(cfg, rng)
    new_state, metrics = jax.jit(
        lambda st, b: ts.train_step(cfg, tcfg, st, b))(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed (strict: any movement at all counts)
    p0 = jax.tree_util.tree_leaves(state.params)[0]
    p1 = jax.tree_util.tree_leaves(new_state.params)[0]
    assert np.abs(np.asarray(p0) - np.asarray(p1)).max() > 1e-7


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
        "deepseek-v3-ep32": (9, 7168, 128, 128, 2048, 16160),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected


def test_moe_configs():
    m = get_config("mixtral-8x7b").moe
    assert (m.num_experts, m.top_k) == (8, 2)
    d = get_config("deepseek-v3-671b").moe
    assert (d.num_experts, d.top_k, d.num_shared_experts) == (256, 8, 1)
    j = get_config("jamba-1.5-large-398b").moe
    assert (j.num_experts, j.top_k) == (16, 2)


def test_param_counts_plausible():
    """Analytic param counts should be in the right ballpark of the names."""
    approx = {
        "phi4-mini-3.8b": (3.0e9, 5.5e9),
        "gemma2-2b": (2.0e9, 3.7e9),
        "gemma2-27b": (22e9, 33e9),
        "qwen3-32b": (28e9, 40e9),
        "mixtral-8x7b": (40e9, 56e9),
        "deepseek-v3-671b": (580e9, 750e9),
        "rwkv6-1.6b": (1.2e9, 2.4e9),
        "jamba-1.5-large-398b": (330e9, 460e9),
    }
    for arch, (lo, hi) in approx.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} not in [{lo:.1e},{hi:.1e}]"


def test_deepseek_active_params():
    cfg = get_config("deepseek-v3-671b")
    active = cfg.param_count(active_only=True)
    assert 30e9 <= active <= 45e9, f"{active:.3e}"   # ~37B active
