"""Serving engine: prefill -> padded decode caches -> batched decode loop.

The engine owns the jit'd prefill/decode executables for one model variant
on one device. The paper's Local Node "Inference" state calls into this; the
Gateway's dispatcher decides which variant each node loads.

Cache layout notes:
  * prefill returns raw seq-length caches; ``pad_caches`` places them into
    max_len decode buffers. For sliding-window layers the cache is a ring
    buffer keyed by absolute position (slot = pos % window), so the last
    `window` tokens are rolled so that slot (pos % window) holds position
    pos — see tests/test_serving.py for the invariant check.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.batching import BatchFormation
from repro.models import attention as attn_lib
from repro.models import model as model_lib
from repro.models import transformer as tfm
from repro.serving.spans import record


def _pad_kv(raw: attn_lib.KVCache, max_len: int, seq_len: int,
            window: Optional[int]) -> attn_lib.KVCache:
    """raw.k: (L, B, S, KV, D) stacked per group-unit. Returns decode cache."""
    def pad_one(x):
        if window is None:
            target = max_len
            pad = target - x.shape[2]
            out = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            return out
        w = min(window, max_len)
        # ring buffer: slot = pos % w must hold position pos
        if x.shape[2] >= w:
            last = x[:, :, -w:]                      # positions S-w .. S-1
            shift = seq_len % w
            return jnp.roll(last, shift=shift, axis=2)
        pad = w - x.shape[2]
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return attn_lib.KVCache(k=pad_one(raw.k), v=pad_one(raw.v))


def pad_caches(cfg: ModelConfig, raw_caches, seq_len: int, max_len: int):
    """Convert prefill caches (raw length) to decode caches (max_len)."""
    assert max_len >= seq_len, (
        f"decode max_len={max_len} shorter than prefill length {seq_len} "
        "(stub-frontend archs prepend stub_embed_len positions)")
    out = {}
    for g in tfm.layer_plan(cfg):
        unit_out = {}
        for i, sl in enumerate(g.pattern):
            c = raw_caches[g.name][f"sub{i}"]
            if sl.mixer == "gqa":
                window = None
                if cfg.attention_kind == "sliding" or (
                        cfg.attention_kind == "local_global"
                        and not sl.is_global):
                    window = cfg.sliding_window
                unit_out[f"sub{i}"] = _pad_kv(c, max_len, seq_len, window)
            elif sl.mixer == "mla":
                pad = max_len - c.latent.shape[2]
                unit_out[f"sub{i}"] = attn_lib.MLACache(
                    latent=jnp.pad(c.latent, ((0, 0), (0, 0), (0, pad), (0, 0))),
                    k_rope=jnp.pad(c.k_rope, ((0, 0), (0, 0), (0, pad), (0, 0))))
            else:   # mamba / rwkv states are fixed-size
                unit_out[f"sub{i}"] = c
        out[g.name] = unit_out
    return out


def _named(f, *args, **kwargs):
    """``functools.partial(f, ...)`` under ``f``'s name, so that its jitted
    program is ``jit_<name>`` in traces and compile logs (a bare partial is
    ``jit__unknown``). Only the name is copied: ``functools.wraps`` would
    also point ``inspect.signature`` at ``f``'s unbound arguments."""
    p = functools.partial(f, *args, **kwargs)
    p.__name__ = f.__name__
    return p


@functools.lru_cache(maxsize=32)
def _init_program(cfg: ModelConfig):
    return jax.jit(_named(tfm.init_params, cfg, dtype=jnp.dtype(cfg.dtype)))


def init_params_on(cfg: ModelConfig, rng: jax.Array, device: jax.Device):
    """Random serving weights in the config's dtype, drawn by one jitted
    program on ``device`` (it runs where its key is committed): no float32
    copy of the model is ever materialised, on the host or on the device."""
    return _init_program(cfg)(jax.device_put(rng, device))


@dataclasses.dataclass
class EngineConfig:
    max_len: int = 512
    use_kernels: bool = False
    donate_cache: bool = True


@functools.lru_cache(maxsize=32)
def _programs(cfg: ModelConfig, use_kernels: bool, donate_cache: bool):
    """Jitted prefill/decode, shared by every engine of one variant so that
    an engine rebuilt on a device reuses the programs compiled there."""
    prefill = jax.jit(_named(model_lib.prefill, cfg, use_kernels=use_kernels))
    decode = jax.jit(
        _named(model_lib.decode_step, cfg, use_kernels=use_kernels),
        donate_argnums=(1,) if donate_cache else ())
    return prefill, decode


class Engine:
    """One model variant, jit'd, on one device (default: the first)."""

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: EngineConfig = EngineConfig(),
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        self.ecfg = ecfg
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self._prefill, self._decode = _programs(cfg, ecfg.use_kernels,
                                                ecfg.donate_cache)
        self._decode_shapes = {}    # (tokens shape, dtype) -> _decode_args

    def prefill(self, tokens: jax.Array, embeds: Optional[jax.Array] = None):
        """Returns (logits, decode caches, lengths, expert_counts).
        ``expert_counts``: for a config with experts, the rows routed to
        held experts and the rows the grouped matmuls were given, summed
        over its expert layers (a (2,) device array); else None."""
        tokens = jax.device_put(tokens, self.device)
        if embeds is not None:
            embeds = jax.device_put(embeds, self.device)
        logits, raw, *counts = self._prefill(self.params, tokens, embeds)
        seq_len = tokens.shape[1] + (embeds.shape[1] if embeds is not None else 0)
        caches = pad_caches(self.cfg, raw, seq_len, self.ecfg.max_len)
        lengths = jnp.full((tokens.shape[0],), seq_len, jnp.int32,
                           device=self.device)
        return logits, caches, lengths, (counts[0] if counts else None)

    def decode(self, caches, lengths, tokens):
        return self._decode(self.params, caches, lengths,
                            jax.device_put(tokens, self.device))

    def compile(self, tokens: jax.Array) -> None:
        """Compile prefill and decode for this batch shape ahead of serving
        (a later call with the same shapes reuses the executables). Runs no
        prefill and leaves nothing on the device: decode compiles against
        the shapes of the cache, lengths and token that ``prefill(tokens)``
        and its argmax would give, on this engine's device as the served
        arrays are, so the caller's own prefill is the only one. Spans:
        ``engine.compile`` around it all; inside, ``engine.aot_prefill`` and
        ``engine.aot_decode`` around each program's compile (near 0 when it
        is already compiled)."""
        tokens = jax.device_put(tokens, self.device)
        with record("engine.compile"):
            with record("engine.aot_prefill"):
                self._prefill.lower(self.params, tokens, None).compile()
            with record("engine.aot_decode"):
                self._decode.lower(self.params,
                                   *self._decode_args(tokens)).compile()

    def _decode_args(self, tokens: jax.Array):
        """(caches, lengths, token) of the first decode step after
        ``prefill(tokens)``, as shapes on this engine's device. Kept per
        batch shape: tracing the prefill for them takes milliseconds."""
        key = (tokens.shape, tokens.dtype)
        if key not in self._decode_shapes:
            def first_step(tokens):
                logits, caches, lengths, _ = self.prefill(tokens)
                return caches, lengths, jnp.argmax(logits, axis=-1).astype(
                    jnp.int32)
            on_device = jax.sharding.SingleDeviceSharding(self.device)
            self._decode_shapes[key] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=on_device,
                                               weak_type=a.weak_type),
                jax.eval_shape(first_step, tokens))
        return self._decode_shapes[key]

    def release(self):
        """Free this engine's weights on its device now, so that the next
        level's weights fit (a chip holds one full-width level at a time)."""
        for leaf in jax.tree_util.tree_leaves(self.params):
            leaf.delete()
        self.params = None

    def generate(self, tokens: jax.Array, num_steps: int,
                 embeds: Optional[jax.Array] = None,
                 sample_rng: Optional[jax.Array] = None) -> np.ndarray:
        """Greedy (or sampled) generation; returns (B, num_steps) tokens."""
        logits, caches, lengths, _ = self.prefill(tokens, embeds)
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for i in range(num_steps):
            out.append(np.asarray(tok))
            logits, caches, lengths = self.decode(caches, lengths, tok)
            if sample_rng is not None:
                sample_rng, sub = jax.random.split(sample_rng)
                tok = jax.random.categorical(sub, logits).astype(jnp.int32)
            else:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return np.stack(out, axis=1)


class BatchScheduler:
    """Batch scheduler for one worker group's prompt queue.

    Two modes sharing one :class:`~repro.core.batching.BatchFormation`
    policy (the same policy the simulator's batch-aware node runtime
    forms engine batches with):

      * **static** (default, the original behaviour): ``next_batch()``
        drains up to ``batch_size`` prompts whenever any are queued —
        partial batches launch immediately;
      * **continuous**: ``next_batch(now)`` launches a full batch at
        once, but holds a partial batch until its oldest prompt has
        waited ``window_s`` (join-on-arrival: prompts added meanwhile
        ride the same batch; a join that fills it makes the next call
        launch immediately).
    """

    def __init__(self, batch_size: int, *, continuous: bool = False,
                 window_s: float = 0.0):
        self.batch_size = batch_size
        self.continuous = continuous
        self.formation = BatchFormation(max_batch=batch_size,
                                        window_s=window_s)
        self.queue: List[np.ndarray] = []
        self._enqueue_s: List[float] = []

    def add(self, prompt: np.ndarray, now: float = 0.0):
        self.queue.append(prompt)
        self._enqueue_s.append(now)

    def next_batch(self, now: float = 0.0) -> Optional[np.ndarray]:
        if not self.queue:
            return None
        if self.continuous and not self.formation.ready(
                len(self.queue), now - self._enqueue_s[0]):
            return None             # hold the partial batch for joiners
        n = self.formation.take(len(self.queue))
        batch, self.queue = self.queue[:n], self.queue[n:]
        self._enqueue_s = self._enqueue_s[n:]
        max_l = max(len(p) for p in batch)
        out = np.zeros((n, max_l), dtype=np.int32)
        for i, p in enumerate(batch):
            out[i, -len(p):] = p      # left-pad
        return out
