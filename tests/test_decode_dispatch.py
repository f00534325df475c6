"""Decode-backend dispatch parity: ``attend_cache`` / ``attend_paged`` must
produce matching results whether they route through the fused Pallas kernels
(interpret mode on CPU) or the pure-JAX block/page scan — across GQA/MQA,
windowed and full attention, codec on/off, MLA, and tp in {1, 2}.

The stores are built through the real write paths (``fill_from_prefill`` /
``paged_insert_many`` equivalents would drag in the whole engine; instead we
drive ``append_token``/``append_token_paged`` inside shard_map so ring
state, block flushes and page allocation are all the production article).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLAConfig, ModelConfig, RunConfig
from repro.core import collectives as cl
from repro.core.collectives import CodecConfig
from repro.kernels import ops as kops
from repro.models import cache as cache_mod
from repro.models import layers

RNG = np.random.default_rng(7)
BLK = 4


def _cfg(n_heads, n_kv_heads, mla=False):
    return ModelConfig(
        name="t", family="dense", n_layers=1, d_model=32, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_ff=64, vocab_size=128, head_dim=8,
        mla=MLAConfig(kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
                      v_dim=8) if mla else None)


def _run(codec_on, backend):
    codec = CodecConfig(cache_block=BLK, decode_backend=backend) if codec_on \
        else dataclasses.replace(CodecConfig.off(), cache_block=BLK,
                                 decode_backend=backend)
    return RunConfig(codec=codec)


def _mesh(tp):
    return jax.make_mesh((tp,), ("model",))


def _attend_fixed(cfg, run, tp, q, stream, length, spec, window):
    """Build a fixed store by appending ``stream`` tokens, then attend."""
    mesh = _mesh(tp)

    def f(q_, vals):
        kv = cache_mod.empty_kv(cfg, run, q_.shape[0], 32 * tp, tp)

        def body(kv_c, v):
            return cache_mod.append_token(cfg, run, kv_c, v, tp), None

        kv, _ = jax.lax.scan(body, kv, vals)
        return cache_mod.attend_cache(cfg, run, kv, q_, spec, tp,
                                      window=window)

    fj = jax.jit(cl.shmap(f, mesh, (P(), P()), P()))
    return np.asarray(fj(q, stream))


def _attend_paged_fn(cfg, run, tp, n_slots, q, stream, lengths, spec,
                     window):
    """Drive per-slot appends (ragged via the active mask), then attend."""
    mesh = _mesh(tp)
    max_len = 32 * tp

    def f(q_, vals, lens):
        pkv = cache_mod.empty_paged_kv(cfg, run, n_slots, max_len, tp)
        n_tok = vals.shape[0]

        def body(carry, v):
            pkv_c, cur = carry
            active = cur < lens
            pkv_c = cache_mod.append_token_paged(cfg, run, pkv_c, v, cur,
                                                 active, tp)
            return (pkv_c, cur + active.astype(jnp.int32)), None

        (pkv, _), _ = jax.lax.scan(body, (pkv, jnp.zeros_like(lens)), vals)
        return cache_mod.attend_paged(cfg, run, pkv, q_, lens, spec, tp,
                                      window=window)

    fj = jax.jit(cl.shmap(f, mesh, (P(), P(), P()), P()))
    return np.asarray(fj(q, stream, lengths))


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("codec_on", [True, False], ids=["codec", "raw"])
@pytest.mark.parametrize("heads", [(4, 2), (3, 1)], ids=["gqa", "mqa"])
@pytest.mark.parametrize("window", [None, 5], ids=["full", "windowed"])
def test_attend_paged_backend_parity(tp, codec_on, heads, window):
    cfg = _cfg(*heads)
    n_slots = 3
    hq = cfg.padded_heads(tp)
    w = cache_mod.kv_width(cfg)
    n_tok = 3 * BLK * tp + 2
    lengths = jnp.asarray([n_tok, BLK * tp + 1, 0], jnp.int32)
    stream = jnp.asarray(RNG.normal(0, 0.5, (n_tok, n_slots, w)),
                         jnp.bfloat16)
    q = jnp.asarray(RNG.normal(0, 1, (n_slots, hq, 1, cfg.head_dim)),
                    jnp.bfloat16)
    spec = layers.AttnSpec(causal=True, windowed=window is not None)
    outs = {}
    for backend in ("jax", "interpret"):
        run = _run(codec_on, backend)
        outs[backend] = _attend_paged_fn(cfg, run, tp, n_slots, q, stream,
                                         lengths, spec, window)
    np.testing.assert_allclose(
        np.asarray(outs["jax"], np.float32),
        np.asarray(outs["interpret"], np.float32), rtol=2e-2, atol=2e-2)
    # empty slot produces all-zero attention on both paths
    assert np.all(np.asarray(outs["interpret"], np.float32)[2] == 0.0)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("codec_on", [True, False], ids=["codec", "raw"])
def test_attend_cache_backend_parity(tp, codec_on):
    cfg = _cfg(4, 2)
    b = 2
    hq = cfg.padded_heads(tp)
    w = cache_mod.kv_width(cfg)
    n_tok = 2 * BLK * tp + 3
    stream = jnp.asarray(RNG.normal(0, 0.5, (n_tok, b, w)), jnp.bfloat16)
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, 1, cfg.head_dim)), jnp.bfloat16)
    spec = layers.AttnSpec(causal=True, softcap=30.0)
    outs = {}
    for backend in ("jax", "interpret"):
        run = _run(codec_on, backend)
        outs[backend] = _attend_fixed(cfg, run, tp, q, stream, n_tok, spec,
                                      None)
    np.testing.assert_allclose(
        np.asarray(outs["jax"], np.float32),
        np.asarray(outs["interpret"], np.float32), rtol=2e-2, atol=2e-2)


def test_attend_paged_mla_backend_parity():
    cfg = _cfg(4, 4, mla=True)
    tp, n_slots = 2, 2
    hq = cfg.padded_heads(tp)
    w = cache_mod.kv_width(cfg)                 # lora + rope latent
    hd_q = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    n_tok = BLK * tp + 3
    lengths = jnp.asarray([n_tok, 2], jnp.int32)
    stream = jnp.asarray(RNG.normal(0, 0.5, (n_tok, n_slots, w)),
                         jnp.bfloat16)
    q = jnp.asarray(RNG.normal(0, 1, (n_slots, hq, 1, hd_q)), jnp.bfloat16)
    spec = layers.AttnSpec(
        causal=True,
        scale=(cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim) ** -0.5)
    outs = {}
    for backend in ("jax", "interpret"):
        run = _run(True, backend)
        outs[backend] = _attend_paged_fn(cfg, run, tp, n_slots, q, stream,
                                         lengths, spec, None)
    assert outs["jax"].shape[-1] == cfg.mla.kv_lora_rank
    np.testing.assert_allclose(
        np.asarray(outs["jax"], np.float32),
        np.asarray(outs["interpret"], np.float32), rtol=2e-2, atol=2e-2)


def test_resolve_decode_backend():
    assert kops.resolve_decode_backend(CodecConfig()) == "jax"  # CPU auto
    assert kops.resolve_decode_backend(
        CodecConfig(decode_backend="interpret")) == "interpret"
    with pytest.raises(ValueError, match="decode_backend"):
        kops.resolve_decode_backend(CodecConfig(decode_backend="nope"))
    # compiled kernels off-TPU are refused, never silently interpreted
    with pytest.raises(RuntimeError, match="TPU"):
        kops.resolve_decode_backend(CodecConfig(decode_backend="pallas"))
    with pytest.raises(RuntimeError, match="TPU"):
        kops.histogram(jnp.zeros((8,), jnp.bfloat16))
