"""Serving weight plane: LEXI-packed at-rest params (``core.weights``) must
be invisible to the token stream — serving from the packed store has to emit
bit-identical tokens to raw bf16 weights across dense / hybrid / MoE configs
and both weight backends (exact unpack-then-einsum and the fused
decompress_matmul kernel), while the store itself holds fewer HBM bytes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig, RunConfig, SSMConfig
from repro.core import weights as W
from repro.core.collectives import CodecConfig
from repro.kernels import ops as kops
from repro.serve import Request, ServeEngine

RNG = np.random.default_rng(0)


class TestResolveWeightBackend:
    def test_auto(self):
        want = "pallas" if kops.on_tpu() else "jax"
        assert kops.resolve_weight_backend(CodecConfig()) == want
        assert kops.resolve_weight_backend(None) == want

    @pytest.mark.parametrize("be", ["pallas", "interpret", "jax"])
    def test_explicit(self, be):
        codec = dataclasses.replace(CodecConfig(), weight_backend=be)
        if be == "pallas" and not kops.on_tpu():
            # compiled kernels need the chip: no silent interpreter fallback
            with pytest.raises(RuntimeError, match="TPU"):
                kops.resolve_weight_backend(codec)
        else:
            assert kops.resolve_weight_backend(codec) == be

    def test_invalid(self):
        codec = dataclasses.replace(CodecConfig(), weight_backend="zorp")
        with pytest.raises(ValueError, match="weight_backend"):
            kops.resolve_weight_backend(codec)


def _tree():
    mk = lambda shape, std=0.05: jnp.asarray(RNG.normal(0, std, shape),
                                             jnp.bfloat16)
    return {
        "embed": mk((512, 64)),          # gather consumer -> stays raw
        "blocks": {
            "wq": mk((64, 64)),          # 4096 elems -> packs
            "stack": mk((3, 64, 64)),    # stacked (scan) leaf -> packs
            "scale": jnp.ones((64,), jnp.bfloat16),   # 1-D -> raw
            "small": mk((8, 8)),         # below MIN_COMPRESS_SIZE -> raw
        },
    }


def _specs():
    return {
        "embed": P(None, "model"),
        "blocks": {"wq": P(None, "model"), "stack": P(None, None, "model"),
                   "scale": P(), "small": P()},
    }


class TestPackServingParams:
    def test_eligibility_and_losslessness(self):
        params = _tree()
        pk, sp = W.pack_serving_params(params, _specs(), backend="jax", tp=1)
        assert isinstance(pk["blocks"]["wq"], W.PackedWeight)
        assert isinstance(pk["blocks"]["stack"], W.PackedWeight)
        assert not isinstance(pk["embed"], W.PackedWeight)
        assert not isinstance(pk["blocks"]["scale"], W.PackedWeight)
        assert not isinstance(pk["blocks"]["small"], W.PackedWeight)
        # the packed store decodes back bit-exactly
        for name in ("wq", "stack"):
            assert jnp.array_equal(W.unpack_weight(pk["blocks"][name]),
                                   params["blocks"][name]), name
        # specs mirror the packed layout for shard_map tree matching
        assert isinstance(sp["blocks"]["wq"], W.PackedWeight)
        assert sp["blocks"]["wq"].signman == P(None, "model")
        assert sp["blocks"]["stack"].planes == P(None, None, "model", None)
        assert sp["embed"] == P(None, "model")

    def test_idempotent(self):
        pk, sp = W.pack_serving_params(_tree(), _specs(), backend="jax")
        pk2, sp2 = W.pack_serving_params(pk, sp, backend="jax")
        assert pk2["blocks"]["wq"] is pk["blocks"]["wq"]
        assert jax.tree.structure(pk2) == jax.tree.structure(pk)

    def test_bytes_metering(self):
        params = _tree()
        pk, _ = W.pack_serving_params(params, _specs(), backend="jax")
        stored, raw = W.weight_plane_bytes(pk)
        want_raw = sum(2 * l.size for l in jax.tree.leaves(params))
        assert raw == want_raw
        assert stored < raw
        # adaptive k picks the smallest escape-free dictionary
        assert 4 <= pk["blocks"]["wq"].k <= 6

    def test_tp_sharded_n_must_stay_lane_aligned(self):
        # local N = 40 at tp=2 -> 20 per shard, not %32: leaf stays raw
        mk = lambda s: jnp.asarray(RNG.normal(0, 0.05, s), jnp.bfloat16)
        params = {"w": mk((128, 40))}
        pk, _ = W.pack_serving_params(params, {"w": P(None, "model")}, tp=2)
        assert not isinstance(pk["w"], W.PackedWeight)


# tiny serving configs: d_ff / vocab sized so attention, MLP, MoE-expert and
# LM-head leaves all clear MIN_COMPRESS_SIZE and lane alignment (vocab 512,
# expert d_ff 64) — the packed plane is actually exercised, not bypassed
CASES = {
    "dense": ModelConfig(name="t2", family="dense", n_layers=2, d_model=64,
                         n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=512,
                         head_dim=16),
    "hybrid": ModelConfig(
        name="h", family="hybrid", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
        parallel_hybrid=True, attn_layout="hymba_3global", window=16,
        ssm=SSMConfig(d_state=16, headdim=8, chunk=16), sub_quadratic=True),
    "moe": ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=512,
                       head_dim=16,
                       moe=MoEConfig(n_experts=4, top_k=2, d_ff=64,
                                     n_shared=1, capacity_factor=4.0)),
}


def _run_cfg(wb: str) -> RunConfig:
    codec = dataclasses.replace(CodecConfig(cache_block=4),
                                decode_backend="jax", weight_backend=wb)
    return RunConfig(codec=codec)


def _requests():
    rng = np.random.default_rng(7)
    specs = [(8, 4), (16, 3), (12, 4)]
    return [Request(uid=i,
                    prompt=rng.integers(0, 512, (s,)).astype(np.int32),
                    max_new_tokens=n) for i, (s, n) in enumerate(specs)]


_RAW_TOKENS = {}


def _raw_tokens(case, tp=1):
    """Raw-weights reference stream, computed once per (case, tp)."""
    if (case, tp) not in _RAW_TOKENS:
        eng = ServeEngine(CASES[case], _run_cfg("auto"), tp=tp, n_slots=2,
                          max_len=48, seed=1)
        res, st = eng.run(_requests())
        assert not st.weights_compressed
        _RAW_TOKENS[(case, tp)] = [r.tokens for r in res]
    return _RAW_TOKENS[(case, tp)]


@pytest.mark.parametrize("wb", ["jax", "interpret"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_stream_identity_packed_vs_raw(case, wb):
    eng = ServeEngine(CASES[case], _run_cfg(wb), tp=1, n_slots=2,
                      max_len=48, seed=1, compress_weights=True)
    res, st = eng.run(_requests())
    assert [r.tokens for r in res] == _raw_tokens(case)
    assert st.weights_compressed
    assert st.weight_backend == wb
    # something actually packed, and the metered store shrank
    assert st.weight_bytes_per_step < st.weight_raw_bytes_per_step
    assert st.weight_ratio < 0.95


def test_stream_identity_tp2_fused():
    """Fused kernel under shard_map: tp=2 packed serving must match the
    tp=2 raw stream token-for-token."""
    eng = ServeEngine(CASES["dense"], _run_cfg("interpret"), tp=2,
                      n_slots=2, max_len=48, seed=1, compress_weights=True)
    res, st = eng.run(_requests())
    assert [r.tokens for r in res] == _raw_tokens("dense", tp=2)
    assert st.weights_compressed and st.weight_ratio < 0.95
