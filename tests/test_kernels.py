"""Pallas kernel tests: shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU; the kernels target TPU BlockSpecs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixed
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def bf16(shape, std=0.1):
    return jnp.asarray(RNG.normal(0, std, shape), jnp.bfloat16)


def narrow_bf16(shape, n_exp=8):
    """bf16 values spanning exactly ``n_exp`` exponents (deterministic) —
    packs escape-free even at k=4 (15-symbol dictionary)."""
    rng = np.random.default_rng(7)
    mag = 2.0 ** rng.integers(-n_exp, 0, shape).astype(np.float64)
    mant = 1.0 + rng.integers(0, 128, shape) / 128.0
    sgn = rng.choice([-1.0, 1.0], shape)
    return jnp.asarray(sgn * mag * mant, jnp.bfloat16)


def assert_bits_equal(a, b):
    assert jnp.array_equal(jax.lax.bitcast_convert_type(a, jnp.uint16),
                           jax.lax.bitcast_convert_type(b, jnp.uint16))


class TestHistogramKernel:
    @pytest.mark.parametrize("shape", [(4096,), (3, 4096), (2, 5, 4096),
                                       (1000,), (7, 321)])
    def test_matches_ref(self, shape):
        x = bf16(shape)
        assert jnp.array_equal(ops.histogram(x, interpret=True),
                               ref.histogram_ref(x.reshape(1, -1)))

    def test_extreme_values(self):
        x = jnp.asarray([0.0, -0.0, 1e38, -1e-38, 3.14] * 1000,
                        jnp.float32).astype(jnp.bfloat16)
        assert jnp.array_equal(ops.histogram(x, interpret=True),
                               ref.histogram_ref(x.reshape(1, -1)))


class TestPackUnpackKernels:
    @pytest.mark.parametrize("k", [4, 5, 6])
    @pytest.mark.parametrize("shape", [(8192,), (2, 3, 4096), (5000,)])
    def test_roundtrip(self, k, shape):
        x = bf16(shape)
        ct = ops.pack(x, k=k, interpret=True)
        assert_bits_equal(ops.unpack(ct, interpret=True), x)

    @pytest.mark.parametrize("k", [5, 6])
    def test_bit_compatible_with_fixed(self, k):
        """Kernel output is interchangeable with the pure-JAX codec."""
        x = bf16((3, 4096))
        ct_k = ops.pack(x, k=k, interpret=True)
        ct_f = fixed.compress(x, k=k)
        for name in ("signman", "planes", "dict_syms", "esc_pos", "esc_raw"):
            assert jnp.array_equal(getattr(ct_k, name), getattr(ct_f, name)), name
        # cross-decode: kernel-packed -> jnp decode and vice versa
        assert_bits_equal(fixed.decompress(ct_k), x)
        assert_bits_equal(ops.unpack(ct_f, interpret=True), x)

    def test_escapes_patch(self):
        x = np.asarray(bf16(8192), np.float32)
        x[::311] = RNG.uniform(1e28, 1e36, x[::311].shape)
        xj = jnp.asarray(x).astype(jnp.bfloat16)
        ct = ops.pack(xj, k=4, interpret=True)
        assert int(ct.n_escapes) >= 0
        assert_bits_equal(ops.unpack(ct, interpret=True), xj)


class TestDecompressMatmul:
    @pytest.mark.parametrize("mkn", [(128, 256, 512), (256, 128, 256),
                                     (64, 512, 128)])
    def test_matches_ref(self, mkn):
        m, k_, n = mkn
        x = bf16((m, k_), 1.0)
        w = bf16((k_, n), 0.02)
        sm, pl, d, nesc = ops.compress_weight(w)
        assert int(nesc) == 0
        out = ops.matmul_compressed(x, sm, pl, d, bm=64, bk=64, bn=128, interpret=True)
        want = ref.decompress_matmul_ref(x, sm, pl, d, 6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-6, atol=2e-5)

    def test_bit_exact_single_kblock(self):
        x = bf16((64, 128), 1.0)
        w = bf16((128, 256), 0.05)
        sm, pl, d, _ = ops.compress_weight(w)
        out = ops.matmul_compressed(x, sm, pl, d, bm=64, bk=128, bn=256, interpret=True)
        want = jnp.dot(x, w, preferred_element_type=jnp.float32)
        assert jnp.array_equal(out, want)

    def test_weight_decode_lossless(self):
        w = bf16((128, 512), 0.02)
        sm, pl, d, _ = ops.compress_weight(w)
        ident = jnp.eye(128, dtype=jnp.bfloat16)
        out = ops.matmul_compressed(ident, sm, pl, d, bm=128, bk=128, bn=256, interpret=True)
        assert jnp.array_equal(out.astype(jnp.bfloat16), w)

    @pytest.mark.parametrize("k", [4, 5, 6])
    def test_k_sweep(self, k):
        """Small dictionaries: weights with few distinct exponents pack at
        k=4 without escapes and the kernel must track the ref bit-for-bit
        (single k-block -> one jnp.dot on both sides)."""
        x = bf16((16, 128), 1.0)
        w = narrow_bf16((128, 256))
        sm, pl, d, nesc = ops.compress_weight(w, k=k)
        assert int(nesc) == 0
        out = ops.matmul_compressed(x, sm, pl, d, k=k, bm=64, bk=128, bn=256, interpret=True)
        want = ref.decompress_matmul_ref(x, sm, pl, d, k)
        assert jnp.array_equal(out, want)

    @pytest.mark.parametrize("mkn", [(1, 128, 256),   # M=1 decode row
                                     (5, 100, 96),    # ragged M and K
                                     (33, 70, 64)])
    def test_nonmultiple_shapes(self, mkn):
        """Serving shapes don't align to kernel tiles: the wrapper pads M/K/N
        up to block multiples and slices the result (N still %32 — the packed
        layout's lane width)."""
        m, k_, n = mkn
        x = bf16((m, k_), 1.0)
        w = bf16((k_, n), 0.02)
        sm, pl, d, nesc = ops.compress_weight(w)
        assert int(nesc) == 0
        out = ops.matmul_compressed(x, sm, pl, d, interpret=True)
        assert out.shape == (m, n)
        want = ref.decompress_matmul_ref(x, sm, pl, d, 6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-6, atol=2e-5)

    @pytest.mark.parametrize("tp", [1, 2])
    def test_inside_shard_map(self, tp):
        """Tensor-parallel serving slices packed weights along N (signman
        and planes shard on the model axis, the dictionary replicates)."""
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives as cl
        x = bf16((4, 128), 1.0)
        w = narrow_bf16((128, 64 * tp))
        sm, pl, d, nesc = ops.compress_weight(w)
        assert int(nesc) == 0
        mesh = jax.make_mesh((tp,), ("model",))
        f = lambda x_, sm_, pl_, d_: ops.matmul_compressed(x_, sm_, pl_, d_, interpret=True)
        fj = jax.jit(cl.shmap(
            f, mesh,
            (P(), P(None, "model"), P(None, "model", None), P()),
            P(None, "model")))
        out = fj(x, sm, pl, d)
        want = ref.decompress_matmul_ref(x, sm, pl, d, 6)
        assert jnp.array_equal(out, want)


def _normalized(out, l):
    return np.asarray(out) / np.maximum(np.asarray(l)[..., None], 1e-30)


class TestDecodeAttend:
    """Fused decompress+attend kernel (fixed store) vs the pure-jnp oracle.

    The kernel computes masks in-kernel from (length, ti, window) and fuses
    the raw ring as its final grid step, so the oracle receives the same
    scalars and the comparison covers the whole decode-attention semantics.
    """

    @pytest.mark.parametrize("cfg", [(2, 4, 2, 16, 3, 32),
                                     (1, 5, 1, 16, 2, 32),
                                     (2, 8, 4, 32, 2, 64)])
    @pytest.mark.parametrize("codec_on", [True, False], ids=["codec", "raw"])
    def test_matches_ref(self, cfg, codec_on):
        b, h, hkv, hd, nblk, blk = cfg
        from repro.core import fixed
        from repro.kernels.decode_attend import WINDOW_NONE, decode_attend
        w = 2 * hkv * hd
        g = max(h // hkv, 1)
        kv_idx = tuple(min(i // g, hkv - 1) for i in range(h))
        scale = hd ** -0.5
        blocks = bf16((nblk, b, blk, w), 0.5)
        ring = bf16((b, blk, w), 0.5)
        length = (nblk - 1) * blk + blk // 2   # nblk-1 full blocks + ring
        q = bf16((b, h, hd), 1.0)
        if codec_on:
            cts = jax.vmap(lambda v: fixed.compress(v, k=5))(blocks)
            args = (q, cts.signman.reshape(nblk, -1), cts.planes,
                    cts.dict_syms, cts.esc_pos, cts.esc_raw, None, ring)
        else:
            args = (q, None, None, None, None, None, blocks, ring)
        out, m, l = decode_attend(*args, length, 0, WINDOW_NONE, k=5,
                                  hkv=hkv, hd=hd, kv_idx=kv_idx, scale=scale,
                                  tp=1, interpret=True)
        want = ref.decode_attend_ref(q, blocks, ring, length, kv_idx=kv_idx,
                                     scale=scale)
        np.testing.assert_allclose(_normalized(out, l), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_windowed_and_sharded_positions(self):
        b, h, hkv, hd, nblk, blk = 2, 4, 2, 16, 3, 8
        from repro.core import fixed
        from repro.kernels.decode_attend import decode_attend
        w = 2 * hkv * hd
        kv_idx = (0, 0, 1, 1)
        scale = hd ** -0.5
        blocks = bf16((nblk, b, blk, w), 0.5)
        ring = bf16((b, blk, w), 0.5)
        q = bf16((b, h, hd), 1.0)
        cts = jax.vmap(lambda v: fixed.compress(v, k=5))(blocks)
        for tp, ti, length, window in [(2, 0, 37, 11), (2, 1, 37, 11),
                                       (4, 3, 61, 5)]:
            out, m, l = decode_attend(
                q, cts.signman.reshape(nblk, -1), cts.planes, cts.dict_syms,
                cts.esc_pos, cts.esc_raw, None, ring, length, ti, window,
                k=5, hkv=hkv,
                hd=hd, kv_idx=kv_idx, scale=scale, tp=tp, interpret=True)
            want = ref.decode_attend_ref(q, blocks, ring, length,
                                         kv_idx=kv_idx, scale=scale,
                                         window=window, tp=tp, ti=ti)
            np.testing.assert_allclose(_normalized(out, l), np.asarray(want),
                                       rtol=1e-4, atol=1e-4, err_msg=(tp, ti))

    def test_escapes_patched_in_kernel(self):
        """Values outside the k=4 dictionary recover via the side channel."""
        b, h, hkv, hd, nblk, blk = 1, 4, 2, 16, 2, 8
        from repro.core import fixed
        from repro.kernels.decode_attend import WINDOW_NONE, decode_attend
        w = 2 * hkv * hd
        x = np.asarray(bf16((nblk, b, blk, w), 0.5), np.float32)
        # deterministic block 0: 15 frequent exponents fill the k=4
        # dictionary exactly, then 4 rare huge values MUST take the escape
        # side channel (capacity max(n/128, 8) = 8 here) — guaranteed
        # 0 < n_escapes <= capacity regardless of RNG state
        base = np.float32(2.0) ** ((np.arange(blk * w) % 15) - 10)
        base[-4:] = np.float32(2.0) ** np.asarray([40, 45, 50, 55])
        x[0] = base.reshape(b, blk, w)
        blocks = jnp.asarray(x).astype(jnp.bfloat16)
        ring = bf16((b, blk, w), 0.5)
        q = bf16((b, h, hd), 1.0)
        cts = jax.vmap(lambda v: fixed.compress(v, k=4))(blocks)
        assert int(cts.n_escapes.max()) > 0
        out, m, l = decode_attend(
            q, cts.signman.reshape(nblk, -1), cts.planes, cts.dict_syms,
            cts.esc_pos, cts.esc_raw, None, ring, nblk * blk + 2, 0,
            WINDOW_NONE, k=4,
            hkv=hkv, hd=hd, kv_idx=(0, 0, 1, 1), scale=hd ** -0.5, tp=1,
            interpret=True)
        want = ref.decode_attend_ref(q, blocks, ring, nblk * blk + 2,
                                     kv_idx=(0, 0, 1, 1), scale=hd ** -0.5)
        np.testing.assert_allclose(_normalized(out, l), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestDecodeAttendPaged:
    """Page-table kernel vs the pure-jnp oracle: per-slot lengths, unmapped
    pages, GQA vs MQA, windowed/full, codec on/off, MLA."""

    @pytest.mark.parametrize("heads", [(4, 2), (5, 1), (8, 8)],
                             ids=["gqa", "mqa", "mha"])
    @pytest.mark.parametrize("codec_on", [True, False], ids=["codec", "raw"])
    @pytest.mark.parametrize("window", [None, 9], ids=["full", "windowed"])
    def test_matches_ref(self, heads, codec_on, window):
        h, hkv = heads
        hd, blk, n_s, maxp, n_pages = 16, 8, 3, 3, 9
        from repro.core import fixed
        from repro.kernels.decode_attend import (WINDOW_NONE,
                                                 decode_attend_paged)
        w = 2 * hkv * hd
        g = max(h // hkv, 1)
        kv_idx = tuple(min(i // g, hkv - 1) for i in range(h))
        scale = hd ** -0.5
        tp, ti = 2, 1
        pages = bf16((n_pages, blk, w), 0.5)
        ring = bf16((n_s, blk, w), 0.5)
        pt = jnp.asarray(RNG.integers(0, n_pages, (n_s, maxp)), jnp.int32)
        pt = pt.at[1, 1:].set(-1)                # short slot: unmapped tail
        lengths = jnp.asarray([2 * blk * tp + 3, 2, maxp * blk * tp],
                              jnp.int32)
        q = bf16((n_s, h, hd), 1.0)
        if codec_on:
            cts = jax.vmap(lambda v: fixed.compress(v, k=5))(pages)
            args = (q, cts.signman, cts.planes, cts.dict_syms, cts.esc_pos,
                    cts.esc_raw, None, ring)
        else:
            args = (q, None, None, None, None, None, pages, ring)
        win = WINDOW_NONE if window is None else window
        out, m, l = decode_attend_paged(
            *args, jnp.clip(pt, 0, None), lengths, ti, win, k=5, hkv=hkv,
            hd=hd, kv_idx=kv_idx, scale=scale, tp=tp, interpret=True)
        want = ref.paged_decode_attend_ref(
            q, pages, pt, lengths, ring, kv_idx=kv_idx, scale=scale,
            window=win, tp=tp, ti=ti)
        np.testing.assert_allclose(_normalized(out, l), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_mla_latent_payload(self):
        lora, rope, h, blk, n_s, maxp, n_pages = 24, 8, 4, 8, 2, 2, 5
        from repro.core import fixed
        from repro.kernels.decode_attend import (WINDOW_NONE,
                                                 decode_attend_paged)
        w = lora + rope
        pages = bf16((n_pages, blk, w), 0.5)
        ring = bf16((n_s, blk, w), 0.5)
        pt = jnp.asarray(RNG.integers(0, n_pages, (n_s, maxp)), jnp.int32)
        lengths = jnp.asarray([blk + 3, 2 * blk], jnp.int32)
        q = bf16((n_s, h, w), 1.0)
        cts = jax.vmap(lambda v: fixed.compress(v, k=5))(pages)
        out, m, l = decode_attend_paged(
            q, cts.signman, cts.planes, cts.dict_syms, cts.esc_pos,
            cts.esc_raw, None, ring, jnp.clip(pt, 0, None), lengths, 0,
            WINDOW_NONE, k=5,
            hkv=1, hd=w, kv_idx=(), scale=w ** -0.5, mla_lora=lora, tp=1,
            interpret=True)
        want = ref.paged_decode_attend_ref(
            q, pages, pt, lengths, ring, kv_idx=(), scale=w ** -0.5,
            mla_lora=lora, tp=1, ti=0)
        assert out.shape == (n_s, h, lora)
        np.testing.assert_allclose(_normalized(out, l), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
