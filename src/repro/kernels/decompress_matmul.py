"""Pallas TPU kernel: fused JIT weight decompression + matmul.

The paper stores weights compressed in DRAM/HBM and decompresses them
just-in-time "near compute".  On TPU, "near compute" is VMEM: this kernel
streams packed weight tiles HBM→VMEM, decodes them on the VPU, and feeds the
MXU — HBM weight traffic is the *packed* size, and the decompressed tile
never round-trips to HBM.  This is the memory-roofline payoff of LEXI for
the decode phase (weight-bandwidth-bound).

    out (M,N) f32 = x (M,K) bf16 @ W_packed (K,N)

W_packed = (signman (K,N) u8, planes (k,N/32,K) u32, dict (2^k,) u8), as
produced by ``ref.compress_weight_2d``: word (w, r) of plane b holds bit b
of the codes of W[r, 32w:32w+32].  The word axis sits second-minor so a
(bn/32, bk) tile of words is a legal Mosaic block for any N; in-kernel the
words expand along sublanes into the transposed exponent tile (bn, bk),
which one XLU transpose turns into the (bk, bn) tile the MXU consumes.
The dictionary is a scalar-prefetch operand (SMEM): each code maps to its
exponent by a 2^k-way compare-select against scalars.  Escape-free leaves
only (the param packer picks the smallest escape-free k per leaf).

Serving shapes are arbitrary (M=1 decode rows, tp-sharded N): the packed
weight is never padded or copied.  The K tile divides K (the largest
multiple of 128 that does, else K itself), the N tile is ``bn`` with a
partial last block (out-of-range columns compute garbage that is never
written back), and only x is padded along M.  N must be a multiple of 32
(the bit-plane word width — a pack-time invariant of the format).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 32
VMEM_LIMIT = 64 * 1024 * 1024


def _dm_kernel(dict_ref, x_ref, sm_ref, planes_ref, out_ref, *, k: int):
    # --- decode the W tile from packed fields -------------------------------
    words = planes_ref[...]                           # (k, bn/32, bk) u32
    nw, bk = words.shape[1], words.shape[2]
    sub = jax.lax.broadcasted_iota(jnp.uint32, (nw, LANES, bk), 1)
    codes = jnp.zeros((nw, LANES, bk), jnp.uint32)
    for b in range(k):                                # unrolled
        bits = (words[b][:, None, :] >> sub) & jnp.uint32(1)
        codes = codes | (bits << jnp.uint32(b))
    codes = codes.reshape(nw * LANES, bk).astype(jnp.int32)   # (bn, bk)
    exp = jnp.zeros(codes.shape, jnp.int32)
    for j in range(1 << k):                           # SMEM dictionary
        exp = jnp.where(codes == j, dict_ref[j], exp)
    sm = sm_ref[...].astype(jnp.int32)                # (bk, bn)
    bits16 = ((sm & 0x80) << 8) | (exp.T << 7) | (sm & 0x7F)
    w = jax.lax.bitcast_convert_type(bits16.astype(jnp.uint16), jnp.bfloat16)

    # --- MXU matmul with K-accumulation --------------------------------------
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)


def _k_tile(kk: int, want: int) -> int:
    """K tile: ``want`` if it divides K, else the largest multiple of 128
    up to 2048 that divides K, else K itself."""
    if kk % want == 0:
        return want
    for t in range(min(2048, kk) // 128 * 128, 0, -128):
        if kk % t == 0:
            return t
    return kk


@functools.partial(jax.jit,
                   static_argnames=("k", "bm", "bk", "bn", "interpret"))
def decompress_matmul(x: jax.Array, signman: jax.Array, planes: jax.Array,
                      dict_syms: jax.Array, *, k: int = 6, bm: int = 256,
                      bk: int = 2048, bn: int = 256,
                      interpret: bool = False) -> jax.Array:
    """x (M,K) bf16 @ packed W (K,N) -> (M,N) f32.  Any M/K/N (N % 32 == 0);
    ``bn`` should be a multiple of 256 when N > bn (word-tile alignment)."""
    m, kk = x.shape
    n = signman.shape[1]
    assert n % LANES == 0, "packed N must be a multiple of 32 (bit-plane lanes)"
    bm, bn = min(bm, m), min(bn, n)
    bk = _k_tile(kk, bk)
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    grid = (mp // bm, pl.cdiv(n, bn), kk // bk)
    out = pl.pallas_call(
        functools.partial(_dm_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, l, d: (i, l)),
                pl.BlockSpec((bk, bn), lambda i, j, l, d: (l, j)),
                pl.BlockSpec((k, bn // LANES, bk),
                             lambda i, j, l, d: (0, j, l)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, l, d: (i, j))),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="decompress_matmul",
    )(dict_syms.astype(jnp.int32), x, signman, planes)
    return out[:m] if mp != m else out
