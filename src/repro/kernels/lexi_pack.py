"""Pallas TPU kernel: LEXI-FW exponent pack (the paper's egress encoder).

Splits a BF16 stream into {sign·mantissa bytes, bit-plane-packed k-bit
exponent codes} at link rate.  This is the hardware-adapted analogue of the
paper's M-lane LUT encoder: the 256-entry encode LUT lives in VMEM and every
lane of the VPU performs the lookup simultaneously (the paper replicates the
LUT per lane for the same reason).

Layout: input is reshaped to (G, B) blocks (B = 32*128 elements); each grid
step packs one block entirely in VMEM:

    x (1, B) bf16  ->  signman (1, B) u8, planes (1, k, B/32) u32

Bit-plane packing groups 32 *consecutive* elements per uint32 word, matching
``repro.core.packing`` bit-for-bit, so kernel output is interchangeable with
the pure-JAX codec.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import BLOCK_ELEMS

LANES = 32


def _pack_kernel(x_ref, lut_ref, sm_ref, planes_ref, *, k: int):
    xb = x_ref[0]                                     # (B,) bf16
    u16 = jax.lax.bitcast_convert_type(xb, jnp.uint16)
    sign = (u16 >> 15).astype(jnp.uint8)
    man = (u16 & jnp.uint16(0x7F)).astype(jnp.uint8)
    sm_ref[0] = (sign << 7) | man
    exp = ((u16 >> 7) & jnp.uint16(0xFF)).astype(jnp.int32)
    codes = jnp.take(lut_ref[...], exp, axis=0)       # (B,) uint32 VMEM LUT
    grouped = codes.reshape(-1, LANES)                # (B/32, 32) flat groups
    lane = jnp.arange(LANES, dtype=jnp.uint32)
    for b in range(k):                                # unrolled: k <= 8
        planes_ref[0, b] = jnp.sum(
            ((grouped >> jnp.uint32(b)) & jnp.uint32(1)) << lane,
            axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def lexi_pack(x: jax.Array, enc_lut: jax.Array, *, k: int,
              block: int = BLOCK_ELEMS, interpret: bool = False):
    """Pack a (G, B) bf16 stream. Returns (signman (G,B) u8,
    planes (G,k,B/32) u32)."""
    g, b = x.shape
    assert b % LANES == 0 and b % block == 0 or b == block, (g, b, block)
    grid = (g,)
    return pl.pallas_call(
        functools.partial(_pack_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, b), lambda i: (i, 0)),
            pl.BlockSpec((256,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, b), lambda i: (i, 0)),
            pl.BlockSpec((1, k, b // LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, b), jnp.uint8),
            jax.ShapeDtypeStruct((g, k, b // LANES), jnp.uint32),
        ],
        interpret=interpret,
    )(x, enc_lut)
