"""Pallas TPU kernel: 256-bin exponent histogram (the paper's M-lane unit).

The paper builds the per-layer exponent histogram with M parallel lanes of
small frequency caches merged through an arbiter.  The TPU-native equivalent
is an MXU trick: split the 8-bit exponent into hi/lo nibbles, one-hot each to
(N, 16), and compute ``hiOH^T @ loOH`` — a single 16×N×16 matmul whose
(16, 16) result *is* the 256-bin histogram (hist[hi*16+lo]).  The systolic
array plays the role of the paper's parallel counting lanes.

Grid steps accumulate into the same output block (standard Pallas reduction
pattern), so arbitrarily long streams cost one (16,16) tile of VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(x_ref, hist_ref):
    xb = x_ref[0]                                     # (B,) bf16
    u16 = jax.lax.bitcast_convert_type(xb, jnp.uint16)
    exp = ((u16 >> 7) & jnp.uint16(0xFF)).astype(jnp.int32)
    hi = (exp >> 4)[:, None]                          # (B, 1)
    lo = (exp & 15)[:, None]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 16), 1)
    hi_oh = (hi == iota).astype(jnp.float32)          # (B, 16)
    lo_oh = (lo == iota).astype(jnp.float32)          # (B, 16)
    counts = jax.lax.dot_general(                     # (16, 16) on the MXU
        hi_oh, lo_oh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += counts.reshape(-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def exp_histogram(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """256-bin exponent histogram of a (G, B) bf16 stream -> (256,) int32."""
    g, b = x.shape
    return pl.pallas_call(
        _hist_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((1, b), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((256,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((256,), jnp.int32),
        interpret=interpret,
    )(x)
