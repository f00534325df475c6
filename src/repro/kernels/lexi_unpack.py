"""Pallas TPU kernel: LEXI-FW exponent unpack (the paper's ingress decoder).

Inverse of ``lexi_pack``: reconstructs BF16 values from {sign·mantissa bytes,
bit-plane-packed codes, dictionary}.  This is the TPU analogue of the paper's
multi-stage LUT decoder — but where variable-length Huffman needs 4 staged
prefix tables, the fixed-width code resolves every symbol with one 32-entry
dictionary lookup per element, implemented as an unrolled select-sum so it
lowers to pure VPU ops (no dynamic gather on the critical path).

Escapes are NOT resolved here (they are data-dependent scatter); the ops.py
wrapper patches the <=C escape positions afterwards — the paper's escape is
likewise resolved by a separate final-stage path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import BLOCK_ELEMS

LANES = 32


def _unpack_kernel(sm_ref, planes_ref, dict_ref, x_ref, *, k: int):
    sm = sm_ref[0]                                    # (B,) uint8
    words = planes_ref[0]                             # (k, B/32) uint32
    lane = jnp.arange(LANES, dtype=jnp.uint32)
    codes = jnp.zeros(words.shape[1:] + (LANES,), jnp.uint32)
    for b in range(k):                                # unrolled
        bits = (words[b][:, None] >> lane) & jnp.uint32(1)
        codes = codes | (bits << jnp.uint32(b))
    codes = codes.reshape(-1)                         # (B,) flat groups of 32
    d = dict_ref[...]                                 # (2^k,) uint8
    exp = jnp.zeros_like(codes, dtype=jnp.uint16)
    for j in range(d.shape[0]):                       # unrolled select-sum
        exp = jnp.where(codes == jnp.uint32(j), jnp.uint16(0) + d[j], exp)
    smu = sm.astype(jnp.uint16)
    u16 = ((smu & jnp.uint16(0x80)) << 8) | (exp << 7) | (smu & jnp.uint16(0x7F))
    x_ref[0] = jax.lax.bitcast_convert_type(u16, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def lexi_unpack(signman: jax.Array, planes: jax.Array, dict_syms: jax.Array,
                *, k: int, interpret: bool = False) -> jax.Array:
    """Unpack (G,B) blocks back to bf16 (escape-free fast path)."""
    g, b = signman.shape
    return pl.pallas_call(
        functools.partial(_unpack_kernel, k=k),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, b), lambda i: (i, 0)),
            pl.BlockSpec((1, k, b // LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((dict_syms.shape[0],), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, b), jnp.bfloat16),
        interpret=interpret,
    )(signman, planes, dict_syms)
