"""LEXI-FW: the static-shape deployment codec (TPU adaptation of LEXI).

XLA collectives need static shapes, so the in-graph codec trades Huffman's
variable-length entropy coding for a *fixed-width dictionary* code while
keeping the paper's structure intact:

  * per-tensor histogram of the 8-bit exponent field (the paper's M-lane
    histogram unit),
  * frequency-ranked dictionary of the 2^k - 1 most common exponents (the
    paper's 32-entry codebook; default k=5 → 31 symbols + escape),
  * reserved ESCAPE index (2^k - 1) with a fixed-capacity side channel of
    (position, raw exponent) pairs (the paper's escape code + raw suffix),
  * sign+mantissa travel verbatim as one byte (the paper's flit layout
    {header, signs, mantissas, coded exponents}).

Wire cost per value: 8 (signman) + k (code) bits + C/N·(32+8) (escape slots)
+ 2^k·8/N (dictionary) ⇒ ~1.20× for k=5, ~1.30× for k=4, vs Huffman's ~1.47×.
Losslessness: exact whenever #escapes <= C; the encoder reports ``n_escapes``
so callers can detect overflow (never observed on real tensor distributions —
the paper reports zero escapes; property tests exercise the path anyway).

Everything here is jit/vmap/shard_map-compatible pure JAX; the Pallas kernels
in ``repro.kernels`` implement the hot paths with identical semantics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import entropy as E
from . import packing

DEFAULT_K = 5
# Escape side-channel capacity as a fraction of N (1/128 ≈ 0.8% of values).
DEFAULT_ESC_FRAC = 128


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Compressed:
    """A LEXI-FW compressed BF16 tensor (all fields static-shaped).

    ``signman``: (N,) uint8 — sign<<7 | mantissa, verbatim.
    ``planes``:  (k, Np/32) uint32 — bit-plane-packed dictionary indices
                 (Np = N padded to a multiple of 32).
    ``dict_syms``: (2^k,) uint8 — frequency-ranked exponent dictionary;
                 slot 2^k - 1 is the reserved ESCAPE (stored as 0).
    ``esc_pos``: (C,) int32 — element positions of escapes (Np = empty slot).
    ``esc_raw``: (C,) uint8 — raw exponents for the escape slots.
    ``n_escapes``: () int32 — total escapes seen (> C means overflow).
    ``shape``/``k``/``n``: static aux data.
    """

    signman: jax.Array
    planes: jax.Array
    dict_syms: jax.Array
    esc_pos: jax.Array
    esc_raw: jax.Array
    n_escapes: jax.Array
    shape: Tuple[int, ...]
    k: int

    # -- pytree plumbing -----------------------------------------------------
    def tree_flatten(self):
        children = (self.signman, self.planes, self.dict_syms,
                    self.esc_pos, self.esc_raw, self.n_escapes)
        return children, (self.shape, self.k)

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, k = aux
        return cls(*children, shape=shape, k=k)

    # -- accounting -----------------------------------------------------------
    @property
    def n(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    def wire_bytes(self) -> int:
        """Bytes that actually cross a link / sit in HBM."""
        return (self.signman.size * 1 + self.planes.size * 4 +
                self.dict_syms.size * 1 + self.esc_pos.size * 4 +
                self.esc_raw.size * 1 + 4)

    def ratio(self) -> float:
        """Compression ratio vs raw BF16."""
        return (2.0 * self.n) / self.wire_bytes()


def esc_index(k: int) -> int:
    return (1 << k) - 1


def build_dictionary(hist: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Frequency-ranked dictionary + 256-entry encode LUT.

    Returns (dict_syms (2^k,) uint8, enc_lut (256,) uint32).  Exponents not in
    the top 2^k - 1 map to the ESCAPE index.  Mirrors the paper's bitonic-
    sort + LUT-programming pipeline (hw model: ``repro.hw.codebook_pipeline``).
    """
    esc = esc_index(k)
    order = jnp.argsort(-hist.astype(jnp.int32), stable=True)  # 256 symbols
    top = order[:esc]
    present = hist[top] > 0
    dict_syms = jnp.where(present, top, 0).astype(jnp.uint8)
    dict_syms = jnp.concatenate(
        [dict_syms, jnp.zeros((1,), jnp.uint8)])  # escape slot
    enc_lut = jnp.full((256,), esc, jnp.uint32)
    # Only program slots whose symbol actually occurs (absent symbols keep
    # the escape mapping, so duplicate zeros in dict_syms are harmless).
    slot = jnp.where(present, jnp.arange(esc, dtype=jnp.uint32),
                     jnp.uint32(esc))
    enc_lut = enc_lut.at[top.astype(jnp.int32)].set(slot)
    return dict_syms, enc_lut


def exponent_histogram(exp: jax.Array) -> jax.Array:
    """256-bin histogram of (n,) int32 exponents, with no scatter.

    The nibble trick of ``kernels/exp_histogram.py`` in plain XLA: one-hot
    the high and low nibbles to (16, n) int8 and contract over n with int32
    accumulation, so ``hist[hi*16 + lo]`` is one small exact matmul.
    """
    nib = jnp.arange(16, dtype=jnp.int32)[:, None]
    hi = ((exp >> 4) == nib).astype(jnp.int8)                 # (16, n)
    lo = ((exp & 15) == nib).astype(jnp.int8)
    counts = jnp.einsum("im,jm->ij", hi, lo,
                        preferred_element_type=jnp.int32)
    return counts.reshape(256)


def encode_exponents(exp: jax.Array, dict_syms: jax.Array,
                     enc_lut: jax.Array, k: int) -> jax.Array:
    """``enc_lut[exp]`` as one compare-select per dictionary slot.

    Slot j codes ``dict_syms[j]`` only where the LUT programs that symbol
    to j (absent symbols, stored as 0, keep the escape), so at most one
    slot matches a value.  Any dictionary and LUT that
    :func:`build_dictionary` makes give the codes of the 256-entry gather
    without reading it per value.
    """
    esc = esc_index(k)
    syms = dict_syms[:esc].astype(jnp.int32)
    live = enc_lut[syms] == jnp.arange(esc, dtype=jnp.uint32)
    codes = jnp.full(exp.shape, esc, jnp.uint32)
    for j in range(esc):
        codes = jnp.where((exp == syms[j]) & live[j], jnp.uint32(j), codes)
    return codes


def escape_side_channel(exp: jax.Array, esc_mask: jax.Array, c: int,
                        np_: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(esc_pos (c,), esc_raw (c,), n_escapes) of the escapes in ``esc_mask``.

    Slot s holds the (s+1)-th escape: its position is where the running
    count of escapes first reaches s+1, found by binary search, so no
    scatter over the n values is needed.  Empty slots hold the sentinel
    ``np_`` and raw 0; escapes past the capacity are only counted.
    """
    n = exp.shape[0]
    seen = jnp.cumsum(esc_mask.astype(jnp.int32))
    n_escapes = seen[-1]
    want = jnp.arange(1, c + 1, dtype=jnp.int32)
    pos = jnp.searchsorted(seen, want, side="left").astype(jnp.int32)
    held = want <= n_escapes
    esc_pos = jnp.where(held, pos, np_)
    raw = exp[jnp.minimum(pos, n - 1)].astype(jnp.uint8)
    esc_raw = jnp.where(held, raw, jnp.uint8(0))
    return esc_pos, esc_raw, n_escapes


def _encode(x: jax.Array, k: int, esc_capacity: int | None, dictionary=None):
    """The shared body of :func:`compress` and :func:`compress_with_dict`."""
    shape = tuple(x.shape)
    u16 = E.jnp_to_u16(x).reshape(-1)
    n = u16.size
    np_ = packing.pad_to_lanes(n)
    c = esc_capacity if esc_capacity is not None else max(n // DEFAULT_ESC_FRAC, 8)
    signman = E.jnp_signman(u16)
    exp = ((u16 >> 7) & 0xFF).astype(jnp.int32)
    if dictionary is None:
        dictionary = build_dictionary(exponent_histogram(exp), k)
    dict_syms, enc_lut = dictionary

    codes = encode_exponents(exp, dict_syms, enc_lut, k)      # (n,) uint32
    planes = packing.bitplane_pack(jnp.pad(codes, (0, np_ - n)), k)
    esc_pos, esc_raw, n_escapes = escape_side_channel(
        exp, codes == esc_index(k), c, np_)
    return Compressed(signman=signman, planes=planes, dict_syms=dict_syms,
                      esc_pos=esc_pos, esc_raw=esc_raw, n_escapes=n_escapes,
                      shape=shape, k=k)


@functools.partial(jax.jit, static_argnames=("k", "esc_capacity"))
def compress(x: jax.Array, *, k: int = DEFAULT_K,
             esc_capacity: int | None = None) -> Compressed:
    """Compress a BF16 tensor (any shape) into a :class:`Compressed`.

    No scatter, sort or gather runs over the values (on the TPU, XLA turns
    a scatter into a sort over every value); the one gather reads the at
    most ``esc_capacity`` escapes."""
    return _encode(x, k, esc_capacity)


@jax.jit
def decompress(ct: Compressed) -> jax.Array:
    """Exact inverse of :func:`compress` (given no escape overflow)."""
    n = ct.n
    codes = packing.bitplane_unpack(ct.planes, ct.k)[:n]     # (n,) uint32
    exp = ct.dict_syms[codes.astype(jnp.int32)]              # (n,) uint8
    # Patch escapes from the side channel (sentinel positions drop).
    exp = exp.at[ct.esc_pos].set(ct.esc_raw, mode="drop")
    u16 = E.jnp_combine(ct.signman, exp)
    return E.jnp_from_u16(u16).reshape(ct.shape)


# ---------------------------------------------------------------------------
# Dictionary-free variant for inner loops (collectives): the dictionary is
# built per call anyway, but some call sites (e.g. a2a dispatch) prefer a
# caller-provided dictionary so all shards agree on the mapping.
# ---------------------------------------------------------------------------

def compress_with_dict(x: jax.Array, dict_syms: jax.Array, enc_lut: jax.Array,
                       *, k: int = DEFAULT_K,
                       esc_capacity: int | None = None) -> Compressed:
    """:func:`compress` with a caller's :func:`build_dictionary` output."""
    return _encode(x, k, esc_capacity, (dict_syms, enc_lut))


def wire_ratio(k: int = DEFAULT_K, esc_frac: int = DEFAULT_ESC_FRAC) -> float:
    """Analytic wire compression ratio of LEXI-FW (per-value amortized)."""
    bits = 8.0 + k + (40.0 / esc_frac)  # 32-bit pos + 8-bit raw per slot
    return 16.0 / bits
