"""Pure-jnp oracles for every Pallas kernel in this package.

Layout contract shared by kernels and oracles (and bit-compatible with
``repro.core.fixed`` / ``repro.core.packing``):

* tensors are processed as (G, B) row-major blocks of a flattened stream,
  B = BLOCK_ELEMS (default 32*128 = 4096, MXU/VPU aligned);
* exponent codes are bit-plane packed in flat groups of 32 consecutive
  elements: planes[(g,) b, w] holds bit b of elements 32*w .. 32*w+31 of
  block g;
* the encode LUT maps the 8-bit exponent to a k-bit dictionary index with
  ESCAPE = 2^k - 1; the decode dictionary maps index -> exponent byte.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import entropy as E
from repro.core import packing

BLOCK_ROWS = 32
BLOCK_COLS = 128
BLOCK_ELEMS = BLOCK_ROWS * BLOCK_COLS


def pack_ref(x: jax.Array, enc_lut: jax.Array, k: int):
    """Oracle for ``lexi_pack``: (G, B) bf16 -> (signman (G,B) u8,
    planes (G,k,B/32) u32)."""
    g, b = x.shape
    u16 = E.jnp_to_u16(x)
    signman = E.jnp_signman(u16)
    exp = ((u16 >> 7) & 0xFF).astype(jnp.int32)
    codes = enc_lut[exp]                              # (G, B) uint32
    planes = packing.bitplane_pack(codes, k)          # (G, k, B/32)
    return signman, planes


def unpack_ref(signman: jax.Array, planes: jax.Array, dict_syms: jax.Array,
               k: int) -> jax.Array:
    """Oracle for ``lexi_unpack``: inverse of pack_ref (escapes handled by
    the caller via the side channel)."""
    codes = packing.bitplane_unpack(planes, k)        # (G, B)
    exp = dict_syms[codes.astype(jnp.int32)]          # (G, B) uint8
    u16 = E.jnp_combine(signman, exp)
    return E.jnp_from_u16(u16)


def histogram_ref(x: jax.Array) -> jax.Array:
    """Oracle for ``exp_histogram``: 256-bin exponent histogram (int32)."""
    u16 = E.jnp_to_u16(x)
    exp = ((u16 >> 7) & 0xFF).astype(jnp.int32).reshape(-1)
    return jnp.zeros((256,), jnp.int32).at[exp].add(1)


def decompress_matmul_ref(x: jax.Array, signman: jax.Array, planes: jax.Array,
                          dict_syms: jax.Array, k: int) -> jax.Array:
    """Oracle for ``decompress_matmul``: x (M,K) bf16 @ packed W (K,N).

    ``planes`` is (k, N/32, K): word (w, r) packs the codes of
    W[r, 32w:32w+32] (row r's codes in flat groups of 32 along N, with the
    word axis ahead of K so W tiles cleanly along both axes).
    """
    codes = weight_codes(planes, k)                               # (K, N)
    exp = dict_syms[codes.astype(jnp.int32)]
    u16 = E.jnp_combine(signman, exp)
    w = E.jnp_from_u16(u16)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def weight_codes(planes: jax.Array, k: int) -> jax.Array:
    """(..., k, N/32, K) weight bit planes -> (..., K, N) uint32 codes."""
    return packing.bitplane_unpack(jnp.moveaxis(planes, -1, -3), k)


def compress_weight_2d(w: jax.Array, k: int = 6):
    """Packer for matmul weights: (K,N) bf16 ->
    (signman (K,N) u8, planes (k,N/32,K) u32, dict (2^k,) u8, n_escapes).

    k defaults to 6 for at-rest weights: a 63-symbol dictionary empirically
    covers every exponent of real weight tensors (distinct ~23), so the
    fused kernel never sees an escape; ``n_escapes`` lets callers verify.
    """
    from repro.core import fixed
    kk, n = w.shape
    assert n % 32 == 0, "N must be a multiple of 32"
    u16 = E.jnp_to_u16(w)
    signman = E.jnp_signman(u16)
    exp = ((u16 >> 7) & 0xFF).astype(jnp.int32)
    hist = jnp.zeros((256,), jnp.int32).at[exp.reshape(-1)].add(1)
    dict_syms, enc_lut = fixed.build_dictionary(hist, k)
    codes = enc_lut[exp]                              # (K, N)
    esc = fixed.esc_index(k)
    n_escapes = jnp.sum((codes == esc).astype(jnp.int32))
    planes = packing.bitplane_pack(codes, k)          # (K, k, N/32)
    planes = jnp.transpose(planes, (1, 2, 0))         # (k, N/32, K)
    return signman, planes, dict_syms, n_escapes


from .decode_attend import WINDOW_NONE  # one sentinel everywhere


def _softmax_attend(q, k, v, ok, scale, softcap, mla: bool):
    """Single-pass masked softmax attention (independent summation order
    from the kernels' online accumulation — a true oracle).

    q (B,H,hd); k/v (B,L,[H,]hd); ok (B,L).  Returns normalized (B,H,hd_v).
    """
    if mla:
        s = jnp.einsum("bhd,bnd->bhn", q, k,
                       preferred_element_type=jnp.float32) * scale
    else:
        s = jnp.einsum("bhd,bnhd->bhn", q, k,
                       preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(ok[:, None, :], s, -2.0e38)
    m = s.max(-1)
    p = jnp.where(ok[:, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.maximum(p.sum(-1), 1e-30)
    if mla:
        out = jnp.einsum("bhn,bnd->bhd", p, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bhn,bnhd->bhd", p, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    return out / l[..., None]


def _head_views(vals, kv_idx, hd, mla_lora):
    """(B, L, W) payload -> per-head (k, v) for the oracle attention."""
    if mla_lora is not None:
        return vals, vals[..., :mla_lora]
    b, L, w = vals.shape
    hkv = w // (2 * hd)
    kv = vals.reshape(b, L, hkv, 2, hd)
    kidx = jnp.asarray(kv_idx)
    k = jnp.take(kv[..., 0, :], kidx, axis=2)       # (B, L, H, hd)
    v = jnp.take(kv[..., 1, :], kidx, axis=2)
    return k, v


def decode_attend_ref(q, blocks_bf16, ring, length, *, kv_idx, scale,
                      softcap=None, mla_lora=None, window=WINDOW_NONE,
                      tp=1, ti=0):
    """Oracle for ``decode_attend`` (fixed store): q (B,H,hd); blocks
    (nblk,B,blk,W) decompressed bf16; ring (B,blk,W); length/ti python ints.
    Returns the NORMALIZED single-shard attention (B,H,hd_v) f32 — compare
    against the kernel's out/l."""
    nblk, b, blk, w = blocks_bf16.shape
    loc_len = max((length - 1 - ti) // tp + 1, 0)
    nfull = loc_len // blk
    vals = jnp.concatenate(
        [jnp.moveaxis(blocks_bf16, 0, 1).reshape(b, nblk * blk, w), ring],
        axis=1)
    sl = jnp.concatenate([jnp.arange(nblk * blk),
                          nfull * blk + jnp.arange(blk)])
    live = jnp.concatenate([jnp.arange(nblk * blk) // blk < nfull,
                            nfull * blk + jnp.arange(blk) < loc_len])
    pos = sl * tp + ti
    ok = live & (pos < length) & (pos > length - 1 - window)
    k, v = _head_views(vals, kv_idx, q.shape[-1], mla_lora)
    return _softmax_attend(q, k, v, jnp.broadcast_to(ok[None], (b, ok.size)),
                           scale, softcap, mla_lora is not None)


def paged_decode_attend_ref(q, pages_bf16, page_table, lengths, ring, *,
                            kv_idx, scale, softcap=None, mla_lora=None,
                            window=WINDOW_NONE, tp=1, ti=0):
    """Oracle for ``decode_attend_paged``: q (S,H,hd); pages (P,blk,W)
    decompressed bf16; page_table (S,maxp) int32 (-1 unmapped); lengths (S,)
    ints; ring (S,blk,W).  Returns normalized (S,H,hd_v) f32."""
    n_s, maxp = page_table.shape
    _, blk, w = pages_bf16.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    loc_len = jnp.maximum((lengths - 1 - ti) // tp + 1, 0)      # (S,)
    nfull = loc_len // blk
    gathered = pages_bf16[jnp.clip(page_table, 0, None)]        # (S,maxp,blk,W)
    vals = jnp.concatenate([gathered.reshape(n_s, maxp * blk, w), ring],
                           axis=1)
    sl = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(maxp * blk)[None], (n_s, maxp * blk)),
         nfull[:, None] * blk + jnp.arange(blk)[None]], axis=1)
    live = jnp.concatenate(
        [jnp.arange(maxp * blk)[None] // blk < nfull[:, None],
         nfull[:, None] * blk + jnp.arange(blk)[None] < loc_len[:, None]],
        axis=1)
    pos = sl * tp + ti
    ok = live & (pos < lengths[:, None]) \
        & (pos > lengths[:, None] - 1 - window)
    k, v = _head_views(vals, kv_idx, q.shape[-1], mla_lora)
    return _softmax_attend(q, k, v, ok, scale, softcap, mla_lora is not None)
