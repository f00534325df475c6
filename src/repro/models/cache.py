"""Hybrid caches (attention KV + SSM state), LEXI-block-compressed.

This is the paper's "hybrid cache" path: caches are compressed block-by-block
when written back to memory and decompressed just before use (§4.1).  The TPU
layout:

* the KV cache is **sequence-sharded over "model", interleaved**: shard t
  owns global positions {p : p % tp == t}.  Writes round-robin across shards
  (balanced), every shard holds ~len/tp live slots, and decode attention is
  a partial attention per shard merged with one tiny psum
  (``layers.merge_partials``) — no head-divisibility constraints ever.
* each full block of ``block`` owned slots is stored as a LEXI-FW
  ``Compressed`` (K and V of the block packed together); a bf16 ring buffer
  holds the in-flight block.  HBM-side cache traffic is the packed size.
* the decode step streams compressed blocks through a scan, decompressing
  one block at a time (the VMEM-sized working set of a fused kernel) with
  online-softmax accumulation.
* MLA caches the *latent* (c_kv ‖ k_rope) instead of K/V — LEXI compresses
  the latent stream (already 4-8x smaller than full KV: double win).
* the SSM state cache is the fixed-size recurrent state (f32 master for
  recurrence stability — see note at bottom).

With ``CodecConfig.cache=False`` blocks are stored raw bf16 with identical
structure, giving the A/B for the roofline.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core import fixed, packing
from repro.core.collectives import CodecConfig
from repro.kernels import ops as kops
from repro.kernels.decode_attend import page_plane_shape
from . import layers
from .ssm import SSMState

WINDOW_NONE = kops.WINDOW_NONE     # "no window" sentinel (huge i32)


class KVBlocks(NamedTuple):
    """Per-layer, per-shard compressed KV block store.

    Payload width W = kv_width(cfg): 2*Hkv*hd for plain attention (K‖V),
    kv_lora+rope for MLA.  Block value shape: (B, block, W).
    """
    signman: Optional[jax.Array]    # (nblk, N) u8, N = B*block*W
    planes: Optional[jax.Array]     # (nblk, k, Npad/32) u32
    dict_syms: Optional[jax.Array]  # (nblk, 2^k) u8
    esc_pos: Optional[jax.Array]    # (nblk, C) i32
    esc_raw: Optional[jax.Array]    # (nblk, C) u8
    raw_blocks: Optional[jax.Array] # (nblk, B, block, W) bf16 when codec off
    ring: jax.Array                 # (B, block, W) bf16 in-flight block
    length: jax.Array               # () i32 global tokens written (all shards)


def kv_width(cfg: ModelConfig) -> int:
    if cfg.mla is not None:
        return cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    return 2 * cfg.n_kv_heads * cfg.head_dim


def n_blocks(cfg: ModelConfig, run: RunConfig, max_len: int, tp: int) -> int:
    """Capacity in blocks per shard (prefill length + decode growth room)."""
    slots = max_len // tp
    return slots // run.codec.cache_block + 2


def empty_kv(cfg: ModelConfig, run: RunConfig, batch_loc: int, max_len: int,
             tp: int) -> KVBlocks:
    w = kv_width(cfg)
    blk = run.codec.cache_block
    nblk = n_blocks(cfg, run, max_len, tp)
    n = batch_loc * blk * w
    npad = packing.pad_to_lanes(n)
    c = run.codec.esc_capacity(n)
    k = run.codec.k
    if run.codec.cache:
        return KVBlocks(
            signman=jnp.zeros((nblk, n), jnp.uint8),
            planes=jnp.zeros((nblk, k, npad // 32), jnp.uint32),
            dict_syms=jnp.zeros((nblk, 1 << k), jnp.uint8),
            esc_pos=jnp.full((nblk, c), npad, jnp.int32),
            esc_raw=jnp.zeros((nblk, c), jnp.uint8),
            raw_blocks=None,
            ring=jnp.zeros((batch_loc, blk, w), jnp.bfloat16),
            length=jnp.zeros((), jnp.int32))
    return KVBlocks(signman=None, planes=None, dict_syms=None, esc_pos=None,
                    esc_raw=None,
                    raw_blocks=jnp.zeros((nblk, batch_loc, blk, w),
                                         jnp.bfloat16),
                    ring=jnp.zeros((batch_loc, blk, w), jnp.bfloat16),
                    length=jnp.zeros((), jnp.int32))


def store_block(kv: KVBlocks, idx, vals: jax.Array,
                codec: CodecConfig) -> KVBlocks:
    """Write one full block (B, blk, W) into slot ``idx``."""
    if codec.cache:
        ct = fixed.compress(vals, k=codec.k,
                            esc_capacity=codec.esc_capacity(vals.size))
        upd = jax.lax.dynamic_update_index_in_dim
        return kv._replace(
            signman=upd(kv.signman, ct.signman, idx, 0),
            planes=upd(kv.planes, ct.planes, idx, 0),
            dict_syms=upd(kv.dict_syms, ct.dict_syms, idx, 0),
            esc_pos=upd(kv.esc_pos, ct.esc_pos, idx, 0),
            esc_raw=upd(kv.esc_raw, ct.esc_raw, idx, 0))
    return kv._replace(raw_blocks=jax.lax.dynamic_update_index_in_dim(
        kv.raw_blocks, vals, idx, 0))


def load_block(kv: KVBlocks, idx, batch_loc: int, blk: int, w: int,
               codec: CodecConfig) -> jax.Array:
    if codec.cache:
        ct = fixed.Compressed(
            signman=kv.signman[idx], planes=kv.planes[idx],
            dict_syms=kv.dict_syms[idx], esc_pos=kv.esc_pos[idx],
            esc_raw=kv.esc_raw[idx], n_escapes=jnp.zeros((), jnp.int32),
            shape=(batch_loc, blk, w), k=codec.k)
        return fixed.decompress(ct)
    return kv.raw_blocks[idx]


# ---------------------------------------------------------------------------
# prefill -> decode transition
# ---------------------------------------------------------------------------

def fill_from_prefill(cfg: ModelConfig, run: RunConfig, kv: KVBlocks,
                      vals_loc: jax.Array, seq_len: int, tp: int) -> KVBlocks:
    """Load this shard's interleaved slots (B, S/tp, W) into the block store.

    ``vals_loc`` must already be this shard's interleaved sequence slice with
    full head width (the engine's all_to_all produces it).
    """
    b, slots, w = vals_loc.shape
    blk = run.codec.cache_block
    nfull = slots // blk
    rem = slots - nfull * blk

    if nfull:
        def body(kv_c, i):
            vals = jax.lax.dynamic_slice_in_dim(vals_loc, i * blk, blk, axis=1)
            return store_block(kv_c, i, vals, run.codec), None

        kv, _ = jax.lax.scan(body, kv, jnp.arange(nfull))
    if rem:  # partial tail lives in the raw ring (slots nfull*blk + i)
        ring = jax.lax.dynamic_update_slice_in_dim(
            kv.ring, vals_loc[:, nfull * blk:].astype(jnp.bfloat16), 0, 1)
        kv = kv._replace(ring=ring)
    return kv._replace(length=jnp.asarray(seq_len, jnp.int32))


# ---------------------------------------------------------------------------
# decode: append + attend
# ---------------------------------------------------------------------------

def split_kv_payload(cfg: ModelConfig, vals: jax.Array, hq: int):
    """Cache payload (B, L, W) -> (k, v) per-query-head views.

    Plain attention: (B,Hq,L,hd) with the static GQA head map (pad query
    heads clip onto the last kv head).  MLA: the latent travels whole,
    (B,1,L,lora+rope) / (B,1,L,lora).  Shared by the fixed-batch block
    store and the paged store so the two decode paths cannot diverge.
    """
    b, L, _ = vals.shape
    if cfg.mla is not None:
        lora = cfg.mla.kv_lora_rank
        return vals[:, None], vals[:, None, :, :lora]
    import numpy as _np
    g_real = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    kv_idx = jnp.asarray(_np.clip(_np.arange(hq) // g_real, 0,
                                  cfg.n_kv_heads - 1))
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    kvv = vals.reshape(b, L, hkv, 2, hd)
    k = kvv[:, :, :, 0].transpose(0, 2, 1, 3)
    v = kvv[:, :, :, 1].transpose(0, 2, 1, 3)
    return jnp.take(k, kv_idx, axis=1), jnp.take(v, kv_idx, axis=1)


def merge_partial(carry, po, pm, pl):
    """Online-softmax accumulation of one attention partial into (out,m,l)."""
    out, m, l = carry
    m_new = jnp.maximum(m, pm)
    a_old, a_new = jnp.exp(m - m_new), jnp.exp(pm - m_new)
    return (out * a_old[..., None] + po * a_new[..., None],
            m_new, l * a_old + pl * a_new)


# ---------------------------------------------------------------------------
# decode attention: shared masking + streaming helpers and backend dispatch
#
# Both cache stores (fixed-batch blocks, paged pool) stream [compressed
# blocks ‖ raw ring] with the same live-slot arithmetic; the per-block scan
# body exists ONCE here (the "jax" backend), and the fused Pallas kernels
# (``kernels.decode_attend``) implement identical semantics for the
# pallas/interpret backends — selected via ``run.codec.decode_backend``
# (see ``kernels.ops.resolve_decode_backend``).
# ---------------------------------------------------------------------------


def effective_window(spec: layers.AttnSpec, window):
    """Traced window size with the huge-sentinel convention: masking is
    always ``pos > L - 1 - window``, so non-windowed layers pass a value
    no live position can fail."""
    if spec.windowed and window is not None:
        return jnp.asarray(window, jnp.int32)
    return jnp.asarray(WINDOW_NONE, jnp.int32)


def stream_mask(lengths, i, blk: int, tp: int, ti, window, ring: bool):
    """Live mask (..., blk) for block ``i`` (or the ring) of the slot
    stream.  ``lengths`` is () for the fixed store or (S,) for the paged
    store; shard ``ti`` owns interleaved global positions p % tp == ti."""
    lengths = jnp.asarray(lengths, jnp.int32)
    loc_len = jnp.maximum((lengths - 1 - ti) // tp + 1, 0)
    nfull = loc_len // blk
    if ring:
        sl = nfull[..., None] * blk + jnp.arange(blk)
        live = sl < loc_len[..., None]
    else:
        sl = jnp.broadcast_to(i * blk + jnp.arange(blk),
                              lengths.shape + (blk,))
        live = jnp.broadcast_to((i < nfull)[..., None], sl.shape)
    pos = sl * tp + ti
    ok = (pos < lengths[..., None]) & (pos > lengths[..., None] - 1 - window)
    return ok & live


def gqa_head_table(cfg: ModelConfig, hq: int) -> tuple:
    """Static per-query-head kv index table (pad heads clip onto the last
    kv head) — must match ``split_kv_payload``'s dynamic take."""
    import numpy as _np
    g_real = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    return tuple(int(x) for x in
                 _np.clip(_np.arange(hq) // g_real, 0, cfg.n_kv_heads - 1))


def _attend_scan_jax(cfg, q, spec, hq, load_fn, n_steps, valid_fn,
                     ring_kv, ring_ok):
    """The ONE pure-JAX streaming-attention body: scan compressed blocks,
    then the raw ring, with online-softmax partial merging."""
    b = q.shape[0]
    hd_v = (cfg.mla.kv_lora_rank if cfg.mla is not None else cfg.head_dim)

    def scan_blk(carry, i):
        k, v = split_kv_payload(cfg, load_fn(i), hq)
        po, pm, pl = layers.attention_partial(q, k, v, valid_fn(i), spec)
        return merge_partial(carry, po, pm, pl), None

    init = (jnp.zeros((b, hq, 1, hd_v), jnp.float32),
            jnp.full((b, hq, 1), layers.NEG_INF, jnp.float32),
            jnp.zeros((b, hq, 1), jnp.float32))
    (out, m, l), _ = jax.lax.scan(scan_blk, init, jnp.arange(n_steps))

    kr, vr = split_kv_payload(cfg, ring_kv, hq)
    po, pm, pl = layers.attention_partial(q, kr, vr, ring_ok, spec)
    return merge_partial((out, m, l), po, pm, pl)


def _kernel_statics(cfg: ModelConfig, run: RunConfig, q: jax.Array,
                    spec: layers.AttnSpec):
    """Static kwargs shared by both fused-kernel entry points."""
    hq = q.shape[1]
    hd = q.shape[-1]
    return dict(
        k=run.codec.k,
        hkv=cfg.n_kv_heads,
        hd=cfg.head_dim,
        kv_idx=(() if cfg.mla is not None else gqa_head_table(cfg, hq)),
        scale=(spec.scale if spec.scale is not None else hd ** -0.5),
        softcap=spec.softcap,
        mla_lora=(cfg.mla.kv_lora_rank if cfg.mla is not None else None))


def append_token(cfg: ModelConfig, run: RunConfig, kv: KVBlocks,
                 new_vals: jax.Array, tp: int) -> KVBlocks:
    """Append one token's KV/latent (B, W) at global position kv.length.

    Only the owner shard (length % tp) actually mutates its ring; when the
    ring fills, it is compressed into the next block slot (paper: caches are
    compressed block-by-block when written back).
    """
    blk = run.codec.cache_block
    ti = jax.lax.axis_index("model")
    pos = kv.length
    owner = (pos % tp) == ti
    loc = pos // tp                              # owner's local slot index
    ring_idx = loc % blk
    ring_new = jax.lax.dynamic_update_index_in_dim(
        kv.ring, new_vals.astype(jnp.bfloat16)[:, None], ring_idx, 1)
    ring_out = jnp.where(owner, ring_new, kv.ring)
    kv = kv._replace(ring=ring_out, length=pos + 1)

    # flush when the owner's ring just filled (global condition per shard;
    # non-owners keep their store untouched via the same `owner` predicate)
    flush = owner & (ring_idx == blk - 1)
    blk_idx = loc // blk

    def do_flush(kv_c):
        return store_block(kv_c, blk_idx, kv_c.ring, run.codec)

    return jax.lax.cond(flush, do_flush, lambda c: c, kv)


def attend_cache(cfg: ModelConfig, run: RunConfig, kv: KVBlocks,
                 q: jax.Array, spec: layers.AttnSpec, tp: int,
                 window=None) -> jax.Array:
    """Decode attention: q (B,Hq,1,hd) FULL heads on every shard; streams
    this shard's compressed blocks + ring; merges across shards.

    MLA decode uses the *absorbed* form and calls this with q already
    projected into latent space (hd = lora+rope) and hd_v = lora; the
    caller then applies the value up-projection.

    The backend (fused Pallas kernel vs pure-JAX scan) comes from
    ``run.codec.decode_backend``.  Returns (B,Hq,1,hd_v) bf16, fully
    normalized across shards.
    """
    b, hq, _, _ = q.shape
    blk = run.codec.cache_block
    w = kv_width(cfg)
    ti = jax.lax.axis_index("model")
    length = kv.length
    win = effective_window(spec, window)
    backend = kops.resolve_decode_backend(run.codec)

    if backend != "jax":
        out, m, l = kops.decode_attend(
            q[:, :, 0], kv.signman, kv.planes, kv.dict_syms, kv.esc_pos,
            kv.esc_raw, kv.raw_blocks, kv.ring, length, ti, win, tp=tp,
            interpret=(backend == "interpret"),
            **_kernel_statics(cfg, run, q, spec))
        return layers.merge_partials(out[:, :, None, :], m[..., None],
                                     l[..., None], "model")

    nblk = (kv.signman.shape[0] if run.codec.cache
            else kv.raw_blocks.shape[0])
    load = lambda i: load_block(kv, i, b, blk, w, run.codec)
    valid = lambda i: jnp.broadcast_to(
        stream_mask(length, i, blk, tp, ti, win, ring=False)[None], (b, blk))
    ring_ok = jnp.broadcast_to(
        stream_mask(length, 0, blk, tp, ti, win, ring=True)[None], (b, blk))
    out, m, l = _attend_scan_jax(cfg, q, spec, hq, load, nblk, valid,
                                 kv.ring, ring_ok)
    return layers.merge_partials(out, m, l, "model")


# ---------------------------------------------------------------------------
# Paged KV cache (continuous batching)
#
# The block store above is fixed-batch: all B sequences advance in lockstep
# and share one global length.  The paged store below decouples them so a
# scheduler can admit/evict sequences mid-flight (vLLM-style paging, with
# LEXI block compression as the page representation):
#
# * a pool of fixed-size *pages*, each holding ``block`` interleaved-owned
#   slots of ONE sequence, LEXI-FW-compressed on fill (codec on) or raw bf16
#   (codec off) — the compressed layout of a page is byte-identical to a
#   B=1 block of the fixed-batch store, so a prefilled sequence's blocks
#   copy straight into pages with no decompress/recompress round trip;
# * a per-slot page table mapping block index -> page id (-1 = unmapped)
#   plus a page_used bitmap for functional in-graph allocation;
# * per-slot bf16 rings for the in-flight partial block and per-slot
#   lengths, so every sequence appends/attends at its own position.
#
# All of it remains per-shard state inside shard_map: shard t owns global
# positions {p : p % tp == t} of every sequence, exactly like the fixed
# store, so decode attention stays a partial-per-shard + one tiny psum.
# ---------------------------------------------------------------------------


class PagedKV(NamedTuple):
    """Per-layer, per-shard paged KV store (one sequence per slot).

    Page payload shape: (block, W); compressed fields have leading n_pages.

    **Page lifecycle (refcount / copy-on-write convention).**  A page is
    immutable once full: it is written exactly once (trunk insert via
    ``paged_insert_many`` or a ring flush in ``append_token_paged``) and
    never rewritten while ``page_used`` is set.  That immutability is what
    makes prefix sharing safe: several slots' page-table rows may point at
    the SAME page id (mapped by ``map_prefix_pages``), and the only mutable
    per-sequence state — the partially filled tail block — lives in each
    slot's private ``ring`` row, so "copy-on-write" is simply "the tail is
    never shared" (a slot that outgrows a shared prefix flushes its ring
    into a freshly allocated page, never into a shared one).  Reference
    counts are HOST-side state (the serving scheduler owns them, keyed by
    prefix content with per-shard page-id vectors, because page ids may
    diverge across shards after unaligned releases); the device-side
    contract is only: ``release_pages(..., free_mask)`` clears exactly the
    pages the host decided hit refcount zero, while shared pages stay
    ``page_used`` until their last referencing slot releases.
    """
    signman: Optional[jax.Array]    # (P, block, W) u8
    planes: Optional[jax.Array]     # (P, k, *page_plane_shape) u32
    dict_syms: Optional[jax.Array]  # (P, 2^k) u8
    esc_pos: Optional[jax.Array]    # (P, C) i32
    esc_raw: Optional[jax.Array]    # (P, C) u8
    raw_pages: Optional[jax.Array]  # (P, block, W) bf16 when codec off
    page_table: jax.Array           # (S, maxp) i32, -1 = unmapped
    page_used: jax.Array            # (P,) bool
    ring: jax.Array                 # (S, block, W) bf16 in-flight blocks


def max_pages_per_slot(run: RunConfig, max_len: int, tp: int) -> int:
    return (max_len // tp) // run.codec.cache_block + 2


def page_bytes(cfg: ModelConfig, run: RunConfig) -> Tuple[int, int]:
    """(stored_bytes, raw_bytes) per page per shard — the serving metric.

    Derived from the abstract shapes of the actual store (one source of
    truth: whatever ``empty_paged_kv`` allocates per page is what HBM pays).
    """
    if cfg.n_heads == 0:            # attention-free: no KV pages at all
        return 0, 0
    import numpy as _np
    pkv = jax.eval_shape(lambda: empty_paged_kv(cfg, run, 1,
                                                run.codec.cache_block, 1))
    per_page = lambda f: int(_np.prod(f.shape[1:])) * f.dtype.itemsize
    raw = per_page(pkv.ring)                       # ring row == one raw page
    if not run.codec.cache:
        return raw, raw
    stored = sum(per_page(f) for f in (pkv.signman, pkv.planes,
                                       pkv.dict_syms, pkv.esc_pos,
                                       pkv.esc_raw))
    return stored, raw


def empty_paged_kv(cfg: ModelConfig, run: RunConfig, n_slots: int,
                   max_len: int, tp: int,
                   n_pages: Optional[int] = None) -> PagedKV:
    w = kv_width(cfg)
    blk = run.codec.cache_block
    maxp = max_pages_per_slot(run, max_len, tp)
    # In-graph allocation (append_token_paged) has no way to fail loudly on
    # pool exhaustion — it would hand out a live page.  Oversubscription is
    # therefore rejected here, at construction, where it CAN fail loudly.
    if n_pages is not None and n_pages < n_slots * maxp:
        raise ValueError(
            f"page pool oversubscription unsupported: n_pages={n_pages} < "
            f"n_slots*max_pages={n_slots * maxp}")
    P_ = n_pages if n_pages is not None else n_slots * maxp
    n = blk * w
    npad = packing.pad_to_lanes(n)
    c = run.codec.esc_capacity(n)
    k = run.codec.k
    pt = jnp.full((n_slots, maxp), -1, jnp.int32)
    used = jnp.zeros((P_,), jnp.bool_)
    ring = jnp.zeros((n_slots, blk, w), jnp.bfloat16)
    if run.codec.cache:
        return PagedKV(
            signman=jnp.zeros((P_, blk, w), jnp.uint8),
            planes=jnp.zeros((P_, k) + page_plane_shape(blk, w, npad),
                             jnp.uint32),
            dict_syms=jnp.zeros((P_, 1 << k), jnp.uint8),
            esc_pos=jnp.full((P_, c), npad, jnp.int32),
            esc_raw=jnp.zeros((P_, c), jnp.uint8),
            raw_pages=None, page_table=pt, page_used=used, ring=ring)
    return PagedKV(signman=None, planes=None, dict_syms=None, esc_pos=None,
                   esc_raw=None,
                   raw_pages=jnp.zeros((P_, blk, w), jnp.bfloat16),
                   page_table=pt, page_used=used, ring=ring)


def load_pages(pkv: PagedKV, page_ids: jax.Array, blk: int, w: int,
               codec: CodecConfig) -> jax.Array:
    """Gather + decompress one page per slot.  page_ids (S,) -> (S, blk, W).

    Unmapped ids (-1) load page 0; callers mask those positions invalid.
    """
    pid = jnp.clip(page_ids, 0, None)
    if codec.cache:
        n_s = pid.shape[0]
        ct = fixed.Compressed(
            signman=pkv.signman[pid].reshape(n_s, -1),
            planes=pkv.planes[pid].reshape(n_s, codec.k, -1),
            dict_syms=pkv.dict_syms[pid], esc_pos=pkv.esc_pos[pid],
            esc_raw=pkv.esc_raw[pid],
            n_escapes=jnp.zeros(pid.shape, jnp.int32),
            shape=(blk, w), k=codec.k)
        return jax.vmap(fixed.decompress)(ct)
    return pkv.raw_pages[pid]


def append_token_paged(cfg: ModelConfig, run: RunConfig, pkv: PagedKV,
                       new_vals: jax.Array, lengths: jax.Array,
                       active: jax.Array, tp: int) -> PagedKV:
    """Append one token's KV/latent (S, W) at each slot's own position.

    Only the owner shard of each slot's next position writes its ring;
    inactive slots are untouched.  Rings that just filled are compressed
    into freshly allocated pages (free-list allocation stays in-graph:
    argsort of the used bitmap yields free page ids deterministically).
    """
    blk = run.codec.cache_block
    ti = jax.lax.axis_index("model")
    pos = lengths                                    # (S,)
    owner = (pos % tp) == ti
    write = owner & active
    loc = pos // tp
    ring_idx = loc % blk
    oh = (ring_idx[:, None] == jnp.arange(blk)[None]) & write[:, None]
    ring = jnp.where(oh[..., None], new_vals.astype(jnp.bfloat16)[:, None],
                     pkv.ring)
    pkv = pkv._replace(ring=ring)

    flush = write & (ring_idx == blk - 1)
    blk_idx = loc // blk                             # page-table column
    maxp = pkv.page_table.shape[1]
    n_pages = pkv.page_used.shape[0]

    def do_flush(pkv_c: PagedKV) -> PagedKV:
        free_order = jnp.argsort(pkv_c.page_used)    # free pages first
        rank = jnp.cumsum(flush.astype(jnp.int32)) - 1
        page = free_order[jnp.clip(rank, 0, n_pages - 1)]
        tgt = jnp.where(flush, page, n_pages)        # sentinel drops
        if run.codec.cache:
            ct = jax.vmap(lambda r: fixed.compress(
                r, k=run.codec.k,
                esc_capacity=run.codec.esc_capacity(r.size)))(pkv_c.ring)
            pkv_c = pkv_c._replace(
                signman=pkv_c.signman.at[tgt].set(
                    ct.signman.reshape((-1,) + pkv_c.signman.shape[1:]),
                    mode="drop"),
                planes=pkv_c.planes.at[tgt].set(
                    ct.planes.reshape((-1,) + pkv_c.planes.shape[1:]),
                    mode="drop"),
                dict_syms=pkv_c.dict_syms.at[tgt].set(ct.dict_syms,
                                                      mode="drop"),
                esc_pos=pkv_c.esc_pos.at[tgt].set(ct.esc_pos, mode="drop"),
                esc_raw=pkv_c.esc_raw.at[tgt].set(ct.esc_raw, mode="drop"))
        else:
            pkv_c = pkv_c._replace(
                raw_pages=pkv_c.raw_pages.at[tgt].set(pkv_c.ring,
                                                      mode="drop"))
        ohp = (blk_idx[:, None] == jnp.arange(maxp)[None]) & flush[:, None]
        pt = jnp.where(ohp, page[:, None], pkv_c.page_table)
        used = pkv_c.page_used.at[tgt].set(True, mode="drop")
        return pkv_c._replace(page_table=pt, page_used=used)

    any_flush = jnp.any(flush)
    # The scope names the flush in a profiler trace: the conditional and
    # the ops of its taken branch, which inherit it where XLA's rewrites
    # (the scatters become sorts) leave an op without an op_name.
    with jax.named_scope("lexi.ring_flush"):
        return jax.lax.cond(any_flush, do_flush, lambda c: c, pkv)


def flush_work(lengths, active, blk: int, tp: int) -> Tuple[int, int]:
    """(rings_filled, rings_compressed) of ``append_token_paged`` steps,
    on the host: ``lengths``/``active`` are the slots' write positions and
    write masks, (S,) for one step or (steps, S) summed over steps.

    The rule is the device's: a written slot's ring fills when
    ``(length // tp) % blk == blk - 1``, and a shard that sees any ring
    fill compresses (codec off: copies) the rings of all S slots, one page
    per slot per layer.  Counts are per step over all shards, not per
    layer (every attention layer flushes together)."""
    lengths = np.asarray(lengths)
    active = np.asarray(active, bool)
    fills = active & ((lengths // tp) % blk == blk - 1)
    shard = lengths % tp
    flushing = sum(int((fills & (shard == t)).any(axis=-1).sum())
                   for t in range(tp))
    return int(fills.sum()), flushing * lengths.shape[-1]


def attend_paged(cfg: ModelConfig, run: RunConfig, pkv: PagedKV,
                 q: jax.Array, lengths: jax.Array, spec: layers.AttnSpec,
                 tp: int, window=None) -> jax.Array:
    """Per-slot paged decode attention: q (S,Hq,1,hd) FULL heads on every
    shard; streams each slot's pages via its page table, then the rings;
    merges across shards.  ``lengths`` (S,) are post-append token counts.

    The backend (fused page-table Pallas kernel vs pure-JAX scan) comes
    from ``run.codec.decode_backend``.  Returns (S,Hq,1,hd_v) bf16, fully
    normalized across shards.
    """
    b, hq, _, _ = q.shape
    blk = run.codec.cache_block
    w = kv_width(cfg)
    ti = jax.lax.axis_index("model")
    maxp = pkv.page_table.shape[1]
    win = effective_window(spec, window)
    backend = kops.resolve_decode_backend(run.codec)

    if backend != "jax":
        out, m, l = kops.decode_attend_paged(
            q[:, :, 0], pkv.signman, pkv.planes, pkv.dict_syms, pkv.esc_pos,
            pkv.esc_raw, pkv.raw_pages, pkv.ring,
            jnp.clip(pkv.page_table, 0, None),
            lengths, ti, win, tp=tp, interpret=(backend == "interpret"),
            **_kernel_statics(cfg, run, q, spec))
        return layers.merge_partials(out[:, :, None, :], m[..., None],
                                     l[..., None], "model")

    load = lambda i: load_pages(pkv, pkv.page_table[:, i], blk, w, run.codec)
    valid = lambda i: stream_mask(lengths, i, blk, tp, ti, win, ring=False)
    ring_ok = stream_mask(lengths, 0, blk, tp, ti, win, ring=True)
    out, m, l = _attend_scan_jax(cfg, q, spec, hq, load, maxp, valid,
                                 pkv.ring, ring_ok)
    return layers.merge_partials(out, m, l, "model")


def paged_insert_many(cfg: ModelConfig, run: RunConfig, pkv: PagedKV,
                      kvb: KVBlocks, slots: jax.Array, seq_len: int,
                      tp: int) -> PagedKV:
    """Scatter ``B`` prefilled B=1 block stores into paged slots ``slots``.

    ``kvb`` is a stack of B independent B=1 fixed stores (leading batch
    axis, as produced by a vmapped prefill): the compressed layout of a
    (1, blk, W) block equals a (blk, W) page byte-for-byte (same element
    count, same dictionary build), so full blocks transfer by one batched
    array scatter; each partial tail transfers as that slot's ring row.

    ``seq_len`` is a static int and MUST be a multiple of tp (the admission
    trunk is bucket-aligned; unaligned leftovers replay through
    ``append_token_paged`` afterwards), so every shard owns the same static
    number of full blocks — which also keeps page-id allocation in lockstep
    across shards for freshly admitted trunks.
    """
    assert seq_len % tp == 0, (seq_len, tp)
    blk = run.codec.cache_block
    nb = kvb.ring.shape[0]
    nfull = (seq_len // tp) // blk                   # static, same per shard
    maxp = pkv.page_table.shape[1]
    assert nfull <= maxp, (nfull, maxp)

    used = pkv.page_used
    if nfull:
        free_order = jnp.argsort(used)               # free pages first
        pages = free_order[:nb * nfull].reshape(nb, nfull)
        tgt = pages.reshape(-1)                      # distinct ids
        if run.codec.cache:
            pkv = pkv._replace(
                signman=pkv.signman.at[tgt].set(
                    kvb.signman[:, :nfull].reshape((nb * nfull,) +
                                                   pkv.signman.shape[1:])),
                planes=pkv.planes.at[tgt].set(
                    kvb.planes[:, :nfull].reshape((nb * nfull,) +
                                                  pkv.planes.shape[1:])),
                dict_syms=pkv.dict_syms.at[tgt].set(
                    kvb.dict_syms[:, :nfull].reshape((nb * nfull,) +
                                                     pkv.dict_syms.shape[1:])),
                esc_pos=pkv.esc_pos.at[tgt].set(
                    kvb.esc_pos[:, :nfull].reshape((nb * nfull,) +
                                                   pkv.esc_pos.shape[1:])),
                esc_raw=pkv.esc_raw.at[tgt].set(
                    kvb.esc_raw[:, :nfull].reshape((nb * nfull,) +
                                                   pkv.esc_raw.shape[1:])))
        else:
            pkv = pkv._replace(
                raw_pages=pkv.raw_pages.at[tgt].set(
                    kvb.raw_blocks[:, :nfull, 0].reshape(
                        (nb * nfull,) + pkv.raw_pages.shape[1:])))
        used = used.at[tgt].set(True)
        rows = jnp.concatenate(
            [pages, jnp.full((nb, maxp - nfull), -1, jnp.int32)], axis=1)
    else:
        rows = jnp.full((nb, maxp), -1, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    pt = pkv.page_table.at[slots].set(rows)
    ring = pkv.ring.at[slots].set(kvb.ring[:, 0])
    return pkv._replace(page_table=pt, page_used=used, ring=ring)


def map_prefix_pages(pkv: PagedKV, slot, page_ids: jax.Array,
                     n_cols) -> PagedKV:
    """Map already-filled shared pages into slot ``slot``'s table row.

    ``page_ids`` (maxp,) holds this shard's page ids for the matched full
    prefix columns (entries beyond ``n_cols`` are ignored); the slot's ring
    starts empty (the shared prefix is block-aligned; the tail is private —
    see the PagedKV lifecycle note).  Zero data moves: sharing is pure
    page-table indirection, the caller (host scheduler) owns the refcounts.
    """
    maxp = pkv.page_table.shape[1]
    n_pages = pkv.page_used.shape[0]
    cols = jnp.arange(maxp)
    n_cols = jnp.asarray(n_cols, jnp.int32)
    row = jnp.where(cols < n_cols, page_ids, -1)
    slot = jnp.asarray(slot, jnp.int32)
    pt = jax.lax.dynamic_update_index_in_dim(pkv.page_table, row, slot, 0)
    # shared pages are live already; the masked set is a no-op re-assert
    tgt = jnp.where(cols < n_cols, page_ids, n_pages)
    used = pkv.page_used.at[tgt].set(True, mode="drop")
    ring = jax.lax.dynamic_update_index_in_dim(
        pkv.ring, jnp.zeros_like(pkv.ring[0]), slot, 0)
    return pkv._replace(page_table=pt, page_used=used, ring=ring)


class PageWire(NamedTuple):
    """One slot's cache payload in transfer layout (per layer, per shard).

    The dense, slot-ordered view of a sequence's pages that crosses a
    replica boundary: ``export_sequence`` gathers it out of a pool,
    ``import_sequence`` scatters it into another pool.  Compressed fields
    are BYTE-IDENTICAL to the pool pages they came from (no decompress /
    recompress round trip); page-id indirection never crosses the wire —
    column order IS the sequence order.

    Leaves are ``None`` exactly as in ``PagedKV`` (codec on: compressed
    fields; codec off: ``raw_pages``).  Shapes (n_cols = exported full-page
    columns, the max over shards; trailing invalid columns are zeroed):

      signman   (n_cols, N) u8          N = block*W
      planes    (n_cols, k, Npad/32) u32
      dict_syms (n_cols, 2^k) u8
      esc_pos   (n_cols, C) i32
      esc_raw   (n_cols, C) u8
      raw_pages (n_cols, block, W) bf16
      ring      (block, W) bf16         the in-flight partial tail block
    """
    signman: Optional[jax.Array]
    planes: Optional[jax.Array]
    dict_syms: Optional[jax.Array]
    esc_pos: Optional[jax.Array]
    esc_raw: Optional[jax.Array]
    raw_pages: Optional[jax.Array]
    ring: jax.Array


def local_full_pages(length, ti, blk: int, tp: int):
    """Full pages shard ``ti`` holds for a sequence of ``length`` tokens
    (interleaved ownership: shard t owns positions p % tp == t)."""
    length = jnp.asarray(length, jnp.int32)
    loc_len = jnp.maximum((length - 1 - ti) // tp + 1, 0)
    return loc_len // blk


def export_n_cols(length: int, blk: int, tp: int) -> int:
    """Static page-column count of a wire payload: the max over shards of
    ``local_full_pages`` — host-side mirror of the device arithmetic."""
    return max(max((int(length) - 1 - t) // tp + 1, 0) // blk
               for t in range(tp)) if length > 0 else 0


def export_sequence(pkv: PagedKV, slot, n_cols: int, length,
                    tp: int, col0=0) -> PageWire:
    """Gather slot ``slot``'s cache payload into transfer layout.

    The disaggregated-prefill seam: a prefill replica exports each admitted
    sequence as a :class:`PageWire` whose compressed planes are byte-copied
    from its pool pages (pages are immutable once full, so the gather IS
    the serialization — no decompress/recompress round trip), and a decode
    replica scatters it into its own pool via :func:`import_sequence`.

    ``n_cols`` is static (``export_n_cols``); shards holding fewer full
    pages (``length % (block*tp) != 0``) zero their trailing columns so the
    payload is deterministic.  ``slot``/``length`` may be traced.

    **Chunked mode.**  ``col0`` (traced, default 0) windows the gather to
    page columns ``[col0, col0 + n_cols)`` — the streaming-prefill export:
    as admission fills pages, the prefill replica gathers just the freshly
    completed columns and ships them ahead of the closing blob as
    ``repro.serve.transport.pack_chunk`` frames (columns at or past a
    shard's ``local_full_pages`` are zeroed exactly as in whole-sequence
    mode, and the window is re-keyed on ``n_cols`` only, so the jit cache
    stays small).

    **WIRE FORMAT (version 1).**  The byte framing a transport ships (see
    ``repro.serve.transport.SequenceBlob.to_wire``) — everything little-
    endian, arrays serialized as raw C-order bytes in exactly this order:

      header:
        magic      4B  b"LXSQ"
        version    u8  = 1        (bump on ANY layout change)
        flags      u8  bit0 codec-on, bit1 KV present, bit2 SSM present
        tp         u16            per-shard layout: every array below
        n_layers   u16            carries a leading (tp, n_layers) pair of
        n_cols     u16            axes, shard-major then layer
        block      u16            tokens per page per shard
        w          u32            payload width W (kv_width)
        k          u16            dictionary index bits
        esc_cap    u32            C, escape side-channel slots per page
        npad       u32            N padded to lanes (planes row = npad/32 u32)
        length     u32            tokens held by the sequence (all shards)
        cur_token  i32            next decode input (last emitted token)
        n_emitted  u16            tokens generated so far (normally 1)
        emitted    n_emitted x i32
      ssm section (iff flag bit2; dims header then arrays, per shard/layer):
        nh_loc u16, headdim u16, d_state u16, d_conv-1 u16, di_loc u32
        h       (tp, L, nh_loc, headdim, d_state) f32
        conv_x  (tp, L, d_conv-1, di_loc) bf16
        conv_bc (tp, L, d_conv-1, 2*d_state) bf16
      ring section (iff flag bit1):
        ring    (tp, L, block, w) bf16
      page section (iff flag bit1) — one entry per VALID column, iterated
      shard-major, then layer, then column (shard t has
      ``local_full_pages(length, t)`` valid columns):
        tag     u8   0 = inline payload, 1 = content reference
        digest  12B  sha256(payload)[:12]
        payload      iff tag 0: the page's fields back to back —
                     codec on : signman (N u8) ‖ planes (k*npad/32 u32) ‖
                                dict_syms (2^k u8) ‖ esc_pos (C i32) ‖
                                esc_raw (C u8)
                     codec off: raw page (block*w bf16)

    Tag-1 entries let a transport replace pages the receiver already holds
    (content-addressed dedup); a receiver resolves them from its digest
    store and must fail loudly on an unknown digest.
    """
    blk, w = pkv.ring.shape[1], pkv.ring.shape[2]
    maxp = pkv.page_table.shape[1]
    ti = jax.lax.axis_index("model")
    nfull = local_full_pages(length, ti, blk, tp)
    row = pkv.page_table[jnp.asarray(slot, jnp.int32)]       # (maxp,)
    cols = jnp.asarray(col0, jnp.int32) + jnp.arange(n_cols)
    valid = (cols < nfull) & (cols < maxp)
    pid = jnp.where(valid,
                    jnp.clip(row[jnp.clip(cols, 0, maxp - 1)], 0, None), 0)

    def take(field, zero_dtype, wire_shape=None):
        if field is None:
            return None
        out = field[pid]
        mask = valid.reshape((n_cols,) + (1,) * (out.ndim - 1))
        out = jnp.where(mask, out, jnp.zeros((), zero_dtype))
        return out if wire_shape is None else out.reshape(wire_shape)

    k = pkv.planes.shape[1] if pkv.planes is not None else 0
    return PageWire(
        signman=take(pkv.signman, jnp.uint8, (n_cols, -1)),
        planes=take(pkv.planes, jnp.uint32, (n_cols, k, -1)),
        dict_syms=take(pkv.dict_syms, jnp.uint8),
        esc_pos=take(pkv.esc_pos, jnp.int32),
        esc_raw=take(pkv.esc_raw, jnp.uint8),
        raw_pages=take(pkv.raw_pages, jnp.bfloat16),
        ring=pkv.ring[jnp.asarray(slot, jnp.int32)])


def import_sequence(pkv: PagedKV, slot, wire: PageWire, length,
                    tp: int, col0=0) -> PagedKV:
    """Scatter a :class:`PageWire` into slot ``slot`` of this pool.

    Exact inverse of :func:`export_sequence` up to page ids: fresh pages
    come from THIS pool's free list (argsort of ``page_used`` — works for
    any permutation of the free list, ids need not match the exporting
    pool's), the compressed fields are byte-copied into them, and the
    slot's page-table row maps them in sequence order.  Columns beyond this
    shard's ``local_full_pages`` are dropped via the sentinel-scatter
    convention.  The re-export of an imported slot is bit-identical to the
    original wire payload (round-trip proof in ``tests/test_disagg.py``).

    ``col0`` (traced, default 0) makes the import PARTIAL: the wire columns
    represent global page columns ``[col0, col0 + n_cols)`` and the table
    row's entries below ``col0`` are left as they are — the decode-replica
    prefix-reuse path maps already-resident shared pages into columns
    ``[0, col0)`` first (``map_prefix_pages``) and imports only the
    unmatched suffix columns from the wire.

    In-graph allocation cannot fail loudly, so the HOST must check pool
    capacity before dispatching an import (``col0 + n_cols <= max pages per
    slot`` and enough free pages on every shard/layer) — see
    ``repro.serve.disagg.DecodeReplica.import_handoff``, which rejects
    oversubscription before any device state mutates.

    See the export docstring for the WIRE FORMAT this pair defines.
    """
    lead = wire.signman if pkv.signman is not None else wire.raw_pages
    n_cols = lead.shape[0]
    blk = pkv.ring.shape[1]
    maxp = pkv.page_table.shape[1]
    n_pages = pkv.page_used.shape[0]
    assert n_cols <= maxp, (n_cols, maxp)
    ti = jax.lax.axis_index("model")
    nfull = local_full_pages(length, ti, blk, tp)
    slot = jnp.asarray(slot, jnp.int32)
    col0 = jnp.asarray(col0, jnp.int32)

    free_order = jnp.argsort(pkv.page_used)          # free pages first
    pages = free_order[:n_cols] if n_cols else jnp.zeros((0,), jnp.int32)
    valid = col0 + jnp.arange(n_cols) < nfull
    tgt = jnp.where(valid, pages, n_pages)           # sentinel drops
    if pkv.signman is not None:
        pool = lambda f, v: v.reshape((n_cols,) + f.shape[1:])
        pkv = pkv._replace(
            signman=pkv.signman.at[tgt].set(pool(pkv.signman, wire.signman),
                                            mode="drop"),
            planes=pkv.planes.at[tgt].set(pool(pkv.planes, wire.planes),
                                          mode="drop"),
            dict_syms=pkv.dict_syms.at[tgt].set(wire.dict_syms, mode="drop"),
            esc_pos=pkv.esc_pos.at[tgt].set(wire.esc_pos, mode="drop"),
            esc_raw=pkv.esc_raw.at[tgt].set(wire.esc_raw, mode="drop"))
    else:
        pkv = pkv._replace(
            raw_pages=pkv.raw_pages.at[tgt].set(wire.raw_pages, mode="drop"))
    used = pkv.page_used.at[tgt].set(True, mode="drop")
    cols = jnp.arange(maxp)
    padded = jnp.zeros((maxp,), jnp.int32).at[col0 + jnp.arange(n_cols)].set(
        pages.astype(jnp.int32), mode="drop")
    prev = pkv.page_table[slot]                      # kept below col0
    row = jnp.where(cols < col0, prev,
                    jnp.where(cols < nfull, padded, -1))
    pt = jax.lax.dynamic_update_index_in_dim(pkv.page_table, row, slot, 0)
    ring = jax.lax.dynamic_update_index_in_dim(pkv.ring, wire.ring, slot, 0)
    return pkv._replace(page_table=pt, page_used=used, ring=ring)


def release_pages(pkv: PagedKV, slots_mask: jax.Array,
                  free_mask: Optional[jax.Array] = None) -> PagedKV:
    """Unmap masked slots' table rows and free their pages.

    ``free_mask`` None (no sharing): every page referenced by a masked row
    is freed.  With prefix sharing the host passes ``free_mask`` (n_pages,)
    bool — exactly the pages whose refcount hit zero — so pages still
    referenced by other slots' rows stay ``page_used``.
    """
    pt = pkv.page_table
    if free_mask is None:
        n_pages = pkv.page_used.shape[0]
        owned = slots_mask[:, None] & (pt >= 0)
        tgt = jnp.where(owned, pt, n_pages).reshape(-1)  # sentinel drops
        used = pkv.page_used.at[tgt].set(False, mode="drop")
    else:
        used = pkv.page_used & ~free_mask
    pt2 = jnp.where(slots_mask[:, None], -1, pt)
    return pkv._replace(page_table=pt2, page_used=used)
