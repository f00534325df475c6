"""Pallas TPU kernels: decode attention over a LEXI-compressed KV cache.

The paper's decode-phase story fused into one kernel family: each grid step
streams ONE compressed cache block HBM→VMEM ({sign·mantissa bytes, bit-plane
packed exponent codes, 2^k-entry dictionary, escape side channel}), decodes
it on the VPU, and runs one online-softmax attention step on the MXU — the
decompressed block never touches HBM, so cache bandwidth is the packed size.

Two entry points share the decode + attend body:

``decode_attend``  — fixed-batch block store (``models.cache.KVBlocks``).
    Blocks are indexed directly by the grid; all B sequences share one
    traced ``length``.  Grid = (nblk + 1,): the final step attends over the
    raw bf16 ring (the in-flight partial block) instead of a decoded block.

``decode_attend_paged`` — paged store (``models.cache.PagedKV``), the
    continuous-batching serving path.  **Page-table calling convention**:
    the kernel reads through per-slot page-id indirection — ``page_ids``
    (S, maxp) int32 is a scalar-prefetch operand, and the BlockSpec
    index_map of every compressed field is ``lambda s, i, pids, ...:
    pids[s, i]``, so the DMA engine fetches slot ``s``'s ``i``-th page
    directly from the page pool with no gather materialised in HBM.
    Unmapped table entries must be clipped to a valid page id by the caller;
    the wrapper re-points dead columns at the slot's last live page so the
    pipeline fetches nothing new for them (an unchanged block index is not
    re-copied), and the kernel skips their decode.  ``lengths`` (S,) holds
    per-slot token counts (post-append); grid = (S, maxp + 1) with the page
    axis innermost, so each slot's online-softmax accumulator lives in VMEM
    across its pages; column ``maxp`` is the ring step.

Layouts the Mosaic compiler accepts (every block's last two dims are the
array's own, or multiples of the (8, 128) tile):

* sign·mantissa bytes as (rows, W) per page, bit planes as (k, rows, W/32)
  — the flat LEXI-FW stream (32 consecutive elements per u32 word) viewed
  row by row, so the page pool stores them natively as (P, blk, W) and
  (P, k, blk, W/32) (``page_plane_shape``; payloads with W % 32 != 0 keep
  one flat plane row and decode through a reshape that only the
  interpreter runs);
* the exponent dictionary and the escape side channel as SMEM blocks of
  shape (1, 1, n): the decode reads them as scalars.

Decode: words are expanded to one code per lane in 32-row chunks, mapped
through the dictionary with a 2^k-way compare-select against SMEM scalars,
combined with the sign·mantissa bytes into bf16 bit patterns (int32), then
the escape side channel is applied (positions are stored in stream order,
so the walk stops at the first empty slot; each escape rewrites the
exponent field of one element) — bit-exact with ``fixed.decompress``,
overflow included (escapes beyond capacity keep the dictionary's ESCAPE
slot, exponent 0).

Attention per block: for every KV head, the MXU computes all query heads
against that head's K (and P·V), and a static head mask keeps the rows
that map to it — no per-head relayout, any GQA/MQA table.  MLA payloads
(``mla_lora`` set) are one shared latent: k = payload, v = payload[:, :lora].
Logit soft-capping (gemma2) follows ``layers.attention_partial``.

Outputs are unnormalised partials (out f32, m, l) — merge across shards
with ``layers.merge_partials`` exactly like the pure-JAX path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
WINDOW_NONE = 1 << 30      # matches models.attention.GLOBAL_WINDOW
LANES = 32                 # codes per u32 bit-plane word
CHUNK_ROWS = 32            # rows decoded per step (the u8 sublane tile)
VMEM_LIMIT = 100 * 1024 * 1024


def page_plane_shape(rows: int, w: int, npad: int) -> tuple:
    """(rows, words-per-row) view of one block's (k, npad/32) bit planes.

    Row-aligned whenever W is a multiple of 32 (every real KV width); else
    one flat row of npad/32 words."""
    if w % LANES == 0:
        return rows, w // LANES
    return 1, npad // LANES


# ---------------------------------------------------------------------------
# shared kernel body pieces
# ---------------------------------------------------------------------------

def _expand_codes(words, k: int):
    """(k, r, nw) u32 bit planes -> (r, nw*32) int32 codes."""
    r, nw = words.shape[1], words.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.uint32, (r, nw, LANES), 2)
    codes = jnp.zeros((r, nw, LANES), jnp.uint32)
    for b in range(k):                                  # unrolled
        bits = (words[b][:, :, None] >> lane) & jnp.uint32(1)
        codes = codes | (bits << jnp.uint32(b))
    return codes.reshape(r, nw * LANES).astype(jnp.int32)


def _bf16_bits(codes, sm, dict_ref, k: int):
    """codes/sm (r, w) int32 -> bf16 bit patterns (int32) via the SMEM
    dictionary (2^k compare-selects against scalars)."""
    exp = jnp.zeros(codes.shape, jnp.int32)
    for j in range(1 << k):                             # unrolled
        exp = jnp.where(codes == j, dict_ref[0, 0, j].astype(jnp.int32), exp)
    return ((sm & 0x80) << 8) | (exp << 7) | (sm & 0x7F)


def _decode_block(sm_ref, planes_ref, dict_ref, escp_ref, escr_ref, bits_scr,
                  kv_scr, *, k: int, npad: int):
    """Decode the current compressed block into ``kv_scr`` (rows, W) bf16.

    sm_ref (1, rows, W) u8; planes_ref (1, k, pr, pw) u32; dict/esc refs
    (1, 1, n) in SMEM; bits_scr (rows, W) int32 scratch."""
    rows, w = kv_scr.shape
    pr = planes_ref.shape[2]
    if pr == rows:                                      # row-aligned planes
        chunk = CHUNK_ROWS if rows % CHUNK_ROWS == 0 else rows

        def body(g, carry):
            r0 = pl.multiple_of(g * chunk, chunk)
            codes = _expand_codes(planes_ref[0, :, pl.ds(r0, chunk), :], k)
            sm = sm_ref[0, pl.ds(r0, chunk), :].astype(jnp.int32)
            bits_scr[pl.ds(r0, chunk), :] = _bf16_bits(codes, sm, dict_ref,
                                                       k)
            return carry

        jax.lax.fori_loop(0, rows // chunk, body, 0)
    else:                                               # flat plane row
        codes = _expand_codes(planes_ref[0], k)[0, :rows * w]
        sm = sm_ref[0].astype(jnp.int32)
        bits_scr[...] = _bf16_bits(codes.reshape(rows, w), sm, dict_ref, k)

    # escape side channel: position-ordered, empty slots hold npad
    c_cap = escp_ref.shape[-1]

    def esc_live(c):
        pos = escp_ref[0, 0, jnp.minimum(c, c_cap - 1)]
        return (c < c_cap) & (pos < npad)

    def esc_patch(c):
        pos = escp_ref[0, 0, c]
        raw = escr_ref[0, 0, c].astype(jnp.int32)
        r = pos // w
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        row = bits_scr[pl.ds(r, 1), :]
        fixed = (row & ~jnp.int32(0xFF << 7)) | (raw << 7)
        bits_scr[pl.ds(r, 1), :] = jnp.where(lane == pos % w, fixed, row)
        return c + 1

    jax.lax.while_loop(esc_live, esc_patch, 0)
    kv_scr[...] = jax.lax.bitcast_convert_type(
        bits_scr[...].astype(jnp.uint16), jnp.bfloat16)


def _live_mask(L, i, is_ring, blk: int, tp: int, ti, window):
    """(1, blk) live mask of block ``i`` (or the ring) for one sequence of
    ``L`` tokens — mirrors ``models.cache.stream_mask``."""
    loc_len = jnp.maximum((L - 1 - ti) // tp + 1, 0)
    nfull = loc_len // blk
    base = jnp.where(is_ring, nfull * blk, i * blk)
    # live slots end at loc_len in the ring; a full block is all-or-nothing
    hi = jnp.where(is_ring, loc_len, jnp.where(i < nfull, base + blk, 0))
    sl = base + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    pos = sl * tp + ti
    return (sl < hi) & (pos < L) & (pos > L - 1 - window)


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _attend(kv, r0: int, blk: int, q, ok, *, hkv: int, hd: int,
            kv_idx: tuple, scale: float, softcap, mla_lora):
    """One block's attention partial, mirroring ``layers.attention_partial``.

    kv: ref holding the payload, rows [r0, r0 + blk) attended; q (H, hd);
    ok (1, blk).  Returns (po (H, hd_v) f32, m (H, 1), l (H, 1))."""
    h = q.shape[0]
    rows = pl.ds(r0, blk)
    if mla_lora is not None:
        lat = kv[rows, :]
        s = _dot_nt(q, lat) * scale
    else:
        head = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0)
        masks = []
        s = jnp.zeros((h, blk), jnp.float32)
        for kh in range(hkv):
            qh = [x for x, y in enumerate(kv_idx) if y == kh]
            if not qh:
                continue
            sel = functools.reduce(jnp.logical_or, [head == x for x in qh])
            masks.append((kh, sel))
            s_kh = _dot_nt(q, kv[rows, pl.ds(2 * kh * hd, hd)]) * scale
            s = jnp.where(sel, s_kh, s)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(ok, s, NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    if mla_lora is not None:
        po = jnp.dot(p, lat[:, :mla_lora].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    else:
        po = jnp.zeros((h, hd), jnp.float32)
        for kh, sel in masks:
            v = kv[rows, pl.ds((2 * kh + 1) * hd, hd)].astype(jnp.float32)
            po = jnp.where(sel, jnp.dot(p, v,
                                        preferred_element_type=jnp.float32),
                           po)
    return po, m, l


def _accumulate(out_ref, m_ref, l_ref, po, pm, pl_):
    """Online-softmax merge of one partial into the output refs — the same
    arithmetic as ``models.cache.merge_partial`` so backends agree."""
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, pm)
    a_old = jnp.exp(m_old - m_new)
    a_new = jnp.exp(pm - m_new)
    out_ref[...] = out_ref[...] * a_old + po * a_new
    l_ref[...] = l_ref[...] * a_old + pl_ * a_new
    m_ref[...] = m_new


def _init(out_ref, m_ref, l_ref):
    out_ref[...] = jnp.zeros_like(out_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _codec_operands(signman, planes, dicts, esc_pos, esc_raw, rows: int,
                    w: int, k: int):
    """Kernel-native views of a block store's compressed fields (no-ops for
    the page pool, which is stored in these shapes)."""
    nb = signman.shape[0]
    npad = planes.shape[-1] * LANES if planes.ndim == 3 else \
        planes.shape[-2] * planes.shape[-1] * LANES
    pr, pw = page_plane_shape(rows, w, npad)
    c = esc_raw.shape[-1]
    return (signman.reshape(nb, rows, w), planes.reshape(nb, k, pr, pw),
            dicts.reshape(nb, 1, -1), esc_pos.reshape(nb, 1, c),
            esc_raw.reshape(nb, 1, c)), npad


def _codec_specs(rows: int, w: int, k: int, pr: int, pw: int, c: int,
                 nd: int, idx):
    """BlockSpecs for the five compressed fields; ``idx`` maps grid
    coordinates (+ prefetch refs) to the block index."""
    smem = pltpu.SMEM
    return [
        pl.BlockSpec((1, rows, w), lambda *a: (idx(*a), 0, 0)),
        pl.BlockSpec((1, k, pr, pw), lambda *a: (idx(*a), 0, 0, 0)),
        pl.BlockSpec((1, 1, nd), lambda *a: (idx(*a), 0, 0),
                     memory_space=smem),
        pl.BlockSpec((1, 1, c), lambda *a: (idx(*a), 0, 0),
                     memory_space=smem),
        pl.BlockSpec((1, 1, c), lambda *a: (idx(*a), 0, 0),
                     memory_space=smem),
    ]


# ---------------------------------------------------------------------------
# fixed-batch store kernel
# ---------------------------------------------------------------------------

def _fixed_kernel(len_ref, meta_ref, q_ref, *rest, k: int, npad: int,
                  hkv: int, hd: int, kv_idx: tuple, scale: float, softcap,
                  mla_lora, tp: int, blk: int, nblk: int, codec_on: bool):
    if codec_on:
        (sm_ref, planes_ref, dict_ref, escp_ref, escr_ref, ring_ref,
         out_ref, m_ref, l_ref, bits_scr, kv_scr) = rest
    else:
        raw_ref, ring_ref, out_ref, m_ref, l_ref = rest
    b = q_ref.shape[0]
    i = pl.program_id(0)
    is_ring = i == nblk
    ti, window = meta_ref[0], meta_ref[1]
    L = len_ref[0]
    ok = _live_mask(L, i, is_ring, blk, tp, ti, window)
    live = jnp.logical_not(is_ring) & (i < (jnp.maximum(
        (L - 1 - ti) // tp + 1, 0) // blk))
    att = functools.partial(_attend, hkv=hkv, hd=hd, kv_idx=kv_idx,
                            scale=scale, softcap=softcap, mla_lora=mla_lora)

    @pl.when(i == 0)
    def _():
        _init(out_ref, m_ref, l_ref)

    def step(kv, base):
        for bi in range(b):
            po, pm, pl_ = att(kv, base + bi * blk, blk, q_ref[bi], ok)
            _accumulate(out_ref.at[bi], m_ref.at[bi], l_ref.at[bi],
                        po, pm, pl_)

    @pl.when(live)
    def _():
        if codec_on:
            _decode_block(sm_ref, planes_ref, dict_ref, escp_ref, escr_ref,
                          bits_scr, kv_scr, k=k, npad=npad)
            step(kv_scr, 0)
        else:
            for bi in range(b):
                po, pm, pl_ = att(raw_ref.at[0, bi], 0, blk, q_ref[bi], ok)
                _accumulate(out_ref.at[bi], m_ref.at[bi], l_ref.at[bi],
                            po, pm, pl_)

    @pl.when(is_ring)
    def _():
        for bi in range(b):
            po, pm, pl_ = att(ring_ref.at[bi], 0, blk, q_ref[bi], ok)
            _accumulate(out_ref.at[bi], m_ref.at[bi], l_ref.at[bi],
                        po, pm, pl_)


def decode_attend(q, signman, planes, dicts, esc_pos, esc_raw, raw_blocks,
                  ring, length, ti, window, *, k: int, hkv: int, hd: int,
                  kv_idx: tuple, scale: float, softcap=None, mla_lora=None,
                  tp: int = 1, interpret: bool = False):
    """Fused decompress+attend over a fixed-batch block store + its ring.

    q (B, H, hd); codec on: signman (nblk, B*blk*W) u8, planes
    (nblk, k, n/32) u32, dicts (nblk, 2^k) u8, esc_pos (nblk, C) i32,
    esc_raw (nblk, C) u8; codec off: raw_blocks (nblk, B, blk, W) bf16.
    ring (B, blk, W) bf16; length/ti/window are traced scalars.  Returns
    (out (B,H,hd_v) f32 unnormalized, m (B,H), l (B,H)) — merge across
    shards with ``layers.merge_partials`` as usual.
    """
    codec_on = signman is not None
    b, h, _ = q.shape
    blk, w = ring.shape[-2], ring.shape[-1]
    nblk = signman.shape[0] if codec_on else raw_blocks.shape[0]
    hd_v = mla_lora if mla_lora is not None else hd
    lens = jnp.asarray(length, jnp.int32).reshape(1)
    meta = jnp.stack([jnp.asarray(ti, jnp.int32),
                      jnp.asarray(window, jnp.int32)])
    last = lambda i, *s: jnp.minimum(i, nblk - 1)
    q_spec = pl.BlockSpec((b, h, q.shape[-1]), lambda i, *s: (0, 0, 0))
    ring_spec = pl.BlockSpec((b, blk, w), lambda i, *s: (0, 0, 0))
    scratch, npad = [], 0
    if codec_on:
        fields, npad = _codec_operands(signman, planes, dicts, esc_pos,
                                       esc_raw, b * blk, w, k)
        _, _, pr, pw = fields[1].shape
        in_specs = [q_spec] + _codec_specs(
            b * blk, w, k, pr, pw, esc_raw.shape[-1], dicts.shape[-1],
            last) + [ring_spec]
        operands = (q, *fields, ring)
        scratch = [pltpu.VMEM((b * blk, w), jnp.int32),
                   pltpu.VMEM((b * blk, w), jnp.bfloat16)]
    else:
        in_specs = [q_spec,
                    pl.BlockSpec((1, b, blk, w),
                                 lambda i, *s: (last(i), 0, 0, 0)),
                    ring_spec]
        operands = (q, raw_blocks, ring)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblk + 1,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, h, hd_v), lambda i, *s: (0, 0, 0)),
            pl.BlockSpec((b, h, 1), lambda i, *s: (0, 0, 0)),
            pl.BlockSpec((b, h, 1), lambda i, *s: (0, 0, 0)),
        ],
        scratch_shapes=scratch)
    kern = functools.partial(
        _fixed_kernel, k=k, npad=npad, hkv=hkv, hd=hd, kv_idx=tuple(kv_idx),
        scale=scale, softcap=softcap, mla_lora=mla_lora, tp=tp, blk=blk,
        nblk=nblk, codec_on=codec_on)
    out, m, l = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, hd_v), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="decode_attend",
    )(lens, meta, *operands)
    return out, m[..., 0], l[..., 0]


# ---------------------------------------------------------------------------
# paged store kernel (continuous batching)
# ---------------------------------------------------------------------------

def _paged_kernel(pid_ref, len_ref, meta_ref, q_ref, *rest, k: int,
                  npad: int, hkv: int, hd: int, kv_idx: tuple, scale: float,
                  softcap, mla_lora, tp: int, blk: int, maxp: int,
                  codec_on: bool):
    if codec_on:
        (sm_ref, planes_ref, dict_ref, escp_ref, escr_ref, ring_ref,
         out_ref, m_ref, l_ref, bits_scr, kv_scr) = rest
    else:
        raw_ref, ring_ref, out_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    i = pl.program_id(1)
    is_ring = i == maxp
    ti, window = meta_ref[0], meta_ref[1]
    L = len_ref[s]
    ok = _live_mask(L, i, is_ring, blk, tp, ti, window)
    live = jnp.logical_not(is_ring) & (i < (jnp.maximum(
        (L - 1 - ti) // tp + 1, 0) // blk))
    att = functools.partial(_attend, hkv=hkv, hd=hd, kv_idx=kv_idx,
                            scale=scale, softcap=softcap, mla_lora=mla_lora)

    @pl.when(i == 0)
    def _():
        _init(out_ref, m_ref, l_ref)

    def step(kv):
        po, pm, pl_ = att(kv, 0, blk, q_ref[0], ok)
        _accumulate(out_ref.at[0], m_ref.at[0], l_ref.at[0], po, pm, pl_)

    @pl.when(live)
    def _():
        if codec_on:
            _decode_block(sm_ref, planes_ref, dict_ref, escp_ref, escr_ref,
                          bits_scr, kv_scr, k=k, npad=npad)
            step(kv_scr)
        else:
            step(raw_ref.at[0])

    @pl.when(is_ring)
    def _():
        step(ring_ref.at[0])


def decode_attend_paged(q, signman, planes, dicts, esc_pos, esc_raw,
                        raw_pages, ring, page_ids, lengths, ti, window, *,
                        k: int, hkv: int, hd: int, kv_idx: tuple,
                        scale: float, softcap=None, mla_lora=None,
                        tp: int = 1, interpret: bool = False):
    """Fused decompress+attend through a page table (see module docstring).

    q (S, H, hd); page pool fields have leading n_pages (signman
    (P, blk, W) or (P, blk*W) u8, planes (P, k, *page_plane_shape) or
    (P, k, Npad/32) u32, dicts (P, 2^k) u8, esc_pos (P, C) i32, esc_raw
    (P, C) u8; codec off: raw_pages (P, blk, W) bf16); ring (S, blk, W);
    page_ids (S, maxp) int32 with unmapped entries ALREADY clipped to a
    valid id; lengths (S,) post-append token counts; ti/window traced
    scalars.  Returns per-slot partials (out (S,H,hd_v) f32, m (S,H),
    l (S,H)).
    """
    codec_on = signman is not None
    n_s, h, _ = q.shape
    blk, w = ring.shape[-2], ring.shape[-1]
    maxp = page_ids.shape[1]
    hd_v = mla_lora if mla_lora is not None else hd
    lens = jnp.asarray(lengths, jnp.int32).reshape(n_s)
    ti = jnp.asarray(ti, jnp.int32)
    meta = jnp.stack([ti, jnp.asarray(window, jnp.int32)])
    # dead columns (and the ring column maxp) re-point at the slot's last
    # live page: an unchanged block index is not fetched again
    nfull = jnp.maximum((lens - 1 - ti) // tp + 1, 0) // blk
    col = jnp.minimum(jnp.arange(maxp + 1)[None],
                      jnp.maximum(nfull - 1, 0)[:, None])
    pids = jnp.take_along_axis(page_ids, jnp.minimum(col, maxp - 1), axis=1)
    page = lambda s, i, pid, *r: pid[s, i]
    q_spec = pl.BlockSpec((1, h, q.shape[-1]), lambda s, i, *r: (s, 0, 0))
    ring_spec = pl.BlockSpec((1, blk, w), lambda s, i, *r: (s, 0, 0))
    scratch, npad = [], 0
    if codec_on:
        fields, npad = _codec_operands(signman, planes, dicts, esc_pos,
                                       esc_raw, blk, w, k)
        _, _, pr, pw = fields[1].shape
        in_specs = [q_spec] + _codec_specs(
            blk, w, k, pr, pw, esc_raw.shape[-1], dicts.shape[-1],
            page) + [ring_spec]
        operands = (q, *fields, ring)
        scratch = [pltpu.VMEM((blk, w), jnp.int32),
                   pltpu.VMEM((blk, w), jnp.bfloat16)]
    else:
        in_specs = [q_spec,
                    pl.BlockSpec((1, blk, w),
                                 lambda s, i, pid, *r: (pid[s, i], 0, 0)),
                    ring_spec]
        operands = (q, raw_pages, ring)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_s, maxp + 1),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, h, hd_v), lambda s, i, *r: (s, 0, 0)),
            pl.BlockSpec((1, h, 1), lambda s, i, *r: (s, 0, 0)),
            pl.BlockSpec((1, h, 1), lambda s, i, *r: (s, 0, 0)),
        ],
        scratch_shapes=scratch)
    kern = functools.partial(
        _paged_kernel, k=k, npad=npad, hkv=hkv, hd=hd, kv_idx=tuple(kv_idx),
        scale=scale, softcap=softcap, mla_lora=mla_lora, tp=tp, blk=blk,
        maxp=maxp, codec_on=codec_on)
    out, m, l = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_s, h, hd_v), jnp.float32),
            jax.ShapeDtypeStruct((n_s, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_s, h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="decode_attend_paged",
    )(pids, lens, meta, *operands)
    return out, m[..., 0], l[..., 0]
