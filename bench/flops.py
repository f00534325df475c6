"""Operations and bytes of the served model and of its two kernels,
computed from shapes: the yardstick for rooflines and ``step_mfu``.

Shapes come from the configuration (the model family's ``Dims``, under
``bench/models``) and from the geometry of the program's stores, read once
per run (``geometry``): the per-page bytes of the KV pool and the bytes of
each packed weight.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer_matmul_params(m) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return m.d * q + 2 * m.d * kv + q * m.d + 3 * m.d * m.ffn


def token_flops(m, ctx, logits: bool = True) -> float:
    """Model FLOPs of one token that attends to ``ctx`` positions (itself
    included): 2 per weight of every matrix product (the LM head only where
    logits are produced) plus 4·L·H·hd·ctx for QK^T and PV."""
    w = m.layers * layer_matmul_params(m) + (m.d * m.vocab if logits else 0)
    return 2.0 * w + 4.0 * m.layers * m.heads * m.head_dim * ctx


def window_model_flops(m, attend_steps: Iterable[np.ndarray],
                       admits: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of every token the window processed: one per live slot
    in each decode or replay step (at its context length), and each
    prefilled trunk (causal, logits at its last position only).  Bucket
    padding and pages mapped from the cache do not count."""
    total = 0.0
    for lens in attend_steps:
        total += len(lens) * token_flops(m, 0) + (
            4.0 * m.layers * m.heads * m.head_dim * float(np.sum(lens)))
    for trunk, batch in admits:
        per_seq = (trunk * token_flops(m, 0, logits=False)
                   + 2.0 * m.d * m.vocab
                   + 4.0 * m.layers * m.heads * m.head_dim
                   * trunk * (trunk + 1) / 2)
        total += batch * per_seq
    return total


# ---------------------------------------------------------------------------
# decode_attend_paged: one call per layer per decode/replay step
# ---------------------------------------------------------------------------

def attend_call(m, lens: np.ndarray, page_bytes: int, block: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged attention over the slots whose
    output is used, each at its live length.  Bytes are the live tokens' KV
    as stored: full pages at the pool's per-page bytes, the partial block
    at raw bf16 K and V, plus q in bf16 and the f32 output."""
    lens = np.asarray(lens, np.int64)
    hd, h, w = m.head_dim, m.heads, 2 * m.kv_heads * m.head_dim
    flops = 4.0 * h * hd * float(lens.sum())
    kv = (lens // block) * page_bytes + (lens % block) * w * 2
    io = len(lens) * h * hd * (2 + 4)
    return flops, float(kv.sum() + io)


def least_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def attend_least_s(m, attend_steps, page_bytes: int, block: int,
                   peaks: dict) -> float:
    return m.layers * sum(
        least_s(*attend_call(m, lens, page_bytes, block), peaks)
        for lens in attend_steps if len(lens))


# ---------------------------------------------------------------------------
# decompress_matmul: one call per packed weight per step, and per prefill
# ---------------------------------------------------------------------------

def matmul_call(rows: int, k: int, n: int, packed_bytes: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of x (rows, k) bf16 @ packed W (k, n) -> f32: the
    packed planes as stored, read once, plus x and the output."""
    return 2.0 * rows * k * n, float(packed_bytes + rows * k * 2
                                      + rows * n * 4)


def matmul_least_s(packed: List[dict], attend_steps, admits,
                   peaks: dict) -> Optional[float]:
    """Least time of every fused weight matmul the window ran.  ``packed``
    lists the program's packed weights as ``{"k", "n", "bytes", "count",
    "head"}`` (``count`` calls per step: the layers; ``head``: the LM
    head, which prefill runs on one row per sequence)."""
    if not packed:
        return None
    total = 0.0
    for lens in attend_steps:
        rows = len(lens)
        if rows:
            total += sum(p["count"] * least_s(*matmul_call(
                rows, p["k"], p["n"], p["bytes"]), peaks) for p in packed)
    for trunk, batch in admits:
        for p in packed:
            rows = batch * (1 if p["head"] else trunk)
            total += p["count"] * least_s(*matmul_call(
                rows, p["k"], p["n"], p["bytes"]), peaks)
    return total
