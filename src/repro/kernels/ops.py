"""Jit'd public wrappers around the Pallas kernels.

These are the entry points models/benchmarks use; each wrapper

* reshapes arbitrary tensors into the kernels' (G, B) block layout,
* runs the compiled (Mosaic) kernel unless the caller names
  ``interpret=True`` — the Pallas interpreter is a CPU test hook, never a
  silent fallback: asking for a compiled kernel with no TPU present raises,
* round-trips escapes through the jnp side channel so the overall semantics
  match ``repro.core.fixed`` exactly.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import entropy as E
from repro.core import fixed
from . import ref
from .decode_attend import (WINDOW_NONE, decode_attend,  # noqa: F401
                            decode_attend_paged)
from .decompress_matmul import decompress_matmul as _dm
from .exp_histogram import exp_histogram as _hist
from .lexi_pack import lexi_pack as _pack
from .lexi_unpack import lexi_unpack as _unpack


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def require_tpu(what: str = "pallas") -> None:
    """Compiled Pallas kernels target the TPU: refuse loudly elsewhere."""
    if not on_tpu():
        raise RuntimeError(
            f"{what}: compiled Pallas kernels need a TPU, but JAX's default "
            f"backend is {jax.default_backend()!r}; use the 'jax' backend, "
            "or 'interpret' to run the kernels under the Pallas interpreter")


def _mode(interpret: bool) -> bool:
    if not interpret:
        require_tpu()
    return interpret


# ---------------------------------------------------------------------------
# decode-attention backend dispatch
#
# ``CodecConfig.decode_backend`` selects how the serving decode path computes
# cache attention; ``models.cache.attend_cache``/``attend_paged`` both route
# through here so fixed-batch and paged decode cannot diverge:
#
#   auto      -- pallas on TPU, jax elsewhere (the only sane defaults)
#   pallas    -- the fused decompress+attend kernels, compiled (TPU only:
#                resolving it with no TPU present raises)
#   interpret -- the same kernels under the Pallas interpreter (CPU testing:
#                exercises the exact kernel logic, slowly)
#   jax       -- the pure-JAX block/page scan (reference semantics)
# ---------------------------------------------------------------------------

DECODE_BACKENDS = ("auto", "pallas", "interpret", "jax")


def resolve_decode_backend(codec=None) -> str:
    """Resolve a CodecConfig's decode_backend to a concrete backend name."""
    be = getattr(codec, "decode_backend", "auto") if codec is not None \
        else "auto"
    if be not in DECODE_BACKENDS:
        raise ValueError(f"decode_backend must be one of {DECODE_BACKENDS}, "
                         f"got {be!r}")
    if be == "auto":
        return "pallas" if on_tpu() else "jax"
    if be == "pallas":
        require_tpu("decode_backend='pallas'")
    return be


# ---------------------------------------------------------------------------
# serving weight-matmul backend dispatch
#
# ``CodecConfig.weight_backend`` selects how matmuls against PackedWeight
# leaves (the compressed-at-rest serving store, ``core.weights``) compute.
# ``models.layers.matmul_f32``/``pdot`` route every weight-consuming einsum
# through here, so attention/MLP/MoE/LM-head cannot diverge:
#
#   auto      -- pallas on TPU, jax elsewhere
#   pallas    -- fused decompress_matmul (packed tiles HBM->VMEM, decoded on
#                the VPU, fed to the MXU; bf16 W never lands in HBM); TPU
#                only, resolving it with no TPU present raises
#   interpret -- the same kernel under the Pallas interpreter (CPU testing)
#   jax       -- exact in-graph unpack + einsum (the CPU correctness gate:
#                bit-identical to serving from raw bf16 weights)
# ---------------------------------------------------------------------------

WEIGHT_BACKENDS = ("auto", "pallas", "interpret", "jax")


def resolve_weight_backend(codec=None) -> str:
    """Resolve a CodecConfig's weight_backend to a concrete backend name."""
    be = getattr(codec, "weight_backend", "auto") if codec is not None \
        else "auto"
    if be not in WEIGHT_BACKENDS:
        raise ValueError(f"weight_backend must be one of {WEIGHT_BACKENDS}, "
                         f"got {be!r}")
    if be == "auto":
        return "pallas" if on_tpu() else "jax"
    if be == "pallas":
        require_tpu("weight_backend='pallas'")
    return be


def matmul_packed(x: jax.Array, pw) -> jax.Array:
    """``x @ unpack(pw)`` in f32 for a ``core.weights.PackedWeight`` leaf,
    on the backend baked into the leaf at pack time.

    The fused path handles 2-D packed leaves (stacked leaves are sliced by
    scan/indexing before they get here, but vmapped closures can still see
    them — those fall back to the exact path, as does backend "jax")."""
    from repro.core import weights as W
    be = pw.backend
    if be == "jax" or pw.signman.ndim != 2:
        w = W.unpack_weight(pw)
        return jnp.einsum("...k,kn->...n", x, w,
                          preferred_element_type=jnp.float32)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.bfloat16)
    out = _dm(x2, pw.signman, pw.planes, pw.dict_syms, k=pw.k,
              interpret=_mode(be == "interpret"))
    return out.reshape(lead + (out.shape[-1],))


def _blockify(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """Flatten + zero-pad to (G, block)."""
    flat = x.reshape(-1)
    n = flat.size
    pad = (-n) % block
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block), n


def histogram(x: jax.Array, *, block: int = ref.BLOCK_ELEMS,
              interpret: bool = False) -> jax.Array:
    """256-bin exponent histogram of any bf16 tensor (Pallas).

    Zero-padding adds counts to bin 0 (exponent of +0.0); the wrapper
    subtracts them so the result matches ``ref.histogram_ref`` exactly.
    """
    xb, n = _blockify(x.astype(jnp.bfloat16), block)
    hist = _hist(xb, interpret=_mode(interpret))
    pad = xb.size - n
    return hist.at[0].add(-pad)


def pack(x: jax.Array, *, k: int = fixed.DEFAULT_K,
         esc_capacity: int | None = None,
         block: int = ref.BLOCK_ELEMS,
         interpret: bool = False) -> fixed.Compressed:
    """Kernel-backed equivalent of ``fixed.compress`` (same Compressed)."""
    shape = tuple(x.shape)
    x = x.astype(jnp.bfloat16)
    n = x.size
    c = esc_capacity if esc_capacity is not None else max(
        n // fixed.DEFAULT_ESC_FRAC, 8)
    hist = histogram(x, block=block, interpret=interpret)
    dict_syms, enc_lut = fixed.build_dictionary(hist, k)
    xb, _ = _blockify(x, block)
    sm_b, planes_b = _pack(xb, enc_lut, k=k, block=block,
                           interpret=_mode(interpret))
    g = xb.shape[0]
    signman = sm_b.reshape(-1)[:n]
    # (G, k, B/32) -> (k, G*B/32): grid-major plane order == flat group order.
    planes = jnp.moveaxis(planes_b, 1, 0).reshape(k, -1)
    # escape side channel (host-of-graph jnp; rare path)
    esc = fixed.esc_index(k)
    u16 = E.jnp_to_u16(x).reshape(-1)
    exp = ((u16 >> 7) & 0xFF).astype(jnp.int32)
    codes = enc_lut[exp]
    esc_mask = codes == esc
    slot = jnp.cumsum(esc_mask.astype(jnp.int32)) - 1
    n_escapes = jnp.sum(esc_mask.astype(jnp.int32))
    write_slot = jnp.where(esc_mask & (slot < c), slot, c)
    np_ = xb.size
    esc_pos = jnp.full((c + 1,), np_, jnp.int32).at[write_slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")[:c]
    esc_raw = jnp.zeros((c + 1,), jnp.uint8).at[write_slot].set(
        exp.astype(jnp.uint8), mode="drop")[:c]
    return fixed.Compressed(signman=signman, planes=planes,
                            dict_syms=dict_syms, esc_pos=esc_pos,
                            esc_raw=esc_raw, n_escapes=n_escapes,
                            shape=shape, k=k)


def unpack(ct: fixed.Compressed, *, block: int = ref.BLOCK_ELEMS,
           interpret: bool = False) -> jax.Array:
    """Kernel-backed equivalent of ``fixed.decompress``."""
    n = ct.n
    k = ct.k
    w = ct.planes.shape[-1]                      # total words
    bw = block // 32
    g = w // bw
    planes_b = jnp.moveaxis(ct.planes.reshape(k, g, bw), 0, 1)  # (G,k,bw)
    sm = jnp.pad(ct.signman, (0, g * block - n))
    sm_b = sm.reshape(g, block)
    xb = _unpack(sm_b, planes_b, ct.dict_syms, k=k,
                 interpret=_mode(interpret))
    out = xb.reshape(-1)[:n]
    # patch escapes: rebuild full bf16 values at the <=C escape positions
    # (gather signman clip-safe; sentinel positions drop at the scatter)
    pos = jnp.minimum(ct.esc_pos, n - 1)
    smv = ct.signman[pos].astype(jnp.uint16)
    fix_u16 = ((smv & 0x80) << 8) | (ct.esc_raw.astype(jnp.uint16) << 7) \
        | (smv & 0x7F)
    fix_val = jax.lax.bitcast_convert_type(fix_u16, jnp.bfloat16)
    out = out.at[ct.esc_pos].set(fix_val, mode="drop")
    return out.reshape(ct.shape)


def compress_weight(w: jax.Array, *, k: int = 6):
    """(K,N) bf16 -> packed fields for ``matmul_compressed``."""
    return ref.compress_weight_2d(w.astype(jnp.bfloat16), k=k)


def matmul_compressed(x: jax.Array, signman: jax.Array, planes: jax.Array,
                      dict_syms: jax.Array, *, k: int = 6,
                      bm: int = 256, bk: int = 2048, bn: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Fused just-in-time-decompress matmul (paper's near-compute decode)."""
    return _dm(x.astype(jnp.bfloat16), signman, planes, dict_syms, k=k,
               bm=bm, bk=bk, bn=bn, interpret=_mode(interpret))
