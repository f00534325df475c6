"""Compressed-at-rest parameter store (paper path (i): offline weights).

Weights are compressed once (offline / at load), live in HBM as LEXI-FW
packed buffers, and are decompressed just-in-time near compute — either by
the pure-JAX path here (dry-run friendly) or by the fused
``decompress_matmul`` Pallas kernel on real hardware.

Small leaves (norm scales, biases, scalars) stay raw: packing them would cost
more in dictionary/escape overhead than it saves, exactly like the paper only
compresses the bulk streams.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import entropy, fixed, packing
from .collectives import CodecConfig

MIN_COMPRESS_SIZE = 1 << 12   # leaves below 4096 elements stay raw
WEIGHT_K = 6                  # exponent-code width for at-rest serving weights
LANES = 32                    # bit-plane word width (columns per u32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MaybeCompressed:
    """A leaf that is either raw or a :class:`fixed.Compressed`."""

    value: Any           # jax.Array | fixed.Compressed
    compressed: bool

    def tree_flatten(self):
        return (self.value,), (self.compressed,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])


def _should_compress(x: jax.Array) -> bool:
    return (x.ndim >= 1 and x.size >= MIN_COMPRESS_SIZE
            and x.dtype in (jnp.bfloat16, jnp.float32))


def compress_params(params: Any, cfg: CodecConfig) -> Any:
    """Pytree of arrays -> pytree of MaybeCompressed."""

    def one(x):
        if cfg.weights and _should_compress(x):
            return MaybeCompressed(
                fixed.compress(x.astype(jnp.bfloat16), k=cfg.k,
                               esc_capacity=cfg.esc_capacity(x.size)),
                True)
        return MaybeCompressed(x, False)

    return jax.tree_util.tree_map(one, params)


def decompress_params(cparams: Any) -> Any:
    """Inverse of :func:`compress_params` (exact for the compressed leaves)."""

    def one(leaf: MaybeCompressed):
        return fixed.decompress(leaf.value) if leaf.compressed else leaf.value

    return jax.tree_util.tree_map(
        one, cparams, is_leaf=lambda l: isinstance(l, MaybeCompressed))


def stored_bytes(cparams: Any) -> int:
    """HBM bytes of the compressed store (the paper's Fig-1b metric)."""
    total = 0

    def one(leaf: MaybeCompressed):
        nonlocal total
        if leaf.compressed:
            total += leaf.value.wire_bytes()
        else:
            total += leaf.value.size * leaf.value.dtype.itemsize
        return leaf

    jax.tree_util.tree_map(one, cparams,
                           is_leaf=lambda l: isinstance(l, MaybeCompressed))
    return total


def param_bytes(params: Any) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(params))


def fsdp_gather_params(cparams: Any, axis_name: str,
                       cfg: CodecConfig) -> Any:
    """FSDP-style per-layer weight all-gather with packed wire format.

    Parameters live sharded *and* compressed; gathering for use moves packed
    bytes over ICI (the paper's "transmit weights in compact lossless form"),
    decompressing only at the consumer.  Call inside shard_map with leaves
    pre-sharded along their first axis.
    """

    def one(leaf: MaybeCompressed):
        if leaf.compressed:
            gathered = jax.lax.all_gather(leaf.value, axis_name, axis=0,
                                          tiled=False)
            parts = jax.vmap(fixed.decompress)(gathered)
            return parts.reshape((-1,) + parts.shape[2:])
        return jax.lax.all_gather(leaf.value, axis_name, axis=0, tiled=True)

    return jax.tree_util.tree_map(
        one, cparams, is_leaf=lambda l: isinstance(l, MaybeCompressed))


# ---------------------------------------------------------------------------
# serving-side packed store: whole-model weights in the LEXI-FW 2-D layout
# consumed by the fused ``kernels.decompress_matmul`` kernel
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedWeight:
    """A bulk 2-D (or stacked-2-D) weight leaf in LEXI-FW packed form.

    Fields follow ``kernels.ref.compress_weight_2d``, with any leading
    stack dims (scan-stacked layers, MoE experts) prepended to every child
    so ``lax.scan`` / indexing slice all three buffers coherently:

      signman   (..., K, N)       u8   sign<<7 | mantissa
      planes    (..., k, N/32, K) u32  bit-planes of k-bit exponent codes
      dict_syms (..., 2^k)        u8   per-slice exponent dictionary

    ``aux`` carries ``k`` and the *resolved* compute backend baked in at
    pack time ("pallas" | "interpret" | "jax"), so jit caches key on the
    dispatch decision and model code needs no config threading.  The format
    is escape-free by construction: the packer verifies zero escapes per
    slice and leaves escaping tensors raw.
    """

    signman: Any
    planes: Any
    dict_syms: Any
    k: int = WEIGHT_K
    backend: str = "jax"

    @property
    def shape(self):          # logical (unpacked) weight shape
        return self.signman.shape

    @property
    def ndim(self):
        return self.signman.ndim

    def tree_flatten(self):
        return ((self.signman, self.planes, self.dict_syms),
                (self.k, self.backend))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0], aux[1])


def _is_packed(x) -> bool:
    return isinstance(x, PackedWeight)


def unpack_weight(pw: PackedWeight) -> jax.Array:
    """Exact in-graph decode of a packed leaf back to bf16 (the pure-JAX
    reference plane — mirrors ``kernels.ref.decompress_matmul_ref``'s
    decode, vmapped over any leading stack dims)."""

    def one(sm, pls, d):
        codes = packing.bitplane_unpack(jnp.moveaxis(pls, -1, -3), pw.k)
        exp = d[codes.astype(jnp.int32)]
        return entropy.jnp_from_u16(entropy.jnp_combine(sm, exp))

    fn = one
    for _ in range(pw.signman.ndim - 2):
        fn = jax.vmap(fn)
    return fn(pw.signman, pw.planes, pw.dict_syms)


def _leaf_eligible(path: str, x, spec, tp: int, n_stack: int = 0) -> bool:
    """Bulk 2-D matmul operands only.  Raw stays raw when:

    - it is an embedding table (consumed by gather, not matmul),
    - it is small (dictionary overhead beats the savings), not bf16, or has
      fewer than 2 dims beyond its ``n_stack`` scan-stacking dims (a
      stacked per-layer bias or norm scale is a vector, not a matrix),
    - its tp-local column count breaks the 32-lane bit-plane alignment, or
    - (checked later, at pack time) any 2-D slice needs escape symbols.
    """
    if "embed" in path:
        return False
    if (not hasattr(x, "dtype") or x.dtype != jnp.bfloat16
            or x.ndim - n_stack < 2):
        return False
    if x.shape[-2] * x.shape[-1] < MIN_COMPRESS_SIZE:
        return False
    dims = tuple(spec) if spec is not None else ()
    dims = dims + (None,) * (x.ndim - len(dims))
    n_local = x.shape[-1] // tp if dims[-1] is not None else x.shape[-1]
    return n_local % LANES == 0


def _row_chunks(w):
    """(K, N) -> (K/R, R, N) with R the largest power of two <= 256 dividing
    K: the packer walks a weight R rows at a time, so its int32
    temporaries stay a few R x N (an LM head is 10^8+ elements)."""
    r = next(r for r in (256, 128, 64, 32, 16, 8, 4, 2, 1)
             if w.shape[0] % r == 0)
    return w.reshape(-1, r, w.shape[1])


def _exponents(rows):
    return ((entropy.jnp_to_u16(rows) >> 7) & 0xFF).astype(jnp.int32)


def _slice_hist(w):
    """256-bin exponent histogram of one (K, N) slice."""
    one = lambda rows: jnp.zeros((256,), jnp.int32).at[
        _exponents(rows).reshape(-1)].add(1)
    return jax.lax.map(one, _row_chunks(w)).sum(0)


def _escapes_per_k(x, max_k: int) -> jax.Array:
    """(max_k - 3,) worst-slice escape counts of leaf ``x`` at code widths
    k = 4..max_k: the elements whose exponent falls outside the 2^k - 1
    most frequent of its 2-D slice (the dictionary the packer builds)."""
    hist = jax.lax.map(_slice_hist, x.reshape((-1,) + x.shape[-2:]))
    top = jnp.cumsum(-jnp.sort(-hist, axis=-1), axis=-1)
    total = hist.sum(-1)
    return jnp.stack([(total - top[:, (1 << k) - 2]).max()
                      for k in range(4, max_k + 1)])


def _pack_2d(w, k: int):
    """(K, N) bf16 -> (signman (K,N) u8, planes (k,N/32,K) u32, dict (2^k,)
    u8): the bytes of ``kernels.ref.compress_weight_2d``, built a row
    chunk at a time."""
    dict_syms, enc_lut = fixed.build_dictionary(_slice_hist(w), k)

    def one(rows):
        codes = enc_lut[_exponents(rows)]
        planes = packing.bitplane_pack(codes, k)          # (R, k, N/32)
        return (entropy.jnp_signman(entropy.jnp_to_u16(rows)),
                jnp.transpose(planes, (1, 2, 0)))         # (k, N/32, R)

    sm, planes = jax.lax.map(one, _row_chunks(w))
    kk, n = w.shape
    return (sm.reshape(kk, n),
            jnp.moveaxis(planes, 0, 2).reshape(k, n // LANES, kk), dict_syms)


def _pack_leaf(x, max_k: int, shardings=None):
    """On-device pack of one leaf at the smallest escape-free code width
    k ∈ {4..max_k} (weight exponent histograms are narrow, so most leaves
    fit k=4 → 12 of 16 bits per element).  All leading-dim slices share k
    (it is leaf-level aux) and are packed one at a time (``lax.map``) to
    bound temporaries; ``shardings`` (a PackedWeight of shardings) places
    the packed fields where the raw leaf's spec says.  Returns
    ``(fields, k)`` or None if even max_k would need escapes — that leaf
    stays raw."""
    esc = jax.device_get(jax.jit(_escapes_per_k, static_argnums=1)(x, max_k))
    ok = [k for k, n in zip(range(4, max_k + 1), esc) if int(n) == 0]
    if not ok:
        return None
    k = ok[0]
    out_sh = (None if shardings is None else
              (shardings.signman, shardings.planes, shardings.dict_syms))
    return jax.jit(_pack_stack, static_argnums=1,
                   out_shardings=out_sh)(x, k), k


def _pack_stack(x, k: int):
    """``_pack_2d`` over every leading-dim slice of ``x``, one at a time."""
    lead = x.shape[:-2]
    fields = jax.lax.map(lambda w: _pack_2d(w, k),
                         x.reshape((-1,) + x.shape[-2:]))
    return tuple(f.reshape(lead + f.shape[1:]) for f in fields)


def _packed_spec(spec, ndim: int, k: int, backend: str):
    """Derive the PartitionSpec node for a packed leaf from the raw leaf's
    spec: signman keeps it, planes are (k, N/32, K) — an unsharded ``k``
    axis, then the word axis sharded exactly like N (eligibility guarantees
    the local column count is lane-aligned), then K — and the per-slice
    dictionary keeps only the leading stack dims.  The node's aux (k, backend) must equal
    the param node's so shard_map's tree matching lines the specs up."""
    from jax.sharding import PartitionSpec as P
    dims = tuple(spec) if spec is not None else ()
    dims = dims + (None,) * (ndim - len(dims))
    lead, kd, nd = dims[:-2], dims[-2], dims[-1]
    return PackedWeight(P(*lead, kd, nd),
                        P(*lead, None, nd, kd),
                        P(*lead, None), k, backend)


def pack_serving_params(params: Any, pspecs: Any, *, k: int = WEIGHT_K,
                        backend: str = "jax", tp: int = 1, mesh=None,
                        stacked: tuple = ()):
    """Whole-model serving param store: bulk 2-D leaves -> PackedWeight
    (escape-free LEXI-FW layout at the smallest code width ≤ ``k``),
    everything else raw.  Packing runs on the device that holds each leaf;
    with ``mesh`` the packed fields are laid out by their specs on it.
    Leaves under a top-level key in ``stacked`` carry one leading
    scan-stacking (layer) dim.
    Returns ``(packed_params, packed_pspecs)`` with spec nodes swapped to
    match.  Idempotent: already-packed leaves pass through (disagg
    replicas share one params tree)."""
    from jax.sharding import PartitionSpec as P
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_packed)
    # PartitionSpec is tuple-like, so flatten the spec tree with its own
    # is_leaf (None / P / PackedWeight) instead of flatten_up_to
    sflat, sdef = jax.tree_util.tree_flatten(
        pspecs, is_leaf=lambda s: s is None or isinstance(s, (P, PackedWeight)))
    assert len(flat) == len(sflat), (len(flat), len(sflat))
    out_p, out_s = [], []
    for (path, x), spec in zip(flat, sflat):
        pstr = jax.tree_util.keystr(path)
        if _is_packed(x):
            out_p.append(x)
            out_s.append(spec if _is_packed(spec)
                         else _packed_spec(spec, x.ndim, x.k, x.backend))
            continue
        packed = None
        n_stack = int(any(pstr.startswith(f"['{key}']") for key in stacked))
        if _leaf_eligible(pstr, x, spec, tp, n_stack):
            shardings = None if mesh is None else jax.tree_util.tree_map(
                lambda sp: jax.sharding.NamedSharding(mesh, sp),
                _packed_spec(spec, x.ndim, k, backend),
                is_leaf=lambda sp: isinstance(sp, P))
            packed = _pack_leaf(x, k, shardings)
        if packed is None:
            out_p.append(x)
            out_s.append(spec)
        else:
            fields, leaf_k = packed
            out_p.append(PackedWeight(*fields, leaf_k, backend))
            out_s.append(_packed_spec(spec, x.ndim, leaf_k, backend))
    return (jax.tree_util.tree_unflatten(treedef, out_p),
            jax.tree_util.tree_unflatten(sdef, out_s))


def weight_plane_bytes(params: Any) -> tuple:
    """(stored, raw_bf16) HBM bytes of the serving weight store — the
    per-decode-step weight traffic, analytically, the way
    ``models/cache.py:page_bytes`` meters KV bytes.  ``stored`` counts
    packed buffers for PackedWeight leaves and full bf16 for raw ones;
    ``raw_bf16`` is the same store with every leaf unpacked."""
    stored = raw = 0
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=_is_packed):
        if _is_packed(leaf):
            stored += sum(int(b.size) * b.dtype.itemsize
                          for b in (leaf.signman, leaf.planes,
                                    leaf.dict_syms))
            raw += int(leaf.signman.size) * 2
        else:
            stored += int(leaf.size) * leaf.dtype.itemsize
            raw += int(leaf.size) * leaf.dtype.itemsize
    return stored, raw
