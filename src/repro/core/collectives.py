"""LEXI-compressed ICI collectives (the TPU analogue of NoC-port codecs).

The paper places codecs at the egress/ingress ports of chiplet routers so
that activations/caches cross the interconnect compressed.  On a TPU pod the
"ports" are the collectives, so each wrapper here:

    pack (VPU, near compute)  ->  collective on packed buffers  ->  unpack

All wrappers are meant to be called *inside* ``shard_map`` (they use named
axes).  With ``CodecConfig.enabled=False`` they degrade to the plain
collective so compressed/uncompressed graphs differ only in the codec — this
is how the roofline A/B in EXPERIMENTS.md is produced.

Compressible collectives: all_gather / all_to_all / ppermute (pure data
movement) and the all-gather half of psum (reduce_scatter must stay
uncompressed: lossless exponent coding does not commute with addition — the
paper's NoC never reduces in transit, so this is the honest TPU mapping).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import fixed


@dataclasses.dataclass(frozen=True)
class CodecConfig:
    """Where/how LEXI applies in a model run (first-class config knob)."""

    enabled: bool = True                # master switch (activations/ICI)
    weights: bool = True                # compressed-at-rest params (+FSDP AG)
    cache: bool = True                  # block-compressed hybrid caches
    grads: bool = True                  # compressed AG half of grad sync
    k: int = fixed.DEFAULT_K            # dictionary index width (bits)
    esc_frac: int = fixed.DEFAULT_ESC_FRAC  # escape capacity = N // esc_frac
    cache_block: int = 256              # tokens per compressed KV block
    # decode-attention backend: auto | pallas | interpret | jax (see
    # repro.kernels.ops.resolve_decode_backend).  auto = pallas on TPU,
    # pure-JAX elsewhere; interpret runs the fused kernels on CPU.
    decode_backend: str = "auto"
    # serving weight-matmul backend: auto | pallas | interpret | jax (see
    # repro.kernels.ops.resolve_weight_backend).  Same semantics: how
    # PackedWeight leaves are multiplied — fused decompress_matmul
    # (pallas/interpret) or exact unpack-then-einsum (jax).
    weight_backend: str = "auto"

    def esc_capacity(self, n: int) -> int:
        return max(n // self.esc_frac, 8)

    @classmethod
    def off(cls) -> "CodecConfig":
        return cls(enabled=False, weights=False, cache=False, grads=False)

    @classmethod
    def weights_only(cls) -> "CodecConfig":
        """Paper Table 3 middle row: offline-compressed weights only."""
        return cls(enabled=False, weights=True, cache=False, grads=False)


DEFAULT_CODEC = CodecConfig()


def shmap(f, mesh, in_specs, out_specs):
    """Project-standard shard_map: vma checking off (the codec's scatter
    ops defeat replication inference; correctness is covered by tests)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _compress(x: jax.Array, cfg: CodecConfig) -> fixed.Compressed:
    return fixed.compress(x, k=cfg.k, esc_capacity=cfg.esc_capacity(x.size))


# ---------------------------------------------------------------------------
# all_gather
# ---------------------------------------------------------------------------

def compressed_all_gather(x: jax.Array, axis_name: str | Tuple[str, ...],
                          cfg: CodecConfig = DEFAULT_CODEC, *,
                          gather_axis: int = 0, tiled: bool = True) -> jax.Array:
    """all_gather with LEXI-FW packing on the wire.

    ``x`` is the local shard; the result concatenates all shards along
    ``gather_axis`` (tiled) or stacks a new leading axis (not tiled).
    """
    if not cfg.enabled:
        return jax.lax.all_gather(x, axis_name, axis=gather_axis, tiled=tiled)
    ct = _compress(x, cfg)
    gathered = jax.lax.all_gather(ct, axis_name, axis=0, tiled=False)
    parts = jax.vmap(fixed.decompress)(gathered)      # (S, *x.shape)
    if not tiled:
        # untiled inserts a NEW axis: gather_axis indexes the output's
        # ndim+1 axes, so it must not be folded modulo x.ndim
        return jnp.moveaxis(parts, 0, gather_axis)
    gather_axis = gather_axis % x.ndim          # normalize negative axes
    # tiled: fold the shard axis into gather_axis with one moveaxis+reshape
    # (constant trace size; a per-shard concat loop grows with shard count)
    moved = jnp.moveaxis(parts, 0, gather_axis)       # (..., S, g, ...)
    shape = list(x.shape)
    shape[gather_axis] = parts.shape[0] * x.shape[gather_axis]
    return moved.reshape(shape)


# ---------------------------------------------------------------------------
# psum = reduce_scatter (raw) + all_gather (compressed)
# ---------------------------------------------------------------------------

def _axis_size(axis_name) -> int:
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    size = 1
    for a in names:
        size *= jax.lax.psum(1, a)
    return int(size)


def compressed_psum(x: jax.Array, axis_name: str | Tuple[str, ...],
                    cfg: CodecConfig = DEFAULT_CODEC, *,
                    scatter_axis: int | None = None) -> jax.Array:
    """Allreduce as RS + LEXI-compressed AG (beyond-paper gradient trick).

    The RS half moves raw bf16 (it sums); the AG half moves packed bytes —
    total wire bytes drop from 2·(S-1)/S·|x| to (1 + 1/r)·(S-1)/S·|x| with r
    the packing ratio.  ``scatter_axis`` must divide by the axis size; if
    none is given the first divisible axis is used, and if none divides the
    call falls back to a plain (uncompressed) psum.
    """
    if not cfg.enabled:
        return jax.lax.psum(x, axis_name)
    size = _axis_size(axis_name)
    if scatter_axis is None:
        scatter_axis = next((i for i, d in enumerate(x.shape) if d % size == 0),
                            None)
        if scatter_axis is None:
            return jax.lax.psum(x, axis_name)
    part = jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                                tiled=True)
    return compressed_all_gather(part, axis_name, cfg, gather_axis=scatter_axis)


def sync_gradients(grads: Any, axis_names: Sequence[str],
                   cfg: CodecConfig = DEFAULT_CODEC) -> Any:
    """Data-parallel gradient synchronization for a pytree.

    Leaves are flattened and concatenated into one fused buffer (single
    collective — latency-optimal at scale), padded to the axis size, then
    mean-reduced with the compressed RS+AG schedule.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.bfloat16) for l in leaves])
    axis_size = 1
    for a in axis_names:
        axis_size *= jax.lax.psum(1, a)
    pad = (-flat.size) % int(axis_size)
    flat = jnp.pad(flat, (0, pad))
    if cfg.enabled and cfg.grads:
        total = compressed_psum(flat, tuple(axis_names), cfg)
    else:
        total = jax.lax.psum(flat, tuple(axis_names))
    total = total / axis_size
    out = []
    off = 0
    for sz, shp, leaf in zip(sizes, shapes, leaves):
        out.append(total[off:off + sz].reshape(shp).astype(leaf.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# all_to_all (MoE dispatch/return)
# ---------------------------------------------------------------------------

def compressed_all_to_all(x: jax.Array, axis_name: str,
                          cfg: CodecConfig = DEFAULT_CODEC, *,
                          split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
    """all_to_all with per-destination-slice LEXI packing.

    ``x`` has its ``split_axis`` divisible by the axis size; each slice is
    compressed with its own dictionary (the paper's per-layer codebook --
    here per-destination), shuffled packed, and decompressed at the receiver.
    """
    if not cfg.enabled:
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)
    size = jax.lax.psum(1, axis_name)
    x = jnp.moveaxis(x, split_axis, 0)
    lead = x.shape[0]
    x = x.reshape((size, lead // size) + x.shape[1:])
    ct = jax.vmap(functools.partial(
        fixed.compress, k=cfg.k,
        esc_capacity=cfg.esc_capacity(x[0].size)))(x)
    shuffled = jax.tree_util.tree_map(
        lambda f: jax.lax.all_to_all(f, axis_name, split_axis=0,
                                     concat_axis=0, tiled=False), ct)
    parts = jax.vmap(fixed.decompress)(shuffled)
    parts = parts.reshape((lead,) + parts.shape[2:])
    parts = jnp.moveaxis(parts, 0, split_axis)
    if concat_axis != split_axis:
        parts = jnp.moveaxis(parts, split_axis, concat_axis)
    return parts


# ---------------------------------------------------------------------------
# ppermute (pipeline stage forwarding / halo exchange)
# ---------------------------------------------------------------------------

def compressed_ppermute(x: jax.Array, axis_name: str,
                        perm: Sequence[Tuple[int, int]],
                        cfg: CodecConfig = DEFAULT_CODEC) -> jax.Array:
    """collective_permute with LEXI packing (inter-stage activations)."""
    if not cfg.enabled:
        return jax.lax.ppermute(x, axis_name, perm)
    ct = _compress(x, cfg)
    moved = jax.tree_util.tree_map(
        lambda f: jax.lax.ppermute(f, axis_name, perm), ct)
    return fixed.decompress(moved)


# ---------------------------------------------------------------------------
# Differentiable wrappers — used in model *forward* passes.
#
# The codec's bit ops are not differentiable, but decompress∘compress is the
# identity (lossless), so each wrapper carries a custom VJP whose cotangent
# path is the transposed collective — itself LEXI-compressed when it is pure
# data movement (activation gradients cross the same links in reverse, and
# the paper's codec sits on every port).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def lexi_all_gather(x: jax.Array, axis_name, cfg: CodecConfig,
                    gather_axis: int = 0) -> jax.Array:
    """Differentiable compressed all_gather (tiled along ``gather_axis``)."""
    return compressed_all_gather(x, axis_name, cfg, gather_axis=gather_axis)


def _lag_fwd(x, axis_name, cfg, gather_axis):
    return lexi_all_gather(x, axis_name, cfg, gather_axis), None


def _lag_bwd(axis_name, cfg, gather_axis, _, ct):
    # transpose of (tiled) all_gather = psum_scatter; it sums, so it moves raw.
    return (jax.lax.psum_scatter(ct, axis_name,
                                 scatter_dimension=gather_axis, tiled=True),)


lexi_all_gather.defvjp(_lag_fwd, _lag_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def lexi_psum(x: jax.Array, axis_name, cfg: CodecConfig) -> jax.Array:
    """Differentiable psum whose AG half is compressed (see compressed_psum).

    Requires ``x.shape[0]`` divisible by the axis size when compression is on.
    """
    return compressed_psum(x, axis_name, cfg)


def _lps_fwd(x, axis_name, cfg):
    return lexi_psum(x, axis_name, cfg), None


def _lps_bwd(axis_name, cfg, _, ct):
    # JAX convention: transpose(psum) = psum (per-shard losses sum).  The
    # backward collective is itself an allreduce, so reuse the compressed
    # RS+AG schedule for it.
    return (compressed_psum(ct, axis_name, cfg),)


lexi_psum.defvjp(_lps_fwd, _lps_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lexi_all_to_all(x: jax.Array, axis_name, cfg: CodecConfig,
                    split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
    """Differentiable compressed all_to_all (MoE dispatch/return)."""
    return compressed_all_to_all(x, axis_name, cfg, split_axis=split_axis,
                                 concat_axis=concat_axis)


def _la2a_fwd(x, axis_name, cfg, split_axis, concat_axis):
    return lexi_all_to_all(x, axis_name, cfg, split_axis, concat_axis), None


def _la2a_bwd(axis_name, cfg, split_axis, concat_axis, _, ct):
    # all_to_all is its own transpose with split/concat swapped; gradients
    # are activations in transit -> compress them too.
    return (lexi_all_to_all(ct, axis_name, cfg, concat_axis, split_axis),)


lexi_all_to_all.defvjp(_la2a_fwd, _la2a_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def lexi_ppermute(x: jax.Array, axis_name,
                  perm: Tuple[Tuple[int, int], ...],
                  cfg: CodecConfig = DEFAULT_CODEC) -> jax.Array:
    """Differentiable compressed collective_permute (pipeline forwarding)."""
    return compressed_ppermute(x, axis_name, perm, cfg)


def _lpp_fwd(x, axis_name, perm, cfg):
    return lexi_ppermute(x, axis_name, perm, cfg), None


def _lpp_bwd(axis_name, perm, cfg, _, ct):
    inv = tuple((d, s) for (s, d) in perm)
    return (lexi_ppermute(ct, axis_name, inv, cfg),)


lexi_ppermute.defvjp(_lpp_fwd, _lpp_bwd)
