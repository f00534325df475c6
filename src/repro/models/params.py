"""Declarative parameter tables: one source of truth for shapes, shardings
and initializers.

Modules declare ``{name: PDef(shape, spec, init)}``; the table is then used
to (1) initialize real arrays for smoke/e2e tests, (2) produce
ShapeDtypeStruct + NamedSharding for the dry-run, (3) drive FSDP placement
(an extra "data" axis on the largest eligible dim, gathered explicitly —
and LEXI-compressed — inside the scan body).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PDef:
    shape: Tuple[int, ...]
    spec: Tuple[Optional[str], ...]   # mesh axis per dim (None = replicated)
    init: str = "normal"              # normal | zeros | ones | normal:<std>
    dtype: Any = jnp.bfloat16
    fsdp_dim: Optional[int] = None    # filled by apply_fsdp

    def partition_spec(self) -> P:
        return P(*self.spec)


Table = Dict[str, Any]   # nested dict with PDef leaves


def is_pdef(x) -> bool:
    return isinstance(x, PDef)


def tmap(fn: Callable[[PDef], Any], table: Table) -> Any:
    return jax.tree_util.tree_map(fn, table, is_leaf=is_pdef)


def stack(table: Table, n: int) -> Table:
    """Prepend a scan (layer) dimension to every leaf."""
    return tmap(lambda d: dataclasses.replace(
        d, shape=(n,) + d.shape, spec=(None,) + d.spec,
        fsdp_dim=None if d.fsdp_dim is None else d.fsdp_dim + 1), table)


def apply_fsdp(table: Table, data_axes: Tuple[str, ...], data_size: int,
               min_size: int) -> Table:
    """Shard the largest eligible replicated dim over the data axes.

    Skips leaves that are small or have no divisible free dim.  The chosen
    dim is recorded so the forward pass knows to all-gather (compressed)
    before use.
    """

    def one(d: PDef) -> PDef:
        size = int(np.prod(d.shape))
        if size < min_size:
            return d
        cands = [(dim, s) for dim, (s, sp) in enumerate(zip(d.shape, d.spec))
                 if sp is None and s % data_size == 0 and s > 1]
        if not cands:
            return d
        dim = max(cands, key=lambda c: c[1])[0]
        entry = data_axes[0] if len(data_axes) == 1 else tuple(data_axes)
        spec = tuple(entry if i == dim else sp
                     for i, sp in enumerate(d.spec))
        return dataclasses.replace(d, spec=spec, fsdp_dim=dim)

    return tmap(one, table)


def _init_leaf(d: PDef, key: jax.Array) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    std = float(d.init.split(":")[1]) if ":" in d.init else 0.02
    return (jax.random.normal(key, d.shape, jnp.float32) * std
            ).astype(d.dtype)


def init_params(table: Table, key: jax.Array, mesh=None) -> Any:
    """Materialize real arrays from a seed.  Each leaf is built by its own
    jitted program; with ``mesh`` it is born laid out by its spec, so no
    device ever holds a whole sharded leaf or its f32 temporary."""
    leaves, treedef = jax.tree_util.tree_flatten(table, is_leaf=is_pdef)
    keys = jax.random.split(key, len(leaves))
    out = []
    for d, k in zip(leaves, keys):
        sharding = (None if mesh is None else
                    jax.sharding.NamedSharding(mesh, d.partition_spec()))
        out.append(jax.jit(_init_leaf, static_argnums=0,
                           out_shardings=sharding)(d, k))
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(table: Table) -> Any:
    """ShapeDtypeStruct pytree (dry-run: no allocation)."""
    return tmap(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), table)


def param_pspecs(table: Table) -> Any:
    """PartitionSpec pytree for shard_map in_specs / NamedSharding."""
    return tmap(lambda d: d.partition_spec(), table)


def fsdp_dims(table: Table) -> Any:
    """Pytree of Optional[int]: which dim to all-gather over data."""
    return tmap(lambda d: d.fsdp_dim, table)


def local_view(table: Table, mesh_shape: Dict[str, int]) -> Any:
    """Per-shard shapes (what shard_map sees) — for memory estimates."""

    def one(d: PDef):
        shape = []
        for s, sp in zip(d.shape, d.spec):
            axes = sp if isinstance(sp, tuple) else (sp,) if sp else ()
            div = int(np.prod([mesh_shape[a] for a in axes])) if axes else 1
            shape.append(s // div)
        return tuple(shape)

    return tmap(one, table)
