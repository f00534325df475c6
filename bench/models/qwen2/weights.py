"""Seeded weights for a Qwen1.5-style decoder, made by the benchmark.

``make(dims, seed)`` builds the whole model on the device in one jitted
call, in bfloat16, in the layout of the published checkpoints (rotate-half
RoPE; matrices stored ``(in, out)``).  The plain reference reads this tree.
``to_program`` maps it onto the serving program's parameter tree, which is
what a checkpoint loader of the program has to do: stack the layers, rename
the leaves, pad the vocabulary, and reorder each q/k head's columns from
rotate-half pairs ``(i, i + hd/2)`` to the interleaved pairs
``(2i, 2i + 1)`` that the program's RoPE rotates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02                 # matrices, embedding and biases
NORM_STD = 0.1             # norm scales are 1 + N(0, NORM_STD)


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    eps: float
    theta: float

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        h = conf["num_attention_heads"]
        return cls(vocab=conf["vocab_size"], d=conf["hidden_size"],
                   layers=conf["num_hidden_layers"], heads=h,
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf.get("head_dim",
                                     conf["hidden_size"] // h),
                   ffn=conf["intermediate_size"], eps=conf["rms_norm_eps"],
                   theta=conf["rope_theta"])


def key_of(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _shapes(m: Dims) -> dict:
    L, d, q, kv, f = (m.layers, m.d, m.heads * m.head_dim,
                      m.kv_heads * m.head_dim, m.ffn)
    return {
        "embed": (m.vocab, d), "norm": (d,), "lm_head": (d, m.vocab),
        "layers": {"ln1": (L, d), "wq": (L, d, q), "bq": (L, q),
                   "wk": (L, d, kv), "bk": (L, kv), "wv": (L, d, kv),
                   "bv": (L, kv), "wo": (L, q, d), "ln2": (L, d),
                   "wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}}


def _draw(m: Dims, key):
    shapes = _shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        z = jax.random.normal(k, shape, jnp.float32)
        if "ln" in name or "norm" in name:
            w = 1.0 + NORM_STD * z
        else:
            w = STD * z
        out.append(w.astype(jnp.bfloat16))
    return jax.tree_util.tree_unflatten(tree, out)


def make(m: Dims, seed: int):
    """The reference's weight tree, bf16, made on the device."""
    return jax.jit(_draw, static_argnums=0)(m, key_of(seed))


def rope_perm(head_dim: int) -> np.ndarray:
    """Program column j of a head takes published column ``perm[j]``."""
    half = np.arange(head_dim // 2)
    return np.stack([half, half + head_dim // 2], 1).reshape(-1)


def _to_program(m: Dims, vocab_padded: int, w):
    hd = m.head_dim

    def heads(a, n):                     # (..., n*hd) -> interleaved pairs
        shp = a.shape
        a = a.reshape(shp[:-1] + (n, hd))[..., rope_perm(hd)]
        return a.reshape(shp)

    pad = vocab_padded - m.vocab
    lw = w["layers"]
    return {
        "embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
        "final_norm": w["norm"],
        "lm_head": jnp.pad(w["lm_head"], ((0, 0), (0, pad))),
        "blocks": {
            "ln1": lw["ln1"], "ln2": lw["ln2"],
            "attn": {"wq": heads(lw["wq"], m.heads),
                     "bq": heads(lw["bq"], m.heads),
                     "wk": heads(lw["wk"], m.kv_heads),
                     "bk": heads(lw["bk"], m.kv_heads),
                     "wv": lw["wv"], "bv": lw["bv"], "wo": lw["wo"]},
            "mlp": {"w_gate": lw["wg"], "w_up": lw["wu"],
                    "w_down": lw["wd"]}}}


def program_params(m: Dims, vocab_padded: int, seed: int):
    """The program's parameter tree for ``seed``, made in one jitted call
    (the published-layout tree exists only inside it)."""
    f = jax.jit(lambda k: _to_program(m, vocab_padded, _draw(m, k)))
    return f(key_of(seed))
