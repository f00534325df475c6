"""Plain float32 forward pass of a Qwen1.5 (Qwen2-architecture) decoder.

Written from the published architecture, not from the program: token
embedding; per layer RMSNorm, attention with biases on q/k/v, rotate-half
RoPE, causal softmax, output projection, residual, RMSNorm, SwiGLU MLP,
residual; final RMSNorm and an untied LM head.  Every matrix product runs
in float32 at ``Precision.HIGHEST``.  It imports nothing of the program
and reads only the benchmark's own weight tree (``weights.py`` beside it).

``quant="fp8"`` is the control: the same forward with the operands of every
linear layer rounded to float8 e4m3 (weights per output column, activations
per token, each scaled by its absolute maximum), the lower precision a
serving path could be tempted to use in place of bfloat16.

The forward runs one padded sequence at a time and scans over layers, so
only one layer's weights are ever in float32; attention runs in blocks of
queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, H, hd), rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q, k, v):
    """Causal attention, q/k/v (T, H, hd) with H already grouped."""
    t, h, hd = q.shape
    nb = t // Q_BLOCK
    kpos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    return jax.lax.map(block, jnp.arange(nb)).reshape(t, h, hd)


def _layer(m, quant, x, lw):
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, lw["ln1"], m.eps)
    q = (_linear(h, lw["wq"], quant) + lw["bq"].astype(jnp.float32)
         ).reshape(t, m.heads, m.head_dim)
    k = (_linear(h, lw["wk"], quant) + lw["bk"].astype(jnp.float32)
         ).reshape(t, m.kv_heads, m.head_dim)
    v = (_linear(h, lw["wv"], quant) + lw["bv"].astype(jnp.float32)
         ).reshape(t, m.kv_heads, m.head_dim)
    q, k = _rope(q, pos, m.theta), _rope(k, pos, m.theta)
    g = m.heads // m.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    a = _attention(q, k, v).reshape(t, m.heads * m.head_dim)
    x = x + _linear(a, lw["wo"], quant)
    h = _rms(x, lw["ln2"], m.eps)
    gate = _linear(h, lw["wg"], quant)
    up = _linear(h, lw["wu"], quant)
    return x + _linear(jax.nn.silu(gate) * up, lw["wd"], quant), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def hidden(m, quant, w, tokens):
    """Final normed hidden states (T, d) f32 of one padded sequence."""
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, m, quant), x, w["layers"])
    return _rms(x, w["norm"], m.eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits(quant, w, h):
    return _linear(h, w["lm_head"], quant)


def logits(m, w, tokens: np.ndarray, rows: np.ndarray,
           quant=None) -> np.ndarray:
    """Logits (len(rows), vocab) f32 at positions ``rows`` of ``tokens``.

    The sequence is padded to a multiple of ``Q_BLOCK`` (one compiled
    program per padded length); causal masking keeps the padding out of
    every position that is read."""
    t = len(tokens)
    tp = -(-t // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((tp,), np.int32)
    toks[:t] = tokens
    h = hidden(m, quant, w, jnp.asarray(toks))
    return np.asarray(_logits(quant, w, h[jnp.asarray(rows)]))


def widest_gap(m, w, prompt: np.ndarray, served: np.ndarray,
               quant=None) -> float:
    """Widest gap, over the served tokens, between the float32 reference's
    best logit and its logit of the token chosen there.

    Without ``quant`` the chosen token is the served one.  With ``quant``
    it is the token that the lower-precision forward puts first at each
    position of the same prompt and served tokens (the control)."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    ref = logits(m, w, seq, rows)
    chosen = served
    if quant is not None:
        chosen = logits(m, w, seq, rows, quant).argmax(-1)
    picked = ref[np.arange(len(rows)), chosen]
    return float(np.max(ref.max(-1) - picked))
