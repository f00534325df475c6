"""The LEXI-FW encoder is byte-identical to its scatter formulation.

``fixed.compress`` builds its histogram, codes and escape side channel
with no scatter, sort or gather over the values.  The reference below is
the formulation it replaced (scatter-add histogram, 256-entry LUT gather,
escape scatters into C+1 slots); every field must match it exactly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import entropy as E
from repro.core import fixed, packing


def _reference(x, *, k, esc_capacity=None, dictionary=None):
    u16 = E.jnp_to_u16(x).reshape(-1)
    n = u16.size
    np_ = packing.pad_to_lanes(n)
    c = esc_capacity if esc_capacity is not None else max(
        n // fixed.DEFAULT_ESC_FRAC, 8)
    esc = fixed.esc_index(k)
    exp = ((u16 >> 7) & 0xFF).astype(jnp.int32)
    if dictionary is None:
        hist = jnp.zeros((256,), jnp.int32).at[exp].add(1)
        dictionary = fixed.build_dictionary(hist, k)
    dict_syms, enc_lut = dictionary
    codes = jnp.pad(enc_lut[exp], (0, np_ - n))
    esc_mask = codes[:n] == esc
    slot = jnp.cumsum(esc_mask.astype(jnp.int32)) - 1
    write_slot = jnp.where(esc_mask & (slot < c), slot, c)
    esc_pos = jnp.full((c + 1,), np_, jnp.int32).at[write_slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")[:c]
    esc_raw = jnp.zeros((c + 1,), jnp.uint8).at[write_slot].set(
        exp.astype(jnp.uint8), mode="drop")[:c]
    return fixed.Compressed(
        signman=E.jnp_signman(u16), planes=packing.bitplane_pack(codes, k),
        dict_syms=dict_syms, esc_pos=esc_pos, esc_raw=esc_raw,
        n_escapes=jnp.sum(esc_mask.astype(jnp.int32)), shape=tuple(x.shape),
        k=k)


FIELDS = ("signman", "planes", "dict_syms", "esc_pos", "esc_raw",
          "n_escapes")


def _assert_identical(got, want):
    assert got.shape == want.shape and got.k == want.k
    for f in FIELDS:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert a.dtype == b.dtype and a.shape == b.shape, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def _bf16(values):
    return jnp.asarray(np.asarray(values, np.float32)).astype(jnp.bfloat16)


def _from_bits(bits):
    return jax.lax.bitcast_convert_type(jnp.asarray(bits, jnp.uint16),
                                        jnp.bfloat16)


rng = np.random.default_rng(14)
SPECIALS = [0.0, -0.0, np.inf, -np.inf, np.nan, 1e-40, -3e-39, 9e-41,
            1e38, -1e38, 1.5, -2.25]

# name -> (input, k, esc_capacity, the escapes the case must hold)
CASES = {
    "n_unaligned": (_bf16(rng.normal(0, 0.3, 1000)), 5, None, "none"),
    "no_escapes": (_bf16(rng.normal(0, 0.05, (33, 64))), 5, None, "none"),
    "escapes_under_capacity": (
        _bf16(2.0 ** rng.uniform(-60, 60, 2000)), 5, 2000, "under"),
    "escape_overflow": (
        _bf16(2.0 ** rng.uniform(-60, 60, 4000)), 4, 128, "over"),
    "all_zero": (jnp.zeros((7, 45), jnp.bfloat16), 5, None, "none"),
    "special_values": (_bf16(SPECIALS * 37), 4, None, "any"),
    "random_bits": (_from_bits(rng.integers(0, 1 << 16, 3001)), 5, 64,
                    "over"),
    "few_exponents": (_bf16(rng.choice([1.0, -2.0, 0.5, 3.0], 777)), 5,
                      None, "none"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compress_matches_scatter_reference(case):
    x, k, c, escapes = CASES[case]
    got = fixed.compress(x, k=k, esc_capacity=c)
    want = jax.jit(functools.partial(_reference, k=k, esc_capacity=c))(x)
    _assert_identical(got, want)
    cap = got.esc_pos.shape[0]
    n_esc = int(got.n_escapes)
    assert {"none": n_esc == 0, "under": 0 < n_esc <= cap,
            "over": n_esc > cap, "any": True}[escapes], n_esc


def _ring_batch():
    """Four (256, 512) rings as the flush stacks them: a normal one, an
    empty one, one with escapes under capacity and one that overflows."""
    shape = (256, 512)
    normal = rng.normal(0, 0.5, shape)
    wide = rng.normal(0, 0.5, shape)
    wide.flat[rng.choice(wide.size, 700, replace=False)] = \
        2.0 ** rng.uniform(-100, 100, 700)
    rings = jnp.stack([_bf16(normal), jnp.zeros(shape, jnp.bfloat16),
                       _bf16(wide),
                       _from_bits(rng.integers(0, 1 << 16, shape))])
    return rings


def test_vmapped_ring_flush_matches_scatter_reference():
    rings = _ring_batch()
    c = rings[0].size // 128
    got = jax.vmap(lambda r: fixed.compress(r, k=5, esc_capacity=c))(rings)
    want = jax.jit(jax.vmap(
        lambda r: _reference(r, k=5, esc_capacity=c)))(rings)
    _assert_identical(got, want)
    n_esc = np.asarray(got.n_escapes)
    assert n_esc[1] == 0 and 0 < n_esc[2] <= c < n_esc[3]


@pytest.mark.parametrize("source", ["other_tensor", "own_tensor"])
def test_compress_with_dict_matches_scatter_reference(source):
    x = _bf16(rng.normal(0, 1.0, 1500) * 2.0 ** rng.integers(-20, 20, 1500))
    base = x if source == "own_tensor" else _bf16(rng.normal(0, 0.01, 900))
    u16 = E.jnp_to_u16(base).reshape(-1)
    hist = jnp.zeros((256,), jnp.int32).at[(u16 >> 7) & 0xFF].add(1)
    dictionary = fixed.build_dictionary(hist, 5)
    got = fixed.compress_with_dict(x, *dictionary, k=5, esc_capacity=600)
    want = _reference(x, k=5, esc_capacity=600, dictionary=dictionary)
    _assert_identical(got, want)
    assert int(got.n_escapes) > 0


HISTOGRAM_STREAMS = {
    "uniform": rng.integers(0, 256, 5000),
    "one_bin": np.full(3000, 255),
    "edges": np.repeat([0, 15, 16, 240, 255], [1, 2, 700, 3, 999]),
}


@pytest.mark.parametrize("stream", list(HISTOGRAM_STREAMS))
def test_histogram_matches_bincount(stream):
    exp = HISTOGRAM_STREAMS[stream].astype(np.int32)
    hist = fixed.exponent_histogram(jnp.asarray(exp))
    assert hist.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(hist),
                                  np.bincount(exp, minlength=256))
