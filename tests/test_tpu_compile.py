"""The serving kernels compile for a TPU v5e at the smoke run's shapes.

The chip's compiler is installed without the chip: a described ``v5e:2x2``
topology lets ``jit(...).lower(...).compile()`` run Mosaic on each kernel,
which refuses what the interpreter accepts (block shapes off the (8, 128)
tiling, unsupported in-kernel ops, VMEM overflows).  Shapes are
qwen1.5-1.8b at tp=1 (16 KV heads of 128, 256-token pages, 4 slots, 2048
tokens per slot) and its weight matmuls at decode (M = 4 slots).

This is the only test file that describes the chip.  The topology is
described inside a module fixture — never at import — so every test worker
collects the same tests and only the worker running this file loads the
TPU library; the persistent compilation cache is off around these tests
(entries written without a chip cannot be read back).
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attend import (WINDOW_NONE, decode_attend,
                                         decode_attend_paged,
                                         page_plane_shape)
from repro.kernels.decompress_matmul import decompress_matmul

S, H, HKV, HD, BLK, K = 4, 16, 16, 128, 256, 5
MAXP = 2048 // BLK + 2           # models.cache.max_pages_per_slot, tp=1
W = 2 * HKV * HD
N_PAGE = BLK * W


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_ops(text, name):
    """The compiled program's instructions named ``name``, as the profiler
    shows an op: ``%name.N = ...`` without the leading ``ROOT``."""
    lines = (ln.strip().removeprefix("ROOT ") for ln in text.splitlines())
    return [ln for ln in lines if ln.startswith(f"%{name}.")]


@pytest.mark.parametrize("codec_on", [True, False], ids=["codec", "raw"])
def test_decode_attend_paged_compiles(one_chip, codec_on):
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                sharding=one_chip)
    pool = S * MAXP
    c = N_PAGE // 128                  # CodecConfig.esc_capacity
    fields = ((sd((pool, BLK, W), jnp.uint8),
               sd((pool, K) + page_plane_shape(BLK, W, N_PAGE), jnp.uint32),
               sd((pool, 1 << K), jnp.uint8), sd((pool, c), jnp.int32),
               sd((pool, c), jnp.uint8), None) if codec_on else
              (None,) * 5 + (sd((pool, BLK, W), jnp.bfloat16),))

    def attend(q, sm, planes, dicts, escp, escr, raw, ring, pids, lens):
        return decode_attend_paged(
            q, sm, planes, dicts, escp, escr, raw, ring, pids, lens, 0,
            WINDOW_NONE, k=K, hkv=HKV, hd=HD, kv_idx=tuple(range(H)),
            scale=HD ** -0.5, tp=1)

    text = _compiled_text(attend, (
        sd((S, H, HD), jnp.bfloat16), *fields,
        sd((S, BLK, W), jnp.bfloat16), sd((S, MAXP), jnp.int32),
        sd((S,), jnp.int32)))
    assert "tpu_custom_call" in text
    # the kernel carries its name, and the benchmark's reader still finds it
    from bench.metrics import decode_attend_paged_roofline as reader
    (op,) = _kernel_ops(text, "decode_attend_paged")
    assert "tpu_custom_call" in op and reader.is_kernel(op)


def test_decode_attend_fixed_compiles(one_chip):
    b, blk, hkv, nblk = 2, 128, 2, 4
    w = 2 * hkv * HD
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                sharding=one_chip)

    def attend(q, raw, ring, length):
        return decode_attend(
            q, None, None, None, None, None, raw, ring, length, 0,
            WINDOW_NONE, k=K, hkv=hkv, hd=HD, kv_idx=(0, 0, 1, 1),
            scale=HD ** -0.5, tp=1)

    text = _compiled_text(attend, (
        sd((b, 4, HD), jnp.bfloat16), sd((nblk, b, blk, w), jnp.bfloat16),
        sd((b, blk, w), jnp.bfloat16), sd((), jnp.int32)))
    (op,) = _kernel_ops(text, "decode_attend")
    assert "tpu_custom_call" in op


@pytest.mark.parametrize("kn", [(2048, 5504), (2048, 151936), (5504, 2048)],
                         ids=["mlp_up", "lm_head", "mlp_down"])
def test_decompress_matmul_compiles(one_chip, kn):
    kk, n = kn
    k = 4
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                sharding=one_chip)
    text = _compiled_text(
        lambda *a: decompress_matmul(*a, k=k),
        (sd((S, kk), jnp.bfloat16), sd((kk, n), jnp.uint8),
         sd((k, n // 32, kk), jnp.uint32), sd((1 << k,), jnp.uint8)))
    assert "tpu_custom_call" in text
    from bench.metrics import decompress_matmul_roofline as reader
    ops = _kernel_ops(text, "decompress_matmul")
    assert ops and all(reader.is_kernel(op) for op in ops)


def _big_data_ops(text, big):
    """The compiled program's sorts, scatters and gathers that move ``big``
    or more elements: any sort or scatter with an operand or result that
    large, and any gather whose indices or result are.  A gather's source
    may be large, since it reads only as many elements as it has indices;
    those are returned too, as ``(line, result elements)``, for the caller
    to bound."""
    shape = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
    inst = re.compile(r"(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)"
                      r"\((.*?)\)")
    parsed = [m.groups() for m in map(inst.match, (
        ln.strip() for ln in text.splitlines())) if m]
    defs = {name: ty for name, ty, _, _ in parsed}

    def elems(ty):
        return max((math.prod(int(d) for d in dims.split(",") if d)
                    for dims in shape.findall(ty)), default=0)

    moving, reads = [], []
    for name, ty, op, args in parsed:
        if op not in ("sort", "scatter", "gather"):
            continue
        sizes = [elems(defs.get(a, "")) for a in re.findall(r"%([\w.\-]+)",
                                                            args)]
        if op == "gather":
            if max(elems(ty), sizes[1]) >= big:
                moving.append(f"{name}: {ty}")
            elif sizes[0] >= big:
                reads.append((f"{name}: {ty}", elems(ty)))
        elif max([elems(ty)] + sizes) >= big:
            moving.append(f"{name}: {ty}")
    return moving, reads


def test_ring_flush_encoder_moves_no_value_through_sort_scatter_gather(
        one_chip):
    """The flush's ``vmap(fixed.compress)`` over S rings of BLK x W values
    compiles with no sort, scatter or gather over the values: XLA lowers
    those to sorts over every value on the TPU.  What reads a whole ring
    by index is the escape side channel, at most C values per ring."""
    from repro.core import fixed
    c = N_PAGE // 128                  # CodecConfig.esc_capacity
    text = _compiled_text(
        jax.vmap(lambda r: fixed.compress(r, k=K, esc_capacity=c)),
        (jax.ShapeDtypeStruct((S, BLK, W), jnp.bfloat16, sharding=one_chip),))
    moving, reads = _big_data_ops(text, N_PAGE)
    assert not moving, moving[:3]
    assert all(n <= S * c for _, n in reads), reads
