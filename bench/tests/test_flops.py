"""The FLOP/byte functions against shapes worked by hand, and the peak
table."""

import numpy as np
import pytest

from bench import flops, peaks
from bench.models.qwen2 import Dims

M = Dims(vocab=10, d=4, layers=2, heads=2, kv_heads=1, head_dim=2, ffn=8,
         eps=1e-6, theta=1e4)


def test_token_flops():
    # per layer: q 4*4 + k,v 2*(4*2) + o 4*4 + mlp 3*4*8 = 144 weights
    assert flops.layer_matmul_params(M) == 144
    # 2 * (2 layers * 144 + head 4*10) + 4 * L2 * H2 * hd2 * ctx
    assert flops.token_flops(M, 5) == 2 * 328 + 32 * 5
    assert flops.token_flops(M, 5, logits=False) == 2 * 288 + 32 * 5


def test_window_model_flops():
    steps = [np.asarray([3, 7])]
    # two decode tokens at contexts 3 and 7, one trunk of 2 tokens
    want = (2 * 656 + 32 * 10) + (2 * 576 + 2 * 40 + 32 * 3)
    assert flops.window_model_flops(M, steps, [(2, 1)]) == want


def test_attend_call():
    # W = 2 * kv 1 * hd 2 = 4 values/token; slot at 300 tokens holds one
    # full 256-token page (1000 B) and 44 ring tokens at 8 B; slot at 10
    # holds 10 ring tokens; q bf16 + out f32 = 2*2*6 B per slot
    f, b = flops.attend_call(M, np.asarray([300, 10]), 1000, 256)
    assert f == 4 * 2 * 2 * 310
    assert b == 1000 + 44 * 8 + 10 * 8 + 2 * 24


def test_matmul_call_and_least_time():
    f, b = flops.matmul_call(3, 4, 5, 100)
    assert (f, b) == (120, 100 + 3 * 4 * 2 + 3 * 5 * 4)
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_s(1000, 50, p) == 10.0       # compute bound
    assert flops.least_s(100, 50, p) == 5.0         # memory bound


def test_matmul_least_s_counts_steps_and_prefill():
    p = {"bf16_flops": 1e30, "hbm_bytes_per_s": 1.0}  # bytes bound
    packed = [{"k": 4, "n": 5, "bytes": 100, "count": 2, "head": False},
              {"k": 4, "n": 10, "bytes": 50, "count": 1, "head": True}]
    steps = [np.asarray([1, 2, 3])]
    got = flops.matmul_least_s(packed, steps, [(8, 2)], p)
    step = 2 * (100 + 3 * 8 + 3 * 20) + (50 + 3 * 8 + 3 * 40)
    prefill = 2 * (100 + 16 * 8 + 16 * 20) + (50 + 2 * 8 + 2 * 40)
    assert got == step + prefill
    assert flops.matmul_least_s([], steps, [], p) is None


def test_peak_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
