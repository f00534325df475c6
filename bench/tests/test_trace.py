"""The trace reduction on a small trace: busy time as the union of device
op intervals inside the window, device time per op, kernels found by
name, and idle gaps labelled by the harness span open on the host."""

from pathlib import Path

import pytest

from bench import trace

TESTDATA = Path(__file__).resolve().parent.parent / "testdata"

# device ops (ns, relative to 1000): a [0, 5), b [3, 8) overlaps a,
# kernel [10, 12), c [20, 30) starts inside and ends outside the window
# [0, 25); host spans: decode [0, 9), admit [9, 25)
HAND = """
planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops"
  timestamp_ns: 1000
  events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
  events { metadata_id: 2 offset_ps: 3000 duration_ps: 5000 }
  events { metadata_id: 3 offset_ps: 10000 duration_ps: 2000 }
  events { metadata_id: 4 offset_ps: 20000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "decompress_matmul.3" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.4" } } }
planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python"
  timestamp_ns: 1000
  events { metadata_id: 1 offset_ps: 0 duration_ps: 25000 }
  events { metadata_id: 2 offset_ps: 0 duration_ps: 9000 }
  events { metadata_id: 3 offset_ps: 9000 duration_ps: 16000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.decode" } }
  event_metadata { key: 3 value { id: 3 name: "bench.admit" } } }
"""


def _profile(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_reduction_by_hand():
    red = trace.reduce(_profile(HAND))
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(25e-9)
    # busy: [0, 8), [10, 12) and [20, 25): ops are clipped to the window
    assert red["busy_s"] == pytest.approx(15e-9)
    assert red["ops"]["fusion.4"] == (pytest.approx(5e-9), 1)
    assert trace.kernel_time(
        red, lambda op: trace.op_name(op) == "decompress_matmul") == (
        pytest.approx(2e-9), 1)
    # idle [8, 10) under decode; [12, 20) under admit
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.decode"] == pytest.approx(2e-9)
    assert gaps["bench.admit"] == pytest.approx(8e-9)
    assert red["device_ops"][0][0] in ("fusion.1", "fusion.2")


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(_profile(
            'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "x" } }'))


def test_recorded_excerpt():
    """4 ms of a qwen1.5-1.8b.chat trace on one TPU v5e (16 slots): one
    paged-attention call, seven fused weight matmuls, and the harness's
    admission span."""
    import re

    from bench.metrics import (decode_attend_paged_roofline as attend,
                               decompress_matmul_roofline as matmul)
    text = (TESTDATA / "chat_trace_excerpt.pbtxt").read_text()
    red = trace.reduce(_profile(text))
    assert red["window_s"] == pytest.approx(4e-3)
    idle = sum(v for _, v in red["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"])
    assert [k for k, _ in red["idle_gaps"]] == ["bench.admit"]
    # the attention call's own duration, read straight from the file
    device = text.split("planes {")[1]
    (mid,) = re.findall(r'key: (\d+) value \{ id: \d+ name: "%closed_call',
                        device)
    (dur,) = re.findall(rf"metadata_id: {mid} offset_ps: \d+ "
                        r"duration_ps: (\d+)", device)
    assert trace.kernel_time(red, attend.is_kernel) == (
        pytest.approx(int(dur) * 1e-12), 1)
    sec, n = trace.kernel_time(red, matmul.is_kernel)
    assert n == 7 and 0 < sec < red["busy_s"]
    assert red["device_ops"][0][0].startswith("%closed_call")
