"""Reduction of a profiler trace to device busy time, per-op device time
and idle gaps labelled by the harness span that was open on the host.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``.  Device planes are named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation that ran (a Pallas
kernel is one such operation).  Host planes hold the harness's own spans
(``jax.profiler.TraceAnnotation``), named ``bench.<what>``; the span
``bench.window`` marks the traced window.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10
SHORT = 120                 # characters of an op's HLO text in the breakdown


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(pd) -> dict:
    """Busy seconds (mean over devices), traced window seconds, device time
    and count per op name, the ops with the most self time, and idle
    seconds per host span label.

    The window is the ``bench.window`` span when the trace has one, else
    the first to the last device op."""
    spans: List[Tuple[int, int, str]] = []
    devices: Dict[str, List] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((int(e.start_ns), int(e.end_ns),
                                      e.name))
    if not devices:
        raise ValueError("the trace has no device op line "
                         f"({DEVICE_PREFIX}*/{OPS_LINE})")
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][0], win[0][1]
    else:
        allev = [e for evs in devices.values() for e in evs]
        lo, hi = min(e[1] for e in allev), max(e[2] for e in allev)
    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    own: Dict[str, float] = defaultdict(float)
    busy, gaps = [], defaultdict(float)
    inner = sorted((s for s in spans if s[2] != WINDOW_SPAN),
                   key=lambda s: s[1] - s[0])
    for evs in devices.values():
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                  if b > lo and a < hi]          # clipped to the window
        for name, a, b in inside:
            ops[name][0] += (b - a) * 1e-9
            ops[name][1] += 1
        for name, sec in _self_times(inside):
            own[name] += sec
        u = _union([(a, b) for _, a, b in inside])
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_label(inner, (a + b) // 2)] += (b - a) * 1e-9 / len(
                    devices)
    top = sorted(own.items(), key=lambda kv: -kv[1])
    return {
        "n_devices": len(devices),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "ops": {k: (v[0], v[1]) for k, v in ops.items()},
        "device_ops": [[k[:SHORT], v / len(devices)] for k, v in top[:TOP]],
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def _self_times(events):
    """(name, seconds) of each op's own time: an op that contains others
    (a loop, a conditional) keeps only the time no op inside it covers."""
    out, stack = [], []              # stack of [name, end, own_ns]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            out.append((top[0], top[2] * 1e-9))
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    out.extend((n, own * 1e-9) for n, _, own in stack)
    return out


def _label(spans, t: int) -> str:
    """The innermost harness span open at ``t``."""
    for a, b, name in spans:
        if a <= t <= b:
            return name
    return "host.between_spans"


def op_name(op: str) -> str:
    """``%decompress_matmul.95 = f32[...] custom-call(...)`` ->
    ``decompress_matmul``: the HLO instruction's own name, no number."""
    return op.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]


def kernel_time(red: dict, match) -> Tuple[float, int]:
    """Summed device seconds and count of the ops for which
    ``match(hlo_text)`` holds, per device."""
    s, n = 0.0, 0
    for op, (sec, cnt) in red["ops"].items():
        if match(op):
            s += sec
            n += cnt
    return s / red["n_devices"], n // red["n_devices"]
