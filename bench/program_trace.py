"""The serving program's own spans and scopes in a profiler trace.

The program annotates its engine-lane host work as ``serve.<name>`` spans
(``repro.serve.telemetry.Tracer.span``), with integer counts (``steps``,
``rings_filled``, ``rings_compressed``, ...) as the spans' stats, and names
device work with ``jax.named_scope("lexi.<what>")``, which XLA keeps in each
op's ``op_name``.  On the device the profiler gives that ``op_name`` as the
``tf_op`` stat of the op's event metadata, which ``jax.profiler.ProfileData``
does not expose, so the device's op line is read here from the serialized
``XSpace`` itself.

``reduce(xspace)`` reads them over the traced window (the ``bench.window``
span, as ``trace.reduce`` takes it):

* ``spans``: for each ``serve.*`` name, the seconds, the count and the sum
  of each integer stat of the spans that lie inside the window;
* ``scopes``: device self time per ``lexi.*`` scope, mean over devices.  An
  op's scope is the innermost one in its ``op_name``; an op with none takes
  the scope of the innermost op that encloses it on the device's line (a
  conditional or loop holds its branch's ops), since XLA's rewrites leave
  the ops they create without an ``op_name``.  Self time is counted as
  ``trace._self_times`` counts it, so nothing is counted twice;
* ``idle_gaps``: the window's idle time, labelled like ``trace.reduce``'s
  but by the innermost open span of either prefix, ``bench.`` or ``serve.``.

It adds to ``trace.reduce`` and changes none of its keys; ``bench/run.py``
does not call it yet (PERF.md §7).
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterator, List, Tuple

from bench import trace

SERVE_PREFIX = "serve."
SCOPE = re.compile(r"\blexi\.[A-Za-z0-9_.]+")
OP_NAME_STAT = "tf_op"


def scope_of(op_name: str) -> str:
    """The innermost ``lexi.*`` scope in an op's ``op_name``, or ''."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else ""


# ---------------------------------------------------------------------------
# the device's op line, from the XSpace protobuf (tsl/profiler xplane.proto)
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield key >> 3, v


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _device_ops(plane: bytes) -> List[Tuple[str, int, int]]:
    """(op_name, start_ns, end_ns) of the plane's XLA Ops events, with the
    nanoseconds rounded as ``ProfileData`` gives them."""
    stat_names: Dict[int, str] = {}
    metas: Dict[int, bytes] = {}
    lines = []
    for f, v in _fields(plane):
        if f == 3:                                   # XPlane.lines
            lines.append(v)
        elif f == 4:                                 # event_metadata
            k, md = _map_entry(v)
            metas[k] = md
        elif f == 5:                                 # stat_metadata
            k, sm = _map_entry(v)
            stat_names[k] = next((bytes(x).decode() for g, x in _fields(sm)
                                  if g == 2), "")
    op_stat = [k for k, name in stat_names.items() if name == OP_NAME_STAT]
    op_names: Dict[int, str] = {}
    for k, md in metas.items():
        for f, v in _fields(md):
            if f != 5:                               # XEventMetadata.stats
                continue
            st = dict(_fields(v))
            if st.get(1) in op_stat:
                if 5 in st:                          # str_value
                    op_names[k] = bytes(st[5]).decode()
                elif 7 in st:                        # ref_value
                    op_names[k] = stat_names.get(st[7], "")
    out = []
    for line in lines:
        fields = list(_fields(line))
        if dict(fields).get(2, b"") != trace.OPS_LINE.encode():
            continue
        t0_ps = dict(fields).get(3, 0) * 1000        # timestamp_ns
        for f, ev in fields:
            if f != 4:                               # XLine.events
                continue
            e = dict(_fields(ev))
            a = t0_ps + e.get(2, 0)                  # offset_ps
            b = a + e.get(3, 0)                      # duration_ps
            out.append((op_names.get(e.get(1, 0), ""), a // 1000, b // 1000))
    return out


def device_ops(xspace: bytes) -> Dict[str, List[Tuple[str, int, int]]]:
    """{device plane name: [(op_name, start_ns, end_ns)]} of a serialized
    XSpace's ``XLA Ops`` lines."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:                                   # XSpace.planes
            continue
        name = next((bytes(v).decode() for g, v in _fields(plane)
                     if g == 2), "")
        if name.startswith(trace.DEVICE_PREFIX):
            out[name] = _device_ops(plane)
    return out


def _scoped(events: List[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """(scope, start, end): each op's own scope, else its innermost
    enclosing op's."""
    out, stack = [], []                  # stack of (end, scope)
    for op_name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        scope = scope_of(op_name) or (stack[-1][1] if stack else "")
        stack.append((b, scope))
        out.append((scope, a, b))
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def reduce(xspace: bytes) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    spans: List[Tuple[int, int, str, dict]] = []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((trace.SPAN_PREFIX, SERVE_PREFIX)):
                    spans.append((int(e.start_ns), int(e.end_ns), e.name,
                                  dict(e.stats)))
    devices = device_ops(xspace)
    if not devices:
        raise ValueError("the trace has no device op line "
                         f"({trace.DEVICE_PREFIX}*/{trace.OPS_LINE})")
    win = [s for s in spans if s[2] == trace.WINDOW_SPAN]
    if win:
        lo, hi = win[0][0], win[0][1]
    else:
        allev = [e for evs in devices.values() for e in evs]
        lo, hi = min(e[1] for e in allev), max(e[2] for e in allev)

    per_span: Dict[str, dict] = {}
    for a, b, name, stats in spans:
        if not name.startswith(SERVE_PREFIX) or a < lo or b > hi:
            continue
        d = per_span.setdefault(name, {"s": 0.0, "n": 0, "stats": {}})
        d["s"] += (b - a) * 1e-9
        d["n"] += 1
        for k, v in stats.items():
            if isinstance(v, int):
                d["stats"][k] = d["stats"].get(k, 0) + v

    scopes: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    inner = sorted((s[:3] for s in spans if s[2] != trace.WINDOW_SPAN),
                   key=lambda s: s[1] - s[0])
    for evs in devices.values():
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in _scoped(evs)
                  if b > lo and a < hi]
        for scope, sec in trace._self_times(inside):
            if scope:
                scopes[scope] += sec / len(devices)
        u = trace._union([(a, b) for _, a, b in inside])
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[trace._label(inner, (a + b) // 2)] += (
                    (b - a) * 1e-9 / len(devices))
    return {
        "spans": per_span,
        "scopes": dict(scopes),
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:trace.TOP]],
    }
