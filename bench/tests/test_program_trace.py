"""The reduction of the program's own spans and scopes
(``bench/program_trace.py``) on small traces: ``serve.*`` spans with their
stats, device self time per ``lexi.*`` scope (a conditional that holds the
scope's ops is not counted twice), and idle gaps labelled by the innermost
open span of either prefix."""

from pathlib import Path

import pytest

from bench import program_trace, trace

TESTDATA = Path(__file__).resolve().parent.parent / "testdata"

# device ops (ns, relative to 1000): the flush's conditional [0, 10)
# holding a sort [1, 4) that XLA left without an op_name and a scatter
# [4, 9), the attention kernel [12, 15) (its op_name by reference), a fusion
# [20, 22) with none; host: bench.window [0, 25), bench.decode [0, 16)
# around serve.decode_window [0, 15) and serve.token_walk [15, 16),
# bench.admit [16, 25) around serve.admit_phase [16, 25), which holds
# serve.replay_window [17, 23) and serve.page_upkeep [23, 24); serve.finish
# [24, 27) ends outside the window
FLUSH = "jit(decode)/while/body/lexi.ring_flush/cond"
HAND = f"""
planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 name: "XLA Ops"
  timestamp_ns: 1000
  events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000 }}
  events {{ metadata_id: 2 offset_ps: 1000 duration_ps: 3000 }}
  events {{ metadata_id: 3 offset_ps: 4000 duration_ps: 5000 }}
  events {{ metadata_id: 4 offset_ps: 12000 duration_ps: 3000 }}
  events {{ metadata_id: 5 offset_ps: 20000 duration_ps: 2000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "cond.1"
    stats {{ metadata_id: 1 str_value: "{FLUSH}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "sort.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.3"
    stats {{ metadata_id: 1 str_value: "{FLUSH}/branch_1_fun/scatter" }} }}
  }}
  event_metadata {{ key: 4 value {{ id: 4 name: "decode_attend_paged.4"
    stats {{ metadata_id: 1 ref_value: 2 }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "fusion.5" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2
    name: "jit(decode)/lexi.attend/decode_attend_paged" }} }} }}
planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: "python"
  timestamp_ns: 1000
  events {{ metadata_id: 1 offset_ps: 0 duration_ps: 25000 }}
  events {{ metadata_id: 2 offset_ps: 0 duration_ps: 16000 }}
  events {{ metadata_id: 3 offset_ps: 0 duration_ps: 15000
    stats {{ metadata_id: 1 int64_value: 2 }}
    stats {{ metadata_id: 2 int64_value: 1 }}
    stats {{ metadata_id: 3 int64_value: 8 }} }}
  events {{ metadata_id: 4 offset_ps: 15000 duration_ps: 1000 }}
  events {{ metadata_id: 5 offset_ps: 16000 duration_ps: 9000 }}
  events {{ metadata_id: 6 offset_ps: 16000 duration_ps: 9000 }}
  events {{ metadata_id: 7 offset_ps: 17000 duration_ps: 6000
    stats {{ metadata_id: 1 int64_value: 1 }}
    stats {{ metadata_id: 2 int64_value: 0 }}
    stats {{ metadata_id: 3 int64_value: 8 }} }}
  events {{ metadata_id: 8 offset_ps: 23000 duration_ps: 1000
    stats {{ metadata_id: 4 str_value: "free_slots" }} }}
  events {{ metadata_id: 9 offset_ps: 24000 duration_ps: 3000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.decode" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "serve.decode_window" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "serve.token_walk" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "bench.admit" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "serve.admit_phase" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "serve.replay_window" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "serve.page_upkeep" }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "serve.finish" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "steps" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "rings_filled" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "rings_compressed" }} }}
  stat_metadata {{ key: 4 value {{ id: 4 name: "what" }} }} }}
"""


def _profile(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def _xspace(text):
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_spans_scopes_and_gap_labels_by_hand():
    red = program_trace.reduce(_xspace(HAND))
    spans = red["spans"]
    assert set(spans) == {"serve.decode_window", "serve.token_walk",
                          "serve.admit_phase", "serve.replay_window",
                          "serve.page_upkeep"}       # finish leaves it
    dw = spans["serve.decode_window"]
    assert dw["s"] == pytest.approx(15e-9) and dw["n"] == 1
    assert dw["stats"] == {"steps": 2, "rings_filled": 1,
                           "rings_compressed": 8}
    assert spans["serve.replay_window"]["stats"]["rings_filled"] == 0
    assert spans["serve.page_upkeep"]["stats"] == {}   # `what` is a name
    # the sort inherits the conditional's scope; self time counts the
    # conditional's 10 ns once
    assert red["scopes"] == {"lexi.ring_flush": pytest.approx(10e-9),
                             "lexi.attend": pytest.approx(3e-9)}
    # idle [10, 12) in decode_window, [15, 20) mid 17 in replay_window,
    # [22, 25) mid 23 in page_upkeep
    assert dict(red["idle_gaps"]) == {
        "serve.decode_window": pytest.approx(2e-9),
        "serve.replay_window": pytest.approx(5e-9),
        "serve.page_upkeep": pytest.approx(3e-9)}
    # trace.reduce, which knows only bench.* spans, sees the same idle time
    old = trace.reduce(_profile(HAND))
    assert dict(old["idle_gaps"]) == {"bench.decode": pytest.approx(2e-9),
                                      "bench.admit": pytest.approx(8e-9)}


def test_device_ops_read_op_names_from_the_event_metadata():
    (ops,) = program_trace.device_ops(_xspace(HAND)).values()
    assert ops == [(FLUSH, 1000, 1010), ("", 1001, 1004),
                   (f"{FLUSH}/branch_1_fun/scatter", 1004, 1009),
                   ("jit(decode)/lexi.attend/decode_attend_paged", 1012,
                    1015), ("", 1020, 1022)]


@pytest.mark.parametrize("op_name, scope", [
    (f"{FLUSH}/sort", "lexi.ring_flush"),
    ("jit(f)/lexi.outer/lexi.inner/add", "lexi.inner"),
    ("jit(decode)/while/body/dot_general", ""),
    ("", "")])
def test_scope_of(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


def test_recorded_excerpt_without_program_spans():
    """The first recorded excerpt has neither ``serve.*`` spans nor
    ``tf_op`` stats: no spans, no scopes, and ``trace.reduce``'s gaps."""
    text = (TESTDATA / "chat_trace_excerpt.pbtxt").read_text()
    red = program_trace.reduce(_xspace(text))
    assert red["spans"] == {} and red["scopes"] == {}
    old = trace.reduce(_profile(text))["idle_gaps"]
    assert [k for k, _ in red["idle_gaps"]] == [k for k, _ in old]
    assert [v for _, v in red["idle_gaps"]] == pytest.approx(
        [v for _, v in old], abs=1e-9)


def test_recorded_flush_excerpt():
    """240 ms of a qwen1.5-1.8b.chat trace on one TPU v5e (8 slots, seed
    2147484011): one layer's ring flush, a conditional whose branch holds
    ops that XLA left without an ``op_name`` (the scatters it turned into
    sorts), inside a ``serve.decode_window`` that outlasts the excerpt."""
    import re

    from bench.metrics import decode_attend_paged_roofline as attend
    text = (TESTDATA / "chat_flush_excerpt.pbtxt").read_text()
    old = trace.reduce(_profile(text))
    red = program_trace.reduce(_xspace(text))
    idle = sum(v for _, v in red["idle_gaps"])
    assert old["busy_s"] + idle == pytest.approx(old["window_s"])
    assert red["spans"] == {}            # no serve.* span lies inside
    # the flush's conditional, read straight from the file, holds the
    # scope's whole device time, and most of it is in ops with no op_name
    device = text.split("planes {")[1]
    (mid,) = re.findall(r'key: (\d+)\s+value \{\s+id: \d+\s+name: "%cond',
                        device)
    (dur,) = re.findall(rf"metadata_id: {mid}\s+(?:offset_ps: \d+\s+)?"
                        r"duration_ps: (\d+)", device)
    assert red["scopes"] == {"lexi.ring_flush": pytest.approx(
        int(dur) * 1e-12)}
    named = sum(sec for op, (sec, _) in old["ops"].items()
                if op.startswith("%fusion.2 "))
    assert named < 0.5 * red["scopes"]["lexi.ring_flush"]
    # the paged attention kernel is found by its name
    assert trace.kernel_time(old, attend.is_kernel)[1] >= 1
    assert any(op.startswith("%decode_attend_paged.") for op in old["ops"])
