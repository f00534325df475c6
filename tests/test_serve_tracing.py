"""The program's own readings of its serving loop: the ring-flush counter
(``models/cache.py:flush_work``) against the pages the device allocated,
the admission counters, the ``serve.*`` spans as profiler annotations with
their counts as stats, and the ``lexi.ring_flush`` scope in the compiled
decode program."""

import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, RunConfig
from repro.core.collectives import CodecConfig
from repro.models.cache import flush_work
from repro.serve import Request, ServeEngine
from repro.serve.telemetry import ENGINE_LANE, Tracer

CFG = ModelConfig(name="t1", family="dense", n_layers=2, d_model=64,
                  n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=500,
                  head_dim=16)
BLK, SLOTS, MAXLEN = 4, 3, 64
COUNTERS = ("cache.rings_filled", "cache.rings_compressed",
            "serve.replay_tokens", "serve.prompt_tokens_admitted")


def _engine(tracer=None, **kw):
    run = RunConfig(codec=dataclasses.replace(
        CodecConfig(cache_block=BLK), decode_backend="jax"))
    return ServeEngine(CFG, run, tp=1, n_slots=SLOTS, max_len=MAXLEN,
                       seed=3, tracer=tracer, **kw)


def _requests():
    rng = np.random.default_rng(5)
    lens, outs = (9, 14, 6, 11, 17), (13, 9, 15, 7, 10)
    return [Request(uid=i, prompt=rng.integers(0, 500, (n,)).astype(
        np.int32), max_new_tokens=m)
        for i, (n, m) in enumerate(zip(lens, outs))]


def _watch_dispatches(eng, log):
    """Record (page table, lengths) of the paged state before and after
    every decode and replay dispatch."""
    for name in ("_decode_for", "_replay_for"):
        make = getattr(eng, name)

        def watched(k, make=make):
            fn = make(k)

            def run(pp, st, *a):
                out = fn(pp, st, *a)
                log.append((np.asarray(st.kv.page_table),
                            np.asarray(out[1].kv.page_table),
                            np.asarray(st.lengths)))
                return out
            return run
        setattr(eng, name, watched)


@pytest.mark.parametrize("fuse", [1, 32], ids=["one_step", "fused"])
def test_flush_counters_match_the_pages_the_device_allocated(fuse):
    eng = _engine(max_fuse_steps=fuse)
    log = []
    _watch_dispatches(eng, log)
    reqs = _requests()
    results, _ = eng.run(reqs)
    assert len(results) == len(reqs)
    got = eng.registry.snapshot()["counters"]
    filled = steps = 0
    for before, after, len0 in log:
        # (tp, L, S, maxp) page tables; every layer flushes together
        new = (after >= 0) & (before < 0)
        assert (new.sum(axis=(0, 2, 3)) == new[:, 0].sum()).all()
        filled += int(new[:, 0].sum())
        # the step of a dispatch that filled slot s's column c (tp = 1)
        _, s, c = np.nonzero(new[0, 0][None])
        steps += len({int((c_ + 1) * BLK - 1 - len0.reshape(-1)[s_])
                      for s_, c_ in zip(s, c)})
    assert filled > 0 and steps > 0
    assert got["cache.rings_filled"] == filled
    assert got["cache.rings_compressed"] == steps * SLOTS
    assert got["serve.prompt_tokens_admitted"] == sum(
        len(r.prompt) for r in reqs)
    assert 0 < got["serve.replay_tokens"] < got[
        "serve.prompt_tokens_admitted"]


def test_flush_work_rule():
    # one step, blk 4, tp 1: slot 0 writes position 3 (fills), slot 1
    # position 5, slot 2 position 7 but inactive
    assert flush_work([3, 5, 7], [True, True, False], 4, 1) == (1, 3)
    assert flush_work([2, 5, 6], [True, True, True], 4, 1) == (0, 0)
    # summed over steps: (steps, S)
    lens = np.array([[3, 7], [4, 8]])
    assert flush_work(lens, np.ones_like(lens, bool), 4, 1) == (2, 2)
    # tp 2: position p is written by shard p % 2 at its local index p // 2;
    # positions 6 and 7 fill (local 3) on shards 0 and 1, position 14
    # (local 7) on shard 0: two shards flush all three rings
    assert flush_work([6, 7, 14], [True] * 3, 4, 2) == (3, 6)
    assert flush_work([6, 1, 14], [True] * 3, 4, 2) == (2, 3)


def test_serve_cli_metrics_json_holds_the_counters(tmp_path):
    import importlib.util
    import json
    import pathlib

    from repro.launch import serve as serve_cli
    metrics, spans = tmp_path / "m.json", tmp_path / "t.json"
    assert serve_cli.main([
        "--arch", "qwen3-4b", "--reduced", "--mesh", "1x1",
        "--prompt-len", "16", "--new-tokens", "6", "--continuous",
        "--requests", "3", "--slots", "2", "--metrics-json", str(metrics),
        "--trace-out", str(spans)]) == 0
    got = json.loads(metrics.read_text())["counters"]
    assert set(COUNTERS) <= set(got)
    assert 0 <= got["serve.replay_tokens"] < got[
        "serve.prompt_tokens_admitted"]
    path = (pathlib.Path(__file__).resolve().parents[1] / "scripts"
            / "trace_summary.py")
    spec = importlib.util.spec_from_file_location("trace_summary", path)
    summary = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(summary)
    assert summary.main([str(spans), "--check"]) == 0


def test_span_reads_one_clock_and_emits_when_enabled():
    for enabled in (False, True):
        tr = Tracer(enabled)
        with tr.span("replay_window", pid="serve", tid=ENGINE_LANE,
                     steps=4, rings_filled=1) as sp:
            pass
        assert sp.seconds >= 0 and sp.t1 >= sp.t0
        if enabled:
            (ev,) = tr.events
            assert ev["name"] == "replay_window"
            assert ev["args"] == {"steps": 4, "rings_filled": 1}
            assert ev["ts"] == sp.t0 and ev["dur"] == sp.t1 - sp.t0
        else:
            assert tr.events == []


def test_engine_spans_reach_the_profiler_with_their_counts(tmp_path):
    from jax.profiler import ProfileData
    eng = _engine()
    reqs = _requests()
    eng.run(reqs[:1])                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs[1:])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    seen, stat = {}, {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    seen[e.name] = seen.get(e.name, 0) + 1
                    for k, v in e.stats:
                        if k.startswith("rings_") or k == "steps":
                            key = (e.name, k)
                            stat[key] = stat.get(key, 0) + v
    for name in ("admit_phase", "admit_batch", "replay_window",
                 "decode_window", "token_walk", "finish", "page_upkeep"):
        assert seen.get(f"serve.{name}"), name
    got = eng.registry.snapshot()["counters"]
    for k in ("rings_filled", "rings_compressed"):
        assert (stat.get(("serve.replay_window", k), 0)
                + stat.get(("serve.decode_window", k), 0)) == got[f"cache.{k}"]
    assert stat[("serve.decode_window", "steps")] == got["serve.decode_steps"]


def test_compiled_decode_names_the_flush_sort():
    """The flush's sort (the free-page argsort) and the conditional that
    holds it carry the scope in their op_name."""
    eng = _engine()
    fn = eng._decode_for(1)
    text = fn.lower(eng.params, eng.state,
                    jnp.zeros((SLOTS, 1), jnp.int32)).compile().as_text()
    for op in (" sort(", " conditional("):
        found = [ln for ln in text.splitlines() if op in ln]
        assert found, op
        assert all("/lexi.ring_flush/" in ln for ln in found), found[0][:300]
