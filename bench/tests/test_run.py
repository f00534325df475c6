"""The harness, rehearsed on the CPU at a size a test can hold.

``run_cell`` is driven past the look for a chip.  The driver's loop must
serve the same streams as ``ServeEngine.run``; a sound run must be correct
and print the contract's keys; the fp8 control must turn ``correct``
false through the same comparison (the faults of the served path:
``test_faults.py``)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest
from bench import check, harness, models, peaks, run, traffic
from bench.models.qwen2 import reference, weights
from bench.tests.conftest import TINY_GAP_LIMIT, tiny_mix

SEED = 2**31 + 77                # seeds may exceed 32 bits
PEAKS = peaks.PEAKS["TPU v5 lite"]


def _cell(conf, mix):
    e2e = [{"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"}]
    return harness.Cell("tiny.chat", {"chips": 1}, conf, mix, e2e, [])


def short_fuse(eng):
    eng.max_fuse_steps = 2       # fewer programs to compile on the CPU


def one_bucket_mix():
    mix = tiny_mix()
    mix["prompt_tokens"] = {"lo": 16, "hi": 31}   # one admission bucket
    return mix


def run_tiny(conf, hook=short_fuse, control=None):
    return run.run_cell(_cell(conf, one_bucket_mix()), SEED, 0.5, False,
                        peaks=PEAKS, device={"platform": "cpu"},
                        t_start=time.perf_counter(), control=control,
                        engine_hook=hook)


def test_driver_loop_serves_the_streams_of_engine_run(conf):
    from repro.serve.scheduler import Request
    stream = traffic.Traffic(one_bucket_mix(), conf["vocab_size"], SEED)
    specs = [stream.next() for _ in range(6)]
    eng = harness.build_engine(conf, SEED)
    short_fuse(eng)
    results, _ = eng.run([Request(uid=s.uid, prompt=s.prompt,
                                  max_new_tokens=s.max_new_tokens)
                          for s in specs])
    want = {r.uid: r.tokens for r in results}
    eng.drop_cache()            # the same requests again, from cold

    class Fixed:
        def __init__(self):
            self.n = 0

        def next(self):
            self.n += 1
            return specs[self.n - 1]

    drv = harness.Driver(eng, Fixed(), harness.CompileCounter())
    drv.joined = drv.n_clients = len(specs)     # every client at once
    drv.ready.extend(range(len(specs)))
    drv.per_pass = len(specs)
    drv.one_pass()
    drv.per_pass = 0                            # each client sends once
    while drv.live:
        drv.one_pass()
    got = {u: r.tokens for u, r in drv.every.items()}
    assert got == want


def test_sound_run_is_correct_and_prints_the_contract_keys(conf):
    res = run_tiny(conf)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    gap = res["check"]["max_logit_gap"]
    assert gap["limit"] == TINY_GAP_LIMIT
    assert res["correct"] and gap["value"] <= TINY_GAP_LIMIT


def test_the_fp8_control_in_the_programs_place_is_not_correct(conf):
    res = run_tiny(conf, control="fp8")
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT


def test_reference_agrees_with_the_served_logits(conf):
    from repro.serve.scheduler import Request
    stream = traffic.Traffic(one_bucket_mix(), conf["vocab_size"], SEED)
    specs = [stream.next() for _ in range(3)]
    eng = harness.build_engine(conf, SEED)
    got = eng.probe_logits([Request(uid=s.uid, prompt=s.prompt,
                                    max_new_tokens=4) for s in specs])
    m = weights.Dims.of(conf)
    w = weights.make(m, SEED)
    for i, s in enumerate(specs):
        # the engine's first token, then the next step's logits
        first = int(np.argmax(reference.logits(
            m, w, s.prompt, np.asarray([len(s.prompt) - 1]))[0]))
        seq = np.concatenate([s.prompt, [first]])
        want = reference.logits(m, w, seq, np.asarray([len(seq) - 1]))[0]
        assert np.max(np.abs(got[i] - want)) < 0.02 * np.max(np.abs(want))


def test_sample_holds_the_longest_and_one_per_slot():
    reqs = [harness.Req(uid=i, client=0, prompt=np.zeros(10 + i, np.int32),
                        sent=0.0, slot=i % 4, tokens=[1, 2, 3])
            for i in range(20)]
    a = check.sample(reqs, 5)
    assert a[0].uid == 19
    assert {r.slot for r in a} == {0, 1, 2, 3} and len(a) <= 5
    assert [r.uid for r in a] == [r.uid for r in check.sample(reqs, 5)]
    assert check.sample([], 5) == []


def test_traffic_keeps_its_sizes_across_seeds():
    mix = tiny_mix()
    a, b = traffic.Traffic(mix, 1009, 1), traffic.Traffic(mix, 1009, 2)
    for _ in range(5):
        sa, sb = a.next(), b.next()
        assert len(sa.prompt) == len(sb.prompt)
        assert 8 <= len(sa.prompt) <= 40
        assert sa.max_new_tokens == sb.max_new_tokens
        assert not np.array_equal(sa.prompt, sb.prompt)
    s = traffic.Traffic(mix, 1009, 1).next()
    again = traffic.Traffic(mix, 1009, 1).next()
    assert np.array_equal(again.prompt, s.prompt)


def test_program_weights_are_the_reference_weights_reordered(conf):
    m = weights.Dims.of(conf)
    ref = weights.make(m, SEED)
    prog = weights.program_params(m, 1024, SEED)
    perm = weights.rope_perm(m.head_dim)
    wq = np.asarray(ref["layers"]["wq"], np.float32).reshape(
        m.layers, m.d, m.heads, m.head_dim)
    got = np.asarray(prog["blocks"]["attn"]["wq"], np.float32).reshape(
        m.layers, m.d, m.heads, m.head_dim)
    assert np.array_equal(got, wq[..., perm])
    assert prog["embed"].shape == (1024, m.d)
    assert np.all(np.asarray(prog["lm_head"][:, m.vocab:],
                             np.float32) == 0)
    assert prog["embed"].dtype == jnp.bfloat16


def test_the_family_is_found_by_model_type(conf):
    assert models.family(conf).Dims.of(conf).d == 128
    with pytest.raises(ValueError, match="no model family"):
        models.family(dict(conf, model_type="no_such_family"))
