"""The program's own admission counters measure what the harness counts
from outside: over the same CPU window, the registry's
``serve.replay_tokens`` and ``serve.prompt_tokens_admitted`` move by the
``Record``'s ``replay_tokens`` and ``prompt_tokens`` (what
``replay_token_share`` reads)."""

from bench import harness, traffic
from bench.tests.conftest import tiny_mix

SEED = 2**31 + 91


def test_registry_counts_what_the_record_counts(conf):
    eng = harness.build_engine(conf, SEED)
    eng.max_fuse_steps = 2           # fewer programs to compile on the CPU
    mix = tiny_mix()
    mix["prompt_tokens"] = {"lo": 17, "hi": 31}   # every prompt replays
    stream = traffic.Traffic(mix, conf["vocab_size"], SEED)
    drv = harness.Driver(eng, stream, harness.CompileCounter())
    for _ in range(2):               # admissions before the window too
        drv.one_pass()
    keys = ("serve.replay_tokens", "serve.prompt_tokens_admitted")
    before = [eng.sync_metrics(drv.ls).value(k) for k in keys]
    rec = drv.window(1.0)
    after = [eng.sync_metrics(drv.ls).value(k) for k in keys]
    assert before[0] > 0 and rec.replay_tokens > 0
    assert [a - b for a, b in zip(after, before)] == [rec.replay_tokens,
                                                       rec.prompt_tokens]
