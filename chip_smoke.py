#!/usr/bin/env python3
"""Smoke run of the LEXI serving path on a TPU.

Drives ``repro.serve.ServeEngine`` continuous batching — the engine that
``python -m repro.launch.serve --continuous`` builds — end to end at a
published model width with every LEXI plane on (block-compressed KV pages,
packed weights served through the fused kernels), and checks the results
by the repository's own means.  Everything runs in this one process: a
chip belongs to the process that first touches JAX.

    python3 chip_smoke.py             # one chip:  qwen1.5-1.8b, tp=1
    python3 chip_smoke.py --chips 4   # four chips: codeqwen1.5-7b, tp=4

One chip: backends ``auto`` must resolve to ``pallas``; 8 seeded requests
(512/1024-token prompts, 32/64 new tokens) run to completion on 4 slots
with the cache codec on and off (streams must be identical — the codec is
lossless), and the first decode step's logits of the served path are
compared with the same engine on the pure-JAX backends and raw weights.
Four chips: only the tensor-parallel path — codeqwen1.5-7b (too large for
one chip) at tp=4, codec full against the cache and activation codecs off
(both on the same packed weights), per-device memory, and a check that no
sharded parameter leaf sits whole on one device.

Weights come from ``init_params`` with a fixed seed and prompts are seeded
random tokens.  Lines before the last are smoke output, not benchmark
numbers (they include compilation).  The last line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failure
exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# compiled-path vs reference tolerance on first-decode-step logits: the
# kernels accumulate attention block by block in f32 and the pure-JAX path
# lets XLA order its reductions, so agreement is close but not bitwise
LOGIT_ATOL = 0.05
LOGIT_RTOL = 0.02


def log(*a):
    print("[smoke]", *a, flush=True)


def check(ok, what):
    """A failed smoke check raises (unlike ``assert``, never compiled out)."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU present (JAX sees "
              f"{devices[0].platform!r} devices); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log("compile cache:", enable_compile_cache())

    compile_s = [0.0]

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    used = devices[:args.chips]
    log("devices:", [(d.platform, d.device_kind, d.id) for d in used])
    if args.chips == 1:
        one_chip(args.seed, compile_s)
    else:
        four_chips(args.seed, compile_s)
    print(json.dumps({"ok": True, "device": {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used)}}))
    return 0


class _Laps:
    """Wall and backend-compile seconds per smoke phase."""

    def __init__(self, compile_s):
        self.compile_s = compile_s
        self.t, self.c = time.perf_counter(), compile_s[0]

    def __call__(self, what):
        t, c = time.perf_counter(), self.compile_s[0]
        log(f"{what}: {t - self.t:.1f} s wall, {c - self.c:.1f} s of it "
            "backend compile")
        self.t, self.c = t, c


def _requests(vocab, seed, lens=(1024, 512, 512, 1024, 512, 1024, 1024, 512),
              new=(32,) * 4 + (64,) * 4):
    """Seeded prompts at admission buckets (no replay tail) with budgets
    that keep each wave of four requests in lockstep."""
    import numpy as np
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, vocab, (n,)
                                               ).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(zip(lens,
                                                                    new))]


def _engine(cfg, codec, *, tp, params=None, compress_weights, seed):
    from repro.configs.base import RunConfig
    from repro.serve.scheduler import ServeEngine
    return ServeEngine(cfg, RunConfig(codec=codec, seed=seed), tp=tp,
                       n_slots=4, max_len=2048, params=params, seed=seed,
                       compress_weights=compress_weights,
                       max_fuse_steps=1)


def _serve(name, eng, reqs, compile_s):
    import jax
    c0, t0 = compile_s[0], time.perf_counter()
    res, st = eng.run(reqs)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in res)
    log(f"{name}: {len(res)}/{len(reqs)} requests finished, {toks} tokens "
        f"served in {wall:.1f} s wall (compile {compile_s[0] - c0:.1f} s "
        "included)")
    log(f"{name}: decode_backend={st.decode_backend} "
        f"weight_backend={eng.weight_backend} "
        f"peak kv pages stored/raw bytes={st.peak_cache_bytes}/"
        f"{st.peak_cache_raw_bytes} "
        f"weights stored/raw bytes={eng._weight_bytes[0]}/"
        f"{eng._weight_bytes[1]}")
    check(len(res) == len(reqs), f"{name}: {len(res)} results")
    for r, q in zip(res, reqs):
        check(len(r.tokens) == q.max_new_tokens,
              f"{name}: request {r.uid} emitted {len(r.tokens)} tokens")
    mem = [(d.id, d.device_kind,
            (d.memory_stats() or {}).get("peak_bytes_in_use"))
           for d in jax.devices()[:eng.tp]]
    log(f"{name}: memory_stats peak_bytes_in_use so far per device {mem}")
    _drop(eng)
    return [r.tokens for r in res]


def _drop(eng):
    """Release an engine's device state and params now (its jitted
    closures may outlive the object)."""
    import gc
    eng.state = eng.params = None
    gc.collect()


def one_chip(seed, compile_s):
    import dataclasses
    import gc

    import numpy as np
    from repro.configs import get_config
    from repro.core.collectives import CodecConfig
    from repro.kernels import ops

    cfg = get_config("qwen1.5-1.8b")
    served = CodecConfig(cache_block=256)
    be = (ops.resolve_decode_backend(served),
          ops.resolve_weight_backend(served))
    log(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.head_dim} kv_heads={cfg.n_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}; tp=1, cache_block="
        f"{served.cache_block}")
    log(f"resolved backends: decode={be[0]} weight={be[1]}")
    check(be == ("pallas", "pallas"), f"backends resolved to {be}")
    reqs = _requests(cfg.vocab_size, seed)
    lap = _Laps(compile_s)

    # (b) reference: the same engine on the pure-JAX backends, raw weights;
    # two 512-token prompts (one admission program, shared with serving)
    probe_reqs, n = reqs[1:3], 2
    ref_codec = dataclasses.replace(served, decode_backend="jax",
                                    weight_backend="jax")
    ref = _engine(cfg, ref_codec, tp=1, compress_weights=False, seed=seed)
    raw_params = ref.params
    lap("reference engine built (params initialised on the chip)")
    want = ref.probe_logits(probe_reqs)[:n]
    _drop(ref)
    lap("reference engine: admission + first decode step logits")
    probe = _engine(cfg, served, tp=1, params=raw_params,
                    compress_weights=True, seed=seed)
    packed = probe.params
    lap("served engine built (weights packed on the chip)")
    got = probe.probe_logits(probe_reqs)[:n]
    _drop(probe)
    del raw_params
    gc.collect()
    lap("served engine: admission + first decode step logits")
    diff = float(np.max(np.abs(got - want)))
    bound = LOGIT_ATOL + LOGIT_RTOL * float(np.max(np.abs(want)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"first decode step logits vs pure-JAX/raw-weight engine: max abs "
        f"diff {diff:.4g} (bound {bound:.4g} = {LOGIT_ATOL} + {LOGIT_RTOL}"
        f" * max|logit|), argmax agreement {agree:.2f}")
    check(np.all(np.isfinite(got)), "non-finite served logits")
    check(diff <= bound, f"logit diff {diff} > {bound}")

    # (a) the lossless cache codec: on and off serve identical streams
    on = _serve("codec on ", _engine(cfg, served, tp=1, params=packed,
                                     compress_weights=True, seed=seed),
                reqs, compile_s)
    off_codec = dataclasses.replace(served, cache=False)
    off = _serve("codec off", _engine(cfg, off_codec, tp=1, params=packed,
                                      compress_weights=True, seed=seed),
                 reqs, compile_s)
    check(on == off, "cache codec on/off token streams differ")
    lap("served 8 requests with the cache codec on, then off")
    log("token streams identical with the cache codec on and off")
    log(f"total backend compile seconds: {compile_s[0]:.1f}")


def four_chips(seed, compile_s):
    import jax
    from repro.configs import get_config
    from repro.core.collectives import CodecConfig

    cfg = get_config("codeqwen1.5-7b")
    log(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.head_dim} kv_heads={cfg.n_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}; tp=4")
    # one wave of four 512-token prompts: the tensor-parallel path and its
    # comparison, at the least chip time
    reqs = _requests(cfg.vocab_size, seed, lens=(512,) * 4, new=(32,) * 4)
    lap = _Laps(compile_s)
    full = CodecConfig(cache_block=256)
    eng = _engine(cfg, full, tp=4, compress_weights=True, seed=seed)
    lap("engine built (params initialised and packed on the mesh)")
    whole = []
    for path, a in jax.tree_util.tree_flatten_with_path(eng.params)[0]:
        sh = a.sharding
        spec = getattr(sh, "spec", ())
        if len(sh.device_set) != 4 or (
                any(x is not None for x in spec)
                and sh.shard_shape(a.shape) == a.shape):
            whole.append(jax.tree_util.keystr(path))
    log(f"parameter leaves not laid out across the 4 devices as their "
        f"specs say: {whole}")
    check(not whole, f"misplaced parameter leaves {whole}")
    # codec off keeps the same packed weights: the weight plane changes
    # only the f32 summation order of each matmul (checked against the
    # pure-JAX engine on one chip), which greedy streams cannot tolerate;
    # the cache and activation codecs must not change a single token
    packed = eng.params
    on = _serve("codec full", eng, reqs, compile_s)
    off = _serve("codec off ", _engine(cfg, CodecConfig.off(), tp=4,
                                       params=packed, compress_weights=True,
                                       seed=seed),
                 reqs, compile_s)
    check(on == off, "codec full/off token streams differ")
    lap("served 4 requests with codec full, then codec off")
    log("token streams identical with codec full and codec off")
    log(f"total backend compile seconds: {compile_s[0]:.1f}")


if __name__ == "__main__":
    sys.exit(main())
