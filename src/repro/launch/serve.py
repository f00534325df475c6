"""Serving demo: fixed-batch (prefill a prompt batch, decode greedily) or
continuous batching (request stream through the paged-cache ServeEngine),
with LEXI-compressed weights/activations/caches.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --reduced \
        --batch 4 --prompt-len 64 --new-tokens 32 --mesh 1x4
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --continuous --requests 8 --slots 4 --mesh 1x4
    python -m repro.launch.serve --arch qwen1.5-1.8b --no-reduced \
        --continuous --compress-weights          # full width, all devices
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, make_reduced
from repro.configs.base import MeshConfig, RunConfig
from repro.core import collectives as cl
from repro.core.collectives import CodecConfig
from repro.launch.mesh import make_mesh_from_config
from repro.models import lm, params as PM
from repro.serve import engine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shrink the model to a demo size (--no-reduced "
                         "serves the published widths)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL device mesh (default: 1x<all devices>)")
    ap.add_argument("--codec", default="full",
                    choices=["full", "weights", "off"])
    ap.add_argument("--continuous", action="store_true",
                    help="serve a request stream through the "
                         "continuous-batching engine")
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous mode: number of queued requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous mode: decode slots")
    ap.add_argument("--decode-backend", default="auto",
                    choices=["auto", "pallas", "interpret", "jax"],
                    help="decode-attention backend (fused Pallas kernels "
                         "vs pure-JAX scan)")
    ap.add_argument("--compress-weights", action="store_true",
                    help="continuous/disagg: serve from the LEXI-packed "
                         "at-rest weight store (fused JIT decompress+matmul "
                         "on the decode path; token streams are identical)")
    ap.add_argument("--weight-backend", default="auto",
                    choices=["auto", "pallas", "interpret", "jax"],
                    help="how packed weights are multiplied (fused "
                         "decompress_matmul vs exact unpack-then-einsum)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="continuous mode: evict a slot when it emits "
                         "this token id")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="continuous mode: disable prefix-cache page "
                         "sharing between requests")
    ap.add_argument("--disagg", action="store_true",
                    help="serve through disaggregated prefill->decode "
                         "replicas over compressed page transfer")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="--disagg: number of prefill replicas")
    ap.add_argument("--decode-replicas", type=int, default=1,
                    help="--disagg: number of decode replicas")
    ap.add_argument("--streaming", action="store_true",
                    help="--disagg: stream full compressed pages across "
                         "the transfer link as admission fills them "
                         "(prefill-side streaming export); multi-process "
                         "serving lives in repro.launch.disagg_host")
    ap.add_argument("--stop-seq", type=str, default=None,
                    help="continuous/disagg: comma-separated token ids; "
                         "a slot stops when its stream ends with them "
                         "(stop_reason=stop_string)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Chrome trace-event JSON (Perfetto-"
                         "loadable) of the request lifecycle spans here "
                         "(continuous/disagg modes)")
    ap.add_argument("--metrics-json", type=str, default=None,
                    help="write the run's metrics-registry snapshot "
                         "(repro.serve.telemetry) as JSON here")
    args = ap.parse_args(argv)

    devs = jax.devices()
    print(f"[serve] devices: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}")
    d, m = (int(x) for x in (args.mesh or f"1x{len(devs)}").split("x"))
    mesh_cfg = MeshConfig(data=d, model=m, pod=1)
    mesh = make_mesh_from_config(mesh_cfg)
    import dataclasses
    codec = {"full": CodecConfig(cache_block=32),
             "weights": CodecConfig.weights_only(),
             "off": CodecConfig.off()}[args.codec]
    codec = dataclasses.replace(codec, decode_backend=args.decode_backend,
                                weight_backend=args.weight_backend)
    run = RunConfig(codec=codec)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg, tp=m)

    if args.continuous or args.disagg:
        return _serve_continuous(cfg, run, m, args)

    table = lm.lm_table(cfg, mesh_cfg, run)
    dims = lm.lm_fsdp_dims(table)
    params = PM.init_params(table, jax.random.key(run.seed))
    pspecs = PM.param_pspecs(table)
    tp = mesh_cfg.model
    B, S, N = args.batch, args.prompt_len, args.new_tokens
    maxlen = S + N + codec.cache_block
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    extras = {}
    if cfg.frontend == "vision_stub":
        extras["front_embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.n_frontend_tokens, cfg.d_model)),
            jnp.bfloat16)
    if cfg.encdec:
        extras["enc_embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, S, cfg.d_model)), jnp.bfloat16)

    def serve(pp, toks, extra):
        logits, st = engine.prefill(cfg, run, pp, dims, toks, maxlen, tp,
                                    front_embeds=extra.get("front_embeds"),
                                    enc_embeds=extra.get("enc_embeds"))
        outs = []
        tok = engine.greedy_token(cfg, logits, tp)
        for _ in range(N):
            outs.append(tok)
            logits, st = engine.decode_step(cfg, run, pp, dims, st, tok, tp)
            tok = engine.greedy_token(cfg, logits, tp)
        outs.append(tok)
        return jnp.concatenate(outs, axis=1)

    espec = {k: P("data") for k in extras}
    f = jax.jit(cl.shmap(serve, mesh,
                         (pspecs, P("data"), espec), P("data")))
    t0 = time.time()
    out = np.asarray(f(params, prompts, extras))
    dt = time.time() - t0
    print(f"[serve] {B} seqs x ({S} prompt + {N} new) in {dt:.1f}s "
          f"({B * N / dt:.1f} tok/s incl. compile)")
    t0 = time.time()
    out = np.asarray(f(params, prompts, extras))
    dt = time.time() - t0
    n_tok = B * (N + 1)
    print(f"[serve] steady-state: {B * N / dt:.1f} tok/s")
    print(f"[serve] stats: {B} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s), "
          f"decode backend {codec.decode_backend}")
    if args.metrics_json:
        from repro.serve.telemetry import MetricsRegistry
        reg = MetricsRegistry()
        reg.counter("serve.requests").set(B)
        reg.counter("serve.tokens").set(n_tok)
        reg.counter("serve.decode_steps").set(N)
        reg.gauge("serve.wall_s", agg="max").set(dt)
        _write_json(args.metrics_json, reg.snapshot())
        print(f"[serve] metrics -> {args.metrics_json}")
    print("[serve] sample continuations:", out[:2, :12].tolist())
    return 0


def _write_json(path: str, obj) -> None:
    import json
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _serve_continuous(cfg, run, tp: int, args) -> int:
    """Request-stream mode: queue > slots, mixed prompt lengths.  With
    --disagg the stream runs through prefill->decode replicas connected by
    compressed page transfer instead of one monolithic engine."""
    from repro.serve import ServeEngine
    from repro.serve.scheduler import demo_serving_setup, format_stats
    from repro.serve.telemetry import Tracer
    run, max_len, reqs = demo_serving_setup(
        run, cfg.vocab_size, tp, args.prompt_len, args.new_tokens,
        args.requests)
    stops = ([tuple(int(t) for t in args.stop_seq.split(","))]
             if args.stop_seq else None)
    tracer = Tracer(enabled=args.trace_out is not None)
    if args.disagg:
        from repro.serve.disagg import DisaggEngine, format_disagg_stats
        eng = DisaggEngine(cfg, run, tp=tp,
                           n_prefill=args.prefill_replicas,
                           n_decode=args.decode_replicas,
                           n_slots=args.slots, max_len=max_len,
                           seed=run.seed, eos_id=args.eos_id,
                           stop_seqs=stops, streaming=args.streaming,
                           compress_weights=args.compress_weights,
                           tracer=tracer)
        results, st = eng.run(reqs)
        snap = eng.metrics_snapshot()
        print("[serve] disagg:", format_disagg_stats(st))
    else:
        eng = ServeEngine(cfg, run, tp=tp, n_slots=args.slots,
                          max_len=max_len, seed=run.seed,
                          eos_id=args.eos_id, stop_seqs=stops,
                          prefix_sharing=not args.no_prefix_sharing,
                          compress_weights=args.compress_weights,
                          tracer=tracer)
        results, st = eng.run(reqs)
        snap = eng.registry.snapshot()
        print("[serve] continuous:", format_stats(st))
    if args.trace_out:
        tracer.write(args.trace_out)
        print(f"[serve] trace -> {args.trace_out} "
              f"({len(tracer.events)} spans)")
    if args.metrics_json:
        _write_json(args.metrics_json, snap)
        print(f"[serve] metrics -> {args.metrics_json}")
    print("[serve] sample continuations:",
          [(r.tokens[:6], r.stop_reason) for r in results[:2]])
    return 0


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
