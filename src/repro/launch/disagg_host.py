"""Multi-process disaggregated serving: decode-replica hosts in their own
OS processes, fed by a driver over the socket page transport.

Roles:

  decode   — build a decode replica and serve it behind a TCP listener
             (``repro.serve.net.server.PageHost``).  Prints one
             ``READY host=... port=...`` line once listening (``--port 0``
             picks a free port), then handles driver sessions.
  driver   — build prefill replicas + a ``DisaggEngine`` whose decode
             replicas are REMOTE (``--decode-addr host:port[,host:port...]``),
             run a shared-prefix demo request stream through the socket,
             and print the link accounting.  ``--check`` also runs the
             monolithic engine and asserts byte-identical token streams.
  selftest — spawn one decode host as a child process and run the driver
             against it with ``--check``: the two-process smoke test CI
             runs (exit code 0 = streams identical across the socket).

Both processes must be launched with the SAME model/codec/geometry/seed
flags: the hello handshake exchanges a config fingerprint and refuses the
session otherwise (params are re-derived deterministically from the seed on
each side, which is what makes cross-process streams byte-identical).

    PYTHONPATH=src python -m repro.launch.disagg_host --role decode \
        --model tiny-bench --codec on --port 7070
    PYTHONPATH=src python -m repro.launch.disagg_host --role driver \
        --model tiny-bench --codec on --decode-addr 127.0.0.1:7070 --check
    PYTHONPATH=src python -m repro.launch.disagg_host --selftest
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import queue
import socket
import subprocess
import sys
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np


def tiny_bench_config():
    """The tiny dense model the serving bench uses (``benchmarks/run.py``)
    — small enough that two engine-building processes fit a CI runner,
    real enough to exercise pages/rings/dedup end to end."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name="bench", family="dense", n_layers=2, d_model=64,
                       n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=512,
                       head_dim=16)


def build_cfg_run(args):
    """(cfg, run) from the shared model flags — MUST be deterministic in
    the flags alone, both processes call it."""
    from repro.configs import get_config, make_reduced
    from repro.configs.base import RunConfig
    from repro.core.collectives import CodecConfig
    if args.model == "tiny-bench":
        cfg = tiny_bench_config()
    else:
        cfg = make_reduced(get_config(args.model), tp=args.tp)
    codec = (CodecConfig(cache_block=args.cache_block) if args.codec == "on"
             else dataclasses.replace(CodecConfig.off(),
                                      cache_block=args.cache_block))
    codec = dataclasses.replace(codec, decode_backend=args.decode_backend,
                                weight_backend=args.weight_backend)
    return cfg, RunConfig(codec=codec)


def _fingerprint(args, cfg, run) -> bytes:
    from repro.serve.net.framing import config_fingerprint
    return config_fingerprint(cfg, run.codec, args.tp, args.slots,
                              args.max_len, args.seed, eos_id=args.eos_id)


def demo_requests(cfg, args) -> List:
    """Deterministic shared-prefix request mix (duplicates + a fork,
    staggered budgets) sized to the --max-len pool."""
    from repro.serve import Request
    rng = np.random.default_rng(args.seed)
    v = cfg.vocab_size
    plen = min(args.prompt_len, args.max_len - 2 * args.new_tokens)
    plen = max(plen, args.tp)
    base_a = rng.integers(0, v, (plen,)).astype(np.int32)
    base_b = rng.integers(0, v, (max(args.tp, plen * 2 // 3),)
                          ).astype(np.int32)
    forked = np.concatenate([base_a[:plen * 2 // 3],
                             rng.integers(0, v, (plen - plen * 2 // 3,)
                                          ).astype(np.int32)])
    prompts = [base_a, base_b, base_a, forked]
    return [Request(uid=i, prompt=prompts[i % len(prompts)],
                    max_new_tokens=args.new_tokens * (2 if i % 2 == 0
                                                      else 1))
            for i in range(args.requests)]


# ---------------------------------------------------------------------------
# roles
# ---------------------------------------------------------------------------


def run_decode_host(args) -> int:
    from repro.serve import DecodeReplica, PageHost, ServeEngine
    cfg, run = build_cfg_run(args)
    eng = ServeEngine(cfg, run, tp=args.tp, n_slots=args.slots,
                      max_len=args.max_len, seed=args.seed,
                      eos_id=args.eos_id, store_pages=args.store_pages,
                      compress_weights=args.compress_weights)
    host = PageHost(DecodeReplica(eng), _fingerprint(args, cfg, run),
                    max_store_pages=args.store_pages)
    listener = socket.create_server((args.host, args.port))
    actual = listener.getsockname()[1]
    print(f"READY host={args.host} port={actual}", flush=True)
    try:
        host.serve_forever(listener, once=args.once)
    finally:
        listener.close()
    return 0


def run_driver(args) -> int:
    from repro.serve import DisaggEngine, ServeEngine, SocketTransport
    from repro.serve.disagg import format_disagg_stats
    from repro.serve.telemetry import Tracer
    cfg, run = build_cfg_run(args)
    addrs = [a for a in args.decode_addr.split(",") if a]
    transport = SocketTransport()
    tracer = Tracer(enabled=args.trace_out is not None)
    eng = DisaggEngine(cfg, run, tp=args.tp,
                       n_prefill=args.prefill_replicas,
                       n_slots=args.slots, max_len=args.max_len,
                       seed=args.seed, eos_id=args.eos_id,
                       transport=transport, streaming=args.streaming,
                       decode_addrs=addrs, store_pages=args.store_pages,
                       compress_weights=args.compress_weights,
                       tracer=tracer)
    reqs = demo_requests(cfg, args)
    results, st = eng.run(reqs)
    # fleet metrics fold the remote replicas' METRICS RPC snapshots, so
    # query them BEFORE the session closes
    snap = eng.metrics_snapshot() if args.metrics_json else None
    transport.close()
    if args.trace_out:
        tracer.write(args.trace_out)
        print(f"[disagg_host] trace -> {args.trace_out} "
              f"({len(tracer.events)} spans)")
    if snap is not None:
        import json
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=1)
        print(f"[disagg_host] metrics -> {args.metrics_json}")
    print("[disagg_host] socket:", format_disagg_stats(st))
    if args.check:
        mono = ServeEngine(cfg, run, tp=args.tp, n_slots=args.slots,
                           max_len=args.max_len, seed=args.seed,
                           eos_id=args.eos_id)
        res_m, _ = mono.run(demo_requests(cfg, args))
        for x, y in zip(res_m, results):
            if x.tokens != y.tokens or x.stop_reason != y.stop_reason:
                print(f"[disagg_host] STREAM MISMATCH uid={x.uid}: "
                      f"mono={x.tokens} socket={y.tokens}")
                return 1
        print(f"[disagg_host] check ok: {len(results)} streams "
              "byte-identical to the monolithic engine across the socket")
    return 0


# ---------------------------------------------------------------------------
# child-process helper (shared by --selftest, the bench socket scenario,
# and tests/test_net.py)
# ---------------------------------------------------------------------------


def spawn_decode_host(model_args: Sequence[str], *, tp: int = 1,
                      timeout: float = 240.0
                      ) -> Tuple[subprocess.Popen, int]:
    """Start ``--role decode --port 0 --once`` as a child process with the
    given model flags; returns ``(proc, port)`` once it prints READY.
    Kills the child and raises on startup failure.

    A TPU belongs to the first process that touches JAX: when this process
    already holds one, a child that needs it would fail or hang on the
    TPU library's lock, so that case is refused up front."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() == "tpu":
        raise RuntimeError(
            "spawn_decode_host: this process already holds the TPU, so a "
            "decode-host child could not reach it; run the decode host as "
            "its own process (--role decode) and the driver elsewhere, or "
            "serve both in one process (repro.serve.DisaggEngine)")
    env = dict(os.environ)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    if tp > 1 and "XLA_FLAGS" not in env:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={max(tp, 8)}"
    cmd = [sys.executable, "-m", "repro.launch.disagg_host",
           "--role", "decode", "--port", "0", "--once", *model_args]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    # a reader thread enforces the timeout even while blocked on a silent
    # child, and keeps draining after READY so the child never blocks on a
    # full stdout pipe
    out_q: "queue.Queue[Optional[str]]" = queue.Queue()

    def _reader():
        for line in proc.stdout:
            out_q.put(line)
        out_q.put(None)

    threading.Thread(target=_reader, daemon=True).start()
    port = None
    lines: List[str] = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            line = out_q.get(timeout=min(1.0, max(deadline - time.time(),
                                                  0.01)))
        except queue.Empty:
            continue
        if line is None:
            break                        # child died before READY
        lines.append(line)
        if line.startswith("READY "):
            port = int(line.split("port=")[1])
            break
    if port is None:
        proc.kill()
        raise RuntimeError("decode host failed to start:\n"
                           + "".join(lines[-30:]))
    return proc, port


def run_selftest(args) -> int:
    model_args = ["--model", args.model, "--codec", args.codec,
                  "--cache-block", str(args.cache_block),
                  "--tp", str(args.tp), "--slots", str(args.slots),
                  "--max-len", str(args.max_len), "--seed", str(args.seed),
                  "--decode-backend", args.decode_backend,
                  "--weight-backend", args.weight_backend,
                  "--store-pages", str(args.store_pages)]
    if args.compress_weights:
        model_args += ["--compress-weights"]
    if args.eos_id is not None:
        model_args += ["--eos-id", str(args.eos_id)]
    proc, port = spawn_decode_host(model_args, tp=args.tp)
    try:
        args.decode_addr = f"127.0.0.1:{port}"
        args.check = True
        return run_driver(args)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", default=None,
                    choices=["decode", "driver"],
                    help="decode: serve a replica behind a TCP port; "
                         "driver: run requests through remote replicas")
    ap.add_argument("--selftest", action="store_true",
                    help="spawn one decode child + run the driver with "
                         "--check (the two-process smoke test)")
    # shared model/geometry flags — MUST match across processes (the hello
    # handshake enforces it via a config fingerprint)
    ap.add_argument("--model", default="tiny-bench",
                    help="'tiny-bench' or a named arch (reduced)")
    ap.add_argument("--codec", default="on", choices=["on", "off"])
    ap.add_argument("--cache-block", type=int, default=8)
    ap.add_argument("--decode-backend", default="jax",
                    choices=["auto", "pallas", "interpret", "jax"])
    ap.add_argument("--compress-weights", action="store_true",
                    help="serve from the LEXI-packed at-rest weight store "
                         "(both replica kinds; token streams unchanged)")
    # default "auto" (NOT "jax" like --decode-backend): weight_backend is
    # part of the codec repr the config fingerprint hashes, and external
    # drivers (tests, bench) build codecs with the "auto" default
    ap.add_argument("--weight-backend", default="auto",
                    choices=["auto", "pallas", "interpret", "jax"])
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--eos-id", type=int, default=None)
    # decode-host flags
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (printed on the READY line)")
    ap.add_argument("--once", action="store_true",
                    help="exit after the first driver session ends")
    ap.add_argument("--store-pages", type=int, default=4096,
                    help="LRU cap (pages) for the content-addressed "
                         "stores: the transport digest store AND the "
                         "engine PageCache warm tier")
    # driver flags
    ap.add_argument("--decode-addr", default=None,
                    help="comma-separated host:port decode hosts")
    ap.add_argument("--prefill-replicas", type=int, default=1)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--streaming", action="store_true", default=True,
                    help="stream full pages during admission (default)")
    ap.add_argument("--no-streaming", dest="streaming",
                    action="store_false")
    ap.add_argument("--check", action="store_true",
                    help="driver: also run the monolithic engine and "
                         "assert identical token streams")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="driver: write a Chrome trace-event JSON of the "
                         "request lifecycle spans here")
    ap.add_argument("--metrics-json", type=str, default=None,
                    help="driver: write the fleet-merged metrics snapshot "
                         "(local registries + per-host METRICS RPC) here")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    print(f"[host] devices: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)
    if args.selftest:
        return run_selftest(args)
    if args.role == "decode":
        return run_decode_host(args)
    if args.role == "driver":
        if not args.decode_addr:
            ap.error("--role driver needs --decode-addr")
        return run_driver(args)
    ap.error("pick --role decode|driver or --selftest")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
