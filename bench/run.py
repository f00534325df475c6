#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload qwen1.5-1.8b.chat --seed 7 \\
        --seconds 30 --trace 0

The cell (configuration, traffic mix, metrics) is read from
``BENCHMARK.json``.  Set-up builds the engine with the seed's weights,
compiles or loads every program the mix reaches and lets the clients join
one per pass until the loop compiles nothing; then the window measures for
``--seconds``.  ``--trace 1`` records
a profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.  Afterwards the served tokens are checked against the
plain float32 reference (``bench/check.py``).  ``--control fp8`` puts the
lower-precision reference in the program's place for that comparison, so
that it reads ``correct`` false: a calibration of the limit, never a cell's
run.

The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"check"}``; the last lines of standard error give each number compared
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.  JAX's persistent compilation cache
lives in ``bench/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / "bench" / ".jax_cache"
TRACE_SECONDS = 10          # the profiler traces the window's first passes
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def enable_cache():
    """JAX's persistent compilation cache in the checkout, at a fixed path,
    for every program however fast it compiles; set before JAX is
    imported, in place of any directory the environment names."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def run_cell(cell, seed: int, seconds: float, trace_on: bool, *,
             peaks: dict, device: dict, t_start: float, control=None,
             engine_hook=None) -> dict:
    """One run of ``cell``: set-up, window, metrics, check.  Returns the
    result object.  ``engine_hook(eng)``, when given, is applied to the
    engine before set-up (tests use it to break the timed path)."""
    import jax
    from bench import check, harness, models, trace, traffic

    counter = harness.CompileCounter()
    conf = cell.conf
    eng = harness.build_engine(conf, seed)
    if engine_hook is not None:
        engine_hook(eng)
    stream = traffic.Traffic(cell.mix, conf["vocab_size"], seed)
    drv = harness.Driver(eng, stream, counter)
    warmed = harness.precompile(eng, cell.mix)
    drv.ramp()
    # what set-up left on the heap is not scanned again by the collector
    # inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; programs warmed {warmed}; in set-up "
        f"{counter.compiled} backend compiles ({counter.compile_s:.1f} s), "
        f"{counter.cache_hits} persistent-cache hits")

    tdir = None
    if trace_on:
        tdir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        rec = drv.window(seconds, tdir.name, min(TRACE_SECONDS, seconds))
    else:
        rec = drv.window(seconds)
    gc.unfreeze()
    log(f"compiles_in_window: {rec.compiles} (programs lowered or "
        f"compiled between window open and close)")
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    rec.dims = models.family(conf).Dims.of(conf)
    rec.peaks = peaks
    rec.geometry = harness.geometry(eng)
    reqs = list(drv.every.values())
    tokens = harness.tokens_in_window(rec, reqs)
    e2e = {"tokens_per_s": tokens / rec.window_s, "setup_s": setup_s}
    attempted = sum(1 for r in reqs if r.sent <= rec.t_close and (
        r.finished is None or r.finished > rec.t_open))
    log(f"window {rec.window_s:.3f} s: {tokens} tokens, "
        f"{len(rec.reqs)} requests finished, {rec.decode_steps} decode "
        f"steps, {rec.replay_steps} replay steps, {len(rec.admits)} "
        f"prefill dispatches")

    metrics, breakdown = {}, None
    if trace_on:
        rec.trace = trace.reduce(trace.load(trace.find_xplane(tdir.name)))
        tdir.cleanup()
        device.update(busy_s=rec.trace["busy_s"],
                      window_s=rec.trace["window_s"])
        breakdown = {"device_ops": rec.trace["device_ops"],
                     "idle_gaps": rec.trace["idle_gaps"]}
        for m in cell.per_layer:
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            v = mod.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    # the check: engine freed first, so the reference sets no peak
    t_check = time.perf_counter()
    served = drv.served()
    eng.state = eng.params = None
    del eng, drv
    gc.collect()
    picked = check.sample(served, seed)
    # with ``control``, the tokens that the lower-precision reference puts
    # first at the same positions stand in for the served ones
    gap = (max(check.gaps(conf, seed, picked, quant=control)) if picked
           else None)
    limit = conf["check"]["max_logit_gap"]
    correct = gap is not None and gap <= limit
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    n_tok = sum(len(r.tokens) for r in picked)
    result["check"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    who = f"the {control} control's tokens" if control else "served tokens"
    log(f"check: {len(picked)} requests, {n_tok} {who} against the "
        f"float32 reference, {time.perf_counter() - t_check:.1f} s")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="judge the lower-precision reference's tokens in "
                         "place of the served ones (calibration only: it "
                         "has to read correct false)")
    args = ap.parse_args(argv)

    enable_cache()
    from bench import harness, peaks as peaks_mod
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX sees {devices[0].platform!r} devices; nothing "
            "was run")
        return 2
    chips = cell.workload["chips"]
    if len(devices) < chips:
        log(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
        return 2
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": chips}
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks=peaks_mod.peaks_for(kind), device=device,
                      t_start=T_START, control=args.control)
    for name, v in result["check"].items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
