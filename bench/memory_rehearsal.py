#!/usr/bin/env python3
"""Print ``memory_analysis()`` of a cell's admit, replay and decode programs
at the cell's sizes, and the device memory the engine holds.

    python3 bench/memory_rehearsal.py --workload qwen1.5-1.8b.chat

Run by hand on the chip (it builds the cell's engine there); not a test.
The output sizes ``n_slots`` and ``max_len`` in the configuration files:
the paged state is not donated, so a dispatch holds its input state and
its output state at once.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _gb(n):
    return round(n / 1e9, 3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from bench.run import enable_cache
    enable_cache()
    import jax
    import jax.numpy as jnp
    from bench import harness
    cell = harness.load_cell(args.workload)
    eng = harness.build_engine(cell.conf, args.seed)
    dev = jax.devices()[0]
    state_b = sum(a.nbytes for a in jax.tree_util.tree_leaves(eng.state))
    param_b = sum(a.nbytes for a in jax.tree_util.tree_leaves(eng.params))
    out = {"workload": cell.name, "device_kind": dev.device_kind,
           "state_gb": _gb(state_b), "params_gb": _gb(param_b),
           "in_use_gb": _gb((dev.memory_stats() or {}).get(
               "bytes_in_use", 0)), "programs": {}}
    pr = cell.mix["prompt_tokens"]
    buckets = sorted({eng._bucket_of(n) for n in (pr["lo"], pr["hi"])})
    s = eng.n_slots
    progs = {f"admit_{b}x1": (eng._admit_for(b, 1), (
        eng.params, eng.state, jnp.zeros((1, b), jnp.int32),
        jnp.zeros((1,), jnp.int32))) for b in buckets}
    k = eng.max_fuse_steps
    progs[f"decode_{k}"] = (eng._decode_for(k), (
        eng.params, eng.state, jnp.zeros((s, 1), jnp.int32)))
    progs[f"replay_{k}"] = (eng._replay_for(k), (
        eng.params, eng.state, jnp.zeros((k, s, 1), jnp.int32),
        jnp.zeros((k, s), bool)))
    for name, (fn, a) in progs.items():
        ma = fn.lower(*a).compile().memory_analysis()
        out["programs"][name] = {
            "argument_gb": _gb(ma.argument_size_in_bytes),
            "output_gb": _gb(ma.output_size_in_bytes),
            "temp_gb": _gb(ma.temp_size_in_bytes),
            "alias_gb": _gb(ma.alias_size_in_bytes),
            "peak_estimate_gb": _gb(ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes)}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
