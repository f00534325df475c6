"""End-to-end trainer (runnable on CPU with reduced configs; the same code
path drives the production mesh).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 50 --mesh 2x4 --ckpt-dir /tmp/ckpt --ckpt-every 20 \
        [--simulate-failure 30] [--resume]

Demonstrates: manual-SPMD train step, LEXI codec on all transports,
checkpoint/restart fault tolerance, straggler monitoring.
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import SHAPES, get_config, make_reduced
from repro.configs.base import MeshConfig, RunConfig, ShapeConfig
from repro.core.collectives import CodecConfig
from repro.data import pipeline as data_mod
from repro.launch.mesh import make_mesh_from_config
from repro.models import lm
from repro.train import checkpoint as ckpt_mod
from repro.train import fault
from repro.train import train_step as TS


def train_loop(cfg, shape: ShapeConfig, mesh_cfg: MeshConfig,
               run: RunConfig, *, steps: int, ckpt_dir: Optional[str],
               ckpt_every: int, resume: bool,
               fail_at: Optional[int] = None, log=print) -> Dict:
    mesh = make_mesh_from_config(mesh_cfg)
    table = lm.lm_table(cfg, mesh_cfg, run)
    step_fn = TS.make_shard_mapped_step(cfg, run, mesh_cfg, table, mesh,
                                        total_steps=steps)
    data = data_mod.for_config(cfg, shape, seed=run.seed)

    start = 0
    state = TS.init_state(table, seed=run.seed)
    if resume and ckpt_dir and (ckpt_mod.latest_step(ckpt_dir) is not None):
        start = ckpt_mod.latest_step(ckpt_dir)
        state = ckpt_mod.restore(ckpt_dir, state)
        log(f"[train] resumed from step {start}")

    mon = fault.StragglerMonitor(
        on_straggler=lambda s, dt, p95: log(
            f"[fault] straggler at step {s}: {dt * 1e3:.0f}ms vs p95 "
            f"{p95 * 1e3:.0f}ms"))
    wd = fault.Watchdog(deadline_s=600.0)
    losses = []
    for step in range(start, steps):
        if fail_at is not None and step == fail_at:
            raise fault.SimulatedFailure(f"injected failure at step {step}")
        batch = data.batch_at(step)
        wd.arm()
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        wd.disarm()
        mon.record(step, dt)
        losses.append(loss)
        if step % max(1, steps // 20) == 0 or step == steps - 1:
            log(f"[train] step {step:5d} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} {dt * 1e3:.0f}ms")
        if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0:
            path = ckpt_mod.save(ckpt_dir, step + 1, state)
            sz = ckpt_mod.stored_size(ckpt_dir, step + 1)
            log(f"[ckpt] step {step + 1} -> {path} "
                f"({sz['stored_bytes'] / 1e6:.1f} MB vs "
                f"{sz['raw_bytes'] / 1e6:.1f} MB raw, LEXI "
                f"{sz['raw_bytes'] / max(sz['stored_bytes'], 1):.2f}x)")
    if ckpt_dir:
        ckpt_mod.save(ckpt_dir, steps, state)
    return {"final_loss": losses[-1] if losses else float("nan"),
            "first_loss": losses[0] if losses else float("nan"),
            "stragglers": mon.straggler_steps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--codec", default="full",
                    choices=["full", "weights", "off"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure", type=int, default=None,
                    help="inject a failure at this step once, then recover")
    args = ap.parse_args(argv)

    devs = jax.devices()
    print(f"[train] devices: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}")
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh_cfg = MeshConfig(data=d, model=m, pod=1)
    codec = {"full": CodecConfig(), "weights": CodecConfig.weights_only(),
             "off": CodecConfig.off()}[args.codec]
    run = RunConfig(codec=codec, warmup_steps=max(args.steps // 10, 1))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg, tp=m)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    failed_once = {"done": False}

    def run_once() -> Dict:
        fail_at = None
        if args.simulate_failure is not None and not failed_once["done"]:
            failed_once["done"] = True
            fail_at = args.simulate_failure
        return train_loop(cfg, shape, mesh_cfg, run, steps=args.steps,
                          ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every or 0,
                          resume=True, fail_at=fail_at)

    out = fault.run_with_restarts(run_once, max_restarts=2)
    print(f"[train] done: loss {out['first_loss']:.4f} -> "
          f"{out['final_loss']:.4f}, restarts={out['restarts']}")
    return 0


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
