"""Sharded training launcher (production entry point).

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 20 --mesh-shape 1,1 [--no-smoke]

``--smoke`` (the default) trains the architecture's reduced config;
``--no-smoke`` trains it at its published widths.  The step is
:func:`make_sharded_train_step` over a ``(data, model)`` mesh; on the
container that mesh is (1, 1).  Includes the fault-tolerance loop:
checkpoint-every-k, auto-resume and a straggler/deadline monitor.
"""
import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import get_config
from ..data import DataConfig, SyntheticLM
from ..training import optimizer as opt
from ..training.train import init_sharded, make_sharded_train_step
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


class StragglerMonitor:
    """Deadline-based straggler detection: if a step exceeds
    `factor` x the trailing-median step time, log it (and in a multi-host
    deployment, trigger the controller's slow-host protocol)."""

    def __init__(self, factor: float = 3.0, window: int = 20):
        self.factor = factor
        self.times = []
        self.window = window
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        import statistics
        slow = (len(self.times) >= 5
                and dt > self.factor * statistics.median(self.times))
        self.times.append(dt)
        self.times = self.times[-self.window:]
        if slow:
            self.flagged += 1
        return slow


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default) or published widths")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh-shape", default="1,1")
    ap.add_argument("--hyper-connections", type=int, default=0,
                    help="mHC residual stream count (0 disables)")
    ap.add_argument("--fused-mhc-bwd", action="store_true",
                    help="run the mHC backward through the extracted "
                         "mhc_stream_bwd fusion chain (DESIGN.md §16); "
                         "requires --hyper-connections > 0 to matter")
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "repro_launch_train"))
    ap.add_argument("--ckpt-every", type=int, default=10)
    return ap.parse_args(argv)


def config_from_args(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.hyper_connections:
        cfg = cfg.scaled(hyper_connections=args.hyper_connections)
    return cfg


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = config_from_args(args)
    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    axes = ("data", "model")[: len(shape)] if len(shape) <= 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(shape, axes)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                                  global_batch=args.batch))
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    mon = StragglerMonitor()

    batch0 = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    step_fn, (pshard, oshard, bshard) = make_sharded_train_step(
        cfg, ocfg, mesh, batch0, args.grad_accum,
        fused_backward=args.fused_mhc_bwd)
    params, state = init_sharded(cfg, pshard, oshard)
    start = 0
    if mgr.latest_step() is not None:
        restored, meta = mgr.restore(mgr.latest_step(),
                                     {"params": params, "opt": state})
        params = jax.device_put(restored["params"], pshard)
        state = jax.device_put(restored["opt"], oshard)
        start = meta["data_step"]
        print(f"[resume] from step {start}")

    for step in range(start, args.steps):
        t0 = time.time()
        batch = jax.device_put(
            {k: jnp.asarray(v) for k, v in data.batch(step).items()},
            bshard)
        params, state, metrics = step_fn(params, state, batch)
        metrics = jax.device_get(metrics)
        dt = time.time() - t0
        if mon.observe(dt):
            print(f"[straggler] step {step} took {dt:.2f}s "
                  f"(median {np.median(mon.times):.2f}s)")
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"{dt:.2f}s", flush=True)
        if step and step % args.ckpt_every == 0:
            mgr.save(step, {"params": params, "opt": state},
                     meta={"data_step": step})
    mgr.save(args.steps, {"params": params, "opt": state},
             meta={"data_step": args.steps})
    mgr.wait()
    print(f"done ({mon.flagged} straggler events); checkpoints in "
          f"{args.ckpt_dir}")


if __name__ == "__main__":
    main()
