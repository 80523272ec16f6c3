"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

CPU-runnable end-to-end: reduced config by default (--full lowers the real
config; only sensible on a real cluster).  Wires the full substrate: data
pipeline -> sharded train step -> checkpointing -> fault-tolerant restart.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import REGISTRY, reduced
from repro.data.pipeline import SyntheticLMStream
from repro.distributed import sharding as shd
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_dev_mesh
from repro.models.model import Model, ModelOptions
from repro.training import checkpoint as ckpt
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import (TrainStepConfig, init_state,
                                       make_train_step)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="use the full (not reduced) architecture config")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = REGISTRY[args.arch]
    if not args.full:
        cfg = reduced(cfg)
    mesh = make_dev_mesh()
    strategy = shd.strategy_for_mesh(mesh)
    model = Model(cfg, ModelOptions())
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    step_cfg = TrainStepConfig(optimizer=opt_cfg, accum_steps=args.accum)

    stream = SyntheticLMStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.batch)
    state = init_state(model, jax.random.PRNGKey(0), opt_cfg)
    start_step = 0
    if args.resume and args.ckpt_dir:
        got = ckpt.restore_latest(args.ckpt_dir, state)
        if got is not None:
            state, meta = got
            start_step = meta["step"]
            stream = SyntheticLMStream.restore(
                meta["data_state"], vocab_size=cfg.vocab_size,
                seq_len=args.seq, global_batch=args.batch)
            print(f"resumed from step {start_step}")

    batch0 = stream.next()
    specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch0.items()}
    jitted, _, _ = make_train_step(model, mesh, strategy, step_cfg, specs)

    t0 = time.time()
    batch = batch0
    for i in range(start_step, args.steps):
        state, metrics = jitted(state, batch)
        batch = stream.next()
        if (i + 1) % args.log_every == 0 or i == start_step:
            loss = float(metrics["loss"])
            dt = time.time() - t0
            tok_s = (i + 1 - start_step) * args.batch * args.seq / dt
            print(f"step {i + 1:5d}  loss {loss:7.4f}  "
                  f"lr {float(metrics['lr']):.2e}  {tok_s:,.0f} tok/s",
                  flush=True)
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, i + 1, state,
                      meta={"data_state": stream.state()}, async_write=True)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, state,
                  meta={"data_state": stream.state()})
    print("done")


if __name__ == "__main__":
    main()
