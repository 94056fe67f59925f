"""End-to-end training launcher (example application driver).

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 300 --batch 8 --seq 128

Runs the full production loop on whatever devices exist: config -> params
-> sharded train step -> fault-tolerant driver (periodic async checkpoints,
restart-on-failure, straggler monitor) -> metrics.  With --chaos it injects
a failure mid-run to demonstrate restore-and-resume."""
from __future__ import annotations

import argparse
import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.models import lm
from repro.optim import adamw, warmup_cosine
from repro.checkpoint import CheckpointManager
from repro.runtime.driver import TrainDriver
from repro.runtime.meshctx import use_mesh
from repro.data.tokens import TokenStream
from repro.launch.mesh import make_smoke_mesh
from repro.launch.jax_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--chaos", action="store_true",
                    help="inject a failure at 60%% progress (demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    mod = configs.get(args.arch)
    assert mod.FAMILY == "lm", "train.py drives LM archs; see examples/"
    cfg = mod.smoke_config() if args.smoke else mod.full_config()
    if args.seq % cfg.loss_chunks:
        cfg = dataclasses.replace(cfg, loss_chunks=1)
    print(f"[train] {cfg.name}: {cfg.n_params():,} params "
          f"({cfg.n_active_params():,} active)")

    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(key, cfg)
    opt = adamw(warmup_cosine(args.lr, 20, args.steps), weight_decay=0.01)
    mesh = make_smoke_mesh()

    def step_fn(state, batch):
        p, o = state
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True)(p, b, cfg)
        p, o, om = opt.update(grads, o, p)
        return (p, o), {"loss": loss, **metrics, **om}

    jit_step = jax.jit(step_fn, donate_argnums=0)

    def make_data(start):
        return TokenStream(cfg.vocab, args.batch, args.seq, seed=args.seed,
                           start_step=start)

    chaos = {"armed": args.chaos}

    def injector(step):
        if chaos["armed"] and step == int(args.steps * 0.6):
            chaos["armed"] = False
            return True
        return False

    ckpt = CheckpointManager(args.ckpt_dir, keep_last=2)
    with use_mesh(mesh):
        driver = TrainDriver(
            step_fn=jit_step, init_state=(params, opt.init(params)),
            make_data=make_data, ckpt=ckpt, ckpt_every=args.ckpt_every,
            failure_injector=injector if args.chaos else None,
            log_every=max(args.steps // 20, 1))
        state, report = driver.run(args.steps)

    # final eval on fresh batches
    losses = []
    stream = make_data(10_000)
    for _ in range(4):
        b = {k: jnp.asarray(v) for k, v in next(stream).items()}
        losses.append(float(lm.loss_fn(state[0], b, cfg)[0]))
    print(f"[train] done: eval_loss={np.mean(losses):.4f} report={report}")
    return np.mean(losses), report


if __name__ == "__main__":
    main()
