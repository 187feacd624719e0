"""Training launcher.

Runs any registered architecture (full or ``--reduced`` smoke scale) with the
fault-tolerant runner, checkpointing, and synthetic data.  On the CPU
container use ``--reduced``; on a real pod drop it and point ``--devices`` at
the production mesh (the step function, shardings, and data pipeline are the
same objects the dry-run compiles).

Example (CPU, ~20M params, a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 300 --batch 8 --seq 256 --ckpt /tmp/ck
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS
from repro.core.api import ParallelContext
from repro.core.strategies import available_strategies, get_strategy
from repro.data.synthetic import SyntheticConfig, SyntheticDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime.fault_tolerance import FailureInjector, FaultTolerantRunner
from repro.runtime.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (fault-tolerance demo)")
    ap.add_argument(
        "--strategy", default="tokenring",
        # window-only strategies need a window= the full-attention layers of
        # a training run never pass, serving-side schedules (decode /
        # prefill) only run against a resident cache, and two-axis rings are
        # planned via plan(topology=...); don't advertise any of them
        choices=["auto"] + [
            n for n in available_strategies()
            if not get_strategy(n).requires_window
            and not get_strategy(n).serving_side
            and get_strategy(n).ring_axes == 1
        ],
    )
    ap.add_argument(
        "--impl", default="auto",
        choices=["auto", "pallas", "pallas_interpret", "xla"],
        help="flash-attention kernel impl (forward AND backward; 'auto' is "
        "pallas on TPU, xla elsewhere)",
    )
    ap.add_argument("--block-q", type=int, default=512)
    ap.add_argument("--block-k", type=int, default=512)
    ap.add_argument(
        "--block-q-bwd", type=int, default=None,
        help="backward dq/dkv kernel Q tile (default: --block-q)",
    )
    ap.add_argument(
        "--block-k-bwd", type=int, default=None,
        help="backward dq/dkv kernel KV tile (default: --block-k)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    pctx = ParallelContext(
        mesh=None, strategy=args.strategy, impl=args.impl,
        block_q=args.block_q, block_k=args.block_k,
        block_q_bwd=args.block_q_bwd, block_k_bwd=args.block_k_bwd,
    )
    bundle = build_model(cfg, pctx)

    inj = FailureInjector([args.fail_at]) if args.fail_at is not None else None
    tcfg = TrainerConfig(
        lr=args.lr,
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        microbatches=args.microbatches,
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt,
        opt=AdamWConfig(),
    )
    trainer = Trainer(bundle, tcfg, step_hook=inj)
    data = SyntheticDataset(
        SyntheticConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
            seed=args.seed, layout=cfg.layout, sp_degree=pctx.sp_degree,
        )
    )

    if args.ckpt:
        runner = FaultTolerantRunner(trainer, max_restarts=3)
        state, hist = runner.run(jax.random.PRNGKey(args.seed), data, steps=args.steps)
    else:
        state = trainer.init_state(jax.random.PRNGKey(args.seed))
        state, hist = trainer.run(state, data, steps=args.steps)
    print(f"final step {int(state['step'])}  loss {hist[-1]:.4f} "
          f"(start {hist[0]:.4f})")
    return hist


if __name__ == "__main__":
    main()
