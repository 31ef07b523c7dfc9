"""Training launcher.

Two entry points, matching the paper's two workload kinds:

* ``--mode rl``  — the QuaRL study itself: train an RL policy with any
  algorithm/env/quantization mode (this is what the benchmarks drive).
* ``--mode lm``  — the framework's LM trainer: any assigned architecture,
  on the local host mesh (CPU smoke) or the production mesh, with mixed
  precision, QAT, 8-bit Adam, checkpointing, and the synthetic data
  pipeline. On real TPU pods the same script runs under
  ``jax.distributed.initialize()``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode rl --algo ppo \\
      --env cartpole --quant qat8:delay=100 --iterations 300
  PYTHONPATH=src python -m repro.launch.train --mode lm \\
      --arch xlstm-125m --reduced --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("rl", "lm"), default="rl")
    # rl
    ap.add_argument("--algo", default="ppo")
    ap.add_argument("--env", default="cartpole")
    ap.add_argument("--iterations", type=int, default=200)
    ap.add_argument("--quant", default="none")
    ap.add_argument("--seed", type=int, default=0)
    # lm
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized variant")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    # both modes: fault tolerance (repro.checkpoint)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest committed checkpoint in "
                         "--ckpt-dir (rl mode: bitwise-identical to the "
                         "uninterrupted run)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="checkpoints retained in --ckpt-dir (<=0: all)")
    # rl mode: self-healing supervisor (repro.resilience)
    ap.add_argument("--fault-plan", default=None, metavar="SEED:SPEC",
                    help="run under the resilience supervisor with this "
                         "deterministic fault plan, e.g. "
                         "'7:bitflip_push@4,straggler@6:delay_s=0.2' "
                         "(see docs/resilience.md)")
    ap.add_argument("--supervised", action="store_true",
                    help="run under the resilience supervisor without "
                         "injected faults (retry/rollback on real ones)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="supervisor resume-retries per rollback level")
    ap.add_argument("--rollback", type=int, default=1,
                    help="supervisor rollback-to-previous-checkpoint "
                         "escalations after retries exhaust")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "rl":
        return run_rl(args)
    return run_lm(args)


def run_rl(args) -> int:
    from repro.core.qconfig import QuantConfig
    from repro.rl import loops
    quant = QuantConfig.parse(args.quant)
    kwargs = dict(algo=args.algo, env_name=args.env,
                  iterations=args.iterations, quant=quant, seed=args.seed,
                  record_every=max(args.iterations // 10, 1),
                  checkpoint_dir=args.ckpt_dir,
                  checkpoint_every=args.ckpt_every,
                  resume=args.resume, checkpoint_keep=args.ckpt_keep)
    if args.fault_plan is not None or args.supervised:
        from repro import resilience
        plan = (resilience.FaultPlan.parse(args.fault_plan)
                if args.fault_plan else None)
        sup_cfg = resilience.SupervisorConfig(
            max_retries=args.max_retries, max_rollbacks=args.rollback)
        try:
            res, report = resilience.supervise(kwargs, plan=plan,
                                               config=sup_cfg)
        except resilience.SupervisorAbort as e:
            print(f"[train/rl] {e.report.summary()}")
            return 1
        print(f"[train/rl] {report.summary()}")
    else:
        res = loops.train(**kwargs)
    print(f"[train/rl] {args.algo} on {args.env} quant={quant.label()}: "
          f"eval rewards {['%.1f' % r for r in res.rewards]} "
          f"({res.wall_time_s:.0f}s)")
    return 0


def run_lm(args) -> int:
    import jax
    import jax.numpy as jnp

    from repro import checkpoint as ckpt_lib
    from repro.configs import base as cfgs
    from repro.data import SyntheticLMDataset
    from repro.launch import steps as steps_lib
    from repro.models import transformer
    from repro.optim import adam as adam_lib

    cfg = cfgs.get_reduced(args.arch) if args.reduced else cfgs.get(args.arch)
    adam_cfg = adam_lib.AdamConfig(lr=args.lr, eightbit=cfg.optimizer_8bit)
    train_step, adam_cfg = steps_lib.make_train_step(cfg, adam_cfg)
    train_step = jax.jit(train_step, donate_argnums=(0, 1))

    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, key,
                                     dtype=jnp.dtype(cfg.mp.param_dtype))
    if args.resume and args.ckpt_dir:
        # params-only warm start (the rl mode has the full bitwise-resume
        # contract; the lm demo loop checkpoints just the params)
        last = ckpt_lib.latest_step(args.ckpt_dir)
        if last is not None:
            params = ckpt_lib.load_checkpoint(
                args.ckpt_dir, {"params": params}, step=last)["params"]
            print(f"[train/lm] resumed params from step {last}")
    opt = adam_lib.adam_init(params, adam_cfg)
    qat = transformer.init_qat_collection(cfg) if cfg.quant.is_qat else {}
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[train/lm] {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"quant={cfg.quant.label()}, mp={cfg.mp.compute_dtype}, "
          f"8bit-adam={adam_cfg.eightbit}")

    data = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq,
                              batch=args.batch, seed=args.seed)
    it = data.batches()
    t0 = time.time()
    for step, batch in enumerate(it):
        if step >= args.steps:
            break
        jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.cross_attn or cfg.encoder_layers:
            jbatch["encoder_out"] = jnp.zeros(
                (args.batch, max(cfg.encoder_seq, 4), cfg.d_model),
                jnp.dtype(cfg.mp.compute_dtype))
        params, opt, qat, metrics = train_step(params, opt, jbatch, qat)
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"  step {step:5d}  loss {float(metrics['loss']):.4f}  "
                  f"grad_norm {float(metrics.get('grad_norm', 0)):.3f}  "
                  f"({time.time() - t0:.0f}s)")
        if args.ckpt_dir and args.ckpt_every and \
                (step + 1) % args.ckpt_every == 0:
            path = ckpt_lib.save_checkpoint(args.ckpt_dir,
                                            {"params": params}, step=step)
            print(f"  saved {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
