"""Serving launcher: LM decoding demo + the RL policy-serving service.

Two modes:

* **LM mode** (default): batched greedy decoding with (optionally int8)
  weights and (optionally int8) KV caches — the paper's deployment case
  study scaled to the assigned architectures.
* **RL mode** (``--rl-env``): trains a policy (any topology —
  ``fused`` / ``actor-learner`` / ``async`` — with fp32/int8/int4 actors,
  uniform or prioritized replay, any kernel backend incl. the native-XLA
  int8 path), then stands up the **continuous-batching policy server**
  (``repro.serving``): concurrent sessions multiplexed onto shape-bucketed
  padded batches against a packed actor cache with zero-copy hot-swap.
  This CLI is a thin veneer — the subsystem lives in
  ``src/repro/serving/``; see ``docs/serving.md``.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \\
      --reduced --batch 4 --prompt-len 32 --new-tokens 32 --quant ptq_int8 \\
      --int8-cache
  PYTHONPATH=src python -m repro.launch.serve --rl-env cartpole \\
      --topology async --actor-backend int4 --calib-batch 64 \\
      --serve-sessions 256 --serve-steps 4
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time


def _serve_policy(args) -> int:
    """ActorQ deployment through the continuous-batching policy server.

    Trains the policy, then pushes it into a ``repro.serving.PolicyServer``
    (``--actor-backend`` fp32 | int8 | int4 packed caches; ``--calib-batch``
    > 0 calibrates static activation scales at push so MLP actors serve
    through the single-pass fused kernel; ``--kernel-backend`` = pallas |
    interpret | ref | xla | auto picks the GEMM path) and drives
    ``--serve-sessions`` concurrent env sessions against it, demonstrating
    a zero-copy hot-swap mid-load.  Reports cache footprint, sustained
    actions/sec and p50/p99 per-step latency.
    """
    import jax
    import jax.numpy as jnp

    from repro import serving
    from repro.core import ptq
    from repro.rl import actorq, loops
    from repro.rl.actor_learner import ALGOS as REPLAY_ALGOS
    from repro.rl.envs import make as make_env

    env = make_env(args.rl_env)
    topo_kw = {}
    if args.topology in ("actor-learner", "async"):
        # replay algorithms only (the paper's DQN/D4PG analogues)
        algo = "dqn" if not env.spec.continuous else "ddpg"
        topo_kw = dict(topology=args.topology,
                       num_actors=args.num_actors,
                       sync_every=args.sync_every)
    else:
        algo = "ppo" if not env.spec.continuous else "ddpg"
    if args.replay != "uniform" and algo not in REPLAY_ALGOS:
        raise SystemExit(
            f"--replay {args.replay} needs a replay algorithm; fused "
            f"discrete envs train {algo} — use --topology actor-learner")
    if algo in REPLAY_ALGOS:
        topo_kw.update(replay=args.replay,
                       priority_exponent=args.priority_exponent,
                       is_beta=args.is_beta)
    res = loops.train(algo, args.rl_env, iterations=max(args.rl_iters, 1),
                      record_every=max(args.rl_iters, 1), eval_episodes=2,
                      seed=args.seed, steps_per_call=args.steps_per_call,
                      actor_backend=args.actor_backend,
                      calib_batch=args.calib_batch,
                      checkpoint_dir=args.ckpt_dir,
                      checkpoint_every=args.ckpt_every,
                      resume=args.resume, **topo_kw)
    if algo in REPLAY_ALGOS and args.replay == "prioritized":
        print(f"[serve-rl] prioritized replay: alpha="
              f"{args.priority_exponent} is_beta={args.is_beta}")
    if args.topology in ("actor-learner", "async") and res.divergences:
        div = ", ".join(f"{d:.4f}" for d in res.divergences[-1])
        unit = "learner updates" if args.topology == "async" \
            else "iterations"
        print(f"[serve-rl] {args.topology} ({algo}): {args.num_actors} "
              f"actors, sync_every={args.sync_every} {unit}, last "
              f"per-actor divergence [{div}]")
    if args.topology == "async" and res.actor_lags:
        print(f"[serve-rl] async overlap: {len(res.actor_lags)} param "
              f"pushes, mean actor lag "
              f"{sum(res.actor_lags) / len(res.actor_lags):.1f} learner "
              f"updates")
    params = res.state.params
    fp32_bytes = ptq.tree_nbytes(params)

    buckets = tuple(int(b) for b in args.buckets.split(","))
    server = serving.PolicyServer(
        env.spec, actor_backend=args.actor_backend,
        kernel_backend=args.kernel_backend, buckets=buckets,
        max_wait_us=args.max_wait_us, calib_batch=args.calib_batch)

    calib_obs = None
    if actorq.is_quantized(args.actor_backend) and args.calib_batch:
        # deployment-time calibration: static activation scales from the
        # states the *trained* policy actually visits — a short greedy
        # rollout from reset (reset draws alone sit near the origin for
        # the classic-control envs and would saturate the scales once the
        # served policy drifts) -> the single-pass fused MLP kernel
        # answers every action query in one dispatch
        qparams = actorq.pack_actor_params(
            params, actorq.backend_bits(args.actor_backend))
        calib_obs = serving.greedy_calib_obs(
            env, qparams, args.calib_batch, args.seed + 1,
            kernel_backend=args.kernel_backend)
    entry = server.push_params(params, calib_obs=calib_obs)
    if calib_obs is not None:
        if actorq.ACT_QUANT in entry.cache:
            print(f"[serve-rl] static requant: calibrated on "
                  f"{calib_obs.shape[0]} obs -> fused single-pass actor")
        else:
            # conv policies keep the per-layer path (calibration is a
            # documented no-op for CNN caches)
            print("[serve-rl] static requant: conv policy — calibration "
                  "skipped, per-layer path served")
    server.warmup()
    print(f"[serve-rl] env={args.rl_env} algo={algo} "
          f"actor={args.actor_backend} kernel={args.kernel_backend} "
          f"params={fp32_bytes / 1e3:.1f}KB fp32 -> "
          f"{entry.nbytes / 1e3:.1f}KB served "
          f"({fp32_bytes / max(entry.nbytes, 1):.2f}x) "
          f"buckets={list(buckets)} max_wait={args.max_wait_us}us")

    # drive N concurrent env sessions against the server: each session
    # steps its own (client-side) env with the actions the server returns
    import numpy as np

    from repro.rl.env import batched_env

    n = args.serve_sessions
    benv = batched_env(env, n)
    e_state, obs = benv.reset(jax.random.PRNGKey(args.seed))
    latencies = []
    t0 = time.time()
    with server:
        sids = [server.open_session() for _ in range(n)]
        for step_i in range(args.serve_steps):
            if step_i == args.serve_steps // 2 and args.serve_steps > 1:
                # live hot-swap under load: repack + republish (zero-copy
                # reference swap; in-flight batches finish on the old
                # cache, the next dispatch serves the new version)
                swapped = server.push_params(params)
                print(f"[serve-rl] hot-swap at step {step_i}: now serving "
                      f"cache version {swapped.version}")
            o_host = np.asarray(obs)
            reqs = [server.submit(sid, o_host[i])
                    for i, sid in enumerate(sids)]
            results = [r.result(timeout=120) for r in reqs]
            latencies.extend(r.latency_s for r in results)
            actions = jnp.asarray(np.stack([r.action for r in results]))
            if not env.spec.continuous:
                actions = actions.astype(jnp.int32)
            e_state, obs, _, _ = benv.step(
                e_state, actions, jax.random.fold_in(
                    jax.random.PRNGKey(args.seed), step_i))
        for sid in sids:
            server.close_session(sid)
    dt = time.time() - t0
    stats = server.stats()
    lat = np.asarray(latencies) * 1e3
    print(f"[serve-rl] {n} sessions x {args.serve_steps} steps in "
          f"{dt:.3f}s ({len(latencies) / dt:.0f} actions/s); per-step "
          f"latency p50 {np.percentile(lat, 50):.2f}ms "
          f"p99 {np.percentile(lat, 99):.2f}ms; "
          f"{stats['dispatches']} dispatches, mean batch "
          f"{stats['served'] / max(stats['dispatches'], 1):.1f}, "
          f"served by cache v{stats['version']}")
    print("           first actions:",
          np_list(results[0].action) if env.spec.continuous
          else [int(r.action) for r in results[:8]])
    return 0


def np_list(x):
    import numpy as np
    return np.asarray(x).tolist()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="h2o-danube-1.8b",
                    help="LM mode: transformer architecture to decode")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="LM mode: decoding batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--quant", default="none",
                    help="none | ptq_fp16 | ptq_int8 (weights)")
    ap.add_argument("--int8-cache", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rl-env", default=None,
                    help="serve an RL policy instead of an LM "
                         "(ActorQ deployment; e.g. cartpole, airnav)")
    ap.add_argument("--actor-backend", default="fp32",
                    choices=["fp32", "int8", "int4"],
                    help="int8 = W8A8 packed actor; int4 = byte-packed "
                         "W4A8 (half the served cache)")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["pallas", "interpret", "ref", "xla", "auto"])
    ap.add_argument("--calib-batch", type=int, default=0,
                    help="static-requant calibration batch for quantized "
                         "actors: >0 calibrates per-layer activation "
                         "scales (training caches at every sync, the "
                         "served cache once at deploy) and runs MLP "
                         "actors as ONE fused kernel pass; 0 = dynamic "
                         "per-layer quantization")
    ap.add_argument("--rl-iters", type=int, default=20,
                    help="training iterations before serving (--rl-env)")
    ap.add_argument("--steps-per-call", type=int, default=10,
                    help="scan-fused driver chunk for --rl-env training")
    ap.add_argument("--topology", default="fused",
                    choices=["fused", "actor-learner", "async"],
                    help="--rl-env training topology. actor-learner = the "
                         "paper's distributed ActorQ paradigm "
                         "(bulk-synchronous); async = overlapped actors/"
                         "learner over a double-buffered replay (no "
                         "host barrier). Both need a replay algorithm, so "
                         "discrete envs train DQN there vs PPO under "
                         "fused (the printed summary names the algo)")
    ap.add_argument("--num-actors", type=int, default=2,
                    help="actor replicas for the actor-learner topologies")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="learner->actor param push cadence: iterations "
                         "under --topology actor-learner, learner "
                         "*updates* under --topology async")
    ap.add_argument("--replay", default="uniform",
                    choices=["uniform", "prioritized"],
                    help="--rl-env replay discipline (DQN/DDPG): "
                         "prioritized = sum-tree PER with IS correction")
    ap.add_argument("--priority-exponent", type=float, default=0.6,
                    help="PER alpha; 0.0 degrades to bitwise-uniform")
    ap.add_argument("--is-beta", type=float, default=0.4,
                    help="initial IS-correction exponent (anneals to 1)")
    ap.add_argument("--serve-sessions", type=int, default=64,
                    help="concurrent env sessions driven against the "
                         "policy server after training (--rl-env)")
    ap.add_argument("--serve-steps", type=int, default=5,
                    help="env steps each serving session takes (a live "
                         "hot-swap fires at the halfway step)")
    ap.add_argument("--buckets", default="8,32,128,512",
                    help="ascending padded batch shapes the server "
                         "compiles (largest = admission max batch)")
    ap.add_argument("--max-wait-us", type=int, default=2000,
                    help="admission straggler wait: dispatch once the "
                         "oldest queued request is this old (0 = never "
                         "wait; the tail-latency knob)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint the training phase here "
                         "(repro.checkpoint async writer)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="iterations between training checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="resume training from the newest checkpoint in "
                         "--ckpt-dir before serving")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.rl_env:
        return _serve_policy(args)

    import jax
    import jax.numpy as jnp

    from repro.configs import base as cfgs
    from repro.core import ptq
    from repro.core.qconfig import QuantConfig
    from repro.models import transformer

    cfg = cfgs.get_reduced(args.arch) if args.reduced else cfgs.get(args.arch)
    quant = QuantConfig.parse(args.quant)
    if args.int8_cache:
        cfg = dataclasses.replace(
            cfg, quant=dataclasses.replace(cfg.quant, int8_kv_cache=True))

    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, key)
    fp32_bytes = ptq.tree_nbytes(params)
    if quant.is_ptq:
        params = ptq.ptq_simulate(params, quant)  # simulated int math
    print(f"[serve] {cfg.name} quant={quant.label()} "
          f"int8_cache={cfg.quant.int8_kv_cache} "
          f"params={fp32_bytes / 1e6:.1f}MB fp32"
          + (f" -> {fp32_bytes / 4 / 1e6:.1f}MB int8 packed"
             if quant.mode.value == "ptq_int" else ""))

    total_len = args.prompt_len + args.new_tokens
    caches = transformer.init_caches(cfg, args.batch, total_len,
                                     dtype=jnp.float32)
    tokens = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab)
    enc = None
    if cfg.cross_attn or cfg.encoder_layers:
        enc = jax.random.normal(key, (args.batch, max(cfg.encoder_seq, 4),
                                      cfg.d_model)) * 0.02

    @jax.jit
    def step(params, caches, tok, pos):
        logits, caches = transformer.decode_step(cfg, params, tok, caches,
                                                 pos, encoder_out=enc)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), caches

    # prefill token-by-token (teacher forcing) then greedy decode
    t0 = time.time()
    out_tokens = []
    tok = tokens[:, :1]
    for pos in range(total_len - 1):
        nxt, caches = step(params, caches, tok, jnp.asarray(pos))
        tok = tokens[:, pos + 1:pos + 2] if pos + 1 < args.prompt_len \
            else nxt[:, None]
        if pos + 1 >= args.prompt_len:
            out_tokens.append(nxt)
    dt = time.time() - t0
    n_gen = args.batch * len(out_tokens)
    print(f"[serve] generated {len(out_tokens)} tokens x {args.batch} seqs "
          f"in {dt:.2f}s ({n_gen / dt:.1f} tok/s on "
          f"{jax.devices()[0].platform})")
    print("        first sequence:", [int(t[0]) for t in out_tokens][:16])
    return 0


if __name__ == "__main__":
    sys.exit(main())
