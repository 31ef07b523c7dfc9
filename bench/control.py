"""Readings that set a cell's correctness limits (not part of a timed run).

    python bench/control.py --workload <name> --seeds 11 12 13 ... [--int4 3]

For each seed, in one process with the cell's chunk compiled once, it
builds the fresh carry, runs the first chunk as a timed run's set-up does
(``check.collect``), and prints one JSON line of readings, each a dict of
the cell's numbers (``check.learner_numbers`` and
``check.actor_numbers``):

* ``program``: the program against the reference;
* ``control_bf16``: the reference computed in bfloat16 (parameters,
  activations, gradients, Adam state) put in the program's place;
* ``fault_half_batch``: the reference that takes the mean over the first
  half of each batch only;
* ``fault_no_exchange`` (on a mesh): each chip's learner on its own rows,
  with no gradient exchange; the first replica is read;
* ``fault_action``: each action the actors chose moved to the next action;
* ``fault_push_skipped``: the actors keep acting on the seed's weights
  after the first push: the reference's own greedy actions with those
  weights, put in place of the actions chosen after the push.

``--int4 n`` then runs the first ``n`` seeds with the program's own W4A8
actor path (``actor_backend="int4"``), the control of the int8 actors, and
prints their ``action_gap`` and ``push_action_gap`` as ``control_int4``.

A state left unchanged reads ``update_gap`` 1 and ``opt_steps`` equal to
the updates run, by construction, and needs no run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def _readings(drv, spec, seed, mesh_devices):
    import jax.numpy as jnp

    import check
    import reference as ref

    policy, traffic = spec["policy"], spec["traffic"]
    k_w = run.key_of(seed, 0)
    _, stash = check.first_iterations(drv, seed, traffic, run.key_of)
    w0 = check.seed_weights(policy, k_w)
    n_up = len(stash["batches"])
    refr = check.reference_run(stash, policy, traffic, w0)

    def numbers(prog, acted=None, pushed_acted=None):
        out = check.learner_numbers(prog, refr, n_up)
        out.update(check.actor_numbers(policy.forward, w0, refr["pushed"],
                                       stash, acted, pushed_acted))
        return out

    out = {"seed": seed, "program": numbers(stash)}
    ctl = check.reference_run(stash, policy, traffic, w0,
                              dtype=jnp.bfloat16)
    out["control_bf16"] = numbers(ctl)
    half = [{k: v[: len(v) // 2] for k, v in b.items()}
            for b in stash["batches"]]
    out["fault_half_batch"] = numbers(
        check.reference_run(stash, policy, traffic, w0, batches=half))
    if mesh_devices > 1:
        quarter = [{k: v[: len(v) // mesh_devices] for k, v in b.items()}
                   for b in stash["batches"]]
        out["fault_no_exchange"] = numbers(
            check.reference_run(stash, policy, traffic, w0,
                                batches=quarter))
    n_actions = policy.config["env"]["n_actions"]
    out["fault_action"] = numbers(
        stash, acted=(stash["acted_action"] + 1) % n_actions,
        pushed_acted=(stash["pushed_action"] + 1) % n_actions)
    stale = ref.q_values(policy.forward, w0,
                         stash["pushed_obs"]).argmax(axis=1)
    out["fault_push_skipped"] = numbers(stash, pushed_acted=stale)
    return out


def main(argv=None):
    """Print one line of readings per seed (see the module docstring)."""
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--int4", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import check
    from drive_actor_learner import ActorLearnerDriver

    spec = run.load_cell(args.workload)
    chips = spec["cell"]["chips"]
    devices, _ = run.find_devices(chips)
    run.enable_compile_cache()
    mesh = jax.make_mesh((chips,), ("actor",), devices=devices) \
        if chips > 1 else None
    with contextlib.ExitStack() as stack:
        sink = stack.enter_context(open(args.out, "a")) if args.out \
            else None

        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()

        drv = ActorLearnerDriver(spec["policy"], spec["traffic"], mesh)
        for seed in args.seeds:
            emit(_readings(drv, spec, seed, chips))
        if args.int4:
            pol4 = spec["policy"].with_config(actor_backend="int4")
            drv4 = ActorLearnerDriver(pol4, spec["traffic"], mesh)
            for seed in args.seeds[: args.int4]:
                _, stash = check.first_iterations(drv4, seed,
                                                  spec["traffic"],
                                                  run.key_of)
                w0 = check.seed_weights(pol4, run.key_of(seed, 0))
                refr = check.reference_run(stash, pol4, spec["traffic"], w0)
                emit({"seed": seed, "control_int4": check.actor_numbers(
                    pol4.forward, w0, refr["pushed"], stash)})

if __name__ == "__main__":
    main()
