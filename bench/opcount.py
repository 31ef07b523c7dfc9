"""Operations and bytes of a cell's work, computed from its shapes.

Counts follow the algorithm, not the program's lowering: a GEMM of
``(M, K) @ (K, N)`` is ``2 M K N`` operations, and the least bytes it
moves are its operands once and its result once.  For the int8 kernels
the operands are int8 and the result is float32.  What a policy runs, and
through which kernels, is its kind's to count (``policies/<kind>.py``);
the helpers here are the arithmetic the kinds share.

``work`` gives one chip's share of a run of iterations:

* the actors' int8 kernels: the kind's ``rollout_kernels`` at each of
  ``rollout_steps`` per iteration, and its ``push_kernels`` at each
  parameter push, over the chip's ``local_actors * n_envs``
  environments;
* the float operations: the kind's ``learner_flops`` per learner update
  over the chip's share of the batch, and its ``push_flops`` at each
  push.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

Gemm = Tuple[int, int, int]          # (M, K, N)


def dense_gemms(n_obs: int, d_in: int, widths: Sequence[int]) -> List[Gemm]:
    """The GEMMs of a chain of dense layers of ``widths`` over ``n_obs``
    rows of ``d_in`` features."""
    out = []
    for wd in widths:
        out.append((n_obs, d_in, wd))
        d_in = wd
    return out


def gemm_ops(gs: List[Gemm]) -> float:
    """Operations of a list of GEMMs."""
    return float(sum(2 * m * k * n for m, k, n in gs))


def int8_matmul_bytes(m: int, k: int, n: int) -> float:
    """Least bytes of one ``int8_matmul`` call."""
    return float(m * k + k * n + 4 * m * n)


def fused_qmlp_bytes(gs: List[Gemm]) -> float:
    """Least bytes of one ``fused_qmlp`` call over a whole MLP."""
    m = gs[0][0]
    return float(m * gs[0][1] + sum(k * n for _, k, n in gs)
                 + 4 * m * gs[-1][2])


def int8_matmul_calls(gs: List[Gemm]) -> Dict[str, tuple]:
    """``{"int8_matmul": (ops, bytes, calls)}`` of one call per GEMM."""
    return {"int8_matmul": (gemm_ops(gs),
                            sum(int8_matmul_bytes(*g) for g in gs),
                            len(gs))}


def chain_learner_flops(fwd: List[Gemm]) -> float:
    """One learner update of a chain of GEMMs: the online forward, its
    backward (weight and input gradients of every GEMM but the input
    gradient of the first) and the target's forward."""
    m, k, n = fwd[0]
    return 4 * gemm_ops(fwd) - 2 * m * k * n


def _add(acc: Dict, counts: Dict[str, tuple], times: int):
    for kernel, (ops, nbytes, calls) in counts.items():
        e = acc.setdefault(kernel, {"ops": 0.0, "bytes": 0.0, "calls": 0})
        e["ops"] += times * ops
        e["bytes"] += times * nbytes
        e["calls"] += times * calls


def pushes(first_iter: int, n_iters: int, sync_every: int) -> int:
    """Parameter pushes in iterations ``first_iter + 1 .. first_iter +
    n_iters`` (the actor-learner core pushes after iteration ``t`` when
    ``t % sync_every == 0``)."""
    return ((first_iter + n_iters) // sync_every
            - first_iter // sync_every)


def work(policy, traffic: Dict, chips: int, n_iters: int,
         n_pushes: int) -> Dict:
    """One chip's work in ``n_iters`` iterations holding ``n_pushes``
    parameter pushes (see the module docstring); ``policy`` is the
    configuration bound to its kind (``kinds.Policy``)."""
    t, kind, config = traffic, policy.kind, policy.config
    local_obs = t["num_actors"] // chips * t["n_envs"]
    kernels: Dict = {}
    steps = n_iters * t["rollout_steps"]
    _add(kernels, kind.rollout_kernels(config, local_obs), steps)
    _add(kernels, kind.push_kernels(config, local_obs), n_pushes)
    updates = n_iters * t["updates_per_iter"]
    per_chip_batch = t["batch_size"] // chips
    learner = (updates * kind.learner_flops(config, per_chip_batch)
               + n_pushes * kind.push_flops(config, local_obs))
    return {
        "kernels": kernels,
        "actor_int8_ops": sum(e["ops"] for e in kernels.values()),
        "learner_flops": learner,
        "env_steps": steps * local_obs,       # per chip
        "updates": updates,
    }


def roofline_share(ctx: Dict, kernel: str):
    """A kernel's least time at the chip's peaks (the larger of its
    operations at the int8 peak and its bytes at the HBM peak) over its
    device time in the trace, in %; None when the trace holds none."""
    t = ctx["trace"]["kernels"].get(kernel, 0.0)
    work = ctx["work"]["kernels"].get(kernel)
    if t <= 0 or not work:
        return None
    peaks = ctx["peaks"]
    least = max(work["ops"] / peaks["int8_ops"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
