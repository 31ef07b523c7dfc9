"""Operations and bytes of a cell's work, computed from its shapes.

Counts follow the algorithm, not the program's lowering: a GEMM of
``(M, K) @ (K, N)`` is ``2 M K N`` operations, and the least bytes it
moves are its operands once and its result once.  For the int8 kernels
(``kernels/int8_matmul.py``, ``kernels/fused_qmlp.py``) the operands are
int8 and the result is float32.  A conv is counted as the GEMM its im2col
lowering runs: ``M = batch * H * W`` rows of ``K = 9 * C_in``.

``work`` gives one chip's share of a run of iterations:

* the actors' forward passes: ``rollout_steps`` per iteration, over the
  chip's ``local_actors * n_envs`` environments, plus at each parameter
  push the divergence head over the same observations (int8 and float32)
  and, for a calibrated MLP cache, the calibration pass over
  ``calib_batch`` observations through per-layer ``int8_matmul``;
* the learner: per update, the forward and backward of the online network
  and the forward of the target network over the chip's share of the
  batch.  Backward counts the weight and input gradients of every layer but
  the input gradient of the first.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

Gemm = Tuple[int, int, int]          # (M, K, N) per observation row count


def gemms(config: Dict, n_obs: int) -> List[Gemm]:
    """The GEMMs of one forward pass over ``n_obs`` observations."""
    pol, env = config["policy"], config["env"]
    out = []
    if pol["kind"] == "conv":
        h, w, c = env["obs_shape"]
        for f in pol["conv_filters"]:
            out.append((n_obs * h * w, 9 * c, f))
            c = f
        d = h * w * c
        widths = [pol["fc_width"]]
    else:
        d = 1
        for s in env["obs_shape"]:
            d *= s
        widths = list(pol["widths"])
    for wd in widths + [env["n_actions"]]:
        out.append((n_obs, d, wd))
        d = wd
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


def learner_flops(config: Dict, batch: int) -> float:
    """GEMM operations of one learner update over ``batch`` rows."""
    fwd = gemms(config, batch)
    m, k, n = fwd[0]
    return 4 * gemm_ops(fwd) - 2 * m * k * n


def _add(acc: Dict, kernel: str, ops: float, nbytes: float, calls: int):
    e = acc.setdefault(kernel, {"ops": 0.0, "bytes": 0.0, "calls": 0})
    e["ops"] += ops
    e["bytes"] += nbytes
    e["calls"] += calls


def _actor_forward(acc: Dict, config: Dict, n_obs: int, calls: int,
                   calibrated: bool):
    gs = gemms(config, n_obs)
    if calibrated:
        _add(acc, "fused_qmlp", calls * gemm_ops(gs),
             calls * fused_qmlp_bytes(gs), calls)
    else:
        for g in gs:
            _add(acc, "int8_matmul", calls * gemm_ops([g]),
                 calls * int8_matmul_bytes(*g), calls)


def pushes(first_iter: int, n_iters: int, sync_every: int) -> int:
    """Parameter pushes in iterations ``first_iter + 1 .. first_iter +
    n_iters`` (the actor-learner core pushes after iteration ``t`` when
    ``t % sync_every == 0``)."""
    return ((first_iter + n_iters) // sync_every
            - first_iter // sync_every)


def work(config: Dict, traffic: Dict, chips: int, n_iters: int,
         n_pushes: int) -> Dict:
    """One chip's work in ``n_iters`` iterations holding ``n_pushes``
    parameter pushes (see the module docstring)."""
    t = traffic
    local_obs = t["num_actors"] // chips * t["n_envs"]
    calibrated = (config["policy"]["kind"] == "mlp"
                  and config["calib_batch"] > 0)
    kernels: Dict = {}
    steps = n_iters * t["rollout_steps"]
    _actor_forward(kernels, config, local_obs, steps, calibrated)
    _actor_forward(kernels, config, local_obs, n_pushes, calibrated)
    if calibrated:
        _actor_forward(kernels, config, config["calib_batch"], n_pushes,
                       False)
    updates = n_iters * t["updates_per_iter"]
    per_chip_batch = t["batch_size"] // chips
    learner = (updates * learner_flops(config, per_chip_batch)
               + n_pushes * gemm_ops(gemms(config, local_obs)))
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
