"""The whole actor-learner step's share of the chip's peak.

Least time at peak over the traced window: the actors' int8 GEMM
operations at the int8 peak plus the learner's GEMM operations (forward,
backward, target forward, and the float32 divergence head at pushes) at
the bf16 peak, since a float32 matmul at TPU default precision runs in
bf16 passes.  Operations come from ``opcount.work`` (one chip's
share of the window's iterations).
"""


def read(ctx):
    """Whole step's share of the chip's peak, in %."""
    red, work, peaks = ctx["trace"], ctx["work"], ctx["peaks"]
    if red["window_s"] <= 0:
        return None
    least = (work["actor_int8_ops"] / peaks["int8_ops"]
             + work["learner_flops"] / peaks["bf16_flops"])
    return 100.0 * least / red["window_s"]
