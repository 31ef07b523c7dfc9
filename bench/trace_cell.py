"""One traced run of a cell, read by phase, with its trace kept on request.

    python bench/trace_cell.py --workload <name> --seed <n> --seconds <s>
        [--keep DIR] [--hlo FILE] [--bench FILE --data DIR]

Runs ``run.run_cell`` with the profiler on, as ``run.py --trace 1`` does,
and reads the same trace with ``phases.py`` before the harness removes it.
Prints to stderr the ten longest idle gaps of the window, each with the
host events that overlap it, and as its last stdout line one JSON object:
the run's result, each phase's device ms per iteration (``phase_ms``,
empty for a program without the phase scopes), each phase's busiest
operations, and ``traced_env_steps_per_s``, the env steps of the window
over the traced window.

``--keep DIR`` writes the trace into DIR as ``<workload>.xplane.pb``,
trimmed to what the reductions read (``xplane.trim``).  ``--hlo FILE``
then compiles the cell's chunk again (a hit in JAX's compile cache),
writes its HLO text to FILE without what names and debug info change
(``program_text``) and adds its sha256 to the line: two checkouts whose
chunks differ only in op metadata write the same text.
``--bench`` and ``--data`` name another ``BENCHMARK.json`` and the
directory of its configuration, traffic, limits and kind files (the
tests' tiny cells in ``tests/data``).
"""
from __future__ import annotations

import argparse
import base64
import contextlib
import hashlib
import json
import os
import re
import sys

import run  # set-up is counted from this import (``run.T_START``)

_METADATA = re.compile(r", metadata=\{[^}]*\}")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_NAME = re.compile(r"%[A-Za-z0-9_.-]+")


def _kernel_digest(m) -> str:
    """A Pallas kernel's serialized Mosaic module, as the sha256 of its
    text without source locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(m.group(1)))
        asm = module.operation.get_asm(enable_debug_info=False)
    return f'"body":"sha256:{hashlib.sha256(asm.encode()).hexdigest()}"'


def program_text(hlo: str) -> str:
    """Compiled HLO text without what scopes and source lines change: op
    metadata, the tables of source files and stack frames it starts with,
    the source locations inside each Pallas kernel's module, and the
    instruction names, numbered in order of first use instead (XLA's
    counters and merged names follow the op names)."""
    lines = hlo.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    text = _METADATA.sub("", "\n".join(lines[:1] + lines[start:]))
    text = _KERNEL_BODY.sub(_kernel_digest, text)
    names = {}
    return _NAME.sub(lambda m: names.setdefault(m.group(0),
                                                f"%{len(names)}"), text)


def chunk_hlo(spec) -> str:
    """The cell's compiled chunk as HLO text, on one chip."""
    import jax

    from drive_actor_learner import ActorLearnerDriver
    if spec["cell"]["chips"] != 1:
        raise run.BenchError("--hlo compiles one-chip cells only")
    drv = ActorLearnerDriver(spec["policy"], spec["traffic"])
    keys = [run.key_of(0, s) for s in range(4)]
    carry = jax.eval_shape(drv.init, *keys[:3])
    return drv.chunk.lower(*carry, keys[3]).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep")
    ap.add_argument("--hlo")
    ap.add_argument("--bench")
    ap.add_argument("--data", default=run.HERE)
    args = ap.parse_args(argv)

    import phases
    import trace_reduce
    import xplane

    read = {}
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_read(path, n_devices, kernels):
        space = xplane.read(path)
        read["phases"] = phases.reduce_phases(space, n_devices)
        read["gaps"] = phases.idle_gap_events(space, n_devices)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            xplane.trim(path, os.path.join(args.keep,
                                           f"{args.workload}.xplane.pb"))
        return reduce_dir(path, n_devices, kernels)

    try:
        spec = run.load_cell(args.workload, args.bench, args.data)
        devices, peaks = run.find_devices(spec["cell"]["chips"])
        run.enable_compile_cache()
        trace_reduce.reduce_dir = reduce_and_read
        try:
            out = run.run_cell(args.workload, args.seed, args.seconds, True,
                               devices=devices, peaks=peaks,
                               bench_path=args.bench, data_dir=args.data)
        finally:
            trace_reduce.reduce_dir = reduce_dir
        t = spec["traffic"]
        iterations = out["attempted"] * t["steps_per_call"]
        red = read["phases"]
        out["iterations"] = iterations
        out["phase_ms"] = phases.phase_ms(red, iterations)
        out["unnamed_ms"] = 1e3 * red["unnamed_s"] / iterations
        out["inferred_ms"] = 1e3 * red["inferred_s"] / iterations
        out["phase_ops"] = red["ops"]
        out["traced_env_steps_per_s"] = (
            iterations * t["rollout_steps"] * t["num_actors"] * t["n_envs"]
            / red["window_s"])
        if args.hlo:
            text = program_text(chunk_hlo(spec))
            with open(args.hlo, "w") as f:
                f.write(text)
            out["hlo_sha256"] = hashlib.sha256(text.encode()).hexdigest()
    except run.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(phases.format_gaps(read["gaps"]), file=sys.stderr)
    with contextlib.suppress(BrokenPipeError):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
