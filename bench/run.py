"""One run of one benchmark cell on the accelerator.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the configuration's ``policy.kind``
names its policy kind (``bench/policies/<kind>.py``, ``kinds.py``); its
correctness limits are ``bench/limits/<workload>.json`` and each
per-layer metric is read by ``bench/metrics/<metric>.py``.  Nothing here
names a cell or a kind.

A run:

1. finds a TPU with the cell's chips and its peaks in ``peaks.json``, or
   exits non-zero with no result;
2. set-up (``setup_s``, from process start to the first timed dispatch):
   builds the seed's weights and the whole carry in one jitted call,
   compiles the cell's chunk (from the compile cache in the checkout after
   the first run) and runs it once from the fresh carry, reading what the
   correctness check needs (``check.collect``); last, it collects Python's
   garbage and freezes what is left, so that no collection inside the
   window walks the heap that set-up built;
3. dispatches that chunk back to back for ``--seconds`` seconds, keeping
   about ``AHEAD_S`` seconds of chunks queued on the device behind the one
   it waits for, so that a host that stands still leaves the chip fed;
   once ``--seconds`` have passed it dispatches nothing more, waits for
   every chunk it dispatched, and the window ends at that wait: all of
   their work counts, over all of that time.  A compilation inside the
   window is an error.  With ``--trace 1`` the window runs under the
   profiler and the per-layer metrics are read from its trace;
4. reads the peak device memory, frees the program's state, and runs the
   reference over what set-up read (``check.judge``).

The last line of stdout is one JSON object; the numbers compared for
``correct`` come last in it (``checks``) and as the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

# JAX's monitoring events for a backend compile and for a trace to jaxpr
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


WARMUP_CALLS = 4
# device seconds of chunks kept queued behind the one the window waits for
AHEAD_S = 4.0


class BenchError(Exception):
    """A run that cannot produce a result."""


def _load(*parts):
    path = os.path.join(*parts)
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_path: str = None, data_dir: str = HERE
              ) -> dict:
    """The cell's entry, configuration (and, as ``policy``, the
    configuration bound to its kind), traffic, limits and metrics, from
    ``BENCHMARK.json`` and the files under ``data_dir``; a kind is looked
    up under ``data_dir/policies`` before ``bench/policies``."""
    import kinds

    bench = _load(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]

    def applies(metric):
        return name in metric.get("workloads", [name])

    config = _load(data_dir, "configs", f"{cell['config']}.json")
    try:
        policy = kinds.bind(config, data_dir)
    except kinds.KindError as e:
        raise BenchError(str(e)) from e
    return {
        "cell": cell,
        "config": config,
        "policy": policy,
        "traffic": _load(data_dir, "traffic", f"{cell['traffic']}.json"),
        "limits": _load(data_dir, "limits", f"{name}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def find_devices(chips: int, peaks_path: str = None):
    """The first ``chips`` TPU devices and their peaks, or BenchError."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}; this benchmark measures "
                         f"the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = _load(peaks_path or os.path.join(HERE, "peaks.json"))["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return devs[:chips], peaks[kind]


def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout."""
    import jax
    path = os.path.join(ROOT, ".jax_cache", "bench")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Compilations and traces in this process, by JAX's own events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.count += 1


class GcPauses:
    """Pauses of Python's garbage collector in this process: the longest
    before the window and inside it, and inside it a ``python.gc`` span
    around each collection, so that a trace names an idle gap it causes."""

    def __init__(self):
        self.in_window = False
        self.setup_max = self.window_max = 0.0
        self.window_n = 0
        self._start = self._span = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        import jax
        if phase == "start":
            self._start = time.perf_counter()
            if self.in_window:
                self._span = jax.profiler.TraceAnnotation("python.gc")
                self._span.__enter__()
            return
        if self._start is None:
            return
        pause = time.perf_counter() - self._start
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self.in_window:
            self.window_n += 1
            self.window_max = max(self.window_max, pause)
        else:
            self.setup_max = max(self.setup_max, pause)

    def close(self):
        """Stop watching."""
        gc.callbacks.remove(self._on)

    def line(self) -> str:
        """One line for stderr."""
        return (f"gc: longest pause {self.setup_max:.4f} s before the "
                f"window; {self.window_n} collections inside it, longest "
                f"{self.window_max:.4f} s")


def load_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"missing bench/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def key_of(seed: int, salt: int):
    """A PRNG key from a seed of up to 64 bits and a salt."""
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), salt)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices=None, peaks=None, bench_path: str = None,
             data_dir: str = HERE) -> dict:
    """Set-up, window and check of one cell; the result dict."""
    import jax

    import check
    import opcount
    from drive_actor_learner import ActorLearnerDriver, replicas_equal

    spec = load_cell(name, bench_path, data_dir)
    cell, policy, traffic = spec["cell"], spec["policy"], spec["traffic"]
    chips = cell["chips"]
    if devices is None:
        devices, peaks = find_devices(chips)
    counter = CompileCounter()
    pauses = GcPauses()
    mesh = None
    if chips > 1:
        mesh = jax.make_mesh((chips,), ("actor",), devices=devices)

    # -- set-up -------------------------------------------------------------
    drv = ActorLearnerDriver(policy, traffic, mesh)
    (state, env_state, obs, key), stash = check.first_iterations(
        drv, seed, traffic, key_of)
    iters_before = traffic["check_iters"]
    # a mesh compiles the chunk again for the shardings its outputs carry:
    # run it until one call compiles nothing
    for _ in range(WARMUP_CALLS):
        iters_before += traffic["steps_per_call"]
        before = counter.count
        t_call = time.perf_counter()
        state, env_state, obs, key, metrics = drv.chunk(state, env_state,
                                                        obs, key)
        jax.block_until_ready(metrics)
        chunk_s = time.perf_counter() - t_call
        if counter.count == before:
            break
    else:
        raise BenchError(f"the chunk still compiles after {WARMUP_CALLS} "
                         f"warm-up calls")

    # chunks queued behind the one waited for: the last warm-up call,
    # which compiled nothing, times one chunk
    ahead = max(1, int(AHEAD_S // chunk_s))
    gc.collect()
    gc.freeze()

    # -- window -------------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    spans = []
    compiles_before = counter.count
    if trace:
        jax.profiler.start_trace(trace_dir)
    pauses.in_window = True
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    n_chunks = 0
    pending = collections.deque()

    def wait_oldest():
        t_wait = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(pending.popleft())
        t_done = time.perf_counter()
        spans.append(("bench.wait", t_wait - t0, t_done - t0))
        return t_done

    try:
        while True:
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, env_state, obs, key, metrics = drv.chunk(
                    state, env_state, obs, key)
            spans.append(("bench.dispatch", t_call - t0,
                          time.perf_counter() - t0))
            pending.append(metrics)
            n_chunks += 1
            if len(pending) > ahead:
                wait_oldest()
            if time.perf_counter() - t0 >= seconds:
                break
        while pending:
            t_done = wait_oldest()
        window_s = t_done - t0
    finally:
        pauses.in_window = False
        if trace:
            jax.profiler.stop_trace()
        pauses.close()
        gc.unfreeze()
    in_window = counter.count - compiles_before
    if in_window:
        raise BenchError(f"{in_window} compilations inside the window")

    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    if mesh is not None:
        stash["replica_gap"] = replicas_equal(state.learner.params)
    del state, env_state, obs, metrics

    n_iters = n_chunks * traffic["steps_per_call"]
    work = opcount.work(policy, traffic, chips, n_iters, opcount.pushes(
        iters_before, n_iters, traffic["sync_every"]))
    result_metrics = {}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, len(devices),
                                      tuple(work["kernels"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": red, "spans": spans, "work": work, "chips": chips,
               "peaks": peaks}
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = trace_reduce.breakdown(red)
    else:
        e2e = {
            "env_steps_per_s": work["env_steps"] * chips / window_s,
            "learner_updates_per_s": work["updates"] / window_s,
            "setup_s": setup_s,
        }
        for m in spec["end_to_end"]:
            result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}

    # -- correctness --------------------------------------------------------
    numbers = check.judge(stash, policy, traffic, key_of(seed, 0))
    if "replica_gap" in stash:
        numbers["replica_gap"] = stash["replica_gap"]
    limits = spec["limits"]
    correct = check.compare(numbers, limits)
    out = {"correct": bool(correct), "attempted": n_chunks, "failed": 0,
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    print(f"window: {n_chunks} chunks in {window_s:.4f} s, {ahead} queued "
          f"behind the one waited for", file=sys.stderr)
    print(pauses.line(), file=sys.stderr)
    return out


def main(argv=None) -> int:
    """Run one cell once; print its result line (exit 0), or exit 2 with
    no result where the run cannot produce one."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
        devices, peaks = find_devices(spec["cell"]["chips"])
        enable_compile_cache()
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), devices=devices, peaks=peaks)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    with contextlib.suppress(BrokenPipeError):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
