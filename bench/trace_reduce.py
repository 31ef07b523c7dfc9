"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Planes named ``/device:TPU:<n>`` are the chips; their ``XLA Ops`` line
holds one event per operation run on the device.  The host plane holds the
benchmark's own spans (``bench.dispatch``, ``bench.wait``, and
``python.gc`` around each collection of Python's garbage collector inside
the window), written with ``jax.profiler.TraceAnnotation`` on the same
clock.

``reduce_profile`` gives, averaged over the chips used:

* ``window_s``: the traced window, from the first ``bench.dispatch`` to the
  end of the last ``bench.wait``;
* ``busy_s``: the union of the device's operation intervals inside it;
* ``ops``: device seconds by operation (the first 160 characters of its
  HLO text: name, shape, opcode and first operands);
* ``kernels``: device seconds of the int8 Pallas kernels the cell's work
  names (the keys of ``opcount.work``'s ``kernels``, which the policy
  kind counts), by kernel: a ``custom-call`` whose name carries the
  kernel's (``int8_matmul.36``, ``vmap_jit_fused_qmlp__.1``);
* ``collective_s``: device seconds of all-reduce, all-gather,
  reduce-scatter, all-to-all and collective-permute operations;
* ``idle_by_span`` and ``longest_gaps``: the idle intervals inside the
  window, each named by the innermost host span it falls in.

An event's name is its HLO instruction, ``%name = shape opcode(operands)``.
Control flow (``while``, ``conditional``, ``call``) spans the operations
it runs and is left out, so each interval counted is one leaf operation.

Run as a script on a trace directory to print its planes, lines and
busiest operations, which is how the names above were read.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPANS = ("bench.dispatch", "bench.wait")
HOST_SPANS = WINDOW_SPANS + ("python.gc",)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
CONTROL_FLOW = ("while", "conditional", "call")
LABEL_CHARS = 160
_OPCODE = re.compile(r"([a-z][a-z0-9_-]*)\(")


def load(path: str):
    """ProfileData of the one ``.xplane.pb`` under ``path`` (a file or a
    profiler log directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return ProfileData.from_file(path)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def parse_op(hlo: str) -> Tuple[str, str]:
    """``(name, opcode)`` of an HLO instruction's text."""
    name, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest)
    return name.lstrip("%"), (m.group(1) if m else "")


def kernel_of(name: str, opcode: str, kernels: Sequence[str]):
    """The first of ``kernels`` whose name an operation's carries, where
    the operation is a ``custom-call``; else None."""
    if opcode != "custom-call":
        return None
    for k in kernels:
        if k in name:
            return k
    return None


def is_collective(opcode: str) -> bool:
    """An opcode of a collective between chips (sync or async form)."""
    return opcode.startswith(COLLECTIVES)


def device_planes(profile, n_devices: int):
    """The first ``n_devices`` TPU planes that carry operations."""
    planes = [p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)
              and any(line.name == OPS_LINE for line in p.lines)]
    planes.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    return planes[:n_devices]


def host_spans(profile) -> List[Tuple[str, int, int]]:
    """The benchmark's own host spans, ``(name, start_ns, end_ns)``."""
    out = []
    for p in profile.planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda s: s[1])


def reduce_profile(profile, n_devices: int, kernels: Sequence[str]
                   ) -> Dict:
    """The numbers of the module docstring, from a loaded profile;
    ``kernels`` are the names of the kernels to count."""
    spans = host_spans(profile)
    planes = device_planes(profile, n_devices)
    if not planes:
        raise ValueError("the trace has no device plane with XLA Ops")
    window = [s for s in spans if s[0] in WINDOW_SPANS]
    if window:
        lo, hi = window[0][1], max(e for _, _, e in window)
    else:
        evs = [(ev.start_ns, ev.end_ns) for p in planes for line in p.lines
               if line.name == OPS_LINE for ev in line.events]
        lo, hi = min(s for s, _ in evs), max(e for _, e in evs)
    n = len(planes)
    busy_ns, coll_ns = 0, 0
    ops: Dict[str, float] = {}
    by_kernel: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    longest: List[Tuple[float, str]] = []
    for p in planes:
        ivs = []
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                name, opcode = parse_op(ev.name)
                if e <= s or opcode in CONTROL_FLOW:
                    continue
                ivs.append((s, e))
                dur = (e - s) / n
                label = ev.name[:LABEL_CHARS]
                ops[label] = ops.get(label, 0.0) + dur
                k = kernel_of(name, opcode, kernels)
                if k is not None:
                    by_kernel[k] = by_kernel.get(k, 0.0) + dur
                if is_collective(opcode):
                    coll_ns += dur
        busy = _union(ivs)
        busy_ns += sum(e - s for s, e in busy) / n
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) // 2
            label = max(((s, name) for name, s, e in spans
                         if s <= mid < e), default=(0, "outside spans"))[1]
            gaps[label] = gaps.get(label, 0.0) + (ge - gs) / n
            longest.append(((ge - gs) / n, label))
    longest.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "ops": {k: v * 1e-9 for k, v in ops.items()},
        "kernels": {k: v * 1e-9 for k, v in by_kernel.items()},
        "collective_s": coll_ns * 1e-9,
        "idle_by_span": {k: v * 1e-9 for k, v in gaps.items()},
        "longest_gaps": [(lbl, s * 1e-9) for s, lbl in longest[:10]],
    }


def reduce_dir(path: str, n_devices: int, kernels: Sequence[str]) -> Dict:
    """``reduce_profile`` of the trace under ``path``."""
    return reduce_profile(load(path), n_devices, kernels)


def breakdown(red: Dict) -> Dict:
    """The ten busiest device operations and the ten longest idle gaps."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in red["longest_gaps"]]}


def dump(path: str, top: int = 40):
    """Print a trace's planes and lines and its busiest operations."""
    prof = load(path)
    for p in prof.planes:
        lines = [(line.name, len(list(line.events))) for line in p.lines]
        print(f"plane {p.name!r}: {lines}")
    for p in prof.planes:
        if not p.name.startswith(DEVICE_PREFIX):
            continue
        tot: Dict[str, float] = {}
        example = {}
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns
                example.setdefault(ev.name, list(ev.stats))
        print(f"== {p.name}: {len(tot)} op names")
        for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
            stats = {k: (v if not isinstance(v, str) else v[:160])
                     for k, v in example[name]}
            print(f"{ns * 1e-6:10.3f} ms  {name}  {stats}")
        break


if __name__ == "__main__":
    dump(sys.argv[1])
