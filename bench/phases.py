"""Device time of each phase of the actor-learner iteration, from a trace.

The program wraps each phase of an iteration in a ``jax.named_scope``
(``repro.rl.common.PHASES``); the name lands in the HLO ``op_name`` of
every operation traced under it, and the TPU trace carries that
``op_name`` as the ``tf_op`` stat of each device event's metadata
(``xplane.py`` reads it).  Phases nest (``replay_sample`` runs inside
``learner_update``): the innermost phase named in an operation's
``op_name`` owns it.  An operation whose ``op_name`` names no phase (scan
bookkeeping, key splits) counts as ``other``.

Some operations carry no ``op_name`` at all: XLA's TPU passes make them
without one (the scatter fusions of a replay insert, multi-output
fusions, layout and prefetch copies).  Such an operation belongs to the
phase of the operations that run next to it on the device: where the
nearest operations before and after it that carry an ``op_name`` own the
same phase, so does it; otherwise it counts as ``other``.

``reduce_phases`` takes the same leaf operations, window and chips as
``trace_reduce.reduce_profile``: each instant of device busy time counts
once, for the operation that started first, so the phases sum to
``busy_s``.  ``idle_gap_events`` names the longest idle gaps of the first
chip by the host events that overlap them.

    python bench/phases.py <trace dir or .xplane.pb> [--chips n]
        [--iterations n]

prints both, the phases per iteration where ``--iterations`` is given.
"""
from __future__ import annotations

import argparse
import re
import sys
from typing import Dict, List, Tuple

import trace_reduce
import xplane

# the program's phase names (``repro.rl.common.PHASES``)
PHASES = ("actor_forward", "env_step", "replay_insert", "replay_sample",
          "learner_update", "param_push")
OTHER = "other"
HOST_PREFIX = "/host:"
TOP_OPS = 10
_COMPONENT = re.compile(r"(?:[\w.-]+\()*([\w.-]*)\)*")


def phase_of(op_name: str) -> str:
    """The innermost phase an ``op_name`` names, or ``other``.

    A path component names a phase bare (``learner_update``) or inside
    transformations (``jvp(replay_sample)``, ``transpose(jvp(...))``)."""
    found = OTHER
    for comp in op_name.split("/"):
        m = _COMPONENT.fullmatch(comp)
        if m and m.group(1) in PHASES:
            found = m.group(1)
    return found


def _window(space, planes) -> Tuple[int, int]:
    """``trace_reduce.reduce_profile``'s window: the benchmark's spans, or
    the operations' extent where the trace has none."""
    window = [s for s in trace_reduce.host_spans(space)
              if s[0] in trace_reduce.WINDOW_SPANS]
    if window:
        return window[0][1], max(e for _, _, e in window)
    evs = [(ev.start_ns, ev.end_ns) for p in planes for line in p.lines
           if line.name == trace_reduce.OPS_LINE for ev in line.events]
    return min(s for s, _ in evs), max(e for _, e in evs)


def _leaf_ops(plane, lo: int, hi: int):
    """``(start, end, event)`` of the plane's leaf operations, clipped to
    the window, as ``trace_reduce.reduce_profile`` counts them."""
    out = []
    for line in plane.lines:
        if line.name != trace_reduce.OPS_LINE:
            continue
        for ev in line.events:
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            if trace_reduce.parse_op(ev.name)[1] in \
                    trace_reduce.CONTROL_FLOW:
                continue
            out.append((s, e, ev))
    out.sort(key=lambda t: (t[0], t[1]))
    return out


def _owners(leaf) -> Tuple[List[str], List[bool]]:
    """Each leaf operation's phase, and whether it carries no ``op_name``.
    An operation without one takes the phase its nearest neighbours with
    one agree on (module docstring)."""
    names = [xplane.tf_op(ev) for _, _, ev in leaf]
    own = [phase_of(n) if n else None for n in names]
    before, last = [], None
    for ph in own:
        before.append(last)
        last = ph if ph is not None else last
    after, nxt = [None] * len(own), None
    for i in range(len(own) - 1, -1, -1):
        after[i] = nxt
        nxt = own[i] if own[i] is not None else nxt
    out = [ph if ph is not None else
           (b if b is not None and b == a else OTHER)
           for ph, b, a in zip(own, before, after)]
    return out, [not n for n in names]


def reduce_phases(space, n_devices: int) -> Dict:
    """Device seconds by phase, averaged over the chips used.

    ``phases`` holds every phase of ``PHASES`` and ``other``;
    ``named`` is False where no operation names a phase (a program without
    the scopes); ``unnamed_s`` is the time of operations without an
    ``op_name``, and ``inferred_s`` the part of it given to a phase by
    their neighbours; ``ops`` holds each phase's busiest operations
    (``trace_reduce`` labels, seconds)."""
    planes = trace_reduce.device_planes(space, n_devices)
    if not planes:
        raise ValueError("the trace has no device plane with XLA Ops")
    lo, hi = _window(space, planes)
    n = len(planes)
    ns = {p: 0.0 for p in PHASES + (OTHER,)}
    ops: Dict[str, Dict[str, float]] = {p: {} for p in ns}
    named = False
    busy = unnamed = inferred = 0.0
    for plane in planes:
        leaf = _leaf_ops(plane, lo, hi)
        owners, bare = _owners(leaf)
        covered = lo
        for (s, e, ev), ph, no_name in zip(leaf, owners, bare):
            own = e - max(s, covered)
            covered = max(covered, e)
            if own <= 0:
                continue
            named |= ph != OTHER and not no_name
            ns[ph] += own / n
            busy += own / n
            if no_name:
                unnamed += own / n
                inferred += own / n if ph != OTHER else 0.0
            label = ev.name[:trace_reduce.LABEL_CHARS]
            ops[ph][label] = ops[ph].get(label, 0.0) + own * 1e-9 / n
    top = {p: sorted(d.items(), key=lambda kv: -kv[1])[:TOP_OPS]
           for p, d in ops.items()}
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "phases": {p: v * 1e-9 for p, v in ns.items()},
            "named": named, "unnamed_s": unnamed * 1e-9,
            "inferred_s": inferred * 1e-9, "ops": top}


def phase_ms(red: Dict, iterations: int) -> Dict[str, float]:
    """Each phase's device ms per iteration; empty where the trace names
    no phase or holds no iteration."""
    if not red["named"] or iterations <= 0:
        return {}
    return {p: 1e3 * s / iterations for p, s in red["phases"].items()}


def idle_gap_events(space, n_devices: int = 1, top: int = 10,
                    per_gap: int = 8) -> List[Dict]:
    """The first chip's ``top`` longest idle gaps inside the window, each
    with the host events that overlap it, longest overlap first:
    ``{"start_s": from the window's start, "gap_s", "events": [(name,
    overlap_s)]}``."""
    planes = trace_reduce.device_planes(space, n_devices)
    lo, hi = _window(space, planes)
    busy = trace_reduce._union([(s, e) for s, e, _ in
                                _leaf_ops(planes[0], lo, hi)])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((ge - gs, gs, ge) for gs, ge in
                   zip(edges[0::2], edges[1::2]) if ge > gs),
                  reverse=True)[:top]
    host = [ev for p in space.planes if p.name.startswith(HOST_PREFIX)
            for line in p.lines for ev in line.events]
    out = []
    for length, gs, ge in gaps:
        over: Dict[str, int] = {}
        for ev in host:
            o = min(ev.end_ns, ge) - max(ev.start_ns, gs)
            if o > 0:
                over[ev.name] = max(over.get(ev.name, 0), o)
        names = sorted(over.items(), key=lambda kv: -kv[1])[:per_gap]
        out.append({"start_s": (gs - lo) * 1e-9, "gap_s": length * 1e-9,
                    "events": [(k, v * 1e-9) for k, v in names]})
    return out


def format_gaps(gaps: List[Dict]) -> str:
    """The gaps as lines of text, one per gap."""
    return "\n".join(
        f"idle gap {g['gap_s'] * 1e3:.3f} ms at {g['start_s']:.4f} s: "
        + "; ".join(f"{name[:60]} {s * 1e3:.3f} ms"
                    for name, s in g["events"])
        for g in gaps)


def main(argv=None) -> int:
    """Print a trace's phases and its longest idle gaps."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--iterations", type=int, default=0)
    args = ap.parse_args(argv)
    space = xplane.read(args.trace)
    red = reduce_phases(space, args.chips)
    per_iter = phase_ms(red, args.iterations)
    print(f"window {red['window_s']:.6f} s, busy {red['busy_s']:.6f} s, "
          f"phases named: {red['named']}; without an op_name "
          f"{red['unnamed_s']:.6f} s, {red['inferred_s']:.6f} s of it "
          f"given to a phase by its neighbours")
    for p, s in red["phases"].items():
        extra = f"  {per_iter[p]:.4f} ms/iter" if per_iter else ""
        print(f"{p:16s} {s:.6f} s{extra}")
        for label, v in red["ops"][p][:3]:
            print(f"    {v:.6f} s  {label[:100]}")
    print(format_gaps(idle_gap_events(space, args.chips)), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
