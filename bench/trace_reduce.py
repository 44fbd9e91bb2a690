"""Reduction of a JAX profiler trace of the measured window to the numbers
the benchmark reports: the device's busy and idle seconds, device time per
program and per operation, the calls of named kernels with their operand
shapes, and the idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a plain
dict (``normalize``); ``reduce`` works on that dict alone, so a small
recorded trace kept as JSON tests it (``tests/test_bench_trace.py``).

- The window is the host annotation ``bench.window`` that ``run.py`` puts
  around the measured loop.
- Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, named by their HLO instruction text; the
  programs are the events of its ``XLA Modules`` line, and an operation
  belongs to the program whose event holds its start. Busy time is the
  union of the operations' intervals inside the window, averaged over the
  chips.
- A kernel call is an operation whose HLO instruction is named after the
  kernel (a Pallas call is named after its wrapper, e.g.
  ``%int8_roundtrip_pallas.1 = f32[18432,256] custom-call(...)``); its
  shapes are read from that text.
- An idle gap is a stretch of the window on the first chip with no
  operation running. It is labelled by the innermost event of the host
  thread that ran the window that covers its midpoint: a JAX runtime
  event (a jitted call's dispatch, a host transfer, a wait) or, where the
  host ran Python outside any of them, the benchmark's ``bench.call``.
  Events of one thread nest, so one sweep finds them.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def normalize(profile) -> dict:
    """{'devices': {plane: {'ops': [[hlo text, start_ns, dur_ns], ...],
    'modules': [[program, start_ns, dur_ns], ...]}}, 'host': {thread:
    [[name, start_ns, dur_ns], ...]}} from a ``ProfileData``."""
    out = {"devices": {}, "host": {}}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events if e.duration_ns > 0]
                if evs:
                    out["host"][line.name] = evs
    return out


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return normalize(ProfileData.from_file(max(files, key=os.path.getmtime)))


def shapes(hlo: str) -> list:
    """Every array shape in an HLO instruction's text, in order: the
    results first, then the operands. [(dtype, (dims...)), ...]"""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(hlo)]


def program_name(module: str) -> str:
    """'jit_step(1143...)' -> 'jit_step'."""
    return module.split("(")[0]


def op_label(hlo: str) -> str:
    """'%fusion.12 = f32[8,128]{...} fusion(...)' -> 'fusion.12
    f32[8,128]': the instruction and its first result shape."""
    name, _, rest = hlo.partition(" = ")
    first = _SHAPE.search(rest)
    return (name.lstrip("%") + (f" {first.group(0)}" if first else ""))[:120]


def _merge(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _labels(events, times) -> list:
    """For each of the ascending ``times``, the innermost of the nested
    ``events`` that covers it."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][1] <= t:
            while stack and stack[-1][1] + stack[-1][2] < events[i][1]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else "(no host event)")
    return out


def _owner(modules, starts, t) -> str:
    """The program whose event holds time t ('' if none)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1] + modules[i][2]:
        return program_name(modules[i][0])
    return ""


def reduce(trace: dict, n_devices: int = 1, top: int = 10,
           kernels: tuple = ()) -> dict:
    """Busy and window seconds; the ``top`` programs, operations and idle
    gaps by seconds; and for each name in ``kernels`` its calls inside the
    window as [(seconds, shapes), ...]."""
    wins = [(s, s + d, thread) for thread, evs in trace["host"].items()
            for name, s, d in evs if name == WINDOW]
    if not wins:
        raise ValueError("no bench.window annotation in the trace")
    w0, w1, thread = wins[0]
    planes = sorted(trace["devices"])[:n_devices]
    if not planes:
        raise ValueError("no TPU device plane in the trace")
    busy, first_busy = [], None
    per_op, per_program = collections.Counter(), collections.Counter()
    calls = {k: [] for k in kernels}
    for p in planes:
        modules = sorted(trace["devices"][p]["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        ivs = []
        for name, s, d in trace["devices"][p]["ops"]:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            prog = _owner(modules, starts, s)
            per_op[f"{prog}:{op_label(name)}"] += (b - a) * 1e-9
            per_program[prog or "(no program)"] += (b - a) * 1e-9
            for k in kernels:
                if name.startswith(f"%{k}"):
                    calls[k].append(((b - a) * 1e-9, shapes(name)))
        merged = _merge(ivs)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    edges = [w0] + [t for iv in first_busy for t in iv] + [w1]
    spans = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = collections.Counter()
    for (a, b), name in zip(spans, _labels(trace["host"][thread],
                                           [(a + b) / 2 for a, b in spans])):
        gaps[name] += (b - a) * 1e-9
    n = len(planes)
    return {"busy_s": sum(busy) / n, "window_s": (w1 - w0) * 1e-9,
            "programs": [[k, v / n] for k, v in per_program.most_common(top)],
            "device_ops": [[k, v / n] for k, v in per_op.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(top)],
            "kernel_calls": calls}


def reduce_dir(trace_dir: str, n_devices: int = 1,
               kernels: tuple = ()) -> dict:
    return reduce(load(trace_dir), n_devices, kernels=kernels)
