"""Reduction of the program's own spans and scopes in a traced window,
beside ``trace_reduce`` (whose keys it leaves as they are).

- An idle gap of the first chip (as ``trace_reduce`` cuts them) is
  labelled by the innermost host span named ``s2fl.*``
  (``repro.observe.spans``) that covers its midpoint, runtime events
  left out; a gap in none is ``(outside)``: ``span_idle``, seconds by
  span.
- ``span_count``: the ``s2fl.*`` spans that start inside the window;
  ``module_count``: the first chip's programs (``XLA Modules`` events)
  that start inside it.
- An operation's scope path is the ``jax.named_scope`` path XLA keeps in
  its metadata. ``ProfileData`` does not show an event's metadata, so
  ``load`` reads it (``tf_op``) from the trace-event JSON the profiler
  writes beside the ``.xplane.pb``, matched by plane and the op's
  ``device_offset_ps``. Each instant of device time goes to the
  innermost op running (a ``while`` op holds the ops of its body), and
  an op's own time to the first of ``SCOPES`` that is a component of its
  path: ``scope_busy``, seconds by scope, which add up to at most
  ``busy_s``.

``install`` puts ``reduce_dir`` in the place of
``trace_reduce.reduce_dir``, which ``run.py`` calls on a traced window:
the metrics that read these keys (``metrics/_spans.py``) install it when
they are loaded, before the window runs.
"""
from __future__ import annotations

import collections
import functools
import glob
import gzip
import json
import os
import re

import trace_reduce

SPAN_PREFIX, OUTSIDE = "s2fl.", "(outside)"
SCOPES = ("attention", "ffn", "head", "regroup", "sgd")
_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")


@functools.lru_cache(maxsize=1 << 16)
def scope_of(path: str) -> str:
    """The first of ``SCOPES`` that is a component of an op's scope path,
    with JAX's transform wrappers taken off each component
    ('jit(f)/transpose(jvp(attention))/dot_general' -> 'attention'); ''
    if none is."""
    parts = set()
    for part in path.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        parts.add(part)
    return next((s for s in SCOPES if s in parts), "")


def self_seconds(intervals) -> list:
    """Each interval's own time: the instants in which it is the
    innermost (latest started) of the intervals running. The own times
    add up to the union."""
    own = [0.0] * len(intervals)
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    stack, t = [], None

    def close_until(x):
        nonlocal t
        while stack and intervals[stack[-1]][1] <= x:
            j = stack.pop()
            end = intervals[j][1]
            if end > t:
                own[j] += end - t
                t = end
        if stack and x > t:
            own[stack[-1]] += x - t
            t = x

    for i in order:
        a = intervals[i][0]
        t = a if t is None else t
        close_until(a)
        t = max(t, a)
        stack.append(i)
    close_until(float("inf"))
    return own


def op_scopes(trace_json: str) -> dict:
    """{(device plane, device_offset_ps): scope path} of the ``XLA Ops``
    events that carry one (``tf_op``) in a trace-event JSON file; {} if
    there is no such file."""
    if not os.path.exists(trace_json):
        return {}
    with gzip.open(trace_json, "rt") as f:
        events = json.load(f)["traceEvents"]
    planes, lines = {}, set()
    for e in events:
        if e.get("ph") == "M" and e["name"] == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e["name"] == "thread_name" and \
                e["args"]["name"] == trace_reduce.OPS_LINE:
            lines.add((e["pid"], e["tid"]))
    return {(planes[e["pid"]], int(e["args"]["device_offset_ps"])):
            e["args"]["tf_op"] for e in events
            if e.get("ph") == "X" and (e["pid"], e.get("tid")) in lines
            and "tf_op" in e.get("args", {})
            and "device_offset_ps" in e["args"]}


def _offset_ps(event) -> int:
    for k, v in event.stats:
        if k == "device_offset_ps":
            return int(v)
    return -1


def load(trace_dir: str) -> dict:
    """``trace_reduce.normalize`` of the newest trace under ``trace_dir``,
    each device plane with ``op_scopes``: the scope path of each of its
    ``ops`` ('' where none is known)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = max(files, key=os.path.getmtime)
    profile = ProfileData.from_file(path)
    trace = trace_reduce.normalize(profile)
    scopes = op_scopes(path[:-len(".xplane.pb")] + ".trace.json.gz")
    for plane in profile.planes if scopes else ():
        for line in plane.lines:
            if plane.name in trace["devices"] and \
                    line.name == trace_reduce.OPS_LINE:
                trace["devices"][plane.name]["op_scopes"] = [
                    scopes.get((plane.name, _offset_ps(e)), "")
                    for e in line.events]
    return trace


def reduce(trace: dict, n_devices: int = 1) -> dict:
    """``span_idle``, ``span_count``, ``module_count`` and ``scope_busy``
    of the window (see the module's text)."""
    w0, w1, thread = next(
        (s, s + d, thread) for thread, evs in trace["host"].items()
        for name, s, d in evs if name == trace_reduce.WINDOW)
    planes = sorted(trace["devices"])[:n_devices]
    spans = [e for e in trace["host"][thread]
             if e[0].startswith(SPAN_PREFIX)]
    per_scope, first = collections.Counter(), None
    for p in planes:
        dev = trace["devices"][p]
        paths = dev.get("op_scopes") or [""] * len(dev["ops"])
        ivs, scoped = [], []
        for (_, s, d), path in zip(dev["ops"], paths):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                ivs.append((a, b))
                scoped.append(scope_of(path))
        for scope, secs in zip(scoped, self_seconds(ivs)):
            if scope:
                per_scope[scope] += secs * 1e-9
        if first is None:
            first = trace_reduce._merge(ivs)
    edges = [w0] + [t for iv in first for t in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    span_idle = collections.Counter()
    for (a, b), name in zip(gaps, trace_reduce._labels(
            spans, [(a + b) / 2 for a, b in gaps])):
        span_idle[name if name.startswith(SPAN_PREFIX) else OUTSIDE] += \
            (b - a) * 1e-9
    n = len(planes)
    return {"span_idle": dict(span_idle),
            "span_count": dict(collections.Counter(
                name for name, s, _ in spans if w0 <= s < w1)),
            "module_count": sum(w0 <= s < w1 for _, s, _ in
                                trace["devices"][planes[0]]["modules"]),
            "scope_busy": {k: v / n for k, v in per_scope.items()}}


def reduce_dir(trace_dir: str, n_devices: int = 1,
               kernels: tuple = ()) -> dict:
    """``trace_reduce.reduce_dir``'s result with this reduction's keys."""
    trace = load(trace_dir)
    out = trace_reduce.reduce(trace, n_devices, kernels=kernels)
    out.update(reduce(trace, n_devices))
    return out


def install():
    trace_reduce.reduce_dir = reduce_dir
