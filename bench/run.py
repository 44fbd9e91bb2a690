#!/usr/bin/env python3
"""The on-chip benchmark of the S²FL trainer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). The mix names the path
(``bench/paths/<path>.py``) that drives one entry of the program, and
every metric is read by a file of its own (``bench/metrics/<name>.py``).
So a new cell, mix, path or metric is new files, found here by name.

One run: refuse anything but a TPU with the chips the cell asks for;
build the cell and warm every program its window will call (set-up,
``setup_s``); call the path back to back for ``--seconds`` seconds;
read the peak device memory; free the program's state; compare what
the first steps produced with the plain reference (``correct``). The
last line of standard output is one JSON object. With ``--trace 1`` the
window runs under the JAX profiler and the line carries the per-layer
metrics, the device's busy and window seconds and a ``breakdown``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def metrics_of(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


class CompileClock:
    """Sums JAX's own compile-time events (tracing, lowering, backend
    compilation) and counts them, so set-up and the window can report
    their compile seconds and the window its compiles."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax
        self.secs = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.count = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        k = self.EVENTS.get(event)
        if k:
            self.secs[k] += secs
            self.count[k] += 1

    def snapshot(self) -> dict:
        return {"secs": dict(self.secs), "count": dict(self.count)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``<checkout>/.jax_cache``: the path is
    part of the cache key, so it never moves. Every program is kept,
    however fast it compiled, and none is evicted: a size cap below what
    one cell compiles (one InternLM2 step program is about 100 MB) makes
    the cache evict the programs a run has just written, and every run
    compile again."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def peaks_of(device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; a device kind missing
    from the table is an error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def run_window(runner, seconds: float) -> dict:
    """Run the path back to back until ``seconds`` have passed. A path
    with ``dispatch`` and ``wait`` keeps up to ``runner.ahead`` units of
    work queued on the device beyond the one it waits for, so that a host
    stall does not idle the chip; when the time is up it sends nothing
    more and waits for all it sent. Any other path's ``call`` returns once
    its work is done. Either way the window ends with all its work done,
    and the clock is read after that."""
    import jax
    ahead = hasattr(runner, "dispatch")
    calls, pending = [], []
    start = t1 = time.perf_counter()
    end = start + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                if not ahead:
                    calls.append(runner.call())
                else:
                    if t0 < end:
                        pending.append(runner.dispatch())
                    if pending and (t0 >= end or
                                    len(pending) > runner.ahead):
                        calls.append(runner.wait(pending.pop(0)))
            t1 = time.perf_counter()
            if t1 >= end and not pending:
                break
    return {"window_s": t1 - start, "attempted": len(calls),
            "work": float(sum(calls))}


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = find_cell(spec, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s). "
            "Nothing was run.")
        return 2
    devices = devices[:cell["chips"]]
    peaks = peaks_of(devices[0].device_kind)
    log(f"[bench] compile cache {enable_compile_cache()}")
    out = run_cell(spec, cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices, peaks=peaks)
    print(json.dumps(out), flush=True)
    return 0


def run_cell(spec, cell, *, seed, seconds, trace, devices, peaks) -> dict:
    """Everything of one run after the look for the chip: returns the
    result line's object."""
    import jax
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    path = load_module("paths", traffic["path"])
    kind = "per_layer" if trace else "end_to_end"
    wanted = metrics_of(spec, cell["name"], kind)
    readers = {m["name"]: load_module("metrics", m["name"]) for m in wanted}
    kind_name = devices[0].device_kind
    clock = CompileClock()
    log(f"[bench] {cell['name']} seed {seed} on {len(devices)} x "
        f"{kind_name}")

    t0 = time.perf_counter()
    runner = path.Runner(config, traffic, cell=cell["name"], seed=seed,
                         devices=devices, log=log)
    runner.setup()
    setup_s = time.perf_counter() - t0
    c_setup = clock.snapshot()
    log(f"[bench] setup_s={setup_s!r} compile={c_setup}")

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # the runtime's host events, without one event per Python call:
        # that tracer would slow the host loop it is meant to explain
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win = run_window(runner, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    c_win = clock.snapshot()
    compiles_in_window = c_win["count"]["compile"] - c_setup["count"][
        "compile"]
    memory = peak_memory(devices)
    log(f"[bench] window_s={win['window_s']!r} calls={win['attempted']} "
        f"work={win['work']!r} {runner.unit} compiles_in_window="
        f"{compiles_in_window} memory_peak_bytes={memory}")

    reduced = None
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(
            trace_dir, n_devices=len(devices),
            kernels=tuple(r.KERNEL for r in readers.values()
                          if hasattr(r, "KERNEL")))
        log(f"[bench] device time by program: {reduced['programs']}")
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {"setup_s": setup_s, "window_s": win["window_s"],
           "work": win["work"],
           "unit": runner.unit, "flops_per_unit": runner.flops_per_unit(),
           "peaks": peaks,
           "compiles_in_window": compiles_in_window, "trace": reduced,
           "n_devices": len(devices)}
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(ctx)
        if value is None:
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    runner.free()
    try:
        checks = runner.check()
        correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                      for c in checks) and bool(checks)
    except Exception as e:  # the check itself failing is not correct
        import traceback
        traceback.print_exc()
        log(f"[bench] check raised {type(e).__name__}: {e}")
        checks, correct = [], False
    for c in checks:
        log(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r})")

    dev = {"platform": devices[0].platform, "kind": kind_name,
           "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": runner.failed, "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                            "idle_gaps": reduced["idle_gaps"][:10]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


if __name__ == "__main__":
    sys.exit(main())
