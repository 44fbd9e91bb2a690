"""The reduction of the program's own spans and scopes: idle gaps by the
innermost ``s2fl.*`` span, spans and programs started in the window,
device seconds by scope; the per-layer metrics that read them, which
split ``idle_frac.cnn`` whole; and the span names they read, which the
program's span table must hold."""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402
from metrics import _spans  # noqa: E402

US = 1000.0     # ns
IDLE = ("idle_frac.cnn.batch", "idle_frac.cnn.halves",
        "idle_frac.cnn.server_sgd", "idle_frac.cnn.aggregate",
        "idle_frac.cnn.host")


def _reduce(trace):
    """What a traced run's ``trace_reduce.reduce_dir`` returns once the
    span metrics are loaded."""
    out = trace_reduce.reduce(trace)
    out.update(span_reduce.reduce(trace))
    return out


def _trace(spans=True):
    """Window 0..100 us. Device busy 10-20, 30-35, 50-52, 70-75, 95-100
    (us), so idle 0-10, 20-30, 35-50, 52-70, 75-95. The host's round runs
    2-90 inside bench.call: driver 3-60 > execute 4-58 > batch 5-15,
    client_fwd 15-24, server_sgd 33-55 (a runtime event 36-54 inside it);
    aggregate 62-88."""
    ops = [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10 * US, 10 * US],
           ["%dot.2 = f32[8]{0} dot(f32[8]{0} %a)", 30 * US, 5 * US],
           ["%add.3 = f32[8]{0} add(f32[8]{0} %a)", 50 * US, 2 * US],
           ["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p)", 70 * US, 5 * US],
           ["%add.5 = f32[8]{0} add(f32[8]{0} %a)", 95 * US, 5 * US]]
    scopes = ["jit(s2fl_round_step)/jvp(client_fwd)/attention/dot_general",
              "jit(s2fl_round_step)/transpose(jvp(server_loss))/ffn/mul",
              "jit(s2fl_round_step)/jvp(server_loss)/head/reduce_max",
              "jit(s2fl_round_step)/sgd/sub",
              "jit(s2fl_round_step)/jvp(regroup)/gather"]
    modules = [["jit_client_fwd(1)", 9 * US, 12 * US],
               ["jit_multiply(2)", 29 * US, 7 * US],
               ["jit_add(3)", 49 * US, 4 * US],
               ["jit_add(3)", 69 * US, 7 * US],
               ["jit_add(3)", 94 * US, 7 * US],
               ["jit_add(3)", -10 * US, 2 * US]]
    host = [["bench.window", 0.0, 100 * US], ["bench.call", 0.0, 100 * US],
            ["DevicePut", 91 * US, 8 * US]]
    if spans:
        host += [["s2fl.round", 2 * US, 88 * US],
                 ["s2fl.driver", 3 * US, 57 * US],
                 ["s2fl.execute", 4 * US, 54 * US],
                 ["s2fl.batch", 5 * US, 10 * US],
                 ["s2fl.client_fwd", 15 * US, 9 * US],
                 ["s2fl.server_sgd", 33 * US, 22 * US],
                 ["PjitFunction_multiply_", 36 * US, 18 * US],
                 ["s2fl.aggregate", 62 * US, 26 * US],
                 ["s2fl.round", 99 * US, 20 * US]]
    dev = {"ops": ops, "modules": modules}
    if spans:
        dev["op_scopes"] = scopes
    return {"devices": {"/device:TPU:0": dev}, "host": {"python3": host}}


def test_span_idle_counts_and_scopes():
    r = _reduce(_trace())
    # gap midpoints: 5 (round only: 2-90 but driver 3-60, execute 4-58,
    # batch 5-15 starts at 5 -> batch), 25 (execute: client_fwd ended at
    # 24), 42.5 (server_sgd; the runtime event is not a span), 61 (round:
    # driver ended at 60, aggregate starts at 62), 85 (aggregate)
    assert r["span_idle"] == pytest.approx({
        "s2fl.batch": 10e-6, "s2fl.execute": 10e-6,
        "s2fl.server_sgd": 15e-6, "s2fl.round": 18e-6,
        "s2fl.aggregate": 20e-6})
    assert sum(r["span_idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # the runtime event still labels the plain idle gaps
    assert dict(r["idle_gaps"])["PjitFunction_multiply_"] == \
        pytest.approx(15e-6)
    assert r["span_count"] == {
        "s2fl.round": 2, "s2fl.driver": 1, "s2fl.execute": 1,
        "s2fl.batch": 1, "s2fl.client_fwd": 1, "s2fl.server_sgd": 1,
        "s2fl.aggregate": 1}
    assert r["module_count"] == 5          # the one before 0 is out
    assert r["scope_busy"] == pytest.approx({
        "attention": 10e-6, "ffn": 5e-6, "head": 2e-6, "sgd": 5e-6,
        "regroup": 5e-6})


def test_scope_busy_counts_nested_ops_once():
    """A ``while`` op holds its body's ops on the same line: each instant
    goes to the innermost op, so the scopes add up to the busy time."""
    t = _trace()
    dev = t["devices"]["/device:TPU:0"]
    dev["ops"] = [["%while.1 = s32[] while(s32[] %i)", 10 * US, 10 * US],
                  ["%dot.2 = f32[8]{0} dot(f32[8]{0} %a)", 11 * US, 3 * US],
                  ["%mul.3 = f32[8]{0} mul(f32[8]{0} %a)", 15 * US, 4 * US]]
    dev["op_scopes"] = ["jit(f)/jvp(client_fwd)/attention/while",
                        "jit(f)/jvp(client_fwd)/attention/dot_general",
                        "jit(f)/jvp(client_fwd)/ffn/mul"]
    r = _reduce(t)
    assert r["busy_s"] == pytest.approx(10e-6)
    assert r["scope_busy"] == pytest.approx({"attention": 6e-6,
                                             "ffn": 4e-6})


@pytest.mark.parametrize("intervals,own", [
    ([(0, 10), (2, 5), (6, 8)], [5, 3, 2]),      # nested
    ([(0, 2), (3, 5)], [2, 2]),                  # one after another
    ([(0, 5), (3, 8)], [3, 5]),                  # overlapping
    ([(0, 10), (0, 4)], [6, 4]),                 # same start
    ([], [])])
def test_self_seconds(intervals, own):
    assert span_reduce.self_seconds(intervals) == own


def test_gap_outside_every_span():
    t = _trace()
    t["host"]["python3"] = [e for e in t["host"]["python3"]
                            if e[0] != "s2fl.round" or e[1] > 50 * US]
    r = _reduce(t)
    assert r["span_idle"][span_reduce.OUTSIDE] == pytest.approx(18e-6)


def _ctx(trace, unit="samples"):
    return {"trace": trace, "unit": unit, "window_s": 1.0}


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_idle_parts_add_up_to_idle_frac():
    t = _trace()
    # a channel span inside execute holds the 20-30 gap instead
    t["host"]["python3"].append(["s2fl.uplink", 18 * US, 14 * US])
    r = _reduce(t)
    ctx = _ctx(r)
    parts = {n: _read(n, ctx) for n in IDLE}
    assert parts == pytest.approx({
        "idle_frac.cnn.batch": 0.1, "idle_frac.cnn.halves": 0.0,
        "idle_frac.cnn.server_sgd": 0.15, "idle_frac.cnn.aggregate": 0.2,
        "idle_frac.cnn.host": 0.28})
    outside = r["span_idle"].get(span_reduce.OUTSIDE, 0.0) / r["window_s"]
    assert sum(parts.values()) + outside == pytest.approx(
        _read("idle_frac.cnn", ctx))
    assert _read("programs_per_round.cnn", ctx) == 2.5


def test_lm_device_shares():
    r = _reduce(_trace())
    ctx = _ctx(r, "tokens")
    assert _read("device_frac.lm.attention", ctx) == pytest.approx(10 / 27)
    assert _read("device_frac.lm.head", ctx) == pytest.approx(2 / 27)
    assert _read("idle_frac.cnn.batch", ctx) is None


def test_a_program_without_spans_reads_nothing():
    """The parent of this reduction's program, or an untraced run: every
    new metric is absent, and the old keys read as before."""
    bare, full = _reduce(_trace(spans=False)), \
        _reduce(_trace())
    for key in ("busy_s", "window_s", "programs", "device_ops"):
        assert bare[key] == full[key]
    assert bare["span_idle"] == {span_reduce.OUTSIDE: pytest.approx(
        bare["window_s"] - bare["busy_s"])}
    assert bare["scope_busy"] == {}
    for unit in ("samples", "tokens"):
        for ctx in (_ctx(bare, unit), _ctx(None, unit)):
            for name in IDLE + ("programs_per_round.cnn",
                                "device_frac.lm.attention",
                                "device_frac.lm.head"):
                assert _read(name, ctx) is None, (name, unit)


def test_scope_of_takes_transform_wrappers_off():
    assert span_reduce.scope_of(
        "jit(f)/transpose(jvp(attention))/dot_general") == "attention"
    assert span_reduce.scope_of("jit(f)/jvp(server_loss)/head/exp") == \
        "head"
    # an einsum spec or a longer name is not a scope
    assert span_reduce.scope_of("jit(f)/bhsd,head->bs/dot_general") == ""
    assert span_reduce.scope_of("jit(f)/heads/add") == ""
    assert span_reduce.scope_of("") == ""


def test_op_scopes_from_trace_events(tmp_path):
    """The scope paths come from the trace-event JSON beside the
    ``.xplane.pb``, matched by plane and device offset, from the
    ``XLA Ops`` line alone."""
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 4, "name": "thread_name",
         "args": {"name": "Async XLA Ops"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 1.0, "dur": 1.0,
         "name": "fusion.1", "args": {"device_offset_ps": "1000",
                                      "tf_op": "jit(f)/attention/dot"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 2.0, "dur": 1.0,
         "name": "copy-start", "args": {"device_offset_ps": "2000"}},
        {"ph": "X", "pid": 3, "tid": 4, "ts": 1.0, "dur": 1.0,
         "name": "copy-done", "args": {"device_offset_ps": "1000",
                                       "tf_op": "jit(f)/ffn/copy"}}]
    path = tmp_path / "host.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    assert span_reduce.op_scopes(str(path)) == {
        ("/device:TPU:0", 1000): "jit(f)/attention/dot"}
    assert span_reduce.op_scopes(str(tmp_path / "none.json.gz")) == {}


def _xspace_text():
    """A TPU plane (ops: a while 10-20 us holding a dot 11-14, an add
    30-35; one program) and the host thread: window 0-100, round 2-62 >
    batch 3-9, aggregate 40-58. Offsets in ps from the lines' 0 ns."""
    us = 1000000
    ops = [("%while.1 = s32[] while(s32[] %i)", 10, 10),
           ("%dot.2 = f32[8]{0} dot(f32[8]{0} %a)", 11, 3),
           ("%add.3 = f32[8]{0} add(f32[8]{0} %a)", 30, 5)]
    host = [("bench.window", 0, 100), ("bench.call", 0, 100),
            ("s2fl.round", 2, 60), ("s2fl.batch", 3, 6),
            ("s2fl.aggregate", 40, 18)]

    def meta(names):
        return "".join(f' event_metadata {{ key: {i + 1} value {{ id: '
                       f'{i + 1} name: {json.dumps(n)} }} }}'
                       for i, n in enumerate(names))
    op_events = "".join(
        f" events {{ metadata_id: {i + 1} offset_ps: {a * us} duration_ps:"
        f" {d * us} stats {{ metadata_id: 1 int64_value: {a * us} }} }}"
        for i, (_, a, d) in enumerate(ops))
    host_events = "".join(
        f" events {{ metadata_id: {i + 1} offset_ps: {a * us} duration_ps:"
        f" {d * us} }}" for i, (_, a, d) in enumerate(host))
    return (
        'planes { id: 1 name: "/device:TPU:0"'
        f' lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0{op_events} }}'
        ' lines { id: 2 name: "XLA Modules" timestamp_ns: 0 events {'
        f' metadata_id: 4 offset_ps: {9 * us} duration_ps: {27 * us} }} }}'
        + meta([o[0] for o in ops] + ["jit_s2fl_round_step(1)"])
        + ' stat_metadata { key: 1 value { id: 1 name: "device_offset_ps"'
        ' } } }'
        ' planes { id: 2 name: "/host:CPU"'
        f' lines {{ id: 1 name: "python3" timestamp_ns: 0{host_events} }}'
        + meta([h[0] for h in host]) + " }")


def test_traced_run_reduction_reads_a_written_trace(tmp_path):
    """The reduction a traced run calls, once a span metric is loaded,
    on a profiler trace written to disk: the ``.xplane.pb`` and the
    trace-event JSON beside it."""
    from jax.profiler import ProfileData
    _read("idle_frac.cnn.batch", _ctx(None))          # loads, installs
    assert trace_reduce.reduce_dir is span_reduce.reduce_dir
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_xspace_text()))
    json_events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}}] + [
        {"ph": "X", "pid": 3, "tid": 3, "name": "op",
         "args": {"device_offset_ps": str(a * 1000000), "tf_op": path}}
        for a, path in ((10, "jit(s2fl_round_step)/jvp(client_fwd)/"
                             "attention/while"),
                        (11, "jit(s2fl_round_step)/jvp(client_fwd)/ffn/dot"),
                        (30, "jit(s2fl_round_step)/jvp(server_loss)/head/"
                             "add"))]
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": json_events}, f)
    r = trace_reduce.reduce_dir(str(tmp_path))
    assert r["busy_s"] == pytest.approx(15e-6)
    assert dict(r["programs"]) == pytest.approx(
        {"jit_s2fl_round_step": 18e-6})          # the while holds the dot
    assert r["span_idle"] == pytest.approx({
        "s2fl.batch": 10e-6, "s2fl.round": 10e-6,
        span_reduce.OUTSIDE: 65e-6})
    assert r["span_count"] == {"s2fl.round": 1, "s2fl.batch": 1,
                               "s2fl.aggregate": 1}
    assert r["module_count"] == 1
    assert r["scope_busy"] == pytest.approx({"attention": 7e-6,
                                             "ffn": 3e-6, "head": 5e-6})


def test_recorded_trace_reads_no_spans():
    """A recorded TPU trace of a program without spans or scopes (the
    VGG-16 async cell before them): the new metrics read nothing."""
    with gzip.open(os.path.join(HERE, "data",
                                "trace_vgg16_async_0.3s.json.gz"), "rt") as f:
        r = _reduce(json.load(f))
    assert r["span_count"] == {} and r["scope_busy"] == {}
    assert r["span_idle"] == {span_reduce.OUTSIDE: pytest.approx(
        r["window_s"] - r["busy_s"])}
    for name in IDLE + ("programs_per_round.cnn",):
        assert _read(name, _ctx(r)) is None


def test_metrics_read_spans_of_the_program_table():
    """Every span a metric reads is one the program writes, so that a
    rename in the program cannot leave a metric reading zero."""
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from repro.observe.spans import SPANS
    read = {s for spans in _spans.PARTS.values() for s in spans}
    assert _spans.ROUND in SPANS
    assert read <= set(SPANS), read - set(SPANS)
    assert {f"idle_frac.cnn.{p}" for p in _spans.PARTS} | \
        {"idle_frac.cnn.host"} == set(IDLE)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(IDLE) <= listed
    assert {"device_frac.lm.attention", "device_frac.lm.head"} <= listed
    assert {"attention", "head"} <= set(span_reduce.SCOPES)
