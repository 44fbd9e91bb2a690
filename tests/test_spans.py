"""The program's spans and scopes (``repro.observe.spans``): one round of a
tiny CNN federation under ``jax.profiler`` writes every span of the table
into the host plane, nested as the table says, with the round's step
number; the engine's jitted halves carry their layer's name; and the
compiled fused round step carries the block and phase scopes in its
``op_name`` metadata."""
import glob
import os
import re
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, make_reduced
from repro.configs.base import CommConfig
from repro.core.engine import EngineConfig, S2FLEngine
from repro.core.round_step import make_s2fl_train_step
from repro.data.partition import federate
from repro.data.synthetic import make_image_dataset
from repro.models import SplitModel
from repro.observe.spans import SPANS, span


def _host_events(trace_dir):
    """{thread: [(name, start_ns, end_ns, {stat: value})]} of the host
    plane, and the jitted programs the host ran."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    threads, programs = {}, set()
    for line in host.lines:
        for e in line.events:
            with warnings.catch_warnings():   # jaxlib's stats type
                warnings.simplefilter("ignore", DeprecationWarning)
                stats = dict(e.stats)
            threads.setdefault(line.name, []).append(
                (e.name, e.start_ns, e.end_ns, stats))
            if "hlo_module" in stats:
                programs.add(stats["hlo_module"])
    return threads, programs


def _parent(events, ev):
    """The innermost ``s2fl.*`` event of ``events`` that holds ``ev``."""
    holders = [o for o in events if o is not ev and o[0].startswith("s2fl.")
               and o[1] <= ev[1] and ev[2] <= o[2]]
    return min(holders, key=lambda o: o[2] - o[1])[0] if holders else None


@pytest.fixture(scope="module")
def traced_round(tmp_path_factory):
    """The second round of a 4-device resnet8 federation (the first
    compiles), traced; the sequential path with a bf16 model leg, so the
    dispatch and collect legs run too."""
    fed = federate(make_image_dataset(160, seed=0), 4, alpha=0.5, seed=0)
    eng = S2FLEngine(SplitModel(get_config("resnet8")), fed, EngineConfig(
        mode="s2fl", clients_per_round=4, batch_size=8, group_size=2,
        comm=CommConfig(dispatch_codec="bf16")))
    eng.run_round()
    jax.block_until_ready(eng.params)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng.run_round()
        jax.block_until_ready(eng.params)
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def test_every_span_nests_as_the_table_says(traced_round):
    threads, _ = traced_round
    events, = [evs for evs in threads.values()
               if any(e[0] == "s2fl.round" for e in evs)]
    seen = {e[0] for e in events if e[0].startswith("s2fl.")}
    assert seen == set(SPANS)
    for ev in events:
        if ev[0] in SPANS:
            assert _parent(events, ev) == SPANS[ev[0]][0], ev[0]
    rounds = [e for e in events if e[0] == "s2fl.round"]
    assert [e[3]["step_num"] for e in rounds] == [1]
    # one batch, forward, update and per-device legs for each of the 4
    count = {n: sum(e[0] == n for e in events) for n in SPANS}
    for name in ("s2fl.batch", "s2fl.client_fwd", "s2fl.client_update",
                 "s2fl.uplink", "s2fl.downlink", "s2fl.dispatch",
                 "s2fl.collect"):
        assert count[name] == 4, (name, count)
    assert count["s2fl.server_step"] == count["s2fl.server_sgd"] == 2
    cids = {n: {e[3]["cid"] for e in events if e[0] == n}
            for n in ("s2fl.batch", "s2fl.client_fwd", "s2fl.client_update")}
    assert len(cids["s2fl.batch"]) == 4 and \
        cids["s2fl.batch"] == cids["s2fl.client_fwd"] == \
        cids["s2fl.client_update"]


def test_jitted_halves_carry_their_layer_name(traced_round):
    _, programs = traced_round
    assert {"jit_client_fwd", "jit_server_step",
            "jit_client_update"} <= programs, programs


def test_span_names_come_from_the_table():
    with pytest.raises(KeyError, match="not in observe.spans"):
        span("s2fl.nothing")


def _scopes(hlo: str) -> set:
    """Every component of the op_name paths, transform wrappers off."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo):
        for part in path.split("/"):
            while (m := re.match(r"^[\w.-]*\((.*)\)$", part)):
                part = m.group(1)
            out.add(part)
    return out


def test_fused_step_carries_block_and_phase_scopes():
    cfg = make_reduced(get_config("internlm2-1.8b"))
    model = SplitModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S = 4, 8
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "perm": jax.ShapeDtypeStruct((B,), jnp.int32)}
    step = make_s2fl_train_step(cfg, 1, 2, 0.01)
    assert step.__name__ == "s2fl_round_step"
    hlo = jax.jit(step).lower(params, batch).compile().as_text()
    assert {"attention", "ffn", "head", "regroup", "sgd", "client_fwd",
            "server_loss"} <= _scopes(hlo)
