"""The batched cohort compression path (kernels/comm_fused +
comm/fused.py + the channel's *_cohort methods + the engine's fused
local step).

The contract under test is the one comm/fused.py documents: wire bytes
BIT-equal to the sequential per-tensor path (so meters, Eq.-1 clocks and
recorder counters are identical), delivered tensors and residuals within
1e-6 (one fused XLA program may contract multiply-adds differently),
the error-feedback residual dict mutated with sequential-identical
semantics, and rand-k's per-call counter stream advanced one draw per
tensor in sequential transfer order (so checkpoints replay). Edge
shapes ride along: 1-element tensors, tensors smaller than the int8
GROUP, and frac=1.0 sparsifiers (k == n)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.comm import fused
from repro.comm.channel import CommChannel
from repro.comm.codecs import RandomKCodec, TopKCodec, get_codec
from repro.kernels.comm_fused import (fused_cast_roundtrip,
                                      fused_int8_roundtrip,
                                      fused_sparse_roundtrip,
                                      int8_group_geometry)
from repro.kernels.comm_fused.kernel import (int8_roundtrip_pallas,
                                             sparse_combine_pallas)
from repro.kernels.comm_fused.ref import (int8_roundtrip_ref,
                                          sparse_combine_ref)
from repro.kernels.int8_quant.ops import GROUP
from repro.kernels.int8_quant.ref import (int8_dequantize_ref,
                                          int8_quantize_ref)

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# fused kernels vs their jnp oracles (interpret-mode Pallas on CPU)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("r,g", [(1, 1), (3, 16), (37, 256), (300, 64)])
def test_int8_roundtrip_kernel_matches_ref_and_composed_pair(r, g):
    x = jax.random.normal(jax.random.fold_in(KEY, r * g), (r, g)) * 3.0
    out_k = int8_roundtrip_pallas(x, interpret=True)
    out_r = int8_roundtrip_ref(x)
    # interpret-mode Pallas may contract the dequantize multiply-add
    # differently than jnp — the path contract is ≤1e-6, not bit-exact
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-6, rtol=1e-6)
    # the single fused kernel == the quantize/dequantize pair composed
    q, scale, zp = int8_quantize_ref(x)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(int8_dequantize_ref(q, scale,
                                                              zp)),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize(
    "d,n,block_cols",
    [(1, 8, 4096), (5, 33, 4096), (130, 17, 4096),
     # several column tiles, a ragged last one in both axes
     (5, 33, 8), (130, 17, 8)],
    ids=["1-8", "5-33", "130-17", "5-33-cols8", "130-17-cols8"])
def test_sparse_combine_kernel_matches_ref(d, n, block_cols):
    y = jax.random.normal(jax.random.fold_in(KEY, d * n), (d, n))
    mask = (jax.random.uniform(jax.random.fold_in(KEY, d + n), (d, n))
            < 0.3).astype(jnp.float32)
    for scale in (1.0, 4.0):
        out_k, res_k = sparse_combine_pallas(y, mask, scale,
                                             block_cols=block_cols,
                                             interpret=True)
        out_r, res_r = sparse_combine_ref(y, mask, scale)
        np.testing.assert_array_equal(np.asarray(out_k),
                                      np.asarray(out_r))
        np.testing.assert_array_equal(np.asarray(res_k),
                                      np.asarray(res_r))
        # delivered + residual telescopes back to y where mask selects
        # with scale 1
        if scale == 1.0:
            np.testing.assert_allclose(np.asarray(out_k + res_k),
                                       np.asarray(y), atol=1e-6)


# ---------------------------------------------------------------------------
# fused ops vs the sequential per-tensor codecs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d,n", [(1, 1), (3, 7), (4, 300), (2, 1000)])
def test_fused_ops_match_sequential_codecs(d, n):
    x = jax.random.normal(jax.random.fold_in(KEY, 7 * d + n), (d, n))
    int8 = get_codec("int8")
    seq = jnp.stack([int8.roundtrip(x[i])[0] for i in range(d)])
    out, _ = fused_int8_roundtrip(x, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                               atol=1e-6)

    frac = 0.25
    k = max(1, int(np.ceil(frac * n)))
    topk = TopKCodec(frac=frac)
    seq = jnp.stack([topk.roundtrip(x[i])[0] for i in range(d)])
    out, _ = fused_sparse_roundtrip(x, None, k=k, scale=1.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))

    bf16 = get_codec("bf16")
    seq = jnp.stack([bf16.roundtrip(x[i])[0] for i in range(d)])
    out, _ = fused_cast_roundtrip(x, None, wire_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_fused_ef_residual_is_the_sequential_dual():
    d, n = 3, 400
    x = jax.random.normal(KEY, (d, n))
    r = jax.random.normal(jax.random.fold_in(KEY, 1), (d, n)) * 0.1
    out, new_r = fused_int8_roundtrip(x, r)
    y = x + r
    int8 = get_codec("int8")
    seq = jnp.stack([int8.roundtrip(y[i])[0] for i in range(d)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_r), np.asarray(y - seq),
                               atol=1e-6)


def test_int8_group_geometry_matches_metered_bytes():
    int8 = get_codec("int8")
    for n in (1, 7, 255, 256, 257, 1000):
        x = jax.random.normal(jax.random.fold_in(KEY, n), (n,))
        _, nbytes = int8.roundtrip(x)
        g, rows = int8_group_geometry(n)
        assert nbytes == rows * g * 1.0 + rows * 8.0
        assert fused.payload_bytes(int8, n) == nbytes


# ---------------------------------------------------------------------------
# codec edge shapes (sequential path — regression floor for the fused
# equivalence property below)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["fp32", "bf16", "int8", "topk",
                                  "randk"])
@pytest.mark.parametrize("shape", [(1,), (3,), (GROUP - 1,),
                                   (2, GROUP + 5)])
def test_codec_roundtrip_edge_shapes(name, shape):
    codec = get_codec(name, topk_frac=0.5)
    x = jax.random.normal(jax.random.fold_in(KEY, hash(shape) % 997),
                          shape)
    out, nbytes = codec.roundtrip(x)
    assert out.shape == x.shape and out.dtype == x.dtype
    assert nbytes > 0
    # the fused path's analytic accounting is bit-equal to the bytes
    # the sequential encode metered from the materialized payload
    assert fused.payload_bytes(codec, int(np.prod(shape))) == nbytes


def test_topk_frac_one_is_lossless():
    codec = TopKCodec(frac=1.0)
    x = jax.random.normal(KEY, (4, 37))
    out, nbytes = codec.roundtrip(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    assert nbytes == x.size * 8.0 + 4.0
    # and the fused dual delivers the same
    f, _ = fused_sparse_roundtrip(x.reshape(1, -1), None, k=x.size,
                                  scale=1.0)
    np.testing.assert_array_equal(np.asarray(f).reshape(x.shape),
                                  np.asarray(x))


# ---------------------------------------------------------------------------
# rand-k replayable state
# ---------------------------------------------------------------------------
def test_randk_state_export_replays_draw_stream():
    c = RandomKCodec(frac=0.3, seed=9)
    c.draw_indices(100, 30)
    snap = c.state()
    a = [c.draw_indices(100, 30) for _ in range(3)]
    c.set_state(snap)
    b = [c.draw_indices(100, 30) for _ in range(3)]
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    c.reset()
    assert c._calls == 0
    # a fresh codec from the same seed now produces the same stream
    np.testing.assert_array_equal(c.draw_indices(50, 10),
                                  RandomKCodec(frac=0.3,
                                               seed=9).draw_indices(50,
                                                                    10))


def test_channel_codec_state_roundtrip():
    ch = CommChannel("randk", topk_frac=0.2)
    x = jax.random.normal(KEY, (4, 64))
    ch.uplink_features(0, x)
    ch.downlink_grads(0, x)
    snap = ch.export_codec_state()
    a = ch.uplink_features(1, x)
    ch2 = CommChannel("randk", topk_frac=0.2)
    ch2.restore_codec_state(snap)
    b = ch2.uplink_features(1, x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert snap["feature"]["calls"] == 1 and snap["grad"]["calls"] == 1


# ---------------------------------------------------------------------------
# cohort channel == sequential channel (the tentpole property)
# ---------------------------------------------------------------------------
def _equiv_case(name, ef, shapes, rounds=2):
    seq = CommChannel(name, error_feedback=ef, topk_frac=0.3)
    coh = CommChannel(name, error_feedback=ef, topk_frac=0.3)
    worst = 0.0
    for rnd in range(rounds):
        feats = [jax.random.normal(
            jax.random.fold_in(KEY, 101 * rnd + i), shp)
            for i, shp in enumerate(shapes)]
        s_out = [seq.uplink_features(i, {"h": f, "aux": 0.5})
                 for i, f in enumerate(feats)]
        c_out = coh.uplink_features_cohort(
            [(i, {"h": f, "aux": 0.5}) for i, f in enumerate(feats)])
        for a, b in zip(s_out, c_out):
            worst = max(worst, float(jnp.abs(a["h"] - b["h"]).max()))
        s_g = [seq.downlink_grads(i, f * 0.1)
               for i, f in enumerate(feats)]
        c_g = coh.downlink_grads_cohort(
            [(i, f * 0.1) for i, f in enumerate(feats)])
        for a, b in zip(s_g, c_g):
            worst = max(worst, float(jnp.abs(a - b).max()))
    # bytes: BIT-equal, not approx
    assert seq.total_bytes == coh.total_bytes
    for i in range(len(shapes)):
        assert seq.round_payload(i) == coh.round_payload(i)
        assert seq.round_payload_split(i) == coh.round_payload_split(i)
    assert worst <= 1e-6
    # residual accumulators carry the same mass, keyed identically
    assert set(seq._residuals) == set(coh._residuals)
    assert abs(seq.residual_norm() - coh.residual_norm()) \
        <= 1e-4 * max(1.0, seq.residual_norm())
    if name == "randk":
        assert seq.feature_codec._calls == coh.feature_codec._calls
        assert seq.grad_codec._calls == coh.grad_codec._calls


@pytest.mark.parametrize("name", ["fp32", "bf16", "int8", "topk",
                                  "randk"])
@pytest.mark.parametrize("ef", [False, True])
def test_cohort_equals_sequential_channel(name, ef):
    # mixed shapes exercise the (shape, dtype) bucketing; the singleton
    # shape rides a D=1 fused call
    _equiv_case(name, ef, [(8, 33), (8, 33), (8, 33), (4, 5), (1,)])


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(["bf16", "int8", "topk", "randk"]),
       st.booleans(),
       st.lists(st.tuples(st.integers(1, 6), st.integers(1, 40)),
                min_size=2, max_size=6))
def test_cohort_equivalence_property(name, ef, shapes):
    _equiv_case(name, ef, shapes, rounds=2)


def test_cohort_model_legs_match_sequential():
    seq = CommChannel("fp32", dispatch_codec="int8",
                      error_feedback=True)
    coh = CommChannel("fp32", dispatch_codec="int8",
                      error_feedback=True)
    leaves = {cid: [jax.random.normal(jax.random.fold_in(KEY, cid),
                                      (9, 4)),
                    jax.random.normal(jax.random.fold_in(KEY, 50 + cid),
                                      (17,))]
              for cid in range(3)}
    for _ in range(2):
        s = {cid: seq.dispatch_leaves(cid, leaves[cid])
             for cid in range(3)}
        c = coh.dispatch_leaves_cohort(
            [(cid, leaves[cid]) for cid in range(3)])
        for cid, cl in zip(range(3), c):
            for a, b in zip(s[cid], cl):
                assert float(jnp.abs(a - b).max()) <= 1e-6
        s = {cid: seq.collect_leaves(cid, leaves[cid])
             for cid in range(3)}
        c = coh.collect_leaves_cohort(
            [(cid, leaves[cid]) for cid in range(3)])
        for cid, cl in zip(range(3), c):
            for a, b in zip(s[cid], cl):
                assert float(jnp.abs(a - b).max()) <= 1e-6
    assert seq.total_bytes == coh.total_bytes
    for cid in range(3):
        assert seq.round_dispatch(cid) == coh.round_dispatch(cid)
    assert set(seq._residuals) == set(coh._residuals)


def test_cohort_recorder_counts_match_sequential():
    from repro.observe import MetricsRegistry, Recorder
    outs = []
    for mode in ("seq", "coh"):
        reg = MetricsRegistry()
        ch = CommChannel("int8")
        ch.recorder = Recorder(metrics=reg)
        x = jax.random.normal(KEY, (6, 20))
        if mode == "seq":
            for i in range(4):
                ch.uplink_features(i, x)
        else:
            ch.uplink_features_cohort([(i, x) for i in range(4)])
        outs.append({k: v for k, v in reg.snapshot().items()
                     if k.startswith("comm.")})
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# engine integration: fused flags vs the sequential loop
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("codec,ef", [("int8", True), ("randk", True)])
def test_engine_fused_flags_match_sequential(codec, ef):
    import dataclasses

    from repro.configs import CommConfig, get_config
    from repro.core.engine import EngineConfig, S2FLEngine
    from repro.data.partition import federate
    from repro.data.synthetic import make_image_dataset
    from repro.models import SplitModel

    ds = make_image_dataset(240, seed=0)
    fed = federate(ds, 6, alpha=0.3, seed=0)

    def run(fused_comm, fused_server):
        ecfg = EngineConfig(
            mode="s2fl", rounds=2, clients_per_round=4, batch_size=8,
            local_steps=2, seed=0,
            comm=CommConfig(codec=codec, error_feedback=ef,
                            topk_frac=0.2),
            fused_comm=fused_comm, fused_server=fused_server)
        eng = S2FLEngine(SplitModel(get_config("resnet8")), fed, ecfg)
        hist = eng.run(2)
        psum = float(sum(np.asarray(w, np.float64).sum()
                         for w in jax.tree.leaves(eng.params)))
        return hist, psum, eng

    h0, p0, e0 = run(False, False)
    h1, p1, e1 = run(True, True)
    for a, b in zip(h0, h1):
        assert a["comm"] == b["comm"]          # bytes -> clock bit-equal
        assert a["clock"] == b["clock"]
        assert abs(a["loss"] - b["loss"]) < 1e-3
    assert abs(p0 - p1) < 1e-2                 # vmap numerics drift only
    assert abs(e0.channel.residual_norm()
               - e1.channel.residual_norm()) < 1e-2


@pytest.mark.slow
def test_engine_fused_flags_match_sequential_under_faults():
    """Fault teardown (mid-flight kills, quarantine, abandonment) is
    driver-level and path-independent — the fused cohort path must see
    the IDENTICAL exactly-once ledger, comm/clock trace, quarantine
    set and residual mass as the sequential loop under the same fault
    plan. Locks in the ISSUE-8 'fault-plan replay inside the fused
    cohort path' gap as verified-equivalent."""
    import dataclasses

    from repro.configs import CommConfig, get_config
    from repro.core.engine import EngineConfig, S2FLEngine
    from repro.core.faults import FaultPlan
    from repro.data.partition import federate
    from repro.data.synthetic import make_image_dataset
    from repro.models import SplitModel

    ds = make_image_dataset(160, seed=1)
    fed = federate(ds, 5, alpha=0.3, seed=1)
    mk_plan = lambda: FaultPlan.random(
        list(range(5)), 3, seed=11, kill_prob=0.3, rejoin_prob=0.6,
        mid_flight_frac=0.8, server_policy="cancel",
        residual_policy="restore")

    def run(fused):
        ecfg = EngineConfig(
            mode="s2fl", rounds=3, clients_per_round=4, batch_size=8,
            local_steps=2, seed=0,
            comm=CommConfig(codec="int8", error_feedback=True))
        ecfg = dataclasses.replace(
            ecfg,
            driver=dataclasses.replace(ecfg.driver, exec_mode="semi_async",
                                       pipeline=True, quorum=0.5,
                                       staleness_cap=2),
            fused_comm=fused, fused_server=fused)
        eng = S2FLEngine(SplitModel(get_config("resnet8")), fed, ecfg,
                         fault_plan=mk_plan())
        hist = eng.run(3)
        return hist, eng

    h0, e0 = run(False)
    h1, e1 = run(True)
    d0, d1 = e0.driver, e1.driver
    # exactly-once ledger multiset, bit-equal under both paths
    assert (d0.n_dispatched, d0.n_committed, d0.n_abandoned) == \
           (d1.n_dispatched, d1.n_committed, d1.n_abandoned)
    assert d0.n_abandoned > 0                   # the plan actually bit
    for a, b in zip(h0, h1):
        assert a["comm"] == b["comm"]
        assert a["clock"] == b["clock"]
        assert abs(a["loss"] - b["loss"]) < 1e-3
    # quarantined EF residuals: same held devices, same total mass
    assert set(e0.channel._quarantine) == set(e1.channel._quarantine)
    assert abs(e0.channel.residual_norm()
               - e1.channel.residual_norm()) < 1e-2
    assert abs(e0.channel.ef_discarded_mass
               - e1.channel.ef_discarded_mass) < 1e-6
