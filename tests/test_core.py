"""Core S²FL mechanics: split plans, scheduler, balance grouping (Eq. 2),
Algorithm-1 aggregation — unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.aggregation import ClientState, aggregate, fedavg_aggregate
from repro.core.balance import (balance_permutation, eq2_distance,
                                exhaustive_groups, greedy_groups,
                                group_distance, label_histogram)
from repro.core.scheduler import FixedSplitScheduler, SlidingSplitScheduler
from repro.core.simulation import Device, make_device_grid
from repro.core.split import SplitPlan, default_plan
from repro.configs import get_config, make_reduced
from repro.models import SplitModel
from repro.utils.tree import get_subtree, set_subtree

KEY = jax.random.PRNGKey(3)


# ---------------------------------------------------------------------------
# split plan
# ---------------------------------------------------------------------------
@given(st.integers(min_value=2, max_value=80), st.integers(2, 4))
def test_default_plan_properties(n_units, k):
    plan = default_plan(n_units, k=k)
    assert 1 <= plan.k <= k
    assert all(0 < s <= n_units for s in plan.split_points)
    assert plan.split_points == tuple(sorted(set(plan.split_points)))
    if n_units > k:
        assert plan.k == k


# ---------------------------------------------------------------------------
# Eq. 2 grouping
# ---------------------------------------------------------------------------
def test_eq2_distance_uniform_is_zero():
    assert eq2_distance(np.ones(10) * 7) < 1e-12


def test_eq2_distance_skewed_is_large():
    h = np.zeros(10)
    h[3] = 100
    assert eq2_distance(h) > 0.9


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_greedy_groups_partition_property(seed):
    """Grouping is a partition, and grouped distance <= mean singleton
    distance (combining complementary skews can only help on average)."""
    rng = np.random.default_rng(seed)
    x, n_classes = 8, 6
    hists = rng.integers(0, 50, size=(x, n_classes)).astype(float)
    groups = greedy_groups(hists, group_size=2)
    flat = sorted(c for g in groups for c in g)
    assert flat == list(range(x))
    mean_grouped = np.mean([group_distance(hists, g) for g in groups])
    mean_single = np.mean([eq2_distance(h) for h in hists])
    assert mean_grouped <= mean_single + 1e-9


def test_greedy_close_to_exhaustive_on_complementary_data():
    """Clients with complementary halves of the label space: optimal
    pairing reaches ~0; greedy must find it (or near)."""
    n_classes = 10
    hists = []
    for i in range(3):
        a = np.zeros(n_classes)
        a[:5] = 10 + i
        b = np.zeros(n_classes)
        b[5:] = 10 + i
        hists += [a, b]
    hists = np.array(hists)
    greedy = greedy_groups(hists, group_size=2)
    best = exhaustive_groups(hists, group_size=2)
    g_d = sum(group_distance(hists, g) for g in greedy)
    b_d = sum(group_distance(hists, g) for g in best)
    assert g_d <= b_d + 0.05
    assert g_d < 0.05                     # complementary pairs -> uniform


def test_balance_permutation_layout():
    perm = balance_permutation([10, 11, 12, 13],
                               [(11, 13), (12, 10)], per_client=2)
    # group (11,13) first: rows 2,3 then 6,7; group (12,10): 4,5 then 0,1
    assert perm.tolist() == [2, 3, 6, 7, 4, 5, 0, 1]
    assert sorted(perm.tolist()) == list(range(8))


def test_label_histogram():
    h = label_histogram(np.array([0, 0, 3, 9]), 10)
    assert h[0] == 2 and h[3] == 1 and h[9] == 1 and h.sum() == 4


# ---------------------------------------------------------------------------
# scheduler (§3.1)
# ---------------------------------------------------------------------------
def test_scheduler_warmup_traverses_all_splits():
    plan = SplitPlan(n_units=8, split_points=(1, 2, 4))
    sched = SlidingSplitScheduler(plan)
    seen = set()
    for r in range(plan.k):
        sel = sched.select([0, 1, 2])
        assert len(set(sel.values())) == 1
        seen.add(next(iter(sel.values())))
        for c, s in sel.items():
            sched.observe(c, s, t=1.0)
        sched.end_round()
    assert seen == {1, 2, 4}


def test_scheduler_equalizes_straggler_times():
    """Fast device should get a larger split than the slow one after
    warm-up, when time grows with split size."""
    plan = SplitPlan(n_units=8, split_points=(1, 2, 4))
    sched = SlidingSplitScheduler(plan)
    speed = {0: 4.0, 1: 1.0}              # device 0 is 4x faster
    for r in range(plan.k):
        sel = sched.select([0, 1])
        for c, s in sel.items():
            sched.observe(c, s, t=s / speed[c])
        sched.end_round()
    sel = sched.select([0, 1])
    assert sel[0] > sel[1]
    t0 = sel[0] / speed[0]
    t1 = sel[1] / speed[1]
    # chosen splits bring times closer than the worst-case pairing
    assert abs(t0 - t1) <= abs(plan.largest() / speed[1]
                               - plan.smallest() / speed[0])


def test_fixed_scheduler_is_largest_split():
    plan = SplitPlan(n_units=8, split_points=(1, 2, 4))
    sched = FixedSplitScheduler(plan)
    assert set(sched.select([0, 1]).values()) == {4}


# ---------------------------------------------------------------------------
# Eq. 1 simulation
# ---------------------------------------------------------------------------
def test_eq1_straggler_vs_fast_device():
    from repro.core.simulation import BYTES_PER_ELEM, device_round_time_bytes

    def t_of(dev, *, wc_size, feat_size, p, fc, fs):
        nbytes = (2.0 * wc_size + 2.0 * p * feat_size) * BYTES_PER_ELEM
        return device_round_time_bytes(dev, comm_bytes=nbytes, fc=fc, fs=fs)

    slow = Device(0, comp=5e9, rate=1e6)
    fast = Device(1, comp=2e10, rate=5e6)
    t_slow = t_of(slow, wc_size=1e6, feat_size=1e4, p=32, fc=1e10, fs=1e10)
    t_fast = t_of(fast, wc_size=1e6, feat_size=1e4, p=32, fc=1e10, fs=1e10)
    assert t_slow > t_fast
    # smaller portion shrinks the slow device's time
    t_slow_small = t_of(slow, wc_size=1e5, feat_size=1e4, p=32,
                        fc=1e9, fs=1.9e10)
    assert t_slow_small < t_slow
    # the byte path reproduces the seed's element-based Eq.-1 numbers
    # (the deprecated element helpers are gone; this inlines their math)
    legacy = (2.0 * 1e6 + 2.0 * 32 * 1e4) / slow.rate \
        + 1e10 / slow.comp + 1e10 / 5e10
    assert legacy == pytest.approx(t_slow)


def test_device_grid_covers_table1():
    devs = make_device_grid(18)
    comps = {d.comp for d in devs}
    rates = {d.rate for d in devs}
    assert comps == {5e9, 1e10, 2e10}
    assert rates == {1e6, 2e6, 5e6}


# ---------------------------------------------------------------------------
# Algorithm 1 aggregation
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_model():
    return SplitModel(make_reduced(get_config("internlm2-1.8b")))


def test_aggregate_identity(small_model):
    """All sources identical -> aggregate is identity."""
    p = small_model.init(KEY)
    clients = [ClientState(cid=i, params=p, split=1, data_size=float(i + 1),
                           group=0) for i in range(3)]
    out = aggregate(small_model, clients, {0: p})
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)


def test_aggregate_sources_by_split(small_model):
    """A block below the split comes from clients; above, from the server
    copy — with |D_i| weighting (Alg. 1 lines 3-17)."""
    model = small_model
    ones = jax.tree.map(jnp.ones_like, model.init(KEY))
    twos = jax.tree.map(lambda x: 2 * jnp.ones_like(x), ones)
    fives = jax.tree.map(lambda x: 5 * jnp.ones_like(x), ones)
    # two clients, split=1: client trains embed+block:0; server the rest
    clients = [
        ClientState(cid=0, params=ones, split=1, data_size=1.0, group=0),
        ClientState(cid=1, params=twos, split=1, data_size=3.0, group=0),
    ]
    out = aggregate(model, clients, {0: fives})
    # block:0 = (1*1 + 2*3)/4 = 1.75 ; block:1 = 5 (server copy both times)
    b0 = out["blocks"][0]["norm1"]["scale"]
    b1 = out["blocks"][1]["norm1"]["scale"]
    np.testing.assert_allclose(np.asarray(b0), 1.75, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b1), 5.0, rtol=1e-6)
    # embed is client-side, head/final_norm server-side
    np.testing.assert_allclose(np.asarray(out["embed"]["tok"])[0, 0], 1.75,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["final_norm"]["scale"])[0],
                               5.0, rtol=1e-6)


def test_aggregate_mixed_splits(small_model):
    """Different splits: block:1 aggregates client-1's copy with group-0's
    server copy."""
    model = small_model
    ones = jax.tree.map(jnp.ones_like, model.init(KEY))
    twos = jax.tree.map(lambda x: 2 * jnp.ones_like(x), ones)
    fives = jax.tree.map(lambda x: 5 * jnp.ones_like(x), ones)
    clients = [
        ClientState(cid=0, params=ones, split=1, data_size=1.0, group=0),
        ClientState(cid=1, params=twos, split=2, data_size=1.0, group=1),
    ]
    out = aggregate(model, clients, {0: fives, 1: fives})
    b1 = out["blocks"][1]["norm1"]["scale"]
    np.testing.assert_allclose(np.asarray(b1), (5.0 + 2.0) / 2, rtol=1e-6)


def _alg1_oracle(model, clients, server_copies):
    """Algorithm 1 in float64 NumPy, segment by segment; a leaf in no
    segment keeps clients[0]'s value."""
    out = jax.tree.map(lambda x: np.asarray(x, np.float64),
                       clients[0].params)
    total = sum(c.data_size for c in clients)
    for name, path in model.segments():
        acc = None
        for c in clients:
            src = (c.params if name in model.client_segments(c.split)
                   else server_copies[c.group])
            part = jax.tree.map(
                lambda x, w=c.data_size: np.asarray(x, np.float64) * w,
                get_subtree(src, path))
            acc = part if acc is None else jax.tree.map(np.add, acc, part)
        out = set_subtree(out, path, jax.tree.map(lambda x: x / total, acc))
    return out


def _assert_matches_oracle(out, want):
    """rtol 1e-6 against the float64 oracle, where an element that nearly
    cancels in the sum is held to 1e-6 of its leaf's largest value."""
    assert jax.tree.structure(out) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float64), b, rtol=1e-6,
                                   atol=1e-6 * np.abs(b).max())


def _mixed_federation(model, splits, groups, sizes):
    """Random client params with an extra leaf that lies in no segment,
    and one random server copy per group."""
    keys = jax.random.split(KEY, len(splits) + len(set(groups)))

    def params(k, i):
        return {**model.init(k), "unsegmented": jnp.full((3,), float(i))}

    clients = [ClientState(cid=i, params=params(keys[i], i), split=s,
                           data_size=d, group=g)
               for i, (s, g, d) in enumerate(zip(splits, groups, sizes))]
    copies = {g: params(keys[len(splits) + j], 100 + g)
              for j, g in enumerate(sorted(set(groups)))}
    return clients, copies


@pytest.mark.parametrize("arch,splits", [
    ("resnet8", (1, 3, 2, 1, 3)),
    ("internlm2-1.8b", (1, 2, 2, 1, 1)),
    ("zamba2-1.2b", (2, 1, 1, 2, 2)),       # shared_attn follows split 2
])
def test_aggregate_matches_float64_oracle(arch, splits):
    """Mixed splits, two groups, unequal |D_i|: every segment equals the
    float64 Algorithm-1 oracle at rtol 1e-6, and the unsegmented leaf is
    clients[0]'s."""
    cfg = get_config(arch)
    model = SplitModel(cfg if arch == "resnet8" else make_reduced(cfg))
    clients, copies = _mixed_federation(
        model, splits, groups=(0, 1, 0, 1, 1),
        sizes=(37.0, 5.0, 120.0, 64.0, 9.0))
    out = aggregate(model, clients, copies)
    want = _alg1_oracle(model, clients, copies)
    for a in jax.tree.leaves(out):
        assert a.dtype == jnp.float32
    _assert_matches_oracle(out, want)
    np.testing.assert_array_equal(np.asarray(out["unsegmented"]), 0.0)


def test_aggregate_traces_once_per_device_count(small_model, monkeypatch):
    """New data sizes with the same device count reuse the compiled sum:
    the scales are a traced argument, not constants."""
    import repro.core.aggregation as agg
    leaf_sum, traced = agg._leaf_sum, []

    def counting(scales, *xs):          # runs only while jit traces
        traced.append(len(xs))
        return leaf_sum(scales, *xs)

    monkeypatch.setattr(agg, "_leaf_sum", counting)
    n = 7                      # a device count no other test aggregates
    clients, copies = _mixed_federation(
        small_model, (1, 2) * 3 + (1,), (0, 1) * 3 + (0,),
        [float(i + 1) for i in range(n)])
    aggregate(small_model, clients, copies)
    first = len(traced)
    assert first == len(jax.tree.leaves(small_model.abstract()))
    for c in clients:
        c.data_size *= 3.0 + c.cid
    out = aggregate(small_model, clients, copies)
    assert len(traced) == first
    _assert_matches_oracle(out, _alg1_oracle(small_model, clients, copies))


@given(st.lists(st.floats(0.1, 10.0), min_size=2, max_size=4))
@settings(max_examples=10, deadline=None)
def test_fedavg_aggregate_convex(weights):
    trees = [{"w": jnp.full((3,), float(i))} for i in range(len(weights))]
    out = fedavg_aggregate(trees, weights)
    lo, hi = 0.0, float(len(weights) - 1)
    assert float(out["w"][0]) >= lo - 1e-6
    assert float(out["w"][0]) <= hi + 1e-6
    expect = sum(w * i for i, w in enumerate(weights)) / sum(weights)
    np.testing.assert_allclose(float(out["w"][0]), expect, rtol=1e-5)
