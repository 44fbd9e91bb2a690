"""Scheduler variants: paper median-matching vs beyond-paper min-time,
including the non-monotone-time regime where median matching loses.
Rounds are driven through the shared RoundDriver (CallableCost adapts a
plain t_of(cid, split) table)."""
import math

import numpy as np
import pytest

from repro.core.driver import CallableCost, RoundDriver
from repro.core.scheduler import (FixedSplitScheduler, MinTimeScheduler,
                                  SlidingSplitScheduler)
from repro.core.split import SplitPlan


def _run(sched, devices, t_of, rounds=8):
    """devices: ids; t_of(cid, split). Returns post-warmup wall clock."""
    drv = RoundDriver(sched, CallableCost(t_of), devices)
    wall = 0.0
    for r in range(rounds):
        rec = drv.run_round(devices)
        if r >= sched.plan.k:            # §3.1 warm-up rounds excluded
            wall += rec.round_time
    return wall


def test_mintime_never_worse_than_median_monotone():
    """Monotone time-in-split (big-model regime): both schedulers find
    small splits for stragglers; mintime is at least as good."""
    plan = SplitPlan(n_units=10, split_points=(1, 3, 5))
    speed = {0: 8.0, 1: 2.0, 2: 1.0}
    t_of = lambda c, s: (s + 2) / speed[c]
    w_median = _run(SlidingSplitScheduler(plan), list(speed), t_of)
    w_min = _run(MinTimeScheduler(plan), list(speed), t_of)
    assert w_min <= w_median + 1e-9


def test_mintime_wins_when_argmin_straddles_median():
    """Median matching deliberately picks a split whose time is NEAR THE
    MEDIAN even when the device has a strictly faster option — min-time
    takes the faster option and wins the round wall-clock."""
    plan = SplitPlan(n_units=4, split_points=(1, 2, 3))
    # device 1's fastest split (1 -> 4.0) is BELOW the median (5.0), so
    # median matching sends it to split 2 (5.0) instead.
    T = {(0, 1): 4.8, (0, 2): 5.0, (0, 3): 5.2,
         (1, 1): 4.0, (1, 2): 5.0, (1, 3): 9.0}
    t_of = lambda c, s: T[(c, s)]
    w_median = _run(SlidingSplitScheduler(plan), [0, 1], t_of)
    w_min = _run(MinTimeScheduler(plan), [0, 1], t_of)
    assert w_min < w_median
    sched = MinTimeScheduler(plan)
    _run(sched, [0, 1], t_of, rounds=plan.k)
    assert sched.select([0, 1])[1] == 1     # the true argmin


def test_ema_tracks_drifting_device():
    plan = SplitPlan(n_units=4, split_points=(1, 2))
    sched = SlidingSplitScheduler(plan, ema=0.5)
    for t in (10.0, 2.0, 2.0, 2.0, 2.0):
        sched.observe(0, 1, t)
    assert sched.table.get(0, 1) < 3.0      # converged toward 2.0


def test_mintime_falls_back_to_smallest_for_unmeasured():
    """A client with no time-table entries (joined after warm-up) gets
    the smallest split — the safe choice for an unknown device."""
    plan = SplitPlan(n_units=6, split_points=(1, 2, 4))
    sched = MinTimeScheduler(plan)
    t_of = lambda c, s: (s + 1.0) * (c + 1.0)
    _run(sched, [0, 1], t_of, rounds=plan.k + 1)    # table for 0,1 only
    assert not sched.warming_up
    sel = sched.select([0, 1, 99])                  # 99 never measured
    assert sel[99] == plan.smallest()
    assert sel[0] in plan.split_points and sel[1] in plan.split_points
    # same fallback on the median-matching scheduler
    sched2 = SlidingSplitScheduler(plan)
    _run(sched2, [0, 1], t_of, rounds=plan.k + 1)
    assert sched2.select([0, 1, 99])[99] == plan.smallest()


def test_warmup_traverses_all_splits_once_per_cycle():
    """§3.1: the K warm-up rounds dispatch each candidate split exactly
    once (all clients share the split within a round) — observed through
    the driver's per-round split record."""
    plan = SplitPlan(n_units=10, split_points=(1, 3, 5))
    for cls in (SlidingSplitScheduler, MinTimeScheduler):
        sched = cls(plan)
        drv = RoundDriver(sched, CallableCost(lambda c, s: 1.0 + s),
                          [0, 1, 2])
        seen = []
        for r in range(plan.k):
            rec = drv.run_round([0, 1, 2])
            assert len(set(rec.splits.values())) == 1   # shared split
            seen.append(next(iter(rec.splits.values())))
        assert seen == list(plan.split_points)      # each exactly once
        assert len(seen) == plan.k
        assert not sched.warming_up                 # table is warm now


def test_fixed_scheduler_interface():
    plan = SplitPlan(n_units=4, split_points=(1, 2, 3))
    s = FixedSplitScheduler(plan, split=2)
    assert s.select([5])[5] == 2
    s.observe(5, 2, 1.0)
    s.end_round()
    assert not s.warming_up


@pytest.mark.parametrize("ulps", [-2, -1, 0, 1, 2])
def test_median_tie_ignores_last_ulps(ulps):
    """With an even count of times the median is the midpoint of the two
    middle ones; when both are one device's, its two splits tie exactly.
    The first split wins however the last bits of a time round (the
    pipelined clock sums its phases and rounds differently)."""
    plan = SplitPlan(n_units=4, split_points=(1, 2, 3))
    sched = SlidingSplitScheduler(plan)
    t2 = 7.0
    for _ in range(abs(ulps)):
        t2 = np.nextafter(t2, math.copysign(np.inf, ulps))
    T = {(0, 1): 5.0, (0, 2): float(t2), (0, 3): 100.0,
         (1, 1): 0.5, (1, 2): 1.0, (1, 3): 200.0}
    for (c, s), t in T.items():
        sched.observe(c, s, t)
    sched.round = plan.k                    # past the warm-up
    assert sched.select([0, 1]) == {0: 1, 1: 2}
