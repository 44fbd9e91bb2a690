"""The measured window: a path that dispatches ahead keeps at most
``ahead`` units queued beyond the one it waits for, and the window ends
only once every unit it sent is done."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run as harness  # noqa: E402


class Ahead:
    ahead = 3

    def __init__(self, unit_s):
        self.unit_s, self.sent, self.done, self.most = unit_s, 0, 0, 0

    def dispatch(self):
        self.sent += 1
        self.most = max(self.most, self.sent - self.done)
        return self.sent

    def wait(self, handle):
        assert handle == self.done + 1      # waited for in order
        time.sleep(self.unit_s)
        self.done += 1
        return 2.0


class Blocking:
    def __init__(self, unit_s):
        self.unit_s, self.n = unit_s, 0

    def call(self):
        time.sleep(self.unit_s)
        self.n += 1
        return 2.0


def test_ahead_window_drains_what_it_sent():
    r = Ahead(0.01)
    t0 = time.perf_counter()
    win = harness.run_window(r, 0.2)
    assert r.done == r.sent == win["attempted"] > 0
    assert r.most == r.ahead + 1
    assert win["work"] == 2.0 * r.sent
    assert 0.2 <= win["window_s"] <= time.perf_counter() - t0


def test_blocking_window_counts_every_call():
    r = Blocking(0.01)
    win = harness.run_window(r, 0.1)
    assert win["attempted"] == r.n > 0
    assert win["work"] == 2.0 * r.n
    assert win["window_s"] >= 0.1
