"""The generator's schedule: due times, lateness, never early; and the
closed loop's window and tail."""

import numpy as np

from harness import generator


class FakeClock:
    def __init__(self):
        self.now = 1_000_000_000

    def clock_ns(self):
        self.now += 1000            # every look at the clock costs 1 us
        return self.now

    def sleep(self, s):
        self.now += int(s * 1e9)


class Shipper:
    def __init__(self, clock, cost_ns=0, stall=None):
        self.clock, self.cost_ns, self.stall = clock, cost_ns, stall or {}
        self.pushed = []

    def push_batch(self, b):
        self.pushed.append((self.clock.now, b))
        self.clock.now += self.cost_ns + self.stall.get(len(self.pushed), 0)


def _templates(chunk, n=2):
    dt = np.dtype([("key", "<i8"), ("id", "<i8"), ("ts", "<i8"),
                   ("value", "<i8")])
    out = []
    for j in range(n):
        t = np.zeros(chunk, dtype=dt)
        t["id"] = np.arange(j * chunk, (j + 1) * chunk)
        t["value"] = j
        out.append(t)
    return out


def test_open_loop_is_never_early_and_stamps_due_times():
    chunk, rate = 100, 10_000          # a chunk every 10 ms
    clock = FakeClock()
    ship = Shipper(clock, cost_ns=200_000, stall={5: 35_000_000})
    gen = generator.Generator(_templates(chunk), 2 * chunk, {"loop": "open"},
                              chunk, rate, seconds=0.2, tail_seconds=0.05,
                              clock_ns=clock.clock_ns, sleep=clock.sleep)
    gen(ship)
    log = gen.log
    assert log.window_chunks == 19          # 20 fit, one period of grace
    assert log.n_chunks == 19 + 5
    assert log.handed_over == 19
    for j, (t_push, b) in enumerate(ship.pushed):
        due_ns = log.t0_ns + (j + 1) * 10_000_000
        assert t_push >= due_ns                       # never early
        assert b["ts"][0] == j * 10_000               # due time, not push time
        assert b["ts"][-1] == j * 10_000 + 9_900
        assert b["id"][0] == j * chunk                # ids run on over cycles
    late = np.asarray(log.late_us)
    assert late.min() >= 0
    assert late[:5].max() < 50                        # on time before the stall
    assert 24_000 < late[5] < 26_000                  # 35 ms stall, 10 ms period
    assert late[8:].max() < 50                        # and caught up after it


def test_open_loop_counts_what_the_window_did_not_hand_over():
    chunk, rate = 100, 10_000
    clock = FakeClock()
    ship = Shipper(clock, cost_ns=15_000_000)         # slower than the schedule
    gen = generator.Generator(_templates(chunk), 2 * chunk, {"loop": "open"},
                              chunk, rate, seconds=0.2, tail_seconds=0.0,
                              clock_ns=clock.clock_ns, sleep=clock.sleep)
    gen(ship)
    assert gen.log.window_chunks == 19
    assert 12 <= gen.log.handed_over <= 14            # 200 ms / 15 ms a push
    assert gen.log.n_chunks == 19                     # the rest came late
    assert gen.log.pushed == 19                       # late, and not failed
    assert np.asarray(gen.log.late_us)[-1] > 80_000


def test_open_loop_gives_up_far_behind_and_says_what_it_never_pushed():
    chunk, rate = 100, 10_000
    clock = FakeClock()
    ship = Shipper(clock, cost_ns=0, stall={3: 11_000_000_000})
    gen = generator.Generator(_templates(chunk), 2 * chunk, {"loop": "open"},
                              chunk, rate, seconds=0.2, tail_seconds=0.0,
                              clock_ns=clock.clock_ns, sleep=clock.sleep)
    ends = []
    gen.on_window_end = lambda: ends.append(clock.now)
    gen(ship)
    log = gen.log
    assert log.window_chunks == 19
    assert log.pushed == log.handed_over == log.n_chunks == 3
    assert len(ends) == 1
    assert log.window_last_event_us() == 2 * 10_000 + 9_900


def test_closed_loop_window_then_tail():
    chunk = 50
    clock = FakeClock()
    ship = Shipper(clock, cost_ns=4_000_000)
    ends = []
    gen = generator.Generator(_templates(chunk), 2 * chunk,
                              {"loop": "closed", "tail_seconds": 0.02},
                              chunk, None, seconds=0.1,
                              clock_ns=clock.clock_ns, sleep=clock.sleep)
    gen.on_window_end = lambda: ends.append(clock.now)
    gen(ship)
    log = gen.log
    assert len(ends) == 1
    assert log.window_chunks == log.handed_over == log.pushed == 25
    assert 29 <= log.n_chunks <= 31
    assert log.t_window_end_ns - log.t0_ns >= 100_000_000
    for j, (t_push, b) in enumerate(ship.pushed):
        assert (b["ts"] == log.base_us[j]).all()      # one stamp per chunk
        assert b["id"][0] == j * chunk
    assert all(a < b for a, b in zip(log.base_us, log.base_us[1:]))
    assert log.window_last_event_us() == log.base_us[24]
    assert log.busy_ns > 0 and log.blocked_ns >= 25 * 4_000_000
