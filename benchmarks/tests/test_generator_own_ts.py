"""A stream that carries its own event times (a ``ts`` column in the
reference's ``columns()``): late and out-of-order offsets reach the program
as they are, in both loops; and a stream without one gets, byte for byte,
what the generator stamped before it learnt of such streams."""

import hashlib

import numpy as np
import pytest

from harness import generator
from test_generator import FakeClock, Shipper

DT = np.dtype([("key", "<i8"), ("id", "<i8"), ("ts", "<i8"), ("marker", "i1"),
               ("value", "<i8")])
N_KEYS, PERIOD = 4, 48


class Stream:
    """A fixture reference module: ``PERIOD`` events that repeat, ids apart.
    With ``own_ts`` every third event is late by up to 5 ms, every fifth is
    ahead of its chunk's base, the rest trail it by their position -- so a
    chunk is out of order inside itself and against its neighbours."""

    def __init__(self, own_ts):
        self.own_ts = own_ts

    def period_events(self, cfg):
        return PERIOD

    def id_shift(self, cfg, n_events):
        return n_events // N_KEYS

    @staticmethod
    def ts_offsets(i):
        return np.where(i % 3 == 0, -(i % 7) * 800 - 200,
                        np.where(i % 5 == 0, 1500 + i, 10 * (i % 16)))

    def columns(self, cfg, seed, start, n):
        i = np.arange(start, start + n, dtype=np.int64)
        cols = {"key": i % N_KEYS, "id": i // N_KEYS,
                "value": (i * 7 + seed) % 100}
        if self.own_ts:
            cols["ts"] = self.ts_offsets(i)
        return cols


def _run(own_ts, loop, chunk=16, rate=None, seconds=0.1, tail=0.02):
    stream = Stream(own_ts)
    templates, id_shift, found = generator.build_templates(
        stream, {}, 3, DT, chunk)
    assert found is own_ts and len(templates) == PERIOD // chunk
    clock = FakeClock()
    ship = Shipper(clock, cost_ns=3_000_000)
    gen = generator.Generator(templates, id_shift,
                              {"loop": loop, "tail_seconds": tail}, chunk,
                              rate, seconds=seconds, clock_ns=clock.clock_ns,
                              sleep=clock.sleep, own_ts=found)
    gen(ship)
    return gen.log, [b.copy() for _, b in ship.pushed]


@pytest.mark.parametrize("loop,rate", [("closed", None), ("open", 4000)])
def test_own_event_times_reach_the_program(loop, rate):
    chunk = 16
    log, pushed = _run(True, loop, chunk, rate)
    assert log.n_chunks == len(pushed) > PERIOD // chunk      # it cycled
    for_oracle = log.for_oracle()
    assert for_oracle["own_ts"] is True
    late = ahead = disorder = 0
    for j, b in enumerate(pushed):
        # the reference's own columns, from the event index alone
        i = np.arange(j * chunk, (j + 1) * chunk) % PERIOD
        want = int(for_oracle["base_us"][j]) + Stream.ts_offsets(i)
        assert np.array_equal(b["ts"], want)
        assert b["id"][0] == (j * chunk) // N_KEYS            # ids run on
        late += int((b["ts"] < for_oracle["base_us"][j]).sum())
        ahead += int((b["ts"] > for_oracle["base_us"][j] + 1000).sum())
        disorder += int((np.diff(b["ts"]) < 0).sum())
    assert late and ahead and disorder
    if loop == "open":
        # the base is the due time of the chunk's first event, and the due
        # offsets stay in the log beside the stream's own times
        assert for_oracle["base_us"][1] == generator.chunk_base_us(1, chunk,
                                                                   rate)
        assert for_oracle["off_us"][-1] == (chunk - 1) * 1_000_000 // rate
    else:
        assert not for_oracle["off_us"].any()


@pytest.mark.parametrize("loop,rate", [("closed", None), ("open", 4000)])
def test_window_last_event_is_the_latest_event_time_not_the_last_rows(
        loop, rate):
    chunk = 16
    log, pushed = _run(True, loop, chunk, rate)
    in_window = pushed[:log.window_chunks]
    latest = max(int(b["ts"].max()) for b in in_window)
    assert log.window_last_event_us() == latest
    # the last row of the window's last chunk is not the latest event
    assert int(in_window[-1]["ts"][-1]) < latest
    # and a later chunk's base alone does not decide it: an early chunk's
    # far-ahead event may lead
    assert latest > int(log.base_us[log.window_chunks - 1])


def _digest(pushed):
    h = hashlib.sha256()
    for b in pushed:
        h.update(b.tobytes())
    return h.hexdigest()


#: sha256 over every pushed chunk's bytes, taken with the generator as it was
#: before this file existed (``git show e0e1681:benchmarks/harness/generator.py``)
PINNED = {
    ("closed", None): "325dcbff309dab13b6aeacede9bd99118a58a551541c3094da49a6bb7bb1207a",
    ("open", 4000): "d520af99f88eec937926c1224ccfad8530f872a82678bb18bd2acd53cfdb4dbf",
}


@pytest.mark.parametrize("loop,rate", sorted(PINNED, key=str))
def test_a_stream_without_ts_gets_the_bytes_it_got_before(loop, rate):
    log, pushed = _run(False, loop, 16, rate)
    assert log.for_oracle()["own_ts"] is False
    assert _digest(pushed) == PINNED[(loop, rate)]
    for j, b in enumerate(pushed):
        if loop == "closed":
            assert (b["ts"] == log.base_us[j]).all()
        else:
            assert np.array_equal(b["ts"], log.base_us[j] + log.off_us)
    assert log.window_last_event_us() == int(
        log.base_us[log.window_chunks - 1]) + int(log.off_us[-1])
