"""The served mixes' open-loop generator: Zipf slot rates, seeded Poisson
due times, and a driver that sends each frame at its absolute due time and
times it from there, whatever happened to the frames before it."""
import asyncio
import pathlib
import sys

import numpy as np
import pytest

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2]),
                str(pathlib.Path(__file__).resolve().parents[2] / "src")]

from bench import harness, openloop  # noqa: E402


def test_zipf_rates_sum_to_the_total_hottest_first():
    rates = openloop.zipf_rates(300.0, 64, 1.0)
    assert rates.sum() == pytest.approx(300.0)
    assert np.all(np.diff(rates) < 0)
    # Zipf(1) over 64 ranks: the hottest takes 1 / H_64 of the traffic
    h64 = sum(1.0 / i for i in range(1, 65))
    assert rates[0] / 300.0 == pytest.approx(1.0 / h64)
    assert rates[0] / rates[3] == pytest.approx(4.0)


def test_schedule_is_fixed_by_its_seed():
    a = openloop.schedule(200.0, 16, 1.0, 5.0, arrival_seed=3)
    b = openloop.schedule(200.0, 16, 1.0, 5.0, arrival_seed=3)
    c = openloop.schedule(200.0, 16, 1.0, 5.0, arrival_seed=4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    for dues in a:
        assert np.all(np.diff(dues) > 0)
        assert dues.size == 0 or (dues[0] >= 0 and dues[-1] < 5.0)


def test_schedule_offers_the_rate_asked_for():
    dues = openloop.schedule(14.0, 64, 1.0, 20.0, arrival_seed=0)
    got = np.array([len(d) for d in dues])
    assert got.sum() == 280
    want = openloop.zipf_rates(14.0, 64, 1.0) * 20.0
    assert np.all(np.abs(got - want) < 1.0)


def test_counts_round_to_the_total():
    assert list(openloop.counts(np.array([1.6, 1.3, 0.1]), 1.0)) == [2, 1, 0]
    assert openloop.counts(np.full(7, 1 / 7), 10.0).sum() == 10


def test_streams_needed_fills_streams_one_after_another():
    dues = [np.arange(70.0), np.arange(3.0), np.zeros(0)]
    assert openloop.streams_needed(dues, 32) == 3 + 1 + 0


class FakeFrontend:
    """Resolves each frame ``service`` seconds after it is submitted; the
    first submit of every stream holds its client up for ``stall``
    seconds."""

    def __init__(self, service=0.002, stall=0.0):
        self.service, self.stall, self.seen = service, stall, set()

    async def open(self, key):
        return object()

    async def submit(self, stream, frame):
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        result = type("R", (), {"estimate": np.zeros(5), "ess": 1.0,
                                "log_marginal": 0.0})()
        loop.call_later(self.service, fut.set_result, result)
        if self.stall and id(stream) not in self.seen:
            self.seen.add(id(stream))
            await asyncio.sleep(self.stall)
        return fut

    async def close(self, stream):
        return None

    def snapshot(self):
        return {"counters": {}, "series": {}}


def _served(rate, seconds, fe):
    traffic = {"img": 16, "frames": 64, "slots": 4, "capacity": 4,
               "total_rate": rate, "warmup_frames": 1, "min_frames": 2,
               "wait_s": 5.0, "arrival_seed": 7}
    cell = harness.load_cell("track-serve-roi64", traffic)
    d = harness.make_driver(cell, 11, seconds)
    d.prepare(11)
    d.fe = fe
    return d


def test_frames_are_sent_at_their_absolute_due_times():
    d = _served(rate=80.0, seconds=1.0, fe=FakeFrontend())
    win = d.window(1.0)
    due = np.concatenate([r["due"] for r in d.records])
    sent = np.concatenate([r["sent"] for r in d.records])
    assert win.attempted == due.size == sum(len(x) for x in d.dues) > 40
    assert win.failed == 0
    assert np.all(sent >= due - 1e-3)
    assert np.median(sent - due) < 0.01
    d.loop.close()


def test_latency_counts_from_the_due_time_through_a_stall():
    """A stall of 0.4 s at each stream's first frame delays the frames
    due behind it on its slot; their latency counts the wait, not only the
    2 ms of service, and the frames due later are on time again."""
    d = _served(rate=60.0, seconds=2.0, fe=FakeFrontend(stall=0.4))
    win = d.window(2.0)
    assert win.metrics["frame_p95_ms"] > 150.0
    assert win.metrics["frame_p50_ms"] < 100.0
    d.loop.close()


def test_bursts_keep_the_mean_rate_and_share_due_times():
    plain = openloop.schedule(40.0, 8, 1.0, 10.0, arrival_seed=2)
    burst = openloop.schedule(40.0, 8, 1.0, 10.0, arrival_seed=2, burst=4)
    assert sum(map(len, burst)) == pytest.approx(sum(map(len, plain)),
                                                 abs=4 * 8)
    for dues in burst:
        assert dues.size % 4 == 0
        groups = dues.reshape(-1, 4)
        assert np.all(groups == groups[:, :1])
    with pytest.raises(ValueError):
        openloop.schedule(40.0, 8, 1.0, 10.0, arrival_seed=2, burst=0)
