"""Open-loop accounting: latency from the due time, requests never answered
count as failed, percentiles are exact, and traffic is fixed by the seed."""

from __future__ import annotations

import threading

import numpy as np

from perfbench import loadgen


class FakeClock:
    """A clock that only moves when someone sleeps on it."""

    def __init__(self):
        self.t = 0.0
        self.lock = threading.Lock()

    def __call__(self):
        with self.lock:
            return self.t

    def sleep(self, dt):
        with self.lock:
            self.t += dt


def test_latency_counts_from_due_time_through_a_stall():
    clock = FakeClock()
    gate = threading.Event()

    def post(i):
        if i == 0:
            # the server stalls on the first request: the ones behind it
            # were due long before they went out
            clock.sleep(1.0)
        gate.set()
        return 200, b"\0" * 4

    res = loadgen.run_open_loop(post, np.arange(5) / 10.0, workers=1,
                                grace_s=5.0, clock=clock, sleep=clock.sleep)
    assert np.all(res["status"] == 200)
    lat = res["done"] - res["due"]
    # request 1 was due at 0.1 s and answered after the 1 s stall
    assert lat[1] >= 0.9 - 1e-9
    lag = res["sent"] - res["due"]
    assert lag[1] >= 0.9 - 1e-9
    acc = loadgen.account(res["due"], res["sent"], res["done"],
                          res["status"], rows_per_request=16)
    assert acc["failed"] == 0 and acc["attempted"] == 5
    assert acc["serve_p99_ms"] >= 900.0


def test_unanswered_and_errors_count_as_failed():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    sent = due.copy()
    done = np.array([0.05, np.nan, 0.3, 0.4])
    status = np.array([200, 0, 504, 200])
    acc = loadgen.account(due, sent, done, status, rows_per_request=16)
    assert acc["attempted"] == 4
    assert acc["failed"] == 2
    # the failed ones are not in the percentiles
    assert np.isclose(acc["serve_p50_ms"], 50.0)
    assert np.isclose(acc["serve_p99_ms"], 100.0)
    # rows answered over the span from the first due time to the last answer
    assert np.isclose(acc["serve_qps"], 2 * 16 / 0.4)


def test_lost_request_is_failed_not_dropped():
    def post(i):
        if i == 2:
            raise ConnectionResetError("lost")
        return 200, b""

    clock = FakeClock()
    res = loadgen.run_open_loop(post, np.arange(4) / 100.0, workers=2,
                                grace_s=1.0, clock=clock, sleep=clock.sleep)
    acc = loadgen.account(res["due"], res["sent"], res["done"],
                          res["status"], 1)
    assert acc["attempted"] == 4 and acc["failed"] == 1


def test_exact_nearest_rank_percentiles():
    v = np.arange(1, 1001, dtype=float)
    assert loadgen.percentile(v, 50) == 500.0
    assert loadgen.percentile(v, 95) == 950.0
    assert loadgen.percentile(v, 99) == 990.0
    assert loadgen.percentile(v[::-1], 99) == 990.0
    assert loadgen.percentile([7.0], 99) == 7.0


def test_traffic_is_fixed_by_the_seed():
    spec = {"rate_rps": 50.0, "blobs": 16, "blob_sigma": 0.02, "batch": 16}
    a = loadgen.requests_for(spec, 2**33 + 5, 2, 50)
    b = loadgen.requests_for(spec, 2**33 + 5, 2, 50)
    c = loadgen.requests_for(spec, 2**33 + 6, 2, 50)
    assert a.shape == c.shape == (50, 16, 3) and a.dtype == np.float32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() >= 0.0 and a.max() <= 1.0
    # every request's queries sit in one blob
    assert float(a.std(axis=1).max()) < 0.1
    assert loadgen.request_count(50.0, 30.0) == 1500


def test_clustered_mix_draws_as_before():
    """The blob mix with no reuse keeps its exact draws."""
    spec = {"rate_rps": 40.0, "batch": 16, "blobs": 16, "blob_sigma": 0.02}
    seed, n = 2**31 + 11, 30
    centers = np.random.default_rng([seed, 1]).random((16, 3))
    rng = np.random.default_rng([seed, 2])
    pick = rng.integers(16, size=n)
    noise = rng.normal(0.0, 0.02, (n, 16, 3))
    want = np.clip(centers[pick][:, None, :] + noise, 0.0,
                   1.0).astype(np.float32)
    assert np.array_equal(loadgen.requests_for(spec, seed, 2, n), want)
    assert np.array_equal(loadgen.schedule(spec, 30.0),
                          np.arange(1200) / 40.0)


def test_onoff_arrivals_keep_the_mean_rate():
    spec = {"rate_rps": 40.0, "batch": 4, "arrivals": "onoff",
            "on_s": 0.5, "off_s": 1.5}
    due = loadgen.schedule(spec, 10.0)
    assert len(due) == 400
    assert np.all(np.diff(due) > 0) and due[-1] < 10.0
    # nothing is due in an off period; the on periods run at 4x the mean
    assert np.all(due % 2.0 < 0.5 + 1e-9)
    assert np.isclose(np.diff(due[:5]).mean(), 1 / 160.0)


def test_uniform_queries_fill_the_box():
    spec = {"rate_rps": 10.0, "batch": 16, "blobs": 0, "scale": 2.0}
    q = loadgen.requests_for(spec, 5, 2, 200)
    assert q.shape == (200, 16, 3) and q.dtype == np.float32
    assert q.min() >= 0.0 and q.max() < 2.0
    assert float(q.std(axis=1).min()) > 0.3


def test_reuse_is_a_fixed_count_of_earlier_requests():
    spec = {"rate_rps": 10.0, "batch": 8, "blobs": 4, "blob_sigma": 0.02,
            "dup_frac": 0.2, "revisit_frac": 0.3, "revisit_sigma": 0.001,
            "pool": 16}
    for seed in (1, 2**33 + 1):
        q = loadgen.requests_for(spec, seed, 2, 200)
        flat = q.reshape(200, -1)
        repeats = revisits = 0
        for i in range(1, 200):
            gap = np.abs(flat[:i] - flat[i]).max(axis=1)
            if (gap == 0).any():
                repeats += 1
            elif (gap < 0.01).any():
                revisits += 1
        assert (repeats, revisits) == (40, 60)


def test_unknown_or_missing_parameters_are_refused():
    import pytest

    with pytest.raises(ValueError, match="unknown keys"):
        loadgen.mix({"rate_rps": 1.0, "batch": 1, "blob_sigm": 0.1})
    with pytest.raises(ValueError, match="missing"):
        loadgen.mix({"rate_rps": 1.0})
    with pytest.raises(ValueError, match="arrivals"):
        loadgen.mix({"rate_rps": 1.0, "batch": 1, "arrivals": "poisson"})
