#!/usr/bin/env python3
"""Open-loop load generator for ``POST /knn`` (numpy and the standard
library only: it runs as a child process that never imports JAX, so it
shares no interpreter lock with the server).

Request i is due at ``t0 + i / rate``: a dispatcher thread releases it at
that moment to a pool of worker threads, each with its own keep-alive
connection, so a slow answer never delays the next send unless every
worker is busy -- and even then the request's latency is timed from when
it was due, not from when it went out (no coordinated omission). Requests
still unanswered ``grace_s`` after the last one was due count as failed.

Traffic (``kind: open_loop`` mixes) is a file of parameters, all read here
(``PARAMS``; a key this generator does not know is refused):

- arrivals: ``rate_rps`` on average; ``arrivals: fixed`` spaces requests
  evenly, ``onoff`` sends only in ``on_s`` of every ``on_s + off_s``
  seconds, at the rate that keeps the mean;
- queries: each request carries ``batch`` queries, uniform in the box
  ``[0, scale)^3`` (``blobs: 0``) or around one of ``blobs`` Gaussian blob
  centres (sigma ``blob_sigma`` times ``scale``, clipped to the box);
- reuse: a share ``dup_frac`` of the requests repeats an earlier fresh one
  byte for byte, and a share ``revisit_frac`` repeats one jittered by
  ``revisit_sigma`` times ``scale``, the earlier one drawn from the last
  ``pool`` fresh requests.

All of it comes from the seed: the same counts of every kind for every
seed, in another order. A warm-up phase draws its own requests from a
separate stream.

Protocol with the parent: one JSON spec line on stdin; ``READY`` on stdout
after the warm-up; the parent writes ``GO``; the child runs the window,
saves the per-request arrays to the ``out`` path it was given (``.npz``)
and prints ``DONE {summary}``.
"""

from __future__ import annotations

import gc
import http.client
import json
import math
import queue
import sys
import threading
import time

import numpy as np

#: every parameter of an open-loop mix, with its default (None: required);
#: ``what`` and ``assumed`` describe the mix and are not read
PARAMS = {
    "kind": "open_loop", "what": "", "assumed": {},
    "rate_rps": None, "arrivals": "fixed", "on_s": 1.0, "off_s": 0.0,
    "batch": None, "scale": 1.0, "blobs": 0, "blob_sigma": 0.0,
    "dup_frac": 0.0, "revisit_frac": 0.0, "revisit_sigma": 0.0, "pool": 64,
    "workers": 16, "warm_seconds": 3.0, "grace_s": 60.0,
    "trace_delay_s": 2.0, "trace_seconds": 3.0,
}


def mix(traffic: dict) -> dict:
    """A traffic file's parameters with the defaults filled in; unknown
    keys, missing required ones and unknown arrival laws are refused."""
    unknown = set(traffic) - set(PARAMS)
    if unknown:
        raise ValueError(f"open-loop traffic: unknown keys {sorted(unknown)}")
    out = dict(PARAMS, **traffic)
    missing = [k for k, v in out.items() if v is None]
    if missing:
        raise ValueError(f"open-loop traffic: missing {missing}")
    if out["kind"] != "open_loop":
        raise ValueError(f"not an open-loop mix: kind {out['kind']!r}")
    if out["arrivals"] not in ("fixed", "onoff"):
        raise ValueError(f"unknown arrivals {out['arrivals']!r}")
    if out["dup_frac"] + out["revisit_frac"] >= 1.0:
        raise ValueError("dup_frac + revisit_frac leaves no fresh requests")
    return out


def requests_for(spec: dict, seed: int, stream: int, n: int) -> np.ndarray:
    """f32[n, batch, 3] seeded request queries: the blob centres come from
    ``seed`` alone, the requests from ``stream``."""
    spec = mix(spec)
    scale, batch = float(spec["scale"]), int(spec["batch"])
    rng = np.random.default_rng([seed, stream])
    if int(spec["blobs"]) > 0:
        centers = np.random.default_rng([seed, 1]).random(
            (int(spec["blobs"]), 3)) * scale
        pick = rng.integers(len(centers), size=n)
        noise = rng.normal(0.0, float(spec["blob_sigma"]) * scale,
                           (n, batch, 3))
        q = np.clip(centers[pick][:, None, :] + noise, 0.0,
                    scale).astype(np.float32)
    else:
        q = (rng.random((n, batch, 3)) * scale).astype(np.float32)
    return _reuse(spec, q, np.random.default_rng([seed, stream, 1]))


def _reuse(spec: dict, q: np.ndarray, rng) -> np.ndarray:
    """Turn fixed counts of requests into repeats of earlier fresh ones:
    ``dup_frac`` byte for byte, ``revisit_frac`` jittered."""
    n = len(q)
    n_dup = int(round(float(spec["dup_frac"]) * n))
    n_rev = int(round(float(spec["revisit_frac"]) * n))
    if n < 2 or not n_dup + n_rev:
        return q
    role = np.zeros(n, np.int8)          # 0 fresh, 1 repeat, 2 revisit
    later = 1 + rng.permutation(n - 1)   # request 0 is always fresh
    role[later[:n_dup]] = 1
    role[later[n_dup:n_dup + n_rev]] = 2
    fresh = np.flatnonzero(role == 0)
    pool, sigma = int(spec["pool"]), float(spec["revisit_sigma"])
    scale = float(spec["scale"])
    for i in np.flatnonzero(role):
        before = fresh[:np.searchsorted(fresh, i)][-pool:]
        anchor = q[before[int(rng.integers(len(before)))]]
        if role[i] == 1:
            q[i] = anchor
        else:
            q[i] = np.clip(anchor + rng.normal(0.0, sigma * scale,
                                               anchor.shape), 0.0, scale)
    return q


def request_count(rate: float, seconds: float) -> int:
    """Requests due in [0, seconds) at a fixed ``rate`` per second."""
    return max(1, int(math.ceil(rate * seconds - 1e-9)))


def schedule(spec: dict, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of every request due in
    [0, seconds): evenly spaced, or evenly spaced within the on-periods of
    an on/off law at the rate that keeps ``rate_rps`` on average."""
    spec = mix(spec)
    rate = float(spec["rate_rps"])
    if spec["arrivals"] == "fixed":
        return np.arange(request_count(rate, seconds),
                         dtype=np.float64) / rate
    on, off = float(spec["on_s"]), float(spec["off_s"])
    on_rate = rate * (on + off) / on
    active = np.arange(request_count(on_rate, seconds),
                       dtype=np.float64) / on_rate
    period = np.floor(active / on)
    due = period * (on + off) + (active - period * on)
    return due[due < seconds]


def run_open_loop(post, due: np.ndarray, workers: int, grace_s: float,
                  clock=time.perf_counter, sleep=time.sleep) -> dict:
    """Drive ``post(i) -> (status, payload)`` for every request i on the
    schedule ``due``; returns per-request ``due``/``sent``/``done`` times
    (seconds from the window start; ``done`` is NaN where no answer came),
    the HTTP ``status`` (0 = none) and the payloads."""
    n = len(due)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = np.zeros(n, np.int32)
    payload: list = [None] * n
    todo: queue.Queue = queue.Queue()
    t0 = clock()

    def worker():
        while True:
            i = todo.get()
            if i is None:
                return
            sent[i] = clock() - t0
            try:
                code, body = post(i)
            except Exception:  # noqa: BLE001 - a lost request is counted
                continue
            done[i] = clock() - t0
            status[i] = code
            payload[i] = body

    pool = [threading.Thread(target=worker, daemon=True)
            for _ in range(workers)]
    for t in pool:
        t.start()
    for i in range(n):
        wait = due[i] - (clock() - t0)
        if wait > 0:
            sleep(wait)
        todo.put(i)
    for _ in pool:
        todo.put(None)
    deadline = t0 + due[-1] + grace_s
    for t in pool:
        t.join(timeout=max(0.0, deadline - clock()))
    return {"due": due, "sent": sent, "done": done, "status": status,
            "payload": payload}


def percentile(values, p: float) -> float:
    """Exact nearest-rank percentile: the smallest value with at least
    p% of all values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("nan")
    rank = max(1, math.ceil(p / 100.0 * len(v)))
    return float(v[rank - 1])


def account(due, sent, done, status, rows_per_request: int) -> dict:
    """End-to-end numbers of one open-loop window: latency from the moment
    each request was due, over every request answered with 200; requests
    never answered, or answered with an error, count as failed."""
    ok = (status == 200) & np.isfinite(done)
    lat_ms = (done[ok] - due[ok]) * 1e3
    lag_ms = (sent[np.isfinite(sent)] - due[np.isfinite(sent)]) * 1e3
    span = (np.nanmax(done[ok]) - due[0]) if ok.any() else float("nan")
    return {
        "attempted": int(len(due)),
        "failed": int((~ok).sum()),
        "serve_p50_ms": percentile(lat_ms, 50),
        "serve_p99_ms": percentile(lat_ms, 99),
        "serve_qps": float(ok.sum() * rows_per_request / span)
        if ok.any() else 0.0,
        "lag_p99_ms": percentile(lag_ms, 99),
    }


class Client:
    """One worker's keep-alive connection posting binary f32 queries."""

    def __init__(self, host: str, port: int, timeout_s: float):
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.conn = None

    def post(self, body: bytes) -> tuple[int, bytes]:
        if self.conn is None:
            self.conn = http.client.HTTPConnection(self.host, self.port,
                                                   timeout=self.timeout_s)
        try:
            self.conn.request("POST", "/knn", body=body, headers={
                "Content-Type": "application/octet-stream"})
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except Exception:
            self.conn.close()
            self.conn = None
            raise


def _drive(spec: dict, link: dict, queries: np.ndarray,
           due: np.ndarray) -> dict:
    local = threading.local()

    def post(i):
        c = getattr(local, "client", None)
        if c is None:
            c = local.client = Client(link["host"], int(link["port"]),
                                      float(spec["grace_s"]))
        return c.post(queries[i].tobytes())

    return run_open_loop(post, due, int(spec["workers"]),
                         float(spec["grace_s"]))


def main() -> int:
    line = json.loads(sys.stdin.readline())
    link = {k: line.pop(k) for k in ("host", "port", "seed", "seconds",
                                     "out")}
    spec = mix(line)
    seed = int(link["seed"])
    due = schedule(spec, float(link["seconds"]))
    n = len(due)
    queries = requests_for(spec, seed, 2, n)
    warm_s = float(spec["warm_seconds"])
    if warm_s > 0:
        warm_due = schedule(spec, warm_s)
        nw = len(warm_due)
        warm = _drive(spec, link, requests_for(spec, seed, 3, nw), warm_due)
        bad = int((warm["status"] != 200).sum())
        if bad:
            sys.stderr.write(f"loadgen: {bad} of {nw} warm-up requests "
                             f"failed\n")
            return 1
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    # a collector pause in this process would read as a late send
    gc.collect()
    gc.disable()
    res = _drive(spec, link, queries, due)
    gc.enable()
    batch = int(spec["batch"])
    dists = np.full((n, batch), np.nan, np.float32)
    nvals = np.zeros(n, np.int64)
    for i, body in enumerate(res["payload"]):
        if body is not None and res["status"][i] == 200:
            vals = np.frombuffer(body, "<f4")
            nvals[i] = len(vals)
            if len(vals) == batch:
                dists[i] = vals
    np.savez(link["out"], due=res["due"], sent=res["sent"],
             done=res["done"], status=res["status"], dists=dists,
             nvals=nvals, queries=queries)
    summary = account(res["due"], res["sent"], res["done"], res["status"],
                      batch)
    print("DONE " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
