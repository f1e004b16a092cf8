"""Beyond-HBM tiered slab index: host-RAM/mmap slab pool + bounds-driven
prefetch streaming.

The resident engines cap index size at device memory — every slab must be
uploaded before the first query. But the reference's whole point is
datasets that EXCEED one accelerator's memory (PAPER.md §0: billions of
points; "queries never move — trees move"), and PANDA (arXiv:1607.08220)
shows extreme-scale kNN is won by keeping the working set MOVING through a
memory hierarchy rather than demanding full residency. This module tiers
the index in three levels:

- **device** — a working set of slab engines (one ``ResidentKnnEngine``
  per resident slab, ``emit='candidates'`` with global neighbor ids),
  bounded in BYTES by ``--device-slab-budget`` against each engine's
  reported ``device_bytes`` footprint, evicted LRU-with-pin;
- **host RAM** — a bounded pool of materialized numpy slab rows (the
  promotion source; on real hardware these would be pinned/page-locked
  buffers for DMA), LRU-capped at ``--host-pool-slabs``;
- **cold** — the source ``.float3``/``.npy`` file itself (``SlabSource``:
  the exact ``load_slab_rows`` split of serve/engine.py, mmap for
  ``.npy``), so a slab that fell out of both warm tiers is re-read with
  rows byte-identical to what a routed host / the slab handoff would
  materialize.

``StreamingKnnEngine`` is the engine-shaped facade the serving stack
drives (same ``dispatch``/``complete`` split, same /stats-feeding
``stats()`` surface): each batch is routed by a per-slab AABB bounds table
— the in-process twin of the PR-7 ``PodBoundsTable`` — to its
nearest-bounds slab plus every slab whose box contains it, the per-slab
candidate partials are folded with the canonical (dist2, id) merge
(serve/frontend.py ``fold_candidates`` — commutative, so slab completion
order can never change bits), and uncertified (query, slab) pairs
escalate in waves exactly like the routed pod
(``lb * (1 - routing_cert_slack) <= kth²`` keeps a slab in play) until
every skipped slab is CERTIFIED unable to contribute. Exactness is never
traded by DEFAULT: a needed slab that misses both warm tiers STALLS the
batch (counted in ``knn_stream_stall_seconds_total``), it is never
skipped or approximated — results are bit-identical to a fully-resident
engine at EVERY pool size (tests/test_slabpool.py's parity matrix over
budgets {1 slab, half, all}). A request that OPTS INTO the recall-SLO
tier (serve/recall.py, ``stream_skip_cold``) inverts exactly that one
trade: cold promotions whose bounds could still beat the kth distance
are skipped for recall instead of stalled on
(``stream_skipped_promotions``), and the slab warms asynchronously for
the next batch.

Overlap is what makes the tiers affordable (TPU-KNN, arXiv:2206.14286:
the scorer must never starve): ``dispatch`` PINS the batch's slab set
(pinned slabs cannot evict while their programs are in flight), a
dedicated promotion thread uploads prefetched slabs ASYNCHRONOUSLY, and
the PR-2 pipeline announces the NEXT admitted batch's routed slab set a
batch ahead (serve/batcher.py calls ``prefetch_hint`` with the queued
rows after each dispatch) — so promotions ride under the in-flight
batch's compute and a well-hinted stream stalls zero times
(tests/test_slabpool.py: zero stalls once hinted, and bitwise parity
with a resident engine at index size 4x the device budget).

AOT discipline across the churn: all slab engines are padded to ONE shape
class (``pad_shard_rows``) and share an ``ExecutableCache`` keyed by that
class, so an eviction/re-promotion cycle reuses the already-compiled
query programs — ``compile_count`` stays flat no matter how many times a
slab cycles through the pool.
"""

from __future__ import annotations

import math
import os
import queue
import threading
import time

import numpy as np

from mpi_cuda_largescaleknn_tpu.analysis import guarded_by
from mpi_cuda_largescaleknn_tpu.obs.timers import PhaseTimers
from mpi_cuda_largescaleknn_tpu.serve.faults import FaultInjector

_RECORD_BYTES = 12  # one float3 (io/reader.py)


class SlabSource:
    """The cold tier: materialize slab ``s`` of ``S`` on demand.

    Rows are byte-identical to ``serve/engine.py load_slab_rows`` (and
    therefore to a routed host's / the slab handoff's materialization):
    the reference's integer split ``[N*s/S, N*(s+1)/S)`` via
    ``read_file_portion`` for ``.float3``, an mmap slice for ``.npy``, a
    plain slice for an in-memory array (the routed streaming path hands
    its already-loaded host slab here), or — with ``url=`` — a row
    sub-range pulled over HTTP from a host serving the full index
    (``GET /slab_rows?wire=d16&begin=&end=``: the PR-17 delta codec, so
    cold-tier promotions move ~0.55x the f32 bytes and are
    fingerprint-verified lossless after decode; an old host falls back
    to the single-shot f32 body automatically). Reads are stateless and
    thread-compatible — the pool's locking lives above this."""

    def __init__(self, *, path: str | None = None, points=None,
                 url: str | None = None, num_slabs: int,
                 wire: str = "d16", timeout_s: float = 120.0,
                 throttle_bps: float | None = None):
        from mpi_cuda_largescaleknn_tpu.models.sharding import slab_bounds

        if sum(x is not None for x in (path, points, url)) != 1:
            raise ValueError("need exactly one of path=, points= or url=")
        if num_slabs < 1:
            raise ValueError(f"num_slabs must be >= 1, got {num_slabs}")
        self.path = path
        self.num_slabs = int(num_slabs)
        self._points = None
        self._mmap = None
        self._url = None
        if url is not None:
            import json as _json
            import urllib.request as _rq

            self._url = url.rstrip("/")
            self._wire = wire
            self._timeout_s = float(timeout_s)
            self._throttle_bps = throttle_bps
            with _rq.urlopen(self._url + "/stats",
                             timeout=self._timeout_s) as r:
                est = _json.loads(r.read()).get("engine") or {}
            self.n_total = int(est.get("n_points", -1))
            self.dim = int(est.get("dim", 0))
            off = int(est.get("row_offset", -1))
            if self.n_total < 0 or self.dim < 1 or off != 0:
                raise ValueError(
                    f"{url}: not a full-index source host (n_points="
                    f"{self.n_total} dim={self.dim} row_offset={off})")
        elif points is not None:
            self._points = np.asarray(points, np.float32)
            if self._points.ndim != 2 or self._points.shape[1] < 1:
                raise ValueError(f"points must be [N, D], got "
                                 f"{self._points.shape}")
            self.n_total = len(self._points)
            self.dim = int(self._points.shape[1])
        elif path.endswith(".npy"):
            self._mmap = np.load(path, mmap_mode="r")
            if self._mmap.ndim != 2 or self._mmap.shape[1] < 1:
                raise ValueError(f"{path}: expected an [N, D] array, got "
                                 f"shape {list(self._mmap.shape)}")
            self.n_total = len(self._mmap)
            self.dim = int(self._mmap.shape[1])
        else:
            self.n_total = os.path.getsize(path) // _RECORD_BYTES
            self.dim = 3
        #: slab s owns global rows [bounds[s][0], bounds[s][1]) — the
        #: reference's split, shared with every other slab consumer
        self.bounds = slab_bounds(self.n_total, self.num_slabs)

    def read(self, slab: int) -> np.ndarray:
        """Materialize slab ``slab``'s rows (f32[n, dim])."""
        b, e = self.bounds[slab]
        if self._url is not None:
            from mpi_cuda_largescaleknn_tpu.serve.replica import (
                pull_slab_rows,
            )

            rows, off = pull_slab_rows(
                self._url, timeout_s=self._timeout_s, wire=self._wire,
                begin=b, end=e, throttle_bps=self._throttle_bps)
            if off != b or len(rows) != e - b:
                raise ValueError(
                    f"{self._url}: slab {slab} range drifted: got "
                    f"[{off}, {off + len(rows)}) want [{b}, {e})")
            return rows
        if self._points is not None:
            return np.asarray(self._points[b:e], np.float32)
        if self._mmap is not None:
            # the mmap slice copies exactly the slab's pages into RAM —
            # the cold tier never loads the whole file
            return np.asarray(self._mmap[b:e], np.float32)
        from mpi_cuda_largescaleknn_tpu.io.reader import read_file_portion

        rows, begin, _n = read_file_portion(self.path, slab, self.num_slabs)
        assert begin == b, f"slab split drifted: {begin} != {b}"
        return rows

    def scan_aabbs(self, sink=None) -> list[dict]:
        """One bounding box + count per slab ({"lo", "hi", "count"},
        ``lo/hi = None`` for empty slabs — the router's unreachable
        sentinel). Streams one slab at a time, so the scan's resident
        footprint is one slab, not the index. ``sink(slab, rows)`` (if
        given) receives each slab's rows as they are scanned — the
        streaming engine seeds its pool's host tier with them, so the
        scan's I/O is not immediately repeated by the first promotions."""
        from mpi_cuda_largescaleknn_tpu.models.sharding import slab_aabbs

        out = []
        for s in range(self.num_slabs):
            rows = self.read(s)
            out.extend(slab_aabbs(rows, [(0, len(rows))]))
            if sink is not None:
                sink(s, rows)
        return out


class _DeviceSlab:
    """One device-resident slab: its engine, its byte footprint, and its
    LRU tick (a logical counter, not wall-clock — deterministic under the
    tests' injectable clock)."""

    __slots__ = ("engine", "bytes", "tick")

    def __init__(self, engine, nbytes: int, tick: int):
        self.engine = engine
        self.bytes = int(nbytes)
        self.tick = int(tick)


class SlabPool:
    """Tiered slab store: device engines over a host-RAM row pool over the
    cold source, with LRU-with-pin eviction and an async promotion thread.

    ``engine_factory(slab_id, rows, row_begin) -> engine`` builds the
    device tier's entries (the streaming engine supplies the real
    ``ResidentKnnEngine`` factory; unit tests inject fakes — no jax, no
    sleeps). The factory runs OUTSIDE the pool lock: builds take real time
    and must never block /stats scrapes or concurrent pins.

    Exactness contract: ``ensure`` blocks until the slab is resident — a
    miss is a counted STALL (``stream_stall_seconds``), never a skipped or
    approximated slab. Pinned slabs (``pin``/``unpin``: the dispatch path
    pins a batch's routed slab set for the life of its in-flight programs)
    are never evicted; if a single batch's pinned set exceeds the budget
    the pool overcommits transiently (counted) rather than deadlock —
    the budget is a steady-state bound, not a per-batch straitjacket.

    Multi-index tenancy (PR 18): pool keys are either plain slab ints
    (the single-index legacy form — one source, one factory) or
    ``(tenant, slab)`` tuples routed through a per-tenant registry
    (``register``). All tenants share ONE device byte budget and ONE
    host tier, so hot tenants naturally occupy the device tier while
    cold tenants fall back to host-RAM/mmap and ride the same promotion
    + cold-read path; per-tenant hit/stall/eviction accounting rides
    alongside the pool-wide counters. A pool never mixes both key kinds.
    """

    def __init__(self, source: SlabSource | None = None,
                 engine_factory=None, *,
                 device_budget_bytes: int = 0, host_pool_slabs: int = 0,
                 host_pool_bytes: int = 0,
                 faults: FaultInjector | None = None,
                 clock=time.perf_counter):
        self._clock = clock
        self._sleep = time.sleep  # injectable: fault tests never sleep
        self._faults = faults
        self._cv = threading.Condition()
        # --- every field below is shared between caller threads (pin/
        # ensure/stats) and the promotion thread; all access under _cv ---
        #: tenant -> (SlabSource, engine_factory). The legacy single-index
        #: form registers under tenant ``None`` and keys the pool by bare
        #: slab ints; multi-tenant callers register named tenants and key
        #: by (tenant, slab)
        self._routes: guarded_by("_cv") = {}
        if source is not None:
            self._routes[None] = (source, engine_factory)
        #: per-tenant accounting (tuple-keyed pools only): tenant ->
        #: counter dict, updated alongside the pool-wide totals
        self._tenants: guarded_by("_cv") = {}
        self._budget: guarded_by("_cv") = int(device_budget_bytes)
        self._host_cap: guarded_by("_cv") = int(host_pool_slabs)
        self._host_bytes_cap: guarded_by("_cv") = int(host_pool_bytes)
        self._host_bytes: guarded_by("_cv") = 0
        self._device: guarded_by("_cv") = {}
        self._device_bytes: guarded_by("_cv") = 0
        #: host-RAM row pool, insertion-ordered oldest-first (dicts keep
        #: insertion order; move-to-end on hit = LRU)
        self._host: guarded_by("_cv") = {}
        self._pins: guarded_by("_cv") = {}
        self._promoting: guarded_by("_cv") = set()
        self._queued: guarded_by("_cv") = set()
        self._tick: guarded_by("_cv") = 0
        self._closed: guarded_by("_cv") = False
        self.promotions: guarded_by("_cv") = 0
        self.promotion_errors: guarded_by("_cv") = 0
        self.last_error: guarded_by("_cv") = None
        self.evictions: guarded_by("_cv") = 0
        self.host_evictions: guarded_by("_cv") = 0
        self.device_hits: guarded_by("_cv") = 0
        self.host_hits: guarded_by("_cv") = 0
        self.cold_reads: guarded_by("_cv") = 0
        self.overcommits: guarded_by("_cv") = 0
        self.prefetch_enqueued: guarded_by("_cv") = 0
        self.prefetch_errors: guarded_by("_cv") = 0
        self.stream_stalls: guarded_by("_cv") = 0
        self.stream_stall_seconds: guarded_by("_cv") = 0.0
        self._pq: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._prefetch_loop,
                                        daemon=True, name="knn-slab-promote")
        self._thread.start()

    # ------------------------------------------------------- keys & routes

    @staticmethod
    def _as_key(s):
        """Normalize a caller's slab reference to a pool key: bare ints
        for the legacy single-index pool, (tenant, slab) tuples for a
        multi-tenant one."""
        return (s[0], int(s[1])) if isinstance(s, tuple) else int(s)

    def register(self, tenant, source: SlabSource, engine_factory) -> None:
        """Add (or replace) a tenant's cold source + engine factory.
        Registration happens at engine construction, before that
        tenant's keys circulate — routes are read-mostly after."""
        with self._cv:
            self._routes[tenant] = (source, engine_factory)

    def _route(self, key):  # lsk: holds[_cv]
        """(tenant, local slab, source, factory) for a pool key."""
        if isinstance(key, tuple):
            tenant, slab = key
        else:
            tenant, slab = None, int(key)
        src, fac = self._routes[tenant]
        return tenant, slab, src, fac

    def _tacct(self, key):  # lsk: holds[_cv]
        """The per-tenant counter dict for a tuple key (lazily created);
        None for legacy int keys — single-index pools pay nothing."""
        if not isinstance(key, tuple):
            return None
        acct = self._tenants.get(key[0])
        if acct is None:
            acct = self._tenants[key[0]] = {
                "promotions": 0, "evictions": 0, "device_hits": 0,
                "host_hits": 0, "cold_reads": 0, "prefetch_enqueued": 0,
                "stream_stalls": 0, "stream_stall_seconds": 0.0}
        return acct

    # ----------------------------------------------------------- accounting

    def _next_tick(self) -> int:  # lsk: holds[_cv]
        self._tick += 1
        return self._tick

    def _note_stall(self, seconds: float, key=None):  # lsk: holds[_cv]
        self.stream_stalls += 1
        self.stream_stall_seconds += max(0.0, float(seconds))
        acct = self._tacct(key)
        if acct is not None:
            acct["stream_stalls"] += 1
            acct["stream_stall_seconds"] += max(0.0, float(seconds))

    def stall_totals(self, tenant=None) -> tuple:
        """(stalls, cumulative stall seconds) — the drift guard's cheap
        sample, without building the full stats dict. ``tenant`` narrows
        to one tenant's share of a shared pool."""
        with self._cv:
            if tenant is None:
                return self.stream_stalls, self.stream_stall_seconds
            acct = self._tenants.get(tenant)
            if acct is None:
                return 0, 0.0
            return acct["stream_stalls"], acct["stream_stall_seconds"]

    def _host_put(self, key, rows) -> None:  # lsk: holds[_cv]
        """Insert/refresh a slab's rows in the host tier; trim LRU past
        the slab-count cap and/or the byte cap (``--host-pool-bytes`` —
        the byte form keeps mixed-size tenant slabs from blowing the
        tier; the newest insert always survives, like the device tier's
        overcommit). Device-resident slabs keep their own row reference
        (``engine.host_points``), so trimming here never loses data —
        worst case the cold tier resupplies."""
        old = self._host.pop(key, None)
        if old is not None:
            self._host_bytes -= int(getattr(old, "nbytes", 0))
        self._host[key] = rows
        self._host_bytes += int(getattr(rows, "nbytes", 0))
        while ((self._host_cap > 0 and len(self._host) > self._host_cap)
               or (self._host_bytes_cap > 0
                   and self._host_bytes > self._host_bytes_cap
                   and len(self._host) > 1)):
            victim = next(iter(self._host))
            self._host_bytes -= int(
                getattr(self._host[victim], "nbytes", 0))
            del self._host[victim]
            self.host_evictions += 1

    def _evict_to_fit(self, new_bytes: int) -> None:  # lsk: holds[_cv]
        """Evict LRU unpinned device slabs until ``new_bytes`` more fit
        the budget (0 = unbounded). Evicted engines demote their rows to
        the host tier (free re-warm). With nothing evictable the pool
        overcommits — a pinned set wider than the budget must complete,
        not deadlock."""
        if self._budget <= 0:
            return
        while self._device_bytes + new_bytes > self._budget and self._device:
            victims = [(ent.tick, s) for s, ent in self._device.items()
                       if self._pins.get(s, 0) == 0]
            if not victims:
                # counted per PROMOTION that lands over budget (insert
                # time only) — unpin/set-budget re-checks finding the
                # pool still over would overstate one wide batch as many
                if new_bytes > 0:
                    self.overcommits += 1
                return
            _tick, s = min(victims)
            ent = self._device.pop(s)
            self._device_bytes -= ent.bytes
            self.evictions += 1
            acct = self._tacct(s)
            if acct is not None:
                acct["evictions"] += 1
            rows = getattr(ent.engine, "host_points", None)
            if rows is not None:
                self._host_put(s, rows)

    # ------------------------------------------------------------- pin/ensure

    def pin(self, slabs) -> None:
        """Pin each slab against eviction (reference-counted). Pins apply
        whether or not the slab is resident yet — a pinned cold slab
        cannot be evicted between its promotion and its use."""
        with self._cv:
            for s in set(slabs):
                self._pins[s] = self._pins.get(s, 0) + 1

    def unpin(self, slabs) -> None:
        with self._cv:
            for s in set(slabs):
                c = self._pins.get(s, 0) - 1
                if c <= 0:
                    self._pins.pop(s, None)
                else:
                    self._pins[s] = c
            # a batch whose pinned set overcommitted the budget shrinks
            # back the moment its pins release — the budget is the
            # steady-state bound, enforced at every release point
            self._evict_to_fit(0)
            self._cv.notify_all()

    def ensure(self, slab: int, count_stall: bool = True):
        """Return the slab's resident engine, promoting it first if
        needed. A promotion the caller had to WAIT for (cold/host miss, or
        an in-flight promotion it parked behind) is a counted stall unless
        ``count_stall=False`` (warmup/prefetch — data motion the stream
        never waited on)."""
        slab = self._as_key(slab)
        t0 = None
        while True:
            with self._cv:
                ent = self._device.get(slab)
                if ent is not None:
                    ent.tick = self._next_tick()
                    if t0 is None:
                        self.device_hits += 1
                        acct = self._tacct(slab)
                        if acct is not None:
                            acct["device_hits"] += 1
                    elif count_stall:
                        self._note_stall(self._clock() - t0, slab)
                    return ent.engine
                if slab in self._promoting:
                    # another thread (usually the promotion worker) is
                    # already building it — park until it lands
                    if t0 is None:
                        t0 = self._clock()
                    self._cv.wait(0.05)
                    continue
                self._promoting.add(slab)
                if t0 is None:
                    t0 = self._clock()
            break
        try:
            eng = self._build(slab)
        except BaseException as e:
            with self._cv:
                self._promoting.discard(slab)
                self.promotion_errors += 1
                self.last_error = f"slab {slab}: {type(e).__name__}: {e}"
                self._cv.notify_all()
            raise
        with self._cv:
            self._evict_to_fit(eng.device_bytes)
            self._device[slab] = _DeviceSlab(eng, eng.device_bytes,
                                             self._next_tick())
            self._device_bytes += int(eng.device_bytes)
            self._promoting.discard(slab)
            self.promotions += 1
            acct = self._tacct(slab)
            if acct is not None:
                acct["promotions"] += 1
            if count_stall:
                self._note_stall(self._clock() - t0, slab)
            self._cv.notify_all()
        return eng

    def acquire(self, slabs) -> dict:
        """Ensure every slab of a routed set is resident; {key: engine}."""
        return {self._as_key(s): self.ensure(s) for s in slabs}

    def _build(self, key):
        """Materialize rows (host tier first, cold source on miss) and
        build the slab's engine. Runs with NO pool lock held (the brief
        route/host-tier lookups take the lock; the read + factory do
        not)."""
        with self._cv:
            _tenant, slab, src, fac = self._route(key)
            b, _e = src.bounds[slab]
            rows = self._host.get(key)
            if rows is not None:
                self._host.pop(key)
                self._host[key] = rows  # move-to-end = LRU refresh
                self.host_hits += 1
                acct = self._tacct(key)
                if acct is not None:
                    acct["host_hits"] += 1
        if rows is None:
            rows = src.read(slab)
            with self._cv:
                self.cold_reads += 1
                acct = self._tacct(key)
                if acct is not None:
                    acct["cold_reads"] += 1
                self._host_put(key, rows)
        self._maybe_fault(key)
        return fac(slab, rows, b)

    def _maybe_fault(self, key) -> None:
        """Deterministic promotion faults (serve/faults.py): ``latency``
        slows the upload (the slow-promotion stall drill), any other op
        fails it — both on the same seeded grammar the HTTP handlers
        use, keyed as ``PROMOTE /slab/<id>`` (int keys) or
        ``PROMOTE /slab/<tenant>/<id>`` (tenant keys)."""
        if self._faults is None or not self._faults.active():
            return
        path = (f"/slab/{key[0]}/{key[1]}" if isinstance(key, tuple)
                else f"/slab/{key}")
        spec = self._faults.decide(path, "PROMOTE")
        if spec is None:
            return
        if spec.op == "latency":
            self._sleep(spec.delay_s)
        else:
            raise RuntimeError(f"injected promotion fault: {spec.op}")

    # -------------------------------------------------------------- prefetch

    def prefetch(self, slabs) -> None:
        """Enqueue async promotions (dedup against resident / in-flight /
        already-queued). The promotion thread uploads them under the
        in-flight batch's compute; a prefetched slab later ``ensure``d is
        a device hit — zero stall."""
        todo = []
        with self._cv:
            if self._closed:
                return
            for s in slabs:
                s = self._as_key(s)
                ent = self._device.get(s)
                if ent is not None:
                    # a hint declares the WHOLE set hot: refresh resident
                    # members' LRU ticks so promoting the missing ones
                    # cannot evict a sibling of the same hinted set
                    ent.tick = self._next_tick()
                    continue
                if s in self._promoting or s in self._queued:
                    continue
                self._queued.add(s)
                todo.append(s)
                acct = self._tacct(s)
                if acct is not None:
                    acct["prefetch_enqueued"] += 1
            self.prefetch_enqueued += len(todo)
        for s in todo:
            self._pq.put(s)

    def _prefetch_loop(self) -> None:
        while True:
            s = self._pq.get()
            if s is None:
                return
            try:
                self.ensure(s, count_stall=False)
            except Exception:
                # ensure's failure path already recorded the cause in
                # promotion_errors/last_error; count the PREFETCH-path
                # share separately and survive — a dead promotion thread
                # would turn every later miss into a stall
                with self._cv:
                    self.prefetch_errors += 1
            finally:
                # dequeue only AFTER the promotion finished (or failed):
                # discarding before ensure() marks _promoting would open
                # a window where wait_idle sees both sets empty and
                # reports idle mid-build
                with self._cv:
                    self._queued.discard(s)
                    self._cv.notify_all()

    def seed_host(self, slab: int, rows) -> None:
        """Pre-populate the host tier (LRU-capped as usual) without
        touching the hit/miss counters — the startup AABB scan already
        read these rows, so the first promotions should not re-read the
        cold tier for them."""
        with self._cv:
            self._host_put(self._as_key(slab), rows)

    def warm_fill(self, slabs, est_bytes: int) -> list:
        """Promote slabs in order until the next would exceed the budget
        (``est_bytes`` = one slab's footprint; all pool slabs share a
        shape class, so one estimate covers them). Synchronous and
        stall-free by definition — this is warmup, the stream has not
        started."""
        done = []
        for s in slabs:
            s = self._as_key(s)
            with self._cv:
                if s in self._device:
                    continue
                if (self._budget > 0
                        and self._device_bytes + est_bytes > self._budget):
                    break
            self.ensure(s, count_stall=False)
            done.append(s)
        return done

    def wait_idle(self, timeout_s: float = 30.0) -> bool:
        """Block until no promotion is queued or in flight (tests + the
        prefetch-overlap bench use this to separate 'announced ahead'
        from 'stalled on')."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._queued or self._promoting:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(min(remaining, 0.05))
            return True

    # ----------------------------------------------------------------- admin

    def set_device_budget(self, nbytes: int) -> None:
        """Retune the device budget at runtime; shrinking evicts LRU
        unpinned slabs immediately."""
        with self._cv:
            self._budget = int(nbytes)
            self._evict_to_fit(0)

    def resident_engines(self) -> list:
        with self._cv:
            return [ent.engine for ent in self._device.values()]

    def resident_items(self) -> list:
        """[(key, engine)] for every device-resident slab — per-tenant
        facades filter this to their own keys."""
        with self._cv:
            return [(k, ent.engine) for k, ent in self._device.items()]

    def resident_slabs(self) -> list:
        with self._cv:
            return sorted(self._device)

    def close(self) -> None:
        with self._cv:
            self._closed = True
        self._pq.put(None)
        self._thread.join(timeout=10)

    def stats(self) -> dict:
        with self._cv:
            out = {
                "num_slabs": sum(src.num_slabs
                                 for src, _fac in self._routes.values()),
                "device_resident": len(self._device),
                "host_resident": len(self._host),
                "device_bytes_used": self._device_bytes,
                "device_budget_bytes": self._budget,
                "host_pool_slabs": self._host_cap,
                "host_pool_bytes": self._host_bytes_cap,
                "host_bytes_used": self._host_bytes,
                "resident_slabs": sorted(self._device),
                "pinned_slabs": sorted(self._pins),
                "promotions": self.promotions,
                "promotion_errors": self.promotion_errors,
                "last_error": self.last_error,
                "evictions": self.evictions,
                "host_evictions": self.host_evictions,
                "device_hits": self.device_hits,
                "host_hits": self.host_hits,
                "cold_reads": self.cold_reads,
                "overcommits": self.overcommits,
                "prefetch_enqueued": self.prefetch_enqueued,
                "prefetch_errors": self.prefetch_errors,
                "stream_stalls": self.stream_stalls,
                "stream_stall_seconds": round(self.stream_stall_seconds, 6),
            }
            if self._tenants:
                per = {}
                for t, acct in self._tenants.items():
                    d = dict(acct)
                    d["stream_stall_seconds"] = round(
                        d["stream_stall_seconds"], 6)
                    d["device_resident"] = sum(
                        1 for k in self._device
                        if isinstance(k, tuple) and k[0] == t)
                    d["host_resident"] = sum(
                        1 for k in self._host
                        if isinstance(k, tuple) and k[0] == t)
                    d["pinned"] = sum(
                        1 for k in self._pins
                        if isinstance(k, tuple) and k[0] == t)
                    per[t] = d
                out["tenants"] = per
            return out


class _StreamHandle:
    """A dispatched-but-uncompleted streaming batch: the original queries
    (degradation replay + escalation sub-batches), the bounds table's
    lower bounds, the visited matrix, the per-slab in-flight sub-batches,
    the pinned slab set ``complete`` releases, and the recall plan
    (serve/recall.py, None = exact) the batch runs under."""

    __slots__ = ("queries", "n", "engine_name", "t0", "lb", "visited",
                 "subs", "pinned", "plan", "skip_cold", "seeds")

    def __init__(self, queries, n, engine_name, t0, plan=None, seeds=None):
        self.queries = queries
        self.n = n
        self.engine_name = engine_name
        self.t0 = t0
        self.lb = None
        self.visited = None
        self.subs = []
        self.pinned = set()
        self.plan = plan
        #: certified per-row init radii (serve/qcache.py; None = unseeded)
        #: — rides the handle so the fold and every escalation sub-batch
        #: start their heaps at the same certified bound
        self.seeds = seeds
        #: dispatch's ADMITTED skip-cold decision for this batch (the
        #: drift guard may refuse the plan's ask); the fold must follow
        #: the same decision or wave 1 and escalation would disagree
        self.skip_cold = False


class StreamingKnnEngine:
    """Engine facade over a ``SlabPool``: serve an index bigger than
    device memory, bit-identical to a fully-resident engine.

    Same ``dispatch``/``complete``/``query`` contract as
    ``ResidentKnnEngine`` (the batcher, admission wrapper, and HTTP
    server drive it unchanged); ``emit='candidates'`` additionally serves
    ``complete_candidates`` so a routed pod host can itself stream
    sub-slabs (serve_main ``--routing bounds --num-slabs``). Thread
    compatibility matches the resident engine's: the batcher's dispatch
    and completion workers may overlap one batch's escalation with the
    next batch's wave 1 — the pool lock and each slab engine's own lock
    serialize what must serialize.
    """

    def __init__(self, path: str | None = None, *, points=None,
                 num_slabs: int = 0, k: int, device_slab_budget: int = 0,
                 host_pool_slabs: int = 0, host_pool_bytes: int = 0,
                 prefetch_depth: int = 1,
                 mesh=None, engine: str = "auto", bucket_size: int = 0,
                 max_radius: float = math.inf, max_batch: int = 1024,
                 min_batch: int = 8, merge: str = "auto",
                 query_buckets: int = 0, score_dtype: str = "f32",
                 id_offset: int = 0, emit: str = "final",
                 faults: FaultInjector | None = None,
                 source_url: str | None = None,
                 source_wire: str = "d16",
                 source_throttle_bps: float | None = None,
                 skip_cold_stall_limit: float = 0.25,
                 source: SlabSource | None = None,
                 pool: SlabPool | None = None,
                 tenant: str | None = None,
                 shared_exec_cache=None, pad_shard_rows: int = 0,
                 timers: PhaseTimers | None = None,
                 clock=time.perf_counter):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.parallel.ring import resolve_engine
        from mpi_cuda_largescaleknn_tpu.serve.engine import ExecutableCache
        from mpi_cuda_largescaleknn_tpu.serve.frontend import (
            routing_cert_slack,
        )

        if emit not in ("final", "candidates"):
            raise ValueError(f"emit must be 'final' or 'candidates', "
                             f"got {emit!r}")
        if pool is not None and tenant is None:
            raise ValueError("a shared pool= needs a tenant= namespace "
                             "for this engine's (tenant, slab) keys")
        if source is not None:
            self._source = source
        else:
            self._source = SlabSource(path=path, points=points,
                                      url=source_url, num_slabs=num_slabs,
                                      wire=source_wire,
                                      throttle_bps=source_throttle_bps)
        self.num_slabs = self._source.num_slabs
        self.n_points = self._source.n_total
        self.dim = self._source.dim
        if self.n_points < 1:
            raise ValueError("streaming engine needs a non-empty index")
        self.k = int(k)
        self.id_offset = int(id_offset)
        self.emit = emit
        self.max_radius = float(max_radius)
        self.prefetch_depth = int(prefetch_depth)
        self.device_slab_budget = int(device_slab_budget)
        self.host_pool_slabs = int(host_pool_slabs)
        self.host_pool_bytes = int(host_pool_bytes)
        self.tenant = tenant
        self._clock = clock
        #: never retains host rows itself (the pool's tiers do) — the
        #: /slab_rows pull path needs a single contiguous array, which a
        #: streaming host by definition does not keep
        self.host_points = None
        self.mesh = mesh if mesh is not None else get_mesh(None)
        #: shared accounting sink: every slab engine counts fetch/result/
        #: tile totals here, so eviction never zeroes the /stats surface.
        #: A multi-tenant facade passes ONE timers + executable cache to
        #: every tenant view, so compiled programs (and their counters)
        #: are shared across tenants — tenant count never becomes
        #: compile count
        self.timers = timers if timers is not None else PhaseTimers()
        self._exec_cache = (shared_exec_cache if shared_exec_cache
                            is not None else ExecutableCache())
        self.cert_slack = routing_cert_slack(self.dim)
        self._meta_lock = threading.Lock()
        self._engine_name: guarded_by("_meta_lock") = resolve_engine(engine)
        self._degraded_reason: guarded_by("_meta_lock") = None
        self._launch_workers: guarded_by("_meta_lock") = 1
        #: drift guard for the recall tier: recent (clock, cumulative
        #: stall seconds) samples; when the pool is ALREADY stalling
        #: above ``skip_cold_stall_limit`` (fraction of wall time spent
        #: stalled over the sampled window), a ``stream_skip_cold`` plan
        #: is REFUSED for the batch — under traffic drift the skip tier
        #: collapses recall AND still pays promotion churn (TUNING.md),
        #: so exact serving is strictly the better failure mode
        self.skip_cold_stall_limit = float(skip_cold_stall_limit)
        self._stall_ring: guarded_by("_meta_lock") = []
        self.skip_cold_refusals: guarded_by("_meta_lock") = 0
        #: one shape class for every slab engine: pad each engine's local
        #: shards to the LARGEST slab's per-shard row count, so the shared
        #: ExecutableCache hits across slabs and re-promotions
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import AXIS

        num_shards = self.mesh.shape[AXIS]
        max_slab = max(e - b for b, e in self._source.bounds)
        #: one shape class across the POOL: at least this engine's
        #: largest slab, or a caller-supplied class (the multi-tenant
        #: facade passes the max over every tenant so the shared cache
        #: hits across all of them)
        self._pad_shard = max(int(pad_shard_rows),
                              -(-max_slab // num_shards))
        self._engine_kw = dict(
            bucket_size=bucket_size, max_radius=max_radius,
            max_batch=max_batch, min_batch=min_batch, merge=merge,
            query_buckets=query_buckets, score_dtype=score_dtype)
        if pool is not None:
            self._pool = pool
            self._owns_pool = False
            self._pool.register(tenant, self._source, self._make_engine)
        else:
            # a standalone tenant-keyed engine registers its source under
            # the tenant namespace ONLY (every pool key is (tenant, slab));
            # the legacy None route exists just for bare-int keys
            self._pool = SlabPool(
                None if tenant is not None else self._source,
                self._make_engine,
                device_budget_bytes=device_slab_budget,
                host_pool_slabs=host_pool_slabs,
                host_pool_bytes=host_pool_bytes, faults=faults,
                clock=clock)
            self._owns_pool = True
            if tenant is not None:
                self._pool.register(tenant, self._source,
                                    self._make_engine)
        #: per-slab routing boxes (the in-process PodBoundsTable): f64
        #: lo/hi per non-empty slab, +inf lower bound for empty ones.
        #: The scan's rows seed the pool's host tier as they stream by —
        #: the first promotions then re-read RAM, not the cold source
        aabbs = self._source.scan_aabbs(
            sink=lambda s, rows: self._pool.seed_host(self._pkey(s), rows))
        self.slab_aabbs = aabbs
        self._nonempty = np.array([a["count"] > 0 for a in aabbs], bool)
        self._slab_lo = np.array([a["lo"] if a["lo"] is not None
                                  else [np.inf] * self.dim for a in aabbs],
                                 np.float64).reshape(-1, self.dim)
        self._slab_hi = np.array([a["hi"] if a["hi"] is not None
                                  else [-np.inf] * self.dim for a in aabbs],
                                 np.float64).reshape(-1, self.dim)
        # bootstrap: promote the first non-empty slab and adopt its
        # resolved config as the template every sibling shares (all slab
        # engines are built from the same knobs + shape class)
        first = int(np.argmax(self._nonempty))
        t = self._pool.ensure(self._pkey(first), count_stall=False)
        self._template_slab = first
        self.max_batch = t.max_batch
        self.shape_buckets = list(t.shape_buckets)
        self.query_buckets = dict(t.query_buckets)
        self.query_buckets_setting = t.query_buckets_setting
        self.merge_mode = t.merge_mode
        self.score_dtype = t.score_dtype
        self.score_mode = t.score_mode
        self.sort_queries = t.sort_queries
        self.bucket_size = t.bucket_size
        self.num_shards = t.num_shards
        self.slab_device_bytes = int(t.device_bytes)
        self.canonical_ties = t.canonical_ties
        #: pod-surface compatibility (a streaming engine is always one
        #: process; routed hosts wrap it with emit='candidates')
        self.process_index = 0
        self.process_count = 1

    # ---------------------------------------------------------- pool keying

    def _pkey(self, slab: int):
        """This engine's pool key for a local slab id: bare ints for an
        owned single-index pool, (tenant, slab) in a shared pool."""
        return (self.tenant, int(slab)) if self.tenant is not None \
            else int(slab)

    def _pkeys(self, slabs) -> list:
        return [self._pkey(s) for s in slabs]

    def _resident_local(self) -> set:
        """This engine's device-resident LOCAL slab ids (a shared pool
        holds other tenants' keys too — filter to ours)."""
        if self.tenant is None:
            return set(self._pool.resident_slabs())
        return {k[1] for k in self._pool.resident_slabs()
                if isinstance(k, tuple) and k[0] == self.tenant}

    def _my_engines(self) -> list:
        if self.tenant is None:
            return self._pool.resident_engines()
        return [e for k, e in self._pool.resident_items()
                if isinstance(k, tuple) and k[0] == self.tenant]

    # ------------------------------------------------------------ engine mgmt

    def _make_engine(self, slab: int, rows: np.ndarray, row_begin: int):
        """SlabPool engine factory: one canonical-tie candidates engine
        per slab, global ids via the slab's row origin, shared timers +
        AOT cache, common shape class."""
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

        with self._meta_lock:
            engine_name = self._engine_name
            workers = self._launch_workers
        eng = ResidentKnnEngine(
            rows, self.k, mesh=self.mesh, engine=engine_name,
            id_offset=self.id_offset + int(row_begin), emit="candidates",
            timers=self.timers, executable_cache=self._exec_cache,
            pad_shard_rows=self._pad_shard, **self._engine_kw)
        if workers > 1:
            eng.set_launch_workers(workers)
        return eng

    @property
    def slab_pool(self) -> SlabPool:
        return self._pool

    @property
    def engine_name(self) -> str:
        with self._meta_lock:
            return self._engine_name

    @property
    def degraded_reason(self) -> str | None:
        with self._meta_lock:
            return self._degraded_reason

    def can_degrade(self) -> bool:
        with self._meta_lock:
            return self._engine_name == "pallas_tiled"

    def degrade(self, reason: str) -> None:
        """Swap every resident slab engine (and all future promotions) to
        the XLA twin — the resident engine's degradation contract, pool
        wide. Identical results by the twin-engine contract."""
        with self._meta_lock:
            if self._engine_name != "pallas_tiled":
                raise RuntimeError(
                    f"engine '{self._engine_name}' has no fallback")
            self._engine_name = "tiled"
            self._degraded_reason = reason
        for eng in self._my_engines():
            if eng.can_degrade():
                eng.degrade(reason)

    def set_launch_workers(self, n: int) -> None:
        with self._meta_lock:
            self._launch_workers = max(1, int(n))
            n = self._launch_workers
        for eng in self._my_engines():
            eng.set_launch_workers(n)

    def warmup(self) -> dict:
        """Compile every shape bucket ONCE (into the shared cache — every
        slab engine reuses them), then fill the remaining device budget
        with slabs in row order. Returns the template's warmup dict plus
        the warm-fill summary."""
        t = self._pool.ensure(self._pkey(self._template_slab),
                              count_stall=False)
        info = t.warmup()
        filled = self._pool.warm_fill(
            self._pkeys(s for s in range(self.num_slabs)
                        if self._nonempty[s] and s != self._template_slab),
            self.slab_device_bytes)
        info["warm_slabs"] = sorted(
            [self._template_slab]
            + [k[1] if isinstance(k, tuple) else k for k in filled])
        return info

    # ----------------------------------------------------------------- routing

    def _lower_bounds(self, q: np.ndarray) -> np.ndarray:
        """f64[n, S] squared lower-bound distance per (query, slab); +inf
        for empty slabs — the PodBoundsTable decision, in-process."""
        from mpi_cuda_largescaleknn_tpu.utils.math import (
            aabb_lower_bound_dist2,
        )

        out = np.full((len(q), self.num_slabs), np.inf)
        ne = self._nonempty
        if ne.any() and len(q):
            out[:, ne] = aabb_lower_bound_dist2(
                q, self._slab_lo[ne], self._slab_hi[ne])
        return out

    def _wave1_want(self, q: np.ndarray):
        """The PR-7 wave-1 routing rule, shared by dispatch and the
        prefetcher so hints can never warm a different slab set than the
        dispatch will pin: each query wants its nearest-bounds slab PLUS
        every slab whose box contains it (a zero lower bound can never
        certify away). Returns (lb f64[n, S], want bool[n, S])."""
        lb = self._lower_bounds(q)
        first = np.argmin(lb, axis=1)
        reach = np.isfinite(lb[np.arange(len(q)), first])
        want = lb <= 0.0
        rows_r = np.nonzero(reach)[0]
        want[rows_r, first[rows_r]] = True
        return lb, want

    def prefetch_hint(self, queries) -> None:
        """Announce a FUTURE batch's rows: compute its wave-1 slab set and
        enqueue async promotions, so by the time that batch dispatches its
        slabs are warm (the batcher calls this with the queued rows right
        after dispatching the current batch — the PR-2 overlap applied to
        data motion)."""
        q = np.asarray(queries, np.float32).reshape(-1, self.dim)
        if len(q) == 0:
            return
        _lb, want = self._wave1_want(q)
        self.timers.count("prefetch_hints", 1)
        self._pool.prefetch(
            self._pkeys(np.nonzero(want.any(axis=0))[0].tolist()))

    # --------------------------------------------------------------- query API

    #: samples kept by the drift guard: enough history to smooth one
    #: noisy batch, short enough that recovery re-admits within ~a ring
    skip_cold_window = 64

    def _skip_cold_admit(self) -> bool:
        """Drift-aware admission for ``stream_skip_cold`` (TUNING.md's
        PR-16 caveat, closed): sample the pool's cumulative stall clock,
        and refuse the recall plan when the stall FRACTION over the
        sampled window is already above ``skip_cold_stall_limit`` — a
        pool that busy promoting is in traffic drift, where skipping
        collapses recall without saving the churn. Counted in
        ``skip_cold_refusals``; rides the injectable clock."""
        now = self._clock()
        _stalls, stall_s = self._pool.stall_totals(self.tenant)
        with self._meta_lock:
            ring = self._stall_ring
            ring.append((now, stall_s))
            if len(ring) > self.skip_cold_window:
                del ring[0]
            t0, s0 = ring[0]
            span = now - t0
            if len(ring) < 2 or span <= 0.0:
                return True  # no signal yet: admit
            if (stall_s - s0) / span > self.skip_cold_stall_limit:
                self.skip_cold_refusals += 1
                return False
            return True

    def dispatch(self, queries: np.ndarray, plan=None,
                 seed_radius=None) -> _StreamHandle:
        """Wave 1 of the streamed batch: route rows to their
        nearest-bounds slab plus every slab whose box contains them (the
        PR-7 rule — a zero lower bound can never certify away), PIN that
        slab set, promote any non-resident member (a stall, counted), and
        launch the per-slab sub-batches on the slab engines' async launch
        pools. Also enqueues prefetch for the next-nearest
        ``prefetch_depth`` slabs — the likely escalation targets — so an
        escalation wave finds them warm.

        ``plan`` (serve/recall.py RecallPlan, None = exact): the program
        knobs ride into each slab engine's plan-keyed executable, and
        ``stream_skip_cold`` defers every cold wave-1 slab except each
        query's NEAREST one (always ensured, even cold, so every row gets
        k real candidates) — deferred slabs warm asynchronously and are
        reconsidered against the folded kth distance in the escalation
        loop, where a still-cold one is SKIPPED for recall instead of
        stalled on (``stream_skipped_promotions``)."""
        queries = np.ascontiguousarray(
            np.asarray(queries, np.float32).reshape(-1, self.dim))
        n = len(queries)
        # certified radius seeds (serve/qcache.py): exact tier only — an
        # approximate plan's visit schedule (skip_cold) must not interact
        # with a tightened init radius, so seeds are dropped under a plan
        seeds = None
        if seed_radius is not None and plan is None:
            seeds = np.asarray(seed_radius, np.float32).reshape(-1)
            if len(seeds) != n:
                raise ValueError(
                    f"seed_radius has {len(seeds)} rows for {n} queries")
            if not np.any(np.isfinite(seeds)):
                seeds = None
        handle = _StreamHandle(queries, n, self.engine_name, self._clock(),
                               plan=plan, seeds=seeds)
        if n == 0:
            return handle
        lb, want = self._wave1_want(queries)
        visited = np.zeros((n, self.num_slabs), bool)
        handle.skip_cold = (plan is not None and plan.stream_skip_cold
                            and self._skip_cold_admit())
        if handle.skip_cold:
            resident = self._resident_local()
            first = np.argmin(lb, axis=1)
            must = set(int(s) for i, s in enumerate(first)
                       if np.isfinite(lb[i, s]))
            deferred = [s for s in np.nonzero(want.any(axis=0))[0].tolist()
                        if s not in resident and s not in must]
            if deferred:
                # serve this batch from what is warm; warm the rest UNDER
                # its compute for the escalation pass / future batches
                want[:, deferred] = False
                self._pool.prefetch(self._pkeys(deferred))
        wave = [(s, np.nonzero(want[:, s])[0])
                for s in range(self.num_slabs) if want[:, s].any()]
        sids = [s for s, _rows in wave]
        self._pool.pin(self._pkeys(sids))
        handle.pinned.update(sids)
        # hand the whole wave to the promotion thread first: a multi-slab
        # cold wave then builds one slab on this thread while the next
        # builds asynchronously, instead of strictly serial stalls
        self._pool.prefetch(self._pkeys(sids))
        try:
            for s, rows in wave:
                eng = self._pool.ensure(self._pkey(s))
                # seeded slab sub-batch: a slab-local init slot (seed², -1)
                # only ever displaces candidates with d2 ≥ seed², which sit
                # strictly beyond the certified global kth — the fold pushes
                # every filler slot out before certification closes
                if seeds is not None:
                    sub = eng.dispatch(queries[rows],
                                       seed_radius=seeds[rows])
                elif plan is None:
                    sub = eng.dispatch(queries[rows])
                else:
                    sub = eng.dispatch(queries[rows], plan=plan)
                handle.subs.append((s, rows, eng, sub))
                visited[rows, s] = True
        except BaseException:
            # a failed promotion/dispatch must not leak this batch's pins
            # — leaked pins would make the slabs permanently unevictable
            self._pool.unpin(self._pkeys(handle.pinned))
            handle.pinned = set()
            raise
        handle.lb, handle.visited = lb, visited
        if self.prefetch_depth > 0:
            # escalation insurance: the unvisited slabs nearest ANY row of
            # this batch are the ones its escalation waves would stall on
            rest = np.where(want.any(axis=0), np.inf, lb.min(axis=0))
            order = np.argsort(rest, kind="stable")
            depth = [int(s) for s in order[:self.prefetch_depth]
                     if np.isfinite(rest[s])]
            if depth:
                self._pool.prefetch(self._pkeys(depth))
        return handle

    def _complete_fold(self, handle: _StreamHandle):
        """Fold wave partials; escalate uncertified (query, slab) pairs
        until certification closes — the RoutedPodFanout loop, in-process
        and loss-free (every slab is always reachable: a miss stalls, it
        never drains). Returns the folded (d2[n, k], idx[n, k])."""
        from mpi_cuda_largescaleknn_tpu.serve.frontend import fold_candidates

        n, k = handle.n, self.k
        cur_d2 = np.full((n, k), np.inf, np.float32)
        cur_idx = np.full((n, k), -1, np.int32)
        seeds = handle.seeds
        if seeds is not None:
            # certified seeds bound the fold's running kth from wave 1 on:
            # r2 starts at seed² (> the true kth², strictly), so escalation
            # promotes strictly fewer slabs while every slab holding a true
            # top-k or boundary-tied candidate still satisfies
            # lb_safe <= true kth² <= r2 at every wave — identical answer
            cur_d2[:] = (seeds * seeds)[:, None]
        q, lb, visited = handle.queries, handle.lb, handle.visited
        plan = handle.plan
        # recall plan: (c) shave the escalation margin, (d) never stall
        # an escalation wave on a cold slab — skip it for recall instead
        slack = float(plan.route_slack) if plan is not None else 0.0
        skip_cold = handle.skip_cold
        lb_safe = lb * (1.0 - self.cert_slack)
        reachable = np.isfinite(lb_safe)
        subs = handle.subs
        try:
            wave = 1
            while True:
                for s, rows, eng, sub in subs:
                    d2p, idxp = eng.complete_candidates(sub)
                    fold_candidates(cur_d2, cur_idx, rows, d2p, idxp, k)
                r2 = cur_d2[:, k - 1].astype(np.float64)
                need = (~visited) & reachable & (
                    lb_safe <= r2[:, None] * (1.0 - slack))
                if not need.any():
                    break
                sids = [s for s in range(self.num_slabs) if need[:, s].any()]
                if skip_cold:
                    resident = self._resident_local()
                    cold = [s for s in sids if s not in resident]
                    if cold:
                        # the recall sacrifice (d) makes: these bounds
                        # COULD beat the kth distance, but the slab is not
                        # device-resident — give those pairs up, count the
                        # skipped promotions, and warm the slabs async so
                        # the NEXT batch finds them resident
                        self.timers.count("stream_skipped_promotions",
                                          len(cold))
                        for s in cold:
                            visited[need[:, s], s] = True
                        self._pool.prefetch(self._pkeys(cold))
                        sids = [s for s in sids if s in resident]
                        if not sids:
                            continue
                if wave == 1:
                    self.timers.count("stream_escalations",
                                      int(need.any(axis=1).sum()))
                self.timers.count("stream_escalation_waves", 1)
                wave += 1
                new = [s for s in sids if s not in handle.pinned]
                if new:
                    self._pool.pin(self._pkeys(new))
                    handle.pinned.update(new)
                    # overlap multi-slab waves
                    self._pool.prefetch(self._pkeys(new))
                subs = []
                for s in sids:
                    rows = np.nonzero(need[:, s])[0]
                    eng = self._pool.ensure(self._pkey(s))
                    if seeds is not None:
                        sub = eng.dispatch(q[rows], seed_radius=seeds[rows])
                    elif plan is None:
                        sub = eng.dispatch(q[rows])
                    else:
                        sub = eng.dispatch(q[rows], plan=plan)
                    subs.append((s, rows, eng, sub))
                    visited[rows, s] = True
        finally:
            self._pool.unpin(self._pkeys(handle.pinned))
            handle.pinned = set()
        self.timers.hist("stream_batch_seconds").record(
            self._clock() - handle.t0)
        self.timers.count("stream_batches", 1)
        return cur_d2, cur_idx

    def complete(self, handle: _StreamHandle):
        """(dists f32[n], idx i32[n, k]) — the public engine contract,
        bit-identical to a fully-resident engine of the same knobs (the
        canonical fold over canonical-tie slab partials; the routed-pod
        parity argument with slabs instead of hosts)."""
        if handle.n == 0:
            return (np.zeros(0, np.float32),
                    np.zeros((0, self.k), np.int32))
        if self.emit == "candidates":
            raise RuntimeError(
                "emit='candidates' streaming engines return full candidate"
                " rows — use complete_candidates (the routed host's fold)")
        d2, idx = self._complete_fold(handle)
        return np.sqrt(d2[:, self.k - 1]), idx

    def complete_candidates(self, handle: _StreamHandle):
        """Routed-host streaming ``complete``: the folded full candidate
        rows (dist2[n, k], idx[n, k]) over this engine's slabs — what
        POST /route_knn serves when a routed host streams sub-slabs."""
        if handle.n == 0:
            return (np.full((0, self.k), np.inf, np.float32),
                    np.full((0, self.k), -1, np.int32))
        if self.emit != "candidates":
            raise RuntimeError(
                "engine was built with emit='final' — construct with "
                "emit='candidates' for the routed candidate-row contract")
        return self._complete_fold(handle)

    def query(self, queries: np.ndarray, plan=None, seed_radius=None):
        return self.complete(self.dispatch(queries, plan=plan,
                                           seed_radius=seed_radius))

    def refetch_exact(self, queries):
        """Survivor re-fetch hook (PR-17 quantized wire): exact f32
        candidate rows, byte-equal to any earlier batch containing these
        rows — the streaming fold is bit-deterministic per query row
        (commutative fold + certification closure), so re-asking costs a
        promotion at worst, never bits."""
        return self.complete_candidates(self.dispatch(queries))

    def close(self) -> None:
        if self._owns_pool:
            self._pool.close()

    # ------------------------------------------------------------------ stats

    def stats(self) -> dict:
        pool = self._pool.stats()
        cache = self._exec_cache.stats()
        if self.tenant is None:
            my_resident = pool["device_resident"]
        else:
            mine = pool.get("tenants", {}).get(self.tenant, {})
            my_resident = int(mine.get("device_resident", 0))
        with self._meta_lock:
            engine_name = self._engine_name
            degraded_reason = self._degraded_reason
            skip_cold_refusals = self.skip_cold_refusals
        return {
            "engine": engine_name,
            "merge": self.merge_mode,
            "score_dtype": self.score_dtype,
            "score_mode": self.score_mode,
            "dim": self.dim,
            "degraded_reason": degraded_reason,
            "n_points": self.n_points,
            "k": self.k,
            "num_shards": self.num_shards,
            "multihost": False,
            "process_index": self.process_index,
            "process_count": self.process_count,
            "my_positions": list(range(self.num_shards)),
            "row_offset": self.id_offset,
            "emit": self.emit,
            "canonical_ties": self.canonical_ties,
            "max_radius": (None if math.isinf(self.max_radius)
                           else self.max_radius),
            # the routing surface a pod front end folds over: one box per
            # SLAB (the streaming engine's own routing granularity)
            "shard_bounds": self.slab_aabbs,
            "device_bytes": self.slab_device_bytes * my_resident,
            "max_batch": self.max_batch,
            "bucket_size": self.bucket_size,
            "shape_buckets": list(self.shape_buckets),
            # AOT discipline pool-wide: the shared cache's compile count is
            # the recompile-freedom number (flat across slab churn)
            "compiled_shapes": cache["shapes"],
            "compile_count": cache["compiles"],
            "executable_cache": cache,
            "query_buckets": {str(qv): b for qv, b in
                              sorted(self.query_buckets.items())},
            "sort_queries": self.sort_queries,
            "tiles_executed": self.timers.counter("tiles_executed"),
            "tiles_skipped": self.timers.counter("tiles_skipped"),
            "tiles_executed_mxu": self.timers.counter("tiles_executed_mxu"),
            "tiles_skipped_mxu": self.timers.counter("tiles_skipped_mxu"),
            "tiles_executed_vpu": self.timers.counter("tiles_executed_vpu"),
            "tiles_skipped_vpu": self.timers.counter("tiles_skipped_vpu"),
            "fetch_bytes": self.timers.counter("fetch_bytes"),
            "result_rows": self.timers.counter("result_rows"),
            # the tiered-index surface: per-tier residency, budget, hit/
            # miss counters, promotion/eviction totals, stall accounting
            "slab_pool": dict(
                pool,
                slab_device_bytes=self.slab_device_bytes,
                prefetch_depth=self.prefetch_depth,
                prefetch_hints=self.timers.counter("prefetch_hints"),
                **({} if self.tenant is None
                   else {"tenant": self.tenant})),
            "streaming": {
                "num_slabs": self.num_slabs,
                "batches": self.timers.counter("stream_batches"),
                "escalations": self.timers.counter("stream_escalations"),
                "escalation_waves":
                    self.timers.counter("stream_escalation_waves"),
                # recall-SLO tier (stream_skip_cold): cold-slab promotions
                # skipped for recall instead of stalled on
                "skipped_promotions":
                    self.timers.counter("stream_skipped_promotions"),
                # drift guard (PR 17): skip-cold plans refused because
                # the pool's stall fraction was already above the limit
                "skip_cold_refusals": skip_cold_refusals,
                "skip_cold_stall_limit": self.skip_cold_stall_limit,
            },
            "timers": self.timers.report(),
        }
