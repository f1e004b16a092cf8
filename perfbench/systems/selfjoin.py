"""Batch self-join driver: ``UnorderedKNN.run`` back to back (closed loop).

The window calls the model the way ``cli/unordered_main.py`` does, on the
same seeded points each time, until ``--seconds`` have passed; the rate is
every point of every completed job over the time they took. Each job's
answers are sampled (rows drawn from the seed) and compared with the plain
reference after the window.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench.systems.common import checked, compare, make_points

PHASES = ("shard_and_pad", "ring", "extract")

#: the traffic kind this driver reads, and every parameter of it
TRAFFIC_KIND = "closed_jobs"
JOBS = {"kind": TRAFFIC_KIND, "what": "", "assumed": {}, "warm_jobs": 1,
        "check_rows_per_job": None, "trace_jobs": 2}


class Cell:
    def __init__(self, spec, seed: int, seconds: float):
        self.spec = spec
        self.seed = int(seed)
        self.seconds = float(seconds)
        cfg = spec.config
        self.n = int(cfg["points"])
        self.k = int(cfg["k"])
        self.limit = int(cfg["guarantee"]["kth_ulp_max"])
        self.traffic = checked(spec.traffic, JOBS, "closed-jobs traffic")
        self.rows_per_job = int(self.traffic["check_rows_per_job"])
        self.jobs: list[dict] = []
        self.samples: list[tuple[np.ndarray, np.ndarray]] = []
        self.bad_rows = 0
        self.elapsed = None

    # -------------------------------------------------------------- set-up

    def setup(self) -> None:
        from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
        from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

        cfg = self.spec.config
        self.points = make_points(cfg.get("data", {}), self.n, self.seed)
        shards = int(cfg["shards"])
        self.model = UnorderedKNN(
            KnnConfig(k=self.k, engine=cfg["engine"], num_shards=shards),
            mesh=get_mesh(shards))
        for _ in range(int(self.traffic["warm_jobs"])):
            self.model.run(self.points)

    # -------------------------------------------------------------- window

    def _job(self, rng) -> None:
        before = {p: self.model.timers.phases[p].seconds
                  for p in PHASES if p in self.model.timers.phases}
        t0 = time.perf_counter()
        out = self.model.run(self.points)
        t1 = time.perf_counter()
        phases = {p: self.model.timers.phases[p].seconds - before.get(p, 0.0)
                  for p in PHASES if p in self.model.timers.phases}
        stats = self.model.last_stats
        self.jobs.append({"seconds": t1 - t0, "phase_s": phases,
                          "pair_evals": int(stats["pair_evals"]),
                          "tiles": int(stats["tiles"])})
        out = np.asarray(out)
        rows = rng.choice(self.n, size=min(self.rows_per_job, self.n),
                          replace=False)
        if out.shape != (self.n,):
            self.bad_rows += len(rows)
            got = np.full(len(rows), np.nan, np.float32)
        else:
            got = out[rows].astype(np.float32)
        self.samples.append((rows, got))

    def window(self, tracer) -> None:
        rng = np.random.default_rng([self.seed, 1])
        traced = int(self.traffic["trace_jobs"])
        t0 = time.perf_counter()
        if tracer.enabled:
            with tracer.slice():
                for _ in range(traced):
                    self._job(rng)
            self.traced_jobs = list(range(traced))
        while not self.jobs or time.perf_counter() - t0 < self.seconds:
            self._job(rng)
        self.elapsed = time.perf_counter() - t0

    # ------------------------------------------------------------ readings

    def end_to_end(self) -> dict:
        return {"selfjoin_points_per_s":
                len(self.jobs) * self.n / self.elapsed}

    def layer_data(self) -> dict:
        geo = self.spec.config["geometry"]
        traced = [self.jobs[i] for i in getattr(self, "traced_jobs", [])]
        return {
            "points": self.n, "k": self.k, "jobs": len(self.jobs),
            "shards": int(self.spec.config["shards"]),
            "bucket_size": int(geo["bucket_size"]),
            "point_group": int(geo["point_group"]),
            "phase_s": {p: sum(j["phase_s"].get(p, 0.0) for j in self.jobs)
                        for p in PHASES},
            "pair_evals": sum(j["pair_evals"] for j in self.jobs),
            "tiles": sum(j["tiles"] for j in self.jobs),
            "traced_jobs": len(traced),
            "traced_tiles": sum(j["tiles"] for j in traced),
        }

    def attempted_failed(self) -> tuple[int, int]:
        return len(self.jobs), 0

    def release(self) -> None:
        self.model = None

    def _compared(self):
        rows = np.concatenate([r for r, _ in self.samples])
        got = np.concatenate([g for _, g in self.samples])
        return self.points[rows], got

    def check(self) -> dict:
        queries, got = self._compared()
        return compare(self.points, self.k, queries, got, self.bad_rows,
                       self.limit)

    def control_check(self) -> dict:
        queries, got = self._compared()
        return compare(self.points, self.k, queries, got, 0, self.limit,
                       precision="bf16")
