"""The unordered-data pipeline (flagship): random point order, ring exchange.

End-to-end equivalent of ``cudaMpiKNN_unorderedData``'s main()
(unorderedDataVariant.cu:105-239): slab-split the global point set, run the
R-round ring with stationary queries + persistent heaps, extract per-point
k-th-NN distances, and return them in global point order (= concatenation of
slabs in rank order, matching the reference's barrier-fenced rank-serialized
append to one output file, :229-237).
"""

from __future__ import annotations

import numpy as np

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.sharding import (
    check_neighbor_id_capacity,
    pad_and_flatten,
    slab_bounds,
    trim_per_shard,
)
from mpi_cuda_largescaleknn_tpu.obs.timers import PhaseTimers
from mpi_cuda_largescaleknn_tpu.parallel.mesh import AXIS, get_mesh
from mpi_cuda_largescaleknn_tpu.parallel.ring import (
    ring_knn,
    ring_knn_chunked,
    ring_knn_stepwise,
)


class UnorderedKNN:
    """kNN distances for an unordered global point set over a 1-D mesh."""

    def __init__(self, config: KnnConfig, mesh=None):
        config.validate()
        self.config = config
        self.mesh = mesh if mesh is not None else get_mesh(
            config.num_shards if config.num_shards > 0 else None)
        self.timers = PhaseTimers()
        self.last_stats: dict | None = None  # executed-work stats of the
        # most recent run (pair_evals etc., parallel/ring.py _ring_stats)

    def run(self, points: np.ndarray, return_neighbors: bool = False):
        """points f32[N,3] -> f32[N] distance of each point to its k-th NN.

        With ``return_neighbors`` also returns i32[N, k] global neighbor ids
        (ascending by distance; -1 where fewer than k neighbors exist, e.g.
        under ``-r``) — a capability the reference computes but discards
        (the packed u64 entries at unorderedDataVariant.cu:163-168 hold ids
        that extractFinalResult never reads). Ids are int32: datasets beyond
        2^31 points need the distance-only path.
        """
        cfg = self.config
        num_shards = self.mesh.shape[AXIS]
        n_total = len(points)
        if return_neighbors:
            check_neighbor_id_capacity(n_total)

        with self.timers.phase("shard_and_pad",
                               span="selfjoin.shard_and_pad"):
            bounds = slab_bounds(n_total, num_shards)
            shards = [points[b:e] for b, e in bounds]
            flat, ids, counts, npad = pad_and_flatten(
                shards, id_bases=[b for b, _ in bounds],
                dim=int(np.asarray(points).shape[-1]))

        cands = None
        with self.timers.phase("ring", span="selfjoin.ring"):
            if cfg.query_chunk > 0:
                got = ring_knn_chunked(
                    flat, ids, cfg.k, self.mesh, max_radius=cfg.max_radius,
                    engine=cfg.engine, query_tile=cfg.query_tile,
                    point_tile=cfg.point_tile, bucket_size=cfg.bucket_size,
                    point_group=cfg.point_group,
                    chunk_rows=cfg.query_chunk, merge=cfg.merge,
                    score_dtype=cfg.score_dtype,
                    checkpoint_dir=cfg.checkpoint_dir,
                    checkpoint_every=cfg.checkpoint_every,
                    return_candidates=return_neighbors, return_stats=True)
            elif cfg.checkpoint_dir:
                got = ring_knn_stepwise(
                    flat, ids, cfg.k, self.mesh, max_radius=cfg.max_radius,
                    engine=cfg.engine, query_tile=cfg.query_tile,
                    point_tile=cfg.point_tile, bucket_size=cfg.bucket_size,
                    point_group=cfg.point_group,
                    score_dtype=cfg.score_dtype,
                    checkpoint_dir=cfg.checkpoint_dir,
                    checkpoint_every=cfg.checkpoint_every,
                    return_candidates=return_neighbors, return_stats=True)
            else:
                got = ring_knn(
                    flat, ids, cfg.k, self.mesh, max_radius=cfg.max_radius,
                    engine=cfg.engine, query_tile=cfg.query_tile,
                    point_tile=cfg.point_tile, bucket_size=cfg.bucket_size,
                    point_group=cfg.point_group,
                    score_dtype=cfg.score_dtype,
                    return_candidates=return_neighbors, return_stats=True,
                    timers=self.timers)
            # last_stats["engine"] is the kernel the driver dispatched
            if return_neighbors:
                dists, cands, self.last_stats = got
            else:
                dists, self.last_stats = got
            dists = np.asarray(dists)

        with self.timers.phase("extract", span="selfjoin.extract"):
            out = np.concatenate(trim_per_shard(dists, counts, npad))
            if return_neighbors:
                idx = np.concatenate(
                    trim_per_shard(np.asarray(cands.idx), counts, npad))
                return out, idx
        return out
