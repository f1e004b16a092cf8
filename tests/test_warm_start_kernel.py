"""The self-join's warm start as a Pallas kernel (ops/pallas/warm_start.py,
wrapped by ops/tiled.py ``warm_start_self``): the warm rows plus the
traversal with the own bucket skipped give the cold traversal's rows bit
for bit, the distance-only rows keep the same distances, the fused ring
program holds no sort as wide as a bucket, and the ring reports the
kernel's fold passes."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.sharding import (
    pad_and_flatten,
    slab_bounds,
)
from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
from mpi_cuda_largescaleknn_tpu.ops.candidates import init_candidates
from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_tiled import (
    knn_update_tiled_pallas,
)
from mpi_cuda_largescaleknn_tpu.ops.pallas.warm_start import row_block
from mpi_cuda_largescaleknn_tpu.ops.partition import (
    choose_buckets,
    coarsen_buckets,
    partition_points,
)
from mpi_cuda_largescaleknn_tpu.ops.tiled import warm_start_self
from mpi_cuda_largescaleknn_tpu.parallel import ring
from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

from .oracle import assert_dist_equal, kth_nn_dist, random_points


@pytest.fixture(autouse=True)
def fresh_programs():
    ring._ring_program.cache_clear()
    yield
    ring._ring_program.cache_clear()


def _bucketed(n, bucket, group, seed):
    pts = random_points(n, seed=seed)
    q = partition_points(jnp.asarray(pts), bucket_size=bucket)
    return q, coarsen_buckets(q, group)


@pytest.mark.parametrize(
    "n,bucket,group,k,radius",
    [
        (700, 32, 1, 8, np.inf),      # 24-row buckets: pad lanes and ids
        (700, 32, 2, 8, np.inf),      # the fused driver's point group
        (600, 16, 2, 1, np.inf),      # k=1: one pass a row
        (2000, 128, 2, 33, np.inf),   # 256 lanes: a two-segment fold
        (2000, 128, 1, 33, np.inf),   # 128 lanes: one segment
        (700, 32, 2, 25, 0.15),       # finite radius: rows left underfull
        (700, 32, 4, 8, np.inf),      # four buckets to a program's tile
    ],
)
def test_warm_plus_skip_matches_cold_traversal(n, bucket, group, k, radius):
    q, pc = _bucketed(n, bucket, group, seed=n + k)
    rows = q.num_buckets * q.bucket_size
    cold = knn_update_tiled_pallas(init_candidates(rows, k, radius), q, pc,
                                   self_group=group)
    warm0, passes = warm_start_self(pc, k, radius, with_passes=True)
    warm = knn_update_tiled_pallas(warm0, q, pc, skip_self=jnp.int32(1),
                                   self_group=group)
    real = np.asarray(q.ids).reshape(-1) >= 0
    np.testing.assert_array_equal(np.asarray(warm.dist2)[real],
                                  np.asarray(cold.dist2)[real])
    np.testing.assert_array_equal(np.asarray(warm.idx)[real],
                                  np.asarray(cold.idx)[real])
    assert int(passes) > 0


@pytest.mark.parametrize("group,k", [(1, 8), (2, 8), (2, 17)])
def test_distance_only_rows_keep_the_distances(group, k):
    q, pc = _bucketed(900, 32, group, seed=5 + k)
    ids = warm_start_self(pc, k)
    pos = warm_start_self(pc, k, decode_ids=False)
    np.testing.assert_array_equal(np.asarray(pos.dist2),
                                  np.asarray(ids.dist2))
    # undecoded entries are flat lane positions into the bucketed ids,
    # never the traversal fold's own encoding (<= -2)
    p, i = np.asarray(pos.idx), np.asarray(ids.idx)
    assert p.min() >= -1
    flat_ids = np.asarray(pc.ids).reshape(-1)
    adopted = p >= 0
    np.testing.assert_array_equal(flat_ids[p[adopted]], i[adopted])
    np.testing.assert_array_equal(p[~adopted], i[~adopted])


def _lowered_ring(n, bucket, with_ids, k=6):
    """HLO of the fused pallas_tiled program at one shard, and the width
    of the point group's tile (S*G lanes)."""
    pts = random_points(n, seed=81)
    mesh = get_mesh(1)
    flat, ids, _counts, npad = pad_and_flatten(
        [pts[b:e] for b, e in slab_bounds(n, 1)], id_bases=[0], dim=3)
    group = ring._effective_group(0, npad, bucket, "pallas_tiled")
    program, _kernel, _partitioned = ring._ring_program(
        mesh, k, float("inf"), "pallas_tiled", 128, 128, bucket, 1, group,
        "f32", with_ids)
    flat, ids = jnp.asarray(flat), jnp.asarray(ids)
    q = ring.partition_sharded(flat, ids, mesh, bucket)
    num_qb, s_q = choose_buckets(npad, bucket)
    return (program.lower(flat, ids, q).as_text(dialect="hlo"),
            s_q * group, num_qb // group)


_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.+?)\s+([\w-]+)"
                    r"\(([^)]*)\)")


def _sorts_over(text, lanes):
    """The sort and top-k instructions of an HLO module that read or make
    an array whose last dimension is ``lanes``."""
    types, sorts = {}, []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, typ, op, operands = m.groups()
        types[name] = typ
        if op in ("sort", "topk") or "TopK" in line:
            sorts.append((line.strip(), typ, operands))
    wide = []
    for line, typ, operands in sorts:
        shapes = [typ] + [types.get(o.split()[-1].lstrip("%"), "")
                          for o in operands.split(",") if o.strip()]
        if any(f",{lanes}]" in t or f"[{lanes}]" in t for t in shapes):
            wide.append(line)
    return wide


@pytest.mark.parametrize("with_ids", [False, True])
def test_ring_program_holds_no_bucket_wide_sort(with_ids):
    text, lanes, num_pb = _lowered_ring(1500, 32, with_ids)
    # the visit schedule sorts [Bq, Bp] box distances: keep it narrower
    assert num_pb != lanes, (num_pb, lanes)
    assert _sorts_over(text, lanes) == []
    assert "warm_fold" in text


@pytest.mark.parametrize(
    "engine,shards,stepwise",
    [("pallas_tiled", 1, False), ("pallas_tiled", 2, False),
     ("pallas_tiled", 2, True), ("tiled", 1, False)])
def test_warm_fold_passes_counter(engine, shards, stepwise, tmp_path):
    pts = random_points(600, seed=91)
    kw = {"checkpoint_dir": str(tmp_path)} if stepwise else {}
    model = UnorderedKNN(
        KnnConfig(k=6, engine=engine, num_shards=shards, bucket_size=32,
                  query_tile=128, point_tile=128, **kw),
        mesh=get_mesh(shards))
    got = model.run(pts)
    assert_dist_equal(got, kth_nn_dist(pts, pts, 6))
    passes = model.last_stats["warm_fold_passes"]
    if engine == "pallas_tiled":
        # each program folds its rows' own bucket: at least one pass, and
        # at most k for rows that start cold
        npad = -(-len(pts) // shards)
        num_qb, s_q = choose_buckets(npad, 32)
        group = ring._effective_group(0, npad, 32, engine)
        lanes = s_q * group
        programs = shards * (num_qb // group) * (lanes // row_block(lanes))
        assert programs <= passes <= programs * 6
    else:
        assert passes == 0
    if not stepwise:
        assert model.timers.counter("ring.warm_fold_passes") == passes
