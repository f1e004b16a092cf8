"""Distance-only self-joins (parallel/ring.py ``_ring_program``'s
``with_ids``): a job that does not ask for neighbour ids runs a program
that neither decodes ids after its folds nor scatters candidate rows back,
with the same distances, bit for bit, as the job that asks for them; the
ids of that job stay the oracle's."""

import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.sharding import (
    pad_and_flatten,
    slab_bounds,
)
from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
from mpi_cuda_largescaleknn_tpu.ops.partition import choose_buckets
from mpi_cuda_largescaleknn_tpu.parallel import ring
from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

from .oracle import assert_dist_equal, kth_nn_dist, pairwise_dist2_np, \
    random_points

K, BUCKET = 6, 32


@pytest.fixture(autouse=True)
def fresh_programs():
    ring._ring_program.cache_clear()
    yield
    ring._ring_program.cache_clear()


def _model(engine, shards, **kw):
    return UnorderedKNN(
        KnnConfig(k=K, engine=engine, num_shards=shards, bucket_size=BUCKET,
                  query_tile=128, point_tile=128, **kw),
        mesh=get_mesh(shards))


def _oracle_ids(pts, k):
    """Each point's k nearest ids, ascending by (distance, id)."""
    d2 = pairwise_dist2_np(pts, pts)
    return np.argsort(d2, axis=1, kind="stable")[:, :k].astype(np.int32)


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("engine",
                         ["tiled", "pallas_tiled", "bruteforce", "tree"])
def test_distance_only_is_bit_identical(engine, shards):
    pts = random_points(480, seed=60 + shards)
    model = _model(engine, shards)
    plain = model.run(pts)
    assert model.last_stats["neighbor_ids"] is False
    assert model.timers.counter("ring.id_decode_folds") == 0
    dists, idx = model.run(pts, return_neighbors=True)
    assert model.last_stats["neighbor_ids"] is True
    assert (model.timers.counter("ring.id_decode_folds")
            == ring._folds_in_rounds(0, ring.ring_total_rounds(shards),
                                     shards))
    # two configurations of one model: one build each
    assert model.timers.counter("ring.program_builds") == 2
    np.testing.assert_array_equal(plain, dists)
    assert_dist_equal(plain, kth_nn_dist(pts, pts, K))
    np.testing.assert_array_equal(idx, _oracle_ids(pts, K))


def test_distance_only_bf16_rescore_is_bit_identical(monkeypatch):
    # the MXU score's rescore still needs the fold's positions: a
    # distance-only fold keeps them instead of ids, and carried entries
    # must not be rescored again by a later fold
    monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")
    pts = random_points(2 * 96, seed=70, dim=8)
    model = _model("pallas_tiled", 2, score_dtype="bf16")
    plain = model.run(pts)
    dists, idx = model.run(pts, return_neighbors=True)
    np.testing.assert_array_equal(plain, dists)
    assert_dist_equal(plain, kth_nn_dist(pts, pts, K))
    np.testing.assert_array_equal(idx, _oracle_ids(pts, K))


def _lowered_hlo(shards, with_ids, n=600):
    """HLO of the fused pallas_tiled program at a small shape, and its
    per-device row count, query bucket count and bucket size."""
    pts = random_points(n, seed=80)
    mesh = get_mesh(shards)
    bounds = slab_bounds(n, shards)
    flat, ids, _counts, npad = pad_and_flatten(
        [pts[b:e] for b, e in bounds], id_bases=[b for b, _ in bounds],
        dim=3)
    group = ring._effective_group(0, npad, BUCKET, "pallas_tiled")
    program, _kernel, partitioned = ring._ring_program(
        mesh, K, float("inf"), "pallas_tiled", 128, 128, BUCKET, shards,
        group, "f32", with_ids)
    assert partitioned
    flat, ids = jnp.asarray(flat), jnp.asarray(ids)
    q = ring.partition_sharded(flat, ids, mesh, BUCKET)
    text = program.lower(flat, ids, q).as_text(dialect="hlo")
    num_qb, s_q = choose_buckets(npad, BUCKET)
    return text, npad, num_qb, s_q


def _ops(text, op, shape):
    """The HLO lines whose ``op`` makes an array of ``shape``."""
    found = []
    for line in text.splitlines():
        lhs, is_op, _ = line.partition(f" {op}(")
        if is_op and f"[{shape}]" in lhs.partition("=")[2]:
            found.append(line)
    return found


@pytest.mark.parametrize("shards", [1, 4])
def test_distance_only_program_drops_the_id_work(shards):
    for with_ids in (True, False):
        text, npad, num_qb, s_q = _lowered_hlo(shards, with_ids)
        # final_fn's candidate rows scattered back to input order
        rows = _ops(text, "scatter", f"{npad},{K}")
        # the decode after a fold: through the visit order, then through
        # the shard's id table, each an i32[Bq, S*k] gather
        decode = _ops(text, "gather", f"{num_qb},{s_q * K}")
        # the k-th distances are scattered back either way
        assert _ops(text, "scatter", f"{npad}")
        if with_ids:
            assert len(rows) == 2, rows
            assert len(decode) >= 2, decode
        else:
            assert rows == [] and decode == [], rows + decode


def test_distance_only_jobs_build_once():
    pts = random_points(500, seed=90)
    model = _model("pallas_tiled", 2)
    first = model.run(pts)
    second = model.run(pts)
    np.testing.assert_array_equal(first, second)
    assert model.timers.counter("ring.program_builds") == 1
    assert model.timers.counter("ring.id_decode_folds") == 0
    assert ring._ring_program.cache_info().currsize == 1
