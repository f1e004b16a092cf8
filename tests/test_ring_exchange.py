"""What the ring exchanges (parallel/ring.py ``ring.exchange_bytes``): the
bytes every device sends in a self-join's rotations, counted on the host
from the shard's static shapes -- both counter-rotating copies of the
points, ids and bucket boxes, once per round but the last -- and summed
process-wide while a profiler trace is collected."""

import tempfile

import jax
import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
from mpi_cuda_largescaleknn_tpu.obs.timers import (
    PhaseTimers,
    traced_counters_snapshot,
)
from mpi_cuda_largescaleknn_tpu.ops.partition import choose_buckets
from mpi_cuda_largescaleknn_tpu.parallel import ring
from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

from .oracle import assert_dist_equal, kth_nn_dist, random_points

N, K, BUCKET, GROUP = 1200, 8, 32, 2


@pytest.fixture(autouse=True)
def fresh_programs():
    ring._ring_program.cache_clear()
    yield
    ring._ring_program.cache_clear()


def _traced(fn):
    """Run ``fn`` under a host-only profiler trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()


def copy_bytes(n_local: int) -> int:
    """One copy of one device's rotating shard: the point-group-coarsened
    partition's f32 points and i32 ids, and an f32 lower and upper box per
    coarse bucket."""
    b, s = choose_buckets(n_local, BUCKET)
    return b * s * (3 * 4 + 4) + 2 * (b // GROUP) * 3 * 4


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_exchange_bytes_from_shapes_and_traced_only(shards):
    pts = random_points(N, seed=40 + shards)
    model = UnorderedKNN(KnnConfig(k=K, engine="tiled", num_shards=shards,
                                   bucket_size=BUCKET, point_group=GROUP),
                         mesh=get_mesh(shards))
    before = traced_counters_snapshot().get("ring.exchange_bytes", 0)
    got = model.run(pts)
    assert_dist_equal(got, kth_nn_dist(pts, pts, K))
    # per device: both copies, once per round but the last
    per_device = 2 * copy_bytes(N // shards) * (
        ring.ring_total_rounds(shards) - 1)
    want = shards * per_device
    assert model.last_stats["exchange_bytes"] == want
    assert model.timers.counter("ring.exchange_bytes") == want
    if shards == 1:
        assert want == 0
    # an untraced job adds nothing to the traced sum; a traced one adds
    # its count
    assert traced_counters_snapshot().get("ring.exchange_bytes", 0) == before
    _traced(lambda: model.run(pts))
    assert (traced_counters_snapshot().get("ring.exchange_bytes", 0)
            == before + want)
    assert model.timers.counter("ring.exchange_bytes") == 2 * want


def test_rotation_bytes_counts_both_copies_of_every_leaf():
    shard = (jax.ShapeDtypeStruct((4, 10, 3), np.float32),
             np.zeros((4, 10), np.int32),
             jax.ShapeDtypeStruct((4, 3), np.float32),
             jax.ShapeDtypeStruct((4, 3), np.float32))
    assert ring.rotation_bytes(shard) == 2 * (480 + 160 + 48 + 48)


@pytest.mark.parametrize("by", [0, 1, 3])
def test_counts_reach_the_traced_sum_only_while_traced(by):
    t = PhaseTimers()
    name = f"test.traced_count.{by}"
    t.count(name, by)
    assert name not in traced_counters_snapshot()
    _traced(lambda: (t.count(name, by), t.count(name, by)))
    assert traced_counters_snapshot()[name] == 2 * by
    assert t.counter(name) == 3 * by
