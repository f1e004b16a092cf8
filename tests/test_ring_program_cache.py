"""The fused ring program (parallel/ring.py ``_ring_program``) is built once
per configuration and reused by every later ``ring_knn`` call: repeated
jobs neither re-trace nor re-lower it, and the answers stay the oracle's."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
from mpi_cuda_largescaleknn_tpu.parallel import ring
from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

from .oracle import assert_dist_equal, kth_nn_dist, random_points


@pytest.fixture(autouse=True)
def fresh_programs():
    # the builder is process-wide: start every test from no built program,
    # so each model's counter reads only this test's builds
    ring._ring_program.cache_clear()
    yield
    ring._ring_program.cache_clear()


def _model(**kw):
    kw.setdefault("k", 4)
    kw.setdefault("bucket_size", 32)
    return UnorderedKNN(KnnConfig(engine="tiled", num_shards=2, **kw),
                        mesh=get_mesh(2))


def _builds(model):
    return model.timers.counters_snapshot().get("ring.program_builds", 0)


def test_repeated_jobs_build_once_and_agree_bitwise():
    pts = random_points(600, seed=21)
    model = _model()
    first = model.run(pts)
    stats = dict(model.last_stats)
    second = model.run(pts)
    assert np.array_equal(first, second)
    assert model.last_stats == stats
    assert _builds(model) == 1
    assert model.timers.spans_snapshot()["ring.launch"][0] == 2
    assert_dist_equal(first, kth_nn_dist(pts, pts, 4))


@pytest.mark.parametrize("change", [{"k": 6}, {"bucket_size": 64}])
def test_other_configuration_builds_its_own(change):
    pts = random_points(500, seed=22)
    base = _model()
    base.run(pts)
    other = _model(**change)
    got = other.run(pts)
    assert _builds(base) == _builds(other) == 1
    assert ring._ring_program.cache_info().currsize == 2
    assert_dist_equal(got, kth_nn_dist(pts, pts, other.config.k))
    # and the first configuration still answers with its own program
    assert_dist_equal(base.run(pts), kth_nn_dist(pts, pts, 4))
    assert _builds(base) == 1


def test_new_point_count_reuses_the_builder():
    model = _model()
    for n, seed in ((600, 23), (333, 24), (600, 25)):
        pts = random_points(n, seed=seed)
        assert_dist_equal(model.run(pts), kth_nn_dist(pts, pts, 4))
    assert _builds(model) == 1
    assert ring._ring_program.cache_info().currsize == 1


@pytest.mark.parametrize("radius", [jnp.inf, math.inf, 0.09],
                         ids=["jnp_inf", "math_inf", "finite"])
def test_max_radius_forms_key_the_builder(radius):
    pts = random_points(400, seed=26)
    model = _model(max_radius=radius)
    got = model.run(pts)
    assert_dist_equal(got, kth_nn_dist(pts, pts, 4, max_radius=radius))
    # an infinite radius given either way is one configuration
    _model(max_radius=math.inf if radius == jnp.inf else radius).run(pts)
    assert ring._ring_program.cache_info().currsize == 1
    assert _builds(model) == 1
