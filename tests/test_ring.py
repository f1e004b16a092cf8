import jax
import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

from .oracle import assert_dist_equal, kth_nn_dist, random_points


def _cfg(**kw):
    kw.setdefault("k", 8)
    kw.setdefault("query_tile", 128)
    kw.setdefault("point_tile", 128)
    return KnnConfig(**kw)


def test_ring_matches_oracle_8_shards():
    pts = random_points(1000, seed=1)
    model = UnorderedKNN(_cfg(), mesh=get_mesh(8))
    got = model.run(pts)
    want = kth_nn_dist(pts, pts, 8)
    assert_dist_equal(got, want)


def test_rank_count_invariance():
    # the reference's implicit oracle (SURVEY.md §4): output is independent of
    # the number of ranks. 1 device vs 8 devices must agree.
    pts = random_points(777, seed=2)  # odd size -> uneven slabs
    d1 = UnorderedKNN(_cfg(), mesh=get_mesh(1)).run(pts)
    d8 = UnorderedKNN(_cfg(), mesh=get_mesh(8)).run(pts)
    assert_dist_equal(d8, d1)


def test_ring_tree_engine_matches_bruteforce():
    pts = random_points(600, seed=3)
    dbf = UnorderedKNN(_cfg(), mesh=get_mesh(4)).run(pts)
    dtr = UnorderedKNN(_cfg(engine="tree"), mesh=get_mesh(4)).run(pts)
    assert_dist_equal(dtr, dbf)


def test_cross_shard_heap_fill():
    # k larger than any single shard's point count: heaps can only fill via
    # the cross-round merge
    pts = random_points(64, seed=4)
    model = UnorderedKNN(_cfg(k=20), mesh=get_mesh(8))  # 8 pts/shard
    got = model.run(pts)
    assert_dist_equal(got, kth_nn_dist(pts, pts, 20))


def test_ring_with_radius():
    pts = random_points(400, seed=5)
    r = 0.06
    got = UnorderedKNN(_cfg(k=10, max_radius=r), mesh=get_mesh(8)).run(pts)
    assert_dist_equal(got, kth_nn_dist(pts, pts, 10, max_radius=r))


def test_fewer_points_than_shards():
    pts = random_points(5, seed=6)
    got = UnorderedKNN(_cfg(k=2), mesh=get_mesh(8)).run(pts)
    assert_dist_equal(got, kth_nn_dist(pts, pts, 2))


def test_timers_populated():
    pts = random_points(100, seed=7)
    model = UnorderedKNN(_cfg(k=3), mesh=get_mesh(2))
    model.run(pts)
    rep = model.timers.report()
    assert "ring" in rep and rep["ring"]["seconds"] > 0


def test_resolve_engine_off_tpu():
    from mpi_cuda_largescaleknn_tpu.parallel.ring import resolve_engine

    # CPU fixture: auto must stay on the XLA twin (Pallas would only
    # interpret here); explicit names pass through untouched
    assert resolve_engine("auto") == "tiled"
    for name in ("tiled", "pallas_tiled", "bruteforce", "tree", "pallas"):
        assert resolve_engine(name) == name


def test_resolve_engine_on_tpu(monkeypatch):
    import jax

    from mpi_cuda_largescaleknn_tpu.parallel.ring import resolve_engine

    # the backend alone decides: no device-kind guessing, no import probe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_engine("auto") == "pallas_tiled"
    assert resolve_engine("tiled") == "tiled"


@pytest.mark.parametrize("model_cls", ["unordered", "prepartitioned"])
@pytest.mark.parametrize("driver", ["fused", "chunked", "stepwise"])
@pytest.mark.parametrize("engine", ["tiled", "bruteforce"])
def test_stats_report_dispatched_engine(engine, driver, model_cls, tmp_path):
    from mpi_cuda_largescaleknn_tpu.models.prepartitioned import (
        PrePartitionedKNN,
    )

    kw = {"fused": {}, "chunked": {"query_chunk": 64},
          "stepwise": {"checkpoint_dir": str(tmp_path)}}[driver]
    cfg = _cfg(k=4, engine=engine, **kw)
    pts = random_points(160, seed=11)
    if model_cls == "unordered":
        model = UnorderedKNN(cfg, mesh=get_mesh(2))
        model.run(pts)
    else:
        model = PrePartitionedKNN(cfg, mesh=get_mesh(2))
        model.run([pts[:80], pts[80:]])
    assert model.last_stats["engine"] == engine


def test_stats_engine_is_observed_not_resolved(monkeypatch):
    # a dispatcher that ran the XLA twin for "pallas_tiled" must show up
    # as "tiled" in the stats the CLIs print (chip_smoke compares them
    # with resolve_engine): the name comes from the kernel dispatched
    from mpi_cuda_largescaleknn_tpu.parallel import ring

    real = ring._tiled_engine_fn
    monkeypatch.setattr(ring, "_tiled_engine_fn", lambda e: real("tiled"))
    # the fused program is built once per configuration: the patched
    # dispatcher needs a fresh build, and must not leave it to later tests
    ring._ring_program.cache_clear()
    try:
        model = UnorderedKNN(_cfg(k=4, engine="pallas_tiled"),
                             mesh=get_mesh(2))
        got = model.run(random_points(160, seed=12))
    finally:
        ring._ring_program.cache_clear()
    assert model.last_stats["engine"] == "tiled"
    pts = random_points(160, seed=12)
    assert_dist_equal(got, kth_nn_dist(pts, pts, 4))


def test_tiled_dispatch_refuses_unresolved_engine():
    from mpi_cuda_largescaleknn_tpu.parallel.ring import _tiled_engine_fn

    assert _tiled_engine_fn("pallas_tiled")[0] == "pallas_tiled"
    with pytest.raises(ValueError, match="resolve 'auto' first"):
        _tiled_engine_fn("auto")


def test_measure_exchange_bandwidth_method():
    from mpi_cuda_largescaleknn_tpu.parallel.ring import (
        measure_exchange_bandwidth,
    )

    rep = measure_exchange_bandwidth(get_mesh(8), 1000, bucket_size=64,
                                     reps=3)
    assert rep["num_shards"] == 8
    # bucketed shard bytes: B*S*(12+4) + 2*B*12 for the bounds
    from mpi_cuda_largescaleknn_tpu.ops.partition import choose_buckets
    b, s = choose_buckets(1000, 64)
    assert rep["shard_bytes"] == b * s * 16 + 2 * b * 12
    assert rep["exchange_GB_per_sec_per_link"] > 0
    # round_seconds is a rounded control-subtracted delta: on a contended
    # host it can legitimately round to 0.0 — only its sign is invariant
    assert rep["round_seconds"] >= 0


def test_partition_sharded_bit_identical_to_partition_points():
    """The hoisted per-shard partition (one compiled level program) must be
    BIT-identical to tracing partition_points per shard — the checkpoint
    fingerprint and every engine's tie order depend on it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_cuda_largescaleknn_tpu.ops.partition import partition_points
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.parallel.ring import partition_sharded

    rng = np.random.default_rng(77)
    shards = len(jax.devices())
    npad = 192
    pts = rng.random((shards * npad, 3)).astype(np.float32)
    ids = np.arange(shards * npad, dtype=np.int32)
    q = partition_sharded(pts, ids, get_mesh(shards), 32)
    b_local = q.pts.shape[0] // shards
    for r in range(shards):
        ref = partition_points(jnp.asarray(pts[r * npad:(r + 1) * npad]),
                               jnp.asarray(ids[r * npad:(r + 1) * npad]),
                               bucket_size=32)
        sl = slice(r * b_local, (r + 1) * b_local)
        np.testing.assert_array_equal(np.asarray(q.pts[sl]),
                                      np.asarray(ref.pts))
        np.testing.assert_array_equal(np.asarray(q.ids[sl]),
                                      np.asarray(ref.ids))
        np.testing.assert_array_equal(np.asarray(q.pos[sl]),
                                      np.asarray(ref.pos))
        np.testing.assert_array_equal(np.asarray(q.lower[sl]),
                                      np.asarray(ref.lower))
        np.testing.assert_array_equal(np.asarray(q.upper[sl]),
                                      np.asarray(ref.upper))
