"""MXU matmul-form distance scoring (ops/distance.py) vs the elementwise
kernels: the bf16 score + exact f32 rescore must be BIT-IDENTICAL — final
(dist2, idx) including tie ids — to the f32 elementwise path, across
D in {3, 8, 64}, shard counts R in {1, 2, 4}, both merge placements, and
the Pallas / XLA tiled twins; plus the adversarial bf16-ulp property test
(points closer than a bf16 ulp at large ||p|| tie in the approximate score,
and the exact rescore must still recover the exact top-k)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_cuda_largescaleknn_tpu.core.types import PAD_SENTINEL
from mpi_cuda_largescaleknn_tpu.ops.brute_force import knn_update_bruteforce
from mpi_cuda_largescaleknn_tpu.ops.candidates import (
    extract_final_result,
    init_candidates,
)
from mpi_cuda_largescaleknn_tpu.ops.distance import (
    elementwise_dist2,
    mxu_min_dim,
    mxu_scores,
    norms2,
    rescore_width,
    score_tile,
)
from mpi_cuda_largescaleknn_tpu.ops.partition import (
    partition_points,
    scatter_back,
)
from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_tiled import (
    knn_update_tiled_pallas,
)
from mpi_cuda_largescaleknn_tpu.ops.tiled import knn_update_tiled
from tests.oracle import kth_nn_dist, pairwise_dist2_np, random_points

DIMS = (3, 8, 64)


def _with_dups_and_pads(d, seed, n=450, dups=24):
    """A point set with duplicate points (exact ties) and a count that is
    NOT a multiple of any bucket/tile size (ragged sentinel pads)."""
    pts = random_points(n, seed=seed, dim=d)
    pts[n - dups:n] = pts[: dups]  # exact duplicates -> exact tie classes
    return pts


class TestScoreTile:
    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")

    def test_elementwise_matches_legacy_3d_tree(self):
        """The D-generic scorer at D=3 is the exact (dx2+dy2)+dz2 tree —
        bitwise equal to the numpy oracle (the contraction guard makes XLA
        round every step like numpy does)."""
        q = random_points(100, seed=0)
        p = random_points(300, seed=1)
        import jax

        got = np.asarray(jax.jit(elementwise_dist2)(jnp.asarray(q),
                                                    jnp.asarray(p)))
        np.testing.assert_array_equal(got, pairwise_dist2_np(q, p))

    @pytest.mark.parametrize("d", DIMS)
    def test_bf16_survivors_are_exactly_rescored(self, d):
        """score_tile bf16 returns EXACT f32 distances for its survivors:
        every (value, id) pair it emits equals the elementwise tile's value
        at that id — bit for bit."""
        import jax

        q = random_points(40, seed=2, dim=d)
        p = random_points(600, seed=3, dim=d)
        k = 8
        f = jax.jit(lambda q, p: score_tile(
            q, p, jnp.arange(600, dtype=jnp.int32), k, score_dtype="bf16"))
        d2, idx = f(jnp.asarray(q), jnp.asarray(p))
        d2, idx = np.asarray(d2), np.asarray(idx)
        assert d2.shape == (40, rescore_width(k, 600))
        full = np.asarray(jax.jit(elementwise_dist2)(jnp.asarray(q),
                                                     jnp.asarray(p)))
        np.testing.assert_array_equal(d2, np.take_along_axis(full, idx,
                                                             axis=1))
        # lane order restored: survivor ids ascend per row
        assert np.all(np.diff(idx, axis=1) > 0)

    def test_mxu_scores_are_actually_approximate(self):
        """Sanity that the property tests below test something: the bf16
        matmul-form scores really do deviate from the exact distances (the
        rescore is earning its keep)."""
        q = random_points(64, seed=4, scale=100.0)
        p = random_points(512, seed=5, scale=100.0)
        approx = np.asarray(mxu_scores(jnp.asarray(q), jnp.asarray(p)))
        exact = pairwise_dist2_np(q, p)
        assert not np.array_equal(approx, exact)
        # but they are close in the relative-to-norms sense
        scale = float(np.max(norms2(jnp.asarray(p))))
        assert np.max(np.abs(approx - exact)) < 0.05 * scale


class TestBruteForceMxu:
    """Satellite: the D-generic brute-force tile layout (PAD_SENTINEL
    padding path included), with the D=8 test the issue asks for."""

    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")


    @pytest.mark.parametrize("d", DIMS)
    def test_bitwise_parity_and_oracle(self, d):
        pts = _with_dups_and_pads(d, seed=6)
        qs = random_points(77, seed=7, dim=d)  # ragged vs 32/64 tiles
        k = 8
        st = init_candidates(len(qs), k)
        f32 = knn_update_bruteforce(st, jnp.asarray(qs), jnp.asarray(pts),
                                    query_tile=32, point_tile=64)
        b16 = knn_update_bruteforce(st, jnp.asarray(qs), jnp.asarray(pts),
                                    query_tile=32, point_tile=64,
                                    score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))
        want = np.sqrt(np.sort(pairwise_dist2_np(qs, pts), axis=1)[:, k - 1])
        np.testing.assert_array_equal(
            np.sqrt(np.asarray(b16.dist2)[:, k - 1]), want)

    def test_max_radius_parity_d8(self):
        pts = random_points(400, seed=8, dim=8)
        qs = random_points(50, seed=9, dim=8)
        r = 0.5  # hits both filled and under-full rows at D=8 in [0,1]^8
        st = init_candidates(len(qs), 6, max_radius=r)
        f32 = knn_update_bruteforce(st, jnp.asarray(qs), jnp.asarray(pts))
        b16 = knn_update_bruteforce(st, jnp.asarray(qs), jnp.asarray(pts),
                                    score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))
        assert np.any(np.asarray(f32.idx) == -1)  # radius actually bites


class TestTiledMxu:
    """The XLA traversal twin: bf16 vs f32 bit-parity across the full
    local matrix (ties, ragged pads, duplicate points, max_radius, both
    tie disciplines), D in {3, 8, 64}."""

    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")


    @pytest.mark.parametrize("d", DIMS)
    @pytest.mark.parametrize("canonical", [False, True])
    def test_bitwise_parity(self, d, canonical):
        pts = _with_dups_and_pads(d, seed=10 + d)
        k = 8
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        st = init_candidates(q.num_buckets * q.bucket_size, k)
        f32, tiles_f = knn_update_tiled(st, q, q, with_stats=True,
                                        canonical_ties=canonical)
        b16, tiles_b = knn_update_tiled(st, q, q, with_stats=True,
                                        canonical_ties=canonical,
                                        score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))
        # same schedule, same prune radii -> same executed-tile count
        assert int(tiles_f) == int(tiles_b)
        # and the result is oracle-exact
        dists = extract_final_result(f32).reshape(q.num_buckets,
                                                  q.bucket_size)
        got = np.asarray(scatter_back(dists, q.pos, len(pts), fill=jnp.inf))
        np.testing.assert_array_equal(got, kth_nn_dist(pts, pts, k))

    @pytest.mark.parametrize("d", (3, 8))
    def test_max_radius_parity(self, d):
        pts = random_points(300, seed=20, dim=d)
        r = 0.25 if d == 3 else 0.8
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        st = init_candidates(q.num_buckets * q.bucket_size, 5, max_radius=r)
        f32 = knn_update_tiled(st, q, q)
        b16 = knn_update_tiled(st, q, q, score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))

    def test_precomputed_norms_change_nothing(self, ):
        pts = random_points(256, seed=21, dim=8)
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        st = init_candidates(q.num_buckets * q.bucket_size, 4)
        a = knn_update_tiled(st, q, q, score_dtype="bf16")
        b = knn_update_tiled(st, q, q, score_dtype="bf16",
                             point_norms2=norms2(q.pts))
        np.testing.assert_array_equal(np.asarray(a.dist2), np.asarray(b.dist2))
        np.testing.assert_array_equal(np.asarray(a.idx), np.asarray(b.idx))

    def test_full_stats_fold_counter_is_real(self):
        """with_stats='full' returns an honest fold counter: positive when
        merges ran, bounded by the tile-count upper bound, and ZERO folds
        exactly when zero tiles executed (the old stub fabricated 0)."""
        pts = random_points(300, seed=22)
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        st = init_candidates(q.num_buckets * q.bucket_size, 4)
        out, tiles, folds = knn_update_tiled(st, q, q, with_stats="full")
        assert int(tiles) > 0 and int(folds) > 0
        assert int(folds) <= int(tiles)  # a fold merges >= 1 tile (chunk*V)
        # all-padding queries -> traversal prunes everything immediately
        pad = jnp.full((64, 3), PAD_SENTINEL, jnp.float32)
        qp = partition_points(pad, bucket_size=32)
        stp = init_candidates(qp.num_buckets * qp.bucket_size, 4)
        _, tiles0, folds0 = knn_update_tiled(stp, qp, q, with_stats="full")
        assert int(tiles0) == 0 and int(folds0) == 0


class TestShippedMxuThreshold:
    """The shipped default, no override: at the reference's D=3, and at
    D=8, a bf16 request lowers to the very program the f32 request runs
    (below ``mxu_min_dim()`` the VPU path is the fast path); at D=64 the
    matmul form engages. Either way the answer is the f32 path's, bit for
    bit."""

    @pytest.fixture(autouse=True)
    def _shipped_default(self, monkeypatch):
        monkeypatch.delenv("LSK_MXU_MIN_DIM", raising=False)

    @pytest.mark.parametrize("d,same_program",
                             [(3, True), (8, True), (64, False)])
    def test_bf16_request_bitwise_f32(self, d, same_program):
        pts = _with_dups_and_pads(d, seed=80 + d, n=700)
        q = partition_points(jnp.asarray(random_points(300, seed=90 + d,
                                                       dim=d)),
                             bucket_size=64)
        p = partition_points(jnp.asarray(pts), bucket_size=64)
        st = init_candidates(q.num_buckets * q.bucket_size, 16)
        fns = {m: jax.jit(lambda st, q, p, m=m: knn_update_tiled(
                   st, q, p, with_stats=True, score_dtype=m))
               for m in ("f32", "bf16")}
        texts = {m: fn.lower(st, q, p).as_text() for m, fn in fns.items()}
        assert (texts["f32"] == texts["bf16"]) is same_program
        assert (d < mxu_min_dim()) is same_program
        (f32, tiles_f), (b16, tiles_b) = (fns[m](st, q, p)
                                          for m in ("f32", "bf16"))
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))
        assert int(tiles_f) == int(tiles_b)


class TestPallasMxu:
    """The Pallas twin: widened-row approx fold + post-kernel exact
    rescore must match its own f32 mode bit for bit (canonical rows)."""

    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")


    @pytest.mark.parametrize("d", DIMS)
    def test_bitwise_parity(self, d):
        kernel = knn_update_tiled_pallas
        pts = _with_dups_and_pads(d, seed=30 + d)
        k = 8
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        st = init_candidates(q.num_buckets * q.bucket_size, k)
        f32 = kernel(st, q, q, canonical_ties=True)
        b16 = kernel(st, q, q, canonical_ties=True, score_dtype="bf16")
        # bitwise over REAL query rows. Sentinel pad rows are not queries
        # and every driver drops them (scatter_back skips pos == -1, the
        # serving engine slices them off): a pad row sits at distance 0
        # from the pad points in the exact f32 score (1e30 - 1e30 = 0) but
        # overflows to +inf in the matmul form (|p|^2 = 3e60), so the two
        # modes legitimately disagree there — the same rows
        # test_warm_start_parity_bf16 below already excludes
        real = np.asarray(q.ids).reshape(-1) >= 0
        d2 = np.asarray(f32.dist2)[real]
        np.testing.assert_array_equal(d2, np.asarray(b16.dist2)[real])
        # ids bitwise below the k-boundary. AT an exact equal-distance
        # boundary (the duplicate points above) the f32 kernel's in-VMEM
        # fold keeps strict-< adoption, so which duplicate it keeps follows
        # visit order, while the bf16 path re-sorts a widened window by
        # (dist2, id) — the kernel's documented boundary discipline
        # (knn_update_tiled_pallas ``canonical_ties``), not a parity bug
        ia, ib = np.asarray(f32.idx)[real], np.asarray(b16.idx)[real]
        boundary = d2 == d2[:, -1:]
        np.testing.assert_array_equal(ia[~boundary], ib[~boundary])

    def test_warm_start_parity_bf16(self):
        kernel = knn_update_tiled_pallas
        from mpi_cuda_largescaleknn_tpu.ops.tiled import warm_start_self

        pts = random_points(400, seed=33)
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        st = init_candidates(q.num_buckets * q.bucket_size, 8)
        cold = kernel(st, q, q)
        warm = kernel(warm_start_self(q, 8), q, q, skip_self=jnp.int32(1),
                      score_dtype="bf16")
        real = np.asarray(q.ids).reshape(-1) >= 0
        np.testing.assert_array_equal(np.asarray(warm.dist2)[real],
                                      np.asarray(cold.dist2)[real])


class TestRingMxu:
    """Shard counts R in {1, 2, 4} x merge placements: the full ring /
    replicate-traverse-merge drivers under bf16 vs f32, bit-identical."""

    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")


    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_ring_knn_parity(self, shards):
        import jax
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.parallel.ring import ring_knn

        mesh = get_mesh(shards)
        pts = random_points(shards * 96, seed=40 + shards, dim=8)
        ids = np.arange(len(pts), dtype=np.int32)
        k = 4
        a, ca = ring_knn(pts, ids, k, mesh, bucket_size=16,
                         return_candidates=True)
        b, cb = ring_knn(pts, ids, k, mesh, bucket_size=16,
                         score_dtype="bf16", return_candidates=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(ca.dist2),
                                      np.asarray(cb.dist2))
        np.testing.assert_array_equal(np.asarray(ca.idx), np.asarray(cb.idx))

    @pytest.mark.parametrize("merge", ["host", "device"])
    def test_chunked_merge_parity(self, merge):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.parallel.ring import ring_knn_chunked

        mesh = get_mesh(4)
        pts = random_points(4 * 64, seed=50, dim=8)
        ids = np.arange(len(pts), dtype=np.int32)
        a = ring_knn_chunked(pts, ids, 4, mesh, chunk_rows=32,
                             bucket_size=16, merge=merge)
        b = ring_knn_chunked(pts, ids, 4, mesh, chunk_rows=32,
                             bucket_size=16, merge=merge,
                             score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestServeMxu:
    """The serving engine end to end: score_dtype in the AOT key, the
    precomputed-norms resident operand, per-mode tile counters, and a
    D=8 index served through the full dispatch/complete path."""

    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")


    def test_engine_parity_and_counters(self):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

        pts = random_points(1024, seed=60)
        mesh = get_mesh(2)
        qs = random_points(40, seed=61)
        eng_f = ResidentKnnEngine(pts, 8, mesh=mesh, engine="tiled",
                                  bucket_size=32, max_batch=64, min_batch=16)
        eng_b = ResidentKnnEngine(pts, 8, mesh=mesh, engine="tiled",
                                  bucket_size=32, max_batch=64, min_batch=16,
                                  score_dtype="bf16")
        df, nf = eng_f.query(qs)
        db, nb = eng_b.query(qs)
        np.testing.assert_array_equal(df, db)
        np.testing.assert_array_equal(nf, nb)
        sf, sb = eng_f.stats(), eng_b.stats()
        assert sf["score_dtype"] == "f32" and sb["score_dtype"] == "bf16"
        # per-mode attribution: each engine counts under ITS scorer only
        assert sf["tiles_executed_vpu"] == sf["tiles_executed"] > 0
        assert sf["tiles_executed_mxu"] == 0
        assert sb["tiles_executed_mxu"] == sb["tiles_executed"] > 0
        assert sb["tiles_executed_vpu"] == 0
        # distinct AOT programs, one compile each (key carries the dtype)
        assert eng_f.compile_count == 1 and eng_b.compile_count == 1

    def test_engine_serves_d8(self):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

        pts = random_points(512, seed=62, dim=8)
        eng = ResidentKnnEngine(pts, 4, mesh=get_mesh(1), engine="tiled",
                                bucket_size=32, max_batch=32, min_batch=8,
                                score_dtype="bf16")
        assert eng.dim == 8 and not eng.sort_queries
        qs = random_points(19, seed=63, dim=8)
        dists, nbrs = eng.query(qs)
        want = np.sqrt(np.sort(pairwise_dist2_np(qs, pts), axis=1)[:, 3])
        np.testing.assert_array_equal(dists, want)


class TestBf16UlpProperty:
    """The adversarial exactness property: a cluster of points separated
    by LESS than a bf16 ulp at large ||p|| ties in the approximate score
    (top_k then picks by lane, blind to the true order), and the exact f32
    rescore must still recover the exact top-k — while a hypothetical
    no-rescore bf16 path provably could not."""

    @pytest.fixture(autouse=True)
    def _force_mxu_at_all_dims(self, monkeypatch):
        # exercise the matmul-form machinery at EVERY D (the shipped
        # default falls back to the exact path below mxu_min_dim()=16,
        # where the MXU cannot win — ops/distance.py)
        monkeypatch.setenv("LSK_MXU_MIN_DIM", "1")


    @pytest.mark.parametrize("d", (3, 64))
    def test_rescore_recovers_exact_topk(self, d):
        rng = np.random.default_rng(70 + d)
        k = 8
        base = np.full((d,), 512.0, np.float32)  # bf16 ulp at 512 is 2.0
        # 2k cluster points, pairwise distances ~1e-3 — far below the bf16
        # score error (~||p|| * ulp); lane order is randomized so approx
        # tie-breaking cannot accidentally equal the true order
        cluster = base[None, :] + (rng.random((2 * k, d)).astype(np.float32)
                                   * 1e-3)
        filler = rng.random((400, d)).astype(np.float32)  # near origin: far
        pts = np.concatenate([cluster, filler]).astype(np.float32)
        perm = rng.permutation(len(pts))
        pts = pts[perm]
        q = (base + 0.5).astype(np.float32)[None, :]
        # the approximate scores genuinely cannot rank the cluster
        approx = np.asarray(mxu_scores(jnp.asarray(q), jnp.asarray(pts)))[0]
        exact = pairwise_dist2_np(q, pts)[0]
        cl = np.argsort(exact)[: 2 * k]
        assert len(np.unique(approx[cl])) < 2 * k or not np.array_equal(
            np.argsort(approx[cl], kind="stable"),
            np.argsort(exact[cl], kind="stable"))
        # ...but the rescored engine recovers the exact top-k, bitwise
        st = init_candidates(1, k)
        f32 = knn_update_bruteforce(st, jnp.asarray(q), jnp.asarray(pts))
        b16 = knn_update_bruteforce(st, jnp.asarray(q), jnp.asarray(pts),
                                    score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))
        np.testing.assert_array_equal(np.asarray(b16.dist2)[0],
                                      np.sort(exact, kind="stable")[:k])

    def test_identical_points_tie_by_id_under_canonical(self):
        """Exact duplicates at large norm: every copy ties in BOTH exact
        and approx scores; canonical mode must keep the smallest ids."""
        d, k = 8, 4
        base = np.full((d,), 512.0, np.float32)
        pts = np.concatenate([np.tile(base, (6, 1)),
                              random_points(200, seed=71, dim=d)])
        q = partition_points(jnp.asarray(np.concatenate(
            [base[None, :] + 0.25, random_points(63, seed=72, dim=d)])),
            bucket_size=16)
        p = partition_points(jnp.asarray(pts), bucket_size=16)
        st = init_candidates(q.num_buckets * q.bucket_size, k)
        f32 = knn_update_tiled(st, q, p, canonical_ties=True)
        b16 = knn_update_tiled(st, q, p, canonical_ties=True,
                               score_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(f32.idx),
                                      np.asarray(b16.idx))
        np.testing.assert_array_equal(np.asarray(f32.dist2),
                                      np.asarray(b16.dist2))
        # the query row nearest the dup stack holds ids 0..3 (smallest of
        # the 6 tied copies) under the canonical order
        qpos = np.asarray(q.pos).reshape(-1)
        row = int(np.where(qpos == 0)[0][0])
        np.testing.assert_array_equal(np.asarray(f32.idx)[row],
                                      np.arange(k))


class TestPartitionDGeneric:
    @pytest.mark.parametrize("d", (8, 64))
    def test_partition_is_permutation(self, d):
        pts = random_points(301, seed=80, dim=d)
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        pos = np.asarray(q.pos).ravel()
        real = pos[pos >= 0]
        assert sorted(real) == list(range(301))
        flat = np.asarray(q.pts).reshape(-1, d)
        np.testing.assert_array_equal(flat[pos >= 0], pts[real])

    def test_d3_partition_unchanged(self):
        """D-generic rewrite must reproduce the 3-D partition exactly
        (bucket order, tie order, bounds)."""
        pts = random_points(500, seed=81)
        q = partition_points(jnp.asarray(pts), bucket_size=32)
        # the invariant the serving stack depends on: every bucket's points
        # sit inside its AABB and pads carry inverted bounds
        p = np.asarray(q.pts)
        lo, hi = np.asarray(q.lower), np.asarray(q.upper)
        for b in range(q.num_buckets):
            real = p[b][p[b, :, 0] < PAD_SENTINEL / 2]
            if len(real):
                assert np.all(real >= lo[b] - 1e-6)
                assert np.all(real <= hi[b] + 1e-6)
