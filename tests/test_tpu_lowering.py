"""TPU lowering and compilation of the Pallas kernels — no chip needed.

Two layers, both from a CPU-only process:

- ``jax.export(..., platforms=["tpu"])`` runs the JAX -> Mosaic-MLIR
  frontend (layout rules, op-support checks, dynamic-slice rejections) at
  the geometries the bench and tune sweep use, and embeds the Mosaic
  payload in a ``tpu_custom_call``.
- The ``*_compiles_for_v5e`` tests run the TPU compiler itself for a
  DESCRIBED v5e (``topologies.get_topology_desc``), at the real sizes of
  chip_smoke.py: the compiler refuses what the chip would (VMEM overuse,
  unaligned slices, programs that do not fit HBM), and
  ``memory_analysis()`` gives the bytes the program needs. A compile that
  passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mpi_cuda_largescaleknn_tpu.core.types import CandidateState
from mpi_cuda_largescaleknn_tpu.ops.candidates import init_candidates
from mpi_cuda_largescaleknn_tpu.ops.partition import (
    BucketedPoints,
    coarsen_buckets,
    partition_points,
)
from mpi_cuda_largescaleknn_tpu.ops.tiled import warm_start_self


def _export_tiled(n, k, bucket_size, group, warm):
    from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_tiled import (
        knn_update_tiled_pallas,
    )

    rng = np.random.default_rng(0)
    pts = rng.random((n, 3)).astype(np.float32)
    q = partition_points(jnp.asarray(pts), bucket_size=bucket_size)
    pc = coarsen_buckets(q, group) if group > 1 else q
    if warm:
        st = warm_start_self(pc, k)
    else:
        st = init_candidates(q.num_buckets * q.bucket_size, k)

    def f(st_d2, st_idx, qpts, qids, ppts, pids):
        qq = BucketedPoints(qpts, qids, q.lower, q.upper, q.pos)
        pp = BucketedPoints(ppts, pids, pc.lower, pc.upper, pc.pos)
        out = knn_update_tiled_pallas(
            CandidateState(st_d2, st_idx), qq, pp, interpret=False,
            skip_self=jnp.int32(1 if warm else 0), self_group=group,
            with_stats="full")
        return out[0].dist2, out[0].idx, out[1], out[2]

    args = (st.dist2, st.idx, q.pts, q.ids, pc.pts, pc.ids)
    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    assert b"tpu_custom_call" in exp.mlir_module_serialized
    return exp


@pytest.mark.parametrize(
    "bucket_size,group,k,warm",
    [
        (256, 2, 8, True),    # auto default: the round-5 tune winner
        (512, 1, 8, True),    # round-4 default (checkpoint-compat path)
        (64, 8, 8, True),     # the tune sweep's pair-budget geometry
        (64, 8, 100, True),   # k=100: segmented fold (LSK_FOLD_SEGS path)
        (256, 1, 8, False),   # cold heap, no coarsening (probe stage shape)
    ],
)
def test_traversal_kernel_lowers_for_tpu(bucket_size, group, k, warm):
    _export_tiled(16384, k, bucket_size, group, warm)


def _warm_start_fn(k):
    def f(pts, ids, lo, hi):
        pc = BucketedPoints(pts, ids, lo, hi, ids)
        st, passes = warm_start_self(pc, k, decode_ids=False,
                                     with_passes=True, interpret=False)
        return st.dist2, st.idx, passes
    return f


def _warm_start_shapes(sds, group=2):
    """The warm start's operands at 1M points: bucket 256 gives B=4096
    fine buckets of S=248 rows, G=2 makes [2048, 496] coarse buckets."""
    from mpi_cuda_largescaleknn_tpu.ops.partition import choose_buckets

    b, s = choose_buckets(1_000_000, 256)
    assert (b, s) == (4096, 248)
    bc = b // group
    return (sds((bc, group * s, 3), jnp.float32),
            sds((bc, group * s), jnp.int32), sds((bc, 3), jnp.float32),
            sds((bc, 3), jnp.float32))


@pytest.mark.parametrize("k", [8, 100])
def test_warm_start_kernel_lowers_for_tpu(k):
    exp = jax.export.export(jax.jit(_warm_start_fn(k)), platforms=["tpu"])(
        *_warm_start_shapes(jax.ShapeDtypeStruct))
    assert b"tpu_custom_call" in exp.mlir_module_serialized


def test_flat_kernel_lowers_for_tpu():
    from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_bf import knn_update_pallas

    rng = np.random.default_rng(1)
    q = rng.random((1024, 3)).astype(np.float32)
    p = rng.random((4096, 3)).astype(np.float32)
    st = init_candidates(1024, 8)

    def f(d2, idx, q_, p_):
        out = knn_update_pallas(CandidateState(d2, idx), q_, p_,
                                query_tile=256, point_tile=2048,
                                interpret=False)
        return out.dist2, out.idx

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        st.dist2, st.idx, q, p)
    assert b"tpu_custom_call" in exp.mlir_module_serialized


# ------------------------------------------------ compiled for a v5e chip

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one (guide: on-chip-measurement §2)
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _check_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return total


@pytest.mark.parametrize("k", [8, 100])
def test_traversal_kernel_compiles_for_v5e(k, one_chip, no_persistent_cache):
    """The traversal kernel at 1M points (auto geometry: bucket 256,
    point group 2, warm heap) — the ring program's hot loop."""
    from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_tiled import (
        knn_update_tiled_pallas,
    )
    from mpi_cuda_largescaleknn_tpu.ops.partition import choose_buckets

    group = 2
    b, s = choose_buckets(1_000_000, 256)
    bc = b // group

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(d2, idx, qpts, qids, qlo, qhi, ppts, pids, plo, phi):
        q = BucketedPoints(qpts, qids, qlo, qhi, qids)
        p = BucketedPoints(ppts, pids, plo, phi, pids)
        out = knn_update_tiled_pallas(
            CandidateState(d2, idx), q, p, interpret=False,
            skip_self=jnp.int32(1), self_group=group, with_stats=True)
        return out[0].dist2, out[0].idx, out[1]

    f32, i32 = jnp.float32, jnp.int32
    compiled = jax.jit(f).lower(
        sds((b * s, k), f32), sds((b * s, k), i32),
        sds((b, s, 3), f32), sds((b, s), i32), sds((b, 3), f32),
        sds((b, 3), f32), sds((bc, group * s, 3), f32),
        sds((bc, group * s), i32), sds((bc, 3), f32),
        sds((bc, 3), f32)).compile()
    _check_compiled(compiled)


@pytest.mark.parametrize("k", [8, 100])
def test_warm_start_kernel_compiles_for_v5e(k, one_chip, no_persistent_cache):
    """The self-join's warm start at 1M points (S=248, G=2): one program a
    fine query bucket, no sort."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(_warm_start_fn(k)).lower(
        *_warm_start_shapes(sds)).compile()
    _check_compiled(compiled)
    text = compiled.as_text()
    assert " sort(" not in text
    # not the traversal kernel's name, which its roofline reads
    assert "%warm_fold" in text and "%_run" not in text


def test_flat_kernel_compiles_for_v5e(one_chip, no_persistent_cache):
    """The flat brute-force kernel (engine ``pallas``): 8K queries
    against 1M points, k=8."""
    from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_bf import knn_update_pallas

    nq, n, k = 8192, 1_000_000, 8

    def f(d2, idx, q, p):
        out = knn_update_pallas(CandidateState(d2, idx), q, p,
                                query_tile=256, point_tile=2048,
                                interpret=False)
        return out.dist2, out.idx

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(f).lower(
        sds((nq, k), jnp.float32), sds((nq, k), jnp.int32),
        sds((nq, 3), jnp.float32), sds((n, 3), jnp.float32)).compile()
    _check_compiled(compiled)
