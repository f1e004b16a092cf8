"""Operations and bytes of the ``knn_tiled`` Pallas kernel, from shapes and
the program's own work counters.

The kernel (``ops/pallas/knn_tiled.py``) walks, for each query bucket of
S_q rows, a nearest-first list of point tiles of T lanes. Each visited tile
is one DMA of a [4, T_pad] f32 block (x, y, z and a spare row; T padded to
128 lanes) from HBM, and S_q * T squared distances of 8 f32 operations
each (3 subtractions, 3 multiplications, 2 additions). The candidate fold
is further work not counted here, so the operation count -- and every
roofline share built on it -- is a lower bound.

Geometry follows the program's bucketing rule: B = the power of two at or
above ceil(n / bucket_size) buckets, each padded to a multiple of 8 rows.
"""

from __future__ import annotations

FLOPS_PER_PAIR = 8
ROWS_PER_TILE = 4          # x, y, z, and one spare (norm) row
LANE = 128
F32 = 4


def next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def choose_buckets(n: int, bucket_size: int) -> tuple[int, int]:
    """(bucket count B, padded rows per bucket S)."""
    b = next_pow2(max(1, -(-n // max(bucket_size, 1))))
    s = -(-max(-(-n // b), 1) // 8) * 8
    return b, s


def selfjoin_work(n: int, shards: int, bucket_size: int, point_group: int,
                  tiles: int, jobs: int, k: int) -> dict:
    """flops and HBM bytes of the kernel over ``jobs`` self-joins whose ring
    stats summed to ``tiles``. Those stats add one warm-start tile per
    query bucket per shard that XLA scores outside the kernel
    (``parallel/ring.py _warm_tiles``); they are taken out here."""
    n_local = -(-n // shards)
    b, s = choose_buckets(n_local, bucket_size)
    group = min(point_group, b)
    warm = shards * b * jobs
    kernel_tiles = max(0, tiles - warm)
    lanes = s * group
    lanes_pad = -(-lanes // LANE) * LANE
    # one query-bucket grid per fold, and a job folds every shard once on
    # every device: R folds in the bidirectional ring's R // 2 + 1 rounds
    # (two a round, one in round 0 and in the even-R antipodal round)
    folds = shards
    q_buckets = shards * b * folds * jobs
    per_qbucket = (s * 3 * F32 + s * F32          # query block + ids
                   + 2 * 2 * s * k * F32          # candidates in and out
                   + 2 * (b // group) * F32)      # SMEM visit schedule
    return {
        "flops": kernel_tiles * s * lanes * FLOPS_PER_PAIR,
        "bytes": kernel_tiles * ROWS_PER_TILE * lanes_pad * F32
        + q_buckets * per_qbucket,
        "kernel_tiles": kernel_tiles,
    }


def serve_work(tile_rows: int, point_tile: int, min_query_rows: int) -> dict:
    """flops and HBM bytes of the serving kernel over ``tile_rows`` executed
    tile-rows (the engine's ``tiles_executed`` unit: one query row against
    one point tile of ``point_tile`` lanes). Tiles are fetched once per
    query bucket, so bytes take the narrowest query bucket the engine
    compiled -- an upper bound on the bytes."""
    lanes_pad = -(-point_tile // LANE) * LANE
    visits = -(-tile_rows // max(1, min_query_rows))
    return {"flops": tile_rows * point_tile * FLOPS_PER_PAIR,
            "bytes": visits * ROWS_PER_TILE * lanes_pad * F32}


def roofline_share(work: dict, seconds: float, peaks: dict) -> tuple:
    """(percent of the roofline, which bound applies): the least time the
    chip could take -- the larger of flops over the f32 vector peak and
    bytes over HBM bandwidth -- over the kernel's device time."""
    t_flops = work["flops"] / peaks["f32_vpu_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    bound = "vpu" if t_flops >= t_bytes else "hbm"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
