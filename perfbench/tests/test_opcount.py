"""The self-join kernel's work count: one query-bucket grid per fold, and
every device folds every shard once per job."""

from __future__ import annotations

from perfbench import opcount


def test_one_shard_counts_as_before():
    """1M points on one shard: one fold per job, the count every reading
    of ``knn_tiled_roofline.batch`` so far was made with."""
    assert opcount.selfjoin_work(1_000_000, 1, 256, 2, 123_456, 2, 8) == {
        "flops": 113_427_152_896, "bytes": 1_371_013_120,
        "kernel_tiles": 115_264}


def test_four_shards_count_four_folds_per_device_per_job():
    """4M points on four shards (1M each: 4,096 buckets of 248 rows, tiles
    of two buckets padded to 512 lanes): each of 4 devices folds 4 shards
    in each of 2 jobs, in the ring's 3 rounds."""
    jobs, tiles, k = 2, 500_000, 8
    w = opcount.selfjoin_work(4_000_000, 4, 256, 2, tiles, jobs, k)
    b, s = 4096, 248
    assert opcount.choose_buckets(1_000_000, 256) == (b, s)
    kernel_tiles = tiles - 4 * b * jobs          # warm-start tiles out
    per_qbucket = s * 12 + s * 4 + 2 * 2 * s * k * 4 + 2 * (b // 2) * 4
    q_buckets = 4 * b * 4 * jobs                 # devices x buckets x folds
    assert w["kernel_tiles"] == kernel_tiles
    assert w["flops"] == kernel_tiles * s * 2 * s * 8
    assert w["bytes"] == kernel_tiles * 4 * 512 * 4 + q_buckets * per_qbucket
    assert w["bytes"] == 10_655_891_456
