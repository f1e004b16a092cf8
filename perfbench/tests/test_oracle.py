"""The plain reference against brute force, and the control against the
reference: the control (bfloat16) must fail the limit the sound path
meets."""

from __future__ import annotations

import numpy as np

from perfbench.oracle import WORST_ULP, GridReference, max_ulp, ulp_gap


def brute_kth(points, q, k):
    d = points - q
    d2 = (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]) + d[:, 2] * d[:, 2]
    return np.sqrt(np.partition(d2, k - 1)[k - 1])


def test_grid_reference_is_exact_against_brute_force():
    rng = np.random.default_rng(3)
    for n, k in ((2000, 8), (5000, 100)):
        pts = rng.random((n, 3), dtype=np.float32)
        # self queries, off-box queries and clustered queries
        queries = np.concatenate([
            pts[:50], rng.random((30, 3)).astype(np.float32) * 1.4 - 0.2,
            np.clip(0.5 + 0.02 * rng.standard_normal((30, 3)), 0, 1
                    ).astype(np.float32)])
        got = GridReference(pts, k).kth_dists(queries)
        want = np.array([brute_kth(pts, q, k) for q in queries], np.float32)
        assert max_ulp(got, want) == 0


def test_bf16_control_fails_the_limit():
    rng = np.random.default_rng(4)
    pts = rng.random((20000, 3), dtype=np.float32)
    ref = GridReference(pts, 8)
    queries = pts[:200]
    gap = max_ulp(ref.kth_dists(queries, "bf16"), ref.kth_dists(queries))
    assert gap > 1000


def test_ulp_gap_reads_bad_answers_as_worst():
    want = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    got = np.array([1.0, np.nextafter(np.float32(2.0), np.float32(3.0)),
                    np.nan, -4.0], np.float32)
    assert list(ulp_gap(got, want)) == [0, 1, WORST_ULP, WORST_ULP]
    assert max_ulp(got[:2], want) == WORST_ULP
    assert ulp_gap(np.float32([np.inf]), np.float32([1.0]))[0] > 1 << 29
