"""Pieces shared by the system drivers: seeded data, parameter checks and
the comparison."""

from __future__ import annotations

import numpy as np

from perfbench.oracle import WORST_ULP, GridReference, ulp_gap

#: every parameter of a configuration's ``data``, with its default
DATA = {"dist": "uniform", "scale": 1.0, "blobs": 0, "blob_sigma": 0.0,
        "what": ""}


def checked(params: dict, known: dict, what: str) -> dict:
    """``params`` with the defaults of ``known`` filled in; a key that
    ``known`` does not list, or a required one (default None) that is
    missing, is refused, so a misspelt parameter never runs the default."""
    unknown = set(params) - set(known)
    if unknown:
        raise ValueError(f"{what}: unknown keys {sorted(unknown)}")
    out = dict(known, **params)
    missing = sorted(k for k, v in out.items() if v is None)
    if missing:
        raise ValueError(f"{what}: missing {missing}")
    return out


def make_points(data: dict, n: int, seed: int) -> np.ndarray:
    """``n`` float32 points in the box [0, scale)^3, made from ``seed``:
    uniform, or (``dist: blobs``) Gaussian blobs of sigma ``blob_sigma``
    times ``scale`` around ``blobs`` uniform centres, clipped to the box."""
    data = checked(data, DATA, "data")
    scale = float(data["scale"])
    rng = np.random.default_rng([seed, 0])
    if data["dist"] == "uniform":
        pts = rng.random((n, 3), dtype=np.float32)
        return pts if scale == 1.0 else (pts * scale).astype(np.float32)
    if data["dist"] != "blobs" or int(data["blobs"]) < 1:
        raise ValueError(f"data: unknown distribution {data}")
    centers = np.random.default_rng([seed, 0, 1]).random(
        (int(data["blobs"]), 3)) * scale
    pick = rng.integers(len(centers), size=n)
    pts = centers[pick] + rng.normal(
        0.0, float(data["blob_sigma"]) * scale, (n, 3))
    return np.clip(pts, 0.0, scale).astype(np.float32)


def compare(points: np.ndarray, k: int, queries: np.ndarray,
            got: np.ndarray, bad_rows: int, limit: int,
            precision: str = "f32") -> dict:
    """The cell's compared number: the widest gap, in float32 ulp, between
    an answered k-th distance and the reference's. ``bad_rows`` answers
    that came back malformed (wrong length) read ``WORST_ULP``.

    ``precision="bf16"`` puts the control in the program's place: the
    reference's own answers computed in bfloat16, for the same queries."""
    ref = GridReference(points, k)
    want = ref.kth_dists(queries)
    if precision != "f32":
        got = ref.kth_dists(queries, precision)
    worst = int(ulp_gap(got, want).max()) if len(want) else 0
    if bad_rows:
        worst = WORST_ULP
    return {"kth_ulp_max": {"value": worst, "limit": limit}}
