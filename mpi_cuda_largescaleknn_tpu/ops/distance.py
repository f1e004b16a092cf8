"""Shared distance scorers: elementwise (VPU) and matmul-form (MXU).

Every distance tile in this package used to be computed elementwise —
``(dx*dx + dy*dy) + dz*dz`` broadcast over a [Q, T] tile — which is
perfectly regular VPU work but leaves the MXU (the overwhelming majority
of a TPU's FLOP/s) idle on the hot path, and hardwires D=3. TPU-KNN
(arXiv:2206.14286) shows the fix: expand

    ||q - p||^2 = ||q||^2 + ||p||^2 - 2 q.p

so the dominant term is ONE dense [Q, D] x [D, T] matmul per tile. The
cross term is scored in bf16 (f32 accumulation — the MXU's native mode);
the norms stay exact f32. The catch is exactness: the expansion's
cancellation error is unbounded relative to the direct form (a pair
separated by less than a bf16 ulp at large ||p|| scores identically), so
the bf16 scores are used ONLY to select survivors — the top
``rescore_width(k)`` lanes per row — which are then rescored with the
exact elementwise f32 form before they ever reach ``merge_candidates``.
Final (dist2, idx) results are bit-identical to the elementwise kernel
whenever the true top-k of a tile lands inside the survivor window (the
default window is 2k wide; see docs/TUNING.md "Distance kernel" for when
that holds and when it cannot).

Both forms are D-generic: the elementwise scorer reduces components in a
fixed left-to-right order, so at D=3 it is the exact expression tree
``(d0*d0 + d1*d1) + d2*d2`` the kernels always used — swapping call sites
onto this module changes no bits.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

#: score dtypes the kernels accept: "f32" = exact elementwise on the VPU
#: (the default, and the only mode with an unconditional exactness proof);
#: "bf16" = matmul-form MXU scoring + exact f32 rescore of the survivors.
SCORE_DTYPES = ("f32", "bf16")


def validate_score_dtype(score_dtype: str) -> str:
    if score_dtype not in SCORE_DTYPES:
        raise ValueError(f"unknown score_dtype '{score_dtype}' "
                         f"(expected one of {SCORE_DTYPES})")
    return score_dtype


def opaque_one(like: jnp.ndarray) -> jnp.ndarray:
    """A runtime-opaque f32 ``1.0`` — the FMA-contraction guard.

    XLA:CPU freely contracts ``di*di + acc`` into one fused multiply-add,
    and it does so DIFFERENTLY per fusion context (a [Q, T] broadcast tile
    vs a [Q, W] gathered rescore of the very same pairs came out +-1 ulp
    apart in round-6 measurements). The exactness contract of this module —
    the survivor rescore reproduces the elementwise tile BIT FOR BIT — needs
    every exact-distance site to round every op the same way, so each square
    is multiplied by this value: ``x * 1.0 == x`` exactly under IEEE-754,
    and because the 1.0 here is DERIVED FROM RUNTIME DATA (``v*0 + 1`` of
    ``like``'s first element, which no strict-IEEE compiler may fold — ``v``
    could be non-finite for all it knows) the multiply survives into
    codegen and the following add has a multiply-by-opaque (never a raw
    square) as its operand — un-contractible. The results are then the
    correctly-rounded sequential values (= the numpy oracle's), identical
    in every context. (An ``optimization_barrier``-hidden constant does NOT
    work: XLA's barrier expander strips it before codegen and the 1.0 folds
    right back.) ``like`` must be finite, which every coordinate in this
    package is — PAD_SENTINEL included."""
    v = jnp.asarray(like, jnp.float32).reshape(-1)[0]
    return v * jnp.float32(0.0) + jnp.float32(1.0)


def accumulate_sq(acc, di, one):
    """One guarded square-accumulate step: ``acc + (di*di)*one`` with the
    fixed left-to-right association every exact scorer in this package
    uses. ``one`` is ``opaque_one()`` (or any runtime-opaque 1.0 — the
    Pallas kernels derive theirs from ``program_id``, which Mosaic can
    lower where the barrier cannot). ``acc=None`` starts the chain."""
    sq = (di * di) * one
    return sq if acc is None else acc + sq


def norms2(pts: jnp.ndarray) -> jnp.ndarray:
    """f32[..., D] -> f32[...]: squared norm, fixed left-to-right component
    order (the precomputed ||p||^2 term of the matmul expansion — exact f32,
    never bf16: only the cross term is approximated)."""
    acc = pts[..., 0] * pts[..., 0]
    for i in range(1, pts.shape[-1]):
        acc = acc + pts[..., i] * pts[..., i]
    return acc


def elementwise_dist2(q: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Exact squared distances f32[..., Q, D] x f32[..., T, D] ->
    f32[..., Q, T], fixed left-to-right component order — at D=3 the exact
    ``(dx*dx + dy*dy) + dz*dz`` expression tree of the original kernels
    (NOT the matmul expansion, whose cancellation error is unbounded).
    Every step carries the ``opaque_one`` contraction guard, so the values
    are the correctly-rounded sequential ones in every fusion context."""
    d = q.shape[-1]
    one = opaque_one(q)
    acc = None
    for i in range(d):
        di = q[..., :, None, i] - p[..., None, :, i]
        acc = accumulate_sq(acc, di, one)
    return acc


def mxu_min_dim() -> int:
    """Smallest point dimensionality at which ``score_dtype="bf16"``
    actually engages the matmul-form scorer; below it the exact elementwise
    path IS the fast path (at D=3 the MXU would run at 3/128 utilization
    and the survivor-selection machinery is pure overhead; 16 is a
    crossover measured on the CPU, not on the chip), so requesting bf16
    there silently scores exactly (tests/test_distance.py
    ``TestShippedMxuThreshold``). ``LSK_MXU_MIN_DIM``
    overrides (trace-time; the parity tests pin it to 1 to exercise the
    MXU machinery at every D)."""
    try:
        v = int(os.environ.get("LSK_MXU_MIN_DIM", "") or 0)
    except ValueError:
        v = 0                       # a bad sweep value must tune, not crash
    return v if v > 0 else 16


def rescore_width(k: int, t: int) -> int:
    """bf16 survivor window per row: how many approx-top lanes of a width-
    ``t`` tile get the exact f32 rescore. Default ``max(2k, 16)`` — wide
    enough that a true top-k candidate is dropped only when more than
    ``width - k`` tile lanes score within bf16 error of the k-th distance
    (docs/TUNING.md). ``LSK_RESCORE_WIDTH`` overrides (trace-time, like the
    kernel-geometry env knobs)."""
    try:
        w = int(os.environ.get("LSK_RESCORE_WIDTH", "") or 0)
    except ValueError:
        w = 0                       # a bad sweep value must tune, not crash
    if w <= 0:
        w = max(2 * k, 16)
    return min(t, max(w, k))


def split_bf16(x: jnp.ndarray):
    """Split f32 into (hi, lo) bf16 terms with ``hi + lo ~= x`` to ~16
    mantissa bits — the standard bf16x3 precision-recovery decomposition
    for MXU matmuls."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def mxu_scores(q: jnp.ndarray, p: jnp.ndarray,
               pn2: jnp.ndarray | None = None,
               qn2: jnp.ndarray | None = None) -> jnp.ndarray:
    """Approximate squared distances via the matmul expansion: the cross
    term rides the MXU as THREE bf16 dot_generals with f32 accumulation
    (the bf16x3 split — hi.hi + hi.lo + lo.hi — carrying ~16 mantissa
    bits); the norms ride exact f32. One-pass bf16 was measured missing
    true top-k members in 15% of rows on the serving fixture (absolute
    error ~||p||*2^-9 swamps the inter-candidate gaps); the split brings
    the error to ~scale*2^-16, far below any non-adversarial gap, so the
    default survivor window holds. Shapes as ``elementwise_dist2``;
    ``pn2``/``qn2`` accept precomputed norms (per-bucket ||p||^2 is
    computed once at index upload by the serving engine)."""
    if qn2 is None:
        qn2 = norms2(q)
    if pn2 is None:
        pn2 = norms2(p)
    qh, ql = split_bf16(q)
    ph, plo = split_bf16(jnp.swapaxes(p, -1, -2))
    cross = (jnp.matmul(qh, ph, preferred_element_type=jnp.float32)
             + jnp.matmul(qh, plo, preferred_element_type=jnp.float32)
             + jnp.matmul(ql, ph, preferred_element_type=jnp.float32))
    return qn2[..., :, None] + pn2[..., None, :] - 2.0 * cross


def score_tile(q: jnp.ndarray, p: jnp.ndarray, pid: jnp.ndarray, k: int, *,
               score_dtype: str = "f32", mask: jnp.ndarray | None = None,
               pn2: jnp.ndarray | None = None, skip_rescore: bool = False):
    """Score one distance tile, ready for ``merge_candidates``.

    Args:
      q: f32[..., Q, D] queries. p: f32[..., T, D] points (shared across the
        tile's Q rows). pid: i32[..., T] point ids, broadcastable against
        the [..., Q, T] score tile. mask: optional bool broadcastable to
        [..., Q, T]; False lanes can never be adopted (their distances are
        forced to +inf — in BOTH modes, including after the rescore).
      pn2: optional precomputed f32[..., T] squared point norms (bf16 mode).
      skip_rescore: approximate one-pass mode (the recall-SLO tier's knob,
        serve/recall.py): under bf16 at D >= ``mxu_min_dim`` the raw
        matmul-form scores are fed straight to the merge — no survivor
        top_k, no exact rescore — trading the bf16x3 error bound
        (~scale * 2^-16) for the cost of the selection machinery. Scores
        are clamped at 0 (the expansion can cancel slightly negative).
        Below the MXU threshold the elementwise path is exact AND fastest,
        so the knob is a no-op there by design.

    Returns ``(cand_d2, cand_idx)``:

    - ``score_dtype="f32"``: the full exact elementwise tile, width T —
      exactly what the kernels always fed their merges.
    - ``score_dtype="bf16"``: width ``rescore_width(k, T)``. The matmul-form
      bf16 scores pick the survivors per row; survivor lane indices are
      re-sorted ASCENDING so the tile fed to the merge is a subsequence of
      the original lane order (fold-arrival tie discipline preserved), and
      every survivor's distance is recomputed with the exact elementwise f32
      form — values reaching the candidate state are never approximate.
    """
    validate_score_dtype(score_dtype)
    t = p.shape[-2]
    w = rescore_width(k, t)
    if (score_dtype == "f32" or q.shape[-1] < mxu_min_dim()
            or (w >= t and not skip_rescore)):
        # exact full-width tile (also the bf16 fallback below the MXU
        # dimensionality threshold, and when the survivor window would
        # cover every lane anyway — then the top_k buys nothing)
        d2 = elementwise_dist2(q, p)
        if mask is not None:
            d2 = jnp.where(mask, d2, jnp.inf)
        idx = jnp.broadcast_to(pid[..., None, :], d2.shape)
        return d2, idx

    scores = mxu_scores(q, p, pn2=pn2)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.inf)
    if skip_rescore:
        # one-pass approximate tile: full width, raw expansion scores
        # (masked lanes stay +inf; the 0-clamp keeps sqrt() downstream
        # finite when cancellation dips a self-pair slightly negative)
        idx = jnp.broadcast_to(pid[..., None, :], scores.shape)
        return jnp.maximum(scores, jnp.float32(0.0)), idx
    _neg, pos = jax.lax.top_k(-scores, w)               # [..., Q, W]
    # restore lane order: the survivors must reach the merge as a
    # subsequence of the tile's original lanes, or equal-distance
    # candidates would change fold-arrival order vs the elementwise kernel
    pos = jax.lax.sort(pos, dimension=pos.ndim - 1)
    # gather survivor coordinates ([..., 1, T, D] x [..., Q, W, 1] -> the
    # gather broadcasts over Q and D) and rescore them exactly — the
    # guarded recipe makes these bits EQUAL to elementwise_dist2's
    pg = jnp.take_along_axis(p[..., None, :, :], pos[..., None], axis=-2)
    one = opaque_one(q)
    acc = None
    for i in range(q.shape[-1]):
        acc = accumulate_sq(acc, q[..., :, None, i] - pg[..., i], one)
    # gather ids/mask THROUGH broadcasting ([..., 1, T] against the
    # [..., Q, W] positions) — materializing full [..., Q, T] copies first
    # measurably dominated the D=3 tile cost
    idx = jnp.take_along_axis(pid[..., None, :], pos, axis=-1)
    if mask is not None:
        # a masked lane selected only because too few lanes were live must
        # stay +inf — its EXACT distance may be finite (pruned buckets hold
        # real points), and adopting it would break the prune's exactness
        if mask.ndim >= 2 and mask.shape[-2] == 1:   # per-tile mask rows
            keep = jnp.take_along_axis(
                jnp.broadcast_to(mask, scores.shape[:-2] + (1, t)),
                pos, axis=-1)
        else:                                        # per-query mask rows
            keep = jnp.take_along_axis(
                jnp.broadcast_to(mask, scores.shape), pos, axis=-1)
        acc = jnp.where(keep, acc, jnp.inf)
    return acc, idx
