"""BASELINE benchmark suite — instruments the configs of BASELINE.json.

The reference publishes no numbers (BASELINE.md), so this suite ESTABLISHES
the baseline: per config it reports kNN queries/sec and the cross-shard
exchange bandwidth measured by parallel/ring.py measure_exchange_bandwidth.

Each config runs in its own subprocess with its own device environment:
single-chip configs use the real TPU when reachable; multi-shard configs use
the virtual-device CPU mesh (this container exposes ONE real chip — the
multi-chip path is validated for correctness/compilation there and measured
for real on a pod). Sizes scale down automatically off-TPU; results are
labeled with platform + actual size so nothing is presented as something it
is not.

    python benchmarks.py            # quick sizes
    python benchmarks.py --full     # BASELINE.json sizes where feasible

Writes benchmarks_report.json and prints one JSON line per config.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

# report lives next to this script, not the cwd — the driver may invoke
# benchmarks.py from anywhere, and the --only merge must find the prior
# report it protects
REPORT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks_report.json")

_CHILD = r"""
import json, os, sys, time
import numpy as np

spec = json.loads(sys.argv[1])

import jax  # noqa: E402  (after env is set by parent)

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
from mpi_cuda_largescaleknn_tpu.models.prepartitioned import PrePartitionedKNN
from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

n, k, shards = spec["n"], spec["k"], spec["shards"]
rng = np.random.default_rng(11)
pts = rng.random((n, 3)).astype(np.float32)
cfg = KnnConfig(k=k, engine=spec.get("engine", "auto"),
                query_chunk=spec.get("query_chunk", 0),
                # 0 = engine-aware auto (ring.resolve_bucket_size)
                bucket_size=spec.get("bucket_size", 0))
mesh = get_mesh(shards)

extra = {}
if spec["pipeline"] == "unordered":
    model = UnorderedKNN(cfg, mesh=mesh)
    model.run(pts)                        # compile warmup
    model.timers.phases.clear()
    t0 = time.perf_counter()
    out = model.run(pts)
    dt = time.perf_counter() - t0
    assert out.shape == (n,)
else:
    pts = pts[np.argsort(pts[:, 0], kind="stable")]
    bounds = [(n * r // shards, n * (r + 1) // shards) for r in range(shards)]
    parts = [pts[b:e] for b, e in bounds]
    model = PrePartitionedKNN(cfg, mesh=mesh)
    model.run(parts)
    model.timers.phases.clear()
    t0 = time.perf_counter()
    outs = model.run(parts)
    dt = time.perf_counter() - t0
    assert sum(len(o) for o in outs) == n
    # measured rounds-to-exit vs schedule optima: a rank needs peer j iff
    # box_dist(box_i, box_j) < its worst k-th distance. The reference's
    # nearest-first matching (prePartitionedDataVariant.cu:304-322) moves
    # one tree per rank per round -> best possible 1 + max_i(#needed).
    # Our bidirectional ring (parallel/demand.py) delivers two trees per
    # round -> bound 1 + ceil(max_i(#needed)/2). PARITY.md discusses the
    # trade; round-4 measurements motivated the counter-rotation.
    los = np.array([p.min(0) for p in parts]); his = np.array([p.max(0) for p in parts])
    # box-box distance: max(0, lo_i - hi_j, lo_j - hi_i) per dim, 2-norm
    d = np.maximum(0.0, np.maximum(los[:, None, :] - his[None, :, :],
                                   los[None, :, :] - his[:, None, :]))
    boxdist = np.sqrt((d ** 2).sum(-1))
    worst = np.array([o.max() for o in outs])
    needed = ((boxdist < worst[:, None]).sum(1) - 1)  # excl. self
    extra["demand_rounds_measured"] = (model.last_stats or {}).get("rounds")
    extra["demand_rounds_reference_best"] = int(needed.max()) + 1
    # exact bidir-ring optimum: a needed peer at ring offset o arrives in
    # round o (two counter-rotating copies), so the schedule cannot beat
    # 1 + max over needed (i, j) of min(|i-j| mod R, |j-i| mod R)
    idx = np.arange(shards)
    offs = np.minimum((idx[:, None] - idx[None, :]) % shards,
                      (idx[None, :] - idx[:, None]) % shards)
    need_mask = (boxdist < worst[:, None]) & ~np.eye(shards, dtype=bool)
    extra["demand_rounds_bidir_bound"] = (
        int((offs * need_mask).max()) + 1 if need_mask.any() else 1)
    extra["needed_peers_per_shard"] = needed.tolist()
    # per-direction rotation gating (parallel/demand.py): fraction of the
    # ungated scheme's exchange bytes (2 rotations/round/device) not moved
    st = model.last_stats or {}
    rot = np.asarray(st.get("rotations_run") or [])
    # chunked runs SUM rotations over chunks but report 'rounds' as the
    # per-chunk max — the ungated-bytes denominator must sum rounds too
    rounds_den = (sum(st["rounds_per_chunk"]) if st.get("rounds_per_chunk")
                  else st.get("rounds") or 0)
    if rot.size and rounds_den:
        extra["exchange_rotations_run_per_device"] = rot.tolist()
        extra["exchange_bytes_saved_frac"] = round(
            1.0 - float(rot.mean()) / (2 * rounds_den), 3)

if shards > 1:
    # MEASURED per-round rotation bandwidth (ppermute minus no-comm
    # control, parallel/ring.py)
    from mpi_cuda_largescaleknn_tpu.parallel.ring import (
        measure_exchange_bandwidth,
    )
    extra["exchange_measured"] = measure_exchange_bandwidth(
        mesh, -(-n // shards), bucket_size=cfg.bucket_size,
        engine=cfg.engine)

rep = model.timers.report()
ring = rep.get("ring") or rep.get("demand_ring") or {}
from mpi_cuda_largescaleknn_tpu.obs.cost import cost_report
pair_evals = (getattr(model, "last_stats", None) or {}).get("pair_evals", 0)
cr = (cost_report(pair_evals, ring.get("seconds", dt),
                  jax.devices()[0].device_kind)
      if pair_evals and jax.default_backend() == "tpu" else {})
print("RESULT " + json.dumps({
    "config": spec["name"],
    "pipeline": spec["pipeline"],
    "n_points": n, "k": k, "shards": shards,
    "scaled_down": spec.get("scaled", False),
    "platform": jax.devices()[0].platform,
    "queries_per_sec": round(n / dt, 1),
    "seconds": round(dt, 3),
    "device_seconds": ring.get("seconds"),
    # headline exchange figure: the MEASURED per-link rotation bandwidth
    # (parallel/ring.py measure_exchange_bandwidth), None when not measured
    "exchange_GB_per_sec": extra.get("exchange_measured", {}).get(
        "exchange_GB_per_sec_per_link"),
    "stats": getattr(model, "last_stats", None),
    **cr, **extra,
}), flush=True)
"""


def _tpu_ok(timeout_s: float | None = None) -> bool:
    # probed in a child: this parent stays JAX-free, so each config child
    # (run one at a time) can own the chip
    if timeout_s is None:
        timeout_s = float(os.environ.get("BENCHSUITE_PROBE_S", 300))
    probe = ("import jax; d=jax.devices(); "
             "import sys; sys.exit(0 if d and d[0].platform != 'cpu' else 1)")
    try:
        return subprocess.run([sys.executable, "-c", probe],
                              timeout=timeout_s,
                              capture_output=True).returncode == 0
    except subprocess.TimeoutExpired:
        return False


def main() -> int:
    full = "--full" in sys.argv
    # validate flags BEFORE the TPU probe: a usage error must fail in
    # milliseconds
    only = None
    if "--only" in sys.argv:
        idx = sys.argv.index("--only") + 1
        if idx >= len(sys.argv) or sys.argv[idx].startswith("-"):
            sys.stderr.write("usage: benchmarks.py [--full] --only <name>\n")
            return 2
        only = sys.argv[idx]
    tpu = _tpu_ok()

    def env_for(shards: int, use_tpu: bool):
        env = dict(os.environ)
        if not use_tpu:
            env["JAX_PLATFORMS"] = "cpu"
            flags = env.get("XLA_FLAGS", "")
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={shards}"
            ).strip()
        return env

    # (name, pipeline, (shards, n, k) full, (shards, n, k) quick, extras)
    # quick mode scales N (and nothing else) down so the CPU smoke run
    # finishes in minutes — k and shard count stay AT SPEC so the
    # k-scaling cost center (the width-2k merge, ops/candidates.py) and the
    # 64-shard round-count behavior are really exercised; results carry the
    # actual parameters so scaled runs cannot masquerade as spec runs
    configs = [
        ("unordered_1dev_k8", "unordered",
         (1, 1_000_000, 8), (1, 200_000 if tpu else 20_000, 8), {}),
        # k-scaling curve on one device (TPU-eligible): same N, k swept —
        # the merge cost center scales with k (width-2k sorted rows)
        ("unordered_1dev_k32", "unordered",
         (1, 1_000_000, 32), (1, 100_000 if tpu else 20_000, 32), {}),
        ("unordered_1dev_k100", "unordered",
         (1, 1_000_000, 100), (1, 100_000 if tpu else 10_000, 100), {}),
        ("unordered_8shard_k100", "unordered",
         (8, 400_000, 100), (8, 8_000, 100), {}),
        ("prepartitioned_8shard_k100", "prepartitioned",
         (8, 400_000, 100), (8, 8_000, 100), {}),
        ("prepartitioned_64shard_k500_overlap", "prepartitioned",
         (64, 256_000, 500), (64, 32_000, 500), {"bucket_size": 128}),
        ("unordered_streaming_chunked_k100", "unordered",
         (8, 400_000, 100), (8, 8_000, 100), {"query_chunk": 1024}),
    ]

    config_order = [c[0] for c in configs]
    if only is not None:
        # targeted re-run (e.g. one config crashed under a loaded host):
        # substring filter on config name; rows MERGE into the existing
        # report, replacing that config's old row, instead of clobbering
        # the other configs' results
        configs = [c for c in configs if only in c[0]]
        if not configs:
            sys.stderr.write(f"no config matches {only!r}\n")
            return 2

    results = []
    for name, pipeline, full_snk, quick_snk, extras in configs:
        shards, n, k = full_snk if full else quick_snk
        use_tpu = tpu and shards == 1
        spec = {"name": name, "pipeline": pipeline, "shards": shards,
                "n": n, "k": k, "scaled": not full, **extras}
        try:
            r = subprocess.run(
                [sys.executable, "-c", _CHILD, json.dumps(spec)],
                timeout=float(os.environ.get("BENCHSUITE_TIMEOUT_S", 1200)),
                capture_output=True, text=True,
                env=env_for(shards, use_tpu))
        except subprocess.TimeoutExpired:
            results.append({"config": name, "error": "timeout"})
            print(json.dumps(results[-1]), flush=True)
            continue
        line = next((ln for ln in r.stdout.splitlines()
                     if ln.startswith("RESULT ")), None)
        if r.returncode != 0 or line is None:
            results.append({"config": name,
                            "error": (r.stderr or "no output")[-500:]})
        else:
            results.append(json.loads(line[len("RESULT "):]))
        print(json.dumps(results[-1]), flush=True)

    if only is not None:
        try:
            with open(REPORT_PATH) as f:
                prior = json.load(f)
            prior_ok = {r.get("config"): r for r in prior.get("results", [])
                        if "error" not in r}
            # a failed re-run must not clobber a prior good measurement
            # (e.g. retrying on a weaker host): keep the old row then
            results = [r if "error" not in r
                       else prior_ok.get(r.get("config"), r)
                       for r in results]
            rerun = {r.get("config") for r in results}
            results = [r for r in prior.get("results", [])
                       if r.get("config") not in rerun] + results
            # keep the committed report's canonical row order (stable
            # human diffs); unknown configs sink to the end
            results.sort(key=lambda r: (
                config_order.index(r["config"])
                if r.get("config") in config_order else len(config_order)))
            # top-level flags describe ALL rows: after a mixed-provenance
            # merge they can only be trusted when both runs agree —
            # disagreement nulls the flag (falsy for naive consumers; the
            # per-row scaled_down/platform fields stay authoritative)
            if prior.get("full") != full:
                full = None
            if prior.get("tpu_available") != tpu:
                tpu = None
        except (OSError, ValueError):
            pass  # no prior report: write just the re-run rows
    with open(REPORT_PATH, "w") as f:
        json.dump({"full": full, "tpu_available": tpu,
                   "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
