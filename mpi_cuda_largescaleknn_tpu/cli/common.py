"""Shared CLI parsing — the reference's exact 5-flag surface plus TPU knobs.

The reference's hand-rolled argv loop (identical in both programs,
unorderedDataVariant.cu:114-135 / prePartitionedDataVariant.cu:185-206):
positional input path, ``-o`` output, ``-k`` int (required), ``-r`` float max
search radius (default inf), ``-g`` GPU-affinity modulus, anything else ->
usage error + exit(1). We preserve that contract verbatim and add
double-dash TPU-side options the reference has no analogue for.
"""

from __future__ import annotations

import math
import sys

from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig

TPU_FLAGS = """
TPU-side options (no reference analogue):
  --shards N        size of the 1-D device mesh (default: all devices)
  --engine E        tiled | pallas_tiled | bruteforce | tree | pallas | auto
                    (default auto = pallas_tiled, the fused nearest-first
                    kernel, on real TPUs; the XLA twin `tiled` elsewhere)
  --query-tile N    queries per inner tile (flat engines; default 2048)
  --point-tile N    tree points per inner tile (flat engines; default 2048)
  --bucket-size N   points per spatial bucket (tiled engines; default
                    auto: engine-tuned, see docs/TUNING.md)
  --point-group N   coarsen the resident point side by this power-of-two
                    factor (tiled engines; default auto: engine-tuned,
                    pass 1 to disable — see docs/TUNING.md; chunked runs
                    coarsen the resident side only)
  --query-chunk N   stream queries in chunks of N rows per device;
                    bounds candidate-heap memory to N*k per device for runs
                    whose heaps exceed HBM (e.g. -k 100 at 100M+ points)
  --merge M         chunked runs: host | device | auto — where the
                    cross-shard top-k merge runs (device keeps it inside
                    the SPMD program on the global mesh axis and fetches
                    final rows only; auto = device on power-of-two meshes,
                    host with a logged warning otherwise)
  --score-dtype T   distance scoring: f32 (exact elementwise, the default)
                    | bf16 (matmul-form MXU score + exact f32 rescore of
                    the top survivors — same final results whenever the
                    true top-k sits inside the rescore window; see
                    docs/TUNING.md "Distance kernel")
  --profile-dir D   write a jax.profiler trace; its host events name the
                    self-join's steps (selfjoin.shard_and_pad, selfjoin.ring,
                    selfjoin.extract) and the ring's (ring.stage,
                    ring.partition, ring.launch: the fused program's
                    trace/lower/load and enqueue, ring.wait: the device)
  --timings         print phase timings as JSON to stderr: calls and
                    seconds per phase (shard_and_pad, ring, extract) and
                    per ring span (ring.stage ... ring.wait), and the
                    ring.program_builds and ring.warm_fold_passes
                    counters
  --checkpoint-dir D  snapshot exchange state between rounds (both
                    pipelines); an interrupted run relaunched with the same
                    args resumes at the lost round
  --checkpoint-every N  rounds between snapshots (default 1)
  --selfcheck N     after the run, verify N random outputs against an exact
                    streamed recomputation and fail loudly on mismatch (the
                    working version of the reference's disabled probe blocks)
  --write-indices P  also write the k neighbor IDs per point (int32, ascending
                    by distance, -1 = fewer than k found): unordered -> one
                    file P in global point order; prepartitioned -> one
                    P_%06d.int32 per shard. The reference computes these but
                    discards them (unorderedDataVariant.cu extractFinalResult)
  --coordinator A   multi-host: coordinator address host:port (the reference's
                    mpirun; here jax.distributed). Launch ONE copy of this CLI
                    per host with the same args plus --host-id
  --num-hosts N     multi-host: number of cooperating processes
  --host-id I       multi-host: this process's id in [0, N)
"""


def usage(program: str, error: str) -> "NoReturn":  # noqa: F821
    sys.stderr.write(f"Error: {error}\n\n")
    sys.stderr.write(
        f"{program} -k <k> [-r <maxRadius>] <input> -o <output>\n{TPU_FLAGS}")
    sys.exit(1)


def parse_args(program: str, argv: list[str]):
    """Returns (config, in_path, out_path, extras dict)."""
    k = 0
    max_radius = math.inf
    affinity = 0
    in_path = ""
    out_path = ""
    extras = {"shards": None, "engine": "auto", "query_tile": 2048,
              "point_tile": 2048, "bucket_size": 0, "point_group": 0,
              "profile_dir": None,
              "timings": False, "checkpoint_dir": None, "checkpoint_every": 1,
              "write_indices": None, "query_chunk": 0, "selfcheck": 0,
              "merge": "host", "score_dtype": "f32",
              "coordinator": None, "num_hosts": 1, "host_id": 0}
    i = 0
    try:
        while i < len(argv):
            arg = argv[i]
            if arg == "-o":
                i += 1; out_path = argv[i]
            elif not arg.startswith("-"):
                in_path = arg
            elif arg == "-r":
                i += 1; max_radius = float(argv[i])
            elif arg == "-g":
                i += 1; affinity = int(argv[i])
            elif arg == "-k":
                i += 1; k = int(argv[i])
            elif arg == "--shards":
                i += 1; extras["shards"] = int(argv[i])
            elif arg == "--engine":
                i += 1; extras["engine"] = argv[i]
            elif arg == "--query-tile":
                i += 1; extras["query_tile"] = int(argv[i])
            elif arg == "--point-tile":
                i += 1; extras["point_tile"] = int(argv[i])
            elif arg == "--bucket-size":
                i += 1; extras["bucket_size"] = int(argv[i])
            elif arg == "--point-group":
                i += 1; extras["point_group"] = int(argv[i])
            elif arg == "--profile-dir":
                i += 1; extras["profile_dir"] = argv[i]
            elif arg == "--timings":
                extras["timings"] = True
            elif arg == "--checkpoint-dir":
                i += 1; extras["checkpoint_dir"] = argv[i]
            elif arg == "--checkpoint-every":
                i += 1; extras["checkpoint_every"] = int(argv[i])
            elif arg == "--write-indices":
                i += 1; extras["write_indices"] = argv[i]
            elif arg == "--query-chunk":
                i += 1; extras["query_chunk"] = int(argv[i])
            elif arg == "--merge":
                i += 1; extras["merge"] = argv[i]
            elif arg == "--score-dtype":
                i += 1; extras["score_dtype"] = argv[i]
            elif arg == "--selfcheck":
                i += 1; extras["selfcheck"] = int(argv[i])
            elif arg == "--coordinator":
                i += 1; extras["coordinator"] = argv[i]
            elif arg == "--num-hosts":
                i += 1; extras["num_hosts"] = int(argv[i])
            elif arg == "--host-id":
                i += 1; extras["host_id"] = int(argv[i])
            else:
                usage(program, f"unknown cmdline arg '{arg}'")
            i += 1
    except (IndexError, ValueError):
        usage(program, f"invalid or missing value for '{argv[i - 1] if i else ''}'")

    if not in_path:
        usage(program, "no input file name specified")
    if not out_path:
        usage(program, "no output file name specified")
    if k < 1:
        usage(program, "no k specified, or invalid k value")

    cfg = KnnConfig(k=k, max_radius=max_radius, device_affinity=affinity,
                    engine=extras["engine"], query_tile=extras["query_tile"],
                    point_tile=extras["point_tile"],
                    bucket_size=extras["bucket_size"],
                    point_group=extras["point_group"],
                    num_shards=extras["shards"] or 0,
                    query_chunk=extras["query_chunk"],
                    merge=extras["merge"],
                    score_dtype=extras["score_dtype"],
                    profile_dir=extras["profile_dir"],
                    checkpoint_dir=extras["checkpoint_dir"],
                    checkpoint_every=extras["checkpoint_every"])
    return cfg, in_path, out_path, extras
