"""``tpuknn-serve`` — the online serving entry point (no reference analogue).

The batch CLIs answer one self-join and exit; this one loads a point set,
builds the resident sharded index, AOT-compiles every shape bucket, and
serves queries over HTTP until killed:

    python -m mpi_cuda_largescaleknn_tpu.cli.serve_main points.float3 -k 100 \
        [--port 8080] [--engine auto] [--merge auto] [--shards R] \
        [--max-batch 1024] [--max-delay-ms 2] [--max-queue-rows 4096] \
        [--timeout-ms 5000]

Endpoints: POST /knn (JSON or binary), GET /healthz, /stats, /metrics
(Prometheus text). With --tenant (repeatable) the process serves MANY
indexes from one slab pool: POST /v1/<tenant>/knn, per-tenant /stats
namespaces, {tenant=} metric labels, and per-tenant admission quotas
(docs/SERVING.md "Multi-index tenancy"). See tools/loadgen.py.
"""

from __future__ import annotations

import math
import sys

# BEFORE any jax import: the persistent compile cache env vars are read at
# backend init, and a serving process is exactly the caller that must never
# repay the ~220s cold compile twice
from mpi_cuda_largescaleknn_tpu.utils.compile_cache import (
    enable_persistent_cache,
)

SERVE_FLAGS = """
  -k N              neighbors per query (required)
  -r R              max search radius (default inf)
  --port P          HTTP port (default 8080; 0 = pick a free port)
  --host H          bind address (default 127.0.0.1)
  --engine E        tiled | pallas_tiled | bruteforce | auto (default auto)
  --merge M         host | device | auto (default auto): where the R-way
                    cross-shard top-k merge runs — device keeps it inside
                    the SPMD program (all_to_all reduce-scatter + top_k;
                    one final [Q,k] fetch, no numpy merge), host fetches
                    R partials; auto = device on power-of-two meshes
  --shards N        size of the 1-D device mesh (default: all devices)
  --bucket-size N   points per spatial bucket (0 = engine-tuned auto)
  --score-dtype T   distance scoring: f32 (exact elementwise, the default)
                    | bf16 (matmul-form MXU score + exact f32 rescore of
                    the top survivors; docs/TUNING.md "Distance kernel")
  --query-buckets N query-side buckets per padded batch (0 = auto, ~k
                    queries per bucket; 1 = single whole-batch bucket AND
                    disables the Morton admission sort — the pre-locality
                    behavior). Served batches are Morton-sorted so the
                    buckets are spatially tight, tightening each bucket's
                    prune radius; see docs/TUNING.md "Query locality"
  --max-batch N     widest padded query batch / shape bucket (default 1024)
  --min-batch N     narrowest shape bucket (default 8)
  --num-slabs N     tiered slab index (beyond-HBM streaming; default 0 =
                    fully resident): split the index into N row slabs and
                    serve them through the device/host-RAM/mmap slab pool
                    (serve/slabpool.py) — bit-identical to fully resident
                    at every budget; a cold slab STALLS, never
                    approximates (docs/SERVING.md "Tiered slab index")
  --device-slab-budget B  device-memory budget in bytes for the resident
                    slab working set (suffixes k/m/g; 0 = unbounded),
                    counted against each slab engine's reported
                    device_bytes footprint; LRU-with-pin eviction
  --host-pool-bytes B  host-RAM row-pool budget in bytes (suffixes
                    k/m/g; 0 = unbounded); slabs past it re-read from
                    the mmap/file cold tier. Byte accounting is what
                    keeps mixed-size tenant slabs from blowing the host
                    tier — prefer it over --host-pool-slabs
  --host-pool-slabs N  DEPRECATED fallback: the same cap counted in
                    slabs (0 = unbounded). Kept for existing deploy
                    scripts; slab counts only bound memory when every
                    slab is the same size — use --host-pool-bytes.
                    Both caps apply when both are set
  --tenant NAME=PATH  multi-index tenancy (repeatable; serve/tenancy.py):
                    serve PATH's index as tenant NAME at
                    POST /v1/NAME/knn. All tenants share ONE slab pool
                    (--device-slab-budget, --host-pool-bytes), one AOT
                    executable cache (compile count stays flat as
                    tenants grow), and one admission controller. The
                    FIRST --tenant is the default tenant — legacy /knn
                    routes to it. Each tenant's index is split into
                    --num-slabs slabs (default 1). Incompatible with
                    pod/routed/standby modes; a positional input file is
                    not used (and rejected) in tenancy mode
  --tenant-quota-rows N  per-tenant admission quota: each tenant may
                    hold at most N queued+in-flight rows of the global
                    --max-queue-rows budget (0 = unsliced, global cap
                    only). Over-quota requests get 429 + Retry-After
                    like global overload, so one hot tenant cannot
                    starve the rest
  --prefetch-depth N  next-nearest slabs promoted asynchronously per
                    dispatched batch (default 1; the batcher additionally
                    announces the next batch's routed slab set a batch
                    ahead — docs/TUNING.md "Tiered slab index")
  --max-delay-ms F  micro-batch flush deadline (default 2.0)
  --pipeline-depth N  batches in flight between dispatch and demux
                    (default 2: next batch's device traversal overlaps the
                    previous batch's host merge; 1 = fully serialized)
  --max-queue-rows N  admission cap on queued+running rows (default 4096)
  --timeout-ms F    default per-request deadline (default 5000)
  --qcache-rows N   certified query cache capacity in cached rows
                    (default 4096; 0 disables the cache —
                    serve/qcache.py): byte-identical exact-hit reuse
                    keyed by (tenant, index generation, plan, query
                    bytes), plus in-flight dedup of concurrent
                    duplicates (docs/SERVING.md "Query cache & radius
                    seeding")
  --qcache-seed-rows N  triangle-inequality seed pool rows per tenant
                    (default 512; 0 disables radius seeding while
                    keeping the hit/dedup tiers): near-duplicates of a
                    cached query start their top-k heap at a certified
                    radius r = d_k(q0) + ||q - q0|| — provably
                    bit-identical answers, earlier tile pruning
  --recall-policy PATH  recall-SLO plan table (JSON from
                    tools/recall_harness.py) replacing the built-in
                    calibrated defaults; requests carrying
                    ``"recall": 0.95`` (or ``?recall=`` for binary) are
                    served by the cheapest plan whose measured recall
                    meets the target, flagged ``exact: false``
                    (serve/recall.py; docs/SERVING.md "Recall-SLO tier").
                    Exact stays the default for requests with no target
  --seq-timeout-s F how long a pod host waits for its turn in the
                    /shard_knn sequence order before answering 503 +
                    Retry-After (default 120; replicate mode only — a
                    lower seq that never arrives means the pod stream is
                    stalled). Fault injection for failure drills rides the
                    KNN_FAULTS env var / POST /faults (serve/faults.py)
  --no-warmup       skip compiling all shape buckets before serving
                    (first request per bucket then pays the compile)
  --timings         print the engine's phases and spans (serve.http,
                    serve.queue, serve.engine.stage, ...; docs/SERVING.md
                    "Spans") as JSON on shutdown; /stats carries the same
                    under engine.timers
  --verbose         log each HTTP request to stderr

Multi-host (pod) mode — launch ONE copy per host with the same args plus
--host-id; the processes join one global device mesh (jax.distributed, the
batch CLIs' lifecycle) and each serves its 1/R slice of the pod-final
answer over POST /shard_knn to the pod front end
(python -m mpi_cuda_largescaleknn_tpu.serve.frontend --hosts ...):
  --coordinator A   coordinator address host:port
  --num-hosts N     number of cooperating serving processes
  --host-id I       this process's id in [0, N)
  --routing M       off | bounds (default off). bounds = shard-local
                    routing: the hosts stay INDEPENDENT (no coordinator,
                    no global mesh) — each loads only its row slab
                    [N*i/H, N*(i+1)/H) of the input, serves full candidate
                    rows on POST /route_knn, and the front end routes each
                    query by the hosts' shard bounding boxes
                    (docs/SERVING.md "Shard-local routing"). Routing wins
                    need a spatially-ordered input file (the io
                    partitioner's Morton order); an unordered file stays
                    exact but routes every query everywhere
  --standby         routed mode only: start as a WARM STANDBY — load no
                    slab, build no engine, and wait for the pod front
                    end's replica manager to direct an adoption
                    (POST /adopt_slab). The standby then materializes the
                    named slab from this process's input file (or pulls
                    it from a surviving replica), AOT-warms every shape
                    bucket, and serves it — fingerprint-gated by the
                    front end before any query routes here
                    (docs/SERVING.md "Replication & slab handoff")
  --wire M          auto | f32 (default auto). Host-side wire-codec
                    capability: auto advertises the compressed codecs
                    (q16 candidate rows, d16 slab transfer — served only
                    when the peer asks; docs/SERVING.md "Wire formats");
                    f32 advertises and serves only the uncompressed
                    codec — the supported old-binary emulation for mixed
                    pods, and the kill switch if a codec misbehaves
"""


def usage(error: str) -> "NoReturn":  # noqa: F821
    sys.stderr.write(f"Error: {error}\n\n")
    sys.stderr.write(f"tpuknn-serve -k <k> [options] <input>\n{SERVE_FLAGS}")
    sys.exit(1)


def parse_bytes(text: str) -> int:
    """'268435456', '256m', '2g', '64k' -> bytes (suffixes are binary)."""
    t = text.strip().lower()
    mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}.get(t[-1:], 1)
    return int(float(t[:-1] if mult > 1 else t) * mult)


def parse_serve_args(argv: list[str]) -> dict:
    opt = {"k": 0, "max_radius": math.inf, "in_path": "", "port": 8080,
           "host": "127.0.0.1", "engine": "auto", "merge": "auto",
           "score_dtype": "f32", "shards": None,
           "bucket_size": 0, "query_buckets": 0,
           "max_batch": 1024, "min_batch": 8,
           "num_slabs": 0, "device_slab_budget": 0,
           "host_pool_slabs": 0, "host_pool_bytes": 0,
           "tenants": [], "tenant_quota_rows": 0,
           "prefetch_depth": 1,
           "max_delay_ms": 2.0, "pipeline_depth": 2,
           "max_queue_rows": 4096, "seq_timeout_s": None,
           "qcache_rows": 4096, "qcache_seed_rows": 512,
           "recall_policy": None,
           "timeout_ms": 5000.0, "warmup": True, "timings": False,
           "verbose": False,
           "coordinator": None, "num_hosts": 1, "host_id": 0,
           "routing": "off", "standby": False, "wire": "auto"}
    i = 0
    try:
        while i < len(argv):
            arg = argv[i]
            if not arg.startswith("-"):
                opt["in_path"] = arg
            elif arg == "-k":
                i += 1; opt["k"] = int(argv[i])
            elif arg == "-r":
                i += 1; opt["max_radius"] = float(argv[i])
            elif arg == "--port":
                i += 1; opt["port"] = int(argv[i])
            elif arg == "--host":
                i += 1; opt["host"] = argv[i]
            elif arg == "--engine":
                i += 1; opt["engine"] = argv[i]
            elif arg == "--merge":
                i += 1; opt["merge"] = argv[i]
            elif arg == "--score-dtype":
                i += 1; opt["score_dtype"] = argv[i]
            elif arg == "--shards":
                i += 1; opt["shards"] = int(argv[i])
            elif arg == "--bucket-size":
                i += 1; opt["bucket_size"] = int(argv[i])
            elif arg == "--query-buckets":
                i += 1; opt["query_buckets"] = int(argv[i])
            elif arg == "--max-batch":
                i += 1; opt["max_batch"] = int(argv[i])
            elif arg == "--min-batch":
                i += 1; opt["min_batch"] = int(argv[i])
            elif arg == "--num-slabs":
                i += 1; opt["num_slabs"] = int(argv[i])
            elif arg == "--device-slab-budget":
                i += 1; opt["device_slab_budget"] = parse_bytes(argv[i])
            elif arg == "--host-pool-slabs":
                i += 1; opt["host_pool_slabs"] = int(argv[i])
            elif arg == "--host-pool-bytes":
                i += 1; opt["host_pool_bytes"] = parse_bytes(argv[i])
            elif arg == "--tenant":
                i += 1
                name, sep, path = argv[i].partition("=")
                if not sep or not name or not path:
                    usage(f"--tenant wants NAME=PATH, got '{argv[i]}'")
                opt["tenants"].append((name, path))
            elif arg == "--tenant-quota-rows":
                i += 1; opt["tenant_quota_rows"] = int(argv[i])
            elif arg == "--prefetch-depth":
                i += 1; opt["prefetch_depth"] = int(argv[i])
            elif arg == "--max-delay-ms":
                i += 1; opt["max_delay_ms"] = float(argv[i])
            elif arg == "--pipeline-depth":
                i += 1; opt["pipeline_depth"] = int(argv[i])
            elif arg == "--max-queue-rows":
                i += 1; opt["max_queue_rows"] = int(argv[i])
            elif arg == "--timeout-ms":
                i += 1; opt["timeout_ms"] = float(argv[i])
            elif arg == "--seq-timeout-s":
                i += 1; opt["seq_timeout_s"] = float(argv[i])
            elif arg == "--qcache-rows":
                i += 1; opt["qcache_rows"] = int(argv[i])
            elif arg == "--qcache-seed-rows":
                i += 1; opt["qcache_seed_rows"] = int(argv[i])
            elif arg == "--recall-policy":
                i += 1; opt["recall_policy"] = argv[i]
            elif arg == "--coordinator":
                i += 1; opt["coordinator"] = argv[i]
            elif arg == "--num-hosts":
                i += 1; opt["num_hosts"] = int(argv[i])
            elif arg == "--host-id":
                i += 1; opt["host_id"] = int(argv[i])
            elif arg == "--routing":
                i += 1; opt["routing"] = argv[i]
            elif arg == "--standby":
                opt["standby"] = True
            elif arg == "--wire":
                i += 1; opt["wire"] = argv[i]
            elif arg == "--no-warmup":
                opt["warmup"] = False
            elif arg == "--timings":
                opt["timings"] = True
            elif arg == "--verbose":
                opt["verbose"] = True
            else:
                usage(f"unknown cmdline arg '{arg}'")
            i += 1
    except (IndexError, ValueError):
        usage(f"invalid or missing value for '{argv[i - 1] if i else ''}'")
    if opt["tenants"]:
        if opt["in_path"]:
            usage("tenancy mode takes its inputs from --tenant NAME=PATH "
                  f"— drop the positional input '{opt['in_path']}'")
        if opt["num_hosts"] > 1 or opt["routing"] != "off" or opt["standby"]:
            usage("--tenant (multi-index tenancy) is single-process "
                  "serving — it does not combine with pod, routed, or "
                  "standby modes")
        names = [n for n, _p in opt["tenants"]]
        if len(set(names)) != len(names):
            usage(f"duplicate tenant names in {names}")
    elif not opt["in_path"]:
        usage("no input file name specified")
    if opt["k"] < 1:
        usage("no k specified, or invalid k value")
    if opt["routing"] not in ("off", "bounds"):
        usage(f"--routing must be off or bounds, got '{opt['routing']}'")
    if opt["wire"] not in ("auto", "f32"):
        usage(f"--wire must be auto or f32, got '{opt['wire']}'")
    if opt["routing"] == "bounds" and opt["coordinator"]:
        usage("--routing bounds hosts are independent processes — they "
              "never join a global mesh, so --coordinator is a config "
              "error (use --routing off for the pod-collective mode)")
    if opt["standby"] and opt["routing"] != "bounds":
        usage("--standby is the routed tier's slab-handoff target — "
              "launch with --routing bounds")
    if opt["num_slabs"] < 0:
        usage(f"--num-slabs must be >= 0, got {opt['num_slabs']}")
    if opt["num_slabs"] > 0:
        if opt["num_hosts"] > 1 and opt["routing"] != "bounds":
            usage("--num-slabs (tiered slab streaming) does not combine "
                  "with the pod-collective mode — the streamed slab set "
                  "varies per batch, a pod-wide SPMD program cannot; use "
                  "--routing bounds hosts (each streams its own slab)")
        if opt["standby"]:
            usage("--standby hosts materialize their engine at adoption "
                  "time — launch the adopted engine without --num-slabs")
    return opt


def main(argv: list[str] | None = None) -> int:
    opt = parse_serve_args(sys.argv[1:] if argv is None else argv)
    enable_persistent_cache()

    from mpi_cuda_largescaleknn_tpu.io.reader import read_points
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import (
        get_mesh,
        initialize_distributed,
    )
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.server import (
        build_server,
        serve_forever,
    )

    routed = opt["routing"] == "bounds"
    if opt["num_hosts"] > 1 and not routed:
        # pod mode: join the global mesh BEFORE any device query — the
        # engine below then builds over all hosts' devices and its AOT
        # query programs are pod-wide collectives (serve/frontend.py)
        initialize_distributed(opt["coordinator"], opt["num_hosts"],
                               opt["host_id"])

    if routed and opt["standby"]:
        # warm standby (slab handoff): no slab, no engine — record the
        # engine-construction knobs and wait for POST /adopt_slab from
        # the front end's replica manager (serve/replica.py)
        from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

        standby_config = {
            "path": opt["in_path"], "num_hosts": opt["num_hosts"],
            "k": opt["k"], "shards": opt["shards"],
            "engine": opt["engine"], "merge": opt["merge"],
            "bucket_size": opt["bucket_size"],
            "max_radius": opt["max_radius"],
            "max_batch": opt["max_batch"], "min_batch": opt["min_batch"],
            "query_buckets": opt["query_buckets"],
            "score_dtype": opt["score_dtype"]}
        server = HostSliceServer((opt["host"], opt["port"]), None,
                                 routing="bounds",
                                 standby_config=standby_config,
                                 wire=opt["wire"],
                                 verbose=opt["verbose"])
        host, port = server.server_address[:2]
        print(f"standby host on http://{host}:{port} — no slab adopted "
              f"yet; waiting for POST /adopt_slab ({opt['in_path']}, "
              f"{opt['num_hosts']} slabs)")
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            print("shutting down")
        finally:
            server.close()
        return 0

    if opt["tenants"]:
        # multi-index tenancy: many indexes behind ONE slab pool, one AOT
        # cache, one admission controller (serve/tenancy.py). Each
        # tenant's index streams as --num-slabs slabs (default 1)
        from mpi_cuda_largescaleknn_tpu.serve.tenancy import (
            MultiTenantEngine,
            TenantSpec,
        )

        engine = MultiTenantEngine(
            [TenantSpec(name, path=path,
                        num_slabs=max(1, opt["num_slabs"]))
             for name, path in opt["tenants"]],
            k=opt["k"], mesh=get_mesh(opt["shards"]),
            device_slab_budget=opt["device_slab_budget"],
            host_pool_slabs=opt["host_pool_slabs"],
            host_pool_bytes=opt["host_pool_bytes"],
            prefetch_depth=opt["prefetch_depth"], engine=opt["engine"],
            bucket_size=opt["bucket_size"], max_radius=opt["max_radius"],
            max_batch=opt["max_batch"], min_batch=opt["min_batch"],
            merge=opt["merge"], query_buckets=opt["query_buckets"],
            score_dtype=opt["score_dtype"])
        print(f"multi-index tenancy: {len(opt['tenants'])} tenants "
              f"({', '.join(n for n, _p in opt['tenants'])}), "
              f"{engine.n_points} points total, default tenant "
              f"'{engine.default_tenant}', quota "
              f"{opt['tenant_quota_rows'] or 'unsliced'} rows/tenant")
        recall_policy = None
        if opt["recall_policy"]:
            from mpi_cuda_largescaleknn_tpu.serve.recall import RecallPolicy

            recall_policy = RecallPolicy.from_file(opt["recall_policy"])
        server = build_server(
            engine, host=opt["host"], port=opt["port"],
            max_delay_s=opt["max_delay_ms"] / 1e3,
            pipeline_depth=opt["pipeline_depth"],
            max_queue_rows=opt["max_queue_rows"],
            default_timeout_s=opt["timeout_ms"] / 1e3,
            verbose=opt["verbose"], recall_policy=recall_policy,
            tenant_quota_rows=opt["tenant_quota_rows"],
            qcache_rows=opt["qcache_rows"],
            qcache_seed_rows=opt["qcache_seed_rows"])
        try:
            serve_forever(server, warmup=opt["warmup"])
        except KeyboardInterrupt:
            print("shutting down")
        finally:
            server.close()
            if opt["timings"]:
                sys.stderr.write(engine.timers.dump() + "\n")
        return 0

    streaming = opt["num_slabs"] > 0
    id_offset = 0
    if routed and streaming:
        # beyond-HBM routed host: load THIS host's row slab once, then
        # stream it as --num-slabs sub-slabs through the tiered pool —
        # the host's device budget no longer caps its slab size
        # (docs/SERVING.md "Tiered slab index")
        from mpi_cuda_largescaleknn_tpu.serve.engine import load_slab_rows
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            StreamingKnnEngine,
        )

        if not (0 <= opt["host_id"] < opt["num_hosts"]):
            usage(f"--host-id {opt['host_id']} outside [0, "
                  f"{opt['num_hosts']})")
        rows, id_offset, n_total = load_slab_rows(
            opt["in_path"], opt["host_id"], opt["num_hosts"])
        engine = StreamingKnnEngine(
            points=rows, num_slabs=opt["num_slabs"], k=opt["k"],
            device_slab_budget=opt["device_slab_budget"],
            host_pool_slabs=opt["host_pool_slabs"],
            host_pool_bytes=opt["host_pool_bytes"],
            prefetch_depth=opt["prefetch_depth"],
            mesh=get_mesh(opt["shards"]), engine=opt["engine"],
            bucket_size=opt["bucket_size"], max_radius=opt["max_radius"],
            max_batch=opt["max_batch"], min_batch=opt["min_batch"],
            merge=opt["merge"], query_buckets=opt["query_buckets"],
            score_dtype=opt["score_dtype"], id_offset=id_offset,
            emit="candidates")
        print(f"routed host {opt['host_id']}/{opt['num_hosts']}: streaming"
              f" rows [{id_offset}:{id_offset + engine.n_points}) of "
              f"{n_total} as {opt['num_slabs']} slabs (device budget "
              f"{opt['device_slab_budget'] or 'unbounded'} B)")
    elif routed:
        # shard-local routing: this process owns ONE row slab of the index
        # and serves it independently — no global mesh, global neighbor
        # ids via the engine's id offset, full candidate rows emitted for
        # the front end's cross-host fold. Only the slab is MATERIALIZED
        # (serve/engine.py materialize_slab_engine — the same path the
        # slab handoff's /adopt_slab uses): routed hosts exist so each
        # box holds 1/H of the index, so a whole-file read would defeat
        # the point
        from mpi_cuda_largescaleknn_tpu.serve.engine import (
            materialize_slab_engine,
        )

        if not (0 <= opt["host_id"] < opt["num_hosts"]):
            usage(f"--host-id {opt['host_id']} outside [0, "
                  f"{opt['num_hosts']})")
        engine, id_offset, n_total = materialize_slab_engine(
            opt["in_path"], opt["host_id"], opt["num_hosts"],
            k=opt["k"], shards=opt["shards"], engine=opt["engine"],
            merge=opt["merge"], bucket_size=opt["bucket_size"],
            max_radius=opt["max_radius"], max_batch=opt["max_batch"],
            min_batch=opt["min_batch"],
            query_buckets=opt["query_buckets"],
            score_dtype=opt["score_dtype"])
        print(f"routed host {opt['host_id']}/{opt['num_hosts']}: loaded "
              f"rows [{id_offset}:{id_offset + engine.n_points}) of "
              f"{n_total} from {opt['in_path']}")
    elif streaming:
        # single-process beyond-HBM serving: the index stays in the
        # source file (mmap cold tier) + a bounded host-RAM pool; only
        # --device-slab-budget bytes of slab engines are resident at once
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            StreamingKnnEngine,
        )

        engine = StreamingKnnEngine(
            opt["in_path"], num_slabs=opt["num_slabs"], k=opt["k"],
            device_slab_budget=opt["device_slab_budget"],
            host_pool_slabs=opt["host_pool_slabs"],
            host_pool_bytes=opt["host_pool_bytes"],
            prefetch_depth=opt["prefetch_depth"],
            mesh=get_mesh(opt["shards"]), engine=opt["engine"],
            bucket_size=opt["bucket_size"], max_radius=opt["max_radius"],
            max_batch=opt["max_batch"], min_batch=opt["min_batch"],
            merge=opt["merge"], query_buckets=opt["query_buckets"],
            score_dtype=opt["score_dtype"])
        n_total = engine.n_points
        print(f"tiered index: {n_total} points from {opt['in_path']} in "
              f"{opt['num_slabs']} slabs ({engine.slab_device_bytes} B "
              f"per resident slab; device budget "
              f"{opt['device_slab_budget'] or 'unbounded'} B, host pool "
              + (f"{opt['host_pool_bytes']} B" if opt["host_pool_bytes"]
                 else f"{opt['host_pool_slabs'] or 'unbounded'} slabs")
              + ")")
    else:
        points = read_points(opt["in_path"])
        n_total = len(points)
        print(f"loaded {len(points)} points from {opt['in_path']}")
        engine = ResidentKnnEngine(
            points, opt["k"], mesh=get_mesh(opt["shards"]),
            engine=opt["engine"], bucket_size=opt["bucket_size"],
            max_radius=opt["max_radius"], max_batch=opt["max_batch"],
            min_batch=opt["min_batch"], merge=opt["merge"],
            query_buckets=opt["query_buckets"],
            score_dtype=opt["score_dtype"])

    if opt["num_hosts"] > 1 or routed:
        from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

        server = HostSliceServer((opt["host"], opt["port"]), engine,
                                 routing=opt["routing"],
                                 seq_timeout_s=opt["seq_timeout_s"],
                                 wire=opt["wire"],
                                 verbose=opt["verbose"])
        try:
            if opt["warmup"]:
                # pod mode: collective — every host compiles+executes the
                # same bucket sequence in lock-step before any fan-out
                # traffic lands. Routed mode: local warmup, no lock-step.
                info = engine.warmup()
                print(f"warmup compiles done: {info['per_bucket_s']}")
            server.ready = True
            host, port = server.server_address[:2]
            if routed:
                print(f"serving routed slab host {opt['host_id']}/"
                      f"{opt['num_hosts']} on http://{host}:{port} "
                      f"(rows [{id_offset}:{id_offset + engine.n_points}) "
                      f"of {n_total})")
            else:
                print(f"serving pod slice {engine.process_index}/"
                      f"{engine.process_count} on http://{host}:{port} "
                      f"(mesh positions {engine.stats()['my_positions']})")
            server.serve_forever()
        except KeyboardInterrupt:
            print("shutting down")
        finally:
            server.close()
            if opt["timings"]:
                sys.stderr.write(engine.timers.dump() + "\n")
        return 0
    recall_policy = None
    if opt["recall_policy"]:
        from mpi_cuda_largescaleknn_tpu.serve.recall import RecallPolicy

        recall_policy = RecallPolicy.from_file(opt["recall_policy"])
        print(f"recall policy from {opt['recall_policy']}: "
              + ", ".join(f"{p.name} (est {p.recall_estimated:g})"
                          for p in recall_policy.plans))
    server = build_server(
        engine, host=opt["host"], port=opt["port"],
        max_delay_s=opt["max_delay_ms"] / 1e3,
        pipeline_depth=opt["pipeline_depth"],
        max_queue_rows=opt["max_queue_rows"],
        default_timeout_s=opt["timeout_ms"] / 1e3,
        verbose=opt["verbose"],
        recall_policy=recall_policy,
        qcache_rows=opt["qcache_rows"],
        qcache_seed_rows=opt["qcache_seed_rows"])
    try:
        serve_forever(server, warmup=opt["warmup"])
    except KeyboardInterrupt:
        print("shutting down")
    finally:
        server.close()
        if opt["timings"]:
            sys.stderr.write(engine.timers.dump() + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
