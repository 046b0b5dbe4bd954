"""One coherent configuration object for the whole framework.

The reference scatters configuration across a hand-rolled flag parser and JVM
system properties (SURVEY.md §5.6: tsd.feature.compactions,
tsd.core.auto_create_metrics, tsd.http.staticroot, tsd.http.cachedir). Here
every knob lives in a single dataclass, constructible from CLI flags or a
dict, defaulting to the reference's behavior.
"""

from __future__ import annotations

import dataclasses
import multiprocessing


@dataclasses.dataclass
class Config:
    # storage
    table: str = "tsdb"
    uidtable: str = "tsdb-uid"
    wal_path: str | None = None
    fsync: bool = False
    throttle_rows: int | None = None
    # Series-sharded storage (storage/sharded.py): partition rows by a
    # stable hash of the series identity into N independent shards,
    # each with its own memtable/WAL/sstable tier — parallel checkpoint
    # spills, per-shard (~1/N-sized) merge pauses. 1 = the single
    # MemKVStore. With persistence, wal_path is the store DIRECTORY
    # and the count is pinned by its SHARDS.json manifest.
    shards: int = 1
    # Write-side sstable format (opentsdb_tpu/compress/):
    # - "none": spill the uncompressed TSST3 layout (the default —
    #   bytes on disk identical to previous releases).
    # - "tsst4": spill compressed columnar blocks (delta-of-delta
    #   timestamps, XOR floats, zigzag int deltas; zlib/verbatim
    #   fallbacks; per-block self-describing). Read side is
    #   format-sniffed per file, so v1-v4 generations mix freely and
    #   flipping this only changes FUTURE spills; compaction
    #   re-encodes as generations merge.
    sstable_codec: str = "none"
    # WAL group commit (storage/kv.py): > 0 lets concurrent appends
    # coalesce into one buffered write + fsync per this many
    # milliseconds — acks (telnet ok lines, HTTP 2xx, router-forwarded
    # puts) still release only AFTER the covering fsync, so the
    # durability contract is unchanged and the crash matrix proves it.
    # 0 (default) keeps today's flush-per-append behavior with
    # bit-identical WAL bytes.
    wal_group_ms: float = 0.0
    # Spill-encode pipelining (storage/sstable.py): overlap per-block
    # TSST4 encoding (including its self-check round-trip) with the
    # spill's file writes using this many encoder threads. Output
    # bytes are identical to serial encode (blocks drain in submission
    # order); 0 disables. Automatically serialized while faultpoints
    # are armed so crash schedules stay deterministic.
    spill_encode_workers: int = 2
    # Fused decode-plus-aggregate serving (compress/kernels.py): let
    # eligible downsample queries run straight off TSST4 blocks — the
    # decoded column exists only inside one XLA program. Answers are
    # exact (the path declines rather than approximates); off forces
    # the classic decode-then-reduce scan.
    sstable_fused_agg: bool = True
    # Device-side block cache (compress/devcache.py): the most decoded
    # POINTS it may keep resident on device (8 bytes a point: the
    # qualifier-delta and value columns), a block a row of its
    # slabs. Warm fused queries then upload per-record arrays, or their
    # matched points alone, instead of re-uploading and re-decoding
    # payload byte streams. ~100 MB at the default, which a CPU backend
    # keeps; a daemon takes --device-block-points and, on a device that
    # states its memory, holds this at boot to half of what the device
    # has left beside its window, which is also what it is unstated
    # (tools/cli.py). What is allocated is what the store's blocks
    # need, up to this; past it the least recently used blocks make
    # room. 0 disables the cache, and on one device the fused plan.
    devblock_points: int = 1 << 23

    # core behavior (names mirror the reference's system properties)
    auto_create_metrics: bool = False   # tsd.core.auto_create_metrics
    enable_compactions: bool = True     # tsd.feature.compactions
    flush_interval: float = 10.0        # compaction thread wake period (s)
    checkpoint_interval: float = 0.0    # spill+WAL-truncate period (s); 0=off
    compaction_min_flush_threshold: int = 100
    compaction_max_concurrent_flushes: int = 10_000
    compaction_flush_speed: int = 2

    # Materialized rollup tier (opentsdb_tpu/rollup/): per-series
    # coarse-window summaries (count/sum/min/max/first/last + t-digest
    # and HLL sketch columns) computed at checkpoint-spill time into a
    # parallel per-shard store, served by the query planner for
    # window-aligned downsamples. Writer daemons with a persistent
    # store only; a stale or missing tier degrades to raw scans.
    enable_rollups: bool = False
    rollup_resolutions: tuple = (3600, 86400)  # ascending, each divides next
    rollup_pack: int = 48          # windows packed per rollup row
    rollup_digest_k: int = 64      # t-digest centroids per window (0=off)
    rollup_hll_p: int = 8          # HLL registers exponent per window
    rollup_sketch_min_res: int = 86400  # sketch columns at res >= this
    rollup_catchup: str = "background"  # background | sync | off
    # After a crash mid-fold, catch up by refolding ONLY the windows
    # the persisted in-flight snapshot names (ROLLUP.json "inflight")
    # instead of rebuilding the whole tier. False forces the legacy
    # full rebuild (the parity oracle for tests).
    rollup_incremental_catchup: bool = True
    # Incremental delta folds (rollup/delta.py): maintain per-(series,
    # coarse-window) point buffers at ingest time so the checkpoint
    # fold summarizes ONLY from memory for windows whose full point
    # set is buffered, skipping the spilled-key re-read. Windows
    # touched by deletes, backfill into already-folded history, or
    # buffer eviction fall back to the full re-read; either path
    # produces byte-identical records. False forces every fold down
    # the full re-read (the parity oracle for tests).
    rollup_delta_fold: bool = True
    # Total buffered points across all delta windows; oldest windows
    # are evicted (to the full-fold path) past this. ~17 B/point.
    rollup_delta_points: int = 1 << 22
    # Moment-sketch columns (opentsdb_tpu/sketch/moment.py,
    # arXiv:1803.01969): ~104 B/record of count/min/max/power-moments
    # (+ log-moments), merged by pure addition — the tiny quantile
    # column that lets dsagg-pNN queries serve approximately with a
    # guaranteed error enclosure, at under a quarter of the default
    # 64-centroid t-digest column's bytes. 0 disables; stored at
    # resolutions >= rollup_moment_min_res (0 = every resolution).
    rollup_moment_k: int = 5
    rollup_moment_min_res: int = 0
    # Accuracy-budgeted sketch allocation (opentsdb_tpu/sketch/
    # budget.py, Storyboard-style): > 0 replaces the uniform
    # sketch_min_res/moment_min_res cutoffs with an optimized
    # per-resolution kind/size allocation spending this many bytes.
    # `tsdb sketch-plan` previews the allocation.
    sketch_byte_budget: int = 0
    # The admission ladder's bounded-error step: a degraded pNN query
    # is served approximately whenever its reported relative error
    # bound is <= this budget (0 = any bound admits; the answer always
    # REPORTS its bound either way).
    degrade_max_error: float = 0.0
    # Debug oracle: derive the rollup planner's dirty-window set BOTH
    # ways — the O(1)-maintained store index and the legacy full
    # memtable-key sweep — and fail loudly on divergence. Test-only
    # (the sweep is exactly the O(memtable) cost the index removes).
    rollup_sweep_check: bool = False

    # Query fast path (query/executor.py "fragment cache"): cache
    # decoded per-(selector, aligned time-chunk) columnar span
    # fragments, validated against the store's per-shard content
    # epochs and dirty-base set — repeat dashboard queries re-decode
    # only chunks with memtable-resident (dirty) data; frozen history
    # serves from RAM. Answers are bit-identical to cold scans.
    qcache: bool = True
    qcache_chunk_s: int = 6 * 3600   # chunk width (rounded to row span)
    # Total cached points across fragments (25 B a point of host RAM).
    # Sized so that ONE metric of a large fleet fits whole (4,000 series
    # x 13 h of 10 s data = 18.7M points): the cache is an LRU, and a
    # fleet-wide request walks its metric's chunks in order, so a
    # budget a little under the metric evicts each chunk just before
    # the next request asks for it again, and nothing ever hits.
    qcache_points: int = 1 << 25
    qcache_fragments: int = 1024     # max distinct fragments
    qcache_max_chunks: int = 512     # wider ranges scan unchunked/uncached

    # streaming sketches: device-resident per-series t-digests and
    # per-(metric, tagk) HyperLogLogs folded in at ingest (north star;
    # replaces the reference's Histogram.java streaming-stats role)
    enable_sketches: bool = True
    sketch_compression: int = 128       # t-digest centroids per series
    sketch_hll_p: int = 12              # 2^p registers per (metric, tagk)
    # Buffered points before an automatic background fold. Large on
    # purpose: fold cost per point falls with batch size (each series'
    # chunk amortizes one K-centroid merge sort), and the bound is NOT a
    # query-staleness bound — queries drain the buffer first, so answers
    # are always exact as of the query. It only caps fold burstiness and
    # the redundant re-fold window after a crash (checkpoint + WAL
    # replay re-folds whatever was buffered).
    sketch_flush_points: int = 1 << 20

    # device-resident columnar hot window (storage/devstore.py): recent
    # ingest kept in device HBM so steady-state queries skip the
    # host->device upload (the measured query bottleneck on real TPU)
    device_window: bool = True
    device_window_staging: int = 1 << 20   # points per upload chunk
    device_window_points: int = 1 << 26    # resident budget (26 B/point:
    #                                        13 B a slot, chunks padded
    #                                        to twice their points)
    # Mesh-sharded hot set (storage/devshard.py): shard the resident
    # window over the mesh devices on the series axis so capacity and
    # dashboard throughput scale with mesh width. 0 = off (single
    # window, historical behavior); N >= 1 = N logical shards round-
    # robined over the mesh devices (N may exceed the device count —
    # the tier-1 suite runs the whole sharded path on one CPU device).
    devwindow_shards: int = 0
    # Halve window-query [G, B] value payloads on the wire by casting
    # to bfloat16 ON DEVICE before the device->host fetch (whether
    # wide group-by fetches are payload-bound on a local chip: not
    # measured).
    # bfloat16, not float16: same 2-byte payload but float32 exponent
    # range, so big group sums cannot overflow to inf (f16 tops out at
    # 65504). OPT-IN: it trades the window path's byte-exactness vs
    # the scan path for bytes — ~2-3 significant digits, fine for
    # dashboard pixels, wrong for billing.
    wire_bf16: bool = False

    # Observability (opentsdb_tpu/obs/):
    # - slow_query_ms: /q requests slower than this are traced and
    #   logged as one-line JSON records (span tree + plan labels +
    #   shard/replica attribution) into the trace ring and the slow-
    #   query logger. 0 disables; queries are then only traced when
    #   explicitly asked (?trace=1).
    # - selfmon_interval_s: period of the self-monitoring loop that
    #   snapshots /stats and ingests it into the store itself as
    #   tsd.* series (the reference's StatsCollector pattern). 0 = off.
    # - trace_ring: bounded count of trace/slow-query records kept in
    #   memory and served at /api/traces.
    slow_query_ms: float = 0.0
    selfmon_interval_s: float = 0.0
    trace_ring: int = 256

    # Distributed serve tier (opentsdb_tpu/serve/):
    # - role: "writer" (the single ingesting daemon), "replica" (a
    #   read-only daemon that TAILS the writer's WAL continuously —
    #   bounded staleness instead of checkpoint-interval refresh), or
    #   "router" (the stateless front door fanning /q across replicas).
    # - max_staleness_ms: the replica staleness CONTRACT. A replica
    #   whose last successful WAL catch-up is older than this serves
    #   every /q answer with a "degraded": "stale" tag (and reports
    #   unhealthy at /healthz) — answers may lag the writer, but never
    #   silently. 0 disables the contract (refresh-interval semantics).
    # - tail_interval_s: the tailer's poll period between WAL suffix
    #   replays; steady-state lag is ~one interval.
    role: str = "writer"
    max_staleness_ms: float = 0.0
    tail_interval_s: float = 0.25

    # Cluster write tier (opentsdb_tpu/cluster/):
    # - cluster: membership switch. A writer adopts (or creates) the
    #   EPOCH.json next to its WAL, stamps its epoch into every WAL
    #   segment it opens, and fences every mutation once a promotion
    #   bumps the persisted epoch past its own (FencedWriterError).
    #   Replicas in cluster mode accept /promote.
    # - cluster_owner: this daemon's label in EPOCH.json bumps
    #   (defaults to host:port at daemon start).
    # - epoch_check_interval_s: the zombie guard's stat cadence —
    #   mutations re-read the epoch file at most this often (rotation
    #   and manifest commits always re-read).
    # - writer_grace_ms (router role): how long the writer's /healthz
    #   must stay dead before the router promotes a replica. 0
    #   disables automatic failover (promotion stays operator-driven
    #   via /promote).
    # - trace_sample_n: 1-in-N always-on query trace sampling feeding
    #   the trace ring, so slow queries between incidents have ambient
    #   baselines. 0 disables.
    cluster: bool = False
    cluster_owner: str | None = None
    epoch_check_interval_s: float = 0.05
    writer_grace_ms: float = 0.0
    trace_sample_n: int = 0

    # Multi-writer sharding (cluster/ownership.py; router role only):
    # - router_writers: writer base URLs. With >1, the router fans
    #   telnet/HTTP ingest by the series-hash ownership map and fans
    #   reads over each slot's owner history (answers merge).
    # - cluster_map: CLUSTER.json path. Missing file: an equal-split
    #   map over router_writers is created there. The map's epoch
    #   versions every handoff.
    # - cluster_slots: hash-space granularity for a newly created map.
    router_writers: tuple = ()
    cluster_map: str | None = None
    cluster_slots: int = 64

    # Router-side bounded result cache (the fragment-cache stamp
    # discipline one level up): full-service /q JSON answers cached
    # keyed by (normalized query, ownership-map epoch, staleness
    # bound); entries expire at router_rcache_ms. 0 entries = off.
    router_rcache: int = 0
    router_rcache_ms: float = 1000.0

    # Admission control / backpressure (serve/admission.py). All off
    # by default (0); per-tenant buckets key on the ?tenant= query
    # param (HTTP) or the connection's tenant (telnet; "default").
    # - ingest_rate/_burst_s: per-tenant token bucket in points/s;
    #   over-quota puts shed with "Please throttle" + Retry-After
    #   instead of queueing.
    # - ingest_queue_points: global cap on decoded-but-not-yet-applied
    #   points across connections — sheds before memory does.
    # - query_rate/_burst: per-tenant queries/s bucket (429 when dry).
    # - query_max_inflight N: the load-shedding ladder. Below N
    #   queries in flight: full service. N..2N: degraded — traces are
    #   stripped and /q serves ROLLUP-ONLY (no raw stitching; results
    #   tagged "degraded": "rollup-only"; queries the tier cannot
    #   serve get 503 + Retry-After). At 2N: 503 + Retry-After.
    ingest_rate: float = 0.0
    ingest_burst_s: float = 2.0
    ingest_queue_points: int = 0
    query_rate: float = 0.0
    query_burst: float = 8.0
    query_max_inflight: int = 0

    # Tenant cardinality control plane (opentsdb_tpu/tenant/):
    # - tenant_accounting: track per-tenant series cardinality from
    #   the ingest path's series-identity hash (exact set below
    #   tenant_exact_cutoff distinct series, HLL above it) plus
    #   heavy-hitter summaries; snapshotted to TENANTS.json in the
    #   checkpoint bracket and rebuilt from storage on a torn/foreign
    #   state file. Writer daemons only (replicas never account).
    # - tenant_max_series: refuse a NEW series from a tenant already
    #   at this many distinct series (0 = unlimited). Existing series
    #   keep ingesting; the refusal is a declared wire error (telnet
    #   "tenant series limit exceeded" line / HTTP 429), never a
    #   retryable throttle.
    # - tenant_global_max_series: directory-wide backstop across all
    #   tenants (0 = unlimited).
    # - tenant_limit_mode: "enforce" refuses; "warn" only counts +
    #   logs what would have been refused (tenant.would_refuse).
    # - tenant_overrides: ("name=limit", ...) per-tenant caps beating
    #   the blanket tenant_max_series; 0 = unlimited for that tenant.
    tenant_accounting: bool = True
    tenant_max_series: int = 0
    tenant_global_max_series: int = 0
    tenant_limit_mode: str = "enforce"
    tenant_overrides: tuple = ()
    tenant_exact_cutoff: int = 4096
    tenant_hll_p: int = 12
    tenant_topk: int = 16

    # Query router (serve/router.py; role="router" only).
    # - router_backends: replica base URLs ("http://host:port").
    # - writer_url: where forwarded telnet puts go (None = reject).
    # - router_deadline_ms: total per-request budget; each hop gets
    #   the remainder.
    # - router_retries: max additional attempts on OTHER replicas
    #   after a failed/expired hop (capped exponential backoff).
    # - router_hedge_ms: send a hedged duplicate to the next replica
    #   when the first hop is slower than this; first response wins,
    #   the loser is cancelled. 0 = derive from the observed p95 hop
    #   latency; negative disables hedging.
    # - probe_interval_s / router_eject_after: /healthz probe cadence
    #   and the consecutive-failure count that ejects a replica from
    #   rotation (readmitted on the next healthy probe).
    router_backends: tuple = ()
    writer_url: str | None = None
    router_deadline_ms: float = 10_000.0
    router_retries: int = 2
    router_backoff_ms: float = 50.0
    router_hedge_ms: float = 0.0
    probe_interval_s: float = 1.0
    router_eject_after: int = 3

    # compute backend: 'tpu' = jitted JAX kernels; 'cpu' = numpy oracle
    backend: str = "tpu"
    # device mesh for distributed query execution: 0 = single-device;
    # N>1 = shard fused downsample queries over the first N local chips
    mesh_devices: int = 0
    # Unified mesh execution plane (opentsdb_tpu/parallel/compile.py):
    # "" = no mesh (every kernel single-device, unchanged bytes);
    # "N" = a 1-D series-hash mesh over the first N local devices;
    # "RxC" = the 2-D hybrid (host, series) mesh — R DCN rows of C
    # ICI chips. With a mesh, eligible query reductions shard via
    # psum/all-gather combines, the fused TSST4 stage shards on the
    # block axis (pjit leg), and expert_parallel can route mixed
    # dashboard batches. Supersedes mesh_devices when set. On CPU the
    # virtual device count comes from
    # XLA_FLAGS=--xla_force_host_platform_device_count=N.
    mesh_shape: str = ""
    # Expert-parallel dashboard serving (parallel/expert.py): with a
    # mesh, a mixed /q batch (>= 2 sub-queries, one shared downsample
    # interval, moment + percentile aggregators) packs into expert
    # buckets and runs under ONE mesh dispatch instead of
    # serializing. Batches that fall off the path DECLINE loudly
    # (per-result plan: "expert-decline" + mesh.expert.decline
    # counter) and serve serially — exact-or-fall-back, the TSINT
    # fused-decline discipline.
    expert_parallel: bool = False
    # Served mesh-plane deployment mode (tsd --mesh-plane, PR 18):
    # non-empty = coordinator address ("host:port"); the daemon joins a
    # gloo/TPU process plane via jax.distributed.initialize before the
    # backend initializes (parallel/fleet.py). Each process still
    # serves its OWN local mesh (multi-controller jax cannot run
    # per-request cross-process collectives); plane membership is
    # reported in /healthz so the serve router fans out by mesh width.
    mesh_plane: str = ""
    mesh_plane_procs: int = 1          # processes in the plane
    mesh_plane_id: int = 0             # this process's plane rank
    # Rollup checkpoint fold on device (rollup/summary.py
    # window_summaries_device): accumulate the per-window sum in f64 on
    # the accelerator where the backend supports it, else f32 with the
    # contract RELAXED — either way the fold kind is DECLARED in the
    # tier state ("fold": host-f64 | device-f64 | device-f32) because
    # XLA reduction order makes even the f64 device fold tolerance-
    # level, not byte-identical, vs the host pairwise sum. Default off:
    # the rollup parity suite pins the host-f64 byte contract.
    rollup_device_fold: bool = False

    # network
    port: int = 4242
    bind: str = "0.0.0.0"
    staticroot: str | None = None       # tsd.http.staticroot
    cachedir: str | None = None         # tsd.http.cachedir
    worker_threads: int = dataclasses.field(
        default_factory=lambda: 2 * multiprocessing.cpu_count())

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown config keys: {sorted(unknown)}")
        return cls(**d)
