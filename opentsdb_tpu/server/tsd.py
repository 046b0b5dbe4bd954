"""The TSD network server: one asyncio TCP listener, two protocols.

Parity: reference src/tsd/ — PipelineFactory's first-byte protocol sniff
(a capital ASCII letter means HTTP, :68-98), the telnet command set
(put/stats/version/help/exit/diediedie/dropcaches, RpcHandler :66-96), and
the HTTP endpoint set (/ /aggregators /diediedie /dropcaches /favicon.ico
/logs /q /s /stats /suggest /version, :71-103) plus a /distinct extension
for the HLL cardinality aggregator.

Design departure (fixing the reference's acknowledged flaw, GraphHandler
:180-181 "XXX ... will block Netty"): queries run in a bounded thread pool
off the event loop, so ingest keeps flowing while graphs render. The /q
disk cache keyed on the query-string hash follows GraphHandler
(:335-468): nocache honored, max-age from the end-time rules.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import functools
import hashlib
import itertools
import json
import logging
import os
import time
import urllib.parse

from opentsdb_tpu import __version__
from opentsdb_tpu.build_data import build_data, version_string
from opentsdb_tpu.core import tags as tags_mod
from opentsdb_tpu.core.errors import (
    BadRequestError,
    FencedWriterError,
    NoSuchUniqueName,
    OverloadedError,
    PleaseThrottleError,
    ReadOnlyStoreError,
)
from opentsdb_tpu.graph.plot import Plot
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS, read_rss_bytes
from opentsdb_tpu.obs.ring import TraceRing, log_slow, make_record
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.query.grammar import parse_m
from opentsdb_tpu.server import logbuffer, qjson
from opentsdb_tpu.stats.collector import LatencyDigest, StatsCollector
from opentsdb_tpu.utils import jaxenv, timeparse
from typing import NamedTuple


class HttpRequest(NamedTuple):
    """What an HttpRpc handler sees (the reference's HttpQuery analog,
    src/tsd/HttpQuery.java, reduced to the parsed request surface)."""
    method: str
    path: str
    q: dict                    # last-value-wins query params
    params: dict               # full multi-value query params
    query_string: str
    body: bytes = b""          # request body (bounded at
    #                            MAX_BODY_BYTES; b"" for GETs)

LOG = logging.getLogger(__name__)

MAX_LINE = 1024       # per-line telnet framing limit (reference
                      # LineBasedFrameDecoder's 1024 B discard protection)
MAX_BUFFER = 1 << 22  # pipelined-burst buffer bound for the bulk path
                      # (4 MiB: bigger bursts = bigger native-decode
                      # batches and fewer pipeline turns per point)

# Protocol-level error counters (the wire.py error-path contract):
# every >= 400 HTTP response and every telnet line the server answered
# with an error bumps these — a collector watching them sees malformed
# clients, oversized bodies, and shed load without parsing log text.
_M_HTTP_ERRORS = METRICS.counter("http.errors")
_M_TELNET_ERRORS = METRICS.counter("telnet.errors")
# The /q JSON answer: its encode on the event-loop thread (the timer
# is observed through obs_trace.timed; registered here so that /stats
# lists it from boot) and the bytes of the bodies it made.
METRICS.timer("http.q.encode")
_M_Q_BYTES = METRICS.counter("http.q.bytes")

# Test-only sabotage hook (scripts/servematrix.py --bug): names a
# deliberate serve-tier bug the staleness-oracle gate must catch.
# "stale-serve" suppresses the degraded/stale tagging while the
# replica keeps serving — the exact contract violation the matrix
# exists to flag.
_SERVE_BUG = os.environ.get("TSDB_SERVE_BUG", "")


def _retry_after(seconds: float) -> dict:
    """Retry-After is integral delta-seconds on the wire; never 0 (a
    0 invites an instant retry storm from well-behaved clients)."""
    import math
    return {"Retry-After": str(max(1, math.ceil(seconds)))}


def _parse_max_error(q) -> float | None:
    """The shared ``max_error=`` budget parse for /q and /sketch:
    a positive relative half-width, or None when absent."""
    if "max_error" not in q:
        return None
    try:
        max_error = float(q["max_error"])
    except ValueError:
        raise BadRequestError(
            f"invalid max_error: {q['max_error']}") from None
    if max_error <= 0:
        raise BadRequestError("max_error must be > 0")
    return max_error


def _put_prefix_len(buf: bytes) -> int:
    """Byte length of the longest prefix of complete ``put `` lines.

    Vectorized: the per-line find/startswith loop cost ~200 ns x ~28k
    lines per MiB (~210 ms per million points) on the socket ingest
    path. Four numpy gathers test every line head at once."""
    if len(buf) < 4096:
        pos = 0
        while True:
            nl = buf.find(b"\n", pos)
            if nl < 0:
                return pos
            if not buf.startswith(b"put ", pos):
                return pos
            pos = nl + 1
    import numpy as np

    if not buf.startswith(b"put "):
        return 0
    arr = np.frombuffer(buf, np.uint8)
    nls = np.flatnonzero(arr == 10)
    if len(nls) == 0:
        return 0
    # Line i (i >= 1) starts at nls[i-1] + 1; it must begin "put ".
    starts = nls[:-1] + 1
    # A line start too close to the end can't hold "put " — treat as
    # non-put so the prefix stops before it (the loop path does too,
    # via startswith failing).
    in_range = starts + 4 <= len(buf)
    okput = (in_range
             & (arr[np.minimum(starts, len(buf) - 1)] == 0x70)
             & (arr[np.minimum(starts + 1, len(buf) - 1)] == 0x75)
             & (arr[np.minimum(starts + 2, len(buf) - 1)] == 0x74)
             & (arr[np.minimum(starts + 3, len(buf) - 1)] == 0x20))
    bad = np.flatnonzero(~okput)
    if len(bad) == 0:
        return int(nls[-1]) + 1
    # Prefix = complete put lines before the first non-put line start.
    return int(nls[bad[0]]) + 1

_CONTENT_TYPES = {
    ".html": "text/html; charset=UTF-8",
    ".css": "text/css",
    ".js": "application/javascript",
    ".png": "image/png",
    ".gif": "image/gif",
    ".ico": "image/x-icon",
    ".txt": "text/plain",
}


class TSDServer:
    def __init__(self, tsdb, executor: QueryExecutor | None = None) -> None:
        self.tsdb = tsdb
        if executor is None:
            mesh = None
            shape = tsdb.config.mesh_shape or ""
            if shape:
                from opentsdb_tpu.parallel.plan import build_mesh

                mesh = build_mesh(shape)
            elif tsdb.config.mesh_devices > 1:
                from opentsdb_tpu.parallel import make_mesh

                mesh = make_mesh(tsdb.config.mesh_devices)
            if mesh is not None:
                from opentsdb_tpu.parallel.compile import \
                    set_mesh_devices
                set_mesh_devices(int(mesh.devices.size))
            executor = QueryExecutor(tsdb, mesh=mesh)
        self.executor = executor
        self.config = tsdb.config
        # Expert-parallel dashboard serving: the knob alone arms the
        # ATTEMPT — a knob-on daemon without a (multi-device) mesh
        # still DECLARES the decline (plan: "expert-decline",
        # mesh.expert.decline{reason=no-mesh}) instead of silently
        # serving serially, so a misconfigured fleet is visible.
        self.expert_enabled = bool(self.config.expert_parallel)
        if self.config.cachedir:
            # The /q disk cache writes <hash>.txt.tmp files here; create
            # the directory up front so a fresh --cachedir works without
            # operator mkdir (the reference requires a pre-existing dir,
            # GraphHandler.java:335-346 — friendlier here).
            os.makedirs(self.config.cachedir, exist_ok=True)
        self._server: asyncio.AbstractServer | None = None
        self._shutdown = asyncio.Event()
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(2, self.config.worker_threads))
        self.log_ring = logbuffer.install()
        # counters (reference ConnectionManager/RpcHandler/PutDataPointRpc)
        self.connections_established = 0
        self.exceptions_caught = 0
        self.telnet_rpcs = 0
        self.http_rpcs = 0
        self.rpcs_unknown = 0
        self.requests_put = 0
        self.hbase_errors_put = 0
        self.illegal_arguments_put = 0
        self.unknown_metrics_put = 0
        self.put_latency = LatencyDigest()
        self.http_latency = LatencyDigest()
        self.graph_latency = LatencyDigest()
        self.cache_hits = 0
        self.cache_misses = 0
        self.start_time = int(time.time())
        # Observability (opentsdb_tpu/obs/): the trace ring holds the
        # last N traced/slow queries for /api/traces; the self-monitor
        # ingests the /stats snapshot into the store itself as tsd.*
        # series every selfmon_interval_s (0 = off — constructed
        # anyway so tests can run_once() deterministically).
        self.trace_ring = TraceRing(self.config.trace_ring)
        # 1-in-N ambient trace sampling counter (Config.trace_sample_n).
        self._trace_sample_seq = 0
        # Per-plan serve counters (raw / resident / fused / rollup /
        # approx), the /queries view's feed: bounded label set, bumped
        # once per sub-query.
        self.plan_counts: dict[str, int] = {}
        from opentsdb_tpu.obs.selfmon import SelfMonitor
        self.selfmon = SelfMonitor(
            tsdb, self._collect_stats, self.config.selfmon_interval_s)
        # Serve tier (opentsdb_tpu/serve/): admission control runs on
        # every daemon (all knobs default off); the WAL tailer is
        # attached by the CLI for --role replica daemons and owns the
        # staleness contract surfaced at /healthz and in /q tags.
        from opentsdb_tpu.serve.admission import AdmissionController
        self.admission = AdmissionController(self.config)
        self.tailer = None
        # Serializes cluster role transitions (/promote, /demote):
        # they run in the worker pool, so two retried requests can
        # both pass the event-loop idempotency check — the second
        # bump would fence the writer the first one just made.
        import threading
        self._role_lock = threading.Lock()
        self._register_default_commands()

    def attach_tailer(self, tailer) -> None:
        """Wire a serve.tailer.WalTailer into /healthz, /stats, and
        the /q staleness tagging (replica-role daemons)."""
        self.tailer = tailer

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle_conn, self.config.bind, self.config.port)
        self.selfmon.start()
        LOG.info("Ready to serve on %s:%d", self.config.bind,
                 self.config.port)

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        await self._shutdown.wait()
        await self.stop()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        self.selfmon.stop()
        if self.tailer is not None:
            self.tailer.stop()
        self._pool.shutdown(wait=False)
        self.tsdb.shutdown()
        LOG.info("Server shut down")

    def request_shutdown(self) -> None:
        self._shutdown.set()

    @property
    def port(self) -> int:
        return self._server.sockets[0].getsockname()[1]

    # ------------------------------------------------------------------
    # Connection handling: protocol sniff
    # ------------------------------------------------------------------

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        self.connections_established += 1
        try:
            first = await reader.read(1)
            if not first:
                return
            if b"A" <= first <= b"Z":
                await self._handle_http(first, reader, writer)
            else:
                await self._handle_telnet(first, reader, writer)
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        except Exception:
            self.exceptions_caught += 1
            LOG.exception("Unexpected exception from client")
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    # ------------------------------------------------------------------
    # Telnet protocol
    # ------------------------------------------------------------------

    async def _handle_telnet(self, first: bytes, reader, writer) -> None:
        buf = first
        # Connection-scoped tenant id (the telnet analog of ?tenant=):
        # a `tenant <id>` line attributes every LATER put on this
        # connection — admission buckets and the cardinality
        # accounting see the same id the router's HTTP face sees. The
        # router forwards the line ahead of forwarded puts, so
        # attribution survives the hop (it used to stop at the
        # router).
        conn = {"tenant": "default", "line": 0}
        # Per-connection two-stage ingest pipeline (SURVEY §2.9 PP row):
        # chunk N's decode runs in the pool while chunk N-1's ingest is
        # still applying — the server-loop form of wire.pipelined_ingest.
        # ``pending`` is the newest chunk's in-order ingest task,
        # ``older`` the one before it; awaiting ``older`` before
        # spawning a third bounds the pipeline (and its buffered bytes)
        # at two chunks in flight — socket backpressure does the rest.
        pending: asyncio.Task | None = None
        older: asyncio.Task | None = None
        try:
            while not self._shutdown.is_set():
                nl = buf.find(b"\n")
                if nl < 0:
                    if len(buf) > MAX_BUFFER:
                        raise ValueError(
                            "frame length exceeds buffer limit")
                    chunk = await reader.read(
                        max(MAX_BUFFER + 1 - len(buf), 1))
                    if not chunk:
                        break
                    buf += chunk
                    continue
                # Bulk fast path: a pipelined burst of puts decodes
                # natively into columnar arrays and lands through
                # add_batch — this is how the 1M dps/s target is met
                # (SURVEY.md §7). One scan finds the longest prefix of
                # complete put lines; anything after it falls to the
                # per-line command path below.
                if buf.startswith(b"put ") and buf.find(b"\n", nl + 1) >= 0:
                    prefix_len = _put_prefix_len(buf)
                    if prefix_len > nl + 1:
                        chunk, buf = buf[:prefix_len], buf[prefix_len:]
                        if older is not None:
                            await older
                        # The connection's line counter advances NOW
                        # (synchronously, before the next chunk is
                        # carved) so each in-flight bulk task knows the
                        # exact stream line its chunk starts at — error
                        # lines report the connection-wide line number,
                        # not the chunk-relative offset.
                        line_base = conn["line"]
                        conn["line"] += chunk.count(b"\n")
                        older, pending = pending, asyncio.create_task(
                            self._bulk_puts_pipelined(
                                chunk, pending, writer,
                                conn["tenant"], line_base))
                        continue
                # Ordering: bulk results (error lines, stats) land
                # before any later single-line command executes.
                if pending is not None:
                    await pending
                    pending = older = None
                line, buf = buf[:nl], buf[nl + 1:]
                conn["line"] += 1
                if len(line) > MAX_LINE:
                    raise ValueError(f"frame length exceeds {MAX_LINE}")
                words = tags_mod.split_string(
                    line.decode("utf-8", "replace").rstrip("\r"))
                if not words:
                    continue
                self.telnet_rpcs += 1
                if not await self._telnet_command(words, writer, conn):
                    return
        finally:
            # Retrieve both tasks (even on error paths) so no exception
            # is left unawaited; the first failure propagates.
            tasks = [t for t in (older, pending) if t is not None]
            if tasks:
                results = await asyncio.gather(*tasks,
                                               return_exceptions=True)
                for r in results:
                    if isinstance(r, BaseException):
                        raise r

    async def _bulk_puts_pipelined(self, chunk: bytes,
                                   prev: asyncio.Task | None,
                                   writer,
                                   tenant: str = "default",
                                   line_base: int = 0) -> None:
        """Stage A (decode) runs immediately in the pool — overlapping
        the previous chunk's stage B — then awaits ``prev`` so ingest
        and error reporting stay in arrival order. ``line_base`` is the
        connection-wide line number of this chunk's first line, so a
        mid-batch parse error reports its exact stream line."""
        from opentsdb_tpu.server import wire

        t0 = time.time()
        loop = asyncio.get_running_loop()
        batch = await loop.run_in_executor(
            self._pool, functools.partial(
                wire.decode_puts, chunk, line_base=line_base))
        if prev is not None:
            await prev
        # Ingest admission (serve/admission.py): shed the whole batch
        # with a throttle line + retry hint BEFORE it allocates store
        # work — collectors already understand "Please throttle".
        npts = len(batch.sid)
        wait = self.admission.admit_ingest(npts, tenant) if npts \
            else 0.0
        if wait > 0:
            self.telnet_rpcs += npts + len(batch.errors)
            self.requests_put += npts + len(batch.errors)
            self.hbase_errors_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(
                f"put: Please throttle writes: over ingest quota, "
                f"retry after {max(wait, 0.1):.1f}s\n".encode())
            await writer.drain()
            return
        try:
            n, series_errors = await loop.run_in_executor(
                self._pool,
                functools.partial(wire.ingest_batch, self.tsdb, batch,
                                  tenant=tenant))
        finally:
            if npts:
                self.admission.ingest_done(npts)
        self.telnet_rpcs += n + len(batch.errors)
        self.requests_put += n + len(batch.errors)
        elines = list(batch.error_lines)
        for k, err in enumerate(batch.errors):
            self.illegal_arguments_put += 1
            _M_TELNET_ERRORS.inc()
            # 1-based stream line numbers when the decoder attributed
            # them (the native path doesn't); same line prefix either
            # way so `grep "put: illegal argument"` keeps working.
            at = f" at line {elines[k] + 1}" if k < len(elines) else ""
            writer.write(
                f"put: illegal argument{at}: {err}\n".encode())
        for err in series_errors:
            _M_TELNET_ERRORS.inc()
            if "No such name" in err:
                self.unknown_metrics_put += 1
                writer.write(f"put: unknown metric: {err}\n".encode())
            elif "throttle" in err.lower():
                self.hbase_errors_put += 1
                writer.write(
                    f"put: Please throttle writes: {err}\n".encode())
            elif "[tenant-limit]" in err:
                # Declared cardinality refusal (tenant/limits.py),
                # tagged by wire.ingest_batch: NOT a throttle — the
                # series can never ingest until the limit moves, so
                # the line must not invite a retry loop. The rest of
                # the batch (existing series) already applied.
                self.hbase_errors_put += 1
                writer.write(
                    f"put: tenant series limit exceeded: {err}\n"
                    .encode())
            elif "read-only" in err:
                self.hbase_errors_put += 1
                writer.write(
                    f"put: read-only replica: {err}\n".encode())
            elif "[fenced]" in err:
                # FencedWriterError, tagged by wire.ingest_batch with
                # a stable marker (message wording may drift): this
                # daemon has been deposed — refuse loudly, the router
                # forwards to the current writer.
                self.hbase_errors_put += 1
                writer.write(f"put: fenced writer: {err}\n".encode())
            else:
                self.illegal_arguments_put += 1
                writer.write(f"put: illegal argument: {err}\n".encode())
        self.put_latency.add((time.time() - t0) * 1000)
        await writer.drain()

    # ------------------------------------------------------------------
    # Command registries (the reference's TelnetRpc/HttpRpc SPIs,
    # src/tsd/TelnetRpc.java:22 / HttpRpc.java:20 / RpcHandler.java
    # :66-103 — but as plain dicts a deployment can extend at runtime).
    # ------------------------------------------------------------------

    def register_telnet(self, command: str, handler) -> None:
        """Register ``handler(words, writer) -> bool | None`` for a
        telnet command; returning False closes the connection. A
        handler carrying a truthy ``_wants_conn`` attribute is called
        ``handler(words, writer, conn)`` with the per-connection state
        dict instead (the built-in ``put``/``tenant`` pair use it for
        connection-scoped tenant attribution)."""
        self.telnet_commands[command] = handler

    def register_http(self, route: str, handler) -> None:
        """Register ``async handler(req) -> (status, ctype, body,
        headers)`` for an exact path (no trailing slash)."""
        self.http_routes[route] = handler

    def _register_default_commands(self) -> None:
        self.telnet_commands = {
            "put": self._cmd_put,
            "tenant": self._cmd_tenant,
            "version": lambda words, writer: writer.write(
                self._version_text().encode()),
            "stats": lambda words, writer: writer.write(
                ("\n".join(self._collect_stats()) + "\n").encode()),
            "help": lambda words, writer: writer.write((
                "available commands: "
                + " ".join(sorted(self.telnet_commands))
                + "\n").encode()),
            "exit": lambda words, writer: False,
            "dropcaches": self._cmd_dropcaches,
            "diediedie": self._cmd_diediedie,
        }
        self.http_routes = {
            "/": self._http_home,
            "/aggregators": self._http_aggregators,
            "/version": self._http_version,
            "/stats": self._http_stats,
            "/logs": self._http_logs,
            "/suggest": lambda req: self._suggest(req.q),
            "/q": lambda req: self._query(req.q, req.query_string,
                                          req.params),
            "/distinct": lambda req: self._distinct(req.q),
            "/sketch": lambda req: self._sketch(req.q),
            "/forecast": lambda req: self._forecast(req.q, req.params),
            "/fault": self._http_fault,
            "/queries": self._http_queries_page,
            "/api/queries": self._http_queries,
            "/tenants": self._http_tenants_page,
            "/api/tenants": self._http_tenants,
            "/api/put": self._http_put,
            "/promote": self._http_promote,
            "/demote": self._http_demote,
            "/healthz": self._http_healthz,
            "/api/mesh/reshard": self._http_mesh_reshard,
            "/metrics": self._http_metrics,
            "/api/traces": self._http_traces,
            "/dropcaches": self._http_dropcaches,
            "/diediedie": self._http_diediedie,
            "/favicon.ico": self._http_favicon,
        }

    def _cmd_tenant(self, words, writer, conn):
        # Connection-scoped attribution: `tenant <id>` binds every
        # later put to <id>'s quota + cardinality budget.
        if len(words) != 2 or not words[1]:
            _M_TELNET_ERRORS.inc()
            writer.write(b"tenant: need exactly one id\n")
        else:
            conn["tenant"] = words[1]
            writer.write(f"tenant {words[1]}\n".encode())
    _cmd_tenant._wants_conn = True

    def _cmd_put(self, words, writer, conn):
        self._telnet_put(words, writer, conn["tenant"])
    _cmd_put._wants_conn = True

    def _cmd_dropcaches(self, words, writer):
        self.tsdb.drop_caches()
        writer.write(b"Caches dropped.\n")

    def _cmd_diediedie(self, words, writer):
        writer.write(b"Cleaning up and exiting now.\n")
        self.request_shutdown()
        return False

    async def _telnet_command(self, words: list[str], writer,
                              conn: dict | None = None) -> bool:
        """Dispatch one telnet command; False closes the connection.
        ``conn`` is the per-connection state dict (tenant id)."""
        conn = conn if conn is not None else {"tenant": "default"}
        handler = self.telnet_commands.get(words[0])
        if handler is None:
            self.rpcs_unknown += 1
            _M_TELNET_ERRORS.inc()
            writer.write(f"unknown command: {words[0]}\n".encode())
            await writer.drain()
            return True
        # Per-command latency timer (the HTTP _route twin). The bulk
        # put pipeline bypasses this dispatcher by design — it's
        # covered by rpc.latency/put and the wal.* instruments.
        with METRICS.timer("telnet.handler", {"cmd": words[0]}).time():
            if getattr(handler, "_wants_conn", False):
                out = handler(words, writer, conn)
            else:
                out = handler(words, writer)
            if asyncio.iscoroutine(out):
                out = await out
        # Per-command backpressure: a slow reader pipelining commands
        # must throttle the loop, not grow the transport buffer.
        await writer.drain()
        return out is not False

    def _telnet_put(self, words: list[str], writer,
                    tenant: str = "default") -> None:
        """Parity: reference PutDataPointRpc.importDataPoint (:93-123)."""
        from opentsdb_tpu.core.errors import TenantLimitError
        t0 = time.time()
        self.requests_put += 1
        try:
            wait = self.admission.admit_ingest(1, tenant)
            if wait > 0:
                # Shed: admit_ingest took NO slot, so nothing to
                # release (pairing ingest_done here would free
                # capacity someone else's batch is really using).
                raise PleaseThrottleError(
                    f"over ingest quota, retry after "
                    f"{max(wait, 0.1):.1f}s")
            self.admission.ingest_done(1)
            if len(words) < 5:
                raise ValueError("not enough arguments"
                                 f" (need least 5, got {len(words)})")
            metric = words[1]
            timestamp = tags_mod.parse_long(words[2])
            if timestamp <= 0:
                raise ValueError("invalid timestamp: " + str(timestamp))
            # Same strict value grammar as the bulk/native path, so
            # acceptance never depends on pipelining.
            is_float, ival, fval = tags_mod.parse_value(words[3])
            tag_map: dict[str, str] = {}
            for tag in words[4:]:
                tags_mod.parse(tag_map, tag)
            if is_float:
                self.tsdb.add_point(metric, timestamp, fval, tag_map,
                                    tenant=tenant)
            else:
                self.tsdb.add_point(metric, timestamp, ival, tag_map,
                                    tenant=tenant)
            self.put_latency.add((time.time() - t0) * 1000)
        except TenantLimitError as e:
            # Declared cardinality refusal (tenant/limits.py): a
            # DISTINCT line from the throttle — collectors must not
            # treat it as transient; the put can never succeed until
            # the limit is raised. Existing series keep ingesting.
            self.hbase_errors_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(
                f"put: tenant series limit exceeded: {e}\n".encode())
        except NoSuchUniqueName as e:
            self.unknown_metrics_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(f"put: unknown metric: {e}\n".encode())
        except (ValueError, ArithmeticError) as e:
            self.illegal_arguments_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(f"put: illegal argument: {e}\n".encode())
        except PleaseThrottleError as e:
            self.hbase_errors_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(f"put: Please throttle writes: {e}\n".encode())
        except ReadOnlyStoreError as e:
            # A replica daemon (--read-only) serves reads only; tell
            # the collector to write to the writer frontend instead.
            self.hbase_errors_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(f"put: read-only replica: {e}\n".encode())
        except FencedWriterError as e:
            # Deposed writer (cluster/epoch.py): a promotion bumped
            # the epoch past ours while this daemon was wedged. The
            # put is REFUSED — never acked, never applied to a
            # replayable file — and the collector should re-send to
            # the router, which forwards to the current writer.
            self.hbase_errors_put += 1
            _M_TELNET_ERRORS.inc()
            writer.write(f"put: fenced writer (superseded by epoch "
                         f"{e.current_epoch}): {e}\n".encode())

    # ------------------------------------------------------------------
    # HTTP protocol
    # ------------------------------------------------------------------

    # HTTP request bounds (the telnet path's MAX_BUFFER analog).
    MAX_HEADER_BYTES = 65536
    MAX_BODY_BYTES = 1 << 20

    async def _handle_http(self, first: bytes, reader, writer) -> None:
        """Persistent-connection HTTP loop.

        Parity: reference HttpQuery.java:471-530 keeps HTTP/1.1
        connections alive between requests; :432 renders errors on graph
        requests as PNG so browser <img> embeds show the failure. Bounds:
        headers capped at MAX_HEADER_BYTES, bodies at MAX_BODY_BYTES
        (413) — the read path never buffers unbounded client data.
        """
        data = first
        while not self._shutdown.is_set():
            while b"\r\n\r\n" not in data:
                chunk = await reader.read(4096)
                if not chunk:
                    return
                data = data + chunk
                if len(data) > self.MAX_HEADER_BYTES:
                    await self._http_respond(
                        writer, 431, "text/plain",
                        b"Request Header Fields Too Large\n", {}, False)
                    return
            head, _, data = data.partition(b"\r\n\r\n")
            lines = head.decode("latin-1").split("\r\n")
            try:
                method, target, version = lines[0].split(" ", 2)
            except ValueError:
                return
            headers = {}
            for ln in lines[1:]:
                k, _, v = ln.partition(":")
                headers[k.strip().lower()] = v.strip()
            # Drain (and bound) the request body so the next request on
            # the connection parses from a clean boundary.
            try:
                clen = int(headers.get("content-length", "0") or "0")
            except ValueError:
                return
            if clen > self.MAX_BODY_BYTES:
                await self._http_respond(
                    writer, 413, "text/plain",
                    b"Payload Too Large\n", {}, False)
                return
            while len(data) < clen:
                chunk = await reader.read(1 << 16)
                if not chunk:
                    return
                data += chunk
            req_body, data = data[:clen], data[clen:]
            keep = (version.strip().upper() == "HTTP/1.1"
                    and headers.get("connection", "").lower() != "close")

            t0 = time.time()
            try:
                status, ctype, body, extra = await self._route(
                    method, target, req_body)
            except BadRequestError as e:
                status, extra = e.status, {}
                ctype, body = self._error_body(target, str(e))
            except NoSuchUniqueName as e:
                status, extra = 400, {}
                ctype, body = self._error_body(target, str(e))
            except OverloadedError as e:
                # Admission shed: an explicit retry signal, not a
                # failure — 429 (tenant quota) / 503 (load) with an
                # honest Retry-After.
                status, extra = e.status, _retry_after(e.retry_after)
                ctype, body = "text/plain", f"{e}\n".encode()
            except Exception as e:
                self.exceptions_caught += 1
                LOG.exception("HTTP error on %s", target)
                status, extra = 500, {}
                ctype, body = self._error_body(
                    target, f"Internal Server Error: {e}")
            self.http_latency.add((time.time() - t0) * 1000)
            await self._http_respond(writer, status, ctype, body, extra,
                                     keep)
            if not keep:
                return

    def _error_body(self, target: str, message: str) -> tuple[str, bytes]:
        """Error payload; PNG-rendered for graph requests so <img>
        embeds show the failure (reference HttpQuery.java:432)."""
        parsed = urllib.parse.urlsplit(target)
        if parsed.path == "/q" and "png" in urllib.parse.parse_qs(
                parsed.query, keep_blank_values=True):
            try:
                from opentsdb_tpu.graph.plot import render_error_png
                return "image/png", render_error_png(message)
            except Exception:  # fall back to text on render failure
                pass
        return "text/plain", f"{message}\n".encode()

    async def _http_respond(self, writer, status: int, ctype: str,
                            body: bytes, extra: dict,
                            keep: bool) -> None:
        reason = {200: "OK", 304: "Not Modified", 400: "Bad Request",
                  404: "Not Found", 405: "Method Not Allowed",
                  413: "Payload Too Large",
                  429: "Too Many Requests",
                  431: "Request Header Fields Too Large",
                  500: "Internal Server Error",
                  503: "Service Unavailable"}.get(status, "OK")
        if status >= 400:
            _M_HTTP_ERRORS.inc()
        hdrs = [f"HTTP/1.1 {status} {reason}",
                f"Content-Type: {ctype}",
                f"Content-Length: {len(body)}",
                f"Connection: {'keep-alive' if keep else 'close'}"]
        for k, v in extra.items():
            hdrs.append(f"{k}: {v}")
        writer.write(("\r\n".join(hdrs) + "\r\n\r\n").encode() + body)
        await writer.drain()

    async def _route(self, method: str, target: str,
                     body: bytes = b""):
        self.http_rpcs += 1
        parsed = urllib.parse.urlsplit(target)
        path = parsed.path
        params = urllib.parse.parse_qs(parsed.query, keep_blank_values=True)
        q = {k: v[-1] for k, v in params.items()}

        if path.startswith("/s/") or path == "/s":
            return self._static_file(path[2:].lstrip("/"))
        route = path.rstrip("/") or "/"
        handler = self.http_routes.get(route)
        if handler is None:
            self.rpcs_unknown += 1
            return 404, "text/plain", b"Page Not Found\n", {}
        req = HttpRequest(method=method, path=path, q=q, params=params,
                          query_string=parsed.query, body=body)
        # Per-endpoint latency timer: tagged by the ROUTE (a bounded
        # label set), never the raw path — /metrics cardinality must
        # not scale with request strings.
        with METRICS.timer("http.handler", {"endpoint": route}).time():
            out = handler(req)
            if asyncio.iscoroutine(out):
                out = await out
        return out

    # -- built-in HTTP handlers ----------------------------------------

    def _http_home(self, req) -> tuple:
        # Serve the query UI (reference: HomePage bootstraps the GWT
        # client, RpcHandler.java:304-317) with a no-cache header so UI
        # updates take effect immediately (an operator staticroot copy
        # would otherwise carry the year-long /s header).
        status, ctype, body, hdrs = self._static_file("index.html")
        if status == 200:
            return (status, ctype, body,
                    dict(hdrs, **{"Cache-Control": "no-cache"}))
        return (200, "text/html; charset=UTF-8",
                self._homepage().encode(), {})

    def _http_aggregators(self, req) -> tuple:
        return (200, "application/json",
                json.dumps(Aggregators.available()).encode(), {})

    def _http_version(self, req) -> tuple:
        if "json" in req.q:
            info = dict(build_data(), start_time=self.start_time)
            return (200, "application/json",
                    json.dumps(info).encode(), {})
        return 200, "text/plain", self._version_text().encode(), {}

    def _http_stats(self, req) -> tuple:
        lines = self._collect_stats()
        if "json" in req.q:
            return (200, "application/json",
                    json.dumps(lines).encode(), {})
        return 200, "text/plain", ("\n".join(lines) + "\n").encode(), {}

    def _http_logs(self, req) -> tuple:
        logbuffer_lines = self.log_ring.formatted()
        if "level" in req.q:
            try:
                logbuffer.set_level(req.q["level"])
            except ValueError as e:
                raise BadRequestError(str(e)) from None
        if "json" in req.q:
            return (200, "application/json",
                    json.dumps(logbuffer_lines).encode(), {})
        return (200, "text/plain",
                ("\n".join(logbuffer_lines) + "\n").encode(), {})

    def _http_fault(self, req) -> tuple:
        """Fault-injection admin (fault/faultpoints.py): integration
        tests arm failpoints on a LIVE tsd process.

            GET /fault                     registry snapshot (JSON)
            GET /fault?arm=site=mode:k=v   arm (spec grammar; crash
                                           modes WILL kill the daemon
                                           at the next hit — the point)
            GET /fault?disarm=site         disarm one site
            GET /fault?clear=1             disarm everything
        """
        from opentsdb_tpu.fault import faultpoints as fp
        q = req.q
        if "arm" in q:
            try:
                fp.install_spec(q["arm"])
            except ValueError as e:
                raise BadRequestError(str(e)) from None
        if "disarm" in q:
            fp.disarm(q["disarm"])
        if "clear" in q:
            fp.clear()
        return (200, "application/json",
                json.dumps(fp.status()).encode(), {})

    def _http_healthz(self, req) -> tuple:
        """Liveness + the replica staleness contract. The router's
        probes key on both the status code and the body: 200/ok keeps
        (or readmits) a replica in rotation, 503/stale ejects it from
        preference while the body still carries the measured lag. In
        cluster mode the body also carries the writer epoch this
        daemon owns (or is fenced behind) — the router's promotion
        manager keys demote-on-return off exactly this."""
        if self.tailer is not None:
            body = self.tailer.health()
        else:
            body = {
                "role": self.config.role,
                "ok": True,
                "read_only": bool(getattr(self.tsdb.store, "read_only",
                                          False)),
            }
        store = self.tsdb.store
        epoch = getattr(store, "writer_epoch", None)
        if epoch is not None:
            body["writer_epoch"] = int(epoch)
        guard = getattr(store, "epoch_guard", None)
        if guard is not None and guard.fenced:
            # Deposed but alive: reads still serve (coherent, just no
            # longer advancing), every write refuses. The router sees
            # this and issues /demote.
            body["fenced"] = True
            body["fenced_by_epoch"] = guard.fenced_epoch
        body["uptime_s"] = int(time.time()) - self.start_time
        body["inflight_queries"] = self.admission.inflight_queries
        # Which device this daemon serves from, as jax reports it, and
        # where its compiled programs persist.
        body["device"] = jaxenv.device_info()
        body["compile_cache_dir"] = jaxenv.compile_cache_dir()
        mesh = self._mesh_serving_info()
        if mesh is not None:
            # The router's fan-out weights series ownership by this
            # width (resident hot-set shards): a wide backend owns
            # proportionally more of the series space.
            body["mesh"] = mesh
        status = 200 if body.get("ok") else 503
        return (status, "application/json",
                json.dumps(body).encode(), {})

    def _mesh_serving_info(self) -> dict | None:
        """The serving-mesh block for /healthz and /api/queries: plane
        membership (when --mesh-plane joined one) and the sharded
        resident hot set's live shape. None when neither is on — the
        body stays byte-compatible for non-mesh fleets."""
        from opentsdb_tpu.parallel.fleet import plane_info
        plane = plane_info()
        dw = getattr(self.tsdb, "devwindow", None)
        sharded = dw is not None and hasattr(dw, "shard_of")
        if plane is None and not sharded:
            return None
        out: dict = {"width": dw.n_shards if sharded else 1}
        if plane is not None:
            out["plane"] = dict(plane)
        if sharded:
            out["resident"] = {
                "shards": dw.n_shards,
                # Per shard: the device it is pinned to (None =
                # default placement) and the points resident there.
                "shard_devices": dw.shard_device_ids(),
                "shard_points": dw.shard_resident_points(),
                "points": dw.resident_points(),
                "generation": dw.generation,
                "reshards": dw.reshard_count,
                "last_reshard_ms": round(dw.reshard_ms, 2),
            }
        return out

    async def _http_mesh_reshard(self, req) -> tuple:
        """Live hot-set resharding admin: ``/api/mesh/reshard?shards=N``
        redistributes the resident device columns over N shards
        (coherent swap — pre-swap queries finish on the complete old
        set; see storage/devshard.py). Runs in the worker pool: the
        drain/rebuild must not block the event loop's ingest."""
        dw = getattr(self.tsdb, "devwindow", None)
        if dw is None or not hasattr(dw, "shard_of"):
            raise BadRequestError(
                "resident hot set is not sharded (start the daemon "
                "with --devwindow-shards or --mesh-plane)")
        try:
            n = int(req.q.get("shards", "0"))
        except ValueError:
            raise BadRequestError(
                f"invalid shards: {req.q.get('shards')}") from None
        if n < 1:
            raise BadRequestError("shards must be >= 1")
        loop = asyncio.get_running_loop()
        try:
            stats = await loop.run_in_executor(
                self._pool, lambda: dw.reshard(n_shards=n))
        except RuntimeError as e:
            return (409, "application/json",
                    json.dumps({"error": str(e)}).encode(), {})
        return (200, "application/json",
                json.dumps(stats).encode(), {})

    # ------------------------------------------------------------------
    # Cluster failover (opentsdb_tpu/cluster/): promote / demote
    # ------------------------------------------------------------------

    async def _http_promote(self, req) -> tuple:
        """Replica → writer takeover. The router's promotion manager
        (cluster/promote.py) calls this when the writer's /healthz has
        been dead past the grace; operators can call it by hand.
        Bumps the persisted epoch (EPOCH.json CAS), reopens the WAL
        tail read-write under a fresh inode, swaps sketches + rollups
        into writer mode, and stops the tailer. Idempotent: asking an
        already-promoted daemon again returns its epoch without
        another bump (a retry after a lost response must not
        re-depose anyone)."""
        path = getattr(self.tsdb, "cluster_epoch_path", None)
        if not path:
            raise BadRequestError(
                "not a cluster member (start the daemon with "
                "--cluster)")
        store = self.tsdb.store
        if not getattr(store, "read_only", False):
            return (200, "application/json", json.dumps({
                "role": "writer", "already_writer": True,
                "epoch": int(getattr(store, "writer_epoch", 0) or 0),
            }).encode(), {})
        expect = None
        if req.q.get("expect"):
            try:
                expect = int(req.q["expect"])
            except ValueError:
                raise BadRequestError("expect must be an integer") \
                    from None
        loop = asyncio.get_running_loop()
        epoch = await loop.run_in_executor(
            self._pool, functools.partial(self._do_promote, path,
                                          expect))
        return (200, "application/json", json.dumps(
            {"role": "writer", "epoch": epoch}).encode(), {})

    def _do_promote(self, path: str, expect: int | None) -> int:
        from opentsdb_tpu.cluster import epoch as _ep
        from opentsdb_tpu.fault.faultpoints import fire as _fault
        # One role transition at a time: the event-loop idempotency
        # check races its own executor dispatch (two retried /promote
        # requests can both pass it), and a second bump after the
        # first promotion landed would instantly fence the freshly
        # promoted writer. Re-check under the lock.
        with self._role_lock:
            if not getattr(self.tsdb.store, "read_only", False):
                return int(getattr(self.tsdb.store, "writer_epoch", 0)
                           or 0)
            # Bump BEFORE touching the tailer: a failed bump (CAS
            # conflict, disk error) must leave the replica exactly as
            # it was — still tailing. The bump is durable; crash
            # after it leaves an epoch with no acting writer, and the
            # next promotion attempt bumps past it.
            owner = (self.config.cluster_owner
                     or f"{self.config.bind}:{self.config.port}")
            new = _ep.bump_epoch(path, owner=owner, expect=expect)
            _fault("cluster.promote.bumped", path)
            guard = _ep.EpochGuard(
                path, new,
                interval_s=self.config.epoch_check_interval_s)
            tailer, self.tailer = self.tailer, None
            if tailer is not None:
                # The tailer is the replica's only refresh driver; it
                # must stop BEFORE the store flips writable
                # (refresh_replica on a writable store raises —
                # correctly).
                tailer.stop()
            try:
                self.tsdb.promote(new, epoch_guard=guard)
            except BaseException:
                # The store restored itself to a coherent replica; go
                # back to tailing so this daemon keeps its place in
                # rotation while the router tries the next candidate.
                from opentsdb_tpu.serve.tailer import WalTailer
                self.tailer = WalTailer(self.tsdb)
                self.tailer.start()
                raise
            self.config.role = "writer"
            # A promoted replica inherits the spill cadence it was
            # configured with (0 = manual/shutdown checkpoints only,
            # the plain-writer default).
            self.tsdb.compactionq.checkpoint_interval = \
                self.config.checkpoint_interval or 0.0
            LOG.warning("promoted to writer at epoch %d", new)
            return new

    async def _http_demote(self, req) -> tuple:
        """Writer → tailing replica (the deposed writer's way back
        into the fleet). The router calls this when a fenced or
        stale-epoch writer reappears; idempotent on replicas."""
        path = getattr(self.tsdb, "cluster_epoch_path", None)
        if not path:
            raise BadRequestError(
                "not a cluster member (start the daemon with "
                "--cluster)")
        if getattr(self.tsdb.store, "read_only", False):
            return (200, "application/json", json.dumps(
                {"role": "replica", "already_replica": True}).encode(),
                {})
        if os.environ.get("TSDB_CLUSTER_BUG") == "split-brain":
            # The servematrix cluster gate: an unfenced zombie ignores
            # the protocol entirely — it neither fences its writes nor
            # complies with demotion. The matrix must catch what such
            # a writer does to the cluster.
            return (500, "text/plain",
                    b"demote sabotaged by TSDB_CLUSTER_BUG\n", {})
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(self._pool, self._do_demote)
        return (200, "application/json", json.dumps(
            {"role": "replica"}).encode(), {})

    def _do_demote(self) -> None:
        with self._role_lock:
            if getattr(self.tsdb.store, "read_only", False):
                return  # a concurrent demote won the race; idempotent
            self.tsdb.demote()
            self.config.role = "replica"
            if not self.config.max_staleness_ms:
                # The staleness contract defaults ON for replicas (the
                # cmd_tsd replica-role default) — a demoted daemon
                # serves under the same promise as a born replica.
                self.config.max_staleness_ms = 5000.0
            # The tailer becomes the ONLY refresh driver: the
            # compaction timer must stop double-driving
            # refresh_replica (the make_tsdb role=replica exclusion,
            # applied at runtime).
            self.tsdb.compactionq.checkpoint_interval = 0.0
            from opentsdb_tpu.serve.tailer import WalTailer
            self.tailer = WalTailer(self.tsdb)
            self.tailer.start()
            LOG.warning("demoted to tailing replica")

    def _degraded_reason(self, load_degraded: bool) -> str | None:
        """The /q result tag: "stale" when the replica staleness
        contract is violated, "rollup-only" under load shedding's
        degraded step, both comma-joined when both hold. None = full
        service. The stale half is what the bounded-staleness oracle
        checks — and what TSDB_SERVE_BUG=stale-serve sabotages so the
        serve matrix's gate can prove the oracle catches a lying
        replica."""
        reasons = []
        if (self.tailer is not None and self.tailer.stale()
                and _SERVE_BUG != "stale-serve"):
            reasons.append("stale")
        if load_degraded:
            reasons.append("rollup-only")
        return ",".join(reasons) if reasons else None

    def _note_plan(self, plan: str, approx: bool = False) -> None:
        """Bump the bounded per-plan counters: planner-choice labels
        collapse to raw/resident/fused/rollup/approx (rollup
        resolution labels like "1h" fold into "rollup"; a degraded
        rollup answer that carries approx metadata counts BOTH)."""
        if plan.startswith("approx"):
            key = "approx"
        elif plan in ("raw", "resident", "fused", "expert"):
            key = plan
        else:
            key = "rollup"
        self.plan_counts[key] = self.plan_counts.get(key, 0) + 1
        if approx and key != "approx":
            self.plan_counts["approx"] = \
                self.plan_counts.get("approx", 0) + 1

    def _http_queries(self, req) -> tuple:
        """JSON feed behind the /queries browser view: per-plan serve
        counters, the sketch-serving contract counters, rollup tier
        state, fragment-cache hit rates — the query-planner sibling of
        the router's /api/topology."""
        from opentsdb_tpu.rollup.tier import res_label
        tier = getattr(self.tsdb, "rollups", None)
        rollup = None
        if tier is not None:
            rollup = {
                "ready": bool(tier.ready),
                "resolutions": [res_label(r) for r in tier.resolutions],
                "hits": {res_label(r): tier.hits.get(r, 0)
                         for r in tier.resolutions},
                "fallbacks": dict(tier.fallbacks),
                "sketch_alloc": {
                    res_label(r): {"digest_k": a[0], "moment_k": a[1],
                                   "hll_p": a[2]}
                    for r, a in sorted(tier.sketch_alloc.items())},
                "sketch_bytes": dict(tier.sketch_bytes),
                # Checkpoint fold sourcing: windows served from the
                # in-memory delta buffers vs full re-reads of spilled
                # rows (rollup/delta.py). A healthy append-mostly
                # daemon should see delta dominate.
                "folds": {"delta": tier.fold_delta,
                          "full": tier.fold_full},
            }
            if tier.delta is not None:
                rollup["delta"] = tier.delta.stats()
        sketch: dict = {}
        for name, kind, tkey, obj in METRICS._snapshot():
            if not name.startswith("sketch."):
                continue
            label = name[len("sketch."):]
            if tkey:
                label += "{" + ",".join(
                    f"{k}={v}" for k, v in tkey) + "}"
            if kind == "counter":
                sketch[label] = obj.value
            elif kind == "timer":
                sketch[label + ".count"] = obj.count
                sketch[label + ".p95"] = round(
                    obj.digest.percentile(95), 4)
        from opentsdb_tpu.parallel.compile import cache_info
        mesh_ex = getattr(self.executor, "mesh", None)
        expert_counts = {"serve": 0, "decline": 0}
        for name, kind, tkey, obj in METRICS._snapshot():
            if name == "mesh.expert.serve":
                expert_counts["serve"] += obj.value
            elif name == "mesh.expert.decline":
                expert_counts["decline"] += obj.value
        # The fused-on-compressed-blocks coverage line: what fraction
        # of fused-eligible batteries actually served fused, why the
        # rest declined, and how warm the device block cache is.
        fused = {"attempt": 0, "served": 0, "declines": {},
                 "devcache": {"hit": 0, "miss": 0, "evict": 0}}
        for name, kind, tkey, obj in METRICS._snapshot():
            if name == "compress.fused.attempt":
                fused["attempt"] += obj.value
            elif name == "compress.fused.served":
                fused["served"] += obj.value
            elif name == "compress.fused.decline":
                reason = dict(tkey).get("reason", "?")
                fused["declines"][reason] = \
                    fused["declines"].get(reason, 0) + obj.value
            elif name.startswith("compress.devcache."):
                fused["devcache"][name.rsplit(".", 1)[1]] = (
                    obj.read() if kind == "gauge" else obj.value)
        fused["coverage"] = (fused["served"] / fused["attempt"]
                             if fused["attempt"] else 0.0)
        # The ingest fast path (wire decode + WAL group commit):
        # batches-per-fsync is the coalescing win, wait_ms p95 the
        # latency each acked batch paid for its covering fsync.
        from opentsdb_tpu.server import wire
        ingest = {"group": {"batches": 0, "points": 0, "fsyncs": 0,
                            "waits": 0, "wait_ms_p95": 0.0},
                  "parse": {"count": 0, "p95_ms": 0.0},
                  # Which telnet decoder this process loaded: the
                  # native .so is an untracked build product.
                  "decoder": ("native" if wire.native_available()
                              else "python")}
        for name, kind, tkey, obj in METRICS._snapshot():
            if name == "wal.group.batches":
                ingest["group"]["batches"] += obj.value
            elif name == "wal.group.points":
                ingest["group"]["points"] += obj.value
            elif name == "wal.group.fsyncs":
                ingest["group"]["fsyncs"] += obj.value
            elif name == "wal.group.wait_ms" and kind == "timer":
                ingest["group"]["waits"] += obj.count
                ingest["group"]["wait_ms_p95"] = round(
                    obj.digest.percentile(95), 4)
            elif name == "ingest.parse" and kind == "timer":
                ingest["parse"]["count"] += obj.count
                ingest["parse"]["p95_ms"] = round(
                    obj.digest.percentile(95), 4)
        g = ingest["group"]
        g["batches_per_fsync"] = (g["batches"] / g["fsyncs"]
                                  if g["fsyncs"] else 0.0)
        body = {
            "uptime_s": int(time.time()) - self.start_time,
            "plans": dict(self.plan_counts),
            "fused": fused,
            "ingest": ingest,
            "sketch": sketch,
            "rollup": rollup,
            # The mesh execution plane's compile-cache line: devices
            # in the configured mesh, plan-cache size/hit/miss (a
            # steady dashboard should stop missing after warmup), and
            # the expert serve/decline counters.
            "mesh": {
                "devices": (int(mesh_ex.devices.size)
                            if mesh_ex is not None else 1),
                "expert_enabled": bool(self.expert_enabled),
                "compile_cache": cache_info(),
                "expert": expert_counts,
                # Serving-mesh shape (None outside --mesh-plane /
                # --devwindow-shards): plane membership + the sharded
                # resident hot set's live width/points/reshard stats.
                "serving": self._mesh_serving_info(),
            },
            "qcache": {"hit": self.executor.qcache_hits,
                       "miss": self.executor.qcache_misses,
                       "bypass": self.executor.qcache_bypasses},
            "admission": {
                "inflight": self.admission.inflight_queries,
                "degraded": self.admission.query_degraded,
                "shed_load": self.admission.query_shed_load,
            },
        }
        return (200, "application/json", json.dumps(body).encode(), {})

    def _http_queries_page(self, req) -> tuple:
        return (200, "text/html; charset=UTF-8",
                _QUERIES_HTML.encode(), {"Cache-Control": "no-cache"})

    # ------------------------------------------------------------------
    # Tenant cardinality control plane (opentsdb_tpu/tenant/)
    # ------------------------------------------------------------------

    def _http_tenants(self, req) -> tuple:
        """JSON feed behind the /tenants view: per-tenant series
        cardinality (exact or HLL tier, error declared), the limit
        governing each tenant, refusal counters, and the heavy-hitter
        summaries (top series by points, top metric prefixes by new
        series). Replicas and accounting-off daemons answer with
        enabled: false instead of 404 — the fleet shape is uniform."""
        acct = getattr(self.tsdb, "tenants", None)
        if acct is None:
            body = {"enabled": False,
                    "role": self.config.role}
            return (200, "application/json",
                    json.dumps(body).encode(), {})
        body = acct.snapshot_info(
            getattr(self.tsdb, "tenant_limits", None))
        body["enabled"] = True
        admission = self.admission
        body["admission"] = {
            "tenants": max(len(admission._ingest_buckets),
                           len(admission._query_buckets)),
            "evicted": admission.tenants_evicted,
            "collapsed": admission.tenants_collapsed,
        }
        return (200, "application/json", json.dumps(body).encode(), {})

    def _http_tenants_page(self, req) -> tuple:
        return (200, "text/html; charset=UTF-8",
                _TENANTS_HTML.encode(), {"Cache-Control": "no-cache"})

    async def _http_put(self, req) -> tuple:
        """HTTP ingest: a POST body of telnet-format ``put`` lines
        (no leading "put " required per line — both spellings
        accepted) or a JSON datapoint object/array (the reference
        ``/api/put`` shape), attributed to ``?tenant=``. Both bodies
        decode into the same columnar batch. The HTTP face of the
        tenant-limit contract: when every line was refused by the
        cardinality limiter the answer is 429 naming the limit;
        partial refusals report per-series errors in a 200 body so
        the caller can split permanent refusals from parse noise."""
        from opentsdb_tpu.server import wire
        if req.method != "POST":
            raise BadRequestError("POST a body of put lines", 405)
        if not req.body.strip():
            raise BadRequestError("empty body")
        tenant = req.q.get("tenant", "default")
        raw = req.body
        loop = asyncio.get_running_loop()
        # JSON bodies are unambiguous: no telnet put line can start
        # with '{' or '[' (the metric charset forbids both).
        if raw.lstrip()[:1] in (b"{", b"["):
            try:
                obj = json.loads(raw)
            except ValueError as e:
                raise BadRequestError(f"invalid json: {e}")
            try:
                batch = await loop.run_in_executor(
                    self._pool, wire.decode_json_puts, obj)
            except ValueError as e:
                raise BadRequestError(str(e))
        else:
            if not raw.endswith(b"\n"):
                raw += b"\n"
            # Accept bare "metric ts value tags" lines by prefixing
            # the telnet verb; lines already carrying it pass through.
            lines = []
            for ln in raw.split(b"\n"):
                if ln and not ln.startswith(b"put "):
                    ln = b"put " + ln
                lines.append(ln)
            raw = b"\n".join(lines)
            batch = await loop.run_in_executor(
                self._pool, wire.decode_puts, raw)
        npts = len(batch.sid)
        wait = self.admission.admit_ingest(npts, tenant) if npts \
            else 0.0
        if wait > 0:
            raise OverloadedError(
                f"over ingest quota for tenant {tenant!r}", wait,
                status=429)
        try:
            n, series_errors = await loop.run_in_executor(
                self._pool,
                functools.partial(wire.ingest_batch, self.tsdb, batch,
                                  tenant=tenant))
        finally:
            if npts:
                self.admission.ingest_done(npts)
        self.requests_put += n
        errors = list(batch.errors) + series_errors
        refused = [e for e in series_errors if "[tenant-limit]" in e]
        body = {"points": n, "errors": errors,
                "tenant": tenant,
                "refused_series": len(refused)}
        if refused and n == 0:
            # Everything the caller sent was a refused NEW series:
            # the declared 429 face, naming the limit — and no
            # Retry-After, because a retry cannot succeed until the
            # limit moves (this is not a throttle).
            limits = getattr(self.tsdb, "tenant_limits", None)
            body["error"] = refused[0]
            body["limit"] = (limits.limit_for(tenant)
                             if limits is not None else None)
            return (429, "application/json",
                    json.dumps(body).encode(), {})
        return 200, "application/json", json.dumps(body).encode(), {}

    def _http_metrics(self, req) -> tuple:
        """Prometheus text exposition: the metrics registry (typed —
        counters, gauges, timer summaries) merged with the classic
        /stats lines (untyped gauges, deduplicated against the
        registry's families) so one scrape covers both worlds."""
        body = METRICS.prometheus_text(extra_lines=self._collect_stats())
        return (200, "text/plain; version=0.0.4; charset=utf-8",
                body.encode(), {})

    def _http_traces(self, req) -> tuple:
        """The trace ring: the last Config.trace_ring traced queries
        (explicit ?trace=1 requests + every slow query), newest last.
        ``?slow=1`` filters to slow-flagged records."""
        records = self.trace_ring.snapshot()
        if "slow" in req.q and req.q["slow"] not in ("", "0"):
            records = [r for r in records if r.get("slow")]
        return 200, "application/json", json.dumps(records).encode(), {}

    def _http_dropcaches(self, req) -> tuple:
        self.tsdb.drop_caches()
        return 200, "text/plain", b"Caches dropped.\n", {}

    def _http_diediedie(self, req) -> tuple:
        self.request_shutdown()
        return (200, "text/html; charset=UTF-8",
                b"Cleaning up and exiting now.\n", {})

    def _http_favicon(self, req) -> tuple:
        return 404, "text/plain", b"", {}

    def _suggest(self, q) -> tuple:
        kind = q.get("type", "metrics")
        prefix = q.get("q", "")
        try:
            limit = int(q.get("max", "25"))
        except ValueError:
            raise BadRequestError("invalid 'max' parameter") from None
        if kind == "metrics":
            names = self.tsdb.metrics.suggest(prefix, limit)
        elif kind == "tagk":
            names = self.tsdb.tagk.suggest(prefix, limit)
        elif kind == "tagv":
            names = self.tsdb.tagv.suggest(prefix, limit)
        else:
            raise BadRequestError(f"Invalid 'type' parameter: {kind}")
        return 200, "application/json", json.dumps(names).encode(), {}

    # -- /q ------------------------------------------------------------

    async def _query(self, q, query_string: str, params) -> tuple:
        if "start" not in q:
            raise BadRequestError("Missing parameter: start")
        tz = q.get("tz")
        now = int(time.time())
        start = timeparse.parse_date(q["start"], tz=tz, now=now)
        end_param = q.get("end")
        end = timeparse.parse_date(end_param, tz=tz, now=now) \
            if end_param else now
        ms = params.get("m", [])
        if not ms:
            raise BadRequestError("Missing parameter: m")

        # Admission (serve/admission.py): a dry per-tenant bucket is
        # 429, the ladder's top is 503 — both via OverloadedError so
        # the Retry-After reaches the wire. DEGRADE takes a slot like
        # OK (the work still runs, just cheaper), released in the
        # finally below. Only VALID requests consume slots: the
        # parameter checks above stay outside.
        from opentsdb_tpu.serve import admission as _adm
        verdict, retry = self.admission.admit_query(
            q.get("tenant", "default"))
        if verdict == _adm.SHED_QUOTA:
            raise OverloadedError(
                f"query quota exceeded for tenant "
                f"{q.get('tenant', 'default')!r}", retry, status=429)
        if verdict == _adm.SHED_LOAD:
            raise OverloadedError(
                "shedding load: too many queries in flight", retry,
                status=503)
        # ?degrade=rollup-only: an overloaded ROUTER asking for the
        # cheap path on this hop — honor it exactly like the local
        # ladder's degraded step (trace stripped, rollup-only, tagged).
        degrade = (verdict == _adm.DEGRADE
                   or q.get("degrade") == "rollup-only")
        try:
            return await self._query_admitted(q, query_string, params,
                                              ms, start, end, degrade)
        finally:
            self.admission.query_done()

    async def _query_admitted(self, q, query_string: str, params, ms,
                              start: int, end: int,
                              degrade: bool) -> tuple:
        # Tracing: requested explicitly (?trace=1) or implied for
        # every query when a slow-query threshold is configured (the
        # span tree is what makes the slow-query record debuggable).
        # The per-hook cost is one global-int check when off and a
        # perf_counter pair per STAGE when on — never per point.
        # The degraded ladder step sheds trace work FIRST: span
        # bookkeeping is pure overhead when the goal is staying up.
        want_trace = (q.get("trace", "0") not in ("", "0")
                      and not degrade)
        slow_ms = float(self.config.slow_query_ms or 0)
        # Ambient 1-in-N trace sampling (Config.trace_sample_n): every
        # Nth query is traced into the ring even when nobody asked and
        # nothing is slow, so the traces BETWEEN incidents exist when
        # a slow-query record needs a baseline to compare against.
        # Sampled traces keep normal caching (a disk-cache hit simply
        # isn't traced — the baseline is of executed queries).
        sample_n = int(self.config.trace_sample_n or 0)
        sampled = False
        if sample_n > 0 and not degrade and not want_trace:
            self._trace_sample_seq += 1
            sampled = self._trace_sample_seq % sample_n == 0
        do_trace = want_trace or sampled or (slow_ms > 0
                                             and not degrade)
        # One trace_id a request: the router's fan-out id when this is
        # a hop (trace_parent: the hop's records on this replica then
        # carry the SAME id as the router's assembled tree, and
        # /api/traces correlates across processes), else minted here,
        # once — every sub-query's tree, ring record and profiler
        # annotation of the request shares it.
        trace_id = ((q.get("trace_parent") or obs_trace.new_trace_id())
                    if do_trace else None)
        # The result tag for anything less than full service ("stale",
        # "rollup-only", or both): evaluated once per request, echoed
        # per-result in JSON and as X-Tsd-Degraded so the router can
        # propagate it without parsing bodies. Degraded answers bypass
        # the disk cache both ways — caching one would serve it after
        # recovery, and a cached full answer carries no tag.
        degraded = self._degraded_reason(degrade)
        # Approximate serving opt-in (sketch/serving.py): ``approx=1``
        # allows sketch-served percentile downsamples at any reported
        # bound; ``max_error=X`` (relative half-width) implies the
        # opt-in AND caps it — a sketch answer whose bound exceeds X
        # falls back to the exact path. The ladder's degraded step
        # implies approx for percentile queries (bounded-error
        # degradation) under Config.degrade_max_error.
        from opentsdb_tpu.sketch.serving import ApproxSpec
        max_error = _parse_max_error(q)
        approx_on = (q.get("approx", "0") not in ("", "0")
                     or max_error is not None)
        if degrade and max_error is None:
            cfg_budget = float(self.config.degrade_max_error or 0)
            max_error = cfg_budget if cfg_budget > 0 else None
        aspec = ApproxSpec(approx_on, max_error)
        # An explicitly traced request bypasses the /q disk cache both
        # ways: a cached body carries no trace, and a trace of a disk
        # read would claim the query cost nothing. Approx opt-in does
        # NOT bypass: the cache key is the md5 of the full query string
        # (approx=1/max_error included), so an exact caller can never
        # land on an approx slot, and X-Tsd-Approx survives hits via
        # the .meta sidecar like the drag-zoom headers.
        cache_path = (None if want_trace or degraded
                      else self._cache_path(query_string, q))
        now = int(time.time())
        if cache_path and self._cache_fresh(cache_path, q, end, now):
            with open(cache_path, "rb") as f:
                body = f.read()
            # A PNG under 21 bytes (minimum possible PNG) cannot be
            # valid, and a 0-byte .json cannot either (an empty JSON
            # result serializes as b"[]") — regenerate instead of
            # serving garbage (reference GraphHandler.isDiskCacheHit
            # :367-374; our tmp+rename writes make this
            # near-impossible, but an operator touching files in the
            # cachedir shouldn't wedge a graph). Zero-byte .txt bodies
            # are NOT rejected: an empty ascii result is the
            # negative-cache hit — a query known to plot 0 points is
            # re-served from disk without re-running the executor
            # (reference :399-419).
            corrupt = ((cache_path.endswith(".png") and len(body) < 21)
                       or (cache_path.endswith(".json")
                           and len(body) == 0))
            if not corrupt:
                self.cache_hits += 1
                ctype = ("image/png" if cache_path.endswith(".png")
                         else "text/plain" if cache_path.endswith(".txt")
                         else "application/json")
                extra = {}
                try:  # drag-zoom headers survive cache hits via a sidecar
                    with open(cache_path + ".meta") as f:
                        extra = json.load(f)
                except (OSError, ValueError):
                    pass
                return 200, ctype, body, extra
        self.cache_misses += 1

        loop = asyncio.get_running_loop()
        results = []
        # Per-metric render options: o= params pair up positionally with
        # m= params (reference GraphHandler.doGraph :155-187).
        os_ = params.get("o", [])
        result_opts: list[str] = []
        result_plans: list[str] = []
        result_cached: list[bool] = []
        result_traces: list[dict | None] = []
        result_approx: list[dict | None] = []
        # Expert-parallel batch serving (parallel/expert.py, behind
        # Config.expert_parallel + a mesh): a mixed multi-sub-query
        # dashboard packs into expert buckets and runs in ONE mesh
        # dispatch. Attempted only on the full-service path (tracing,
        # the degrade ladder, and approx contracts keep their serial
        # semantics); a decline is DECLARED — per-result
        # plan: "expert-decline" + the mesh.expert.decline counter —
        # and the batch serves serially, answers unchanged.
        expert_label = None
        expert_specs: list | None = None
        if (self.expert_enabled and len(ms) >= 2 and not do_trace
                and not degrade and not aspec.enabled):
            specs = []
            for m in ms:
                parsed = parse_m(m)
                specs.append(QuerySpec(
                    metric=parsed.metric, tags=parsed.tags,
                    aggregator=parsed.aggregator, rate=parsed.rate,
                    downsample=parsed.downsample,
                    counter=parsed.counter,
                    counter_max=parsed.counter_max,
                    reset_value=parsed.reset_value))
            per_spec, reason = await loop.run_in_executor(
                self._pool,
                functools.partial(self.executor.run_expert_batch,
                                  specs, start, end))
            if per_spec is not None:
                # Counters bump PER SUB-QUERY, the serial loop's unit —
                # the /queries plans table must not mix units across a
                # mesh rollout.
                METRICS.counter("mesh.expert.serve").inc(len(ms))
                for _ in ms:
                    self._note_plan("expert")
                expert_label = "expert"
                for mi, rs in enumerate(per_spec):
                    results.extend(rs)
                    result_opts.extend(
                        [os_[mi] if mi < len(os_) else ""] * len(rs))
                    result_plans.extend(["expert"] * len(rs))
                    result_cached.extend([False] * len(rs))
                    result_traces.extend([None] * len(rs))
                    result_approx.extend([None] * len(rs))
                ms = ()
            else:
                METRICS.counter("mesh.expert.decline",
                                {"reason": reason}).inc(len(ms))
                self.plan_counts["expert-decline"] = \
                    self.plan_counts.get("expert-decline", 0) + len(ms)
                expert_label = "expert-decline"
                # The serial fallback reuses the parsed specs — a
                # declined batch must not pay the parse twice.
                expert_specs = specs
        for mi, m in enumerate(ms):
            if expert_specs is not None:
                spec = expert_specs[mi]
            else:
                parsed = parse_m(m)
                spec = QuerySpec(
                    metric=parsed.metric, tags=parsed.tags,
                    aggregator=parsed.aggregator, rate=parsed.rate,
                    downsample=parsed.downsample,
                    counter=parsed.counter,
                    counter_max=parsed.counter_max,
                    reset_value=parsed.reset_value)
            # Planner choice for this sub-query ("raw", "resident", or
            # a rollup resolution label) — surfaced in JSON metadata.
            # Returned with the results: reading it back off the shared
            # executor after the pool hop could pick up a CONCURRENT
            # request's label.
            trace = (obs_trace.Trace(m, trace_id=trace_id)
                     if do_trace else None)
            run = functools.partial(self.executor.run_approx,
                                    spec, start, end, trace,
                                    rollup_only=degrade, approx=aspec)
            if trace is None:
                rs, plan, cached, ainfo = await loop.run_in_executor(
                    self._pool, run)
            else:
                # The sub-query's two waits outside its root span, as
                # children of it: for a pool thread to begin it
                # (http.q.queue) and, once that thread has returned,
                # for this coroutine to run again (http.q.resume).
                hops = obs_trace.Hops()
                rs, plan, cached, ainfo = await loop.run_in_executor(
                    self._pool, hops.run, run)
                hops.attach(trace.root, "http.q")
            ajson = (ainfo.as_json() if hasattr(ainfo, "as_json")
                     else ainfo)
            self._note_plan(plan, approx=ajson is not None)
            tdict = None
            if trace is not None:
                rec = make_record(
                    m, trace, plan, cached, slow_ms,
                    getattr(self.tsdb.store, "shard_count", 1) or 1,
                    bool(getattr(self.tsdb.store, "read_only", False)))
                tdict = rec["trace"]
                # The ring holds what an operator would want to SEE at
                # /api/traces: every explicit trace, every slow query,
                # and the 1-in-N ambient samples (flagged, so ?slow=1
                # still filters to incidents). Threshold-only tracing
                # of fast queries stays out — it would flush the ring
                # with noise between incidents.
                if sampled:
                    rec["sampled"] = True
                if want_trace or sampled or rec["slow"]:
                    self.trace_ring.add(rec)
                if rec["slow"]:
                    log_slow(rec)
            results.extend(rs)
            result_opts.extend([os_[mi] if mi < len(os_) else ""] * len(rs))
            result_plans.extend([plan] * len(rs))
            result_cached.extend([cached] * len(rs))
            # One tree a sub-query, on its first result: repeated on
            # every result, a 4,000-group answer would carry (and the
            # event loop encode) 4,000 copies of it.
            result_traces.extend([tdict] + [None] * (len(rs) - 1)
                                 if rs else [])
            result_approx.extend([ajson] * len(rs))

        extra: dict = {}
        if degraded:
            extra["X-Tsd-Degraded"] = degraded
        approx_served = [a for a in result_approx if a]
        if approx_served:
            # Declared approximation, header form (the router
            # propagates it like X-Tsd-Degraded): the kinds involved
            # plus the worst reported relative bound (when numeric).
            kinds = sorted({a.get("kind", "?") for a in approx_served})
            rels = [a.get("rel_error") for a in approx_served
                    if isinstance(a.get("rel_error"), (int, float))]
            tagv = ",".join(kinds)
            if rels:
                tagv += f";rel_error={max(rels):.6g}"
            extra["X-Tsd-Approx"] = tagv
        if "ascii" in q:
            body = self._ascii_output(results).encode()
            ctype = "text/plain"
        elif "json" in q:
            # On the event-loop thread and outside every span, written
            # in fragments from the results' arrays (server/qjson.py):
            # a wide answer is thousands of short calls between which
            # the GIL can pass to the query workers, and still the
            # loop's largest piece of work.
            with obs_trace.timed("http.q.encode"):
                body = qjson.encode(
                    self._json_output(
                        results, result_plans, result_cached,
                        result_traces if want_trace else None,
                        degraded=degraded,
                        approx=result_approx,
                        expert=expert_label))
            _M_Q_BYTES.inc(len(body))
            ctype = "application/json"
        else:
            t0 = time.time()
            body, extra = await loop.run_in_executor(
                self._pool, self._render_png, results, start, end, q,
                result_opts)
            self.graph_latency.add((time.time() - t0) * 1000)
            ctype = "image/png"
        if cache_path:
            tmp = cache_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(body)
            os.replace(tmp, cache_path)
            if extra:
                with open(cache_path + ".meta.tmp", "w") as f:
                    json.dump(extra, f)
                os.replace(cache_path + ".meta.tmp", cache_path + ".meta")
        return 200, ctype, body, extra

    def _cache_path(self, query_string: str, q) -> str | None:
        if self.config.cachedir is None or "nocache" in q:
            return None
        suffix = (".txt" if "ascii" in q
                  else ".json" if "json" in q else ".png")
        h = hashlib.md5(query_string.encode()).hexdigest()
        return os.path.join(self.config.cachedir, h + suffix)

    def _cache_fresh(self, path: str, q, end: int, now: int) -> bool:
        """Staleness rules following reference computeMaxAge (:223-244):
        queries ending >1d in the past cache long; recent/relative
        queries cache briefly."""
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            return False
        if end < now - 86400:
            max_age = 86400
        elif timeparse.is_relative_date(q.get("end")):
            max_age = 60
        else:
            max_age = 300
        if (self.tailer is not None
                and self.config.max_staleness_ms > 0):
            # Staleness-contract replicas: a disk-cache hit adds its
            # age to the answer's staleness, so cap it at the contract
            # bound — the cache can never make a fresh replica serve
            # an answer older than it promises.
            max_age = min(max_age,
                          self.config.max_staleness_ms / 1000.0)
        return (now - mtime) < max_age

    @staticmethod
    def _fmt_value(v: float) -> str:
        return str(int(v)) if float(v).is_integer() else repr(float(v))

    def _ascii_output(self, results) -> str:
        """One "metric timestamp value tags" line per point (reference
        GraphHandler.respondAsciiQuery :770-818) — re-importable."""
        out = []
        for r in results:
            tag_str = " ".join(
                f"{k}={v}" for k, v in sorted(r.tags.items()))
            for ts, v in zip(r.timestamps, r.values):
                line = f"{r.metric} {int(ts)} {self._fmt_value(v)}"
                out.append(line + (" " + tag_str if tag_str else ""))
        return "\n".join(out) + ("\n" if out else "")

    def _json_output(self, results, plans=None, cached=None,
                     traces=None, degraded=None, approx=None,
                     expert=None):
        """One entry a result, for qjson.encode: six keys, ``dps`` a
        view of the result's arrays (``items()`` as a dict's), and the
        keys below only where there is something to declare."""
        out = [{
            "metric": r.metric,
            "tags": r.tags,
            "aggregateTags": r.aggregated_tags,
            "rollup": plan,
            # Fragment-cache provenance: True iff this sub-query's
            # whole range served from warm decoded fragments.
            "cached": bool(hit),
            "dps": qjson.Dps(r.timestamps, r.values),
        } for r, plan, hit in zip(
            results,
            itertools.chain(plans or (), itertools.repeat("raw")),
            itertools.chain(cached or (), itertools.repeat(False)))]
        if expert:
            # Expert-path provenance, DECLARED either way: "expert"
            # when the batch served through the mesh's expert buckets,
            # "expert-decline" when it was eligible for the attempt
            # but fell off the path (ragged shapes, rate, no-lerp
            # aggs) and served serially — the TSINT fused-decline
            # discipline: falling back is fine, silently is not.
            for ent in out:
                ent["plan"] = expert
        if degraded:
            # Anything less than full service is DECLARED per result:
            # "stale" (replica lag beyond the contract) and/or
            # "rollup-only" (load shedding omitted raw stitching).
            for ent in out:
                ent["degraded"] = degraded
        if approx and any(approx):
            # The error contract: a sketch-served answer carries its
            # kind + reported bound per result ("approx": {"kind":
            # "tdigest"|"moment"|"rollup-stale", "error": ...}).
            for ent, a in zip(out, approx):
                if a:
                    ent["approx"] = a
        if traces is not None:
            # ?trace=1 only: the per-sub-query span tree, inline.
            for ent, t in zip(out, traces):
                if t is not None:
                    ent["trace"] = t
        return out

    def _render_png(self, results, start, end, q,
                    result_opts=None) -> tuple[bytes, dict]:
        plot = Plot(start, end)
        if "wxh" in q:
            w, _, h = q["wxh"].partition("x")
            try:
                plot.set_dimensions(int(w), int(h))
            except ValueError:
                raise BadRequestError(
                    f"invalid wxh parameter: {q['wxh']}") from None
        plot.set_params({k: v for k, v in q.items() if k in (
            "title", "ylabel", "yrange", "ylog", "key", "nokey",
            "bgcolor", "fgcolor", "y2label", "y2range", "y2log",
            "smooth")})
        for i, r in enumerate(results):
            label = r.metric
            if r.tags:
                label += "{" + ",".join(
                    f"{k}={v}" for k, v in sorted(r.tags.items())) + "}"
            plot.add(label, r.timestamps, r.values,
                     result_opts[i] if result_opts else "")
        body = plot.render()
        # Pixel->time mapping headers for the web UI's drag-zoom: the
        # axes bbox in PNG pixels plus the plotted time range. (The GWT
        # client hardcodes gnuplot's margins for this; we report the
        # real bbox instead.)
        hdrs = {"X-Time-Range": f"{int(start)},{int(end)}"}
        if plot.plot_area is not None:
            hdrs["X-Plot-Area"] = ",".join(map(str, plot.plot_area))
        return body, hdrs

    async def _distinct(self, q) -> tuple:
        """Cardinality extension: distinct values of one tag key.

        Without ``start`` (or with ``stream`` set), answered from the
        streaming per-(metric, tagk) HLL registers updated at ingest —
        all-time, no storage rescan, staleness bounded by the sketch
        flush threshold. With a time range and no tag filter, the
        rollup tier serves an exact count from record presence
        (O(windows); executor.sketch_distinct falls back to the exact
        scan when the tier can't cover the range); with a tag filter
        the scan-based path runs.
        """
        for req in ("metric", "tagk"):
            if req not in q:
                raise BadRequestError(f"Missing parameter: {req}")
        loop = asyncio.get_running_loop()
        if "stream" in q or "start" not in q:
            if "end" in q and "stream" not in q:
                # Mirror /sketch: end= alone must not silently answer
                # the all-time streaming estimate for a ranged intent.
                raise BadRequestError(
                    "distinct range needs start= (end= alone would "
                    "silently answer all-time)")
            n = await loop.run_in_executor(
                self._pool, self.executor.sketch_distinct, q["metric"],
                q["tagk"])
            if n is None:
                raise BadRequestError(
                    f"no streaming sketch state for metric {q['metric']}"
                    f" / tagk {q['tagk']} (pass start= for a scan)")
            # The streaming estimate is an HLL — declare it under the
            # error contract like every other approximate answer.
            from opentsdb_tpu.sketch.bounds import hll_error
            err = hll_error(self.config.sketch_hll_p, n)
            body = json.dumps({
                "metric": q["metric"], "tagk": q["tagk"], "distinct": n,
                "source": "stream",
                "approx": {"kind": "hll", "error": err}}).encode()
            return (200, "application/json", body,
                    {"X-Tsd-Approx": f"hll;error={err:.6g}"})
        now = int(time.time())
        start = timeparse.parse_date(q["start"], now=now)
        end = timeparse.parse_date(q["end"], now=now) if "end" in q else now
        tag_map: dict[str, str] = {}
        if "tags" in q and q["tags"]:
            for t in q["tags"].split(","):
                tags_mod.parse(tag_map, t)
        if not tag_map:
            # What actually answered ("rollup" or the exact-scan
            # fallback), returned alongside the count so concurrent
            # /distinct requests can't mislabel each other.
            n, source = await loop.run_in_executor(
                self._pool, self.executor.sketch_distinct_with_source,
                q["metric"], q["tagk"], start, end)
        else:
            n = await loop.run_in_executor(
                self._pool, self.executor.distinct_tagv, q["metric"],
                tag_map, q["tagk"], start, end)
            source = "scan"
        body = json.dumps({"metric": q["metric"], "tagk": q["tagk"],
                           "distinct": n, "source": source}).encode()
        return 200, "application/json", body, {}

    async def _sketch(self, q) -> tuple:
        """Streaming-quantile extension: all-time percentiles of the
        matching series' merged t-digests, answered from device-resident
        sketch state with no storage rescan (the Histogram.java
        streaming-stats replacement). Params: ``m=metric{tag=v,...}``
        (no aggregator prefix) and ``q=p50,p99`` (or 0.5,0.99).
        """
        if "m" not in q:
            raise BadRequestError("Missing parameter: m")
        expr = q["m"]
        tag_map: dict[str, str] = {}
        try:
            metric = tags_mod.parse_with_metric(expr, tag_map)
        except ValueError as e:
            raise BadRequestError(str(e)) from None
        qs = []
        for part in q.get("q", "p50,p95,p99").split(","):
            part = part.strip()
            try:
                if part.startswith("p") and part[1:].isdigit():
                    d = part[1:]
                    # p5 -> 0.05, p99 -> 0.99 (whole percent); three or
                    # more digits use the aggregator-registry spelling
                    # where digits follow the decimal point: p999 ->
                    # 0.999 (so "p100" is 0.100, not the maximum — ask
                    # for q=1.0 explicitly).
                    qs.append(int(d) / 100 if len(d) <= 2
                              else int(d) / 10 ** len(d))
                else:
                    qs.append(float(part))
            except ValueError:
                raise BadRequestError(
                    f"bad quantile: {part}") from None
            if not 0.0 <= qs[-1] <= 1.0:
                raise BadRequestError(f"quantile out of range: {part}")
        # Optional time range: served from the rollup tier's per-window
        # digest columns (exact raw fallback) instead of the all-time
        # streaming digests.
        start = end = None
        if "start" in q:
            now = int(time.time())
            start = timeparse.parse_date(q["start"], now=now)
            end = (timeparse.parse_date(q["end"], now=now)
                   if "end" in q else now)
        elif "end" in q:
            raise BadRequestError(
                "sketch range needs start= (end= alone would silently "
                "answer all-time)")
        max_error = _parse_max_error(q)
        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(
            self._pool, self.executor.sketch_quantiles, metric, tag_map,
            qs, start, end, max_error)
        hdrs = {}
        ap = out.get("approx") if isinstance(out, dict) else None
        if ap:
            hdrs["X-Tsd-Approx"] = (
                f"{ap.get('kind', '?')}"
                f";rel_error={ap.get('rel_error', 0):.6g}")
        return 200, "application/json", json.dumps(out).encode(), hdrs

    async def _forecast(self, q, params) -> tuple:
        """Model extension: Holt-Winters / EWMA forecasts + anomaly
        bands over a query's result series (no reference analog — the
        predictive layer on top of the /q pipeline). Params: start, end,
        m= (must include a downsample to define the model's bucket
        grid), horizon (future buckets, default 10), season (buckets,
        default 0), alpha/beta/gamma, nsigma (default 3).
        """
        import numpy as np

        if "start" not in q:
            raise BadRequestError("Missing parameter: start")
        now = int(time.time())
        tz = q.get("tz")
        start = timeparse.parse_date(q["start"], tz=tz, now=now)
        end = timeparse.parse_date(q["end"], tz=tz, now=now) \
            if q.get("end") else now
        ms = params.get("m", [])
        if not ms:
            raise BadRequestError("Missing parameter: m")

        def num(name, default, lo, hi, as_int=False):
            try:
                v = float(q.get(name, default))
                if as_int:
                    v = int(v)
            except (ValueError, OverflowError):
                raise BadRequestError(
                    f"invalid '{name}' parameter") from None
            if not (lo <= v <= hi):
                raise BadRequestError(
                    f"'{name}' out of range [{lo}, {hi}]")
            return v

        # season/horizon bound both memory (they size device arrays) and
        # XLA recompiles (they're static shapes).
        horizon = num("horizon", 10, 1, 10000, as_int=True)
        season = num("season", 0, 0, 10000, as_int=True)
        alpha = num("alpha", 0.3, 0.0, 1.0)
        beta = num("beta", 0.1, 0.0, 1.0)
        gamma = num("gamma", 0.1, 0.0, 1.0)
        nsigma = num("nsigma", 3.0, 0.1, 1000.0)
        model = q.get("model", "hw")
        if model not in ("hw", "ewma"):
            raise BadRequestError(f"unknown model: {model}")

        loop = asyncio.get_running_loop()
        results = []
        interval = None
        for m in ms:
            parsed = parse_m(m)
            if not parsed.downsample:
                raise BadRequestError(
                    "forecast queries need a downsample interval "
                    "(e.g. m=sum:5m-avg:metric) to define the model grid")
            if interval is None:
                interval = parsed.downsample[0]
            elif interval != parsed.downsample[0]:
                raise BadRequestError(
                    "all m= specs must share one downsample interval")
            spec = QuerySpec(
                metric=parsed.metric, tags=parsed.tags,
                aggregator=parsed.aggregator, rate=parsed.rate,
                downsample=parsed.downsample, counter=parsed.counter,
                counter_max=parsed.counter_max,
                reset_value=parsed.reset_value)
            rs = await loop.run_in_executor(
                self._pool, self.executor.run, spec, start, end)
            results.extend(rs)

        def compute():
            from opentsdb_tpu.models import (anomaly_bands, ewma,
                                             hw_forecast)
            from opentsdb_tpu.query.grid import _pad_size

            grid0 = start - start % interval
            T = max((end - grid0) // interval + 1, 1)
            S = max(len(results), 1)
            # Pad the model shapes to powers of two: masked tail buckets
            # and empty padded series carry the scan state through
            # unchanged, so results are identical — but every distinct
            # query span stops triggering an XLA recompile of the
            # smoothing scan (the same _pad_size discipline as /q).
            Tp, Sp = _pad_size(T), _pad_size(S)
            vals = np.zeros((Sp, Tp), np.float32)
            mask = np.zeros((Sp, Tp), bool)
            for i, r in enumerate(results):
                idx = ((np.asarray(r.timestamps) - grid0) //
                       interval).astype(int)
                ok = (idx >= 0) & (idx < T)
                vals[i, idx[ok]] = np.asarray(r.values)[ok]
                mask[i, idx[ok]] = True
            if model == "ewma":
                fitted = np.asarray(ewma(vals, mask, alpha))[:S, :T]
                level = fitted[:, -1]
                fc = np.repeat(level[:, None], horizon, axis=1)
                bands = None
            else:
                bands = {k: np.asarray(v) for k, v in anomaly_bands(
                    vals, mask, alpha, beta, gamma, season,
                    nsigma).items()}
                fc = np.asarray(hw_forecast(
                    bands["level"], bands["trend"], bands["seasonal"],
                    horizon=_pad_size(horizon), season_length=season,
                    t_fitted=T))[:S, :horizon]
                grid_keys = ("fitted", "upper", "lower", "sigma",
                             "anomaly")
                bands = {k: (v[:S, :T] if k in grid_keys else v[:S])
                         for k, v in bands.items()}
                fitted = bands["fitted"]
            vals, mask = vals[:S, :T], mask[:S, :T]
            future_ts = grid0 + (T + np.arange(horizon)) * interval
            grid_ts = grid0 + np.arange(T) * interval

            if "png" in q:
                from opentsdb_tpu.graph.plot import render_forecast_png

                rseries = []
                for i, r in enumerate(results):
                    label = r.metric + (
                        "{" + ",".join(f"{k}={v}" for k, v in
                                       sorted(r.tags.items())) + "}"
                        if r.tags else "")
                    mk = mask[i]
                    anom = (bands["anomaly"][i] if bands is not None
                            else np.zeros(T, bool))
                    rseries.append({
                        "label": label,
                        "obs_ts": grid_ts[mk], "obs": vals[i][mk],
                        "fit_ts": grid_ts[mk], "fit": fitted[i][mk],
                        "upper": (bands["upper"][i][mk]
                                  if bands is not None else None),
                        "lower": (bands["lower"][i][mk]
                                  if bands is not None else None),
                        "fc_ts": future_ts, "fc": fc[i],
                        "anom_ts": grid_ts[anom], "anom": vals[i][anom],
                    })
                width, height = 1024, 768
                if "wxh" in q:
                    ws, _, hs = q["wxh"].partition("x")
                    try:
                        width, height = int(ws), int(hs)
                    except ValueError:
                        raise BadRequestError(
                            f"invalid wxh parameter: {q['wxh']}") \
                            from None
                    if not (8 <= width <= 4096 and 8 <= height <= 4096):
                        raise BadRequestError(
                            f"invalid dimensions {q['wxh']}")
                return render_forecast_png(
                    rseries, start, int(future_ts[-1]),
                    width=width, height=height, title=q.get("title"),
                    params={k: v for k, v in q.items()
                            if k in ("yrange", "ylog", "nokey")}), \
                    "image/png"

            out = []
            for i, r in enumerate(results):
                entry = {
                    "metric": r.metric, "tags": r.tags,
                    "model": model,
                    "fitted": {str(int(t)): float(v) for t, v, mk in
                               zip(grid_ts, fitted[i], mask[i]) if mk},
                    "forecast": {str(int(t)): float(v) for t, v in
                                 zip(future_ts, fc[i])},
                }
                if bands is not None:
                    entry["anomalies"] = [
                        int(t) for t, a in zip(grid_ts, bands["anomaly"][i])
                        if a]
                    entry["upper"] = {
                        str(int(t)): float(v) for t, v, mk in
                        zip(grid_ts, bands["upper"][i], mask[i]) if mk}
                    entry["lower"] = {
                        str(int(t)): float(v) for t, v, mk in
                        zip(grid_ts, bands["lower"][i], mask[i]) if mk}
                out.append(entry)
            return json.dumps(out).encode(), "application/json"

        body, ctype = await loop.run_in_executor(self._pool, compute)
        return 200, ctype, body, {}

    # -- static files / home page --------------------------------------

    # Packaged web UI (the GWT-client replacement): used when no
    # --staticroot is configured, or as a fallback below a custom root.
    _PACKAGED_STATIC = os.path.join(os.path.dirname(__file__), "static")

    def _static_file(self, rel: str) -> tuple:
        if ".." in rel:
            raise BadRequestError("Malformed path", 404)
        rel = rel or "index.html"
        path = None
        for root in (self.config.staticroot, self._PACKAGED_STATIC):
            if root is None:
                continue
            cand = os.path.join(root, rel)
            if os.path.isfile(cand):
                path = cand
                break
        if path is None:
            return 404, "text/plain", b"File Not Found\n", {}
        with open(path, "rb") as f:
            body = f.read()
        ext = os.path.splitext(path)[1]
        ctype = _CONTENT_TYPES.get(ext, "application/octet-stream")
        if path.startswith(self._PACKAGED_STATIC):
            # Packaged UI files aren't content-hashed: an upgrade must
            # reach browsers. Only operator staticroot assets (hashed GWT
            # style) earn the year-long header (reference :30-54).
            hdrs = {"Cache-Control": "no-cache"}
        else:
            hdrs = {"Cache-Control": "max-age=31536000"}
        return 200, ctype, body, hdrs

    def _homepage(self) -> str:
        return f"""<html><head><title>TSD (opentsdb_tpu)</title></head>
<body><h1>opentsdb_tpu {__version__}</h1>
<p>A TPU-native time-series database.</p>
<ul>
<li><a href="/aggregators">/aggregators</a></li>
<li>/q?start=1h-ago&amp;m=sum:metric&#123;tag=value&#125;&amp;ascii</li>
<li>/suggest?type=metrics&amp;q=prefix</li>
<li><a href="/stats">/stats</a></li>
<li><a href="/tenants">/tenants</a></li>
<li><a href="/metrics">/metrics</a></li>
<li><a href="/api/traces">/api/traces</a></li>
<li><a href="/version">/version</a></li>
<li><a href="/logs">/logs</a></li>
</ul></body></html>"""

    # -- stats ----------------------------------------------------------

    def _version_text(self) -> str:
        return version_string()

    def _collect_stats(self) -> list[str]:
        c = StatsCollector("tsd")
        c.record("connectionmgr.connections", self.connections_established)
        c.record("connectionmgr.exceptions", self.exceptions_caught)
        c.record("rpc.received", self.telnet_rpcs, "type=telnet")
        c.record("rpc.received", self.http_rpcs, "type=http")
        c.record("rpc.errors", self.rpcs_unknown, "type=unknown")
        c.record("rpc.errors", self.hbase_errors_put, "type=hbase_errors")
        c.record("rpc.errors", self.illegal_arguments_put,
                 "type=illegal_arguments")
        c.record("rpc.errors", self.unknown_metrics_put,
                 "type=unknown_metrics")
        c.record("rpc.requests", self.requests_put, "type=put")
        c.record("http.latency", self.http_latency, "type=all")
        c.record("http.latency", self.graph_latency, "type=graph")
        c.record("rpc.latency", self.put_latency, "type=put")
        c.record("scan.latency", self.executor.scan_latency, "type=query")
        c.record("http.graph.requests", self.cache_hits, "cache=hit")
        c.record("http.graph.requests", self.cache_misses, "cache=miss")
        c.record("qcache.hit", self.executor.qcache_hits)
        c.record("qcache.miss", self.executor.qcache_misses)
        c.record("qcache.bypass", self.executor.qcache_bypasses)
        for plan, n in sorted(self.plan_counts.items()):
            c.record("query.plan", n, f"plan={plan}")
        from opentsdb_tpu.fault import faultpoints as _fp
        fstat = _fp.status()
        c.record("fault.sites_armed", len(fstat["armed"]))
        c.record("fault.fired", sum(fstat["fired"].values()))
        for site, n in sorted(fstat["fired"].items()):
            c.record("fault.fired_site", n, f"site={site}")
        c.record("uptime", int(time.time()) - self.start_time)
        c.record("uptime_s", int(time.time()) - self.start_time)
        rss = read_rss_bytes()
        if rss:
            c.record("process.rss_bytes", rss)
        # Every thread's CPU time: its rise over an interval, beside the
        # interval, is how many cores the process kept busy.
        c.record("process.cpu_ms", round(time.process_time() * 1000.0, 3))
        c.record("traces.recorded", self.trace_ring.recorded)
        c.record("traces.slow", self.trace_ring.slow)
        # Serve tier: the staleness contract (replica role) and the
        # admission/shedding counters — self-monitoring ingests these
        # as tsd.replica.* / tsd.admission.* series, which is what
        # `tsdb check -m tsd.replica.lag_ms ...` alerts on.
        if self.tailer is not None:
            self.tailer.collect_stats(c)
        self.admission.collect_stats(c)
        c.record("selfmon.cycles", self.selfmon.cycles)
        c.record("selfmon.points", self.selfmon.points)
        c.record("selfmon.errors", self.selfmon.errors)
        self.tsdb.collect_stats(c)
        # Engine instruments (obs/registry.py): WAL append/fsync,
        # checkpoint phases, per-shard spills, rollup folds, fsck,
        # per-handler latency — timers expand to p50/p95/p99 +
        # .count/.sum_ms lines.
        METRICS.collect(c)
        return c.lines


# ---------------------------------------------------------------------------
# /queries: the query-planner dashboard — per-plan serve counters
# (raw / resident / fused / rollup / approx), the sketch-serving
# error-contract counters, the rollup tier's per-resolution sketch
# allocation, fragment-cache rates. The /topology pattern one layer
# down: one self-contained page over the /api/queries JSON feed,
# served from memory, auto-refreshing.
# ---------------------------------------------------------------------------

_TENANTS_HTML = """<!doctype html>
<html><head><meta charset="utf-8"><title>tsd tenants</title>
<style>
 body{font:13px/1.45 system-ui,sans-serif;margin:1.2em;background:#fafafa;
      color:#222}
 h1{font-size:1.2em;margin:0 0 .2em}
 h2{font-size:1em;margin:1.2em 0 .3em}
 table{border-collapse:collapse;background:#fff;min-width:36em}
 th,td{border:1px solid #ddd;padding:.25em .6em;text-align:left;
       font-variant-numeric:tabular-nums}
 th{background:#f0f0f0;font-weight:600}
 .ok{color:#0a7d32}.bad{color:#c0392b}.warn{color:#b8860b}
 #meta{color:#666;font-size:.9em;margin-bottom:.8em}
 .pill{display:inline-block;padding:0 .5em;border-radius:.8em;
       background:#eee;margin-right:.4em}
 small{color:#888}
</style></head><body>
<h1>Tenant cardinality</h1>
<div id="meta">loading /api/tenants&hellip;</div>
<div id="tenants"></div><div id="hh"></div><div id="adm"></div>
<script>
function esc(v){return String(v).replace(/&/g,"&amp;")
  .replace(/</g,"&lt;").replace(/>/g,"&gt;");}
function fmt(v){return v===null||v===undefined?"&mdash;":esc(v);}
function table(title, heads, rows){
  var h="<h2>"+title+"</h2><table><tr>"+heads.map(
    function(x){return "<th>"+x+"</th>";}).join("")+"</tr>";
  h+=rows.map(function(r){return "<tr>"+r.map(
    function(c){return "<td>"+c+"</td>";}).join("")+"</tr>";}).join("");
  return h+"</table>";
}
function pills(title, obj){
  return "<h2>"+title+"</h2>"+Object.keys(obj).sort().map(function(k){
    return "<span class='pill'>"+esc(k)+": "+esc(obj[k])+"</span>";
  }).join("")||"&mdash;";
}
function render(t){
  if(!t.enabled){
    document.getElementById("meta").innerHTML=
      "tenant accounting is off on this daemon (role "+
      fmt(t.role)+")";
    return;
  }
  document.getElementById("meta").innerHTML=
    "tracked series "+t.tracked_series+" &middot; mode "+fmt(t.mode)+
    " &middot; global limit "+(t.global_limit||"&infin;")+
    " &middot; snapshots "+t.snapshots_written+
    " &middot; refreshed "+new Date().toLocaleTimeString();
  var names=Object.keys(t.tenants||{});
  var rows=names.map(function(n){
    var e=t.tenants[n];
    var over=e.limit&&e.series>=e.limit;
    var ser=e.series+(e.tier==="hll"
      ?" <small>&plusmn;"+Math.round(e.error*100)+"% (hll)</small>":"");
    return [esc(n), over?"<span class='bad'>"+ser+"</span>":ser,
      e.limit?esc(e.limit):"&infin;", e.points,
      e.refused?"<span class='bad'>"+e.refused+"</span>":0,
      e.would_refuse||0];});
  document.getElementById("tenants").innerHTML=
    table("Tenants",["tenant","series","limit","points","refused",
                     "would refuse"],rows);
  var hh="";
  names.forEach(function(n){
    var e=t.tenants[n];
    if((e.top_series||[]).length)
      hh+=table("Heavy hitters &mdash; "+esc(n),
        ["series","points","err","","prefix","new series","err"],
        e.top_series.map(function(s,i){
          var p=(e.top_prefixes||[])[i]||{};
          return [esc(s.series),s.points,s.err,"",
            fmt(p.prefix),fmt(p.new_series),fmt(p.err)];}));
  });
  document.getElementById("hh").innerHTML=hh;
  document.getElementById("adm").innerHTML=
    pills("Admission buckets", t.admission||{});
}
function tick(){
  fetch("/api/tenants").then(function(r){return r.json();})
    .then(render)
    .catch(function(e){document.getElementById("meta").innerHTML=
      "<span class='bad'>fetch failed: "+esc(e)+"</span>";});
}
tick(); setInterval(tick, 2000);
</script></body></html>
"""

_QUERIES_HTML = """<!doctype html>
<html><head><meta charset="utf-8"><title>tsd queries</title>
<style>
 body{font:13px/1.45 system-ui,sans-serif;margin:1.2em;background:#fafafa;
      color:#222}
 h1{font-size:1.2em;margin:0 0 .2em}
 h2{font-size:1em;margin:1.2em 0 .3em}
 table{border-collapse:collapse;background:#fff;min-width:30em}
 th,td{border:1px solid #ddd;padding:.25em .6em;text-align:left;
       font-variant-numeric:tabular-nums}
 th{background:#f0f0f0;font-weight:600}
 .ok{color:#0a7d32}.bad{color:#c0392b}.warn{color:#b8860b}
 #meta{color:#666;font-size:.9em;margin-bottom:.8em}
 .pill{display:inline-block;padding:0 .5em;border-radius:.8em;
       background:#eee;margin-right:.4em}
</style></head><body>
<h1>Query planner</h1>
<div id="meta">loading /api/queries&hellip;</div>
<div id="plans"></div><div id="sketch"></div>
<div id="rollup"></div><div id="caches"></div>
<script>
function esc(v){return String(v).replace(/&/g,"&amp;")
  .replace(/</g,"&lt;").replace(/>/g,"&gt;");}
function fmt(v){return v===null||v===undefined?"&mdash;":esc(v);}
function table(title, heads, rows){
  var h="<h2>"+title+"</h2><table><tr>"+heads.map(
    function(x){return "<th>"+x+"</th>";}).join("")+"</tr>";
  h+=rows.map(function(r){return "<tr>"+r.map(
    function(c){return "<td>"+c+"</td>";}).join("")+"</tr>";}).join("");
  return h+"</table>";
}
function pills(title, obj){
  return "<h2>"+title+"</h2>"+Object.keys(obj).sort().map(function(k){
    return "<span class='pill'>"+esc(k)+": "+esc(obj[k])+"</span>";
  }).join("")||"&mdash;";
}
function render(t){
  document.getElementById("meta").innerHTML=
    "up "+t.uptime_s+"s &middot; refreshed "+
    new Date().toLocaleTimeString();
  var order=["raw","resident","fused","rollup","approx","expert",
             "expert-decline"];
  var p=t.plans||{};
  document.getElementById("plans").innerHTML=
    table("Plans served",["plan","results"],order.filter(function(k){
      return p[k];}).map(function(k){
        var cls=k==="approx"?" class='warn'":"";
        return ["<span"+cls+">"+esc(k)+"</span>", p[k]];}));
  var f=t.fused;
  if(f&&f.attempt){
    var dec=Object.keys(f.declines||{}).sort().map(function(k){
      return esc(k)+"="+esc(f.declines[k]);}).join(" ")||"none";
    var dc=f.devcache||{};
    document.getElementById("plans").innerHTML+=
      "<p>fused coverage: <b>"+(100*f.coverage).toFixed(1)+"%</b> ("+
      f.served+"/"+f.attempt+" batteries) &middot; declines: "+dec+
      " &middot; devcache hit/miss/evict: "+(dc.hit||0)+"/"+
      (dc.miss||0)+"/"+(dc.evict||0)+"</p>";
  }
  document.getElementById("sketch").innerHTML=
    pills("Sketch serving (error contract)", t.sketch||{});
  var r=t.rollup;
  if(r){
    var rows=Object.keys(r.sketch_alloc||{}).map(function(res){
      var a=r.sketch_alloc[res];
      return [esc(res),(r.hits||{})[res]||0,a.digest_k,a.moment_k,
              a.hll_p];});
    document.getElementById("rollup").innerHTML=
      table("Rollup tier "+(r.ready?"<span class='ok'>ready</span>"
        :"<span class='bad'>not ready</span>"),
        ["res","hits","digest_k","moment_k","hll_p"],rows)
      +pills("Fallbacks", r.fallbacks||{})
      +pills("Sketch bytes written", r.sketch_bytes||{});
  } else { document.getElementById("rollup").innerHTML=""; }
  var mesh=t.mesh||{};
  var cc=mesh.compile_cache||{};
  document.getElementById("caches").innerHTML=
    pills("Mesh execution ("+(mesh.devices||1)+" device"+
          ((mesh.devices||1)>1?"s":"")+
          (mesh.expert_enabled?", expert on":"")+")",
          {"compile cache":(cc.size||0)+" plans",
           "hit":cc.hit||0,"miss":cc.miss||0,
           "expert served":(mesh.expert||{}).serve||0,
           "expert declined":(mesh.expert||{}).decline||0})+
    pills("Fragment cache", t.qcache||{})+
    pills("Admission", t.admission||{});
}
function tick(){
  fetch("/api/queries").then(function(r){return r.json();})
    .then(render)
    .catch(function(e){document.getElementById("meta").innerHTML=
      "<span class='bad'>fetch failed: "+esc(e)+"</span>";});
}
tick(); setInterval(tick, 2000);
</script></body></html>
"""
