"""compile_with_plan: the one entry point for device execution.

The unified mesh compilation layer (ROADMAP "unified mesh compilation
layer"; SNIPPETS.md's Titanax ``compile_step_with_plan`` shape): every
kernel the query/rollup/fused paths dispatch goes through

    fn = compile_with_plan(body, plan, mesh[, statics])

- ``mesh is None`` (the default everywhere no mesh is configured):
  exactly ``jax.jit(body, static_argnames=plan.static_argnames,
  donate_argnums=plan.donate_argnums)`` — the migration off per-site
  jits is a bit-for-bit no-op.
- mesh + plan specs, style "pjit": prefer explicit shardings —
  ``jax.jit`` with in_/out_shardings built as NamedShardings of the
  plan's PartitionSpecs over the mesh. The body stays a global-view
  program; GSPMD partitions it and inserts the collectives.
- mesh + plan specs, style "shard_map": the fallback for map-style
  bodies with explicit collectives (psum/all_gather written out) —
  ``jax.shard_map`` over the mesh wrapped in one jit.

Results cache per (fn, plan, mesh, statics) — repeat dashboards never
rebuild a wrapper, and jax's own executable cache below keys on shapes
as usual. ``statics`` exists because shard_map bodies can't take jit
static kwargs through the wrapper: pass them as a hashable tuple of
(name, value) pairs and they bind into the body before wrapping (and
into the cache key).

Every body compiles under ``jax.named_scope(plan.name)``: the plan's
name is in each operation's metadata (``op_name`` in the HLO, the
operation's name in a profiler trace), so a trace's device time can be
summed by plan and a kernel keeps its name through a refactor.

Observability: ``mesh.compile`` times wrapper builds AND any dispatch
that triggered a fresh XLA compile (detected via the jitted callable's
cache size growing); ``mesh.dispatch`` times every mesh-leg dispatch;
``mesh.cache.hit/miss`` count plan-cache outcomes; ``mesh.devices``
gauges the process's configured mesh width. Single-device dispatches
are NOT timed — the plane adds one None-check to the no-mesh hot path.
"""

from __future__ import annotations

import functools
import threading

import jax
from jax.sharding import NamedSharding

from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.parallel.plan import ExecPlan

_M_COMPILE = _metrics.timer("mesh.compile")
_M_DISPATCH = _metrics.timer("mesh.dispatch")
_C_HIT = _metrics.counter("mesh.cache.hit")
_C_MISS = _metrics.counter("mesh.cache.miss")

# Process-wide mesh width for the /stats + /metrics gauge: 1 until a
# server configures a mesh (set_mesh_devices). Gauges re-read on
# every scrape, so role changes show up live.
_MESH_DEVICES = 1
_metrics.gauge("mesh.devices", lambda: _MESH_DEVICES)
_metrics.gauge("mesh.cache.size", lambda: len(_CACHE))


def set_mesh_devices(n: int) -> None:
    global _MESH_DEVICES
    _MESH_DEVICES = int(n)


_CACHE: dict = {}
_CACHE_LOCK = threading.Lock()


def cache_info() -> dict:
    """Plan-cache counters for /api/queries (the compile-cache line)."""
    return {"size": len(_CACHE),
            "hit": int(_C_HIT.value),
            "miss": int(_C_MISS.value),
            "devices": _MESH_DEVICES}


def _shardings(mesh, specs):
    if specs is None:
        return None
    if isinstance(specs, tuple):
        return tuple(NamedSharding(mesh, s) for s in specs)
    return NamedSharding(mesh, specs)


class _MeshDispatch:
    """Mesh-leg callable: times every dispatch, and books the ones
    that triggered a fresh XLA compile (cache-size growth) under
    ``mesh.compile`` too — so /stats separates steady-state dispatch
    cost from cold-compile cost without tracing hooks."""

    __slots__ = ("_fn",)

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *args, **kwargs):
        import time as _time
        fn = self._fn
        try:
            before = fn._cache_size()
        except Exception:
            before = None
        t0 = _time.perf_counter()
        out = fn(*args, **kwargs)
        ms = (_time.perf_counter() - t0) * 1000.0
        _M_DISPATCH.observe(ms)
        if before is not None:
            try:
                if fn._cache_size() > before:
                    _M_COMPILE.observe(ms)
            except Exception:
                pass
        return out


def compile_with_plan(fn, plan: ExecPlan, mesh=None, statics: tuple = ()):
    """Compile ``fn`` per ``plan`` for ``mesh``; cached.

    ``statics``: hashable ((name, value), ...) keyword bindings for
    mesh styles (shard_map bodies take no jit-static kwargs through
    the wrapper). With ``mesh=None`` they simply bind before the jit,
    so one body serves both legs.
    """
    key = (fn, plan, mesh, statics)
    with _CACHE_LOCK:
        hit = _CACHE.get(key)
    if hit is not None:
        _C_HIT.inc()
        return hit
    _C_MISS.inc()
    with _M_COMPILE.time():
        body = functools.partial(fn, **dict(statics)) if statics else fn
        # The wrapper keeps the body's signature (functools.wraps, so
        # jit still resolves static_argnames) and its name (a partial
        # has none of its own), so the module is still jit_<fn>.
        body = jax.named_scope(plan.name)(body)
        body.__name__ = getattr(fn, "__name__", plan.name)
        # Statics bound through ``statics`` are no longer call-time
        # kwargs; keeping them in static_argnames would confuse jit's
        # signature inspection (and pjit rejects kwargs outright when
        # shardings are specified).
        bound = frozenset(k for k, _ in statics)
        static_names = tuple(n for n in plan.static_argnames
                             if n not in bound)
        # A 1-device mesh is NOT the single-device leg: shard_map
        # bodies reference their axis names (psum/all_gather) and must
        # still compile under the mesh — that 1-vs-N-device sameness
        # is exactly what the parity batteries compare.
        single = mesh is None or plan.in_specs is None
        if single:
            compiled = jax.jit(body,
                               static_argnames=static_names,
                               donate_argnums=plan.donate_argnums)
            wrapped = compiled if mesh is None else _MeshDispatch(compiled)
        elif plan.style == "pjit":
            # Explicit shardings exist: prefer the pjit path (jax.jit
            # with shardings) so the partitioner sees them; the body
            # stays global-view.
            compiled = jax.jit(
                body,
                in_shardings=_shardings(mesh, plan.in_specs),
                out_shardings=_shardings(mesh, plan.out_specs),
                static_argnames=static_names,
                donate_argnums=plan.donate_argnums)
            wrapped = _MeshDispatch(compiled)
        else:
            # Map-style fallback: the body is written per-shard with
            # explicit collectives.
            mapped = jax.shard_map(body, mesh=mesh,
                                   in_specs=plan.in_specs,
                                   out_specs=plan.out_specs)
            compiled = jax.jit(mapped,
                               static_argnames=static_names,
                               donate_argnums=plan.donate_argnums)
            wrapped = _MeshDispatch(compiled)
    with _CACHE_LOCK:
        # First writer wins so concurrent compilers share one jit
        # cache (two wrappers would each compile every shape class).
        got = _CACHE.setdefault(key, wrapped)
    return got


def jit_plan(plan: ExecPlan):
    """Decorator form for the module-level single-device kernels:
    ``@jit_plan(PLAN)`` == the old ``functools.partial(jax.jit,
    static_argnames=...)`` — same jit, same statics, one registry."""
    def deco(fn):
        return compile_with_plan(fn, plan, None)
    return deco


def clear_cache() -> None:
    """Test hook: drop every cached wrapper (NOT jax's own lowered
    cache — semantics don't change, only the plane's bookkeeping)."""
    with _CACHE_LOCK:
        _CACHE.clear()
