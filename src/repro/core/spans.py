"""Host spans, plan counters and per-solve records.

``span(name)`` times one stage of the plan engine on the host.  It opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profile shows the
stage on the host line beside the device ops, and it nests on a per-thread
stack.  The outermost span of a thread opens a *record*; when that span
closes, the record (plain numbers and strings only) enters a bounded ring
that :func:`solve_records` reads::

    {"name": "sla.solve", "id": 17, "start_ns": ..., "end_ns": ...,
     "seconds": 0.41,                       # the root's duration
     "incl_s": {"plan.solve": 0.40, ...},   # per span name, children in
     "self_s": {"plan.solve": 0.01, ...},   # per span name, children out
     "counters": {"setup_reuse": 1, "jax_lowerings": 1, ...},
     "lowered": ["jit(while)"],             # programs lowered (≤ 16 kept)
     "traced": False}                       # root ran under a JAX trace

``count(key, k)`` is the one way ``PLAN_STATS`` is incremented; it also
adds to the record of the span open on the calling thread.  A
``jax.monitoring`` listener, installed when the first span opens, counts
JAX's own compile events the same way (``jax_traces``, ``jax_lowerings``,
``jax_compiles``, ``jax_cache_hits``), so a record says whether its solve
traced, lowered or compiled a program again, and which one.

The recorder is always on: a span costs a few microseconds, and the
annotation about one more when no profiler session is active.  A record
whose root opened under a JAX trace (``traced``) holds trace-time timings.

``scoped(name, fn)`` is the device-side counterpart: ``fn``'s ops traced
under ``jax.named_scope(name)`` (loop bodies, preconditioner applies).
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Dict, List, Optional

import jax
from jax._src import core as _jcore    # trace_state_clean: no public alias

RING = 1024              # completed records kept
MAX_LOWERED = 16         # lowered program names kept per record

# observable analyze/setup/cache/compile counters (reset with
# ``dispatch.reset_plan_stats``); ``count`` is their only writer
PLAN_STATS: Dict[str, int] = {
    "analyze": 0,          # SolverPlan constructions (pattern analyses)
    "setup": 0,            # values-dependent setups actually executed
    "setup_reuse": 0,      # setups served from the per-values memo
    "factorize": 0,        # numeric factorizations run by the direct backend
    "cache_hit": 0,        # plan served from a SparseTensor's plan cache
    "cache_miss": 0,       # plan analyzed fresh
    "transpose_shared": 0,  # adjoint reused the forward plan (or its factors)
    "t_partition": 0,      # distributed Aᵀ partitions built (once per plan)
    "coarsen": 0,          # AMG pattern coarsenings (symbolic, once/pattern)
    "galerkin": 0,         # AMG numeric Galerkin products (once/values array)
    "mg3d_build": 0,       # 3-D geometric MG hierarchies (once/values array)
    "mg3d_levels": 0,      # levels of those hierarchies (added per build)
    "kernel_plan": 0,      # BELL conversions run by the analyze-time kernel plan
    "evictions": 0,        # plans dropped by the bounded LRU plan cache
    "jac_color": 0,        # Jacobian pattern colorings (once per SparseNewton)
    "jac_assemble": 0,     # numeric Jacobian assemblies (jvp probe sweeps)
    "solve_program_build": 0,  # single-device solve programs traced
    "solve_program_call": 0,   # calls of a plan's cached solve program
    "jax_traces": 0,       # jaxprs traced by JAX (counted once a span opened)
    "jax_lowerings": 0,    # programs lowered to MLIR
    "jax_compiles": 0,     # backend compiles
    "jax_cache_hits": 0,   # programs loaded from the persistent compile cache
}

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lowerings",
    "/jax/core/compile/backend_compile_duration": "jax_compiles",
    "/jax/compilation_cache/cache_hits": "jax_cache_hits",
}

_tls = threading.local()
_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_listening = False


class _Record:
    __slots__ = ("name", "id", "start_ns", "incl", "self_", "counters",
                 "lowered", "traced")

    def __init__(self, name: str, start_ns: int):
        self.name = name
        self.id = next(_ids)
        self.start_ns = start_ns
        self.incl: Dict[str, int] = {}
        self.self_: Dict[str, int] = {}
        self.counters: Dict[str, int] = {}
        self.lowered: List[str] = []
        self.traced = not _jcore.trace_state_clean()

    def close(self, end_ns: int) -> dict:
        return {"name": self.name, "id": self.id, "start_ns": self.start_ns,
                "end_ns": end_ns, "seconds": (end_ns - self.start_ns) * 1e-9,
                "incl_s": {k: v * 1e-9 for k, v in self.incl.items()},
                "self_s": {k: v * 1e-9 for k, v in self.self_.items()},
                "counters": dict(self.counters),
                "lowered": list(self.lowered), "traced": self.traced}


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _record() -> Optional[_Record]:
    st = getattr(_tls, "stack", None)
    return st[0][0] if st else None


def count(key: str, k: int = 1) -> None:
    """Add ``k`` to ``PLAN_STATS[key]`` and to the open record, if any."""
    PLAN_STATS[key] = PLAN_STATS.get(key, 0) + k
    rec = _record()
    if rec is not None:
        rec.counters[key] = rec.counters.get(key, 0) + k


def _on_jax_event(event: str, *args, **kw) -> None:
    key = _JAX_EVENTS.get(event)
    if key is None:
        return
    count(key)
    rec = _record()
    if (key == "jax_lowerings" and rec is not None
            and len(rec.lowered) < MAX_LOWERED):
        rec.lowered.append(str(kw.get("fun_name", "?")))


def _listen() -> None:
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        jax.monitoring.register_event_listener(_on_jax_event)


class span:
    """Context manager timing one named host stage (see the module doc)."""
    __slots__ = ("name", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        _listen()
        st = _stack()
        now = time.perf_counter_ns()
        rec = st[0][0] if st else _Record(self.name, now)
        st.append([rec, now, 0])          # record, start, children's time
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        now = time.perf_counter_ns()
        st = _stack()
        rec, t0, child = st.pop()
        dur = now - t0
        rec.incl[self.name] = rec.incl.get(self.name, 0) + dur
        rec.self_[self.name] = rec.self_.get(self.name, 0) + dur - child
        if st:
            st[-1][2] += dur
        else:
            _ring.append(rec.close(now))
        return False


def solve_records(n: Optional[int] = None) -> List[dict]:
    """The newest ``n`` completed records (all kept when None), oldest
    first, as plain dicts."""
    recs = list(_ring)
    return recs if n is None else recs[-n:] if n > 0 else []


def clear_records() -> None:
    _ring.clear()


def scoped(name: str, fn):
    """``fn`` with the ops it traces under ``jax.named_scope(name)``: a
    device-side name, free at run time (it sets op metadata, and names a
    Pallas custom call)."""
    def wrapper(*args):
        with jax.named_scope(name):
            return fn(*args)
    return wrapper


def spanned(name: str):
    """Decorator form of :class:`span`."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return wrapper
    return deco
