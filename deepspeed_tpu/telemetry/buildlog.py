"""The build ledger: one record a program jax built, and who caused it.

jax 0.9.0 says what a jit build costs through ``jax.monitoring``, on every
build and on nothing else (a call that finds its executable emits none of
it): a scalar when tracing, lowering or the backend's compile BEGINS and a
duration when it ends, each with ``fun_name``, and between the last two what
the persistent cache did (a request, a hit with the seconds it took to read
and the seconds it saved, a miss once the new entry is written). The
listeners here fold them, thread by thread, into one record a program:

``program``      jax's ``fun_name`` (``jit(packed_forward)``; a primitive
                 applied eagerly reads ``jit(broadcast_in_dim)``)
``t``            ``time.perf_counter()`` when the build began: the clock of
                 ``telemetry._now``, so of every span
``trace_s``      Python tracing to a jaxpr. A jit called inside another's
                 trace is traced there and counted there, once; a whole
                 build inside a trace or a lowering (a constant made
                 eagerly) is a record of its own, with its lowering and
                 compile taken out of the outer program's seconds
``lower_s``      jaxpr to MLIR: every Pallas kernel's Mosaic lowering
``compile_s``    the backend's compile (0 where it was loaded)
``load_s``       jax's whole compile step where the executable came out of
                 the persistent cache (hashing the key, reading,
                 deserializing); ``read_s`` is the read and deserialize alone
``cache``        ``"hit"``, ``"miss"`` (the request found nothing: it
                 compiled) or ``"off"`` (no cache directory, or no request);
                 ``stored`` says a miss was written back (jax keeps no entry
                 that compiled in under
                 ``jax_persistent_cache_min_compile_time_secs``: such a
                 program misses in every process)
``saved_s``      what jax says the hit saved
``under``        the name of the innermost ``telemetry.span`` open on the
                 thread when the build began (None under none) and ``tags``,
                 its attributes then: ``round`` and ``dispatch`` or ``step``,
                 and for a serving dispatch the shape that names the
                 program, ``seq_bucket``, ``chunk_bucket``, ``verify_k``

Always on, as the flight recorder is: a ring of ``CAPACITY`` records with
lifetime totals that survive eviction, touched only when jax builds
something. A build that compiled (not loaded) under a span whose ``round``
or ``step`` is past the first is a recompile in steady state: it goes to the
flight recorder (``kind="compile"``). With telemetry enabled each record also
goes through ``record_compile`` (``Telemetry.record_build``).

Stdlib, ``jax.monitoring`` and two reads of ``jax.config``.
"""

import collections
import threading
import time

import jax
from jax import monitoring

from deepspeed_tpu.telemetry import flightrec as _flightrec

#: records kept; older ones leave the ring and stay in ``totals()``
CAPACITY = 256

_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/core/compile/backend_compile_duration": "compile_s"}
# what the persistent cache says between a lowering and the end of the compile
# step: three counts (kept as True) and two durations (kept as seconds)
_CACHE = {"/jax/compilation_cache/compile_requests_use_cache": "request",
          "/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "stored",
          "/jax/compilation_cache/cache_retrieval_time_sec": "read_s",
          "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}

_now = time.perf_counter


class _Thread(threading.local):
    """What one thread's builds are folded from. ``span`` is a weak
    reference to the innermost open ``telemetry.span`` (``core._Span`` keeps
    it: one store on enter, one on exit; weak, so that a span an exception
    left unended is not the cause of every later build)."""
    span = None
    depth = 0          # phases open: a phase that ends above 0 is nested
    t0 = 0.0           # when the outermost open phase began
    cause = None       # the span open then
    inner_s = 0.0      # whole builds finished inside the open outermost phase
    traced = None      # (t, trace_s, fun_name, cause): traced, not lowered yet
    lowered = ()       # records lowered and not compiled yet (AOT: a few)
    cache = None       # what the cache said since the last compile step


_thread = _Thread()
_lock = threading.Lock()
_ring = collections.deque(maxlen=CAPACITY)
_totals = collections.Counter()   # programs, the seconds by part, cache outcomes
_cost = [0.0, 0]                  # this module's handlers: seconds, calls
_sink = None                      # the Telemetry pipeline, fed when enabled


def _open_span():
    ref = _thread.span
    while ref is not None:
        span = ref()
        if span is None or span._t0 is not None:
            return span
        ref = span._outer       # ended out of order: the one it opened under
    return None


def _on_start(event, value, **kw):
    """A phase begins (jax's scalar of the same name as its duration)."""
    if event in _PHASES:
        t = _now()
        th = _thread
        th.depth += 1
        if th.depth == 1:
            th.t0, th.inner_s, th.cause = t, 0.0, _open_span()
        _cost[0] += _now() - t
        _cost[1] += 1


def _on_count(event, **kw):
    _said(event, True)


def _said(event, value):
    what = _CACHE.get(event)
    if what is not None:
        th = _thread
        if th.cache is None:
            th.cache = {}
        th.cache[what] = value


def _on_duration(event, seconds, **kw):
    part = _PHASES.get(event)
    if part is None:
        _said(event, seconds)
        return
    t = _now()
    th = _thread
    th.depth = max(th.depth - 1, 0)
    if part != "trace_s":
        _built(th, part, seconds, kw.get("fun_name"), t)
    elif th.depth == 0:     # a nested jit's trace is inside its caller's
        if th.traced is not None:               # traced and never lowered
            _totals["traces_unlowered"] += 1
            _totals["unlowered_s"] += th.traced[1]
        th.traced = (th.t0, max(seconds - th.inner_s, 0.0),
                     kw.get("fun_name"), th.cause)
    _cost[0] += _now() - t
    _cost[1] += 1


def _built(th, part, seconds, name, t):
    """A lowering or a compile step ended at ``t``: nested (a whole build
    inside another program's trace or lowering) where a phase is open."""
    nested = th.depth > 0
    if nested:
        th.inner_s += seconds
    else:
        seconds = max(seconds - th.inner_s, 0.0)
    began, cause = (t - seconds, None) if nested else (th.t0, th.cause)
    if part == "lower_s":
        rec = _new_record(name, began, cause)
        traced = th.traced
        if not nested and traced is not None and name is not None \
                and name.endswith("(" + str(traced[2]) + ")"):
            rec["t"], rec["trace_s"], th.traced = traced[0], traced[1], None
            _set_cause(rec, traced[3])
        rec["lower_s"] = seconds
        th.lowered += (rec,)
        if len(th.lowered) > 8:                 # lowered, never compiled
            _finish(th.lowered[0])
            th.lowered = th.lowered[1:]
        return
    rec = next((r for r in reversed(th.lowered) if r["program"] == name), None)
    if rec is None:         # the lowering was cached: compiled again as it stood
        rec = _new_record(name, began, cause)
    else:
        th.lowered = tuple(r for r in th.lowered if r is not rec)
    said, th.cache = th.cache or {}, None
    if said.get("hit"):
        rec.update(cache="hit", load_s=seconds, read_s=said.get("read_s", 0.0),
                   saved_s=said.get("saved_s", 0.0))
    else:
        on = said.get("request") and jax.config.jax_compilation_cache_dir \
            and jax.config.jax_enable_compilation_cache
        rec.update(cache="miss" if on else "off", compile_s=seconds,
                   stored=bool(said.get("stored")))
    _finish(rec)


def _new_record(program, t, cause):
    rec = {"program": program, "t": t, "trace_s": 0.0, "lower_s": 0.0,
           "compile_s": 0.0, "load_s": 0.0, "read_s": 0.0, "saved_s": 0.0,
           "cache": "off", "stored": False, "under": None, "tags": {}}
    _set_cause(rec, cause if cause is not None else _open_span())
    return rec


def _set_cause(rec, span):
    if span is not None:
        rec["under"], rec["tags"], rec["_span"] = span.name, dict(span.tags), span


def seconds_of(rec):
    """What the host spent on one record's program, all parts."""
    return rec["trace_s"] + rec["lower_s"] + rec["compile_s"] + rec["load_s"]


def _finish(rec):
    span = rec.pop("_span", None)
    with _lock:
        _ring.append(rec)
        for part in ("trace_s", "lower_s", "compile_s", "load_s", "saved_s"):
            _totals[part] += rec[part]
        _totals[rec["cache"]] += 1
        _totals["programs"] += 1
    tags = rec["tags"]
    if rec["compile_s"] and (tags.get("round", 0) > 0 or tags.get("step", 0) > 0):
        _flightrec.record("compile", rec["program"], detail={
            "under": rec["under"], "cache": rec["cache"],
            "seconds": round(seconds_of(rec), 6), **tags})
    tm = _sink
    if tm is not None and tm.enabled:
        names = []
        while span is not None:
            names.append(span.name)
            span = span._outer and span._outer()
        tm.record_build(rec, names)


def build_log(last=None):
    """The records the ring holds, oldest first (copies); ``last``: only the
    newest so many."""
    with _lock:
        recs = list(_ring)
    if last is not None:
        recs = recs[len(recs) - last:] if last > 0 else []
    return [dict(r, tags=dict(r["tags"])) for r in recs]


def build_count():
    """Programs built so far in this process: monotone, one read."""
    return _totals["programs"]


def build_ms(built):
    """Milliseconds of host time the newest ``built`` records took (a span's
    ``build_ms`` beside its ``built``); 0.0 for none, with no lock."""
    if not built:
        return 0.0
    return round(1e3 * sum(seconds_of(r) for r in build_log(built)), 3)


def totals():
    """Lifetime sums, eviction or not: ``programs``, the seconds by part,
    the cache outcomes (``hit``, ``miss``, ``off``), traces that were never
    lowered, and ``listener_s`` over ``listener_calls``: what this module's
    own handlers took."""
    with _lock:
        return dict(_totals, listener_s=_cost[0], listener_calls=_cost[1])


def install(sink=None):
    """Register the listeners, once however often it is called (whoever
    cleared jax's lists in between); ``sink`` is the pipeline to feed."""
    global _sink
    with _lock:
        if sink is not None:
            _sink = sink
    uninstall()
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_listener(_on_count)
    monitoring.register_event_duration_secs_listener(_on_duration)


def uninstall():
    for unregister, listener in (
            (monitoring.unregister_scalar_listener, _on_start),
            (monitoring.unregister_event_listener, _on_count),
            (monitoring.unregister_event_duration_listener, _on_duration)):
        try:
            unregister(listener)
        except (AssertionError, ValueError):    # not registered
            pass
