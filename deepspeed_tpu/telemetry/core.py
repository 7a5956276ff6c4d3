"""Process-global telemetry pipeline — the unified observability layer.

One object owns every measurement stream the runtime produces:

- **spans** (``span("fwd", step=3)`` / ``span_begin``/``end``): host phases
  of the train loop and the serving round. Every span is a
  ``jax.profiler.TraceAnnotation`` named ``ds/<name>`` with its attributes,
  so any profiler capture holds it on the device trace's clock; a span
  never waits for the device (it times the host's part: the dispatch).
- **metrics** (``record(name, value, kind, **tags)``): scalar samples,
  appended to an in-memory list and (when configured) a JSON-lines file.
- **counters** (``count(name, **tags)``): monotone per-tag counts.
- **comm** (``record_comm``): per-op per-mesh-axis message bytes, latency and
  algbw/busbw (``utils/comms_logging.calc_bw_log`` factors).
- **dispatch** (``record_dispatch``): per-kernel sharded/fallback/veto
  outcomes with reason codes from ``ops/registry.sharded_kernel_call``.
- **compile** (``record_compile``): per-program compile seconds + persistent
  compilation-cache hit/miss (and AOT ``memory_analysis`` byte breakdown)
  from the AOT path, and every jit build from the build ledger
  (``telemetry/buildlog.py``: always on; it feeds this stream through
  ``record_build`` when the pipeline is enabled).
- **memory** (``record_memory`` / ``sample_memory``): HBM occupancy samples
  from ``accelerator.memory_stats()`` — per-point stream, process peak
  watermark, Chrome-trace counter track, and (on ``RESOURCE_EXHAUSTED``)
  an OOM post-mortem listing the top live buffers by size.
- **goodput ledger** (``ledger_step`` + span/comm/compile classification):
  every wall-second of the run bucketed into
  ``compute / comm / compile / ckpt / stall / idle``, joined with the
  model's per-step FLOPs (``set_model_flops``) into per-step and rolling
  ``mfu`` and ``goodput`` gauges.
- **serving stream** (``record_hist`` / ``serving_event`` /
  ``serving_gauge`` / ``record_request_phase``): per-request lifecycle
  latencies (TTFT, TPOT, e2e, queue-wait) land in fixed-bucket log2
  histograms with p50/p95/p99 extraction; scheduler/KV gauges
  (token-budget utilization, running/waiting, KV-block occupancy,
  fragmentation) keep last+peak and a Chrome counter track; each request
  gets its own Chrome-trace lane (a synthetic tid named ``request/<uid>``)
  carrying its queued/prefill/decode/finish phases.

Every JSON-lines record is stamped with ``(host, pid, run_id)`` so
``scripts/trace_merge.py`` can fold N per-host streams into one Chrome trace
with per-host tracks and a straggler report.

Exporters: Chrome-trace JSON (``chrome://tracing`` / Perfetto) for spans, a
JSON-lines metrics file, Monitor fan-out events (``monitor_events``) for the
CSV/TB/W&B backends; spans are in every ``jax.profiler`` capture whether
the pipeline is enabled or not.

Disabled (the default) every entry point but ``span`` is a constant-time
no-op, and a span is one short-lived annotation object: no lock, no file
I/O, no state kept but the thread's innermost open span (for the build
ledger: one store on enter, one on exit) — see
``tests/test_telemetry.py::test_disabled_noop_fast_path``.

Beyond the standard library this module imports only ``jax.profiler`` (the
annotation every span opens); the rest of jax is imported lazily inside the
enabled-only paths.
"""

import atexit
import json
import math
import os
import socket
import threading
import time
import weakref

from jax.profiler import TraceAnnotation

# the always-on black box (telemetry/flightrec.py): Fault/* and Recovery/*
# events, SLO violations and memory samples are mirrored into its bounded
# ring so an abnormal exit can flush them as a postmortem bundle — even
# when this pipeline itself is disabled. Stdlib-only, so import-safe here.
from deepspeed_tpu.telemetry import flightrec as _flightrec
# the build ledger (telemetry/buildlog.py) names the span a program was built
# under: every span keeps the thread's innermost open one there
from deepspeed_tpu.telemetry import buildlog as _buildlog
from deepspeed_tpu.telemetry.buildlog import _thread as _open

#: event-name prefixes mirrored into the flight recorder ring. A module
#: constant so the disabled-path check in record() allocates nothing.
_FLIGHT_FAULT_PREFIX = "Fault/"
_FLIGHT_PREFIXES = ("Fault/", "Recovery/")
_FLIGHT_SPAN_PREFIXES = ("Recovery/", "recovery/")

# injectable clocks (the PR-2 pattern, see docs/OBSERVABILITY.md): tests pin
# time by monkeypatching THESE module aliases, never time.* globally (which
# would break jax internals). All span/ledger timing reads _now; _now_wall
# is only for human-facing stamps (run ids).
_now = time.perf_counter
_now_wall = time.time

#: goodput-ledger classification (docs/OBSERVABILITY.md). Every wall-second of an
#: enabled run lands in exactly one bucket; ``idle`` is the unattributed
#: remainder (wall − sum of the others, floored at 0).
LEDGER_CATEGORIES = ("compute", "comm", "compile", "ckpt", "stall", "idle")

_COMPUTE_SPANS = frozenset({"fwd", "bwd", "step", "eval"})

#: THE per-chip peak bf16 FLOP/s table, keyed by ``device_kind`` (public
#: specs: Google Cloud TPU documentation, per-generation system pages).
#: bench.py and the scripts read it through ``peak_bf16_flops``; there is
#: no CPU row and no default — MFU is a device metric.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_bf16_flops(device_kind):
    """Peak bf16 FLOP/s of one chip of ``device_kind``; an unknown device
    raises (a guessed denominator would make the MFU meaningless)."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(f"no peak FLOP/s known for device_kind "
                       f"{device_kind!r}; add it to PEAK_BF16_FLOPS with "
                       f"its source") from None


def _ledger_category(span_name):
    """Ledger bucket for a span name, or None for container/unclassified
    spans. ``recovery/*`` spans deliberately map to None: they WRAP the
    ``ckpt/*`` spans that do the work, and charging both would double-count
    the interval."""
    if span_name in _COMPUTE_SPANS:
        return "compute"
    if span_name.startswith("ckpt"):
        return "ckpt"
    if span_name == "dataloader":
        return "stall"
    return None


#: fixed-bucket histogram geometry: bucket 0 holds values <= HIST_MIN (1us),
#: bucket i holds (HIST_MIN*2^(i-1), HIST_MIN*2^i], the last bucket is the
#: overflow (>~2400s). Log2 spacing bounds the per-sample cost to one
#: ``math.log2`` and keeps relative quantile error within one octave, while
#: observed min/max clamping (below) keeps reported percentiles exact at the
#: distribution edges.
HIST_BUCKETS = 44
HIST_MIN = 1e-6


def _hist_bucket(v):
    if v <= HIST_MIN:
        return 0
    return min(1 + int(math.log2(v / HIST_MIN)), HIST_BUCKETS - 1)


def _hist_bounds(i):
    lo = 0.0 if i == 0 else HIST_MIN * 2.0 ** (i - 1)
    return lo, HIST_MIN * 2.0 ** i


def _hist_quantile(h, q):
    """Quantile by cumulative bucket walk + linear interpolation inside the
    landing bucket, clamped to the observed [min, max] (so a single-valued
    histogram reports that exact value, and p50 <= p95 <= p99 always holds:
    the walk is monotone in q and the clamp is order-preserving)."""
    target = q * h["count"]
    cum = 0
    for i, c in enumerate(h["counts"]):
        if c == 0:
            continue
        if cum + c >= target:
            lo, hi = _hist_bounds(i)
            v = lo + (hi - lo) * (target - cum) / c
            return min(max(v, h["min"]), h["max"])
        cum += c
    return h["max"]


def _default_peak_flops():
    """Peak FLOP/s of one local device for the ledger's MFU denominator when
    the caller passed none: the table's row on a TPU (unknown kinds raise),
    0.0 elsewhere — a run without an accelerator has no MFU to report."""
    import jax
    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        return 0.0
    return peak_bf16_flops(dev.device_kind)


# --- atexit export hook: registered AT MOST ONCE per process ---------------
# configure()/reset() cycles (tests re-init the pipeline dozens of times) and
# even multiple Telemetry instances must not stack export hooks — each extra
# hook would re-export (and with multiple instances, clobber) the trace file.
_ATEXIT_LOCK = threading.Lock()
_ATEXIT_REGISTERED = False
_ATEXIT_INSTANCES = []


def _register_atexit(instance):
    global _ATEXIT_REGISTERED
    with _ATEXIT_LOCK:
        if instance not in _ATEXIT_INSTANCES:
            _ATEXIT_INSTANCES.append(instance)
        if not _ATEXIT_REGISTERED:
            atexit.register(_atexit_export_all)
            _ATEXIT_REGISTERED = True


def _atexit_export_all():
    for inst in list(_ATEXIT_INSTANCES):
        inst._atexit_export()


class _Span(TraceAnnotation):
    """A live scoped measurement. Usable as a context manager
    (``with telemetry.span("fwd", step=3): ...``) or via the explicit
    ``span_begin``/``end`` pair when the scope spans methods.

    Every span IS a ``jax.profiler.TraceAnnotation`` named ``ds/<name>``
    carrying its attributes, so it lands in any profiler capture on the
    device trace's clock; with no profiler session that is one small object
    and about a microsecond. ``_tm`` is the pipeline only when telemetry is
    enabled: then the span also feeds the span stats, the JSONL file and
    the Chrome trace. A span never waits for the device. ``_outer`` is a
    weak reference to the span that was the thread's innermost when this one
    opened: the build ledger names a program's cause by it, and a span that
    an exception left unended stops counting as open once nothing holds it."""

    __slots__ = ("_tm", "name", "tags", "_t0", "_outer")

    def __init__(self, tm, name, tags):
        super().__init__("ds/" + name, **tags)
        self._tm = tm
        self.name = name
        self.tags = tags
        TraceAnnotation.__enter__(self)
        # None once ended; the clock is read only for the pipeline's sinks
        self._t0 = _now() if tm is not None else 0.0
        self._outer, _open.span = _open.span, weakref.ref(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def set(self, **attrs):
        """Attributes known only once the span is open (a batch's bucket
        after it is built): same sinks as those given at the start."""
        self.tags.update(attrs)
        self.set_metadata(**attrs)

    def end(self, token=None):
        """Close the span (ending twice records once). ``token`` is accepted
        for the callers that used to hand a device value over, and ignored."""
        t0, self._t0 = self._t0, None
        if t0 is None:
            return 0.0
        TraceAnnotation.__exit__(self, None, None, None)
        _open.span = self._outer
        tm, self._tm = self._tm, None
        if tm is None:
            return 0.0
        dt = _now() - t0
        tm._end_span(self.name, t0, dt, self.tags or None)
        return dt


class Telemetry:
    """The process-global telemetry pipeline (one instance per process,
    module-level singleton in ``deepspeed_tpu/telemetry/__init__.py``)."""

    def __init__(self):
        self._lock = threading.RLock()
        self.enabled = False
        self._reset_state()
        # exporter wiring (survives reset() so a reset mid-run keeps sinks)
        self.jsonl_path = None
        self.chrome_trace_path = None
        self.monitor_prefix = "Telemetry/"
        self._jsonl_fh = None
        # multi-host identity: stamped onto every JSONL record so
        # scripts/trace_merge.py can attribute streams (survives reset)
        try:
            self.host = socket.gethostname()
        except Exception:
            self.host = "localhost"
        self.run_id = os.environ.get("DS_TPU_HARNESS_RUN_ID") or \
            f"{os.getpid()}-{int(_now_wall())}"
        # goodput-ledger model parameters (survive reset, like sinks)
        self.memory_enabled = True
        self._flops_per_step = 0.0
        self._peak_flops = 0.0
        # SLO class targets ({name: {"ttft_target_s", "tpot_target_s",
        # "attainment_target"}}) — configuration like the sinks, so reset()
        # keeps them; set_slo_classes replaces the whole set
        self.slo_classes = {}

    def _reset_state(self):
        self._epoch = _now()
        self.trace_events = []    # chrome-trace event dicts
        self.metrics = []         # every record() sample, in order
        self.counters = {}        # name -> {tag_key: int}
        self.span_stats = {}      # name -> [count, total_s]
        self.comm_stats = {}      # (op, axis) -> [count, bytes, secs, algbw, busbw, wire_bytes]
        self.dispatch_stats = {}  # (kernel, outcome, reason) -> count
        self.compile_stats = {}   # program -> {seconds, topology, cache}
        # memory stream
        self.memory_samples = []  # {"point", "bytes_in_use", "peak_...", ...}
        self.memory_peak = 0      # process-level HBM watermark (bytes)
        self.last_oom_report = None
        # serving stream
        self.hist_stats = {}       # name -> {counts, count, sum, min, max}
        self.serving_counters = {}  # lifecycle event -> count
        self.serving_gauges = {}   # name -> [last, peak]
        self._request_lanes = {}   # uid -> synthetic chrome tid
        # time-series stream (telemetry/timeseries.py): name -> SeriesRing.
        # Gauges and histograms feed their ring implicitly, so every
        # {last,peak} stream also carries a windowed trajectory;
        # record_series adds free-form ones.
        self.series = {}
        self.slo_stats = {}        # class -> metric -> [attained, violations]
        self._flow_ids = {}        # uid -> chrome flow id (request chains)
        # fleet stream (router admission + prefill/decode handoffs)
        self.fleet_counters = {}   # admission outcome -> count
        self.fleet_gauges = {}     # name -> [last, peak]
        # moe stream (expert load / drop / a2a wire gauges)
        self.moe_gauges = {}       # name -> [last, peak]
        self.fleet_handoff = {"count": 0, "pages_shipped": 0,
                              "pages_bound": 0, "bytes": 0,
                              "wire_bytes": 0, "total_s": 0.0}
        # goodput ledger (seconds per category; idle derived at summary time)
        self.ledger_secs = {c: 0.0 for c in LEDGER_CATEGORIES if c != "idle"}
        self._ledger_epoch = self._epoch
        self._ledger_last_step_ts = None
        self._ledger_steps = 0
        self._mfu_last = 0.0
        self._mfu_roll = 0.0
        # device-timeline overlap report (telemetry/overlap.py), attached
        # post-hoc by attach_overlap(); rides summary()["overlap"]
        self.overlap_report = None

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def configure(self, config=None, enabled=None, jsonl_path=None,
                  chrome_trace_path=None, memory=None, flops_per_step=None,
                  peak_flops=None):
        """Configure from a ``TelemetryConfig`` (runtime/config.py
        ``telemetry`` section) and/or explicit overrides. Paths set to ""
        disable that exporter."""
        with self._lock:
            if config is not None:
                enabled = getattr(config, "enabled", enabled) \
                    if enabled is None else enabled
                jsonl_path = getattr(config, "jsonl_path", jsonl_path) \
                    if jsonl_path is None else jsonl_path
                chrome_trace_path = getattr(config, "chrome_trace_path",
                                            chrome_trace_path) \
                    if chrome_trace_path is None else chrome_trace_path
                memory = getattr(config, "memory", memory) \
                    if memory is None else memory
                flops_per_step = getattr(config, "flops_per_step",
                                         flops_per_step) \
                    if flops_per_step is None else flops_per_step
                peak_flops = getattr(config, "peak_flops", peak_flops) \
                    if peak_flops is None else peak_flops
            if memory is not None:
                self.memory_enabled = bool(memory)
            if flops_per_step:
                self._flops_per_step = float(flops_per_step)
            if peak_flops:
                self._peak_flops = float(peak_flops)
            if jsonl_path is not None:
                if self._jsonl_fh is not None and \
                        jsonl_path != self.jsonl_path:
                    try:
                        self._jsonl_fh.close()
                    except Exception:
                        pass
                    self._jsonl_fh = None
                self.jsonl_path = jsonl_path or None
            if chrome_trace_path is not None:
                self.chrome_trace_path = chrome_trace_path or None
                if self.chrome_trace_path:
                    _register_atexit(self)
            if enabled is not None:
                was = self.enabled
                self.enabled = bool(enabled)
                if self.enabled and not was:
                    # ledger wall time starts when measurement starts, not
                    # at the (possibly much earlier) import of this module
                    self._ledger_epoch = _now()
                    self._ledger_last_step_ts = None

    def _atexit_export(self):
        if self.enabled and self.chrome_trace_path and self.trace_events:
            try:
                self.export_chrome_trace()
            except Exception:
                pass

    def reset(self):
        """Drop every accumulated measurement (sink config stays)."""
        with self._lock:
            self._reset_state()

    def close(self):
        with self._lock:
            if self._jsonl_fh is not None:
                try:
                    self._jsonl_fh.close()
                except Exception:
                    pass
                self._jsonl_fh = None

    # ------------------------------------------------------------------
    # spans
    # ------------------------------------------------------------------
    def span(self, name, **tags):
        """Scoped measurement ``ds/<name>`` in the profiler's trace; enabled,
        also in this pipeline's sinks. Never syncs; disabled it takes no
        lock and keeps no state."""
        return _Span(self if self.enabled else None, name, tags)

    span_begin = span  # same object, explicit begin/end idiom

    def _end_span(self, name, t0, dt, tags):
        if name.startswith(_FLIGHT_SPAN_PREFIXES):
            # recovery intervals (emergency saves, ckpt fallback, reshard)
            # belong in the black box next to the faults that caused them
            _flightrec.record("recovery", name,
                              detail={"seconds": round(dt, 6),
                                      **(tags or {})})
        with self._lock:
            st = self.span_stats.get(name)
            if st is None:
                st = self.span_stats[name] = [0, 0.0]
            st[0] += 1
            st[1] += dt
            cat = _ledger_category(name)
            if cat is not None:
                self.ledger_secs[cat] += dt
            ev = {"name": name, "ph": "X", "cat": "span",
                  "ts": round((t0 - self._epoch) * 1e6, 3),
                  "dur": round(dt * 1e6, 3),
                  "pid": os.getpid(), "tid": threading.get_ident() & 0xffff}
            if tags:
                ev["args"] = tags
            self.trace_events.append(ev)
            self._emit_jsonl({"name": name, "kind": "span", "value": dt,
                              "unit": "s", "tags": tags or {}})

    # ------------------------------------------------------------------
    # metrics + counters
    # ------------------------------------------------------------------
    def record(self, name, value, kind="gauge", **tags):
        """Record one scalar sample. ``kind``: "gauge" | "counter" | "bytes"
        | "seconds" (free-form strings are kept verbatim). ``Fault/*`` and
        ``Recovery/*`` events additionally land in the flight-recorder ring
        — with telemetry disabled too, so postmortem bundles always carry
        the fault history."""
        if name.startswith(_FLIGHT_PREFIXES):
            _flightrec.record(
                "fault" if name.startswith(_FLIGHT_FAULT_PREFIX)
                else "recovery", name, detail=tags or None)
        if not self.enabled:
            return
        with self._lock:
            if kind == "counter":
                per = self.counters.setdefault(name, {})
                key = tuple(sorted(tags.items()))
                per[key] = per.get(key, 0) + value
            self.metrics.append({"name": name, "kind": kind, "value": value,
                                 "tags": tags or {}})
            self._emit_jsonl({"name": name, "kind": kind, "value": value,
                              "tags": tags or {}})

    def count(self, name, n=1, **tags):
        self.record(name, n, kind="counter", **tags)

    # ------------------------------------------------------------------
    # layer-specific recorders
    # ------------------------------------------------------------------
    def record_comm(self, op, nbytes, seconds, axis=None, traced=False,
                    wire_bytes=None):
        """One collective: bytes moved, wall seconds (host-level latency, or
        trace-emission time for in-trace calls), algbw/busbw via the ring
        correction factors. ``axis`` is the mesh axis (name or tuple).
        ``wire_bytes`` is the bytes that actually cross the link when they
        differ from the logical fp32 ``nbytes`` (quantized collectives:
        packed ints + fp32 group scales); algbw/busbw stay on the logical
        bytes so they remain comparable across precisions."""
        if not self.enabled:
            return
        from deepspeed_tpu.utils.comms_logging import calc_bw_log
        n = None
        try:
            from jax import lax
            n = int(lax.axis_size(axis))   # only resolvable in-trace
        except Exception:
            pass
        algbw, busbw = calc_bw_log(op, nbytes, seconds, n=n)
        axis_key = "/".join(axis) if isinstance(axis, (tuple, list)) \
            else (axis or "?")
        with self._lock:
            st = self.comm_stats.get((op, axis_key))
            if st is None:
                st = self.comm_stats[(op, axis_key)] = [0, 0, 0.0, 0.0, 0.0,
                                                        0]
            st[0] += 1
            st[1] += nbytes
            st[2] += seconds
            st[3] += algbw
            st[4] += busbw
            st[5] += wire_bytes if wire_bytes is not None else nbytes
            if not traced:
                # traced collectives report trace-emission time and run
                # INSIDE a compute span — charging them would double-count
                self.ledger_secs["comm"] += seconds
            ev = {"name": f"comm:{op}", "ph": "X", "cat": "comm",
                  "ts": round((_now() - seconds - self._epoch)
                              * 1e6, 3),
                  "dur": round(seconds * 1e6, 3),
                  "pid": os.getpid(), "tid": threading.get_ident() & 0xffff,
                  "args": {"bytes": nbytes, "axis": axis_key,
                           "traced": bool(traced),
                           "wire_bytes": (wire_bytes if wire_bytes is not None
                                          else nbytes)}}
            self.trace_events.append(ev)
            self._emit_jsonl({"name": f"comm/{op}", "kind": "bytes",
                              "value": nbytes,
                              "tags": {"axis": axis_key, "seconds": seconds,
                                       "algbw_gbs": round(algbw, 4),
                                       "busbw_gbs": round(busbw, 4),
                                       "traced": bool(traced),
                                       "wire_bytes": (wire_bytes
                                                      if wire_bytes is not None
                                                      else nbytes)}})

    def record_dispatch(self, kernel, outcome, reason, mesh_size=None):
        """One ``sharded_kernel_call`` decision. ``outcome``: "sharded" |
        "fallback" | "veto"; ``reason``: see docs/OBSERVABILITY.md table."""
        if not self.enabled:
            return
        with self._lock:
            key = (kernel, outcome, reason)
            self.dispatch_stats[key] = self.dispatch_stats.get(key, 0) + 1
            self._emit_jsonl({"name": f"dispatch/{kernel}", "kind": "counter",
                              "value": 1,
                              "tags": {"outcome": outcome, "reason": reason,
                                       "mesh_size": mesh_size}})

    def record_compile(self, program, seconds, topology=None, cache=None,
                       memory=None):
        """One AOT/jit compile: wall seconds + persistent-cache outcome
        ("hit" | "miss" | "unknown"). ``memory`` is the optional
        ``compiled.memory_analysis()`` byte breakdown (argument/output/temp/
        generated-code bytes)."""
        if not self.enabled:
            return
        with self._lock:
            entry = {"seconds": round(seconds, 3), "topology": topology,
                     "cache": cache or "unknown"}
            if memory:
                entry["memory"] = {k: int(v) for k, v in memory.items()
                                   if v is not None}
            self.compile_stats[program] = entry
            self.ledger_secs["compile"] += seconds
            tags = {"topology": topology, "cache": cache or "unknown"}
            if memory:
                tags["memory"] = entry["memory"]
            self._emit_jsonl({"name": f"compile/{program}", "kind": "seconds",
                              "value": seconds, "tags": tags})

    def record_build(self, rec, span_names):
        """One record of the build ledger (``telemetry/buildlog.py``: a
        program jax traced, lowered, compiled or loaded) into the compile
        sinks, under its name and, for a serving dispatch, its buckets.
        ``span_names``: the spans it was built under, innermost first; the
        first with a ledger category books these seconds as its own when it
        ends (a recompile inside ``fwd``), so they are taken from it here:
        a second belongs to one bucket (the summary reads a bucket that is
        in debt until its span ends as 0)."""
        if not self.enabled:
            return
        tags = rec["tags"]
        program = rec["program"]
        if "seq_bucket" in tags:
            program += f"[{tags['seq_bucket']}x{tags['chunk_bucket']}]"
        seconds = _buildlog.seconds_of(rec)
        self.record_compile(program, seconds,
                            cache=rec["cache"] if rec["cache"] != "off" else None)
        for name in span_names:
            cat = _ledger_category(name)
            if cat is not None:
                with self._lock:
                    self.ledger_secs[cat] -= seconds
                break

    # ------------------------------------------------------------------
    # serving stream (docs/OBSERVABILITY.md "Serving")
    # ------------------------------------------------------------------
    def record_hist(self, name, value, **tags):
        """One sample into the fixed-bucket log2 histogram ``name`` (values
        in seconds for latency hists, but unitless values work too). The
        aggregate — count/sum/min/max + per-bucket counts — feeds
        ``hist_percentiles`` and ``summary()["serving"]["histograms"]``."""
        if not self.enabled:
            return
        v = max(float(value), 0.0)
        with self._lock:
            self._record_hist_locked(name, v)
            # every histogram sample also folds into its ring time series,
            # so latency streams carry a trajectory (summary().timeseries)
            self._record_series_locked(name, _now() - self._epoch, v)
            self._emit_jsonl({"name": name, "kind": "hist", "value": v,
                              "tags": tags or {}})

    def _record_hist_locked(self, name, v):
        h = self.hist_stats.get(name)
        if h is None:
            h = self.hist_stats[name] = {
                "counts": [0] * HIST_BUCKETS, "count": 0, "sum": 0.0,
                "min": float("inf"), "max": 0.0}
        h["counts"][_hist_bucket(v)] += 1
        h["count"] += 1
        h["sum"] += v
        if v < h["min"]:
            h["min"] = v
        if v > h["max"]:
            h["max"] = v

    def hist_percentiles(self, name, qs=(0.5, 0.95, 0.99)):
        """Percentiles of histogram ``name`` as a tuple aligned with ``qs``,
        or None when the histogram has no samples."""
        with self._lock:
            h = self.hist_stats.get(name)
            if not h or not h["count"]:
                return None
            return tuple(_hist_quantile(h, q) for q in qs)

    # ------------------------------------------------------------------
    # time-series stream (telemetry/timeseries.py)
    # ------------------------------------------------------------------
    def _record_series_locked(self, name, rel_ts, v):
        ring = self.series.get(name)
        if ring is None:
            from deepspeed_tpu.telemetry.timeseries import SeriesRing
            ring = self.series[name] = SeriesRing()
        ring.record(rel_ts, v)

    def record_series(self, name, value, **tags):
        """One sample into the fixed-window ring time series ``name``
        (epoch-relative windows of ``timeseries.DEFAULT_WINDOW_S`` seconds,
        O(1) memory — old windows fall off the ring). Gauges and histograms
        feed their series implicitly; this is the entry point for free-form
        trajectories. Disabled: a single boolean check, zero clock reads."""
        if not self.enabled:
            return
        v = float(value)
        with self._lock:
            self._record_series_locked(name, _now() - self._epoch, v)
            self._emit_jsonl({"name": name, "kind": "series", "value": v,
                              "tags": tags or {}})

    def series_windows(self, name):
        """Live windows of series ``name`` (oldest first, see
        ``SeriesRing.windows``), or None when the series does not exist or
        telemetry is disabled."""
        if not self.enabled:
            return None
        with self._lock:
            ring = self.series.get(name)
            return None if ring is None else ring.windows()

    def _timeseries_summary(self):
        # caller holds self._lock
        return {name: ring.summary()
                for name, ring in sorted(self.series.items())}

    # ------------------------------------------------------------------
    # SLO classes (docs/SERVING.md "SLO classes")
    # ------------------------------------------------------------------
    def set_slo_classes(self, classes):
        """Install per-class latency targets
        (``{name: {"ttft_target_s": .., "tpot_target_s": ..,
        "attainment_target": 0.99}}``). Configuration like the sinks —
        survives ``reset()``; ``slo_observe`` consults it per sample."""
        cleaned = {}
        for name, spec in (classes or {}).items():
            spec = dict(spec or {})
            cleaned[str(name)] = {
                "ttft_target_s": (float(spec["ttft_target_s"])
                                  if spec.get("ttft_target_s") is not None
                                  else None),
                "tpot_target_s": (float(spec["tpot_target_s"])
                                  if spec.get("tpot_target_s") is not None
                                  else None),
                "attainment_target": float(
                    spec.get("attainment_target") or 0.99)}
        with self._lock:
            self.slo_classes = cleaned

    @staticmethod
    def _gauge_locked(gauges, name, v):
        g = gauges.get(name)
        if g is None:
            gauges[name] = [v, v]
        else:
            g[0] = v
            if v > g[1]:
                g[1] = v

    def slo_observe(self, slo_class, metric, value, n=1):
        """Record one latency observation against class ``slo_class``'s
        ``metric`` target ("ttft" | "tpot"): the per-class histogram
        (``serving/<metric>_s/<class>``), the attainment counters
        (``attained + violations == requests`` by construction), the
        request/violation ring series, and the rolling burn-rate /
        error-budget gauges derived from those series' windows (burn rate
        1.0 = violating at exactly the budgeted rate; see
        docs/OBSERVABILITY.md). Unknown classes and classes without a
        target for ``metric`` only get the per-class histogram."""
        if not self.enabled or not slo_class:
            return
        v = max(float(value), 0.0)
        rel = _now() - self._epoch
        with self._lock:
            self._record_hist_locked(f"serving/{metric}_s/{slo_class}", v)
            cls = self.slo_classes.get(slo_class)
            target = (cls or {}).get(f"{metric}_target_s")
            if target is None:
                return
            per = self.slo_stats.get(slo_class)
            if per is None:
                per = self.slo_stats[slo_class] = {}
            st = per.get(metric)
            if st is None:
                st = per[metric] = [0, 0]
            ok = v <= target
            st[0 if ok else 1] += n
            if not ok:
                _flightrec.record("slo", f"slo/{slo_class}/{metric}_violation",
                                  detail={"value": round(v, 6),
                                          "target_s": target, "n": n})
            # one JSONL line per observation so multi-host tooling
            # (scripts/trace_merge.py) can rebuild per-class attainment
            # per host from the raw streams
            self._emit_jsonl({"name": f"slo/{slo_class}/{metric}",
                              "kind": "slo", "value": v,
                              "tags": {"slo_class": slo_class,
                                       "metric": metric, "n": n,
                                       "attained": bool(ok),
                                       "target_s": target}})
            req_name = f"slo/{slo_class}/{metric}_requests"
            viol_name = f"slo/{slo_class}/{metric}_violations"
            self._record_series_locked(req_name, rel, float(n))
            if not ok:
                self._record_series_locked(viol_name, rel, float(n))
            budget = max(1.0 - cls["attainment_target"], 1e-9)
            req_ring = self.series[req_name]
            viol_ring = self.series.get(viol_name)
            # rolling burn rate: violation fraction over the LIVE windows,
            # over the budgeted violation fraction
            win_req = sum(w["count"] for w in req_ring.windows())
            win_viol = (sum(w["count"] for w in viol_ring.windows())
                        if viol_ring is not None else 0)
            burn = (win_viol / win_req / budget) if win_req else 0.0
            # lifetime error budget (total_count survives ring eviction,
            # so this stays run-wide on long replays)
            life_viol = viol_ring.total_count if viol_ring is not None else 0
            consumed = ((life_viol / req_ring.total_count / budget)
                        if req_ring.total_count else 0.0)
            self._gauge_locked(self.serving_gauges,
                               f"slo/{slo_class}/{metric}_burn_rate", burn)
            self._gauge_locked(
                self.serving_gauges,
                f"slo/{slo_class}/{metric}_error_budget_remaining",
                max(1.0 - consumed, 0.0))

    def slo_snapshot(self):
        """Per-class attainment snapshot (the live ``summary()["slo"]``
        section); {} when disabled or nothing observed."""
        if not self.enabled:
            return {}
        with self._lock:
            return self._slo_summary()

    def _slo_summary(self):
        # caller holds self._lock
        out = {}
        for cls, per in sorted(self.slo_stats.items()):
            spec = self.slo_classes.get(cls) or {}
            entry = {"targets": {k: spec.get(k) for k in
                                 ("ttft_target_s", "tpot_target_s")},
                     "attainment_target": spec.get("attainment_target"),
                     "metrics": {}}
            for metric, (ok, viol) in sorted(per.items()):
                total = ok + viol
                entry["metrics"][metric] = {
                    "requests": total, "attained": ok, "violations": viol,
                    "attainment": round(ok / total, 6) if total else 1.0}
            out[cls] = entry
        return out

    def serving_event(self, event, n=1, **tags):
        """Count one request-lifecycle event ("submitted", "finished",
        "evicted", "preempted", "resumed", ...) — surfaced in
        ``summary()["serving"]["requests"]``."""
        if not self.enabled:
            return
        with self._lock:
            self.serving_counters[event] = \
                self.serving_counters.get(event, 0) + n
            self._emit_jsonl({"name": f"serving/req/{event}",
                              "kind": "counter", "value": n,
                              "tags": tags or {}})

    def serving_gauge(self, name, value, **tags):
        """Record a scheduler/KV gauge sample: keeps last + peak, emits a
        Chrome counter track ("C" event) and a JSONL line. Host-side values
        only — callers must never sync the device to produce one."""
        if not self.enabled:
            return
        v = float(value)
        with self._lock:
            rel = _now() - self._epoch
            self._gauge_locked(self.serving_gauges, name, v)
            self._record_series_locked(name, rel, v)
            self.trace_events.append(
                {"name": name, "ph": "C", "cat": "serving",
                 "ts": round(rel * 1e6, 3),
                 "pid": os.getpid(), "args": {"value": v}})
            self._emit_jsonl({"name": name, "kind": "gauge", "value": v,
                              "tags": tags or {}})

    def gauge_value(self, name):
        """Last recorded value of serving gauge ``name`` (None when disabled
        or never recorded). O(1) dict read — this is how gauges become an
        INPUT: the scheduler's preemption precedence and the router's shed
        precedence read the live ``slo/<class>/<metric>_burn_rate`` gauges
        every round without touching histograms or series."""
        if not self.enabled:
            return None
        with self._lock:
            g = self.serving_gauges.get(name)
            return g[0] if g is not None else None

    def slo_class_targets(self):
        """The installed per-class SLO targets (``set_slo_classes`` shape);
        {} when none configured. Shared policy input for shed/preemption
        precedence (scheduler + fleet router)."""
        with self._lock:
            return dict(self.slo_classes)

    def record_request_phase(self, uid, phase, t0, dur=None, **args):
        """One lifecycle phase of request ``uid`` on its own Chrome-trace
        lane. Each uid gets a synthetic tid (named ``request/<uid>`` via a
        one-time thread_name metadata event); ``dur`` seconds makes a
        complete ("X") slice anchored at perf_counter time ``t0``, ``dur``
        None makes an instant ("i") marker (finish/evict/preempt/resume)."""
        if not self.enabled:
            return
        with self._lock:
            tid = self._request_lanes.get(uid)
            if tid is None:
                # lanes sort after the real-thread tids (0xffff mask above)
                tid = 0x10000 + (len(self._request_lanes) & 0xFFFF)
                self._request_lanes[uid] = tid
                self.trace_events.append(
                    {"name": "thread_name", "ph": "M", "pid": os.getpid(),
                     "tid": tid, "args": {"name": f"request/{uid}"}})
            ev = {"name": f"req/{phase}", "cat": "serving",
                  "ts": round((t0 - self._epoch) * 1e6, 3),
                  "pid": os.getpid(), "tid": tid,
                  "args": {"uid": uid, **args}}
            if dur is None:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = round(dur * 1e6, 3)
            self.trace_events.append(ev)
            self._emit_jsonl({"name": f"serving/phase/{phase}",
                              "kind": "span", "value": dur or 0.0,
                              "tags": {"uid": uid, **args}})

    def record_request_flow(self, uid, point, end=False, **args):
        """One hop of request ``uid``'s cross-replica causal chain as a
        Chrome flow event: the first call for a uid opens the chain (ph
        "s"), later calls step it (ph "t"), ``end=True`` terminates it (ph
        "f"). Every hop of a uid shares ONE flow id — derived from the uid,
        not a local sequence, so the same request on the prefill and decode
        replicas (different processes, different JSONLs) still shares the
        id after ``scripts/trace_merge.py`` folds the files, and the
        admit -> prefill -> handoff -> decode -> finish hops render as one
        arrowed chain across replica tracks."""
        if not self.enabled:
            return
        with self._lock:
            rel = _now() - self._epoch
            fid = self._flow_ids.get(uid)
            if fid is None:
                ph = "s"
                fid = self._flow_ids[uid] = int(uid)
            else:
                ph = "f" if end else "t"
            ev = {"name": "reqflow", "cat": "serving", "ph": ph, "id": fid,
                  "ts": round(rel * 1e6, 3), "pid": os.getpid(),
                  "tid": self._request_lanes.get(uid, 0),
                  "args": {"uid": uid, "point": point, **args}}
            if ph == "f":
                ev["bp"] = "e"
            self.trace_events.append(ev)
            self._emit_jsonl({"name": f"serving/flow/{point}",
                              "kind": "flow", "value": fid,
                              "tags": {"uid": uid, "flow_phase": ph,
                                       **args}})

    def _serving_summary(self):
        # caller holds self._lock
        hists = {}
        for name, h in sorted(self.hist_stats.items()):
            if h["count"]:
                p50, p95, p99 = (_hist_quantile(h, q)
                                 for q in (0.5, 0.95, 0.99))
                entry = {"count": h["count"],
                         "mean_s": round(h["sum"] / h["count"], 6),
                         "min_s": round(h["min"], 6),
                         "max_s": round(h["max"], 6),
                         "p50_s": round(p50, 6), "p95_s": round(p95, 6),
                         "p99_s": round(p99, 6)}
            else:
                entry = {"count": 0, "mean_s": 0.0, "min_s": 0.0,
                         "max_s": 0.0, "p50_s": 0.0, "p95_s": 0.0,
                         "p99_s": 0.0}
            hists[name] = entry
        gauges = {name: {"last": round(g[0], 6), "peak": round(g[1], 6)}
                  for name, g in sorted(self.serving_gauges.items())}
        return {"requests": {k: int(v) for k, v in
                             sorted(self.serving_counters.items())},
                "histograms": hists, "gauges": gauges}

    # ------------------------------------------------------------------
    # fleet stream (docs/OBSERVABILITY.md "Fleet")
    # ------------------------------------------------------------------
    def fleet_event(self, event, n=1, **tags):
        """Count one fleet-router admission outcome ("admitted", "queued",
        "rejected", "affinity_hit", ...) — surfaced in
        ``summary()["fleet"]["events"]``."""
        if not self.enabled:
            return
        with self._lock:
            self.fleet_counters[event] = \
                self.fleet_counters.get(event, 0) + n
            self._emit_jsonl({"name": f"fleet/req/{event}",
                              "kind": "counter", "value": n,
                              "tags": tags or {}})

    def fleet_gauge(self, name, value, **tags):
        """Fleet-level gauge (router queue depth, predicted TTFT, shed
        rate): keeps last + peak, emits a Chrome counter track and a JSONL
        line. Host-side values only, like ``serving_gauge``."""
        if not self.enabled:
            return
        v = float(value)
        with self._lock:
            rel = _now() - self._epoch
            self._gauge_locked(self.fleet_gauges, name, v)
            self._record_series_locked(name, rel, v)
            self.trace_events.append(
                {"name": name, "ph": "C", "cat": "fleet",
                 "ts": round(rel * 1e6, 3),
                 "pid": os.getpid(), "args": {"value": v}})
            self._emit_jsonl({"name": name, "kind": "gauge", "value": v,
                              "tags": tags or {}})

    def record_handoff(self, uid, pages, nbytes, seconds, src="prefill",
                       dst="decode", bound=None, wire_nbytes=None):
        """One prefill->decode KV page handoff: aggregates pages / bytes /
        latency into ``summary()["fleet"]["handoff"]`` (perf_gate checks
        the accounting identity ``pages_shipped == pages_bound``), records
        a ``fleet/handoff_s`` histogram sample, and drops a "handoff"
        slice on the request's Chrome-trace lane so the shipping cost sits
        visibly between the prefill and decode phases.

        ``nbytes`` is the device page footprint; ``wire_nbytes`` is what
        actually crosses (or would cross) the link — serialized int8+scale
        frame bytes, excluding transfer-bucket padding. They differ whenever
        pages are quantized, so the fleet payload's wire-vs-fp32 ratio must
        come from ``wire_bytes``, never ``bytes``."""
        if not self.enabled:
            return
        seconds = float(seconds)
        t_end = _now()
        with self._lock:
            h = self.fleet_handoff
            h["count"] += 1
            h["pages_shipped"] += int(pages)
            h["pages_bound"] += int(pages if bound is None else bound)
            h["bytes"] += int(nbytes)
            h["wire_bytes"] += int(nbytes if wire_nbytes is None
                                   else wire_nbytes)
            h["total_s"] += seconds
            self._emit_jsonl({"name": "fleet/handoff", "kind": "seconds",
                              "value": seconds,
                              "tags": {"uid": uid, "pages": int(pages),
                                       "bytes": int(nbytes),
                                       "wire_bytes": int(
                                           nbytes if wire_nbytes is None
                                           else wire_nbytes),
                                       "src": src, "dst": dst}})
        self.record_hist("fleet/handoff_s", seconds)
        self.record_request_phase(uid, "handoff", t_end - seconds, seconds,
                                  pages=int(pages), bytes=int(nbytes),
                                  src=src, dst=dst)
        self.record_request_flow(uid, "handoff", pages=int(pages))

    def _fleet_summary(self):
        # caller holds self._lock
        h = self.fleet_handoff
        gauges = {name: {"last": round(g[0], 6), "peak": round(g[1], 6)}
                  for name, g in sorted(self.fleet_gauges.items())}
        return {"events": {k: int(v) for k, v in
                           sorted(self.fleet_counters.items())},
                "gauges": gauges,
                "handoff": {"count": int(h["count"]),
                            "pages_shipped": int(h["pages_shipped"]),
                            "pages_bound": int(h["pages_bound"]),
                            "bytes": int(h["bytes"]),
                            "wire_bytes": int(h["wire_bytes"]),
                            "total_s": round(h["total_s"], 6)}}

    # ------------------------------------------------------------------
    # moe stream (docs/OBSERVABILITY.md "MoE")
    # ------------------------------------------------------------------
    def moe_gauge(self, name, value, **tags):
        """Record one expert-routing gauge sample ("moe/expert_load_max_frac",
        "moe/drop_rate", "moe/a2a_wire_bytes", ...): keeps last + peak, emits
        a Chrome counter track and a JSONL line. Host-side concrete values
        only — called post-step on fetched routing stats, never at trace
        time."""
        if not self.enabled:
            return
        v = float(value)
        with self._lock:
            rel = _now() - self._epoch
            self._gauge_locked(self.moe_gauges, name, v)
            self._record_series_locked(name, rel, v)
            self.trace_events.append(
                {"name": name, "ph": "C", "cat": "moe",
                 "ts": round(rel * 1e6, 3),
                 "pid": os.getpid(), "args": {"value": v}})
            self._emit_jsonl({"name": name, "kind": "gauge", "value": v,
                              "tags": tags or {}})

    def _moe_summary(self):
        # caller holds self._lock
        gauges = {name: {"last": round(g[0], 6), "peak": round(g[1], 6)}
                  for name, g in sorted(self.moe_gauges.items())}
        return {"gauges": gauges}

    # ------------------------------------------------------------------
    # memory stream
    # ------------------------------------------------------------------
    def record_memory(self, point, stats=None, device_index=0, **tags):
        """Record one HBM occupancy sample at a named ``point`` ("step",
        "ckpt/save", "watchdog_stall", ...). When ``stats`` is None the
        accelerator is sampled (one ``memory_stats()`` call — enabled path
        only; disabled is a single boolean check with zero device syncs).
        Returns the stats dict recorded, or None when disabled/off."""
        if not self.enabled or not self.memory_enabled:
            return None
        if stats is None:
            stats = self._read_memory_stats(device_index)
        if not stats:
            return None
        in_use = int(stats.get("bytes_in_use", 0) or 0)
        peak = int(stats.get("peak_bytes_in_use", in_use) or in_use)
        with self._lock:
            sample = {"point": point, "bytes_in_use": in_use,
                      "peak_bytes_in_use": peak,
                      "bytes_limit": int(stats.get("bytes_limit", 0) or 0)}
            if tags:
                sample["tags"] = tags
            self.memory_samples.append(sample)
            if peak > self.memory_peak:
                self.memory_peak = peak
            # Chrome counter track: one "C" event per sample
            self.trace_events.append(
                {"name": "hbm_bytes_in_use", "ph": "C", "cat": "memory",
                 "ts": round((_now() - self._epoch) * 1e6, 3),
                 "pid": os.getpid(),
                 "args": {"bytes_in_use": in_use}})
            self._emit_jsonl({"name": f"memory/{point}", "kind": "bytes",
                              "value": in_use,
                              "tags": {**(tags or {}),
                                       "peak_bytes_in_use": peak}})
        _flightrec.record("memory", f"memory/{point}",
                          detail={"bytes_in_use": in_use,
                                  "peak_bytes_in_use": peak})
        return stats

    def sample_memory(self, point, device_index=0, **tags):
        """Read accelerator memory stats and return them, recording through
        the memory stream when enabled. Unlike ``record_memory`` this ALWAYS
        reads the device (callers like ``see_memory_usage`` and the ragged
        KV-cache budget need the numbers even with telemetry off)."""
        stats = self._read_memory_stats(device_index)
        if self.enabled and self.memory_enabled and stats:
            self.record_memory(point, stats=stats,
                               device_index=device_index, **tags)
        return stats

    @staticmethod
    def _read_memory_stats(device_index=0):
        try:
            from deepspeed_tpu.accelerator import get_accelerator
            return get_accelerator().memory_stats(device_index) or {}
        except Exception:
            return {}

    def maybe_oom_postmortem(self, exc, top_n=10):
        """If ``exc`` looks like an HBM exhaustion error, dump an OOM
        post-mortem (top-N live buffers by size) through the Fault/* path.
        Returns the report dict, or None when not an OOM / disabled."""
        if not self.enabled:
            return None
        msg = str(exc)
        name = type(exc).__name__
        if "RESOURCE_EXHAUSTED" not in msg and \
                "ResourceExhausted" not in name and \
                "out of memory" not in msg.lower():
            return None
        return self.oom_postmortem(error=msg, top_n=top_n)

    def oom_postmortem(self, error=None, top_n=10):
        """Unconditional OOM post-mortem: snapshot HBM stats and the top-N
        ``jax.live_arrays()`` by size (shape/dtype/nbytes/sharding)."""
        if not self.enabled:
            return None
        buffers = []
        try:
            import jax
            arrs = sorted(jax.live_arrays(),
                          key=lambda a: getattr(a, "nbytes", 0),
                          reverse=True)
            for a in arrs[:top_n]:
                try:
                    buffers.append({
                        "shape": list(getattr(a, "shape", ()) or ()),
                        "dtype": str(getattr(a, "dtype", "?")),
                        "nbytes": int(getattr(a, "nbytes", 0) or 0),
                        "sharding": str(getattr(a, "sharding", None))})
                except Exception:
                    continue
        except Exception:
            pass
        stats = self._read_memory_stats()
        report = {"error": error,
                  "live_buffer_count": len(buffers),
                  "live_bytes_total": sum(b["nbytes"] for b in buffers),
                  "top_buffers": buffers,
                  "memory_stats": stats}
        with self._lock:
            self.last_oom_report = report
        self.count("Fault/oom", error=(error or "")[:200],
                   live_buffers=len(buffers))
        if stats:
            self.record_memory("oom", stats=stats)
        # an OOM is an abnormal path: leave the incident artifact (no-op
        # when no postmortem destination is configured)
        _flightrec.flush_bundle("oom", detail=(error or "")[:300],
                                extra={"oom_report": {
                                    "live_buffer_count": len(buffers),
                                    "live_bytes_total": report[
                                        "live_bytes_total"]}})
        return report

    # ------------------------------------------------------------------
    # goodput / MFU ledger
    # ------------------------------------------------------------------
    def set_model_flops(self, flops_per_step=None, peak_flops=None):
        """Set the MFU numerator (model FLOPs per optimizer step across all
        chips) and denominator (aggregate peak FLOP/s). The flops profiler
        calls this automatically from ``profile_engine_step``; the peak
        defaults to a per-device-kind table when unset."""
        with self._lock:
            if flops_per_step is not None:
                self._flops_per_step = float(flops_per_step)
            if peak_flops is not None:
                self._peak_flops = float(peak_flops)

    def ledger_add(self, category, seconds):
        """Charge ``seconds`` of wall time to a ledger category directly —
        used by non-span sources (watchdog stall idle time)."""
        if not self.enabled or seconds <= 0:
            return
        if category not in self.ledger_secs:
            return
        with self._lock:
            self.ledger_secs[category] += seconds

    def ledger_step(self, step=None, flops=None):
        """Mark one optimizer-step boundary: computes the per-step interval,
        updates the per-step and rolling ``mfu``/``goodput`` gauges and
        records them. Returns (mfu, goodput) or None when disabled."""
        if not self.enabled:
            return None
        now = _now()
        if flops is None:
            flops = self._flops_per_step
        peak = self._peak_flops or _default_peak_flops()
        with self._lock:
            last = self._ledger_last_step_ts
            self._ledger_last_step_ts = now
            self._ledger_steps += 1
            if last is not None and flops and peak:
                dt = now - last
                if dt > 0:
                    self._mfu_last = flops / dt / peak
            wall = now - self._ledger_epoch
            if wall > 0 and flops and peak and self._ledger_steps > 0:
                self._mfu_roll = flops * self._ledger_steps / wall / peak
            goodput = (self.ledger_secs["compute"] / wall) if wall > 0 else 0.0
            mfu, roll = self._mfu_last, self._mfu_roll
        self.record("mfu", round(mfu, 6), kind="gauge",
                    rolling=round(roll, 6), step=step)
        self.record("goodput", round(goodput, 6), kind="gauge", step=step)
        return mfu, goodput

    def _ledger_summary(self):
        # caller holds self._lock
        wall = max(_now() - self._ledger_epoch, 0.0)
        secs = {k: round(max(v, 0.0), 6) for k, v in self.ledger_secs.items()}
        accounted = sum(secs.values())
        secs["idle"] = round(max(wall - accounted, 0.0), 6)
        goodput = (self.ledger_secs["compute"] / wall) if wall > 0 else 0.0
        return {"wall_s": round(wall, 6), "seconds": secs,
                "steps": self._ledger_steps,
                "flops_per_step": self._flops_per_step,
                "peak_flops": self._peak_flops or _default_peak_flops(),
                "mfu": round(self._mfu_last, 6),
                "mfu_rolling": round(self._mfu_roll, 6),
                "goodput": round(goodput, 6),
                # host-timed wall inside compiled step() — opaque to the
                # ledger: "compute" here includes any comm XLA overlapped
                # (or failed to overlap) under it. Only an attached overlap
                # report (summary()["overlap"]) splits it. See
                # docs/OBSERVABILITY.md "Overlap & critical path".
                "in_jit_opaque_s": round(
                    self.ledger_secs.get("compute", 0.0), 6)}

    # ------------------------------------------------------------------
    # overlap report (telemetry/overlap.py)
    # ------------------------------------------------------------------
    def attach_overlap(self, report):
        """Attach a device-timeline overlap report (built by
        :mod:`deepspeed_tpu.telemetry.overlap` from a profiler trace or the
        chip-free analytic mode) so it rides ``summary()["overlap"]``, the
        bench payloads and the perf gate. Validates structurally; raises
        ``ValueError`` on a malformed report. Returns the report, or None
        when telemetry is disabled (constant-time no-op)."""
        if not self.enabled:
            return None
        from deepspeed_tpu.telemetry import overlap as _overlap
        errs = _overlap.validate_report(report)
        if errs:
            raise ValueError("invalid overlap report: " + "; ".join(errs))
        with self._lock:
            self.overlap_report = report
            self.record("overlap/exposed_comm_s",
                        report["exposed_comm_s"], kind="gauge",
                        mode=report.get("mode", "trace"),
                        overlap_fraction=report["overlap_fraction"])
        return report

    # ------------------------------------------------------------------
    # exporters
    # ------------------------------------------------------------------
    def _emit_jsonl(self, obj):
        # callers hold self._lock
        if not self.jsonl_path:
            return
        if self._jsonl_fh is None:
            d = os.path.dirname(self.jsonl_path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._jsonl_fh = open(self.jsonl_path, "a")
        obj["ts"] = round(_now() - self._epoch, 6)
        # multi-host identity for scripts/trace_merge.py
        obj["host"] = self.host
        obj["pid"] = os.getpid()
        obj["run_id"] = self.run_id
        self._jsonl_fh.write(json.dumps(obj) + "\n")
        self._jsonl_fh.flush()

    def export_chrome_trace(self, path=None):
        """Write accumulated spans as a Chrome-trace file (the
        ``{"traceEvents": [...]}`` object form — load in ``chrome://tracing``
        or https://ui.perfetto.dev). Returns the path written."""
        path = path or self.chrome_trace_path
        if not path:
            raise ValueError("no chrome_trace_path configured")
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with self._lock:
            meta = [{"name": "process_name", "ph": "M", "pid": os.getpid(),
                     "args": {"name": f"{self.host}:{os.getpid()}"}}]
            doc = {"traceEvents": meta + list(self.trace_events),
                   "displayTimeUnit": "ms",
                   "otherData": {"producer": "deepspeed_tpu.telemetry",
                                 "host": self.host,
                                 "run_id": self.run_id}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def summary(self):
        """One JSON-able dict aggregating every stream — embedded into
        BENCH_*.json / the AOT artifact (schema:
        ``deepspeed_tpu/telemetry/summary.schema.json``)."""
        if not self.enabled:
            return {"enabled": False}
        with self._lock:
            spans = {name: {"count": c, "total_s": round(tot, 6),
                            "mean_s": round(tot / c, 6) if c else 0.0}
                     for name, (c, tot) in sorted(self.span_stats.items())}
            comm = {}
            total_bytes = 0
            total_wire_bytes = 0
            for (op, axis), (c, nb, secs, algbw, busbw, wb) in \
                    sorted(self.comm_stats.items()):
                comm.setdefault(op, {})[axis] = {
                    "count": c, "bytes": nb, "wire_bytes": wb,
                    "total_s": round(secs, 6),
                    "algbw_gbs": round(algbw / c, 4) if c else 0.0,
                    "busbw_gbs": round(busbw / c, 4) if c else 0.0}
                total_bytes += nb
                total_wire_bytes += wb
            dispatch = {}
            for (kernel, outcome, reason), c in \
                    sorted(self.dispatch_stats.items()):
                dispatch.setdefault(kernel, {}).setdefault(
                    outcome, {})[reason] = c
            compile_sec = dict(self.compile_stats)
            hits = sum(1 for v in compile_sec.values()
                       if v.get("cache") == "hit")
            misses = sum(1 for v in compile_sec.values()
                         if v.get("cache") == "miss")
            counters = {name: {",".join(f"{k}={v}" for k, v in key) or "_": n
                               for key, n in per.items()}
                        for name, per in sorted(self.counters.items())}
            memory = {"peak_bytes": int(self.memory_peak),
                      "sample_count": len(self.memory_samples),
                      "last_bytes_in_use": int(
                          self.memory_samples[-1]["bytes_in_use"])
                      if self.memory_samples else 0,
                      "oom": self.last_oom_report is not None}
            out = {"enabled": True, "spans": spans,
                   "comm": {"ops": comm, "total_bytes": total_bytes,
                            "total_wire_bytes": total_wire_bytes},
                   "dispatch": dispatch,
                   "compile": {"programs": compile_sec,
                               "cache_hits": hits, "cache_misses": misses},
                   "counters": counters,
                   "memory": memory,
                   "ledger": self._ledger_summary(),
                   "serving": self._serving_summary(),
                   "fleet": self._fleet_summary(),
                   "moe": self._moe_summary(),
                   "timeseries": self._timeseries_summary(),
                   "slo": self._slo_summary()}
            if self.overlap_report is not None:
                out["overlap"] = self.overlap_report
            return out

    def format_summary(self):
        """DeepSpeed-style fixed-width tables over every stream."""
        s = self.summary()
        if not s.get("enabled"):
            return "telemetry disabled"
        lines = []
        if s["spans"]:
            lines.append(f"{'Span':<24}{'Count':<10}{'Total(ms)':<14}"
                         f"{'Mean(ms)':<14}")
            for name, st in s["spans"].items():
                lines.append(f"{name:<24}{st['count']:<10}"
                             f"{st['total_s']*1e3:<14.2f}"
                             f"{st['mean_s']*1e3:<14.2f}")
        if s["comm"]["ops"]:
            lines.append(f"{'Comm. Op':<20}{'Axis':<10}{'Count':<10}"
                         f"{'Bytes':<14}{'algbw(GB/s)':<14}{'busbw(GB/s)':<14}")
            for op, per_axis in s["comm"]["ops"].items():
                for axis, st in per_axis.items():
                    lines.append(f"{op:<20}{axis:<10}{st['count']:<10}"
                                 f"{st['bytes']:<14}{st['algbw_gbs']:<14.2f}"
                                 f"{st['busbw_gbs']:<14.2f}")
            lines.append(f"comm total bytes: {s['comm']['total_bytes']}")
        if s["dispatch"]:
            lines.append(f"{'Kernel':<24}{'Outcome':<12}{'Reason':<16}"
                         f"{'Count':<8}")
            for kernel, outs in s["dispatch"].items():
                for outcome, reasons in outs.items():
                    for reason, c in reasons.items():
                        lines.append(f"{kernel:<24}{outcome:<12}"
                                     f"{reason:<16}{c:<8}")
        if s["compile"]["programs"]:
            lines.append(f"{'Program':<32}{'Compile(s)':<12}{'Cache':<10}")
            for name, st in s["compile"]["programs"].items():
                lines.append(f"{name:<32}{st['seconds']:<12}"
                             f"{st['cache']:<10}")
        led = s["ledger"]
        if led["wall_s"] > 0:
            lines.append(f"{'Ledger':<14}{'Seconds':<12}{'Share':<8}")
            for cat in LEDGER_CATEGORIES:
                sec = led["seconds"].get(cat, 0.0)
                share = sec / led["wall_s"] if led["wall_s"] else 0.0
                lines.append(f"{cat:<14}{sec:<12.3f}{share:<8.1%}")
            lines.append(f"wall: {led['wall_s']:.3f}s  steps: {led['steps']}"
                         f"  mfu: {led['mfu_rolling']:.4f}"
                         f"  goodput: {led['goodput']:.4f}")
        mem = s["memory"]
        if mem["sample_count"]:
            lines.append(f"hbm peak: {mem['peak_bytes']} bytes"
                         f"  ({mem['sample_count']} samples"
                         f"{', OOM observed' if mem['oom'] else ''})")
        ov = s.get("overlap")
        if ov:
            lines.append(
                f"overlap[{ov['mode']}]: comm {ov['comm_s']*1e3:.2f} ms  "
                f"exposed {ov['exposed_comm_s']*1e3:.2f} ms "
                f"({ov['exposed_fraction']:.1%})  "
                f"overlap {ov['overlap_fraction']:.1%}")
        srv = s.get("serving", {})
        if srv.get("histograms"):
            lines.append(f"{'Serving hist':<26}{'Count':<8}{'p50(ms)':<12}"
                         f"{'p95(ms)':<12}{'p99(ms)':<12}")
            for name, st in srv["histograms"].items():
                lines.append(f"{name:<26}{st['count']:<8}"
                             f"{st['p50_s']*1e3:<12.2f}"
                             f"{st['p95_s']*1e3:<12.2f}"
                             f"{st['p99_s']*1e3:<12.2f}")
        if srv.get("requests"):
            lines.append("requests: " + "  ".join(
                f"{k}={v}" for k, v in srv["requests"].items()))
        for cls, e in s.get("slo", {}).items():
            for metric, m in e["metrics"].items():
                lines.append(
                    f"slo[{cls}/{metric}]: {m['attained']}/{m['requests']} "
                    f"attained ({m['attainment']:.1%}, "
                    f"{m['violations']} violations)")
        flt = s.get("fleet", {})
        if flt.get("events"):
            lines.append("fleet: " + "  ".join(
                f"{k}={v}" for k, v in flt["events"].items()))
        if flt.get("handoff", {}).get("count"):
            h = flt["handoff"]
            lines.append(f"handoffs: {h['count']}  pages: "
                         f"{h['pages_shipped']}->{h['pages_bound']}  "
                         f"bytes: {h['bytes']}  total: {h['total_s']*1e3:.2f} ms")
        return "\n".join(lines) if lines else "telemetry: no samples"

    def log_summary(self, print_log=True):
        out = self.format_summary()
        if print_log:
            from deepspeed_tpu.utils.logging import logger
            logger.info("\n" + out)
        return out

    def monitor_events(self, step):
        """Aggregates as Monitor event tuples (name, value, step) — the
        MonitorMaster fan-out bridge, drained by the engine at its
        steps_per_print cadence."""
        if not self.enabled:
            return []
        s = self.summary()
        p = self.monitor_prefix
        events = []
        for name, st in s["spans"].items():
            events.append((f"{p}Span/{name}_mean_ms",
                           st["mean_s"] * 1e3, step))
        if s["comm"]["total_bytes"]:
            events.append((f"{p}Comm/total_bytes",
                           s["comm"]["total_bytes"], step))
        for kernel, outs in s["dispatch"].items():
            for outcome, reasons in outs.items():
                events.append((f"{p}Dispatch/{kernel}/{outcome}",
                               sum(reasons.values()), step))
        if s["memory"]["peak_bytes"]:
            events.append((f"{p}Memory/peak_hbm_bytes",
                           s["memory"]["peak_bytes"], step))
        led = s["ledger"]
        if led["steps"]:
            events.append((f"{p}Ledger/mfu", led["mfu_rolling"], step))
            events.append((f"{p}Ledger/goodput", led["goodput"], step))
        ov = s.get("overlap")
        if ov:
            events.append((f"{p}Overlap/exposed_comm_s",
                           ov["exposed_comm_s"], step))
            events.append((f"{p}Overlap/overlap_fraction",
                           ov["overlap_fraction"], step))
        srv = s.get("serving", {})
        for name, st in srv.get("histograms", {}).items():
            if st["count"]:
                leaf = name.rsplit("/", 1)[-1]
                events.append((f"{p}Serving/{leaf}_p50_ms",
                               st["p50_s"] * 1e3, step))
                events.append((f"{p}Serving/{leaf}_p99_ms",
                               st["p99_s"] * 1e3, step))
        for name, g in srv.get("gauges", {}).items():
            leaf = name.rsplit("/", 1)[-1]
            events.append((f"{p}Serving/{leaf}", g["last"], step))
        flt = s.get("fleet", {})
        for name, v in flt.get("events", {}).items():
            events.append((f"{p}Fleet/{name}", v, step))
        for name, g in flt.get("gauges", {}).items():
            leaf = name.rsplit("/", 1)[-1]
            events.append((f"{p}Fleet/{leaf}", g["last"], step))
        if flt.get("handoff", {}).get("count"):
            events.append((f"{p}Fleet/handoff_bytes",
                           flt["handoff"]["bytes"], step))
        for cls, e in s.get("slo", {}).items():
            for metric, m in e["metrics"].items():
                events.append((f"{p}SLO/{cls}/{metric}_attainment",
                               m["attainment"], step))
        return events
