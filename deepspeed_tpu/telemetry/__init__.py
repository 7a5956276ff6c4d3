"""Unified telemetry: step tracing, collective-bandwidth accounting,
kernel-dispatch counters, compile timing, HBM memory accounting, a
goodput/MFU wall-time ledger, and Chrome-trace export.

Module-level functions delegate to ONE process-global :class:`Telemetry`
pipeline so every layer (engine, comm, ops registry, AOT scripts, benches)
feeds the same sinks::

    from deepspeed_tpu import telemetry

    telemetry.configure(enabled=True, jsonl_path="metrics.jsonl",
                        chrome_trace_path="trace.json")
    with telemetry.span("fwd", step=3):   # ds/fwd in any profiler capture
        loss = step(batch)                # never waits for the device
    telemetry.record("loss", float(loss), kind="gauge", step=1)
    print(telemetry.log_summary())
    telemetry.export_chrome_trace()

Disabled (the default), every call here but ``span`` is a constant-time
no-op — no jax sync, no file I/O — and a span is only its profiler annotation. See docs/OBSERVABILITY.md for config keys, the exporter
matrix and the dispatch reason-code table.

Two things record all the same, bounded and stdlib-cheap: the flight
recorder (``flightrec``) and the **build ledger** (``buildlog``): one record
a program jax traced, lowered, compiled or loaded, with the seconds of each
part, the persistent cache's answer and the span it was built under, fed by
``jax.monitoring``'s own compile events (``build_log()``, ``build_count()``,
``build_ms(n)``). Steady state pays nothing for it: a call that finds its
executable emits no event. Enabled, each record also goes through
``record_compile``, so the compile stream and the goodput ledger's
``compile`` category hold every jit build.
"""

from deepspeed_tpu.telemetry import buildlog, flightrec  # noqa: F401
from deepspeed_tpu.telemetry.buildlog import (  # noqa: F401
    build_count, build_log, build_ms)
from deepspeed_tpu.telemetry.core import Telemetry  # noqa: F401

_GLOBAL = Telemetry()
buildlog.install(sink=_GLOBAL)     # the build ledger: always on, enabled or not


def get_telemetry():
    """The process-global pipeline object."""
    return _GLOBAL


def enabled():
    return _GLOBAL.enabled


def configure(config=None, **kwargs):
    """Configure the global pipeline (see :meth:`Telemetry.configure`)."""
    _GLOBAL.configure(config=config, **kwargs)


def record(name, value, kind="gauge", **tags):
    _GLOBAL.record(name, value, kind=kind, **tags)


def count(name, n=1, **tags):
    _GLOBAL.count(name, n=n, **tags)


def span(name, **tags):
    return _GLOBAL.span(name, **tags)


def span_begin(name, **tags):
    return _GLOBAL.span_begin(name, **tags)


def record_comm(op, nbytes, seconds, axis=None, traced=False,
                wire_bytes=None):
    _GLOBAL.record_comm(op, nbytes, seconds, axis=axis, traced=traced,
                        wire_bytes=wire_bytes)


def record_dispatch(kernel, outcome, reason, mesh_size=None):
    _GLOBAL.record_dispatch(kernel, outcome, reason, mesh_size=mesh_size)


def record_compile(program, seconds, topology=None, cache=None, memory=None):
    _GLOBAL.record_compile(program, seconds, topology=topology, cache=cache,
                           memory=memory)


def record_hist(name, value, **tags):
    """One sample into a fixed-bucket log2 histogram (serving latencies)."""
    _GLOBAL.record_hist(name, value, **tags)


def hist_percentiles(name, qs=(0.5, 0.95, 0.99)):
    """Percentile tuple for histogram ``name`` (None when empty)."""
    return _GLOBAL.hist_percentiles(name, qs=qs)


def serving_event(event, n=1, **tags):
    """Count one request-lifecycle event (submitted/finished/evicted/...)."""
    _GLOBAL.serving_event(event, n=n, **tags)


def serving_gauge(name, value, **tags):
    """Record a scheduler/KV gauge sample (last + peak + counter track)."""
    _GLOBAL.serving_gauge(name, value, **tags)


def gauge_value(name):
    """Last value of serving gauge ``name`` (None when disabled/absent) —
    the O(1) read that turns burn-rate gauges into a scheduler input."""
    return _GLOBAL.gauge_value(name)


def slo_class_targets():
    """Installed per-class SLO targets ({} when none configured)."""
    return _GLOBAL.slo_class_targets()


def record_request_phase(uid, phase, t0, dur=None, **args):
    """One request-lifecycle phase on the request's Chrome-trace lane."""
    _GLOBAL.record_request_phase(uid, phase, t0, dur=dur, **args)


def record_request_flow(uid, point, end=False, **args):
    """One hop of a request's cross-replica flow chain (Chrome flow event:
    first call opens with ph "s", later ones step "t", ``end=True`` "f")."""
    _GLOBAL.record_request_flow(uid, point, end=end, **args)


def record_series(name, value, **tags):
    """One sample into the fixed-window ring time series ``name``."""
    _GLOBAL.record_series(name, value, **tags)


def series_windows(name):
    """Live windows of series ``name`` (None when absent/disabled)."""
    return _GLOBAL.series_windows(name)


def set_slo_classes(classes):
    """Install per-class SLO latency targets (survives ``reset()``)."""
    _GLOBAL.set_slo_classes(classes)


def slo_observe(slo_class, metric, value, n=1):
    """One latency observation against an SLO class target ("ttft"/"tpot"):
    per-class histogram, attainment counters, burn-rate gauges."""
    _GLOBAL.slo_observe(slo_class, metric, value, n=n)


def slo_snapshot():
    """Live per-class attainment snapshot ({} when disabled)."""
    return _GLOBAL.slo_snapshot()


def fleet_event(event, n=1, **tags):
    """Count one fleet-router admission outcome (admitted/queued/rejected)."""
    _GLOBAL.fleet_event(event, n=n, **tags)


def fleet_gauge(name, value, **tags):
    """Record a fleet-level gauge (queue depth, predicted TTFT, shed rate)."""
    _GLOBAL.fleet_gauge(name, value, **tags)


def moe_gauge(name, value, **tags):
    """Record an expert-routing gauge (load fraction, drop rate, a2a wire)."""
    _GLOBAL.moe_gauge(name, value, **tags)


def record_moe_step(exp_counts, total_routed, dropped=0, a2a_wire_bytes=None):
    """Record one step's expert-routing stats as the three standard MoE
    gauges. ``exp_counts``: per-expert PRE-drop assignment counts (host-side
    concrete values — fetch before calling, never at trace time);
    ``total_routed``: total (token, expert) assignments; ``dropped``: count
    that exceeded capacity (0 on the dropless path); ``a2a_wire_bytes``: the
    step's expert all-to-all wire bytes when known."""
    if not _GLOBAL.enabled:
        return
    counts = [float(c) for c in exp_counts]
    total = float(total_routed) or 1.0
    _GLOBAL.moe_gauge("moe/expert_load_max_frac",
                      max(counts) / total if counts else 0.0)
    _GLOBAL.moe_gauge("moe/drop_rate", float(dropped) / total)
    if a2a_wire_bytes is not None:
        _GLOBAL.moe_gauge("moe/a2a_wire_bytes", float(a2a_wire_bytes))


def record_handoff(uid, pages, nbytes, seconds, src="prefill", dst="decode",
                   bound=None, wire_nbytes=None):
    """Record one prefill->decode KV page handoff (bytes/latency/pages;
    ``wire_nbytes`` = TRUE serialized wire bytes vs device page bytes)."""
    _GLOBAL.record_handoff(uid, pages, nbytes, seconds, src=src, dst=dst,
                           bound=bound, wire_nbytes=wire_nbytes)


def record_memory(point, stats=None, device_index=0, **tags):
    """Record one HBM occupancy sample (no-op + None when disabled)."""
    return _GLOBAL.record_memory(point, stats=stats,
                                 device_index=device_index, **tags)


def sample_memory(point, device_index=0, **tags):
    """Read accelerator memory stats (always) and record them (when
    enabled). Returns the stats dict."""
    return _GLOBAL.sample_memory(point, device_index=device_index, **tags)


def maybe_oom_postmortem(exc, top_n=10):
    """Dump an OOM post-mortem if ``exc`` is an HBM-exhaustion error."""
    return _GLOBAL.maybe_oom_postmortem(exc, top_n=top_n)


def flight_record(kind, name, detail=None, ts=None):
    """Append one event to the always-on flight-recorder ring
    (telemetry/flightrec.py) — records even when telemetry is disabled."""
    return flightrec.record(kind, name, detail=detail, ts=ts)


def flush_postmortem(reason, **kwargs):
    """Flush a postmortem bundle (see :func:`flightrec.flush_bundle`);
    returns the bundle path, or None when no destination is configured."""
    return flightrec.flush_bundle(reason, **kwargs)


def oom_postmortem(error=None, top_n=10):
    return _GLOBAL.oom_postmortem(error=error, top_n=top_n)


def set_model_flops(flops_per_step=None, peak_flops=None):
    _GLOBAL.set_model_flops(flops_per_step=flops_per_step,
                            peak_flops=peak_flops)


def ledger_add(category, seconds):
    _GLOBAL.ledger_add(category, seconds)


def ledger_step(step=None, flops=None):
    return _GLOBAL.ledger_step(step=step, flops=flops)


def attach_overlap(report):
    """Attach a device-timeline overlap report (see telemetry/overlap.py)
    so it rides ``summary()["overlap"]`` and the perf gate. Returns None
    when telemetry is disabled."""
    return _GLOBAL.attach_overlap(report)


def summary():
    return _GLOBAL.summary()


def format_summary():
    return _GLOBAL.format_summary()


def log_summary(print_log=True):
    return _GLOBAL.log_summary(print_log=print_log)


def monitor_events(step):
    return _GLOBAL.monitor_events(step)


def export_chrome_trace(path=None):
    return _GLOBAL.export_chrome_trace(path)


def reset():
    _GLOBAL.reset()


def close():
    _GLOBAL.close()
