"""Automated perf gate: fail loudly on throughput/MFU/HBM/compile/serving
regression.

Compares a CANDIDATE measurement (a ``BENCH_*.json`` payload, a
``telemetry.summary()`` dict, or a ``BASELINE.json``-style doc) against a
BASELINE of any of the same shapes, with configurable relative thresholds:

    python scripts/perf_gate.py --baseline BASELINE.json \
        --candidate BENCH_r07.json \
        --max-tokens-drop 0.10 --max-mfu-drop 0.10 \
        --max-hbm-growth 0.10 --max-compile-growth 0.50

Serving-path metrics (``bench_serving.py --replay`` payloads or a summary's
``serving`` section) gate the latency direction: TTFT/TPOT p50+p99 and peak
KV-block occupancy regress when they GROW (``--max-ttft-growth``,
``--max-tpot-growth``, ``--max-kv-occupancy-growth``). Overlap reports
(``summary()["overlap"]`` / ``scripts/overlap_report.py`` payloads) gate
exposed-comm seconds the same way (``--max-exposed-growth``) and are
shape-validated on every input (finite, exposure <= comm total, fractions
in [0, 1]).

Only metrics present on BOTH sides are compared (an empty baseline —
``BASELINE.json`` before any published number — passes with a warning, so
the gate can be wired into CI before the first on-hardware run). Exit codes:

    0  pass (no compared metric regressed beyond its threshold)
    2  malformed input (unreadable file, schema violation, no JSON)
    3  regression (at least one metric beyond threshold)

``--dry-run`` validates inputs only — parses both docs, validates any
embedded telemetry summary against ``telemetry/summary.schema.json``, and
schema-checks the checked-in kernel tuning tables
(``deepspeed_tpu/autotuning/tables/``: valid per
``kernel_table.validate_table`` AND covering every ``BENCH_SHAPES`` bucket)
and drives the overlap analyzer jax-free over a fixed analytic schedule
(``check_overlap_analytic``), and re-derives the checked-in scheduled
overlap baseline (``onchip_results/overlap_analytic_baseline.json``)
jax-free, requiring the scheduled exposed seconds to reproduce and to sit
>= 30% below its serialized worst case (``check_overlap_schedule``), and
validates the checked-in shared-prefix replay baseline
(``onchip_results/serving_prefix_baseline.json``): prefix-mix payload shape
(hit rate in [0, 1], tokens saved <= prompt tokens, finite percentiles) plus
the acceptance ratchet — >= 40% prefill-token reduction, hit rate > 0.5,
cached TTFT p50 no worse than the cache-off leg (``check_prefix_baseline``)
— and validates the checked-in disaggregated fleet replay baseline
(``onchip_results/serving_fleet_baseline.json``): payload shape (finite
ordered percentiles for both legs, shed rate in [0, 1], every shipped KV
page bound) plus the fleet acceptance ratchet — saturation-rate multiplier
>= 2x the single replica, shed rate <= 0.1, at least one real handoff,
fleet TTFT p99 no worse than the saturated single replica
(``check_fleet_baseline``) — and validates the checked-in KV-fabric
baseline (``onchip_results/serving_kvfabric_baseline.json``): serialized
wire bytes per page <= 0.3x the fp32 device bytes they replace, the delta
leg shipping measurably fewer bytes than the no-delta leg, zero CRC
failures, every leg bit-exact against the monolithic reference, and a
two-process leg (decode in a separate OS process) that completed every
request (``check_kvfabric_baseline``) — and validates the checked-in
long-context KV
tiering baseline (``onchip_results/serving_longctx_baseline.json``):
payload shape (finite ordered percentiles, host occupancy in [0, 1], the
swap accounting identity ``swapped_out == swapped_in + swap_dropped +
resident_host_blocks``) plus the tiering acceptance ratchet — int8
capacity multiplier >= 2x at the fp leg's KV HBM budget, at least one
spill and one restore recorded, zero live swap-outs, a positive prefill
reduction across the spill/restore round trip (``check_longctx_baseline``;
stall growth between runs gates via ``--max-swap-stall-growth``) — and
validates the checked-in speculative-decode baseline
(``onchip_results/serving_speculate_baseline.json``): payload shape
(accept rate and verify-batch occupancy in [0, 1], the speculation counter
identity ``speculated == accepted + rejected``, a boolean parity flag)
plus the acceptance ratchet — tokens/s multiplier >= 1.5x plain decode on
the template-heavy greedy replay, greedy parity True (the bit-exactness
oracle), at least one token drafted and accepted
(``check_speculate_baseline``) — and
validates the checked-in elastic-reshard drill baseline
(``onchip_results/elastic_drill_baseline.json``): world sequence 8→4→8,
zero steps lost or double-applied, bitwise-equal restore-step losses, and
each reshard leg under the wall-clock ceiling
(``check_elastic_baseline``) — and traces the MoE hierarchical expert
all-to-all on 8 forced-host CPU devices requiring the quantized DCN leg's
wire bytes <= 0.5x fp32 with the ICI leg full precision
(``check_moe_wire``), and re-derives the checked-in MoE scheduled overlap
baseline (``onchip_results/moe_overlap_baseline.json``) jax-free,
requiring the chunked a2a/expert pipeline's exposed seconds to reproduce
and to sit >= 30% below its serialized worst case
(``check_moe_baseline``) — and validates every checked-in measured-cost
profile store (``onchip_results/profile_*.json``: schema via
``profile_store.validate_store`` plus a resolver round trip requiring the
``measured`` reason code, ``check_profile_store``) — and validates the
checked-in SLO replay baseline
(``onchip_results/serving_slo_baseline.json``): per-class attainment
arithmetic (``attained + violations == requests``), worst per-class
attainment >= 0.9, and >= 3 live time-series rings embedded
(``check_slo_baseline``; live runs gate via ``--min-slo-attainment``, and
every input doc's ``timeseries``/``slo_classes`` sections are
shape-validated) — then exits 0/2 without comparing. The tier-1 lane runs ``--dry-run`` against
the repo's own BASELINE.json so a malformed baseline, summary, or tuning
table fails fast on CPU (docs/OBSERVABILITY.md).
"""

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA_PATH = os.path.join(REPO_ROOT, "deepspeed_tpu", "telemetry",
                           "summary.schema.json")

#: metric -> (direction, threshold flag); "down" = lower candidate is a
#: regression, "up" = higher candidate is a regression
GATES = {
    "tokens_per_sec": ("down", "max_tokens_drop"),
    "mfu": ("down", "max_mfu_drop"),
    "goodput": ("down", "max_goodput_drop"),
    "peak_hbm_bytes": ("up", "max_hbm_growth"),
    "compile_seconds": ("up", "max_compile_growth"),
    # serving latency (bench_serving --replay / summary["serving"]): higher
    # is a regression
    "ttft_p50_s": ("up", "max_ttft_growth"),
    "ttft_p99_s": ("up", "max_ttft_growth"),
    "tpot_p50_s": ("up", "max_tpot_growth"),
    "tpot_p99_s": ("up", "max_tpot_growth"),
    "peak_kv_occupancy": ("up", "max_kv_occupancy_growth"),
    # overlap report (telemetry/overlap.py): exposed-comm seconds growing
    # means the schedule got worse at hiding collectives
    "exposed_comm_s": ("up", "max_exposed_growth"),
    # prefix-cache effectiveness (bench_serving --replay --prefix-mix):
    # the hit rate or the prefill-token reduction shrinking means prompt
    # reuse got worse
    "prefix_hit_rate": ("down", "max_prefix_hit_drop"),
    "prefill_reduction": ("down", "max_prefix_hit_drop"),
    # fleet replay (bench_serving --fleet --replay): the saturation-rate
    # multiplier over the monolithic single replica shrinking means the
    # disaggregation dividend regressed
    "rate_multiplier": ("down", "max_rate_multiplier_drop"),
    # long-context tiering (bench_serving --long-context): total seconds
    # stalled restoring spilled KV blocks from host DRAM growing means the
    # swap path got slower (or restores stopped overlapping decode)
    "swap_in_stall_s": ("up", "max_swap_stall_growth"),
    # chaos replay (bench_serving --chaos --diurnal): completed tokens per
    # live-replica-second UNDER FAULTS shrinking means recovery or the
    # autoscaler got more wasteful (re-prefill churn, idle over-provision)
    "goodput_tokens_per_replica_sec": ("down", "max_goodput_drop"),
}

#: extra/doc keys lifted verbatim into the metric dict when positive
SERVING_KEYS = ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                "peak_kv_occupancy")

#: prefix-mix payload keys (bench_serving --replay --prefix-mix); lifted and
#: validated only when present — plain replay payloads don't carry them
PREFIX_KEYS = ("prefix_hit_rate", "prefill_reduction")

#: fleet replay payload keys (bench_serving --fleet --replay); lifted only
#: when present (the rate multiplier rides the fleet payload's extra)
FLEET_KEYS = ("rate_multiplier",)

#: long-context tiering payload keys (bench_serving --long-context); lifted
#: only when present
LONGCTX_KEYS = ("swap_in_stall_s",)

#: chaos replay payload keys (bench_serving --chaos --diurnal); lifted only
#: when present
CHAOS_KEYS = ("goodput_tokens_per_replica_sec",)


def load_doc(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot read {path}: {e}", file=sys.stderr)
        return None


def find_summary(doc):
    """Locate an embedded telemetry summary in any accepted doc shape."""
    if not isinstance(doc, dict):
        return None
    if "enabled" in doc and ("spans" in doc or doc.get("enabled") is False):
        return doc  # the doc IS a summary
    extra = doc.get("extra")
    if isinstance(extra, dict) and isinstance(extra.get("telemetry"), dict):
        return extra["telemetry"]
    if isinstance(doc.get("telemetry"), dict):
        return doc["telemetry"]
    return None


def extract_metrics(doc):
    """Comparable metrics from any accepted doc shape. Absent metrics are
    simply not compared."""
    m = {}
    if not isinstance(doc, dict):
        return m
    # bench payload: {"metric": "...tokens_per_sec...", "value": N, "extra": {}}
    # (overlap payloads carry exposed SECONDS as value — lower is better,
    # the opposite gate direction, so never lift them as throughput)
    if "value" in doc and "metric" in doc and \
            "overlap" not in str(doc.get("metric", "")):
        try:
            v = float(doc["value"])
            if v > 0:
                m["tokens_per_sec"] = v
        except (TypeError, ValueError):
            pass
    extra = doc.get("extra") if isinstance(doc.get("extra"), dict) else {}
    for src in (extra, doc):
        if "mfu" in src and "mfu" not in m:
            try:
                v = float(src["mfu"])
                if v > 0:
                    m["mfu"] = v
            except (TypeError, ValueError):
                pass
        if "peak_hbm_bytes" in src and "peak_hbm_bytes" not in m:
            try:
                v = int(src["peak_hbm_bytes"])
                if v > 0:
                    m["peak_hbm_bytes"] = v
            except (TypeError, ValueError):
                pass
        for key in SERVING_KEYS + PREFIX_KEYS + FLEET_KEYS + LONGCTX_KEYS \
                + CHAOS_KEYS:
            if key in src and key not in m:
                try:
                    v = float(src[key])
                    if v > 0:
                        m[key] = v
                except (TypeError, ValueError):
                    pass
    # BASELINE.json: {"published": {metric: value, ...}}
    pub = doc.get("published")
    if isinstance(pub, dict):
        for key, val in pub.items():
            try:
                val = float(val)
            except (TypeError, ValueError):
                continue
            for gate in GATES:
                if gate in key and gate not in m and val > 0:
                    m[gate] = val
    # telemetry summary (bare or embedded)
    s = find_summary(doc)
    if isinstance(s, dict) and s.get("enabled"):
        led = s.get("ledger", {})
        for key in ("mfu_rolling", "mfu"):
            if led.get(key) and "mfu" not in m:
                m["mfu"] = float(led[key])
                break
        if led.get("goodput") and "goodput" not in m:
            m["goodput"] = float(led["goodput"])
        mem = s.get("memory", {})
        if mem.get("peak_bytes") and "peak_hbm_bytes" not in m:
            m["peak_hbm_bytes"] = int(mem["peak_bytes"])
        progs = s.get("compile", {}).get("programs", {})
        total = sum(p.get("seconds", 0.0) for p in progs.values()
                    if isinstance(p, dict))
        if total > 0 and "compile_seconds" not in m:
            m["compile_seconds"] = total
        # serving stream: TTFT/TPOT percentiles + peak KV occupancy
        srv = s.get("serving", {})
        hists = srv.get("histograms", {}) if isinstance(srv, dict) else {}
        for hist_name, prefix in (("serving/ttft_s", "ttft"),
                                  ("serving/tpot_s", "tpot")):
            h = hists.get(hist_name)
            if isinstance(h, dict) and h.get("count"):
                for q in ("p50_s", "p99_s"):
                    key = f"{prefix}_{q}"
                    if key not in m and h.get(q, 0) > 0:
                        m[key] = float(h[q])
        g = srv.get("gauges", {}).get("serving/kv_occupancy") \
            if isinstance(srv, dict) else None
        if isinstance(g, dict) and g.get("peak", 0) > 0 and \
                "peak_kv_occupancy" not in m:
            m["peak_kv_occupancy"] = float(g["peak"])
    # overlap report: summary["overlap"] or a payload's extra["overlap"]
    for src in (find_summary(doc) or {}, extra, doc):
        ov = src.get("overlap") if isinstance(src, dict) else None
        if isinstance(ov, dict) and "exposed_comm_s" not in m:
            try:
                v = float(ov["exposed_comm_s"])
            except (KeyError, TypeError, ValueError):
                continue
            if v > 0:
                m["exposed_comm_s"] = v
    return m


def check_kernel_tables(tables_dir=None):
    """Validate every checked-in kernel tuning table (schema via
    ``kernel_table.validate_table``) and require the default-device table to
    cover all ``BENCH_SHAPES`` bucket keys. Returns (report, errors).

    ``kernel_table`` is loaded standalone (it is stdlib-only at module
    scope), so this check runs in the tier-1 dry-run lane without jax."""
    import importlib.util
    mod_path = os.path.join(REPO_ROOT, "deepspeed_tpu", "autotuning",
                            "kernel_table.py")
    spec = importlib.util.spec_from_file_location("_kernel_table", mod_path)
    kt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kt)

    tables_dir = tables_dir or kt.TABLES_DIR
    errors = []
    report = {"tables": {}, "bench_coverage": {}}
    try:
        names = sorted(n for n in os.listdir(tables_dir)
                       if n.endswith(".json"))
    except OSError as e:
        return report, [f"kernel tables dir unreadable: {e}"]
    if not names:
        errors.append(f"no kernel tuning tables under {tables_dir}")
    for name in names:
        path = os.path.join(tables_dir, name)
        doc = load_doc(path)
        if doc is None:
            errors.append(f"{name}: unreadable")
            continue
        errs = kt.validate_table(doc)
        report["tables"][name] = {"entries": len(doc.get("entries", {})),
                                  "errors": errs}
        errors.extend(f"{name}: {e}" for e in errs)
        if not errs:
            # bench-shape coverage: every shape the bench/AOT lanes run must
            # resolve as "tuned" on this device's table
            missing = []
            for kernel, shapes in kt.BENCH_SHAPES.items():
                for dims, dtype in shapes:
                    key = kt.bucket_key(kernel, dims, dtype)
                    if key not in doc["entries"]:
                        missing.append(key)
            report["bench_coverage"][name] = {
                "covered": not missing, "missing": missing}
            if missing:
                errors.append(f"{name}: bench shapes uncovered: {missing}")
    return report, errors


#: qgZ acceptance: wire bytes of the quantized DCN exchange relative to the
#: fp32 reduce-scatter path (ZeRO++: int8 + fp32 group scales ≈ 0.25)
QGZ_WIRE_MAX_RATIO = 0.3


def check_qgz_wire():
    """Trace (compile nothing, execute nothing) the qgZ hierarchical
    exchange on 8 forced-host CPU devices and require the DCN (``dpr``) leg's
    wire bytes <= ``QGZ_WIRE_MAX_RATIO`` x the logical fp32 bytes. The
    quantized collectives record ``wire_bytes`` comm telemetry at trace
    time, so ``jit(...).lower`` is enough — no TPU, no execution.

    Returns (report, errors); skipped without error when jax is missing or
    the host cannot present 8 devices (the dry-run lane must stay runnable
    on minimal CI hosts)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is baked into the image
        return {"skipped": f"jax unavailable: {e}"}, []
    if len(jax.devices()) < 8:
        return {"skipped": f"needs 8 devices, have {len(jax.devices())}"}, []
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        all_to_all_quant_reduce)

    telemetry.configure(enabled=True)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dpr", "dp"))
    grad = jax.ShapeDtypeStruct((8, 8192), jnp.float32)
    fn = jax.shard_map(
        lambda g: all_to_all_quant_reduce(g, intra_axis="dp",
                                          inter_axis="dpr"),
        mesh=mesh, in_specs=P(), out_specs=P(("dpr", "dp")),
        check_vma=False)
    jax.jit(fn).lower(grad)   # trace-time record_comm only

    ops = telemetry.summary().get("comm", {}).get("ops", {})
    report, errors = {}, []
    quant = ops.get("all_to_all_quant", {})
    if not quant:
        return report, ["qgz trace recorded no all_to_all_quant telemetry"]
    for axis, st in sorted(quant.items()):
        ratio = (st["wire_bytes"] / st["bytes"]) if st["bytes"] else 0.0
        report[axis] = {"bytes": st["bytes"],
                        "wire_bytes": st["wire_bytes"],
                        "ratio": round(ratio, 4)}
    dcn = report.get("dpr")
    if dcn is None:
        errors.append("qgz trace recorded no DCN (dpr) exchange")
    elif dcn["ratio"] > QGZ_WIRE_MAX_RATIO:
        errors.append(f"qgz DCN wire ratio {dcn['ratio']} > "
                      f"{QGZ_WIRE_MAX_RATIO}")
    return report, errors


#: MoE expert a2a acceptance: wire bytes of the quantized DCN dispatch/
#: combine leg relative to fp32 (int8 + fp32 group scales ≈ 0.26); the ICI
#: leg must stay full precision (payload-preserving token exchange)
MOE_WIRE_MAX_RATIO = 0.5


def check_moe_wire():
    """Trace (compile nothing, execute nothing) the hierarchical MoE expert
    all-to-all on 8 forced-host CPU devices and require the DCN (``dpr``)
    leg's wire bytes <= ``MOE_WIRE_MAX_RATIO`` x the logical fp32 bytes
    while the ICI (``ep``) leg stays full precision. Same trace-only idiom
    as :func:`check_qgz_wire` — the collectives record ``wire_bytes``
    telemetry at trace time under the "a2a_dispatch" op.

    Returns (report, errors); skipped without error when jax is missing or
    the host cannot present 8 devices."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is baked into the image
        return {"skipped": f"jax unavailable: {e}"}, []
    if len(jax.devices()) < 8:
        return {"skipped": f"needs 8 devices, have {len(jax.devices())}"}, []
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        moe_hierarchical_a2a)

    telemetry.configure(enabled=True)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dpr", "ep"))
    # [inter, intra, rows, d_model] per-peer token slabs
    tok = jax.ShapeDtypeStruct((4, 2, 16, 2048), jnp.float32)
    fn = jax.shard_map(
        lambda x: moe_hierarchical_a2a(x, intra_axis="ep", inter_axis="dpr",
                                       inter_bits=8),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    jax.jit(fn).lower(tok)   # trace-time record_comm only

    ops = telemetry.summary().get("comm", {}).get("ops", {})
    report, errors = {}, []
    a2a = ops.get("a2a_dispatch", {})
    if not a2a:
        return report, ["moe trace recorded no a2a_dispatch telemetry"]
    for axis, st in sorted(a2a.items()):
        ratio = (st["wire_bytes"] / st["bytes"]) if st["bytes"] else 0.0
        report[axis] = {"bytes": st["bytes"],
                        "wire_bytes": st["wire_bytes"],
                        "ratio": round(ratio, 4)}
    dcn = report.get("dpr")
    ici = report.get("ep")
    if dcn is None:
        errors.append("moe trace recorded no DCN (dpr) a2a leg")
    elif dcn["ratio"] > MOE_WIRE_MAX_RATIO:
        errors.append(f"moe DCN a2a wire ratio {dcn['ratio']} > "
                      f"{MOE_WIRE_MAX_RATIO}")
    if ici is None:
        errors.append("moe trace recorded no ICI (ep) a2a leg")
    elif ici["wire_bytes"] != ici["bytes"]:
        errors.append(
            f"moe ICI a2a leg is not full precision "
            f"(wire {ici['wire_bytes']} != logical {ici['bytes']}) — "
            "quantization belongs on the DCN leg only")
    return report, errors


def validate_summary(doc):
    """Schema-validate an embedded summary when jsonschema is available.
    Returns an error string or None."""
    s = find_summary(doc)
    if s is None:
        return None  # nothing embedded — nothing to validate
    try:
        import jsonschema
    except ImportError:
        return None
    try:
        with open(SCHEMA_PATH) as f:
            schema = json.load(f)
        jsonschema.validate(s, schema)
    except jsonschema.ValidationError as e:
        return f"summary schema violation: {e.message}"
    except (OSError, ValueError) as e:
        return f"cannot load schema {SCHEMA_PATH}: {e}"
    return None


def validate_serving_payload(doc):
    """Shape-check a bench_serving --replay payload: a SUCCESSFUL run (value
    > 0) must carry every serving metric, with finite ordered percentiles.
    Error payloads (value 0 + extra.error) pass untouched. Pure dict checks —
    runs in the tier-1 dry-run lane without jax or jsonschema. Returns an
    error string or None."""
    if not isinstance(doc, dict):
        return None
    if "serving_replay" not in str(doc.get("metric", "")):
        return None
    try:
        if float(doc.get("value", 0)) <= 0:
            return None
    except (TypeError, ValueError):
        return None
    extra = doc.get("extra")
    if not isinstance(extra, dict):
        return "serving replay payload has no extra dict"
    for key in SERVING_KEYS:
        v = extra.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            return f"serving replay payload: extra[{key!r}] missing or " \
                   f"non-numeric (got {v!r})"
        if not (v == v and abs(v) != float("inf")):
            return f"serving replay payload: extra[{key!r}] not finite"
    for prefix in ("ttft", "tpot"):
        if extra[f"{prefix}_p50_s"] > extra[f"{prefix}_p99_s"]:
            return f"serving replay payload: {prefix} p50 > p99"
    if not 0.0 <= extra["peak_kv_occupancy"] <= 1.0:
        return "serving replay payload: peak_kv_occupancy outside [0, 1]"
    return _validate_prefix_fields(extra)


def _validate_prefix_fields(extra):
    """Shape-check the prefix-mix fields riding a replay payload's extra
    (present only for ``--prefix-mix`` runs): hit rate in [0, 1], saved and
    executed prefill tokens consistent with the prompt total, finite ordered
    nocache percentiles. Returns an error string or None."""
    if "prefix_hit_rate" not in extra:
        return None  # plain replay payload — nothing prefix to check
    def bad_num(v):
        return not isinstance(v, (int, float)) or isinstance(v, bool) or \
            not (v == v and abs(v) != float("inf"))
    for key in ("prefix_hit_rate", "prefill_tokens_saved",
                "executed_prefill_tokens", "executed_prefill_tokens_nocache",
                "prefill_reduction", "ttft_p50_nocache_s",
                "ttft_p99_nocache_s"):
        if bad_num(extra.get(key)):
            return f"prefix-mix payload: extra[{key!r}] missing or not finite"
    if not 0.0 <= extra["prefix_hit_rate"] <= 1.0:
        return "prefix-mix payload: prefix_hit_rate outside [0, 1]"
    prompt_total = extra.get("prompt_tokens_total")
    if isinstance(prompt_total, int) and prompt_total > 0:
        if extra["prefill_tokens_saved"] > prompt_total:
            return "prefix-mix payload: prefill_tokens_saved > prompt tokens"
        if extra["executed_prefill_tokens"] + extra["prefill_tokens_saved"] \
                > prompt_total:
            return "prefix-mix payload: executed + saved > prompt tokens"
    if not -1.0 <= extra["prefill_reduction"] <= 1.0:
        return "prefix-mix payload: prefill_reduction outside [-1, 1]"
    if extra["ttft_p50_nocache_s"] > extra["ttft_p99_nocache_s"]:
        return "prefix-mix payload: nocache ttft p50 > p99"
    return None


def validate_fleet_payload(doc):
    """Shape-check a bench_serving --fleet --replay payload: a SUCCESSFUL
    run (value > 0) must carry finite ordered percentiles for BOTH legs
    (fleet and the single-replica reference), a shed rate in [0, 1], a
    finite positive rate multiplier, and page conservation — every shipped
    KV page bound at a decode replica (a shipped-but-unbound page means the
    handoff protocol leaked). Pure dict checks — runs in the tier-1 dry-run
    lane without jax. Returns an error string or None."""
    if not isinstance(doc, dict):
        return None
    if "serving_fleet_replay" not in str(doc.get("metric", "")):
        return None
    try:
        if float(doc.get("value", 0)) <= 0:
            return None
    except (TypeError, ValueError):
        return None
    extra = doc.get("extra")
    if not isinstance(extra, dict):
        return "fleet replay payload has no extra dict"
    def bad_num(v):
        return not isinstance(v, (int, float)) or isinstance(v, bool) or \
            not (v == v and abs(v) != float("inf"))
    for key in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                "single_ttft_p50_s", "single_ttft_p99_s", "rate_multiplier",
                "shed_rate", "requests_per_sec", "single_requests_per_sec",
                "handoffs", "pages_shipped", "pages_bound"):
        if bad_num(extra.get(key)):
            return f"fleet replay payload: extra[{key!r}] missing or " \
                   f"not finite (got {extra.get(key)!r})"
    for prefix in ("ttft", "tpot", "single_ttft"):
        if extra[f"{prefix}_p50_s"] > extra[f"{prefix}_p99_s"]:
            return f"fleet replay payload: {prefix} p50 > p99"
    if not 0.0 <= extra["shed_rate"] <= 1.0:
        return "fleet replay payload: shed_rate outside [0, 1]"
    if extra["rate_multiplier"] <= 0:
        return "fleet replay payload: rate_multiplier not positive"
    if extra["pages_shipped"] != extra["pages_bound"]:
        return (f"fleet replay payload: pages_shipped "
                f"{extra['pages_shipped']} != pages_bound "
                f"{extra['pages_bound']} — KV handoff leaked pages")
    if extra["handoffs"] < 0:
        return "fleet replay payload: negative handoff count"
    return None


def validate_kvfabric_payload(doc):
    """Shape-check a bench_serving --fleet --two-process payload: a
    SUCCESSFUL run (value > 0) must carry a wire-to-fp32 ratio in (0, 1),
    finite byte/page accounting for the no-delta and delta legs, a
    two_process sub-dict with its own fabric counters, and parity booleans
    for every leg. Pure dict checks — runs in the tier-1 dry-run lane
    without jax. Returns an error string or None."""
    if not isinstance(doc, dict):
        return None
    if "serving_kvfabric" not in str(doc.get("metric", "")):
        return None
    try:
        if float(doc.get("value", 0)) <= 0:
            return None
    except (TypeError, ValueError):
        return None
    extra = doc.get("extra")
    if not isinstance(extra, dict):
        return "kvfabric payload has no extra dict"

    def bad_num(v):
        return not isinstance(v, (int, float)) or isinstance(v, bool) or \
            not (v == v and abs(v) != float("inf"))
    for key in ("wire_fp32_ratio", "wire_page_bytes", "fp32_page_bytes",
                "nodelta_wire_bytes", "delta_wire_bytes", "wire_bytes_saved",
                "pages_shipped", "pages_delta_skipped", "crc_failures",
                "failed_handoffs", "handoffs"):
        if bad_num(extra.get(key)):
            return f"kvfabric payload: extra[{key!r}] missing or not " \
                   f"finite (got {extra.get(key)!r})"
    if not 0.0 < extra["wire_fp32_ratio"] < 1.0:
        return "kvfabric payload: wire_fp32_ratio outside (0, 1)"
    if extra["wire_page_bytes"] * extra["fp32_page_bytes"] <= 0:
        return "kvfabric payload: non-positive page byte costs"
    for key in ("parity_nodelta", "parity_delta"):
        if not isinstance(extra.get(key), bool):
            return f"kvfabric payload: extra[{key!r}] missing or not a bool"
    tp = extra.get("two_process")
    if not isinstance(tp, dict):
        return "kvfabric payload has no two_process leg"
    for key in ("handoffs", "transfers", "pages_shipped",
                "wire_bytes_shipped", "crc_naks", "fallbacks",
                "lost_requests"):
        if bad_num(tp.get(key)):
            return f"kvfabric payload: two_process[{key!r}] missing or " \
                   f"not finite (got {tp.get(key)!r})"
    if not isinstance(tp.get("parity"), bool):
        return "kvfabric payload: two_process['parity'] missing or " \
               "not a bool"
    return None


def validate_chaos_payload(doc):
    """Shape-check a bench_serving --chaos payload: a SUCCESSFUL run
    (value > 0) must carry finite recovery/elasticity accounting (losses,
    re-admissions, leaks, scale actions), ordered latency percentiles, a
    shed rate in [0, 1], non-negative per-class sheds, and the router's
    accounting identity — every submit admitted, rejected, or queued, with
    zero in-flight backlog after the drain (anything else means a terminal
    outcome failed to retire). Pure dict checks — runs in the tier-1
    dry-run lane without jax. Returns an error string or None."""
    if not isinstance(doc, dict):
        return None
    if "serving_chaos" not in str(doc.get("metric", "")):
        return None
    try:
        if float(doc.get("value", 0)) <= 0:
            return None
    except (TypeError, ValueError):
        return None
    extra = doc.get("extra")
    if not isinstance(extra, dict):
        return "chaos payload has no extra dict"
    def bad_num(v):
        return not isinstance(v, (int, float)) or isinstance(v, bool) or \
            not (v == v and abs(v) != float("inf"))
    for key in ("goodput_tokens_per_replica_sec", "wall_s",
                "replica_seconds", "replica_losses", "readmitted",
                "leaked_pages", "scale_ups", "scale_downs",
                "interactive_sheds", "shed_rate", "fault_trips",
                "requests_lost", "ttft_p50_s", "ttft_p99_s",
                "tpot_p50_s", "tpot_p99_s"):
        if bad_num(extra.get(key)):
            return f"chaos payload: extra[{key!r}] missing or not finite " \
                   f"(got {extra.get(key)!r})"
    for prefix in ("ttft", "tpot"):
        if extra[f"{prefix}_p50_s"] > extra[f"{prefix}_p99_s"]:
            return f"chaos payload: {prefix} p50 > p99"
    if not 0.0 <= extra["shed_rate"] <= 1.0:
        return "chaos payload: shed_rate outside [0, 1]"
    for key in ("replica_losses", "readmitted", "leaked_pages", "scale_ups",
                "scale_downs", "interactive_sheds", "requests_lost"):
        if extra[key] < 0:
            return f"chaos payload: negative {key}"
    if extra["replica_seconds"] < extra["wall_s"]:
        return ("chaos payload: replica_seconds below wall_s — the "
                "live-replica integral cannot undercount a 1-replica fleet")
    shed = extra.get("shed_by_class")
    if not isinstance(shed, dict) or \
            any(bad_num(v) or v < 0 for v in shed.values()):
        return "chaos payload: shed_by_class missing or malformed"
    acct = extra.get("accounting")
    if not isinstance(acct, dict) or \
            bad_num(acct.get("in_flight")) or bad_num(
                acct.get("backlog_total")):
        return "chaos payload: accounting section missing or malformed"
    if acct.get("identity_holds") is not True:
        return ("chaos payload: router accounting identity does not hold "
                "(admitted + rejected + queued != submitted)")
    if acct["in_flight"] != 0 or acct["backlog_total"] != 0:
        return ("chaos payload: drained run left phantom backlog "
                f"(in_flight={acct['in_flight']}, "
                f"backlog_total={acct['backlog_total']}) — some terminal "
                "outcome never retired from the router")
    return None


def validate_longctx_payload(doc):
    """Shape-check a bench_serving --long-context payload: a SUCCESSFUL run
    (value > 0) must carry finite ordered latency percentiles, a host-tier
    occupancy in [0, 1], non-negative stall seconds, and the swap
    accounting identity — every block swapped out is either swapped back
    in, explicitly dropped (host tier full), or still resident on host
    (``swapped_out == swapped_in + swap_dropped + resident_host_blocks``; a
    mismatch means the spill path leaked or resurrected blocks). Pure dict
    checks — runs in the tier-1 dry-run lane without jax. Returns an error
    string or None."""
    if not isinstance(doc, dict):
        return None
    if "serving_longctx" not in str(doc.get("metric", "")):
        return None
    try:
        if float(doc.get("value", 0)) <= 0:
            return None
    except (TypeError, ValueError):
        return None
    extra = doc.get("extra")
    if not isinstance(extra, dict):
        return "long-context payload has no extra dict"
    def bad_num(v):
        return not isinstance(v, (int, float)) or isinstance(v, bool) or \
            not (v == v and abs(v) != float("inf"))
    for key in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                "swap_in_stall_s", "swap_out_stall_s", "host_kv_occupancy",
                "swapped_out", "swapped_in", "swap_dropped",
                "resident_host_blocks", "swap_outs_live",
                "capacity_multiplier", "concurrent_sequences_per_chip",
                "concurrent_sequences_per_chip_fp", "prefill_reduction"):
        if bad_num(extra.get(key)):
            return f"long-context payload: extra[{key!r}] missing or " \
                   f"not finite (got {extra.get(key)!r})"
    for prefix in ("ttft", "tpot"):
        if extra[f"{prefix}_p50_s"] > extra[f"{prefix}_p99_s"]:
            return f"long-context payload: {prefix} p50 > p99"
    if not 0.0 <= extra["host_kv_occupancy"] <= 1.0:
        return "long-context payload: host_kv_occupancy outside [0, 1]"
    for key in ("swap_in_stall_s", "swap_out_stall_s", "swapped_out",
                "swapped_in", "swap_dropped", "resident_host_blocks"):
        if extra[key] < 0:
            return f"long-context payload: extra[{key!r}] negative"
    if extra["swapped_out"] != extra["swapped_in"] + extra["swap_dropped"] \
            + extra["resident_host_blocks"]:
        return (f"long-context payload: swapped_out {extra['swapped_out']} "
                f"!= swapped_in {extra['swapped_in']} + dropped "
                f"{extra['swap_dropped']} + resident "
                f"{extra['resident_host_blocks']} — the host tier leaked "
                f"or resurrected KV blocks")
    if extra["capacity_multiplier"] <= 0:
        return "long-context payload: capacity_multiplier not positive"
    if not -1.0 <= extra["prefill_reduction"] <= 1.0:
        return "long-context payload: prefill_reduction outside [-1, 1]"
    return None


def validate_speculate_payload(doc):
    """Shape-check a bench_serving --speculate payload: a SUCCESSFUL run
    (value > 0) must carry a finite tokens/s multiplier consistent with the
    recorded walls, an accept rate and verify-batch occupancy in [0, 1],
    the speculation counter identity (``speculated == accepted +
    rejected``), a tokens-per-round >= 1, and a boolean greedy-parity flag.
    Pure dict checks — runs in the tier-1 dry-run lane without jax.
    Returns an error string or None."""
    if not isinstance(doc, dict):
        return None
    if "serving_speculate" not in str(doc.get("metric", "")):
        return None
    try:
        if float(doc.get("value", 0)) <= 0:
            return None
    except (TypeError, ValueError):
        return None
    extra = doc.get("extra")
    if not isinstance(extra, dict):
        return "speculate payload has no extra dict"
    def bad_num(v):
        return not isinstance(v, (int, float)) or isinstance(v, bool) or \
            not (v == v and abs(v) != float("inf"))
    for key in ("tokens_per_sec_multiplier", "accept_rate",
                "verify_batch_occupancy", "speculated_tokens",
                "accepted_tokens", "rejected_tokens", "tokens_per_round",
                "wall_s", "wall_plain_s"):
        if bad_num(extra.get(key)):
            return f"speculate payload: extra[{key!r}] missing or " \
                   f"not finite (got {extra.get(key)!r})"
    if not isinstance(extra.get("greedy_parity"), bool):
        return "speculate payload: greedy_parity missing or not a boolean"
    if not 0.0 <= extra["accept_rate"] <= 1.0:
        return "speculate payload: accept_rate outside [0, 1]"
    if not 0.0 <= extra["verify_batch_occupancy"] <= 1.0:
        return "speculate payload: verify_batch_occupancy outside [0, 1]"
    if extra["tokens_per_sec_multiplier"] <= 0:
        return "speculate payload: tokens_per_sec_multiplier not positive"
    for key in ("speculated_tokens", "accepted_tokens", "rejected_tokens"):
        if extra[key] < 0:
            return f"speculate payload: extra[{key!r}] negative"
    if extra["speculated_tokens"] != \
            extra["accepted_tokens"] + extra["rejected_tokens"]:
        return (f"speculate payload: speculated_tokens "
                f"{extra['speculated_tokens']} != accepted "
                f"{extra['accepted_tokens']} + rejected "
                f"{extra['rejected_tokens']} — the verify loop lost or "
                f"double-counted drafted tokens")
    if extra["tokens_per_round"] < 1.0:
        return "speculate payload: tokens_per_round below 1 — a decode " \
               "round always commits at least the plain-decode token"
    if extra["wall_s"] <= 0 or extra["wall_plain_s"] <= 0:
        return "speculate payload: non-positive wall seconds"
    return None


def _bad_num(v):
    return not isinstance(v, (int, float)) or isinstance(v, bool) or \
        not (v == v and abs(v) != float("inf"))


def validate_timeseries_payload(doc):
    """Shape-check the ``timeseries`` section of any embedded telemetry
    summary (``telemetry/timeseries.py`` ring rollups): positive window
    width, window counts >= 1, finite ordered min/mean/max, strictly
    increasing window indices, and live window counts never exceeding the
    lifetime total. Pure dict checks — runs in the tier-1 dry-run lane
    without jax or jsonschema. Returns an error string or None."""
    s = find_summary(doc)
    ts = s.get("timeseries") if isinstance(s, dict) else None
    if not isinstance(ts, dict):
        return None  # nothing embedded — nothing to validate
    for name, ring in ts.items():
        if not isinstance(ring, dict):
            return f"timeseries[{name!r}]: not a dict"
        if _bad_num(ring.get("window_s")) or ring["window_s"] <= 0:
            return f"timeseries[{name!r}]: window_s missing or not positive"
        if not isinstance(ring.get("num_windows"), int) or \
                ring["num_windows"] < 1:
            return f"timeseries[{name!r}]: num_windows missing or < 1"
        if not isinstance(ring.get("total_count"), int) or \
                ring["total_count"] < 0:
            return f"timeseries[{name!r}]: total_count missing or negative"
        wins = ring.get("windows")
        if not isinstance(wins, list):
            return f"timeseries[{name!r}]: windows missing or not a list"
        if len(wins) > ring["num_windows"]:
            return f"timeseries[{name!r}]: more live windows than the ring"
        prev_idx = None
        live = 0
        for w in wins:
            if not isinstance(w, dict):
                return f"timeseries[{name!r}]: window entry not a dict"
            if not isinstance(w.get("count"), int) or w["count"] < 1:
                return f"timeseries[{name!r}]: window count < 1 (sparse " \
                       f"rings never keep empty windows)"
            for k in ("sum", "min", "max", "mean"):
                if _bad_num(w.get(k)):
                    return f"timeseries[{name!r}]: window {k} not finite"
            if not w["min"] <= w["mean"] <= w["max"]:
                return f"timeseries[{name!r}]: window min/mean/max unordered"
            idx = w.get("index")
            if not isinstance(idx, int):
                return f"timeseries[{name!r}]: window index missing"
            if prev_idx is not None and idx <= prev_idx:
                return f"timeseries[{name!r}]: window indices not " \
                       f"strictly increasing"
            prev_idx = idx
            live += w["count"]
        if live > ring["total_count"]:
            return f"timeseries[{name!r}]: live window counts {live} exceed " \
                   f"lifetime total_count {ring['total_count']}"
    return None


def validate_slo_payload(doc):
    """Shape-check the per-SLO-class section riding a payload's extra
    (``extra["slo_classes"]``, bench_serving --replay / --fleet) and the
    summary's ``slo`` section: per-metric attainment arithmetic
    (``attained + violations == requests``), attainment in [0, 1] and
    consistent with the counters, ordered finite percentiles, and an
    ``extra["slo_min_attainment"]`` that matches the derived worst class.
    Pure dict checks — runs in the tier-1 dry-run lane without jax.
    Returns an error string or None."""
    if not isinstance(doc, dict):
        return None
    extra = doc.get("extra") if isinstance(doc.get("extra"), dict) else {}
    sections = []
    for src in (extra, find_summary(doc) or {}):
        for key in ("slo_classes", "slo"):
            sec = src.get(key) if isinstance(src, dict) else None
            if isinstance(sec, dict) and sec and \
                    not any(sec is s for s in sections):
                sections.append(sec)
    if not sections:
        return None
    worst = None
    for sec in sections:
        for cls, entry in sec.items():
            if not isinstance(entry, dict):
                return f"slo_classes[{cls!r}]: not a dict"
            metrics = entry.get("metrics")
            if not isinstance(metrics, dict) or not metrics:
                return f"slo_classes[{cls!r}]: no metrics recorded"
            for metric, st in metrics.items():
                if not isinstance(st, dict):
                    return f"slo_classes[{cls!r}][{metric!r}]: not a dict"
                for k in ("requests", "attained", "violations"):
                    if not isinstance(st.get(k), int) or st[k] < 0:
                        return f"slo_classes[{cls!r}][{metric!r}]: {k} " \
                               f"missing or negative"
                if st["attained"] + st["violations"] != st["requests"]:
                    return (f"slo_classes[{cls!r}][{metric!r}]: attained "
                            f"{st['attained']} + violations "
                            f"{st['violations']} != requests "
                            f"{st['requests']} — attainment counters leaked")
                att = st.get("attainment")
                if _bad_num(att) or not 0.0 <= att <= 1.0:
                    return f"slo_classes[{cls!r}][{metric!r}]: attainment " \
                           f"missing or outside [0, 1]"
                if st["requests"] and \
                        abs(att - st["attained"] / st["requests"]) > 1e-3:
                    return f"slo_classes[{cls!r}][{metric!r}]: attainment " \
                           f"{att} inconsistent with its own counters"
                if worst is None or att < worst:
                    worst = att
            pcts = entry.get("percentiles")
            if pcts is not None:
                if not isinstance(pcts, dict):
                    return f"slo_classes[{cls!r}]: percentiles not a dict"
                for metric, p in pcts.items():
                    for k in ("p50_s", "p95_s", "p99_s"):
                        if _bad_num(p.get(k)) if isinstance(p, dict) else True:
                            return f"slo_classes[{cls!r}][{metric!r}]: " \
                                   f"percentile {k} missing or not finite"
                    if not p["p50_s"] <= p["p95_s"] <= p["p99_s"]:
                        return f"slo_classes[{cls!r}][{metric!r}]: " \
                               f"percentiles unordered"
    floor = extra.get("slo_min_attainment")
    if floor is not None:
        if _bad_num(floor) or not 0.0 <= floor <= 1.0:
            return "slo_min_attainment missing or outside [0, 1]"
        if worst is not None and abs(floor - worst) > 1e-3:
            return (f"slo_min_attainment {floor} does not match the worst "
                    f"per-class attainment {worst} — the payload's headline "
                    f"drifted from its own class table")
    return None


def _slo_min_attainment(doc):
    """Worst per-class attainment carried by ``doc`` (the
    ``extra.slo_min_attainment`` headline, else derived from
    ``extra.slo_classes``); None when the doc has no SLO data."""
    if not isinstance(doc, dict):
        return None
    extra = doc.get("extra") if isinstance(doc.get("extra"), dict) else {}
    v = extra.get("slo_min_attainment")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return float(v)
    worst = None
    sec = extra.get("slo_classes")
    if isinstance(sec, dict):
        for entry in sec.values():
            for st in (entry.get("metrics") or {}).values():
                att = st.get("attainment") if isinstance(st, dict) else None
                if isinstance(att, (int, float)) and \
                        (worst is None or att < worst):
                    worst = float(att)
    return worst


def _load_overlap_module():
    """Load telemetry/overlap.py standalone (stdlib-only at module scope,
    same pattern as kernel_table) so overlap validation runs in the tier-1
    dry-run lane without importing the package or jax."""
    import importlib.util
    mod_path = os.path.join(REPO_ROOT, "deepspeed_tpu", "telemetry",
                            "overlap.py")
    spec = importlib.util.spec_from_file_location("_overlap", mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def validate_overlap_payload(doc):
    """Structurally validate any overlap report riding this doc — a bare
    ``summary()["overlap"]`` section, a payload's ``extra["overlap"]``
    (``scripts/overlap_report.py``), or a doc-level ``overlap`` key: every
    number finite, exposure <= comm total, fractions in [0, 1]. Pure dict
    checks via the standalone overlap module — no jax, no jsonschema.
    Returns an error string or None."""
    if not isinstance(doc, dict):
        return None
    extra = doc.get("extra") if isinstance(doc.get("extra"), dict) else {}
    reports = []
    for src in (find_summary(doc) or {}, extra, doc):
        ov = src.get("overlap") if isinstance(src, dict) else None
        if isinstance(ov, dict) and not any(ov is r for r in reports):
            reports.append(ov)
    if not reports:
        return None
    try:
        ov_mod = _load_overlap_module()
    except Exception as e:
        return f"cannot load overlap module: {e}"
    for rep in reports:
        errs = ov_mod.validate_report(rep)
        if errs:
            return "overlap report invalid: " + "; ".join(errs)
    return None


#: overlap-schedule acceptance: the checked-in scheduled baseline's exposed
#: seconds must sit at or below this fraction of its own serialized worst
#: case (>= 30% reduction — ROADMAP item 2's ratchet)
OVERLAP_SCHEDULE_MAX_RATIO = 0.7
OVERLAP_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                     "overlap_analytic_baseline.json")


def _load_overlap_schedule_module():
    """Load runtime/zero/overlap_schedule.py standalone (stdlib-only at
    module scope) with the standalone overlap module plugged into its
    ``_OVERLAP`` injection point — the scheduled-baseline re-derivation runs
    in the tier-1 dry-run lane without the package or jax."""
    import importlib.util
    mod_path = os.path.join(REPO_ROOT, "deepspeed_tpu", "runtime", "zero",
                            "overlap_schedule.py")
    spec = importlib.util.spec_from_file_location("_overlap_schedule",
                                                  mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._OVERLAP = _load_overlap_module()
    return mod


def check_overlap_schedule(baseline_path=None):
    """Re-derive the checked-in scheduled overlap baseline jax-free and hold
    it to the ratchet: rebuild the two-resource timeline from the recorded
    ``extra.overlap.schedule`` block (plan + compute_s + comm seconds),
    require the recomputed exposed seconds to match the recorded payload
    value, and require exposed <= ``OVERLAP_SCHEDULE_MAX_RATIO`` x the
    serialized worst case. Returns (report, errors) for the dry-run lane."""
    path = baseline_path or OVERLAP_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no scheduled baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable scheduled baseline {path}"]
    ov = doc.get("extra", {}).get("overlap") if isinstance(doc, dict) else None
    sched = ov.get("schedule") if isinstance(ov, dict) else None
    if not isinstance(sched, dict):
        return {}, ["scheduled baseline has no extra.overlap.schedule block"]
    try:
        osched = _load_overlap_schedule_module()
    except Exception as e:
        return {}, [f"cannot load overlap_schedule module: {e}"]
    errors = [f"schedule block: {e}"
              for e in osched.validate_schedule(sched)]
    if errors:
        return {}, errors
    plan = osched.OverlapPlan.from_dict(sched)
    recomputed = osched.plan_exposure(sched["compute_s"], sched["comm_ops"],
                                      plan)
    recorded = float(ov.get("exposed_comm_s", doc.get("value", -1.0)))
    serialized = float(sched["serialized_exposed_comm_s"])
    tol = max(1e-9, 1e-4 * max(serialized, recorded))
    if abs(recomputed - recorded) > tol:
        errors.append(
            f"recomputed exposed {recomputed:.3e}s does not match the "
            f"recorded baseline {recorded:.3e}s — the schedule block and "
            f"payload value drifted apart (regenerate with "
            f"scripts/overlap_report.py --analytic --schedule)")
    if serialized > 0 and recomputed > OVERLAP_SCHEDULE_MAX_RATIO * serialized:
        errors.append(
            f"scheduled exposed {recomputed:.3e}s > "
            f"{OVERLAP_SCHEDULE_MAX_RATIO} x serialized {serialized:.3e}s — "
            f"the overlap pass no longer hides >= "
            f"{1 - OVERLAP_SCHEDULE_MAX_RATIO:.0%} of the worst case")
    return {"exposed_comm_s": round(recomputed, 9),
            "serialized_exposed_comm_s": serialized,
            "reduction_fraction": round(
                (serialized - recomputed) / serialized, 6)
            if serialized > 0 else 0.0,
            "prefetch_depth": plan.prefetch_depth,
            "grad_buckets": plan.grad_buckets}, errors


MOE_OVERLAP_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                         "moe_overlap_baseline.json")


def check_moe_baseline(baseline_path=None):
    """Re-derive the checked-in MoE scheduled overlap baseline jax-free and
    hold it to the ratchet: rebuild the chunked dispatch/expert/combine
    timeline from the recorded ``extra.overlap.schedule`` block, require the
    recomputed exposed seconds to match the recorded value, and require
    exposed <= ``OVERLAP_SCHEDULE_MAX_RATIO`` x the serialized worst case —
    :func:`check_overlap_schedule`'s twin over
    ``moe_scheduled_intervals``/``moe_plan_exposure``. Returns
    (report, errors) for the dry-run lane."""
    path = baseline_path or MOE_OVERLAP_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no moe scheduled baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable moe scheduled baseline {path}"]
    ov = doc.get("extra", {}).get("overlap") if isinstance(doc, dict) else None
    sched = ov.get("schedule") if isinstance(ov, dict) else None
    if not isinstance(sched, dict):
        return {}, ["moe baseline has no extra.overlap.schedule block"]
    try:
        osched = _load_overlap_schedule_module()
    except Exception as e:
        return {}, [f"cannot load overlap_schedule module: {e}"]
    errors = [f"schedule block: {e}"
              for e in osched.validate_schedule(sched)]
    if errors:
        return {}, errors
    moe_classes = {"moe_dispatch", "moe_combine"}
    if not any(osched._op_class(s.get("op")) in moe_classes
               for s in sched["comm_ops"]):
        return {}, ["moe baseline schedule has no a2a_dispatch/a2a_combine "
                    "ops — not an MoE inventory"]
    plan = osched.OverlapPlan.from_dict(sched)
    recomputed = osched.moe_plan_exposure(sched["compute_s"],
                                          sched["comm_ops"], plan)
    recorded = float(ov.get("exposed_comm_s", doc.get("value", -1.0)))
    serialized = float(sched["serialized_exposed_comm_s"])
    tol = max(1e-9, 1e-4 * max(serialized, recorded))
    if abs(recomputed - recorded) > tol:
        errors.append(
            f"recomputed moe exposed {recomputed:.3e}s does not match the "
            f"recorded baseline {recorded:.3e}s — the schedule block and "
            f"payload value drifted apart (regenerate with "
            f"python bench.py --moe)")
    if serialized > 0 and recomputed > OVERLAP_SCHEDULE_MAX_RATIO * serialized:
        errors.append(
            f"moe scheduled exposed {recomputed:.3e}s > "
            f"{OVERLAP_SCHEDULE_MAX_RATIO} x serialized {serialized:.3e}s — "
            f"the chunked a2a pipeline no longer hides >= "
            f"{1 - OVERLAP_SCHEDULE_MAX_RATIO:.0%} of the worst case")
    return {"exposed_comm_s": round(recomputed, 9),
            "serialized_exposed_comm_s": serialized,
            "reduction_fraction": round(
                (serialized - recomputed) / serialized, 6)
            if serialized > 0 else 0.0,
            "a2a_chunks": plan.a2a_chunks}, errors


#: prefix-cache acceptance for the checked-in shared-prefix replay baseline:
#: the recorded run must have skipped >= 40% of prefill tokens with a hit
#: rate > 0.5 and a no-worse TTFT p50 than its own cache-off leg
PREFIX_MIN_REDUCTION = 0.40
PREFIX_MIN_HIT_RATE = 0.5
PREFIX_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                    "serving_prefix_baseline.json")


def check_prefix_baseline(baseline_path=None):
    """Validate the checked-in ``--prefix-mix`` replay baseline: payload
    shape (``validate_serving_payload`` incl. the prefix fields), internal
    consistency (executed + saved vs the recorded nocache leg), and the
    acceptance ratchet — prefill reduction >= ``PREFIX_MIN_REDUCTION``, hit
    rate > ``PREFIX_MIN_HIT_RATE``, cached TTFT p50 <= the nocache leg's.
    Pure dict checks over recorded values (wall-clock legs cannot be
    re-derived jax-free). Returns (report, errors) for the dry-run lane."""
    path = baseline_path or PREFIX_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no prefix baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable prefix baseline {path}"]
    err = validate_serving_payload(doc)
    if err:
        return {}, [f"prefix baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    if "prefix_hit_rate" not in extra:
        return {}, ["prefix baseline payload carries no prefix-mix fields "
                    "(regenerate with bench_serving --replay --prefix-mix)"]
    errors = []
    hit, red = extra["prefix_hit_rate"], extra["prefill_reduction"]
    executed = extra["executed_prefill_tokens"]
    nocache = extra["executed_prefill_tokens_nocache"]
    if nocache > 0:
        derived = (nocache - executed) / nocache
        if abs(derived - red) > 1e-3:
            errors.append(
                f"prefix baseline: recorded prefill_reduction {red} does not "
                f"match derived {derived:.6f} from executed token counts")
    if red < PREFIX_MIN_REDUCTION:
        errors.append(f"prefix baseline: prefill reduction {red} < "
                      f"{PREFIX_MIN_REDUCTION} — prompt reuse regressed")
    if hit <= PREFIX_MIN_HIT_RATE:
        errors.append(f"prefix baseline: prefix_hit_rate {hit} <= "
                      f"{PREFIX_MIN_HIT_RATE}")
    if extra["ttft_p50_s"] > extra["ttft_p50_nocache_s"]:
        errors.append(
            f"prefix baseline: cached TTFT p50 {extra['ttft_p50_s']}s worse "
            f"than the cache-off leg {extra['ttft_p50_nocache_s']}s")
    return {"prefix_hit_rate": hit, "prefill_reduction": red,
            "executed_prefill_tokens": executed,
            "executed_prefill_tokens_nocache": nocache,
            "ttft_p50_s": extra["ttft_p50_s"],
            "ttft_p50_nocache_s": extra["ttft_p50_nocache_s"]}, errors


#: fleet acceptance for the checked-in disaggregated replay baseline: the
#: recorded run must sustain >= 2x the single replica's saturation request
#: rate (the ISSUE's dividend) without shedding more than 10% of admits,
#: and must actually have exercised the KV handoff path
FLEET_MIN_RATE_MULTIPLIER = 2.0
FLEET_MAX_SHED_RATE = 0.1
FLEET_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                   "serving_fleet_baseline.json")


def check_fleet_baseline(baseline_path=None):
    """Validate the checked-in ``--fleet --replay`` baseline: payload shape
    (``validate_fleet_payload`` incl. page conservation), then the
    acceptance ratchet — rate multiplier >= ``FLEET_MIN_RATE_MULTIPLIER``,
    shed rate <= ``FLEET_MAX_SHED_RATE``, at least one real KV handoff, and
    fleet TTFT p99 no worse than the saturated single replica's (the whole
    point of admitting onto prefill-only replicas). Pure dict checks over
    recorded values (wall-clock legs cannot be re-derived jax-free).
    Returns (report, errors) for the dry-run lane."""
    path = baseline_path or FLEET_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no fleet baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable fleet baseline {path}"]
    err = validate_fleet_payload(doc)
    if err:
        return {}, [f"fleet baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    if "rate_multiplier" not in extra:
        return {}, ["fleet baseline payload carries no fleet fields "
                    "(regenerate with bench_serving --fleet --replay)"]
    errors = []
    mult = extra["rate_multiplier"]
    if mult < FLEET_MIN_RATE_MULTIPLIER:
        errors.append(
            f"fleet baseline: rate multiplier {mult} < "
            f"{FLEET_MIN_RATE_MULTIPLIER} — the disaggregated fleet no "
            f"longer sustains the required saturation-rate dividend")
    if extra["shed_rate"] > FLEET_MAX_SHED_RATE:
        errors.append(f"fleet baseline: shed_rate {extra['shed_rate']} > "
                      f"{FLEET_MAX_SHED_RATE}")
    if extra["handoffs"] <= 0:
        errors.append("fleet baseline: no KV handoffs recorded — the run "
                      "never exercised prefill->decode shipping")
    if extra["ttft_p99_s"] > extra["single_ttft_p99_s"]:
        errors.append(
            f"fleet baseline: fleet TTFT p99 {extra['ttft_p99_s']}s worse "
            f"than the saturated single replica "
            f"{extra['single_ttft_p99_s']}s")
    return {"rate_multiplier": mult, "shed_rate": extra["shed_rate"],
            "handoffs": extra["handoffs"],
            "pages_shipped": extra["pages_shipped"],
            "ttft_p99_s": extra["ttft_p99_s"],
            "single_ttft_p99_s": extra["single_ttft_p99_s"]}, errors


#: KV-fabric acceptance for the checked-in --fleet --two-process baseline:
#: a serialized int8 page (data row + fp32 scale) must cost at most this
#: fraction of the fp32 device bytes it replaces — (hd+4)/(4*hd), so the
#: 0.3 ceiling needs head_dim > 13 and holds 0.28125 at the bench's hd=32
KVFABRIC_MAX_WIRE_FP32_RATIO = 0.3
KVFABRIC_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                      "serving_kvfabric_baseline.json")


def check_kvfabric_baseline(baseline_path=None):
    """Validate the checked-in KV-fabric baseline: payload shape
    (``validate_kvfabric_payload``), then the acceptance ratchet — wire
    bytes per page <= ``KVFABRIC_MAX_WIRE_FP32_RATIO`` of fp32, the delta
    leg shipped measurably fewer bytes than the no-delta leg (with at
    least one page actually delta-skipped), zero CRC failures across the
    in-process legs, every leg bit-exact against the monolithic reference,
    and the two-process leg completed every request with zero losses.
    Pure dict checks over recorded values (the wall-clock legs cannot be
    re-derived jax-free). Returns (report, errors) for the dry-run
    lane."""
    path = baseline_path or KVFABRIC_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no kvfabric baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable kvfabric baseline {path}"]
    err = validate_kvfabric_payload(doc)
    if err:
        return {}, [f"kvfabric baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    if "wire_fp32_ratio" not in extra:
        return {}, ["kvfabric baseline payload carries no fabric fields "
                    "(regenerate with bench_serving --fleet --two-process)"]
    errors = []
    ratio = extra["wire_fp32_ratio"]
    if ratio > KVFABRIC_MAX_WIRE_FP32_RATIO:
        errors.append(
            f"kvfabric baseline: wire/fp32 byte ratio {ratio} > "
            f"{KVFABRIC_MAX_WIRE_FP32_RATIO} — the serialized page format "
            f"no longer pays for itself over shipping raw fp32")
    if extra["delta_wire_bytes"] >= extra["nodelta_wire_bytes"]:
        errors.append(
            f"kvfabric baseline: delta leg shipped "
            f"{extra['delta_wire_bytes']} bytes >= no-delta leg "
            f"{extra['nodelta_wire_bytes']} — delta-shipping saved nothing "
            f"on the prefix-mix trace")
    if extra["pages_delta_skipped"] <= 0 or extra["wire_bytes_saved"] <= 0:
        errors.append("kvfabric baseline: no pages delta-skipped — the "
                      "digest exchange never suppressed a transfer")
    if extra["crc_failures"] != 0:
        errors.append(f"kvfabric baseline: {extra['crc_failures']} CRC "
                      f"failure(s) on an uninjected run — the wire is "
                      f"corrupting pages")
    if extra["failed_handoffs"] != 0:
        errors.append(f"kvfabric baseline: {extra['failed_handoffs']} "
                      f"failed handoff(s)")
    if not (extra["parity_nodelta"] and extra["parity_delta"]):
        errors.append("kvfabric baseline: an in-process wire leg lost "
                      "greedy parity with the monolithic reference")
    tp = extra["two_process"]
    if tp["lost_requests"] != 0:
        errors.append(f"kvfabric baseline: two-process leg lost "
                      f"{tp['lost_requests']} request(s)")
    if not tp["parity"]:
        errors.append("kvfabric baseline: two-process leg lost greedy "
                      "parity — the serialized boundary is not bit-exact")
    if tp["handoffs"] <= 0:
        errors.append("kvfabric baseline: two-process leg recorded no "
                      "handoffs — the pipe transport never shipped a page")
    return {"wire_fp32_ratio": ratio,
            "nodelta_wire_bytes": extra["nodelta_wire_bytes"],
            "delta_wire_bytes": extra["delta_wire_bytes"],
            "wire_bytes_saved": extra["wire_bytes_saved"],
            "pages_delta_skipped": extra["pages_delta_skipped"],
            "crc_failures": extra["crc_failures"],
            "two_process_lost": tp["lost_requests"],
            "two_process_handoffs": tp["handoffs"]}, errors


#: chaos-replay acceptance for the checked-in baseline: the recorded run
#: must have ACTUALLY taken faults (a replica loss with live re-admissions),
#: recovered without losing a request or leaking a KV page, replaced the
#: lost capacity (scale-up), and kept the interactive class attained while
#: batch (or untagged) traffic absorbed every shed
CHAOS_MIN_INTERACTIVE_ATTAINMENT = 0.9
CHAOS_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                   "serving_chaos_baseline.json")


def check_chaos_baseline(baseline_path=None):
    """Validate the checked-in ``--chaos --diurnal`` baseline: payload shape
    (``validate_chaos_payload`` incl. the router accounting identity), then
    the acceptance ratchet — at least one injected replica loss with
    ``readmitted > 0``, zero requests lost, zero leaked KV pages, at least
    one autoscaler scale-up (the lost capacity was replaced), zero
    interactive sheds, interactive attainment >=
    ``CHAOS_MIN_INTERACTIVE_ATTAINMENT`` under faults, and a positive
    goodput per replica-second (the number the candidate-vs-baseline run
    ratchets via ``--max-goodput-drop``). Pure dict checks over recorded
    values. Returns (report, errors) for the dry-run lane."""
    path = baseline_path or CHAOS_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no chaos baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable chaos baseline {path}"]
    err = validate_chaos_payload(doc)
    if err:
        return {}, [f"chaos baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    if "replica_losses" not in extra:
        return {}, ["chaos baseline payload carries no chaos fields "
                    "(regenerate with bench_serving --chaos --diurnal)"]
    errors = []
    if extra["replica_losses"] < 1 or extra["fault_trips"] < 1:
        errors.append("chaos baseline: no replica loss recorded — the run "
                      "never exercised the recovery path")
    if extra["readmitted"] <= 0:
        errors.append("chaos baseline: replica lost but nothing re-admitted "
                      "— in-flight recovery never ran")
    if extra["requests_lost"] != 0:
        errors.append(f"chaos baseline: {extra['requests_lost']} admitted "
                      f"request(s) lost — recovery must complete every "
                      f"admitted stream")
    if extra["leaked_pages"] != 0:
        errors.append(f"chaos baseline: {extra['leaked_pages']} KV page(s) "
                      f"leaked after the drain")
    if extra["scale_ups"] < 1:
        errors.append("chaos baseline: autoscaler never scaled up — the "
                      "lost capacity was not replaced")
    if extra["interactive_sheds"] != 0:
        errors.append(f"chaos baseline: {extra['interactive_sheds']} "
                      f"interactive shed(s) — shedding must land on looser "
                      f"classes only")
    att = extra.get("interactive_attainment")
    if att is None:
        errors.append("chaos baseline: no interactive_attainment recorded")
    elif att < CHAOS_MIN_INTERACTIVE_ATTAINMENT:
        errors.append(f"chaos baseline: interactive attainment {att} < "
                      f"{CHAOS_MIN_INTERACTIVE_ATTAINMENT} under faults")
    goodput = extra["goodput_tokens_per_replica_sec"]
    if goodput <= 0:
        errors.append("chaos baseline: non-positive goodput per "
                      "replica-second")
    return {"goodput_tokens_per_replica_sec": goodput,
            "replica_losses": extra["replica_losses"],
            "readmitted": extra["readmitted"],
            "leaked_pages": extra["leaked_pages"],
            "scale_ups": extra["scale_ups"],
            "scale_downs": extra["scale_downs"],
            "interactive_sheds": extra["interactive_sheds"],
            "interactive_attainment": att}, errors


#: long-context tiering acceptance for the checked-in baseline: at the fp
#: leg's KV HBM budget the int8 pool must fit >= 2x the max-context
#: sequences, the recorded run must actually have spilled AND revived
#: prefix blocks through the host tier, and no live sequence may have paid
#: the preemption path while parked blocks could spill instead
LONGCTX_MIN_CAPACITY_MULTIPLIER = 2.0
LONGCTX_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                     "serving_longctx_baseline.json")


def check_longctx_baseline(baseline_path=None):
    """Validate the checked-in ``--long-context`` tiering baseline: payload
    shape (``validate_longctx_payload`` incl. the swap accounting
    identity), then the acceptance ratchet — int8 capacity multiplier >=
    ``LONGCTX_MIN_CAPACITY_MULTIPLIER`` at the equal HBM budget, at least
    one spill AND one restore recorded (the run exercised the tier), zero
    live swap-outs, and a positive prefill reduction across the
    spill/restore round trip. Pure dict checks over recorded values
    (wall-clock legs cannot be re-derived jax-free). Returns
    (report, errors) for the dry-run lane."""
    path = baseline_path or LONGCTX_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no long-context baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable long-context baseline {path}"]
    err = validate_longctx_payload(doc)
    if err:
        return {}, [f"longctx baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    if "swapped_out" not in extra:
        return {}, ["longctx baseline payload carries no tiering fields "
                    "(regenerate with bench_serving --long-context)"]
    errors = []
    mult = extra["capacity_multiplier"]
    if mult < LONGCTX_MIN_CAPACITY_MULTIPLIER:
        errors.append(
            f"longctx baseline: capacity multiplier {mult} < "
            f"{LONGCTX_MIN_CAPACITY_MULTIPLIER} — int8 KV pages no longer "
            f"fit 2x the sequences at the fp leg's HBM budget")
    if extra["swapped_out"] < 1:
        errors.append("longctx baseline: no KV blocks spilled — the run "
                      "never pressured the host tier")
    if extra["swapped_in"] < 1:
        errors.append("longctx baseline: no KV blocks restored — spilled "
                      "prefix chains never revived")
    if extra["swap_outs_live"] != 0:
        errors.append(
            f"longctx baseline: {extra['swap_outs_live']} live swap-outs — "
            f"a live sequence paid for pressure while parked blocks could "
            f"spill (pressure order broken)")
    if extra["prefill_reduction"] <= 0:
        errors.append("longctx baseline: prefill reduction not positive — "
                      "restored prefix chains saved no prefill work")
    return {"capacity_multiplier": mult,
            "concurrent_sequences_per_chip":
                extra["concurrent_sequences_per_chip"],
            "swapped_out": extra["swapped_out"],
            "swapped_in": extra["swapped_in"],
            "swap_in_stall_s": extra["swap_in_stall_s"],
            "prefill_reduction": extra["prefill_reduction"]}, errors


#: speculative-decode acceptance for the checked-in baseline: on the
#: prefix-heavy greedy replay the draft-then-verify leg must beat plain
#: decode by >= 1.5x wall-clock at bit-exact output (greedy parity), with
#: a sane accept rate and at least one drafted token — a drop below the
#: ratchet means drafting or verify-batching regressed
SPECULATE_MIN_MULTIPLIER = 1.5
SPECULATE_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                       "serving_speculate_baseline.json")


def check_speculate_baseline(baseline_path=None):
    """Validate the checked-in ``--speculate`` baseline: payload shape
    (``validate_speculate_payload`` incl. the speculation counter
    identity), then the acceptance ratchet — tokens/s multiplier >=
    ``SPECULATE_MIN_MULTIPLIER`` on the template-heavy greedy replay,
    greedy parity True (the speculate leg reproduced the plain stream
    token-for-token — the bit-exactness oracle), accept rate in (0, 1],
    and at least one token actually drafted. Pure dict checks over
    recorded values (wall-clock legs cannot be re-derived jax-free).
    Returns (report, errors) for the dry-run lane."""
    path = baseline_path or SPECULATE_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no speculate baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable speculate baseline {path}"]
    err = validate_speculate_payload(doc)
    if err:
        return {}, [f"speculate baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    if "tokens_per_sec_multiplier" not in extra:
        return {}, ["speculate baseline payload carries no speculation "
                    "fields (regenerate with bench_serving --speculate)"]
    errors = []
    mult = extra["tokens_per_sec_multiplier"]
    if mult < SPECULATE_MIN_MULTIPLIER:
        errors.append(
            f"speculate baseline: tokens/s multiplier {mult} < "
            f"{SPECULATE_MIN_MULTIPLIER} — draft-then-verify no longer "
            f"pays for its verify overhead on the prefix-heavy replay")
    if extra["greedy_parity"] is not True:
        errors.append(
            "speculate baseline: greedy parity broken — the speculate leg "
            "diverged from the plain greedy stream (accept/rollback is "
            "committing tokens plain decode would not have emitted)")
    if extra["speculated_tokens"] < 1:
        errors.append("speculate baseline: no tokens drafted — the run "
                      "never exercised the draft-then-verify path")
    if extra["accepted_tokens"] < 1:
        errors.append("speculate baseline: no drafted token accepted — "
                      "the drafter never matched the model's stream")
    return {"tokens_per_sec_multiplier": mult,
            "accept_rate": extra["accept_rate"],
            "verify_batch_occupancy": extra["verify_batch_occupancy"],
            "greedy_parity": extra["greedy_parity"],
            "speculated_tokens": extra["speculated_tokens"],
            "tokens_per_round": extra["tokens_per_round"]}, errors


#: elastic reshard drill acceptance for the checked-in baseline
#: (onchip_results/elastic_drill_baseline.json, regenerated with
#: ``scripts/fault_drill.py --emit-elastic-baseline``): the 8→4→8 CPU
#: drill must lose zero steps, double-apply none, restore bitwise on the
#: full world, and keep each reshard leg under the wall-clock ceiling
ELASTIC_MAX_RESHARD_S = 30.0
ELASTIC_WORLD_SEQUENCE = [8, 4, 8]
# elastic_reshard.RESTORE_LOSS_MAX_ULPS (this file imports no jax): on the
# survivors' world the restore step's loss is summed in another order
ELASTIC_RESTORE_MAX_ULPS = 4
ELASTIC_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                     "elastic_drill_baseline.json")


def check_elastic_baseline(baseline_path=None):
    """Validate the checked-in elastic-reshard drill baseline: the recorded
    run shrank 8→4 on a mid-step slice loss and re-expanded 4→8
    (``world_sequence``), lost zero steps and double-applied none across
    both reshards, restored the loss bitwise at the reshard step back on the
    full world and within a few ulps on the survivors', kept
    the optimizer step count equal to the step budget, and each reshard
    leg's wall-seconds ratchets under :data:`ELASTIC_MAX_RESHARD_S`. Pure
    dict checks over recorded values (the drill itself needs jax + 8 CPU
    devices). Returns (report, errors) for the dry-run lane."""
    path = baseline_path or ELASTIC_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no elastic drill baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable elastic drill baseline {path}"]
    if not isinstance(doc, dict) or doc.get("drill") != "elastic-reshard-8-4-8":
        return {}, ["elastic baseline: not an elastic-reshard drill payload "
                    "(regenerate with fault_drill.py --emit-elastic-baseline)"]
    required = ("world_sequence", "steps_lost", "steps_double_applied",
                "restore_loss_bitwise_equal", "reshard_s", "steps",
                "final_optimizer_step")
    missing = [k for k in required if k not in doc]
    if missing:
        return {}, [f"elastic baseline: missing fields {missing}"]
    errors = []
    if list(doc["world_sequence"]) != ELASTIC_WORLD_SEQUENCE:
        errors.append(
            f"elastic baseline: world sequence {doc['world_sequence']} != "
            f"{ELASTIC_WORLD_SEQUENCE} — the drill did not shrink to the "
            f"surviving half and re-expand")
    if doc["steps_lost"] != 0:
        errors.append(f"elastic baseline: {doc['steps_lost']} steps lost — "
                      f"the reshard dropped part of the loss trajectory")
    if doc["steps_double_applied"] != 0:
        errors.append(
            f"elastic baseline: {doc['steps_double_applied']} steps "
            f"double-applied — the restore replayed a committed step")
    if not doc["restore_loss_bitwise_equal"]:
        errors.append("elastic baseline: restore-step loss not bitwise "
                      "equal to the full-world reference — the universal "
                      "reshard-restore altered state")
    ulps = max(doc.get("restore_loss_ulps", {}).values(), default=0)
    if ulps > ELASTIC_RESTORE_MAX_ULPS:
        errors.append(f"elastic baseline: a restore-step loss lies {ulps} "
                      f"float32 ulps from the full-world reference "
                      f"(ceiling {ELASTIC_RESTORE_MAX_ULPS})")
    if doc["final_optimizer_step"] != doc["steps"]:
        errors.append(
            f"elastic baseline: optimizer step count "
            f"{doc['final_optimizer_step']} != step budget {doc['steps']}")
    reshard_s = doc["reshard_s"]
    for leg in ("shrink", "expand"):
        if leg not in reshard_s:
            errors.append(f"elastic baseline: no {leg} reshard recorded")
        elif not 0 < reshard_s[leg] <= ELASTIC_MAX_RESHARD_S:
            errors.append(
                f"elastic baseline: {leg} reshard took {reshard_s[leg]}s "
                f"(ceiling {ELASTIC_MAX_RESHARD_S}s)")
    return {"world_sequence": list(doc["world_sequence"]),
            "steps_lost": doc["steps_lost"],
            "steps_double_applied": doc["steps_double_applied"],
            "restore_loss_bitwise_equal": doc["restore_loss_bitwise_equal"],
            "reshard_s": reshard_s}, errors


def check_overlap_analytic():
    """Drive the overlap analyzer end-to-end jax-free: build the analytic
    serialized schedule from a fixed collective inventory, attribute it,
    and require the report to validate AND model every collective as fully
    exposed (the synchronous-XLA worst case the scheduling pass ratchets
    from). Returns (report, errors) for the dry-run lane."""
    try:
        ov = _load_overlap_module()
    except Exception as e:
        return {}, [f"cannot load overlap module: {e}"]
    per_device = ov.analytic_intervals(1e-3, [
        {"op": "all_gather", "axis": "dp", "bytes": 1 << 20,
         "seconds": 2e-4, "count": 2},
        {"op": "reduce_scatter", "axis": "dp", "bytes": 1 << 20,
         "seconds": 3e-4},
        {"op": "all_reduce", "axis": "dp", "bytes": 4096, "seconds": 5e-5},
    ])
    report = ov.overlap_report(per_device, mode="analytic")
    errors = ov.validate_report(report)
    if not errors and abs(report["exposed_comm_s"]
                          - report["comm_s"]) > 1e-9:
        errors.append("analytic serialized schedule must be fully exposed "
                      f"(exposed {report['exposed_comm_s']} != comm "
                      f"{report['comm_s']})")
    if not errors and not report["critical_path"]["ops"]:
        errors.append("analytic report has an empty critical path")
    return {"exposed_comm_s": report.get("exposed_comm_s"),
            "comm_s": report.get("comm_s"),
            "collectives": len(report.get("collectives", [])),
            "critical_path_ops": len(
                report.get("critical_path", {}).get("ops", []))}, errors


def _load_profile_store_module():
    """Load telemetry/profile_store.py standalone (stdlib-only at module
    scope, the kernel_table idiom) so the measured per-op cost stores are
    validated in the tier-1 dry-run lane without the package or jax."""
    import importlib.util
    mod_path = os.path.join(REPO_ROOT, "deepspeed_tpu", "telemetry",
                            "profile_store.py")
    spec = importlib.util.spec_from_file_location("_profile_store", mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_profile_store(stores_dir=None):
    """Validate every checked-in measured-cost profile store
    (``onchip_results/profile_*.json``, schema via
    ``profile_store.validate_store``) and round-trip one entry per store
    through the resolver, requiring the ``measured`` reason code — a store
    whose own keys resolve as ``roofline_fallback`` would silently disable
    the measured-cost path in ``overlap_schedule``. Returns
    (report, errors); skipped without error when no store is checked in."""
    stores_dir = stores_dir or os.path.join(REPO_ROOT, "onchip_results")
    try:
        names = sorted(n for n in os.listdir(stores_dir)
                       if n.startswith("profile_") and n.endswith(".json"))
    except OSError:
        names = []
    if not names:
        return {"skipped": f"no profile stores under {stores_dir}"}, []
    try:
        ps = _load_profile_store_module()
    except Exception as e:
        return {}, [f"cannot load profile_store module: {e}"]
    report, errors = {"stores": {}}, []
    for name in names:
        path = os.path.join(stores_dir, name)
        doc = load_doc(path)
        if doc is None:
            errors.append(f"{name}: unreadable")
            continue
        errs = ps.validate_store(doc)
        entries = doc.get("entries", {}) if isinstance(doc, dict) else {}
        report["stores"][name] = {"entries": len(entries), "errors": errs}
        errors.extend(f"{name}: {e}" for e in errs)
        if errs or not entries:
            if not errs and not entries:
                errors.append(f"{name}: store has no entries")
            continue
        # resolver round trip on the store's own first key (the bucket is
        # already a power of two, so it maps back to itself)
        key = sorted(entries)[0]
        op, bucket, dtype = key.split("|")
        seconds, reason = ps.resolve(op, int(bucket[1:]), dtype=dtype,
                                     path=path)
        report["stores"][name]["resolved"] = {
            "key": key, "seconds": seconds, "reason": reason}
        if reason != "measured" or seconds is None:
            errors.append(
                f"{name}: key {key} resolved as {reason!r} — the store's "
                f"own entries must resolve with the 'measured' reason code")
    return report, errors


#: SLO replay acceptance for the checked-in baseline
#: (onchip_results/serving_slo_baseline.json, regenerated with
#: ``bench_serving --replay`` — the replay lane always tags requests with
#: the two built-in SLO classes): every class's recorded attainment must
#: clear the floor and the run must carry live time-series trajectories
SLO_MIN_ATTAINMENT = 0.9
SLO_MIN_SERIES = 3
SLO_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                 "serving_slo_baseline.json")


def check_slo_baseline(baseline_path=None):
    """Validate the checked-in SLO replay baseline: payload shape
    (``validate_serving_payload`` + ``validate_slo_payload`` incl. the
    attainment arithmetic), then the acceptance ratchet — both built-in SLO
    classes present with recorded requests, worst per-class attainment >=
    ``SLO_MIN_ATTAINMENT``, and an embedded summary carrying >=
    ``SLO_MIN_SERIES`` non-empty time-series rings (the trajectory plane
    must actually have recorded). Pure dict checks over recorded values.
    Returns (report, errors) for the dry-run lane."""
    path = baseline_path or SLO_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no slo baseline at {path}"}, []
    doc = load_doc(path)
    if doc is None:
        return {}, [f"unreadable slo baseline {path}"]
    err = validate_serving_payload(doc) or validate_slo_payload(doc) \
        or validate_timeseries_payload(doc)
    if err:
        return {}, [f"slo baseline: {err}"]
    extra = doc.get("extra", {}) if isinstance(doc, dict) else {}
    classes = extra.get("slo_classes")
    if not isinstance(classes, dict) or not classes:
        return {}, ["slo baseline payload carries no slo_classes section "
                    "(regenerate with bench_serving --replay)"]
    errors = []
    if len(classes) < 2:
        errors.append(f"slo baseline: only {len(classes)} SLO class(es) "
                      f"recorded — the replay lane tags two")
    for cls, entry in sorted(classes.items()):
        if not any(st.get("requests", 0) > 0
                   for st in (entry.get("metrics") or {}).values()):
            errors.append(f"slo baseline: class {cls!r} recorded no requests")
    worst = _slo_min_attainment(doc)
    if worst is None:
        errors.append("slo baseline: no attainment derivable")
    elif worst < SLO_MIN_ATTAINMENT:
        errors.append(
            f"slo baseline: worst per-class attainment {worst} < "
            f"{SLO_MIN_ATTAINMENT} — the serving path stopped meeting its "
            f"recorded SLO targets")
    s = find_summary(doc) or {}
    series = s.get("timeseries") if isinstance(s, dict) else None
    live = [n for n, ring in (series or {}).items()
            if isinstance(ring, dict) and ring.get("windows")]
    if len(live) < SLO_MIN_SERIES:
        errors.append(
            f"slo baseline: only {len(live)} non-empty time-series rings "
            f"embedded (need >= {SLO_MIN_SERIES}) — the trajectory plane "
            f"did not record")
    return {"classes": sorted(classes),
            "min_attainment": worst,
            "live_series": len(live)}, errors


#: graftlint ratchet: per-rule/per-file finding counts frozen by this doc
#: may only go down (see docs/ANALYSIS.md; regenerate with
#: scripts/graftlint.py --write-baseline)
LINT_BASELINE_PATH = os.path.join(REPO_ROOT, "onchip_results",
                                  "lint_baseline.json")


def _load_astlint_module():
    """Load analysis/astlint.py standalone (stdlib-only at module scope, the
    same idiom as ``_load_overlap_module``) so the tier-1 dry-run lane lints
    the tree without importing the package or jax."""
    import importlib.util
    mod_path = os.path.join(REPO_ROOT, "deepspeed_tpu", "analysis",
                            "astlint.py")
    spec = importlib.util.spec_from_file_location("_astlint", mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_lint_baseline(baseline_path=None, scan_root=None):
    """Run graftlint Layer A over the package and ratchet against the
    checked-in lint baseline. Returns (report, errors) for the dry-run
    lane — a new finding in any guarded (rule, file) is an error, exactly
    the exit-3 condition ``scripts/graftlint.py`` enforces standalone."""
    path = baseline_path or LINT_BASELINE_PATH
    if not os.path.exists(path):
        return {"skipped": f"no lint baseline at {path}"}, []
    try:
        lint = _load_astlint_module()
    except Exception as e:
        return {}, [f"cannot load astlint module: {e}"]
    baseline, err = lint.load_baseline(path)
    if err:
        return {}, [err]
    root = scan_root or os.path.join(REPO_ROOT, "deepspeed_tpu")
    findings = lint.lint_paths([root], relative_to=REPO_ROOT)
    verdict = lint.check_baseline(findings, baseline)
    return {"findings": len(findings), "counts": verdict["counts"],
            "improvements": verdict["improvements"]}, \
        verdict["regressions"]


#: checked-in exemplar postmortem bundle (telemetry/flightrec.py) — the
#: bundle schema and the analyzer's signature catalogue are pinned against
#: each other here; regenerate alongside any flightrec format bump
POSTMORTEM_EXEMPLAR_DIR = os.path.join(REPO_ROOT, "onchip_results",
                                       "postmortem_exemplar")


def _load_postmortem_module():
    """Load scripts/postmortem.py standalone (stdlib-only — the analyzer
    must run on hosts without jax, so the dry-run lane holds it to that)."""
    import importlib.util
    mod_path = os.path.join(REPO_ROOT, "scripts", "postmortem.py")
    spec = importlib.util.spec_from_file_location("_postmortem", mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def validate_postmortem_bundle(exemplar_dir=None):
    """Schema-validate the checked-in exemplar bundle with the analyzer's
    own ``validate_bundle`` (manifest spine, event keys, seq order,
    payload files). Returns (report, errors) for the dry-run lane."""
    d = exemplar_dir or POSTMORTEM_EXEMPLAR_DIR
    if not os.path.isdir(d):
        return {"skipped": f"no postmortem exemplar at {d}"}, []
    try:
        pm = _load_postmortem_module()
    except Exception as e:
        return {}, [f"cannot load postmortem module: {e}"]
    bundles = pm.find_bundles([d])
    if not bundles:
        return {}, [f"no postmortem-* bundle under {d}"]
    errors = []
    for b in bundles:
        errors.extend(f"{os.path.basename(b)}: {e}"
                      for e in pm.validate_bundle(b))
    return {"bundles": len(bundles)}, errors


def check_postmortem_classify(exemplar_dir=None):
    """Pin the exemplar's classification: the full analyzer pipeline
    (discover -> validate -> merge by run_id -> classify) must produce
    exactly one ``backend_unavailable`` incident — a signature-catalogue
    or timeline regression flips this. Returns (report, errors)."""
    d = exemplar_dir or POSTMORTEM_EXEMPLAR_DIR
    if not os.path.isdir(d):
        return {"skipped": f"no postmortem exemplar at {d}"}, []
    try:
        pm = _load_postmortem_module()
    except Exception as e:
        return {}, [f"cannot load postmortem module: {e}"]
    report, errors = pm.analyze([d])
    if report is None:
        return {}, errors
    incidents = [i["incident"] for i in report["incidents"]]
    if incidents != ["backend_unavailable"]:
        errors = list(errors) + [
            f"exemplar classified {incidents} != ['backend_unavailable'] — "
            f"the signature catalogue drifted from the bundle format"]
    events = sum(i["event_count"] for i in report["incidents"])
    if events < 3:
        errors = list(errors) + [
            f"exemplar incident carries {events} ring events (< 3) — the "
            f"flight-recorder timeline went missing from the bundle"]
    return {"incidents": incidents, "events": events}, errors


def compare(baseline, candidate, thresholds):
    """-> (verdicts, regressed). Only metrics on both sides are gated."""
    verdicts = []
    regressed = False
    for name, (direction, flag) in sorted(GATES.items()):
        if name not in baseline or name not in candidate:
            continue
        base, cand = baseline[name], candidate[name]
        thr = thresholds[flag]
        if base <= 0:
            continue
        delta = (cand - base) / base
        if direction == "down":
            bad = delta < -thr
        else:
            bad = delta > thr
        regressed |= bad
        verdicts.append({"metric": name, "baseline": base,
                         "candidate": cand, "delta": round(delta, 4),
                         "threshold": thr, "direction": direction,
                         "regressed": bad})
    return verdicts, regressed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", required=True)
    ap.add_argument("--candidate", default="",
                    help="candidate doc; optional with --dry-run")
    ap.add_argument("--summary", default="",
                    help="optional standalone telemetry summary JSON merged "
                         "into the candidate metrics")
    ap.add_argument("--max-tokens-drop", type=float, default=0.10)
    ap.add_argument("--max-mfu-drop", type=float, default=0.10)
    ap.add_argument("--max-goodput-drop", type=float, default=0.10)
    ap.add_argument("--max-hbm-growth", type=float, default=0.10)
    ap.add_argument("--max-compile-growth", type=float, default=0.50)
    ap.add_argument("--max-ttft-growth", type=float, default=0.10)
    ap.add_argument("--max-tpot-growth", type=float, default=0.10)
    ap.add_argument("--max-kv-occupancy-growth", type=float, default=0.10)
    ap.add_argument("--max-exposed-growth", type=float, default=0.10,
                    help="allowed relative growth in exposed-comm seconds "
                         "(overlap report)")
    ap.add_argument("--max-prefix-hit-drop", type=float, default=0.10,
                    help="allowed relative drop in prefix-cache hit rate / "
                         "prefill reduction (--prefix-mix payloads)")
    ap.add_argument("--max-rate-multiplier-drop", type=float, default=0.10,
                    help="allowed relative drop in the fleet saturation-"
                         "rate multiplier (--fleet --replay payloads)")
    ap.add_argument("--max-swap-stall-growth", type=float, default=0.25,
                    help="allowed relative growth in host-tier swap-in "
                         "stall seconds (--long-context payloads)")
    ap.add_argument("--min-slo-attainment", type=float, default=None,
                    help="fail (exit 3) when the candidate's worst "
                         "per-SLO-class attainment (extra.slo_min_attainment "
                         "/ extra.slo_classes) is below this floor; exit 2 "
                         "when the candidate carries no SLO data")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate inputs (parse + summary schema) only")
    args = ap.parse_args(argv)

    docs = {"baseline": load_doc(args.baseline)}
    if args.candidate:
        docs["candidate"] = load_doc(args.candidate)
    if args.summary:
        docs["summary"] = load_doc(args.summary)
    for label, doc in docs.items():
        if doc is None:
            return 2
        err = validate_summary(doc) or validate_serving_payload(doc) \
            or validate_fleet_payload(doc) or validate_chaos_payload(doc) \
            or validate_longctx_payload(doc) \
            or validate_speculate_payload(doc) \
            or validate_overlap_payload(doc) \
            or validate_timeseries_payload(doc) or validate_slo_payload(doc)
        if err:
            print(f"perf_gate: {label}: {err}", file=sys.stderr)
            return 2

    if args.dry_run:
        table_report, table_errors = check_kernel_tables()
        for err in table_errors:
            print(f"perf_gate: kernel_table: {err}", file=sys.stderr)
        qgz_report, qgz_errors = check_qgz_wire()
        for err in qgz_errors:
            print(f"perf_gate: qgz_wire: {err}", file=sys.stderr)
        moe_wire_report, moe_wire_errors = check_moe_wire()
        for err in moe_wire_errors:
            print(f"perf_gate: moe_wire: {err}", file=sys.stderr)
        overlap_report, overlap_errors = check_overlap_analytic()
        for err in overlap_errors:
            print(f"perf_gate: overlap: {err}", file=sys.stderr)
        sched_report, sched_errors = check_overlap_schedule()
        for err in sched_errors:
            print(f"perf_gate: overlap_schedule: {err}", file=sys.stderr)
        moe_base_report, moe_base_errors = check_moe_baseline()
        for err in moe_base_errors:
            print(f"perf_gate: moe_baseline: {err}", file=sys.stderr)
        prefix_report, prefix_errors = check_prefix_baseline()
        for err in prefix_errors:
            print(f"perf_gate: prefix_cache: {err}", file=sys.stderr)
        fleet_report, fleet_errors = check_fleet_baseline()
        for err in fleet_errors:
            print(f"perf_gate: fleet: {err}", file=sys.stderr)
        kvfabric_report, kvfabric_errors = check_kvfabric_baseline()
        for err in kvfabric_errors:
            print(f"perf_gate: kvfabric: {err}", file=sys.stderr)
        chaos_report, chaos_errors = check_chaos_baseline()
        for err in chaos_errors:
            print(f"perf_gate: chaos: {err}", file=sys.stderr)
        longctx_report, longctx_errors = check_longctx_baseline()
        for err in longctx_errors:
            print(f"perf_gate: longctx: {err}", file=sys.stderr)
        spec_report, spec_errors = check_speculate_baseline()
        for err in spec_errors:
            print(f"perf_gate: speculate: {err}", file=sys.stderr)
        elastic_report, elastic_errors = check_elastic_baseline()
        for err in elastic_errors:
            print(f"perf_gate: elastic: {err}", file=sys.stderr)
        lint_report, lint_errors = check_lint_baseline()
        for err in lint_errors:
            print(f"perf_gate: lint: {err}", file=sys.stderr)
        profile_report, profile_errors = check_profile_store()
        for err in profile_errors:
            print(f"perf_gate: profile_store: {err}", file=sys.stderr)
        slo_report, slo_errors = check_slo_baseline()
        for err in slo_errors:
            print(f"perf_gate: slo: {err}", file=sys.stderr)
        pm_report, pm_errors = validate_postmortem_bundle()
        for err in pm_errors:
            print(f"perf_gate: postmortem_bundle: {err}", file=sys.stderr)
        pm_cls_report, pm_cls_errors = check_postmortem_classify()
        for err in pm_cls_errors:
            print(f"perf_gate: postmortem_classify: {err}", file=sys.stderr)
        errors = table_errors + qgz_errors + moe_wire_errors \
            + overlap_errors + sched_errors + moe_base_errors \
            + prefix_errors + fleet_errors + kvfabric_errors \
            + chaos_errors \
            + longctx_errors + spec_errors + elastic_errors + lint_errors \
            + profile_errors + slo_errors + pm_errors + pm_cls_errors
        print(json.dumps({"dry_run": True,
                          "inputs_ok": not errors,
                          "kernel_table": table_report,
                          "qgz_wire": qgz_report,
                          "moe_wire": moe_wire_report,
                          "overlap": overlap_report,
                          "overlap_schedule": sched_report,
                          "moe_baseline": moe_base_report,
                          "prefix_cache": prefix_report,
                          "fleet": fleet_report,
                          "kvfabric": kvfabric_report,
                          "chaos": chaos_report,
                          "longctx": longctx_report,
                          "speculate": spec_report,
                          "elastic": elastic_report,
                          "lint": lint_report,
                          "profile_store": profile_report,
                          "slo": slo_report,
                          "postmortem_bundle": pm_report,
                          "postmortem_classify": pm_cls_report,
                          "metrics": {label: extract_metrics(doc)
                                      for label, doc in docs.items()}}))
        return 2 if errors else 0

    if "candidate" not in docs:
        print("perf_gate: --candidate is required without --dry-run",
              file=sys.stderr)
        return 2
    base_m = extract_metrics(docs["baseline"])
    cand_m = extract_metrics(docs["candidate"])
    if "summary" in docs:
        for k, v in extract_metrics(docs["summary"]).items():
            cand_m.setdefault(k, v)

    thresholds = {"max_swap_stall_growth": args.max_swap_stall_growth,
                  "max_tokens_drop": args.max_tokens_drop,
                  "max_mfu_drop": args.max_mfu_drop,
                  "max_goodput_drop": args.max_goodput_drop,
                  "max_hbm_growth": args.max_hbm_growth,
                  "max_compile_growth": args.max_compile_growth,
                  "max_ttft_growth": args.max_ttft_growth,
                  "max_tpot_growth": args.max_tpot_growth,
                  "max_kv_occupancy_growth": args.max_kv_occupancy_growth,
                  "max_exposed_growth": args.max_exposed_growth,
                  "max_prefix_hit_drop": args.max_prefix_hit_drop,
                  "max_rate_multiplier_drop": args.max_rate_multiplier_drop}
    verdicts, regressed = compare(base_m, cand_m, thresholds)
    if args.min_slo_attainment is not None:
        att = _slo_min_attainment(docs["candidate"])
        if att is None:
            print("perf_gate: --min-slo-attainment given but the candidate "
                  "carries no per-class SLO data", file=sys.stderr)
            return 2
        bad = att < args.min_slo_attainment
        regressed |= bad
        verdicts.append({"metric": "slo_min_attainment", "baseline":
                         args.min_slo_attainment, "candidate": att,
                         "delta": round(att - args.min_slo_attainment, 6),
                         "threshold": args.min_slo_attainment,
                         "direction": "down", "regressed": bad})
    result = {"compared": len(verdicts), "regressed": regressed,
              "verdicts": verdicts,
              "baseline_metrics": base_m, "candidate_metrics": cand_m}
    print(json.dumps(result, indent=2))
    if not verdicts:
        print("perf_gate: WARNING no overlapping metrics to compare "
              "(empty baseline?) — passing", file=sys.stderr)
        return 0
    if regressed:
        bad = [v["metric"] for v in verdicts if v["regressed"]]
        print(f"perf_gate: REGRESSION in {', '.join(bad)}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
