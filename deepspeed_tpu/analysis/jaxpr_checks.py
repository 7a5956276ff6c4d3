"""graftlint Layer B — jaxpr-level checks for traced programs.

Layer A (``astlint``) sees the source; this module sees what jax actually
traced. The gap matters: an fp32 upcast hides inside a ``jnp.mean``, a
collective's axis binding depends on which shard_map wrapped the call, and
the overlap planner's claimed collective inventory is only honest if the
scheduled program traces the same ops the plan priced. These checks walk a
``ClosedJaxpr`` (recursing through pjit/shard_map/scan/cond sub-jaxprs) for:

* **JX001 upcast**: ``convert_element_type`` to float32 from bf16 in a
  bf16 program, excluding jnp's intentional accumulation upcasts (a
  convert consumed *only* by reduce primitives is how ``bf16.sum()``
  is supposed to look) and tiny scalars below ``min_elems``.
* **JX002 unbound collective**: a collective primitive whose axis names
  are not bound by any enclosing shard_map — it would fail at lowering
  on real meshes, or silently run on an implicit axis.
* **JX003 callback**: ``pure_callback``/``io_callback``/``debug_callback``
  inside a hot program — each one is a host round-trip per step.
* **plan drift** (``check_plan_drift``): the overlap plan's comm_ops
  inventory vs what the scheduled program actually traces, compared by
  the same prefetch/bucket/tail classes ``overlap_schedule._op_class``
  uses.

jax is REQUIRED here — this file runs in the ``lint`` pytest lane
(``pytest -m lint``), never in the tier-1 stdlib dry-run path. Callers
trace with ``jax.make_jaxpr`` (no compile, no execution), so the checks
are cheap enough for CI.
"""

import numpy as np

import jax

from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "iter_eqns", "check_upcasts", "check_collectives", "check_callbacks",
    "check_program", "check_moe_wire", "check_verify_prefill_parity",
    "collective_inventory", "check_plan_drift", "trace_jaxpr",
]

#: collective primitives and how they map onto the overlap plan's op names
_COLLECTIVE_PRIMS = {
    "all_gather": "all_gather",
    "psum": "all_reduce",
    "all_reduce": "all_reduce",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
}
#: reduce-style consumers that legitimize an accumulation upcast
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "reduce_precision", "cumsum",
    "cumlogsumexp", "cummax", "cummin", "cumprod",
}
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}

#: plan-op -> schedule class, mirroring ``overlap_schedule._op_class`` —
#: kept in sync by test_jaxpr_checks (drift here would silently un-gate)
_PREFETCH_OPS = ("all_gather", "gather")
_BUCKET_OPS = ("reduce_scatter", "psum_scatter", "all_to_all", "exchange")
_MOE_DISPATCH_OPS = ("a2a_dispatch",)
_MOE_COMBINE_OPS = ("a2a_combine",)


def op_class(op):
    """prefetch | bucket | tail | moe_dispatch | moe_combine — the overlap
    schedule's cost classes."""
    name = str(op).lower()
    # moe classes first: "a2a_*" must not fall through to the generic
    # "all_to_all"/"exchange" bucket class
    if any(k in name for k in _MOE_DISPATCH_OPS):
        return "moe_dispatch"
    if any(k in name for k in _MOE_COMBINE_OPS):
        return "moe_combine"
    if any(k in name for k in _PREFETCH_OPS):
        return "prefetch"
    if any(k in name for k in _BUCKET_OPS):
        return "bucket"
    return "tail"


def trace_jaxpr(fn, *args, **kwargs):
    """``jax.make_jaxpr`` without executing or compiling ``fn``."""
    return jax.make_jaxpr(fn)(*args, **kwargs)


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def _sub_jaxprs(params):
    for v in params.values():
        if isinstance(v, (Jaxpr, ClosedJaxpr)):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, (Jaxpr, ClosedJaxpr)):
                    yield x


def _shard_map_axes(eqn):
    """Axis names a shard_map eqn binds for its body (manual axes only)."""
    mesh = eqn.params.get("mesh")
    names = set(getattr(mesh, "axis_names", ()) or ())
    auto = eqn.params.get("auto") or frozenset()
    return frozenset(n for n in names if n not in auto)


def iter_eqns(jaxpr, bound_axes=frozenset(), path=()):
    """Yield ``(eqn, bound_axes, path)`` over every equation, recursing
    into sub-jaxprs. ``bound_axes`` accumulates axis names bound by
    enclosing shard_map eqns; ``path`` is the tuple of enclosing primitive
    names (outermost first) for finding messages."""
    for eqn in _as_jaxpr(jaxpr).eqns:
        prim = eqn.primitive.name
        yield eqn, bound_axes, path
        inner_axes = bound_axes
        if prim == "shard_map":
            inner_axes = bound_axes | _shard_map_axes(eqn)
        for sub in _sub_jaxprs(eqn.params):
            for item in iter_eqns(sub, inner_axes, path + (prim,)):
                yield item


def _axis_names(eqn):
    """Axis names a collective eqn operates over, across jax's spellings."""
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(raw, (str, int)):
        raw = (raw,)
    return tuple(raw)


def _eqn_loc(eqn, path):
    where = " > ".join(path) if path else "top level"
    return f"{eqn.primitive.name} at {where}"


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_upcasts(closed, min_elems=4096):
    """JX001: bf16 -> f32 ``convert_element_type`` whose result feeds
    non-reduce math. A convert consumed ONLY by reduce primitives is jnp's
    intentional accumulation upcast (``bf16.sum()`` must accumulate in f32
    or lose mantissa); anything else re-widens activations/grads the
    program claimed were bf16 — 2x the HBM traffic the cost model priced.
    Scalars/small tensors under ``min_elems`` are noise, not bandwidth."""
    findings = []
    for eqn, _axes, path in iter_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = np.dtype(eqn.params.get("new_dtype"))
        src_aval = eqn.invars[0].aval
        src = np.dtype(src_aval.dtype)
        if not (src == np.dtype("bfloat16") and new == np.dtype("float32")):
            continue
        if int(np.prod(src_aval.shape or (1,))) < min_elems:
            continue
        out = eqn.outvars[0]
        # scan the eqn's own jaxpr level for consumers of the converted var
        consumers = []
        owner = closed
        for e2, _a, p2 in iter_eqns(closed):
            if p2 == path and any(v is out for v in e2.invars):
                consumers.append(e2.primitive.name)
        del owner
        if consumers and all(c in _REDUCE_PRIMS for c in consumers):
            continue  # accumulation upcast — the one we want
        findings.append({
            "check": "JX001", "severity": "error",
            "eqn": _eqn_loc(eqn, path),
            "message": (f"bf16->f32 upcast of shape {tuple(src_aval.shape)} "
                        f"feeds {sorted(set(consumers)) or ['program output']}"
                        f" — non-accumulation f32 math in a bf16 program"),
        })
    return findings


def check_collectives(closed, extra_bound=()):
    """JX002: collectives whose axis names no enclosing shard_map binds.
    ``extra_bound`` names axes the caller knows are bound outside the
    traced fragment (e.g. tracing a shard_map BODY directly)."""
    findings = []
    extra = frozenset(extra_bound)
    for eqn, bound, path in iter_eqns(closed):
        prim = eqn.primitive.name
        if prim not in _COLLECTIVE_PRIMS:
            continue
        missing = [a for a in _axis_names(eqn)
                   if a not in bound and a not in extra]
        if missing:
            findings.append({
                "check": "JX002", "severity": "error",
                "eqn": _eqn_loc(eqn, path),
                "message": (f"collective {prim} over axis {missing} with no "
                            f"enclosing shard_map binding it — lowering on "
                            f"a real mesh will fail or pick an implicit "
                            f"axis"),
            })
    return findings


def check_callbacks(closed, allow=()):
    """JX003: host callbacks traced into the program. Each one is a
    device->host->device round trip per execution — on the micro-step or
    decode step that is a synchronous stall the overlap schedule cannot
    hide. ``allow`` lists callback target names (``str(callback)``
    substrings) that are accepted (e.g. an intentional debug lane)."""
    findings = []
    for eqn, _axes, path in iter_eqns(closed):
        prim = eqn.primitive.name
        if prim not in _CALLBACK_PRIMS:
            continue
        target = str(eqn.params.get("callback", ""))
        if any(a and a in target for a in allow):
            continue
        findings.append({
            "check": "JX003", "severity": "error",
            "eqn": _eqn_loc(eqn, path),
            "message": (f"{prim} traced into the program ({target[:80]}) — "
                        f"a host round-trip every step; hoist it out of the "
                        f"hot path or move it to telemetry"),
        })
    return findings


def check_moe_wire(closed, wire_bits, inter_axis=None):
    """JX004: the MoE expert all-to-all's traced wire precision vs what the
    layer was CONFIGURED to send. With ``a2a_wire_bits`` set, the dispatch
    and combine payloads must cross the wire as byte-wide integers (the
    block-quantized q tensor); an fp32 payload means the quantization was
    configured but never reached the collective — 4x the DCN bytes the
    perf gate priced.

    Two findings: (a) ``wire_bits`` set but NO byte-wide all_to_all traced
    anywhere; (b) ``inter_axis`` given and the float elements crossing it
    outnumber the byte-wide elements (scales are a ~1/group_size sliver —
    float payload dominating means the data leg itself is fp)."""
    if not wire_bits:
        return []
    int_elems = 0
    inter_float_elems = 0
    inter_int_elems = 0
    for eqn, _axes, path in iter_eqns(closed):
        if eqn.primitive.name != "all_to_all":
            continue
        aval = eqn.invars[0].aval
        n = int(np.prod(aval.shape or (1,)))
        byte_wide = (np.dtype(aval.dtype).kind in "iu"
                     and np.dtype(aval.dtype).itemsize == 1)
        if byte_wide:
            int_elems += n
        if inter_axis is not None and inter_axis in _axis_names(eqn):
            if byte_wide:
                inter_int_elems += n
            elif np.dtype(aval.dtype).kind == "f":
                inter_float_elems += n
    findings = []
    if int_elems == 0:
        findings.append({
            "check": "JX004", "severity": "error",
            "eqn": "all_to_all (program-wide)",
            "message": (f"a2a_wire_bits={wire_bits} configured but no "
                        f"byte-wide all_to_all traced — the quantized wire "
                        f"never materialized; every leg is full precision"),
        })
    elif inter_axis is not None and inter_float_elems > max(inter_int_elems,
                                                            1):
        findings.append({
            "check": "JX004", "severity": "error",
            "eqn": f"all_to_all over {inter_axis!r}",
            "message": (f"float elements over {inter_axis!r} "
                        f"({inter_float_elems}) exceed the byte-wide payload "
                        f"({inter_int_elems}) — the fp data leg rides the "
                        f"axis int{wire_bits} was configured for"),
        })
    return findings


def check_program(closed, dtype="bfloat16", min_elems=4096,
                  extra_bound=(), allow_callbacks=()):
    """All three eqn checks over one program. ``dtype`` gates JX001 —
    upcast findings only make sense for bf16 programs."""
    findings = []
    if np.dtype(dtype) == np.dtype("bfloat16"):
        findings += check_upcasts(closed, min_elems=min_elems)
    findings += check_collectives(closed, extra_bound=extra_bound)
    findings += check_callbacks(closed, allow=allow_callbacks)
    return findings


def _scan_signatures(closed):
    """(printed body jaxpr, location) of every ``scan`` eqn in trace order."""
    sigs = []
    for eqn, _axes, path in iter_eqns(closed):
        if eqn.primitive.name == "scan":
            sigs.append((str(eqn.params.get("jaxpr", "")), _eqn_loc(eqn, path)))
    return sigs


def check_verify_prefill_parity(prefill_closed, verify_closed):
    """JX005: the speculative verify forward must lower through the SAME
    layer ``scan`` as plain ragged prefill. The draft-then-verify design
    only holds its bit-exactness oracle (and its cost model) if the verify
    chunk rides the ragged prefill kernels — a forked trunk or a
    dense-decode fallback would silently re-trace a different layer program
    whose logits can drift from the plain decode stream. Both programs
    close over the shared ``_ragged_trunk``, so their layer scans must
    print identically; any divergence is a fork.

    Pass the two ``jax.make_jaxpr`` traces (plain ``ragged_forward`` and
    ``ragged_forward_verify``) over the same pool/table shapes."""
    findings = []
    pre = _scan_signatures(prefill_closed)
    ver = _scan_signatures(verify_closed)
    if not pre:
        findings.append({
            "check": "JX005", "severity": "error",
            "eqn": "scan (prefill program)",
            "message": "plain prefill traced no layer scan — cannot "
                       "establish the kernel the verify forward must share",
        })
    if not ver:
        findings.append({
            "check": "JX005", "severity": "error",
            "eqn": "scan (verify program)",
            "message": "verify forward traced no layer scan — the draft "
                       "chunk is not running the scanned ragged prefill "
                       "kernels at all",
        })
    if findings:
        return findings
    if [s for s, _ in pre] != [s for s, _ in ver]:
        where = next((loc for (sp, _), (sv, loc) in zip(pre, ver)
                      if sp != sv), ver[0][1])
        findings.append({
            "check": "JX005", "severity": "error",
            "eqn": where,
            "message": (f"verify forward's layer scan diverges from plain "
                        f"prefill ({len(pre)} vs {len(ver)} scans) — the "
                        f"verify chunk is not lowering through the shared "
                        f"ragged prefill kernel (trunk fork or dense-decode "
                        f"fallback); bit-exact accept/reject is void"),
        })
    return findings


# ---------------------------------------------------------------------------
# overlap-plan drift
# ---------------------------------------------------------------------------

def collective_inventory(closed):
    """Traced collectives, counted by plan-op name and schedule class::

        {"ops": {"all_gather": 8, "reduce_scatter": 4},
         "classes": {"prefetch": 8, "bucket": 4}}
    """
    ops, classes = {}, {}
    for eqn, _axes, _path in iter_eqns(closed):
        name = _COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if name is None:
            continue
        ops[name] = ops.get(name, 0) + 1
        c = op_class(name)
        classes[c] = classes.get(c, 0) + 1
    return {"ops": dict(sorted(ops.items())),
            "classes": dict(sorted(classes.items()))}


def merge_inventories(*invs):
    """Union several programs' inventories (the scheduled step is split
    across micro_step and apply_step — the plan prices the whole round)."""
    out = {"ops": {}, "classes": {}}
    for inv in invs:
        for k in ("ops", "classes"):
            for name, n in inv.get(k, {}).items():
                out[k][name] = out[k].get(name, 0) + n
    out["ops"] = dict(sorted(out["ops"].items()))
    out["classes"] = dict(sorted(out["classes"].items()))
    return out


def check_plan_drift(plan, inventory):
    """Does the overlap plan's priced collective inventory match what the
    scheduled program actually traces? Compared by schedule class
    (prefetch/bucket/tail), because that is the granularity the planner
    prices and the exposure model hides. ``plan`` is an
    ``OverlapPlan.to_dict()`` (or the ``comm_ops`` list itself);
    ``inventory`` comes from :func:`collective_inventory` /
    :func:`merge_inventories`.

    Returns ``{"ok", "planned_classes", "traced_classes",
    "missing_in_trace", "missing_in_plan"}`` — a class the plan prices
    that never traces means the plan claims overlap for comm that does
    not exist; a traced class the plan omits means unpriced comm the
    exposure model never saw."""
    comm_ops = plan.get("comm_ops", plan) if isinstance(plan, dict) else plan
    planned = {}
    for op in comm_ops:
        name = op["op"] if isinstance(op, dict) else str(op)
        c = op_class(name)
        planned[c] = planned.get(c, 0) + int(
            op.get("count", 1) if isinstance(op, dict) else 1)
    traced = dict(inventory.get("classes", {}))
    missing_in_trace = sorted(c for c in planned if c not in traced)
    missing_in_plan = sorted(c for c in traced if c not in planned)
    return {
        "ok": not missing_in_trace and not missing_in_plan,
        "planned_classes": dict(sorted(planned.items())),
        "traced_classes": dict(sorted(traced.items())),
        "missing_in_trace": missing_in_trace,
        "missing_in_plan": missing_in_plan,
    }
