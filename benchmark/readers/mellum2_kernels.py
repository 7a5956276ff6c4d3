"""Per-layer metrics of Mellum2's expert layer and of its attention over two
kinds of pages, from the device trace, with the work from
``benchmark/peaks_mellum2.py``.

params: ``work`` one of

- ``moe_gmm`` (a roofline share): the device events whose name matches
  ``match`` (the expert GEMMs: megablox's kernel is the HLO operation
  ``gmm``) against the least time the window's dispatches need for their
  three GEMMs, each dispatch ``max(FLOPs / peak, bytes / peak)`` of its real
  tokens, read from the program's ``ds/serving/build`` spans;
- ``mixed_attn`` (a roofline share): the events matching ``match`` (the paged
  kernel) against the least time of the rows the window's rounds ran, each row
  ``max(FLOPs / peak, bytes / peak)`` of its new tokens and the position it
  ended at (``attn_rows`` of the harness's ``round`` spans, the new driver's
  attribute), the sliding layers reading the window's reach and the full
  layers the whole context;
- ``moe_share`` (a share of the device's busy time): the device time of the
  events that ran under the ``jax.named_scope`` ``scope`` (the expert layer's:
  router, sort, the three GEMMs, unsort and combine), found through the
  operations' ``op_name`` in the trace file (``benchmark/xplane_scopes.py``),
  over the busy time of the traced window. Its note also says what share of
  the expert rows were padded slots' (``expert_rows_padded`` of the spans).

Each says in a note what the number rests on. A trace without the events, the
spans or the attributes (a program from before this model) gives None.
"""

import re

from benchmark import peaks, peaks_mellum2 as work, program_spans as ps, trace


def _took(events):
    return sum(b - a for a, b in events) / 1e9


def _least(pairs, peak):
    """Sum over (flops, bytes) of the least time each needs, and how many of
    them compute bounds."""
    least = [peaks.roofline_seconds(f, b, peak) for f, b in pairs]
    return sum(t for t, _ in least), sum(bound == "compute" for _, bound in least)


def moe_gmm(ctx, params):
    cfg = ctx["cell"].config
    loaded = ps.for_run(ctx)
    builds = [s[3] for s in ps.named(loaded, ps.BUILD)] if loaded else []
    builds = [a for a in builds if a.get("expert_rows")]
    events = trace.kernel_events(ctx["trace"], params["match"])
    if not events or not builds:
        return None
    tokens = [int(a["real_tokens"]) for a in builds]
    least, compute = _least([(work.moe_gmm_flops(cfg, t), work.moe_gmm_bytes(cfg, t))
                             for t in tokens], ctx["peaks"])
    took = _took(events)
    ctx["notes"].append(
        f"mellum2_kernels moe_gmm: {len(events)} events, {took:.4f} s on the device, least "
        f"{least:.4f} s ({compute} of {len(tokens)} dispatches compute-bound, the rest "
        f"memory-bound); {sum(tokens)} real tokens, "
        f"{sum(int(a['expert_rows']) for a in builds)} expert rows")
    return 100.0 * least / took


def mixed_attn(ctx, params):
    cfg = ctx["cell"].config
    rounds = [attrs["attn_rows"] for name, _, _, attrs in ctx["spans"]
              if name == "round" and "attn_rows" in attrs]
    events = trace.kernel_events(ctx["trace"], params["match"])
    rows = [row for r in rounds for row in r]
    if not events or not rows:
        return None
    least, compute = _least([(work.mixed_attn_flops(cfg, new, end),
                              work.mixed_attn_bytes(cfg, new, end)) for new, end in rows],
                            ctx["peaks"])
    took = _took(events)
    decode = [end for new, end in rows if new == 1]
    ctx["notes"].append(
        f"mellum2_kernels mixed_attn: {len(events)} events, {took:.4f} s on the device, least "
        f"{least:.4f} s; {len(rows)} rows in {len(rounds)} rounds, {compute} compute-bound "
        f"(chunks), {len(decode)} decode rows at contexts of {min(decode, default=0)}-"
        f"{max(decode, default=0)}, of them {sum(e > cfg['sliding_window'] for e in decode)} "
        f"past the window")
    return 100.0 * least / took


def moe_share(ctx, params):
    from benchmark import xplane_scopes
    path = ctx.get("trace_path") or ps.find_trace(ctx["cell"].name)
    busy = ctx["summary"]["busy_s"]
    if path is None or not busy:
        return None
    names = xplane_scopes.op_names(path)
    under = re.compile(params["scope"])
    lo, hi = trace.window_of(ctx["trace"])
    parts, total = {}, 0.0
    for name, a, b in next(iter(ctx["trace"]["devices"].values())):
        if b <= lo or a >= hi or trace.short_name(name).rsplit(" ", 1)[-1] in trace.CONTAINERS:
            continue
        m = under.search(names.get(name, ""))
        if m:
            took = (min(b, hi) - max(a, lo)) / 1e9
            total += took
            part = m.group(1) if m.groups() and m.group(1) else "rest"
            parts[part] = parts.get(part, 0.0) + took
    if not total:
        return None
    loaded = ps.for_run(ctx)
    builds = [s[3] for s in ps.named(loaded, ps.BUILD)] if loaded else []
    rows = sum(int(a.get("expert_rows", 0)) for a in builds)
    padded = sum(int(a.get("expert_rows_padded", 0)) for a in builds)
    ctx["notes"].append(
        f"mellum2_kernels moe_share: {total:.4f} s of {busy:.4f} s busy under {params['scope']!r}: "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]))
        + f"; padded slots' share of expert rows {padded} / {rows + padded} = "
        + (f"{100.0 * padded / (rows + padded):.2f} %" if rows + padded else "no rows"))
    return 100.0 * total / busy


WORK = {"moe_gmm": moe_gmm, "mixed_attn": mixed_attn, "moe_share": moe_share}


def read(ctx, params):
    if ctx["trace"] is None:
        return None
    return WORK[params["work"]](ctx, params)
