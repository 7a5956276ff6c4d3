"""Per-layer metrics of Kanana-2's latent attention, from the device trace,
with the work from ``benchmark/peaks_kanana2.py``.

params: ``work`` one of

- ``mla_attn`` (a roofline share): the device events whose name matches
  ``match`` (the latent walk: the kernel's name is ``paged_mla``) and the
  further events under the ``jax.named_scope`` ``scope`` (``mla_read``: the
  layout changes around the kernel), against the least time of the
  rows the window's rounds ran, each row ``max(FLOPs / peak, bytes / peak)`` of
  its new tokens and the position it ended at (``attn_rows`` of the harness's
  ``round`` spans): the bytes are the row's latent pages read once, the FLOPs
  the lesser of the absorbed form's and the materialised form's (every head's
  keys and values up-projected first), whatever the program runs;
- ``mla_share`` (a share of the device's busy time): the device time of the
  events that ran under the scope ``mla_attn`` (q projection and absorption,
  the latent write, the read, the up-projection of the values and the output
  projection), found through the operations' ``op_name`` in the trace file
  (``benchmark/xplane_scopes.py``), over the busy time of the traced window.
  Its note gives the shares under ``moe_ffn`` and of the sampler's sort beside
  it, and the latent pages held.

Each says in a note what the number rests on. A trace without the events, the
scopes or the attributes (a program from before this model) gives None.
"""

import re

from benchmark import peaks_kanana2 as work, program_spans as ps, trace, xplane_scopes
from benchmark.readers.mellum2_kernels import _least, _took


def _scoped(ctx):
    """[(HLO text, op_name strings, seconds inside the window)] of the first
    device's events, containers left out; None without the trace file. Read
    once a run and kept in ``ctx``."""
    if "scoped_events" not in ctx:
        path = ctx.get("trace_path") or ps.find_trace(ctx["cell"].name)
        scoped = None
        if path is not None:
            names = xplane_scopes.op_names(path)
            lo, hi = trace.window_of(ctx["trace"])
            scoped = [(name, names.get(name, ""), (min(b, hi) - max(a, lo)) / 1e9)
                      for name, a, b in next(iter(ctx["trace"]["devices"].values()))
                      if b > lo and a < hi and trace.short_name(name).rsplit(" ", 1)[-1]
                      not in trace.CONTAINERS]
        ctx["scoped_events"] = scoped
    return ctx["scoped_events"]


def mla_attn(ctx, params):
    cfg = ctx["cell"].config
    rounds = [attrs["attn_rows"] for name, _, _, attrs in ctx["spans"]
              if name == "round" and "attn_rows" in attrs]
    rows = [row for r in rounds for row in r]
    events = trace.kernel_events(ctx["trace"], params["match"])
    if not events or not rows:
        return None
    took = _took(events)
    # what else the read runs: the layout changes around the kernel
    kernel, under = re.compile(params["match"]), re.compile(params["scope"])
    beside = sum(t for name, op, t in _scoped(ctx) or ()
                 if under.search(op) and not kernel.search(name))
    least, compute = _least([(work.mla_attn_flops(cfg, new, end),
                              work.mla_attn_bytes(cfg, new, end)) for new, end in rows],
                            ctx["peaks"])
    decode = [end for new, end in rows if new == 1]
    ctx["notes"].append(
        f"kanana2_kernels mla_attn: {len(events)} events, {took:.4f} s on the device and "
        f"{beside:.4f} s of other operations under {params['scope']!r}, least {least:.4f} s; "
        f"{len(rows)} rows in {len(rounds)} rounds, {compute} compute-bound (chunks), "
        f"{len(decode)} decode rows at contexts of {min(decode, default=0)}-"
        f"{max(decode, default=0)}, {sum(end for _, end in rows)} latent rows read")
    return 100.0 * least / (took + beside)


def mla_share(ctx, params):
    busy = ctx["summary"]["busy_s"]
    scoped = _scoped(ctx)
    if not scoped or not busy:
        return None
    under = re.compile(params["scope"])
    parts, total = {}, 0.0
    for _, op, took in scoped:
        m = under.search(op)
        if m:
            total += took
            part = m.group(1) if m.groups() and m.group(1) else "rest"
            parts[part] = parts.get(part, 0.0) + took
    if not total:
        return None
    experts = sum(t for _, op, t in scoped if "/moe_ffn/" in op)
    sort = sum(t for name, _, t in scoped if re.match(r"%?sort", name))
    loaded = ps.for_run(ctx)
    builds = [s[3] for s in ps.named(loaded, ps.BUILD)] if loaded else []
    pages = [int(a["latent_pages"]) for a in builds if "latent_pages" in a]
    ctx["notes"].append(
        f"kanana2_kernels mla_share: {total:.4f} s of {busy:.4f} s busy under {params['scope']!r}: "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]))
        + f"; beside it moe_ffn {experts:.4f} s = {100 * experts / busy:.2f} %, the sampler's "
        f"sort {sort:.4f} s = {100 * sort / busy:.2f} %; latent pages held "
        f"{min(pages, default=0)}-{max(pages, default=0)}")
    return 100.0 * total / busy


WORK = {"mla_attn": mla_attn, "mla_share": mla_share}


def read(ctx, params):
    if ctx["trace"] is None:
        return None
    return WORK[params["work"]](ctx, params)
