"""Per-layer metrics of Kimi-Linear's mixers, from the device trace and the
program's ``ds/serving/build`` spans, with the work from
``benchmark/peaks_kimi_linear.py``.

params: ``work`` one of

- ``kda_step`` (a roofline share): the device events whose name matches
  ``match`` (the kernel's own name, ``kda_step``) against the bytes the
  window's decode dispatches must move (``kda_step_rows`` of the build spans:
  a row's state read and written once a KDA layer, its token's vectors in and
  its o out) over the chip's bandwidth;
- ``kda_chunk`` (a roofline share): the events matching ``match``
  (``kda_chunk``) against the least time of the window's chunk dispatches,
  each ``max(FLOPs / peak, bytes / peak)`` of its ``kda_chunk_tokens`` and its
  rows, the counts those of the chunk form at C = 64 whatever the kernel does;
- ``kda_share`` (a share of the device's busy time): the device time of the
  events under the scope ``kda`` (projections, convolution, the state kernel,
  norm, gate and ``o_proj``), by part; its note gives the shares under
  ``mla_attn`` and ``moe_ffn`` beside it, so that the largest is seen;
- ``nope_mla`` (a roofline share): ``kanana2_kernels.mla_attn`` as it is (the
  latent walk ``paged_mla`` and what else runs under ``mla_read``) against
  ``peaks_kanana2``'s count over this configuration's MLA layers;
- ``chunk_tokens_share``: ``kda_chunk_tokens`` over all real tokens of the
  window's dispatches, in percent: how much of the traffic the chunk kernel
  sees (a property of the traffic, printed so that a change in it is seen as
  a change of work).

Each says in a note what the number rests on. A run without the events, the
scopes or the attributes (a program from before this model) gives None.
"""

import re

from benchmark import peaks, peaks_kimi_linear as work, program_spans as ps, trace
from benchmark.readers import kanana2_kernels
from benchmark.readers.longcat_flash_kernels import _CellAs
from benchmark.readers.mellum2_kernels import _least, _took


def _builds(ctx, key):
    """The window's build spans' attributes that carry ``key``."""
    loaded = ps.for_run(ctx)
    builds = [s[3] for s in ps.named(loaded, ps.BUILD)] if loaded else []
    return [a for a in builds if key in a]


def kda_step(ctx, params):
    cfg = ctx["cell"].config
    builds = _builds(ctx, "kda_step_rows")
    events = trace.kernel_events(ctx["trace"], params["match"])
    rows = sum(int(a["kda_step_rows"]) for a in builds)
    if not events or not rows:
        return None
    least, bound = peaks.roofline_seconds(work.kda_step_flops(cfg, rows),
                                          work.kda_step_bytes(cfg, rows), ctx["peaks"])
    took = _took(events)
    ctx["notes"].append(
        f"kimi_linear_kernels kda_step: {len(events)} events, {took:.4f} s on the device, least "
        f"{least:.4f} s ({bound}-bound); {rows} rows in "
        f"{sum(int(a['kda_step_rows']) > 0 for a in builds)} dispatches, "
        f"{work.state_bytes(cfg)} B of state a row and layer")
    return 100.0 * least / took


def kda_chunk(ctx, params):
    cfg = ctx["cell"].config
    builds = [a for a in _builds(ctx, "kda_chunk_tokens") if int(a["kda_chunk_tokens"])]
    events = trace.kernel_events(ctx["trace"], params["match"])
    if not events or not builds:
        return None
    pairs = [(work.kda_chunk_flops(cfg, int(a["kda_chunk_tokens"])),
              work.kda_chunk_bytes(cfg, int(a["seqs"]), int(a["kda_chunk_tokens"])))
             for a in builds]
    least, compute = _least(pairs, ctx["peaks"])
    took = _took(events)
    ctx["notes"].append(
        f"kimi_linear_kernels kda_chunk: {len(events)} events, {took:.4f} s on the device, least "
        f"{least:.4f} s ({compute} of {len(builds)} dispatches compute-bound); "
        f"{sum(int(a['kda_chunk_tokens']) for a in builds)} tokens in the chunk form")
    return 100.0 * least / took


def kda_share(ctx, params):
    busy = ctx["summary"]["busy_s"]
    scoped = kanana2_kernels._scoped(ctx)
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
    beside = {name: sum(t for _, op, t in scoped if f"/{name}/" in op)
              for name in ("mla_attn", "moe_ffn")}
    slots = [int(a["state_slots"]) for a in _builds(ctx, "state_slots")]
    ctx["notes"].append(
        f"kimi_linear_kernels kda_share: {total:.4f} s of {busy:.4f} s busy under "
        f"{params['scope']!r}: "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]))
        + "; beside it " + ", ".join(f"{k} {v:.4f} s = {100 * v / busy:.2f} %"
                                     for k, v in beside.items())
        + f"; slots held {min(slots, default=0)}-{max(slots, default=0)}")
    return 100.0 * total / busy


def nope_mla(ctx, params):
    kanana2_kernels._scoped(ctx)          # read once, kept in ``ctx`` for every reader
    return kanana2_kernels.mla_attn(
        dict(ctx, cell=_CellAs(ctx["cell"], work.mla_config(ctx["cell"].config))), params)


def chunk_tokens_share(ctx, params):
    builds = _builds(ctx, "kda_chunk_tokens")
    real = sum(int(a["real_tokens"]) for a in builds)
    if not real:
        return None
    chunk = sum(int(a["kda_chunk_tokens"]) for a in builds)
    ctx["notes"].append(
        f"kimi_linear_kernels chunk_tokens_share: {chunk} of {real} real tokens in "
        f"{len(builds)} dispatches took the chunk form, "
        f"{sum(int(a['kda_step_rows']) for a in builds)} the one-step update")
    return 100.0 * chunk / real


WORK = {"kda_step": kda_step, "kda_chunk": kda_chunk, "kda_share": kda_share,
        "nope_mla": nope_mla, "chunk_tokens_share": chunk_tokens_share}


def read(ctx, params):
    if ctx["trace"] is None:
        return None
    return WORK[params["work"]](ctx, params)
